"""Share of the traced stretch's device-idle time whose idle gaps no
layer span explains: the gap's label holds none of the system's spans,
or ``checker.search`` as the innermost of them. Idle time is the window
less the busy time (mean over devices); labels past the reduction's
top ten count as unattributed, so the share errs high (profiler trace
and the system's spans)."""

from harness import checkspans, spec


def attributed(label: str) -> bool:
    prefixes = spec.load_module("drivers", "common").SPAN_PREFIXES
    own = [p for p in label.split("/") if p.startswith(prefixes)]
    return bool(own) and own[-1] != checkspans.ROOT


def read(run):
    if run.trace is None:
        return None
    idle = run.trace.window_s * run.trace.idle_share
    if idle <= 0:
        return 0.0
    explained = sum(s for label, s in run.trace.gaps if attributed(label))
    return 100.0 * max(0.0, idle - explained) / idle
