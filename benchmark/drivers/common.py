"""What every driver reads from the system under test: its history
type, its compile and search counters, its host spans, and the marks of
a verdict that did not come from the device."""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Tuple

#: Result keys (or values) that mean a verdict was not the device's.
OFF_DEVICE = ("fallback-from", "backend-fallback", "cpu-fallback")

#: The system's host spans that label idle gaps in a trace.
SPAN_PREFIXES = ("checker.", "engine.")


def to_history(rows: Iterable[tuple]):
    """Benchmark rows ``(type, process, f, value)`` as the system's
    ``History``, one event per row, timed by position."""
    from jepsen_tpu.history import History, Op
    h = History()
    for t, (typ, p, f, v) in enumerate(rows):
        h.append(Op(type=typ, f=f, value=v, process=p, time=t))
    return h


def verdict(result: Dict[str, Any]):
    """True or False when the result decided on the device, else None."""
    if not isinstance(result, dict) or off_device(result):
        return None
    if result.get("backend") != "tpu":
        return None
    v = result.get("valid")
    return v if v is True or v is False else None


def off_device(result: Any) -> bool:
    if isinstance(result, dict):
        return any(k in result for k in OFF_DEVICE) or any(
            off_device(v) for v in result.values())
    if isinstance(result, (list, tuple)):
        return any(off_device(v) for v in result)
    return isinstance(result, str) and result in OFF_DEVICE


def counters() -> Dict[str, float]:
    """The system's compile accounting and search-level counter."""
    from jepsen_tpu.checker import tpu
    from jepsen_tpu.obs import metrics
    snap = dict(tpu.compile_snapshot())
    snap["levels"] = metrics.counter("jtpu_search_levels_total").total()
    return snap


def spans() -> List[Tuple[int, int, str]]:
    """The system's recorded host spans as (start, end) in
    ``time.monotonic_ns()`` and name."""
    from jepsen_tpu.obs import trace
    tr = trace.tracer()
    out = []
    for rec in tr.spans():
        name = rec.get("name", "")
        if name.startswith(SPAN_PREFIXES) and rec.get("dur"):
            s = rec["ts"] + tr.epoch_ns
            out.append((s, s + rec["dur"], name))
    return out


def work_entries(result: Dict[str, Any]) -> List[Tuple[int, int, int, int,
                                                       int]]:
    """``(capacity, window, expand, crash_width, levels)`` for each rung a
    search ran, from a result's ``work`` list."""
    out = []
    for rung, crash, _tiebreak, levels in result.get("work") or ():
        cap, win, exp = rung
        out.append((int(cap), int(win), int(exp), int(crash), int(levels)))
    return out
