"""Per-check sums of the system's layer spans, for the per-layer
metrics that read them (``benchmark/metrics/*_s_per_check.py``).

A window check is the stretch ``Check.mono`` between the harness's clock
readings around one driver call; its spans are the system's host spans
(``drivers/common.spans()``, ``time.monotonic_ns()``) that start and end
inside it. A reading is None unless every window check holds exactly
one ``checker.search`` span: a check path the spans do not cover, or a
span ring that evicted part of the window, gives no number."""

from __future__ import annotations

from typing import List, Optional, Tuple

from harness import profile, spec

ROOT = "checker.search"

#: The spans whose time is not the root's own (``supervise_s_per_check``
#: reads what the root holds outside them).
COVERED = ("checker.lint", "checker.pack", "checker.plan",
           "checker.segment", "checker.device.")

Span = Tuple[int, int, str]


def window_checks(run) -> Optional[List[List[Span]]]:
    """Each window check's spans, or None (see the module's doc)."""
    spans = sorted(spec.load_module("drivers", "common").spans())
    out = []
    for ck in run.window:
        lo, hi = ck.mono
        inside = [s for s in spans if s[0] >= lo and s[1] <= hi]
        if sum(1 for s in inside if s[2] == ROOT) != 1:
            return None
        out.append(inside)
    return out or None


def seconds_per_check(run, name: str) -> Optional[float]:
    """Seconds of the spans called ``name``, summed over the window's
    checks and divided by their number."""
    checks = window_checks(run)
    if checks is None:
        return None
    ns = sum(e - s for c in checks for s, e, n in c if n == name)
    return ns / 1e9 / len(checks)


def root_self_seconds_per_check(run) -> Optional[float]:
    """Seconds of each check's ``checker.search`` span that no
    :data:`COVERED` span covers, per window check."""
    checks = window_checks(run)
    if checks is None:
        return None
    ns = 0.0
    for c in checks:
        (lo, hi), = [(s, e) for s, e, n in c if n == ROOT]
        covered = profile.union((s, e) for s, e, n in c
                                if n.startswith(COVERED))
        ns += (hi - lo) - profile.total(profile.clip(covered, lo, hi))
    return ns / 1e9 / len(checks)
