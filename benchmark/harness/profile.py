"""The profiler trace of a traced stretch, and its reduction to numbers.

The benchmark keeps its own copy of this reduction so that no change to
the system under test can move the yardstick:

* a device's busy time is the union of the intervals in which an XLA
  operation ran on it (nested operations count once);
* its idle share is one minus busy over the traced window;
* an operation's time is its self time: its duration less the part its
  nested operations cover, summed by operation name;
* an idle gap is a stretch of the traced window in which no device ran
  an operation; it is labelled by the host spans that covered its
  middle, innermost last.

The trace is read in memory from a ``ProfilerSession`` (no file is
written), via ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

import re
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]   # (start_ns, end_ns)
SYNC = "bench.sync"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float,
         hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (merged) leaves uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


_OP_NAME = re.compile(r"^%?([^\s=]+)")


def op_name(hlo: str) -> str:
    """``%fusion.41 = (...) fusion(...)`` -> ``fusion.41``."""
    m = _OP_NAME.match(hlo)
    return m.group(1) if m else hlo[:64]


def self_times(events: Sequence[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Self time (ns) by operation name over one device line, whose
    events nest: a parent's time less its children's."""
    out: Dict[str, float] = {}
    stack: List[List] = []   # [end, name, child_ns, start]

    def close(frame):
        s = frame[1]
        out[s] = out.get(s, 0.0) + (frame[0] - frame[3]) - frame[2]

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] += min(end, stack[-1][0]) - start
        stack.append([end, name, 0.0, start])
    while stack:
        close(stack.pop())
    return out


@dataclass
class Trace:
    """What one traced stretch holds, in the profiler's clock (ns)."""
    devices: Dict[str, List[Tuple[float, float, str]]]  # plane -> ops
    host: List[Tuple[float, float, str]]                # annotations
    sync_ns: Optional[float] = None    # the SYNC annotation's start
    sync_mono_ns: Optional[int] = None  # time.monotonic_ns() inside it
    window: Optional[Interval] = None
    lines: List[str] = field(default_factory=list)  # what the trace held

    def busy(self) -> Dict[str, List[Interval]]:
        lo, hi = self.window
        return {d: clip(union((s, e) for s, e, _ in evs), lo, hi)
                for d, evs in self.devices.items()}


@dataclass
class Reduction:
    window_s: float
    busy_s: Dict[str, float]              # per device plane
    idle_share: float                     # mean over devices, 0..1
    op_self_s: Dict[str, float]           # summed over devices
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)


def from_profile_data(pd, window_name: str) -> Trace:
    """Device operation lines and the caller thread's host events from a
    ``ProfileData``. The traced window is the span of the host
    annotations named ``window_name``."""
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    host: List[Tuple[float, float, str]] = []
    short: Dict[str, str] = {}   # an op's full HLO text -> its name
    lines = []
    for plane in pd.planes:
        lines.extend(f"{plane.name}/{line.name}" for line in plane.lines)
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                evs = devices.setdefault(plane.name, [])
                for e in line.events:
                    hlo = e.name
                    name = short.get(hlo)
                    if name is None:
                        name = short[hlo] = op_name(hlo)
                    s = e.start_ns
                    evs.append((s, s + e.duration_ns, name))
        elif plane.name.startswith("/host:"):
            # the caller's thread: the host line that holds our marks
            for line in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]
                if any(n in (SYNC, window_name) for _, _, n in evs):
                    host.extend(evs)
    tr = Trace(devices=devices, host=host, lines=lines)
    marks = [h for h in host if h[2] == window_name]
    if marks:
        tr.window = (min(h[0] for h in marks), max(h[1] for h in marks))
    syncs = [h for h in host if h[2] == SYNC]
    if syncs:
        tr.sync_ns = syncs[0][0]
    return tr


def reduce(tr: Trace, host_spans: Sequence[Tuple[float, float, str]] = (),
           n_gaps: int = 10) -> Optional[Reduction]:
    """Numbers of one traced stretch; None when it holds no device
    operation or no window. ``host_spans`` are extra host intervals
    (already in the profiler's clock) that label idle gaps."""
    if tr.window is None or not any(tr.devices.values()):
        return None
    lo, hi = tr.window
    span = hi - lo
    busy = tr.busy()
    busy_s = {d: total(iv) / 1e9 for d, iv in busy.items()}
    idle = sum(1.0 - b * 1e9 / span for b in busy_s.values()) / len(busy_s)
    ops: Dict[str, float] = {}
    for evs in tr.devices.values():
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in evs
                  if e > lo and s < hi]
        for name, ns in self_times(inside).items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9
    any_busy = union(iv for ivs in busy.values() for iv in ivs)
    labels = list(host_spans) + [h for h in tr.host if h[2] != SYNC]
    by_label: Dict[str, float] = {}
    for s, e in gaps(any_busy, lo, hi):
        mid = (s + e) / 2
        cover = sorted((h for h in labels if h[0] <= mid < h[1]),
                       key=lambda h: (h[0], -h[1]))
        label = "/".join(h[2] for h in cover) or "outside any span"
        by_label[label] = by_label.get(label, 0.0) + (e - s) / 1e9
    top = sorted(by_label.items(), key=lambda kv: -kv[1])[:n_gaps]
    return Reduction(window_s=span / 1e9, busy_s=busy_s, idle_share=idle,
                     op_self_s=ops, gaps=top)


class Session:
    """A profiler session started and stopped in process, python tracer
    off; the trace comes back as ``ProfileData`` without a file."""

    def __init__(self):
        import jax
        from jax._src.lib import _profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self._sess = _profiler.ProfilerSession(opts)
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(SYNC):
            self.sync_mono_ns = time.monotonic_ns()
        self.stop_s: Optional[float] = None

    def stop(self, window_name: str) -> Trace:
        from jax.profiler import ProfileData
        t = time.perf_counter()
        pd = ProfileData.from_serialized_xspace(self._sess.stop())
        self.stop_s = time.perf_counter() - t
        tr = from_profile_data(pd, window_name)
        tr.sync_mono_ns = self.sync_mono_ns
        return tr


def to_profiler_clock(tr: Trace, spans_mono: Iterable[Tuple[int, int, str]]
                      ) -> List[Tuple[float, float, str]]:
    """Host spans timed by ``time.monotonic_ns()`` moved onto the
    profiler's clock through the SYNC annotation; empty when the trace
    holds no SYNC mark."""
    if tr.sync_ns is None or tr.sync_mono_ns is None:
        return []
    off = tr.sync_ns - tr.sync_mono_ns
    return [(s + off, e + off, n) for s, e, n in spans_mono]
