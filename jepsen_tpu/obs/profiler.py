"""Device-level profiling: what did the accelerator itself run?

The host-side tracer (:mod:`jepsen_tpu.obs.trace`) can say a
``checker.segment`` took 1.4 s; it cannot say which XLA kernel or
fusion burned that time on the device. ``jax.profiler`` can — its
capture writes a TensorBoard profile directory whose
``*.trace.json.gz`` is Chrome trace-event JSON with one process per
device (``/device:TPU:0``) and per XLA runtime thread. This module is
the glue:

* :func:`capture` — an **opt-in** (``JTPU_PROF=1`` / ``--profile``)
  context manager the device checkers wrap their searches in. It
  starts ``jax.profiler.start_trace`` into ``<run_dir>/profile/`` and
  records a ``prof.capture`` host span over the captured region, with a
  ``jtpu.sync`` profiler annotation inside it whose host clock reading
  the span keeps (``sync_ts``) — the shared clock the merge below
  aligns on. Everything is
  failure-tolerant: no jax, no profiler support on the platform, a
  capture that raises — all silent no-ops, and with profiling off (the
  default) no artifact differs by a byte from the pre-profiler tree
  (asserted by tests).
* :func:`read_profile` — locate and parse the captured device trace,
  extracting **device-lane** events (any process named ``/device:*``,
  plus XLA runtime threads on CPU-only captures, where the backend has
  no device process) as normalized records. Tolerates absent,
  truncated, or garbage capture files — a SIGKILL mid-capture loses
  the capture, never the run (``tools/chaos_matrix.py --only
  prof-kill`` drills exactly that).
* :func:`merge_into_host` — align the profiler's clock to the host
  trace via the ``jtpu.sync`` mark and parent each device record
  under the ``checker.segment`` / ``checker.device.*`` host span whose
  interval contains it, so the Perfetto export shows a device-track
  lane nested under the matching host span.
* :func:`top_kernels` — per-rung top-k kernel **self-time** rollups,
  the ``jtpu trace summary`` payload that answers "which fusion is the
  rung actually made of".

Kill switch: ``JTPU_PROF`` (default **off** — profiling costs real
overhead and disk, unlike the always-on host tracer). Profiling also
requires ``JTPU_TRACE`` on: without the host trace there is no anchor
to merge against, and the byte-identity contract of ``JTPU_TRACE=0``
must hold regardless of ``JTPU_PROF``.
"""

from __future__ import annotations

import glob
import gzip
import json
import logging
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.obs import trace as obs_trace

log = logging.getLogger("jepsen.obs")

#: The profile directory's name inside a run's store directory.
PROFILE_DIRNAME = "profile"

#: The host anchor span recorded over each captured region.
CAPTURE_SPAN = "prof.capture"

#: The profiler annotation opened once at the start of each capture.
#: ``time.monotonic_ns()`` read inside it is stored on the capture span
#: as ``sync_ts`` (tracer-relative), and the annotation carries the
#: span's sid as ``capture``: one instant named on both clocks.
SYNC = "jtpu.sync"

#: Host span names a device record may be parented under (deepest wins).
HOST_PARENTS = ("checker.segment", "checker.device.batch",
                "checker.device.sharded")

#: Synthetic tid base for device lanes in merged records (far above any
#: OS thread id's low bits colliding in the same waterfall row).
DEVICE_TID_BASE = 1 << 40

#: XLA runtime thread names on captures without a device process (the
#: CPU backend runs its thunks on host threads): these lanes carry the
#: kernel/fusion executions and stand in as the device track.
_XLA_THREAD_RE = re.compile(
    r"XLA|Xla|TFRT|StreamExecutor|tf_Compute", re.ASCII)

_CAPTURES_TOTAL = obs_metrics.counter(
    "jtpu_prof_captures_total",
    "device-profiler captures completed, labeled outcome=ok|failed")

_lock = threading.Lock()
_DIR: Optional[str] = None     # armed run directory (attach/detach)
_ACTIVE = False                # a capture is in flight
_FAILED: Optional[str] = None  # sticky: the platform refused a capture


def enabled() -> bool:
    """Whether device profiling is opted in (JTPU_PROF, default OFF).
    Requires the host tracer too: merging needs the host-span anchor,
    and JTPU_TRACE=0 byte-identity must hold regardless."""
    on = os.environ.get("JTPU_PROF", "0").lower() in (
        "1", "true", "yes", "on")
    return on and obs_trace.enabled()


def attach(store_dir: Optional[str]) -> None:
    """Arm the profiler with a run's store directory (core.run /
    analyze call this next to the tracer's start_run). No directory is
    created until a capture actually starts."""
    global _DIR
    with _lock:
        _DIR = store_dir or None


def detach() -> None:
    global _DIR
    with _lock:
        _DIR = None


def profile_dir(run_dir: str) -> str:
    return os.path.join(run_dir, PROFILE_DIRNAME)


class _Capture:
    """The capture context. One instance per ``capture()`` call; inert
    when disabled, dir-less, nested inside another capture, or after a
    platform failure (sticky — one refusal means every later attempt
    would refuse identically)."""

    def __init__(self):
        self.dir: Optional[str] = None
        self.span = None

    def __enter__(self) -> "_Capture":
        global _ACTIVE, _FAILED
        with _lock:
            if not enabled() or _DIR is None or _ACTIVE or _FAILED:
                return self
            target = profile_dir(_DIR)
            _ACTIVE = True
        created = False
        try:
            import jax
            # created up front: the directory's appearance IS the
            # "capture in flight" signal (chaos prof-kill polls it;
            # jax only materializes files at stop_trace)
            if not os.path.isdir(target):
                os.makedirs(target, exist_ok=True)
                created = True
            jax.profiler.start_trace(target)
        except Exception as e:  # noqa: BLE001 — profiling must not wedge
            if created:
                # leave no artifact behind: an unsupported platform
                # must be byte-identical to JTPU_PROF=0 (asserted)
                try:
                    os.rmdir(target)
                except OSError:
                    pass
            with _lock:
                _ACTIVE = False
                _FAILED = f"{type(e).__name__}: {e}"
            _CAPTURES_TOTAL.inc(outcome="failed")
            log.warning("device profiling unavailable (%s); JTPU_PROF "
                        "is a no-op on this platform", _FAILED)
            return self
        self.dir = target
        # the anchor: a host span covering the captured region, closed
        # when the capture stops, holding the host reading of the sync
        # mark the profiler records in the same instant
        self.span = obs_trace.span(CAPTURE_SPAN, dir=PROFILE_DIRNAME)
        self.span.__enter__()
        sid = getattr(self.span, "sid", None)
        if sid is not None:
            with jax.profiler.TraceAnnotation(SYNC, capture=sid):
                mono = time.monotonic_ns()
            self.span.set(sync_ts=mono - obs_trace.tracer().epoch_ns)
        return self

    def __exit__(self, *exc) -> bool:
        global _ACTIVE
        if self.dir is None:
            return False
        try:
            import jax
            jax.profiler.stop_trace()
            _CAPTURES_TOTAL.inc(outcome="ok")
        except Exception as e:  # noqa: BLE001
            _CAPTURES_TOTAL.inc(outcome="failed")
            log.warning("device-profiler stop failed: %s", e)
        finally:
            if self.span is not None:
                self.span.__exit__(None, None, None)
            with _lock:
                _ACTIVE = False
        return False


def capture() -> _Capture:
    """``with profiler.capture(): <device search>`` — a no-op unless
    JTPU_PROF is on and a run directory is armed. Nested captures are
    no-ops (the outermost wins), so every device path may wrap
    unconditionally."""
    return _Capture()


# ---------------------------------------------------------------------------
# Reading a capture
# ---------------------------------------------------------------------------


def find_traces(prof_dir: str) -> List[str]:
    """The capture's trace-event files (``*.trace.json.gz`` /
    ``*.trace.json``), oldest first. Empty when the capture was killed
    before ``stop_trace`` wrote them (only ``.xplane.pb`` — or nothing
    — survives a SIGKILL mid-capture)."""
    hits = (glob.glob(os.path.join(prof_dir, "**", "*.trace.json.gz"),
                      recursive=True)
            + glob.glob(os.path.join(prof_dir, "**", "*.trace.json"),
                        recursive=True))
    return sorted(hits)


def parse_trace(path: str) -> Tuple[List[dict], Dict[str, Any]]:
    """One profiler trace file -> (device records, stats). Device
    records are ``{"name", "ts", "dur", "lane", "track": "device"}``
    with ts/dur in **nanoseconds relative to the capture** (the
    profiler emits microseconds). When the capture holds the
    :data:`SYNC` annotation (in any host lane), ``stats["sync"]`` is its
    start on the same clock and every record carries it as ``sync``,
    with the capture span's sid as ``capture``. A truncated or corrupt
    file (SIGKILL mid-write) degrades to ``([], {"error": ...})`` —
    never raises."""
    stats: Dict[str, Any] = {"events": 0, "device": 0}
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rb") as f:
                doc = json.loads(f.read())
        else:
            with open(path, "rb") as f:
                doc = json.loads(f.read())
    except Exception as e:  # noqa: BLE001 — a torn capture is data loss,
        #                     not a failure of the run that owns it
        return [], {"events": 0, "device": 0,
                    "error": f"{type(e).__name__}: {e}"}
    events = doc.get("traceEvents") if isinstance(doc, dict) else None
    if not isinstance(events, list):
        return [], {"events": 0, "device": 0, "error": "no traceEvents"}

    proc_name: Dict[Any, str] = {}
    thread_name: Dict[tuple, str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            proc_name[e.get("pid")] = str(
                (e.get("args") or {}).get("name", ""))
        elif e.get("name") == "thread_name":
            thread_name[(e.get("pid"), e.get("tid"))] = str(
                (e.get("args") or {}).get("name", ""))

    def lane_of(e) -> Optional[str]:
        pname = proc_name.get(e.get("pid"), "")
        tname = thread_name.get((e.get("pid"), e.get("tid")), "")
        if pname.startswith("/device:"):
            return f"{pname}/{tname}" if tname else pname
        # CPU-only captures have no /device: process; the XLA runtime
        # threads carry the thunk/fusion executions and stand in
        if _XLA_THREAD_RE.search(tname):
            return f"{pname or 'host'}/{tname}"
        return None

    out: List[dict] = []
    sync = None
    for e in events:
        if e.get("ph") != "X":
            continue
        stats["events"] += 1
        name = str(e.get("name", "?"))
        if name.startswith("$"):        # python-tracer frames, not XLA
            continue
        try:
            ts = int(float(e["ts"]) * 1e3)          # us -> ns
            dur = int(float(e.get("dur", 0)) * 1e3)
        except (KeyError, TypeError, ValueError):
            continue
        lane = lane_of(e)
        if lane is None:
            if name == SYNC and sync is None:
                sync = (ts, (e.get("args") or {}).get("capture"))
            continue
        out.append({"name": name, "ts": ts, "dur": dur,
                    "lane": lane, "track": "device"})
        stats["device"] += 1
    if sync is not None:
        stats["sync"] = sync[0]
        try:
            capture = int(sync[1])
        except (TypeError, ValueError):
            capture = None
        for r in out:
            r["sync"] = sync[0]
            r["capture"] = capture
    out.sort(key=lambda r: r["ts"])
    return out, stats


def read_profile(run_dir: str) -> Tuple[List[dict], Dict[str, Any]]:
    """Every device record of a run's capture, capture-relative ns.
    ``(records, stats)`` with ``stats["files"]`` counting trace files
    found; absent/empty/killed captures answer ``([], ...)``."""
    pdir = profile_dir(run_dir)
    stats: Dict[str, Any] = {"files": 0, "events": 0, "device": 0,
                             "errors": 0}
    if not os.path.isdir(pdir):
        return [], stats
    records: List[dict] = []
    for path in find_traces(pdir):
        recs, s = parse_trace(path)
        stats["files"] += 1
        stats["events"] += s.get("events", 0)
        stats["device"] += s.get("device", 0)
        if s.get("error"):
            stats["errors"] += 1
        records.extend(recs)
    records.sort(key=lambda r: r["ts"])
    return records, stats


# ---------------------------------------------------------------------------
# Merging into the host trace
# ---------------------------------------------------------------------------


def merge_into_host(host_records: List[dict],
                    device_records: List[dict]) -> List[dict]:
    """Shift device records onto the host trace's clock and parent
    each under the host span that contained it.

    Alignment: the profiler's epoch is arbitrary (each capture starts
    its own), the host tracer's is ``time.monotonic_ns`` at process
    start. Each capture names one instant on both clocks: the
    :data:`SYNC` annotation's start in the profiler's (the record's
    ``sync``) and the ``sync_ts`` of the ``prof.capture`` span whose
    sid the annotation carries, so a record moves by the difference
    (the benchmark harness aligns its own trace the same way). Only a
    legacy capture, with no sync mark or no matching span, falls back
    to mapping its earliest device timestamp onto the earliest capture
    span's start (or, with none, the earliest host span's); that rule
    shifts every record early by the host work between the capture's
    start and its first device operation.

    Each device record then gets ``pid`` = the sid of the deepest
    :data:`HOST_PARENTS` span whose interval contains its midpoint
    (fallback: its capture span), and a synthetic per-lane ``tid`` so
    the export renders device lanes as their own tracks. Returns the
    NEW records only (callers concatenate)."""
    if not device_records:
        return []
    synced = {int(r["sid"]): r for r in host_records
              if r.get("name") == CAPTURE_SPAN and "sync_ts" in r
              and "sid" in r}

    def sync_anchor(r):
        return synced.get(r.get("capture")) if "sync" in r else None

    legacy = [r for r in device_records if sync_anchor(r) is None]
    anchors = [r for r in host_records
               if r.get("name") == CAPTURE_SPAN]
    if anchors:
        anchor_ts = min(int(r.get("ts", 0)) for r in anchors)
        anchor_sid = min(anchors, key=lambda r: int(r.get("ts", 0))
                         ).get("sid", 0)
    elif host_records:
        anchor_ts = min(int(r.get("ts", 0)) for r in host_records)
        anchor_sid = 0
    else:
        anchor_ts, anchor_sid = 0, 0
    legacy_offset = (anchor_ts - min(int(r["ts"]) for r in legacy)
                     if legacy else 0)

    parents = sorted(
        ((int(r.get("ts", 0)), int(r.get("ts", 0)) + int(r.get("dur", 0)),
          int(r.get("sid", 0)))
         for r in host_records if r.get("name") in HOST_PARENTS
         and r.get("dur", 0) > 0),
        key=lambda t: t[1] - t[0])      # narrowest (deepest) first

    rung_by_sid = {int(r.get("sid", 0)): r.get("rung")
                   for r in host_records
                   if r.get("name") in HOST_PARENTS
                   and r.get("rung") is not None}

    lanes: Dict[str, int] = {}
    out: List[dict] = []
    for r in device_records:
        cap = sync_anchor(r)
        if cap is None:
            ts, pid = int(r["ts"]) + legacy_offset, anchor_sid
        else:
            ts = int(r["ts"]) + int(cap["sync_ts"]) - int(r["sync"])
            pid = int(cap["sid"])
        dur = int(r.get("dur", 0))
        mid = ts + dur // 2
        for lo, hi, sid in parents:
            if lo <= mid <= hi:
                pid = sid
                break
        lane = str(r.get("lane", "device"))
        tid = lanes.setdefault(lane, DEVICE_TID_BASE + len(lanes))
        rec = {"name": r["name"], "ts": ts, "dur": dur, "tid": tid,
               "sid": 0, "track": "device", "lane": lane}
        if pid:
            rec["pid"] = pid
        if pid in rung_by_sid:
            rec["rung"] = rung_by_sid[pid]
        out.append(rec)
    return out


def merged_records(run_dir: str) -> Tuple[List[dict], Dict[str, Any]]:
    """Host trace + device capture of one run directory, merged.
    Degrades to the host records alone when there is no (readable)
    capture — the ``trace export`` contract either way."""
    host, stats = obs_trace.read_trace(
        os.path.join(run_dir, obs_trace.TRACE_NAME))
    dev, pstats = read_profile(run_dir)
    merged = host + merge_into_host(host, dev)
    stats = dict(stats)
    stats["device"] = len(dev)
    stats["profile-files"] = pstats.get("files", 0)
    stats["profile-errors"] = pstats.get("errors", 0)
    return merged, stats


# ---------------------------------------------------------------------------
# Kernel rollups
# ---------------------------------------------------------------------------


def kernel_self_times(device_records: List[dict]) -> List[dict]:
    """Per-(rung, name) SELF-time rollup over the device lanes. Device
    events nest by interval within a lane (a fusion inside a thunk
    executor inside an executable run), so self time is computed with
    an interval stack per lane: each event's duration minus the time
    the events nested directly inside it cover. Returns rows sorted by
    self time descending:
    ``{"name", "rung", "count", "self-ns", "total-ns"}``."""
    by_lane: Dict[str, List[dict]] = {}
    for r in device_records:
        by_lane.setdefault(str(r.get("lane", "?")), []).append(r)
    acc: Dict[tuple, Dict[str, int]] = {}

    def close(frame: dict) -> None:
        row = acc[frame["key"]]
        row["self-ns"] += frame["dur"] - frame["child"]

    for recs in by_lane.values():
        # equal-start ties: the longer event is the outer one
        recs = sorted(recs, key=lambda r: (int(r["ts"]),
                                           -int(r.get("dur", 0))))
        stack: List[dict] = []   # {"end", "key", "dur", "child"}
        for r in recs:
            ts = int(r["ts"])
            dur = int(r.get("dur", 0))
            while stack and stack[-1]["end"] <= ts:
                close(stack.pop())
            if stack:
                stack[-1]["child"] += dur
            rung = r.get("rung")
            key = (json.dumps(rung) if rung is not None else None,
                   str(r.get("name", "?")))
            row = acc.setdefault(key, {"count": 0, "self-ns": 0,
                                       "total-ns": 0})
            row["count"] += 1
            row["total-ns"] += dur
            stack.append({"end": ts + dur, "key": key, "dur": dur,
                          "child": 0})
        while stack:
            close(stack.pop())
    rows = [{"rung": (json.loads(k[0]) if k[0] else None), "name": k[1],
             **v} for k, v in acc.items()]
    rows.sort(key=lambda r: -r["self-ns"])
    return rows


def top_kernels(device_records: List[dict], k: int = 10) -> List[dict]:
    """The top-k kernel rows by self time (see
    :func:`kernel_self_times`) — the ``jtpu trace summary`` payload."""
    return kernel_self_times(device_records)[:max(0, k)]


def _reset_for_tests() -> None:
    global _DIR, _ACTIVE, _FAILED
    with _lock:
        _DIR, _ACTIVE, _FAILED = None, False, None
