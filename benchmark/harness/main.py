"""One run of one cell: set-up, a measured window, an optional traced
stretch, then the comparison with the plain reference.

* Set-up: imports, device check, the pool of histories from ``--seed``,
  and one check of every pool item through the system, which compiles
  (or loads from the compile cache in the checkout) every shape the
  window will use. ``setup_s`` runs from the first line of ``run.py``
  to the window's start.
* Window: one caller, a closed loop over the pool in its seeded order.
  It closes at the end of the first whole pass over the pool that ends
  at or after ``--seconds``: every check in it is whole, every pool item
  is in it equally often, and the rate is all the work over all the
  time, the same work for every seed.
* ``--trace 1``: after the window, the next checks of the loop run under
  the profiler (at least ``TRACE_MIN_S`` of them) and the per-layer
  metrics are reported in place of the end-to-end ones. ``--trace 0``
  never starts the profiler.
* Correct: every verdict returned in the window and the traced stretch
  is held to the reference's verdict for the same history (per key for
  keyed runs). A verdict that is missing, undecided, or marked as
  computed off the device counts as wrong. The reference runs after the
  device memory peak is read, on the host.

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (verdicts), ``metrics``, ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``, each number
compared with its limit. A run that finds no accelerator, or fewer
chips than the cell asks for, prints no result and exits 3.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from harness import profile, spec, traffic

TRACE_MIN_S = 0.5
NO_DEVICE_EXIT = 3


@dataclass
class Check:
    item: Any
    t0: float
    t1: float
    answers: Dict[Any, Any]
    counters: Dict[str, float]
    work: list
    error: Optional[str] = None
    mono: tuple = (0, 0)       # (start, end) in time.monotonic_ns()

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0


@dataclass
class Run:
    """What metric readers see (``benchmark/metrics/<name>.py``)."""
    cell: spec.Cell
    device_kind: str
    chips: int
    setup_s: float
    warm: Dict[str, float]
    window: List[Check]
    window_s: float
    window_counters: Dict[str, float]
    traced: List[Check] = field(default_factory=list)
    trace: Optional[profile.Reduction] = None
    trace_counters: Dict[str, float] = field(default_factory=dict)


def _delta(after: Dict[str, float], before: Dict[str, float]):
    return {k: after[k] - before.get(k, 0) for k in after}


def _parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _devices(platform: str, chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != platform or len(devs) < chips:
        print(f"run.py: want {chips} {platform} device(s), JAX found "
              f"{len(devs)} {d.platform} ({d.device_kind})", file=sys.stderr)
        return None
    return devs


def _memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _one(driver, ctx, item, prepared, annotate: bool = False) -> Check:
    c0 = driver.counters()
    t0 = time.perf_counter()
    m0 = time.monotonic_ns()
    error, result = None, None
    try:
        if annotate:
            from jax.profiler import TraceAnnotation
            with TraceAnnotation("bench.check"):
                result = driver.check(ctx, prepared)
        else:
            result = driver.check(ctx, prepared)
    except Exception:  # noqa: BLE001 - a check that raises is a wrong answer
        error = traceback.format_exc()
        print(error, file=sys.stderr)
    t1 = time.perf_counter()
    ck = Check(item, t0, t1,
               driver.answers(item, result) if error is None
               else {k: None for k in item.histories},
               _delta(driver.counters(), c0),
               driver.work(result) if error is None else [], error,
               (m0, time.monotonic_ns()))
    return ck


def run_cell(argv, t0: float, platform: str = "tpu",
             overrides: Optional[Dict[str, Any]] = None,
             cache_dir: Optional[str] = os.path.join(spec.ROOT, ".jax_cache"),
             driver: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """The run's result object, or None when the devices are wrong.
    ``driver`` puts another driver in the configuration's place (the
    control, ``drivers/control.py``)."""
    args = _parse(argv)
    if spec.ROOT not in sys.path:
        sys.path.append(spec.ROOT)   # the system under test
    if cache_dir:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    cell = spec.find_cell(args.workload)
    params = spec.mix_params(cell)
    params.update(overrides or {})
    if params.get("loop", "closed") != "closed":
        raise ValueError("this harness runs closed loops with one caller")
    driver = spec.load_module("drivers", driver or cell.config["driver"])
    reference = spec.load_module("reference", cell.config["reference"])
    devs = _devices(platform, cell.chips)
    if devs is None:
        return None
    kind = devs[0].device_kind

    t_dev = time.perf_counter()
    pool = traffic.make_pool(params, args.seed,
                             refutes=lambda rows: not reference.check(rows))
    t_pool = time.perf_counter()
    ctx = driver.setup(cell.chips)
    prepared = [driver.prepare(ctx, item) for item in pool]
    c0 = driver.counters()
    t_warm = time.perf_counter()
    for item, p in zip(pool, prepared):
        _one(driver, ctx, item, p)   # a failure here fails the window too
    warm = _delta(driver.counters(), c0)

    # the pool and the prepared histories live to the end of the run:
    # keep them out of the collector's way, so that its work in the
    # window is the system's own garbage
    gc.collect()
    gc.freeze()
    w0 = time.perf_counter()
    setup_s = w0 - t0
    print(f"# setup: {setup_s:.3f}s = start and devices {t_dev - t0:.3f}s"
          f" + pool {t_pool - t_dev:.3f}s + prepare {t_warm - t_pool:.3f}s"
          f" + warm pass {w0 - t_warm:.3f}s (first calls "
          f"{warm['cold']:.0f}, {warm['compile-s']:.3f}s; persistent cache "
          f"hits {warm['persistent-hits']:.0f}, misses "
          f"{warm['persistent-misses']:.0f})", file=sys.stderr)
    c0 = driver.counters()
    window: List[Check] = []
    i = 0
    while i % len(pool) or not window or window[-1].t1 - w0 < args.seconds:
        j = i % len(pool)
        window.append(_one(driver, ctx, pool[j], prepared[j]))
        i += 1
    run = Run(cell, kind, cell.chips, setup_s, warm, window,
              window[-1].t1 - w0, _delta(driver.counters(), c0))

    stop_s = None
    if args.trace:
        c0 = driver.counters()
        sess = profile.Session()
        s0 = time.perf_counter()
        while not run.traced or time.perf_counter() - s0 < TRACE_MIN_S:
            j = i % len(pool)
            run.traced.append(_one(driver, ctx, pool[j], prepared[j],
                                   annotate=True))
            i += 1
        tr = sess.stop("bench.check")
        stop_s = sess.stop_s
        run.trace_counters = _delta(driver.counters(), c0)
        lo, hi = run.traced[0].mono[0], run.traced[-1].mono[1]
        spans = [s for s in driver.spans() if s[1] > lo and s[0] < hi]
        run.trace = profile.reduce(tr, profile.to_profiler_clock(tr, spans))
        if run.trace is None:
            print(f"# trace: nothing to reduce; it held {tr.lines}",
                  file=sys.stderr)

    memory_peak = _memory_peak(devs[:cell.chips])
    del prepared, ctx
    gc.collect()

    r0 = time.perf_counter()
    truth: Dict[Any, bool] = {}
    attempted = failed = 0
    for ck in run.window + run.traced:
        for key, got in ck.answers.items():
            memo = (id(ck.item), key)
            if memo not in truth:
                truth[memo] = reference.check(ck.item.histories[key])
            attempted += 1
            failed += got is not truth[memo]
    reference_s = time.perf_counter() - r0

    want = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in want:
        value = spec.load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    out = {"correct": failed == 0,
           "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.mean_busy_s
        device["window_s"] = run.trace.window_s
        top = sorted(run.trace.op_self_s.items(), key=lambda kv: -kv[1])
        out["breakdown"] = {"device_ops": [[n, s] for n, s in top[:10]],
                            "idle_gaps": [[n, s] for n, s in
                                          run.trace.gaps[:10]]}
    refuted = sum(1 for v in truth.values() if v is False)
    walls: Dict[int, List[float]] = {}
    for ck in run.window:
        walls.setdefault(ck.item.base, []).append(ck.wall_s)
    print("# checks: base:median wall s " + " ".join(
        f"{b}:{statistics.median(w):.3f}" for b, w in sorted(walls.items())),
        file=sys.stderr)
    print(f"# run: window {run.window_s:.3f}s, {len(run.window)} checks; "
          f"traced {len(run.traced)} checks, profiler stop "
          f"{'-' if stop_s is None else round(stop_s, 3)}s; reference "
          f"{reference_s:.3f}s over {len(truth)} histories "
          f"({refuted} refuted)", file=sys.stderr)
    print(f"compared: wrong_verdicts={failed} limit=0 (of {attempted})",
          file=sys.stderr, flush=True)
    out["compared"] = {"wrong_verdicts": {"value": failed, "limit": 0}}
    return out


def main(argv, t0: float) -> int:
    try:
        out = run_cell(argv, t0)
    except Exception:  # noqa: BLE001 - no result line on a broken run
        traceback.print_exc()
        return 1
    if out is None:
        return NO_DEVICE_EXIT
    print(json.dumps(out), flush=True)
    return 0
