#!/usr/bin/env python
"""CI profiling gate: run ONE profiled CPU-mode localkv check and
assert the merged host+device trace validates as Chrome/Perfetto JSON,
inside a wall-clock bound.

The device-profiling path (`JTPU_PROF=1` / `--profile`,
doc/observability.md "Device profiling") crosses four layers — the
jax.profiler capture in the supervised search, the capture-file parser,
the host/device clock merge, and the Chrome export — and a regression
in any of them would only surface on a real profiled run. This gate IS
that run, in CI terms: a real localkv suite (real daemons, real
sockets) checked through the device path with profiling on, then the
merged export validated structurally:

* the export is valid JSON with a non-empty ``traceEvents`` list where
  every event carries ``name`` + ``ph`` and complete events carry
  numeric ``ts``/``dur`` (what Perfetto's importer requires);
* the host trace contains ``checker.segment`` spans and a
  ``prof.capture`` anchor (the capture actually scoped the search);
* when the platform's profiler produced a readable capture (it does on
  the CPU backend), at least one device-track record merged in, with a
  ``pid`` parent link — the "kernel span nested under a host span"
  contract. A platform refusing capture is reported, not failed (the
  opt-in is specified to degrade to a silent no-op).

Usage: python tools/prof_gate.py [--budget SECONDS]
Exit code 0 iff the merged trace validates within the budget
(default 30 s; run next to tools/lint_gate.py in CI).
"""

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JTPU_PROF"] = "1"
# small segments: several checkpointed device calls, so the capture
# demonstrably spans segment boundaries
os.environ.setdefault("JTPU_SEGMENT_ITERS", "64")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--budget", type=float, default=30.0,
                    help="wall-clock bound for the whole gate (s)")
    ap.add_argument("--time-limit", type=int, default=4,
                    help="localkv workload seconds")
    args = ap.parse_args()
    t0 = time.time()

    from jepsen_tpu import cli, core
    from jepsen_tpu.obs import profiler, trace as trace_ns
    from jepsen_tpu.suites.localkv import localkv_test

    run_dir = os.path.join(
        tempfile.mkdtemp(prefix="jepsen-prof-gate-"), "local-kv", "run")
    test = localkv_test({"time-limit": args.time_limit,
                         "nemesis-period": 2, "backend": "tpu"})
    test["store-dir"] = run_dir
    test = core.run(test)
    if test["results"].get("valid") is not True:
        print(f"# prof-gate: FAILED — profiled localkv run did not "
              f"validate ({test['results'].get('valid')!r})",
              file=sys.stderr)
        return 1

    host, stats = trace_ns.read_trace(
        os.path.join(run_dir, trace_ns.TRACE_NAME))
    names = {r.get("name") for r in host}
    problems = []
    if "checker.segment" not in names:
        problems.append("no checker.segment host span recorded")
    captured = profiler.CAPTURE_SPAN in names
    dev, pstats = profiler.read_profile(run_dir)
    merged_dev = profiler.merge_into_host(host, dev)
    if captured and pstats["files"] and not merged_dev:
        problems.append("capture produced trace files but zero device "
                        "records merged")
    if merged_dev and not any(r.get("pid") for r in merged_dev):
        problems.append("no merged device record is parented under a "
                        "host span")

    # the merged export must validate as Chrome/Perfetto JSON
    export = os.path.join(os.path.dirname(run_dir), "chrome.json")
    rc = cli.run(cli.default_commands(),
                 ["trace", "export", "--store", run_dir, "-o", export])
    if rc != 0:
        problems.append(f"trace export exited {rc}")
    else:
        try:
            with open(export) as f:
                doc = json.load(f)
            evs = doc.get("traceEvents")
            if not isinstance(evs, list) or not evs:
                problems.append("export has no traceEvents")
            else:
                for e in evs:
                    if "name" not in e or "ph" not in e:
                        problems.append(f"malformed event: {e!r:.80}")
                        break
                    if e["ph"] == "X" and not (
                            isinstance(e.get("ts"), (int, float))
                            and isinstance(e.get("dur"), (int, float))):
                        problems.append(
                            f"complete event without numeric ts/dur: "
                            f"{e!r:.80}")
                        break
        except ValueError as e:
            problems.append(f"export is not valid JSON: {e}")

    wall = time.time() - t0
    if wall > args.budget:
        problems.append(f"gate overran its {args.budget:.0f}s budget "
                        f"({wall:.1f}s)")

    print(f"# prof-gate: {stats['spans']} host span(s), "
          f"{pstats['files']} capture file(s), {len(merged_dev)} device "
          f"record(s) merged"
          + ("" if captured else
             " (platform refused capture: opt-in degraded to no-op)")
          + f", {wall:.1f}s")
    if problems:
        for p in problems:
            print(f"# prof-gate: FAILED — {p}", file=sys.stderr)
        return 1
    print("# prof-gate: merged trace validates as Chrome/Perfetto JSON")
    return 0


if __name__ == "__main__":
    sys.exit(main())
