"""Device profiling + fleet telemetry (jepsen_tpu.obs.profiler /
.fleet): the JTPU_PROF opt-in and its no-op guarantees, capture-file
parsing and host/device merging, per-rung kernel rollups, compile-cache
accounting and the `# compile:` line, the fleet merge with skewed
clocks, and the watch/web/CLI surfaces. Tier-1 under the ``prof``
marker (doc/observability.md "Device profiling" / "Compile accounting"
/ "Fleet view" are the operator views)."""

import gzip
import json
import os

import pytest

from jepsen_tpu.obs import fleet as fleet_ns
from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.obs import profiler
from jepsen_tpu.obs import trace as obs_trace

pytestmark = pytest.mark.prof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _supervised(tmp_store=None, **kw):
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.ops.encode import pack_with_init
    from jepsen_tpu.resilience import supervised_check_packed
    from jepsen_tpu.testing import simulate_register_history
    h = simulate_register_history(150, n_procs=5, n_vals=4, seed=3)
    p, kernel = pack_with_init(h, CASRegister())
    if tmp_store is not None:
        profiler.attach(str(tmp_store))
    try:
        return supervised_check_packed(p, kernel, capacity=64, expand=8,
                                       segment_iters=8, **kw)
    finally:
        profiler.detach()


# ---------------------------------------------------------------------------
# The opt-in and its no-op guarantees
# ---------------------------------------------------------------------------


class TestProfilerOptIn:
    def setup_method(self):
        profiler._reset_for_tests()

    def test_default_off(self, monkeypatch):
        monkeypatch.delenv("JTPU_PROF", raising=False)
        assert profiler.enabled() is False
        monkeypatch.setenv("JTPU_PROF", "1")
        assert profiler.enabled() is True
        # profiling requires the host tracer: JTPU_TRACE=0 wins
        monkeypatch.setenv("JTPU_TRACE", "0")
        assert profiler.enabled() is False

    def test_prof_off_writes_nothing(self, tmp_path, monkeypatch):
        monkeypatch.delenv("JTPU_PROF", raising=False)
        r = _supervised(tmp_store=tmp_path)
        assert r["valid"] is True
        assert sorted(os.listdir(tmp_path)) == []

    def test_unsupported_platform_is_a_silent_noop(self, tmp_path,
                                                   monkeypatch):
        # JTPU_PROF=1 on a platform whose profiler refuses to start:
        # byte-identical artifacts to JTPU_PROF=0 (same artifact set —
        # no profile/ dir, nothing else) and identical verdicts. The
        # JTPU_TRACE=0 tests' degradation contract, one knob over.
        import jax
        monkeypatch.setenv("JTPU_PROF", "1")

        def refuse(*a, **k):
            raise RuntimeError("profiler unsupported on this platform")

        monkeypatch.setattr(jax.profiler, "start_trace", refuse)
        on_dir = tmp_path / "on"
        on_dir.mkdir()
        r1 = _supervised(tmp_store=on_dir)
        monkeypatch.setenv("JTPU_PROF", "0")
        profiler._reset_for_tests()
        off_dir = tmp_path / "off"
        off_dir.mkdir()
        r0 = _supervised(tmp_store=off_dir)
        assert r1["valid"] == r0["valid"]
        assert r1["levels"] == r0["levels"]
        assert sorted(os.listdir(on_dir)) == sorted(os.listdir(off_dir))
        assert not os.path.isdir(profiler.profile_dir(str(on_dir)))
        # the refusal is sticky: later captures no-op without retrying
        monkeypatch.setenv("JTPU_PROF", "1")
        r2 = _supervised(tmp_store=on_dir)
        assert r2["valid"] is True
        assert not os.path.isdir(profiler.profile_dir(str(on_dir)))

    def test_capture_noop_without_run_dir(self, monkeypatch):
        monkeypatch.setenv("JTPU_PROF", "1")
        with profiler.capture() as cap:
            assert cap.dir is None  # nothing armed: nothing captured

    def test_real_capture_on_cpu(self, tmp_path, monkeypatch):
        # the CPU backend's profiler is real: the capture directory
        # appears, the trace file parses, and merged records nest under
        # checker.segment host spans — the acceptance contract, on the
        # capture this host can actually make
        monkeypatch.setenv("JTPU_PROF", "1")
        tr0 = obs_trace.tracer().recorded
        r = _supervised(tmp_store=tmp_path)
        assert r["valid"] is True
        pdir = profiler.profile_dir(str(tmp_path))
        assert os.path.isdir(pdir)
        assert profiler.find_traces(pdir), "capture wrote no trace file"
        dev, stats = profiler.read_profile(str(tmp_path))
        assert stats["files"] >= 1 and stats["errors"] == 0
        assert dev, "no device-lane records extracted"
        host = [s for s in obs_trace.tracer().spans()]
        assert any(s["name"] == profiler.CAPTURE_SPAN for s in host)
        merged = profiler.merge_into_host(host, dev)
        assert merged
        seg_sids = {s["sid"] for s in host
                    if s["name"] == "checker.segment"}
        assert any(m.get("pid") in seg_sids for m in merged), \
            "no device record parented under a checker.segment span"
        assert obs_trace.tracer().recorded > tr0


#: One supervised check captured with a host lead-in before its first
#: segment, traced into a run directory: argv run_dir, lead-in seconds.
_LEAD_IN_RUN = """
import sys, time
from jepsen_tpu import resilience
from jepsen_tpu.models import CASRegister
from jepsen_tpu.obs import profiler, trace
from jepsen_tpu.ops.encode import pack_with_init
from jepsen_tpu.testing import simulate_register_history
run_dir, lead_in = sys.argv[1], float(sys.argv[2])
h = simulate_register_history(150, n_procs=5, n_vals=4, seed=3)
p, kernel = pack_with_init(h, CASRegister())

def check():
    return resilience.supervised_check_packed(
        p, kernel, capacity=64, expand=8, segment_iters=8)

check()   # compile outside the capture
resilience._inject_fault = (
    lambda ctx: time.sleep(lead_in) if ctx["segment"] == 0 else None)
trace.start_run(run_dir)
profiler.attach(run_dir)
assert check()["valid"] is True
profiler.detach()
trace.finish_run()
"""


class TestSharedClockMerge:
    """The merge aligns on the capture's sync mark, so host work between
    the capture's start and its first device operation cannot shift the
    device records out of the host spans that launched them. The capture
    runs in a process of its own: the profiler records every thread of
    its process, and no other test's threads may add records."""

    LEAD_IN_S = 0.06

    @pytest.fixture(scope="class")
    def run_dir(self, tmp_path_factory):
        import subprocess
        import sys
        d = tmp_path_factory.mktemp("lead-in")
        env = dict(os.environ, JTPU_PROF="1", JTPU_TRACE="1",
                   JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        p = subprocess.run(
            [sys.executable, "-c", _LEAD_IN_RUN, str(d),
             str(self.LEAD_IN_S)], env=env, capture_output=True,
            text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-3000:]
        return str(d)

    def _read(self, run_dir):
        host, _ = obs_trace.read_trace(
            os.path.join(run_dir, obs_trace.TRACE_NAME))
        dev, _ = profiler.read_profile(run_dir)
        assert dev, "no device-lane records extracted"
        (cap,) = [s for s in host if s["name"] == profiler.CAPTURE_SPAN]
        return host, dev, cap

    def test_device_records_lie_inside_their_parent_span(self, run_dir):
        host, dev, cap = self._read(run_dir)
        assert all(r["capture"] == cap["sid"] for r in dev)
        merged, _ = profiler.merged_records(run_dir)
        merged = [m for m in merged if m.get("track") == "device"]
        assert len(merged) == len(dev)
        # every timed operation starts inside a device-call span, and
        # one that also ends inside it is parented under it (the CPU
        # client's pool threads may close an event after the host has
        # its results, so an end can overhang a loaded host's span)
        calls = [s for s in host if s["name"] in profiler.HOST_PARENTS]
        ops = [m for m in merged if m["dur"] > 0]
        assert ops
        for m in ops:
            (holder,) = [s for s in calls
                         if s["ts"] <= m["ts"] <= s["ts"] + s["dur"]]
            if m["ts"] + m["dur"] <= holder["ts"] + holder["dur"]:
                assert m["pid"] == holder["sid"], m
        # the first device op comes after the host lead-in
        first = min(m["ts"] for m in merged)
        assert first - cap["ts"] >= self.LEAD_IN_S * 1e9

    def test_capture_without_sync_merges_by_legacy_rule(self, run_dir):
        host, dev, cap = self._read(run_dir)
        legacy = [{k: v for k, v in r.items()
                   if k not in ("sync", "capture")} for r in dev]
        merged = profiler.merge_into_host(host, legacy)
        # the earliest device op lands on the capture span's start,
        # ahead of the lead-in the host spent before it
        assert min(m["ts"] for m in merged) == cap["ts"]
        synced = profiler.merge_into_host(host, dev)
        shift = min(m["ts"] for m in synced) - min(m["ts"]
                                                   for m in merged)
        assert shift >= self.LEAD_IN_S * 1e9


# ---------------------------------------------------------------------------
# Parsing + merging (synthetic captures: deterministic, platform-free)
# ---------------------------------------------------------------------------


def _write_capture(tmp_path, events, gz=True):
    pdir = os.path.join(str(tmp_path), profiler.PROFILE_DIRNAME,
                        "plugins", "profile", "2026_08_04")
    os.makedirs(pdir, exist_ok=True)
    doc = {"displayTimeUnit": "ns", "traceEvents": events}
    data = json.dumps(doc).encode()
    if gz:
        path = os.path.join(pdir, "host.trace.json.gz")
        with gzip.open(path, "wb") as f:
            f.write(data)
    else:
        path = os.path.join(pdir, "host.trace.json")
        with open(path, "wb") as f:
            f.write(data)
    return path


_TPU_EVENTS = [
    {"ph": "M", "pid": 9, "name": "process_name",
     "args": {"name": "/device:TPU:0"}},
    {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
     "args": {"name": "XLA Ops"}},
    {"ph": "M", "pid": 7, "name": "process_name",
     "args": {"name": "/host:CPU"}},
    {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
     "args": {"name": "python"}},
    # device kernels: an outer executable with two nested fusions
    {"ph": "X", "pid": 9, "tid": 1, "ts": 100.0, "dur": 50.0,
     "name": "jit_seg.1"},
    {"ph": "X", "pid": 9, "tid": 1, "ts": 110.0, "dur": 20.0,
     "name": "fusion.3"},
    {"ph": "X", "pid": 9, "tid": 1, "ts": 135.0, "dur": 10.0,
     "name": "sort.7"},
    # host python frames must NOT extract
    {"ph": "X", "pid": 7, "tid": 2, "ts": 90.0, "dur": 80.0,
     "name": "$api.py:141 jit"},
]


class TestParseMerge:
    def test_parse_extracts_device_lanes_only(self, tmp_path):
        path = _write_capture(tmp_path, _TPU_EVENTS)
        recs, stats = profiler.parse_trace(path)
        assert stats["device"] == 3
        assert [r["name"] for r in recs] == ["jit_seg.1", "fusion.3",
                                             "sort.7"]
        # us -> ns, lane carries device + thread name
        assert recs[0]["ts"] == 100_000 and recs[0]["dur"] == 50_000
        assert recs[0]["lane"] == "/device:TPU:0/XLA Ops"
        assert all(r["track"] == "device" for r in recs)

    def test_parse_tolerates_garbage_and_truncation(self, tmp_path):
        pdir = os.path.join(str(tmp_path), profiler.PROFILE_DIRNAME)
        os.makedirs(pdir)
        bad = os.path.join(pdir, "torn.trace.json.gz")
        with open(bad, "wb") as f:
            f.write(b"\x1f\x8b\x08\x00garbage-not-a-gzip-stream")
        recs, stats = profiler.parse_trace(bad)
        assert recs == [] and "error" in stats
        recs, stats = profiler.read_profile(str(tmp_path))
        assert recs == [] and stats["errors"] == 1
        # absent capture: empty, no exception
        recs, stats = profiler.read_profile(str(tmp_path / "nope"))
        assert recs == [] and stats["files"] == 0

    def test_xla_runtime_threads_stand_in_on_cpu(self, tmp_path):
        events = [
            {"ph": "M", "pid": 7, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            {"ph": "M", "pid": 7, "tid": 3, "name": "thread_name",
             "args": {"name": "tf_XLATfrtCpuClient/-117"}},
            {"ph": "X", "pid": 7, "tid": 3, "ts": 10.0, "dur": 5.0,
             "name": "broadcast_add_fusion"},
        ]
        path = _write_capture(tmp_path, events, gz=False)
        recs, stats = profiler.parse_trace(path)
        assert stats["device"] == 1
        assert recs[0]["name"] == "broadcast_add_fusion"

    def test_merge_aligns_clock_and_parents(self):
        host = [
            {"name": profiler.CAPTURE_SPAN, "ts": 1_000_000,
             "dur": 300_000, "tid": 5, "sid": 1},
            {"name": "checker.segment", "ts": 1_050_000, "dur": 100_000,
             "tid": 5, "sid": 2, "pid": 1, "rung": [64, 32, 8]},
            {"name": "checker.segment", "ts": 1_200_000, "dur": 80_000,
             "tid": 5, "sid": 3, "pid": 1, "rung": [32, 32, 4]},
        ]
        dev = [
            # startup work before the first segment (compile etc.)
            {"name": "startup", "ts": 500_000, "dur": 10_000,
             "lane": "/device:TPU:0/XLA Ops", "track": "device"},
            {"name": "fusion.1", "ts": 560_000, "dur": 40_000,
             "lane": "/device:TPU:0/XLA Ops", "track": "device"},
            {"name": "fusion.2", "ts": 710_000, "dur": 40_000,
             "lane": "/device:TPU:0/XLA Ops", "track": "device"},
        ]
        merged = profiler.merge_into_host(host, dev)
        # earliest device ts (500_000) maps onto the capture span start
        # (1_000_000): offset +500_000
        assert merged[0]["ts"] == 1_000_000
        assert merged[0]["pid"] == 1          # pre-segment: capture
        assert merged[1]["ts"] == 1_060_000   # inside segment sid=2
        assert merged[1]["pid"] == 2
        assert merged[1]["rung"] == [64, 32, 8]
        assert merged[2]["ts"] == 1_210_000   # inside segment sid=3
        assert merged[2]["pid"] == 3
        assert merged[2]["rung"] == [32, 32, 4]
        assert merged[0]["tid"] >= profiler.DEVICE_TID_BASE
        # chrome export of the merged stream stays structurally valid
        doc = obs_trace.to_chrome(host + merged)
        assert all("name" in e and "ph" in e
                   for e in doc["traceEvents"])

    def test_sync_mark_aligns_each_capture(self, tmp_path):
        host_lane = [
            {"ph": "M", "pid": 7, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
             "args": {"name": "python"}},
            {"ph": "X", "pid": 7, "tid": 2, "ts": 20.0, "dur": 1.0,
             "name": profiler.SYNC, "args": {"capture": "4"}},
        ]
        path = _write_capture(tmp_path, _TPU_EVENTS + host_lane)
        recs, stats = profiler.parse_trace(path)
        assert stats["sync"] == 20_000 and stats["device"] == 3
        assert all(r["sync"] == 20_000 and r["capture"] == 4
                   for r in recs)
        host = [
            {"name": profiler.CAPTURE_SPAN, "ts": 1_000_000,
             "dur": 300_000, "tid": 5, "sid": 4, "sync_ts": 1_000_500},
            {"name": "checker.segment", "ts": 1_070_000, "dur": 100_000,
             "tid": 5, "sid": 6, "pid": 4},
        ]
        merged = profiler.merge_into_host(host, recs)
        # profiler 20 us is host 1_000_500 ns: +980_500 ns for every op
        assert [m["ts"] for m in merged] == [1_080_500, 1_090_500,
                                             1_115_500]
        assert {m["pid"] for m in merged} == {6}
        # a record whose capture span is missing falls back to the
        # legacy rule, onto the earliest capture span's start
        orphan = [dict(recs[0], capture=99)]
        assert profiler.merge_into_host(host, orphan)[0]["ts"] == 1_000_000

    def test_merge_empty_device_is_empty(self):
        assert profiler.merge_into_host([{"name": "x", "ts": 0,
                                          "dur": 1, "sid": 1}], []) == []


class TestKernelRollup:
    def test_self_time_subtracts_nested(self):
        dev = [
            {"name": "exec", "ts": 0, "dur": 100, "lane": "L",
             "rung": [64, 32, 8]},
            {"name": "fusion", "ts": 10, "dur": 60, "lane": "L",
             "rung": [64, 32, 8]},
            {"name": "sort", "ts": 20, "dur": 30, "lane": "L",
             "rung": [64, 32, 8]},
            # a second rung's copy of the same kernel rolls up apart
            {"name": "fusion", "ts": 200, "dur": 50, "lane": "L",
             "rung": [32, 32, 4]},
        ]
        rows = profiler.kernel_self_times(dev)
        by = {(tuple(r["rung"]), r["name"]): r for r in rows}
        assert by[((64, 32, 8), "exec")]["self-ns"] == 40   # 100-60
        assert by[((64, 32, 8), "fusion")]["self-ns"] == 30  # 60-30
        assert by[((64, 32, 8), "sort")]["self-ns"] == 30
        assert by[((32, 32, 4), "fusion")]["self-ns"] == 50
        # sorted by self time descending; top_kernels truncates
        assert rows[0]["self-ns"] >= rows[-1]["self-ns"]
        assert len(profiler.top_kernels(dev, k=2)) == 2

    def test_separate_lanes_do_not_nest(self):
        dev = [
            {"name": "a", "ts": 0, "dur": 100, "lane": "L1"},
            {"name": "b", "ts": 10, "dur": 50, "lane": "L2"},
        ]
        rows = {r["name"]: r for r in profiler.kernel_self_times(dev)}
        assert rows["a"]["self-ns"] == 100
        assert rows["b"]["self-ns"] == 50


# ---------------------------------------------------------------------------
# Compile-cache accounting
# ---------------------------------------------------------------------------


class TestCompileAccounting:
    def test_cold_then_cache_hit(self):
        from jepsen_tpu.checker import tpu as T
        from jepsen_tpu.models import CASRegister
        from jepsen_tpu.testing import simulate_register_history
        h = simulate_register_history(90, n_procs=3, n_vals=4, seed=41)
        before = T.compile_snapshot()
        # capacity 72 is no ladder rung: a fresh executable shape
        r = T.check_history_tpu(h, CASRegister(), capacity=72,
                                expand=8, segment_iters=16)
        assert r["valid"] is True
        d1 = T.compile_delta(before)
        assert d1["cold"] >= 1
        assert d1["compile-s"] > 0
        mid = T.compile_snapshot()
        r = T.check_history_tpu(h, CASRegister(), capacity=72,
                                expand=8, segment_iters=16)
        d2 = T.compile_delta(mid)
        assert d2["cold"] == 0
        assert d2["cache-hits"] >= 1
        assert d2["execute-s"] > 0

    def test_compile_line_format(self):
        from jepsen_tpu.checker import tpu as T
        delta = {"cold": 2, "cache-hits": 5, "persistent-hits": 0,
                 "persistent-misses": 0, "compile-s": 1.5,
                 "execute-s": 0.25, "transfer-bytes": 2_000_000}
        line = T.compile_line(delta, wall_s=2.0)
        assert line.startswith("# compile: cold=2 shape(s) 1.500s")
        assert "cache-hit=5" in line
        assert "execute=0.250s" in line
        assert "transfer=2.0MB" in line
        assert "host=0.250s of 2.000s wall" in line

    def test_persistent_cache_listener_counts_hits(self):
        from jepsen_tpu.checker import tpu as T
        T._ensure_cache_listener()
        try:
            from jax import monitoring
        except ImportError:
            pytest.skip("no jax.monitoring")
        h0 = T._PERSISTENT_HIT.total()
        m0 = T._PERSISTENT_MISS.total()
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event("/jax/compilation_cache/cache_misses")
        assert T._PERSISTENT_HIT.total() == h0 + 1
        assert T._PERSISTENT_MISS.total() == m0 + 1

    def test_segment_path_counts_too(self):
        from jepsen_tpu.checker import tpu as T
        before = T.compile_snapshot()
        r = _supervised()   # capacity=64/8, segment_iters=8
        assert r["valid"] is True
        d = T.compile_delta(before)
        # either cold (first run in this process) or cache-hits moved;
        # every segment is one accounted call
        assert d["cold"] + d["cache-hits"] >= r["segments"]


# ---------------------------------------------------------------------------
# Fleet merge
# ---------------------------------------------------------------------------


def _host_dir(tmp_path, name, epoch_ns, imbalance=None, headroom=None,
              state="done", level=500):
    d = tmp_path / name
    d.mkdir()
    recs = [
        {"name": "core.run", "ts": epoch_ns, "dur": 9_000_000,
         "tid": 1, "sid": 1},
        {"name": "checker.device.batch", "ts": epoch_ns + 1_000_000,
         "dur": 2_000_000, "tid": 1, "sid": 2, "pid": 1},
        {"name": "client.invoke", "ts": epoch_ns + 4_000_000,
         "dur": 1_000, "tid": 2, "sid": 3},
    ]
    with open(d / "trace.jsonl", "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    metrics = {
        "jtpu_search_levels_total": {
            "kind": "counter", "help": "levels",
            "series": {"": float(level)}},
    }
    if imbalance is not None:
        metrics["jtpu_shard_imbalance_ratio"] = {
            "kind": "gauge", "help": "imb", "series": {"": imbalance}}
    if headroom is not None:
        metrics["jtpu_device_headroom_ratio"] = {
            "kind": "gauge", "help": "head", "series": {"": headroom}}
    with open(d / "metrics.json", "w") as f:
        json.dump(metrics, f)
    with open(d / "progress.json", "w") as f:
        json.dump({"state": state, "ts": 1.0, "level": level,
                   "level-budget": 1000, "frontier-rows": 8,
                   "segments": 3}, f)
    return str(d)


class TestFleetMerge:
    def test_merge_aligns_skewed_clocks_and_labels_hosts(self,
                                                         tmp_path):
        # two synthetic hosts whose tracer epochs differ by 5 s: after
        # the merge both anchor spans start at the same instant, every
        # record carries its host, and each (host, tid) track is
        # monotonic
        d1 = _host_dir(tmp_path, "host-a", epoch_ns=1_000_000,
                       imbalance=1.4, headroom=0.3)
        d2 = _host_dir(tmp_path, "host-b",
                       epoch_ns=5_000_000_000, imbalance=1.05,
                       headroom=0.6)
        merged = fleet_ns.merge([d1, d2])
        assert merged["hosts"] == ["host-a", "host-b"]
        assert merged["anchor"] == "checker.device.batch"
        anchors = {}
        for r in merged["trace"]:
            assert r["host"] in ("host-a", "host-b")
            if r["name"] == "checker.device.batch":
                anchors[r["host"]] = r["ts"]
        assert anchors["host-a"] == anchors["host-b"]
        # monotonic per (host, tid) track
        last = {}
        for r in merged["trace"]:
            key = (r["host"], r.get("tid"))
            assert r["ts"] >= last.get(key, float("-inf"))
            last[key] = r["ts"]
        # metrics series re-keyed with a host label + fleet aggregates
        lv = merged["metrics"]["jtpu_search_levels_total"]
        assert lv["series"]['{host="host-a"}'] == 500.0
        assert lv["series"]['{host="host-b"}'] == 500.0
        assert lv["fleet"][""] == 1000.0          # counters sum
        imb = merged["metrics"]["jtpu_shard_imbalance_ratio"]
        assert imb["fleet"][""] == 1.4            # gauges max
        # per-host summary rows carry the fleet-view signals
        rows = {s["host"]: s for s in merged["summary"]}
        assert rows["host-a"]["imbalance"] == pytest.approx(1.4)
        assert rows["host-b"]["headroom"] == pytest.approx(0.6)

    def test_merge_tolerates_ragged_hosts(self, tmp_path):
        d1 = _host_dir(tmp_path, "full", epoch_ns=0)
        bare = tmp_path / "bare"
        bare.mkdir()
        (bare / "progress.json").write_text(
            json.dumps({"state": "searching", "ts": 2.0, "level": 10,
                        "level-budget": 100}))
        merged = fleet_ns.merge([d1, str(bare)])
        assert merged["anchor"] is None  # one host has no trace
        rows = {s["host"]: s for s in merged["summary"]}
        assert rows["bare"]["spans"] == 0
        assert rows["bare"]["level"] == 10
        lines = fleet_ns.format_fleet(merged)
        assert any("bare:" in ln for ln in lines)

    def test_fleet_chrome_export_one_process_per_host(self, tmp_path):
        d1 = _host_dir(tmp_path, "h1", epoch_ns=0)
        d2 = _host_dir(tmp_path, "h2", epoch_ns=7_000_000_000)
        doc = fleet_ns.to_chrome(fleet_ns.merge([d1, d2]))
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert {m["args"]["name"] for m in metas} == \
            {"jtpu:h1", "jtpu:h2"}
        pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] != "M"}
        assert pids == {1, 2}

    def test_watch_fleet_cli(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d1 = _host_dir(tmp_path, "host-a", epoch_ns=0, imbalance=1.2,
                       headroom=0.4)
        d2 = _host_dir(tmp_path, "host-b", epoch_ns=3_000_000_000,
                       headroom=0.1)
        rc = cli.run(cli.default_commands(),
                     ["watch", "--fleet", d1, d2, "--once"])
        assert rc == cli.OK
        out = capsys.readouterr().out
        assert "host-a:" in out and "host-b:" in out
        assert "imbalance 1.20x" in out
        assert "headroom 10%" in out
        # a missing host dir is an argument error, not a crash
        rc = cli.run(cli.default_commands(),
                     ["watch", "--fleet", str(tmp_path / "nope"),
                      "--once"])
        assert rc == cli.INVALID_ARGS

    def test_web_fleet_endpoint(self, tmp_path):
        import urllib.error
        import urllib.request

        from jepsen_tpu import web
        run = tmp_path / "t" / "20260804T000002.000"
        run.mkdir(parents=True)
        _host_dir(run, "host-a", epoch_ns=0, imbalance=1.3,
                  headroom=0.5)
        _host_dir(run, "host-b", epoch_ns=2_000_000_000,
                  imbalance=1.0, headroom=0.2)
        server = web.serve_background(root=str(tmp_path))
        base = f"http://127.0.0.1:{server.server_port}"
        try:
            page = urllib.request.urlopen(
                base + "/fleet/t/20260804T000002.000").read().decode()
            assert "host-a" in page and "host-b" in page
            assert "1.30x" in page and "20%" in page
            with urllib.request.urlopen(
                    base + "/fleet/t/20260804T000002.000?format=json"
                    ) as r:
                doc = json.load(r)
            assert doc["hosts"] == ["host-a", "host-b"]
            assert len(doc["summary"]) == 2
            # a run without host artifacts 404s rather than 500s
            (tmp_path / "t" / "empty").mkdir()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/fleet/t/empty")
            assert ei.value.code == 404
        finally:
            server.shutdown()

    def test_single_host_run_is_a_one_host_fleet(self, tmp_path):
        d = _host_dir(tmp_path, "solo", epoch_ns=0)
        assert fleet_ns.discover_hosts(d) == [d]
        merged = fleet_ns.merge(fleet_ns.discover_hosts(d))
        assert merged["hosts"] == ["solo"]


# ---------------------------------------------------------------------------
# CLI surfaces: trace summary --format json + kernel lines
# ---------------------------------------------------------------------------


class TestTraceCLISurfaces:
    def _store(self, tmp_path, with_profile=False):
        d = tmp_path / "run"
        d.mkdir()
        tr = obs_trace.Tracer(path=str(d / "trace.jsonl"))
        with tr.span(profiler.CAPTURE_SPAN):
            with tr.span("checker.segment", phase="execute",
                         rung=[64, 32, 8]):
                pass
        tr.detach()
        if with_profile:
            _write_capture(d, _TPU_EVENTS)
        return str(d)

    def test_summary_format_json(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d = self._store(tmp_path, with_profile=True)
        rc = cli.run(cli.default_commands(),
                     ["trace", "summary", "--store", d,
                      "--format", "json"])
        assert rc == cli.OK
        out = capsys.readouterr().out
        doc = json.loads(out)
        assert doc["stats"]["spans"] == 2
        assert "checker.segment" in doc["summary"]
        assert "self-time" in doc
        assert doc["kernels"], "device kernels missing from JSON"
        assert {"name", "self-ns", "count"} <= set(doc["kernels"][0])

    def test_summary_prints_kernel_table(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d = self._store(tmp_path, with_profile=True)
        rc = cli.run(cli.default_commands(),
                     ["trace", "summary", "--store", d])
        assert rc == cli.OK
        out = capsys.readouterr().out
        assert "device kernels" in out
        assert "fusion.3" in out

    def test_export_merges_device_track(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d = self._store(tmp_path, with_profile=True)
        out_path = str(tmp_path / "chrome.json")
        rc = cli.run(cli.default_commands(),
                     ["trace", "export", "--store", d, "-o", out_path])
        assert rc == cli.OK
        with open(out_path) as f:
            doc = json.load(f)
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"checker.segment", "jit_seg.1", "fusion.3"} <= names
        # the device events ride a synthetic device tid
        dev = [e for e in doc["traceEvents"]
               if e["name"] == "jit_seg.1"]
        assert dev[0]["tid"] >= profiler.DEVICE_TID_BASE

    def test_export_without_profile_unchanged(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d = self._store(tmp_path, with_profile=False)
        rc = cli.run(cli.default_commands(),
                     ["trace", "summary", "--store", d])
        assert rc == cli.OK
        out = capsys.readouterr().out
        assert "device kernels" not in out


# ---------------------------------------------------------------------------
# Satellites: ring-drop counter, HELP escaping, bench-gate attribution
# ---------------------------------------------------------------------------


class TestSatellites:
    def test_ring_overflow_counts_drops(self):
        c = obs_metrics.REGISTRY.counter("jtpu_trace_spans_dropped_total")
        before = c.value()
        tr = obs_trace.Tracer(ring=16)
        for i in range(36):
            with tr.span(f"s{i}"):
                pass
        assert tr.dropped == 20
        assert c.value() - before == 20

    def test_help_text_escaping(self):
        reg = obs_metrics.Registry()
        reg.counter("jtpu_esc_total", "line one\nline two \\ back")
        text = reg.to_prometheus()
        assert ("# HELP jtpu_esc_total line one\\nline two \\\\ back"
                in text)
        assert "\nline two" not in text.replace("\\n", "")

    def test_counter_and_histogram_totals(self):
        reg = obs_metrics.Registry()
        c = reg.counter("jtpu_tot_total")
        c.inc(2, kind="a")
        c.inc(3, kind="b")
        assert c.total() == 5
        assert c.total(kind="a") == 2
        h = reg.histogram("jtpu_tot_seconds", buckets=(1.0,))
        h.observe(0.5, phase="execute", kind="x")
        h.observe(2.0, phase="execute", kind="y")
        h.observe(9.0, phase="compile", kind="x")
        t = h.total(phase="execute")
        assert t["count"] == 2 and t["sum"] == pytest.approx(2.5)

    def test_bench_gate_attribution(self, tmp_path):
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import bench_gate
        base = {"value": 1.0, "cold_s": 10.0, "platform": "cpu",
                "compile_s": 8.0, "execute_s": 1.0, "transfer_mb": 5.0,
                "compile": {"cold_compile_s": 8.0,
                            "warm_execute_s": 1.0}}
        for i in range(1, 4):
            with open(tmp_path / f"BENCH_r{i:02d}.json", "w") as f:
                json.dump({"n": i, "parsed": dict(base)}, f)
        # round 4 regresses: cold_s triples, driven by compile_s
        bad = dict(base, cold_s=45.0, compile_s=40.0,
                   compile={"cold_compile_s": 40.0,
                            "warm_execute_s": 1.0})
        with open(tmp_path / "BENCH_r04.json", "w") as f:
            json.dump({"n": 4, "parsed": bad}, f)
        doc = bench_gate.gate(str(tmp_path))
        assert doc["ok"] is False
        att = doc["attribution"]
        assert att, "regression carries no attribution"
        assert att[0]["axis"] in ("compile_s", "compile.cold_compile_s")
        assert att[0]["ratio"] == pytest.approx(5.0)
        execs = [a for a in att if a["axis"] == "execute_s"]
        assert execs and execs[0]["ratio"] == pytest.approx(1.0)
        # a clean trajectory carries none
        with open(tmp_path / "BENCH_r04.json", "w") as f:
            json.dump({"n": 4, "parsed": dict(base)}, f)
        doc = bench_gate.gate(str(tmp_path))
        assert doc["ok"] is True and "attribution" not in doc
