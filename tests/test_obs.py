"""Observability subsystem (jepsen_tpu.obs): span tracer semantics,
metrics registry math, export formats, run artifacts, the JTPU_TRACE
kill switch, the /metrics + /live endpoints, and the search
observatory (live progress, device memory accounting, shard balance).
Tier-1 under the ``obs`` marker (doc/observability.md is
the operator view)."""

import json
import os
import threading
import urllib.request

import pytest

from jepsen_tpu import obs
from jepsen_tpu.obs import devices as obs_devices
from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.obs import observatory as obs_observatory
from jepsen_tpu.obs import trace as obs_trace

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


class TestSpans:
    def test_nesting_parents_and_order(self):
        tr = obs_trace.Tracer()
        with tr.span("outer", layer="core"):
            with tr.span("inner"):
                pass
            with tr.span("inner2"):
                pass
        recs = tr.spans()
        assert [r["name"] for r in recs] == ["inner", "inner2", "outer"]
        outer = recs[2]
        assert "pid" not in outer and outer["layer"] == "core"
        assert recs[0]["pid"] == outer["sid"]
        assert recs[1]["pid"] == outer["sid"]
        assert all(r["dur"] >= 0 and r["ts"] >= 0 for r in recs)

    def test_name_attr_does_not_collide(self):
        # span("x", name=...) must record name as an attribute, not
        # clobber the span's own name (positional-only parameter)
        tr = obs_trace.Tracer()
        with tr.span("core.run", name="etcd-cas"):
            pass
        (r,) = tr.spans()
        assert r["name"] == "core.run"

    def test_exception_recorded_and_propagated(self):
        tr = obs_trace.Tracer()
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("kaput")
        (r,) = tr.spans()
        assert r["error"] == "ValueError: kaput"

    def test_threads_do_not_cross_parent(self):
        tr = obs_trace.Tracer()
        done = threading.Event()

        def child():
            with tr.span("child-span"):
                pass
            done.set()

        with tr.span("parent-span"):
            t = threading.Thread(target=child)
            t.start()
            t.join()
        assert done.is_set()
        by_name = {r["name"]: r for r in tr.spans()}
        # the other thread's span is a root: no cross-thread parent
        assert "pid" not in by_name["child-span"]
        assert by_name["child-span"]["tid"] != \
            by_name["parent-span"]["tid"]

    def test_ring_is_bounded(self):
        tr = obs_trace.Tracer(ring=16)
        for i in range(100):
            with tr.span(f"s{i}"):
                pass
        recs = tr.spans()
        assert len(recs) == 16
        assert recs[-1]["name"] == "s99"
        assert tr.recorded == 100

    def test_event_is_instant(self):
        tr = obs_trace.Tracer()
        tr.event("search.oom", outcome="pool-halved")
        (r,) = tr.spans()
        assert r["dur"] == 0 and r["outcome"] == "pool-halved"

    def test_kill_switch(self, monkeypatch):
        monkeypatch.setenv("JTPU_TRACE", "0")
        before = obs.tracer().recorded
        sp = obs.span("nope", x=1)
        assert sp is obs_trace.NOOP_SPAN
        with sp:
            sp.set(y=2)
        obs.event("nope-either")
        assert obs.tracer().recorded == before
        monkeypatch.setenv("JTPU_TRACE", "1")
        with obs.span("yes"):
            pass
        assert obs.tracer().recorded == before + 1


class TestTraceArtifact:
    def test_sink_and_tail_tolerant_read(self, tmp_path):
        p = str(tmp_path / "trace.jsonl")
        tr = obs_trace.Tracer(path=p)
        with tr.span("a"):
            with tr.span("b"):
                pass
        tr.detach()
        recs, stats = obs_trace.read_trace(p)
        assert stats == {"spans": 2, "torn": 0, "corrupt": 0,
                         "traces": 0}
        assert [r["name"] for r in recs] == ["b", "a"]
        # a SIGKILL mid-write leaves a torn, unterminated tail: dropped
        # silently, earlier records intact
        with open(p, "ab") as f:
            f.write(b'{"name": "torn", "ts": 12')
        recs, stats = obs_trace.read_trace(p)
        assert stats == {"spans": 2, "torn": 1, "corrupt": 0,
                         "traces": 0}
        # a corrupt MIDDLE line (terminated) counts as corruption
        with open(p, "ab") as f:
            f.write(b'3, "dur": 0}garbage\n')
        with tr.span("c"):
            pass  # ring only; sink detached
        recs, stats = obs_trace.read_trace(p)
        assert stats["corrupt"] == 1 and stats["spans"] == 2

    def test_chrome_export_matches_golden(self):
        records = [
            {"name": "core.run", "ts": 1000, "dur": 9000, "tid": 7,
             "sid": 1, "name_attr": "demo"},
            {"name": "checker.segment", "ts": 2000, "dur": 3000,
             "tid": 7, "sid": 2, "pid": 1, "phase": "compile",
             "level": 0},
            {"name": "search.oom", "ts": 6000, "dur": 0, "tid": 7,
             "sid": 3, "pid": 1, "outcome": "pool-halved-to-64"},
        ]
        golden_path = os.path.join(REPO, "tests", "fixtures", "obs",
                                   "chrome_golden.json")
        with open(golden_path) as f:
            golden = json.load(f)
        assert obs_trace.to_chrome(records,
                                   process_name="golden") == golden
        # structural invariants Perfetto relies on: complete events
        # carry dur, instants carry a scope, ts is microseconds
        evs = golden["traceEvents"]
        assert evs[1]["ph"] == "X" and evs[1]["ts"] == 1.0
        assert evs[3]["ph"] == "i" and evs[3]["s"] == "t"

    def test_summarize(self):
        recs = [{"name": "a", "ts": 0, "dur": 5},
                {"name": "a", "ts": 1, "dur": 7},
                {"name": "b", "ts": 2, "dur": 1}]
        s = obs_trace.summarize(recs)
        assert s["a"] == {"count": 2, "total-ns": 12, "max-ns": 7}
        assert list(s) == ["a", "b"]


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


class TestMetrics:
    def _registry(self):
        return obs_metrics.Registry()

    def test_counter_labels(self):
        reg = self._registry()
        c = reg.counter("jtpu_x_total", "things")
        c.inc()
        c.inc(2, f="read")
        c.inc(3, f="read")
        assert c.value() == 1
        assert c.value(f="read") == 5

    def test_gauge_set_max(self):
        reg = self._registry()
        g = reg.gauge("jtpu_hwm")
        g.set_max(4)
        g.set_max(2)
        assert g.value() == 4
        g.set(1)
        assert g.value() == 1

    def test_histogram_bucket_math(self):
        reg = self._registry()
        h = reg.histogram("jtpu_lat_seconds", "l",
                          buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.05, 0.5, 50.0):
            h.observe(v)
        s = h.series()
        # non-cumulative internal tallies: <=0.01, <=0.1, <=1.0, +Inf
        assert s["buckets"] == [1, 2, 1, 1]
        assert s["count"] == 5
        assert abs(s["sum"] - 50.605) < 1e-9
        # exposition is cumulative
        text = "\n".join(h.expose())
        assert 'le="0.01"} 1' in text
        assert 'le="0.1"} 3' in text
        assert 'le="1"} 4' in text
        assert 'le="+Inf"} 5' in text
        assert "jtpu_lat_seconds_count 5" in text

    def test_prometheus_exposition_format(self):
        reg = self._registry()
        reg.counter("jtpu_a_total", "a help").inc(2, f='with"quote',
                                                  g="line\nbreak")
        reg.gauge("jtpu_b", "b help").set(1.5)
        text = reg.to_prometheus()
        assert text.endswith("\n")
        assert "# HELP jtpu_a_total a help" in text
        assert "# TYPE jtpu_a_total counter" in text
        assert "# TYPE jtpu_b gauge" in text
        # label escaping per the exposition spec
        assert 'f="with\\"quote"' in text
        assert 'g="line\\nbreak"' in text
        assert "jtpu_b 1.5" in text

    def test_type_conflict_raises(self):
        reg = self._registry()
        reg.counter("jtpu_dup")
        with pytest.raises(TypeError):
            reg.gauge("jtpu_dup")

    def test_snapshot_roundtrips_as_json(self, tmp_path):
        reg = self._registry()
        reg.counter("jtpu_c_total").inc(4)
        reg.histogram("jtpu_h_seconds", buckets=(1.0,)).observe(0.5)
        doc = json.loads(json.dumps(reg.snapshot()))
        assert doc["jtpu_c_total"]["series"][""] == 4
        assert doc["jtpu_h_seconds"]["series"][""]["count"] == 1


# ---------------------------------------------------------------------------
# Instrumented layers
# ---------------------------------------------------------------------------


class TestInstrumentation:
    def test_wal_fsync_histogram(self, tmp_path):
        from jepsen_tpu import journal
        from jepsen_tpu.history import Op
        h = obs_metrics.REGISTRY.histogram("jtpu_wal_fsync_seconds")
        before = (h.series(sync="op") or {"count": 0})["count"]
        j = journal.Journal(str(tmp_path / "history.wal"), sync="op")
        for i in range(3):
            j.append(Op(type="invoke", f="read", process=i))
        j.close()
        after = h.series(sync="op")["count"]
        assert after - before == 3
        b = obs_metrics.REGISTRY.histogram("jtpu_wal_batch_records")
        assert b.series() and b.series()["count"] > 0

    def test_supervised_search_surfaces_telemetry(self):
        from jepsen_tpu.models import CASRegister
        from jepsen_tpu.ops.encode import pack_with_init
        from jepsen_tpu.resilience import supervised_check_packed
        from jepsen_tpu.testing import simulate_register_history
        h = simulate_register_history(150, n_procs=5, n_vals=4, seed=3)
        p, kernel = pack_with_init(h, CASRegister())
        before = obs.tracer().recorded
        r = supervised_check_packed(p, kernel, capacity=64, expand=8,
                                    segment_iters=8)
        assert r["valid"] is True
        assert r["segments"] >= 1
        assert set(r["device-s"]) == {"compile", "execute"}
        assert len(r["segment-levels"]) == r["segments"]
        assert sum(r["segment-levels"]) == r["levels"]
        assert r["frontier-hwm"] >= 1
        assert r["transfer-bytes"] > 0
        seg_spans = [s for s in obs.tracer().spans()
                     if s["name"] == "checker.segment"]
        assert obs.tracer().recorded > before
        assert seg_spans and {s["phase"] for s in seg_spans} <= \
            {"compile", "execute"}
        assert seg_spans[-1]["level_end"] == r["levels"]

    def test_traced_and_untraced_verdicts_match(self, monkeypatch):
        # the kill-switch acceptance bar: JTPU_TRACE=0 changes nothing
        # about verdicts or level counts
        from jepsen_tpu.checker.tpu import check_history_tpu
        from jepsen_tpu.models import CASRegister
        from jepsen_tpu.testing import simulate_register_history
        h = simulate_register_history(400, n_procs=5, n_vals=8, seed=9,
                                      crash_p=0.01)
        monkeypatch.setenv("JTPU_TRACE", "1")
        r1 = check_history_tpu(h, CASRegister(), segment_iters=64)
        monkeypatch.setenv("JTPU_TRACE", "0")
        r0 = check_history_tpu(h, CASRegister(), segment_iters=64)
        assert r1["valid"] == r0["valid"]
        assert r1["levels"] == r0["levels"]
        assert r1["segment-levels"] == r0["segment-levels"]

    def test_run_artifacts_and_kill_switch(self, tmp_path, monkeypatch):
        from jepsen_tpu import core, generator as gen
        from jepsen_tpu.testing import atom_test

        from jepsen_tpu.checker import noop_checker

        def one_run(root):
            t = atom_test(**{"store-root": str(root),
                             "concurrency": 2, "nodes": ["a", "b"]})
            t["generator"] = gen.clients(gen.limit(
                10, lambda test, p: {"f": "read", "value": None}))
            t["checker"] = noop_checker()
            return core.run(t)

        monkeypatch.setenv("JTPU_TRACE", "1")
        t = one_run(tmp_path / "on")
        d = t["store-dir"]
        arts = sorted(os.listdir(d))
        assert "trace.jsonl" in arts and "metrics.json" in arts
        recs, stats = obs_trace.read_trace(
            os.path.join(d, "trace.jsonl"))
        names = {r["name"] for r in recs}
        assert {"core.run", "core.run_case", "client.invoke",
                "checker.check"} <= names
        snap = json.load(open(os.path.join(d, "metrics.json")))
        assert "jtpu_op_timeouts_total" in snap
        assert "jtpu_wal_fsync_seconds" in snap

        monkeypatch.setenv("JTPU_TRACE", "0")
        t = one_run(tmp_path / "off")
        arts = sorted(os.listdir(t["store-dir"]))
        assert "trace.jsonl" not in arts and "metrics.json" not in arts
        assert "progress.json" not in arts
        assert t["results"]["valid"] is True


# ---------------------------------------------------------------------------
# Device memory accounting (obs/devices.py)
# ---------------------------------------------------------------------------


class _FakeDev:
    def __init__(self, stats, platform="tpu", id=0):
        self._stats = stats
        self.platform = platform
        self.id = id

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


class TestDevices:
    def test_cpu_backend_is_a_silent_noop(self):
        # tier-1 runs JAX_PLATFORMS=cpu: memory_stats() is None there,
        # so the whole accounting stack must answer empty/None without
        # touching a gauge or raising
        rows = obs_devices.poll()
        assert rows == []
        assert obs_devices.headroom_ratio() is None

    def test_memory_stats_none_and_raising_tolerated(self):
        assert obs_devices.memory_stats(_FakeDev(None)) is None
        assert obs_devices.memory_stats(
            _FakeDev(RuntimeError("unsupported"))) is None
        assert obs_devices.memory_stats(_FakeDev({})) is None

    def test_poll_updates_gauges_and_headroom(self, monkeypatch):
        devs = [_FakeDev({"bytes_in_use": 600, "bytes_limit": 1000,
                          "peak_bytes_in_use": 800}, id=0),
                _FakeDev({"bytes_in_use": 100, "bytes_limit": 1000},
                         id=1)]
        monkeypatch.setattr(obs_devices, "_devices", lambda: devs)
        rows = obs_devices.poll()
        assert len(rows) == 2
        assert rows[0]["headroom"] == pytest.approx(0.4)
        assert obs_devices.headroom_ratio(rows) == pytest.approx(0.4)
        g = obs_metrics.REGISTRY.gauge("jtpu_device_bytes_in_use")
        assert g.value(device="tpu:0") == 600
        assert g.value(device="tpu:1") == 100
        assert obs_metrics.REGISTRY.gauge(
            "jtpu_device_peak_bytes_in_use").value(device="tpu:0") == 800

    def test_headroom_threshold_env(self, monkeypatch):
        monkeypatch.delenv("JTPU_HEADROOM_MIN", raising=False)
        assert obs_devices.headroom_threshold() == \
            obs_devices.DEFAULT_HEADROOM_MIN
        monkeypatch.setenv("JTPU_HEADROOM_MIN", "0.2")
        assert obs_devices.headroom_threshold() == 0.2
        monkeypatch.setenv("JTPU_HEADROOM_MIN", "junk")
        assert obs_devices.headroom_threshold() == \
            obs_devices.DEFAULT_HEADROOM_MIN

    def test_low_headroom_preemptively_halves_the_pool(self,
                                                       monkeypatch):
        # a fake backend reporting 1% headroom: the supervised search
        # halves its pool BEFORE any OOM, exactly once per rung
        from jepsen_tpu.models import CASRegister
        from jepsen_tpu.ops.encode import pack_with_init
        from jepsen_tpu.resilience import supervised_check_packed
        from jepsen_tpu.testing import simulate_register_history
        devs = [_FakeDev({"bytes_in_use": 990, "bytes_limit": 1000})]
        monkeypatch.setattr(obs_devices, "_devices", lambda: devs)
        monkeypatch.setenv("JTPU_HEADROOM_MIN", "0.05")
        # this test targets the REACTIVE halving path; the ahead-of-time
        # plan gate (doc/plan.md) would reject this synthetic 1 kB
        # device before the reactive machinery could ever be exercised
        monkeypatch.setenv("JTPU_PLAN_GATE", "0")
        h = simulate_register_history(150, n_procs=5, n_vals=4, seed=3)
        p, kernel = pack_with_init(h, CASRegister())
        r = supervised_check_packed(p, kernel, capacity=64, expand=8,
                                    segment_iters=8)
        assert r["valid"] is True
        pre = [a for a in r["attempts"]
               if str(a.get("outcome", "")).startswith(
                   "preemptive-halve")]
        assert len(pre) == 1
        assert pre[0]["headroom"] == pytest.approx(0.01)
        assert r["rung"][0] == 32


# ---------------------------------------------------------------------------
# The search observatory (obs/observatory.py) + watch surfaces
# ---------------------------------------------------------------------------


class TestObservatory:
    def test_publish_ewma_eta_and_format(self):
        ob = obs_observatory.Observatory()
        assert ob.snapshot() is None
        ob.begin(level_budget=1000, rung=(64, 32, 8), segment_iters=100)
        ob.publish(level=100, frontier=40, segments=1, seg_seconds=0.1,
                   levels_delta=100, expansions=800)
        ob.publish(level=200, frontier=30, segments=2, seg_seconds=0.1,
                   levels_delta=100, expansions=800)
        p = ob.snapshot()
        assert p["state"] == "searching"
        assert p["level"] == 200 and p["frontier-rows"] == 30
        assert p["segments"] == 2 and p["segments-est"] == 10
        assert p["levels-per-s"] == pytest.approx(1000, rel=0.01)
        assert p["eta-s"] == pytest.approx(0.8, rel=0.01)
        line = obs_observatory.format_status(p)
        assert "level 200/1000" in line and "frontier 30 rows" in line
        ob.finish(valid=True, levels=250)
        p = ob.snapshot()
        assert p["state"] == "done" and p["valid"] is True
        assert p["level"] == 250
        # finishing again (early-out paths) must not clobber anything
        ob.finish(valid=False)
        assert ob.snapshot()["valid"] is True

    def test_progress_file_and_kill_switch(self, tmp_path,
                                           monkeypatch):
        monkeypatch.setenv("JTPU_TRACE", "1")
        ob = obs_observatory.Observatory()
        ob.attach(str(tmp_path))
        ob.begin(level_budget=10, rung=(8, 32, 2), segment_iters=4)
        ob.publish(level=4, frontier=2, segments=1, seg_seconds=0.01,
                   levels_delta=4, expansions=8)
        ob.finish(valid=True)
        doc = obs_observatory.read_progress(str(tmp_path))
        assert doc and doc["state"] == "done" and doc["level"] == 4
        # kill switch: attach refuses the sink, nothing is written
        monkeypatch.setenv("JTPU_TRACE", "0")
        off_dir = tmp_path / "off"
        off_dir.mkdir()
        ob2 = obs_observatory.Observatory()
        ob2.attach(str(off_dir))
        ob2.begin(level_budget=10, rung=(8, 32, 2), segment_iters=4)
        ob2.publish(level=4, frontier=2, segments=1, seg_seconds=0.01,
                    levels_delta=4, expansions=8)
        ob2.finish(valid=True)
        assert not os.path.exists(
            str(off_dir / obs_observatory.PROGRESS_NAME))
        # ...but the in-memory snapshot still works (run --watch path)
        assert ob2.snapshot()["state"] == "done"

    def test_read_progress_tolerates_garbage(self, tmp_path):
        assert obs_observatory.read_progress(str(tmp_path)) is None
        (tmp_path / obs_observatory.PROGRESS_NAME).write_text("{nope")
        assert obs_observatory.read_progress(str(tmp_path)) is None

    def test_supervised_search_publishes_live_progress(self, tmp_path):
        from jepsen_tpu.models import CASRegister
        from jepsen_tpu.ops.encode import pack_with_init
        from jepsen_tpu.resilience import supervised_check_packed
        from jepsen_tpu.testing import simulate_register_history
        h = simulate_register_history(150, n_procs=5, n_vals=4, seed=3)
        p, kernel = pack_with_init(h, CASRegister())
        obs_observatory.attach(str(tmp_path))
        try:
            r = supervised_check_packed(p, kernel, capacity=64,
                                        expand=8, segment_iters=8)
        finally:
            obs_observatory.detach()
        snap = obs_observatory.snapshot()
        assert snap["state"] == "done" and snap["valid"] is True
        assert snap["level"] == r["levels"]
        assert snap["segments"] == r["segments"]
        doc = obs_observatory.read_progress(str(tmp_path))
        assert doc and doc["state"] == "done"
        assert obs_metrics.REGISTRY.gauge(
            "jtpu_search_level").value() == r["levels"]
        assert obs_metrics.REGISTRY.gauge(
            "jtpu_search_inflight").value() == 0

    def test_live_status_printer(self):
        import io
        out = io.StringIO()
        obs_observatory.OBSERVATORY.begin(
            level_budget=100, rung=(8, 32, 2), segment_iters=10)
        obs_observatory.OBSERVATORY.publish(
            level=10, frontier=4, segments=1, seg_seconds=0.01,
            levels_delta=10, expansions=20)
        stop = obs_observatory.live_status_printer(interval=0.01,
                                                   out=out)
        import time as _t
        _t.sleep(0.1)
        stop()
        assert "# watch: level" in out.getvalue()


class TestWatchCLI:
    def test_watch_once_and_degradation(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d = tmp_path / "run"
        d.mkdir()
        # no progress.json at all: a graceful line, exit 0 (the watch
        # path must be a silent no-op for pre-observatory runs)
        rc = cli.run(cli.default_commands(),
                     ["watch", "--store", str(d), "--once"])
        assert rc == cli.OK
        out = capsys.readouterr().out
        assert "no search progress" in out
        (d / obs_observatory.PROGRESS_NAME).write_text(json.dumps(
            {"state": "searching", "ts": 1.0, "level": 50,
             "level-budget": 200, "frontier-rows": 8, "segments": 2,
             "segments-est": 20, "levels-per-s": 500.0,
             "configs-per-s": 4000.0, "eta-s": 0.3}))
        rc = cli.run(cli.default_commands(),
                     ["watch", "--store", str(d), "--once"])
        assert rc == cli.OK
        out = capsys.readouterr().out
        assert "level 50/200" in out and "eta 0.3s" in out
        rc = cli.run(cli.default_commands(),
                     ["watch", "--store", str(tmp_path / "nope"),
                      "--once"])
        assert rc == cli.INVALID_ARGS


# ---------------------------------------------------------------------------
# Shard balance accounting
# ---------------------------------------------------------------------------


class TestCostAccounting:
    def test_shard_balance_accounting(self):
        import numpy as np
        from jepsen_tpu.checker.tpu import _shard_balance
        pk = np.array([5, 4, 3, 2, 9, 0, 0, 0], np.int32)
        pa = np.array([1, 1, 1, 1, 1, 0, 0, 0], bool)
        bal = _shard_balance((pk, pk, pa), 2)
        assert bal["devices"] == 2
        assert bal["live-rows"] == [4, 1]
        assert bal["deepest-k"] == [5, 9]
        assert bal["imbalance-ratio"] == pytest.approx(1.6)
        assert obs_metrics.REGISTRY.gauge(
            "jtpu_shard_imbalance_ratio").value() == pytest.approx(1.6)
        # odd split: refuses rather than mis-attributing rows
        assert _shard_balance((pk, pk, pa), 3) is None


# ---------------------------------------------------------------------------
# Surfaces: web + CLI
# ---------------------------------------------------------------------------


class TestWebMetrics:
    def test_metrics_roundtrip_and_waterfall(self, tmp_path):
        import urllib.error
        from jepsen_tpu import web
        run = tmp_path / "t" / "20260804T000000.000"
        run.mkdir(parents=True)
        (run / "results.json").write_text('{"valid": true}')
        tr = obs_trace.Tracer(path=str(run / "trace.jsonl"))
        with tr.span("core.run"):
            with tr.span("checker.check"):
                pass
        tr.detach()
        obs_metrics.counter("jtpu_web_roundtrip_total",
                            "test series").inc(7, who="roundtrip")
        server = web.serve_background(root=str(tmp_path))
        base = f"http://127.0.0.1:{server.server_port}"
        try:
            with urllib.request.urlopen(base + "/metrics") as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith(
                    "text/plain; version=0.0.4")
                body = r.read().decode()
            assert "# TYPE jtpu_web_roundtrip_total counter" in body
            assert 'jtpu_web_roundtrip_total{who="roundtrip"} 7' in body
            home = urllib.request.urlopen(base + "/").read().decode()
            assert "/trace/t/20260804T000000.000" in home
            wf = urllib.request.urlopen(
                base + "/trace/t/20260804T000000.000").read().decode()
            assert "core.run" in wf and "span(s) over" in wf
            # a run without a trace 404s rather than erroring
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/trace/t/nope")
            assert ei.value.code == 404
        finally:
            server.shutdown()

    def test_live_endpoint(self, tmp_path):
        import urllib.error
        from jepsen_tpu import web
        run = tmp_path / "t" / "20260804T000001.000"
        run.mkdir(parents=True)
        server = web.serve_background(root=str(tmp_path))
        base = f"http://127.0.0.1:{server.server_port}"
        url = base + "/live/t/20260804T000001.000"
        try:
            # run exists but never published: progress null, not a 500
            with urllib.request.urlopen(url) as r:
                doc = json.load(r)
            assert r.status == 200 and doc["progress"] is None
            (run / obs_observatory.PROGRESS_NAME).write_text(
                json.dumps({"state": "searching", "ts": 7.5,
                            "level": 10, "level-budget": 100,
                            "frontier-rows": 4, "segments": 1}))
            with urllib.request.urlopen(url) as r:
                doc = json.load(r)
            assert doc["progress"]["level"] == 10
            # long-poll: already-seen ts blocks until the (capped)
            # wait elapses, fresh ts returns immediately
            import time as _t
            t0 = _t.monotonic()
            with urllib.request.urlopen(url + "?wait=1&since=7.5") as r:
                json.load(r)
            assert _t.monotonic() - t0 >= 0.9
            t0 = _t.monotonic()
            with urllib.request.urlopen(url + "?wait=5&since=7.0") as r:
                doc = json.load(r)
            assert _t.monotonic() - t0 < 2
            assert doc["progress"]["ts"] == 7.5
            # a missing run 404s with a JSON body
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/live/t/nope")
            assert ei.value.code == 404
            # the trace page of a progress-bearing run carries the strip
            (run / "trace.jsonl").write_text(
                '{"name": "core.run", "ts": 0, "dur": 5, "tid": 1, '
                '"sid": 1}\n')
            page = urllib.request.urlopen(
                base + "/trace/t/20260804T000001.000").read().decode()
            assert "liveBar" in page \
                and "/live/t/20260804T000001.000" in page
        finally:
            server.shutdown()


class TestTraceCLI:
    def _store_with_trace(self, tmp_path):
        d = tmp_path / "run"
        d.mkdir()
        tr = obs_trace.Tracer(path=str(d / "trace.jsonl"))
        with tr.span("core.run"):
            with tr.span("checker.segment", phase="execute"):
                pass
        tr.detach()
        return str(d)

    def test_export_chrome(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d = self._store_with_trace(tmp_path)
        out = str(tmp_path / "chrome.json")
        rc = cli.run(cli.default_commands(),
                     ["trace", "export", "--store", d, "-o", out])
        assert rc == cli.OK
        doc = json.load(open(out))
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"core.run", "checker.segment"} <= names

    def test_summary_and_missing_store(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d = self._store_with_trace(tmp_path)
        rc = cli.run(cli.default_commands(),
                     ["trace", "summary", "--store", d])
        assert rc == cli.OK
        out = capsys.readouterr().out
        assert "# trace:" in out and "checker.segment" in out
        rc = cli.run(cli.default_commands(),
                     ["trace", "summary", "--store",
                      str(tmp_path / "nope")])
        assert rc == cli.INVALID_ARGS

    def test_summary_top_self_time(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d = self._store_with_trace(tmp_path)
        rc = cli.run(cli.default_commands(),
                     ["trace", "summary", "--store", d, "--top", "5"])
        assert rc == cli.OK
        out = capsys.readouterr().out
        assert "top" in out and "self" in out

    def test_self_time_rollup_subtracts_children(self):
        recs = [
            {"name": "outer", "ts": 0, "dur": 100, "tid": 1, "sid": 1},
            {"name": "inner", "ts": 10, "dur": 80, "tid": 1, "sid": 2,
             "pid": 1},
            {"name": "inner", "ts": 95, "dur": 4, "tid": 1, "sid": 3,
             "pid": 1},
        ]
        top = obs_trace.self_time_rollup(recs)
        # outer's 100ns minus its children's 84ns = 16ns of self time
        assert top["outer"] == {"count": 1, "self-ns": 16,
                                "p95-ns": 16}
        assert top["inner"]["count"] == 2
        assert top["inner"]["self-ns"] == 84
        assert top["inner"]["p95-ns"] == 80

    def test_recover_emits_trace_summary(self, tmp_path, capsys):
        # a dead run with a WAL and a trace: recover prints the
        # `# trace:` span-count line next to `# recovery:`/`# lint:`
        from jepsen_tpu import cli, journal, store
        from jepsen_tpu.history import Op
        d = tmp_path / "kv" / "r1"
        d.mkdir(parents=True)
        j = journal.Journal(str(d / "history.wal"))
        j.append(Op(type="invoke", f="read", process=0, time=1))
        j.append(Op(type="ok", f="read", value=1, process=0, time=2))
        j.close()
        tr = obs_trace.Tracer(path=str(d / "trace.jsonl"))
        with tr.span("client.invoke", f="read"):
            pass
        tr.detach()
        store.write_state(str(d), "running")
        st = json.load(open(d / "run.state"))
        st["pid"] = 2 ** 22 + 1  # beyond pid_max: reads as dead
        (d / "run.state").write_text(json.dumps(st))
        assert store.run_status(str(d)) == "dead"
        rc = cli.run(cli.default_commands(),
                     ["recover", "--store", str(d), "--no-analyze"])
        assert rc == cli.OK
        out = capsys.readouterr().out
        assert "# recovery:" in out and "# lint:" in out
        assert "# trace: 1 span(s) recovered from trace.jsonl" in out


# ---------------------------------------------------------------------------
# The lint rule guarding the discipline
# ---------------------------------------------------------------------------


class TestTraceInJitLint:
    def _lint(self, tmp_path, body):
        from jepsen_tpu.analysis import jax_lint
        p = tmp_path / "mod.py"
        p.write_text(body)
        return jax_lint.lint_file(str(p), root=str(tmp_path))

    def test_flags_clock_and_span_in_traced_body(self, tmp_path):
        findings = self._lint(tmp_path, (
            "import time\n"
            "from jepsen_tpu import obs\n"
            "from jax import lax\n"
            "def search(x):\n"
            "    def body(c):\n"
            "        t0 = time.monotonic()\n"
            "        with obs.span('level'):\n"
            "            c = c + 1\n"
            "        return c\n"
            "    return lax.while_loop(lambda c: c < x, body, 0)\n"))
        rules = [f.rule for f in findings]
        assert rules.count("JAX-TRACE-IN-JIT") == 2
        assert all(f.severity == "error" for f in findings
                   if f.rule == "JAX-TRACE-IN-JIT")

    def test_host_side_timing_is_clean(self, tmp_path):
        # the sanctioned pattern: clock + span around the device call,
        # outside any traced body
        findings = self._lint(tmp_path, (
            "import time\n"
            "import jax\n"
            "from jepsen_tpu import obs\n"
            "def timed(fn, *args):\n"
            "    with obs.span('checker.device'):\n"
            "        t0 = time.perf_counter()\n"
            "        out = jax.block_until_ready(fn(*args))\n"
            "        dt = time.perf_counter() - t0\n"
            "    return out, dt\n"))
        assert not [f for f in findings
                    if f.rule == "JAX-TRACE-IN-JIT"]

    def test_flags_progress_publish_in_traced_body(self, tmp_path):
        findings = self._lint(tmp_path, (
            "from jepsen_tpu.obs import observatory\n"
            "from jax import lax\n"
            "def search(x):\n"
            "    def body(c):\n"
            "        observatory.publish(level=c)\n"
            "        return c + 1\n"
            "    return lax.while_loop(lambda c: c < x, body, 0)\n"))
        rules = [f.rule for f in findings]
        assert rules.count("JAX-TRACE-IN-JIT") == 1

    def test_allowlist_suppresses_sanctioned_site(self, tmp_path,
                                                  monkeypatch):
        from jepsen_tpu.analysis import jax_lint
        body = (
            "from jepsen_tpu.obs import observatory\n"
            "from jax import lax\n"
            "def supervise(x):\n"
            "    def body(c):\n"
            "        observatory.publish(level=c)\n"
            "        return c + 1\n"
            "    return lax.while_loop(lambda c: c < x, body, 0)\n")
        p = tmp_path / "mod.py"
        p.write_text(body)
        findings = jax_lint.lint_file(str(p), root=str(tmp_path))
        assert [f for f in findings if f.rule == "JAX-TRACE-IN-JIT"]
        monkeypatch.setattr(jax_lint, "TRACE_IN_JIT_ALLOWLIST",
                            (("mod.py", "supervise"),))
        findings = jax_lint.lint_file(str(p), root=str(tmp_path))
        assert not [f for f in findings
                    if f.rule == "JAX-TRACE-IN-JIT"]

    def test_repo_checker_stack_obeys_the_rule(self):
        # the instrumented production files themselves must be clean
        from jepsen_tpu.analysis import jax_lint
        for rel in ("jepsen_tpu/checker/tpu.py",
                    "jepsen_tpu/resilience.py",
                    "jepsen_tpu/obs/trace.py",
                    "jepsen_tpu/obs/observatory.py",
                    "jepsen_tpu/obs/devices.py"):
            findings = jax_lint.lint_file(os.path.join(REPO, rel),
                                          root=REPO)
            assert not [f for f in findings
                        if f.rule == "JAX-TRACE-IN-JIT"], rel


# ---------------------------------------------------------------------------
# Request-scoped distributed tracing (doc/observability.md, "Request
# tracing"): W3C traceparent plumbing, the thread-local trace-context
# slot, and the cross-process stitcher
# ---------------------------------------------------------------------------


class TestTraceContext:
    def test_traceparent_roundtrip(self):
        tid = obs_trace.new_trace_id()
        assert len(tid) == 32 and int(tid, 16) >= 0
        hdr = obs_trace.format_traceparent(tid, "00f067aa0ba902b7")
        assert hdr == f"00-{tid}-00f067aa0ba902b7-01"
        assert obs_trace.parse_traceparent(hdr) == \
            (tid, "00f067aa0ba902b7")

    def test_format_traceparent_renders_integer_sids(self):
        hdr = obs_trace.format_traceparent("ab" * 16, 7)
        assert hdr == f"00-{'ab' * 16}-{7:016x}-01"
        # with no span id yet (echoing at admission) a random non-zero
        # one is minted — the spec forbids all-zero span ids
        minted = obs_trace.format_traceparent("ab" * 16)
        _, sid = obs_trace.parse_traceparent(minted)
        assert int(sid, 16) != 0

    def test_parse_traceparent_rejects_malformed(self):
        bad = (None, 7, "", "garbage", "00-short-beef-01",
               "00-" + "g" * 32 + "-" + "1" * 16 + "-01",   # non-hex
               "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # zero tid
               "00-" + "1" * 32 + "-" + "0" * 16 + "-01")   # zero sid
        for hdr in bad:
            assert obs_trace.parse_traceparent(hdr) is None, hdr

    def test_context_stamps_records_and_guard_restores(self):
        tr = obs_trace.Tracer()
        tid = obs_trace.new_trace_id()
        with tr.span("untraced"):
            pass
        with tr.context(tid, "00f067aa0ba902b7"):
            assert tr.current_context() == (tid, "00f067aa0ba902b7")
            with tr.span("outer"):
                with tr.span("inner"):
                    pass
            # nested guard (a gang member re-run) restores the OUTER
            # request's id, not None
            other = obs_trace.new_trace_id()
            with tr.context(other):
                tr.event("rerun")
            assert tr.current_context()[0] == tid
        assert tr.current_context() == (None, None)
        recs = {r["name"]: r for r in tr.spans()}
        assert "trace" not in recs["untraced"]
        assert recs["outer"]["trace"] == tid
        assert recs["inner"]["trace"] == tid
        assert recs["rerun"]["trace"] == other
        # only the context ROOT carries the inbound parent span id
        assert recs["outer"]["parent"] == "00f067aa0ba902b7"
        assert "parent" not in recs["inner"]

    def test_context_is_thread_local(self):
        tr = obs_trace.Tracer()
        tid = obs_trace.new_trace_id()
        seen = {}

        def worker():
            seen["ctx"] = tr.current_context()
            with tr.span("other-thread"):
                pass

        with tr.context(tid):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen["ctx"] == (None, None)
        recs = {r["name"]: r for r in tr.spans()}
        assert "trace" not in recs["other-thread"]

    def test_by_trace_groups_and_read_trace_counts(self, tmp_path):
        p = str(tmp_path / "trace.jsonl")
        tr = obs_trace.Tracer(path=p)
        t1, t2 = obs_trace.new_trace_id(), obs_trace.new_trace_id()
        with tr.context(t1):
            with tr.span("a"):
                pass
        with tr.context(t2):
            with tr.span("b"):
                pass
        with tr.span("background"):
            pass
        tr.detach()
        recs, stats = obs_trace.read_trace(p)
        assert stats["traces"] == 2
        groups = obs_trace.by_trace(recs)
        assert set(groups) == {t1, t2}
        assert [r["name"] for r in groups[t1]] == ["a"]

    def test_sync_event_carries_wall_anchor(self, tmp_path, monkeypatch):
        monkeypatch.delenv("JTPU_TRACE", raising=False)
        p = str(tmp_path / "trace.jsonl")
        obs_trace.tracer().attach(p)
        try:
            obs_trace.sync_event()
        finally:
            obs_trace.tracer().detach()
        recs, _ = obs_trace.read_trace(p)
        sync = [r for r in recs if r["name"] == "trace.sync"]
        assert sync and isinstance(sync[0]["wall_ns"], int)
        assert sync[0]["wall_ns"] > 10 ** 18   # nanoseconds since 1970


class TestStitchRequest:
    def _host(self, d, tid, names, epoch_wall, ts0=1000, step=500):
        """Write one fake host dir: a trace.sync anchor claiming this
        tracer's monotonic epoch began at ``epoch_wall`` ns wall time,
        then spans under ``tid``."""
        os.makedirs(d, exist_ok=True)
        recs = [{"name": "trace.sync", "ts": 0, "dur": 0, "tid": 1,
                 "sid": 1, "wall_ns": epoch_wall}]
        ts = ts0
        for i, name in enumerate(names):
            recs.append({"name": name, "ts": ts, "dur": 100, "tid": 1,
                         "sid": i + 2, "trace": tid})
            ts += step
        with open(os.path.join(d, "trace.jsonl"), "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")

    def test_two_processes_align_on_wall_clock(self, tmp_path):
        from jepsen_tpu.obs import fleet as obs_fleet
        tid = obs_trace.new_trace_id()
        base = 1_700_000_000_000_000_000
        self._host(str(tmp_path), tid, ["serve.request"], base)
        # the worker booted 5000ns later: identical raw ts values must
        # land AFTER the daemon's on the aligned timeline
        self._host(str(tmp_path / "w0"), tid, ["checker.segment"],
                   base + 5000)
        out = obs_fleet.stitch_request(str(tmp_path), tid)
        assert out["trace-id"] == tid and out["method"] == "wall-clock"
        assert len(out["hosts"]) == 2
        names = [r["name"] for r in out["records"]]
        assert names == ["serve.request", "checker.segment"]
        seg = out["records"][1]
        assert seg["ts"] == 1000 + 5000 and seg["host"] == "w0"

    def test_filters_to_one_trace_and_tolerates_extra_dirs(
            self, tmp_path):
        from jepsen_tpu.obs import fleet as obs_fleet
        tid, noise = obs_trace.new_trace_id(), obs_trace.new_trace_id()
        base = 1_700_000_000_000_000_000
        self._host(str(tmp_path / "main"), tid,
                   ["serve.request"], base)
        self._host(str(tmp_path / "elsewhere"), noise,
                   ["other.request"], base)
        out = obs_fleet.stitch_request(
            str(tmp_path / "main"), tid,
            extra_dirs=[str(tmp_path / "elsewhere"),
                        str(tmp_path / "vanished")])
        assert [r["name"] for r in out["records"]] == ["serve.request"]

    def test_single_process_needs_no_alignment(self, tmp_path):
        from jepsen_tpu.obs import fleet as obs_fleet
        tid = obs_trace.new_trace_id()
        self._host(str(tmp_path), tid, ["a", "b"],
                   1_700_000_000_000_000_000)
        out = obs_fleet.stitch_request(str(tmp_path), tid)
        assert out["method"] is None
        assert [r["name"] for r in out["records"]] == ["a", "b"]

    def test_to_chrome_renders_one_process_per_host(self, tmp_path):
        from jepsen_tpu.obs import fleet as obs_fleet
        tid = obs_trace.new_trace_id()
        base = 1_700_000_000_000_000_000
        self._host(str(tmp_path), tid, ["serve.request"], base)
        self._host(str(tmp_path / "w0"), tid, ["checker.segment"],
                   base)
        out = obs_fleet.stitch_request(str(tmp_path), tid)
        doc = obs_fleet.to_chrome({"hosts": out["hosts"],
                                   "trace": out["records"]})
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert len(metas) == 2
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in spans} == {1, 2}

    def test_request_trace_html_renders_waterfall(self, tmp_path):
        from jepsen_tpu import web
        from jepsen_tpu.obs import fleet as obs_fleet
        tid = obs_trace.new_trace_id()
        base = 1_700_000_000_000_000_000
        self._host(str(tmp_path), tid, ["serve.request"], base)
        self._host(str(tmp_path / "w0"), tid, ["checker.segment"],
                   base + 1000)
        out = obs_fleet.stitch_request(str(tmp_path), tid)
        html_text = web.request_trace_html(out)
        assert tid in html_text
        assert "serve.request" in html_text
        assert "checker.segment" in html_text
        assert "w0" in html_text


class TestTraceSummaryIntegrity:
    def test_summary_surfaces_torn_corrupt_and_json(self, tmp_path,
                                                    capsys):
        from jepsen_tpu import cli
        d = tmp_path / "run"
        d.mkdir()
        tr = obs_trace.Tracer(path=str(d / "trace.jsonl"))
        with tr.context(obs_trace.new_trace_id()):
            with tr.span("checker.segment", phase="execute"):
                pass
        tr.detach()
        with open(d / "trace.jsonl", "ab") as f:
            f.write(b'{"name": "mid", "ts": 1}garbage\n')  # corrupt
            f.write(b'{"name": "torn", "ts": 12')          # torn tail
        rc = cli.run(cli.default_commands(),
                     ["trace", "summary", "--store", str(d)])
        assert rc == cli.OK
        out = capsys.readouterr().out
        assert "# trace: integrity: 1 torn, 1 corrupt line(s); " \
               "1 request trace id(s)" in out
        rc = cli.run(cli.default_commands(),
                     ["trace", "summary", "--store", str(d),
                      "--format", "json"])
        assert rc == cli.OK
        doc = json.loads(capsys.readouterr().out)
        assert doc["stats"]["torn"] == 1
        assert doc["stats"]["corrupt"] == 1
        assert doc["stats"]["traces"] == 1


class TestTraceRequestCLI:
    def _serve_store(self, tmp_path):
        """A dead serve store: serve.wal maps a request id to its
        trace id, trace.jsonl holds the spans."""
        from jepsen_tpu import serve as serve_ns
        d = tmp_path / "serve"
        d.mkdir()
        tid = obs_trace.new_trace_id()
        j = serve_ns.RequestJournal(str(d / "serve.wal"))
        j.append({"event": "accepted", "id": "r-000001", "trace": tid})
        j.append({"event": "done", "id": "r-000001"})
        j.close()
        tr = obs_trace.Tracer(path=str(d / "trace.jsonl"))
        with tr.context(tid):
            with tr.span("serve.request", id="r-000001"):
                with tr.span("checker.segment", phase="execute"):
                    pass
            tr.event("serve.verdict", id="r-000001")
        tr.detach()
        return str(d), tid

    def test_request_id_resolves_through_wal(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d, tid = self._serve_store(tmp_path)
        rc = cli.run(cli.default_commands(),
                     ["trace", "request", "r-000001", "--store", d])
        assert rc == cli.OK
        out = capsys.readouterr().out
        assert tid in out
        for name in ("serve.request", "checker.segment",
                     "serve.verdict"):
            assert name in out

    def test_literal_trace_id_and_json_format(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d, tid = self._serve_store(tmp_path)
        rc = cli.run(cli.default_commands(),
                     ["trace", "request", tid, "--store", d,
                      "--format", "json"])
        assert rc == cli.OK
        doc = json.loads(capsys.readouterr().out)
        assert doc["trace-id"] == tid
        assert [r["name"] for r in doc["records"]] == \
            ["serve.request", "checker.segment", "serve.verdict"]

    def test_unresolvable_id_fails_cleanly(self, tmp_path, capsys):
        from jepsen_tpu import cli
        d, _ = self._serve_store(tmp_path)
        rc = cli.run(cli.default_commands(),
                     ["trace", "request", "r-nope", "--store", d])
        assert rc == cli.INVALID_ARGS
        assert "couldn't resolve" in capsys.readouterr().err
