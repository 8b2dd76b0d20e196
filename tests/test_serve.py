"""Check-daemon + engine tests (the `serve` marker, doc/serve.md).

Covers the explicit executable Engine (warm-cache accounting: a second
check in the same shape bucket pays ZERO cold compiles), the CRC'd
request WAL and restart replay, admission control (bounded queue /
tenant quota / footprint budget → 429 + Retry-After), fair per-tenant
dequeue, the per-bucket circuit breaker (trip, half-open probe, close),
per-request deadlines (:info/timeout), graceful drain, the HTTP API
end-to-end, and the JTPU_SERVE kill-switch identity contract.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from jepsen_tpu import serve as serve_ns
from jepsen_tpu.checker import tpu as T
from jepsen_tpu.checker.engine import Engine, default_engine
from jepsen_tpu.history import History
from jepsen_tpu.models import CASRegister
from jepsen_tpu.ops.encode import pack_with_init

pytestmark = pytest.mark.serve


def _ops(n_pairs=2, value=1):
    """A small valid register history as raw op dicts (what tenants
    POST)."""
    rows = []
    t = 0
    for i in range(n_pairs):
        rows.append({"type": "invoke", "f": "write", "value": value + i,
                     "process": 0, "time": t})
        rows.append({"type": "ok", "f": "write", "value": value + i,
                     "process": 0, "time": t + 1})
        rows.append({"type": "invoke", "f": "read", "value": None,
                     "process": 1, "time": t + 2})
        rows.append({"type": "ok", "f": "read", "value": value + i,
                     "process": 1, "time": t + 3})
        t += 4
    return rows


def _packed(ops=None):
    return pack_with_init(History.of(ops or _ops()), CASRegister())


def _daemon(tmp_path, start=False, **cfg):
    cfg.setdefault("root", str(tmp_path / "serve"))
    cfg.setdefault("backend", "tpu")
    d = serve_ns.CheckDaemon(serve_ns.ServeConfig(**cfg))
    if start:
        d.start()
    return d


def _wait_done(daemon, rid, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        doc = daemon.status(rid)
        if doc and doc["state"] == "done":
            return doc
        time.sleep(0.02)
    raise AssertionError(f"request {rid} never finished: "
                         f"{daemon.status(rid)}")


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class TestEngine:
    def test_factories_route_through_engine_and_memoize(self):
        p, kernel = _packed()
        kid = T._kernel_key(kernel)
        f1 = T._jit_segment(kid, 32, 32, 4, 1)
        f2 = T._jit_segment(kid, 32, 32, 4, 1)
        assert f1 is f2  # the explicit table, same contract as lru_cache
        eng = default_engine()
        assert eng.builds >= 1 and eng.hits >= 1

    def test_lru_eviction_bounds_the_table(self):
        p, kernel = _packed()
        kid = T._kernel_key(kernel)
        eng = Engine("evict-test", max_entries=2)
        for cap in (8, 16, 32):
            eng.jit_segment(kid, cap, 32, 4, 1)
        assert len(eng._fns) == 2
        assert eng.builds == 3

    def test_bucket_key_groups_shapes(self):
        p1, kernel = _packed(_ops(2))
        p2, _ = _packed(_ops(2, value=7))     # same shape, other values
        p3, _ = _packed(_ops(40))             # bigger required bucket
        assert Engine.bucket_key(p1, kernel) == \
            Engine.bucket_key(p2, kernel)
        assert Engine.bucket_key(p1, kernel) != \
            Engine.bucket_key(p3, kernel)

    def test_warm_then_same_bucket_checks_pay_zero_cold(self):
        """The warm-path satellite + acceptance proof: after Engine.warm
        a check in the bucket performs no cold compile (cold counter
        delta 0) and accounts as cache hits; a SECOND history in the
        same bucket rides the same executables."""
        from jepsen_tpu.resilience import supervised_check_packed
        eng = default_engine()
        p1, kernel = _packed(_ops(3))
        p2, _ = _packed(_ops(3, value=5))
        assert eng.bucket_key(p1, kernel) == eng.bucket_key(p2, kernel)
        eng.warm(p1, kernel)
        before = T.compile_snapshot()
        r1 = supervised_check_packed(p1, kernel)
        d1 = T.compile_delta(before)
        assert r1["valid"] is True
        assert d1["cold"] == 0, f"warm bucket cold-compiled: {d1}"
        assert d1["cache-hits"] >= 1
        mid = T.compile_snapshot()
        r2 = supervised_check_packed(p2, kernel)
        d2 = T.compile_delta(mid)
        assert r2["valid"] is True
        assert d2["cold"] == 0, f"second same-bucket check went cold: {d2}"
        assert d2["cache-hits"] >= 1

    def test_warm_is_idempotent_per_bucket(self):
        eng = default_engine()
        p, kernel = _packed(_ops(3))
        first = eng.warm(p, kernel)
        again = eng.warm(p, kernel)
        assert again["already-warm"] is True
        assert eng.warm_info(eng.bucket_key(p, kernel)) is not None
        assert first["shapes"] >= 1 or first["already-warm"]

    def test_configure_compile_cache_honors_the_env(self, tmp_path,
                                                    monkeypatch):
        import jax
        from jepsen_tpu.checker import engine as engine_mod
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xc"))
        try:
            assert engine_mod.configure_compile_cache() == str(tmp_path / "xc")
            assert jax.config.jax_compilation_cache_dir == str(tmp_path / "xc")
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# Request journal
# ---------------------------------------------------------------------------


class TestRequestJournal:
    def test_replay_returns_only_unfinished(self, tmp_path):
        path = str(tmp_path / "serve.wal")
        j = serve_ns.RequestJournal(path)
        j.append({"event": "accepted", "id": "a", "history": _ops()})
        j.append({"event": "accepted", "id": "b", "history": _ops()})
        j.append({"event": "done", "id": "a", "valid": "True"})
        j.close()
        pending, stats = serve_ns.RequestJournal.replay(path)
        assert [r["id"] for r in pending] == ["b"]
        assert stats["records"] == 3 and stats["corrupt"] == 0

    def test_torn_tail_tolerated(self, tmp_path):
        path = str(tmp_path / "serve.wal")
        j = serve_ns.RequestJournal(path)
        j.append({"event": "accepted", "id": "a", "history": _ops()})
        j.close()
        with open(path, "ab") as f:
            f.write(b"deadbeef {torn-mid-wri")  # no newline: torn tail
        pending, stats = serve_ns.RequestJournal.replay(path)
        assert [r["id"] for r in pending] == ["a"]
        assert stats["torn"] == 1

    def test_dropped_records_are_terminal(self, tmp_path):
        path = str(tmp_path / "serve.wal")
        j = serve_ns.RequestJournal(path)
        j.append({"event": "accepted", "id": "a", "history": _ops()})
        j.append({"event": "dropped", "id": "a", "reason": "malformed"})
        j.close()
        pending, _ = serve_ns.RequestJournal.replay(path)
        assert pending == []


# ---------------------------------------------------------------------------
# Admission control + backpressure (no workers: requests stay queued)
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_bounded_queue_429_with_retry_after(self, tmp_path):
        d = _daemon(tmp_path, queue_max=2, tenant_max=10)
        for _ in range(2):
            code, _, _ = d.submit({"model": "cas-register",
                                   "history": _ops()})
            assert code == 202
        code, body, hdrs = d.submit({"model": "cas-register",
                                     "history": _ops()})
        assert code == 429
        assert body["error"] == "queue-full"
        assert int(hdrs["Retry-After"]) >= 1
        d.stop()

    def test_tenant_quota_protects_other_tenants(self, tmp_path):
        d = _daemon(tmp_path, queue_max=10, tenant_max=1)
        code, _, _ = d.submit({"tenant": "greedy",
                               "model": "cas-register",
                               "history": _ops()})
        assert code == 202
        code, body, hdrs = d.submit({"tenant": "greedy",
                                     "model": "cas-register",
                                     "history": _ops()})
        assert code == 429 and body["error"] == "tenant-quota"
        assert "Retry-After" in hdrs
        code, _, _ = d.submit({"tenant": "modest",
                               "model": "cas-register",
                               "history": _ops()})
        assert code == 202  # the quota is per tenant, not global
        d.stop()

    def test_footprint_budget_rejects_past_admission_bytes(self, tmp_path):
        d = _daemon(tmp_path, queue_max=10, bytes_budget=512)
        code, body, hdrs = d.submit({"model": "cas-register",
                                     "history": _ops()})
        assert code == 429 and body["error"] == "footprint"
        assert body["predicted-bytes"] > 512 == body["budget-bytes"]
        assert "Retry-After" in hdrs
        d.stop()

    def test_malformed_history_400_with_rule_ids(self, tmp_path):
        d = _daemon(tmp_path)
        bad = [{"type": "invoke", "f": "write", "value": 1,
                "process": 0, "time": 0},
               {"type": "invoke", "f": "write", "value": 2,
                "process": 0, "time": 1}]  # process reuse
        code, body, _ = d.submit({"model": "cas-register",
                                  "history": bad})
        assert code == 400 and body["error"] == "malformed"
        assert body.get("lint")
        d.stop()

    def test_unknown_model_and_empty_history_400(self, tmp_path):
        d = _daemon(tmp_path)
        assert d.submit({"model": "nope", "history": _ops()})[0] == 400
        assert d.submit({"model": "cas-register",
                         "history": []})[0] == 400
        d.stop()

    def test_draining_503(self, tmp_path):
        d = _daemon(tmp_path)
        d.draining = True
        code, body, hdrs = d.submit({"model": "cas-register",
                                     "history": _ops()})
        assert code == 503 and body["error"] == "draining"
        assert "Retry-After" in hdrs
        d.stop()


class TestFairDequeue:
    def test_round_robin_across_tenants(self, tmp_path):
        d = _daemon(tmp_path, queue_max=32, tenant_max=32)
        for i in range(3):
            d.submit({"tenant": "t1", "model": "cas-register",
                      "history": _ops(value=i + 1)})
        d.submit({"tenant": "t2", "model": "cas-register",
                  "history": _ops(value=9)})
        order = [d._dequeue().tenant for _ in range(4)]
        # one t2 request interleaves within the first two slots instead
        # of waiting behind all of t1's backlog
        assert "t2" in order[:2], order
        assert sorted(order) == ["t1", "t1", "t1", "t2"]
        d.stop()


# ---------------------------------------------------------------------------
# Circuit breaker
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    BUCKET = ("cas-register", 16, 0, 32)

    def test_trip_halfopen_probe_close(self):
        import random as _random
        from jepsen_tpu.resilience import OOM
        br = serve_ns.CircuitBreaker(2, 0.05, rng=_random.Random(3))
        ok, _, probe = br.allow(self.BUCKET)
        assert ok and not probe
        br.record(self.BUCKET, OOM, probe=False)
        br.record(self.BUCKET, OOM, probe=False)
        ok, retry, _ = br.allow(self.BUCKET)
        assert not ok and retry > 0
        time.sleep(0.08)
        ok, _, probe = br.allow(self.BUCKET)
        assert ok and probe            # half-open: exactly one probe
        ok2, _, _ = br.allow(self.BUCKET)
        assert not ok2                 # second concurrent probe refused
        br.record(self.BUCKET, None, probe=True)
        ok, _, probe = br.allow(self.BUCKET)
        assert ok and not probe        # closed again

    def test_probe_failure_doubles_cooldown(self):
        import random as _random
        from jepsen_tpu.resilience import WEDGE
        br = serve_ns.CircuitBreaker(1, 0.05, rng=_random.Random(5))
        br.record(self.BUCKET, WEDGE, probe=False)
        time.sleep(0.08)
        ok, _, probe = br.allow(self.BUCKET)
        assert ok and probe
        br.record(self.BUCKET, WEDGE, probe=True)
        snap = br.snapshot()
        rec = list(snap.values())[0]
        assert rec["state"] == "open"
        assert rec["cooldown-s"] == pytest.approx(0.1)

    def test_invalid_verdicts_do_not_trip(self):
        br = serve_ns.CircuitBreaker(1, 0.05)
        br.record(self.BUCKET, None, probe=False)   # clean check
        ok, _, _ = br.allow(self.BUCKET)
        assert ok

    def test_daemon_breaker_rejects_then_probes(self, tmp_path,
                                                monkeypatch):
        d = _daemon(tmp_path, start=True, queue_max=16,
                    breaker_fails=2, breaker_cooldown_s=0.1)
        monkeypatch.setattr(
            serve_ns.CheckDaemon, "_check",
            lambda self, req: {"valid": "unknown",
                               "error": "RESOURCE_EXHAUSTED (fake)",
                               "error-class": "oom"})
        for _ in range(2):
            code, body, _ = d.submit({"model": "cas-register",
                                      "history": _ops()})
            assert code == 202
            _wait_done(d, body["id"])
        code, body, hdrs = d.submit({"model": "cas-register",
                                     "history": _ops()})
        assert code == 503 and body["error"] == "breaker-open"
        assert "Retry-After" in hdrs
        time.sleep(0.2)                # past the jittered cooldown
        monkeypatch.setattr(serve_ns.CheckDaemon, "_check",
                            lambda self, req: {"valid": True})
        code, body, _ = d.submit({"model": "cas-register",
                                  "history": _ops()})
        assert code == 202             # the half-open probe
        _wait_done(d, body["id"])
        code, _, _ = d.submit({"model": "cas-register",
                               "history": _ops()})
        assert code == 202             # probe success closed the breaker
        d.stop()


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------


class TestDeadline:
    def test_overrun_returns_info_timeout(self, tmp_path, monkeypatch):
        d = _daemon(tmp_path, start=True, deadline_s=0.15)

        def slow(self, req):
            time.sleep(1.5)
            return {"valid": True}

        monkeypatch.setattr(serve_ns.CheckDaemon, "_check", slow)
        code, body, _ = d.submit({"model": "cas-register",
                                  "history": _ops()})
        assert code == 202
        doc = _wait_done(d, body["id"])
        assert doc["result"]["valid"] == "unknown"
        assert doc["result"]["error"] == ":info/timeout"
        assert doc["result"]["serve"]["timed-out"] is True
        assert d.stats["timeouts"] == 1
        d.stop()

    def test_per_request_deadline_overrides_default(self, tmp_path,
                                                    monkeypatch):
        d = _daemon(tmp_path, start=True, deadline_s=None)
        monkeypatch.setattr(
            serve_ns.CheckDaemon, "_check",
            lambda self, req: (time.sleep(0.5), {"valid": True})[1])
        code, body, _ = d.submit({"model": "cas-register",
                                  "history": _ops(), "deadline-s": 0.1})
        assert code == 202
        doc = _wait_done(d, body["id"])
        assert doc["result"]["error"] == ":info/timeout"
        d.stop()


# ---------------------------------------------------------------------------
# Crash safety: journal replay re-runs unfinished work, verdicts match
# the offline path
# ---------------------------------------------------------------------------


class TestCrashReplay:
    def test_killed_daemon_replays_and_matches_offline(self, tmp_path):
        # incarnation 1: accepts (journals) two requests but is "killed"
        # before any worker ran them
        d1 = _daemon(tmp_path, queue_max=8)
        for v in (1, 5):
            code, _, _ = d1.submit({"tenant": "t", "model":
                                    "cas-register",
                                    "history": _ops(value=v)})
            assert code == 202
        d1.journal.close()             # SIGKILL: nothing else persisted

        # incarnation 2 replays the WAL and finishes the work
        d2 = _daemon(tmp_path, start=True, queue_max=8)
        assert d2.replay_stats["requeued"] == 2
        assert d2.stats["replayed"] == 2
        with d2._lock:
            rids = list(d2._by_id)
        docs = [_wait_done(d2, rid) for rid in rids]
        d2.stop()

        # verdicts identical to the offline analyze path
        from jepsen_tpu.checker import check_safe
        from jepsen_tpu.checker.wgl import linearizable
        for doc, v in zip(sorted(docs, key=lambda x: x["id"]), (1, 5)):
            offline = check_safe(
                linearizable(CASRegister(), backend="tpu"),
                {"name": "offline"}, History.of(_ops(value=v)))
            assert doc["result"]["valid"] is offline["valid"] is True

    def test_drain_finishes_inflight_leaves_queued_journaled(
            self, tmp_path, monkeypatch):
        """The drain contract: in-flight work completes, queued work is
        NOT started — it stays journaled for the next incarnation."""
        d = _daemon(tmp_path, start=True)
        running = threading.Event()

        def slowish(self, req):
            running.set()
            time.sleep(0.4)
            return {"valid": True}

        monkeypatch.setattr(serve_ns.CheckDaemon, "_check", slowish)
        code, b1, _ = d.submit({"model": "cas-register",
                                "history": _ops()})
        assert code == 202
        assert running.wait(timeout=5)      # b1 is in flight
        code, b2, _ = d.submit({"model": "cas-register",
                                "history": _ops(value=7)})
        assert code == 202                  # b2 queued behind it
        out = d.drain(timeout_s=10)
        assert out["drained"] is True
        assert d.status(b1["id"])["state"] == "done"
        assert d.status(b2["id"])["state"] == "queued"
        d.stop()
        pending, _ = serve_ns.RequestJournal.replay(d.journal.path)
        assert [r["id"] for r in pending] == [b2["id"]]

    def test_finished_requests_are_not_replayed(self, tmp_path):
        d1 = _daemon(tmp_path, start=True)
        code, body, _ = d1.submit({"model": "cas-register",
                                   "history": _ops()})
        assert code == 202
        _wait_done(d1, body["id"])
        d1.stop()
        d2 = _daemon(tmp_path)
        pending, _ = serve_ns.RequestJournal.replay(d2.journal.path)
        assert pending == []
        d2.stop()


# ---------------------------------------------------------------------------
# HTTP API end-to-end
# ---------------------------------------------------------------------------


def _post(port, path, doc):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(doc).encode() if doc is not None else b"",
        method="POST")
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.load(r), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e), dict(e.headers)


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}") as r:
            return r.status, json.load(r)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


class TestHTTP:
    def test_check_poll_healthz_drain(self, tmp_path):
        cfg = serve_ns.ServeConfig(root=str(tmp_path / "serve"),
                                   backend="tpu")
        daemon, server = serve_ns.run_daemon(
            cfg, host="127.0.0.1", port=0,
            store_root=str(tmp_path / "store"))
        port = server.server_port
        try:
            code, body, _ = _post(port, "/check",
                                  {"tenant": "http", "model":
                                   "cas-register", "history": _ops()})
            assert code == 202 and body["state"] == "queued"
            rid = body["id"]
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                code, doc = _get(port, f"/check/{rid}")
                if doc["state"] == "done":
                    break
                time.sleep(0.05)
            assert doc["state"] == "done"
            assert doc["result"]["valid"] is True
            # the result also persisted as a file
            assert os.path.exists(os.path.join(cfg.root, f"{rid}.json"))

            code, health = _get(port, "/healthz")
            assert code == 200 and health["ok"] is True
            assert health["stats"]["completed"] >= 1
            assert health["engine"]["warm-buckets"]

            code, doc = _get(port, "/check/nope")
            assert code == 404

            code, drained, _ = _post(port, "/drain", None)
            assert code == 200 and drained["drained"] is True
            assert daemon.drained.wait(timeout=5)
        finally:
            server.shutdown()
            daemon.stop()

    def test_saturated_queue_http_429_retry_after(self, tmp_path):
        cfg = serve_ns.ServeConfig(root=str(tmp_path / "serve"),
                                   queue_max=0)
        daemon, server = serve_ns.run_daemon(
            cfg, host="127.0.0.1", port=0,
            store_root=str(tmp_path / "store"))
        try:
            code, body, hdrs = _post(
                server.server_port, "/check",
                {"model": "cas-register", "history": _ops()})
            assert code == 429 and body["error"] == "queue-full"
            assert int(hdrs["Retry-After"]) >= 1
        finally:
            server.shutdown()
            daemon.stop()

    def test_bad_json_400_and_results_browser_still_mounted(self,
                                                            tmp_path):
        cfg = serve_ns.ServeConfig(root=str(tmp_path / "serve"))
        daemon, server = serve_ns.run_daemon(
            cfg, host="127.0.0.1", port=0,
            store_root=str(tmp_path / "store"))
        port = server.server_port
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/check", data=b"{not json",
                method="POST")
            try:
                with urllib.request.urlopen(req) as r:
                    code = r.status
            except urllib.error.HTTPError as e:
                code = e.code
            assert code == 400
            # the grown handler still serves the results browser + the
            # Prometheus exposition (one port, one scrape target)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics") as r:
                text = r.read().decode()
            assert "jtpu_serve_queue_depth" in text
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/") as r:
                assert r.status == 200
        finally:
            server.shutdown()
            daemon.stop()


# ---------------------------------------------------------------------------
# Observability surfacing
# ---------------------------------------------------------------------------


class TestServeObservability:
    def test_heartbeat_feeds_watch_and_live(self, tmp_path):
        from jepsen_tpu.obs import observatory
        d = _daemon(tmp_path, start=True)
        code, body, _ = d.submit({"model": "cas-register",
                                  "history": _ops()})
        assert code == 202
        _wait_done(d, body["id"])
        d._publish(force=True)
        p = observatory.read_progress(d.config.root)
        assert p is not None and p["serve"]["completed"] >= 1
        line = observatory.format_status(p)
        assert line.startswith("# serve: ")
        assert "queue" in line and "done" in line
        d.stop()


# ---------------------------------------------------------------------------
# Kill switch: daemon unused == identical behavior
# ---------------------------------------------------------------------------


class TestKillSwitch:
    def test_env_alone_changes_no_verdicts(self, monkeypatch):
        p, kernel = _packed(_ops(3))
        monkeypatch.delenv("JTPU_SERVE", raising=False)
        r_off = T.check_packed_tpu(p, kernel)
        monkeypatch.setenv("JTPU_SERVE", "1")
        r_on = T.check_packed_tpu(p, kernel)
        for key in ("valid", "levels", "rung", "work", "crash-width"):
            assert r_off.get(key) == r_on.get(key)

    def test_plain_serve_handler_has_no_daemon_routes(self):
        from jepsen_tpu import web
        server = web.serve(host="127.0.0.1", port=0, root="store")
        try:
            handler = server.RequestHandlerClass
            assert not hasattr(handler, "daemon")
            assert "do_POST" not in dir(web.Handler) or \
                not hasattr(web.Handler, "do_POST")
        finally:
            server.server_close()

    def test_serve_cli_defaults_keep_daemon_off(self, monkeypatch):
        from jepsen_tpu import cli
        monkeypatch.delenv("JTPU_SERVE", raising=False)
        spec = cli.serve_cmd()["serve"]
        ns = spec["parser"]().parse_args([])
        assert ns.check_daemon is False
        assert serve_ns.serve_enabled() is False

    def test_importing_serve_leaves_checks_identical(self):
        import jepsen_tpu.serve  # noqa: F401 — the import IS the test
        p, kernel = _packed(_ops(3))
        r1 = T.check_packed_tpu(p, kernel)
        r2 = T.check_packed_tpu(p, kernel)
        for key in ("valid", "levels", "rung", "work"):
            assert r1.get(key) == r2.get(key)


# ---------------------------------------------------------------------------
# Gang-scheduled concurrent batching (doc/serve.md, "Concurrent
# batching"): coalescing, serial equivalence, poison bisection, the
# JTPU_SERVE_BATCH kill switch
# ---------------------------------------------------------------------------

#: keys on which a gang verdict must be indistinguishable from serial.
_VERDICT_KEYS = ("valid", "levels", "max-linearized-prefix",
                 "final-states", "frontier-op")


def _conc_ops(n, seed, value_base=0):
    """A CONCURRENT register history (4 procs, interleaved invokes) —
    deep enough that a segment_iters=1 gang needs several barriers,
    which the deadline-cancel test relies on."""
    import random as _random
    rng = _random.Random(seed)
    ops, t, pend, val = [], 0, {}, value_base
    for _ in range(n):
        p = rng.choice((0, 1, 2, 3))
        if p in pend:
            inv = pend.pop(p)
            ops.append({"process": p, "type": "ok", "f": inv["f"],
                        "value": inv["value"], "time": t})
        else:
            f = rng.choice(("write", "read"))
            v = val if f == "write" else None
            if f == "write":
                val += 1
            inv = {"process": p, "type": "invoke", "f": f, "value": v,
                   "time": t}
            ops.append(inv)
            pend[p] = inv
        t += 1
    for p, inv in pend.items():
        ops.append({"process": p, "type": "ok", "f": inv["f"],
                    "value": inv["value"], "time": t})
        t += 1
    return ops


def _offline(ops):
    from jepsen_tpu.checker import check_safe
    from jepsen_tpu.checker.wgl import linearizable
    return check_safe(linearizable(CASRegister(), backend="tpu"),
                      {"name": "gang-offline"}, History.of(ops))


@pytest.fixture
def gang_fault():
    """Install/clear the checker.tpu gang fault seam."""
    def install(fn):
        T._GANG_FAULT = fn
    yield install
    T._GANG_FAULT = None


class TestCheckPackedGang:
    def test_gang_verdicts_match_serial(self):
        """The tentpole equivalence leg: one vmapped gang call renders
        per-member verdicts identical to serial check_packed_tpu."""
        histories = [_ops(3), _ops(5, value=9), _conc_ops(24, 3),
                     _ops(6, value=40)]
        pks, kernel = [], None
        for ops in histories:
            p, kernel = _packed(ops)
            pks.append(p)
        gang = T.check_packed_gang(pks, kernel)
        assert len(gang) == len(pks)
        for g, p in zip(gang, pks):
            serial = T.check_packed_tpu(p, kernel)
            for key in _VERDICT_KEYS:
                assert g.get(key) == serial.get(key), (key, g, serial)
            assert g["gang-size"] == len(pks)

    def test_empty_and_trivial_members(self):
        p, kernel = _packed(_ops(3))
        assert T.check_packed_gang([], kernel) == []

    def test_deadline_cancels_lane_not_cohort(self):
        """A member whose deadline passes is cancelled at the next
        segment barrier (:info/timeout, gang-cancelled) while its
        cohort finishes with serial-identical verdicts."""
        victim = _conc_ops(24, 5)
        cohort = _conc_ops(24, 6, value_base=100)
        pks, kernel = [], None
        for ops in (victim, cohort):
            p, kernel = _packed(ops)
            pks.append(p)
        out = T.check_packed_gang(
            pks, kernel, deadlines=[time.monotonic() - 1.0, None],
            segment_iters=1)
        from jepsen_tpu.checker import UNKNOWN
        assert out[0]["valid"] is UNKNOWN
        assert out[0]["error"] == ":info/timeout"
        assert out[0]["gang-cancelled"] is True
        serial = T.check_packed_tpu(pks[1], kernel)
        for key in _VERDICT_KEYS:
            assert out[1].get(key) == serial.get(key)

    def test_gang_fault_seam_raises_through(self, gang_fault):
        p, kernel = _packed(_ops(3))

        def boom(pks):
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")

        gang_fault(boom)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            T.check_packed_gang([p], kernel)


class TestBisectPoison:
    def test_isolates_single_poison(self):
        from jepsen_tpu.resilience import bisect_poison
        calls = []

        def run_gang(span):
            calls.append(list(span))
            if 3 in span:
                raise RuntimeError("RESOURCE_EXHAUSTED: poison")
            return [{"valid": True, "member": m} for m in span]

        results, poison, bisections = bisect_poison(
            list(range(6)), run_gang)
        assert poison == [3]
        assert bisections >= 1
        assert results[3]["error-class"] == "oom"
        for i in (0, 1, 2, 4, 5):
            assert results[i] == {"valid": True, "member": i}
        # the poison was isolated by splitting, not by 6 serial runs
        assert calls[0] == [0, 1, 2, 3, 4, 5]

    def test_all_clean_no_bisection(self):
        from jepsen_tpu.resilience import bisect_poison
        results, poison, bisections = bisect_poison(
            [10, 11], lambda span: [{"valid": True}] * len(span))
        assert poison == [] and bisections == 0
        assert all(r == {"valid": True} for r in results)

    def test_result_failure_class_drives_split(self):
        """A run_gang returning a single failure DICT (not raising)
        bisects too — the resilience result taxonomy is the trigger."""
        from jepsen_tpu.resilience import bisect_poison

        def run_gang(span):
            if 1 in span:
                return {"valid": "unknown", "error": "wedged",
                        "error-class": "wedge"}
            return [{"valid": True}] * len(span)

        results, poison, _ = bisect_poison([0, 1], run_gang)
        assert poison == [1]
        assert results[0] == {"valid": True}
        assert results[1]["error-class"] == "wedge"


class TestGangServe:
    def test_burst_coalesces_and_matches_offline(self, tmp_path):
        """4 same-bucket requests journaled by a killed incarnation
        re-queue together on restart — the worker's first dequeue leads
        a deterministic gang of 4, and every verdict matches the
        offline analyze path."""
        histories = [_ops(3), _ops(4, value=9), _ops(5, value=20),
                     _conc_ops(24, 7)]
        d1 = _daemon(tmp_path)
        for i, ops in enumerate(histories):
            code, _, _ = d1.submit({"tenant": f"t{i % 2}",
                                    "model": "cas-register",
                                    "history": ops})
            assert code == 202
        d1.journal.close()          # SIGKILL before any worker ran

        d2 = _daemon(tmp_path, start=True, workers=1,
                     batch_wait_ms=200.0)
        assert d2.batcher is not None
        assert d2.replay_stats["requeued"] == 4
        with d2._lock:
            rids = list(d2._by_id)
        docs = {rid: _wait_done(d2, rid) for rid in rids}
        assert d2.stats["batches"] >= 1
        assert d2.stats["max-batch"] >= 2
        d2.stop()
        by_order = sorted(docs.values(), key=lambda x: x["id"])
        pending, _ = serve_ns.RequestJournal.replay(d2.journal.path)
        assert pending == []        # every gang member reached done
        for doc in by_order:
            gang = doc["result"]["serve"]["gang"]
            assert gang["size"] >= 2 and gang["poison"] is False
        # order-insensitive equality against offline (ids regenerate)
        served = sorted(repr(d["result"]["valid"]) for d in by_order)
        offline = sorted(repr(_offline(o)["valid"]) for o in histories)
        assert served == offline

    def test_gang_wal_records_membership(self, tmp_path):
        d1 = _daemon(tmp_path)
        for v in (1, 5):
            d1.submit({"model": "cas-register",
                       "history": _ops(3, value=v)})
        d1.journal.close()
        d2 = _daemon(tmp_path, start=True, workers=1,
                     batch_wait_ms=200.0)
        with d2._lock:
            rids = list(d2._by_id)
        for rid in rids:
            _wait_done(d2, rid)
        d2.stop()
        gang_events, done_gangs = [], []
        from jepsen_tpu import journal as journal_ns
        records, _ = journal_ns.read_json_records(d2.journal.path)
        for rec in records:
            if rec.get("event") == "gang":
                gang_events.append(rec)
            if rec.get("event") == "done" and rec.get("gang"):
                done_gangs.append(rec)
        assert gang_events and sorted(gang_events[0]["ids"]) == \
            sorted(rids)
        assert done_gangs and all(
            sorted(rec["gang"]) == sorted(rids) for rec in done_gangs)

    def test_poison_member_isolated_breaker_counts_one(
            self, tmp_path, gang_fault):
        """The fault-isolation acceptance: one poison member OOMs any
        gang containing it; bisection fails ONLY it, survivors' verdicts
        match offline, and the bucket's breaker counts exactly 1."""
        survivors = [_ops(3), _ops(4, value=9), _ops(5, value=20)]
        poison = _ops(7, value=50)   # same bucket, unique row count
        poison_n = _packed(poison)[0].n
        assert all(_packed(o)[0].n != poison_n for o in survivors)

        def fault(pks):
            if any(p.n == poison_n for p in pks):
                raise RuntimeError("RESOURCE_EXHAUSTED: injected gang "
                                   "OOM")

        gang_fault(fault)
        d1 = _daemon(tmp_path)
        rid_p = d1.submit({"tenant": "a", "model": "cas-register",
                           "history": poison})[1]["id"]
        rid_s = [d1.submit({"tenant": "ab"[i % 2],
                            "model": "cas-register", "history": o}
                           )[1]["id"] for i, o in enumerate(survivors)]
        d1.journal.close()

        d2 = _daemon(tmp_path, start=True, workers=1,
                     batch_wait_ms=200.0, breaker_fails=5)
        with d2._lock:
            # replay regenerates nothing: ids persist through the WAL
            assert set(d2._by_id) == {rid_p, *rid_s}
        doc_p = _wait_done(d2, rid_p)
        docs_s = [_wait_done(d2, r) for r in rid_s]

        res = doc_p["result"]
        assert res["serve"]["gang"]["poison"] is True
        assert res["error-class"] == "oom"
        assert res["serve"]["gang"]["size"] == 4
        assert d2.stats["poisoned"] == 1
        assert d2.stats["bisections"] >= 1
        for doc, ops in zip(docs_s, survivors):
            r = doc["result"]
            assert r["serve"]["gang"]["poison"] is False
            offline = _offline(ops)
            for key in _VERDICT_KEYS:
                assert r.get(key) == offline.get(key), (key, r)
        snap = d2.breaker.snapshot()
        fails = [r["fails"] for r in snap.values()]
        assert fails == [1], snap    # exactly the poison, nothing else
        d2.stop()

    def test_kill_switch_restores_serial_path(self, tmp_path,
                                              monkeypatch):
        monkeypatch.setenv("JTPU_SERVE_BATCH", "0")
        cfg = serve_ns.ServeConfig(root=str(tmp_path / "serve"),
                                   backend="tpu")
        assert cfg.batch_enabled is False
        d = serve_ns.CheckDaemon(cfg)
        assert d.batcher is None     # no scheduler object at all
        d.start()
        code, body, _ = d.submit({"model": "cas-register",
                                  "history": _ops(3)})
        assert code == 202
        doc = _wait_done(d, body["id"])
        assert doc["result"]["valid"] is True
        assert "gang" not in doc["result"]["serve"]
        d.stop()

    def test_batch_max_one_disables_scheduler(self, tmp_path):
        d = _daemon(tmp_path, batch_max=1)
        assert d.batcher is None
        d.stop()

    def test_retry_after_ewma_divides_by_batch_size(self, tmp_path):
        """The Retry-After satellite: a gang's wall-clock is amortized
        over its realized batch size, so an 8-wide 8 s batch reads as
        1 s/request — not 8."""
        d = _daemon(tmp_path, queue_max=16)
        reqs = []
        for v in (1, 5, 9):
            code, body, _ = d.submit({"model": "cas-register",
                                      "history": _ops(3, value=v)})
            assert code == 202
        for _ in range(3):
            reqs.append(d._dequeue())
        d._finish(reqs[0], {"valid": True}, 8.0, batch_size=8)
        assert d._service_ewma == pytest.approx(1.0)
        d._finish(reqs[1], {"valid": True}, 4.0, batch_size=4)
        assert d._service_ewma == pytest.approx(1.0)   # same per-request
        d._finish(reqs[2], {"valid": True}, 2.0)       # serial: 2 s/req
        assert d._service_ewma == pytest.approx(0.3 * 2.0 + 0.7 * 1.0)
        d.stop()


class TestWalGangReplay:
    def test_torn_tail_mid_gang_replays_all_members(self, tmp_path):
        """A SIGKILL that tears the WAL mid-gang-record: every accepted
        member still replays (none had a done record), the torn gang
        line is skipped, and verdicts match offline."""
        histories = [_ops(3), _ops(4, value=9)]
        d1 = _daemon(tmp_path)
        for ops in histories:
            assert d1.submit({"model": "cas-register",
                              "history": ops})[0] == 202
        d1.journal.close()
        with open(d1.journal.path, "ab") as f:
            f.write(b'deadbeef {"event": "gang", "ids": [tor')  # torn
        pending, stats = serve_ns.RequestJournal.replay(d1.journal.path)
        assert len(pending) == 2 and stats["torn"] == 1
        d2 = _daemon(tmp_path, start=True, workers=1,
                     batch_wait_ms=150.0)
        assert d2.replay_stats["requeued"] == 2
        with d2._lock:
            rids = list(d2._by_id)
        docs = [_wait_done(d2, rid) for rid in rids]
        d2.stop()
        served = sorted(repr(doc["result"]["valid"]) for doc in docs)
        offline = sorted(repr(_offline(o)["valid"]) for o in histories)
        assert served == offline

    def test_complete_gang_records_are_replay_inert(self, tmp_path):
        """A COMPLETE gang record (all members done) must not re-queue
        anything: gang membership is evidence, not acceptance."""
        d1 = _daemon(tmp_path, start=True, workers=1,
                     batch_wait_ms=100.0)
        ids = []
        for v in (1, 5):
            code, body, _ = d1.submit({"model": "cas-register",
                                       "history": _ops(3, value=v)})
            ids.append(body["id"])
        for rid in ids:
            _wait_done(d1, rid)
        d1.stop()
        pending, stats = serve_ns.RequestJournal.replay(d1.journal.path)
        assert pending == []
        assert stats["records"] >= 4   # accepted x2 (+gang) + done x2

    def test_interleaved_tenants_replay_in_acceptance_order(
            self, tmp_path):
        """Replay preserves WAL acceptance order across interleaved
        tenants; the re-formed gang then serves both tenants in one
        dispatch."""
        d1 = _daemon(tmp_path, queue_max=16)
        expect = []
        for i in range(4):
            code, body, _ = d1.submit(
                {"tenant": "ab"[i % 2], "model": "cas-register",
                 "history": _ops(3 + i, value=10 * i)})
            assert code == 202
            expect.append((body["id"], "ab"[i % 2]))
        d1.journal.close()
        pending, _ = serve_ns.RequestJournal.replay(d1.journal.path)
        assert [(p["id"], p["tenant"]) for p in pending] == expect
        d2 = _daemon(tmp_path, start=True, workers=1,
                     batch_wait_ms=200.0)
        assert d2.replay_stats["requeued"] == 4
        docs = [_wait_done(d2, rid) for rid, _ in expect]
        sizes = {doc["result"]["serve"]["gang"]["size"]
                 for doc in docs}
        tenants = {doc["tenant"] for doc in docs}
        assert sizes == {4} and tenants == {"a", "b"}
        d2.stop()


# ---------------------------------------------------------------------------
# Warm-state eviction (the --engine-max-buckets satellite)
# ---------------------------------------------------------------------------


class TestWarmEviction:
    def test_lru_evicts_oldest_warm_bucket(self):
        eng = Engine("evict-warm", max_warm_buckets=1)
        p1, kernel = _packed(_ops(2))
        p2, _ = _packed(_ops(40))          # a different shape bucket
        b1 = Engine.bucket_key(p1, kernel)
        b2 = Engine.bucket_key(p2, kernel)
        assert b1 != b2
        eng.warm(p1, kernel, rungs=1)
        eng.warm(p2, kernel, rungs=1)
        assert eng.warm_buckets() == [b2]  # LRU: oldest claim dropped
        assert eng.evictions == 1

    def test_touch_refreshes_lru_order(self):
        eng = Engine("evict-touch", max_warm_buckets=2)
        p1, kernel = _packed(_ops(2))
        p2, _ = _packed(_ops(40))
        p3, _ = _packed(_ops(10))
        keys = {Engine.bucket_key(p, kernel) for p in (p1, p2, p3)}
        assert len(keys) == 3, "need three distinct buckets"
        eng.warm(p1, kernel, rungs=1)
        eng.warm(p2, kernel, rungs=1)
        eng.warm(p1, kernel, rungs=1)      # touch: p1 is now newest
        eng.warm(p3, kernel, rungs=1)      # evicts p2, not p1
        assert Engine.bucket_key(p1, kernel) in eng.warm_buckets()
        assert Engine.bucket_key(p2, kernel) not in eng.warm_buckets()

    def test_env_and_setter_bound_the_claim(self, monkeypatch):
        monkeypatch.setenv("JTPU_ENGINE_MAX_BUCKETS", "3")
        assert Engine("env-bound").max_warm_buckets == 3
        monkeypatch.delenv("JTPU_ENGINE_MAX_BUCKETS")
        eng = Engine("set-bound")
        assert eng.max_warm_buckets == 0   # unbounded by default
        p1, kernel = _packed(_ops(2))
        p2, _ = _packed(_ops(40))
        eng.warm(p1, kernel, rungs=1)
        eng.warm(p2, kernel, rungs=1)
        eng.set_max_warm_buckets(1)        # trims immediately
        assert len(eng.warm_buckets()) == 1 and eng.evictions == 1

    def test_daemon_healthz_reports_eviction_state(self, tmp_path):
        d = _daemon(tmp_path, engine_max_buckets=2)
        assert d.engine.max_warm_buckets == 2
        health = d.healthz()
        assert health["engine"]["max-warm-buckets"] == 2
        assert health["engine"]["evictions"] == 0
        d.stop()


# ---------------------------------------------------------------------------
# Shared-secret auth (the --auth-token satellite)
# ---------------------------------------------------------------------------


class TestAuth:
    def _server(self, tmp_path, token):
        cfg = serve_ns.ServeConfig(root=str(tmp_path / "serve"),
                                   backend="tpu", auth_token=token)
        return serve_ns.run_daemon(cfg, host="127.0.0.1", port=0,
                                   store_root=str(tmp_path / "store"))

    def test_post_routes_require_bearer_token(self, tmp_path):
        daemon, server = self._server(tmp_path, "s3cret")
        port = server.server_port
        doc = {"model": "cas-register", "history": _ops()}
        try:
            code, body, hdrs = _post(port, "/check", doc)
            assert code == 401 and body["error"] == "unauthorized"
            assert hdrs.get("WWW-Authenticate") == "Bearer"

            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/check",
                data=json.dumps(doc).encode(), method="POST",
                headers={"Authorization": "Bearer wrong"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req)
            assert ei.value.code == 401

            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/check",
                data=json.dumps(doc).encode(), method="POST",
                headers={"Authorization": "Bearer s3cret"})
            with urllib.request.urlopen(req) as r:
                assert r.status == 202

            # metrics / healthz / the results browser stay open
            assert _get(port, "/healthz")[0] == 200
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics") as r:
                assert r.status == 200

            code, _, _ = _post(port, "/drain", None)
            assert code == 401     # drain is a mutating route too
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/drain", data=b"",
                method="POST",
                headers={"Authorization": "Bearer s3cret"})
            with urllib.request.urlopen(req) as r:
                assert json.load(r)["drained"] is True
        finally:
            server.shutdown()
            daemon.stop()

    def test_no_token_configured_keeps_routes_open(self, tmp_path):
        daemon, server = self._server(tmp_path, None)
        try:
            code, _, _ = _post(server.server_port, "/check",
                               {"model": "cas-register",
                                "history": _ops()})
            assert code == 202
        finally:
            server.shutdown()
            daemon.stop()

    def test_env_token_configures_daemon(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JTPU_SERVE_TOKEN", "from-env")
        cfg = serve_ns.ServeConfig(root=str(tmp_path / "serve"))
        assert cfg.auth_token == "from-env"


# ---------------------------------------------------------------------------
# Request-scoped distributed tracing (doc/observability.md, "Request
# tracing"): one trace id from POST /check to verdict
# ---------------------------------------------------------------------------


class TestRequestTrace:
    def test_inbound_traceparent_honored_and_echoed(self, tmp_path):
        from jepsen_tpu.obs import trace as obs_trace
        tid = obs_trace.new_trace_id()
        d = _daemon(tmp_path, start=True)
        code, body, hdrs = d.submit(
            {"model": "cas-register", "history": _ops(),
             "traceparent": f"00-{tid}-00f067aa0ba902b7-01"})
        assert code == 202
        assert body["trace"] == tid
        got = obs_trace.parse_traceparent(hdrs.get("traceparent"))
        assert got is not None and got[0] == tid
        doc = _wait_done(d, body["id"])
        assert doc["trace"] == tid
        assert doc["result"]["serve"]["trace"] == tid
        d.stop()

    def test_minted_when_absent_or_malformed(self, tmp_path):
        d = _daemon(tmp_path, start=True)
        _, b1, _ = d.submit({"model": "cas-register",
                             "history": _ops()})
        _, b2, _ = d.submit({"model": "cas-register",
                             "history": _ops(3),
                             "traceparent": "garbage-header"})
        assert len(b1["trace"]) == 32 and int(b1["trace"], 16) >= 0
        assert len(b2["trace"]) == 32
        assert b1["trace"] != b2["trace"]   # one id PER request
        for b in (b1, b2):
            _wait_done(d, b["id"])
        d.stop()

    def test_phase_breakdown_sums_to_wall_time(self, tmp_path):
        d = _daemon(tmp_path, start=True)
        code, body, _ = d.submit({"model": "cas-register",
                                  "history": _ops()})
        assert code == 202
        doc = _wait_done(d, body["id"])
        serve_doc = doc["result"]["serve"]
        ph = serve_doc["phases"]
        assert set(ph) == {"queue_s", "coalesce_s", "compile_s",
                           "device_s", "verdict_s"}
        assert all(v >= 0 for v in ph.values())
        # the three check-side phases partition the measured wall time
        check_side = ph["compile_s"] + ph["device_s"] + ph["verdict_s"]
        assert abs(check_side - serve_doc["seconds"]) < 0.05
        assert ph["device_s"] > 0           # the check ran on device
        d.stop()

    def test_trace_artifact_spans_admission_to_verdict(self, tmp_path):
        from jepsen_tpu.obs import trace as obs_trace
        d = _daemon(tmp_path, start=True)
        code, body, _ = d.submit({"model": "cas-register",
                                  "history": _ops()})
        assert code == 202
        tid = body["trace"]
        _wait_done(d, body["id"])
        d.stop()                            # flushes + detaches sink
        path = os.path.join(d.config.root, "trace.jsonl")
        recs, stats = obs_trace.read_trace(path)
        assert stats["torn"] == 0 and stats["corrupt"] == 0
        assert any(r["name"] == "trace.sync" for r in recs)
        mine = obs_trace.by_trace(recs).get(tid, [])
        names = {r["name"] for r in mine}
        assert {"serve.request", "serve.verdict"} <= names
        # the device segment joined the same trace (a previously-run
        # suite may have warmed the engine's bucket already, in which
        # case engine.warm legitimately never runs — the fresh-process
        # CI gate asserts the full ≥4-phase waterfall)
        assert names & {"checker.segment", "engine.warm"}
        req_spans = [r for r in mine if r["name"] == "serve.request"]
        assert req_spans and req_spans[0]["id"] == body["id"]

    def test_replay_keeps_original_trace_id(self, tmp_path):
        from jepsen_tpu import journal as journal_ns
        from jepsen_tpu.obs import trace as obs_trace
        tid = obs_trace.new_trace_id()
        d1 = _daemon(tmp_path)
        code, body, _ = d1.submit(
            {"model": "cas-register", "history": _ops(),
             "traceparent": f"00-{tid}-00f067aa0ba902b7-01"})
        assert code == 202 and body["trace"] == tid
        d1.journal.close()                  # SIGKILL before any work
        d2 = _daemon(tmp_path, start=True)
        assert d2.replay_stats["requeued"] == 1
        with d2._lock:
            rid = next(iter(d2._by_id))
        doc = _wait_done(d2, rid)
        assert doc["trace"] == tid          # NOT a fresh mint
        assert doc["result"]["serve"]["trace"] == tid
        d2.stop()
        records, _ = journal_ns.read_json_records(d2.journal.path)
        accepted = [r for r in records if r.get("event") == "accepted"]
        assert accepted and all(r["trace"] == tid for r in accepted)

    def test_gang_members_traced_and_verdicts_bit_identical(
            self, tmp_path):
        """Tracing ON must not perturb gang verdicts: every member's
        verdict matches the offline serial path bit-for-bit, each
        member keeps its OWN trace id, and non-leaders link to the
        leader's trace via serve.gang.join."""
        from jepsen_tpu.obs import trace as obs_trace
        histories = [_ops(3), _ops(4, value=9), _ops(5, value=20)]
        d1 = _daemon(tmp_path)
        for ops in histories:
            code, _, _ = d1.submit({"model": "cas-register",
                                    "history": ops})
            assert code == 202
        d1.journal.close()
        d2 = _daemon(tmp_path, start=True, workers=1,
                     batch_wait_ms=200.0)
        with d2._lock:
            rids = list(d2._by_id)
        docs = [_wait_done(d2, rid) for rid in rids]
        assert d2.stats["batches"] >= 1
        d2.stop()
        tids = [doc["trace"] for doc in docs]
        assert len(set(tids)) == len(tids)  # one id per member
        for doc in docs:
            assert doc["result"]["serve"]["gang"]["size"] >= 2
            assert doc["result"]["serve"]["trace"] == doc["trace"]
            assert "phases" in doc["result"]["serve"]
        served = sorted(repr(doc["result"]["valid"]) for doc in docs)
        offline = sorted(repr(_offline(o)["valid"]) for o in histories)
        assert served == offline
        recs, _ = obs_trace.read_trace(
            os.path.join(d2.config.root, "trace.jsonl"))
        joins = [r for r in recs if r["name"] == "serve.gang.join"]
        assert joins                         # non-leaders linked
        leader_tid = joins[0]["leader"]
        assert leader_tid in tids
        assert all(j["trace"] != leader_tid for j in joins)
        gang_spans = [r for r in recs if r["name"] == "serve.gang"]
        assert gang_spans and \
            gang_spans[0]["trace"] == leader_tid

    def test_http_roundtrip_carries_traceparent(self, tmp_path):
        from jepsen_tpu.obs import trace as obs_trace
        tid = obs_trace.new_trace_id()
        cfg = serve_ns.ServeConfig(root=str(tmp_path / "serve"),
                                   backend="tpu")
        daemon, server = serve_ns.run_daemon(
            cfg, host="127.0.0.1", port=0,
            store_root=str(tmp_path / "store"))
        port = server.server_port
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/check",
                data=json.dumps({"model": "cas-register",
                                 "history": _ops()}).encode(),
                method="POST",
                headers={"traceparent":
                         f"00-{tid}-00f067aa0ba902b7-01"})
            with urllib.request.urlopen(req) as r:
                assert r.status == 202
                body = json.load(r)
                assert body["trace"] == tid
                echoed = r.headers.get("traceparent")
            assert obs_trace.parse_traceparent(echoed)[0] == tid
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/check/"
                        f"{body['id']}") as r:
                    doc = json.load(r)
                    hdr = r.headers.get("traceparent")
                if doc["state"] == "done":
                    break
                time.sleep(0.05)
            assert doc["state"] == "done"
            assert doc["result"]["serve"]["phases"]["device_s"] > 0
            assert obs_trace.parse_traceparent(hdr)[0] == tid
            # the stitched waterfall renders over HTTP too
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/trace/request/"
                    f"{body['id']}") as r:
                page = r.read().decode()
            assert tid in page and "serve.request" in page
        finally:
            server.shutdown()
            daemon.stop()


class TestTraceKillSwitch:
    def test_off_leaves_no_trace_anywhere(self, tmp_path, monkeypatch):
        """JTPU_TRACE=0 is the identity: no trace ids minted, no
        traceparent echoed, no trace keys in the WAL, no trace.jsonl,
        no phases in results — byte-compatible with the pre-tracing
        daemon."""
        from jepsen_tpu import journal as journal_ns
        monkeypatch.setenv("JTPU_TRACE", "0")
        d = _daemon(tmp_path, start=True)
        code, body, hdrs = d.submit(
            {"model": "cas-register", "history": _ops(),
             "traceparent": "00-" + "ab" * 16
                            + "-00f067aa0ba902b7-01"})
        assert code == 202
        assert "trace" not in body
        assert "traceparent" not in hdrs
        doc = _wait_done(d, body["id"])
        assert "trace" not in doc
        assert "trace" not in doc["result"]["serve"]
        assert "phases" not in doc["result"]["serve"]
        d.stop()
        assert not os.path.exists(
            os.path.join(d.config.root, "trace.jsonl"))
        records, _ = journal_ns.read_json_records(d.journal.path)
        assert all("trace" not in r for r in records)

    def test_verdicts_identical_on_and_off(self, tmp_path,
                                           monkeypatch):
        ops = _ops(3)
        monkeypatch.setenv("JTPU_TRACE", "0")
        d_off = _daemon(tmp_path / "off", start=True)
        _, b_off, _ = d_off.submit({"model": "cas-register",
                                    "history": ops})
        r_off = _wait_done(d_off, b_off["id"])["result"]
        d_off.stop()
        monkeypatch.setenv("JTPU_TRACE", "1")
        d_on = _daemon(tmp_path / "on", start=True)
        _, b_on, _ = d_on.submit({"model": "cas-register",
                                  "history": ops})
        r_on = _wait_done(d_on, b_on["id"])["result"]
        d_on.stop()
        for key in ("valid", "levels", "rung", "work"):
            assert r_off.get(key) == r_on.get(key)


class TestTenantLatencyLabels:
    def test_queue_wait_labeled_per_tenant_with_exemplars(
            self, tmp_path):
        """Satellite: the queue-wait histogram is labeled per tenant
        (fairness is observable per tenant, not just in aggregate) and
        traced requests leave OpenMetrics exemplars pointing at their
        trace ids."""
        before = {
            t: serve_ns._QUEUE_WAIT.snapshot().get(
                f'{{tenant="{t}"}}', {"count": 0})["count"]
            for t in ("tenA", "tenB")}
        d = _daemon(tmp_path, start=True)
        rids, tids = [], {}
        for i in range(4):
            tenant = "tenA" if i % 2 == 0 else "tenB"
            code, body, _ = d.submit({"model": "cas-register",
                                      "tenant": tenant,
                                      "history": _ops(2 + i)})
            assert code == 202
            rids.append(body["id"])
            tids[body["id"]] = body["trace"]
        for rid in rids:
            _wait_done(d, rid)
        d.stop()
        snap = serve_ns._QUEUE_WAIT.snapshot()
        # fairness: BOTH tenants' waits were observed, two each —
        # neither tenant's latency hides in the other's series
        for t in ("tenA", "tenB"):
            series = snap.get(f'{{tenant="{t}"}}')
            assert series is not None, snap.keys()
            assert series["count"] - before[t] == 2
        lines = serve_ns._QUEUE_WAIT.expose()
        ex_lines = [ln for ln in lines if " # {trace_id=" in ln]
        assert ex_lines, "no exemplar on any queue-wait bucket"
        assert any(tid in ln for ln in ex_lines
                   for tid in tids.values())

    def test_coalesce_wait_labeled_per_tenant(self, tmp_path):
        d1 = _daemon(tmp_path)
        for v in (1, 5):
            d1.submit({"model": "cas-register", "tenant": "gangT",
                       "history": _ops(3, value=v)})
        d1.journal.close()
        d2 = _daemon(tmp_path, start=True, workers=1,
                     batch_wait_ms=200.0)
        with d2._lock:
            rids = list(d2._by_id)
        for rid in rids:
            _wait_done(d2, rid)
        assert d2.stats["batches"] >= 1
        d2.stop()
        snap = serve_ns._COALESCE_WAIT.snapshot()
        series = snap.get('{tenant="gangT"}')
        assert series is not None and series["count"] >= 1


class TestOldestInflight:
    def test_healthz_and_watch_line_surface_age(self, tmp_path):
        from jepsen_tpu.obs import observatory
        d = _daemon(tmp_path)
        assert d.healthz()["oldest-inflight-s"] is None
        req = serve_ns.CheckRequest(id="r-stuck", tenant="t",
                                    model="cas-register", history=[])
        req.started_at = time.monotonic() - 12.5
        with d._lock:
            d._inflight[req.id] = req
        age = d.healthz()["oldest-inflight-s"]
        assert age is not None and 12.0 < age < 14.0
        d._publish(force=True)
        p = observatory.read_progress(d.config.root)
        line = observatory.format_status(p)
        assert "oldest-inflight 12." in line
        with d._lock:
            del d._inflight[req.id]
        d._publish(force=True)
        p = observatory.read_progress(d.config.root)
        assert "oldest-inflight" not in observatory.format_status(p)

    def test_age_counts_from_dequeue_not_submit(self, tmp_path):
        d = _daemon(tmp_path)
        req = serve_ns.CheckRequest(id="r-q", tenant="t",
                                    model="cas-register", history=[])
        req.queued_at = time.monotonic() - 100.0   # long queue wait
        req.started_at = time.monotonic() - 2.0    # just dequeued
        with d._lock:
            d._inflight[req.id] = req
        age = d.healthz()["oldest-inflight-s"]
        assert age is not None and age < 5.0


class TestTracerAttachRace:
    def test_attach_detach_races_serve_workers(self, tmp_path):
        """Satellite: re-pointing the tracer sink while serve workers
        stream spans must neither raise nor tear lines — every record
        lands whole in whichever file held the sink."""
        from jepsen_tpu.obs import trace as obs_trace
        d = _daemon(tmp_path, start=True, workers=2)
        paths = [str(tmp_path / f"alt{i}.jsonl") for i in range(2)]
        stop = threading.Event()
        errors = []

        def flipper():
            i = 0
            try:
                while not stop.is_set():
                    obs_trace.tracer().attach(paths[i % 2])
                    i += 1
                    time.sleep(0.001)
            except Exception as e:          # pragma: no cover
                errors.append(e)

        t = threading.Thread(target=flipper)
        t.start()
        try:
            rids = []
            for i in range(6):
                code, body, _ = d.submit({"model": "cas-register",
                                          "history": _ops(2 + i % 3)})
                assert code == 202
                rids.append(body["id"])
            for rid in rids:
                _wait_done(d, rid)
        finally:
            stop.set()
            t.join()
            obs_trace.tracer().detach()
            d.stop()
        assert not errors
        total = 0
        for p in paths:
            if os.path.exists(p):
                recs, stats = obs_trace.read_trace(p)
                assert stats["torn"] == 0 and stats["corrupt"] == 0
                total += stats["spans"]
        assert total > 0                    # the races did overlap


# ---------------------------------------------------------------------------
# Per-tenant rate limiting (the fleet-capacity-aware 429 satellite)
# ---------------------------------------------------------------------------


class TestRateLimit:
    def test_token_bucket_refills_lazily(self):
        tb = serve_ns.TokenBucket(10.0, 2)
        assert tb.take() == 0.0
        assert tb.take() == 0.0            # burst of 2 admits two
        wait = tb.take()
        assert 0.0 < wait <= 0.1           # then ~1/rate until a token
        time.sleep(wait + 0.02)
        assert tb.take() == 0.0            # refilled

    def test_burst_exceeded_gets_429_with_retry_after(self, tmp_path):
        d = _daemon(tmp_path, queue_max=32, rate_limit=0.5, rate_burst=2)
        try:
            for _ in range(2):
                code, _, _ = d.submit({"model": "cas-register",
                                       "history": _ops(),
                                       "tenant": "bursty"})
                assert code == 202
            code, body, hdrs = d.submit({"model": "cas-register",
                                         "history": _ops(),
                                         "tenant": "bursty"})
            assert code == 429
            assert body["error"] == "rate-limited"
            assert body["retry-after-s"] > 0
            assert "Retry-After" in hdrs
            assert d.stats["rate-limited"] == 1
            # an independent tenant still has its own full bucket
            code, _, _ = d.submit({"model": "cas-register",
                                   "history": _ops(), "tenant": "calm"})
            assert code == 202
        finally:
            d.stop()

    def test_replay_bypasses_rate_limit(self, tmp_path):
        """WAL replay re-admits accepted requests regardless of the
        limiter: the 202 was already promised in a prior life."""
        d = _daemon(tmp_path, queue_max=32, rate_limit=0.5, rate_burst=1)
        try:
            for i in range(3):
                code, _, _ = d.submit({"model": "cas-register",
                                       "history": _ops(2 + i),
                                       "tenant": "replayed"},
                                      replayed=True)
                assert code == 202
            assert d.stats["rate-limited"] == 0
        finally:
            d.stop()

    def test_no_limit_by_default(self, tmp_path):
        d = _daemon(tmp_path, queue_max=32)
        try:
            assert d.config.rate_limit == 0.0
            for _ in range(8):
                code, _, _ = d.submit({"model": "cas-register",
                                       "history": _ops(),
                                       "tenant": "free"})
                assert code == 202
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# Fleet-width-aware Retry-After (satellite: EWMA x live host count)
# ---------------------------------------------------------------------------


class TestRetryAfterFleetWidth:
    def _stub_placer(self, width):
        import types
        return types.SimpleNamespace(width=lambda: width,
                                     live=lambda: width,
                                     hosts=[None] * width,
                                     stats={"remeshes": 0},
                                     stop=lambda: None)

    def test_ewma_tracks_host_seconds(self, tmp_path):
        """An 8 s gang of 8 on a 4-host fleet burned 32 host-seconds:
        4 host-seconds/request, NOT 1 — so the hint survives a shrink
        to one host without underestimating."""
        d = _daemon(tmp_path, queue_max=16)
        d.placer = self._stub_placer(4)
        try:
            code, body, _ = d.submit({"model": "cas-register",
                                      "history": _ops(3)})
            assert code == 202
            req = d._dequeue()
            d._finish(req, {"valid": True}, 8.0, batch_size=8)
            assert d._service_ewma == pytest.approx(4.0)
        finally:
            d.placer = None
            d.stop()

    def test_retry_after_divides_by_live_width(self, tmp_path):
        """The same backlog reads 4x shorter on a 4-host fleet — and
        stretches right back when the fleet shrinks (host loss)."""
        d = _daemon(tmp_path, queue_max=16)
        try:
            for v in (1, 5):
                code, _, _ = d.submit({"model": "cas-register",
                                       "history": _ops(3, value=v)})
                assert code == 202
            d._service_ewma = 20.0
            single = d._retry_after()
            d.placer = self._stub_placer(4)
            quad = d._retry_after()
            assert quad == pytest.approx(single / 4)
            d.placer = self._stub_placer(1)     # fleet lost 3 hosts
            assert d._retry_after() == pytest.approx(single)
        finally:
            d.placer = None
            d.stop()


# ---------------------------------------------------------------------------
# Breaker DCN-neutrality (satellite: fleet-retried classes don't trip)
# ---------------------------------------------------------------------------


class TestBreakerDcnNeutral:
    BUCKET = ("cas-register", 16, 0, 32)

    def test_dcn_class_failures_do_not_trip(self):
        from jepsen_tpu.resilience import DCN, TRANSIENT
        br = serve_ns.CircuitBreaker(2, 0.05)
        for cls in (DCN, TRANSIENT, DCN, DCN):
            br.record(self.BUCKET, cls, probe=False)
        ok, _, _ = br.allow(self.BUCKET)
        assert ok, "fleet-retried DCN failures must not open the breaker"
        assert br.open_count() == 0

    def test_dcn_neither_trips_nor_resets(self):
        """Neutral means neutral: a DCN blip between two real OOMs
        neither counts toward the threshold nor wipes the first OOM's
        strike."""
        from jepsen_tpu.resilience import DCN, OOM
        br = serve_ns.CircuitBreaker(2, 0.05)
        br.record(self.BUCKET, OOM, probe=False)
        br.record(self.BUCKET, DCN, probe=False)    # neutral
        rec = list(br.snapshot().values())[0]
        assert rec["fails"] == 1                    # not reset to 0
        br.record(self.BUCKET, OOM, probe=False)
        ok, _, _ = br.allow(self.BUCKET)
        assert not ok                               # 2 real strikes trip

    def test_dcn_probe_frees_the_slot(self):
        """A half-open probe that ends in a DCN blip must release the
        probe slot (else the breaker wedges half-open forever) without
        closing or re-opening."""
        import random as _random
        from jepsen_tpu.resilience import DCN, OOM
        br = serve_ns.CircuitBreaker(1, 0.05, rng=_random.Random(7))
        br.record(self.BUCKET, OOM, probe=False)
        time.sleep(0.08)
        ok, _, probe = br.allow(self.BUCKET)
        assert ok and probe
        br.record(self.BUCKET, DCN, probe=True)     # inconclusive probe
        ok, _, probe = br.allow(self.BUCKET)
        assert ok and probe, "slot freed: the NEXT probe may run"


# ---------------------------------------------------------------------------
# Byte-based warm eviction (the headroom-driven satellite)
# ---------------------------------------------------------------------------


class TestByteEviction:
    def _warm_two(self, eng):
        p1, kernel = _packed(_ops(2))
        p2, _ = _packed(_ops(40))
        b1 = Engine.bucket_key(p1, kernel)
        b2 = Engine.bucket_key(p2, kernel)
        assert b1 != b2
        eng.warm(p1, kernel, rungs=1)
        eng.warm(p2, kernel, rungs=1)
        return b1, b2

    def test_warm_records_carry_bytes(self):
        eng = Engine("bytes-rec")
        self._warm_two(eng)
        assert eng.warm_bytes() > 0        # plan-priced, not guessed

    def test_bytes_budget_trims_stalest_first(self):
        eng = Engine("bytes-budget")
        b1, b2 = self._warm_two(eng)
        total = eng.warm_bytes()
        eng.set_max_warm_bytes(total - 1)  # over budget by one byte
        assert eng.warm_buckets() == [b2]  # stalest (b1) evicted
        assert eng.evictions == 1

    def test_bytes_budget_keeps_newest_bucket(self):
        """Even an absurd 1-byte budget never evicts the LAST warm
        bucket — the serving path must keep its working set."""
        eng = Engine("bytes-floor")
        _, b2 = self._warm_two(eng)
        eng.set_max_warm_bytes(1)
        assert eng.warm_buckets() == [b2]

    def test_env_budget_wired(self, monkeypatch):
        monkeypatch.setenv("JTPU_ENGINE_BYTES_BUDGET", "12345")
        assert Engine("env-bytes").max_warm_bytes == 12345

    def test_evict_below_headroom_driven_by_gauge(self):
        """Memory pressure (headroom below the floor) evicts stalest
        buckets one at a time until the gauge recovers — count-blind,
        byte-driven."""
        eng = Engine("headroom")
        b1, b2 = self._warm_two(eng)
        ratios = iter([0.01, 0.05])        # starved, then recovered
        n = eng.evict_below_headroom(0.02, poll=lambda: next(ratios))
        assert n == 1
        assert eng.warm_buckets() == [b2]

    def test_evict_below_headroom_stops_at_last_bucket(self):
        eng = Engine("headroom-floor")
        self._warm_two(eng)
        n = eng.evict_below_headroom(0.5, poll=lambda: 0.0)
        assert n == 1                      # evicted down to one...
        assert len(eng.warm_buckets()) == 1   # ...then stopped

    def test_evict_below_headroom_no_pressure_is_noop(self):
        eng = Engine("headroom-ok")
        self._warm_two(eng)
        assert eng.evict_below_headroom(0.02, poll=lambda: 0.9) == 0
        assert len(eng.warm_buckets()) == 2

    def test_healthz_reports_byte_state(self, tmp_path):
        d = _daemon(tmp_path, engine_bytes_budget=1 << 20)
        try:
            assert d.engine.max_warm_bytes == 1 << 20
            health = d.healthz()
            assert health["engine"]["max-warm-bytes"] == 1 << 20
            # the daemon shares the process-wide engine, so other
            # tests' warm buckets may already be claimed here
            assert health["engine"]["warm-bytes"] == \
                d.engine.warm_bytes()
        finally:
            d.stop()
