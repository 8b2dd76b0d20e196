"""Resilient execution layer: checkpointed segments, wedge watchdog, OOM
backoff, structured retries (jepsen_tpu.resilience) — plus the bounded
client ops and nemesis-wedge accounting in core.py.

The injected-fault scenarios carry the ``chaos`` marker;
tools/chaos_matrix.py sweeps the same grid standalone."""

import os
import threading
import time
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jepsen_tpu import resilience
from jepsen_tpu.checker import UNKNOWN
from jepsen_tpu.checker import tpu as tpu_mod
from jepsen_tpu.checker.tpu import (
    DEFAULT_SEGMENT_ITERS, _carry0_host, _segment_config, check_history_tpu)
from jepsen_tpu.checker.wgl import check_packed
from jepsen_tpu.history import History
from jepsen_tpu.models import CASRegister
from jepsen_tpu.models.core import CAS_REGISTER_KERNEL
from jepsen_tpu.ops.encode import pack_with_init
from jepsen_tpu.resilience import (
    FATAL, OOM, TRANSIENT, WEDGE, Checkpoint, RetryPolicy, WedgeError,
    classify_failure, supervised_check_packed)
from jepsen_tpu.testing import simulate_register_history, wide_history


@pytest.fixture(autouse=True)
def clean_resilience_state(monkeypatch):
    """No fault hook or runtime-wedge verdict may leak between tests."""
    monkeypatch.setattr(resilience, "_inject_fault", None)
    # fast, deterministic backoff everywhere
    monkeypatch.setenv("JEPSEN_RETRY_BASE", "0.001")
    yield
    resilience._reset_wedge_for_tests()


def _packed(h, model=None):
    return pack_with_init(h, model or CASRegister())


def fast_policy(**kw):
    kw.setdefault("backoff_base_s", 0.001)
    kw.setdefault("backoff_cap_s", 0.01)
    return RetryPolicy(**kw)


class TestClassification:
    def test_taxonomy(self):
        assert classify_failure(WedgeError("x")) == WEDGE
        assert classify_failure(MemoryError()) == OOM
        assert classify_failure(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory")) == OOM
        assert classify_failure(
            RuntimeError("XLA:TPU compile... failed to allocate")) == OOM
        assert classify_failure(ConnectionResetError("peer")) == TRANSIENT
        assert classify_failure(TimeoutError("rpc")) == TRANSIENT
        assert classify_failure(
            RuntimeError("UNAVAILABLE: endpoint draining")) == TRANSIENT
        assert classify_failure(ValueError("bad shape")) == FATAL
        assert classify_failure(AssertionError()) == FATAL

    def test_backoff_capped_and_jittered(self):
        p = RetryPolicy(backoff_base_s=0.1, backoff_cap_s=0.5,
                        jitter=False)
        assert p.delay(1) == pytest.approx(0.1)
        assert p.delay(2) == pytest.approx(0.2)
        assert p.delay(10) == pytest.approx(0.5)  # capped
        pj = RetryPolicy(backoff_base_s=0.1, backoff_cap_s=0.5)
        for a in (1, 3, 9):
            d = pj.delay(a)
            full = min(0.5, 0.1 * 2 ** (a - 1))
            assert full / 2 <= d <= full

    def test_policy_env_defaults(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_RETRY_BASE", "0.25")
        monkeypatch.setenv("JEPSEN_RETRY_CAP", "2.5")
        p = RetryPolicy()
        assert p.backoff_base_s == pytest.approx(0.25)
        assert p.backoff_cap_s == pytest.approx(2.5)


class TestSegmentConfig:
    def test_default_and_env(self, monkeypatch):
        monkeypatch.delenv("JTPU_SEGMENT_ITERS", raising=False)
        assert _segment_config(None) == DEFAULT_SEGMENT_ITERS
        monkeypatch.setenv("JTPU_SEGMENT_ITERS", "64")
        assert _segment_config(None) == 64

    def test_argument_wins(self, monkeypatch):
        monkeypatch.setenv("JTPU_SEGMENT_ITERS", "64")
        assert _segment_config(7) == 7

    @pytest.mark.parametrize("arg,env,shown", [
        (0, None, "segment_iters must be a positive integer, got 0"),
        (-3, None, "segment_iters must be a positive integer, got -3"),
        (None, "0", "JTPU_SEGMENT_ITERS must be a positive integer, "
                    "got '0'"),
        (None, "-1", "JTPU_SEGMENT_ITERS must be a positive integer, "
                     "got '-1'"),
        (None, "nope", "JTPU_SEGMENT_ITERS must be a positive integer, "
                       "got 'nope'"),
    ], ids=["arg-zero", "arg-negative", "env-zero", "env-negative",
            "env-not-a-number"])
    def test_rejects_what_is_not_a_positive_count(self, monkeypatch, arg,
                                                  env, shown):
        if env is None:
            monkeypatch.delenv("JTPU_SEGMENT_ITERS", raising=False)
        else:
            monkeypatch.setenv("JTPU_SEGMENT_ITERS", env)
        with pytest.raises(ValueError) as ei:
            _segment_config(arg)
        assert str(ei.value) == shown

    @pytest.mark.parametrize("how", ["argument", "env"])
    def test_check_history_rejects_zero_before_the_device(
            self, monkeypatch, how):
        h = simulate_register_history(30, n_procs=3, n_vals=4, seed=1)

        def boom(*a, **k):
            raise AssertionError("a search executable was built")

        monkeypatch.setattr(tpu_mod, "_jit_segment", boom)
        kw = {}
        if how == "argument":
            kw["segment_iters"] = 0
        else:
            monkeypatch.setenv("JTPU_SEGMENT_ITERS", "0")
        with pytest.raises(ValueError, match="got '?0'?$"):
            check_history_tpu(h, CASRegister(), **kw)


def _wgl_valid(h):
    p, kernel = _packed(h)
    return check_packed(p, kernel)["valid"]


def _phantom_read(h):
    """``h`` with its first ok read answering 99, a value no op wrote:
    refuted whatever the interleaving."""
    rows = list(h)
    i = next(i for i, o in enumerate(rows)
             if o.type == "ok" and o.f == "read" and o.value is not None)
    rows[i] = rows[i].replace(value=99)
    return History.of(rows)


class TestSegmentedEqualsMonolithic:
    """Any segment length runs the same search: small segments (many
    barriers, the carry round-tripping through the host each time)
    against one monolithic segment covering the whole search
    (DEFAULT_SEGMENT_ITERS), and both against the host WGL engine's
    verdict."""

    def test_differential_random_histories(self, monkeypatch):
        import random
        rng = random.Random(11)
        for i in range(8):
            h = simulate_register_history(
                120, n_procs=4, n_vals=4, seed=100 + i,
                crash_p=0.05, overlap_p=0.5)
            one = check_history_tpu(
                h, CASRegister(), segment_iters=DEFAULT_SEGMENT_ITERS)
            seg = check_history_tpu(
                h, CASRegister(), segment_iters=rng.choice((1, 3, 9, 17)))
            assert one["segments"] == 1
            assert seg["valid"] == one["valid"] == _wgl_valid(h)
            assert seg["levels"] == one["levels"]
            assert seg["rung"] == one["rung"]
            assert seg["segments"] >= 1

    def test_refutation_evidence_identical(self, monkeypatch):
        h = wide_history(16, 2, seed=5, corrupt=True)
        one = check_history_tpu(h, CASRegister(),
                                segment_iters=DEFAULT_SEGMENT_ITERS)
        seg = check_history_tpu(h, CASRegister(), segment_iters=6)
        assert one["valid"] is False and seg["valid"] is False
        assert _wgl_valid(h) is False
        for k in ("max-linearized-prefix", "final-states", "levels",
                  "rung"):
            assert seg.get(k) == one.get(k), k

    @pytest.mark.parametrize("refuted", [False, True],
                             ids=["valid", "refuted"])
    @pytest.mark.parametrize("seg", [1, 3, 9, 17, DEFAULT_SEGMENT_ITERS])
    def test_matches_the_host_engine(self, seg, refuted):
        h = simulate_register_history(90, n_procs=4, n_vals=4, seed=31,
                                      crash_p=0.03, overlap_p=0.5)
        if refuted:
            h = _phantom_read(h)
        want = _wgl_valid(h)
        assert want is (not refuted)
        r = check_history_tpu(h, CASRegister(), segment_iters=seg)
        assert r["valid"] is want
        assert r["segment-iters"] == seg
        assert r["segments"] >= -(-r["levels"] // seg)

    def test_result_carries_resilience_keys(self):
        h = simulate_register_history(60, n_procs=3, n_vals=4, seed=1)
        r = check_history_tpu(h, CASRegister(), segment_iters=8)
        assert r["valid"] is True
        assert r["segments"] >= 1
        assert r["segment-iters"] == 8
        assert r["attempts"][-1]["event"] == "rung-complete"
        assert r["attempts"][-1]["levels"] == r["levels"]


class TestCheckpointRoundTrip:
    def test_save_load_roundtrip(self, tmp_path):
        h = simulate_register_history(100, n_procs=4, n_vals=4, seed=3,
                                      crash_p=0.05)
        p, kernel = _packed(h)
        cps = []
        base = supervised_check_packed(p, kernel, capacity=64, expand=8,
                                       segment_iters=6,
                                       on_checkpoint=cps.append)
        assert cps, "multi-segment search must emit checkpoints"
        mid = cps[len(cps) // 2]
        path = str(tmp_path / "search.npz")
        mid.save(path)
        loaded = Checkpoint.load(path)
        assert loaded.rung == mid.rung
        assert loaded.segment == mid.segment
        assert loaded.level == mid.level
        for a, b in zip(loaded.carry, mid.carry):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        resumed = supervised_check_packed(p, kernel, capacity=64, expand=8,
                                          segment_iters=6, resume=loaded)
        assert resumed["valid"] == base["valid"]
        assert resumed["levels"] == base["levels"]

    @pytest.mark.chaos
    def test_kill_mid_run_resumes_identically(self):
        """The acceptance scenario: a search killed after N segments
        (injected exception) resumes from its checkpoint and returns a
        verdict identical to the uninterrupted run, attempt trail
        included."""
        h = simulate_register_history(150, n_procs=5, n_vals=4, seed=9,
                                      crash_p=0.03)
        p, kernel = _packed(h)
        uninterrupted = supervised_check_packed(
            p, kernel, capacity=128, expand=8, segment_iters=8)
        cps = []

        def kill_at_3(ctx):
            if ctx["segment"] == 3:
                raise ValueError("simulated mid-run kill")

        resilience._inject_fault = kill_at_3
        try:
            with pytest.raises(ValueError) as ei:
                supervised_check_packed(
                    p, kernel, capacity=128, expand=8, segment_iters=8,
                    policy=fast_policy(max_retries=0),
                    on_checkpoint=cps.append)
        finally:
            resilience._inject_fault = None
        # the dying search left its trail on the exception
        assert ei.value.resilience_trail
        assert len(cps) == 3
        resumed = supervised_check_packed(
            p, kernel, capacity=128, expand=8, segment_iters=8,
            resume=cps[-1])
        assert resumed["valid"] == uninterrupted["valid"]
        assert resumed["levels"] == uninterrupted["levels"]
        assert resumed["segments"] == uninterrupted["segments"]


class TestInjectedOOM:
    @pytest.mark.chaos
    def test_oom_shrinks_pool_and_stays_sound(self):
        h = simulate_register_history(150, n_procs=5, n_vals=4, seed=4,
                                      crash_p=0.02)
        p, kernel = _packed(h)
        oracle = check_packed(p, kernel)
        fired = []

        def oom_twice(ctx):
            if ctx["segment"] == 1 and len(fired) < 2:
                fired.append(ctx["effective"])
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: Out of memory while trying to "
                    "allocate the search pool")

        resilience._inject_fault = oom_twice
        try:
            r = supervised_check_packed(
                p, kernel, capacity=256, expand=16, segment_iters=8,
                policy=fast_policy())
        finally:
            resilience._inject_fault = None
        assert len(fired) == 2
        assert r["valid"] == oracle["valid"]
        assert r["rung"][0] == 64          # 256 -> 128 -> 64
        assert r["rung-requested"] == (256, 32, 16)
        ooms = [a for a in r["attempts"] if a.get("event") == OOM]
        assert len(ooms) == 2
        assert all("backoff-s" in a for a in ooms)

    @pytest.mark.chaos
    def test_oom_at_floor_reports_unknown_with_trail(self):
        h = simulate_register_history(80, n_procs=4, n_vals=4, seed=6)
        p, kernel = _packed(h)

        def always_oom(ctx):
            raise MemoryError("oom")

        resilience._inject_fault = always_oom
        try:
            r = supervised_check_packed(
                p, kernel, capacity=32, expand=4, segment_iters=8,
                policy=fast_policy())
        finally:
            resilience._inject_fault = None
        assert r["valid"] is UNKNOWN
        assert "pool floor" in r["error"]
        assert any(a.get("outcome") == "gave-up" for a in r["attempts"])


class TestInjectedWedge:
    @pytest.mark.chaos
    def test_wedge_falls_back_to_cpu_and_completes(self):
        """The acceptance scenario: a mid-execution wedge completes on
        the CPU fallback instead of hanging."""
        h = simulate_register_history(150, n_procs=5, n_vals=4, seed=8,
                                      crash_p=0.02)
        p, kernel = _packed(h)
        base = supervised_check_packed(p, kernel, capacity=128, expand=8,
                                       segment_iters=8)
        wedged = []

        def wedge_once(ctx):
            if ctx["segment"] == 2 and not wedged:
                wedged.append(ctx["backend"])
                raise WedgeError("injected wedged execution")

        resilience._inject_fault = wedge_once
        try:
            with pytest.warns(RuntimeWarning,
                              match="execution wedged.*mid-run"):
                r = supervised_check_packed(
                    p, kernel, capacity=128, expand=8, segment_iters=8)
        finally:
            resilience._inject_fault = None
        assert wedged == ["default"]
        assert r["valid"] == base["valid"]
        assert r["levels"] == base["levels"]
        assert r["backend-fallback"] == "cpu"
        wedge_events = [a for a in r["attempts"]
                        if a.get("event") == WEDGE]
        assert wedge_events and \
            wedge_events[0]["outcome"] == "cpu-fallback"
        # the wedge verdict is process-sticky: later supervised work
        # starts on the fallback directly
        assert resilience.runtime_wedged()

    @pytest.mark.chaos
    def test_wedge_on_fallback_gives_up_visibly(self):
        h = simulate_register_history(60, n_procs=3, n_vals=4, seed=2)
        p, kernel = _packed(h)

        def always_wedge(ctx):
            raise WedgeError("wedged everywhere")

        resilience._inject_fault = always_wedge
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                r = supervised_check_packed(
                    p, kernel, capacity=32, expand=4, segment_iters=8)
        finally:
            resilience._inject_fault = None
        assert r["valid"] is UNKNOWN
        assert "wedged" in r["error"]

    def test_real_watchdog_fires_on_hung_segment(self, monkeypatch):
        """A device executable that genuinely blocks past its deadline is
        abandoned by the REAL watchdog thread in _call_segment and
        classified as a wedge; the checkpoint completes on the CPU
        fallback."""
        from jepsen_tpu.checker import tpu as T
        h = simulate_register_history(60, n_procs=3, n_vals=4, seed=2)
        p, kernel = _packed(h)
        real_jit = T._jit_segment
        hung = []
        release = threading.Event()

        def hanging_jit(*a, **kw):
            fn = real_jit(*a, **kw)

            def wrapped(*args):
                if not hung:
                    hung.append(1)
                    release.wait(20)  # wedge the first device call
                    raise RuntimeError("hung call released at teardown")
                return fn(*args)

            return wrapped

        monkeypatch.setattr(T, "_jit_segment", hanging_jit)
        try:
            with pytest.warns(RuntimeWarning, match="execution wedged"):
                r = supervised_check_packed(
                    p, kernel, capacity=32, expand=4, segment_iters=8,
                    deadline_s=0.2)
        finally:
            release.set()  # free the abandoned watchdog thread
        assert hung, "the hang must actually have been exercised"
        assert r["valid"] in (True, False)
        assert r["backend-fallback"] == "cpu"


class TestInjectedTransient:
    @pytest.mark.chaos
    def test_transient_retries_with_jitter_then_succeeds(self):
        h = simulate_register_history(80, n_procs=4, n_vals=4, seed=5)
        p, kernel = _packed(h)
        base = supervised_check_packed(p, kernel, capacity=64, expand=8,
                                       segment_iters=8)
        flakes = []

        def flaky(ctx):
            if ctx["segment"] == 1 and len(flakes) < 2:
                flakes.append(1)
                raise ConnectionResetError("transient RPC reset")

        resilience._inject_fault = flaky
        try:
            r = supervised_check_packed(
                p, kernel, capacity=64, expand=8, segment_iters=8,
                policy=fast_policy(max_retries=3))
        finally:
            resilience._inject_fault = None
        assert r["valid"] == base["valid"]
        assert r["levels"] == base["levels"]
        retries = [a for a in r["attempts"]
                   if a.get("event") == TRANSIENT]
        assert len(retries) == 2

    def test_transient_retries_exhausted_raises_with_trail(self):
        h = simulate_register_history(60, n_procs=3, n_vals=4, seed=5)
        p, kernel = _packed(h)

        def always_flaky(ctx):
            raise TimeoutError("endpoint never answers")

        resilience._inject_fault = always_flaky
        try:
            with pytest.raises(TimeoutError) as ei:
                supervised_check_packed(
                    p, kernel, capacity=32, expand=4, segment_iters=8,
                    policy=fast_policy(max_retries=2))
        finally:
            resilience._inject_fault = None
        trail = ei.value.resilience_trail
        assert [a["outcome"] for a in trail] == \
            ["retry-1", "retry-2", "retries-exhausted"]


class TestLocalKVHistories:
    """Checkpoint/resume on histories produced by the REAL localkv
    harness run (daemons, sockets, SIGSTOP nemesis) — the workload the
    resilient checker exists to serve."""

    @pytest.fixture(scope="class")
    def localkv_history(self):
        from jepsen_tpu import core
        from jepsen_tpu.suites.localkv import localkv_test
        test = localkv_test({"time-limit": 3, "nemesis-period": 1.0})
        test["store-dir"] = None
        test["checker"] = None
        out = core.run(test)
        h = out["history"]
        assert len(h) > 20, "localkv run produced no meaningful history"
        return h

    def test_checkpoint_resume_equals_uninterrupted(self, localkv_history):
        p, kernel = _packed(localkv_history)
        cps = []
        uninterrupted = supervised_check_packed(
            p, kernel, segment_iters=4, on_checkpoint=cps.append)
        oracle = check_packed(p, kernel)
        assert uninterrupted["valid"] == oracle["valid"]
        if not cps:
            pytest.skip("search finished inside one segment")
        for cp in (cps[0], cps[len(cps) // 2]):
            resumed = supervised_check_packed(
                p, kernel, segment_iters=4, resume=cp)
            assert resumed["valid"] == uninterrupted["valid"]
            assert resumed["levels"] == uninterrupted["levels"]

    @pytest.mark.chaos
    def test_kill_and_resume_on_real_history(self, localkv_history):
        p, kernel = _packed(localkv_history)
        base = supervised_check_packed(p, kernel, segment_iters=4)
        if base["segments"] < 3:
            pytest.skip("history too short to kill mid-run")
        cps = []

        def killer(ctx):
            if ctx["segment"] == 2:
                raise ValueError("killed mid-run")

        resilience._inject_fault = killer
        try:
            with pytest.raises(ValueError):
                supervised_check_packed(
                    p, kernel, segment_iters=4,
                    policy=fast_policy(max_retries=0),
                    on_checkpoint=cps.append)
        finally:
            resilience._inject_fault = None
        resumed = supervised_check_packed(p, kernel, segment_iters=4,
                                          resume=cps[-1])
        assert resumed["valid"] == base["valid"]
        assert resumed["levels"] == base["levels"]


class TestBoundedClientOps:
    @pytest.mark.chaos
    def test_hung_client_yields_info_and_reincarnates(self):
        """with_op_timeout end to end: one op hangs forever; the worker
        records :info and reincarnates instead of stalling the run."""
        from jepsen_tpu import core, generator as gen
        from jepsen_tpu.testing import (
            AtomClient, SharedRegister, atom_test)

        class HangingClient(AtomClient):
            invocations = [0]
            hangs = [0]

            def open(self, test, node):
                return HangingClient(self.register)

            def invoke(self, test, op):
                # deterministic early hang: the 3rd invocation overall
                # blocks forever, while plenty of generator budget
                # remains for the reincarnated process to act
                with lock:
                    HangingClient.invocations[0] += 1
                    me = HangingClient.invocations[0]
                if me == 3 and not HangingClient.hangs[0]:
                    HangingClient.hangs[0] = 1
                    threading.Event().wait(60)  # a truly stuck call
                return super().invoke(test, op)

        lock = threading.Lock()

        reg = SharedRegister()
        t = atom_test(reg)
        t["client"] = HangingClient(reg)
        t["op-timeout"] = 0.3
        t["store-dir"] = None
        # staggered so the generator still has ops to hand out after the
        # 0.3s hang detection — the reincarnated process must get to act
        t["generator"] = gen.clients(
            gen.stagger(0.02, gen.limit(150, gen.cas_gen())))
        t0 = time.time()
        t = core.run(t)
        assert time.time() - t0 < 30, "hung op must not stall the run"
        assert HangingClient.hangs[0] == 1
        h = t["history"]
        infos = [o for o in h
                 if o.is_info and o.process != "nemesis"
                 and o.error and "OpTimeout" in str(o.error)]
        assert infos, "the hung op must surface as an info op"
        # reincarnation: the abandoned logical process never acts again,
        # its thread continues as p + concurrency
        dead = infos[0].process
        later = [o for o in h if o.index > infos[0].index]
        assert all(o.process != dead for o in later)
        assert any(isinstance(o.process, int)
                   and o.process >= t["concurrency"] for o in h)

    def test_with_op_timeout_passthrough_and_raise(self):
        from jepsen_tpu.core import OpTimeout, with_op_timeout
        assert with_op_timeout(5.0, lambda: 42) == 42
        with pytest.raises(OpTimeout, match="op-timeout"):
            with_op_timeout(0.05, lambda: time.sleep(10))
        # exceptions pass through unmangled
        with pytest.raises(KeyError):
            with_op_timeout(5.0, lambda: {}["missing"])


class TestNemesisWedgeAccounting:
    @pytest.mark.chaos
    def test_wedged_nemesis_recorded_and_net_healed(self):
        from jepsen_tpu import core, generator as gen
        from jepsen_tpu.history import NEMESIS
        from jepsen_tpu.testing import atom_test

        release = threading.Event()

        class StuckNemesis:
            def setup(self, test):
                return self

            def invoke(self, test, op):
                release.wait(60)  # wedged mid-invocation
                return op

            def teardown(self, test):
                teardowns.append(1)

        class RecordingNet:
            def __init__(self):
                self.healed = 0

            def heal(self, test):
                self.healed += 1

        teardowns = []
        net = RecordingNet()
        t = atom_test()
        t["nemesis"] = StuckNemesis()
        t["net"] = net
        t["store-dir"] = None
        t["nemesis-join-timeout"] = 0.5
        t["generator"] = gen.Any_([
            gen.nemesis(gen.limit(1, gen.start_stop(0, 0))),
            gen.clients(gen.limit(10, gen.cas_gen())),
        ])
        try:
            t = core.run(t)
        finally:
            release.set()
        wedge_ops = [o for o in t["history"]
                     if o.process == NEMESIS and o.f == "nemesis-wedged"]
        assert len(wedge_ops) == 1
        assert "join timeout" in str(wedge_ops[0].error)
        assert teardowns, "teardown must still run for a wedged nemesis"
        assert net.healed >= 1, "net.heal must run in the safety net"

    def test_worker_crash_still_heals_and_tears_down(self):
        from jepsen_tpu import core, generator as gen
        from jepsen_tpu.history import Op
        from jepsen_tpu.testing import atom_test

        class BoomGen(gen.Generator):
            """Hands out a few reads, then blows up the workers."""

            def __init__(self):
                self.n = 0
                self.lock = threading.Lock()

            def op(self, test, process):
                with self.lock:
                    self.n += 1
                    if self.n > 5:
                        raise RuntimeError("generator exploded mid-phase")
                return Op(type="invoke", f="read", value=None)

        class RecordingNet:
            def __init__(self):
                self.healed = 0

            def heal(self, test):
                self.healed += 1

        torn = []

        class Nem:
            def setup(self, test):
                return self

            def invoke(self, test, op):
                return op

            def teardown(self, test):
                torn.append(1)

        net = RecordingNet()
        t = atom_test()
        t["nemesis"] = Nem()
        t["net"] = net
        t["store-dir"] = None
        t["generator"] = gen.clients(BoomGen())
        with pytest.raises(RuntimeError, match="exploded"):
            core.run(t)
        assert torn, "nemesis teardown must run when a worker raises"
        assert net.healed >= 1, "net.heal must run when a worker raises"
