"""Mesh/multi-host helpers (jepsen_tpu.parallel) on the virtual 8-device
CPU mesh from conftest."""

import random

import pytest

from jepsen_tpu import parallel
from jepsen_tpu.models import CASRegister

from test_checker_tpu import random_register_history


class TestMesh:
    def test_make_mesh_all_devices(self):
        mesh = parallel.make_mesh()
        assert dict(mesh.shape) == {"keys": parallel.device_count()}

    def test_make_mesh_subset_and_overflow(self):
        import pytest
        mesh = parallel.make_mesh(4)
        assert dict(mesh.shape) == {"keys": 4}
        with pytest.raises(ValueError):
            parallel.make_mesh(parallel.device_count() + 1)

    def test_shardings(self):
        mesh = parallel.make_mesh(2)
        s = parallel.keyed_sharding(mesh)
        assert s.spec == ("keys",) or tuple(s.spec) == ("keys",)
        r = parallel.replicated_sharding(mesh)
        assert tuple(r.spec) == ()


class TestMultihost:
    def test_initialize_skips_without_coordinator(self, monkeypatch):
        monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
        monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
        assert parallel.initialize_multihost() is False


class TestDistributedCheck:
    def test_keyed_check_over_auto_mesh(self):
        rng = random.Random(5)
        keyed = {k: random_register_history(rng, n_procs=3, n_ops=8,
                                            n_vals=3)
                 for k in range(16)}
        out = parallel.check_keyed_distributed(keyed, CASRegister())
        assert out["backend"] == "tpu"
        assert set(out["results"]) == set(keyed)
        assert out["valid"] in (True, False)

    def test_keyed_mesh_routing_uneven_escalation(self):
        """The dryrun_multichip hardening, under CI: uneven key count
        (padding rows), a non-linearizable key whose False verdict must
        land on exactly that key, and a key only the escalated rung can
        refute — all on the 8-device mesh."""
        import __graft_entry__ as g
        from jepsen_tpu.checker.tpu import check_keyed_tpu
        mesh = parallel.make_mesh(8)
        keyed = {k: random_register_history(random.Random(40 + k),
                                            n_procs=3, n_ops=6, n_vals=3)
                 for k in range(11)}   # 11 + 2 = 13: pads to 16 on 8 devs
        keyed["invalid"] = g._stale_read_history()
        keyed["escalates"] = g._pool_buster_history()
        out = check_keyed_tpu(keyed, CASRegister(), mesh=mesh,
                              ladder=((8, 16, 4), (256, 16, 64)))
        res = out["results"]
        assert res["invalid"]["valid"] is False
        assert res["escalates"]["valid"] is False
        assert out["valid"] is False
        assert len(res) == 13

    def test_pool_buster_unknown_on_slim_rung_alone(self):
        import __graft_entry__ as g
        from jepsen_tpu.checker import UNKNOWN
        from jepsen_tpu.checker.tpu import check_keyed_tpu
        out = check_keyed_tpu({"k": g._pool_buster_history()},
                              CASRegister(), ladder=((8, 16, 4),))
        assert out["results"]["k"]["valid"] is UNKNOWN
        assert out["results"]["k"]["capacity-overflow"] is True


class TestPoolSharded:
    """Single-history scale-out: one search's pool partitioned over the
    mesh (the frontier-parallel WGL of SURVEY §2.5), vs the per-key
    data parallelism of check_keyed_tpu."""

    def _mesh(self):
        from jepsen_tpu.checker.tpu import POOL_AXIS
        return parallel.make_mesh(axis=POOL_AXIS)

    def test_matches_unsharded_verdicts(self):
        from jepsen_tpu.checker import UNKNOWN
        from jepsen_tpu.checker.tpu import (check_history_sharded,
                                            check_history_tpu)
        mesh = self._mesh()
        rng = random.Random(23)
        n = 0
        for i in range(15):
            h = random_register_history(rng, n_procs=4, n_ops=10,
                                        n_vals=3, crash_p=0.1)
            want = check_history_tpu(h, CASRegister())["valid"]
            got = check_history_sharded(h, CASRegister(), mesh,
                                        capacity=64, expand=16)["valid"]
            if UNKNOWN in (want, got):
                continue
            n += 1
            assert got is want, (i, want, got)
        assert n > 8

    def test_refutation_carries_final_states(self):
        from jepsen_tpu.checker.tpu import check_history_sharded
        from jepsen_tpu.history import History, Op
        rows = [Op(type="invoke", f="write", value=1, process=0, time=0),
                Op(type="ok", f="write", value=1, process=0, time=1),
                Op(type="invoke", f="read", value=None, process=1,
                   time=2),
                Op(type="ok", f="read", value=9, process=1, time=3)]
        mesh = self._mesh()
        r = check_history_sharded(History.of(rows), CASRegister(),
                                  mesh, capacity=64, expand=8)
        assert r["valid"] is False
        assert r.get("final-states")
        from jepsen_tpu.checker.tpu import POOL_AXIS
        assert r["pool-sharding"] == f"pool={mesh.shape[POOL_AXIS]}"

    @pytest.mark.parametrize("refuted", [False, True],
                             ids=["valid", "refuted"])
    def test_default_is_segmented_and_matches_one_device(self, refuted):
        """With no segment_iters the pool-sharded search runs its
        checkpointed segments and answers as the one-device search of
        the same rung does: verdict, levels and refutation evidence."""
        from jepsen_tpu.checker.tpu import (POOL_AXIS, check_packed_sharded,
                                            check_packed_tpu)
        from jepsen_tpu.checker.wgl import check_packed
        from jepsen_tpu.history import History
        from jepsen_tpu.ops.encode import pack_with_init
        from jepsen_tpu.testing import simulate_register_history
        h = simulate_register_history(80, n_procs=4, n_vals=4, seed=3,
                                      crash_p=0.02)
        if refuted:
            rows = list(h)
            i = next(i for i, o in enumerate(rows)
                     if o.type == "ok" and o.f == "read"
                     and o.value is not None)
            rows[i] = rows[i].replace(value=99)
            h = History.of(rows)
        p, kernel = pack_with_init(h, CASRegister())
        mesh = parallel.make_mesh(2, axis=POOL_AXIS)
        kw = dict(capacity=64, window=32, expand=16)
        got = check_packed_sharded(p, kernel, mesh, **kw)
        one = check_packed_tpu(p, kernel, **kw)
        assert got["segments"] >= 1
        assert got["valid"] is one["valid"] is (not refuted)
        assert check_packed(p, kernel)["valid"] is (not refuted)
        for k in ("levels", "rung", "max-linearized-prefix",
                  "final-states"):
            assert got.get(k) == one.get(k), k

    def test_divisibility_enforced(self, monkeypatch):
        import pytest as _pytest
        from jepsen_tpu.analysis.plan_lint import PlanRejectedError
        from jepsen_tpu.checker.tpu import POOL_AXIS, check_history_sharded
        from jepsen_tpu.history import History, Op
        h = History.of([Op(type="invoke", f="write", value=1, process=0,
                           time=0),
                        Op(type="ok", f="write", value=1, process=0,
                           time=1)])
        mesh = self._mesh()
        naxis = mesh.shape[POOL_AXIS]
        if naxis == 1:
            _pytest.skip("1-device mesh: every capacity divides")
        # a capacity the mesh axis provably cannot divide, whatever the
        # ambient device count. The plan gate rejects it with a rule id
        # before any jit work (doc/plan.md)...
        with _pytest.raises(PlanRejectedError,
                            match="PLAN-SHARD-INDIVISIBLE"):
            check_history_sharded(h, CASRegister(), mesh,
                                  capacity=8 * naxis + 1)
        # ...and the legacy ValueError still guards the ungated path.
        monkeypatch.setenv("JTPU_PLAN_GATE", "0")
        with _pytest.raises(ValueError, match="divide"):
            check_history_sharded(h, CASRegister(), mesh,
                                  capacity=8 * naxis + 1)


#: Error-text markers meaning "this host/backend cannot run multi-
#: process computations at all" — a capability gap of the CI image
#: (single-host CPU jaxlibs refuse cross-process programs), not a
#: regression in the DCN seam under test.
_DCN_INCAPABLE_MARKERS = (
    "Multiprocess computations aren't implemented on the CPU backend",
    "multiprocess computations aren't implemented",
    "UNIMPLEMENTED",
    "distributed module is not available",
)


def _skip_if_dcn_incapable(exc: BaseException) -> None:
    """Skip (not fail) when the failure text says the backend cannot do
    multi-process execution — the proper capability guard for the
    two-process DCN test on single-host CPU CI."""
    import pytest
    text = f"{type(exc).__name__}: {exc}"
    if any(m in text for m in _DCN_INCAPABLE_MARKERS):
        pytest.skip("multi-process (DCN) computations unsupported on "
                    "this backend/host: " + text.splitlines()[-1][:200])


class TestDCN:
    def test_two_process_dcn_keyed_check(self):
        """Two OS processes join one JAX cluster over a localhost
        coordinator (the DCN seam) and run a keyed check sharded across
        both processes' devices — certifies parallel.py's multi-host
        claim (same jitted program SPMD per host). Skips, rather than
        fails, on hosts whose backend cannot run multi-process
        computations at all (single-host CPU CI images)."""
        import __graft_entry__ as g
        try:
            g.dryrun_dcn(n_procs=2, devices_per_proc=1)
        except RuntimeError as e:
            _skip_if_dcn_incapable(e)
            raise
