"""The search-plan verifier (jepsen_tpu.checker.plan +
jepsen_tpu.analysis.plan_lint): bucket enumeration is exhaustive and
deterministic, abstract evaluation performs ZERO XLA compiles and zero
device executions (asserted via a backend_compile-counting hook),
footprint math matches the real packed arrays byte for byte, the
mandatory pre-search gate rejects oversized / indivisible / overflowing
plans with the right PLAN-* rule before any jit factory is touched, the
JTPU_PLAN_GATE=0 kill switch restores identical verdicts and leaves
history artifacts untouched, and CPU-only degradation is graceful. All
tier-1 (marker: plan)."""

import contextlib
import io
import json
import os
import types

import numpy as np
import pytest

from jepsen_tpu import cli
from jepsen_tpu.analysis import plan_lint
from jepsen_tpu.analysis.plan_lint import PlanRejectedError
from jepsen_tpu.checker import plan as plan_mod
from jepsen_tpu.checker import tpu as T
from jepsen_tpu.checker.plan import Candidate, PlanDims
from jepsen_tpu.history import History
from jepsen_tpu.models import CASRegister
from jepsen_tpu.models.core import kernel_spec_for
from jepsen_tpu.ops.encode import pack_with_init
from jepsen_tpu.testing import simulate_register_history

pytestmark = pytest.mark.plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "fixtures", "plan")


def _history(n=120, seed=3, crash_p=0.02):
    return simulate_register_history(n, n_procs=5, n_vals=4, seed=seed,
                                     crash_p=crash_p)


def _rules(report):
    return sorted({i["rule"] for i in report["issues"]})


@pytest.fixture
def no_limit(monkeypatch):
    monkeypatch.delenv("JTPU_PLAN_BYTES_LIMIT", raising=False)


# ---------------------------------------------------------------------------
# Bucket enumeration
# ---------------------------------------------------------------------------

class TestEnumeration:
    def test_exhaustive_and_deterministic(self):
        dims = PlanDims(n_required=150, n_crashed=3, window_needed=5)
        a = plan_mod.enumerate_candidates(dims)
        b = plan_mod.enumerate_candidates(dims)
        assert a == b
        # the one single-history executable kind, every ladder rung,
        # nothing else
        ladder = T._ladder_for(5)
        assert [c.rung for c in a] == list(ladder)
        assert {c.kind for c in a} == {"segment"}
        # buckets are the real padded widths
        assert all(c.breq == T._bucket(150) for c in a)
        assert all(c.crw == T._crash_width(3) for c in a)

    def test_explicit_rung_collapses_universe(self):
        dims = PlanDims(n_required=150, window_needed=5)
        cands = plan_mod.enumerate_candidates(dims, capacity=256,
                                              window=64, expand=16)
        assert [c.rung for c in cands] == [(256, 64, 16)]

    def test_keyed_dims_enumerate_batch_ladder(self):
        dims = PlanDims(n_required=500, n_crashed=0, window_needed=8,
                        keys=16)
        cands = plan_mod.enumerate_candidates(dims)
        assert {c.kind for c in cands} == {"batch"}
        assert all(c.keys == 16 for c in cands)
        # the adaptive keyed schedule: slim entry rung (hash tie-break)
        # then the dense double-expansion rung
        assert cands[0].tiebreak == "hash"
        assert cands[1].expand >= cands[0].expand * 2

    def test_mesh_axis_adds_sharded_candidate(self):
        dims = PlanDims(n_required=200, window_needed=8)
        cands = plan_mod.enumerate_candidates(dims, mesh_axis=4)
        sh = [c for c in cands if c.kind == "sharded"]
        assert len(sh) == 1 and sh[0].mesh_axis == 4
        # sharded default expand is rounded up to the mesh axis
        assert sh[0].expand % 4 == 0

    def test_crash_overflow_yields_no_candidates(self):
        dims = PlanDims(n_required=100, n_crashed=T.CRASH_MAX + 1,
                        window_needed=4)
        assert plan_mod.enumerate_candidates(dims) == []
        issues = plan_mod.check_dims(dims)
        assert "PLAN-CRASH-WIDTH" in {i.rule for i in issues}


# ---------------------------------------------------------------------------
# Footprint math
# ---------------------------------------------------------------------------

class TestFootprint:
    @pytest.mark.parametrize("n,crash_p", [(80, 0.0), (150, 0.05),
                                           (400, 0.02)])
    def test_cols_bytes_match_real_packed_history(self, n, crash_p):
        p, kernel = pack_with_init(_history(n, crash_p=crash_p),
                                   CASRegister())
        breq = T._bucket(p.n_required)
        crw = T._crash_width(p.n - p.n_required)
        cols = T._split_packed(p, breq, crw, kernel)
        assert plan_mod.cols_nbytes(breq, crw) == T._cols_nbytes(cols)

    def test_carry_bytes_match_carry0_host(self):
        for cap, win, crw in ((32, 32, 0), (128, 64, 8), (1024, 128, 96)):
            carry = T._carry0_host(cap, win, crw, np.int32(0), 10)
            real = sum(int(np.asarray(x).nbytes) for x in carry)
            assert plan_mod.carry_nbytes(cap, win, crw) == real

    def test_footprint_monotone_in_capacity(self):
        dims_args = dict(kind="segment", window=32, expand=8, unroll=1,
                         breq=1024, crw=16)
        sizes = [plan_mod.footprint(Candidate(capacity=c, **dims_args)
                                    )["total-bytes"]
                 for c in (64, 256, 1024, 4096)]
        assert sizes == sorted(sizes) and sizes[0] < sizes[-1]

    def test_sharded_per_device_share(self):
        c = Candidate(kind="sharded", capacity=4096, window=32,
                      expand=512, unroll=1, breq=1024, crw=0,
                      mesh_axis=8)
        fp = plan_mod.footprint(c)
        assert fp["per-device-bytes"] < fp["total-bytes"]

    def test_sharded_footprint_counts_the_carry_as_input(self):
        # a sharded search runs the segment executable, which takes the
        # carry in as well as giving it back: same bytes as a segment
        shape = dict(capacity=4096, window=32, expand=512, unroll=1,
                     breq=1024, crw=8)
        seg = plan_mod.footprint(Candidate(kind="segment", **shape))
        sh = plan_mod.footprint(Candidate(kind="sharded", mesh_axis=8,
                                          **shape))
        assert sh["total-bytes"] == seg["total-bytes"]
        assert (sh["total-bytes"] - sh["cols-bytes"] - sh["grid-bytes"]
                - sh["sort-bytes"]) == 3 * sh["carry-bytes"]


# ---------------------------------------------------------------------------
# Arithmetic checks
# ---------------------------------------------------------------------------

class TestChecks:
    def test_oom_fires_against_byte_limit(self, no_limit):
        dims = PlanDims(n_required=150, n_crashed=3, window_needed=5)
        rep = plan_mod.analyze(dims, bytes_limit=10_000)
        assert rep["selected"] is None
        assert _rules(rep) == ["PLAN-OOM"]

    def test_cheapest_valid_plan_wins_between_limits(self, no_limit):
        dims = PlanDims(n_required=150, n_crashed=3, window_needed=5)
        # a budget that admits the small rungs but rejects the big ones
        rep = plan_mod.analyze(dims, bytes_limit=1_000_000)
        assert rep["selected"] is not None
        statuses = {c["label"]: c["status"] for c in rep["candidates"]}
        assert statuses[rep["selected"]] == "ok"
        assert "rejected" in statuses.values()
        # the selected plan is the FIRST ok candidate (cheapest rung)
        first_ok = next(c["label"] for c in rep["candidates"]
                        if c["status"] == "ok")
        assert rep["selected"] == first_ok

    def test_shard_indivisible_and_skew(self):
        dims = PlanDims(n_required=200, window_needed=8)
        rep = plan_mod.analyze(dims, mesh_axis=3, capacity=128,
                               expand=10, kinds=("sharded",))
        assert "PLAN-SHARD-INDIVISIBLE" in _rules(rep)
        rep2 = plan_mod.analyze(dims, mesh_axis=8, capacity=256,
                                expand=8, kinds=("sharded",))
        assert "PLAN-SHARD-SKEW" in _rules(rep2)
        assert rep2["selected"] is not None  # a warning does not reject

    def test_int32_overflow_dims(self):
        rep = plan_mod.analyze(PlanDims(n_required=2 ** 30,
                                        window_needed=4))
        assert "PLAN-INT32-OVERFLOW" in _rules(rep)
        assert rep["selected"] is None

    def test_window_rules(self):
        dims = PlanDims(n_required=100, window_needed=4)
        rep = plan_mod.analyze(dims, capacity=64, window=256, expand=8)
        assert "PLAN-WINDOW" in _rules(rep)
        wide = plan_mod.analyze(PlanDims(n_required=100,
                                         window_needed=300))
        assert "PLAN-WINDOW-UNBOUNDED" in _rules(wide)
        # unbounded window is a warning: witness-hunt rungs still run
        assert wide["selected"] is not None

    def test_cpu_degrades_gracefully(self, no_limit):
        # no memory stats on CPU: no byte budget, PLAN-OOM cannot fire
        assert plan_mod.plan_bytes_limit() is None
        dims = PlanDims(n_required=150, n_crashed=3, window_needed=5)
        rep = plan_mod.analyze(dims)
        assert rep["bytes-limit"] is None
        assert "PLAN-OOM" not in _rules(rep)
        assert rep["selected"] is not None


# ---------------------------------------------------------------------------
# Abstract evaluation: zero compiles, zero executions
# ---------------------------------------------------------------------------

class TestZeroCompile:
    def test_trace_performs_no_compile_and_no_execution(self,
                                                        monkeypatch):
        import jax
        import jax._src.compiler as jcompiler
        compiles = []
        real = jcompiler.backend_compile

        def spy(*a, **k):
            compiles.append(1)
            return real(*a, **k)

        monkeypatch.setattr(jcompiler, "backend_compile", spy)
        # explicit .compile() after lower() must also be impossible
        monkeypatch.setattr(
            jax.stages.Lowered, "compile",
            lambda self, *a, **k: (_ for _ in ()).throw(
                AssertionError("plan analysis called Lowered.compile")))
        plan_mod._TRACE_MEMO.clear()
        dims = PlanDims(n_required=100, n_crashed=4, window_needed=6)
        kernel = kernel_spec_for(CASRegister())
        rep = plan_mod.analyze(dims, kernel=kernel, trace=True,
                               cost=True)
        assert compiles == []
        assert rep["selected"] is not None
        traced = [c for c in rep["candidates"] if "traced" in c]
        assert traced and all(c["traced"] for c in traced)
        # the lower()-only cost analysis priced the buckets
        assert any(c.get("cost", {}).get("flops", 0) > 0
                   for c in rep["candidates"])

    def test_sharded_candidate_traces_the_pool_axis_segment(
            self, monkeypatch):
        import jax
        from jax.sharding import Mesh
        seen = []
        real = T._jit_segment

        def spy(*a, **k):
            seen.append(a[5:6])
            return real(*a, **k)

        monkeypatch.setattr(T, "_jit_segment", spy)
        plan_mod._TRACE_MEMO.clear()
        mesh = Mesh(np.array(jax.devices()[:2]), (T.POOL_AXIS,))
        rep = plan_mod.analyze(PlanDims(n_required=100, window_needed=6),
                               kernel=kernel_spec_for(CASRegister()),
                               mesh=mesh, trace=True, kinds=("sharded",))
        (row,) = rep["candidates"]
        assert row["kind"] == "sharded" and row["traced"] is True
        assert seen == [(T.POOL_AXIS,)]

    def test_trace_memoized_per_bucket(self):
        plan_mod._TRACE_MEMO.clear()
        dims = PlanDims(n_required=64, window_needed=4)
        kernel = kernel_spec_for(CASRegister())
        plan_mod.analyze(dims, kernel=kernel, trace=True)
        n1 = len(plan_mod._TRACE_MEMO)
        plan_mod.analyze(dims, kernel=kernel, trace=True)
        assert len(plan_mod._TRACE_MEMO) == n1

    def test_broken_kernel_bucket_is_a_trace_finding(self):
        # a kernel whose step does not broadcast state over the op grid
        # (the shape bug the matrix caught in the real noop kernel)
        from jepsen_tpu.models.core import KernelSpec
        broken = KernelSpec(name="broken", init_state=0,
                            step=lambda s, f, v1, v2: (s, f == f),
                            f_codes={})
        dims = PlanDims(n_required=64, window_needed=4)
        rep = plan_mod.analyze(dims, kernel=broken, trace=True)
        assert "PLAN-TRACE" in _rules(rep)
        assert rep["selected"] is None


# ---------------------------------------------------------------------------
# The pre-search gate
# ---------------------------------------------------------------------------

class TestGate:
    def _forbid_jit(self, monkeypatch):
        fired = []

        def bomb(name):
            def f(*a, **k):
                fired.append(name)
                raise AssertionError(f"{name} invoked")
            return f

        for name in ("_jit_segment", "_jit_batch", "_jit_batch_segment"):
            monkeypatch.setattr(T, name, bomb(name))
        return fired

    def test_oversized_capacity_rejected_before_jit(self, monkeypatch):
        monkeypatch.setenv("JTPU_PLAN_BYTES_LIMIT", "200000")
        fired = self._forbid_jit(monkeypatch)
        with pytest.raises(PlanRejectedError) as ei:
            T.check_history_tpu(_history(), CASRegister(),
                                capacity=16384, window=32)
        assert "PLAN-OOM" in str(ei.value)
        assert fired == []
        assert any(f.rule == "PLAN-OOM" for f in ei.value.findings)

    def test_monolithic_path_gated_too(self, monkeypatch):
        # one monolithic segment covering the whole search, and the
        # shortest segments, meet the same gate
        monkeypatch.setenv("JTPU_PLAN_BYTES_LIMIT", "200000")
        fired = self._forbid_jit(monkeypatch)
        for seg in (T.DEFAULT_SEGMENT_ITERS, 1):
            with pytest.raises(PlanRejectedError):
                T.check_history_tpu(_history(), CASRegister(),
                                    capacity=16384, window=32,
                                    segment_iters=seg)
        assert fired == []

    def test_indivisible_mesh_rejected_before_jit(self, monkeypatch):
        fired = self._forbid_jit(monkeypatch)
        mesh = types.SimpleNamespace(shape={T.POOL_AXIS: 3})
        with pytest.raises(PlanRejectedError) as ei:
            T.check_history_sharded(_history(), CASRegister(), mesh,
                                    capacity=128, expand=10)
        assert "PLAN-SHARD-INDIVISIBLE" in str(ei.value)
        assert fired == []

    def test_int32_overflow_rejected_before_jit(self, monkeypatch):
        fired = self._forbid_jit(monkeypatch)
        packed, kernel = pack_with_init(_history(), CASRegister())
        dims = PlanDims(n_required=2 ** 30, window_needed=4)
        with pytest.raises(PlanRejectedError) as ei:
            plan_mod.gate_ladder(dims, kernel, ((64, 32, 8),),
                                 kind="segment", explicit=True)
        assert "PLAN-INT32-OVERFLOW" in str(ei.value)
        assert fired == []

    def test_gate_filters_to_cheapest_valid_rung(self, monkeypatch,
                                                 no_limit):
        # gate_ladder without derate (as the keyed batch calls it)
        # drops the rungs that cannot fit and selects the cheapest fit
        monkeypatch.setenv("JTPU_PLAN_BYTES_LIMIT", "1000000")
        p, kernel = pack_with_init(_history(), CASRegister())
        ladder = T._ladder_for(T._window_needed(p))
        kept, plan = plan_mod.gate_ladder(p, kernel, ladder,
                                          kind="segment", explicit=False)
        assert kept == ladder[:len(kept)] and 0 < len(kept) < len(ladder)
        cap, win, exp = kept[0]
        assert plan["selected"].startswith(f"segment {cap}/{win}/{exp} ")
        assert plan["rejected"]  # the big rungs could not fit 1 MB
        assert all("PLAN-OOM" in c["rules"] for c in plan["rejected"])

    def test_supervised_seeds_pool_from_footprint(self, monkeypatch):
        monkeypatch.setenv("JTPU_PLAN_BYTES_LIMIT", "18000")
        r = T.check_history_tpu(_history(), CASRegister())
        assert r["valid"] is True
        seeds = [a for a in r["attempts"]
                 if str(a.get("outcome", "")).startswith(
                     "plan-seeded-pool-")]
        assert seeds and seeds[0]["predicted-bytes"] <= 18000
        assert r["rung"][0] < T._capacity_ladder()[0][0] or \
            r["rung"][0] < 32

    def test_kill_switch_restores_identical_verdicts(self, monkeypatch,
                                                     no_limit):
        h = _history()
        r_on = T.check_history_tpu(h, CASRegister())
        monkeypatch.setenv("JTPU_PLAN_GATE", "0")
        r_off = T.check_history_tpu(h, CASRegister())
        assert "plan" in r_on and "plan" not in r_off

        def stable(r):
            # everything search-semantic; host-measured wall clocks
            # ("device-s") legitimately vary run to run
            r = dict(r)
            r.pop("plan", None)
            r.pop("device-s", None)
            return r

        assert stable(r_on) == stable(r_off)

    def test_gate_leaves_history_artifact_byte_identical(
            self, monkeypatch, tmp_path, no_limit):
        # the gate runs in the CHECKER; the recorded history artifact
        # must not change in any way between gate-on and gate-off
        src = os.path.join(REPO, "tests", "fixtures", "lint",
                           "good_history.jsonl")
        art = tmp_path / "history.jsonl"
        art.write_bytes(open(src, "rb").read())
        before = art.read_bytes()
        h = History.from_jsonl(art.read_text())
        v_on = T.check_history_tpu(h, CASRegister())["valid"]
        assert art.read_bytes() == before
        monkeypatch.setenv("JTPU_PLAN_GATE", "0")
        v_off = T.check_history_tpu(h, CASRegister())["valid"]
        assert v_on is True and v_off is True
        assert art.read_bytes() == before

    def test_keyed_gate_attaches_plan_entry(self, no_limit):
        keyed = {k: _history(60, seed=k, crash_p=0.0) for k in range(3)}
        r = T.check_keyed_tpu(keyed, CASRegister())
        assert r["valid"] is True
        assert r["plan"]["selected"].startswith("batch ")

    def test_sharded_gate_passes_divisible_mesh(self, no_limit):
        import jax
        from jax.sharding import Mesh
        devs = np.array(jax.devices()[:2])
        mesh = Mesh(devs, (T.POOL_AXIS,))
        r = T.check_history_sharded(_history(80, crash_p=0.0),
                                    CASRegister(), mesh,
                                    capacity=64, expand=8)
        assert r["valid"] is True
        assert r["plan"]["selected"].startswith("sharded ")


# ---------------------------------------------------------------------------
# The plan lint pass + fixture matrix
# ---------------------------------------------------------------------------

class TestMatrix:
    def test_pinned_matrix_is_clean_arithmetically(self, no_limit):
        fs = plan_lint.lint_matrix()
        assert [f for f in fs if f.severity == "error"] == []

    def test_pinned_matrix_traces_clean_in_budget(self, no_limit):
        import time
        plan_mod._TRACE_MEMO.clear()
        t0 = time.time()
        fs = plan_lint.lint_matrix(trace=True)
        wall = time.time() - t0
        assert [f for f in fs if f.severity == "error"] == []
        assert wall < 30, f"full bucket-universe trace took {wall:.1f}s"

    def test_matrix_runs_inside_repo_lint(self, no_limit):
        from jepsen_tpu import analysis
        fs = analysis.lint_repo(passes=("plan",))
        assert [f for f in fs if f.severity == "error"] == []

    def test_findings_from_report_rules_and_anchors(self):
        rep = plan_mod.analyze(PlanDims(n_required=150, n_crashed=3,
                                        window_needed=5),
                               bytes_limit=10_000)
        fs = plan_lint.findings_from_report(rep)
        assert fs and all(f.rule == "PLAN-OOM" for f in fs)
        assert all(f.anchor.endswith("/PLAN-OOM") for f in fs)


# ---------------------------------------------------------------------------
# CLI + SARIF
# ---------------------------------------------------------------------------

def _run_cli(args):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.run(cli.default_commands(), args)
    return rc, buf.getvalue()


class TestCLI:
    def test_good_dims_fixture_passes(self, no_limit):
        rc, out = _run_cli(["plan", "--dims",
                            "@" + os.path.join(FIX, "dims_good.json"),
                            "--no-trace"])
        assert rc == cli.OK
        assert "# plan: selected" in out and "REJ" not in out

    def test_oom_fixture_rejected(self, no_limit):
        rc, out = _run_cli(["plan", "--dims",
                            "@" + os.path.join(FIX, "dims_oom.json"),
                            "--no-trace", "--format", "json"])
        assert rc == cli.TEST_FAILED
        rep = json.loads(out)
        assert "PLAN-OOM" in {i["rule"] for i in rep["issues"]}
        assert rep["selected"] is None

    def test_mesh_fixture_rejected(self, no_limit):
        rc, out = _run_cli(
            ["plan", "--dims",
             "@" + os.path.join(FIX, "dims_mesh_indivisible.json"),
             "--no-trace"])
        assert rc == cli.TEST_FAILED
        assert "PLAN-SHARD-INDIVISIBLE" in out

    def test_int32_fixture_rejected(self, no_limit):
        rc, out = _run_cli(
            ["plan", "--dims",
             "@" + os.path.join(FIX, "dims_int32_overflow.json"),
             "--no-trace"])
        assert rc == cli.TEST_FAILED
        assert "PLAN-INT32-OVERFLOW" in out

    def test_cli_traced_run_zero_compiles(self, monkeypatch, no_limit):
        import jax._src.compiler as jcompiler
        compiles = []
        real = jcompiler.backend_compile
        monkeypatch.setattr(
            jcompiler, "backend_compile",
            lambda *a, **k: compiles.append(1) or real(*a, **k))
        plan_mod._TRACE_MEMO.clear()
        rc, out = _run_cli(["plan", "--dims", "100,2,6"])
        assert rc == cli.OK and compiles == []
        assert "MFLOP/level" in out

    def test_history_input(self, no_limit):
        src = os.path.join(REPO, "tests", "fixtures", "lint",
                           "good_history.jsonl")
        rc, out = _run_cli(["plan", "--history", src, "--no-trace"])
        assert rc == cli.OK and "# plan: selected" in out

    def test_sarif_output_is_valid(self, no_limit):
        rc, out = _run_cli(
            ["plan", "--dims",
             "@" + os.path.join(FIX, "dims_mesh_indivisible.json"),
             "--no-trace", "--format", "sarif"])
        assert rc == cli.TEST_FAILED
        doc = json.loads(out)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert {r["id"] for r in run["tool"]["driver"]["rules"]} == \
            {"PLAN-SHARD-INDIVISIBLE"}
        res = run["results"][0]
        assert res["level"] == "error"
        assert res["partialFingerprints"]["jtpuAnchor/v1"]

    def test_lint_sarif_format(self, no_limit):
        rc, out = _run_cli(["lint", "--format", "sarif"])
        assert rc == cli.OK
        doc = json.loads(out)
        assert doc["version"] == "2.1.0"
        assert doc["runs"][0]["results"] == []

    def test_summary_line_in_analyze_path(self, no_limit):
        line = plan_mod.summary_line(_history(), CASRegister())
        assert line.startswith("# plan:")
        assert "cheapest" in line and "limit n/a" in line
