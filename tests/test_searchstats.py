"""Search analytics (doc/observability.md "Search analytics"): the
per-level counter lane the device search carries, its JTPU_TRACE=0
byte-identity, the searchstats.json rollups, the host-side contention
/ decomposability profiler, and the `jtpu explain` verdict reports."""

import json
import os

import numpy as np
import pytest

from jepsen_tpu import testing
from jepsen_tpu.checker import tpu as T
from jepsen_tpu.history import History, Op
from jepsen_tpu.models import CASRegister
from jepsen_tpu.obs import searchstats as obs_searchstats

pytestmark = pytest.mark.explain

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def H(*rows):
    return History.of([
        Op(type=t, f=f, value=v, process=p, time=i)
        for i, (p, t, f, v) in enumerate(rows)
    ])


def _hist(n=40, seed=2, procs=3, overlap=0.6):
    return testing.simulate_register_history(n, n_procs=procs,
                                             seed=seed,
                                             overlap_p=overlap)


# ---------------------------------------------------------------------------
# The counter lane (checker/tpu.py carry index 13)
# ---------------------------------------------------------------------------


class TestCounterLane:
    def test_cols_match_kernel(self):
        # obs/searchstats.py duplicates the column catalog so the obs
        # package stays jax-free; the two MUST agree or every rollup
        # silently misattributes
        assert obs_searchstats.COLS == T.SEARCHSTAT_COLS
        assert obs_searchstats.NSTAT == T.NSTAT == 5

    def test_counters_populate(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JTPU_TRACE", "1")
        obs_searchstats.attach(str(tmp_path))
        try:
            out = T.check_history_tpu(_hist(), CASRegister())
        finally:
            obs_searchstats.detach()
        assert out["valid"] is True
        ss = out["searchstats"]
        assert ss["levels"] == out["levels"]
        assert ss["expanded-total"] > 0
        assert ss["frontier-peak"] >= 1
        assert 0.0 <= ss["dup-rate"] <= 1.0
        doc = json.loads((tmp_path / "searchstats.json").read_text())
        assert doc["cols"] == list(T.SEARCHSTAT_COLS)
        assert len(doc["levels"]) == out["levels"]
        # every row is the NSTAT-wide int vector the kernel wrote
        assert all(len(r) == T.NSTAT for r in doc["levels"])

    def test_segmented_matches_monolithic_bitwise(self, monkeypatch,
                                                  tmp_path):
        # the acceptance bar: short segments and one monolithic segment
        # covering the whole search must write the SAME counters — the
        # lane rides the carry across segment barriers untouched
        monkeypatch.setenv("JTPU_TRACE", "1")
        h = _hist()
        d1, d2 = tmp_path / "mono", tmp_path / "seg"
        d1.mkdir(), d2.mkdir()
        obs_searchstats.attach(str(d1))
        try:
            out_m = T.check_history_tpu(h, CASRegister())
        finally:
            obs_searchstats.detach()
        obs_searchstats.attach(str(d2))
        try:
            out_s = T.check_history_tpu(h, CASRegister(),
                                        segment_iters=4)
        finally:
            obs_searchstats.detach()
        assert out_m["valid"] is True and out_s["valid"] is True
        l1 = json.loads((d1 / "searchstats.json").read_text())["levels"]
        l2 = json.loads((d2 / "searchstats.json").read_text())["levels"]
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        assert out_m["searchstats"] == out_s["searchstats"]

    def test_trace_off_identity(self, monkeypatch, tmp_path):
        # JTPU_TRACE=0 compiles the original 13-tuple carry: no stats
        # in the result, no searchstats.json artifact, and the verdict
        # fields bit-identical to a counters-on run
        h = _hist()
        monkeypatch.setenv("JTPU_TRACE", "1")
        out_on = T.check_history_tpu(h, CASRegister(), segment_iters=4)
        monkeypatch.setenv("JTPU_TRACE", "0")
        obs_searchstats.attach(str(tmp_path))
        try:
            out_off = T.check_history_tpu(h, CASRegister(),
                                          segment_iters=4)
        finally:
            obs_searchstats.detach()
        assert "searchstats" not in out_off
        assert not (tmp_path / "searchstats.json").exists()
        # deterministic verdict fields are unchanged by the lane
        for k in ("valid", "levels", "rung"):
            assert out_off.get(k) == out_on.get(k), k

    def test_trace_off_carry_shape(self):
        # the host-side carry constructor mirrors the traced one: no
        # stats rows -> the original 13-tuple, rows -> a 14th lane of
        # [rows, NSTAT] int32 zeros
        cols = {"ini": 0}
        c13 = T._carry0_host(8, 16, 4, 0, 0)
        assert len(c13) == 13
        c14 = T._carry0_host(8, 16, 4, 0, 0, stats_rows=6)
        assert len(c14) == 14
        assert c14[13].shape == (6, T.NSTAT)
        assert c14[13].dtype == np.int32
        assert not c14[13].any()
        del cols

    def test_fit_carry_stats_normalizes_checkpoints(self):
        # a checkpoint taken under the other JTPU_TRACE setting must
        # resume against the executable the CURRENT setting compiled
        from jepsen_tpu import resilience
        c13 = T._carry0_host(8, 16, 4, 0, 0)
        grown = resilience._fit_carry_stats(c13, True, 5)
        assert len(grown) == 14 and grown[13].shape == (6, T.NSTAT)
        shrunk = resilience._fit_carry_stats(grown, False, 5)
        assert len(shrunk) == 13
        # already-fitting carries pass through untouched
        assert resilience._fit_carry_stats(c13, False, 5) is c13

    def test_checkpoint_roundtrips_stats_lane(self, tmp_path):
        from jepsen_tpu import resilience
        carry = T._carry0_host(8, 16, 4, 0, 0, stats_rows=3)
        carry = carry[:13] + (np.arange(3 * T.NSTAT, dtype=np.int32)
                              .reshape(3, T.NSTAT),)
        p = str(tmp_path / "ck.npz")

        def ck(c):
            return resilience.Checkpoint(carry=c, rung=(8, 16, 4),
                                         window=16, expand_eff=4,
                                         crash_width=0, segment=1)

        ck(carry).save(p)
        back = resilience.Checkpoint.load(p)
        assert len(back.carry) == 14
        np.testing.assert_array_equal(back.carry[13], carry[13])
        # a pre-lane 13-tuple checkpoint still loads (no slog in npz)
        ck(carry[:13]).save(p)
        assert len(resilience.Checkpoint.load(p).carry) == 13

    def test_keyed_batch_path_carries_no_lane(self, monkeypatch):
        # the dense keyed-batch bench scenario is the overhead
        # criterion's subject: the keyed/gang/sharded paths keep the
        # lane OFF even with tracing on, so counters cost those
        # executables exactly nothing (identity, not a timing bound)
        monkeypatch.setenv("JTPU_TRACE", "1")
        keyed = {k: _hist(16, seed=k, procs=2) for k in range(3)}
        out = T.check_keyed_tpu(keyed, CASRegister())
        assert out["valid"] is True
        assert "searchstats" not in out
        assert not any("searchstats" in (r or {})
                       for r in (out.get("results") or {}).values()
                       if isinstance(r, dict))


# ---------------------------------------------------------------------------
# Rollups + the searchstats.json artifact (obs/searchstats.py)
# ---------------------------------------------------------------------------


class TestRollup:
    LEVELS = np.array([
        # expanded, dup, dominated, trunc, frontier
        [4, 1, 0, 0, 3],
        [6, 2, 1, 1, 4],
        [2, 0, 1, 0, 1],
    ], np.int32)

    def test_rollup_math(self):
        ss = obs_searchstats.rollup(self.LEVELS)
        assert ss["levels"] == 3
        assert ss["expanded-total"] == 12
        assert ss["dup-kills"] == 3
        assert ss["dominance-kills"] == 2
        assert ss["trunc-losses"] == 1
        assert ss["frontier-area"] == 8
        assert ss["frontier-peak"] == 4
        # dup-rate = dup / (dup + dominated + trunc + frontier)
        assert ss["dup-rate"] == pytest.approx(3 / 14, abs=1e-4)
        assert ss["prune-efficiency"] == pytest.approx(5 / 14,
                                                       abs=1e-4)

    def test_rollup_empty(self):
        ss = obs_searchstats.rollup(np.zeros((0, 5), np.int32))
        assert ss["levels"] == 0 and ss["dup-rate"] == 0.0

    def test_record_replaces_prefix(self, tmp_path, monkeypatch):
        # record() carries REPLACE semantics: each barrier rewrites the
        # full per-level prefix, so a torn write self-heals next time
        monkeypatch.setenv("JTPU_TRACE", "1")
        obs_searchstats.attach(str(tmp_path))
        try:
            obs_searchstats.record(self.LEVELS[:2], rung=(8, 16, 4))
            obs_searchstats.finalize(
                obs_searchstats.rollup(self.LEVELS[:2]))
            obs_searchstats.record(self.LEVELS, rung=(8, 16, 4))
            obs_searchstats.finalize(
                obs_searchstats.rollup(self.LEVELS))
        finally:
            obs_searchstats.detach()
        doc = obs_searchstats.read_searchstats(str(tmp_path))
        assert len(doc["levels"]) == 3
        assert doc["summary"]["trunc-losses"] == 1

    def test_trace_off_writes_nothing(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JTPU_TRACE", "0")
        obs_searchstats.attach(str(tmp_path))
        try:
            obs_searchstats.record(self.LEVELS, rung=(8, 16, 4))
            obs_searchstats.finalize(obs_searchstats.rollup(self.LEVELS))
        finally:
            obs_searchstats.detach()
        assert not (tmp_path / "searchstats.json").exists()

    def test_read_is_torn_tolerant(self, tmp_path):
        assert obs_searchstats.read_searchstats(str(tmp_path)) is None
        p = tmp_path / "searchstats.json"
        p.write_text('{"ts": 1, "levels": [[3, 1')  # torn mid-write
        assert obs_searchstats.read_searchstats(str(tmp_path)) is None
        p.write_text(json.dumps(
            {"ts": 1, "cols": list(obs_searchstats.COLS),
             "levels": [[1, 2, 3, 4, 5], "garbage", [1, 2]],
             "summary": {}}))
        doc = obs_searchstats.read_searchstats(str(tmp_path))
        # malformed rows are filtered, not fatal
        assert doc["levels"] == [[1, 2, 3, 4, 5]]

    def test_sparkline(self):
        line = obs_searchstats.sparkline([0, 1, 2, 4, 8])
        assert len(line) == 5
        assert line[0] == "▁" and line[-1] == "█"
        # long series are max-downsampled to the width
        assert len(obs_searchstats.sparkline(list(range(500)),
                                             width=48)) == 48
        assert obs_searchstats.sparkline([]) == ""


# ---------------------------------------------------------------------------
# Live progress + CLI surfaces (satellite: dup-rate/trunc bits)
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_observatory_carries_analytics_bits(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv("JTPU_TRACE", "1")
        from jepsen_tpu.obs import observatory
        # defeat the disk-write throttle so the second publish lands
        monkeypatch.setattr(observatory, "WRITE_INTERVAL_S", 0.0)
        observatory.attach(str(tmp_path))
        try:
            observatory.begin(level_budget=32, rung=(8, 16, 4),
                              segment_iters=4)
            observatory.publish(level=4, frontier=7, segments=1,
                                seg_seconds=0.1, levels_delta=4,
                                expansions=16, dup_rate=0.25, trunc=2)
            observatory.publish(level=8, frontier=5, segments=2,
                                seg_seconds=0.1, levels_delta=4,
                                expansions=16, dup_rate=0.5, trunc=3)
            p = observatory.read_progress(str(tmp_path))
        finally:
            observatory.detach()
        assert p["dup-rate"] == 0.5          # replace semantics
        assert p["trunc-losses"] == 5        # accumulates per rung
        line = observatory.format_status(p)
        assert "dup-rate 50%" in line
        assert "trunc 5" in line

    def test_search_analytics_line(self):
        from jepsen_tpu import cli
        assert cli._search_analytics_line({}) is None
        assert cli._search_analytics_line({"searchstats": None}) is None
        line = cli._search_analytics_line({"searchstats": {
            "levels": 9, "dup-rate": 0.25, "prune-efficiency": 0.5,
            "frontier-area": 40, "frontier-peak": 8,
            "trunc-losses": 2}})
        assert line.startswith("# search:")
        assert "dup-rate 25%" in line
        assert "truncation-losses 2" in line


# ---------------------------------------------------------------------------
# Contention / decomposability profiling (analysis/contention.py)
# ---------------------------------------------------------------------------


class TestContention:
    def test_keyed_disjoint_is_decomposable(self):
        from jepsen_tpu.analysis import contention
        keyed = {k: _hist(20, seed=k, procs=3) for k in range(4)}
        prof = contention.profile(keyed)
        assert prof["decomposable"] is True
        assert prof["decomposability"] >= 0.5
        assert prof["components"] == 4
        assert prof["est-speedup"] > 1.0

    def test_single_key_dense_is_not(self):
        # the acceptance criterion's other half: one dense register
        # history has one conflict component — nothing to decompose
        from jepsen_tpu.analysis import contention
        prof = contention.profile(_hist(60, seed=1, procs=4,
                                        overlap=0.95))
        assert prof["decomposable"] is False
        assert prof["decomposability"] < 0.5
        assert prof["components"] == 1
        assert prof["est-speedup"] == 1.0

    def test_independent_value_convention(self):
        # [key, v] LIST values key the op; a cas (old, new) TUPLE does
        # not (it is payload, not a key)
        from jepsen_tpu.analysis import contention
        h = H((0, "invoke", "write", [0, 1]), (0, "ok", "write", [0, 1]),
              (1, "invoke", "write", [1, 2]), (1, "ok", "write", [1, 2]),
              (2, "invoke", "cas", (1, 2)), (2, "ok", "cas", (1, 2)))
        prof = contention.profile(h)
        # keys 0 and 1, plus the keyless cas in the global component
        assert prof["components"] == 3
        assert prof["keys"] == 2

    def test_concurrency_width(self):
        from jepsen_tpu.analysis import contention
        h = H((0, "invoke", "write", 1), (1, "invoke", "read", None),
              (0, "ok", "write", 1), (1, "ok", "read", 1))
        prof = contention.profile(h)
        assert prof["concurrency"]["max"] == 2
        assert prof["commutativity"]["read-only"] == 1
        assert prof["commutativity"]["mutating"] == 1

    def test_never_raises(self):
        from jepsen_tpu.analysis import contention
        for bad in (None, 42, [], History(), {"k": None}):
            prof = contention.profile(bad)
            assert prof["ops"] == 0
            assert prof["decomposable"] is False
        assert contention.forecast_lines(prof) == \
            ["# contention: unprofilable history"]

    def test_forecast_lines(self):
        from jepsen_tpu.analysis import contention
        keyed = {k: _hist(20, seed=k, procs=3) for k in range(4)}
        lines = contention.forecast_lines(contention.profile(keyed))
        assert all(ln.startswith("# contention:") for ln in lines)
        assert "decomposable" in lines[0]
        assert any("speedup" in ln for ln in lines)


# ---------------------------------------------------------------------------
# jtpu explain (jepsen_tpu/explain.py + CLI + web)
# ---------------------------------------------------------------------------


def _store_run(root, name, history, results, searchstats_dir=None):
    """Materialize a stored run directory the way core.run would."""
    from jepsen_tpu import store
    d = os.path.join(str(root), name, "20260805T120000.000")
    os.makedirs(d, exist_ok=True)
    store.write_history(d, history)
    if results is not None:
        store.write_results(d, results)
    store.write_state(d, "done")
    return d


class TestExplain:
    @pytest.fixture()
    def valid_run(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JTPU_TRACE", "1")
        h = _hist()
        d = _store_run(tmp_path, "reg-valid", h, None)
        obs_searchstats.attach(d)
        try:
            out = T.check_history_tpu(h, CASRegister())
        finally:
            obs_searchstats.detach()
        from jepsen_tpu import store
        store.write_results(d, out)
        return d

    @pytest.fixture()
    def invalid_run(self, tmp_path):
        # a read observes a value never written: non-linearizable
        h = H((0, "invoke", "write", 1), (0, "ok", "write", 1),
              (1, "invoke", "read", None), (1, "ok", "read", 2))
        out = T.check_history_tpu(h, CASRegister())
        assert out["valid"] is False
        return _store_run(tmp_path, "reg-invalid", h, out)

    @pytest.fixture()
    def unknown_run(self, tmp_path, monkeypatch):
        # dense overlap at a pinned tiny rung: the pool truncates live
        # uniques and then dies -> unknown via lossy truncation
        monkeypatch.setenv("JTPU_TRACE", "1")
        h = _hist(60, seed=7, procs=6, overlap=0.98)
        d = _store_run(tmp_path, "reg-unknown", h, None)
        obs_searchstats.attach(d)
        try:
            out = T.check_history_tpu(h, CASRegister(), capacity=2,
                                      window=16, expand=2)
        finally:
            obs_searchstats.detach()
        assert out["valid"] == "unknown" and out["capacity-overflow"]
        from jepsen_tpu import store
        store.write_results(d, out)
        return d

    def test_valid_report(self, valid_run):
        from jepsen_tpu import explain
        rep = explain.explain_report(valid_run)
        assert rep["kind"] == "valid"
        assert rep["searchstats"]["levels"] > 0
        assert rep["frontier-series"]
        text = explain.render_text(rep)
        assert "# explain:" in text
        assert "search shape" in text
        assert "frontier/level" in text

    def test_invalid_report(self, invalid_run):
        from jepsen_tpu import explain
        rep = explain.explain_report(invalid_run)
        assert rep["kind"] == "invalid"
        cex = rep.get("counterexample") or rep.get("counterexample-raw")
        assert cex is not None
        assert cex.get("violating-level") is not None
        text = explain.render_text(rep)
        assert "non-linearizable" in text

    def test_unknown_report_cites_truncation(self, unknown_run):
        from jepsen_tpu import explain
        rep = explain.explain_report(unknown_run)
        assert rep["kind"] == "unknown"
        causes = {c["cause"]: c for c in rep["cause-chain"]}
        assert "lossy-truncation" in causes
        assert causes["lossy-truncation"]["levels"]  # exact levels cited
        text = explain.render_text(rep)
        assert "cause: lossy-truncation" in text

    def test_torn_artifacts_degrade(self, valid_run):
        # a torn searchstats.json and a missing results.json must
        # degrade the report, never crash it (the explain-kill chaos
        # scenario holds the web page to the same contract)
        from jepsen_tpu import explain
        with open(os.path.join(valid_run, "searchstats.json"), "w") as f:
            f.write('{"ts": 1, "levels": [[3,')
        os.unlink(os.path.join(valid_run, "results.json"))
        rep = explain.explain_report(valid_run)
        assert rep["kind"] == "unknown"
        assert any(c["cause"] == "no-verdict"
                   for c in rep["cause-chain"])
        assert "# explain:" in explain.render_text(rep)

    def test_cli_explain(self, valid_run, invalid_run, capsys):
        from jepsen_tpu import cli
        cmds = cli.default_commands()
        assert "explain" in cmds
        rc = cli.run(cmds, ["explain", "--store", valid_run])
        out = capsys.readouterr().out
        assert rc == 0 and "# explain:" in out
        rc = cli.run(cmds, ["explain", "--store", invalid_run,
                            "--format", "json"])
        out = capsys.readouterr().out
        assert rc == 1
        assert json.loads(out)["kind"] == "invalid"
        assert cli.run(cmds, ["explain", "--store",
                              "/no/such/dir"]) == 254

    def test_web_explain_page(self, unknown_run):
        import urllib.request

        from jepsen_tpu import web
        root = os.path.dirname(os.path.dirname(unknown_run))
        rel = os.path.relpath(unknown_run, root)
        server = web.serve_background(root=root)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.server_port}"
                    f"/explain/{rel}", timeout=10) as r:
                assert r.status == 200
                page = r.read().decode()
            assert "# explain:" in page
            assert "lossy-truncation" in page
            # and the home table links to it
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.server_port}/",
                    timeout=10) as r:
                assert f"/explain/{rel}" in r.read().decode()
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# Self-lint: extraction sites live OUTSIDE traced bodies
# ---------------------------------------------------------------------------


class TestLintClean:
    def test_new_surfaces_obey_trace_in_jit(self):
        # every searchstats extraction site is host-side: the kernel
        # writes jnp counters into the carry, and record()/rollup()
        # run at segment barriers (resilience._supervised_check_packed,
        # the one allowlisted body) or after the search returns
        from jepsen_tpu.analysis import jax_lint
        for rel in ("jepsen_tpu/checker/tpu.py",
                    "jepsen_tpu/checker/engine.py",
                    "jepsen_tpu/resilience.py",
                    "jepsen_tpu/obs/searchstats.py",
                    "jepsen_tpu/analysis/contention.py",
                    "jepsen_tpu/explain.py"):
            findings = jax_lint.lint_file(os.path.join(REPO, rel),
                                          root=REPO)
            assert not [f for f in findings
                        if f.rule == "JAX-TRACE-IN-JIT"], rel

    def test_supervised_body_is_the_only_allowlisted_site(self):
        from jepsen_tpu.analysis import jax_lint
        assert ("jepsen_tpu/resilience.py",
                "_supervised_check_packed") \
            in jax_lint.TRACE_IN_JIT_ALLOWLIST
        # the lane itself must NOT need an allowlist entry: searchstats
        # is not a sanctioned obs alias inside traced bodies
        assert "obs_searchstats" not in jax_lint._OBS_ALIASES
