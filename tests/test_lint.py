"""The static-analysis subsystem (jepsen_tpu.analysis): per-pass unit
tests over synthetic good/bad fixtures, the pre-search history gate,
the shared op-type validation, the baseline machinery, and a self-lint
asserting the repo is clean against its committed baseline. All tier-1
(marker: lint)."""

import io
import json
import os
import sys

import pytest

from jepsen_tpu import analysis, cli
from jepsen_tpu.analysis import baseline as bl
from jepsen_tpu.analysis import history_lint as hl
from jepsen_tpu.analysis.opcheck import (INVALID_TYPE_FLAG,
                                         VALID_OP_TYPES, invalid_op_type)
from jepsen_tpu.history import History, Op, VALID_TYPES

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "fixtures", "lint")


def _rules(findings):
    return {f.rule for f in findings}


def _lint(path, **kw):
    return analysis.lint_files([os.path.join(FIX, path)], **kw)


# ---------------------------------------------------------------------------
# Pass 1: suite linter
# ---------------------------------------------------------------------------

class TestSuiteLint:
    def test_bad_suite_fixture_fires_every_rule(self):
        fs = _lint("bad_suite.py")
        assert {"SUITE-OP-TYPE", "SUITE-OP-NO-F",
                "SUITE-CLIENT-NO-INVOKE",
                "SUITE-BLOCKING-NO-TIMEOUT"} <= _rules(fs)
        # findings carry file:line
        assert all(f.path.endswith("bad_suite.py") and f.line > 0
                   for f in fs)

    def test_good_suite_fixture_is_clean(self):
        assert _lint("good_suite.py") == []

    def test_blocking_call_reached_through_self_helper(self):
        fs = [f for f in _lint("bad_suite.py")
              if f.rule == "SUITE-BLOCKING-NO-TIMEOUT"]
        # one direct (urlopen in invoke), one via self._rpc
        assert len(fs) == 2

    def test_registry_cross_check(self):
        from jepsen_tpu.analysis import suite_lint
        paths = [os.path.join(FIX, "bad_suite.py"),
                 os.path.join(FIX, "good_suite.py")]
        reg = {"fine": ("good_suite", "fine_test"),
               "broken": ("bad_suite", "broken_test"),
               "missing-attr": ("good_suite", "no_such_ctor"),
               "missing-mod": ("no_such_module", "x_test")}
        fs = suite_lint.lint_suites(paths, registry=reg)
        assert "SUITE-CTOR-ARITY" in _rules(fs)          # broken_test
        missing = [f for f in fs if f.rule == "SUITE-REGISTRY-MISSING"]
        assert len(missing) == 2                          # attr + module

    def test_real_registry_resolves_statically(self):
        # the real SUITES registry must produce no registry findings
        fs = analysis.lint_repo(passes=("suite",))
        assert "SUITE-REGISTRY-MISSING" not in _rules(fs)
        assert "SUITE-CTOR-ARITY" not in _rules(fs)


# ---------------------------------------------------------------------------
# Pass 2: history linter + the pre-search gate
# ---------------------------------------------------------------------------

class TestHistoryLint:
    def test_bad_history_fixture_fires_every_rule(self):
        fs = _lint("bad_history.jsonl")
        assert {"HIST-DECODE", "HIST-DANGLING-INVOKE", "HIST-PROC-REUSE",
                "HIST-UNMATCHED-COMPLETE", "HIST-OP-TYPE",
                "HIST-INDEX-ORDER"} <= _rules(fs)

    def test_good_history_fixture_has_no_errors(self):
        fs = _lint("good_history.jsonl")
        assert hl.errors(fs) == []
        # the crashed op surfaces as a note, not damage
        assert "HIST-OPEN-INVOKE" in _rules(fs)

    def test_crashed_op_is_legal(self):
        h = History.of([
            Op(type="invoke", f="write", value=1, process=0, time=0),
            Op(type="ok", f="write", value=1, process=0, time=1),
            Op(type="invoke", f="write", value=2, process=1, time=2),
        ])
        assert hl.errors(hl.lint_history(h)) == []

    def test_nemesis_ops_never_pair(self):
        h = History.of([
            Op(type="info", f="start", process="nemesis", time=0),
            Op(type="info", f="stop", process="nemesis", time=1),
            Op(type="info", f="heal-verified", process="nemesis", time=2),
        ])
        assert hl.lint_history(h) == []

    def test_f_mismatch_between_pairs(self):
        h = History.of([
            Op(type="invoke", f="write", value=1, process=0, time=0),
            Op(type="ok", f="read", value=1, process=0, time=1),
        ])
        assert "HIST-F-MISMATCH" in _rules(hl.lint_history(h))

    def test_gate_rejects_with_rule_id_before_any_jit(self, monkeypatch):
        from jepsen_tpu.checker import tpu
        from jepsen_tpu.models import CASRegister

        def boom(*a, **k):  # any compilation attempt is a failure
            raise AssertionError("jit factory invoked for a "
                                 "malformed history")

        for name in ("_jit_segment", "_jit_batch", "_jit_batch_segment"):
            monkeypatch.setattr(tpu, name, boom)
        bad = History.of([
            Op(type="invoke", f="write", value=1, process=0, time=0),
            Op(type="invoke", f="read", value=None, process=0, time=1),
            Op(type="ok", f="read", value=1, process=0, time=2),
        ])
        with pytest.raises(hl.MalformedHistoryError) as ei:
            tpu.check_history_tpu(bad, CASRegister())
        assert "HIST-DANGLING-INVOKE" in str(ei.value)

    def test_gate_surfaces_through_check_safe(self):
        from jepsen_tpu.checker import check_safe
        from jepsen_tpu.checker.wgl import linearizable
        from jepsen_tpu.models import CASRegister
        bad = History.of([
            Op(type="ok", f="read", value=1, process=0, time=0),
        ])
        out = check_safe(linearizable(CASRegister(), backend="tpu"),
                         {}, bad)
        assert out["valid"] == "unknown"
        assert "HIST-UNMATCHED-COMPLETE" in out["error"]

    def test_gate_kill_switch(self, monkeypatch):
        monkeypatch.setenv("JTPU_HISTORY_GATE", "0")
        bad = History.of([
            Op(type="ok", f="read", value=1, process=0, time=0),
        ])
        assert hl.gate_history(bad) == []

    def test_keyed_gate_isolates_the_malformed_key(self):
        from jepsen_tpu.checker.tpu import check_keyed_tpu
        from jepsen_tpu.models import CASRegister
        good = [Op(type="invoke", f="write", value=1, process=0, time=0),
                Op(type="ok", f="write", value=1, process=0, time=1)]
        bad = [Op(type="ok", f="read", value=1, process=0, time=0)]
        out = check_keyed_tpu({"g": History.of(good),
                               "b": History.of(bad)}, CASRegister())
        assert out["results"]["g"]["valid"] is True
        assert out["results"]["b"]["valid"] == "unknown"
        assert out["results"]["b"]["lint"] == {
            "HIST-UNMATCHED-COMPLETE": 1}
        assert out["valid"] == "unknown"


class TestSharedOpValidation:
    def test_one_validation_function(self):
        # the runtime guard and the lint rule share the same notion
        assert tuple(VALID_TYPES) == VALID_OP_TYPES
        for t in VALID_OP_TYPES:
            assert invalid_op_type(t) is None
        assert invalid_op_type("okk")

    def test_from_dict_tolerates_and_flags(self):
        op = Op.from_dict({"type": "okk", "f": "read", "process": 0})
        assert op.type == "okk"  # tolerated
        assert INVALID_TYPE_FLAG in op.extra  # flagged

    def test_from_jsonl_counts_type_errors(self):
        h = History.from_jsonl(
            '{"type": "invoke", "f": "read", "process": 0}\n'
            '{"type": "okk", "f": "read", "process": 0}\n')
        assert len(h) == 2 and h.type_errors == 1
        assert "HIST-OP-TYPE" in _rules(hl.lint_history(h))

    def test_clean_roundtrip_unchanged(self):
        d = {"type": "ok", "f": "read", "value": 3, "process": 0,
             "time": 5, "index": 2}
        assert Op.from_dict(d).to_dict() == d


# ---------------------------------------------------------------------------
# Pass 3: JAX hazard linter
# ---------------------------------------------------------------------------

class TestJaxLint:
    def test_bad_jax_fixture_fires_every_rule(self):
        fs = _lint("bad_jax.py")
        assert {"JAX-HOST-SYNC", "JAX-HOST-CAST",
                "JAX-UNHASHABLE-STATIC", "JAX-INT32-OVERFLOW",
                "JAX-SHIFT-WIDTH"} <= _rules(fs)

    def test_call_closure_reaches_named_helpers(self):
        fs = [f for f in _lint("bad_jax.py")
              if f.rule == "JAX-HOST-SYNC" and "helper" in f.message]
        assert fs, "np call in a loop-body helper must be flagged"

    def test_good_jax_fixture_is_clean(self):
        # trace-time numpy in a host-side builder is idiom, not hazard
        # (including the locally-shadowed module width in pack_shadowed)
        assert _lint("good_jax.py") == []

    def test_named_constant_folding(self):
        # the overflow/shift rules fold module-level named constants,
        # not just literals (pack_named in the bad fixture)
        fs = [f for f in _lint("bad_jax.py") if "pack_named" in f.anchor]
        assert {f.rule for f in fs} == {"JAX-SHIFT-WIDTH",
                                        "JAX-INT32-OVERFLOW"}
        assert len([f for f in fs
                    if f.rule == "JAX-INT32-OVERFLOW"]) == 2

    def test_imported_constant_resolves_through_repo_module(self):
        # RET_INF comes from jepsen_tpu/ops/encode.py: the width chain
        # crosses a module boundary and still folds
        fs = [f for f in _lint("bad_jax.py")
              if f.rule == "JAX-INT32-OVERFLOW"
              and "2147483648" in f.message]
        assert fs, "np.int32(RET_INF + 1) must fold via the import"

    def test_shadowed_name_does_not_fold(self):
        from jepsen_tpu.analysis import jax_lint
        import ast
        tree = ast.parse(
            "W = 40\n"
            "def f(v, n):\n"
            "    W = n & 7\n"
            "    return v << W\n"
            "def g(v):\n"
            "    return v << W\n")
        shadows = jax_lint._shadow_sets(tree)
        env = jax_lint._module_env(tree, None)
        assert env == {"W": 40}
        shifts = [n for n in ast.walk(tree)
                  if isinstance(n, ast.BinOp)
                  and isinstance(n.op, ast.LShift)]
        shadowed = [n for n in shifts
                    if "W" in shadows.get(id(n), ())]
        assert len(shadowed) == 1  # f's shift only; g's folds to 40


# ---------------------------------------------------------------------------
# SARIF export (shared findings core)
# ---------------------------------------------------------------------------

class TestSarif:
    def test_sarif_document_shape(self):
        from jepsen_tpu.analysis import sarif
        fs = _lint("bad_jax.py")
        assert fs
        doc = sarif.to_sarif(fs)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert rule_ids == {f.rule for f in fs}
        assert len(run["results"]) == len(fs)
        r0 = run["results"][0]
        assert r0["level"] in ("error", "warning", "note")
        loc = r0["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("bad_jax.py")
        assert loc["region"]["startLine"] >= 1

    def test_sarif_fingerprints_are_baseline_anchors(self):
        from jepsen_tpu.analysis import sarif
        fs = _lint("bad_jax.py")
        doc = sarif.to_sarif(fs)
        prints = [r["partialFingerprints"]["jtpuAnchor/v1"]
                  for r in doc["runs"][0]["results"]]
        assert sorted(prints) == sorted(f.anchor for f in fs)

    def test_sarif_render_round_trips(self):
        from jepsen_tpu.analysis import sarif
        text = sarif.render(_lint("bad_lockset.py"))
        doc = json.loads(text)
        assert doc["$schema"].endswith("sarif-schema-2.1.0.json")

    def test_lint_gate_sarif_flag(self, tmp_path):
        import subprocess
        import sys as _sys
        out = tmp_path / "lint.sarif"
        r = subprocess.run(
            [_sys.executable, os.path.join(REPO, "tools",
                                           "lint_gate.py"),
             "--sarif", str(out), "--no-plan"],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(out.read_text())
        assert doc["version"] == "2.1.0"
        assert doc["runs"][0]["results"] == []  # clean vs baseline


# ---------------------------------------------------------------------------
# Pass 4: lockset linter
# ---------------------------------------------------------------------------

class TestLocksetLint:
    def test_bad_lockset_fixture(self):
        fs = _lint("bad_lockset.py")
        assert {"LOCK-UNGUARDED", "LOCK-LIFECYCLE"} <= _rules(fs)
        # guarded accesses and plain initialization are NOT flagged
        lines = {f.line for f in fs}
        assert all(line >= 14 for line in lines), \
            "conj_op_ok's guarded accesses were wrongly flagged"

    def test_core_conj_op_is_clean(self):
        fs = analysis.lint_files(["jepsen_tpu/core.py"],
                                 passes=("lockset",))
        assert all("conj_op" not in f.anchor for f in fs)
        assert not [f for f in fs if f.severity == "error"]

    def test_bad_class_fixture_fires_every_rule(self):
        fs = _lint("bad_lockset_class.py", passes=("lockset",))
        assert _rules(fs) == {"LOCK-UNGUARDED", "LOCK-INCONSISTENT",
                              "LOCK-LIFECYCLE"}
        by_rule = {f.rule: f for f in fs}
        # the off-lock mutation is the error; the lifecycle read and
        # the wrong-lock access are downgraded to warnings
        assert by_rule["LOCK-UNGUARDED"].severity == "error"
        assert "racy_incr" in by_rule["LOCK-UNGUARDED"].anchor
        assert by_rule["LOCK-LIFECYCLE"].severity == "warning"
        assert "stop" in by_rule["LOCK-LIFECYCLE"].anchor
        assert "_aux" in by_rule["LOCK-INCONSISTENT"].message

    def test_good_class_fixture_is_clean(self):
        # consistent locking + a '# guarded-by: none' opt-out: no
        # findings, including no LOCK-LIFECYCLE noise
        assert _lint("good_lockset_class.py", passes=("lockset",)) == []


# ---------------------------------------------------------------------------
# Pass 6: deadlock linter
# ---------------------------------------------------------------------------

class TestDeadlockLint:
    def test_cycle_fixture_fires(self):
        fs = _lint("bad_deadlock.py", passes=("deadlock",))
        assert _rules(fs) == {"LOCK-ORDER-CYCLE", "LOCK-HELD-BLOCKING"}
        cyc = [f for f in fs if f.rule == "LOCK-ORDER-CYCLE"]
        assert len(cyc) == 1 and cyc[0].severity == "error"
        # both locks named, with the witnessing call-site edges
        assert "Left._lock" in cyc[0].message
        assert "Right._lock" in cyc[0].message
        assert "poke() calls touch()" in cyc[0].message
        blk = [f for f in fs if f.rule == "LOCK-HELD-BLOCKING"]
        assert len(blk) == 1 and "os.fsync" in blk[0].message

    def test_diamond_lock_order_is_clean(self):
        # two paths through a diamond (top -> left|right -> bottom)
        # converge without reversing an edge: acyclic, no findings
        assert _lint("good_deadlock.py", passes=("deadlock",)) == []


# ---------------------------------------------------------------------------
# Pass 7: crash-consistency (walcheck) linter
# ---------------------------------------------------------------------------

class TestWalcheckLint:
    def test_bad_fixture_fires_every_rule(self):
        fs = _lint("bad_walcheck.py", passes=("walcheck",))
        assert _rules(fs) == {"WAL-ACK-BEFORE-JOURNAL",
                              "ATOMIC-WRITE-DIRECT",
                              "ATOMIC-TMP-SCANNED"}
        wal = [f for f in fs if f.rule == "WAL-ACK-BEFORE-JOURNAL"]
        # both shapes: the unjournaled 202 ack AND the 'done' record
        # journaled before the artifact's os.replace
        assert any("202" in f.message for f in wal)
        assert any("'done'" in f.message for f in wal)

    def test_good_fixture_is_clean(self):
        # journal-before-ack (with the replay-arm and duplicate-re-ack
        # exemptions exercised) + dot-prefixed tmp + os.replace: clean
        assert _lint("good_walcheck.py", passes=("walcheck",)) == []


# ---------------------------------------------------------------------------
# Baseline + CLI + self-lint
# ---------------------------------------------------------------------------

class TestBaseline:
    def test_roundtrip_and_split(self, tmp_path):
        fs = _lint("bad_lockset.py")
        assert fs
        p = tmp_path / "lint.baseline"
        bl.write(str(p), fs)
        loaded = bl.load(str(p))
        assert len(loaded) == len({f.key() for f in fs})
        new, accepted = bl.split(fs, loaded)
        assert new == [] and len(accepted) == len(fs)

    def test_justifications_survive_rewrite(self, tmp_path):
        fs = _lint("bad_lockset.py")
        p = tmp_path / "lint.baseline"
        key = fs[0].key()
        p.write_text(f"{key} — because reasons\n")
        bl.write(str(p), fs)
        assert bl.load(str(p))[key] == "because reasons"

    def test_committed_baseline_entries_are_justified(self):
        for key, just in bl.load().items():
            assert just and "TODO" not in just, \
                f"baseline entry {key!r} lacks a real justification"

    def test_stubbed_reports_todo_and_empty_justifications(self):
        base = {"A f.py#x": "real reason",
                "B g.py#y": bl.STUB,
                "C h.py#z": ""}
        assert bl.stubbed(base) == ["B g.py#y", "C h.py#z"]

    def test_strict_rejects_stub_justifications(self, tmp_path):
        """--write-baseline stubs must be filled in before --strict
        treats the entry as a real acceptance."""
        import contextlib
        target = os.path.join(FIX, "bad_lockset.py")
        p = tmp_path / "b.baseline"
        rc, _ = _run_cli(["lint", "--baseline", str(p),
                          "--write-baseline", target])
        assert rc == cli.OK
        assert bl.STUB in p.read_text()
        # non-strict: the stubbed acceptance still suppresses
        rc, _ = _run_cli(["lint", "--baseline", str(p), target])
        assert rc == cli.OK
        # strict: refused, with a clear per-entry message
        err = io.StringIO()
        with contextlib.redirect_stderr(err):
            rc, _ = _run_cli(["lint", "--strict",
                              "--baseline", str(p), target])
        assert rc == cli.TEST_FAILED
        assert "stub justification" in err.getvalue()
        assert str(p) in err.getvalue()
        # a real justification clears the gate
        p.write_text(p.read_text().replace(
            bl.STUB, "reviewed: fixture intentionally racy"))
        err = io.StringIO()
        with contextlib.redirect_stderr(err):
            rc, _ = _run_cli(["lint", "--strict",
                              "--baseline", str(p), target])
        assert rc == cli.OK, err.getvalue()
        assert "stub justification" not in err.getvalue()


def _run_cli(argv):
    buf = io.StringIO()
    old = sys.stdout
    sys.stdout = buf
    try:
        rc = cli.run(cli.default_commands(), argv)
    finally:
        sys.stdout = old
    return rc, buf.getvalue()


class TestLintCLI:
    def test_bad_fixtures_exit_nonzero_with_location_and_rule(self):
        for fixture in ("bad_suite.py", "bad_jax.py", "bad_lockset.py",
                        "bad_history.jsonl"):
            rc, out = _run_cli(["lint", os.path.join(FIX, fixture)])
            assert rc == cli.TEST_FAILED, fixture
            assert fixture + ":" in out and "[" in out, fixture

    def test_good_fixtures_exit_zero(self):
        rc, out = _run_cli(["lint", os.path.join(FIX, "good_suite.py"),
                            os.path.join(FIX, "good_jax.py"),
                            os.path.join(FIX, "good_history.jsonl")])
        assert rc == cli.OK
        # the only finding is the legal crashed op's note — no gate
        assert ": error:" not in out and ": warning:" not in out
        assert "HIST-OPEN-INVOKE" in out

    def test_missing_path_is_not_clean(self):
        rc, out = _run_cli(["lint", "no/such/file.py"])
        assert rc == cli.TEST_FAILED
        assert "LINT-MISSING-FILE" in out

    def test_json_format(self):
        rc, out = _run_cli(["lint", "--format", "json",
                            os.path.join(FIX, "bad_history.jsonl")])
        assert rc == cli.TEST_FAILED
        doc = json.loads(out)
        assert doc["counts"]["HIST-PROC-REUSE"] == 1

    def test_write_baseline_then_clean(self, tmp_path):
        p = tmp_path / "b.baseline"
        target = os.path.join(FIX, "bad_lockset.py")
        rc, _ = _run_cli(["lint", "--baseline", str(p),
                          "--write-baseline", target])
        assert rc == cli.OK
        rc, out = _run_cli(["lint", "--baseline", str(p), target])
        assert rc == cli.OK and "accepted" in out

    def test_prune_stale_drops_fixed_entries_only(self, tmp_path):
        p = tmp_path / "b.baseline"
        target = os.path.join(FIX, "bad_lockset.py")
        rc, _ = _run_cli(["lint", "--baseline", str(p),
                          "--write-baseline", target])
        assert rc == cli.OK
        # justify the live entries, then plant one stale entry
        p.write_text(p.read_text().replace(
            bl.STUB, "reviewed: fixture intentionally racy"))
        stale_key = "LOCK-UNGUARDED gone.py#fixed/x"
        with open(p, "a", encoding="utf-8") as f:
            f.write(f"{stale_key} — was fixed long ago\n")
        rc, out = _run_cli(["lint", "--baseline", str(p),
                            "--prune-stale", target])
        assert rc == cli.OK
        assert stale_key in out and "1 stale baseline entry pruned" in out
        loaded = bl.load(str(p))
        assert stale_key not in loaded
        # survivors keep their justifications verbatim
        assert loaded and all(j == "reviewed: fixture intentionally racy"
                              for j in loaded.values())
        # a second prune is a no-op
        rc, out = _run_cli(["lint", "--baseline", str(p),
                            "--prune-stale", target])
        assert rc == cli.OK and "0 stale baseline entries pruned" in out

    def test_self_lint_repo_clean_against_committed_baseline(self):
        # the acceptance gate: all four passes over the live tree,
        # exit 0 against lint.baseline
        rc, out = _run_cli(["lint"])
        assert rc == cli.OK, out
        assert "# lint: clean" in out

    def test_lint_gate_tool_is_clean(self):
        import subprocess
        pr = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "lint_gate.py")],
            capture_output=True, text=True, timeout=120)
        assert pr.returncode == 0, pr.stdout + pr.stderr
        assert "clean against the baseline" in pr.stdout
        assert "stale baseline entry" not in pr.stdout

    def test_lint_gate_stale_escalation(self, tmp_path):
        """A stale baseline entry warns for --stale-grace runs (sidecar
        counter), then FAILS the gate until pruned; the prune clears
        the escalation and the next clean run removes the sidecar."""
        import shutil
        import subprocess
        p = tmp_path / "lint.baseline"
        shutil.copyfile(os.path.join(REPO, "lint.baseline"), str(p))
        stale_key = "LOCK-UNGUARDED gone.py#fixed/x"
        with open(p, "a", encoding="utf-8") as f:
            f.write(f"{stale_key} — was fixed long ago\n")
        cmd = [sys.executable,
               os.path.join(REPO, "tools", "lint_gate.py"),
               "--baseline", str(p), "--no-plan", "--stale-grace", "1"]

        r1 = subprocess.run(cmd, capture_output=True, text=True,
                            timeout=120)
        assert r1.returncode == 0, r1.stdout + r1.stderr
        assert "stale baseline entry" in r1.stdout
        assert "[1/1 warning(s)]" in r1.stdout
        assert (tmp_path / "lint.baseline.stale").exists()

        r2 = subprocess.run(cmd, capture_output=True, text=True,
                            timeout=120)
        assert r2.returncode == 1, r2.stdout + r2.stderr
        assert "stale past the 1-run grace" in r2.stderr
        assert "--prune-stale" in r2.stderr

        rc, out = _run_cli(["lint", "--baseline", str(p),
                            "--prune-stale"])
        assert rc == cli.OK and stale_key in out

        r3 = subprocess.run(cmd, capture_output=True, text=True,
                            timeout=120)
        assert r3.returncode == 0, r3.stdout + r3.stderr
        assert "stale baseline entry" not in r3.stdout
        assert not (tmp_path / "lint.baseline.stale").exists()


class TestRecoverPathGate:
    def test_recover_fails_on_structurally_damaged_wal(self, tmp_path):
        """A WAL whose mid-stream completion record was lost (CRC
        corruption) leaves a process reusing itself; recovery must fail
        with a lint diagnostic instead of checking the damaged
        history."""
        import contextlib

        from jepsen_tpu import journal, store
        d = tmp_path / "run"
        d.mkdir()
        ops = [
            Op(type="invoke", f="write", value=1, process=0, time=0),
            # the ok completion for process 0 was here — corrupted away
            Op(type="invoke", f="read", value=None, process=0, time=2),
            Op(type="ok", f="read", value=1, process=0, time=3),
        ]
        with open(d / "history.wal", "wb") as f:
            for o in ops:
                f.write(journal.encode_record(o))
        store.write_state(str(d), "running")
        # fake a dead recorder
        st = store.read_state(str(d))
        st["pid"] = 2 ** 22 + 12345  # vanishingly unlikely to be alive
        import json as _json
        (d / "run.state").write_text(_json.dumps(st))
        assert store.run_status(str(d)) == "dead"

        buf, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(buf), \
                contextlib.redirect_stderr(err):
            rc = cli.run(cli.default_commands(),
                         ["recover", "--store", str(d)])
        assert rc == cli.TEST_FAILED
        assert "# lint:" in buf.getvalue()
        assert "HIST-DANGLING-INVOKE" in buf.getvalue() + err.getvalue()
        # no results.json: the checker never ran on damaged structure
        assert not (d / "results.json").exists()

    def test_analyze_prints_lint_summary(self, tmp_path):
        import contextlib
        d = tmp_path / "run"
        d.mkdir()
        h = History.of([
            Op(type="invoke", f="write", value=1, process=0, time=0),
            Op(type="ok", f="write", value=1, process=0, time=1),
        ]).index()
        (d / "history.jsonl").write_text(h.to_jsonl() + "\n")
        (d / "test.json").write_text('{"name": "t"}')
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cli.run(cli.default_commands(),
                         ["analyze", "--store", str(d)])
        assert rc == cli.OK
        assert "# lint: clean" in buf.getvalue()
