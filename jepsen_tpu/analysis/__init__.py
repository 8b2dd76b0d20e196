"""Static analysis: reject broken suites, malformed histories, and JAX
kernel hazards *before* they burn device time.

The dynamic checker stack only discovers malformed input at run time,
after cluster setup and a (possibly sharded) device search have already
been paid for. The P-compositionality line of work (PAPERS.md: Horn &
Kroening 1504.00204) shows that cheap structural rejection ahead of the
expensive search is where the big constant factors live; this package is
that front end, done statically. Four passes:

1. :mod:`~jepsen_tpu.analysis.suite_lint` — AST pass over every module
   in ``jepsen_tpu/suites/``, cross-checked against the ``SUITES``
   registry (missing/uncallable constructors, client classes that don't
   implement the invoke protocol, op literals with illegal ``type`` or
   missing ``f``, blocking calls on invoke paths without a timeout).
2. :mod:`~jepsen_tpu.analysis.history_lint` — fast structural validator
   over a :class:`~jepsen_tpu.history.History` (unmatched completions,
   process reuse, dangling invokes, non-monotonic indices, undecodable
   lines, illegal op types). Doubles as the mandatory pre-search gate in
   :mod:`jepsen_tpu.checker.tpu` and the ``recover`` path.
3. :mod:`~jepsen_tpu.analysis.jax_lint` — AST pass over
   ``checker/*.py`` and ``ops/encode.py`` for jit-unsafe patterns: host
   syncs inside traced bodies, unhashable arguments defeating the
   ``_jit_segment``/``_jit_batch`` caches, bit-width
   overflow in the packed op encoding.
4. :mod:`~jepsen_tpu.analysis.lockset_lint` — a static race detector
   for the threaded stack: the legacy dict-key engine flags access to
   ``_history_lock``-guarded state outside a ``with
   test["_history_lock"]`` block; the generalized class engine
   auto-discovers per-class locks and guarded attribute sets
   (inference + ``# guarded-by:`` annotations) across the serving
   scope and flags off-lock / wrong-lock access.
5. :mod:`~jepsen_tpu.analysis.plan_lint` — ahead-of-time search-plan
   verification (engine: :mod:`jepsen_tpu.checker.plan`): proves the
   shape buckets the device search would compile actually trace, fit
   the device byte budget, shard cleanly, and stay inside int32 —
   over a pinned model × dims fixture matrix, with zero XLA compiles.
   Doubles as the mandatory pre-search plan gate in
   :mod:`jepsen_tpu.checker.tpu` (kill switch ``JTPU_PLAN_GATE=0``).
6. :mod:`~jepsen_tpu.analysis.deadlock_lint` — joint lock-acquisition
   graph over the serving scope: lock-order cycles
   (``LOCK-ORDER-CYCLE``) and locks held across blocking operations
   (``LOCK-HELD-BLOCKING``: device calls, fsync, sleeps, socket
   sends, joins, subprocess waits).
7. :mod:`~jepsen_tpu.analysis.walcheck_lint` — crash-consistency
   dominance dataflow on the serve/stream intake paths: every success
   ack must be dominated by a WAL append (``WAL-ACK-BEFORE-JOURNAL``),
   run-dir artifacts must go through tmp + ``os.replace``
   (``ATOMIC-WRITE-DIRECT``), and tmp names in dir-scanned
   directories must be dot-prefixed (``ATOMIC-TMP-SCANNED``).

Findings carry file:line, a rule id, and a severity; a committed
baseline file (:mod:`~jepsen_tpu.analysis.baseline`) suppresses
deliberately-accepted findings so CI gates on *new* ones. Exports:
text, JSON, and SARIF 2.1.0 (:mod:`~jepsen_tpu.analysis.sarif`) for
forge PR annotation. CLI: ``python -m jepsen_tpu lint`` (see
doc/lint.md for the rule catalog, doc/plan.md for ``PLAN-*``).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

ERROR = "error"
WARNING = "warning"
NOTE = "note"

#: Gate order: errors always gate; warnings gate in strict mode; notes
#: never gate (they surface legal-but-noteworthy structure, e.g. a
#: crashed op's forever-pending invoke).
SEVERITIES = (ERROR, WARNING, NOTE)


@dataclass
class Finding:
    """One analysis finding.

    ``anchor`` is the line-number-independent identity used for baseline
    matching: ``<enclosing qualname>/<normalized snippet>`` for code
    findings, a structural key for history findings. Line numbers shift
    on every edit; anchors survive reformatting.
    """

    rule: str
    severity: str
    path: str          # repo-relative where possible
    line: int
    message: str
    anchor: str = ""
    col: int = 0

    def key(self) -> str:
        return f"{self.rule} {self.path}#{self.anchor}"

    def format(self) -> str:
        return (f"{self.path}:{self.line}: {self.severity}: "
                f"[{self.rule}] {self.message}")


def repo_root() -> str:
    """The repository root (parent of the jepsen_tpu package)."""
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def relpath(path: str, root: Optional[str] = None) -> str:
    root = root or repo_root()
    ap = os.path.abspath(path)
    try:
        rp = os.path.relpath(ap, root)
    except ValueError:  # different drive (windows)
        return ap
    return ap if rp.startswith("..") else rp


def summarize(findings: Iterable[Finding]) -> Dict[str, int]:
    """Counts by rule id — the ``# lint:`` summary-line payload."""
    out: Dict[str, int] = {}
    for f in findings:
        out[f.rule] = out.get(f.rule, 0) + 1
    return dict(sorted(out.items()))


def summary_line(findings: Iterable[Finding]) -> str:
    """One-line ``# lint:`` summary: counts by rule, 'clean' when none."""
    counts = summarize(findings)
    if not counts:
        return "# lint: clean"
    return "# lint: " + " ".join(f"{r}={n}" for r, n in counts.items())


def worst_severity(findings: Iterable[Finding]) -> Optional[str]:
    rank = {s: i for i, s in enumerate(SEVERITIES)}
    worst = None
    for f in findings:
        if worst is None or rank.get(f.severity, 99) < rank.get(worst, 99):
            worst = f.severity
    return worst


# ---------------------------------------------------------------------------
# Pass orchestration
# ---------------------------------------------------------------------------

#: Default scan scopes, relative to the repo root. The history pass has
#: no default file scope — it runs over histories handed to it (the
#: pre-search gate, `recover`/`analyze`, or `lint --history FILE`).
DEFAULT_SCOPES = {
    "suite": ("jepsen_tpu/suites",),
    "jax": ("jepsen_tpu/checker", "jepsen_tpu/ops/encode.py",
            "jepsen_tpu/obs", "jepsen_tpu/resilience.py",
            "jepsen_tpu/serve.py", "jepsen_tpu/stream.py"),
    "lockset": ("jepsen_tpu/core.py", "jepsen_tpu/journal.py",
                "jepsen_tpu/nemesis", "jepsen_tpu/obs",
                "jepsen_tpu/serve.py", "jepsen_tpu/stream.py",
                "jepsen_tpu/fleet.py", "jepsen_tpu/checker/engine.py"),
    # the deadlock pass is a JOINT analysis: the acquisition graph
    # spans modules, so its scope is one file set, not per-file
    "deadlock": ("jepsen_tpu/serve.py", "jepsen_tpu/stream.py",
                 "jepsen_tpu/fleet.py", "jepsen_tpu/checker/engine.py",
                 "jepsen_tpu/obs/observatory.py",
                 "jepsen_tpu/obs/federation.py",
                 "jepsen_tpu/obs/straggler.py"),
    "walcheck": ("jepsen_tpu/serve.py", "jepsen_tpu/stream.py",
                 "jepsen_tpu/obs/federation.py",
                 "jepsen_tpu/obs/straggler.py"),
}

PASSES = ("suite", "history", "jax", "lockset", "deadlock", "walcheck",
          "plan")


def _expand(paths: Iterable[str], root: str) -> List[str]:
    out: List[str] = []
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isdir(ap):
            for name in sorted(os.listdir(ap)):
                if name.endswith(".py"):
                    out.append(os.path.join(ap, name))
        elif os.path.exists(ap):
            out.append(ap)
    return out


def lint_files(paths: Iterable[str], passes: Iterable[str] = PASSES,
               root: Optional[str] = None) -> List[Finding]:
    """Run the code passes over explicit files (.py) and history
    artifacts (.jsonl / .wal)."""
    from jepsen_tpu.analysis import (
        deadlock_lint, history_lint, jax_lint, lockset_lint, suite_lint,
        walcheck_lint,
    )
    root = root or repo_root()
    passes = tuple(passes)
    findings: List[Finding] = []
    code_files: List[str] = []
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if not os.path.exists(ap):
            # a typo'd path must not read as "clean" — that is exactly
            # the silent-miss failure mode this subsystem exists to kill
            findings.append(Finding(
                rule="LINT-MISSING-FILE", severity=ERROR,
                path=relpath(ap, root), line=0,
                message="no such file", anchor="missing"))
            continue
        if p.endswith((".jsonl", ".wal")):
            if "history" in passes:
                findings.extend(history_lint.lint_history_file(ap,
                                                               root=root))
            continue
        code_files.append(ap)
        if "suite" in passes:
            findings.extend(suite_lint.lint_file(ap, root=root))
        if "jax" in passes:
            findings.extend(jax_lint.lint_file(ap, root=root))
        if "lockset" in passes:
            findings.extend(lockset_lint.lint_file(ap, root=root))
    # joint passes see all named files at once: cross-module lock
    # edges and journal closures don't exist per-file
    if "deadlock" in passes and code_files:
        findings.extend(deadlock_lint.lint_paths(code_files, root=root))
    if "walcheck" in passes and code_files:
        findings.extend(walcheck_lint.lint_paths(code_files, root=root))
    return findings


def lint_repo(root: Optional[str] = None,
              passes: Iterable[str] = PASSES,
              histories: Iterable[str] = ()) -> List[Finding]:
    """Run every pass at its default scope over the repo.

    ``histories`` optionally adds saved history files (.jsonl/.wal) for
    the history pass; the code passes scan their DEFAULT_SCOPES.
    """
    from jepsen_tpu.analysis import (
        deadlock_lint, history_lint, jax_lint, lockset_lint, suite_lint,
        walcheck_lint,
    )
    root = root or repo_root()
    passes = tuple(passes)
    findings: List[Finding] = []
    if "suite" in passes:
        files = _expand(DEFAULT_SCOPES["suite"], root)
        findings.extend(suite_lint.lint_suites(files, root=root))
    if "jax" in passes:
        for f in _expand(DEFAULT_SCOPES["jax"], root):
            findings.extend(jax_lint.lint_file(f, root=root))
    if "lockset" in passes:
        for f in _expand(DEFAULT_SCOPES["lockset"], root):
            findings.extend(lockset_lint.lint_file(f, root=root))
    if "deadlock" in passes:
        findings.extend(deadlock_lint.lint_paths(
            _expand(DEFAULT_SCOPES["deadlock"], root), root=root))
    if "walcheck" in passes:
        findings.extend(walcheck_lint.lint_paths(
            _expand(DEFAULT_SCOPES["walcheck"], root), root=root))
    if "history" in passes:
        for h in histories:
            ap = h if os.path.isabs(h) else os.path.join(root, h)
            findings.extend(history_lint.lint_history_file(ap, root=root))
    if "plan" in passes:
        # not file-scoped: the plan pass verifies the pinned model ×
        # dims fixture matrix (arithmetic only here — tools/lint_gate.py
        # runs the traced variant in CI)
        from jepsen_tpu.analysis import plan_lint
        findings.extend(plan_lint.lint_matrix())
    return findings
