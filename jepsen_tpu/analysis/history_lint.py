"""Pass 2: structural history validation — the pre-search gate.

A malformed history fed to the device checker used to fail *late*: the
packed encoder mis-pairs ops, the search compiles and runs, and the
verdict is garbage (or the search wedges) after the whole jit cost was
paid. This pass is a fast O(n) host walk that rejects structural damage
with a rule id and an op position *before* any packing or compilation —
the P-compositionality lesson (cheap rejection ahead of expensive
search) applied to input validation.

Rules (see doc/lint.md for the catalog):

==========================  ========  =================================
rule                        severity  what it catches
==========================  ========  =================================
HIST-DECODE                 warning   undecodable lines were skipped
                                      when this history was loaded
                                      (surfaced, not fatal: a truncated
                                      artifact stays analyzable — the
                                      PR-2 degradation contract; any
                                      structural damage the loss caused
                                      gates via the rules below)
HIST-OP-TYPE                error     op ``type`` outside
                                      invoke/ok/fail/info (shared
                                      validation with ``Op.from_dict``)
HIST-UNMATCHED-COMPLETE     error     ok/fail completion from a process
                                      with no open invocation
HIST-PROC-REUSE             error     process reused before completion:
                                      an identical invoke re-issued
                                      while the first is still open
HIST-DANGLING-INVOKE        error     an invocation abandoned without
                                      completion while its process went
                                      on to other ops
HIST-INDEX-ORDER            error     assigned ``index`` values are
                                      non-monotonic
HIST-F-MISMATCH             error     a completion whose ``f`` differs
                                      from its invocation's
HIST-INVOKE-NO-F            warning   an invocation with no ``f``
HIST-UNMATCHED-INFO         note      a bare non-nemesis info marker
                                      (tolerated; knossos semantics)
HIST-OPEN-INVOKE            note      invoke still open at history end
                                      (a legal crashed op)
==========================  ========  =================================

Only *error*-severity findings gate; notes surface legal-but-noteworthy
structure (crashed ops are jepsen semantics, not damage).

The device entry points need only to know whether the gate raises, so
they go through :func:`require_well_formed`: :func:`screen_history`, one
tight walk over ``Op`` slots that proves no error rule can fire, and
the full linter only when the screen is in doubt.
"""

from __future__ import annotations

import os
from typing import Any, Iterable, List, Optional

from jepsen_tpu.analysis import ERROR, Finding, NOTE, WARNING, relpath
from jepsen_tpu.analysis.opcheck import (INVALID_TYPE_FLAG,
                                         invalid_op_type)
from jepsen_tpu.history import Op

#: The nemesis pseudo-process: its ops are all ``info`` and never pair
#: as invoke/complete (core.clj:292), so pairing rules exempt it.
NEMESIS = "nemesis"


class MalformedHistoryError(Exception):
    """Raised by :func:`gate_history` when a history has error-severity
    structural findings. Carries the findings so callers (check_safe,
    the recover path, chaos scenarios) can render rule ids."""

    def __init__(self, findings: List[Finding], where: str = "check"):
        self.findings = findings
        head = "; ".join(f.format() for f in findings[:5])
        more = len(findings) - 5
        if more > 0:
            head += f"; ... {more} more"
        super().__init__(
            f"malformed history rejected before {where}: {head}")


def _get(o: Any, key: str, default=None):
    if isinstance(o, dict):
        return o.get(key, default)
    return getattr(o, key, default)


def lint_history(history: Iterable[Any], source: str = "history",
                 decode_errors: Optional[int] = None) -> List[Finding]:
    """Walk a history once and return its structural findings.

    ``history`` may be a :class:`~jepsen_tpu.history.History`, a list of
    Ops, or a list of raw op dicts. ``decode_errors`` defaults to the
    history's own ``decode_errors`` attribute when present (set by
    ``History.from_jsonl``).
    """
    out: List[Finding] = []

    def add(rule, sev, i, msg, anchor=""):
        out.append(Finding(rule=rule, severity=sev, path=source,
                           line=i + 1, message=msg,
                           anchor=anchor or f"op{i}"))

    if decode_errors is None:
        decode_errors = int(getattr(history, "decode_errors", 0) or 0)
    if decode_errors:
        out.append(Finding(
            rule="HIST-DECODE", severity=WARNING, path=source, line=0,
            message=f"{decode_errors} line(s) were undecodable and "
                    f"skipped when this history was loaded",
            anchor="decode"))

    open_by_proc: dict = {}   # process -> (pos, op)
    last_index = None
    for i, o in enumerate(history):
        typ = _get(o, "type")
        f = _get(o, "f")
        proc = _get(o, "process")
        extra = _get(o, "extra") or {}
        flagged = (extra.get(INVALID_TYPE_FLAG)
                   if isinstance(extra, dict) else None) or \
            (_get(o, INVALID_TYPE_FLAG) if isinstance(o, dict) else None)

        bad = invalid_op_type(typ)
        if bad or flagged:
            add("HIST-OP-TYPE", ERROR, i,
                flagged if isinstance(flagged, str) else bad,
                anchor=f"type/{typ!r}")
            continue  # pairing rules assume a legal type

        idx = _get(o, "index", -1)
        if isinstance(idx, int) and idx >= 0:
            if last_index is not None and idx <= last_index:
                add("HIST-INDEX-ORDER", ERROR, i,
                    f"op index {idx} is not greater than the previous "
                    f"assigned index {last_index}",
                    anchor=f"index/{idx}")
            last_index = idx if last_index is None else max(last_index,
                                                            idx)

        if proc == NEMESIS:
            continue  # nemesis ops never pair

        if typ == "invoke":
            if f is None:
                add("HIST-INVOKE-NO-F", WARNING, i,
                    f"invoke by process {proc!r} has no 'f'",
                    anchor=f"no-f/{proc!r}")
            prev = open_by_proc.get(proc)
            if prev is not None:
                j, prev_op = prev
                if (_get(prev_op, "f") == f
                        and _get(prev_op, "value") == _get(o, "value")):
                    add("HIST-PROC-REUSE", ERROR, i,
                        f"process {proc!r} reused before completion: "
                        f"invoke {f!r} re-issued while the invoke at "
                        f"position {j} is still open",
                        anchor=f"reuse/{proc!r}/{f!r}")
                else:
                    add("HIST-DANGLING-INVOKE", ERROR, j,
                        f"invoke {_get(prev_op, 'f')!r} by process "
                        f"{proc!r} at position {j} was abandoned "
                        f"without a completion (the process went on to "
                        f"invoke {f!r} at position {i})",
                        anchor=f"dangling/{proc!r}/"
                               f"{_get(prev_op, 'f')!r}")
            open_by_proc[proc] = (i, o)
        else:  # a completion
            prev = open_by_proc.pop(proc, None)
            if prev is None:
                if typ == "info":
                    add("HIST-UNMATCHED-INFO", NOTE, i,
                        f"info op {f!r} by process {proc!r} has no "
                        f"open invocation",
                        anchor=f"info/{proc!r}/{f!r}")
                else:
                    add("HIST-UNMATCHED-COMPLETE", ERROR, i,
                        f"{typ} completion {f!r} by process {proc!r} "
                        f"has no open invocation",
                        anchor=f"unmatched/{proc!r}/{f!r}")
            elif f is not None and _get(prev[1], "f") is not None \
                    and _get(prev[1], "f") != f:
                add("HIST-F-MISMATCH", ERROR, i,
                    f"completion f={f!r} does not match the open "
                    f"invocation's f={_get(prev[1], 'f')!r} for "
                    f"process {proc!r}",
                    anchor=f"fmismatch/{proc!r}/{f!r}")

    for proc, (j, op_) in sorted(open_by_proc.items(),
                                 key=lambda kv: kv[1][0]):
        add("HIST-OPEN-INVOKE", NOTE, j,
            f"invoke {_get(op_, 'f')!r} by process {proc!r} is still "
            f"open at history end (a crashed op: legal, linearized "
            f"optionally)",
            anchor=f"open/{proc!r}/{_get(op_, 'f')!r}")
    return out


def errors(findings: Iterable[Finding]) -> List[Finding]:
    return [f for f in findings if f.severity == ERROR]


def gate_enabled() -> bool:
    """The pre-search gate's kill switch (JTPU_HISTORY_GATE, default
    on). Exists for emergencies only: with the gate off, a malformed
    history flows into the packed encoder exactly as before."""
    return os.environ.get("JTPU_HISTORY_GATE", "1").lower() not in (
        "0", "false", "no", "off")


def gate_history(history: Iterable[Any], where: str = "device search",
                 source: str = "history") -> List[Finding]:
    """The mandatory pre-search gate: lint, raise on error findings.

    Returns the full finding list (notes included) when the history
    passes, so callers can surface the ``# lint:`` summary. Raises
    :class:`MalformedHistoryError` carrying rule ids and positions when
    any error-severity finding exists.
    """
    if not gate_enabled():
        return []
    findings = lint_history(history, source=source)
    errs = errors(findings)
    if errs:
        raise MalformedHistoryError(errs, where=where)
    return findings


def screen_history(history: Any) -> bool:
    """True only when no error-severity rule of :func:`lint_history`
    can fire on ``history``; False at the first doubt.

    One walk over the ``Op`` slots, keeping only each open invocation's
    ``f``. It proves what the error rules check: legal types with no
    ``INVALID_TYPE_FLAG``, strictly increasing assigned indexes, no
    invoke on a process with an open invocation, no ok/fail without
    one, no completion whose ``f`` differs from its invocation's.
    Nemesis ops are checked for type and index and never pair. Notes
    and warnings (crashed ops, bare infos, an invoke with no ``f``,
    decode errors) never gate, so they pass. Doubt covers a row that is
    not an ``Op``, an ``extra`` that is not a dict, an index that is not
    an ``int``, any exception, and an input that is not a list or tuple
    (a one-shot iterable must be walked once, by the linter).
    """
    if not isinstance(history, (list, tuple)):
        return False
    # A nemesis op enters ``open_f`` like any other, and every branch
    # that could refuse lets it through: no other process shares its
    # key, so it never pairs, and the common path compares no process
    # with NEMESIS.
    open_f: dict = {}
    pop = open_f.pop
    last = -1
    # Op.index before History.index() assigns one; the identity test
    # is a shortcut, and any other -1 passes the checks below too
    unset = -1
    op_cls = Op
    flag = INVALID_TYPE_FLAG
    nemesis = NEMESIS
    try:
        for o in history:
            if type(o) is not op_cls:
                return False
            extra = o.extra
            if extra is not None and (type(extra) is not dict
                                      or extra.get(flag)):
                return False
            idx = o.index
            if idx is not unset:
                if type(idx) is not int:
                    return False
                if idx > last:
                    last = idx
                elif idx >= 0:
                    return False
            typ = o.type
            proc = o.process
            if typ == "invoke":
                if proc in open_f and proc != nemesis:
                    return False
                open_f[proc] = o.f
            elif typ == "ok" or typ == "fail" or typ == "info":
                pf = pop(proc, pop)
                if pf is pop:
                    if typ != "info" and proc != nemesis:
                        return False
                else:
                    f = o.f
                    if f is not None and pf is not None and pf != f \
                            and proc != nemesis:
                        return False
            else:
                return False
    except Exception:
        # doubt: the linter walks it and raises whatever it raises
        return False
    return True


def require_well_formed(history: Any, where: str = "device search") -> str:
    """The device paths' gate: raise exactly what :func:`gate_history`
    raises, walking the full linter only when :func:`screen_history`
    is in doubt. Returns the path taken, ``"screen"`` or ``"lint"``
    (counted in ``jtpu_history_gate_total{path}``), or ``"off"`` under
    the kill switch, where neither walk runs."""
    if not gate_enabled():
        return "off"
    from jepsen_tpu.obs import metrics as obs_metrics
    path = "screen" if screen_history(history) else "lint"
    obs_metrics.counter(
        "jtpu_history_gate_total",
        "device-path history gate calls, labeled path=screen (the "
        "screen proved the history well formed) or path=lint (the full "
        "linter walked it)").inc(path=path)
    if path == "lint":
        gate_history(history, where=where)
    return path


def lint_history_file(path: str, root: Optional[str] = None
                      ) -> List[Finding]:
    """Lint a saved history artifact (.jsonl via History.from_jsonl,
    .wal via the journal reader) — the offline entry the CLI uses."""
    rp = relpath(path, root)
    if path.endswith(".wal"):
        from jepsen_tpu import journal
        try:
            h, stats = journal.read_wal(path)
        except OSError as e:
            return [Finding(rule="HIST-DECODE", severity=ERROR, path=rp,
                            line=0, message=f"unreadable WAL: {e}",
                            anchor="decode")]
        return lint_history(h, source=rp,
                            decode_errors=stats.get("corrupt", 0))
    from jepsen_tpu.history import History
    try:
        with open(path) as f:
            h = History.from_jsonl(f.read())
    except OSError as e:
        return [Finding(rule="HIST-DECODE", severity=ERROR, path=rp,
                        line=0, message=f"unreadable history: {e}",
                        anchor="decode")]
    return lint_history(h, source=rp)
