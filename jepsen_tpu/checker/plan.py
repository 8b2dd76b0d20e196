"""Ahead-of-time search-plan verification — prove a (history, kernel,
capacity, window, mesh) configuration will compile, fit, and shard
cleanly BEFORE any device time is spent.

Everything the device search will do is decidable on the host from the
history's *dimensions* alone: the padded shape buckets
(:func:`~jepsen_tpu.checker.tpu._bucket`,
:func:`~jepsen_tpu.checker.tpu._crash_width`), the escalation rungs
(:func:`~jepsen_tpu.checker.tpu._ladder_for`), the carry / candidate /
sort working set each rung allocates, the mesh-divisibility
preconditions of :func:`~jepsen_tpu.checker.tpu.check_packed_sharded`,
and the int32 encoding bounds (event indices vs :data:`RET_INF`, the
merge-sort key base ``MAXK``). Today those facts are discovered
*reactively* — allocator ``RESOURCE_EXHAUSTED`` answered by
pool-halving, ``ValueError`` deep inside the sharded checker, silent
int-width wraparound. This module evaluates them *ahead of time*:

* **enumeration** — the shape-bucket universe actually reachable from
  ``check_history_tpu`` / ``check_keyed_tpu`` / ``check_packed_sharded``
  for given dims (every (capacity, window, expand) rung × padded
  required width × crashed width × unroll × kind);
* **abstract evaluation** — each bucket's jit factory is traced with
  ``jax.eval_shape`` over ``ShapeDtypeStruct`` inputs (zero XLA
  compiles, zero device executions) and optionally priced with the
  ``lower()``-only XLA cost analysis (:func:`_cost_analysis`: lowering,
  no compile);
* **footprint math** — the packed-column bytes (exactly
  :func:`~jepsen_tpu.checker.tpu._cols_nbytes`), the search carry
  (exactly :func:`~jepsen_tpu.checker.tpu._carry0_host`), and a
  documented model of the expansion-grid + merge-sort working set,
  checked against the device ``bytes_limit``
  (:mod:`jepsen_tpu.obs.devices`) so ``PLAN-OOM`` fires before the
  reactive pool-halving path ever would;
* **admission gating** — the mandatory pre-search gate in
  :mod:`jepsen_tpu.checker.tpu` / :mod:`jepsen_tpu.resilience` (kill
  switch ``JTPU_PLAN_GATE=0``) picks the cheapest *valid* plan,
  records rejected candidates in the result's ``plan`` entry, and
  seeds the supervised search's initial pool from the predicted
  footprint instead of always starting at the rung maximum.

Rule catalog (``PLAN-*``) and the JSON/SARIF schemas: doc/plan.md.
Finding/SARIF integration: :mod:`jepsen_tpu.analysis.plan_lint`.

Graceful degradation is the contract everywhere: a backend with no
memory statistics (CPU) yields no bytes-limit, so ``PLAN-OOM`` cannot
fire and tier-1 ``JAX_PLATFORMS=cpu`` behavior is unchanged;
``JTPU_PLAN_BYTES_LIMIT`` pins a limit explicitly (tests, CI, and the
admission-control daemon of ROADMAP item 1).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from jepsen_tpu.analysis import ERROR, NOTE, WARNING
from jepsen_tpu.checker import tpu as T
from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.ops.encode import PackedHistory, RET_INF

#: The merge-sort invalid-row key base in _search_fn (MAXK = 1 << 30):
#: a valid row's sort key is MAXK - depth, an invalid row's MAXK + 1 +
#: k — both must stay inside int32, which bounds the op count a plan
#: may admit. Folded here exactly like jax_lint's JAX-INT32-OVERFLOW
#: pass folds the literal at its definition site.
MAXK = 1 << 30
INT32_MAX = 2 ** 31 - 1

#: Minimum per-device expansion slice (rows) below which a pool-sharded
#: search is straggler-bound by construction: each mesh shard owns
#: expand/naxis contiguous expansion rows, and slices thinner than this
#: leave most of a shard's vector lanes idle through the step math —
#: the imbalance signature jtpu_shard_imbalance_ratio measures live.
SHARD_MIN_EXPAND_ROWS = 8

_PLAN_REJECTS = obs_metrics.counter(
    "jtpu_plan_rejects_total",
    "search plans rejected ahead of device time, labeled by rule")
_PLAN_SEEDED = obs_metrics.counter(
    "jtpu_plan_seeded_total",
    "supervised-search pools seeded below the rung maximum because the "
    "predicted footprint exceeded the device bytes-limit")
_PLAN_PREDICTED = obs_metrics.gauge(
    "jtpu_plan_predicted_bytes",
    "predicted device working-set bytes of the most recently gated "
    "search plan")


# ---------------------------------------------------------------------------
# Dimensions and candidates
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PlanDims:
    """The history dimensions a plan depends on — everything else about
    the search shape derives from these four numbers (plus the kernel).

    ``n_events`` is the raw history's event count (invocations +
    completions, nemesis included), which bounds the inv/ret event
    indices the packed encoding stores; None estimates it as
    ``2 * (n_required + n_crashed)``."""

    n_required: int
    n_crashed: int = 0
    window_needed: int = 1
    n_events: Optional[int] = None
    keys: int = 1

    @classmethod
    def from_packed(cls, p: PackedHistory) -> "PlanDims":
        nr = p.n_required
        wneed = T._window_needed(p) if nr else 0
        ev = 0
        if p.n:
            finite = p.ret[p.ret != RET_INF]
            ev = int(max(int(p.inv.max(initial=0)),
                         int(finite.max(initial=0)))) + 1
        return cls(n_required=nr, n_crashed=p.n - nr,
                   window_needed=max(wneed, 1), n_events=ev)

    @classmethod
    def from_history(cls, history, model) -> Optional["PlanDims"]:
        """Pack-and-measure; None when the model has no integer kernel
        (the plan question is then moot — the object search runs)."""
        from jepsen_tpu.ops.encode import pack_with_init
        pk = pack_with_init(history, model)
        if pk is None:
            return None
        return cls.from_packed(pk[0])

    def events(self) -> int:
        if self.n_events is not None:
            return int(self.n_events)
        return 2 * (self.n_required + self.n_crashed)

    def to_dict(self) -> Dict[str, Any]:
        return {"n-required": self.n_required,
                "n-crashed": self.n_crashed,
                "window-needed": self.window_needed,
                "n-events": self.events(), "keys": self.keys}


@dataclass(frozen=True)
class Candidate:
    """One concrete executable shape the search could run: a ladder rung
    bound to its padded buckets. ``kind`` matches the jit factory that
    would compile it (single / segment / batch / sharded)."""

    kind: str
    capacity: int
    window: int
    expand: Optional[int]
    unroll: int
    breq: int                 # padded required-section width (_bucket)
    crw: int                  # padded crashed-section width (_crash_width)
    keys: int = 1
    mesh_axis: Optional[int] = None
    tiebreak: str = "lex"

    @property
    def expand_eff(self) -> int:
        return min(self.expand or self.capacity, self.capacity)

    @property
    def mask_words(self) -> int:
        return (self.window + 31) // 32

    @property
    def crash_words(self) -> int:
        return max((self.crw + 31) // 32, 1)

    @property
    def rung(self) -> tuple:
        return (self.capacity, self.window, self.expand)

    def label(self) -> str:
        exp = self.expand if self.expand is not None else "all"
        base = (f"{self.kind} {self.capacity}/{self.window}/{exp} "
                f"@{self.breq}+{self.crw}")
        if self.keys > 1:
            base += f" x{self.keys}"
        if self.mesh_axis:
            base += f" {T.POOL_AXIS}={self.mesh_axis}"
        return base


def _keyed_auto_ladder() -> tuple:
    """The keyed batch's adaptive escalation schedule, exactly as
    check_keyed_tpu builds it (slim entry rung, dense double-expansion
    rung, narrow escalations, wide tail)."""
    lad0 = T._capacity_ladder()
    cap0, exp0 = lad0[0]
    return (((cap0, 32, exp0), (cap0, 32, max(8, exp0 * 2)))
            + tuple((c, 32, e) for c, e in lad0[1:])
            + ((512, 64, 512), (4096, 128, 1024), (16384, 128, 4096)))


def enumerate_candidates(dims: PlanDims,
                         capacity: Optional[int] = None,
                         window: Optional[int] = None,
                         expand: Optional[int] = None,
                         mesh_axis: Optional[int] = None,
                         kinds: Optional[Sequence[str]] = None
                         ) -> List[Candidate]:
    """The bucket universe reachable for these dims: deterministic,
    exhaustive, cheapest-first within each kind.

    With explicit capacity/window/expand the universe collapses to the
    pinned rung (what check_*_tpu would run); otherwise it is the full
    escalation ladder at the history's needed window. ``kinds`` defaults
    to (single, segment) for one key, (batch,) for keyed dims, plus
    (sharded,) when ``mesh_axis`` is given."""
    nr = max(dims.n_required, 1)
    breq = T._bucket(nr)
    crw = T._crash_width(dims.n_crashed)
    if crw is None:
        return []  # crashed-set overflow: a dims-level finding, no plans
    unroll = T._unroll_factor()
    if kinds is None:
        kinds = (("batch",) if dims.keys > 1 else ("single", "segment"))
        if mesh_axis:
            kinds = tuple(kinds) + ("sharded",)
    out: List[Candidate] = []
    if capacity is not None:
        ladder = ((capacity, window or T.WINDOW, expand),)
    else:
        ladder = T._ladder_for(max(dims.window_needed, 1))
    for kind in kinds:
        if kind in ("single", "segment"):
            for cap, win, exp in ladder:
                out.append(Candidate(kind=kind, capacity=cap, window=win,
                                     expand=exp, unroll=unroll,
                                     breq=breq, crw=crw))
        elif kind == "batch":
            if capacity is not None:
                klad = ladder
            else:
                klad = _keyed_auto_ladder()
            for step, (cap, win, exp) in enumerate(klad):
                # the slim entry rung runs hash tie-break + unroll 2
                # (see check_keyed_tpu); later rungs are lex / unroll 1
                first = capacity is None and step <= 1
                out.append(Candidate(
                    kind="batch", capacity=cap, window=win, expand=exp,
                    unroll=(T._unroll_factor(2) if first and step == 0
                            else unroll),
                    breq=breq, crw=crw, keys=dims.keys,
                    tiebreak="hash" if first else "lex"))
        elif kind == "sharded":
            naxis = int(mesh_axis or 1)
            cap = capacity if capacity is not None else 4096
            win = window
            if win is None:
                win = T._window_bucket(max(dims.window_needed, 1))
            exp = expand
            if exp is None:
                # best-first default at ~capacity/8 rounded up to the
                # mesh axis (check_packed_sharded's derivation)
                per = max(1, cap // 8)
                exp = max(naxis, -(-per // naxis) * naxis)
            out.append(Candidate(kind="sharded", capacity=cap,
                                 window=win, expand=exp, unroll=unroll,
                                 breq=breq, crw=crw, mesh_axis=naxis))
    return out


# ---------------------------------------------------------------------------
# Footprint math
# ---------------------------------------------------------------------------


def cols_nbytes(breq: int, crw: int, keys: int = 1) -> int:
    """Host->device payload of the packed columns, exactly matching
    :func:`jepsen_tpu.checker.tpu._cols_nbytes` on the arrays
    ``_split_packed`` produces: seven int32[breq] columns (f, v1, v2,
    ro, fr, inv, ret), the int32[breq+1] suffix-min, five int32[crw]
    crashed columns, and the nr/ini scalars."""
    return 4 * (7 * breq + (breq + 1) + 5 * crw + 2) * keys


def carry_nbytes(capacity: int, window: int, crw: int) -> int:
    """Bytes of one search carry, exactly matching
    :func:`jepsen_tpu.checker.tpu._carry0_host`: per-row int32 k/state/
    pool_k/pool_state, uint32 mask[MW] and cmask[MC], two bool columns,
    plus the five flag/count scalars."""
    mw = (window + 31) // 32
    mc = max((crw + 31) // 32, 1)
    return capacity * (18 + 4 * mw + 4 * mc) + 11


def footprint(cand: Candidate) -> Dict[str, int]:
    """Predicted device working set of one candidate, by component.

    ``cols-bytes`` and ``carry-bytes`` are exact (they mirror the host
    arrays byte for byte). ``grid-bytes`` and ``sort-bytes`` model the
    per-iteration intermediates of ``_search_fn``: the [E, W] required
    successor grid, the [E] closure rows, the [E, CR] crashed grid
    (each row: k + mask words + cmask words + state + valid flag), and
    the lexsort over the merged R = E*W + E + E*CR + (C - E) rows —
    operands double-buffered, one int32 array per sort term. The model
    is deliberately a ceiling on the steady-state HLO buffers, not the
    transient fusion copies; JTPU_PLAN_BYTES_LIMIT calibrates the
    admission threshold per deployment."""
    C, W = cand.capacity, cand.window
    E, CR = cand.expand_eff, cand.crw
    MW, MC = cand.mask_words, cand.crash_words
    row = 4 + 4 * MW + 4 * MC + 4 + 1  # k, mask, cmask, state, valid
    grid = (E * W + E + E * CR) * row
    merged = E * W + E + E * CR + max(C - E, 0)
    # lex sort terms: key1, fk, MW mask words, fs (+ popcount + MC
    # crash words when the crashed section exists); hash adds the mix
    # word + index payload instead of the mask words
    mcr = (CR + 31) // 32
    if cand.tiebreak == "hash":
        terms = 2 + 1 + (1 + mcr if CR else 0)
    else:
        terms = 2 + MW + 1 + (1 + mcr if CR else 0)
    sort = 2 * merged * terms * 4
    carry = carry_nbytes(C, W, CR)
    ncarry = 3 if cand.kind == "segment" else 2  # seg: carry is an input too
    per_key = ncarry * carry + grid + sort
    cols = cols_nbytes(cand.breq, CR, cand.keys)
    total = cols + per_key * cand.keys
    out = {"cols-bytes": cols, "carry-bytes": carry * cand.keys,
           "grid-bytes": grid * cand.keys, "sort-bytes": sort * cand.keys,
           "total-bytes": total}
    if cand.mesh_axis:
        # the pool, grids, and sort rows are partitioned over the mesh
        # axis; the packed columns are replicated per device
        out["per-device-bytes"] = cols + -(-per_key // cand.mesh_axis)
    return out


def plan_bytes_limit() -> Optional[int]:
    """The admission byte budget: JTPU_PLAN_BYTES_LIMIT when set (tests,
    CI, daemon config), else the smallest device allocator limit the
    backend reports (:mod:`jepsen_tpu.obs.devices`), else None — and
    with None the footprint check is inert, which is exactly the CPU
    tier-1 contract."""
    v = os.environ.get("JTPU_PLAN_BYTES_LIMIT")
    if v:
        try:
            return int(v)
        except ValueError:
            pass
    from jepsen_tpu.obs import devices as obs_devices
    limits = [r["bytes-limit"] for r in obs_devices.poll()
              if r.get("bytes-limit")]
    return min(limits) if limits else None


# ---------------------------------------------------------------------------
# Arithmetic verification (no jax required)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PlanIssue:
    rule: str
    severity: str
    message: str
    label: str = ""           # candidate label, "" for dims-level issues

    def to_dict(self) -> Dict[str, Any]:
        return {"rule": self.rule, "severity": self.severity,
                "message": self.message, "label": self.label}


def check_dims(dims: PlanDims) -> List[PlanIssue]:
    """Dims-level safety: the int32 encoding bounds and the crashed-set
    width, independent of any rung choice."""
    issues: List[PlanIssue] = []
    ev = dims.events()
    if ev >= int(RET_INF):
        issues.append(PlanIssue(
            "PLAN-INT32-OVERFLOW", ERROR,
            f"{ev} history events: event indices reach the RET_INF "
            f"sentinel ({int(RET_INF)}) — inv/ret columns would "
            f"silently alias crashed ops"))
    nr = dims.n_required
    if nr and T._bucket(nr) + T.MAX_WINDOW >= MAXK:
        issues.append(PlanIssue(
            "PLAN-INT32-OVERFLOW", ERROR,
            f"padded required width {T._bucket(nr)}: the merge-sort "
            f"key MAXK+1+k ({MAXK}+1+k) leaves int32 — the pool "
            f"ordering would invert"))
    budget = 2 * (nr + dims.n_crashed) + 256
    if budget > INT32_MAX:
        issues.append(PlanIssue(
            "PLAN-INT32-OVERFLOW", ERROR,
            f"level budget {budget} does not fit the int32 level "
            f"counter"))
    if dims.n_crashed > T.CRASH_MAX:
        issues.append(PlanIssue(
            "PLAN-CRASH-WIDTH", ERROR,
            f"{dims.n_crashed} crashed ops exceed the crashed-set "
            f"width {T.CRASH_MAX} (the device path would answer "
            f"UNKNOWN after packing; route to the native engine)"))
    if dims.window_needed > T.MAX_WINDOW:
        issues.append(PlanIssue(
            "PLAN-WINDOW-UNBOUNDED", WARNING,
            f"needed candidate window {dims.window_needed} exceeds "
            f"MAX_WINDOW {T.MAX_WINDOW}: overflow is inevitable, so "
            f"the device search can only hunt a witness, never refute"))
    return issues


def check_candidate(cand: Candidate, dims: PlanDims,
                    bytes_limit: Optional[int]) -> List[PlanIssue]:
    """Candidate-level safety: window bounds, mesh divisibility and
    skew, and the footprint-vs-limit admission check."""
    issues: List[PlanIssue] = []
    lbl = cand.label()
    if cand.window > T.MAX_WINDOW:
        issues.append(PlanIssue(
            "PLAN-WINDOW", ERROR,
            f"window {cand.window} > MAX_WINDOW {T.MAX_WINDOW}: the "
            f"search carries at most {T.MAX_WINDOW // 32} mask words",
            lbl))
    if cand.expand is not None and cand.expand > cand.capacity:
        issues.append(PlanIssue(
            "PLAN-EXPAND-CLAMPED", NOTE,
            f"expand {cand.expand} exceeds capacity {cand.capacity}; "
            f"the search clamps it to the pool size", lbl))
    if cand.mesh_axis:
        naxis = cand.mesh_axis
        if cand.capacity % naxis or cand.expand_eff % naxis:
            issues.append(PlanIssue(
                "PLAN-SHARD-INDIVISIBLE", ERROR,
                f"mesh axis {naxis} must divide capacity "
                f"{cand.capacity} and expand {cand.expand_eff} — the "
                f"SPMD partitioner cannot split the pool rows evenly",
                lbl))
        else:
            per = cand.expand_eff // naxis
            if per < SHARD_MIN_EXPAND_ROWS:
                issues.append(PlanIssue(
                    "PLAN-SHARD-SKEW", WARNING,
                    f"{per} expansion row(s) per device (expand "
                    f"{cand.expand_eff} over {naxis} shards): below "
                    f"{SHARD_MIN_EXPAND_ROWS} rows the global sort "
                    f"concentrates the live frontier on one shard and "
                    f"the others idle (straggler regime)", lbl))
    if bytes_limit is not None:
        fp = footprint(cand)
        need = fp.get("per-device-bytes", fp["total-bytes"])
        if need > bytes_limit:
            issues.append(PlanIssue(
                "PLAN-OOM", ERROR,
                f"predicted working set {need} B exceeds the device "
                f"bytes-limit {bytes_limit} B (carry "
                f"{fp['carry-bytes']} B + grids {fp['grid-bytes']} B "
                f"+ sort {fp['sort-bytes']} B + columns "
                f"{fp['cols-bytes']} B) — the reactive path would "
                f"OOM and halve; reject or shrink ahead of time", lbl))
    return issues


# ---------------------------------------------------------------------------
# Abstract evaluation (jax required; zero compiles, zero executions)
# ---------------------------------------------------------------------------

#: (kernel id, candidate identity) -> {"ok": bool, "error": str|None,
#: "cost": dict|None}; tracing the same bucket twice is pure waste.
_TRACE_MEMO: Dict[tuple, Dict[str, Any]] = {}


def _col_structs(cand: Candidate, jax) -> list:
    """ShapeDtypeStructs matching _split_packed's _COLS layout."""
    i32 = np.int32
    shapes = {
        "f": (cand.breq,), "v1": (cand.breq,), "v2": (cand.breq,),
        "ro": (cand.breq,), "fr": (cand.breq,), "inv": (cand.breq,),
        "ret": (cand.breq,), "sm": (cand.breq + 1,),
        "cf": (cand.crw,), "cv1": (cand.crw,), "cv2": (cand.crw,),
        "cinv": (cand.crw,), "cps": (cand.crw,), "nr": (), "ini": (),
    }
    lead = (cand.keys,) if cand.kind == "batch" else ()
    return [jax.ShapeDtypeStruct(lead + shapes[c], i32) for c in T._COLS]


def _carry_structs(cand: Candidate, jax) -> tuple:
    """ShapeDtypeStructs matching _carry0_host's checkpoint layout."""
    C = cand.capacity
    mw, mc = cand.mask_words, cand.crash_words
    S = jax.ShapeDtypeStruct
    return (S((C,), np.int32), S((C, mw), np.uint32),
            S((C, mc), np.uint32), S((C,), np.int32), S((C,), np.bool_),
            S((), np.bool_), S((), np.bool_), S((), np.bool_),
            S((), np.int32), S((), np.int32),
            S((C,), np.int32), S((C,), np.int32), S((C,), np.bool_))


def _cost_analysis(fn, args) -> Optional[Dict[str, float]]:
    """``fn.lower(*args).cost_analysis()`` normalized to
    ``{"flops", "bytes-accessed"}`` floats; None when the backend or
    jax version does not support it. The HLO analysis counts a while
    body once, so for the search executables it reads per level."""
    ca = fn.lower(*args).cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get("flops", 0.0) or 0.0)
    byts = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0 and byts <= 0:
        return None
    return {"flops": flops, "bytes-accessed": byts}


def trace_candidate(cand: Candidate, kernel, cost: bool = False,
                    mesh=None) -> Dict[str, Any]:
    """Abstractly evaluate one candidate's jit factory: ``jax.eval_shape``
    proves the bucket traces (shape errors surface here, with zero XLA
    compiles and zero device executions), and with ``cost=True`` the
    ``lower()``-only XLA cost analysis predicts per-level flops /
    bytes-accessed (:func:`_cost_analysis`, no compile).

    Returns ``{"ok", "error", "cost"}``; memoized per bucket. A sharded
    candidate needs a real mesh to trace (with_sharding_constraint); when
    none is supplied the result is ``ok=None`` (untraceable here, not
    broken).

    Without ``cost``, a ``single`` candidate reads its ``segment``
    twin's trace: both executables wrap one ``_search_fn`` body (carry
    set-up, level step, loop), and the segment's trace runs all of it
    plus the checkpoint carry's layout, so tracing the body twice finds
    nothing new."""
    if cand.kind == "single" and not cost:
        return trace_candidate(replace(cand, kind="segment"), kernel,
                               mesh=mesh)
    key = (T._kernel_key(kernel), cand.kind, cand.capacity, cand.window,
           cand.expand, cand.unroll, cand.breq, cand.crw, cand.keys,
           cand.tiebreak, cand.mesh_axis, bool(cost))
    hit = _TRACE_MEMO.get(key)
    if hit is not None:
        return dict(hit)
    out: Dict[str, Any] = {"ok": None, "error": None, "cost": None}
    if not T.HAVE_JAX:
        out["error"] = "jax unavailable"
        _TRACE_MEMO[key] = out
        return dict(out)
    import jax
    kid = T._kernel_key(kernel)
    try:
        if cand.kind == "segment":
            fn = T._jit_segment(kid, cand.capacity, cand.window,
                                cand.expand, cand.unroll)
            args = (_col_structs(cand, jax)
                    + [jax.ShapeDtypeStruct((), np.int32),
                       _carry_structs(cand, jax)])
        elif cand.kind == "batch":
            fn = T._jit_batch(kid, cand.capacity, cand.window,
                              cand.expand, cand.unroll,
                              tiebreak=cand.tiebreak)
            args = _col_structs(cand, jax)
        elif cand.kind == "sharded":
            if mesh is None:
                out["error"] = ("sharded bucket needs a mesh to trace; "
                                "arithmetic checks only")
                _TRACE_MEMO[key] = out
                return dict(out)
            fn = T._jit_single(kid, cand.capacity, cand.window,
                               cand.expand, cand.unroll, T.POOL_AXIS)
            args = _col_structs(cand, jax)
        else:
            fn = T._jit_single(kid, cand.capacity, cand.window,
                               cand.expand, cand.unroll)
            args = _col_structs(cand, jax)

        def run():
            jax.eval_shape(fn, *args)
            if cost:
                try:
                    return _cost_analysis(fn, args)
                except Exception:  # noqa: BLE001 — cost is best-effort
                    return None
            return None

        if cand.kind == "sharded":
            with T._mesh_context(mesh):
                out["cost"] = run()
        else:
            out["cost"] = run()
        out["ok"] = True
    except Exception as e:  # noqa: BLE001 — the trace failure IS the finding
        out["ok"] = False
        out["error"] = f"{type(e).__name__}: {e}"
    _TRACE_MEMO[key] = out
    return dict(out)


# ---------------------------------------------------------------------------
# The analyzer
# ---------------------------------------------------------------------------


def analyze(dims: PlanDims, kernel=None,
            capacity: Optional[int] = None,
            window: Optional[int] = None,
            expand: Optional[int] = None,
            mesh_axis: Optional[int] = None,
            mesh=None,
            bytes_limit: Optional[int] = None,
            use_device_limit: bool = True,
            trace: bool = False, cost: bool = False,
            kinds: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    """Verify the whole candidate universe for these dims. Pure host
    work: arithmetic always; with ``trace=True`` every bucket is also
    abstract-evaluated (requires ``kernel``), with ``cost=True`` priced.

    Returns the plan report::

        {"dims": {...}, "bytes-limit": int|None,
         "issues": [{rule, severity, message, label}],
         "candidates": [{"label", "kind", "rung", "breq",
                         "crash-width", "unroll", "footprint": {...},
                         "status": "ok"|"rejected", "issues": [...],
                         "traced": bool|None, "cost": {...}|None}],
         "selected": label|None}

    ``selected`` is the cheapest candidate with no error-severity
    issues — enumeration order is cost-ascending by construction, so
    first-valid IS cheapest-valid."""
    if mesh is not None and mesh_axis is None:
        mesh_axis = int(mesh.shape[T.POOL_AXIS])
    limit = bytes_limit
    if limit is None and use_device_limit:
        limit = plan_bytes_limit()
    dims_issues = check_dims(dims)
    cands = enumerate_candidates(dims, capacity=capacity, window=window,
                                 expand=expand, mesh_axis=mesh_axis,
                                 kinds=kinds)
    issues: List[PlanIssue] = list(dims_issues)
    dims_fatal = any(i.severity == ERROR for i in dims_issues)
    rows: List[Dict[str, Any]] = []
    selected = None
    for cand in cands:
        ci = check_candidate(cand, dims, limit)
        traced = None
        ccost = None
        if trace and kernel is not None and not dims_fatal \
                and not any(i.severity == ERROR for i in ci):
            tr = trace_candidate(cand, kernel, cost=cost, mesh=mesh)
            traced = tr["ok"]
            ccost = tr["cost"]
            if tr["ok"] is False:
                ci = ci + [PlanIssue(
                    "PLAN-TRACE", ERROR,
                    f"bucket fails abstract evaluation: {tr['error']}",
                    cand.label())]
        issues.extend(ci)
        bad = dims_fatal or any(i.severity == ERROR for i in ci)
        row = {"label": cand.label(), "kind": cand.kind,
               "rung": list(cand.rung), "breq": cand.breq,
               "crash-width": cand.crw, "unroll": cand.unroll,
               "footprint": footprint(cand),
               "status": "rejected" if bad else "ok",
               "issues": [i.to_dict() for i in ci]}
        if traced is not None:
            row["traced"] = traced
        if ccost:
            row["cost"] = ccost
        rows.append(row)
        if selected is None and not bad:
            selected = cand.label()
    return {"dims": dims.to_dict(), "bytes-limit": limit,
            "issues": [i.to_dict() for i in issues],
            "candidates": rows, "selected": selected}


def summary_line(history, model) -> str:
    """One ``# plan:`` line for `analyze`/`recover`/bench output:
    candidate count, the cheapest valid plan, predicted footprint, and
    the byte budget — or the rejection rules. Arithmetic only (no
    tracing); never raises."""
    try:
        dims = PlanDims.from_history(history, model)
        if dims is None:
            return "# plan: no integer kernel (object search; unplanned)"
        rep = analyze(dims)
        if rep["selected"] is None:
            rules = sorted({i["rule"] for i in rep["issues"]
                            if i["severity"] == ERROR})
            return ("# plan: REJECTED " + " ".join(rules)
                    + f" over {len(rep['candidates'])} candidate(s)")
        sel = next(c for c in rep["candidates"]
                   if c["label"] == rep["selected"])
        fp = sel["footprint"]["total-bytes"]
        lim = rep["bytes-limit"]
        rejected = sum(1 for c in rep["candidates"]
                       if c["status"] == "rejected")
        return (f"# plan: {len(rep['candidates'])} candidate(s), "
                f"{rejected} rejected, cheapest {rep['selected']}, "
                f"predicted {fp / 1e6:.2f} MB, "
                f"limit {'n/a' if lim is None else f'{lim / 1e6:.1f} MB'}")
    except Exception as e:  # noqa: BLE001 — a summary must never break a run
        return f"# plan: unavailable ({type(e).__name__}: {e})"


# ---------------------------------------------------------------------------
# The pre-search gate (checker/tpu.py + resilience.py call sites)
# ---------------------------------------------------------------------------


def gate_enabled() -> bool:
    """The mandatory pre-search plan gate, kill switch JTPU_PLAN_GATE=0
    (mirrors JTPU_HISTORY_GATE's contract)."""
    return os.environ.get("JTPU_PLAN_GATE", "").strip() != "0"


def _reject(report: Dict[str, Any], where: str):
    from jepsen_tpu.analysis.plan_lint import (PlanRejectedError,
                                               findings_from_report)
    findings = findings_from_report(report)
    errs = sorted({f.rule for f in findings if f.severity == ERROR})
    for r in errs:
        _PLAN_REJECTS.inc(rule=r)
    raise PlanRejectedError(
        f"search plan rejected before {where}: "
        + " ".join(errs), findings=findings, report=report)


def _entry(report: Dict[str, Any]) -> Dict[str, Any]:
    """The compact ``plan`` entry attached to checker results: the
    selected plan plus every rejected candidate with its rules."""
    rejected = [{"label": c["label"], "rung": c["rung"],
                 "rules": sorted({i["rule"] for i in c["issues"]
                                  if i["severity"] == ERROR})}
                for c in report["candidates"] if c["status"] == "rejected"]
    sel = next((c for c in report["candidates"]
                if c["label"] == report["selected"]), None)
    entry = {"selected": report["selected"],
             "bytes-limit": report["bytes-limit"],
             "rejected": rejected}
    if sel is not None:
        entry["predicted-bytes"] = sel["footprint"]["total-bytes"]
        _PLAN_PREDICTED.set(float(entry["predicted-bytes"]))
    return entry


def gate_ladder(p: PackedHistory, kernel, ladder: tuple, kind: str,
                explicit: bool, keys: int = 1,
                derate: bool = False,
                where: str = "the device search"
                ) -> Tuple[tuple, Dict[str, Any]]:
    """Gate an escalation ladder before any packing-adjacent jit work.

    Returns ``(valid_ladder, plan_entry)`` — the rungs that survive the
    arithmetic checks, cheapest first, plus the result's ``plan`` entry.
    Raises :class:`~jepsen_tpu.analysis.plan_lint.PlanRejectedError`
    when nothing survives (and always, immediately, on dims-level
    errors or an explicit pinned rung that fails).

    ``derate=True`` (the supervised auto-ladder) keeps footprint-heavy
    rungs in the ladder — :func:`seed_rung` will shrink their initial
    pool at run time instead — and only rejects when even the policy
    floor cannot fit.

    ``p`` is a :class:`PackedHistory` or, for the keyed batch (whose
    dims aggregate over keys), a prebuilt :class:`PlanDims`."""
    dims = p if isinstance(p, PlanDims) else PlanDims.from_packed(p)
    if keys > 1 and dims.keys != keys:
        dims = PlanDims(dims.n_required, dims.n_crashed,
                        dims.window_needed, dims.n_events, keys=keys)
    limit = plan_bytes_limit()
    nr = max(dims.n_required, 1)
    breq = T._bucket(nr)
    crw = T._crash_width(dims.n_crashed)
    report: Dict[str, Any] = {"dims": dims.to_dict(),
                              "bytes-limit": limit, "issues": [],
                              "candidates": [], "selected": None}
    dims_issues = check_dims(dims)
    report["issues"] = [i.to_dict() for i in dims_issues]
    if any(i.severity == ERROR for i in dims_issues) or crw is None:
        _reject(report, where)
    unroll = T._unroll_factor()
    kept: list = []
    for cap, win, exp in ladder:
        cand = Candidate(kind=kind, capacity=cap, window=win, expand=exp,
                         unroll=unroll, breq=breq, crw=crw, keys=keys)
        ci = check_candidate(cand, dims, limit)
        oom_only = (ci and all(i.rule == "PLAN-OOM" for i in ci
                               if i.severity == ERROR))
        bad = any(i.severity == ERROR for i in ci)
        if bad and derate and oom_only and not explicit:
            # the supervised search will seed this rung's pool down to
            # fit (progress over rejection); reject only if even the
            # smallest seedable pool cannot fit
            floor = Candidate(kind=kind, capacity=8, window=win,
                              expand=exp, unroll=unroll, breq=breq,
                              crw=crw, keys=keys)
            if not any(i.severity == ERROR
                       for i in check_candidate(floor, dims, limit)):
                bad = False
                ci = ci + [PlanIssue(
                    "PLAN-SEEDED", NOTE,
                    "footprint exceeds the limit at full capacity; the "
                    "supervised search seeds a smaller initial pool",
                    cand.label())]
        row = {"label": cand.label(), "kind": kind,
               "rung": list(cand.rung), "breq": breq, "crash-width": crw,
               "unroll": unroll, "footprint": footprint(cand),
               "status": "rejected" if bad else "ok",
               "issues": [i.to_dict() for i in ci]}
        report["candidates"].append(row)
        report["issues"].extend(i.to_dict() for i in ci)
        if not bad:
            kept.append((cap, win, exp))
            if report["selected"] is None:
                report["selected"] = cand.label()
    if not kept:
        _reject(report, where)
    return tuple(kept), _entry(report)


def gate_sharded(p: PackedHistory, kernel, naxis: int, capacity: int,
                 window: int, expand: int,
                 where: str = "the pool-sharded device search"
                 ) -> Dict[str, Any]:
    """Gate the single pool-sharded plan (mesh divisibility, skew,
    footprint, widths). Raises PlanRejectedError on any error-severity
    issue; returns the ``plan`` entry otherwise."""
    dims = PlanDims.from_packed(p)
    limit = plan_bytes_limit()
    crw = T._crash_width(dims.n_crashed)
    report: Dict[str, Any] = {"dims": dims.to_dict(),
                              "bytes-limit": limit, "issues": [],
                              "candidates": [], "selected": None}
    dims_issues = check_dims(dims)
    report["issues"] = [i.to_dict() for i in dims_issues]
    if any(i.severity == ERROR for i in dims_issues) or crw is None:
        _reject(report, where)
    cand = Candidate(kind="sharded", capacity=capacity, window=window,
                     expand=expand, unroll=T._unroll_factor(),
                     breq=T._bucket(max(dims.n_required, 1)), crw=crw,
                     mesh_axis=naxis)
    ci = check_candidate(cand, dims, limit)
    bad = any(i.severity == ERROR for i in ci)
    report["candidates"].append(
        {"label": cand.label(), "kind": "sharded",
         "rung": list(cand.rung), "breq": cand.breq, "crash-width": crw,
         "unroll": cand.unroll, "footprint": footprint(cand),
         "status": "rejected" if bad else "ok",
         "issues": [i.to_dict() for i in ci]})
    report["issues"].extend(i.to_dict() for i in ci)
    if bad:
        _reject(report, where)
    report["selected"] = cand.label()
    return _entry(report)


def pad_for_axis(n: int, naxis: int) -> int:
    """The smallest value >= ``n`` the mesh axis divides — how the
    elastic fleet re-pads a pool when the mesh grows or shrinks (always
    UP: padding adds dead rows; truncating would drop live frontier)."""
    naxis = max(int(naxis), 1)
    return -(-int(n) // naxis) * naxis


def check_remesh(p, naxis: int, capacity: int, window: int,
                 expand: Optional[int],
                 bytes_limit: Optional[int] = None) -> Dict[str, Any]:
    """Re-mesh validation for the elastic fleet layer
    (:mod:`jepsen_tpu.fleet`): re-run the PLAN-SHARD-INDIVISIBLE /
    PLAN-SHARD-SKEW / PLAN-OOM checks against a NEW mesh axis — the
    host-loss / join path, where a failed validation must inform, not
    abort, the surviving search.

    Unlike :func:`gate_sharded` this NEVER raises: the capacity and
    expand are first padded up so the axis divides them
    (:func:`pad_for_axis` — re-meshing must not drop live rows), the
    candidate is checked, and the caller gets the whole verdict::

        {"ok": bool, "naxis", "capacity", "expand",  # post-padding
         "per-device-bytes", "bytes-limit",
         "issues": [{rule, severity, message, label}]}

    ``p`` is a PackedHistory or a prebuilt PlanDims. ``ok`` is False
    only on error-severity issues (a skew WARNING degrades, it does
    not refuse a mesh that keeps the search alive)."""
    dims = p if isinstance(p, PlanDims) else PlanDims.from_packed(p)
    naxis = max(int(naxis), 1)
    cap = pad_for_axis(capacity, naxis)
    exp = None if expand is None else pad_for_axis(expand, naxis)
    limit = bytes_limit if bytes_limit is not None else plan_bytes_limit()
    crw = T._crash_width(dims.n_crashed)
    if crw is None:
        return {"ok": False, "naxis": naxis, "capacity": cap,
                "expand": exp, "per-device-bytes": None,
                "bytes-limit": limit,
                "issues": [PlanIssue(
                    "PLAN-CRASH-WIDTH", ERROR,
                    f"{dims.n_crashed} crashed ops exceed the "
                    f"crashed-set width {T.CRASH_MAX}").to_dict()]}
    cand = Candidate(kind="sharded", capacity=cap, window=window,
                     expand=exp, unroll=T._unroll_factor(),
                     breq=T._bucket(max(dims.n_required, 1)), crw=crw,
                     mesh_axis=naxis)
    issues = check_candidate(cand, dims, limit)
    fp = footprint(cand)
    return {"ok": not any(i.severity == ERROR for i in issues),
            "naxis": naxis, "capacity": cap, "expand": exp,
            "per-device-bytes": fp.get("per-device-bytes",
                                       fp["total-bytes"]),
            "bytes-limit": limit,
            "issues": [i.to_dict() for i in issues]}


def seed_rung(capacity: int, window: int, expand: Optional[int],
              breq: int, crw: int, floor: int,
              kind: str = "segment"
              ) -> Tuple[int, Optional[int], int, Optional[int]]:
    """Seed a supervised rung's initial pool from the predicted
    footprint instead of always starting at the rung maximum: halve
    capacity (and expand with it, mirroring the reactive OOM path)
    until the prediction fits the byte budget or the policy floor is
    reached. Returns ``(capacity, expand, predicted_bytes, limit)`` —
    unchanged when no limit is known (CPU) or the rung already fits."""
    limit = plan_bytes_limit()

    def predict(cap: int, exp: Optional[int]) -> int:
        return footprint(Candidate(
            kind=kind, capacity=cap, window=window, expand=exp,
            unroll=T._unroll_factor(), breq=breq, crw=crw)
        )["total-bytes"]

    cap, exp = capacity, expand
    pred = predict(cap, exp)
    if limit is None:
        return cap, exp, pred, None
    while pred > limit and cap // 2 >= floor:
        cap //= 2
        if isinstance(exp, int):
            exp = max(1, min(exp // 2, cap))
        pred = predict(cap, exp)
    if cap != capacity:
        _PLAN_SEEDED.inc()
    return cap, exp, pred, limit


def request_footprint(dims: PlanDims,
                      kind: str = "segment") -> Optional[int]:
    """Predicted device bytes of the CHEAPEST rung the supervised search
    would run for these dims — the serve daemon's admission-control
    unit: queued + in-flight request footprints are summed against the
    device byte budget (:func:`plan_bytes_limit`), and a request that
    would push the sum past it is answered 429 instead of being allowed
    to OOM a shared fleet. None when the dims cannot plan at all
    (crashed-set overflow — such a request goes UNKNOWN without device
    time, so it costs no budget)."""
    cands = enumerate_candidates(dims, kinds=(kind,))
    if not cands:
        return None
    return int(footprint(cands[0])["total-bytes"])


def gang_footprint(dims: PlanDims, size: int,
                   kind: str = "segment", hosts: int = 1) -> Optional[int]:
    """Predicted device bytes of a ``size``-member GANG over these
    dims — :func:`request_footprint` scaled by the gang size, because
    batched execution (checker.tpu.check_packed_gang) stacks every
    packed column and every pool/carry row on a leading gang axis, so
    the working set is linear in members. The serve daemon's
    BatchScheduler prices the WHOLE gang here BEFORE dispatch
    (doc/serve.md "Concurrent batching") and caps the gang at the
    largest size that fits the admission byte budget — the gang-shaped
    extension of the per-request 429 contract. None when the dims
    cannot plan at all.

    With ``hosts`` > 1 the gang's lanes shard over a fleet
    (doc/serve.md "Fleet-backed serving"): the returned bytes are the
    WIDEST single host's share — ``ceil(size / hosts)`` lanes — so the
    per-host admission budget prices what any one device will actually
    hold, and fleet-wide capacity is ``hosts`` of these."""
    if size < 1:
        return None
    fp = request_footprint(dims, kind=kind)
    if fp is None:
        return None
    lanes = -(-int(size) // max(1, int(hosts)))
    return int(fp) * lanes
