"""Batched linearizability search on TPU — the north-star workload.

This is the device backend for :class:`jepsen_tpu.checker.wgl.
LinearizableChecker` (reference: knossos's wgl/linear algorithms selected at
jepsen/src/jepsen/checker.clj:85-94; the CPU oracle with identical semantics
is :mod:`jepsen_tpu.checker.wgl`).

Design
------
A WGL configuration is ``(k, mask, cmask, state)``: ops ``[0, k)`` in
return order are linearized, ``mask`` bit *o* marks op ``k+o`` as
additionally linearized, ``cmask`` marks taken crashed ops, ``state`` is
the model state as one int32 (see
:class:`jepsen_tpu.models.core.KernelSpec`).

The search is a **best-first pool search** (see :func:`_search_fn`): a pool
of C configurations lives in sorted device arrays, deepest first. Each
iteration is a fixed-shape tensor program:

1. expand the top E pool rows: ``[E] configs × [W] window offsets (+ [CR]
   crashed ops) -> [E*(W+CR)]`` candidate successors through the model's
   branchless integer step kernel (vmapped — thousands of model states per
   vector lane),
2. detect completion (any successor with ``k >= n_required``),
3. merge successors with the unexpanded pool remainder, sort
   lexicographically by (depth, mask, state, |cmask|, cmask) — XLA maps
   this onto the TPU sort unit — mark adjacent duplicates and
   subset-dominated crashed variants,
4. keep the first C rows as the next pool.

Unexpanded pool rows are the backtrack stack, so the search behaves like a
massively-parallel DFS: valid histories complete in ~n iterations even
when the reachable configuration space dwarfs C. The whole search is one
``lax.while_loop`` under ``jit``; histories are the int32 columns of
:class:`jepsen_tpu.ops.encode.PackedHistory`. Independent keys (the
data-parallel axis of reference independent.clj:65-219) batch via ``vmap``
and shard across a ``jax.sharding.Mesh`` — per-key validity is combined
host-side (logical AND), counterexamples gathered per key.

Soundness: a found witness proves linearizability outright. An exhausted
search proves non-linearizability only if the pool never truncated (no
unique config dropped past C) and no candidate ever fell beyond the W
window; otherwise the result is "unknown", the ladder escalates, and the
caller finally falls back to the exact CPU search.
"""

from __future__ import annotations

import time as _hosttime
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from jepsen_tpu import obs
from jepsen_tpu.checker import UNKNOWN
from jepsen_tpu.history import History
from jepsen_tpu.models.core import KernelSpec, Model, kernel_spec_for
from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.obs import profiler as obs_profiler
from jepsen_tpu.ops.encode import PackedHistory, RET_INF, pack_with_init

try:  # JAX is a hard dependency of this module, soft for the package.
    import jax
    import jax.numpy as jnp
    from jax import lax
    HAVE_JAX = True
except ImportError:  # pragma: no cover
    HAVE_JAX = False


#: Default candidate window width: max offset from the frontier an op may
#: be linearized at. Bounded below by the history's max concurrency. The
#: multi-word mask representation supports windows up to MAX_WINDOW; the
#: escalation ladder widens the window together with the pool.
WINDOW = 32
MAX_WINDOW = 128

#: Search steps per while_loop iteration (see body_n in _search_fn).
#: 1 measured best on the CPU backend (math-bound); on TPU, where
#: per-iteration dispatch overhead can dominate these small tensors, set
#: JTPU_UNROLL=2|4 and re-measure — compile time scales with the unroll.
_UNROLL = 1

#: Device iterations per checkpointed segment (see JTPU_SEGMENT_ITERS and
#: jepsen_tpu.resilience): the single-history search runs as an outer host
#: loop of bounded device segments, snapshotting the carry to host between
#: them so a crashed / wedged / preempted search resumes where it left off
#: instead of losing everything.
DEFAULT_SEGMENT_ITERS = 1024


def _level_budget(n: int, n_cr: int) -> int:
    """Iteration budget for a search over ``n`` padded required ops and
    ``n_cr`` padded crashed ops: the witness path alone needs ~n+n_cr
    expansions, and best-first backtracking re-expands some configs (no
    global visited set); past this the run reports UNKNOWN rather than
    spin. Shared by the in-device while_loop condition and the host-side
    segment supervisor (jepsen_tpu.resilience), which must agree on when
    a checkpointed carry is still worth resuming."""
    return 2 * (n + n_cr) + 256


def _bucket(n: int, lo: int = 16) -> int:
    """Round n up to a power of two so jit compilations are shared across
    histories of similar length (padding rows are never candidates)."""
    b = lo
    while b < n:
        b *= 2
    return b


def _suffix_min_inv(inv: np.ndarray, n: int) -> np.ndarray:
    """suffix_min[j] = min(inv[j:]), suffix_min[n] = RET_INF — lets the
    device test "any candidate beyond the window?" with one gather."""
    out = np.full(n + 1, int(RET_INF), dtype=np.int32)
    out[:n] = np.minimum.accumulate(np.asarray(inv[:n])[::-1])[::-1]
    return out


def _trailing_ones(m):
    """Count trailing one-bits of a uint32 array (branchless)."""
    y = ~m
    low = y & (jnp.uint32(0) - y)          # lowest zero bit of m, 0 if none
    return lax.population_count(low - jnp.uint32(1)).astype(jnp.int32)


def _shr1_multi(m, MW: int):
    """Whole-mask right shift by one bit: [*, MW] -> [*, MW]."""
    parts = []
    for w in range(MW):
        lo = m[..., w] >> jnp.uint32(1)
        if w + 1 < MW:
            lo = lo | (m[..., w + 1] << jnp.uint32(31))
        parts.append(lo)
    return jnp.stack(parts, axis=-1)


def _trailing_ones_mw(m, MW: int):
    """Trailing one-bits across the whole [*, MW] mask."""
    tw = [_trailing_ones(m[..., w]) for w in range(MW)]
    t = tw[0]
    for w in range(1, MW):
        t = jnp.where(t == 32 * w, 32 * w + tw[w], t)
    return t


def _shr_by_mw(m, t, MW: int):
    """Whole-mask right shift by a per-row amount t in [0, 32*MW]."""
    mpad = jnp.concatenate(
        [m, jnp.zeros(m.shape[:-1] + (1,), jnp.uint32)], axis=-1)
    ws = (t >> 5)[:, None]
    bs = (t & 31).astype(jnp.uint32)[:, None]
    widx = jnp.arange(MW, dtype=jnp.int32)[None, :]
    a = jnp.take_along_axis(mpad, jnp.clip(widx + ws, 0, MW), axis=-1)
    b = jnp.take_along_axis(mpad, jnp.clip(widx + ws + 1, 0, MW),
                            axis=-1)
    hi = jnp.where(bs > 0, b << jnp.minimum(
        jnp.uint32(32) - bs, jnp.uint32(31)), jnp.uint32(0))
    return (a >> bs) | hi


#: Per-level search-analytics counter columns (doc/observability.md,
#: "Search analytics"). One int32 row per search level when a factory is
#: built with ``stats=True``:
#:   expanded   live pool rows expanded at this level
#:   dup        successor rows killed as adjacent duplicates
#:   dominated  successor rows killed by subset dominance
#:   trunc      unique rows lost to pool truncation (the lossy signal)
#:   frontier   live pool rows surviving into the next level
SEARCHSTAT_COLS = ("expanded", "dup", "dominated", "trunc", "frontier")
NSTAT = len(SEARCHSTAT_COLS)


def _search_fn(step, n: int, n_cr: int, capacity: int, window: int,
               expand: Optional[int] = None, unroll: int = 1,
               shard_axis: Optional[str] = None,
               tiebreak: str = "lex", segment: bool = False,
               stats: bool = False):
    """Build the single-key search. ``n`` is the (static, padded) length of
    the *required* section — ops with finite return, sorted by return index.
    ``n_cr`` is the (static, padded) width of the *crashed* section — 'info'
    ops pending forever, which MAY be linearized at any point after their
    invocation; they get their own bitmask since they never age out of the
    candidate set and so can't live in the offset window.

    Returns a function
      (f, v1, v2, ro, fr, inv, ret, sufmin, cf, cv1, cv2, cinv,
       cps, n_required, init_state) -> (done, lossy, wovf, best_k, levels,
       pool_k, pool_state, pool_alive)
    — five jnp scalars plus the last living pool's [capacity] columns
    (the frontier configs counterexample extraction reads on
    valid:false). Pure jnp — safe under jit, vmap, and shard_map. This
    runs the whole search in one while_loop; the keyed batch
    (Engine.jit_batch) vmaps it. ``segment=True`` instead takes a
    traced level bound and a carry and returns the carry: the
    checkpointed segments every single-history search runs.
    ``ro[j]`` is 1 iff op j is *read-only* — its step can never change the
    state at any state where it succeeds (kernel.readonly) — which drives
    the greedy pure-op closure below.

    ``cps[j]`` is the index of the previous crashed op identical to j
    (same f/v1/v2), or -1: used for the canonical-order pruning below.

    The search is a *best-first pool search*: a pool of C configurations is
    kept sorted deepest-first; each iteration expands only the top
    ``expand`` rows (E) and merges their successors back into the pool — a
    massively-parallel DFS whose unexpanded pool rows are the backtrack
    stack. ``expand=None`` sets E=C, which degenerates to exact
    level-synchronous BFS (every pool row expands every level). When a
    merge produces more than C unique configurations the deepest C survive
    and ``lossy`` is set; the search keeps going rather than aborting,
    because a completion witness found by a truncated pool is still a
    witness. Soundness of the three outcomes: ``done`` proves
    linearizability outright; pool death with ``lossy`` and ``wovf`` both
    false is an exhaustive refutation; anything else is UNKNOWN and the
    caller escalates capacity / falls back to the exact CPU search.

    Two sound prunings keep the crashed-op pool small (2^crashed subsets
    otherwise — the cmask axis):

    * canonical order among identical crashed ops — if an earlier
      identical crashed op is available and untaken, taking this one is
      redundant (any witness can swap the two occurrences);
    * subset dominance — of two configs with equal (k, mask, state), the
      one whose taken-crashed set is a subset of the other's can do
      everything the other can (crashed ops are optional), so the
      superset config is pruned. The lexsort groups equal (k, mask,
      state) rows with cmasks in ascending popcount, and each row is
      tested against its group's first few rows (the likeliest
      dominators) — a bounded, fixed-shape approximation that only ever
      prunes genuinely dominated rows.

    ``stats=True`` appends one extra carry lane: a ``[LMAX+1, NSTAT]``
    int32 per-level counter log (:data:`SEARCHSTAT_COLS`) written with
    pure ``.at[].set`` indexing inside the traced body — zero host sync;
    the host extracts it from the raw carry at segment barriers, so the
    lane is for segment mode. ``stats=False`` compiles the original
    13-lane carry, byte-identical to the pre-analytics executable.
    """
    C, W, CR = capacity, window, n_cr
    E = min(expand or C, C)
    MW = (W + 31) // 32           # mask words (window bits)
    MC = (CR + 31) // 32          # crashed-mask words

    if shard_axis is not None:
        # Pool-sharded mode (single-history scale-out): the pool, the
        # candidate grids derived from it, and the merge sort's operand
        # rows are partitioned over the mesh axis; XLA's SPMD partitioner
        # parallelizes the expansion/step math per shard and inserts the
        # collectives the global sort/dedup needs. Callers guarantee
        # capacity and expand divide the mesh axis.
        from jax.sharding import PartitionSpec as _P

        def _sc(x):
            return jax.lax.with_sharding_constraint(
                x, _P(*((shard_axis,) + (None,) * (x.ndim - 1))))
    else:
        def _sc(x):
            return x
    LEADERS = 8  # group-prefix rows tested as dominators
    import os as _ffo
    #: forced-advances per fast-forward loop iteration. 1 until a clean
    #: measurement says otherwise (sweep via JTPU_FF_UNROLL; on the
    #: loaded build host the sweep was inconclusive within noise).
    FF_UNROLL = int(_ffo.environ.get("JTPU_FF_UNROLL") or "0") or 1
    MAXK = jnp.int32(1 << 30)
    #: iteration budget: the witness path alone needs ~n+CR expansions, and
    #: best-first backtracking re-expands some configs (no global visited
    #: set); past this the run reports UNKNOWN rather than spin.
    LMAX = _level_budget(n, CR)

    # Static bit matrices: bitmat[o, w] has bit (o mod 32) set iff offset o
    # lives in word w — one uint32 AND/OR against them tests/sets any bit of
    # a multi-word mask without dynamic shifts.
    bitmat = np.zeros((max(W, 1), max(MW, 1)), dtype=np.uint32)
    for o in range(W):
        bitmat[o, o >> 5] = np.uint32(1) << np.uint32(o & 31)
    cbitmat = np.zeros((max(CR, 1), max(MC, 1)), dtype=np.uint32)
    for o in range(CR):
        cbitmat[o, o >> 5] = np.uint32(1) << np.uint32(o & 31)

    def _shr1(m):
        return _shr1_multi(m, MW)

    def _trailing_ones_multi(m):
        return _trailing_ones_mw(m, MW)

    def _shr_by(m, t):
        return _shr_by_mw(m, t, MW)

    def search(f, v1, v2, ro, fr, inv, ret, sufmin, cf, cv1, cv2, cinv,
               cps, n_required, init_state, seg_iters=None, carry_in=None):
        offs = jnp.arange(W, dtype=jnp.int32)          # [W]

        def crash_bound(cm_rows):
            """Per-row fast-forward boundary: the first frontier whose
            return exceeds the smallest UNTAKEN crashed invocation — up
            to there no crashed op is linearizable. Computed once per
            level from the expanded rows' cmask and shared by both
            fast_forward call sites."""
            if CR:
                ctk = jnp.any(
                    (cm_rows[:, None, :] & cbitmat[None, :, :]) != 0,
                    axis=-1)                             # [R, CR]
                umin = jnp.min(jnp.where(ctk, RET_INF, cinv[None, :]),
                               axis=-1)                  # [R]
                return jnp.searchsorted(ret, umin, side="right")
            return jnp.full(cm_rows.shape[:1], n, jnp.int32)

        def fast_forward(kk, ss, go, bound):
            """Advance rows through runs of FORCED ops (fr[k]=1: op k is
            the unique required candidate at frontier k, which also
            implies the mask is empty there) without paying a sort-level
            per op. Crashed candidates stop the run via the per-row
            boundary: the first frontier whose return exceeds the
            smallest UNTAKEN crashed invocation — up to there no crashed
            op is linearizable, so the forced successor is truly unique
            and skipping the intermediate configs loses nothing (each
            had exactly one continuation). A failing forced step leaves
            the row at the failing frontier to die (or be reported) in
            the normal expansion. Realistic staggered workloads (etcd's
            1/30-stagger tutorial shape) are mostly forced runs, which
            this collapses from O(n) levels to O(#concurrent regions)."""
            def ff_cond(c):
                return jnp.any(c[2])

            def ff_step(k_, s_, go_):
                kc_ = jnp.clip(k_, 0, n - 1)
                s2_, ok_ = step(s_, f[kc_], v1[kc_], v2[kc_])
                adv = (go_ & (fr[kc_] > 0) & (k_ < bound)
                       & (k_ < n_required) & ok_)
                return (k_ + adv, jnp.where(adv, s2_.astype(jnp.int32),
                                            s_), adv)

            def ff_body(c):
                # several forced advances per while iteration: forced
                # runs are tens of ops long on staggered workloads, and
                # the loop's per-iteration overhead on these tiny [E]
                # tensors otherwise dominates the level (the `adv` flag
                # makes extra applications no-ops, so correctness is
                # unaffected)
                for _ in range(FF_UNROLL):
                    c = ff_step(*c)
                return c

            kk, ss, _ = lax.while_loop(ff_cond, ff_body, (kk, ss, go))
            return kk, ss

        k0 = _sc(jnp.zeros(C, jnp.int32))
        mask0 = _sc(jnp.zeros((C, MW), jnp.uint32))
        cmask0 = _sc(jnp.zeros((C, max(MC, 1)), jnp.uint32))
        state0 = _sc(jnp.full(C, 0, jnp.int32) + init_state)
        alive0 = _sc(jnp.arange(C) == 0)
        # (k, mask, cmask, state, alive, done, lossy, wovf, level, best_k,
        #  pk, ps, pa): the p* slots snapshot the incoming pool each
        # iteration, so when the pool dies (an exhaustive refutation) the
        # LAST LIVING frontier — its (k, state) configs — survives for
        # counterexample extraction without any CPU re-search.
        carry0 = (k0, mask0, cmask0, state0, alive0,
                  n_required == 0, jnp.bool_(False), jnp.bool_(False),
                  jnp.int32(0), jnp.int32(0),
                  k0, state0, alive0)
        if stats:
            # per-level counter log, level-indexed (NOT pool-row-indexed:
            # it never shrinks with the pool and is left unsharded —
            # [LMAX+1, NSTAT] int32 is a few KB at worst)
            carry0 = carry0 + (jnp.zeros((LMAX + 1, NSTAT), jnp.int32),)

        def active(c):
            return (~c[5]) & jnp.any(c[4]) & (c[8] <= LMAX)

        def body(c):
            (k, mask, cmask, state, alive, done, lossy, wovf, level,
             best, _pk, _ps, _pa) = c[:13]

            # -- select the top-E pool rows for expansion (the pool is
            # sorted deepest-first; invalid rows sank in the merge sort) --
            k_e, m_e = k[:E], mask[:E]
            cm_e, s_e, a_e = cmask[:E], state[:E], alive[:E]

            # -- window-overflow probe on the expanded rows ---------------
            kc = jnp.clip(k_e, 0, n - 1)
            ret_k = ret[kc]                                     # [E]
            beyond = sufmin[jnp.clip(k_e + W, 0, n)]            # [E]
            wovf2 = wovf | jnp.any(a_e & (beyond < ret_k))

            # -- expand required ops: [E, W] successor grid ---------------
            j = k_e[:, None] + offs[None, :]                    # [E, W]
            jc = jnp.clip(j, 0, n - 1)
            already = jnp.any(
                (m_e[:, None, :] & bitmat[None, :, :]) != 0, axis=-1)
            cand = (a_e[:, None]
                    & (j < n)
                    & (inv[jc] < ret_k[:, None])
                    & ~already)
            s2, ok = step(s_e[:, None], f[jc], v1[jc], v2[jc])
            # Partial-order reduction: a READ-ONLY candidate (ro: its step
            # can never change the state at ANY state where it succeeds —
            # a register read, a cas(x,x), a set read) that succeeds now
            # can always be linearized immediately: moving it earlier in a
            # witness never invalidates the steps it jumps over, because
            # it changes nothing anywhere. So each expanded config emits
            # ONE closure successor taking all such pure candidates at
            # once, and branches only over the rest. This collapses the
            # 2^reads subset explosion on read-heavy histories and is
            # sound for refutation too (every witness normalizes to a
            # greedy-pure witness, and those are explored exhaustively).
            # NOTE the test must be ro, not "state unchanged here": an op
            # that is incidentally pure at the current state (a rewrite of
            # the current value) may be needed later as a state-RESTORING
            # step, so it is not safely movable.
            pure = cand & ok & (ro[jc] > 0)
            valid = cand & ok & ~pure

            # closure successor: take all pure candidates, then advance the
            # frontier past the (possibly long) run of linearized ops
            pure_bits = jnp.sum(
                jnp.where(pure[:, :, None], bitmat[None, :, :],
                          jnp.uint32(0)),
                axis=1, dtype=jnp.uint32)                       # [E, MW]
            mc_ = m_e | pure_bits
            tc_ = _trailing_ones_multi(mc_)
            kcl = k_e + tc_
            mcl = _shr_by(mc_, tc_)
            closure_ok = a_e & jnp.any(pure, axis=1)            # [E]
            # full reduction: a config with pure candidates emits ONLY its
            # closure successor — impure (and crashed) branches happen
            # after the pure ops are absorbed, from the closure config
            valid = valid & ~closure_ok[:, None]

            # frontier advance for o == 0: skip runs of already-linearized
            m1 = _shr1(m_e)
            t = _trailing_ones_multi(m1)                        # [E]
            k_adv = k_e + 1 + t
            m_adv = _shr_by(m1, t)

            s2 = s2.astype(jnp.int32)
            # forced fast-forward on the frontier-advance successor: when
            # it lands on a forced run, absorb the whole run this level.
            # (fr[k] implies the mask there is empty: a masked op would
            # have been concurrent with op k when it was linearized.)
            ff_bound = crash_bound(cm_e)                 # shared, [E]
            k_adv, s2_0 = fast_forward(k_adv, s2[:, 0], valid[:, 0],
                                       ff_bound)
            s2 = s2.at[:, 0].set(s2_0)

            is0 = offs[None, :] == 0                            # [1, W]
            k2 = jnp.where(is0, k_adv[:, None], k_e[:, None])
            m2 = jnp.where(is0[:, :, None], m_adv[:, None, :],
                           m_e[:, None, :] | bitmat[None, :, :])  # [E,W,MW]
            cm2 = jnp.broadcast_to(cm_e[:, None, :],
                                   (E, W, max(MC, 1)))

            # -- expand crashed ops: [E, CR] successor grid ---------------
            # A crashed op is a candidate once invoked before the frontier
            # op's return; it stays one until taken (pad rows: cinv=RET_INF).
            if CR:
                ctaken = jnp.any(
                    (cm_e[:, None, :] & cbitmat[None, :, :]) != 0, axis=-1)
                ccand = (a_e[:, None]
                         & ~closure_ok[:, None]
                         & (cinv[None, :] < ret_k[:, None])
                         & ~ctaken)
                # canonical order: skip a crashed op whose earlier identical
                # twin is available and untaken
                prevc = jnp.clip(cps, 0, CR - 1)                 # [CR]
                prev_avail = cinv[prevc][None, :] < ret_k[:, None]
                pw = prevc >> 5                                  # [CR]
                pb = (prevc & 31).astype(jnp.uint32)
                prev_taken = ((jnp.take(cm_e, pw, axis=1)
                               >> pb[None, :]) & jnp.uint32(1)) == 1
                redundant = ((cps >= 0)[None, :]
                             & prev_avail & ~prev_taken)
                ccand = ccand & ~redundant
                cs2, cok = step(s_e[:, None], cf[None, :], cv1[None, :],
                                cv2[None, :])
                # a pure crashed op need never be taken: it is optional and
                # leaves the state unchanged, so the untaken config
                # dominates (exactly the subset-dominance rule, applied
                # exhaustively at generation time)
                cvalid = ccand & cok & (cs2 != s_e[:, None])
                ck2 = jnp.broadcast_to(k_e[:, None], (E, CR))
                cmm2 = jnp.broadcast_to(m_e[:, None, :], (E, CR, MW))
                ccm2 = cm_e[:, None, :] | cbitmat[None, :, :]
                cs2 = jnp.broadcast_to(cs2.astype(jnp.int32), (E, CR))
                crash_rows = [
                    (ck2.reshape(-1), cmm2.reshape(-1, MW),
                     ccm2.reshape(-1, max(MC, 1)), cs2.reshape(-1),
                     cvalid.reshape(-1))]
            else:
                crash_rows = []

            # -- flatten both grids, append the unexpanded pool remainder,
            # and check completion ----------------------------------------
            # the closure successor may also land on a forced run
            kcl, scl = fast_forward(kcl, s_e, closure_ok, ff_bound)
            segs = ([(k2.reshape(-1), m2.reshape(-1, MW),
                      cm2.reshape(-1, max(MC, 1)), s2.reshape(-1),
                      valid.reshape(-1)),
                     (kcl, mcl, cm_e, scl, closure_ok)]
                    + crash_rows
                    + [(k[E:], mask[E:], cmask[E:], state[E:], alive[E:])])
            fk = jnp.concatenate([s[0] for s in segs])
            fm = jnp.concatenate([s[1] for s in segs])
            fcm = jnp.concatenate([s[2] for s in segs])
            fs = jnp.concatenate([s[3] for s in segs])
            fv = jnp.concatenate([s[4] for s in segs])
            done2 = done | jnp.any(fv & (fk >= n_required))
            best2 = jnp.maximum(best, jnp.max(jnp.where(fv, fk, 0)))

            # -- dedup + dominance: one lexsort; the deepest configurations
            # sort first (truncation keeps them) and invalid rows sink past
            # MAXK. Depth is the TOTAL linearized count k + |mask| — not k
            # alone: in histories where commit order diverges from return
            # order (e.g. a burst of ~100 concurrent ops completing in an
            # unrelated order) progress accumulates in the mask while k
            # stays near zero, and a k-keyed pool buries it. k rides along
            # as a secondary sort term (configs are only equal when
            # (k, mask, state) all match). cmask words sort last, by
            # popcount, so each (k, mask, state) group leads with its
            # fewest-crashed-taken configs --------------------------------
            pm = fk * 0
            for w in range(MW):
                pm = pm + lax.population_count(fm[:, w]).astype(jnp.int32)
            depth = fk + pm
            key1 = jnp.where(fv, MAXK - depth, MAXK + 1 + fk)
            fmw = [fm[:, w] for w in range(MW)]
            fcmw = [fcm[:, w] for w in range(MC)]
            if tiebreak == "hash":
                # Diversified permutation sort: the comparator sees only
                # (key1, h[, pc, cmask]) plus an index payload; the wide
                # config columns are gathered by the resulting permutation
                # instead of riding through the sort network. h is a
                # 32-bit mix of (k, mask, state): equal configs hash
                # equal, so dedup/dominance groups stay adjacent and the
                # cmask-popcount key still orders within them; distinct
                # configs collide with ~2^-32 probability, and a collision
                # only costs a missed dedup/dominance prune (every
                # equality test below is exact on the gathered columns),
                # never soundness. The hash tie-break RANDOMIZES which
                # equal-depth rows survive pool truncation — measured to
                # diversify the slim-rung beam on dense keyed batches
                # (64x500 dense: 2.4x fewer wall-seconds, max levels
                # 672 -> 510) but to lose the 10k single-history flagship
                # witness from the 32-row pool, so callers choose: keyed
                # first rungs use it, single-history search keeps "lex"
                # (a lossy hash rung escalates to a lex rung, so the only
                # cost of a bad draw is the slim rung's wall time).
                h = fk.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
                for w in range(MW):
                    h = (h ^ fm[:, w]) * jnp.uint32(0x85EBCA6B)
                    h = h ^ (h >> jnp.uint32(13))
                h = (h ^ fs.astype(jnp.uint32)) * jnp.uint32(0xC2B2AE35)
                h = h ^ (h >> jnp.uint32(16))
                iota0 = jnp.arange(fk.shape[0], dtype=jnp.int32)
                if MC:
                    pc = fcmw[0] * 0
                    for w in range(MC):
                        pc = pc + lax.population_count(fcmw[w])
                    keys = [key1, h, pc.astype(jnp.int32)] + fcmw
                else:
                    keys = [key1, h]
                keys = [_sc(t) for t in keys] + [_sc(iota0)]
                sorted_terms = lax.sort(tuple(keys),
                                        num_keys=len(keys) - 1)
                key1 = sorted_terms[0]
                perm = sorted_terms[-1]
                fk = fk[perm]
                fmw = [w_[perm] for w_ in fmw]
                fs = fs[perm]
                fcmw = (list(sorted_terms[3:3 + MC]) if MC else [])
            else:
                if MC:
                    pc = fcmw[0] * 0
                    for w in range(MC):
                        pc = pc + lax.population_count(fcmw[w])
                    terms = ([key1, fk] + fmw
                             + [fs, pc.astype(jnp.int32)] + fcmw)
                else:
                    terms = [key1, fk] + fmw + [fs]
                terms = [_sc(t) for t in terms]
                sorted_terms = lax.sort(tuple(terms), num_keys=len(terms))
                key1 = sorted_terms[0]
                fk = sorted_terms[1]
                fmw = list(sorted_terms[2:2 + MW])
                fs = sorted_terms[2 + MW]
                fcmw = list(sorted_terms[4 + MW:]) if MC else []
            fv = key1 <= MAXK

            def _eq_prev(a):
                return a[1:] == a[:-1]

            grp_eq = _eq_prev(key1) & _eq_prev(fk) & _eq_prev(fs)
            for w in range(MW):
                grp_eq = grp_eq & _eq_prev(fmw[w])
            same_grp = jnp.concatenate(
                [jnp.zeros(1, bool), grp_eq & fv[1:] & fv[:-1]])
            cm_eq = jnp.ones(same_grp.shape[0] - 1, bool)
            for w in range(MC):
                cm_eq = cm_eq & _eq_prev(fcmw[w])
            dup = same_grp & jnp.concatenate([jnp.zeros(1, bool), cm_eq])
            dominated = jnp.zeros(fv.shape, bool)
            if CR:
                iota = jnp.arange(fv.shape[0], dtype=jnp.int32)
                # index of this row's group start (latest non-member row)
                g = lax.cummax(jnp.where(same_grp, jnp.int32(0), iota))
                for p in range(LEADERS):
                    li = jnp.minimum(g + p, iota.shape[0] - 1)
                    lead = ((key1[li] == key1) & (fk[li] == fk)
                            & (fs[li] == fs) & (li < iota) & fv)
                    subset = jnp.ones(fv.shape, bool)
                    for w in range(MW):
                        lead = lead & (fmw[w][li] == fmw[w])
                    for w in range(MC):
                        subset = subset & (
                            (fcmw[w] & fcmw[w][li]) == fcmw[w][li])
                    dominated = dominated | (lead & subset)
            uniq = fv & ~dup & ~dominated

            # -- pool truncation: keep the first C rows (the deepest
            # unique configs; dup/dominated rows inside the prefix occupy
            # dead slots). A unique row past C was dropped: the search is
            # now lossy — keep going (done is still sound), but pool
            # death no longer refutes ------------------------------------
            lossy2 = lossy | jnp.any(uniq[C:])
            k3 = fk[:C]
            m3 = jnp.stack([w_[:C] for w_ in fmw], axis=-1)
            if MC:
                cm3 = jnp.stack([w_[:C] for w_ in fcmw], axis=-1)
            else:
                cm3 = cmask
            s3 = fs[:C]
            a3 = uniq[:C]

            if shard_axis is not None:
                k3, s3, a3 = _sc(k3), _sc(s3), _sc(a3)
                m3 = _sc(m3)
                if MC:
                    cm3 = _sc(cm3)
            new = (k3, m3, cm3, s3, a3, done2, lossy2, wovf2,
                   level + 1, best2, k, state, alive)
            if stats:
                # pure in-kernel counter write: one [NSTAT] int32 row at
                # the level just expanded — no host sync, no shape change
                row = jnp.clip(level, 0, LMAX)
                counts = jnp.stack([
                    jnp.sum(a_e, dtype=jnp.int32),
                    jnp.sum(dup, dtype=jnp.int32),
                    jnp.sum(dominated, dtype=jnp.int32),
                    jnp.sum(uniq[C:], dtype=jnp.int32),
                    jnp.sum(a3, dtype=jnp.int32)])
                new = new + (c[13].at[row].set(counts),)
            # Masked update: lanes finished under vmap must not mutate.
            act = active(c)
            return tuple(jnp.where(act, nw, old) for nw, old in zip(new, c))

        # Unrolled loop body: each while_loop iteration costs fixed
        # per-iteration overhead (condition evaluation + kernel-launch
        # sequencing) that can rival the math on these small tensors, so
        # running `unroll` search steps per iteration amortizes it (body
        # is a masked update — extra applications after completion are
        # no-ops, so correctness is unaffected). The factor is part of
        # the jit cache key (see Engine.jit_segment/jit_batch) so sweeps
        # actually recompile.

        def body_n(c):
            for _ in range(max(1, unroll)):
                c = body(c)
            return c

        if segment:
            # Checkpointed segment mode (jepsen_tpu.resilience): run at
            # most seg_iters levels from the supplied carry and return
            # the RAW carry — the host supervisor snapshots it between
            # segments (the checkpoint), decides continuation, and
            # summarizes via _summarize_carry when the search goes
            # inactive. The body sequence is the one the whole-search
            # loop below runs, so any segment length gives the same
            # verdicts and level counts.
            carry = carry0 if carry_in is None else carry_in
            lvl0 = carry[8]

            def seg_active(c):
                return active(c) & ((c[8] - lvl0) < seg_iters)

            return lax.while_loop(seg_active, body_n, carry)

        out = lax.while_loop(active, body_n, carry0)
        alive_out, done = out[4], out[5]
        lossy, wovf = out[6], out[7]
        level, best = out[8], out[9]
        pk, ps, pa = out[10], out[11], out[12]
        # Stopped at the iteration budget with work left: incomplete, so a
        # non-done outcome must not read as a refutation.
        lossy = lossy | (~done & jnp.any(alive_out))
        return done, lossy, wovf, best, level, pk, ps, pa

    return search


# ---------------------------------------------------------------------------
# Telemetry (doc/observability.md): every metric/span here is recorded on
# the HOST side, around block_until_ready — never inside a traced body
# (the JAX-TRACE-IN-JIT lint rule rejects clocks/spans under jit, where
# they would either poison the trace or time the dispatch, not the math).
# ---------------------------------------------------------------------------

_DEVICE_SECONDS = obs_metrics.histogram(
    "jtpu_device_call_seconds",
    "wall time of one device executable call (host-side, around "
    "block_until_ready), labeled kind=segment|batch|sharded and "
    "phase=compile|execute; 'compile' is the shape's first call in this "
    "process — XLA compilation plus one execution",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0))
_LEVELS_TOTAL = obs_metrics.counter(
    "jtpu_search_levels_total",
    "search levels executed on device (per-call/per-segment deltas)")
_CHIP_LEVELS = obs_metrics.counter(
    "jtpu_keyed_chip_levels_total",
    "levels each chip's loop ran in keyed batch launches: per launch, "
    "the chip's own slowest key, labeled by the chip's place on the "
    "keys axis (0 off a mesh); jtpu_search_levels_total times the "
    "chips over it is the level skew between chips")
_SEGMENTS_TOTAL = obs_metrics.counter(
    "jtpu_search_segments_total", "checkpointed device segments run")
_FRONTIER_HWM = obs_metrics.gauge(
    "jtpu_search_frontier_rows_hwm",
    "high-water mark of live pool rows observed at segment boundaries")
_TRANSFER_BYTES = obs_metrics.counter(
    "jtpu_search_transfer_bytes_total",
    "packed-history and checkpoint bytes moved, labeled by direction")

_SHARD_IMBALANCE = obs_metrics.gauge(
    "jtpu_shard_imbalance_ratio",
    "pool-sharded search straggler imbalance: max over shards of live "
    "frontier rows divided by the mean (1.0 = perfectly balanced)")

# -- compile-cache accounting (doc/observability.md "Compile accounting"):
# every executable shape's first call in this process is a COLD compile
# (XLA compilation + one execution), every later call a cache hit of the
# in-process jit cache. The warm-executable-cache daemon (jtpu serve)
# must prove these counters move the right way.

_COMPILE_COLD = obs_metrics.counter(
    "jtpu_compile_cold_total",
    "executable shapes cold-compiled in this process (first call for "
    "the shape: XLA compilation + one execution), labeled kind")
_COMPILE_HIT = obs_metrics.counter(
    "jtpu_compile_cache_hit_total",
    "device calls that hit an already-compiled executable shape "
    "(in-process jit cache), labeled kind")
_COMPILE_SECONDS = obs_metrics.histogram(
    "jtpu_compile_seconds",
    "wall time of cold first calls per executable shape (XLA "
    "compilation + one execution), labeled kind",
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
             120.0, 300.0))
_PERSISTENT_HIT = obs_metrics.counter(
    "jtpu_persistent_cache_hit_total",
    "XLA persistent-compilation-cache hits (jax.monitoring "
    "/jax/compilation_cache/cache_hits; requires "
    "jax_compilation_cache_dir)")
_PERSISTENT_MISS = obs_metrics.counter(
    "jtpu_persistent_cache_miss_total",
    "XLA persistent-compilation-cache misses (jax.monitoring "
    "/jax/compilation_cache/cache_misses)")

_CACHE_LISTENER_HOOKED = False


def _ensure_cache_listener() -> None:
    """Register a jax.monitoring listener translating the persistent
    compilation cache's hit/miss events into registry counters. Once
    per process."""
    global _CACHE_LISTENER_HOOKED
    if _CACHE_LISTENER_HOOKED:
        return
    _CACHE_LISTENER_HOOKED = True
    from jax import monitoring

    def on_event(name: str, **kw) -> None:
        if "/compilation_cache/cache_hits" in name:
            _PERSISTENT_HIT.inc()
        elif "/compilation_cache/cache_misses" in name:
            _PERSISTENT_MISS.inc()

    monitoring.register_event_listener(on_event)


def persistent_cache_dir() -> Optional[str]:
    """The configured jax persistent-compilation-cache directory, or
    None when off (the # compile: line reports which)."""
    d = jax.config.jax_compilation_cache_dir
    return str(d) if d and jax.config.jax_enable_compilation_cache else None


def _note_call_phase(kind: str, phase: str, seconds: float) -> None:
    """Account one device call's phase: the wall-time histogram plus
    the cold-compile vs cache-hit counters (and their latency split).
    Shared by _timed_call and the resilience supervisor's segment
    path."""
    _ensure_cache_listener()
    _DEVICE_SECONDS.observe(seconds, kind=kind, phase=phase)
    if phase == "compile":
        _COMPILE_COLD.inc(kind=kind)
        _COMPILE_SECONDS.observe(seconds, kind=kind)
    else:
        _COMPILE_HIT.inc(kind=kind)


def compile_snapshot() -> Dict[str, Any]:
    """A registry readout of the compile/execute/transfer accounting —
    diff two of these around a check to attribute its wall-clock
    (:func:`compile_line`)."""
    return {
        "cold": _COMPILE_COLD.total(),
        "cache-hits": _COMPILE_HIT.total(),
        "persistent-hits": _PERSISTENT_HIT.total(),
        "persistent-misses": _PERSISTENT_MISS.total(),
        "compile-s": _COMPILE_SECONDS.total()["sum"],
        "execute-s": _DEVICE_SECONDS.total(phase="execute")["sum"],
        "transfer-bytes": _TRANSFER_BYTES.total(),
    }


def compile_delta(before: Dict[str, Any],
                  after: Optional[Dict[str, Any]] = None
                  ) -> Dict[str, Any]:
    """after - before, field-wise (after defaults to a fresh
    snapshot)."""
    after = after or compile_snapshot()
    return {k: after[k] - before.get(k, 0) for k in after}


def compile_line(delta: Dict[str, Any],
                 wall_s: Optional[float] = None) -> str:
    """One ``# compile:`` attribution line splitting a check's
    wall-clock into cold-compile / execute / transfer — printed by
    analyze and recover. ``delta`` comes from
    :func:`compile_delta` around the check."""
    pc = persistent_cache_dir()
    if pc is None:
        pc_bit = "persistent-cache=off"
    else:
        pc_bit = (f"persistent-cache hit={int(delta['persistent-hits'])}"
                  f"/miss={int(delta['persistent-misses'])}")
    line = (f"# compile: cold={int(delta['cold'])} shape(s) "
            f"{delta['compile-s']:.3f}s | "
            f"cache-hit={int(delta['cache-hits'])} | "
            f"execute={delta['execute-s']:.3f}s | "
            f"transfer={delta['transfer-bytes'] / 1e6:.1f}MB | {pc_bit}")
    if wall_s is not None:
        host = max(0.0, wall_s - delta["compile-s"] - delta["execute-s"])
        line += f" | host={host:.3f}s of {wall_s:.3f}s wall"
    return line

#: Executable shapes (cache key + padded input shape) that have already
#: run once in this process — the compile/execute phase separator.
_EXECUTED_SHAPES: set = set()


def _first_call(key: tuple) -> bool:
    """True iff this executable shape has not run in this process yet.
    First calls pay XLA compilation (the persistent compilation cache
    can shrink but not remove that phase), so their timings are recorded
    under phase="compile" and steady-state calls under "execute" — the
    split the ``# compile:`` and ``# search:`` summaries report."""
    first = key not in _EXECUTED_SHAPES
    _EXECUTED_SHAPES.add(key)
    return first


def _timed_call(kind: str, key: tuple, fn, args, **attrs):
    """Run one jitted executable with host-side phase timing. Returns
    ``(outputs, seconds, phase)`` — outputs fully materialized via
    block_until_ready so the clock covers the device work, not just the
    dispatch."""
    phase = "compile" if _first_call(key) else "execute"
    with obs.span(f"checker.device.{kind}", phase=phase, **attrs):
        t0 = _hosttime.perf_counter()
        out = jax.block_until_ready(fn(*args))
        dt = _hosttime.perf_counter() - t0
    _note_call_phase(kind, phase, dt)
    return out, dt, phase


def _cols_nbytes(cols: dict) -> int:
    """Host->device payload size of one packed-column set."""
    return int(sum(np.asarray(cols[c]).nbytes for c in _COLS))


# The jit caches key on kernel *identity* (two KernelSpecs sharing a name
# must not share compiled search code); the side table pins the object so
# its id cannot be recycled.
_KERNELS_BY_ID: Dict[int, KernelSpec] = {}


def _kernel_key(kernel: KernelSpec) -> int:
    # every jit-factory use passes through here, BEFORE any compile —
    # the persistent-cache listener must be live for the first miss
    _ensure_cache_listener()
    _KERNELS_BY_ID[id(kernel)] = kernel
    return id(kernel)


def _os_environ_get(name: str) -> Optional[str]:
    import os as _os
    return _os.environ.get(name)


def _unroll_factor(default: int = _UNROLL) -> int:
    """Search steps per while_loop iteration. JTPU_UNROLL overrides
    (unset or 0 mean "use the default"); the module default is 1
    (measured best on the CPU backend for the dense single-history
    shapes, where the sort math dominates) — call sites whose workload
    is loop-overhead-bound pass a different default."""
    return int(_os_environ_get("JTPU_UNROLL") or "0") or default


def _engine():
    """The process-default executable Engine (checker/engine.py). The
    lru_cache'd factories this module used to carry became Engine
    methods — same keys, same jit closures — so a long-lived daemon can
    enumerate, warm, and persist what these functions silently cached.
    Imported lazily: importing this module must not build an Engine."""
    from jepsen_tpu.checker import engine as engine_mod
    return engine_mod.default_engine()


def _jit_segment(kernel_id: int, capacity: int, window: int,
                 expand: Optional[int] = None, unroll: int = 1,
                 shard_axis: Optional[str] = None, stats: bool = False):
    """One bounded-iteration device segment of the single-history search
    (the checkpointed mode jepsen_tpu.resilience drives): takes the packed
    columns, a traced per-call iteration bound, and the search carry;
    returns the updated carry. The bound is traced (not static), so
    changing segment length never recompiles. With ``shard_axis`` the
    segment's pool/grids/sort rows are partitioned over the mesh axis:
    the executable of check_packed_sharded (every segment boundary is
    the global merge-sort barrier, so the host carry snapshot between
    segments IS a consistent cross-host checkpoint)."""
    return _engine().jit_segment(kernel_id, capacity, window, expand,
                                 unroll, shard_axis, stats)


def _popcount32_host(a: np.ndarray) -> np.ndarray:
    """Per-element population count of a uint32 array (the SWAR trick;
    numpy grew bitwise_count only in 2.0, and the host merge below must
    match the device's lax.population_count on older numpys too)."""
    a = np.asarray(a, np.uint32).copy()
    a = a - ((a >> np.uint32(1)) & np.uint32(0x55555555))
    a = ((a & np.uint32(0x33333333))
         + ((a >> np.uint32(2)) & np.uint32(0x33333333)))
    a = (a + (a >> np.uint32(4))) & np.uint32(0x0F0F0F0F)
    return ((a * np.uint32(0x01010101)) >> np.uint32(24)).astype(np.int64)


def _pool_sort_host(k, mask, cmask, state, alive) -> np.ndarray:
    """Host-side mirror of _search_fn's merge-sort lex order: the
    permutation putting pool rows deepest-first (valid rows keyed
    MAXK - depth, invalid rows sunk past MAXK, then k, mask words,
    state, cmask popcount, cmask words — exactly the device ``terms``
    sequence for tiebreak="lex").

    This is the global merge-sort barrier's ordering exposed to host
    code: the elastic fleet layer (jepsen_tpu.fleet) merges per-host
    pool shards with it, so a host-side merge and the device sort agree
    on which rows a truncation keeps and which rows a work-stealing
    redistribution deals first."""
    MAXK = np.int64(1 << 30)
    k = np.asarray(k, np.int64)
    mask = np.asarray(mask, np.uint32)
    cmask = np.asarray(cmask, np.uint32)
    state = np.asarray(state, np.int64)
    alive = np.asarray(alive, bool)
    MW = mask.shape[1] if mask.ndim == 2 else 1
    MC = cmask.shape[1] if cmask.ndim == 2 else 1
    mask = mask.reshape(k.shape[0], MW)
    cmask = cmask.reshape(k.shape[0], MC)
    depth = k + sum(_popcount32_host(mask[:, w]) for w in range(MW))
    key1 = np.where(alive, MAXK - depth, MAXK + 1 + k)
    pc = sum(_popcount32_host(cmask[:, w]) for w in range(MC))
    terms = ([key1, k] + [mask[:, w] for w in range(MW)]
             + [state, pc] + [cmask[:, w] for w in range(MC)])
    # np.lexsort's LAST key is primary; the device sort's FIRST is
    return np.lexsort(tuple(terms[::-1]))


def _carry0_host(capacity: int, window: int, n_cr: int, init_state,
                 n_required: int, stats_rows: int = 0) -> tuple:
    """Host-side initial search carry, mirroring _search_fn's carry0
    layout exactly (k, mask, cmask, state, alive, done, lossy, wovf,
    level, best_k, pool_k, pool_state, pool_alive). Built on host so the
    segment supervisor owns the carry end to end — it IS the checkpoint
    format (doc/resilience.md). ``stats_rows > 0`` appends the 14th
    per-level counter lane ([stats_rows, NSTAT] int32 — must equal the
    factory's LMAX+1) for stats-enabled segment executables."""
    MW = (window + 31) // 32
    MC = max((n_cr + 31) // 32, 1)
    k0 = np.zeros(capacity, np.int32)
    mask0 = np.zeros((capacity, MW), np.uint32)
    cmask0 = np.zeros((capacity, MC), np.uint32)
    state0 = np.full(capacity, int(np.int32(init_state)), np.int32)
    alive0 = np.arange(capacity) == 0
    carry = (k0, mask0, cmask0, state0, alive0,
             np.bool_(n_required == 0), np.bool_(False), np.bool_(False),
             np.int32(0), np.int32(0),
             k0.copy(), state0.copy(), alive0.copy())
    if stats_rows:
        carry = carry + (np.zeros((stats_rows, NSTAT), np.int32),)
    return carry


def _carry_active(carry, lmax: int) -> bool:
    """Host mirror of _search_fn's while condition: more segments are
    worth running iff the search isn't done, some pool row lives, and the
    level budget isn't exhausted."""
    done, alive, level = carry[5], carry[4], carry[8]
    return (not bool(done)) and bool(np.any(alive)) and int(level) <= lmax


def _summarize_carry(carry) -> tuple:
    """Host mirror of _search_fn's post-loop summary: returns (done,
    lossy, wovf, best_k, levels, pool). Stopping at the iteration budget
    with work left must not read as a refutation — exactly the
    whole-search loop's final lossy adjustment."""
    done, lossy, wovf = bool(carry[5]), bool(carry[6]), bool(carry[7])
    lossy = lossy or (not done and bool(np.any(carry[4])))
    return (done, lossy, wovf, int(carry[9]), int(carry[8]),
            (carry[10], carry[11], carry[12]))


def _reopen_carry(carry: tuple, n_required: int) -> tuple:
    """Clear a carry's ``done`` flag so a finished search continues over
    an EXTENDED history (the streaming online check, doc/serve.md
    "Streaming API"). ``done`` was latched by the device test
    ``fk >= n_required`` against the OLD required count; with more
    required ops appended past every packed row the same frontier
    configurations are exactly valid for the longer prefix — stable-
    prefix extension appends rows strictly after every existing return
    index, so masks, cmask, states and the pool all transfer unchanged.
    The level/best counters keep counting (that continuity is what the
    crash-resume chaos assertion reads)."""
    done = np.bool_(n_required == 0)
    return carry[:5] + (done,) + carry[6:]


def _fleet_hosts() -> int:
    """The JTPU_FLEET opt-in: N >= 2 routes single-history searches
    through the elastic fleet scheduler (jepsen_tpu.fleet) over an
    N-host (simulated on CPU) mesh. 0, 1, absent, or malformed all mean
    OFF — the single-host paths must stay byte-identical, the same
    kill-switch discipline as JTPU_TRACE / JTPU_PLAN_GATE."""
    v = _os_environ_get("JTPU_FLEET") or ""
    try:
        n = int(v.strip() or "0")
    except ValueError:
        return 0
    return n if n >= 2 else 0


def _segment_config(segment_iters: Optional[int]) -> int:
    """Resolve the segment length, in device levels: an explicit
    argument wins, then JTPU_SEGMENT_ITERS, then DEFAULT_SEGMENT_ITERS.
    Anything but a positive integer raises ValueError."""
    name = "segment_iters"
    if segment_iters is None:
        env = _os_environ_get("JTPU_SEGMENT_ITERS")
        if env is None or not env.strip():
            return DEFAULT_SEGMENT_ITERS
        name, segment_iters = "JTPU_SEGMENT_ITERS", env
    try:
        seg = int(segment_iters)
    except ValueError:
        seg = 0
    if seg < 1:
        raise ValueError(
            f"{name} must be a positive integer, got {segment_iters!r}")
    return seg


def _jit_batch(kernel_id: int, capacity: int, window: int,
               expand: Optional[int] = None, unroll: int = 1,
               tiebreak: str = "lex", mesh=None,
               axis: Optional[str] = None):
    return _engine().jit_batch(kernel_id, capacity, window, expand,
                               unroll, tiebreak, mesh, axis)


def _jit_batch_segment(kernel_id: int, capacity: int, window: int,
                       expand: Optional[int] = None, unroll: int = 1):
    """One checkpointed segment vmapped over a GANG of same-bucket
    single-key histories (engine.jit_batch_segment) — the executable
    behind :func:`check_packed_gang` and the serve daemon's concurrent
    batching."""
    return _engine().jit_batch_segment(kernel_id, capacity, window,
                                       expand, unroll)


#: Max crashed ('info') ops per key (four crashed-mask words). Crash-
#: heavy searches are the hardest axis (every crashed op is optional
#: at every point), so wide-crash histories lean on the canonical-order
#: and subset-dominance prunings and may escalate far — still usually
#: faster than the CPU fallback they previously forced.
CRASH_MAX = 128


def _split_packed(p: PackedHistory, breq: int, cr: int,
                  kernel: Optional[KernelSpec] = None) -> Optional[dict]:
    """Split an (unpadded) PackedHistory into the padded required section
    [breq] and crashed section [cr] device arrays. Returns None when the
    history has more crashed ops than the crashed bitmask can hold."""
    nr = p.n_required
    n_cr = p.n - nr
    if n_cr > cr:
        return None

    def pad(a, width, fill):
        out = np.full(width, fill, dtype=np.int32)
        out[:a.shape[0]] = a
        return out

    from jepsen_tpu.models.core import NIL_ID
    inf = int(RET_INF)
    inv_req = pad(p.inv[:nr], breq, inf)
    # ro[j] = 1 iff required op j is read-only (see kernel.readonly) —
    # feeds the device search's greedy pure-op closure. Padding rows 0.
    # The hook is a pure function of (f, v1, v2), so it is asked once
    # per distinct triple: rows sorted by the triple form runs of equal
    # triples (an exact grouping), one hook call answers each run.
    ro = np.zeros(breq, dtype=np.int32)
    if kernel is not None and kernel.readonly is not None and nr:
        f, v1, v2 = p.f[:nr], p.v1[:nr], p.v2[:nr]
        order = np.lexsort((v2, v1, f))
        sf, s1, s2 = f[order], v1[order], v2[order]
        starts = np.ones(nr, dtype=bool)
        starts[1:] = (sf[1:] != sf[:-1]) | (s1[1:] != s1[:-1]) \
            | (s2[1:] != s2[:-1])
        flags = np.array([1 if kernel.readonly(int(sf[j]), int(s1[j]),
                                               int(s2[j])) else 0
                          for j in np.flatnonzero(starts)], dtype=np.int32)
        ro[order] = flags[np.cumsum(starts) - 1]
    # sm: suffix-min of padded inv (padding is RET_INF, so entries <= nr
    # equal the required-only suffix-min — computed once, reused by fr)
    sm = _suffix_min_inv(inv_req, breq)
    # fr[j] = 1 iff required op j is FORCED: no other required op is
    # concurrent with it (sufmin[j+1] >= ret[j]), so at frontier j with
    # an empty mask the op is the unique required candidate and the
    # search can advance through it without paying a level (the device
    # fast-forward; crashed candidates are excluded dynamically via the
    # per-row boundary). Padding rows 0.
    fr = np.zeros(breq, dtype=np.int32)
    if nr:
        idx = np.searchsorted(sm[:nr + 1], p.ret[:nr], side="left")
        fr[:nr] = (idx <= np.arange(nr) + 1).astype(np.int32)
    # cps[j]: previous crashed op with identical (f, v1, v2), or -1 —
    # drives the canonical-order pruning (identical crashed ops are
    # interchangeable, so only the lowest available untaken one may be
    # linearized first).
    cps = np.full(cr, -1, dtype=np.int32)
    seen: dict = {}
    for j in range(n_cr):
        key = (int(p.f[nr + j]), int(p.v1[nr + j]), int(p.v2[nr + j]))
        if key in seen:
            cps[j] = seen[key]
        seen[key] = j
    return {
        "f": pad(p.f[:nr], breq, 0),
        "v1": pad(p.v1[:nr], breq, NIL_ID),
        "v2": pad(p.v2[:nr], breq, NIL_ID),
        "ro": ro,
        "fr": fr,
        "inv": inv_req,
        "ret": pad(p.ret[:nr], breq, inf),
        "sm": sm,
        "cf": pad(p.f[nr:], cr, 0),
        "cv1": pad(p.v1[nr:], cr, NIL_ID),
        "cv2": pad(p.v2[nr:], cr, NIL_ID),
        "cinv": pad(p.inv[nr:], cr, inf),
        "cps": cps,
        "nr": np.int32(nr),
        # two's-complement view: a state word with the sign bit set (e.g.
        # queue nibble 7 count >= 8) must wrap, not raise OverflowError
        "ini": np.asarray(int(p.init_state) & 0xFFFFFFFF,
                          np.uint32).view(np.int32)[()],
    }


_COLS = ("f", "v1", "v2", "ro", "fr", "inv", "ret", "sm", "cf", "cv1",
         "cv2", "cinv", "cps", "nr", "ini")


def _window_needed(p: PackedHistory) -> int:
    """Smallest window W such that no candidate ever falls beyond the
    frontier window: max over k of (largest j with inv[j] < ret[k]) - k + 1.
    Computed host-side in O(n log n) via the non-decreasing suffix-min of
    inv — lets the escalation ladder skip rungs that would only report
    window overflow."""
    nr = p.n_required
    if nr == 0:
        return 0
    inv = p.inv[:nr]
    sm = _suffix_min_inv(inv, nr)[:nr]     # non-decreasing
    # per frontier k: the largest j with sufmin[j] < ret[k] is
    # searchsorted(sm, ret[k]) - 1; j >= k always holds since
    # sm[k] <= inv[k] < ret[k]. One vectorized pass for all k.
    idx = np.searchsorted(sm, p.ret[:nr], side="left")
    return max(1, int((idx - np.arange(nr)).max()))


def _crash_width(n_cr: int) -> Optional[int]:
    """Padded crashed-section width, or None when over the bitmask limit."""
    if n_cr == 0:
        return 0
    if n_cr > CRASH_MAX:
        return None
    return _bucket(n_cr, lo=8)


def _check_window(window: int) -> None:
    if window > MAX_WINDOW:
        raise ValueError(
            f"window {window} > {MAX_WINDOW}: wider windows need more mask "
            f"words than the search carries")


def _result(done: bool, lossy: bool, wovf: bool, best_k: int, levels: int,
            p: Optional[PackedHistory] = None,
            pool: Optional[tuple] = None) -> Dict[str, Any]:
    if done:
        return {"valid": True, "levels": levels, "backend": "tpu"}
    if not (lossy or wovf):
        out = {"valid": False, "levels": levels,
               "max-linearized-prefix": best_k, "backend": "tpu"}
        if p is not None and p.ops and best_k < len(p.ops):
            inv_op = p.ops[best_k][0]
            out["frontier-op"] = inv_op.to_dict() if inv_op else None
        if pool is not None:
            # Frontier evidence straight off the device: the last living
            # pool's deepest configs (counterexample.analysis consumes
            # these directly — no CPU re-search at 100k+ ops; reference
            # checker.clj:96-107 renders from the analysis configs).
            # The prefix is re-anchored to the POOL's deepest k so the
            # reported states belong to the reported frontier: best_k
            # (the all-time expansion max) can exceed it when the
            # deepest config died childless in an earlier iteration;
            # mixing that k with shallower states would caption the
            # rendering with step outcomes computed from the wrong
            # frontier. The all-time max stays as deepest-expanded.
            pk, ps, pa = (np.asarray(x) for x in pool)
            live = pa & (pk == (pk * pa).max())
            if live.any():
                pool_k = int((pk * pa).max())
                out["final-states"] = sorted(
                    {int(s) for s in ps[live]})[:16]
                if pool_k != best_k:
                    out["deepest-expanded"] = best_k
                    out["max-linearized-prefix"] = pool_k
                    if p is not None and p.ops and pool_k < len(p.ops):
                        inv_op = p.ops[pool_k][0]
                        out["frontier-op"] = (inv_op.to_dict()
                                              if inv_op else None)
        return out
    return {"valid": UNKNOWN, "levels": levels,
            "error": ("beam truncated the frontier" if lossy
                      else "candidate window exceeded"),
            "capacity-overflow": bool(lossy),
            "window-overflow": bool(wovf),
            "backend": "tpu"}


#: Capacity/expand escalation for NARROW histories (window <= 32),
#: window chosen separately per history (_ladder_for). Best-first rungs
#: (expand < capacity) find witnesses cheaply — for most *valid*
#: histories the first rung completes regardless of reachable-space
#: size, since unexpanded pool rows double as the backtrack stack; the
#: readonly closure absorbs whole read runs per step, so a slim first
#: rung decides most histories an order of magnitude faster than a wide
#: one (10k-op flagship on the CPU backend: 9.9s at 1024/64, 1.38s at
#: 128/8, 0.62s at the 32/4 rung _capacity_ladder() picks there —
#: near-identical level counts). Bigger rungs refute exhaustively (pool
#: death with no truncation) or recover witnesses a slim pool greedily
#: dropped. Wide histories use WIDE_LADDER instead (expansion must
#: track frontier width).
CAPACITY_LADDER = ((128, 8), (1024, 64), (4096, 256), (16384, 1024))

#: CPU-backend first rung. Measured on the 10k/100k flagship shapes:
#: per-level cost on CPU scales with pool rows (sort-dominated), so a
#: slim pool decides valid histories fastest — 10k: 1.38s -> 0.62s,
#: 100k: 13.2s -> 6.1s warm — while on TPU the vector lanes amortize
#: pool width and the wider rung's fewer levels win. Harder histories
#: just escalate one rung sooner; rungs 2+ are identical.
CPU_FIRST_RUNG = (32, 4)


def _capacity_ladder():
    """The capacity/expand ladder for the active JAX backend.

    JTPU_FIRST_RUNG="capacity,expand" pins the first rung explicitly, so
    the winning shape on a given accelerator can be deployed via env
    without a code change."""
    import os as _os
    env = _os.environ.get("JTPU_FIRST_RUNG")
    if env:
        try:
            cap, exp = (int(x) for x in env.split(","))
            return ((cap, exp),) + CAPACITY_LADDER[1:]
        except ValueError:
            pass  # malformed override: fall through to the default
    if jax.default_backend() == "cpu":
        return (CPU_FIRST_RUNG,) + CAPACITY_LADDER[1:]
    return CAPACITY_LADDER


def _window_bucket(wneed: int) -> int:
    """The smallest supported window covering the history's needed
    candidate width (capped at MAX_WINDOW: beyond it refutation is
    impossible anyway, but a witness may still be found)."""
    for w in (32, 64, 128):
        if wneed <= w:
            return w
    return MAX_WINDOW


#: Expansion-heavy rungs for WIDE histories (needed window > 32). A
#: wide frontier grows ~window new configs per depth, so a slim
#: best-first expansion falls behind and goes lossy long before any
#: witness: on wide_history(100,4) every slim rung (128/8 .. 4096/256)
#: burns its full level budget lossy, while 512/512 decides in 144
#: levels / ~6 s warm on the CPU backend (vs 343 s for the native DFS).
#: Expansion comparable to the frontier width is the knob, not pool
#: capacity.
WIDE_LADDER = ((512, 512), (4096, 1024), (16384, 4096))


def _ladder_for(wneed: int):
    """Capacity escalates at exactly the window this history needs —
    decoupled from width, so a narrow crash-heavy history never pays
    for multi-word masks. Wide histories (multi-word windows) get the
    expansion-heavy rungs instead of the slim best-first ones."""
    w = _window_bucket(wneed)
    if wneed > MAX_WINDOW:
        # Refutation is impossible at any supported window (overflow is
        # inevitable), so rungs exist only to hunt a witness — and past
        # 4096/1024 the hunt has diminishing returns. Cap the ladder
        # instead of burning minutes on the widest pool; >128-offset
        # exact checking is the native engine's regime (doc/native.md).
        return tuple((c, w, e) for c, e in WIDE_LADDER[:2])
    if wneed > 32:
        return tuple((c, w, e) for c, e in WIDE_LADDER)
    return tuple((c, w, e) for c, e in _capacity_ladder())


def _prep_single(p: PackedHistory,
                 kernel: KernelSpec) -> tuple:
    """Shared single-history preamble for check_packed_tpu and
    check_packed_sharded: (cols, None) on success, (None, result) for
    the trivially-complete and crashed-set-overflow early outs."""
    if p.n_required == 0:
        return None, {"valid": True, "levels": 0, "backend": "tpu"}
    cr = _crash_width(p.n - p.n_required)
    with obs.span("checker.pack"):
        cols = (None if cr is None
                else _split_packed(p, _bucket(p.n_required), cr, kernel))
    if cols is None:
        return None, {
            "valid": UNKNOWN, "backend": "tpu",
            "error": f"{p.n - p.n_required} crashed ops exceed the "
                     f"crashed-set width {CRASH_MAX}"}
    return cols, None


def check_packed_tpu(p: PackedHistory, kernel: KernelSpec,
                     capacity: Optional[int] = None,
                     window: Optional[int] = WINDOW,
                     expand: Optional[int] = None,
                     segment_iters: Optional[int] = None,
                     deadline_s: Optional[float] = None) -> Dict[str, Any]:
    """Check one packed single-key history on the default JAX backend.

    capacity=None auto-escalates through _ladder_for's rungs
    (CAPACITY_LADDER at the history's needed window, or WIDE_LADDER for
    multi-word windows), retrying on capacity overflow (and on window
    overflow while the window can still grow).
    With an explicit capacity, ``expand`` < capacity selects best-first
    search (None = exhaustive level-synchronous BFS).

    The search runs SEGMENTED under the resilience supervisor
    (jepsen_tpu.resilience): bounded device segments with host
    checkpoints between them, OOM shrink-and-retry, and an optional
    per-segment wedge watchdog (``deadline_s``, falling back to the CPU
    backend mid-run). ``segment_iters`` (levels a segment, a positive
    integer) overrides JTPU_SEGMENT_ITERS."""
    if window is not None:
        _check_window(window)
    seg = _segment_config(segment_iters)
    nfleet = _fleet_hosts()
    if nfleet:
        # Elastic fleet opt-in (JTPU_FLEET=N, doc/resilience.md
        # "Elastic fleet"): the search runs under the fleet scheduler —
        # N logical hosts each owning a pool shard, merged at the
        # global sort barrier, surviving host loss/join/skew. Off
        # (0/absent), this branch is never taken.
        from jepsen_tpu import fleet as fleet_mod
        return fleet_mod.check_packed_fleet(
            p, kernel, hosts=nfleet, capacity=capacity, window=window,
            expand=expand, segment_iters=seg)
    from jepsen_tpu import resilience
    return resilience.supervised_check_packed(
        p, kernel, capacity=capacity, window=window, expand=expand,
        segment_iters=seg, deadline_s=deadline_s)


#: Fault-injection seam for the gang dispatch path (the batched twin of
#: resilience._inject_fault): when set, called with the gang's packed
#: members right before any device work — raising from it simulates a
#: device failure of the WHOLE batched call, which is exactly the event
#: resilience.bisect_poison isolates by splitting and re-running.
#: tests/test_serve.py and tools/chaos_matrix.py's serve-batch-poison
#: scenario set and clear it.
_GANG_FAULT: Optional[Callable[[list], None]] = None


def check_packed_gang(pks: Sequence[PackedHistory], kernel: KernelSpec,
                      deadlines: Optional[Sequence[Optional[float]]]
                      = None,
                      segment_iters: Optional[int] = None
                      ) -> List[Dict[str, Any]]:
    """Check a GANG of packed single-key histories in ONE vmapped
    device call per segment — the serve daemon's concurrent-batching
    seam (doc/serve.md "Concurrent batching").

    Per-member semantics are exactly :func:`check_packed_tpu`'s
    segmented search: the same escalation ladder (``_ladder_for`` at
    the member's needed window), the same per-lane search body
    (engine.jit_batch_segment vmaps the ``segment=True`` closure
    jit_segment builds), the same carry summary — so member ``i``'s
    verdict and counterexample artifacts are identical to checking it
    alone. P-compositionality (arXiv:1504.00204) grounds the claim:
    independent histories are independent sub-problems, and a vmap
    lane neither reads nor writes any other lane.

    ``deadlines[i]`` is an ABSOLUTE ``time.monotonic()`` deadline for
    member ``i`` (None = unbounded). A member past its deadline is
    cancelled at the next segment barrier — its lane's live pool rows
    are cleared host-side, making its vmapped while-condition false, so
    later segments no-op the lane while the cohort keeps running — and
    it reports the serve timeout shape ``{"valid": "unknown", "error":
    ":info/timeout", "error-class": "wedge"}``.

    Deliberately NO OOM-halving or plan-seeding happens here: shrinking
    the pool mid-gang would change every lane's shape and break the
    serial-equivalence contract. A failed device call raises to the
    caller, where :func:`jepsen_tpu.resilience.bisect_poison` splits
    the gang and converges on the poison member; callers price the
    whole gang beforehand via
    :func:`jepsen_tpu.checker.plan.gang_footprint`.

    Returns one result dict per member, aligned with ``pks``.
    """
    pks = list(pks)
    if not pks:
        return []
    if _GANG_FAULT is not None:
        _GANG_FAULT(pks)
    results: List[Optional[Dict[str, Any]]] = [None] * len(pks)
    groups = _gang_groups(pks, results)
    if not groups:
        return results
    seg = _segment_config(segment_iters)
    for ladder, idx in groups.items():
        _gang_ladder(pks, kernel, idx, ladder, seg, deadlines, results)
    return results


def _gang_groups(pks, results) -> Dict[tuple, list]:
    """Per-member early outs (the _prep_single trivial / crashed-set-
    overflow cases) written into ``results``, then group survivors by
    their exact escalation ladder: members needing different window
    buckets must escalate exactly as they would serially, not on a
    merged ladder."""
    groups: Dict[tuple, list] = {}
    for i, p in enumerate(pks):
        if p.n_required == 0:
            results[i] = {"valid": True, "levels": 0, "backend": "tpu"}
        elif _crash_width(p.n - p.n_required) is None:
            results[i] = {
                "valid": UNKNOWN, "backend": "tpu",
                "error": f"{p.n - p.n_required} crashed ops exceed the "
                         f"crashed-set width {CRASH_MAX}"}
        else:
            groups.setdefault(
                _ladder_for(_window_needed(p)), []).append(i)
    return groups


def _gang_ladder(pks, kernel, idx, ladder, seg, deadlines,
                 results) -> None:
    """Run one ladder-homogeneous gang group through the escalation
    ladder, writing each member's result into ``results``."""
    kid = _kernel_key(kernel)
    unroll = _unroll_factor()
    breq = max(_bucket(pks[i].n_required) for i in idx)
    crw = max(_crash_width(pks[i].n - pks[i].n_required) for i in idx)
    cols = {i: _split_packed(pks[i], breq, crw, kernel) for i in idx}
    work: Dict[int, list] = {i: [] for i in idx}
    pending = list(idx)
    for cap, win, exp in ladder:
        if not pending:
            return
        rows = [cols[i] for i in pending]
        arrays = [np.stack([np.asarray(c[col]) for c in rows])
                  for col in _COLS]
        cr_pad = int(rows[0]["cf"].shape[0])
        lmax = _level_budget(breq, cr_pad)
        carry_b = tuple(
            np.stack(lanes) for lanes in zip(*(
                _carry0_host(cap, win, cr_pad, c["ini"], int(c["nr"]))
                for c in rows)))
        fn = _jit_batch_segment(kid, cap, win, exp, unroll)
        shape_key = ("batch-segment", kid, cap, win, exp, unroll,
                     len(pending), breq, cr_pad)
        lane_live = [True] * len(pending)
        timed_out: set = set()
        while any(lane_live):
            outs, _, _ = _timed_call(
                "batch-segment", shape_key, fn,
                arrays + [np.int32(seg), carry_b],
                rung=(cap, win, exp), gang=len(pending))
            # writable host snapshot: the checkpoint, and the thing the
            # barrier below edits to cancel an overdue lane
            carry_b = tuple(np.array(x) for x in outs)
            _SEGMENTS_TOTAL.inc()
            now = _hosttime.monotonic()
            for j, i in enumerate(pending):
                if not lane_live[j]:
                    continue
                lane = tuple(a[j] for a in carry_b)
                if not _carry_active(lane, lmax):
                    lane_live[j] = False
                    continue
                dl = deadlines[i] if deadlines else None
                if dl is not None and now >= dl:
                    # deadline barrier-cancel: clear the lane's live
                    # rows so its while-condition goes false; the
                    # cohort's lanes are untouched
                    carry_b[4][j, ...] = False
                    lane_live[j] = False
                    timed_out.add(i)
        still = []
        for j, i in enumerate(pending):
            lane = tuple(a[j] for a in carry_b)
            if i in timed_out:
                # a cancelled lane's carry must NOT be summarized —
                # "no live rows" would misread as a refutation. This is
                # the serve timeout result shape (serve._run_one).
                results[i] = {
                    "valid": UNKNOWN, "error": ":info/timeout",
                    "error-class": "wedge", "backend": "tpu",
                    "levels": int(lane[8]), "rung": (cap, win, exp),
                    "gang-cancelled": True}
                continue
            done, lossy, wovf, best, levels, pool = \
                _summarize_carry(lane)
            _LEVELS_TOTAL.inc(levels)
            out = _result(done, lossy, wovf, best, levels, pks[i],
                          pool=pool)
            out["rung"] = (cap, win, exp)
            out["crash-width"] = _crash_width(
                pks[i].n - pks[i].n_required) or 0
            out["tiebreak"] = "lex"
            work[i].append(((cap, win, exp), out["crash-width"], "lex",
                            levels))
            out["work"] = list(work[i])
            out["gang-size"] = len(pending)
            results[i] = out
            if out["valid"] is UNKNOWN and not (
                    bool(wovf) and win >= MAX_WINDOW
                    and not bool(lossy)):
                still.append(i)
        pending = still


def check_packed_gang_fleet(pks: Sequence[PackedHistory],
                            kernel: KernelSpec,
                            hosts: Sequence[Any],
                            deadlines: Optional[Sequence[Optional[float]]]
                            = None,
                            segment_iters: Optional[int] = None,
                            on_round: Optional[Any] = None,
                            max_retries: int = 2,
                            segment_deadline_s: float = 120.0,
                            stats: Optional[Dict[str, int]] = None,
                            trail: Optional[list] = None,
                            straggler: Optional[Any] = None
                            ) -> List[Dict[str, Any]]:
    """:func:`check_packed_gang`, placed onto FLEET HOSTS instead of
    the local device: each segment round shards the gang's vmapped
    lanes over the live hosts (contiguous chunks), merges the advanced
    carries back at the leader-held barrier, and re-meshes the next
    round onto the survivors when a host dies mid-segment — the
    orphaned lanes simply keep their pre-round carry and re-run on the
    surviving mesh, so no verdict is lost with the host.

    Failure discipline at the shard boundary (the serve-side DCN-vs-
    poison split): :class:`jepsen_tpu.fleet.HostLostError` and
    :data:`jepsen_tpu.resilience.RETRYABLE` worker failures
    (DCN/TRANSIENT) are absorbed HERE — bounded in-place retry, then
    host-lost — and never reach :func:`jepsen_tpu.resilience.
    bisect_poison`, which must only ever see deterministic per-request
    failures (OOM/WEDGE/FATAL raise through as before). When EVERY
    host is gone, still-searching lanes return ``{"valid": "unknown",
    "error": "all fleet hosts lost", "fleet-lost": True}`` with no
    error-class: the serve daemon's UNKNOWN-rerun loop then escalates
    them on the serial/CPU path with zero breaker impact.

    ``on_round(round_idx, hosts)`` is the chaos seam (fires after each
    merge barrier); ``stats``/``trail`` collect placer counters and
    replayable events. Per-member verdicts remain identical to
    :func:`check_packed_gang`'s (same ladder, same lane body, same
    summaries)."""
    pks = list(pks)
    if not pks:
        return []
    if _GANG_FAULT is not None:
        _GANG_FAULT(pks)
    results: List[Optional[Dict[str, Any]]] = [None] * len(pks)
    groups = _gang_groups(pks, results)
    if not groups:
        return results
    seg = _segment_config(segment_iters)
    for ladder, idx in groups.items():
        _gang_ladder_fleet(pks, kernel, idx, ladder, seg, deadlines,
                           results, hosts, on_round, max_retries,
                           segment_deadline_s, stats, trail, straggler)
    return results


def _fleet_lost_result(lane_levels: int) -> Dict[str, Any]:
    """The all-hosts-lost lane shape — UNKNOWN with no error-class, so
    the serve daemon re-runs it serially instead of counting a breaker
    failure or a poison."""
    return {"valid": UNKNOWN, "backend": "tpu",
            "error": "all fleet hosts lost", "fleet-lost": True,
            "levels": lane_levels}


def _gang_ladder_fleet(pks, kernel, idx, ladder, seg, deadlines,
                       results, hosts, on_round, max_retries,
                       segment_deadline_s, stats, trail,
                       straggler=None) -> None:
    """One ladder-homogeneous gang group, sharded over fleet hosts
    per segment round (see :func:`check_packed_gang_fleet`)."""
    from jepsen_tpu import resilience
    from jepsen_tpu.fleet import HostLostError

    def bump(key, n=1):
        if stats is not None:
            stats[key] = stats.get(key, 0) + n

    def note(event, **kw):
        if trail is not None:
            trail.append(dict({"event": event}, **kw))

    breq = max(_bucket(pks[i].n_required) for i in idx)
    crw = max(_crash_width(pks[i].n - pks[i].n_required) for i in idx)
    cols = {i: _split_packed(pks[i], breq, crw, kernel) for i in idx}
    work: Dict[int, list] = {i: [] for i in idx}
    dead: set = set()
    pending = list(idx)
    round_idx = 0
    for cap, win, exp in ladder:
        if not pending:
            return
        rows = [cols[i] for i in pending]
        arrays = [np.stack([np.asarray(c[col]) for c in rows])
                  for col in _COLS]
        cr_pad = int(rows[0]["cf"].shape[0])
        lmax = _level_budget(breq, cr_pad)
        carry_b = tuple(
            np.stack(lanes) for lanes in zip(*(
                _carry0_host(cap, win, cr_pad, c["ini"], int(c["nr"]))
                for c in rows)))
        lane_live = [True] * len(pending)
        timed_out: set = set()
        fleet_lost = False
        while any(lane_live):
            # pre-round liveness sweep: a host that died BETWEEN rounds
            # (no shard outstanding) shrinks the mesh here, before any
            # lane is placed on it
            swept = False
            for h in hosts:
                if id(h) not in dead and not h.alive():
                    dead.add(id(h))
                    swept = True
                    bump("host-losses")
                    note("host-lost", host=getattr(h, "name", "?"),
                         round=round_idx)
            live = [h for h in hosts if id(h) not in dead]
            if not live:
                fleet_lost = True
                break
            if swept:
                bump("remeshes")
                note("remesh", round=round_idx, live=len(live),
                     rung=[cap, win, exp])
            if straggler is not None:
                # straggler advisory: unflagged hosts first (stable
                # order otherwise) — with fewer shards than hosts a
                # flagged host simply receives none. Verdict-neutral:
                # every lane computes the same carry wherever it runs.
                live = straggler.prefer(live)
            # shard ALL pending lanes over the live hosts: inactive
            # lanes no-op in-device (their while-condition is false),
            # which keeps every host's shard shape round-stable
            nshards = min(len(live), len(pending))
            sels = [s for s in np.array_split(np.arange(len(pending)),
                                              nshards) if s.size]
            new_carry = tuple(np.array(x) for x in carry_b)
            subs = []
            for h, sel in zip(live, sels):
                sub_cols = [np.ascontiguousarray(a[sel])
                            for a in arrays]
                sub_carry = tuple(np.ascontiguousarray(c[sel])
                                  for c in carry_b)
                h.submit_gang(sub_cols, sub_carry, kernel, seg,
                              (cap, win, exp), round_idx)
                subs.append((h, sel, sub_cols, sub_carry))
            advanced: set = set()
            lost_this_round = False
            for h, sel, sub_cols, sub_carry in subs:
                attempt = 0
                while True:
                    try:
                        out, _secs = h.collect_gang(segment_deadline_s)
                        if straggler is not None:
                            from jepsen_tpu.obs import straggler as \
                                _straggler_mod
                            straggler.observe_segment(
                                _straggler_mod.host_key(h), _secs)
                        for tgt, c in zip(new_carry, out):
                            tgt[sel] = c
                        advanced.update(int(j) for j in sel)
                        break
                    except HostLostError as e:
                        # the shard's lanes keep their pre-round carry
                        # (merge-back for free) and re-run on the
                        # survivors next round
                        dead.add(id(h))
                        lost_this_round = True
                        bump("host-losses")
                        note("host-lost",
                             host=getattr(h, "name", "?"),
                             round=round_idx, error=str(e))
                        break
                    except RuntimeError as e:
                        cls = resilience.classify_failure(e)
                        if cls not in resilience.RETRYABLE:
                            # deterministic per-request failure:
                            # bisect_poison's territory — raise
                            raise
                        if attempt < max_retries and h.alive():
                            attempt += 1
                            bump("dcn-retries")
                            note("host-retry",
                                 host=getattr(h, "name", "?"),
                                 round=round_idx, attempt=attempt,
                                 **{"class": cls})
                            h.submit_gang(sub_cols, sub_carry, kernel,
                                          seg, (cap, win, exp),
                                          round_idx)
                            continue
                        # retries exhausted: a persistently flaky
                        # interconnect is a lost host, not a poison
                        dead.add(id(h))
                        lost_this_round = True
                        bump("host-losses")
                        note("host-lost",
                             host=getattr(h, "name", "?"),
                             round=round_idx, error=str(e),
                             **{"class": cls})
                        break
            carry_b = new_carry
            _SEGMENTS_TOTAL.inc()
            bump("rounds")
            if lost_this_round:
                bump("remeshes")
                n_live = sum(1 for h in hosts
                             if id(h) not in dead and h.alive())
                verdict = None
                try:
                    from jepsen_tpu.checker import plan as plan_mod
                    verdict = plan_mod.check_remesh(
                        pks[pending[0]], max(1, n_live), cap, win, exp)
                except Exception:  # noqa: BLE001 — advisory only
                    verdict = None
                note("remesh", round=round_idx, live=n_live,
                     rung=[cap, win, exp],
                     ok=None if verdict is None else verdict.get("ok"))
            if on_round is not None:
                on_round(round_idx, hosts)
            round_idx += 1
            now = _hosttime.monotonic()
            for j, i in enumerate(pending):
                if not lane_live[j]:
                    continue
                # only a lane that actually advanced this round can be
                # declared finished; a lost shard's lanes stay live on
                # their pre-round carry
                if j in advanced:
                    lane = tuple(a[j] for a in carry_b)
                    if not _carry_active(lane, lmax):
                        lane_live[j] = False
                        continue
                dl = deadlines[i] if deadlines else None
                if dl is not None and now >= dl:
                    carry_b[4][j, ...] = False
                    lane_live[j] = False
                    timed_out.add(i)
        still = []
        for j, i in enumerate(pending):
            lane = tuple(a[j] for a in carry_b)
            if i in timed_out:
                results[i] = {
                    "valid": UNKNOWN, "error": ":info/timeout",
                    "error-class": "wedge", "backend": "tpu",
                    "levels": int(lane[8]), "rung": (cap, win, exp),
                    "gang-cancelled": True}
                continue
            if fleet_lost and lane_live[j]:
                results[i] = _fleet_lost_result(int(lane[8]))
                continue
            done, lossy, wovf, best, levels, pool = \
                _summarize_carry(lane)
            _LEVELS_TOTAL.inc(levels)
            out = _result(done, lossy, wovf, best, levels, pks[i],
                          pool=pool)
            out["rung"] = (cap, win, exp)
            out["crash-width"] = _crash_width(
                pks[i].n - pks[i].n_required) or 0
            out["tiebreak"] = "lex"
            work[i].append(((cap, win, exp), out["crash-width"], "lex",
                            levels))
            out["work"] = list(work[i])
            out["gang-size"] = len(pending)
            out["fleet"] = True
            results[i] = out
            if out["valid"] is UNKNOWN and not (
                    bool(wovf) and win >= MAX_WINDOW
                    and not bool(lossy)):
                still.append(i)
        if fleet_lost:
            # no capacity to escalate: lanes already holding a genuine
            # rung summary keep it (UNKNOWNs re-run serially upstream)
            return
        pending = still


#: Mesh axis name for pool-sharded single-history searches.
POOL_AXIS = "pool"


def _mesh_context(mesh):
    """Activate a mesh for tracing/execution: ``jax.set_mesh`` where
    this jax has it, else the legacy ``Mesh.__enter__`` global-mesh
    context (pre-0.5 jax) — same semantics for the sharding
    constraints the search body carries."""
    setm = getattr(jax, "set_mesh", None)
    if setm is not None:
        return setm(mesh)
    return mesh


def _shard_balance(pool, naxis: int) -> Optional[Dict[str, Any]]:
    """Per-device frontier accounting for a pool-sharded search. Each
    mesh-axis shard owns ``capacity / naxis`` contiguous pool rows;
    because the merge sort is global, a shard hoarding most of the live
    frontier means the others' lanes idle through the step math — the
    straggler signature. Returns ``{"devices", "live-rows",
    "deepest-k", "imbalance-ratio"}`` (max live rows over mean; 1.0 is
    perfectly balanced) and feeds ``jtpu_shard_imbalance_ratio``. The
    checkpointed path adds ``"peak-live-rows"``: each shard's high-water
    mark over the segment barriers."""
    pk, ps, pa = (np.asarray(x) for x in pool)
    cap = int(pa.shape[0])
    if naxis <= 0 or cap % naxis:
        return None
    per = cap // naxis
    live = [int(np.count_nonzero(pa[i * per:(i + 1) * per]))
            for i in range(naxis)]
    deepest = [int(np.max(pk[i * per:(i + 1) * per]
                          * pa[i * per:(i + 1) * per], initial=0))
               for i in range(naxis)]
    mean = sum(live) / naxis
    ratio = round(max(live) / mean, 3) if mean > 0 else 1.0
    _SHARD_IMBALANCE.set(ratio)
    return {"devices": naxis, "live-rows": live, "deepest-k": deepest,
            "imbalance-ratio": ratio}


def check_packed_sharded(p: PackedHistory, kernel: KernelSpec,
                         mesh: "jax.sharding.Mesh",
                         capacity: int = 4096,
                         window: Optional[int] = None,
                         expand: Optional[int] = None,
                         segment_iters: Optional[int] = None,
                         checkpoint_path: Optional[str] = None,
                         on_checkpoint=None,
                         resume=None) -> Dict[str, Any]:
    """Check ONE packed history with its search pool sharded over a
    device mesh — single-history scale-out, the frontier-parallel WGL of
    SURVEY §2.5: while keyed batches data-parallelize across keys
    (check_keyed_tpu), here the devices cooperate on a single search.
    The pool, the E×W candidate expansion and the model-step math are
    partitioned over the mesh axis; XLA's SPMD partitioner inserts the
    collectives the global merge sort/dedup needs, and validity is a
    scalar all-reduce. The win regime is ultra-wide histories whose
    per-level expansion dwarfs one chip's lanes.

    The mesh axis must divide ``capacity`` and ``expand``; window=None
    picks the history's needed bucket. Returns the same result dict as
    check_packed_tpu.

    The sharded search runs CHECKPOINTED: an outer host loop of
    bounded device segments of ``segment_iters`` levels (None =
    JTPU_SEGMENT_ITERS or DEFAULT_SEGMENT_ITERS; the sharded flavor of
    _jit_segment), snapshotting the carry to host after every segment —
    every segment boundary is the global merge-sort barrier, so the
    snapshot is a consistent cross-host checkpoint (gathered over DCN
    on multi-host meshes). ``checkpoint_path`` / ``on_checkpoint``
    persist/observe the :class:`jepsen_tpu.resilience.Checkpoint`;
    ``resume`` continues one — including on a mesh of a DIFFERENT axis
    size than the one that saved it (the carry is global state; the
    axis only partitions its rows), which is what the elastic fleet
    layer's re-meshing leans on. The body sequence is the one-device
    search's, so verdicts and level counts match it."""
    seg = _segment_config(segment_iters)
    naxis = mesh.shape[POOL_AXIS]
    cols, early = _prep_single(p, kernel)
    if early is not None:
        early["pool-sharding"] = f"{POOL_AXIS}={naxis}"
        return early
    if expand is None:
        # best-first default at ~capacity/8, rounded up to a multiple of
        # the mesh axis (note this differs from check_packed_tpu, where
        # expand=None means exhaustive level-synchronous BFS — a sharded
        # search exists to go big, so best-first is the sane default)
        per = max(1, capacity // 8)
        expand = max(naxis, -(-per // naxis) * naxis)
    if window is None:
        window = _window_bucket(_window_needed(p))
    _check_window(window)
    # Pre-search plan gate: divisibility, per-shard skew, footprint and
    # int32 bounds verified BEFORE the jit factory (PLAN-SHARD-* /
    # PLAN-OOM findings instead of a ValueError mid-compile). The
    # legacy ValueError below stays as the JTPU_PLAN_GATE=0 fallback.
    from jepsen_tpu.checker import plan as plan_mod
    plan_entry = None
    if plan_mod.gate_enabled():
        with obs.span("checker.plan"):
            plan_entry = plan_mod.gate_sharded(p, kernel, naxis, capacity,
                                               window, expand)
    if capacity % naxis or expand % naxis:
        raise ValueError(
            f"the mesh axis ({naxis}) must divide capacity "
            f"({capacity}) and expand ({expand})")
    return _check_sharded_segmented(
        p, kernel, mesh, naxis, cols, capacity, window, expand, seg,
        checkpoint_path, on_checkpoint, resume, plan_entry)


def _check_sharded_segmented(p, kernel, mesh, naxis: int, cols: dict,
                             capacity: int, window: int,
                             expand: int, seg: int,
                             checkpoint_path: Optional[str],
                             on_checkpoint, resume,
                             plan_entry) -> Dict[str, Any]:
    """The checkpointed pool-sharded search: bounded sharded segments
    with a host carry snapshot at every global merge-sort barrier (see
    check_packed_sharded's docstring). Split out so the mesh context
    wraps exactly the device work."""
    unroll = _unroll_factor()
    fn = _jit_segment(_kernel_key(kernel), capacity, window, expand,
                      unroll, POOL_AXIS)
    lmax = _level_budget(cols["f"].shape[0], cols["cf"].shape[0])
    crw = _crash_width(p.n - p.n_required) or 0
    if resume is not None:
        carry = tuple(np.asarray(x) for x in resume.carry)
        if int(carry[0].shape[0]) != capacity:
            raise ValueError(
                f"checkpoint capacity {int(carry[0].shape[0])} != "
                f"requested {capacity}; re-embed the pool first "
                f"(jepsen_tpu.fleet.repad_pool)")
        seg_idx = int(resume.segment)
    else:
        carry = _carry0_host(capacity, window, cols["cf"].shape[0],
                             cols["ini"], int(cols["nr"]))
        seg_idx = 0
    multiproc = jax.process_count() > 1
    # per-shard high-water mark of live pool rows, sampled at every
    # segment barrier: the final pool alone says little (a best-first
    # search often ends with a few rows in the first shard)
    peak = [0] * naxis
    with _mesh_context(mesh):
        while _carry_active(carry, lmax):
            shape_key = ("sharded-segment", _kernel_key(kernel),
                         capacity, window, expand, unroll, naxis,
                         cols["f"].shape[0], cols["cf"].shape[0])
            lvl0 = int(carry[8])
            outs, _, _ = _timed_call(
                "sharded", shape_key, fn,
                [cols[c] for c in _COLS] + [np.int32(seg), carry],
                rung=(capacity, window, expand), axis=naxis,
                segment=seg_idx)
            if multiproc:
                # The carry's pool columns are row-sharded over DCN;
                # the checkpoint must be the GLOBAL state, so gather
                # them at the barrier (scalars are replicated already).
                from jax.experimental import multihost_utils
                carry = tuple(
                    multihost_utils.process_allgather(x, tiled=True)
                    if getattr(x, "ndim", 0) else np.asarray(x)
                    for x in outs)
            else:
                carry = tuple(np.asarray(x) for x in outs)
            seg_idx += 1
            _LEVELS_TOTAL.inc(int(carry[8]) - lvl0)
            _SEGMENTS_TOTAL.inc()
            _FRONTIER_HWM.set_max(int(np.count_nonzero(carry[4])))
            per = capacity // naxis
            peak = [max(pk_, int(np.count_nonzero(carry[4][i * per:
                                                          (i + 1) * per])))
                    for i, pk_ in enumerate(peak)]
            if checkpoint_path or on_checkpoint is not None:
                from jepsen_tpu.resilience import Checkpoint
                cp = Checkpoint(carry=carry,
                                rung=(capacity, window, expand),
                                window=window, expand_eff=expand,
                                crash_width=crw, segment=seg_idx)
                if checkpoint_path:
                    cp.save(checkpoint_path)
                if on_checkpoint is not None:
                    on_checkpoint(cp)
    done, lossy, wovf, best, levels, pool = _summarize_carry(carry)
    out = _result(done, lossy, wovf, best, levels, p, pool=pool)
    balance = _shard_balance(pool, naxis)
    if balance is not None:
        balance["peak-live-rows"] = peak
        out["shard-balance"] = balance
    out["pool-sharding"] = f"{POOL_AXIS}={naxis}"
    out["rung"] = (capacity, window, expand)
    out["crash-width"] = crw
    out["segments"] = seg_idx
    out["segment-iters"] = seg
    if plan_entry is not None:
        out["plan"] = plan_entry
    return out


def check_history_sharded(history: History, model: Model,
                          mesh: "jax.sharding.Mesh",
                          **kwargs) -> Optional[Dict[str, Any]]:
    """Pack + pool-sharded check (see check_packed_sharded). None when
    the model has no integer kernel. Gated like check_history_tpu: a
    malformed history is rejected before packing or compilation."""
    with obs.span("checker.search", kind="single", ops=len(history),
                  keys=1):
        pk = _lint_and_pack(history, model,
                            "the pool-sharded device search")
        if pk is None:
            return None
        packed, kernel = pk
        return check_packed_sharded(packed, kernel, mesh, **kwargs)


def _lint_and_pack(history: History, model: Model, where: str):
    """The single-history preamble: the mandatory history gate, then
    the packed encoding. ``(packed, kernel)``, or None when the model
    has no integer kernel or an op is not encodable."""
    from jepsen_tpu.analysis.history_lint import require_well_formed
    with obs.span("checker.lint") as sp:
        sp.set(path=require_well_formed(history, where=where))
    with obs.span("checker.pack"):
        try:
            return pack_with_init(history, model)
        except ValueError:  # op f unsupported by the integer kernel
            return None


def warm_ladder(p: PackedHistory, kernel: KernelSpec,
                rungs: Optional[int] = None) -> None:
    """Compile (and once-execute) every escalation rung for this history's
    padded shape, so a later timed check pays no compile cost regardless
    of how far it escalates. Now a thin wrapper over
    :meth:`jepsen_tpu.checker.engine.Engine.warm` — the Engine also
    does the ahead-of-time ``lower().compile()`` (persistent-cache feed)
    and records the bucket as warm."""
    _engine().warm(p, kernel, rungs=rungs)


def check_history_tpu(history: History, model: Model,
                      capacity: Optional[int] = None,
                      window: Optional[int] = WINDOW,
                      expand: Optional[int] = None,
                      segment_iters: Optional[int] = None,
                      deadline_s: Optional[float] = None
                      ) -> Optional[Dict[str, Any]]:
    """Entry point used by LinearizableChecker(backend='tpu').

    Returns None when the model has no single-word integer kernel (the
    caller then uses the generic CPU object search).

    The history passes the mandatory pre-search gate first
    (:func:`jepsen_tpu.analysis.history_lint.gate_history`): a
    structurally malformed history — unmatched completions, process
    reuse, illegal op types, non-monotonic indices — raises
    :class:`~jepsen_tpu.analysis.history_lint.MalformedHistoryError`
    with rule ids and positions BEFORE any packing or jit compilation,
    instead of wedging or poisoning a device search a 10 ms host walk
    could have refused.
    """
    if window is not None:
        _check_window(window)
    # the root span of the check: every lint, pack, plan, segment and
    # device span of it nests under this one (doc/observability.md)
    with obs.span("checker.search", kind="single", ops=len(history),
                  keys=1):
        pk = _lint_and_pack(history, model, "the packed device search")
        if pk is None:
            return None
        packed, kernel = pk
        return check_packed_tpu(packed, kernel, capacity, window, expand,
                                segment_iters=segment_iters,
                                deadline_s=deadline_s)


def check_keyed_tpu(keyed: Dict[Any, Sequence], model: Model,
                    capacity: Optional[int] = None,
                    window: Optional[int] = WINDOW,
                    mesh: Optional["jax.sharding.Mesh"] = None,
                    axis: str = "keys",
                    expand: Optional[int] = None,
                    ladder: Optional[tuple] = None) -> Dict[str, Any]:
    """Check a {key: history} map batched on device — the independent-key
    data-parallel axis (reference independent.clj:65-219 lifts generators,
    independent.clj:246-296 fans the checker out per key; here the fan-out
    is a vmapped, mesh-sharded tensor program).

    With a mesh, each crash-width cohort is split over ``axis`` as
    evenly as it goes, each device's share padded to a multiple of
    ``_MESH_KEY_STEP`` keys, and each device runs its own while-loop
    over its own keys (``jax.shard_map``): no collective runs inside a
    level, and the verdict vectors are gathered once a launch ends.
    capacity=None escalates the whole batch through the narrow capacity
    ladder plus WIDE_LADDER tail rungs, re-running only keys whose
    searches overflowed (and only on rungs that actually grow their
    capacity or window).
    """
    if window is not None:
        _check_window(window)
    kernel = kernel_spec_for(model)
    if kernel is None:
        raise ValueError(f"model {model!r} has no integer kernel")
    ops = sum(len(h) for h in keyed.values())
    with obs.span("checker.search", kind="keyed", ops=ops,
                  keys=len(keyed)):
        return _check_keyed(keyed, model, kernel, ops, capacity, window,
                            mesh, axis, expand, ladder)


def _check_keyed(keyed, model, kernel, ops, capacity, window, mesh, axis,
                 expand, ladder) -> Dict[str, Any]:
    """check_keyed_tpu's body, under its ``checker.search`` span: one
    lint pass, one pack pass and one plan pass over the whole batch,
    then the batch ladder."""
    keys = list(keyed.keys())
    if not keys:
        return {"valid": True, "results": {}, "backend": "tpu"}
    results: Dict[Any, Dict[str, Any]] = {}
    packed: Dict[Any, PackedHistory] = {}
    from jepsen_tpu.analysis import summarize
    from jepsen_tpu.analysis.history_lint import (MalformedHistoryError,
                                                  require_well_formed)
    # Per-key pre-search gate: a malformed key goes UNKNOWN with rule
    # ids (the batch must not abort, matching the per-key encode-failure
    # contract below), and never reaches the packed encoder or a
    # compilation.
    linted = []
    with obs.span("checker.lint", keys=len(keys), ops=ops) as sp:
        paths = set()
        for k in keys:
            try:
                paths.add(require_well_formed(
                    keyed[k], where=f"the keyed device search (key {k!r})"))
            except MalformedHistoryError as e:
                paths.add("lint")
                results[k] = {"valid": UNKNOWN, "backend": "tpu",
                              "error": str(e),
                              "lint": summarize(e.findings)}
            else:
                linted.append(k)
        # without a "lint", every key took the same path
        sp.set(path="lint" if "lint" in paths else paths.pop())
    with obs.span("checker.pack", keys=len(linted)):
        for k in linted:
            try:
                packed[k] = pack_with_init(keyed[k], model, kernel)[0]
            except ValueError as e:
                # One key with an op the integer kernel can't encode must
                # not abort the batch; the caller can fall back per key.
                results[k] = {"valid": UNKNOWN, "backend": "tpu",
                              "error": str(e)}

        # Common padded required width across the batch, so compilations are
        # shared. The CRASHED width is per-key-cohort, not batch-wide: the
        # crash grids and the subset-dominance passes are ~2x of per-level
        # cost, and one crashy key must not levy that on a mostly crash-free
        # batch (measured 64x500 dense with 8/64 crashy keys: 3.3 s
        # batch-wide vs ~1.9 s cohorted on the CPU backend). A key with more
        # crashed ops than the bitmask holds goes UNKNOWN alone (per-key
        # split failure), not the whole batch.
        breq = _bucket(max((p.n_required for p in packed.values()),
                           default=1) or 1)

        # rows: (key, cols, window_needed, max_cap_tried, max_win_tried,
        # forced_frac, crash_width) — the tried maxima keep escalation
        # monotone: a key that overflowed a 16384 pool must not re-run on a
        # later rung whose capacity AND window are both no larger (e.g. the
        # wide tail's 512 rung, which exists for deferred wide keys, not
        # lossy narrow ones).
        rows = []
        for key, p in packed.items():
            if p.n_required == 0:
                results[key] = {"valid": True, "levels": 0, "backend": "tpu"}
                continue
            crw = _crash_width(p.n - p.n_required)
            cols = (None if crw is None
                    else _split_packed(p, breq, crw, kernel))
            if cols is None:
                results[key] = {
                    "valid": UNKNOWN, "backend": "tpu",
                    "error": f"{p.n - p.n_required} crashed ops exceed the "
                             f"crashed-set width {CRASH_MAX}"}
                continue
            # forced fraction: how much of the key's required section is
            # forced runs (fr=1). Staggered workloads (~0.9) ride the
            # fast-forward and want the slim first rung; dense workloads
            # (~0.05) want a fatter expansion — the auto ladder starts them
            # one rung later (see the dense rung below).
            nr_ = p.n_required
            ffrac = float(cols["fr"][:nr_].sum()) / nr_
            rows.append((key, cols, _window_needed(p), 0, 0, ffrac, crw, []))

    with obs.span("checker.plan"):
        adaptive = False
        if ladder is not None:
            # caller-supplied escalation rungs (tests, dryruns: small rungs
            # keep compile cost bounded while still exercising escalation)
            if capacity is not None or expand is not None:
                raise ValueError(
                    "pass either ladder= or capacity=/expand=, not both: "
                    "an explicit ladder replaces the whole escalation "
                    "schedule and would silently ignore them")
            for _, win, _ in ladder:
                _check_window(win)
        elif capacity is not None:
            _check_window(window or WINDOW)
            ladder = ((capacity, window or WINDOW, expand),)
        else:
            # capacity ladder at the narrow window first (most keys), then
            # the expansion-heavy wide rungs the per-row deferral routes
            # wide keys to (see WIDE_LADDER). Between the slim first rung
            # and the escalations sits the DENSE rung (same capacity, double
            # expansion): keys with a low forced fraction skip the slim rung
            # and start there — measured on 64x500 CAS batches (CPU backend):
            # dense 5.7 s -> 3.4 s at (32,8) while staggered stays on (32,4)
            # at 0.20 s instead of doubling to 0.42 s.
            lad0 = _capacity_ladder()
            (cap0, exp0) = lad0[0]
            adaptive = True
            ladder = (((cap0, 32, exp0), (cap0, 32, max(8, exp0 * 2)))
                      + tuple((c, 32, e) for c, e in lad0[1:])
                      + ((512, 64, 512), (4096, 128, 1024),
                         (16384, 128, 4096)))

        # Pre-search plan gate over the batch's escalation schedule: dims
        # aggregate over the keys (widest required section, crashiest key,
        # widest needed window, K-fold footprint); rungs that cannot fit or
        # encode are filtered before any batch executable is built, and the
        # rejections land in the result's "plan" entry.
        from jepsen_tpu.checker import plan as plan_mod
        plan_entry = None
        if rows and plan_mod.gate_enabled():
            dims = plan_mod.PlanDims(
                n_required=max(packed[r[0]].n_required for r in rows),
                n_crashed=max(packed[r[0]].n - packed[r[0]].n_required
                              for r in rows),
                window_needed=max(r[2] for r in rows),
                keys=len(rows))
            ladder, plan_entry = plan_mod.gate_ladder(
                dims, kernel, ladder, kind="batch",
                explicit=capacity is not None, keys=len(rows),
                where="the keyed device search")

        # First rung: hash tie-break (diversified beam — measured 2.4x on
        # dense key batches; a bad draw just escalates). Later rungs use the
        # deterministic lex order, as do single-rung ladders (where a lossy
        # draw would have NO lex escalation to fall back to) unless an
        # explicit JTPU_TIEBREAK0=hash asked for the diversified beam anyway
        # (bench sweeps need the override honored even on pinned rungs).
        tb_env = _os_environ_get("JTPU_TIEBREAK0")
        if tb_env not in (None, "lex", "hash"):
            raise ValueError(
                f"JTPU_TIEBREAK0 must be lex|hash, got {tb_env!r}")

    # Opt-in device profiling across the whole batch escalation (one
    # capture, not one per rung); no-op unless JTPU_PROF=1 + a run dir.
    _prof = obs_profiler.capture()
    _prof.__enter__()
    devices: set = set()
    try:
        results = _keyed_ladder(
            ladder, rows, adaptive, tb_env, mesh, axis, packed,
            kernel, results, devices)
    finally:
        _prof.__exit__(None, None, None)
    valid = True
    for r in results.values():
        if r["valid"] is False:
            valid = False
            break
        if r["valid"] is UNKNOWN:
            valid = UNKNOWN
    out = {"valid": valid, "results": results, "backend": "tpu",
           # ids of the devices the batch outputs lived on: one per
           # mesh device when sharded, the default device otherwise
           "devices": sorted(devices)}
    if plan_entry is not None:
        out["plan"] = plan_entry
    return out


#: On a keys mesh each chip's share of a cohort is padded up to a
#: multiple of this many keys. A cohort's size moves with how many of a
#: test run's keys crashed, and every size is an executable of its own
#: (on a v5e-4 host, 136 keys of 300 ops: ~9 s to its first call cold,
#: ~4.9 s warm from the persistent cache); steps of 4 keys a chip put a
#: run's cohorts on a few sizes for a few padded rows.
_MESH_KEY_STEP = 4


def _mesh_rows(n: int, chips: int):
    """``n`` keys split over ``chips`` as evenly as they go, each chip's
    share padded up to a multiple of ``_MESH_KEY_STEP`` rows. Returns
    key ``j``'s row and the padded batch's row count."""
    per = -(-n // chips)
    per += (-per) % _MESH_KEY_STEP
    base, extra = divmod(n, chips)
    rows = np.concatenate([c * per + np.arange(base + (c < extra))
                           for c in range(chips)])
    return rows, per * chips


def _place_keys(arrays, rows, size, mesh, axis):
    """Lay a cohort's column arrays out at ``rows`` of a ``size``-row
    batch and place it split over the mesh axis. Returns
    ``(arrays, multiproc)``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    # The other rows are trivially complete (n_required=0 finishes at
    # level 0) — repeating a real key would re-run its search, possibly
    # the batch's most expensive, once per padded row.
    def _pad_col(a, c):
        out = np.repeat(a[-1:], size, axis=0)
        if c == "nr":
            out[:] = 0
        out[rows] = a
        return out
    arrays = [_pad_col(a, c) for a, c in zip(arrays, _COLS)]
    sh_row = NamedSharding(mesh, P(axis))
    if jax.process_count() > 1:
        # Multi-host (DCN) mesh: device_put cannot address other hosts'
        # devices. Every process holds the SAME global batch (the keyed
        # dict is control-plane data), so each builds the global array
        # from its addressable slices.
        return [jax.make_array_from_callback(a.shape, sh_row,
                                             lambda idx, a=a: a[idx])
                for a in arrays], True
    return [jax.device_put(a, sh_row) for a in arrays], False


def _keyed_ladder(ladder, rows, adaptive, tb_env, mesh, axis, packed,
                  kernel, results, devices):
    """The keyed batch's escalation loop (split out so the profiler
    capture wraps exactly the device work). Adds the ids of the devices
    each launch's outputs lived on to ``devices``."""
    chips = 1 if mesh is None else int(mesh.shape[axis])
    for step, (cap, win, exp) in enumerate(ladder):
        if not rows:
            break
        last_rung = step == len(ladder) - 1
        if len(ladder) > 1 and not last_rung:
            # Route keys whose needed window provably exceeds this rung's
            # straight to the next rung — running them here would only
            # report window overflow. (Narrow keys still finish on the
            # cheap early rungs; one wide key must not drag the whole
            # batch onto the widest pool.) A retried key additionally
            # skips rungs that grow NEITHER its capacity nor its window —
            # re-running a smaller pool on the same window is guaranteed
            # lossy again.
            runnable, deferred = [], []
            for r in rows:
                if adaptive and step == 0 and r[5] < 0.5:
                    # dense key (low forced fraction): start on the
                    # double-expansion dense rung instead of the slim one
                    deferred.append(r)
                elif r[2] <= win and (cap > r[3] or win > r[4]):
                    runnable.append(r)
                else:
                    deferred.append(r)
        else:
            runnable, deferred = rows, []
        if not runnable:
            rows = deferred
            continue
        # On the adaptive ladder both cohort entry rungs (slim rung 0 and
        # the dense rung 1) are "first" rungs for their keys.
        first = step <= (1 if adaptive else 0)
        hash_ok = first and (not last_rung or tb_env is not None)
        tb = (tb_env or "hash") if hash_ok else "lex"
        retry = deferred
        # Sub-batch per crashed-section width: crash-free keys must not
        # pay the crash grids + dominance passes sized for the batch's
        # crashiest key (a distinct compilation per width regardless).
        # On a mesh each cohort is split over the axis (_mesh_rows).
        by_cr: Dict[int, list] = {}
        for r in runnable:
            by_cr.setdefault(r[6], []).append(r)
        for crw, grp in sorted(by_cr.items()):
            arrays = [np.stack([r[1][c] for r in grp]) for c in _COLS]
            multiproc = False
            at = range(len(grp))    # each key's row of the launch
            if mesh is not None:
                at, size = _mesh_rows(len(grp), chips)
                with obs.span("checker.place", keys=len(grp), chips=chips,
                              pad=size - len(grp)):
                    arrays, multiproc = _place_keys(arrays, at, size, mesh,
                                                    axis)
            # The slim entry rung runs the high-forced-fraction cohort
            # (staggered keys), whose levels are fast-forward loops, not
            # sorts — unrolling 2 search steps per while_loop iteration
            # amortizes the outer-loop overhead those levels are made of
            # (measured on a quiet host, 64x500 staggered keys: 0.25 s ->
            # 0.19 s warm, ~parity with the native thread pool; dense
            # cohorts and later rungs measured flat-to-worse, so they
            # keep 1). JTPU_UNROLL still overrides globally.
            unroll = _unroll_factor(2 if adaptive and step == 0
                                    else _UNROLL)
            fn = _jit_batch(_kernel_key(kernel), cap, win, exp,
                            unroll, tiebreak=tb, mesh=mesh, axis=axis)
            shape_key = ("batch", _kernel_key(kernel), cap, win, exp,
                         unroll, tb, tuple(arrays[0].shape), crw, chips)
            _TRANSFER_BYTES.inc(
                sum(int(getattr(a, "nbytes", 0)) for a in arrays),
                direction="host-to-device")
            outs, _, _ = _timed_call(
                "batch", shape_key, fn, arrays,
                rung=(cap, win, exp), keys=len(grp),
                crash_width=crw, tiebreak=tb)
            devices.update(d.id for d in outs[0].sharding.device_set)
            with obs.span("checker.readback", keys=len(grp)):
                if multiproc:
                    # Per-key verdict rows live on their owning host;
                    # gather the scalar verdict vectors so every process
                    # takes identical host-side decisions (escalation
                    # retries stay SPMD-deterministic).
                    from jax.experimental import multihost_utils
                    scalars = tuple(
                        multihost_utils.process_allgather(x, tiled=True)
                        for x in outs[:5])
                else:
                    scalars = outs[:5]
                done, lossy, wovf, best, levels = (np.asarray(x)
                                                   for x in scalars)
            # a vmapped batch advances every key per program level, so
            # each chip executed its own slowest key's level count, and
            # the launch lasted as long as the slowest chip's loop
            chip_levels = levels.reshape(chips, -1).max(axis=1)
            for i, n in enumerate(chip_levels):
                _CHIP_LEVELS.inc(int(n), chip=str(i))
            _LEVELS_TOTAL.inc(int(chip_levels.max()))
            # Pool columns ([capacity] rows per key) are only read for
            # clean refutations — don't ship up to 16384 ints/key
            # off-device (and over DCN) for the common all-valid rung.
            # "Any refutation?" is derived from the gathered scalars, so
            # multi-host processes agree on whether to gather the pools.
            refuted = ~done & ~lossy & ~wovf
            pk = ps = pa = None
            if refuted.any():
                with obs.span("checker.readback", keys=len(grp),
                              pools=True):
                    pools = outs[5:]
                    if multiproc:
                        from jax.experimental import multihost_utils
                        pools = tuple(
                            multihost_utils.process_allgather(x, tiled=True)
                            for x in pools)
                    pk, ps, pa = (np.asarray(x) for x in pools)
            for r, (key, cols, wneed, mcap, mwin, ffrac, _, work) in \
                    zip(at, grp):
                res = _result(bool(done[r]), bool(lossy[r]),
                              bool(wovf[r]), int(best[r]),
                              int(levels[r]), packed[key],
                              pool=(None if pk is None
                                    else (pk[r], ps[r], pa[r])))
                res["rung"] = (cap, win, exp)
                res["crash-width"] = crw
                res["tiebreak"] = tb
                work = work + [((cap, win, exp), crw, tb,
                                int(levels[r]))]
                res["work"] = work
                escalatable = (bool(lossy[r])
                               or (bool(wovf[r]) and win < MAX_WINDOW))
                if (res["valid"] is UNKNOWN and escalatable
                        and not last_rung):
                    retry.append((key, cols, wneed, max(mcap, cap),
                                  max(mwin, win), ffrac, crw, work))
                else:
                    results[key] = res
        rows = retry
    return results
