"""Explicit search-executable engine: the warm heart of `jtpu serve`.

Before this module the compiled search executables lived in
``functools.lru_cache``'d factories inside :mod:`jepsen_tpu.checker.tpu`
(``_jit_segment`` / ``_jit_batch``) — adequate for a
one-shot CLI process, but invisible and unmanageable for a long-lived
daemon: no way to enumerate what is warm, warm a shape ahead of the
first tenant request, persist compilations across restarts, or evict.

The :class:`Engine` makes the executable cache an explicit object:

* **Same keying, same executables** — :meth:`jit_segment` /
  :meth:`jit_batch` / :meth:`jit_batch_segment` take exactly the
  arguments the lru_cache'd factories took and build exactly the same
  ``jax.jit`` closures; the tpu-module functions delegate here, so every
  existing call site (resilience, fleet, plan's zero-compile probes,
  chaos monkeypatches) is unchanged in behavior.
* **Shape buckets** — :meth:`bucket_key` names the padded-shape bucket a
  packed history lands in (required-width bucket, crashed width, window
  bucket): the unit of warming, of the serve daemon's circuit breaker,
  and of the P-compositionality argument for sharing one warm
  executable across many tenants' histories.
* **Ahead-of-time warming** — :meth:`warm` compiles a bucket's
  escalation ladder before any request needs it: ``lower().compile()``
  per rung (feeding XLA's persistent compilation cache when one is
  configured) plus one trivially-complete execution (``n_required=0``
  finishes at level 0) so the in-process jit cache is hot too and later
  timed calls account as ``jtpu_compile_cache_hit_total``, not cold.
  The bucket universe comes from :mod:`jepsen_tpu.checker.plan`'s
  deterministic enumeration — the daemon warms exactly what the search
  could run.
* **Persistent on-disk compilation cache** —
  :func:`configure_compile_cache` (called when an Engine is made) points
  ``jax_compilation_cache_dir`` at ``JAX_COMPILATION_CACHE_DIR`` or the
  checkout's ``.jax_cache/``, so a restarted process re-warms from disk
  instead of re-paying XLA (`jtpu_persistent_cache_hit_total` proves
  it moved).

Nothing here compiles at import time, and a process that never touches
the daemon sees identical behavior to the lru_cache era (asserted by
tests/test_serve.py's kill-switch identity tests).
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from jepsen_tpu.checker import tpu as T
from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.obs import trace as obs_trace

log = logging.getLogger("jepsen.engine")

_WARMED_SHAPES = obs_metrics.counter(
    "jtpu_engine_warmed_shapes_total",
    "executable shapes warmed ahead of time by an Engine (AOT "
    "lower().compile() + trivial execution)")
_WARM_SECONDS = obs_metrics.counter(
    "jtpu_engine_warm_seconds_total",
    "wall seconds spent in ahead-of-time Engine warming")
_ENGINE_BUILDS = obs_metrics.counter(
    "jtpu_engine_builds_total",
    "jit closures constructed by an Engine (first use of a cache key)")
_ENGINE_HITS = obs_metrics.counter(
    "jtpu_engine_cache_hits_total",
    "Engine executable-cache hits (the explicit table that replaced "
    "the lru_cache'd factories)")
_ENGINE_EVICTIONS = obs_metrics.counter(
    "jtpu_engine_evictions_total",
    "warm shape buckets LRU-evicted past the max-warm-buckets cap "
    "(JTPU_ENGINE_MAX_BUCKETS / --engine-max-buckets)")

#: Default executable-table capacity — matches the lru_cache(maxsize=64)
#: the factories used, so eviction behavior is unchanged for CLI runs.
DEFAULT_MAX_ENTRIES = 64


def _env_max_warm_buckets() -> int:
    """JTPU_ENGINE_MAX_BUCKETS: cap on warmed shape buckets per Engine
    (LRU past it); 0 / absent / malformed mean unbounded — the pre-cap
    behavior, byte-identical."""
    import os
    try:
        return max(0, int(os.environ.get("JTPU_ENGINE_MAX_BUCKETS")
                          or "0"))
    except ValueError:
        return 0


def _env_max_warm_bytes() -> int:
    """JTPU_ENGINE_BYTES_BUDGET: byte budget for the warm-bucket claim
    (each warm record carries its bucket's plan-predicted device
    footprint; past the budget the stalest claims are dropped). 0 /
    absent / malformed mean unbounded."""
    import os
    try:
        return max(0, int(os.environ.get("JTPU_ENGINE_BYTES_BUDGET")
                          or "0"))
    except ValueError:
        return 0


class Engine:
    """An explicit, thread-safe cache of compiled search executables.

    One Engine per process is the normal shape (:func:`default_engine`);
    the serve daemon constructs its own so tests can assert warm/cold
    accounting in isolation. Entries are LRU-evicted past
    ``max_entries`` exactly like the ``functools.lru_cache(maxsize=64)``
    they replace.
    """

    def __init__(self, name: str = "default",
                 max_entries: int = DEFAULT_MAX_ENTRIES,
                 max_warm_buckets: Optional[int] = None):
        self.name = name
        self.max_entries = int(max_entries)
        self._lock = threading.Lock()
        self._fns: "collections.OrderedDict[tuple, Any]" = \
            collections.OrderedDict()
        #: bucket_key -> {"shapes", "seconds", "ts"} for warmed buckets,
        #: LRU-ordered (warm() touches; past max_warm_buckets the
        #: stalest bucket's warm claim is dropped and re-warms on next
        #: use — the serve daemon's warm-state eviction policy).
        self._warm: "collections.OrderedDict[tuple, Dict[str, Any]]" = \
            collections.OrderedDict()
        self.max_warm_buckets = (_env_max_warm_buckets()
                                 if max_warm_buckets is None
                                 else max(0, int(max_warm_buckets)))
        self.max_warm_bytes = _env_max_warm_bytes()
        self.evictions = 0
        self.builds = 0
        self.hits = 0
        #: the persistent compile cache directory (set up here: every
        #: search executable is built through an Engine)
        self.compile_cache = configure_compile_cache()

    def __repr__(self):
        with self._lock:
            entries, warm = len(self._fns), len(self._warm)
            builds, hits = self.builds, self.hits
        return (f"<Engine {self.name!r} entries={entries} "
                f"builds={builds} hits={hits} "
                f"warm-buckets={warm}>")

    # -- executable cache ---------------------------------------------------

    def _get(self, key: tuple, build: Callable[[], Any]):
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self._fns.move_to_end(key)
                self.hits += 1
                _ENGINE_HITS.inc()
                return fn
        built = build()          # outside the lock: jit() is cheap but
        with self._lock:         # must not serialize unrelated lookups
            fn = self._fns.get(key)
            if fn is None:
                self._fns[key] = fn = built
                self.builds += 1
                _ENGINE_BUILDS.inc()
                while len(self._fns) > self.max_entries:
                    self._fns.popitem(last=False)
            else:
                self.hits += 1
                _ENGINE_HITS.inc()
        return fn

    def jit_segment(self, kernel_id: int, capacity: int, window: int,
                    expand: Optional[int] = None, unroll: int = 1,
                    shard_axis: Optional[str] = None,
                    stats: bool = False):
        """One bounded-iteration checkpointed segment (the supervised
        executable of every single-history search, sharded or not;
        traced seg_iters, so changing segment length never recompiles).
        ``stats=True`` carries the per-level counter lane as a 14th
        carry element (extracted host-side at segment barriers)."""
        import jax
        kernel = T._KERNELS_BY_ID[kernel_id]

        def build():
            def seg(f, v1, v2, ro, fr, inv, ret, sm, cf, cv1, cv2, cinv,
                    cps, nr, ini, seg_iters, carry):
                search = T._search_fn(kernel.step, f.shape[0],
                                      cf.shape[0], capacity, window,
                                      expand, unroll, shard_axis,
                                      segment=True, stats=stats)
                return search(f, v1, v2, ro, fr, inv, ret, sm, cf, cv1,
                              cv2, cinv, cps, nr, ini, seg_iters, carry)

            return jax.jit(seg)

        return self._get(("segment", kernel_id, capacity, window,
                          expand, unroll, shard_axis, stats), build)

    def jit_batch(self, kernel_id: int, capacity: int, window: int,
                  expand: Optional[int] = None, unroll: int = 1,
                  tiebreak: str = "lex", mesh=None,
                  axis: Optional[str] = None):
        """The vmapped keyed-batch executable.

        With a ``mesh``, the vmapped search runs under ``jax.shard_map``
        over ``axis``: every input and output is split on its leading
        (key) dimension, and each device runs its own while-loop over
        its own keys, stopping at its own slowest key. No collective
        runs inside a level; the verdict vectors come back as one array
        sharded over the mesh. The search's loop carry starts from
        constants, which the replication check types as equal on every
        device, and leaves the body differing per device; the check
        refuses such a loop, so it is off for this one function."""
        import jax
        kernel = T._KERNELS_BY_ID[kernel_id]

        def build():
            def batched(f, v1, v2, ro, fr, inv, ret, sm, cf, cv1, cv2,
                        cinv, cps, nr, ini):
                search = T._search_fn(kernel.step, f.shape[1],
                                      cf.shape[1], capacity, window,
                                      expand, unroll, tiebreak=tiebreak)
                return jax.vmap(search)(
                    f, v1, v2, ro, fr, inv, ret, sm, cf, cv1, cv2, cinv,
                    cps, nr, ini)

            if mesh is None:
                return jax.jit(batched)
            from jax.sharding import PartitionSpec as P
            return jax.jit(jax.shard_map(
                batched, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
                check_vma=False))

        key = ("batch", kernel_id, capacity, window, expand, unroll,
               tiebreak)
        if mesh is not None:
            key += (tuple(int(d.id) for d in mesh.devices.flat), axis)
        return self._get(key, build)

    def jit_batch_segment(self, kernel_id: int, capacity: int,
                          window: int, expand: Optional[int] = None,
                          unroll: int = 1):
        """One bounded-iteration checkpointed segment vmapped over a
        GANG of same-bucket histories — the serve daemon's concurrent-
        batching executable (doc/serve.md "Concurrent batching"). The
        packed columns and the search carry gain a leading gang axis;
        ``seg_iters`` stays shared. The per-lane body is the same
        ``_search_fn(..., segment=True)`` closure :meth:`jit_segment`
        builds, so a gang lane computes exactly the serial segmented
        search — the P-compositionality equality the batching layer's
        serial-equivalence assertions lean on. A lane whose carry is
        done (or whose pool has no live rows) no-ops inside the vmapped
        while_loop, which is what lets the host cancel one member at a
        segment barrier without aborting its cohort."""
        import jax
        kernel = T._KERNELS_BY_ID[kernel_id]

        def build():
            def gang_seg(f, v1, v2, ro, fr, inv, ret, sm, cf, cv1, cv2,
                         cinv, cps, nr, ini, seg_iters, carry):
                search = T._search_fn(kernel.step, f.shape[1],
                                      cf.shape[1], capacity, window,
                                      expand, unroll, segment=True)
                return jax.vmap(
                    search, in_axes=(0,) * 15 + (None, 0))(
                    f, v1, v2, ro, fr, inv, ret, sm, cf, cv1, cv2,
                    cinv, cps, nr, ini, seg_iters, carry)

            return jax.jit(gang_seg)

        return self._get(("batch-segment", kernel_id, capacity, window,
                          expand, unroll), build)

    # -- shape buckets ------------------------------------------------------

    @staticmethod
    def bucket_key(p, kernel=None) -> tuple:
        """The padded-shape bucket a packed history lands in:
        ``(kernel-name, breq, crash-width, window-bucket)``. Histories
        in one bucket compile to (and share) the same executables —
        the P-compositionality sharing the serve daemon leans on. The
        crashed-set-overflow case (crash width None) gets its own
        sentinel bucket; nothing compiles for it anyway."""
        nr = max(int(p.n_required), 1)
        breq = T._bucket(nr)
        crw = T._crash_width(p.n - p.n_required)
        wb = T._window_bucket(max(T._window_needed(p), 1)) \
            if p.n_required else 32
        kname = getattr(kernel, "name", None) or "kernel"
        return (str(kname), breq, -1 if crw is None else crw, wb)

    def warm_info(self, bucket: tuple) -> Optional[Dict[str, Any]]:
        """Warm record for a bucket ({"shapes", "seconds", "ts"}), or
        None when never warmed through this Engine."""
        with self._lock:
            rec = self._warm.get(bucket)
            return dict(rec) if rec else None

    def warm_buckets(self) -> list:
        """The buckets this Engine has warmed, LRU order (stalest
        first — the next eviction victim leads)."""
        with self._lock:
            return list(self._warm)

    def warm_bytes(self) -> int:
        """Total plan-predicted device bytes of the warm-bucket claim
        (sum of each warm record's ``bytes``)."""
        with self._lock:
            return sum(int(r.get("bytes") or 0)
                       for r in self._warm.values())

    def _warm_bytes_locked(self) -> int:
        return sum(int(r.get("bytes") or 0) for r in self._warm.values())

    def _evict_one_locked(self, why: str) -> tuple:
        b, _ = self._warm.popitem(last=False)
        self.evictions += 1
        _ENGINE_EVICTIONS.inc()
        log.info("engine %s: evicted warm bucket %s (%s)",
                 self.name, b, why)
        return b

    def _trim_warm_locked(self) -> None:
        while 0 < self.max_warm_buckets < len(self._warm):
            self._evict_one_locked(f"cap {self.max_warm_buckets}")
        # the byte-based tier: trim stalest-first while the claim's
        # predicted footprint overruns the byte budget. The NEWEST
        # claim always survives — evicting the bucket in active use
        # would thrash re-warms without freeing anything it needs.
        while self.max_warm_bytes > 0 and len(self._warm) > 1 \
                and self._warm_bytes_locked() > self.max_warm_bytes:
            self._evict_one_locked(f"bytes budget {self.max_warm_bytes}")

    def set_max_warm_buckets(self, n: int) -> None:
        """(Re)cap the warm-bucket table — the serve daemon wires
        ``--engine-max-buckets`` here. 0 = unbounded. Shrinking below
        the current population evicts stalest-first immediately. Only
        the warm CLAIM is dropped (the bucket re-warms on next use);
        the compiled executables live in the separately-bounded
        ``max_entries`` jit table, which per-rung keys share across
        buckets and which was always LRU."""
        with self._lock:
            self.max_warm_buckets = max(0, int(n))
            self._trim_warm_locked()

    def set_max_warm_bytes(self, n: int) -> None:
        """(Re)cap the warm claim by PREDICTED BYTES instead of bucket
        count (JTPU_ENGINE_BYTES_BUDGET): each warm record carries its
        bucket's cheapest-rung plan footprint, and the stalest claims
        are dropped while the sum overruns. 0 = unbounded."""
        with self._lock:
            self.max_warm_bytes = max(0, int(n))
            self._trim_warm_locked()

    def evict_below_headroom(self, min_ratio: float,
                             poll=None) -> int:
        """Evict stalest warm claims while LIVE device headroom
        (``jtpu_device_headroom_ratio``, :func:`jepsen_tpu.obs.devices.
        headroom_ratio`) sits below ``min_ratio`` — eviction driven by
        observed memory pressure, not bucket count. ``poll`` overrides
        the device poll (tests inject a gauge; None on CPU leaves the
        table untouched). Dropping a claim releases the bucket to
        re-warm later; the jit table's own LRU then ages out its
        executables. The newest claim always survives. Returns the
        number of buckets evicted."""
        if poll is None:
            from jepsen_tpu.obs import devices as obs_devices
            poll = obs_devices.headroom_ratio
        evicted = 0
        while True:
            try:
                ratio = poll()
            except Exception:  # noqa: BLE001 — the gauge is advisory
                return evicted
            if ratio is None or ratio >= min_ratio:
                return evicted
            with self._lock:
                if len(self._warm) <= 1:
                    return evicted
                self._evict_one_locked(
                    f"headroom {ratio:.3f} < {min_ratio:.3f}")
            evicted += 1

    # -- ahead-of-time warming ---------------------------------------------

    def warm(self, p, kernel, rungs: Optional[int] = None,
             segment_iters: Optional[int] = None) -> Dict[str, Any]:
        """Warm the escalation ladder for this history's shape bucket.

        For each rung of the bucket universe (the same ladder the
        supervised search would escalate through —
        :func:`jepsen_tpu.checker.tpu._ladder_for` at the
        history's needed window, i.e. exactly the candidates
        :func:`jepsen_tpu.checker.plan.enumerate_candidates` prices):

        1. ``fn.lower(...).compile()`` — the ahead-of-time compile.
           With a persistent compilation cache configured
           (:func:`configure_compile_cache`) this also writes the
           executable to disk, so a restarted process re-warms from
           cache instead of from XLA.
        2. one trivially-complete execution (``n_required=0`` finishes
           at level 0) — populates the in-process jit dispatch cache
           and marks the shape executed, so the first real request in
           the bucket accounts as ``jtpu_compile_cache_hit_total``.

        Returns ``{"bucket", "shapes", "seconds", "already-warm"}``.
        Idempotent per bucket: a warm bucket returns immediately."""
        bucket = self.bucket_key(p, kernel)
        with self._lock:
            rec = self._warm.get(bucket)
            if rec is not None:
                # LRU touch: a bucket in active use must not be the
                # eviction victim while a cold one survives
                self._warm.move_to_end(bucket)
        if rec is not None:
            return dict(rec, bucket=bucket, **{"already-warm": True})
        t0 = time.perf_counter()
        shapes = 0
        cr = T._crash_width(p.n - p.n_required)
        cols = (None if cr is None or p.n_required == 0
                else T._split_packed(p, T._bucket(p.n_required), cr,
                                     kernel))
        # the trace picks up the ambient request context, so a served
        # request's phase breakdown attributes this as compile time
        with obs_trace.span("engine.warm", bucket=list(bucket),
                            phase="compile") as sp:
            shapes = self._warm_ladder(p, kernel, cols, rungs,
                                       segment_iters)
            sp.set(shapes=shapes)
        secs = time.perf_counter() - t0
        _WARM_SECONDS.inc(secs)
        # price the claim for the byte-budget tier: the bucket's plan
        # footprint is what its resident working set costs the device
        fp = None
        try:
            from jepsen_tpu.checker import plan as plan_mod
            fp = plan_mod.request_footprint(
                plan_mod.PlanDims.from_packed(p))
        except Exception:  # noqa: BLE001 — pricing is advisory
            fp = None
        rec = {"shapes": shapes, "seconds": round(secs, 6),
               "ts": time.time(), "bytes": int(fp or 0)}
        with self._lock:
            self._warm.setdefault(bucket, rec)
            self._warm.move_to_end(bucket)
            self._trim_warm_locked()
        log.info("engine %s: warmed bucket %s (%d shape(s), %.2fs)",
                 self.name, bucket, shapes, secs)
        return dict(rec, bucket=bucket, **{"already-warm": False})

    def _warm_ladder(self, p, kernel, cols, rungs,
                     segment_iters) -> int:
        import jax
        shapes = 0
        if cols is not None:
            cols = dict(cols)
            cols["nr"] = np.int32(0)
            full = T._ladder_for(T._window_needed(p))
            ladder = full[:rungs] if rungs else full
            seg = T._segment_config(segment_iters)
            kid = T._kernel_key(kernel)
            unroll = T._unroll_factor()
            # warm the executable real calls will select: with tracing
            # on they carry the per-level stats lane (part of the cache
            # key), with it off the original stats-less shape
            stats = obs_trace.enabled()
            lmax = T._level_budget(cols["f"].shape[0],
                                   cols["cf"].shape[0])
            for cap, win, exp in ladder:
                fn = self.jit_segment(kid, cap, win, exp, unroll,
                                      stats=stats)
                carry = T._carry0_host(
                    cap, win, cols["cf"].shape[0], cols["ini"], 0,
                    stats_rows=(lmax + 1) if stats else 0)
                args = ([cols[c] for c in T._COLS]
                        + [np.int32(seg), carry])
                shape_key = ("segment", kid, cap, win, exp, unroll,
                             cols["f"].shape[0], cols["cf"].shape[0],
                             stats)
                try:
                    # AOT compile: feeds the persistent cache; cheap to
                    # follow with the trivial execution, which fills the
                    # in-process dispatch cache for real calls.
                    fn.lower(*args).compile()
                except Exception:  # noqa: BLE001 — AOT is best-effort;
                    pass           # the execution below still warms
                jax.block_until_ready(fn(*args))
                # the compile phase was just paid here: later timed
                # calls at this shape are steady-state cache hits
                T._EXECUTED_SHAPES.add(shape_key)
                shapes += 1
                _WARMED_SHAPES.inc()
        return shapes


# ---------------------------------------------------------------------------
# Persistent on-disk compilation cache
# ---------------------------------------------------------------------------


#: The compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed directory inside the checkout (the path is part of what makes
#: a later process find the entries, so it never depends on a temporary
#: name, a pid or the time).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on XLA's persistent compilation cache for this process and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` when set (and no
    other), else :data:`DEFAULT_CACHE_DIR`. Thresholds are dropped to
    zero so small kernels persist too. :class:`Engine` calls this when
    it is made, so every path that compiles a search (run/analyze,
    the daemon, fleet workers, chip_smoke.py) gets the cache and
    paths that never compile do not import JAX for it."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# The process-default engine (what the tpu-module factories delegate to)
# ---------------------------------------------------------------------------

_DEFAULT: Optional[Engine] = None
_DEFAULT_LOCK = threading.Lock()


def default_engine() -> Engine:
    """The process-global Engine behind ``_jit_segment`` / ``_jit_batch``
    / ``_jit_batch_segment``. Created lazily — importing this module
    compiles nothing."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = Engine("default")
        return _DEFAULT
