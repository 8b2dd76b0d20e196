"""Resilient execution for long-running device work.

The device linearizability search is a long-lived accelerator workload,
and accelerators fail in ways the host code must survive: a wedged XLA
execution that never returns, a ``RESOURCE_EXHAUSTED`` on a pool sized
for a bigger chip, a preempted TPU VM that kills the process mid-search.
This module guards *execution* — the whole run; backend initialization
is left to JAX, and an init failure raises.

Four pieces (doc/resilience.md has the operator view):

* **Checkpointed segments** — the single-history pool search runs as an
  outer host loop of bounded-iteration device segments
  (:func:`jepsen_tpu.checker.tpu._jit_segment`); the search carry is
  snapshotted to host numpy after every segment. The snapshot IS the
  checkpoint: a crashed, preempted or wedged search resumes from it
  instead of restarting. P-compositionality (Horn & Kroening,
  1504.00204) is what makes this sound: the search state is a closed
  configuration set, so cutting the iteration stream anywhere and
  resuming it elsewhere changes nothing about the verdict.
* **Wedge watchdog** — each segment optionally runs under a deadline
  (``deadline_s`` / JTPU_SEGMENT_DEADLINE_S). A segment that overruns is
  abandoned (the reference's util.clj:275-286 ``timeout`` semantics: the
  thread is orphaned, not killed) and the saved checkpoint is re-routed
  to the CPU fallback device with a visible warning (recorded once per
  process: :func:`runtime_wedged`).
* **Structured retry policy** — failures are classified (:data:`OOM` /
  :data:`WEDGE` / :data:`TRANSIENT` / :data:`FATAL`) and answered per
  class: OOM halves the pool (re-embedding the checkpoint, marking the
  search lossy if live rows fell off) under capped exponential backoff;
  transients retry with jitter; wedges escalate to the fallback backend;
  fatals rethrow. Every decision lands in the result's ``attempts``
  trail, so store.py/web.py show *how* a verdict was reached.
* **Bounded client ops** — :func:`jepsen_tpu.core.with_op_timeout` uses
  the same taxonomy on the orchestrator side: a hung ``client.invoke``
  becomes an ``info`` op and the process reincarnates.

The fault-injection seam (:data:`_inject_fault`) lets tests and
``tools/chaos_matrix.py`` drive every branch without a sick device.
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import numpy as np

from jepsen_tpu import obs
from jepsen_tpu.checker import UNKNOWN
from jepsen_tpu.checker import tpu as T
from jepsen_tpu.models.core import KernelSpec, Model
from jepsen_tpu.obs import devices as obs_devices
from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.obs import observatory as obs_observatory
from jepsen_tpu.obs import searchstats as obs_searchstats
from jepsen_tpu.ops.encode import PackedHistory, pack_with_init

log = logging.getLogger("jepsen.resilience")


# ---------------------------------------------------------------------------
# Run-time wedge record (process-wide)
# ---------------------------------------------------------------------------
#
# Backend initialization is not guarded: the process initializes its own
# backend on its first device call, and an init failure raises there.
# What is tracked here is the opt-in EXECUTION watchdog (off unless
# JTPU_SEGMENT_DEADLINE_S is set): once a device segment wedges mid-run,
# the process records it, warns once, and later supervised searches (and
# stream sessions) start on the CPU device directly.

_wedge_state: dict = {}
_wedge_lock = threading.Lock()


def cpu_device():
    """The host fallback device for mid-run degradation, or None when no
    CPU backend is addressable (e.g. JAX_PLATFORMS pinned to an
    accelerator only)."""
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:  # no cpu platform registered
        return None


def runtime_wedged() -> bool:
    """True once a mid-run device wedge was recorded this process —
    supervised searches then start on the CPU fallback directly instead
    of re-feeding work to a device that already ate one search."""
    with _wedge_lock:
        return bool(_wedge_state.get("runtime_wedged"))


def note_runtime_wedge(caller: str, deadline_s: float, **detail) -> bool:
    """Record (once, with a visible warning) that a device EXECUTION
    wedged mid-run. Returns True the first time."""
    with _wedge_lock:
        first = not _wedge_state.get("runtime_wedged")
        _wedge_state["runtime_wedged"] = True
    if first:
        extra = "".join(f" {k}={v}" for k, v in sorted(detail.items()))
        warnings.warn(
            f"device execution wedged past {deadline_s:.1f}s mid-run;"
            f" {caller} resuming from its checkpoint on the CPU "
            f"fallback{extra} (subsequent supervised searches start on "
            f"the fallback; JTPU_SEGMENT_DEADLINE_S tunes the watchdog)",
            RuntimeWarning, stacklevel=3)
    return first


def _reset_wedge_for_tests() -> None:
    with _wedge_lock:
        _wedge_state.clear()

_OOM_TOTAL = obs_metrics.counter(
    "jtpu_search_oom_total",
    "device OOMs answered by pool-halving during supervised searches")
_WEDGE_TOTAL = obs_metrics.counter(
    "jtpu_search_wedge_total",
    "device segments abandoned by the wedge watchdog")
_TRANSIENT_TOTAL = obs_metrics.counter(
    "jtpu_search_transient_retries_total",
    "transient device failures retried from their checkpoint")
_BACKOFF_SECONDS = obs_metrics.counter(
    "jtpu_search_backoff_seconds_total",
    "seconds slept in supervised-search retry backoff")
_PREEMPT_TOTAL = obs_metrics.counter(
    "jtpu_search_preemptive_halve_total",
    "pool halvings triggered by low device-memory headroom BEFORE any "
    "OOM fired (see JTPU_HEADROOM_MIN)")
_DCN_TOTAL = obs_metrics.counter(
    "jtpu_search_dcn_retries_total",
    "cross-host collective / interconnect faults retried from their "
    "checkpoint (the DCN failure class — distinct from OOM/wedge so a "
    "slow interconnect degrades instead of wedging)")

# ---------------------------------------------------------------------------
# Failure taxonomy
# ---------------------------------------------------------------------------

#: Pool/device memory exhaustion: shrink the pool and retry from the
#: checkpoint (soundness note: a truncated pool can still prove validity;
#: it only forfeits exhaustive refutation, which the lossy flag records).
OOM = "oom"
#: A device call that never returned within its deadline: escalate the
#: checkpoint to the fallback backend.
WEDGE = "wedge"
#: Plausibly-recoverable runtime errors (preemption, RPC resets,
#: UNAVAILABLE): retry the same segment with jittered backoff.
TRANSIENT = "transient"
#: A cross-host collective that timed out or aborted mid-flight (DCN
#: gather/all-reduce, distributed-runtime barrier, NCCL ring): retried
#: like a transient (bounded, jittered) but CLASSIFIED apart from
#: OOM/wedge so a slow interconnect degrades visibly instead of being
#: mistaken for a sick chip — the elastic fleet layer
#: (jepsen_tpu.fleet) keys its per-host retry budget on this class.
DCN = "dcn"
#: Everything else — a programming error or corrupted state: rethrow.
FATAL = "fatal"


class WedgeError(Exception):
    """A supervised call exceeded its deadline (the watchdog fired)."""


#: Substrings marking an out-of-memory failure in XLA/driver error text.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED", "Out of memory",
                "out of memory", "OOM", "failed to allocate")

#: Substrings marking transient runtime faults worth a same-shape retry.
_TRANSIENT_MARKERS = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED",
                      "CANCELLED", "preempt", "Connection reset",
                      "Socket closed", "temporarily unavailable")

#: Substrings marking a cross-host collective / interconnect fault
#: (checked BEFORE the transient markers: "all-reduce DEADLINE_EXCEEDED"
#: is a DCN event, not a generic transient). The jax distributed
#: runtime and the XLA collective layer surface these as text.
_DCN_MARKERS = ("collective", "all-reduce", "all_reduce", "all-gather",
                "all_gather", "AllReduce", "AllGather", "NCCL",
                "DCN", "cross-host", "cross_host", "barrier timed out",
                "coordination service", "distributed runtime",
                "heartbeat", "HostLostError")

#: Failure classes the fleet layer retries (or re-meshes around)
#: internally. The serve boundary treats these as NEUTRAL for breaker
#: accounting: a flaky interconnect the fleet already absorbed must not
#: trip a shape bucket open and 503 healthy tenants — and equally must
#: not be mistaken for a poison request by gang bisection.
RETRYABLE = (DCN, TRANSIENT)


def classify_failure(exc: BaseException) -> str:
    """Map an exception to its failure class
    (OOM/WEDGE/DCN/TRANSIENT/FATAL).

    Works on error *text* as well as types: the jax runtime surfaces
    device faults as XlaRuntimeError with a status-code prefix, and this
    module must not import jax internals to pattern-match them."""
    if isinstance(exc, WedgeError):
        return WEDGE
    if isinstance(exc, MemoryError):
        return OOM
    text = f"{type(exc).__name__}: {exc}"
    if any(m in text for m in _OOM_MARKERS):
        return OOM
    if any(m in text for m in _DCN_MARKERS):
        return DCN
    if isinstance(exc, (ConnectionError, TimeoutError, InterruptedError)):
        return TRANSIENT
    if any(m in text for m in _TRANSIENT_MARKERS):
        return TRANSIENT
    return FATAL


def result_failure_class(result: Optional[Dict[str, Any]]
                         ) -> Optional[str]:
    """The dominant failure class of a FINISHED check result — the seam
    the serve daemon's per-bucket circuit breaker classifies through
    (doc/serve.md): raised checks carry ``error-class`` (check_safe),
    supervised searches that aborted record a ``gave-up`` trail event
    with its class, and a clean (or merely escalated) result is None.
    Retried-and-survived OOMs deliberately do NOT count: the taxonomy's
    whole point is that those degrade instead of failing."""
    if not isinstance(result, dict):
        return None
    cls = result.get("error-class")
    if cls in (OOM, WEDGE, DCN, TRANSIENT, FATAL):
        return cls
    for ev in reversed(result.get("attempts") or []):
        if isinstance(ev, dict) and ev.get("outcome") == "gave-up":
            c = ev.get("event")
            if c in (OOM, WEDGE, DCN, TRANSIENT, FATAL):
                return c
    return None


def bisect_poison(members: list, run_gang: Callable[[list], list]
                  ) -> tuple:
    """Fault-isolated gang execution: run ``run_gang`` over the whole
    gang; when the batched call FAILS (raises, or returns a single
    failure dict instead of a per-member list), split the gang in half
    and re-run each half, converging on the poison member(s) — the
    blast-radius containment the serve daemon's concurrent batching
    ships with (doc/serve.md "Concurrent batching").

    The failure taxonomy drives the recursion:
    :func:`result_failure_class` names the gang-level failure's class
    (an injected/real OOM, a wedge, ...); a CLASSIFIED failure on a
    gang of two or more is worth halving — some member provoked it and
    the rest are owed their verdicts — while a failure that has
    converged to one member (or carries no recognised class) is
    attributed to exactly that span: those members get the failure dict
    as their result and land in the poison list, so the caller can fail
    ONLY them and count ONLY them toward breaker accounting.

    ``run_gang(sub)`` takes a sub-list of ``members`` and returns a
    result list aligned with it; an exception it raises is converted to
    a failure dict via :func:`classify_failure`. P-compositionality is
    again what makes re-execution sound: members are independent
    sub-problems, so a half-gang re-run answers exactly what the full
    gang would have.

    Returns ``(results, poison_indices, bisections)`` with ``results``
    aligned to ``members``.
    """
    results: list = [None] * len(members)
    poison: list = []
    bisections = 0

    def fail_dict(exc: BaseException) -> Dict[str, Any]:
        return {"valid": UNKNOWN,
                "error": f"{type(exc).__name__}: {exc}",
                "error-class": classify_failure(exc)}

    def go(span: list) -> None:
        nonlocal bisections
        try:
            out = run_gang([members[i] for i in span])
        except Exception as e:  # noqa: BLE001 — the device call failed
            out = fail_dict(e)
        if isinstance(out, list):
            for i, r in zip(span, out):
                results[i] = r
            return
        cls = result_failure_class(out)
        if len(span) > 1 and cls is not None:
            bisections += 1
            mid = (len(span) + 1) // 2
            go(span[:mid])
            go(span[mid:])
            return
        for i in span:
            results[i] = dict(out)
            poison.append(i)

    go(list(range(len(members))))
    return results, poison, bisections


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if not v:
        return default
    try:
        return float(v)
    except ValueError:
        return default


@dataclass
class RetryPolicy:
    """Per-class retry behavior. Backoff is capped exponential:
    ``min(cap, base * 2**(attempt-1))``, jittered to [50%, 100%] so
    synchronized workers don't stampede a recovering endpoint.
    Base/cap default from JEPSEN_RETRY_BASE / JEPSEN_RETRY_CAP."""

    max_retries: int = 3
    backoff_base_s: float = field(
        default_factory=lambda: _env_float("JEPSEN_RETRY_BASE", 0.05))
    backoff_cap_s: float = field(
        default_factory=lambda: _env_float("JEPSEN_RETRY_CAP", 10.0))
    jitter: bool = True
    #: OOM shrink floor: a pool this small that still OOMs is hopeless.
    min_capacity: int = 8
    rng: random.Random = field(default_factory=random.Random, repr=False)

    def delay(self, attempt: int) -> float:
        d = min(self.backoff_cap_s,
                self.backoff_base_s * (2 ** max(attempt - 1, 0)))
        if self.jitter:
            d *= 0.5 + self.rng.random() / 2
        return d


def retry_until_deadline(fn: Callable[[], Any], deadline_s: float,
                         policy: Optional[RetryPolicy] = None
                         ) -> tuple:
    """Run ``fn`` until it returns truthy or ``deadline_s`` elapses,
    sleeping ``policy.delay(attempt)`` (jittered capped-exponential)
    between attempts; exceptions count as failed attempts. The shared
    deadline+backoff primitive behind the nemesis layer's post-heal
    convergence probes (:func:`jepsen_tpu.nemesis.client_ping_probe`).

    Returns ``(ok, attempts, last_error)`` — ``last_error`` is a short
    string for the trail, or None on success."""
    policy = policy or RetryPolicy()
    t_end = time.monotonic() + deadline_s
    attempts = 0
    last_err: Optional[str] = None
    while True:
        attempts += 1
        try:
            if fn():
                return True, attempts, None
            last_err = "probe returned falsy"
        except Exception as e:  # noqa: BLE001 — a probe failure is data
            last_err = _errstr(e)
        remaining = t_end - time.monotonic()
        if remaining <= 0:
            return False, attempts, last_err
        time.sleep(min(policy.delay(attempts), remaining))


def deadline_stop(deadline_s: float,
                  inner: Optional[Callable[[], bool]] = None
                  ) -> Callable[[], bool]:
    """A ``should_stop`` predicate that fires ``deadline_s`` seconds from
    now (and whenever ``inner`` fires) — bounds the host-side search
    algorithms (wgl/jitlin) the same way the watchdog bounds device
    segments."""
    t_end = time.monotonic() + deadline_s

    def stop() -> bool:
        if inner is not None and inner():
            return True
        return time.monotonic() > t_end

    return stop


# ---------------------------------------------------------------------------
# Checkpoints
# ---------------------------------------------------------------------------

#: Field names of the search carry, in _search_fn's carry order — the
#: checkpoint format (doc/resilience.md documents each slot).
CARRY_FIELDS = ("k", "mask", "cmask", "state", "alive", "done", "lossy",
                "wovf", "level", "best", "pool_k", "pool_state",
                "pool_alive")

#: Optional 14th carry slot: the per-level search-analytics counter log
#: ([LMAX+1, T.NSTAT] int32, level-indexed — doc/observability.md,
#: "Search analytics"). Present only on stats-enabled executables;
#: checkpoints save/load it when present, so pre-analytics checkpoints
#: keep loading and JTPU_TRACE=0 checkpoints stay byte-identical.
CARRY_STATS_FIELD = "slog"


@dataclass
class Checkpoint:
    """A host snapshot of the device search, sufficient to resume it on
    any backend. ``rung`` is the REQUESTED ladder rung; ``expand_eff``
    and the carry's own row count give the effective (possibly
    OOM-shrunk) shape. Serializes to one ``.npz`` file."""

    carry: tuple
    rung: tuple                      # (capacity, window, expand) requested
    window: int
    expand_eff: Optional[int]
    crash_width: int
    segment: int                     # segments completed so far
    #: Streaming partial-verdict metadata (doc/serve.md "Streaming
    #: API"): the event-index watermark of the stable prefix this carry
    #: has searched, and that prefix's required-op count. -1 on offline
    #: checkpoints — pre-streaming .npz files keep loading unchanged.
    watermark: int = -1
    n_required: int = -1

    @property
    def capacity_eff(self) -> int:
        return int(self.carry[0].shape[0])

    @property
    def level(self) -> int:
        return int(self.carry[8])

    def save(self, path: str) -> None:
        meta = dict(
            rung=np.asarray([-1 if x is None else x for x in self.rung],
                            np.int64),
            window=np.int64(self.window),
            expand_eff=np.int64(-1 if self.expand_eff is None
                                else self.expand_eff),
            crash_width=np.int64(self.crash_width),
            segment=np.int64(self.segment),
            watermark=np.int64(self.watermark),
            n_required=np.int64(self.n_required))
        names = CARRY_FIELDS + (CARRY_STATS_FIELD,)
        arrays = {f"carry_{n}": np.asarray(v)
                  for n, v in zip(names, self.carry)}
        # tmp+replace: a crash mid-save must leave the PREVIOUS
        # checkpoint readable — the streaming daemon saves one per
        # segment and a torn .npz would demote a crash-resume to a
        # level-0 restart (doc/resilience.md)
        tmp = f"{path}.tmp.{os.getpid()}.npz"
        np.savez(tmp, **meta, **arrays)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "Checkpoint":
        with np.load(path) as z:
            rung = tuple(None if int(x) < 0 else int(x)
                         for x in z["rung"])
            exp = int(z["expand_eff"])
            carry = tuple(z[f"carry_{n}"] for n in CARRY_FIELDS)
            if f"carry_{CARRY_STATS_FIELD}" in z.files:
                carry = carry + (z[f"carry_{CARRY_STATS_FIELD}"],)
            # scalars round-trip as 0-d arrays; normalize the flag/count
            # slots back to numpy scalars so jit sees identical avals
            carry = (carry[:5]
                     + (np.bool_(carry[5]), np.bool_(carry[6]),
                        np.bool_(carry[7]), np.int32(carry[8]),
                        np.int32(carry[9]))
                     + carry[10:])
            return cls(carry=carry, rung=rung, window=int(z["window"]),
                       expand_eff=None if exp < 0 else exp,
                       crash_width=int(z["crash_width"]),
                       segment=int(z["segment"]),
                       watermark=(int(z["watermark"])
                                  if "watermark" in z.files else -1),
                       n_required=(int(z["n_required"])
                                   if "n_required" in z.files else -1))


def _shrink_carry(carry: tuple, new_cap: int) -> tuple:
    """Re-embed a checkpoint into a half-size pool: keep the first
    ``new_cap`` rows (the pool is sorted deepest-first, so the prefix is
    the best frontier). Returns (carry, dropped): if any LIVE row fell
    off, the search is lossy from here on — a completion is still a
    witness, but pool death no longer refutes."""
    (k, mask, cmask, state, alive, done, lossy, wovf, level, best,
     pk, ps, pa) = carry[:13]
    dropped = bool(np.any(np.asarray(alive)[new_cap:]))
    lossy = np.bool_(bool(lossy) or dropped)
    # the stats lane (carry[13], when present) is level-indexed, not
    # pool-row-indexed — it rides through a pool shrink unchanged
    return ((np.asarray(k)[:new_cap], np.asarray(mask)[:new_cap],
             np.asarray(cmask)[:new_cap], np.asarray(state)[:new_cap],
             np.asarray(alive)[:new_cap], done, lossy, wovf, level, best,
             np.asarray(pk)[:new_cap], np.asarray(ps)[:new_cap],
             np.asarray(pa)[:new_cap]) + tuple(carry[13:]), dropped)


def _fit_carry_stats(carry: tuple, stats: bool, lmax: int) -> tuple:
    """Match a carry's optional stats lane to the executable about to
    run it: a resumed checkpoint may predate the analytics lane (or have
    been saved with tracing toggled the other way). Appending a zero
    lane under-counts the pre-resume levels — acceptable for telemetry,
    and the verdict lanes are untouched either way."""
    if stats and len(carry) == 13:
        return carry + (np.zeros((lmax + 1, T.NSTAT), np.int32),)
    if not stats and len(carry) > 13:
        return carry[:13]
    return carry


def _grow_carry_stats(carry: tuple, lmax: int) -> tuple:
    """Re-pad an existing stats lane to a LARGER level budget: streaming
    extension grows the packed prefix between segments, and the level
    budget (and so the lane's row count) grows with it. Rows already
    counted ride through unchanged — the per-level counter record is
    exactly what the crash-resume chaos assertion reads."""
    if len(carry) <= 13:
        return carry
    slog = np.asarray(carry[13])
    rows = lmax + 1
    if slog.shape[0] >= rows:
        return carry
    grown = np.zeros((rows, slog.shape[1]), np.int32)
    grown[:slog.shape[0]] = slog
    return carry[:13] + (grown,)


# ---------------------------------------------------------------------------
# Segment execution + watchdog
# ---------------------------------------------------------------------------

#: Fault-injection seam for tests and tools/chaos_matrix.py: a callable
#: invoked with a context dict ({rung, effective, segment, level,
#: backend}) right before each device segment; raising from it simulates
#: that failure at that point. None in production.
_inject_fault: Optional[Callable[[Dict[str, Any]], None]] = None


def _call_segment(fn, cols: dict, carry: tuple, seg_iters: int,
                  device=None, deadline_s: Optional[float] = None) -> tuple:
    """Run one device segment and snapshot its carry to host numpy (the
    checkpoint). With a deadline, the call runs in a worker thread under
    the watchdog: the supervisor joins with the deadline and raises
    :class:`WedgeError` if the device never came back — the worker (and
    whatever the device wedged) is abandoned as a daemon."""

    def invoke() -> tuple:
        args = [cols[c] for c in T._COLS]
        if device is not None:
            import jax
            with jax.default_device(device):
                out = fn(*args, np.int32(seg_iters), carry)
                return tuple(np.asarray(x) for x in out)
        out = fn(*args, np.int32(seg_iters), carry)
        return tuple(np.asarray(x) for x in out)

    if deadline_s is None:
        return invoke()
    box: Dict[str, Any] = {}

    def target():
        try:
            box["ok"] = invoke()
        except BaseException as e:  # noqa: BLE001 — relayed to supervisor
            box["err"] = e

    t = threading.Thread(target=target, daemon=True,
                         name="jepsen-device-segment")
    t.start()
    t.join(deadline_s)
    if t.is_alive():
        raise WedgeError(
            f"device segment exceeded its {deadline_s:.1f}s deadline")
    if "err" in box:
        raise box["err"]
    return box["ok"]


def _errstr(e: BaseException) -> str:
    return f"{type(e).__name__}: {e}"


# ---------------------------------------------------------------------------
# The supervised checker
# ---------------------------------------------------------------------------


def supervised_check_packed(p: PackedHistory, kernel: KernelSpec,
                            **kwargs) -> Dict[str, Any]:
    """Checkpointed, supervised single-history device search.

    Semantics match :func:`jepsen_tpu.checker.tpu.check_packed_tpu`
    (identical verdicts and level counts — the device body is the same;
    only the while_loop is cut into host-checkpointed segments), plus:

    * ``deadline_s`` — per-segment wedge watchdog; a wedged segment's
      checkpoint continues on the CPU fallback device.
    * OOM halves the pool and resumes the checkpoint in the smaller
      shape; transients retry with jittered backoff; fatals rethrow
      (with the trail attached as ``exc.resilience_trail``). Below the
      JTPU_HEADROOM_MIN device-memory headroom ratio the pool halves
      PRE-emptively, once per rung, before any allocator failure
      (:mod:`jepsen_tpu.obs.devices`; no-op on stat-less backends).
    * ``resume`` — continue a saved :class:`Checkpoint` (same packed
      history) instead of starting over; ``checkpoint_path`` /
      ``on_checkpoint`` persist/observe checkpoints after each segment.
    * The result carries ``attempts`` (the supervision trail),
      ``segments`` and ``segment-iters`` alongside the usual telemetry
      keys.
    * Live progress (level / frontier width / configs-per-s / ETA) is
      published to :mod:`jepsen_tpu.obs.observatory` after every
      segment — the ``watch`` CLI and ``/live`` endpoint surface.
    """
    try:
        # Opt-in device profiling over the supervised search — the
        # scoped jax.profiler capture whose device trace merges under
        # these checker.segment spans (obs/profiler.py; no-op unless
        # JTPU_PROF=1 and a run directory is armed).
        with obs.profiler.capture():
            out = _supervised_check_packed(p, kernel, **kwargs)
    except BaseException:
        # a raised search must not leave the observatory "searching"
        obs_observatory.finish(valid="error")
        raise
    obs_observatory.finish(valid=out.get("valid"),
                           levels=out.get("levels"))
    return out


def _supervised_check_packed(p: PackedHistory, kernel: KernelSpec,
                             capacity: Optional[int] = None,
                             window: Optional[int] = None,
                             expand: Optional[int] = None,
                             segment_iters: Optional[int] = None,
                             deadline_s: Optional[float] = None,
                             policy: Optional[RetryPolicy] = None,
                             resume: Optional[Checkpoint] = None,
                             checkpoint_path: Optional[str] = None,
                             on_checkpoint: Optional[
                                 Callable[[Checkpoint], None]] = None
                             ) -> Dict[str, Any]:
    if window is not None:
        T._check_window(window)
    seg = segment_iters or T._segment_config(None) or T.DEFAULT_SEGMENT_ITERS
    cols, early = T._prep_single(p, kernel)
    if early is not None:
        return early
    if deadline_s is None:
        deadline_s = _env_float("JTPU_SEGMENT_DEADLINE_S", 0.0) or None
    policy = policy or RetryPolicy()
    # Mandatory pre-search plan gate (doc/plan.md): an explicit rung
    # that cannot fit/shard/encode is rejected BEFORE any compilation;
    # auto-ladder rungs whose only problem is footprint stay in — the
    # seeding below starts their pool at the largest size the predicted
    # footprint says fits, instead of always starting at the rung max
    # and OOM-halving reactively. Kill switch: JTPU_PLAN_GATE=0.
    from jepsen_tpu.checker import plan as plan_mod
    plan_entry = None
    with obs.span("checker.plan"):
        if capacity is not None:
            T._check_window(window or T.WINDOW)
            ladder = ((capacity, window or T.WINDOW, expand),)
        else:
            ladder = T._ladder_for(T._window_needed(p))
        if plan_mod.gate_enabled():
            ladder, plan_entry = plan_mod.gate_ladder(
                p, kernel, ladder, kind="segment",
                explicit=capacity is not None, derate=capacity is None,
                where="the supervised device search")
    crw = T._crash_width(p.n - p.n_required) or 0
    cr_pad = cols["cf"].shape[0]
    lmax = T._level_budget(cols["f"].shape[0], cr_pad)
    # Search analytics (doc/observability.md): with tracing on the
    # segment executables carry the per-level counter lane, extracted
    # here at each segment barrier; JTPU_TRACE=0 selects the stats-off
    # executable and the original 13-slot carry — byte-identical
    # checkpoints and artifacts.
    stats = obs.enabled()
    # A prior mid-run wedge in this process routes new work straight to
    # the CPU fallback.
    fallback = cpu_device() if runtime_wedged() else None
    trail: list = []
    work: list = []
    out: Dict[str, Any] = {}
    # Search telemetry accumulated across rungs and surfaced in the
    # result (doc/observability.md): compile/execute wall split,
    # per-segment level advances, frontier-width high-water mark, and
    # transfer-byte accounting — what lets bench.py and the `# search:`
    # summary attribute wall-clock to compile/device/host phases.
    device_s = {"compile": 0.0, "execute": 0.0}
    seg_levels: list = []
    frontier_hwm = 0
    transfer_bytes = 0
    cols_b = T._cols_nbytes(cols)
    # Pre-emptive OOM avoidance (obs/devices.py): below this headroom
    # ratio the pool halves BEFORE the allocator fails. Inert when the
    # backend exposes no memory stats (CPU) or the knob is <= 0.
    hr_min = obs_devices.headroom_threshold()
    if resume is not None:
        idx = next((i for i, r in enumerate(ladder)
                    if tuple(r) == tuple(resume.rung)), None)
        if idx is None:
            ladder = (tuple(resume.rung),) + tuple(ladder)
        else:
            ladder = ladder[idx:]
    for cap, win, exp in ladder:
        if resume is not None and tuple(resume.rung) == (cap, win, exp):
            carry = tuple(np.asarray(x) if isinstance(x, np.ndarray) else x
                          for x in resume.carry)
            cap_eff = resume.capacity_eff
            exp_eff = resume.expand_eff
            seg_idx = resume.segment
            resume = None
        else:
            cap_eff, exp_eff, seg_idx = cap, exp, 0
            if plan_entry is not None:
                # Footprint-seeded pool: start at the largest halving of
                # the rung whose predicted working set fits the byte
                # budget (JTPU_PLAN_BYTES_LIMIT / device bytes-limit) —
                # the ahead-of-time twin of the reactive OOM halving.
                # No-op when no limit is known (CPU) or the rung fits.
                with obs.span("checker.plan"):
                    cap_s, exp_s, pred, blim = plan_mod.seed_rung(
                        cap, win, exp, breq=cols["f"].shape[0], crw=cr_pad,
                        floor=policy.min_capacity)
                if cap_s != cap_eff:
                    trail.append({"rung": (cap, win, exp),
                                  "effective": (cap_s, win, exp_s),
                                  "segment": 0, "level": 0,
                                  "event": "plan",
                                  "outcome": f"plan-seeded-pool-{cap_s}",
                                  "predicted-bytes": pred,
                                  "bytes-limit": blim})
                    log.warning(
                        "predicted footprint at %s rows exceeds the "
                        "%s B byte budget; seeding the pool at %s "
                        "rows (predicted %s B)", cap, blim, cap_s, pred)
                    cap_eff, exp_eff = cap_s, exp_s
            carry = T._carry0_host(cap_eff, win, cr_pad, cols["ini"],
                                   int(cols["nr"]),
                                   stats_rows=(lmax + 1) if stats else 0)
        carry = _fit_carry_stats(carry, stats, lmax)
        transients = ooms = 0
        preempted = False
        abort: Optional[str] = None
        obs_observatory.begin(
            level_budget=lmax, rung=(cap_eff, win, exp_eff),
            segment_iters=seg,
            backend=("cpu-fallback" if fallback is not None
                     else "default"))
        while T._carry_active(carry, lmax):
            # Segment-boundary device-memory poll: updates the
            # per-device gauges; a headroom ratio below JTPU_HEADROOM_MIN
            # halves the pool BEFORE the allocator fails. Once per rung:
            # the allocator retains freed pages, so in_use does not drop
            # after a halve and re-triggering would cascade to the floor.
            headroom = obs_devices.headroom_ratio()
            if (headroom is not None and hr_min > 0 and not preempted
                    and headroom < hr_min
                    and cap_eff // 2 >= policy.min_capacity):
                new_cap = cap_eff // 2
                carry, dropped = _shrink_carry(carry, new_cap)
                cap_eff = new_cap
                if isinstance(exp_eff, int):
                    exp_eff = max(1, min(exp_eff // 2, cap_eff))
                preempted = True
                _PREEMPT_TOTAL.inc()
                trail.append({"rung": (cap, win, exp),
                              "effective": (cap_eff, win, exp_eff),
                              "segment": seg_idx, "level": int(carry[8]),
                              "event": OOM,
                              "outcome": f"preemptive-halve-to-{cap_eff}",
                              "headroom": round(headroom, 4),
                              "lossy": dropped})
                log.warning(
                    "device headroom %.1f%% below the %.1f%% floor; "
                    "pre-emptively halving the pool to %s rows",
                    100 * headroom, 100 * hr_min, cap_eff)
            unroll = T._unroll_factor()
            fn = T._jit_segment(T._kernel_key(kernel), cap_eff, win,
                                exp_eff, unroll, stats=stats)
            ctx = {"rung": (cap, win, exp),
                   "effective": (cap_eff, win, exp_eff),
                   "segment": seg_idx, "level": int(carry[8]),
                   "backend": ("cpu-fallback" if fallback is not None
                               else "default")}
            shape_key = ("segment", T._kernel_key(kernel), cap_eff, win,
                         exp_eff, unroll, cols["f"].shape[0],
                         cols["cf"].shape[0], stats)
            # phase decided up front, marked executed only on success: a
            # segment that dies mid-compile pays compile again on retry
            phase = ("compile" if shape_key not in T._EXECUTED_SHAPES
                     else "execute")
            lvl0 = int(carry[8])
            try:
                if _inject_fault is not None:
                    _inject_fault(dict(ctx))
                # The watchdog guards the AMBIENT device only: host
                # (fallback) execution is trusted — and its first
                # segment legitimately spends deadline-sized time
                # compiling.
                with obs.span("checker.segment", phase=phase,
                              segment=seg_idx, level=lvl0,
                              rung=[cap_eff, win, exp_eff],
                              backend=ctx["backend"]) as sp:
                    t0 = time.perf_counter()
                    carry = _call_segment(fn, cols, carry, seg,
                                          device=fallback,
                                          deadline_s=(None if fallback
                                                      is not None
                                                      else deadline_s))
                    seg_s = time.perf_counter() - t0
                    sp.set(level_end=int(carry[8]))
            except WedgeError as e:
                _WEDGE_TOTAL.inc()
                if fallback is not None:
                    trail.append({**ctx, "event": WEDGE,
                                  "outcome": "gave-up",
                                  "error": _errstr(e)})
                    abort = ("segment wedged on the CPU fallback too: "
                             f"{e}")
                    break
                dev = cpu_device()
                note_runtime_wedge(
                    "supervised_check_packed",
                    deadline_s or 0.0, level=int(carry[8]))
                if dev is None:
                    trail.append({**ctx, "event": WEDGE,
                                  "outcome": "gave-up",
                                  "error": "no CPU fallback device"})
                    abort = ("segment wedged and no CPU fallback device "
                             f"is available: {e}")
                    break
                trail.append({**ctx, "event": WEDGE,
                              "outcome": "cpu-fallback",
                              "error": _errstr(e)})
                log.warning(
                    "device segment wedged at level %s; resuming the "
                    "checkpoint on the CPU fallback", int(carry[8]))
                fallback = dev
            except Exception as e:  # noqa: BLE001 — classified below
                cls = classify_failure(e)
                if cls == OOM:
                    ooms += 1
                    _OOM_TOTAL.inc()
                    new_cap = cap_eff // 2
                    if new_cap < policy.min_capacity:
                        trail.append({**ctx, "event": OOM,
                                      "outcome": "gave-up",
                                      "error": _errstr(e)})
                        abort = (f"OOM at the {policy.min_capacity}-row "
                                 f"pool floor: {e}")
                        break
                    carry, dropped = _shrink_carry(carry, new_cap)
                    cap_eff = new_cap
                    if isinstance(exp_eff, int):
                        exp_eff = max(1, min(exp_eff // 2, cap_eff))
                    delay = policy.delay(ooms)
                    trail.append({**ctx, "event": OOM,
                                  "outcome": f"pool-halved-to-{cap_eff}",
                                  "lossy": dropped,
                                  "backoff-s": round(delay, 3),
                                  "error": _errstr(e)})
                    log.warning(
                        "device OOM at level %s; halving the pool to %s "
                        "rows and resuming the checkpoint (backoff "
                        "%.2fs)", int(carry[8]), cap_eff, delay)
                    _BACKOFF_SECONDS.inc(delay)
                    time.sleep(delay)
                elif cls in (TRANSIENT, DCN):
                    transients += 1
                    (_DCN_TOTAL if cls == DCN else _TRANSIENT_TOTAL).inc()
                    if transients > policy.max_retries:
                        trail.append({**ctx, "event": cls,
                                      "outcome": "retries-exhausted",
                                      "error": _errstr(e)})
                        try:
                            e.resilience_trail = trail
                        except Exception:  # noqa: BLE001
                            pass
                        raise
                    delay = policy.delay(transients)
                    trail.append({**ctx, "event": cls,
                                  "outcome": f"retry-{transients}",
                                  "backoff-s": round(delay, 3),
                                  "error": _errstr(e)})
                    log.warning(
                        "%s device failure (%s); retrying the "
                        "segment from its checkpoint in %.2fs",
                        cls, _errstr(e), delay)
                    _BACKOFF_SECONDS.inc(delay)
                    time.sleep(delay)
                else:
                    trail.append({**ctx, "event": FATAL,
                                  "outcome": "raised",
                                  "error": _errstr(e)})
                    try:
                        e.resilience_trail = trail
                    except Exception:  # noqa: BLE001
                        pass
                    raise
            else:
                seg_idx += 1
                transients = 0
                # success: mark the shape compiled, account the segment
                # (wall histogram + cold-compile/cache-hit counters)
                T._EXECUTED_SHAPES.add(shape_key)
                device_s[phase] += seg_s
                T._note_call_phase("segment", phase, seg_s)
                lvl1 = int(carry[8])
                seg_levels.append(lvl1 - lvl0)
                alive = int(np.count_nonzero(np.asarray(carry[4])))
                frontier_hwm = max(frontier_hwm, alive)
                T._LEVELS_TOTAL.inc(lvl1 - lvl0)
                T._SEGMENTS_TOTAL.inc()
                T._FRONTIER_HWM.set_max(alive)
                carry_b = sum(int(np.asarray(x).nbytes) for x in carry)
                # each segment re-ships the packed columns and the carry
                # to the device and snapshots the carry back to host
                T._TRANSFER_BYTES.inc(cols_b + carry_b,
                                      direction="host-to-device")
                T._TRANSFER_BYTES.inc(carry_b,
                                      direction="device-to-host")
                transfer_bytes += cols_b + 2 * carry_b
                # search analytics: the counter lane rows this segment
                # advanced through, rolled into searchstats.json and the
                # live dup-rate/truncation bits (host code BETWEEN
                # device segments — never inside the traced body)
                dup_rate = trunc = None
                if stats and len(carry) > 13:
                    slog = np.asarray(carry[13])
                    obs_searchstats.record(slog[:lvl1],
                                           rung=(cap_eff, win, exp_eff))
                    seg_rows = slog[lvl0:lvl1]
                    if seg_rows.size:
                        dup_rate = obs_searchstats.dup_rate(seg_rows)
                        trunc = int(seg_rows[:, 3].sum())
                # live heartbeat: level / frontier / rate / ETA into the
                # observatory gauges + progress.json (the watch surface)
                obs_observatory.publish(
                    level=lvl1, frontier=alive, segments=seg_idx,
                    seg_seconds=seg_s, levels_delta=lvl1 - lvl0,
                    expansions=(lvl1 - lvl0)
                    * min(exp_eff or cap_eff, cap_eff),
                    rung=(cap_eff, win, exp_eff),
                    backend=ctx["backend"], headroom=headroom,
                    warmup=phase == "compile",
                    dup_rate=dup_rate, trunc=trunc)
                if checkpoint_path or on_checkpoint is not None:
                    cp = Checkpoint(carry=carry, rung=(cap, win, exp),
                                    window=win, expand_eff=exp_eff,
                                    crash_width=crw, segment=seg_idx)
                    if checkpoint_path:
                        cp.save(checkpoint_path)
                    if on_checkpoint is not None:
                        on_checkpoint(cp)
        done, lossy, wovf, best, levels, pool = T._summarize_carry(carry)
        rung_eff = (cap_eff, win, exp_eff)
        trail.append({"rung": (cap, win, exp), "effective": rung_eff,
                      "event": ("rung-aborted" if abort is not None
                                else "rung-complete"),
                      "segments": seg_idx, "levels": levels,
                      "backend": ("cpu-fallback" if fallback is not None
                                  else "default")})
        if abort is not None:
            out = {"valid": UNKNOWN, "backend": "tpu", "levels": levels,
                   "error": abort}
        else:
            out = T._result(done, lossy, wovf, best, levels, p, pool=pool)
        out["rung"] = rung_eff
        if rung_eff != (cap, win, exp):
            out["rung-requested"] = (cap, win, exp)
        out["crash-width"] = crw
        out["tiebreak"] = "lex"
        work.append((rung_eff, crw, "lex", levels))
        out["work"] = list(work)
        if plan_entry is not None:
            out["plan"] = plan_entry
        out["segments"] = seg_idx
        out["segment-iters"] = seg
        out["attempts"] = list(trail)
        # Telemetry (doc/observability.md): the compile/execute wall
        # split (host-measured around block_until_ready), per-segment
        # level advances, the frontier-width high-water mark, and
        # bytes shipped to/from the device — what `# search:` summaries
        # and bench.py read to attribute wall-clock.
        out["device-s"] = {k: round(v, 6) for k, v in device_s.items()}
        out["segment-levels"] = list(seg_levels)
        out["frontier-hwm"] = frontier_hwm
        out["transfer-bytes"] = transfer_bytes
        if stats and len(carry) > 13:
            ss = obs_searchstats.rollup(np.asarray(carry[13])[:levels])
            out["searchstats"] = ss
            obs_searchstats.finalize(ss)
        if fallback is not None:
            out["backend-fallback"] = "cpu"
        if out["valid"] is not UNKNOWN:
            return out
        if abort is not None:
            # OOM floor / exhausted fallback: a bigger rung would only
            # fail harder, so escalation stops here
            return out
        if bool(wovf) and win >= T.MAX_WINDOW and not bool(lossy):
            return out  # a bigger frontier won't fix a window overflow
    return out


def supervised_check_history(history, model: Model,
                             **kwargs) -> Optional[Dict[str, Any]]:
    """Pack + supervised check (see supervised_check_packed). None when
    the model has no integer kernel."""
    try:
        pk = pack_with_init(history, model)
    except ValueError:
        return None
    if pk is None:
        return None
    packed, kernel = pk
    return supervised_check_packed(packed, kernel, **kwargs)
