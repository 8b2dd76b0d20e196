"""Streaming ingestion with crash-safe online checking.

`jtpu serve` (doc/serve.md "Streaming API") accepts histories as they
happen instead of after the fact: a client opens a *stream session*,
appends CRC'd chunks of ops under per-chunk sequence numbers, and seals
it with a close. This module owns the two halves behind those routes:

* :class:`StreamSession` — the intake state machine. Chunks are
  idempotent (a re-POST of an already-accepted sequence number is a
  cheap 202, never re-journaled), out-of-order arrivals within a bounded
  reorder window are buffered, and gaps answer 409 with a ``need=<seq>``
  hint so an at-least-once client can always converge. Every accepted
  chunk is appended to the session's own WAL (``streams/<sid>/wal.jsonl``,
  :mod:`jepsen_tpu.journal` framing) BEFORE the ack, so a SIGKILLed
  daemon replays open sessions — same ops, same trace id.

* :class:`StreamRunner` — the online checker. It feeds arriving ops
  through :class:`jepsen_tpu.ops.encode.StreamPacker` and runs the
  segmented device search (the :mod:`jepsen_tpu.resilience` supervisor's
  machinery) over the growing *stable prefix*: at every segment barrier
  it snapshots a **partial verdict** — the search carry plus the prefix
  watermark it has checked — to ``streams/<sid>/checkpoint.npz``. The
  soundness story is the stable-prefix extension property (see
  StreamPacker's docstring): packed columns of a longer stable prefix
  literally extend a shorter one's, so the carry transfers across
  extension (:func:`jepsen_tpu.checker.tpu._reopen_carry`) and a daemon
  killed mid-stream resumes from the checkpointed level — never from
  level 0. An invalid prefix short-circuits the stream immediately
  (fail-fast): pool death without truncation at a stable prefix refutes
  the full history, because every crashed op's invocation lies at or
  past the watermark, so a witness for the whole history restricted to
  the prefix would be a witness for the prefix.

Escalation (capacity-ladder rungs, window growth, lossy/window-overflow
retries) *rebases* — restarts at level 0 on a bigger rung, exactly like
the offline ladder — while crash-resume always continues from the
checkpoint. The distinction is what the ``stream-kill`` chaos scenario
asserts via the per-level counter lane.

This module is imported lazily by serve.py, only when the feature is on
(JTPU_SERVE_STREAM): with the kill switch off, no stream metric names,
routes, or WAL record kinds exist — the daemon is byte-identical to its
pre-streaming behavior.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from jepsen_tpu import obs
from jepsen_tpu import journal as journal_ns
from jepsen_tpu import resilience as R
from jepsen_tpu.checker import UNKNOWN
from jepsen_tpu.checker import tpu as T
from jepsen_tpu.history import History, Op
from jepsen_tpu.models.core import kernel_spec_for
from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.obs import searchstats as obs_searchstats
from jepsen_tpu.obs import trace as obs_trace
from jepsen_tpu.ops.encode import StreamPacker, _Interner

log = logging.getLogger(__name__)

WAL_NAME = "wal.jsonl"
CHECKPOINT_NAME = "checkpoint.npz"
HISTORY_NAME = "history.json"
RESULT_NAME = "result.json"

_CHUNKS = obs_metrics.counter(
    "jtpu_stream_chunks_total", "Stream chunks accepted")
_DUPS = obs_metrics.counter(
    "jtpu_stream_dup_chunks_total", "Duplicate stream chunks absorbed")
_REORDERED = obs_metrics.counter(
    "jtpu_stream_reordered_chunks_total",
    "Out-of-order stream chunks buffered")
_GAPS = obs_metrics.counter(
    "jtpu_stream_gap_rejects_total", "Stream appends rejected on a gap")
_OPS = obs_metrics.counter(
    "jtpu_stream_ops_total", "Stream ops accepted")
_RESUMES = obs_metrics.counter(
    "jtpu_stream_resumes_total",
    "Stream sessions resumed from a partial-verdict checkpoint")
_FAILFAST = obs_metrics.counter(
    "jtpu_stream_failfast_total",
    "Streams short-circuited by an invalid prefix")
_LAG = obs_metrics.gauge(
    "jtpu_stream_lag_ops",
    "Buffered ops not yet covered by a checked stable prefix")


def chunk_crc(ops: list) -> str:
    """CRC of a chunk body, computed over the canonical compact JSON of
    the ops list — the client and server must agree byte-for-byte, so
    both use sort_keys + no whitespace."""
    blob = json.dumps(ops, separators=(",", ":"), sort_keys=True,
                      default=repr).encode()
    return "%08x" % (zlib.crc32(blob) & 0xFFFFFFFF)


def _atomic_json(path: str, doc: Any) -> None:
    """tmp+replace with deterministic serialization: the byte-identity
    tests compare these artifacts across delivery orders and across a
    SIGKILL replay."""
    d, base = os.path.split(path)
    # dot-prefixed so dir scanners (replay, GC) skip torn tmp files
    tmp = os.path.join(d, f".{base}.tmp.{os.getpid()}")
    with open(tmp, "w") as f:
        json.dump(doc, f, separators=(",", ":"), sort_keys=True,
                  default=repr)
    os.replace(tmp, path)


class StreamSession:
    """One open stream: sequencing, reorder absorption, and the WAL.

    All intake mutations happen under :attr:`lock`; :attr:`cond` wakes
    the runner when ops arrive or the stream seals. States: ``open`` ->
    ``closed`` (sealed, runner finishing) -> ``done`` (result persisted);
    a fail-fast refutation moves ``open`` -> ``done`` directly.
    """

    def __init__(self, sid: str, tenant: str, model: str, root: str,
                 reorder_max: int = 64, trace: Optional[str] = None,
                 trace_parent: Optional[str] = None,
                 journal_open: bool = True):
        self.id = sid               # guarded-by: none — immutable after init
        self.tenant = tenant
        self.model = model
        self.dir = os.path.join(root, "streams", sid)
        os.makedirs(self.dir, exist_ok=True)
        self.reorder_max = int(reorder_max)
        self.trace = trace
        self.trace_parent = trace_parent
        self.lock = threading.Lock()
        self.cond = threading.Condition(self.lock)
        self.state = "open"
        self.next_seq = 0               # next contiguous sequence wanted
        self.ops: List[dict] = []       # accepted ops, sequence order
        self.reorder: Dict[int, list] = {}   # journaled, not yet contiguous
        self.dups = 0
        self.reordered = 0
        self.gaps = 0
        self.created = time.time()
        self.closed_at: Optional[float] = None
        self.result: Optional[Dict[str, Any]] = None
        # runner progress mirrored here (under lock) for status/lag
        self.checked_events = 0
        self.checked_level = 0
        self.checked_nr = 0
        self.footprint = 0
        self.runner: Optional["StreamRunner"] = None
        self._wal = open(os.path.join(self.dir, WAL_NAME), "ab")
        if journal_open:
            self._journal({"event": "open", "id": sid, "tenant": tenant,
                           "model": model, "trace": trace,
                           "trace-parent": trace_parent,
                           "ts": round(self.created, 6)})

    # -- WAL ----------------------------------------------------------------

    def _journal(self, rec: dict) -> None:
        """Durable BEFORE the ack: fsync'd so a SIGKILL immediately after
        the 202 cannot lose an accepted chunk."""
        self._wal.write(journal_ns.encode_json_record(rec))
        self._wal.flush()
        os.fsync(self._wal.fileno())

    # -- intake -------------------------------------------------------------

    def append(self, seq: Any, ops: Any,
               crc: Optional[str] = None) -> Tuple[int, Dict[str, Any]]:
        """One chunk. Returns (http_status, body). Idempotent under
        at-least-once delivery: duplicates 202 without re-journaling,
        out-of-order within ``reorder_max`` buffers, gaps beyond it 409
        with the sequence number the server needs next."""
        try:
            seq = int(seq)
        except (TypeError, ValueError):
            return 400, {"error": "seq must be an integer"}
        if seq < 0 or not isinstance(ops, list):
            return 400, {"error": "need seq >= 0 and ops list"}
        if crc is not None and chunk_crc(ops) != crc:
            return 400, {"error": "crc-mismatch", "seq": seq}
        with self.cond:
            if self.state != "open":
                if seq < self.next_seq:
                    # late duplicate of an accepted chunk: still a 202 —
                    # the client's retry loop must converge after close
                    self.dups += 1
                    _DUPS.inc()
                    return 202, {"id": self.id, "seq": seq,
                                 "duplicate": True, "state": self.state,
                                 "need": self.next_seq}
                if (self.state == "done" and self.result is not None
                        and self.result.get("stream", {}).get(
                            "failed-fast")):
                    return 409, {"error": "stream-failed", "id": self.id,
                                 "state": self.state}
                return 409, {"error": "stream-closed", "id": self.id,
                             "state": self.state}
            if seq < self.next_seq or seq in self.reorder:
                self.dups += 1
                _DUPS.inc()
                return 202, {"id": self.id, "seq": seq, "duplicate": True,
                             "need": self.next_seq}
            if seq > self.next_seq:
                if seq - self.next_seq > self.reorder_max:
                    self.gaps += 1
                    _GAPS.inc()
                    return 409, {"error": "gap", "id": self.id,
                                 "seq": seq, "need": self.next_seq,
                                 "reorder-max": self.reorder_max}
                # journaled at accept time: a replay re-buffers it
                self._journal({"event": "chunk", "seq": seq, "ops": ops})
                self.reorder[seq] = ops
                self.reordered += 1
                _REORDERED.inc()
                _CHUNKS.inc()
                return 202, {"id": self.id, "seq": seq, "buffered": True,
                             "need": self.next_seq}
            self._journal({"event": "chunk", "seq": seq, "ops": ops})
            self._admit(seq, ops)
            while self.next_seq in self.reorder:
                self._admit(self.next_seq,
                            self.reorder.pop(self.next_seq))
            _CHUNKS.inc()
            self.cond.notify_all()
            return 202, {"id": self.id, "seq": seq, "ops": len(self.ops),
                         "need": self.next_seq}

    def _admit(self, seq: int, ops: list) -> None:
        self.ops.extend(ops)
        self.next_seq = seq + 1
        _OPS.inc(len(ops))

    def close(self, chunks: Optional[Any] = None
              ) -> Tuple[int, Dict[str, Any]]:
        """Seal the stream. ``chunks`` (the client's total chunk count)
        catches in-flight holes: a close racing a lost chunk answers 409
        with the missing sequence number instead of sealing short."""
        with self.cond:
            if self.state != "open":
                return 200, {"id": self.id, "state": self.state,
                             "ops": len(self.ops)}
            if self.reorder or (chunks is not None
                                and int(chunks) != self.next_seq):
                self.gaps += 1
                _GAPS.inc()
                return 409, {"error": "gap", "id": self.id,
                             "need": self.next_seq,
                             "buffered": sorted(self.reorder)}
            self._journal({"event": "close", "chunks": self.next_seq,
                           "ops": len(self.ops)})
            self.state = "closed"
            self.closed_at = time.time()
            # the canonical history artifact: ops in sequence order,
            # deterministic bytes — identical no matter how chunks were
            # delivered or how many times the daemon was killed
            _atomic_json(os.path.join(self.dir, HISTORY_NAME), self.ops)
            self.cond.notify_all()
            return 200, {"id": self.id, "state": "closed",
                         "chunks": self.next_seq, "ops": len(self.ops)}

    # -- runner handshake ---------------------------------------------------

    def lag(self) -> int:
        with self.lock:
            return max(0, len(self.ops) - self.checked_events)

    def note_progress(self, events: int, level: int, nr: int,
                      footprint: int = 0) -> None:
        with self.lock:
            self.checked_events = events
            self.checked_level = level
            self.checked_nr = nr
            if footprint:
                self.footprint = footprint

    def finish(self, result: Dict[str, Any], secs: float,
               on_done: Optional[Callable[["StreamSession"], None]] = None
               ) -> None:
        """Persist the verdict: result file first (tmp+replace), then the
        terminal WAL record — a crash between them re-runs the check,
        never loses the stream (the daemon's _finish discipline)."""
        _atomic_json(os.path.join(self.dir, RESULT_NAME), result)
        with self.cond:
            self._journal({"event": "verdict",
                           "valid": repr(result.get("valid")),
                           "seconds": round(secs, 6)})
            self.result = result
            self.state = "done"
            self.cond.notify_all()
        if self.trace and obs_trace.enabled():
            with obs_trace.context(self.trace, self.trace_parent):
                obs_trace.event("stream.verdict", id=self.id,
                                valid=repr(result.get("valid")),
                                seconds=round(secs, 6))
        if on_done is not None:
            on_done(self)

    def status(self) -> Dict[str, Any]:
        with self.lock:
            doc = {"id": self.id, "state": self.state,
                   "tenant": self.tenant, "model": self.model,
                   "ops": len(self.ops), "chunks": self.next_seq,
                   "need": self.next_seq,
                   "buffered-chunks": len(self.reorder),
                   "dup-chunks": self.dups, "reordered": self.reordered,
                   "checked-events": self.checked_events,
                   "checked-level": self.checked_level,
                   "lag": max(0, len(self.ops) - self.checked_events)}
            if self.trace:
                doc["trace"] = self.trace
            if self.result is not None:
                doc["result"] = self.result
            return doc

    def stop_wal(self) -> None:
        # under the session lock: closing mid-_journal would turn a
        # concurrent fsync'd append into a ValueError on a closed file
        with self.lock:
            try:
                self._wal.close()
            except OSError:
                pass

    # -- replay -------------------------------------------------------------

    @classmethod
    def replay(cls, sdir: str, root: str,
               reorder_max: int = 64) -> Optional["StreamSession"]:
        """Rebuild a session from its WAL after a crash. Chunks are
        re-admitted in sequence order regardless of arrival order, so
        the replayed ops list — and the history artifact — is
        byte-identical to the pre-crash one. Torn tails are dropped by
        the journal reader; the client's at-least-once retry re-sends
        whatever the tail lost."""
        path = os.path.join(sdir, WAL_NAME)
        if not os.path.exists(path):
            return None
        records, stats = journal_ns.read_json_records(path)
        opened = next((r for r in records if r.get("event") == "open"),
                      None)
        if opened is None:
            return None
        sid = opened.get("id") or os.path.basename(sdir)
        s = cls(sid, opened.get("tenant", "anon"),
                opened.get("model", ""), root, reorder_max=reorder_max,
                trace=opened.get("trace"),
                trace_parent=opened.get("trace-parent"),
                journal_open=False)
        chunks: Dict[int, list] = {}
        closed = False
        verdict = False
        for r in records:
            ev = r.get("event")
            if ev == "chunk":
                chunks[int(r["seq"])] = r.get("ops") or []
            elif ev == "close":
                closed = True
            elif ev == "verdict":
                verdict = True
        for seq in sorted(chunks):
            if seq == s.next_seq:
                s._admit(seq, chunks[seq])
            elif seq > s.next_seq:
                s.reorder[seq] = chunks[seq]
        if closed:
            s.state = "closed"
            s.closed_at = time.time()
            hist = os.path.join(s.dir, HISTORY_NAME)
            if not os.path.exists(hist):
                _atomic_json(hist, s.ops)
        if verdict:
            s.state = "done"
            try:
                with open(os.path.join(s.dir, RESULT_NAME)) as f:
                    s.result = json.load(f)
            except (OSError, ValueError):
                # verdict record without a readable result: re-check
                s.state = "closed" if closed else "open"
                s.result = None
        if stats.get("torn") or stats.get("corrupt"):
            log.warning("stream %s WAL replay dropped %s torn / %s "
                        "corrupt records", sid, stats.get("torn"),
                        stats.get("corrupt"))
        return s


class _Verdict(Exception):
    """Internal control flow: the online loop reached a final result."""

    def __init__(self, result: Dict[str, Any]):
        super().__init__(repr(result.get("valid")))
        self.result = result


class StreamRunner(threading.Thread):
    """Online checker thread for one session.

    Mirrors :func:`jepsen_tpu.resilience._supervised_check_packed`'s
    segment loop, restructured as a state machine so the packed columns
    can be swapped under the live carry at stable-prefix barriers. Three
    transitions touch the carry:

    * **extend** — the stable prefix grew (or the stream closed with no
      new crashed-mask words): rebuild the columns for the longer
      prefix and reopen the same carry
      (:func:`jepsen_tpu.checker.tpu._reopen_carry`). Level and counter
      lane continue — this is the partial verdict surviving.
    * **rebase** — the needed window outgrew the rung, the pool went
      lossy, the window overflowed, or close added more crashed-mask
      words than the carry holds: restart at level 0 on the next/bigger
      rung, exactly the offline escalation ladder.
    * **resume** — a replayed daemon hands the runner the session's
      checkpoint: the carry continues at its saved level over whatever
      prefix the WAL replay reconstructed (always >= the checkpointed
      one, since checkpoints follow journaled chunks).
    """

    def __init__(self, session: StreamSession, model: Any,
                 backend: str = "tpu",
                 segment_iters: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 on_done: Optional[Callable[[StreamSession], None]] = None,
                 resume: bool = True):
        super().__init__(name=f"jtpu-stream-{session.id}", daemon=True)
        self.session = session
        self.model = model
        self.backend = backend
        self.on_done = on_done
        self.checkpoint_path = os.path.join(session.dir, CHECKPOINT_NAME)
        self._halt = threading.Event()
        self._seg = T._segment_config(segment_iters)
        self._deadline_s = deadline_s
        self._policy = R.RetryPolicy()
        self._resume_cp = None
        if resume and os.path.exists(self.checkpoint_path):
            try:
                self._resume_cp = R.Checkpoint.load(self.checkpoint_path)
            except Exception as e:  # noqa: BLE001 — corrupt: start fresh
                log.warning("stream %s: unreadable checkpoint (%s); "
                            "starting from level 0", session.id, e)
        # packer state (exactly pack_with_init's init handling)
        kernel = kernel_spec_for(model) if model is not None else None
        self.kernel = kernel
        self._packer: Optional[StreamPacker] = None
        if kernel is not None and kernel.remap is None:
            intern = _Interner()
            init = (kernel.pack_init(model, intern.id)
                    if kernel.pack_init is not None
                    else kernel.init_state)
            self._packer = StreamPacker(kernel, init_state=init,
                                        intern=intern)
        # search state
        self._fed = 0
        self._p = None
        self._cols = None
        self._carry = None
        self._ladder: Optional[tuple] = None
        self._rung_i = 0
        self._rung = None               # (cap, win, exp) requested
        self._cap_eff = self._exp_eff = None
        self._seg_idx = 0
        self._crw = 0
        self._lmax = 0
        self._checked_nr = 0
        self._checked_wm = 0
        self._final = False
        self._suspended = False         # ladder exhausted mid-stream
        self._stats = obs.enabled()
        self._fallback = (R.cpu_device()
                          if R.runtime_wedged() else None)
        self._transients = 0
        self._ooms = 0
        self._barriers = 0
        self._rebases: List[str] = []
        self._resume_level: Optional[int] = None
        self._failed_fast = False

    def stop(self) -> None:
        self._halt.set()
        with self.session.cond:
            self.session.cond.notify_all()

    # -- main loop ----------------------------------------------------------

    def run(self) -> None:
        t_ctx = (obs_trace.context(self.session.trace,
                                   self.session.trace_parent)
                 if self.session.trace and obs_trace.enabled()
                 else None)
        try:
            if t_ctx is not None:
                with t_ctx:
                    self._run()
            else:
                self._run()
        except _Verdict as v:
            self._deliver(v.result)
        except Exception as e:  # noqa: BLE001 — runner must not die silent
            if self._halt.is_set():
                return      # shutdown race: the checkpoint is the state
            log.exception("stream %s online check crashed", self.session.id)
            self._deliver({"valid": UNKNOWN, "backend": self.backend,
                           "error": f"stream checker crashed: {e}"})

    def _deliver(self, result: Dict[str, Any]) -> None:
        result.setdefault("stream", {}).update(self._telemetry())
        secs = (time.time() - self.session.closed_at
                if self.session.closed_at else 0.0)
        self.session.finish(result, secs, on_done=self.on_done)

    def _telemetry(self) -> Dict[str, Any]:
        s = self.session
        out = {"ops": len(s.ops), "chunks": s.next_seq,
               "dup-chunks": s.dups, "reordered": s.reordered,
               "watermark": self._checked_wm, "barriers": self._barriers,
               "rebases": list(self._rebases),
               "failed-fast": self._failed_fast}
        if self._resume_level is not None:
            out["resume-level"] = self._resume_level
        return out

    def _run(self) -> None:
        if self._packer is None:
            self._run_offline()
            return
        while True:
            new, closed = self._poll()
            if self._halt.is_set():
                return
            if new:
                try:
                    self._packer.feed_ops(new)
                except ValueError as e:
                    raise _Verdict({"valid": UNKNOWN,
                                    "backend": self.backend,
                                    "error": str(e)})
            if closed and not self._final:
                self._rebuild(self._packer.close(), final=True)
            elif (not self._final and not self._suspended
                  and self._packer.stable_required > self._checked_nr):
                self._rebuild(self._packer.stable_packed(), final=False)
            if self._suspended and not self._final:
                continue
            if self._carry is None and self._cols is not None:
                self._seed_carry()
            if self._carry is None:
                continue
            if T._carry_active(self._carry, self._lmax):
                self._segment()
                continue
            done, lossy, wovf, best, levels, pool = \
                T._summarize_carry(self._carry)
            if done:
                if self._final:
                    raise _Verdict(self._result(True, False, False,
                                                best, levels, pool))
                # caught up with the stream: idle until more ops arrive
                continue
            if lossy or wovf:
                self._escalate(lossy, wovf, best, levels)
                continue
            # pool death, nothing truncated: exhaustive refutation of
            # the checked prefix — sound for the full history too
            # (fail-fast; every crashed op invokes at/past the
            # watermark, so restricting any witness to the prefix
            # would witness the prefix)
            if not self._final:
                self._failed_fast = True
                _FAILFAST.inc()
            raise _Verdict(self._result(False, False, False, best,
                                        levels, pool))

    def _poll(self) -> Tuple[list, bool]:
        s = self.session
        with s.cond:
            if (len(s.ops) == self._fed and s.state == "open"
                    and not self._work_pending()):
                s.cond.wait(0.25)
            new = list(s.ops[self._fed:])
            self._fed += len(new)
            closed = s.state != "open"
        return new, closed

    def _work_pending(self) -> bool:
        return (self._carry is not None
                and T._carry_active(self._carry, self._lmax))

    # -- barrier transitions ------------------------------------------------

    def _rebuild(self, p, final: bool) -> None:
        """A stable-prefix barrier: swap the packed columns under the
        carry (extend) or schedule a fresh rung (rebase)."""
        self._barriers += 1
        nr = p.n_required
        if final and nr == 0:
            raise _Verdict({"valid": True, "levels": 0,
                            "backend": "tpu"})
        n_cr = p.n - nr
        crw = (T._crash_width(n_cr) or 0) if final else 0
        if final and T._crash_width(n_cr) is None:
            raise _Verdict({
                "valid": UNKNOWN, "backend": "tpu",
                "error": f"{n_cr} crashed ops exceed the crashed-set "
                         f"width {T.CRASH_MAX}"})
        breq = T._bucket(nr)
        cols = T._split_packed(p, breq, crw, self.kernel)
        wneed = T._window_needed(p)
        lmax = T._level_budget(breq, crw)
        transfer = self._carry is not None
        if transfer and wneed > self._rung[1]:
            self._rebases.append(f"window-{wneed}")
            transfer = False
        if transfer and crw != self._crw and (
                max((crw + 31) // 32, 1)
                != max((self._crw + 31) // 32, 1)):
            # close added crashed-MASK WORDS the carry doesn't hold; a
            # same-word-count widening (0 -> up to 32 crashed ops) keeps
            # the carry — its cmask bits are all zero at width 0
            self._rebases.append(f"crash-width-{crw}")
            transfer = False
        self._p, self._cols, self._crw, self._lmax = p, cols, crw, lmax
        self._final = final or self._final
        if transfer:
            # reopen ONLY when the barrier added required ops: done was
            # latched against fk >= n_required, so a done carry stays
            # correctly done when nr is unchanged (close appending only
            # crashed tail ops adds OPTIONAL witnesses). Clearing done
            # anyway would re-derive it with extra levels — drifting
            # the level counter away from the offline path's.
            if nr > self._checked_nr:
                self._carry = T._reopen_carry(self._carry, nr)
            if self._stats:
                self._carry = R._grow_carry_stats(self._carry, lmax)
        else:
            self._carry = None
            self._ladder = T._ladder_for(wneed)
            self._rung_i = 0
            self._suspended = False
        self._checked_nr = nr
        self._checked_wm = (self._packer.n_events if final
                            else self._packer.watermark)

    def _seed_carry(self) -> None:
        """Start (or resume) a rung over the current columns."""
        cp = self._resume_cp
        self._resume_cp = None
        if cp is not None and 0 <= cp.n_required <= self._checked_nr \
                and cp.window >= T._window_needed(self._p) \
                and cp.crash_width == self._crw:
            carry = tuple(np.asarray(x) if isinstance(x, np.ndarray)
                          else x for x in cp.carry)
            self._rung = tuple(cp.rung)
            idx = next((i for i, r in enumerate(self._ladder)
                        if tuple(r) == self._rung), None)
            if idx is None:
                self._ladder = (self._rung,) + tuple(self._ladder)
                idx = 0
            self._rung_i = idx
            self._cap_eff = cp.capacity_eff
            self._exp_eff = cp.expand_eff
            self._seg_idx = cp.segment
            carry = R._fit_carry_stats(carry, self._stats, self._lmax)
            if self._stats:
                carry = R._grow_carry_stats(carry, self._lmax)
            if self._checked_nr > cp.n_required:
                # the WAL replay reconstructed a LONGER stable prefix
                # than the checkpoint had seen; same no-reopen-on-equal
                # rule as _rebuild's
                carry = T._reopen_carry(carry, self._checked_nr)
            self._carry = carry
            self._resume_level = int(self._carry[8])
            _RESUMES.inc()
            log.info("stream %s: resumed from checkpoint at level %s "
                     "(watermark %s, %s required ops)", self.session.id,
                     self._resume_level, cp.watermark, cp.n_required)
            return
        if cp is not None:
            self._rebases.append("checkpoint-stale")
        cap, win, exp = self._ladder[min(self._rung_i,
                                         len(self._ladder) - 1)]
        T._check_window(win)
        self._rung = (cap, win, exp)
        self._cap_eff, self._exp_eff = cap, exp
        self._seg_idx = 0
        cr_pad = self._cols["cf"].shape[0]
        self._carry = T._carry0_host(
            cap, win, cr_pad, self._cols["ini"], int(self._cols["nr"]),
            stats_rows=(self._lmax + 1) if self._stats else 0)

    def _escalate(self, lossy: bool, wovf: bool, best: int,
                  levels: int) -> None:
        """Lossy/overflow at rung end: rebase on the next rung (level 0
        — the legitimate restart, distinct from crash-resume)."""
        if self._rung_i + 1 >= len(self._ladder):
            if self._final:
                raise _Verdict(self._result(False, lossy, wovf, best,
                                            levels, None))
            # mid-stream ladder exhaustion cannot fail fast (UNKNOWN is
            # not a refutation): buffer until close, then re-ladder over
            # the full history — identical to the offline path
            self._suspended = True
            self._carry = None
            self._rebases.append("suspended")
            return
        self._rung_i += 1
        self._rebases.append(
            "wovf" if wovf else "lossy")
        self._carry = None
        self._seed_carry()

    # -- one device segment -------------------------------------------------

    def _segment(self) -> None:
        cols, carry = self._cols, self._carry
        cap_eff, exp_eff = self._cap_eff, self._exp_eff
        win = self._rung[1]
        unroll = T._unroll_factor()
        fn = T._jit_segment(T._kernel_key(self.kernel), cap_eff, win,
                            exp_eff, unroll, stats=self._stats)
        shape_key = ("segment", T._kernel_key(self.kernel), cap_eff, win,
                     exp_eff, unroll, cols["f"].shape[0],
                     cols["cf"].shape[0], self._stats)
        phase = ("compile" if shape_key not in T._EXECUTED_SHAPES
                 else "execute")
        lvl0 = int(carry[8])
        try:
            with obs.span("stream.segment", phase=phase,
                          segment=self._seg_idx, level=lvl0,
                          rung=[cap_eff, win, exp_eff],
                          watermark=self._checked_wm) as sp:
                t0 = time.perf_counter()
                carry = R._call_segment(
                    fn, cols, carry, self._seg, device=self._fallback,
                    deadline_s=(None if self._fallback is not None
                                else self._deadline_s))
                seg_s = time.perf_counter() - t0
                sp.set(level_end=int(carry[8]))
        except R.WedgeError as e:
            dev = R.cpu_device()
            if self._fallback is not None or dev is None:
                raise _Verdict({"valid": UNKNOWN, "backend": "tpu",
                                "levels": lvl0,
                                "error": f"stream segment wedged: {e}"})
            R.note_runtime_wedge("stream", self._deadline_s or 0.0,
                                 level=lvl0)
            log.warning("stream %s: segment wedged at level %s; "
                        "resuming the checkpoint on the CPU fallback",
                        self.session.id, lvl0)
            self._fallback = dev
            return
        except Exception as e:  # noqa: BLE001 — classified below
            cls = R.classify_failure(e)
            if cls == R.OOM:
                self._ooms += 1
                new_cap = cap_eff // 2
                if new_cap < self._policy.min_capacity:
                    raise _Verdict({"valid": UNKNOWN, "backend": "tpu",
                                    "levels": lvl0,
                                    "error": f"OOM at the pool floor: "
                                             f"{e}"})
                self._carry, _ = R._shrink_carry(self._carry, new_cap)
                self._cap_eff = new_cap
                if isinstance(self._exp_eff, int):
                    self._exp_eff = max(1, min(self._exp_eff // 2,
                                               new_cap))
                time.sleep(self._policy.delay(self._ooms))
                return
            if cls in (R.TRANSIENT, R.DCN):
                self._transients += 1
                if self._transients > self._policy.max_retries:
                    raise
                time.sleep(self._policy.delay(self._transients))
                return
            raise
        self._carry = carry
        self._seg_idx += 1
        self._transients = 0
        T._EXECUTED_SHAPES.add(shape_key)
        T._note_call_phase("segment", phase, seg_s)
        lvl1 = int(carry[8])
        T._LEVELS_TOTAL.inc(lvl1 - lvl0)
        T._SEGMENTS_TOTAL.inc()
        if self._stats and len(carry) > 13:
            slog = np.asarray(carry[13])
            obs_searchstats.record(slog[:lvl1],
                                   rung=(cap_eff, win, exp_eff))
        self.session.note_progress(self._checked_wm, lvl1,
                                   self._checked_nr,
                                   footprint=self._footprint())
        _LAG.set(self.session.lag())
        cp = R.Checkpoint(carry=carry, rung=self._rung, window=win,
                          expand_eff=self._exp_eff, crash_width=self._crw,
                          segment=self._seg_idx,
                          watermark=self._checked_wm,
                          n_required=self._checked_nr)
        cp.save(self.checkpoint_path)

    def _footprint(self) -> int:
        if self._p is None:
            return 0
        try:
            from jepsen_tpu.checker import plan as plan_mod
            return int(plan_mod.request_footprint(
                plan_mod.PlanDims.from_packed(self._p)))
        except Exception:  # noqa: BLE001 — pricing is advisory
            return 0

    def _result(self, done: bool, lossy: bool, wovf: bool, best: int,
                levels: int, pool) -> Dict[str, Any]:
        out = T._result(done, lossy, wovf, best, levels, self._p,
                        pool=pool)
        out["rung"] = (self._cap_eff, self._rung[1], self._exp_eff)
        out["crash-width"] = self._crw
        out["segments"] = self._seg_idx
        out["segment-iters"] = self._seg
        return out

    # -- non-kernel fallback ------------------------------------------------

    def _run_offline(self) -> None:
        """Models without an online-checkable kernel (object models,
        remap kernels whose row identity changes at close): buffer until
        the stream seals, then run the standard offline check — the same
        ``linearizable`` + ``check_safe`` path the daemon uses, so the
        verdict cannot diverge from ``jtpu analyze``."""
        s = self.session
        while True:
            with s.cond:
                if s.state == "open" and not self._halt.is_set():
                    s.cond.wait(0.25)
                state = s.state
                ops = list(s.ops) if state != "open" else None
            if self._halt.is_set():
                return
            if ops is None:
                continue
            break
        from jepsen_tpu.checker import check_safe
        from jepsen_tpu.checker.wgl import linearizable
        h = History.of([Op.from_dict(d) for d in ops])
        checker = linearizable(self.model, backend=self.backend)
        raise _Verdict(check_safe(checker, {"name": f"stream-{s.id}"}, h))
