"""Bit-packed, columnar history encoding — the TPU device format.

The reference keeps histories as vectors of Clojure maps and hands them to
knossos, which searches over them with JVM objects (SURVEY §2.3). Here the
history is *compiled* once, host-side, into fixed-width integer columns that
ship to the device:

- per operation: f-code (int32), v1/v2 (interned value ids, int32),
  inv/ret (event indices, int32; RET_INF for crashed ops), process (int32)
- operations sorted by return index, so the WGL frontier rule "ops returning
  before the first unlinearized op are all linearized" becomes a prefix
  property and a configuration compresses to (prefix length k, window bitmask,
  model state) — one packed uint64 per configuration.

Pairing semantics mirror knossos.history/complete (reference
checker.clj:342): an ok completion's value back-fills the invocation (reads);
'fail' pairs are dropped (the op is known not to have happened); 'info' pairs
are pending forever (RET_INF) and may be linearized optionally.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from jepsen_tpu.history import History, Op
from jepsen_tpu.models.core import KernelSpec, NIL_ID, F_READ

#: Sentinel return index for operations that never returned (crashed 'info'
#: ops): effectively +infinity, still well inside int32.
RET_INF = np.int32(2**31 - 1)


@dataclass
class PackedHistory:
    """Columnar encoding of one (single-key) history, sorted by return index.

    n ops; n_required = number of ops that MUST be linearized (finite ret,
    i.e. 'ok' completions). Ops with ret == RET_INF are crashed ('info') ops
    that MAY be linearized. value_table maps interned ids back to Python
    values for counterexample reporting.
    """

    f: np.ndarray        # int32[n] f-codes
    v1: np.ndarray       # int32[n]
    v2: np.ndarray       # int32[n]
    inv: np.ndarray      # int32[n] invocation event index
    ret: np.ndarray      # int32[n] return event index or RET_INF
    process: np.ndarray  # int32[n]
    n_required: int
    init_state: int
    value_table: List[Any] = field(default_factory=list)
    ops: List[Tuple[Optional[Op], Optional[Op]]] = field(default_factory=list)

    @property
    def n(self) -> int:
        return int(self.f.shape[0])

    def max_concurrency(self) -> int:
        """Max number of ops pending at any event time — bounds the WGL
        window size the device search needs."""
        if self.n == 0:
            return 0
        events = []
        for i in range(self.n):
            events.append((int(self.inv[i]), 1))
            if int(self.ret[i]) != int(RET_INF):
                events.append((int(self.ret[i]), -1))
        events.sort()
        cur = peak = 0
        for _, d in events:
            cur += d
            peak = max(peak, cur)
        # crashed ops stay pending forever
        return peak

    def pad_to(self, n: int) -> "PackedHistory":
        """Right-pad columns to length n with never-linearizable filler ops
        (inv = RET_INF so they are never candidates)."""
        k = n - self.n
        if k < 0:
            raise ValueError(f"cannot pad {self.n} down to {n}")
        if k == 0:
            return self

        def pad(a, fill):
            return np.concatenate(
                [a, np.full(k, fill, dtype=a.dtype)])

        return PackedHistory(
            f=pad(self.f, 0),
            v1=pad(self.v1, NIL_ID),
            v2=pad(self.v2, NIL_ID),
            inv=pad(self.inv, RET_INF),
            ret=pad(self.ret, RET_INF),
            process=pad(self.process, -1),
            n_required=self.n_required,
            init_state=self.init_state,
            value_table=self.value_table,
            ops=self.ops,
        )


class _Interner:
    def __init__(self):
        self.table: Dict[Any, int] = {}
        self.values: List[Any] = []

    def id(self, v: Any) -> int:
        if v is None:
            return int(NIL_ID)
        key = v if isinstance(v, (int, str, bool, float, tuple)) else repr(v)
        i = self.table.get(key)
        if i is None:
            i = len(self.values)
            self.table[key] = i
            self.values.append(v)
        return i


def _op_values(f_code: int, f: Any, inv_value: Any, ok_value: Any,
               intern: _Interner) -> Tuple[int, int]:
    """Split an op's value into (v1, v2) interned ids.

    cas carries (old, new); reads use the *completion* value (knossos
    complete-fills reads); writes use the invocation value.
    pack_history inlines this split for ok rows; StreamPacker calls it,
    and tests/test_pack_columnar.py holds the two packers equal.
    """
    if f == "cas":
        v = inv_value
        if v is None:
            return int(NIL_ID), int(NIL_ID)
        old, new = v
        return intern.id(old), intern.id(new)
    if f_code == F_READ or f == "read":
        return intern.id(ok_value if ok_value is not None else inv_value), int(NIL_ID)
    return intern.id(inv_value), int(NIL_ID)


def pack_history(history: Sequence[Op], kernel: KernelSpec,
                 intern: Optional[_Interner] = None,
                 init_state: Optional[int] = None) -> PackedHistory:
    """Compile a raw single-key history into a PackedHistory.

    Steps: (1) walk events assigning event indices; (2) pair invocations with
    completions per process; (3) drop failed pairs and crashed reads (a
    crashed read constrains nothing); (4) intern values; (5) order ops by
    return index (RET_INF last, tie-broken by invocation index);
    (6) kernel remap (e.g. the queue kernel's value-slot interval
    coloring) and capacity validation — either may raise ValueError, on
    which the caller falls back to the generic object search.

    One walk appends the ok rows straight into int columns: an ok row's
    return index is its completion event, so arrival order IS return
    order and only the crashed section (RET_INF) needs a sort, by
    invocation index. The default (register) value split and the
    interner's int lookup are inlined — this walk is the whole host
    cost of packing a long history.
    """
    intern = intern or _Interner()
    f_codes = kernel.f_codes
    drop_crashed = kernel.drop_crashed
    encode_op = kernel.encode_op
    intern_id = intern.id
    table = intern.table
    nil = int(NIL_ID)
    if encode_op is not None:
        def encode(fc, f, inv_value, ok_value):
            return encode_op(fc, f, inv_value, ok_value, intern_id)
    else:
        def encode(fc, f, inv_value, ok_value):
            return _op_values(fc, f, inv_value, ok_value, intern)

    pending: Dict[Any, Tuple[int, Op]] = {}
    inv_c: List[int] = []
    ret_c: List[int] = []
    f_c: List[int] = []
    v1_c: List[int] = []
    v2_c: List[int] = []
    proc_raw: list = []
    ops: list = []
    crashed = []  # (inv_idx, f, v1, v2, process, inv_op, comp_op)

    for ev, o in enumerate(history):
        typ = o.type
        if typ == "invoke":
            pending[o.process] = (ev, o)
            continue
        entry = pending.pop(o.process, None)
        if entry is None or typ == "fail":
            continue  # unpaired, or known not to have happened
        inv_ev, inv_op = entry
        f = inv_op.f
        fc = f_codes.get(f)
        if fc is None:
            raise ValueError(
                f"op f={f!r} not supported by model {kernel.name!r} "
                f"(codes: {sorted(f_codes)})")
        inv_value = inv_op.value
        if typ == "info":
            if fc == F_READ or (drop_crashed is not None
                                and drop_crashed(fc, inv_value)):
                # crashed read — or a crashed op the reference
                # semantics can never linearize (e.g. a nil-value
                # dequeue) — constrains nothing
                continue
            v1, v2 = encode(fc, f, inv_value, None)
            crashed.append((inv_ev, fc, v1, v2, inv_op.process, inv_op, o))
            continue
        # ok: _op_values' default split, inlined with the int lookup
        if encode_op is not None:
            v1, v2 = encode(fc, f, inv_value, o.value)
        elif f == "cas":
            if inv_value is None:
                v1 = v2 = nil
            else:
                old, new = inv_value
                v1 = table.get(old) if type(old) is int else None
                if v1 is None:
                    v1 = intern_id(old)
                v2 = table.get(new) if type(new) is int else None
                if v2 is None:
                    v2 = intern_id(new)
        else:
            if fc == F_READ or f == "read":
                val = o.value
                if val is None:
                    val = inv_value
            else:
                val = inv_value
            v1 = table.get(val) if type(val) is int else None
            if v1 is None:
                v1 = intern_id(val)
            v2 = nil
        inv_c.append(inv_ev)
        ret_c.append(ev)
        f_c.append(fc)
        v1_c.append(v1)
        v2_c.append(v2)
        proc_raw.append(inv_op.process)
        ops.append((inv_op, o))
    # invocations with no completion at all == crashed (same as info)
    for inv_ev, inv_op in pending.values():
        fc = f_codes.get(inv_op.f)
        if fc is None or fc == F_READ or (
                drop_crashed is not None
                and drop_crashed(fc, inv_op.value)):
            continue
        v1, v2 = encode(fc, inv_op.f, inv_op.value, None)
        crashed.append((inv_ev, fc, v1, v2, inv_op.process, inv_op, None))

    n_required = len(inv_c)
    crashed.sort(key=lambda r: r[0])  # invocation indices are distinct
    for inv_ev, fc, v1, v2, prc, inv_op, comp in crashed:
        inv_c.append(inv_ev)
        f_c.append(fc)
        v1_c.append(v1)
        v2_c.append(v2)
        proc_raw.append(prc)
        ops.append((inv_op, comp))
    ret_c.extend([int(RET_INF)] * len(crashed))
    # dense process ids in row order: required rows, then crashed rows
    procs: Dict[Any, int] = {}
    proc_c = [procs.setdefault(p, len(procs)) for p in proc_raw]

    def col(c):
        return np.array(c, dtype=np.int32)

    packed = PackedHistory(
        f=col(f_c), v1=col(v1_c), v2=col(v2_c), inv=col(inv_c),
        ret=col(ret_c), process=col(proc_c),
        n_required=n_required,
        init_state=(kernel.init_state if init_state is None
                    else init_state),
        value_table=intern.values,
        ops=ops,
    )
    if kernel.remap is not None:
        kernel.remap(packed)     # raises ValueError when it cannot fit
    if kernel.validate is not None:
        kernel.validate(packed)  # raises ValueError on capacity violation
    return packed


def pack_with_init(history: Sequence[Op], model,
                   kernel: Optional[KernelSpec] = None
                   ) -> Optional[Tuple[PackedHistory, KernelSpec]]:
    """Pack a history with the initial state taken from a model *instance*
    (via the kernel's pack_init hook). Returns None when the model has no
    integer kernel; raises ValueError on unsupported op f's (caller falls
    back to the generic object search). Shared by the CPU (checker.wgl) and
    TPU (checker.tpu) backends so the init-state encoding cannot diverge.
    """
    from jepsen_tpu.models.core import kernel_spec_for
    kernel = kernel or kernel_spec_for(model)
    if kernel is None:
        return None
    intern = _Interner()
    init = (kernel.pack_init(model, intern.id)
            if kernel.pack_init is not None else kernel.init_state)
    packed = pack_history(history, kernel, intern, init_state=init)
    return packed, kernel


class StreamPacker:
    """Append-mode packer for streaming ingestion (doc/serve.md
    "Streaming API"): feed raw ops one chunk at a time and read back, at
    any barrier, the packed encoding of the current *stable prefix* —
    the longest event prefix in which every invoked op also completed.

    The stable prefix is what makes an online check sound: no op spans
    its boundary, so every required op of a longer stable prefix sorts
    strictly after every required op of a shorter one (old returns <
    watermark <= new invocations), and the packed columns of the longer
    prefix literally extend the shorter — the device search carry
    transfers across extension (checker.tpu._reopen_carry). The walk is
    pack_history's, one event at a time: fail pairs dropped, crashed
    reads (and kernel.drop_crashed ops) dropped, values interned at
    completion events, processes densely remapped in sorted-row order —
    so :meth:`close` yields arrays identical to a one-shot
    ``pack_history`` over the same op sequence.

    A crashed ('info') op pins the watermark forever: it stays pending
    in real time, so no later prefix is complete. Everything after the
    first crash is checked at close, where crashed ops become the
    crashed section exactly like the offline walk.
    """

    def __init__(self, kernel: KernelSpec,
                 init_state: Optional[int] = None,
                 intern: Optional[_Interner] = None):
        self.kernel = kernel
        self.intern = intern or _Interner()
        self.init_state = (kernel.init_state if init_state is None
                           else init_state)
        if kernel.encode_op is not None:
            self._encode = (lambda fc, f, iv, ov:
                            kernel.encode_op(fc, f, iv, ov,
                                             self.intern.id))
        else:
            self._encode = (lambda fc, f, iv, ov:
                            _op_values(fc, f, iv, ov, self.intern))
        self._ev = 0
        self._pending: Dict[Any, Tuple[int, Op]] = {}
        self._rows: list = []       # completed rows, (ret, inv)-sorted
        self._crashed: list = []    # info rows, info-event order
        self._procs: Dict[Any, int] = {}
        self._proc_col: List[int] = []
        self._watermark = 0         # stable-prefix event count
        self._watermark_rows = 0    # len(_rows) at the watermark
        self._forever_open = 0      # crashed ops pin the watermark
        self._closed = False
        self._final: Optional[PackedHistory] = None

    # -- intake -------------------------------------------------------------

    @property
    def n_events(self) -> int:
        return self._ev

    @property
    def watermark(self) -> int:
        """Event count of the stable prefix (monotone non-decreasing)."""
        return self._watermark

    @property
    def stable_required(self) -> int:
        """Required-op count of the stable prefix — what the online
        search's traced ``n_required`` scalar advances to."""
        return self._watermark_rows

    @property
    def online_ok(self) -> bool:
        """Whether the stable prefix may be checked online: a kernel
        with a global remap (e.g. the queue's value-slot interval
        coloring) re-colors on every extension, so its packing is only
        final at close."""
        return self.kernel.remap is None

    def feed(self, op: Op) -> None:
        """One event — the exact pack_history walk, incrementally."""
        if self._closed:
            raise ValueError("stream packer is closed")
        kernel = self.kernel
        ev = self._ev
        self._ev += 1
        if op.is_invoke:
            self._pending[op.process] = (ev, op)
        elif op.process in self._pending:
            inv_ev, inv_op = self._pending.pop(op.process)
            if op.is_fail:
                pass  # known not to have happened
            else:
                fc = kernel.f_codes.get(inv_op.f)
                if fc is None:
                    raise ValueError(
                        f"op f={inv_op.f!r} not supported by model "
                        f"{kernel.name!r} (codes: "
                        f"{sorted(kernel.f_codes)})")
                if op.is_info:
                    if fc == F_READ or (
                            kernel.drop_crashed is not None
                            and kernel.drop_crashed(fc, inv_op.value)):
                        pass  # constrains nothing — dropped
                    else:
                        v1, v2 = self._encode(fc, inv_op.f,
                                              inv_op.value, None)
                        self._crashed.append(
                            (inv_ev, int(RET_INF), fc, v1, v2,
                             inv_op.process, inv_op, op))
                        self._forever_open += 1
                else:  # ok — completions arrive in return-index order
                    v1, v2 = self._encode(fc, inv_op.f, inv_op.value,
                                          op.value)
                    self._rows.append((inv_ev, ev, fc, v1, v2,
                                       inv_op.process, inv_op, op))
                    prc = inv_op.process
                    if prc not in self._procs:
                        self._procs[prc] = len(self._procs)
                    self._proc_col.append(self._procs[prc])
        # the boundary after this event is stable iff no op spans it:
        # nothing pending, and no crashed op (pending forever) seen
        if not self._pending and not self._forever_open:
            self._watermark = self._ev
            self._watermark_rows = len(self._rows)

    def feed_ops(self, ops: Sequence[Any]) -> None:
        for o in ops:
            self.feed(o if isinstance(o, Op) else Op.from_dict(o))

    # -- read side ----------------------------------------------------------

    def stable_packed(self) -> PackedHistory:
        """The packed stable prefix: required ops only (zero crashed by
        construction), array-identical to ``pack_history`` over the
        watermark's event prefix. Raises ValueError for remap kernels —
        their packing is only final at close (see :attr:`online_ok`)."""
        if not self.online_ok:
            raise ValueError(
                f"kernel {self.kernel.name!r} remaps value slots "
                f"globally; the stable prefix cannot be packed online")
        k = self._watermark_rows
        rows = self._rows[:k]

        def col(i):
            return (np.asarray([r[i] for r in rows], np.int32)
                    if rows else np.zeros(0, np.int32))

        p = PackedHistory(
            f=col(2), v1=col(3), v2=col(4), inv=col(0), ret=col(1),
            process=(np.asarray(self._proc_col[:k], np.int32)
                     if rows else np.zeros(0, np.int32)),
            n_required=k, init_state=self.init_state,
            value_table=self.intern.values,
            ops=[(r[6], r[7]) for r in rows])
        if self.kernel.validate is not None:
            self.kernel.validate(p)  # ValueError -> online unsupported
        return p

    def close(self) -> PackedHistory:
        """Seal the stream. Dangling invocations become crashed ops,
        crashed rows merge in (ret, inv) order, and the kernel
        remap/validate hooks run — the result is identical to a
        one-shot ``pack_history`` over the full op sequence."""
        if self._final is not None:
            return self._final
        self._closed = True
        kernel = self.kernel
        for inv_ev, inv_op in self._pending.values():
            fc = kernel.f_codes.get(inv_op.f)
            if fc is None or fc == F_READ or (
                    kernel.drop_crashed is not None
                    and kernel.drop_crashed(fc, inv_op.value)):
                continue
            v1, v2 = self._encode(fc, inv_op.f, inv_op.value, None)
            self._crashed.append((inv_ev, int(RET_INF), fc, v1, v2,
                                  inv_op.process, inv_op, None))
        self._crashed.sort(key=lambda r: (r[1], r[0]))
        rows = self._rows + self._crashed
        proc_col = list(self._proc_col)
        for r in self._crashed:
            prc = r[5]
            if prc not in self._procs:
                self._procs[prc] = len(self._procs)
            proc_col.append(self._procs[prc])

        def col(i):
            return (np.asarray([r[i] for r in rows], np.int32)
                    if rows else np.zeros(0, np.int32))

        packed = PackedHistory(
            f=col(2), v1=col(3), v2=col(4), inv=col(0), ret=col(1),
            process=(np.asarray(proc_col, np.int32) if rows
                     else np.zeros(0, np.int32)),
            n_required=len(self._rows), init_state=self.init_state,
            value_table=self.intern.values,
            ops=[(r[6], r[7]) for r in rows])
        if kernel.remap is not None:
            kernel.remap(packed)
        if kernel.validate is not None:
            kernel.validate(packed)
        self._final = packed
        return packed


def pack_keyed_histories(keyed: Dict[Any, Sequence[Op]],
                         kernel: KernelSpec) -> Tuple[list, dict]:
    """Pack a {key: history} map (the independent-key axis, reference
    independent.clj:65-219) into a list of equal-length PackedHistories plus
    batched arrays ready for vmap/sharding.

    Returns (packed_list, batch) where batch is a dict of stacked np arrays:
    f, v1, v2, inv, ret: int32[K, n_max]; n_required: int32[K];
    init_state: int32[K].
    """
    keys = list(keyed.keys())
    packed = [pack_history(keyed[k], kernel) for k in keys]
    n_max = max((p.n for p in packed), default=0)
    padded = [p.pad_to(n_max) for p in packed]
    batch = {
        "f": np.stack([p.f for p in padded]) if padded else
        np.zeros((0, 0), np.int32),
        "v1": np.stack([p.v1 for p in padded]) if padded else
        np.zeros((0, 0), np.int32),
        "v2": np.stack([p.v2 for p in padded]) if padded else
        np.zeros((0, 0), np.int32),
        "inv": np.stack([p.inv for p in padded]) if padded else
        np.zeros((0, 0), np.int32),
        "ret": np.stack([p.ret for p in padded]) if padded else
        np.zeros((0, 0), np.int32),
        "n_required": np.asarray([p.n_required for p in padded], np.int32),
        "init_state": np.asarray([p.init_state for p in padded], np.int32),
        "keys": keys,
    }
    return packed, batch
