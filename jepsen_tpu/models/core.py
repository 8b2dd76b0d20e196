"""Stepped-datatype models.

A model is an immutable value with a ``step(op) -> model'`` function; stepping
with an operation the datatype cannot have performed yields an
:class:`Inconsistent` result. This is the knossos ``Model`` interface
(re-exported by the reference at jepsen/src/jepsen/model.clj:4,11 and
documented verbatim in doc/checker.md:43-56), with the reference's model zoo:
CASRegister (model.clj:21-35), Mutex (42-51), Set (58-66), UnorderedQueue
(73-80), FIFOQueue (87-100), NoOp (13-15).

TPU-first addition: models whose state fits in a machine word also carry a
:class:`KernelSpec` — a *branchless integer transition function*
``step(state, f, v1, v2) -> (state', ok)`` written against the numpy
operator surface so it runs identically under numpy, ``jax.numpy`` and
``jax.vmap``. The batched WGL checker (jepsen_tpu.checker.tpu) explores
thousands of model configurations per TPU vector lane through these kernels.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

from jepsen_tpu.history import Op

# ---------------------------------------------------------------------------
# Core protocol
# ---------------------------------------------------------------------------


class Model:
    """Immutable stepped model. Subclasses implement step()."""

    def step(self, op: Op) -> "Model":
        raise NotImplementedError

    def readonly_op(self, op: Op) -> bool:
        """True iff stepping ``op`` can never change the state, at ANY state
        where it succeeds (a register read, a cas(x,x), a set read). Such
        ops can be linearized greedily by the checkers (partial-order
        reduction); defaults to False (no reduction)."""
        return False

    def __eq__(self, other):
        return type(self) is type(other) and self.__dict__ == other.__dict__

    def __hash__(self):
        return hash((type(self), tuple(sorted(self.__dict__.items(),
                                              key=lambda kv: kv[0]))))


class Inconsistent(Model):
    """Terminal model state: the op sequence is not consistent with the
    datatype (knossos.model/inconsistent)."""

    def __init__(self, msg: str):
        self.msg = msg

    def step(self, op: Op) -> "Model":
        return self

    def __repr__(self):
        return f"Inconsistent({self.msg!r})"

    def __eq__(self, other):
        return isinstance(other, Inconsistent)

    def __hash__(self):
        return hash(Inconsistent)


def inconsistent(msg: str) -> Inconsistent:
    return Inconsistent(msg)


def is_inconsistent(m: Any) -> bool:
    return isinstance(m, Inconsistent)


class NoOp(Model):
    """A model which considers any operation valid (model.clj:13-15)."""

    def step(self, op: Op) -> Model:
        return self

    def readonly_op(self, op: Op) -> bool:
        return True

    def __repr__(self):
        return "NoOp"


class CASRegister(Model):
    """A register supporting read / write / cas (model.clj:21-35).

    - write v     -> value := v
    - cas (o, n)  -> if value == o then value := n else inconsistent
    - read v      -> consistent iff v is None (don't-care) or v == value
    """

    __slots__ = ("value",)

    def __init__(self, value: Any = None):
        self.value = value

    def step(self, op: Op) -> Model:
        f, v = op.f, op.value
        if f == "write":
            return CASRegister(v)
        if f == "cas":
            if v is None:
                return inconsistent("cas with nil value")
            old, new = v
            if self.value == old:
                return CASRegister(new)
            return inconsistent(f"can't CAS {self.value} from {old} to {new}")
        if f == "read":
            if v is None or v == self.value:
                return self
            return inconsistent(f"can't read {v} from register {self.value}")
        return inconsistent(f"unknown op f={f}")

    def readonly_op(self, op: Op) -> bool:
        if op.f == "read":
            return True
        if op.f == "cas" and op.value is not None:
            old, new = op.value
            return old == new
        return False

    def __eq__(self, other):
        return isinstance(other, CASRegister) and self.value == other.value

    def __hash__(self):
        return hash(("CASRegister", self.value))

    def __repr__(self):
        return f"CASRegister({self.value!r})"


#: Alias: a plain read/write register is a CASRegister that never sees cas.
Register = CASRegister


class Mutex(Model):
    """A single mutex (model.clj:42-51): acquire/release."""

    __slots__ = ("locked",)

    def __init__(self, locked: bool = False):
        self.locked = locked

    def step(self, op: Op) -> Model:
        if op.f == "acquire":
            if self.locked:
                return inconsistent("cannot acquire a locked mutex")
            return Mutex(True)
        if op.f == "release":
            if not self.locked:
                return inconsistent("cannot release a free mutex")
            return Mutex(False)
        return inconsistent(f"unknown op f={op.f}")

    def __eq__(self, other):
        return isinstance(other, Mutex) and self.locked == other.locked

    def __hash__(self):
        return hash(("Mutex", self.locked))

    def __repr__(self):
        return f"Mutex(locked={self.locked})"


class SetModel(Model):
    """A grow-only set with add / read (model.clj:58-66)."""

    __slots__ = ("items",)

    def __init__(self, items: frozenset = frozenset()):
        self.items = frozenset(items)

    def step(self, op: Op) -> Model:
        if op.f == "add":
            return SetModel(self.items | {op.value})
        if op.f == "read":
            if op.value is None or set(op.value) == set(self.items):
                return self
            return inconsistent(
                f"can't read {op.value} from set {sorted(self.items)}")
        return inconsistent(f"unknown op f={op.f}")

    def readonly_op(self, op: Op) -> bool:
        return op.f == "read"

    def __eq__(self, other):
        return isinstance(other, SetModel) and self.items == other.items

    def __hash__(self):
        return hash(("SetModel", self.items))

    def __repr__(self):
        return f"SetModel({sorted(self.items)!r})"


class UnorderedQueue(Model):
    """A queue which does not order its pending elements (model.clj:73-80):
    dequeue may return any enqueued-but-not-dequeued element."""

    __slots__ = ("pending",)

    def __init__(self, pending: Tuple = ()):
        # multiset as sorted tuple of (repr-key, value) is overkill; use tuple
        # with counting semantics.
        self.pending = tuple(pending)

    def step(self, op: Op) -> Model:
        if op.f == "enqueue":
            return UnorderedQueue(self.pending + (op.value,))
        if op.f == "dequeue":
            if op.value in self.pending:
                p = list(self.pending)
                p.remove(op.value)
                return UnorderedQueue(tuple(p))
            return inconsistent(f"can't dequeue {op.value}")
        return inconsistent(f"unknown op f={op.f}")

    def __eq__(self, other):
        return (isinstance(other, UnorderedQueue)
                and sorted(map(repr, self.pending))
                == sorted(map(repr, other.pending)))

    def __hash__(self):
        return hash(("UnorderedQueue", tuple(sorted(map(repr, self.pending)))))

    def __repr__(self):
        return f"UnorderedQueue({list(self.pending)!r})"


class FIFOQueue(Model):
    """A strictly-ordered queue (model.clj:87-100)."""

    __slots__ = ("queue",)

    def __init__(self, queue: Tuple = ()):
        self.queue = tuple(queue)

    def step(self, op: Op) -> Model:
        if op.f == "enqueue":
            return FIFOQueue(self.queue + (op.value,))
        if op.f == "dequeue":
            if not self.queue:
                return inconsistent("can't dequeue from empty queue")
            head, rest = self.queue[0], self.queue[1:]
            if head == op.value:
                return FIFOQueue(rest)
            return inconsistent(f"expected {head}, dequeued {op.value}")
        return inconsistent(f"unknown op f={op.f}")

    def __eq__(self, other):
        return isinstance(other, FIFOQueue) and self.queue == other.queue

    def __hash__(self):
        return hash(("FIFOQueue", self.queue))

    def __repr__(self):
        return f"FIFOQueue({list(self.queue)!r})"


# Constructor helpers matching the reference's lower-case factories.
def noop() -> NoOp:
    return NoOp()


def cas_register(value: Any = None) -> CASRegister:
    return CASRegister(value)


def register(value: Any = None) -> CASRegister:
    return CASRegister(value)


def mutex() -> Mutex:
    return Mutex()


def set_model() -> SetModel:
    return SetModel()


def unordered_queue() -> UnorderedQueue:
    return UnorderedQueue()


def fifo_queue() -> FIFOQueue:
    return FIFOQueue()


# ---------------------------------------------------------------------------
# Integer transition kernels (TPU surface)
# ---------------------------------------------------------------------------
#
# The batched linearizability checker encodes each op as (f, v1, v2) integer
# columns (see jepsen_tpu.ops.encode) and each model configuration as a single
# int32 state. A KernelSpec supplies the initial state and a branchless step
# function over those integers. ok is returned as a boolean array; state' is
# unspecified where ok is False (the caller discards those configurations).

# f-codes shared by encoder and kernels.
F_READ = 0
F_WRITE = 1
F_CAS = 2
F_ACQUIRE = 3
F_RELEASE = 4
F_ADD = 5
F_ENQUEUE = 6
F_DEQUEUE = 7

#: Interned id for None / "don't care" values.
NIL_ID = -1


@dataclass(frozen=True)
class KernelSpec:
    """Branchless integer semantics of a model.

    step(state, f, v1, v2) -> (state', ok). All arguments may be scalars or
    arrays (numpy or jax.numpy); only ufunc-style operations are used, so the
    same function runs on host for the CPU checker and under vmap/jit for the
    TPU checker.
    """

    name: str
    init_state: int
    step: Callable  # (state, f, v1, v2) -> (state', ok)
    f_codes: dict   # op.f -> int code
    #: Map a model *instance* to its packed initial state, given an interner
    #: fn (value -> id). None means init_state is instance-independent.
    pack_init: Optional[Callable] = None
    #: Kernel-specific op-value encoding:
    #: (f_code, f, inv_value, ok_value, intern_fn) -> (v1, v2). May raise
    #: ValueError when a value does not fit the word encoding (the caller
    #: then falls back to the generic object search). None = default
    #: interning (jepsen_tpu.ops.encode._op_values).
    encode_op: Optional[Callable] = None
    #: Post-pack whole-history validation: (PackedHistory) -> None, raising
    #: ValueError when the packed history violates a kernel capacity
    #: invariant (e.g. queue per-value counts exceeding the nibble width).
    validate: Optional[Callable] = None
    #: Post-pack id rewrite: (PackedHistory) -> None, mutating value-id
    #: columns to fit the kernel's state encoding (e.g. the queue kernel's
    #: value-symmetry slot coloring); raises ValueError when impossible
    #: (the caller falls back to the generic object search). Runs before
    #: validate.
    remap: Optional[Callable] = None
    #: Host predicate (f_code, v1, v2) -> bool: True iff the op's step can
    #: NEVER change the state at any state where it succeeds (register
    #: read, cas(x,x), set read). Drives the checkers' greedy pure-op
    #: closure (partial-order reduction); None disables the reduction.
    #: Must be a pure function of the triple: the packer asks it once
    #: per distinct (f_code, v1, v2) and reuses the answer for every op
    #: that carries the same triple.
    readonly: Optional[Callable] = None
    #: Human rendering of a packed state word for counterexample reports:
    #: (state, value_table) -> str. None falls back to the raw integer.
    describe_state: Optional[Callable] = None
    #: Host predicate (f_code, inv_value) -> bool: True iff a CRASHED op
    #: of this shape can never be linearized under the reference
    #: semantics and so constrains nothing — pack_history drops it
    #: (like crashed reads) instead of failing to encode it. Reference
    #: parity: knossos steps a crashed op with its *invocation* value
    #: (model.clj:87-100 FIFOQueue compares `value` against the head,
    #: model.clj:73-80 UnorderedQueue tests membership), so a nil-value
    #: crashed dequeue — disque/rabbitmq drains, disque.clj:305-310 —
    #: always steps to inconsistent and is never taken by any engine.
    drop_crashed: Optional[Callable] = None


def _cas_register_step(state, f, v1, v2):
    is_read = f == F_READ
    is_write = f == F_WRITE
    is_cas = f == F_CAS
    read_ok = (v1 == NIL_ID) | (state == v1)
    cas_ok = state == v1
    ok = (is_read & read_ok) | is_write | (is_cas & cas_ok)
    # next state: write -> v1; cas-ok -> v2; else unchanged
    state1 = state * (1 - is_write) + v1 * is_write
    take_cas = is_cas & cas_ok
    state2 = state1 * (1 - take_cas) + v2 * take_cas
    return state2, ok


def _mutex_step(state, f, v1, v2):
    is_acq = f == F_ACQUIRE
    is_rel = f == F_RELEASE
    ok = (is_acq & (state == 0)) | (is_rel & (state == 1))
    state1 = state * (1 - is_acq) + is_acq  # acquire -> 1
    state2 = state1 * (1 - is_rel)          # release -> 0
    return state2, ok


def _noop_step(state, f, v1, v2):
    # state must broadcast to the op grid's shape like every other
    # kernel's (the search sorts state next to per-candidate columns;
    # found by the plan verifier's eval_shape matrix — PLAN-TRACE)
    return state + f * 0, (f == f)


# --- grow-only set: state = presence bitmask over <= 31 interned ids -------
#
# add's v1 is the element's bit POSITION; read's v1 is the whole read set as
# a full target WORD (or NIL_ID for a don't-care read), so consistency is
# one integer compare. _set_remap compresses elements into the word by
# READ-SIGNATURE CLASSES: elements contained in exactly the same reads
# are interchangeable, so a class needs only a COUNT field (how many of
# its members are in the set), and a read's exact-set constraint becomes
# state == target where target holds each class's full count iff the
# class is inside the read. Hundreds of unique added elements with a
# handful of reads (the realistic sets workload, e.g. cockroach
# sets.clj) collapse to a few count fields. Elements added more than
# once (or both initial and re-added) are idempotent and get individual
# OR-bits instead (v2 flags the mode per add op).

SET_MAX_IDS = 31          # state bits 0..30: the word stays positive
SET_IMPOSSIBLE_BIT = 30   # reserved: reads of never-added elements


def _set_step(state, f, v1, v2):
    is_add = f == F_ADD
    is_read = f == F_READ
    read_ok = (v1 == NIL_ID) | (state == v1)
    ok = is_add | (is_read & read_ok)
    # add rows carry a UNIT word in v1 (a class-count increment or an
    # idempotent bit); v2 == 1 selects count mode (+), else OR mode
    unit = v1 * is_add * (v1 >= 0)
    plus = is_add & (v2 == 1)
    state2 = (state + unit) * plus + (state | unit) * (1 - plus)
    return state2, ok


def _set_encode(f_code, f, inv_value, ok_value, intern):
    if f_code == F_ADD:
        if inv_value is None:
            raise ValueError("set kernel: nil add value")
        # unbounded interning; _set_remap builds the word layout
        return intern(inv_value), NIL_ID
    # read: completion value (the observed set) wins; intern the whole
    # OBSERVED SET as one table entry for the remap to compile
    val = ok_value if ok_value is not None else inv_value
    if val is None:
        return NIL_ID, NIL_ID
    return intern(tuple(sorted(map(repr, val)))), NIL_ID


def _set_pack_init(model, intern):
    # provisional bitmask over init-element ids (interned first, so ids
    # are 0..k-1); _set_remap re-keys it into the field layout
    m = 0
    for i, e in enumerate(sorted(model.items, key=repr)):
        if intern(e) >= SET_MAX_IDS:
            raise ValueError(
                f"set kernel: more than {SET_MAX_IDS} initial elements")
        m |= 1 << i
    return m


def _set_remap(packed):
    """Compile element ids into the read-signature-class word layout.

    Soundness: two elements whose membership agrees on EVERY observed
    read are interchangeable — no constraint in the history can tell
    them apart — so only the count of a class's added members matters,
    and since every add op (and init member) contributes exactly once
    (duplicate-added elements are exiled to idempotent OR-bits), a count
    field of width ceil(log2(|class|+1)) can never overflow. A read
    containing an element that is never added (and not initial) can
    never be satisfied: its target carries the reserved impossible bit
    no add can set. Raises ValueError when the layout exceeds the 31-bit
    word (the caller falls back to the object search)."""
    from collections import defaultdict

    def key(v):
        return v if isinstance(v, (int, str, bool, float, tuple)) else \
            repr(v)

    init = int(packed.init_state)
    table = packed.value_table
    # element-id universe: init members (ids 0..k-1) + add-row ids
    add_rows = defaultdict(list)      # elem id -> row indices
    read_rows = []                    # (row, set-of-element-keys)
    for j in range(packed.n):
        v = int(packed.v1[j])
        if v < 0:
            continue
        if int(packed.f[j]) == F_ADD:
            add_rows[v].append(j)
        else:
            obs = table[v]            # tuple of sorted reprs
            read_rows.append((j, frozenset(obs)))
    init_ids = [i for i in range(SET_MAX_IDS) if (init >> i) & 1]
    elems = sorted(set(add_rows) | set(init_ids))
    # signature: which reads contain the element (membership by repr,
    # matching the read-set encoding above)
    sig = {}
    for e in elems:
        ek = repr(table[e]) if e < len(table) else repr(e)
        sig[e] = frozenset(j for j, obs in read_rows if ek in obs)
    # OR-tier: idempotent re-adds (multiple add ops, or init + add)
    or_tier = [e for e in elems
               if len(add_rows.get(e, ())) + (e in init_ids) > 1]
    count_classes = defaultdict(list)
    for e in elems:
        if e in or_tier:
            continue
        count_classes[sig[e]].append(e)
    # layout: count fields first, then OR bits; bit 30 reserved
    layout = {}                       # elem id -> (offset, width, mode)
    fields = []                       # (offset, mask, label, members)
    off = 0
    class_off = {}
    for s, members in sorted(count_classes.items(),
                             key=lambda kv: sorted(kv[1])):
        width = max(1, (len(members)).bit_length())
        class_off[s] = (off, width)
        for e in members:
            layout[e] = (off, width, 1)
        fields.append((off, (1 << width) - 1,
                       "|".join(str(table[e]) if e < len(table) else
                                str(e) for e in sorted(members))))
        off += width
    for e in or_tier:
        layout[e] = (off, 1, 0)
        fields.append((off, 1, str(table[e]) if e < len(table)
                       else str(e)))
        off += 1
    if off > SET_IMPOSSIBLE_BIT:
        raise ValueError(
            f"set kernel: field layout needs {off} bits > "
            f"{SET_IMPOSSIBLE_BIT} available")
    # rewrite add rows: v1 = unit word, v2 = mode
    for e, rows in add_rows.items():
        o, w, mode = layout[e]
        for j in rows:
            packed.v1[j] = 1 << o
            packed.v2[j] = mode
    # rewrite read rows: v1 = exact target word
    elem_by_key = {}
    for e in elems:
        elem_by_key[repr(table[e]) if e < len(table) else repr(e)] = e
    for j, obs in read_rows:
        target = 0
        impossible = False
        seen_classes = set()
        for ek in obs:
            e = elem_by_key.get(ek)
            if e is None:
                impossible = True     # read of a never-added element
                continue
            o, w, mode = layout[e]
            if mode == 1:
                seen_classes.add((o, w))
            else:
                target |= 1 << o
        for (o, w) in seen_classes:
            members = [x for x, (xo, xw, xm) in layout.items()
                       if xo == o and xm == 1]
            target |= len(members) << o
        if impossible:
            target |= 1 << SET_IMPOSSIBLE_BIT
        packed.v1[j] = target
    # rebuild init state in the field layout
    new_init = 0
    for e in init_ids:
        o, w, mode = layout[e]
        if mode == 1:
            new_init += 1 << o
        else:
            new_init |= 1 << o
    packed.init_state = new_init
    packed.value_table = fields


# --- unordered queue: state = packed per-value pending counts --------------
#
# 8 interned values x 4-bit counts. Enqueue increments a nibble, dequeue
# decrements it when positive. Capacity invariants (<= 8 distinct values,
# <= 15 simultaneous pending of one value) are enforced by _uqueue_encode /
# _uqueue_validate; violations raise ValueError, and the caller falls back
# to the generic object search.

UQUEUE_MAX_IDS = 8
UQUEUE_MAX_COUNT = 15


def _uqueue_step(state, f, v1, v2):
    """v1 = the op's value-field BIT OFFSET (pre-scaled by _uqueue_remap),
    v2 = the field's count mask ((1<<width)-1). The remap guarantees the
    field count can never exceed the mask along any search path, so the
    increment/decrement arithmetic cannot corrupt neighboring fields."""
    is_enq = f == F_ENQUEUE
    is_deq = f == F_DEQUEUE
    sh = v1 * (v1 >= 0)
    unit = (state * 0 + 1) << sh
    cnt = (state >> sh) & v2
    deq_ok = is_deq & (v1 >= 0) & (cnt > 0)
    ok = is_enq | deq_ok
    # v2 == 0 marks a SINK enqueue (its value is never dequeued, so its
    # count is never read): succeeds, changes nothing
    state2 = state + unit * (is_enq & (v2 > 0)) - unit * deq_ok
    return state2, ok


def _uqueue_encode(f_code, f, inv_value, ok_value, intern):
    val = (ok_value if (f_code == F_DEQUEUE and ok_value is not None)
           else inv_value)
    if val is None:
        # e.g. a crashed dequeue whose removed element is unknowable —
        # the word encoding cannot express "some element"
        raise ValueError("queue kernel: nil op value")
    # unbounded interning here; _uqueue_remap interval-colors the ids
    # onto the UQUEUE_MAX_IDS nibble slots afterwards
    return intern(val), NIL_ID


def _uqueue_pack_init(model, intern):
    s = 0
    for v in model.pending:
        if v is None:
            raise ValueError("queue kernel: nil pending value")
        i = intern(v)
        if i >= UQUEUE_MAX_IDS:
            raise ValueError(
                f"queue kernel: more than {UQUEUE_MAX_IDS} distinct values")
        if ((s >> (4 * i)) & 15) >= UQUEUE_MAX_COUNT:
            raise ValueError("queue kernel: initial pending count overflow")
        s += 1 << (4 * i)
    return s


#: Usable state bits (the int32 sign bit is left clear by construction).
UQUEUE_STATE_BITS = 31


def _uqueue_remap(packed):
    """Value-symmetry bit-field packing, so realistic queue workloads —
    hundreds of unique enqueued values (reference disque.clj:305-310,
    rabbitmq.clj:148-181) — fit one int32 state word.

    Two facts make this possible:

    * **interval sharing** — two values whose *event spans* are disjoint
      can never be pending simultaneously: every op of the earlier value
      returns before any op of the later invokes, so real-time order
      forces all of the earlier value's ops first in any witness (and in
      any WGL search path: the frontier cannot pass the earlier value's
      dequeue unlinearized before the later value's ops become
      candidates). Such values may share a count field. A value's span
      runs from its first event to its last return — extended to
      infinity if any of its ops crashed or it can remain pending.
    * **adaptive field width** — a value enqueued at most once needs a
      1-bit count; <=3 simultaneous pendings 2 bits; <=15 4 bits. The
      dominant unique-value workload therefore fits ~31 simultaneously
      live values, not 8.

    Greedy interval coloring (optimal for interval graphs) builds field
    slots per width class; fields get bit offsets; ops are rewritten to
    (v1 = field offset, v2 = count mask) for _uqueue_step. Overflow of
    any bound (width > 4 bits, total bits > UQUEUE_STATE_BITS) raises
    ValueError and the caller falls back to the object search.

    Mutates packed.v1/v2, packed.init_state (counts re-keyed by field)
    and packed.value_table (per-field (offset, mask, label) triples for
    describe_state)."""
    from jepsen_tpu.ops.encode import RET_INF as _INF
    inf = int(_INF)
    init = int(packed.init_state)
    # span + counts per original interned id; init-pending ids (interned
    # first, ids 0..k, 4-bit counts from _uqueue_pack_init) span from
    # before the history (start -1)
    info = {}  # id -> [start, end, bound(init+enq), deq]
    for i in range(UQUEUE_MAX_IDS):
        c = (init >> (4 * i)) & 15
        if c:
            info[i] = [-1, -1, c, 0]
    for j in range(packed.n):
        v = int(packed.v1[j])
        if v < 0:
            continue
        inv_e, ret_e = int(packed.inv[j]), int(packed.ret[j])
        rec = info.setdefault(v, [inv_e, -1, 0, 0])
        rec[0] = min(rec[0], inv_e)
        rec[1] = max(rec[1], ret_e)
        if int(packed.f[j]) == F_ENQUEUE:
            rec[2] += 1
        else:
            rec[3] += 1
    classes = {1: [], 2: [], 4: []}
    sinks = set()
    for v, rec in sorted(info.items(), key=lambda kv: kv[1][0]):
        if rec[3] == 0:
            # never dequeued: no op ever reads this value's count, so its
            # enqueues are no-ops (sink encoding v1=0/v2=0) and it needs
            # no field at all — the undrained tail of a queue history
            # costs nothing
            sinks.add(v)
            continue
        if rec[2] > rec[3]:
            rec[1] = inf  # can stay pending forever: field never freed
        b = rec[2]
        if b > UQUEUE_MAX_COUNT:
            raise ValueError(
                f"queue kernel: more than {UQUEUE_MAX_COUNT} simultaneous "
                f"pendings of one value would overflow the count field")
        classes[1 if b <= 1 else 2 if b <= 3 else 4].append((v, rec))
    field_slot = {}       # id -> (width, slot_index_within_class)
    n_slots = {}
    labels = {}           # (width, slot) -> [labels]
    for w, vals in classes.items():
        free_at = []      # per slot: last event index occupying it
        for v, rec in vals:           # already span-start sorted
            for s, fa in enumerate(free_at):
                if fa < rec[0]:
                    free_at[s] = rec[1]
                    break
            else:
                s = len(free_at)
                free_at.append(rec[1])
            field_slot[v] = (w, s)
            val = (packed.value_table[v]
                   if 0 <= v < len(packed.value_table) else v)
            labels.setdefault((w, s), []).append(repr(val))
        n_slots[w] = len(free_at)
    if sum(w * n for w, n in n_slots.items()) > UQUEUE_STATE_BITS:
        raise ValueError(
            f"queue kernel: {sum(n_slots.values())} simultaneously-live "
            f"values need more than {UQUEUE_STATE_BITS} state bits")
    # bit offsets: width classes laid out contiguously
    base = {}
    off = 0
    for w in (1, 2, 4):
        base[w] = off
        off += w * n_slots[w]
    field_of = {v: (base[w] + w * s, (1 << w) - 1)
                for v, (w, s) in field_slot.items()}
    for j in range(packed.n):
        v = int(packed.v1[j])
        if v >= 0:
            o, m = field_of.get(v, (0, 0))    # sinks: v1=0, v2=0
            packed.v1[j] = o
            packed.v2[j] = m
    new_init = 0
    for i in range(UQUEUE_MAX_IDS):
        c = (init >> (4 * i)) & 15
        if c and i not in sinks:
            new_init += c << field_of[i][0]
    packed.init_state = new_init
    packed.value_table = [
        (base[w] + w * s, (1 << w) - 1, "|".join(ls))
        for (w, s), ls in sorted(labels.items())]



def _register_describe(state, values):
    if state == NIL_ID:
        return "nil"
    return repr(values[state]) if 0 <= state < len(values) else str(state)


def _mutex_describe(state, values):
    return "locked" if state else "free"


def _set_describe(state, values):
    # after _set_remap, value_table holds (offset, mask, label) fields
    parts = []
    for entry in values:
        if not (isinstance(entry, tuple) and len(entry) == 3):
            return f"state={int(state):#x}"
        off, mask, label = entry
        c = (int(state) >> off) & mask
        if c:
            full = bin(mask).count("1") == 1 or c == mask
            parts.append(f"{label}" if mask == 1
                         else f"{label}:{c}/{mask}")
    return "{" + ", ".join(parts) + "}"


def _uqueue_describe(state, values):
    # after _uqueue_remap, value_table holds (offset, mask, label) fields
    parts = []
    for entry in values:
        if not (isinstance(entry, tuple) and len(entry) == 3):
            return f"state={state:#x}"
        off, mask, label = entry
        c = (int(state) >> off) & mask
        if c:
            parts.append(f"{label}x{c}" if c > 1 else str(label))
    return "pending{" + ", ".join(parts) + "}"


# --- FIFO queue: state = a 7-slot x 4-bit ring word -----------------------
#
# The strictly-ordered queue (model.clj:87-100) needs an ORDERED state, so
# the word is a ring of 4-bit value ids filled from the bottom: nibble 0 is
# the head, enqueue writes id at the first empty nibble, dequeue succeeds
# only when nibble 0 equals the op's id and shifts the whole word down.
# id 0 marks an empty slot, so live ids are 1..15; 7 slots keep the word in
# 28 bits (the int32 sign bit stays clear, so >> is safe). Interval id
# coloring (_fifo_remap) reuses ids across values with disjoint event
# spans, and the maximum span overlap bounds queue depth along ANY search
# path (a pending value's span contains the frontier's return instant), so
# histories validated to depth <= 7 can never overflow the ring.

FIFO_SLOTS = 7
FIFO_MAX_IDS = 15


def _fifo_step(state, f, v1, v2):
    is_enq = f == F_ENQUEUE
    is_deq = f == F_DEQUEUE
    # per-nibble occupancy flags at bits 0,4,8,...: nibble nonzero
    occ = (state | (state >> 1) | (state >> 2) | (state >> 3))
    length = state * 0
    for i in range(FIFO_SLOTS):
        length = length + ((occ >> (4 * i)) & 1)
    enq_ok = is_enq & (length < FIFO_SLOTS)
    deq_ok = is_deq & (v1 > 0) & ((state & 15) == v1)
    ok = enq_ok | deq_ok
    # modulo keeps the shift < 28 even on full-ring rows (where enq_ok
    # already masks the bogus result) so int32 never overflows
    state_enq = state | (v1 << (4 * (length % FIFO_SLOTS) * is_enq))
    state2 = (state_enq * enq_ok
              + (state >> 4) * deq_ok
              + state * (1 - enq_ok - deq_ok))
    return state2, ok


def _fifo_encode(f_code, f, inv_value, ok_value, intern):
    val = (ok_value if (f_code == F_DEQUEUE and ok_value is not None)
           else inv_value)
    if val is None:
        raise ValueError("fifo kernel: nil op value")
    # unbounded interning; _fifo_remap interval-colors ids afterwards
    return intern(val), NIL_ID


def _fifo_pack_init(model, intern):
    s = 0
    if len(model.queue) > FIFO_SLOTS:
        raise ValueError(
            f"fifo kernel: more than {FIFO_SLOTS} initial elements")
    for i, v in enumerate(model.queue):
        if v is None:
            raise ValueError("fifo kernel: nil initial value")
        s |= (intern(v) + 1) << (4 * i)   # provisional; remap re-keys
    return s


def _fifo_remap(packed):
    """Interval id coloring + depth validation for the FIFO ring.

    Same span machinery as _uqueue_remap: a value is pending only while
    the frontier's return instant lies inside its event span, so (a) two
    values with disjoint spans may share a 4-bit id without a dequeue
    ever matching the wrong value, and (b) the maximum number of
    pairwise-overlapping spans bounds ring depth on every search path.
    Raises ValueError (object-search fallback) when more than
    FIFO_MAX_IDS values are simultaneously live or depth can exceed
    FIFO_SLOTS. No sink rule: a never-dequeued value still occupies ring
    order (it can block later dequeues), unlike the unordered queue."""
    from jepsen_tpu.ops.encode import RET_INF as _INF
    inf = int(_INF)
    init = int(packed.init_state)
    info = {}   # id -> [start, end, enq, deq]
    init_ids = []
    for i in range(FIFO_SLOTS):
        nib = (init >> (4 * i)) & 15
        if nib:
            init_ids.append(nib - 1)        # provisional id from pack_init
            rec = info.setdefault(nib - 1, [-1, -1, 0, 0])
            rec[2] += 1                     # each instance occupies a slot
    for j in range(packed.n):
        v = int(packed.v1[j])
        if v < 0:
            continue
        inv_e, ret_e = int(packed.inv[j]), int(packed.ret[j])
        rec = info.setdefault(v, [inv_e, -1, 0, 0])
        rec[0] = min(rec[0], inv_e)
        rec[1] = max(rec[1], ret_e)
        if int(packed.f[j]) == F_ENQUEUE:
            rec[2] += 1
        else:
            rec[3] += 1
    events = []
    for v, rec in info.items():
        if rec[2] > rec[3]:
            rec[1] = inf                    # may stay pending forever
        # depth-overlap events: each pending INSTANCE of the value
        # contributes, bounded by its enqueue count (+1 if in init)
        events.append((rec[0], rec[2]))
        if rec[1] != inf:
            events.append((rec[1], -rec[2]))
    depth = cur = 0
    for _, d in sorted(events):
        cur += d
        depth = max(depth, cur)
    if depth > FIFO_SLOTS:
        raise ValueError(
            f"fifo kernel: queue depth can reach {depth} > {FIFO_SLOTS} "
            f"ring slots")
    id_of = {}
    free_at = [-2] * FIFO_MAX_IDS
    labels = {}
    for v, rec in sorted(info.items(), key=lambda kv: kv[1][0]):
        for s in range(FIFO_MAX_IDS):
            if free_at[s] < rec[0]:
                id_of[v] = s + 1            # ids are 1-based; 0 = empty
                free_at[s] = rec[1]
                val = (packed.value_table[v]
                       if 0 <= v < len(packed.value_table) else v)
                labels.setdefault(s + 1, []).append(repr(val))
                break
        else:
            raise ValueError(
                f"fifo kernel: more than {FIFO_MAX_IDS} simultaneously-"
                f"live values")
    for j in range(packed.n):
        v = int(packed.v1[j])
        if v >= 0:
            packed.v1[j] = id_of[v]
    new_init = 0
    for i in range(FIFO_SLOTS):
        nib = (init >> (4 * i)) & 15
        if nib:
            new_init |= id_of[nib - 1] << (4 * i)
    packed.init_state = new_init
    packed.value_table = [
        "|".join(labels.get(i, [])) for i in range(FIFO_MAX_IDS + 1)]


def _fifo_describe(state, values):
    parts = []
    s = int(state)
    for i in range(FIFO_SLOTS):
        nib = (s >> (4 * i)) & 15
        if not nib:
            break
        label = (values[nib] if nib < len(values) and values[nib]
                 else f"id{nib}")
        parts.append(str(label))
    return "queue[" + ", ".join(parts) + "]"


CAS_REGISTER_KERNEL = KernelSpec(
    name="cas-register",
    init_state=NIL_ID,
    step=_cas_register_step,
    f_codes={"read": F_READ, "write": F_WRITE, "cas": F_CAS},
    pack_init=lambda m, intern: (NIL_ID if m.value is None
                                 else intern(m.value)),
    readonly=lambda f, v1, v2: (f == F_READ
                                or (f == F_CAS and v1 == v2)),
    describe_state=_register_describe,
)

MUTEX_KERNEL = KernelSpec(
    name="mutex",
    init_state=0,
    step=_mutex_step,
    f_codes={"acquire": F_ACQUIRE, "release": F_RELEASE},
    pack_init=lambda m, intern: int(m.locked),
    describe_state=_mutex_describe,
)

NOOP_KERNEL = KernelSpec(
    name="noop",
    init_state=0,
    step=_noop_step,
    f_codes={},
    readonly=lambda f, v1, v2: True,
)

SET_KERNEL = KernelSpec(
    name="set",
    init_state=0,
    step=_set_step,
    f_codes={"add": F_ADD, "read": F_READ},
    pack_init=_set_pack_init,
    encode_op=_set_encode,
    remap=_set_remap,
    readonly=lambda f, v1, v2: f == F_READ,
    describe_state=_set_describe,
)

UNORDERED_QUEUE_KERNEL = KernelSpec(
    name="unordered-queue",
    init_state=0,
    step=_uqueue_step,
    f_codes={"enqueue": F_ENQUEUE, "dequeue": F_DEQUEUE},
    pack_init=_uqueue_pack_init,
    encode_op=_uqueue_encode,
    remap=_uqueue_remap,
    # sink enqueues (v2==0: value never dequeued) succeed and change
    # nothing at any state — safely absorbed by the pure-op closure
    readonly=lambda f, v1, v2: f == F_ENQUEUE and v2 == 0,
    describe_state=_uqueue_describe,
    drop_crashed=lambda fc, inv_value: (fc == F_DEQUEUE
                                        and inv_value is None),
)


FIFO_QUEUE_KERNEL = KernelSpec(
    name="fifo-queue",
    init_state=0,
    step=_fifo_step,
    f_codes={"enqueue": F_ENQUEUE, "dequeue": F_DEQUEUE},
    pack_init=_fifo_pack_init,
    encode_op=_fifo_encode,
    remap=_fifo_remap,
    describe_state=_fifo_describe,
    drop_crashed=lambda fc, inv_value: (fc == F_DEQUEUE
                                        and inv_value is None),
)


def kernel_spec_for(model: Model) -> Optional[KernelSpec]:
    """Return the integer KernelSpec for a model instance, or None if the
    model's state does not fit the single-word encoding. Every reference
    model family (model.clj) now has a device kernel; histories whose
    shape exceeds a kernel's capacity (e.g. FIFO depth > 7) still fall
    back per history via remap/validate ValueErrors."""
    if isinstance(model, CASRegister):
        return CAS_REGISTER_KERNEL
    if isinstance(model, Mutex):
        return MUTEX_KERNEL
    if isinstance(model, NoOp):
        return NOOP_KERNEL
    if isinstance(model, SetModel):
        return SET_KERNEL
    if isinstance(model, UnorderedQueue):
        return UNORDERED_QUEUE_KERNEL
    if isinstance(model, FIFOQueue):
        return FIFO_QUEUE_KERNEL
    return None
