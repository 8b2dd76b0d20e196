"""Plain reference: is a CAS-register history linearizable?

A straightforward just-in-time linearization search (the "linear"
algorithm of Lowe, "Testing for linearizability", 2017, as knossos runs
it), written from the definitions and importing nothing of the system
under test.

Semantics (knossos ``cas-register``, model.clj:21-35, and Jepsen's
history conventions):

* the register starts at ``None``;
* ``write v`` sets it to ``v``; ``cas (old, new)`` needs it to hold
  ``old`` and sets it to ``new``; ``read v`` needs it to hold ``v``,
  and a read of ``None`` constrains nothing;
* an operation completed ``fail`` did not happen and is dropped; one
  completed ``info`` (or never completed) is indeterminate: it may take
  effect once at any point after its invocation, or never. Indeterminate
  reads change nothing and are dropped.

A history is a sequence of ``(type, process, f, value)`` tuples with
``type`` in ``invoke``/``ok``/``fail``/``info``.

The search keeps every configuration ``(value, linearized-pending set,
taken-indeterminate set)`` reachable at the current point of the
history. When an operation returns, each configuration is extended by
linearizing pending operations, in any order, until that one has taken
effect; configurations where it cannot are dropped. Linearizing another
pending operation after it is never needed, since that operation stays
pending. Of two configurations that differ only in which indeterminate
operations they have taken, the one with the subset can do everything
the other can, so the superset is pruned.

``slack`` exists only for the control (``tests/control_readings.py``):
every return is moved that many events later, which lets operations
take effect after they returned. That relaxes linearizability's
real-time order and is the guarantee the control breaks.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

#: Events by which the control moves every return later (``check(h,
#: CONTROL_SLACK)``): enough to let a read see a value some four
#: operations stale.
CONTROL_SLACK = 8

_WRITE, _CAS, _READ = 0, 1, 2
_CODES = {"write": _WRITE, "cas": _CAS, "read": _READ}


def _prepare(history: Sequence[tuple]) -> Tuple[list, list, list]:
    """Pair invocations with completions. Returns the operations that
    take part as ``(code, a, b)``, whether each is indeterminate, and
    their events ``(position, is_return, op_index)``; an indeterminate
    operation has no return event."""
    pending = {}
    outcome = {}
    invokes = []
    for pos, (typ, proc, f, value) in enumerate(history):
        if typ == "invoke":
            if proc in pending:
                raise ValueError(f"process {proc!r} invoked twice at {pos}")
            pending[proc] = pos
            invokes.append(pos)
        else:
            if proc not in pending:
                raise ValueError(f"completion without invocation at {pos}")
            outcome[pending.pop(proc)] = (typ, pos, value)
    ops, crashed, events = [], [], []
    index = {}
    for pos in invokes:
        typ, ret_pos, ret_value = outcome.get(pos, ("info", None, None))
        _, _, f, value = history[pos]
        code = _CODES[f]
        if typ == "fail" or (typ == "info" and code == _READ):
            continue
        if code == _WRITE:
            a, b = value, None
        elif code == _CAS:
            a, b = value
        else:
            a, b = ret_value, None
        index[pos] = len(ops)
        ops.append((code, a, b))
        crashed.append(typ == "info")
        events.append((pos, 0, index[pos]))
        if typ == "ok":
            events.append((ret_pos, 1, index[pos]))
    return ops, crashed, events


def _step(op: tuple, value):
    """The register after ``op`` at ``value``, or a sentinel when the
    operation cannot take effect there."""
    code, a, b = op
    if code == _WRITE:
        return a
    if code == _CAS:
        return b if value == a else _ILLEGAL
    return value if (a is None or a == value) else _ILLEGAL


_ILLEGAL = object()


def _read_only(op: tuple) -> bool:
    code, a, b = op
    return code == _READ or (code == _CAS and a == b)


def _add(table: dict, value, okmask: int, cmask: int) -> bool:
    """Insert a configuration unless one with the same value and
    linearized set and a subset of taken indeterminate ops is there;
    returns whether it went in."""
    key = (value, okmask)
    have = table.get(key)
    if have is None:
        table[key] = [cmask]
        return True
    for c in have:
        if c & ~cmask == 0:
            return False
    have[:] = [c for c in have if cmask & ~c != 0]
    have.append(cmask)
    return True


def _absorb(value, okmask: int, ops: list, reading: list) -> int:
    """``okmask`` with every pending read-only operation that accepts
    ``value`` linearized. Taking such an operation as soon as it can is
    never worse: once done, it constrains nothing later."""
    for bit, j in reading:
        if not okmask & bit and _step(ops[j], value) is not _ILLEGAL:
            okmask |= bit
    return okmask


def check(history: Sequence[tuple], slack: int = 0) -> bool:
    """True iff ``history`` is linearizable for a CAS register."""
    ops, crashed, events = _prepare(history)
    if slack:
        events = [(pos + slack if ret else pos, ret, i)
                  for pos, ret, i in events]
    events.sort()
    slot_of = {}
    free_slots: List[int] = []
    next_slot = 0
    moving: List[Tuple[int, int]] = []     # (slot bit, op) writes and cas
    reading: List[Tuple[int, int]] = []    # (slot bit, op) read-only ops
    crash_ops: List[Tuple[int, int]] = []  # (crash bit, op) invoked
    configs = {(None, 0): [0]}
    for _, is_return, i in events:
        if not is_return:
            if crashed[i]:
                crash_ops.append((1 << len(crash_ops), i))
                continue
            s = free_slots.pop() if free_slots else next_slot
            if s == next_slot:
                next_slot += 1
            slot_of[i] = s
            if not _read_only(ops[i]):
                moving.append((1 << s, i))
                continue
            reading.append((1 << s, i))
            absorbed: dict = {}
            for (value, okmask), cms in configs.items():
                m = _absorb(value, okmask, ops, reading)
                for cm in cms:
                    _add(absorbed, value, m, cm)
            configs = absorbed
            continue
        target = 1 << slot_of[i]
        configs = _extend(configs, ops, moving, reading, crash_ops,
                          target, i)
        if not configs:
            return False
        moving = [(bit, j) for bit, j in moving if j != i]
        reading = [(bit, j) for bit, j in reading if j != i]
        free_slots.append(slot_of.pop(i))
    return True


def _extend(configs: dict, ops: list, moving: list, reading: list,
            crash_ops: list, target: int, i: int) -> dict:
    """Every configuration reachable from ``configs`` by linearizing
    pending operations in which op ``i`` (slot bit ``target``) has taken
    effect, with ``i`` then retired from the linearized set."""
    out: dict = {}
    seen: dict = {}
    frontier = []
    for (value, okmask), cms in configs.items():
        for cm in cms:
            if _add(seen, value, okmask, cm):
                frontier.append((value, okmask, cm))
    op_i = ops[i]
    moves_i = not _read_only(op_i)
    while frontier:
        nxt = []
        for value, okmask, cm in frontier:
            if okmask & target:
                # it took effect earlier, on the way to another return
                _add(out, value, okmask & ~target, cm)
                continue
            if moves_i:
                v = _step(op_i, value)
                if v is not _ILLEGAL:
                    _add(out, v, _absorb(v, okmask, ops, reading), cm)
            for bit, j in moving:
                if okmask & bit or bit == target:
                    continue
                v = _step(ops[j], value)
                if v is _ILLEGAL:
                    continue
                m = _absorb(v, okmask | bit, ops, reading)
                if _add(seen, v, m, cm):
                    nxt.append((v, m, cm))
            for bit, j in crash_ops:
                if cm & bit:
                    continue
                v = _step(ops[j], value)
                if v is _ILLEGAL or v == value:
                    continue
                m = _absorb(v, okmask, ops, reading)
                if _add(seen, v, m, cm | bit):
                    nxt.append((v, m, cm | bit))
        frontier = nxt
    return out
