"""Columnar packing equals the per-op reference it replaced.

``pack_history`` builds its columns in one walk, and ``_split_packed`` /
``_window_needed`` derive theirs with numpy passes. The frozen per-op
versions below are the reference: on every kernel and history shape the
packed fields, all of ``_split_packed``'s device columns, the needed
window and the streaming packer's sealed result must be identical, so
the device receives the same arrays it did before.
"""

import dataclasses
import random
from typing import Any, Dict, Tuple

import numpy as np
import pytest

from jepsen_tpu.checker import tpu as T
from jepsen_tpu.history import Op
from jepsen_tpu.models.core import (CAS_REGISTER_KERNEL, F_READ,
                                    MUTEX_KERNEL, NIL_ID, NOOP_KERNEL,
                                    SET_KERNEL, UNORDERED_QUEUE_KERNEL)
from jepsen_tpu.ops.encode import (RET_INF, PackedHistory, StreamPacker,
                                   _Interner, pack_history)

# ---------------------------------------------------------------------------
# Frozen per-op reference
# ---------------------------------------------------------------------------


def _ref_op_values(f_code, f, inv_value, ok_value, intern):
    if f == "cas":
        v = inv_value
        if v is None:
            return int(NIL_ID), int(NIL_ID)
        old, new = v
        return intern.id(old), intern.id(new)
    if f_code == F_READ or f == "read":
        return intern.id(ok_value if ok_value is not None
                         else inv_value), int(NIL_ID)
    return intern.id(inv_value), int(NIL_ID)


def _ref_pack_history(history, kernel, intern=None, init_state=None):
    intern = intern or _Interner()
    if kernel.encode_op is not None:
        def encode(fc, f, inv_value, ok_value):
            return kernel.encode_op(fc, f, inv_value, ok_value, intern.id)
    else:
        def encode(fc, f, inv_value, ok_value):
            return _ref_op_values(fc, f, inv_value, ok_value, intern)
    pending: Dict[Any, Tuple[int, Op]] = {}
    rows = []
    for ev, o in enumerate(history):
        if o.is_invoke:
            pending[o.process] = (ev, o)
        elif o.process in pending:
            inv_ev, inv_op = pending.pop(o.process)
            if o.is_fail:
                continue
            fc = kernel.f_codes.get(inv_op.f)
            if fc is None:
                raise ValueError(
                    f"op f={inv_op.f!r} not supported by model "
                    f"{kernel.name!r} (codes: {sorted(kernel.f_codes)})")
            if o.is_info:
                if fc == F_READ or (
                        kernel.drop_crashed is not None
                        and kernel.drop_crashed(fc, inv_op.value)):
                    continue
                v1, v2 = encode(fc, inv_op.f, inv_op.value, None)
                rows.append((inv_ev, int(RET_INF), fc, v1, v2,
                             inv_op.process, inv_op, o))
            else:
                v1, v2 = encode(fc, inv_op.f, inv_op.value, o.value)
                rows.append((inv_ev, ev, fc, v1, v2, inv_op.process,
                             inv_op, o))
    for inv_ev, inv_op in pending.values():
        fc = kernel.f_codes.get(inv_op.f)
        if fc is None or fc == F_READ or (
                kernel.drop_crashed is not None
                and kernel.drop_crashed(fc, inv_op.value)):
            continue
        v1, v2 = encode(fc, inv_op.f, inv_op.value, None)
        rows.append((inv_ev, int(RET_INF), fc, v1, v2, inv_op.process,
                     inv_op, None))
    rows.sort(key=lambda r: (r[1], r[0]))
    n = len(rows)
    n_required = sum(1 for r in rows if r[1] != int(RET_INF))

    def col(i, dtype=np.int32):
        return np.asarray([r[i] for r in rows], dtype=dtype)

    procs = {}
    proc_col = []
    for r in rows:
        p = r[5]
        if p not in procs:
            procs[p] = len(procs)
        proc_col.append(procs[p])
    packed = PackedHistory(
        f=col(2), v1=col(3), v2=col(4), inv=col(0), ret=col(1),
        process=np.asarray(proc_col, dtype=np.int32) if n else
        np.zeros(0, np.int32),
        n_required=n_required,
        init_state=(kernel.init_state if init_state is None
                    else init_state),
        value_table=intern.values,
        ops=[(r[6], r[7]) for r in rows],
    )
    if kernel.remap is not None:
        kernel.remap(packed)
    if kernel.validate is not None:
        kernel.validate(packed)
    return packed


def _ref_suffix_min_inv(inv, n):
    out = np.full(n + 1, int(RET_INF), dtype=np.int32)
    for j in range(n - 1, -1, -1):
        out[j] = min(int(inv[j]), int(out[j + 1]))
    return out


def _ref_split_packed(p, breq, cr, kernel=None):
    nr = p.n_required
    n_cr = p.n - nr
    if n_cr > cr:
        return None

    def pad(a, width, fill):
        out = np.full(width, fill, dtype=np.int32)
        out[:a.shape[0]] = a
        return out

    inf = int(RET_INF)
    inv_req = pad(p.inv[:nr], breq, inf)
    ro = np.zeros(breq, dtype=np.int32)
    if kernel is not None and kernel.readonly is not None:
        for j in range(nr):
            if kernel.readonly(int(p.f[j]), int(p.v1[j]), int(p.v2[j])):
                ro[j] = 1
    sm = _ref_suffix_min_inv(inv_req, breq)
    fr = np.zeros(breq, dtype=np.int32)
    if nr:
        idx = np.searchsorted(sm[:nr + 1], p.ret[:nr], side="left")
        fr[:nr] = (idx <= np.arange(nr) + 1).astype(np.int32)
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
        "ini": np.asarray(int(p.init_state) & 0xFFFFFFFF,
                          np.uint32).view(np.int32)[()],
    }


def _ref_window_needed(p):
    nr = p.n_required
    if nr == 0:
        return 0
    sm = _ref_suffix_min_inv(p.inv[:nr], nr)[:nr]
    idx = np.searchsorted(sm, p.ret[:nr], side="left")
    return max(1, int((idx - np.arange(nr)).max()))


# ---------------------------------------------------------------------------
# Histories: one generator for every kernel's operations
# ---------------------------------------------------------------------------


class _Ops:
    """Invocation and completion values of one kernel's operations. The
    packing layer checks no semantics, so values need only be of the
    kind each kernel encodes; queue dequeues take a pending value (the
    oldest), so the queue's value-slot remap fits."""

    def __init__(self, kernel_name, rng, n_vals):
        self.kind = kernel_name
        self.rng = rng
        self.n_vals = n_vals
        self.queue = []
        self.next_value = 0

    def invoke(self):
        r = self.rng.random()
        k = self.rng.randrange
        if self.kind == "set":
            return ("add", k(self.n_vals)) if r < 0.6 else ("read", None)
        if self.kind == "unordered-queue":
            return (("enqueue", None) if r < 0.55 and len(self.queue) < 6
                    else ("dequeue", None))
        if self.kind == "mutex":
            return ("acquire", None) if r < 0.5 else ("release", None)
        if r < 1 / 3:
            return "cas", (k(self.n_vals), k(self.n_vals))
        if r < 2 / 3:
            return "write", k(self.n_vals)
        # some clients name the expected value on a read's invocation;
        # it stands in when the completion carries none
        return "read", (k(self.n_vals) if r > 0.9 else None)

    def at_invoke(self, f, v):
        """The value an invocation carries (a queue enqueue's is fresh)."""
        if f == "enqueue":
            self.next_value += 1
            return self.next_value
        return v

    def complete(self, f, v):
        """(type, value) of a completion; a dequeue of an empty queue
        fails."""
        k = self.rng.randrange
        if f == "enqueue":
            self.queue.append(v)
            return "ok", v
        if f == "dequeue":
            return ("ok", self.queue.pop(0)) if self.queue else ("fail", v)
        if f == "read" and self.kind == "set":
            return "ok", sorted(self.rng.sample(range(self.n_vals),
                                                k(self.n_vals + 1)))
        if f == "read":
            return "ok", (None if k(self.n_vals + 1) == 0
                          else k(self.n_vals))
        return "ok", v


def _history(kernel_name, n_ops, seed=0, n_procs=10, n_vals=5,
             overlap_p=0.05, crash_p=0.0, fail_p=0.0, dangling=0,
             strays=0):
    """A concurrent history of ``n_ops`` operations. ``crash_p`` of the
    completions are ``info`` (the process is reincarnated), ``fail_p``
    fail, the last ``dangling`` operations never complete, and
    ``strays`` completions with no invocation are sprinkled in."""
    rng = random.Random(seed)
    gen = _Ops(kernel_name, rng, n_vals)
    rows = []
    free = list(range(n_procs))
    in_flight = []
    invoked = 0
    while invoked < n_ops or in_flight:
        can_invoke = free and invoked < n_ops
        if can_invoke and (not in_flight or rng.random() < overlap_p):
            p = free.pop(rng.randrange(len(free)))
            f, v = gen.invoke()
            v = gen.at_invoke(f, v)
            rows.append(Op(type="invoke", f=f, value=v, process=p))
            in_flight.append((p, f, v))
            invoked += 1
            continue
        entry = rng.choice(in_flight)
        in_flight.remove(entry)
        p, f, v = entry
        r = rng.random()
        if r < crash_p:
            rows.append(Op(type="info", f=f, value=v, process=p))
            free.append(p + n_procs)
            continue
        typ, val = ("fail", v) if r < crash_p + fail_p else gen.complete(f, v)
        rows.append(Op(type=typ, f=f, value=val, process=p))
        free.append(p)
        if strays and rng.random() < 0.01:
            strays -= 1
            rows.append(Op(type="ok", f=f, value=val, process=-1 - strays))
    for i in range(dangling):
        f, v = gen.invoke()
        rows.append(Op(type="invoke", f=f, value=gen.at_invoke(f, v),
                       process=500 + i))
    return rows


def _crash_max_history(kernel_name):
    """Exactly CRASH_MAX crashed rows after some required ones."""
    f, v = {"set": ("add", 1), "unordered-queue": ("enqueue", None),
            "mutex": ("acquire", None)}.get(kernel_name, ("write", 1))
    rows = _history(kernel_name, 40, seed=7)
    for i in range(T.CRASH_MAX):
        p = 1000 + i
        if f == "enqueue":
            v = 1000 + i  # a queue value is enqueued once
        rows.append(Op(type="invoke", f=f, value=v, process=p))
        if i % 2:
            rows.append(Op(type="info", f=f, value=v, process=p))
    return rows


SHAPES = {
    "staggered-10k": lambda k: _history(k, 10000, seed=2000),
    "dense": lambda k: _history(k, 2000, seed=11, overlap_p=0.6),
    "crash-heavy": lambda k: _history(k, 400, seed=12, overlap_p=0.3,
                                      crash_p=0.2, dangling=6, strays=2),
    "fail-heavy": lambda k: _history(k, 600, seed=13, overlap_p=0.3,
                                     fail_p=0.5, strays=2),
    "keyed-300": lambda k: _history(k, 300, seed=3000, crash_p=0.002),
    "empty": lambda k: [],
    "one-op": lambda k: _history(k, 1, seed=5),
    "crash-max": _crash_max_history,
}

KERNELS = {k.name: k for k in (CAS_REGISTER_KERNEL, SET_KERNEL,
                               UNORDERED_QUEUE_KERNEL, MUTEX_KERNEL,
                               NOOP_KERNEL)}


def _pack(fn, history, kernel):
    """(packed, None), or (None, message) when packing refuses."""
    try:
        return fn(history, kernel, _Interner()), None
    except ValueError as e:
        return None, str(e)


def _assert_packed_equal(new, old):
    for name in ("f", "v1", "v2", "inv", "ret", "process"):
        a, b = getattr(new, name), getattr(old, name)
        assert a.dtype == b.dtype, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert new.n_required == old.n_required
    assert new.init_state == old.init_state
    assert new.value_table == old.value_table
    assert len(new.ops) == len(old.ops)
    for (ni, nc), (oi, oc) in zip(new.ops, old.ops):
        assert ni is oi and nc is oc


def _assert_cols_equal(new, old):
    if old is None:
        assert new is None
        return
    assert set(new) == set(old) == set(T._COLS)
    for c in T._COLS:
        a, b = np.asarray(new[c]), np.asarray(old[c])
        assert a.dtype == b.dtype, c
        np.testing.assert_array_equal(a, b, err_msg=c)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kernel_name", sorted(KERNELS))
def test_columnar_packing_matches_per_op_reference(kernel_name, shape):
    kernel = KERNELS[kernel_name]
    # noop encodes no operation at all: it packs register traffic (and
    # refuses it exactly as the reference does), and its always-true
    # readonly hook is split over the register's packing
    source = "cas-register" if kernel_name == "noop" else kernel_name
    history = SHAPES[shape](source)

    new, new_err = _pack(pack_history, history, kernel)
    old, old_err = _pack(_ref_pack_history, history, kernel)
    assert new_err == old_err
    if new is not None:
        _assert_packed_equal(new, old)
        sp = StreamPacker(kernel)
        for o in history:
            sp.feed(o)
        _assert_packed_equal(sp.close(), new)

    p = (new if kernel_name != "noop"
         else pack_history(history, CAS_REGISTER_KERNEL))
    if p is None:
        return
    nr = p.n_required
    assert T._window_needed(p) == _ref_window_needed(p)
    for n in {0, nr, p.n}:
        np.testing.assert_array_equal(T._suffix_min_inv(p.inv, n),
                                      _ref_suffix_min_inv(p.inv, n))
    breq = T._bucket(nr)
    cr = T._crash_width(p.n - nr)
    cr = T.CRASH_MAX if cr is None else cr
    _assert_cols_equal(T._split_packed(p, breq, cr, kernel),
                       _ref_split_packed(p, breq, cr, kernel))
    _assert_cols_equal(T._split_packed(p, breq, cr),
                       _ref_split_packed(p, breq, cr))


def test_readonly_hook_called_once_per_distinct_triple():
    """The readonly hook is asked once per distinct (f, v1, v2) of the
    required section, never once per op, and its answers land on every
    row of the triple."""
    p = pack_history(_history("cas-register", 3000, seed=9,
                              overlap_p=0.3), CAS_REGISTER_KERNEL)
    calls = []

    def counting(f, v1, v2):
        calls.append((f, v1, v2))
        return CAS_REGISTER_KERNEL.readonly(f, v1, v2)

    kernel = dataclasses.replace(CAS_REGISTER_KERNEL, readonly=counting)
    nr = p.n_required
    cols = T._split_packed(p, T._bucket(nr), 0, kernel)
    distinct = set(zip(p.f[:nr].tolist(), p.v1[:nr].tolist(),
                       p.v2[:nr].tolist()))
    assert len(calls) == len(set(calls)) == len(distinct) < nr
    assert set(calls) == distinct
    _assert_cols_equal(cols, _ref_split_packed(p, T._bucket(nr), 0,
                                               CAS_REGISTER_KERNEL))
