"""In-process test fixtures: the fake backend.

Rebuild of jepsen.tests (jepsen/tests.clj:12-56): ``noop_test`` — a complete
base test map that does nothing — plus ``AtomDB``/``AtomClient``, which
implement the full DB/Client protocols against a local, lock-guarded value so
``core.run`` exercises its entire lifecycle (workers, generator, history,
checker) without SSH or a real database. This is the protocol-boundary seam
the reference uses for its own integration tests (core_test.clj:17-28).
"""

from __future__ import annotations

import random
import threading
from typing import Any, Optional

from jepsen_tpu import client as client_ns
from jepsen_tpu import db as db_ns
from jepsen_tpu import os as os_ns
from jepsen_tpu.history import Op
from jepsen_tpu.models import CASRegister
from jepsen_tpu.util import Atom


def noop_test() -> dict:
    """A test map that does nothing: the default skeleton other tests merge
    over (tests.clj:12-25)."""
    return {
        "name": "noop",
        "nodes": ["n1", "n2", "n3", "n4", "n5"],
        "concurrency": 5,
        "os": os_ns.noop(),
        "db": db_ns.noop(),
        "client": client_ns.noop(),
        "nemesis": None,
        "generator": None,
        "checker": None,
        "ssh": {"mode": "dummy"},
    }


class SharedRegister:
    """A lock-guarded register with atomic cas — the 'database'."""

    def __init__(self, value: Any = None):
        self._value = value
        self._lock = threading.Lock()

    def read(self):
        with self._lock:
            return self._value

    def write(self, v):
        with self._lock:
            self._value = v

    def cas(self, old, new) -> bool:
        with self._lock:
            if self._value == old:
                self._value = new
                return True
            return False


class AtomDB(db_ns.DB):
    """DB whose 'state' is an in-memory register; setup resets it
    (tests.clj:27-34)."""

    def __init__(self, register: Optional[SharedRegister] = None):
        self.register = register or SharedRegister()

    def setup(self, test, node):
        self.register.write(None)

    def teardown(self, test, node):
        self.register.write(None)


class AtomClient(client_ns.Client):
    """Client over the shared register: linearizable by construction
    (tests.clj:36-56)."""

    def __init__(self, register: SharedRegister):
        self.register = register

    def open(self, test, node):
        return AtomClient(self.register)

    def invoke(self, test, op: Op) -> Op:
        if op.f == "read":
            return op.replace(type="ok", value=self.register.read())
        if op.f == "write":
            self.register.write(op.value)
            return op.replace(type="ok")
        if op.f == "cas":
            old, new = op.value
            ok = self.register.cas(old, new)
            return op.replace(type="ok" if ok else "fail")
        raise ValueError(f"unknown op {op.f!r}")


class FlakyClient(AtomClient):
    """AtomClient that sometimes times out *after* applying (or not
    applying) the op — produces indeterminate :info completions so tests can
    exercise process reincarnation and crashed-op checker semantics."""

    def __init__(self, register: SharedRegister, flake_p: float = 0.1,
                 seed: Optional[int] = None):
        super().__init__(register)
        import random
        self.flake_p = flake_p
        self.rng = random.Random(seed)

    def open(self, test, node):
        return FlakyClient(self.register, self.flake_p,
                           self.rng.randrange(2**31))

    def invoke(self, test, op: Op) -> Op:
        if self.rng.random() < self.flake_p:
            # maybe apply, then 'time out'
            if self.rng.random() < 0.5 and op.f != "read":
                super().invoke(test, op)
            raise TimeoutError("simulated client timeout")
        return super().invoke(test, op)


class SharedBank:
    """Lock-guarded accounts for the bank workload; transfers are atomic
    and refuse overdrafts (the semantics cockroach's SQL txns provide,
    bank.clj:33-90)."""

    def __init__(self, n: int = 5, per_account: int = 10):
        self.n = n
        self.total = n * per_account
        self.balances = [per_account] * n
        self._lock = threading.Lock()

    def read(self):
        with self._lock:
            return list(self.balances)

    def transfer(self, frm: int, to: int, amount: int) -> bool:
        with self._lock:
            if self.balances[frm] < amount:
                return False
            self.balances[frm] -= amount
            self.balances[to] += amount
            return True


class BankClient(client_ns.Client):
    """Client over SharedBank; broken=True applies transfers
    non-atomically (debit without credit on a simulated crash window),
    producing wrong-total reads for checker self-tests."""

    def __init__(self, bank: SharedBank, broken: bool = False):
        self.bank = bank
        self.broken = broken
        self._n = 0

    def open(self, test, node):
        return BankClient(self.bank, self.broken)

    def invoke(self, test, op: Op) -> Op:
        if op.f == "read":
            return op.replace(type="ok", value=self.bank.read())
        if op.f == "transfer":
            v = op.value
            if self.broken:
                self._n += 1
                if self._n % 3 == 0:  # lose the credit half of the txn
                    with self.bank._lock:
                        self.bank.balances[v["from"]] -= v["amount"]
                    return op.replace(type="ok")
            ok = self.bank.transfer(v["from"], v["to"], v["amount"])
            return op.replace(type="ok" if ok else "fail")
        raise ValueError(f"unknown op {op.f!r}")


class SharedMonotonic:
    """Monotonic-insert table: add assigns (val, sts) under one lock so
    value order and timestamp order agree (what serializable SQL gives
    monotonic.clj's inserts)."""

    def __init__(self):
        self.rows = []
        self._lock = threading.Lock()
        self._next = 0
        self._sts = 0

    def add(self, proc, node, skew: int = 0):
        with self._lock:
            val = self._next
            self._next += 1
            self._sts += 1
            self.rows.append({"val": val, "sts": self._sts + skew,
                              "proc": proc, "node": node, "tb": 0})
            return val

    def read(self):
        with self._lock:
            return sorted(self.rows, key=lambda r: r["sts"])


class MonotonicClient(client_ns.Client):
    """Client over SharedMonotonic; broken=True injects timestamp skew so
    sts order disagrees with value order."""

    def __init__(self, table: SharedMonotonic, broken: bool = False):
        self.table = table
        self.broken = broken

    def open(self, test, node):
        c = MonotonicClient(self.table, self.broken)
        c.node = node
        return c

    def invoke(self, test, op: Op) -> Op:
        if op.f == "add":
            skew = (-3 if self.broken and self.table._next % 5 == 4 else 0)
            val = self.table.add(op.process, getattr(self, "node", None),
                                 skew)
            return op.replace(type="ok", value=val)
        if op.f == "read":
            return op.replace(type="ok", value=self.table.read())
        raise ValueError(f"unknown op {op.f!r}")


class SharedKV:
    """A flat lock-guarded KV namespace for the sequential workload."""

    def __init__(self):
        self.data = {}
        self._lock = threading.Lock()

    def put(self, k, v=True):
        with self._lock:
            self.data[k] = v

    def get(self, k):
        with self._lock:
            return self.data.get(k)


class SequentialClient(client_ns.Client):
    """Writes insert subkeys in client order; reads probe them in reverse
    (sequential.clj:52-95). broken=True writes subkeys in *reverse* order,
    so a concurrent reader can see a later subkey without an earlier one
    (a trailing nil)."""

    def __init__(self, kv: SharedKV, broken: bool = False):
        self.kv = kv
        self.broken = broken

    def open(self, test, node):
        return SequentialClient(self.kv, self.broken)

    def invoke(self, test, op: Op) -> Op:
        from jepsen_tpu.suites.workloads import subkeys
        key_count = test.get("key-count", 5)
        ks = subkeys(key_count, op.value)
        if op.f == "write":
            if self.broken:
                import time as _t
                for k in reversed(ks):
                    self.kv.put(k)
                    _t.sleep(0.001)  # widen the visibility window
            else:
                for k in ks:
                    self.kv.put(k)
            return op.replace(type="ok")
        if op.f == "read":
            vals = [k if self.kv.get(k) else None for k in reversed(ks)]
            return op.replace(type="ok", value=(op.value, vals))
        raise ValueError(f"unknown op {op.f!r}")


class G2Client(client_ns.Client):
    """Two-table predicate-read + insert (adya.clj:31-43). With a global
    transaction lock the G2 phenomenon is impossible; broken=True drops
    the lock so both inserts for a key can succeed."""

    def __init__(self, broken: bool = False, state=None, lock=None):
        self.broken = broken
        self.state = state if state is not None else {}
        self.lock = lock or threading.Lock()

    def open(self, test, node):
        return G2Client(self.broken, self.state, self.lock)

    def _txn(self, k, a_id, b_id):
        a = self.state.setdefault("a", {})
        b = self.state.setdefault("b", {})
        if any(row["key"] == k for row in a.values()) or \
           any(row["key"] == k for row in b.values()):
            return False
        if a_id is not None:
            a[a_id] = {"key": k, "value": 30}
        else:
            b[b_id] = {"key": k, "value": 30}
        return True

    def invoke(self, test, op: Op) -> Op:
        k, (a_id, b_id) = op.value.key, op.value.value
        if self.broken:
            import time as _t
            ok1 = not any(row["key"] == k
                          for row in self.state.setdefault("a", {}).values())
            ok2 = not any(row["key"] == k
                          for row in self.state.setdefault("b", {}).values())
            _t.sleep(0.001)  # widen the race window
            if ok1 and ok2:
                tbl = self.state["a"] if a_id is not None else self.state["b"]
                tbl[a_id if a_id is not None else b_id] = {"key": k,
                                                           "value": 30}
                return op.replace(type="ok")
            return op.replace(type="fail")
        with self.lock:
            ok = self._txn(k, a_id, b_id)
        return op.replace(type="ok" if ok else "fail")


class SharedQueue:
    """Lock-guarded FIFO for queue workloads."""

    def __init__(self):
        from collections import deque
        self.q = deque()
        self._lock = threading.Lock()

    def enqueue(self, v):
        with self._lock:
            self.q.append(v)

    def dequeue(self):
        with self._lock:
            return self.q.popleft() if self.q else None


class QueueClient(client_ns.Client):
    """Client over SharedQueue; broken=True occasionally drops enqueues
    after acking (lost messages for total-queue self-tests)."""

    def __init__(self, queue: SharedQueue, broken: bool = False):
        self.queue = queue
        self.broken = broken
        self._n = 0

    def open(self, test, node):
        return QueueClient(self.queue, self.broken)

    def invoke(self, test, op: Op) -> Op:
        if op.f == "enqueue":
            self._n += 1
            if self.broken and self._n % 4 == 0:
                return op.replace(type="ok")  # acked but dropped
            self.queue.enqueue(op.value)
            return op.replace(type="ok")
        if op.f in ("dequeue", "drain"):
            v = self.queue.dequeue()
            if v is None:
                return op.replace(type="fail")
            return op.replace(type="ok", value=v)
        raise ValueError(f"unknown op {op.f!r}")


def simulate_register_history(n_ops: int, n_procs: int = 5, n_vals: int = 8,
                              seed: int = 0, cas_p: float = 0.2,
                              crash_p: float = 0.0,
                              overlap_p: float = 0.6):
    """Synthesize a concurrent CAS-register history that is linearizable by
    construction: ops take effect at a random *commit* instant between their
    invocation and completion events (the linearization point), against one
    true register: the north-star workload shape (etcd-style CAS register,
    reference etcd.clj:149-188) that the checker stress tests drive.

    n_ops counts operations (invoke/complete pairs); the returned History has
    ~2*n_ops event rows.
    """
    from jepsen_tpu.history import History

    rng = random.Random(seed)
    h = History()
    value = None
    free = list(range(n_procs))
    in_flight = []  # [process, op, committed?]
    invoked = 0
    t = 0
    while invoked < n_ops or in_flight:
        can_invoke = free and invoked < n_ops
        # overlap_p biases toward keeping several ops in flight: the
        # default 0.6 gives dense concurrency (the stress shape); low
        # values give mostly-sequential STAGGERED histories — the
        # reference's tutorial workloads (etcd.clj:172 staggers 1/30 s),
        # where ops rarely overlap and forced runs dominate.
        if can_invoke and (not in_flight or rng.random() < overlap_p):
            p = free.pop(rng.randrange(len(free)))
            r = rng.random()
            if r < cas_p:
                f, v = "cas", (rng.randrange(n_vals), rng.randrange(n_vals))
            elif r < cas_p + (1 - cas_p) / 2:
                f, v = "write", rng.randrange(n_vals)
            else:
                f, v = "read", None
            h.append(Op(type="invoke", f=f, value=v, process=p, time=t))
            in_flight.append([p, h[-1], False])
            invoked += 1
        else:
            entry = rng.choice(in_flight)
            p, inv_op, committed = entry
            if not committed:
                # commit now: apply the effect at this instant
                if inv_op.f == "write":
                    value = inv_op.value
                    entry[2] = ("ok", inv_op.value)
                elif inv_op.f == "cas":
                    old, new = inv_op.value
                    if value == old:
                        value = new
                        entry[2] = ("ok", inv_op.value)
                    else:
                        entry[2] = ("fail", inv_op.value)
                else:
                    entry[2] = ("ok", value)
                # complete immediately half the time, else stay in flight
                if rng.random() >= 0.5:
                    continue
            typ, val = entry[2]
            in_flight.remove(entry)
            if crash_p and rng.random() < crash_p:
                h.append(Op(type="info", f=inv_op.f, value=inv_op.value,
                            process=p, time=t))
                # jepsen's reincarnation rule (core.clj:175,211): the crashed
                # logical process is replaced by p + concurrency
                free.append(p + n_procs)
            else:
                h.append(Op(type=typ, f=inv_op.f, value=val, process=p,
                            time=t))
                free.append(p)
        t += 1
    return h


def corrupt_one_read(history, rng, bogus=99):
    """Flip ONE random ok-read completion to a bogus value (a stale/phantom
    read) — the standard mutation refutation fuzzers apply to
    valid-by-construction histories. Returns a new History; identity when
    the sampled row isn't a corruptible read."""
    from jepsen_tpu.history import History

    rows = list(history)
    if rows:
        i = rng.randrange(len(rows))
        o = rows[i]
        if o.type == "ok" and o.f == "read" and o.value is not None:
            rows[i] = o.replace(value=bogus)
    return History.of(rows)


def atom_test(register: Optional[SharedRegister] = None, **overrides) -> dict:
    """A runnable in-memory CAS-register test (core_test.clj basic-cas-test
    shape)."""
    reg = register or SharedRegister()
    test = noop_test()
    test.update({
        "name": "atom-cas",
        "db": AtomDB(reg),
        "client": AtomClient(reg),
        "model": CASRegister(),
    })
    test.update(overrides)
    return test


def wide_history(n_procs=100, rounds=2, write_frac=0.12, seed=0,
                 corrupt=False):
    """Rounds of n_procs fully-overlapping ops against one register:
    every op of a round is invoked before any completes, so candidate
    offsets reach ~n_procs-1 and the device search NEEDS a multi-word
    window (the aerospike 100-thread shape, reference
    aerospike/src/aerospike/core.clj:566-575). Read-heavy with unique
    write values keeps the witness value-chain-constrained — wide but
    tractable, like real high-concurrency workloads. Linearizable by
    construction unless ``corrupt``."""
    from jepsen_tpu.history import History

    rng = random.Random(seed)
    h = History()
    value = None
    t = 0
    nextv = 0
    for _ in range(rounds):
        ops = []
        for p in range(n_procs):
            if rng.random() < write_frac:
                f, v = "write", nextv
                nextv += 1
            else:
                f, v = "read", None
            h.append(Op(type="invoke", f=f, value=v, process=p, time=t))
            t += 1
            ops.append((p, f, v))
        rng.shuffle(ops)                   # commit order
        comps = []
        for p, f, v in ops:
            if f == "write":
                value = v
                comps.append((p, "ok", f, v))
            else:
                comps.append((p, "ok", f, value))
        rng.shuffle(comps)                 # return order, independent
        for p, typ, f, v in comps:
            h.append(Op(type=typ, f=f, value=v, process=p, time=t))
            t += 1
    if corrupt:
        rows = list(h)
        for i in range(len(rows) - 1, -1, -1):
            o = rows[i]
            if o.type == "ok" and o.f == "read":
                rows[i] = o.replace(value=10**6)   # never-written value
                break
        h = History.of(rows)
    return h
