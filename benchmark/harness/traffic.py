"""The one traffic generator: a pool of recorded histories from a seed.

A traffic mix is a data file, ``benchmark/traffic/<name>.json``, whose
parameters (history length, concurrency, crash and overlap rates, keys
per test run, pool size, which histories carry a stale read) this module
reads; a configuration's ``generator`` block supplies the defaults.

Histories are plain ``(type, process, f, value)`` tuples. The work in a
pool is fixed by the mix: the base histories come from the mix's
``shape_seed``. ``--seed`` draws only labels and order: a permutation
of the register values, new process ids, new key names, and the order
in which the caller hands the pool to the checker. So every seed gives
the checker the same searches, each under other names, and runs with
different seeds differ only by the system's own noise.
"""

from __future__ import annotations

import random
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Sequence

Row = tuple  # (type, process, f, value)


def register_history(n_ops: int, n_procs: int = 5, n_vals: int = 8,
                     seed: int = 0, cas_p: float = 0.2,
                     crash_p: float = 0.0,
                     overlap_p: float = 0.6) -> List[Row]:
    """A concurrent CAS-register history, linearizable by construction:
    each operation takes effect at a random instant between its
    invocation and its completion, against one true register. A crashed
    operation completes ``info`` and its process is replaced by
    ``p + n_procs`` (Jepsen's reincarnation rule). A copy of the
    system's own generator, so that the benchmark's traffic cannot move
    with it."""
    rng = random.Random(seed)
    rows: List[Row] = []
    value = None
    free = list(range(n_procs))
    in_flight: list = []  # [process, (f, value), committed]
    invoked = 0
    while invoked < n_ops or in_flight:
        can_invoke = free and invoked < n_ops
        if can_invoke and (not in_flight or rng.random() < overlap_p):
            p = free.pop(rng.randrange(len(free)))
            r = rng.random()
            if r < cas_p:
                f, v = "cas", (rng.randrange(n_vals), rng.randrange(n_vals))
            elif r < cas_p + (1 - cas_p) / 2:
                f, v = "write", rng.randrange(n_vals)
            else:
                f, v = "read", None
            rows.append(("invoke", p, f, v))
            in_flight.append([p, (f, v), False])
            invoked += 1
            continue
        entry = rng.choice(in_flight)
        p, (f, v), committed = entry
        if not committed:
            if f == "write":
                value = v
                entry[2] = ("ok", v)
            elif f == "cas":
                if value == v[0]:
                    value = v[1]
                    entry[2] = ("ok", v)
                else:
                    entry[2] = ("fail", v)
            else:
                entry[2] = ("ok", value)
            if rng.random() >= 0.5:
                continue
        typ, val = entry[2]
        in_flight.remove(entry)
        if crash_p and rng.random() < crash_p:
            rows.append(("info", p, f, v))
            free.append(p + n_procs)
        else:
            rows.append((typ, p, f, val))
            free.append(p)
    return rows


def stale_read(rows: Sequence[Row], refutes: Callable[[List[Row]], bool],
               at_frac: float = 0.005) -> List[Row]:
    """``rows`` with one completed read made stale: from the first
    ``ok`` read with a value at or past ``at_frac`` of the rows, each
    read in turn is given each value written before the latest
    completed write, most recent first, until ``refutes`` (the
    reference) says the history is no longer linearizable. An
    indeterminate write or a concurrent one can make a stale read legal,
    hence the search."""
    rows = list(rows)
    written: list = []
    for i, (typ, p, f, v) in enumerate(rows):
        if typ == "ok" and f == "write":
            written.append(v)
        if i < int(len(rows) * at_frac) or typ != "ok" or f != "read" \
                or v is None:
            continue
        for old in dict.fromkeys(reversed(written[:-1])):
            if old == v:
                continue
            twin = rows[:i] + [(typ, p, f, old)] + rows[i + 1:]
            if refutes(twin):
                return twin
    raise ValueError("no stale read that the reference refutes")


def relabel(rows: Sequence[Row], rng: random.Random,
            n_vals: int) -> List[Row]:
    """The same history under a permutation of the values and new
    process ids drawn from ``rng``."""
    perm = list(range(n_vals))
    rng.shuffle(perm)
    procs: Dict[Any, int] = {}
    for _, p, _, _ in rows:
        procs.setdefault(p, 0)
    ids = rng.sample(range(8 * len(procs)), len(procs))
    procs = dict(zip(procs, ids))

    def val(f, v):
        if v is None:
            return None
        if f == "cas":
            return (perm[v[0]], perm[v[1]])
        return perm[v]

    return [(typ, procs[p], f, val(f, v)) for typ, p, f, v in rows]


@dataclass
class Item:
    """One unit the caller hands to the checker: a history, or for a
    keyed mix a test run's ``{key: history}``."""
    base: int                 # index in the mix's fixed base pool
    histories: Dict[Any, List[Row]]

    @property
    def ops(self) -> int:
        """Operations (invocations) in the item, summed over keys."""
        return sum(sum(1 for r in h if r[0] == "invoke")
                   for h in self.histories.values())


def make_pool(params: Dict[str, Any], seed: int,
              refutes: Callable[[List[Row]], bool]) -> List[Item]:
    """The mix's pool of items, labelled and ordered by ``seed``;
    ``refutes`` is the reference's test of a stale-read twin."""
    rng = random.Random(seed)
    shape = params["shape_seed"]
    n_keys = params.get("keys")
    gen = {k: params[k] for k in ("n_ops", "n_procs", "n_vals", "cas_p",
                                  "crash_p", "overlap_p")}
    every = params["stale_every"]
    pool = []
    for b in range(params["pool"]):
        stale = (b % every) == every - 1
        if n_keys is None:
            h = register_history(seed=shape + b, **gen)
            if stale:
                h = stale_read(h, refutes)
            pool.append(Item(b, {None: relabel(h, rng, gen["n_vals"])}))
            continue
        names = rng.sample(range(100 * n_keys), n_keys)
        stale_at = (b * 7) % n_keys if stale else None
        hs = {}
        for k in range(n_keys):
            h = register_history(seed=shape + b * n_keys + k, **gen)
            if k == stale_at:
                h = stale_read(h, refutes)
            hs[names[k]] = relabel(h, rng, gen["n_vals"])
        pool.append(Item(b, hs))
    rng.shuffle(pool)
    return pool
