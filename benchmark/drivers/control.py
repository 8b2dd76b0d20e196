"""Driver for the control: the plain reference with linearizability's
real-time order relaxed (every return moved ``CONTROL_SLACK`` events
later), put in the system's place. A run with it goes through the
harness's own comparison, and its ``correct`` has to come out false.

    python3 benchmark/tests/control_readings.py --cell <cell> --seeds 1 2 3
"""

from __future__ import annotations

from typing import Any, Dict, List

from harness import spec

ref = spec.load_module("reference", "cas_register")

_ZERO = ("cold", "cache-hits", "compile-s", "execute-s", "persistent-hits",
         "persistent-misses", "levels")


def setup(chips: int) -> Dict[str, Any]:
    return {}


def prepare(ctx, item):
    return item.histories


def check(ctx, prepared) -> Dict[Any, bool]:
    return {k: ref.check(rows, ref.CONTROL_SLACK)
            for k, rows in prepared.items()}


def answers(item, result) -> Dict[Any, Any]:
    return {k: result.get(k) for k in item.histories}


def work(result) -> List[tuple]:
    return []


def counters() -> Dict[str, float]:
    return dict.fromkeys(_ZERO, 0.0)


def spans() -> List[tuple]:
    return []
