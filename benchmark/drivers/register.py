"""Driver: one recorded history per call, through the system's public
facade ``linearizable(CASRegister(), backend="tpu").check``."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from harness import spec

common = spec.load_module("drivers", "common")
counters, spans = common.counters, common.spans


def setup(chips: int) -> Dict[str, Any]:
    from jepsen_tpu.checker.wgl import linearizable
    from jepsen_tpu.models import CASRegister
    return {"checker": linearizable(CASRegister(), backend="tpu")}


def prepare(ctx, item):
    return common.to_history(item.histories[None])


def check(ctx, prepared) -> Dict[str, Any]:
    return ctx["checker"].check({}, prepared)


def answers(item, result) -> Dict[Any, Any]:
    """The verdict per key of the item (None: not decided on the
    device)."""
    return {None: common.verdict(result)}


def work(result) -> List[Tuple[int, int, int, int, int]]:
    return common.work_entries(result)
