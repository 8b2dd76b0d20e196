"""Driver: one test run's ``{key: history}`` per call, through the
system's independent-key entry ``check_keyed_tpu``."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from harness import spec

common = spec.load_module("drivers", "common")
counters, spans = common.counters, common.spans


def setup(chips: int) -> Dict[str, Any]:
    from jepsen_tpu.models import CASRegister
    return {"model": CASRegister()}


def prepare(ctx, item):
    return {k: common.to_history(rows) for k, rows in item.histories.items()}


def check(ctx, prepared) -> Dict[str, Any]:
    from jepsen_tpu.checker.tpu import check_keyed_tpu
    return check_keyed_tpu(prepared, ctx["model"])


def answers(item, result) -> Dict[Any, Any]:
    """The verdict per key (None: missing, or not decided on the
    device). A result whose overall verdict disagrees with its keys'
    answers for every key, as None."""
    per_key = (result or {}).get("results") or {}
    out = {k: common.verdict(per_key.get(k)) for k in item.histories}
    decided = [v for v in out.values() if v is not None]
    if len(decided) == len(out) and result.get("valid") is not all(decided):
        return {k: None for k in out}
    return out


def work(result) -> List[Tuple[int, int, int, int, int]]:
    out = []
    for r in ((result or {}).get("results") or {}).values():
        out.extend(common.work_entries(r))
    return out
