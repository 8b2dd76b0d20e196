"""Driver: one test run's ``{key: history}`` per call, through the
system's independent-key entry ``check_keyed_tpu`` on a keys mesh over
the cell's chips (``parallel.make_mesh``). Histories, answers and work
are read as the one-chip keyed driver reads them, but a result whose
``devices`` holds fewer ids than the mesh has chips answers None for
every key: a batch that ran on fewer chips is not this deployment."""

from __future__ import annotations

from typing import Any, Dict

from harness import spec

common = spec.load_module("drivers", "common")
keyed = spec.load_module("drivers", "keyed")
prepare, work, spans = keyed.prepare, keyed.work, common.spans

#: The key ``check`` adds to the system's result: the mesh's chips,
#: which ``answers`` holds the result's devices to.
CHIPS = "bench-mesh-chips"


def setup(chips: int) -> Dict[str, Any]:
    from jepsen_tpu import parallel
    from jepsen_tpu.models import CASRegister
    return {"model": CASRegister(), "mesh": parallel.make_mesh(chips)}


def check(ctx, prepared) -> Dict[str, Any]:
    from jepsen_tpu.checker.tpu import check_keyed_tpu
    out = check_keyed_tpu(prepared, ctx["model"], mesh=ctx["mesh"])
    return {**out, CHIPS: ctx["mesh"].size}


def answers(item, result) -> Dict[Any, Any]:
    result = result or {}
    if len(result.get("devices") or ()) < result.get(CHIPS, 1):
        return {k: None for k in item.histories}
    return keyed.answers(item, result)


def counters() -> Dict[str, float]:
    """The system's counters (``drivers/common.py``) and, where the
    system keeps it, ``chip-levels``: each chip's own levels per launch,
    summed over the chips."""
    from jepsen_tpu.obs import metrics
    snap = common.counters()
    snap["chip-levels"] = metrics.counter(
        "jtpu_keyed_chip_levels_total").total()
    return snap
