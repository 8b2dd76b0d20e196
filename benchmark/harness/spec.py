"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

Each part is a file of its own, so a later change adds cells, mixes and
metrics as new files only:

* ``benchmark/configs/<config>.json`` — a deployment: its source, the
  sizes it fixes, the guarantees it states, the ``driver`` that hands
  its histories to the system and the ``reference`` that judges them;
* ``benchmark/traffic/<traffic>.json`` — a mix's parameters, read by
  the one generator in ``harness/traffic.py``;
* ``benchmark/drivers/<driver>.py``, ``benchmark/reference/<name>.py``
  and ``benchmark/metrics/<metric>.py`` — code found by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, imported once."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    mod_name = f"bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str) -> Cell:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = load_json(os.path.join(BENCH_DIR, "configs",
                                    w["config"] + ".json"))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, w["chips"], config, traffic, e2e, per_layer)


def mix_params(cell: Cell) -> Dict[str, Any]:
    """The generator's parameters: the configuration's defaults under
    the mix's own."""
    params = dict(cell.config.get("generator", {}))
    params.update(cell.traffic)
    return params
