"""The four-chip keyed cell (``etcd-independent.mesh4``) end to end at a
tiny mix, on four virtual CPU devices in a process of its own: traced
and not, a broken timed path, and a result that ran on one device."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT
from harness import spec

CELL = "etcd-independent.mesh4"
TINY_MESH = {"keys": 8, "n_ops": 60, "pool": 2}
NEW = ("mesh_level_skew", "us_per_chip_level", "mesh_search_roofline",
       "place_s_per_check")
#: The one-chip cells' host-layer metrics, which the mesh cell lists too
#: and a CPU run reads (no device plane: the idle shares are absent).
HOST = ("warmup_compile_s", "window_compiles", "host_s_per_check",
        "device_calls_per_check", "lint_s_per_check", "pack_s_per_check",
        "plan_s_per_check", "supervise_s_per_check")

#: Runs the cell four ways in one process (so the mesh executables
#: compile once) and prints one JSON object of the four results.
SCRIPT = r"""
import json, sys, time
sys.path.insert(0, "benchmark")
from harness import main, spec

CELL, TINY = sys.argv[1], json.loads(sys.argv[2])
driver = spec.load_module("drivers", spec.find_cell(CELL).config["driver"])
check = driver.check


def run(trace, fault=None):
    calls = []

    def broken(ctx, prepared):
        out = check(ctx, prepared)
        calls.append(1)
        # set-up's warm pass runs first; break only what the window sees
        return fault(out) if len(calls) > TINY["pool"] else out
    driver.check = broken if fault else check
    return main.run_cell(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "0.5",
         "--trace", str(trace)], time.perf_counter(), platform="cpu",
        overrides=TINY, cache_dir=None)


def flip(out):
    r = out["results"][next(iter(out["results"]))]
    r["valid"] = not r["valid"]
    return out


def one_device(out):
    out["devices"] = out["devices"][:1]
    return out


print(json.dumps({"untraced": run(0), "traced": run(1),
                  "flipped": run(0, flip), "one-device": run(0, one_device)}))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT, CELL, json.dumps(TINY_MESH)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", ["untraced", "traced"])
def test_mesh_cell_end_to_end(runs, trace):
    out = runs[trace]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["count"] == 4
    assert out["device"]["platform"] == "cpu"
    c = spec.find_cell(CELL)
    if trace == "traced":
        assert set(out["metrics"]) <= {m["name"] for m in c.per_layer}
        # a CPU run has no device plane: no device number is reported;
        # the counter and the span are
        for m in ("us_per_chip_level", "mesh_search_roofline"):
            assert m not in out["metrics"]
        assert out["metrics"]["mesh_level_skew"]["value"] >= 1.0
        assert out["metrics"]["place_s_per_check"]["value"] > 0
        assert set(HOST) <= set(out["metrics"])
        assert out["metrics"]["window_compiles"]["value"] == 0
    else:
        assert set(out["metrics"]) == {"ops_per_s", "setup_s"}
        assert out["metrics"]["ops_per_s"]["value"] > 0


def test_mesh_cell_lists_its_metrics():
    c = spec.find_cell(CELL)
    assert c.chips == 4
    assert set(NEW) | set(HOST) <= {m["name"] for m in c.per_layer}
    assert {m["name"] for m in c.end_to_end} == {"ops_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["flipped", "one-device"])
def test_broken_mesh_run_is_not_correct(runs, fault):
    out = runs[fault]
    assert out["correct"] is False
    assert out["compared"]["wrong_verdicts"]["value"] >= 1
    if fault == "one-device":
        # every key of every window check is held to be wrong
        assert out["failed"] == out["attempted"]
