"""The per-layer metrics that read the system's layer spans: both tiny
cells traced on the CPU, and the idle-gap attribution on a synthetic
reduction."""

import time
from types import SimpleNamespace

import pytest

from conftest import TINY
from harness import main, profile, spec

HOST = ("lint_s_per_check", "pack_s_per_check", "plan_s_per_check",
        "supervise_s_per_check")


@pytest.mark.parametrize("cell", sorted(TINY))
def test_host_layers_reported_and_bounded(cell, monkeypatch):
    seen = []
    mod = spec.load_module("metrics", HOST[0])
    read = mod.read

    def keep(run):
        seen.append(run)
        return read(run)
    monkeypatch.setattr(mod, "read", keep)
    out = main.run_cell(
        ["--workload", cell, "--seed", "3000000019", "--seconds", "0.5",
         "--trace", "1"], time.perf_counter(), platform="cpu",
        overrides=TINY[cell], cache_dir=None)
    assert out["correct"] is True
    values = [out["metrics"][m]["value"] for m in HOST]
    assert all(v >= 0 for v in values)
    (run,) = seen
    mean_wall = sum(ck.wall_s for ck in run.window) / len(run.window)
    assert sum(values) <= mean_wall
    # the history gate and packing do real work on every check
    assert values[0] > 0 and values[1] > 0


def test_no_root_span_no_reading():
    """A window check whose stretch holds no ``checker.search`` span (a
    system without the layer spans) gives no number, and no error."""
    check = main.Check(item=None, t0=0.0, t1=1.0, answers={}, counters={},
                       work=[], mono=(1, 2))
    run = SimpleNamespace(window=[check])
    for m in HOST:
        assert spec.load_module("metrics", m).read(run) is None


def test_idle_share_counts_only_layer_labels():
    red = profile.Reduction(
        window_s=1.0, busy_s={"/device:TPU:0": 0.4}, idle_share=0.6,
        op_self_s={},
        gaps=[("bench.check", 0.3),
              ("bench.check/checker.search", 0.2),
              ("bench.check/checker.search/checker.lint", 0.1)])
    share = spec.load_module("metrics", "idle_unattributed_share").read(
        SimpleNamespace(trace=red))
    assert share == pytest.approx(100.0 * 0.5 / 0.6)
    assert spec.load_module("metrics", "idle_unattributed_share").read(
        SimpleNamespace(trace=None)) is None
