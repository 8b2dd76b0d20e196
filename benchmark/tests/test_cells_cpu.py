"""Every cell end to end at tiny sizes on the CPU, traced and not; the real entry point refusing a CPU;
and the comparison catching a broken timed path."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from conftest import BENCH, ROOT, TINY
from harness import main, spec


def run(cell, trace=0, seed=3_000_000_019, overrides=None):
    return main.run_cell(
        ["--workload", cell, "--seed", str(seed), "--seconds", "0.5",
         "--trace", str(trace)], time.perf_counter(), platform="cpu",
        overrides=overrides or TINY[cell], cache_dir=None)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_end_to_end(cell, trace):
    out = run(cell, trace)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert list(out)[-1] == "compared"
    assert out["compared"] == {"wrong_verdicts": {"value": 0, "limit": 0}}
    c = spec.find_cell(cell)
    names = {m["name"] for m in (c.per_layer if trace else c.end_to_end)}
    assert set(out["metrics"]) <= names
    if trace:
        # a CPU run has no device plane: no device number is reported
        for m in ("device_idle_share", "us_per_level", "search_roofline"):
            assert m not in out["metrics"]
        assert "busy_s" not in out["device"] and "breakdown" not in out
        assert out["metrics"]["window_compiles"]["value"] == 0
    else:
        assert {"ops_per_s", "setup_s"} <= set(out["metrics"])
        assert out["metrics"]["ops_per_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == 1


def _flip(result):
    if "results" in result:
        k = next(iter(result["results"]))
        r = result["results"][k]
        r["valid"] = not r["valid"]
    else:
        result["valid"] = not result["valid"]
    return result


def _drop(share):
    def fault(result):
        keys = sorted(result["results"], key=repr)
        for k in keys[:int(len(keys) * share)]:
            del result["results"][k]
        return result
    return fault


@pytest.mark.parametrize("cell,fault", [
    ("etcd-10k.staggered", _flip),
    ("etcd-independent.34x300", _flip),
    ("etcd-independent.34x300", _drop(0.5)),    # half the batch left out
])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    driver = spec.load_module("drivers",
                              spec.find_cell(cell).config["driver"])
    check = driver.check
    calls = []

    def broken(ctx, prepared):
        out = check(ctx, prepared)
        calls.append(1)
        # set-up's warm pass runs first; break only what the window sees
        return fault(out) if len(calls) > TINY[cell]["pool"] else out
    monkeypatch.setattr(driver, "check", broken)
    out = run(cell)
    assert out["correct"] is False
    assert out["compared"]["wrong_verdicts"]["value"] >= 1


def test_off_device_verdict_is_wrong(monkeypatch):
    driver = spec.load_module("drivers", "register")
    check = driver.check

    def marked(ctx, prepared):
        out = check(ctx, prepared)
        out["fallback-from"] = "tpu"
        return out
    monkeypatch.setattr(driver, "check", marked)
    out = run("etcd-10k.staggered")
    assert out["correct"] is False


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "etcd-10k.staggered",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_point_refuses_a_cpu():
    p = _entry(ROOT)
    assert p.returncode == main.NO_DEVICE_EXIT
    assert p.stdout.strip() == ""


def test_benchmark_alone_prints_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _entry(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    script = ("import sys, time; sys.path.insert(0, 'benchmark');"
              "from harness import main;"
              "main.run_cell(['--workload', 'etcd-10k.staggered', '--seed', '5',"
              " '--seconds', '1'], time.perf_counter(), platform='cpu',"
              " cache_dir=None)")
    q = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""),
                       capture_output=True, text=True, timeout=300)
    assert q.returncode != 0 and "jepsen_tpu" in q.stderr


def test_new_cell_from_new_files_only(tmp_path):
    """A configuration, a mix and a metric added as files, with entries
    in BENCHMARK.json, run without any edit to an existing file."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = tmp_path / "benchmark"
    cfg = json.load(open(b / "configs" / "etcd-register.json"))
    cfg["name"] = "tiny-register"
    cfg["generator"] = {"n_ops": 80, "n_procs": 3, "n_vals": 4,
                        "cas_p": 0.3, "crash_p": 0.0}
    (b / "configs" / "tiny-register.json").write_text(json.dumps(cfg))
    (b / "traffic" / "tiny-mix.json").write_text(json.dumps(
        {"overlap_p": 0.5, "pool": 4, "stale_every": 2,
         "shape_seed": 9}))
    (b / "metrics" / "checks_in_window.py").write_text(
        "def read(run):\n    return len(run.window)\n")
    bench["configs"].append({"name": "tiny-register", "source": "test",
                             "file": "benchmark/configs/tiny-register.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.cell", "config": "tiny-register",
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "checks_in_window", "unit": "count",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["tiny.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    script = ("import sys, time, json; sys.path.insert(0, 'benchmark');"
              "from harness import main;"
              "out = main.run_cell(['--workload', 'tiny.cell', '--seed', '9',"
              " '--seconds', '0.3'], time.perf_counter(), platform='cpu',"
              " cache_dir=None); print(json.dumps(out))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    q = subprocess.run([sys.executable, "-c", script], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert q.returncode == 0, q.stderr[-3000:]
    out = json.loads(q.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["metrics"]["checks_in_window"]["value"] >= 4
    assert {"ops_per_s", "setup_s"} <= set(out["metrics"])
