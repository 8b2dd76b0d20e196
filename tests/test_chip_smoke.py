"""chip_smoke.py's phases at a tiny size on the CPU, and its refusal to
report success anywhere but on a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke as cs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_offline_phase_small(capsys, monkeypatch):
    # the phase names the executables built since it began: start from
    # none, whatever an earlier test in this process ran at these shapes
    from jepsen_tpu.checker import tpu as T
    monkeypatch.setattr(T, "_EXECUTED_SHAPES", set())
    cs.phase_offline(n_ops=400)
    out = capsys.readouterr().out
    assert "# phase offline: platform=cpu" in out
    assert "valid=True stale_valid=False" in out
    assert "attempt_backends=default " in out
    assert "rungs=segment:" in out


def test_stale_read_twin_is_refuted_by_the_reference():
    from jepsen_tpu.checker.native import check_history_native
    from jepsen_tpu.models import CASRegister
    h = cs.headline_history(300)
    twin = cs.stale_read_twin(h)
    assert len(twin) == len(h)
    assert sum(a != b for a, b in zip(h, twin)) == 1
    assert check_history_native(twin, CASRegister())["valid"] is False


def test_cli_phase_small(tmp_path, capsys):
    cs.phase_cli(str(tmp_path), seconds=2)
    out = capsys.readouterr().out
    assert "# phase cli: platform=cpu" in out and "valid=True" in out
    assert (tmp_path / "store" / "latest").exists()


def test_keyed_phase_small(capsys):
    cs.phase_keyed(n_keys=5, n_ops=40)
    assert "# phase keyed: platform=cpu" in capsys.readouterr().out


def test_served_phase_small(tmp_path, capsys):
    cs.phase_served(str(tmp_path / "serve"), n_ops=300, keyed_ops=40)
    out = capsys.readouterr().out
    assert "# phase served: platform=cpu" in out
    assert '"completed":3' in out


def test_keyed_mesh_phase_small(capsys):
    cs.phase_keyed_mesh(n_keys=6, n_ops=40, n_devices=4)
    assert "mesh_devices=[0, 1, 2, 3]" in capsys.readouterr().out


def test_wide_sharded_phase_small(capsys):
    cs.phase_wide_sharded(n_procs=24, rounds=2, n_devices=4, capacity=128,
                          expand=128, segment_iters=2)
    out = capsys.readouterr().out
    assert "# phase wide-sharded: platform=cpu" in out
    assert "rung=128/32/128" in out and '"peak-live-rows":[' in out


def test_wide_sharded_phase_fails_when_a_shard_stays_empty():
    # 16 processes never grow a frontier past the first of four shards
    with pytest.raises(cs.SmokeFailure, match="want all of 4 shards live"):
        cs.phase_wide_sharded(n_procs=16, rounds=2, n_devices=4,
                              capacity=32, expand=32, segment_iters=1)


@pytest.mark.parametrize("result,marker", [
    ({"valid": True, "fallback-from": "tpu"}, "fallback-from"),
    ({"results": {3: {"valid": True, "backend-fallback": 1}}},
     "backend-fallback"),
    ({"linear": [{"cpu-fallback": True}]}, "cpu-fallback"),
    ({"attempts": [{"backend": "cpu-fallback"}]}, "cpu-fallback"),
])
def test_fallback_markers_fail_the_phase(result, marker):
    with pytest.raises(cs.SmokeFailure, match=marker):
        cs._no_fallback(result, "test")


def test_a_failing_phase_is_reported_and_later_phases_still_run(capsys):
    ran = []

    def bad():
        raise cs.SmokeFailure("device said maybe")

    failed = cs.run_phases([("bad", bad), ("good", lambda: ran.append(1))])
    assert failed == ["bad"] and ran == [1]
    assert "# phase bad: FAILED" in capsys.readouterr().out


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_the_cpu(argv, capsys):
    assert cs.main(argv) == 1
    last = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(last)
    assert doc["ok"] is False
    assert doc["device"]["platform"] == "cpu"
    assert "no TPU" in doc["error"]


def test_script_alone_fails(tmp_path):
    """In a directory holding chip_smoke.py and nothing else of the repo
    there is no system to smoke: non-zero exit, no ok result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    pr = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                        env=env, capture_output=True, text=True,
                        timeout=120)
    assert pr.returncode != 0
    doc = json.loads(pr.stdout.strip().splitlines()[-1])
    assert doc["ok"] is False
    assert "ModuleNotFoundError" in doc["error"]
