"""No silent CPU fallback: a device entry point whose backend cannot
initialize raises instead of answering from the CPU, and the only
degradation left is the opt-in run-time wedge record in resilience.py."""

import importlib.util
import os
import subprocess
import sys
import warnings

import pytest

from jepsen_tpu import resilience

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HISTORY = ("from jepsen_tpu.models import CASRegister\n"
            "from jepsen_tpu.testing import simulate_register_history\n"
            "h = simulate_register_history(60, n_procs=3, seed=2)\n")

#: Each entry point, driven with a backend that cannot initialize here
#: (JAX_PLATFORMS=tpu on a host with no chip).
ENTRY_POINTS = {
    "facade": ("from jepsen_tpu.checker.wgl import linearizable\n"
               "r = linearizable(CASRegister(), backend='tpu')"
               ".check({}, h)\n"),
    "single": ("from jepsen_tpu.checker.tpu import check_history_tpu\n"
               "r = check_history_tpu(h, CASRegister())\n"),
    "sharded": ("from jax.sharding import AbstractMesh\n"
                "from jepsen_tpu.checker.tpu import check_history_sharded\n"
                "mesh = AbstractMesh((2,), ('pool',))\n"
                "r = check_history_sharded(h, CASRegister(), mesh, "
                "capacity=64, expand=8)\n"),
    "keyed": ("from jepsen_tpu.checker.tpu import check_keyed_tpu\n"
              "r = check_keyed_tpu({0: h, 1: h}, CASRegister())\n"),
}


@pytest.fixture(autouse=True)
def fresh_state():
    resilience._reset_wedge_for_tests()
    yield
    resilience._reset_wedge_for_tests()


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_backend_init_failure_raises(entry):
    code = (_HISTORY + ENTRY_POINTS[entry]
            + "print('VERDICT', r['valid'], r.get('backend'))\n")
    env = {**os.environ, "JAX_PLATFORMS": "tpu", "PYTHONPATH": REPO}
    pr = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                        capture_output=True, text=True, timeout=120)
    assert pr.returncode != 0, pr.stdout
    assert "VERDICT" not in pr.stdout
    assert "Unable to initialize backend" in pr.stderr, pr.stderr[-800:]
    assert "degrading" not in pr.stderr


def test_cli_analyze_fails_loudly_without_the_backend(tmp_path):
    import shutil
    run = tmp_path / "run"
    shutil.copytree(os.path.join(REPO, "examples", "store", "atom-cas"),
                    run)
    env = {**os.environ, "JAX_PLATFORMS": "tpu", "PYTHONPATH": REPO}
    pr = subprocess.run(
        [sys.executable, "-m", "jepsen_tpu", "analyze", "--store",
         str(run), "--backend", "tpu"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    # check_safe turns the checker's crash into an unknown verdict that
    # carries the init error (reference checker semantics) — never a
    # CPU-computed true
    from jepsen_tpu import cli
    assert pr.returncode == cli.TEST_FAILED, pr.stdout[-800:]
    assert '"valid": "unknown"' in pr.stdout
    assert "Unable to initialize backend" in pr.stdout


def test_no_probe_surface_left():
    assert importlib.util.find_spec("jepsen_tpu.accel") is None
    for name in ("ensure_usable", "probe_default_backend", "PROBE_TIMEOUT_S",
                 "_spawn_probe"):
        assert not hasattr(resilience, name), name


def test_cpu_device_is_the_host_backend():
    dev = resilience.cpu_device()
    assert dev is not None and dev.platform == "cpu"


def test_runtime_wedge_is_sticky_and_warns_once():
    assert not resilience.runtime_wedged()
    with pytest.warns(RuntimeWarning, match="execution wedged"):
        assert resilience.note_runtime_wedge("test", 2.5, level=7)
    assert resilience.runtime_wedged()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert not resilience.note_runtime_wedge("test", 2.5)  # silent repeat
    resilience._reset_wedge_for_tests()
    assert not resilience.runtime_wedged()
