"""The persistent compilation cache is placeable from outside:
JAX_COMPILATION_CACHE_DIR when set (and no other directory), else the
checkout's fixed .jax_cache/. The search's Engine sets it up, so the
plain checker facade gets it with no entry-point call. Each case runs a
fresh interpreter over a copy of the package, so the real checkout's
cache is never touched."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROGRAM = (
    "import jax\n"
    "from jepsen_tpu.checker.wgl import linearizable\n"
    "from jepsen_tpu.models import CASRegister\n"
    "from jepsen_tpu.testing import simulate_register_history\n"
    "h = simulate_register_history(30, n_procs=3, seed=1)\n"
    "r = linearizable(CASRegister(), backend='tpu').check({}, h)\n"
    "assert r['valid'] is True, r\n"
    "print('CACHE', jax.config.jax_compilation_cache_dir)\n")


@pytest.fixture
def checkout(tmp_path):
    co = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "jepsen_tpu"), co / "jepsen_tpu",
                    ignore=shutil.ignore_patterns("_build", "__pycache__"))
    return co


def _run(co, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE")}
    env.update(PYTHONPATH=str(co), JAX_PLATFORMS="cpu", **env_over)
    pr = subprocess.run([sys.executable, "-c", _PROGRAM], cwd=co, env=env,
                        capture_output=True, text=True, timeout=120)
    assert pr.returncode == 0, pr.stderr[-800:]
    return pr.stdout.split("CACHE ", 1)[1].split("\n", 1)[0]


def _entries(d):
    return [f for f in os.listdir(d)] if os.path.isdir(d) else []


def test_env_dir_is_used_and_nothing_else(checkout, tmp_path):
    target = tmp_path / "elsewhere"
    assert _run(checkout, JAX_COMPILATION_CACHE_DIR=str(target)) \
        == str(target)
    assert _entries(target)
    assert not (checkout / ".jax_cache").exists()


def test_default_is_the_fixed_in_checkout_dir(checkout):
    used = _run(checkout)
    assert used == str(checkout / ".jax_cache")
    assert _entries(used)
    # the same path every time: a later process finds the entries
    assert _run(checkout) == used


def test_no_code_names_a_tmp_cache():
    hits = []
    for root, _, files in os.walk(os.path.join(REPO, "jepsen_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    if "jepsen_tpu_jit_cache" in fh.read():
                        hits.append(f)
    assert not hits


def test_daemon_reports_the_cache_it_configured(tmp_path, monkeypatch):
    import jax

    from jepsen_tpu import serve
    from jepsen_tpu.checker import engine as engine_mod
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xc"))
    monkeypatch.setattr(engine_mod, "_DEFAULT", None)  # a fresh engine
    try:
        d = serve.CheckDaemon(serve.ServeConfig(root=str(tmp_path / "s")))
        assert d.engine.compile_cache == str(tmp_path / "xc")
        assert d.healthz()["engine"]["persistent-cache"] == str(
            tmp_path / "xc")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
