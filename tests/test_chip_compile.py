"""The chip's compiler accepts the search executables at real shapes.

Compiles for ONE chip of a described (not attached) TPU v5e topology:
the single-key search at the 10k-op headline shape on the two rungs the
headline climbs through, the segmented step the supervised default path
runs, and the vmapped keyed batch at the 50 keys x 200 ops config; and
for all four chips of the host, the keys-mesh batch at the four-chip
cell's shape (136 keys of 300 ops), with no collective in it. No
chip time, nothing runs: a compile that passes is not a chip run. The
(4096, 256) rung (~40 s) and the top rung stay out.

The topology is described inside a fixture (never at import), so every
xdist worker collects the same tests and only the worker given this file
loads the TPU library.
"""

import os

import numpy as np
import pytest

#: TPU v5e HBM per chip (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache
    # but never read back here: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _headline_cols():
    from jepsen_tpu.checker import tpu as T
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.ops.encode import pack_with_init
    from jepsen_tpu.testing import simulate_register_history
    h = simulate_register_history(10_000, n_procs=5, n_vals=16, seed=42,
                                  crash_p=0.002)
    p, kernel = pack_with_init(h, CASRegister())
    cols = T._split_packed(p, T._bucket(p.n_required),
                           T._crash_width(p.n - p.n_required), kernel)
    return cols, kernel


def _keyed_cols():
    from jepsen_tpu.checker import tpu as T
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.models.core import kernel_spec_for
    from jepsen_tpu.ops.encode import pack_with_init
    from jepsen_tpu.testing import simulate_register_history
    packed = [pack_with_init(simulate_register_history(
                  200, n_procs=5, n_vals=8, seed=1000 + k, crash_p=0.002),
                  CASRegister())[0] for k in range(50)]
    kernel = kernel_spec_for(CASRegister())
    breq = T._bucket(max(p.n_required for p in packed))
    crw = max(T._crash_width(p.n - p.n_required) for p in packed)
    rows = [T._split_packed(p, breq, crw, kernel) for p in packed]
    return {c: np.stack([r[c] for r in rows]) for c in T._COLS}, kernel


def _fits(compiled) -> None:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, m


@pytest.mark.parametrize("cap,exp", [(128, 8), (1024, 64)])
def test_segmented_step_compiles_at_headline_shape(one_chip, cap, exp):
    # the chip ladder's first two rungs (CAPACITY_LADDER)
    from jepsen_tpu.checker import tpu as T
    from jepsen_tpu.checker.engine import Engine
    cols, kernel = _headline_cols()
    fn = Engine("chip-compile").jit_segment(
        T._kernel_key(kernel), cap, T.WINDOW, exp, stats=True)
    lmax = T._level_budget(cols["f"].shape[0], cols["cf"].shape[0])
    carry = T._carry0_host(cap, T.WINDOW, cols["cf"].shape[0], cols["ini"],
                           cols["nr"], stats_rows=lmax + 1)
    args = _abstract([cols[c] for c in T._COLS]
                     + [np.int32(T.DEFAULT_SEGMENT_ITERS), carry], one_chip)
    _fits(fn.lower(*args).compile())


def test_keyed_batch_compiles_at_config_shape(one_chip):
    # the keyed ladder's slim entry rung (checker/tpu.py check_keyed_tpu:
    # hash tie-break, 2 steps per iteration); a vmapped batch compiles
    # ~10x slower than the single search, so its later rungs stay out
    from jepsen_tpu.checker import tpu as T
    from jepsen_tpu.checker.engine import Engine
    cols, kernel = _keyed_cols()
    cap, exp = T.CAPACITY_LADDER[0]
    fn = Engine("chip-compile").jit_batch(
        T._kernel_key(kernel), cap, T.WINDOW, exp, 2, tiebreak="hash")
    args = _abstract([cols[c] for c in T._COLS], one_chip)
    _fits(fn.lower(*args).compile())


def test_keyed_mesh_batch_compiles_for_four_chips(topo):
    # the four-chip cell's crash-free cohort (84-95 of its 136 keys of
    # 300 ops, laid out as 24 rows a chip) on the slim entry rung: each
    # chip's own loop, so the chip's compiler puts no collective anywhere
    # in the program
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jepsen_tpu.checker import tpu as T
    from jepsen_tpu.checker.engine import Engine
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.models.core import kernel_spec_for
    mesh = Mesh(np.array(topo.devices), ("keys",))
    kernel = kernel_spec_for(CASRegister())
    cap, exp = T.CAPACITY_LADDER[0]
    fn = Engine("chip-compile").jit_batch(
        T._kernel_key(kernel), cap, T.WINDOW, exp, 2, tiebreak="hash",
        mesh=mesh, axis="keys")
    keys, breq = 96, T._bucket(300)
    shapes = {c: (keys, breq) for c in T._COLS}
    shapes.update(sm=(keys, breq + 1), nr=(keys,), ini=(keys,),
                  **{c: (keys, 0) for c in ("cf", "cv1", "cv2", "cinv",
                                            "cps")})
    sh = NamedSharding(mesh, P("keys"))
    args = [jax.ShapeDtypeStruct(shapes[c], np.int32, sharding=sh)
            for c in T._COLS]
    compiled = fn.lower(*args).compile()
    _fits(compiled)
    hlo = compiled.as_text()
    for op in ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter"):
        assert op not in hlo, op
