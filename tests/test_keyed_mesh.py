"""The keyed batch on a keys mesh (``check_keyed_tpu(..., mesh=...)``) on
the virtual CPU devices of conftest: per-key results equal to the
unsharded batch's and to the host WGL search's, crash-width cohorts on
the mesh, no collective inside the compiled search, and the per-chip
level counters and the ``checker.place`` span."""

import threading

import numpy as np
import pytest

import __graft_entry__ as g
from jepsen_tpu import obs, parallel
from jepsen_tpu.checker.engine import Engine
from jepsen_tpu.checker.tpu import (_COLS, _MESH_KEY_STEP, _kernel_key,
                                    _mesh_rows, check_keyed_tpu)
from jepsen_tpu.checker.wgl import check_model
from jepsen_tpu.models import CASRegister
from jepsen_tpu.models.core import kernel_spec_for
from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.testing import simulate_register_history

#: A slim rung and an escalation rung: the pool buster is UNKNOWN on the
#: first and refuted on the second.
LADDER = ((8, 16, 4), (256, 16, 64))

_UNSHARDED = {}


def _keyed(n_keys, crashy):
    """``n_keys`` keys: a refuted key, a key that escalates past the
    first rung, and linearizable keys, every third of them with crashed
    ops when ``crashy`` (cohorts of crash widths 0 and 8)."""
    keyed = {"refuted": g._stale_read_history(),
             "escalates": g._pool_buster_history()}
    for i in range(n_keys - 2):
        crash_p = 0.1 if crashy and i % 3 == 0 else 0.0
        keyed[f"k{i}"] = simulate_register_history(
            24, n_procs=4, n_vals=5, seed=100 + i, cas_p=0.33,
            crash_p=crash_p, overlap_p=0.3)
    return keyed


def _unsharded(n_keys, crashy):
    if (n_keys, crashy) not in _UNSHARDED:
        keyed = _keyed(n_keys, crashy)
        _UNSHARDED[n_keys, crashy] = (
            keyed, check_keyed_tpu(keyed, CASRegister(), ladder=LADDER))
    return _UNSHARDED[n_keys, crashy]


def _view(r):
    return (r["valid"], r.get("levels"), r.get("best"), r.get("rung"),
            r.get("crash-width"), r.get("final-states"))


@pytest.mark.parametrize("crashy", [False, True], ids=["w0", "w0+w8"])
@pytest.mark.parametrize("n_keys", [5, 13, 34])
@pytest.mark.parametrize("chips", [2, 4])
def test_mesh_results_equal_unsharded_and_host(chips, n_keys, crashy):
    keyed, want = _unsharded(n_keys, crashy)
    got = check_keyed_tpu(keyed, CASRegister(), ladder=LADDER,
                          mesh=parallel.make_mesh(chips))
    widths = {r["crash-width"] for r in want["results"].values()}
    assert widths == ({0, 8} if crashy else {0})
    assert len(got["devices"]) == chips
    assert got["valid"] is want["valid"] is False
    assert set(got["results"]) == set(keyed)
    for k, h in keyed.items():
        g_, w = got["results"][k], want["results"][k]
        assert _view(g_) == _view(w), k
        assert g_["valid"] is check_model(h, CASRegister())["valid"], k
    assert got["results"]["refuted"]["valid"] is False
    assert got["results"]["escalates"]["rung"] == LADDER[1]
    assert got["results"]["refuted"]["final-states"]


def test_mesh_batch_compiles_without_collectives():
    """Each device runs its own loop: the compiled mesh batch holds no
    cross-device collective, in the loop's condition, its body or
    anywhere else."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = parallel.make_mesh(4)
    kernel = kernel_spec_for(CASRegister())
    fn = Engine("no-collectives").jit_batch(
        _kernel_key(kernel), 8, 16, 4, mesh=mesh, axis="keys")
    sh = NamedSharding(mesh, P("keys"))
    shapes = {c: (8, 32) for c in _COLS}
    shapes.update(sm=(8, 33), cf=(8, 8), cv1=(8, 8), cv2=(8, 8),
                  cinv=(8, 8), cps=(8, 8), nr=(8,), ini=(8,))
    args = [jax.ShapeDtypeStruct(shapes[c], np.int32, sharding=sh)
            for c in _COLS]
    hlo = fn.lower(*args).compile().as_text()
    assert "while" in hlo
    for op in ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter"):
        assert op not in hlo, op


def _levels():
    chip = obs_metrics.counter("jtpu_keyed_chip_levels_total")
    return {"search": obs_metrics.counter("jtpu_search_levels_total").total(),
            "chips": [chip.value(chip=str(i)) for i in range(4)]}


def test_chip_counters_and_place_span():
    keyed = {f"k{i}": simulate_register_history(
        24 + 6 * i, n_procs=4, n_vals=5, seed=7 + i, overlap_p=0.3)
        for i in range(6)}
    ladder = (LADDER[1],)
    before = _levels()
    one = check_keyed_tpu(keyed, CASRegister(), ladder=ladder)
    mid = _levels()
    levels = [one["results"][k]["levels"] for k in keyed]
    # off a mesh: one chip, the launch's maximum on both counters
    assert mid["search"] - before["search"] == max(levels)
    assert mid["chips"][0] - before["chips"][0] == max(levels)

    with obs.span("test.mark") as mark:
        pass
    out = check_keyed_tpu(keyed, CASRegister(), ladder=ladder,
                          mesh=parallel.make_mesh(4))
    after = _levels()
    assert [out["results"][k]["levels"] for k in keyed] == levels
    # 6 keys over 4 chips as 2, 2, 1 and 1, each chip's share padded to
    # 4 rows with trivially complete ones
    per_chip = [max(levels[0:2]), max(levels[2:4]), levels[4], levels[5]]
    assert [a - b for a, b in zip(after["chips"], mid["chips"])] == per_chip
    # the launch lasted as long as the slowest chip: the search counter
    # keeps the launch's maximum, so 4 chips were held for 4x its levels
    assert after["search"] - mid["search"] == max(levels)
    assert 4 * max(levels) >= sum(per_chip)
    me = threading.get_ident()
    places = [r for r in obs.tracer().spans()
              if r["sid"] > mark.sid and r["tid"] == me
              and r["name"] == "checker.place"]
    assert [(p["keys"], p["chips"], p["pad"]) for p in places] == [(6, 4, 10)]


@pytest.mark.parametrize("n,chips", [(1, 4), (6, 4), (13, 2), (91, 4),
                                     (102, 4), (45, 4), (34, 4)])
def test_mesh_rows_split_evenly_on_few_sizes(n, chips):
    rows, size = _mesh_rows(n, chips)
    per = size // chips
    assert size == chips * per and per % _MESH_KEY_STEP == 0
    assert per - _MESH_KEY_STEP < -(-n // chips) <= per
    # every key a row of its own, in key order, the chips' shares
    # differing by at most one key
    assert len(rows) == n and list(rows) == sorted(set(rows))
    assert rows.max() < size
    shares = np.bincount(rows // per, minlength=chips)
    assert shares.max() - shares.min() <= 1
