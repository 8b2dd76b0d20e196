"""The control: the reference with linearizability's real-time order
relaxed (every return moved ``CONTROL_SLACK`` events later), put in the
system's place through the harness's own run. It has to come out not
correct."""

import pytest

from conftest import TINY
from harness import spec, traffic
from control_readings import readings
from reference import cas_register as ref


@pytest.mark.parametrize("cell", sorted(TINY))
@pytest.mark.parametrize("seed", [7, 2**31 + 11, 4_000_000_123])
def test_control_is_not_correct(cell, seed):
    r = readings(cell, seed, TINY[cell])
    assert r["correct"] is False
    assert r["control_wrong_verdicts"] >= 1


def test_seed_changes_labels_not_work():
    params = spec.mix_params(spec.find_cell("etcd-10k.staggered"))
    params.update(TINY["etcd-10k.staggered"])
    a = traffic.make_pool(params, 1, lambda r: not ref.check(r))
    b = traffic.make_pool(params, 2, lambda r: not ref.check(r))
    assert sorted(i.base for i in a) == sorted(i.base for i in b)
    assert [i.histories for i in a] != [i.histories for i in b]
    by_base = {i.base: i for i in b}
    for i in a:
        ha, = i.histories.values()
        hb, = by_base[i.base].histories.values()
        assert [r[0] for r in ha] == [r[0] for r in hb]
        assert ref.check(ha) is ref.check(hb)
    assert traffic.make_pool(params, 1, lambda r: not ref.check(r))[0] \
        .histories == a[0].histories
