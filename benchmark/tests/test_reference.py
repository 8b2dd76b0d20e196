"""The plain reference against hand-made cases and, on seeded
histories, against the system's own host engine."""

import pytest

from harness.traffic import register_history, stale_read
from reference import cas_register as ref


def h(*rows):
    return list(rows)


def test_sequential_cases():
    assert ref.check(h(("invoke", 0, "write", 1), ("ok", 0, "write", 1),
                       ("invoke", 1, "read", None), ("ok", 1, "read", 1)))
    # a read that sees an overwritten value after the overwrite returned
    assert not ref.check(h(("invoke", 0, "write", 1), ("ok", 0, "write", 1),
                           ("invoke", 0, "write", 2), ("ok", 0, "write", 2),
                           ("invoke", 1, "read", None), ("ok", 1, "read", 1)))
    # cas needs the old value; a failed op did not happen
    assert not ref.check(h(("invoke", 0, "cas", (1, 2)),
                           ("ok", 0, "cas", (1, 2))))
    assert ref.check(h(("invoke", 0, "cas", (1, 2)),
                       ("fail", 0, "cas", (1, 2))))
    # a read of nil constrains nothing
    assert ref.check(h(("invoke", 0, "write", 3), ("ok", 0, "write", 3),
                       ("invoke", 1, "read", None), ("ok", 1, "read", None)))


def test_concurrency_and_indeterminate_ops():
    # a read concurrent with a write may see either value
    assert ref.check(h(("invoke", 0, "write", 1), ("ok", 0, "write", 1),
                       ("invoke", 0, "write", 2), ("invoke", 1, "read", None),
                       ("ok", 1, "read", 1), ("ok", 0, "write", 2)))
    # an indeterminate write may take effect long after it was invoked
    assert ref.check(h(("invoke", 0, "write", 5), ("info", 0, "write", 5),
                       ("invoke", 1, "write", 1), ("ok", 1, "write", 1),
                       ("invoke", 1, "read", None), ("ok", 1, "read", 5)))
    # ... but only once
    assert not ref.check(h(("invoke", 0, "write", 5), ("info", 0, "write", 5),
                           ("invoke", 1, "write", 1), ("ok", 1, "write", 1),
                           ("invoke", 1, "read", None), ("ok", 1, "read", 5),
                           ("invoke", 1, "write", 1), ("ok", 1, "write", 1),
                           ("invoke", 1, "read", None), ("ok", 1, "read", 5)))


@pytest.mark.parametrize("overlap_p,crash_p", [(0.6, 0.01), (0.05, 0.0)])
def test_agrees_with_the_systems_host_engine(overlap_p, crash_p):
    from drivers.common import to_history
    from jepsen_tpu.checker.native import available, check_history_native
    from jepsen_tpu.models import CASRegister
    if not available():
        pytest.skip("the system's native engine did not build here")
    refuted = 0
    for seed in range(12):
        rows = register_history(400, 5, 6, seed=seed, crash_p=crash_p,
                                overlap_p=overlap_p)
        twin = stale_read(rows, lambda r: not ref.check(r))
        for hist in (rows, twin):
            want = check_history_native(to_history(hist),
                                        CASRegister())["valid"]
            got = ref.check(hist)
            assert got is want
            refuted += got is False
    assert refuted == 12
