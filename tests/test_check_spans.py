"""Layer spans of a device check: one ``checker.search`` root per check
of either entry point (``check_history_tpu``, ``check_keyed_tpu``), with
the history gate (``checker.lint``), packing (``checker.pack``),
planning (``checker.plan``), the device calls and the keyed readback
(``checker.readback``) nested under it; a span count that does not grow
with the keys; and the ``JTPU_TRACE=0`` kill switch. Tier-1 under the
``obs`` marker (doc/observability.md "Span catalog")."""

import collections
import threading

import pytest

from jepsen_tpu import obs
from jepsen_tpu.checker.tpu import (check_history_sharded,
                                    check_history_tpu, check_keyed_tpu)
from jepsen_tpu.history import History, Op
from jepsen_tpu.models import CASRegister
from jepsen_tpu.testing import simulate_register_history

pytestmark = pytest.mark.obs

LAYERS = ("checker.lint", "checker.pack", "checker.plan")
#: A small explicit keyed rung: cheap to compile, no escalation.
LADDER = ((64, 32, 8),)


def _traced(fn):
    """``fn()``'s result and the ``checker.*`` spans this thread recorded
    while it ran, in the order they closed."""
    with obs.span("test.mark") as mark:
        pass
    out = fn()
    me = threading.get_ident()
    recs = [r for r in obs.tracer().spans()
            if r["sid"] > mark.sid and r["tid"] == me
            and r["name"].startswith("checker.")]
    return out, recs


def _root_of(rec, by_sid):
    while rec.get("pid") in by_sid:
        rec = by_sid[rec["pid"]]
    return rec


def _assert_nested(recs, device_span):
    """One root, every other span under it and inside its interval, and
    the layers first met in the order gate, pack, plan, device."""
    roots = [r for r in recs if r["name"] == "checker.search"]
    assert len(roots) == 1
    (root,) = roots
    by_sid = {r["sid"]: r for r in recs}
    for r in recs:
        assert _root_of(r, by_sid) is root
        assert root["ts"] <= r["ts"]
        assert r["ts"] + r["dur"] <= root["ts"] + root["dur"]
    first = {}
    for r in sorted(recs, key=lambda r: r["ts"]):
        first.setdefault(r["name"], r["ts"])
    order = list(LAYERS) + [device_span]
    assert all(n in first for n in order), sorted(first)
    assert [first[n] for n in order] == sorted(first[n] for n in order)
    # the layer spans leave the serve phase accounting alone
    assert all("phase" not in r for r in recs
               if r["name"] in LAYERS + ("checker.search",
                                         "checker.readback"))
    return root


def _history(seed=3, n=150):
    return simulate_register_history(n, n_procs=5, n_vals=4, seed=seed)


def test_segmented_single_check_has_one_root():
    h = _history()
    out, recs = _traced(lambda: check_history_tpu(
        h, CASRegister(), capacity=64, expand=8, segment_iters=8))
    assert out["valid"] is True
    root = _assert_nested(recs, "checker.segment")
    assert (root["kind"], root["ops"], root["keys"]) == ("single", len(h), 1)


def test_monolithic_single_check_has_one_root():
    # the default segment length covers this search in one device call
    h = _history(seed=4)
    out, recs = _traced(lambda: check_history_tpu(h, CASRegister()))
    assert out["valid"] is True and out["segments"] == 1
    _assert_nested(recs, "checker.segment")
    assert sum(r["name"] == "checker.segment" for r in recs) == 1


def test_sharded_single_check_has_one_root():
    from jepsen_tpu import parallel
    h = _history(seed=5, n=60)
    mesh = parallel.make_mesh(2, axis="pool")
    out, recs = _traced(lambda: check_history_sharded(
        h, CASRegister(), mesh, capacity=64, expand=8))
    assert out["valid"] is True
    roots = [r for r in recs if r["name"] == "checker.search"]
    assert len(roots) == 1 and roots[0]["kind"] == "single"
    by_sid = {r["sid"]: r for r in recs}
    assert {r["name"] for r in recs} >= {"checker.lint", "checker.pack",
                                         "checker.device.sharded"}
    assert all(_root_of(r, by_sid) is roots[0] for r in recs)


def test_keyed_check_has_one_root_and_readback():
    keyed = {k: _history(seed=500 + k, n=60) for k in range(4)}
    out, recs = _traced(lambda: check_keyed_tpu(keyed, CASRegister(),
                                                ladder=LADDER))
    assert out["valid"] is True
    root = _assert_nested(recs, "checker.device.batch")
    assert root["kind"] == "keyed" and root["keys"] == 4
    assert root["ops"] == sum(len(h) for h in keyed.values())
    (lint,) = [r for r in recs if r["name"] == "checker.lint"]
    assert (lint["keys"], lint["ops"]) == (4, root["ops"])
    batch = [r for r in recs if r["name"] == "checker.device.batch"]
    back = [r for r in recs if r["name"] == "checker.readback"]
    assert len(back) == len(batch)
    # each readback follows its batch call
    assert all(b["ts"] >= d["ts"] + d["dur"] for d, b in zip(
        sorted(batch, key=lambda r: r["ts"]),
        sorted(back, key=lambda r: r["ts"])))


def test_keyed_refutation_reads_the_pools_back():
    h = History.of([
        Op(type="invoke", f="write", value=1, process=0, time=0),
        Op(type="ok", f="write", value=1, process=0, time=1),
        Op(type="invoke", f="read", value=None, process=1, time=2),
        Op(type="ok", f="read", value=2, process=1, time=3)])
    keyed = {"stale": h, "fine": _history(seed=7, n=40)}
    out, recs = _traced(lambda: check_keyed_tpu(keyed, CASRegister(),
                                                ladder=LADDER))
    assert out["results"]["stale"]["valid"] is False
    assert any(r.get("pools") for r in recs
               if r["name"] == "checker.readback")


def test_keyed_span_count_does_not_grow_with_keys():
    h = _history(seed=11, n=60)
    counts = {}
    for n in (4, 16):
        out, recs = _traced(lambda: check_keyed_tpu(
            {k: h for k in range(n)}, CASRegister(), ladder=LADDER))
        assert out["valid"] is True
        counts[n] = collections.Counter(r["name"] for r in recs)
    assert counts[4] == counts[16]
    assert counts[4]["checker.lint"] == counts[4]["checker.pack"] == 1


def test_malformed_key_stays_unknown_after_the_loop_split():
    good = History.of([
        Op(type="invoke", f="write", value=1, process=0, time=0),
        Op(type="ok", f="write", value=1, process=0, time=1)])
    bad = History.of([Op(type="ok", f="read", value=1, process=0, time=0)])
    out, recs = _traced(lambda: check_keyed_tpu(
        {"g": good, "b": bad, "h": _history(seed=8, n=30)},
        CASRegister(), ladder=LADDER))
    assert out["results"]["b"]["valid"] == "unknown"
    assert out["results"]["b"]["lint"] == {"HIST-UNMATCHED-COMPLETE": 1}
    assert out["results"]["g"]["valid"] is True
    assert out["results"]["h"]["valid"] is True
    assert out["valid"] == "unknown"
    (lint,) = [r for r in recs if r["name"] == "checker.lint"]
    (pack,) = [r for r in recs if r["name"] == "checker.pack"]
    # the malformed key is gated out before the pack pass
    assert lint["keys"] == 3 and pack["keys"] == 2


def test_kill_switch_records_nothing_and_keeps_verdicts(monkeypatch):
    h = _history(seed=9, n=120)
    keyed = {k: _history(seed=600 + k, n=50) for k in range(3)}

    def both():
        single = check_history_tpu(h, CASRegister(), segment_iters=16)
        batch = check_keyed_tpu(keyed, CASRegister(), ladder=LADDER)
        return single, batch

    monkeypatch.setenv("JTPU_TRACE", "1")
    s1, b1 = both()
    monkeypatch.setenv("JTPU_TRACE", "0")
    before = obs.tracer().recorded
    s0, b0 = both()
    assert obs.tracer().recorded == before
    assert (s1["valid"], s1["levels"]) == (s0["valid"], s0["levels"])
    assert b1["valid"] == b0["valid"]
    assert {k: (r["valid"], r["levels"]) for k, r in b1["results"].items()} \
        == {k: (r["valid"], r["levels"]) for k, r in b0["results"].items()}
