"""The history gate's screen (doc/lint.md, "Pass 2"): a differential
test of :func:`screen_history` against the full linter over the lint
fixtures and a seeded mutation corpus, and the device entry points that
gate through :func:`require_well_formed`. Tier-1 (marker: lint)."""

import os
import random

import pytest

from jepsen_tpu.analysis import history_lint as hl
from jepsen_tpu.analysis.opcheck import INVALID_TYPE_FLAG
from jepsen_tpu.history import History, Op
from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.testing import simulate_register_history

pytestmark = pytest.mark.lint

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                   "lint")


def _fixture(name):
    with open(os.path.join(FIX, name)) as f:
        return History.from_jsonl(f.read())


def _ops(*rows):
    return History.of(Op(type=t, f=f, value=v, process=p, time=i)
                      for i, (t, p, f, v) in enumerate(rows))


def _register(seed, crash_p=0.0):
    return simulate_register_history(40, n_procs=4, n_vals=3, seed=seed,
                                     crash_p=crash_p)


# -- the mutation corpus ----------------------------------------------------

def _pick(rng, h, types):
    return rng.choice([i for i, o in enumerate(h) if o.type in types])


def _drop_completion(rng, h):
    del h[_pick(rng, h, ("ok", "fail", "info"))]


def _repeat_invoke(rng, h):
    i = _pick(rng, h, ("invoke",))
    h.insert(i + 1, h[i].replace())


def _invoke_on_open_process(rng, h):
    i = _pick(rng, h, ("invoke",))
    h.insert(i + 1, h[i].replace(f="read" if h[i].f != "read" else "write"))


def _change_f(rng, h):
    i = _pick(rng, h, ("ok", "fail"))
    h[i] = h[i].replace(f="read" if h[i].f != "read" else "cas")


def _illegal_type(rng, h):
    i = _pick(rng, h, ("invoke", "ok", "fail", "info"))
    h[i] = h[i].replace(type="okk")


def _flag_extra(rng, h):
    i = _pick(rng, h, ("invoke", "ok", "fail", "info"))
    h[i] = h[i].replace(extra={INVALID_TYPE_FLAG: "op type 'okk' is bad"})


def _index_backwards(rng, h):
    h.index()
    i = rng.randrange(1, len(h))
    h[i] = h[i].replace(index=rng.randrange(i))


def _raw_dicts(rng, h):
    for i in rng.sample(range(len(h)), 3):
        h[i] = h[i].to_dict()


def _stray_ok(rng, h):
    h.insert(rng.randrange(len(h)), Op(type="ok", f="read", value=1,
                                       process=99))


MUTATIONS = {
    "drop-completion": _drop_completion,
    "repeat-invoke": _repeat_invoke,
    "invoke-on-open-process": _invoke_on_open_process,
    "change-f": _change_f,
    "illegal-type": _illegal_type,
    "flag-extra": _flag_extra,
    "index-backwards": _index_backwards,
    "raw-dicts": _raw_dicts,
    "stray-ok": _stray_ok,
}

#: The mutations that always make an error finding.
ALWAYS_ERROR = {"change-f", "illegal-type", "flag-extra", "index-backwards",
                "stray-ok", "repeat-invoke", "invoke-on-open-process"}


def _mutant(name, seed):
    rng = random.Random(seed)
    h = _register(seed, crash_p=0.1 if seed % 2 else 0.0)
    MUTATIONS[name](rng, h)
    return h


# -- well-formed histories the screen must pass ------------------------------

def _crashed():
    return _register(7, crash_p=0.3)


def _open_at_end():
    h = _register(8)
    return History(h + [Op(type="invoke", f="write", value=2, process=0),
                        Op(type="invoke", f="read", process=1)])


def _bare_info():
    return _ops(("invoke", 0, "write", 1), ("ok", 0, "write", 1),
                ("info", 5, "read", None), ("invoke", 1, "read", None),
                ("info", 1, "read", None))


def _nemesis():
    h = _register(9)
    for i, f in ((3, "start"), (10, "stop"), (20, "start")):
        h.insert(i, Op(type="info", f=f, process="nemesis"))
    h.insert(25, Op(type="invoke", f="kill", process="nemesis"))
    h.insert(26, Op(type="invoke", f="heal", process="nemesis"))
    h.insert(27, Op(type="ok", f="other", process="nemesis"))
    return h


def _invoke_no_f():
    return _ops(("invoke", 0, None, 1), ("ok", 0, "write", 1),
                ("invoke", 1, "read", None), ("ok", 1, None, 1))


def _indexed():
    return _register(10).index()


def _plain_extra():
    h = _register(11)
    h[4] = h[4].replace(extra={"node": "n1"})
    return h


def _decode_errors():
    h = _ops(("invoke", 0, "write", 1), ("ok", 0, "write", 1))
    h.decode_errors = 2
    return h


WELL_FORMED = {
    "register": lambda: _register(3),
    "crashed-infos": _crashed,
    "open-invokes-at-end": _open_at_end,
    "bare-info": _bare_info,
    "nemesis": _nemesis,
    "invoke-no-f": _invoke_no_f,
    "indexed": _indexed,
    "plain-extra": _plain_extra,
    "decode-errors": _decode_errors,
    "good-fixture": lambda: _fixture("good_history.jsonl"),
    "empty": History,
    "tuple": lambda: tuple(_register(12)),
}

#: The malformed fixtures of tests/test_lint.py.
FIXTURES = {
    "bad-fixture": lambda: _fixture("bad_history.jsonl"),
    "dangling-invoke": lambda: _ops(("invoke", 0, "write", 1),
                                    ("invoke", 0, "read", None),
                                    ("ok", 0, "read", 1)),
    "unmatched-complete": lambda: _ops(("ok", 0, "read", 1)),
    "f-mismatch": lambda: _ops(("invoke", 0, "write", 1),
                               ("ok", 0, "read", 1)),
    "okk-from-jsonl": lambda: History.from_jsonl(
        '{"type": "invoke", "f": "read", "process": 0}\n'
        '{"type": "okk", "f": "read", "process": 0}\n'),
}


def _gate_outcome(gate, h):
    """The findings a gate raised, or None when it passed."""
    try:
        gate(h, where="the test")
    except hl.MalformedHistoryError as e:
        return e.findings, str(e)
    return None


def _exact_domain(h):
    """Every row an Op with an int index and a dict or no extra: where
    the screen must say exactly whether an error finding exists."""
    return all(type(o) is Op and type(o.index) is int
               and (o.extra is None or type(o.extra) is dict) for o in h)


def _differential(h):
    passed = hl.screen_history(h)
    errs = hl.errors(hl.lint_history(h))
    if passed:
        assert errs == []
    if _exact_domain(h):
        assert passed == (errs == [])
    assert _gate_outcome(hl.require_well_formed, h) == \
        _gate_outcome(hl.gate_history, h)
    return passed, errs


@pytest.mark.parametrize("name", sorted(MUTATIONS))
@pytest.mark.parametrize("seed", range(6))
def test_screen_agrees_with_the_linter_on_mutants(name, seed):
    passed, errs = _differential(_mutant(name, seed))
    if name in ALWAYS_ERROR:
        assert errs and not passed
    if name == "raw-dicts":
        assert not passed  # a row that is not an Op is doubt


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_screen_refuses_every_malformed_fixture(name):
    passed, errs = _differential(FIXTURES[name]())
    assert errs and not passed


@pytest.mark.parametrize("name", sorted(WELL_FORMED))
def test_screen_passes_well_formed_histories(name):
    h = WELL_FORMED[name]()
    assert hl.screen_history(h)
    assert _differential(h) == (True, [])


@pytest.mark.parametrize("doubt", ["generator", "non-int-index",
                                   "list-extra", "op-subclass"])
def test_doubt_defers_to_the_linter(doubt):
    h = _register(4)
    if doubt == "generator":
        h = (o for o in h)
    elif doubt == "non-int-index":
        h[2] = h[2].replace(index="2")
    elif doubt == "list-extra":
        h[2] = h[2].replace(extra=[])
    else:
        class Sub(Op):
            __slots__ = ()
        h[2] = Sub(**{k: getattr(h[2], k) for k in Op.__slots__})
    assert not hl.screen_history(h)
    if doubt != "generator":  # lint_history would drain it
        assert hl.errors(hl.lint_history(h)) == []
        assert hl.require_well_formed(h) == "lint"


# -- the device entry points -------------------------------------------------

def _gate_count(path):
    return obs_metrics.counter("jtpu_history_gate_total").value(path=path)


def _lint_spans():
    from jepsen_tpu import obs
    return [s for s in obs.tracer().spans() if s["name"] == "checker.lint"]


def test_keyed_malformed_key_is_unknown_and_never_packed(monkeypatch):
    from jepsen_tpu.checker import tpu
    from jepsen_tpu.models import CASRegister
    packed = []
    real_pack = tpu.pack_with_init

    def spy(h, *a, **k):
        packed.append(h)
        return real_pack(h, *a, **k)

    monkeypatch.setattr(tpu, "pack_with_init", spy)
    good = _ops(("invoke", 0, "write", 1), ("ok", 0, "write", 1),
                ("invoke", 1, "read", None), ("ok", 1, "read", 1))
    stale = _ops(("invoke", 0, "write", 1), ("ok", 0, "write", 1),
                 ("invoke", 1, "read", None), ("ok", 1, "read", 2))
    bad = _ops(("ok", 0, "read", 1))
    screened, linted = _gate_count("screen"), _gate_count("lint")
    out = tpu.check_keyed_tpu({"g": good, "s": stale, "b": bad},
                              CASRegister())
    assert out["results"]["g"]["valid"] is True
    assert out["results"]["s"]["valid"] is False
    assert out["results"]["b"]["valid"] == "unknown"
    assert out["results"]["b"]["lint"] == {"HIST-UNMATCHED-COMPLETE": 1}
    assert out["results"]["b"]["error"] == str(
        pytest.raises(hl.MalformedHistoryError, hl.gate_history, bad,
                      where="the keyed device search (key 'b')").value)
    assert all(h is not bad for h in packed) and len(packed) == 2
    assert _gate_count("screen") - screened == 2
    assert _gate_count("lint") - linted == 1
    assert _lint_spans()[-1]["path"] == "lint"


def test_single_malformed_history_raises_as_before():
    from jepsen_tpu.checker.tpu import check_history_tpu
    from jepsen_tpu.models import CASRegister
    bad = FIXTURES["dangling-invoke"]()
    linted = _gate_count("lint")
    with pytest.raises(hl.MalformedHistoryError) as ei:
        check_history_tpu(bad, CASRegister())
    with pytest.raises(hl.MalformedHistoryError) as want:
        hl.gate_history(bad, where="the packed device search")
    assert ei.value.findings == want.value.findings
    assert str(ei.value) == str(want.value)
    assert _gate_count("lint") - linted == 1


def test_clean_history_takes_the_screen():
    from jepsen_tpu.checker.tpu import check_history_tpu
    from jepsen_tpu.models import CASRegister
    screened, linted = _gate_count("screen"), _gate_count("lint")
    out = check_history_tpu(_register(5), CASRegister())
    assert out["valid"] is True
    assert _gate_count("screen") - screened == 1
    assert _gate_count("lint") == linted
    assert _lint_spans()[-1]["path"] == "screen"


def test_kill_switch_runs_neither_walk(monkeypatch):
    from jepsen_tpu.checker import tpu
    from jepsen_tpu.models import CASRegister

    def boom(*a, **k):
        raise AssertionError("a history walk ran with the gate off")

    monkeypatch.setenv("JTPU_HISTORY_GATE", "0")
    monkeypatch.setattr(hl, "screen_history", boom)
    monkeypatch.setattr(hl, "lint_history", boom)
    bad = FIXTURES["unmatched-complete"]()
    assert hl.require_well_formed(bad) == "off"
    assert hl.gate_history(bad) == []
    good = _register(6)
    assert tpu.check_history_tpu(good, CASRegister())["valid"] is True
    out = tpu.check_keyed_tpu({"k": good}, CASRegister())
    assert out["results"]["k"]["valid"] is True
    assert _lint_spans()[-1]["path"] == "off"
