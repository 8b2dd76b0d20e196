"""The trace reduction: interval arithmetic, self times, and a recorded
TPU v5e trace (one staggered check, cut to 8 ms of device activity)."""

import gzip
import json
import os

import pytest

from harness import levelbytes, peaks, profile

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_staggered_check.trace.json.gz")


def test_union_gaps_clip():
    u = profile.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert profile.gaps(u, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert profile.clip(u, 1, 6) == [(1, 3), (5, 6)]
    assert profile.total(u) == 6


def test_self_times_nest():
    evs = [(0, 10, "while"), (1, 4, "sort"), (2, 3, "cmp"), (5, 9, "fusion")]
    st = profile.self_times(evs)
    assert st == {"while": 3, "sort": 2, "cmp": 1, "fusion": 4}
    assert sum(st.values()) == 10


def test_op_name():
    assert profile.op_name("%fusion.41 = (s32[8]) fusion(...)") == "fusion.41"
    assert profile.op_name("while.87") == "while.87"


def _fixture():
    with gzip.open(FIXTURE, "rt") as f:
        fx = json.load(f)
    tr = profile.Trace(
        devices={d: [tuple(e) for e in evs]
                 for d, evs in fx["devices"].items()},
        host=[tuple(h) for h in fx["host"]], window=tuple(fx["window"]))
    return fx, tr


def test_recorded_trace():
    fx, tr = _fixture()
    r = profile.reduce(tr)
    (dev,) = r.busy_s
    want = fx["expect"]
    assert r.busy_s[dev] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r.idle_share == pytest.approx(want["idle_share"], rel=1e-9)
    # self times partition the busy time when operations nest
    assert sum(r.op_self_s.values()) == pytest.approx(r.busy_s[dev],
                                                      rel=1e-6)
    top = sorted(r.op_self_s.items(), key=lambda kv: -kv[1])[:5]
    assert [n for n, _ in top] == [n for n, _ in want["top_ops"]]
    assert 0 < r.idle_share < 1 and r.window_s > r.busy_s[dev]
    assert r.gaps[0][0] == "bench.window"


def test_host_spans_label_gaps():
    fx, tr = _fixture()
    lo, _ = tr.window
    first_op = min(e[0] for e in next(iter(tr.devices.values())))
    span = (lo, first_op, "checker.pack")
    r = profile.reduce(tr, [span])
    assert r.gaps[0][0].endswith("checker.pack")


def test_to_profiler_clock():
    tr = profile.Trace(devices={}, host=[], sync_ns=1000.0,
                       sync_mono_ns=50)
    assert profile.to_profiler_clock(tr, [(60, 70, "x")]) == \
        [(1010.0, 1020.0, "x")]
    assert profile.to_profiler_clock(profile.Trace({}, []), [(1, 2, "x")]) \
        == []


def test_no_device_no_reduction():
    tr = profile.Trace(devices={}, host=[(0, 5, "bench.check")],
                       window=(0, 5))
    assert profile.reduce(tr) is None


def test_level_bytes_and_peaks():
    # (128, 32, 8) with 8 indeterminate slots: 5 words a row
    assert levelbytes.row_words(32, 8) == 5
    cands = 8 * 40
    assert levelbytes.level_bytes(128, 32, 8, 8) == \
        4 * (5 * (2 * 128 + cands) + 2 * 4 * (128 + cands))
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu", "hbm_bytes_per_s")
