"""Elastic fleet layer (jepsen_tpu.fleet): pool split/merge surgery at
the merge-sort barrier, host-loss re-meshing, work-stealing rebalance,
join admission, the DCN failure class, checkpoint resume across a
CHANGED mesh size, the JTPU_FLEET kill switch, and the obs/fleet.py
dead-host tolerance + watch/live imbalance surfacing satellites."""

import json
import os
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jepsen_tpu import fleet, resilience
from jepsen_tpu.checker import UNKNOWN
from jepsen_tpu.checker import tpu as T
from jepsen_tpu.checker import plan as plan_mod
from jepsen_tpu.checker.wgl import check_packed
from jepsen_tpu.fleet import (ElasticFleet, FleetPolicy, LocalHost,
                              check_packed_fleet, merge_pool,
                              repad_pool, shard_imbalance, split_pool)
from jepsen_tpu.models import CASRegister
from jepsen_tpu.ops.encode import pack_with_init
from jepsen_tpu.resilience import (DCN, TRANSIENT, Checkpoint,
                                   classify_failure,
                                   supervised_check_packed)
from jepsen_tpu.testing import simulate_register_history

pytestmark = pytest.mark.fleet


@pytest.fixture(autouse=True)
def fleet_env(monkeypatch):
    """Fleet tests must not inherit ambient fleet/plan knobs, and the
    kill switch must be provably OFF unless a test turns it on."""
    for var in ("JTPU_FLEET", "JTPU_FLEET_IMBALANCE_MAX",
                "JTPU_FLEET_IMBALANCE_LEVELS", "JTPU_FLEET_STEAL",
                "JTPU_FLEET_DEAD_S", "JTPU_PLAN_BYTES_LIMIT"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("JEPSEN_RETRY_BASE", "0.001")
    yield


def _packed(seed=7, n=150, crash_p=0.02):
    h = simulate_register_history(n, n_procs=5, n_vals=4, seed=seed,
                                  crash_p=crash_p)
    return pack_with_init(h, CASRegister())


def fast_policy(**kw):
    from jepsen_tpu.resilience import RetryPolicy
    kw.setdefault("backoff_base_s", 0.001)
    kw.setdefault("backoff_cap_s", 0.01)
    return FleetPolicy(retry=RetryPolicy(**kw))


def _skewed_pool(cap=32, live=6, window=32, crw=8):
    """A synthetic pool with all live rows in shard 0's block —
    maximal straggler skew."""
    mw, mc = (window + 31) // 32, max((crw + 31) // 32, 1)
    k = np.zeros(cap, np.int32)
    k[:live] = np.arange(live, 0, -1, dtype=np.int32)
    mask = np.zeros((cap, mw), np.uint32)
    cmask = np.zeros((cap, mc), np.uint32)
    state = np.arange(cap, dtype=np.int32)
    alive = np.zeros(cap, bool)
    alive[:live] = True
    return (k, mask, cmask, state, alive)


class TestPoolSurgery:
    def test_split_contiguous_roundtrip(self):
        pool = _skewed_pool()
        parts = split_pool(pool, 4)
        assert len(parts) == 4
        assert all(p[0].shape[0] == 8 for p in parts)
        merged, dropped = merge_pool(parts, 32)
        assert not dropped
        # every live config survives the split+merge
        assert int(np.count_nonzero(merged[4])) == 6

    def test_split_interleave_deals_live_rows(self):
        pool = _skewed_pool(cap=32, live=6)
        before, _ = shard_imbalance(pool, 4)
        assert before == 4.0          # shard 0 hoards the frontier
        parts = split_pool(pool, 4, interleave=True)
        lives = [int(np.count_nonzero(p[4])) for p in parts]
        assert sorted(lives) == [1, 1, 2, 2]
        # the deal conserves every live config
        merged, _ = merge_pool(parts, 32)
        assert int(np.count_nonzero(merged[4])) == 6

    def test_merge_dedups_and_sorts_deepest_first(self):
        pool = _skewed_pool(cap=8, live=3)
        # duplicate the pool: every live config appears twice
        merged, dropped = merge_pool([pool, pool], 8)
        assert not dropped
        assert int(np.count_nonzero(merged[4])) == 3
        k, alive = merged[0], merged[4]
        live_k = k[alive]
        # deepest-first prefix, live rows compacted to the front
        assert list(live_k) == sorted(live_k, reverse=True)
        assert alive[:3].all() and not alive[3:].any()

    def test_merge_truncation_marks_dropped(self):
        pool = _skewed_pool(cap=8, live=8)
        other = _skewed_pool(cap=8, live=8)
        other[3][:] += 100            # distinct states: no dedup
        merged, dropped = merge_pool([pool, other], 8)
        assert dropped
        assert int(np.count_nonzero(merged[4])) == 8

    def test_repad_grow_and_shrink(self):
        pool = _skewed_pool(cap=8, live=4)
        grown, dropped = repad_pool(pool, 12)
        assert not dropped and grown[0].shape[0] == 12
        assert int(np.count_nonzero(grown[4])) == 4
        shrunk, dropped = repad_pool(grown, 4)
        assert not dropped and shrunk[0].shape[0] == 4
        _, dropped = repad_pool(pool, 2)   # live rows past the cut
        assert dropped

    def test_pool_sort_host_matches_device_orientation(self):
        # invalid rows sink; deeper (k + |mask|) rows lead
        k = np.array([1, 5, 3, 9], np.int32)
        mask = np.zeros((4, 1), np.uint32)
        mask[2, 0] = 0b111            # depth 3 + 3 = 6
        cmask = np.zeros((4, 1), np.uint32)
        state = np.zeros(4, np.int32)
        alive = np.array([True, True, True, False])
        perm = T._pool_sort_host(k, mask, cmask, state, alive)
        assert list(k[perm]) == [3, 5, 1, 9]   # 6, 5, 1 then dead


class TestRemeshValidation:
    def test_pad_for_axis(self):
        assert plan_mod.pad_for_axis(32, 3) == 33
        assert plan_mod.pad_for_axis(32, 4) == 32
        assert plan_mod.pad_for_axis(1, 8) == 8

    def test_check_remesh_pads_and_validates(self):
        p, _ = _packed()
        rm = plan_mod.check_remesh(p, 3, 32, 32, 8)
        assert rm["ok"] is True
        assert rm["capacity"] % 3 == 0 and rm["capacity"] >= 32
        assert rm["expand"] % 3 == 0
        assert rm["per-device-bytes"] > 0

    def test_check_remesh_never_raises_on_oom(self):
        p, _ = _packed()
        rm = plan_mod.check_remesh(p, 2, 16384, 32, 1024,
                                   bytes_limit=10_000)
        assert rm["ok"] is False
        assert any(i["rule"] == "PLAN-OOM" for i in rm["issues"])


class TestFleetSearch:
    def test_verdicts_match_single_host(self):
        for seed in (3, 7, 11):
            p, kernel = _packed(seed=seed)
            base = supervised_check_packed(p, kernel, segment_iters=8)
            out = check_packed_fleet(p, kernel, hosts=2,
                                     segment_iters=8)
            assert out["valid"] == base["valid"] == \
                check_packed(p, kernel)["valid"]
            assert out["fleet"]["hosts"] == ["host0", "host1"]
            assert out["segments"] >= 1
            assert out["segment-iters"] == 8

    def test_refutation_matches_and_carries_evidence(self):
        from jepsen_tpu.history import History, Op
        rows = [Op(type="invoke", f="write", value=1, process=0, time=0),
                Op(type="ok", f="write", value=1, process=0, time=1),
                Op(type="invoke", f="read", value=None, process=1,
                   time=2),
                Op(type="ok", f="read", value=9, process=1, time=3)]
        p, kernel = pack_with_init(History.of(rows), CASRegister())
        out = check_packed_fleet(p, kernel, hosts=2, segment_iters=4,
                                 capacity=32, window=32, expand=8)
        assert out["valid"] is False
        assert out.get("final-states")

    def test_host_kill_remeshes_and_verdict_survives(self):
        p, kernel = _packed()
        base = supervised_check_packed(p, kernel, segment_iters=2)

        def chaos(round_idx, fl):
            if round_idx == 2 and fl.hosts[1].state == "live":
                fl.hosts[1].kill()

        out = check_packed_fleet(p, kernel, hosts=2, segment_iters=2,
                                 on_round=chaos)
        assert out["valid"] == base["valid"]
        outcomes = [e.get("outcome") for e in out["attempts"]]
        assert "host-removed" in outcomes
        assert "remesh-to-1-hosts" in outcomes
        assert out["fleet"]["hosts-lost"] == 1
        assert out["fleet"]["remesh-count"] >= 1
        assert out["fleet"]["live"] == ["host0"]

    def test_all_hosts_lost_aborts_unknown(self):
        p, kernel = _packed()

        def chaos(round_idx, fl):
            for h in fl.hosts:
                h.kill()

        out = check_packed_fleet(p, kernel, hosts=2, segment_iters=2,
                                 on_round=chaos)
        assert out["valid"] is UNKNOWN
        assert "all fleet hosts lost" in out["error"]

    def test_steal_fires_on_skew_and_verdict_matches_no_steal(
            self, monkeypatch):
        p, kernel = _packed()
        monkeypatch.setenv("JTPU_FLEET_IMBALANCE_MAX", "1.01")
        monkeypatch.setenv("JTPU_FLEET_IMBALANCE_LEVELS", "1")
        out = check_packed_fleet(p, kernel, hosts=2, segment_iters=2)
        steals = [e for e in out["attempts"]
                  if e.get("outcome") == "steal-rebalance"]
        assert steals, "imbalance over threshold never stole"
        for s in steals:
            assert s["imbalance_after"] <= s["imbalance_before"]
        assert out["fleet"]["steal-count"] == len(steals)
        assert out["fleet"]["peak-imbalance"] > 1.01
        monkeypatch.setenv("JTPU_FLEET_STEAL", "0")
        nosteal = check_packed_fleet(p, kernel, hosts=2,
                                     segment_iters=2)
        assert nosteal["fleet"]["steal-count"] == 0
        assert nosteal["valid"] == out["valid"]

    def test_join_admitted_at_barrier(self):
        p, kernel = _packed()
        joined = []

        def chaos(round_idx, fl):
            if round_idx == 1 and not joined:
                h = LocalHost("late")
                joined.append(h)
                fl.request_join(h)

        out = check_packed_fleet(p, kernel, hosts=2, segment_iters=2,
                                 on_round=chaos)
        assert out["valid"] is True
        outcomes = [str(e.get("outcome", "")) for e in out["attempts"]]
        assert any(o.startswith("join-admitted-3-hosts")
                   for o in outcomes)
        assert "remesh-to-3-hosts" in outcomes
        assert out["fleet"]["hosts-joined"] == 1
        assert "late" in out["fleet"]["hosts"]

    def test_join_rejected_by_footprint(self):
        p, kernel = _packed()
        asked = []

        def chaos(round_idx, fl):
            if round_idx == 1 and not asked:
                asked.append(1)
                # the byte budget collapses mid-run: the would-be
                # third host's per-device footprint no longer fits
                os.environ["JTPU_PLAN_BYTES_LIMIT"] = "1"
                fl.request_join(LocalHost("late"))

        try:
            out = check_packed_fleet(p, kernel, hosts=2,
                                     segment_iters=2, on_round=chaos)
        finally:
            os.environ.pop("JTPU_PLAN_BYTES_LIMIT", None)
        outcomes = [e.get("outcome") for e in out["attempts"]]
        assert "join-rejected" in outcomes
        rej = next(e for e in out["attempts"]
                   if e.get("outcome") == "join-rejected")
        assert "PLAN-OOM" in rej["rules"]
        assert out["fleet"]["hosts-joined"] == 0
        assert "late" not in out["fleet"]["hosts"]

    def test_dcn_fault_retries_then_succeeds(self):
        p, kernel = _packed()
        boom = {"left": 2}

        def flaky(ctx):
            if boom["left"]:
                boom["left"] -= 1
                raise RuntimeError(
                    "DCN all-reduce collective timed out")

        hosts = [LocalHost("h0"), LocalHost("h1", chaos=flaky)]
        out = check_packed_fleet(p, kernel, hosts=hosts,
                                 segment_iters=8,
                                 policy=fast_policy())
        assert out["valid"] is True
        retries = [e for e in out["attempts"]
                   if e.get("event") == "host-retry"]
        assert len(retries) == 2
        assert all(r["class"] == DCN for r in retries)
        # both hosts survived: a slow interconnect degrades, it does
        # not remove the host
        assert out["fleet"]["hosts-lost"] == 0

    def test_dcn_retries_exhausted_removes_host(self):
        p, kernel = _packed()

        def always(ctx):
            raise RuntimeError("NCCL all-gather aborted")

        hosts = [LocalHost("h0"), LocalHost("h1", chaos=always)]
        out = check_packed_fleet(
            p, kernel, hosts=hosts, segment_iters=8,
            policy=fast_policy(max_retries=1))
        assert out["valid"] is True      # survivor finishes
        lost = [e for e in out["attempts"]
                if e.get("event") == "host-lost"]
        assert lost and lost[0]["host"] == "h1"
        assert lost[0]["class"] == DCN


class TestDCNClassification:
    def test_collective_text_classifies_dcn_not_transient(self):
        assert classify_failure(RuntimeError(
            "all-reduce DEADLINE_EXCEEDED across hosts")) == DCN
        assert classify_failure(RuntimeError(
            "NCCL ring broke")) == DCN
        assert classify_failure(RuntimeError(
            "coordination service heartbeat lost")) == DCN
        # plain transients stay transient
        assert classify_failure(RuntimeError(
            "UNAVAILABLE: connection dropped")) == TRANSIENT
        # OOM stays OOM even with collective-ish text nearby
        assert classify_failure(RuntimeError(
            "RESOURCE_EXHAUSTED during all-reduce")) == \
            resilience.OOM


class TestChangedMeshResume:
    """Satellite: Checkpoint save under N shards, resume under M —
    frontier rows conserved, verdict identical to uninterrupted."""

    def _fleet_cps(self, p, kernel, hosts):
        cps = []
        out = check_packed_fleet(p, kernel, hosts=hosts,
                                 segment_iters=2,
                                 on_checkpoint=cps.append)
        return out, cps

    @pytest.mark.parametrize("save_hosts,resume_hosts",
                             [(4, 2), (2, 4)])
    def test_fleet_resume_across_mesh_sizes(self, save_hosts,
                                            resume_hosts):
        p, kernel = _packed()
        base, cps = self._fleet_cps(p, kernel, save_hosts)
        assert cps, "search finished before any checkpoint"
        cp = cps[len(cps) // 2]
        live_saved = int(np.count_nonzero(np.asarray(cp.carry[4])))
        resumed = check_packed_fleet(p, kernel, hosts=resume_hosts,
                                     segment_iters=2, resume=cp)
        assert resumed["valid"] == base["valid"]
        # conservation: the resumed run's first split sees every live
        # frontier row the checkpoint carried (repad never drops)
        pool, dropped = repad_pool(
            cp.carry[:5],
            plan_mod.pad_for_axis(np.asarray(cp.carry[0]).shape[0],
                                  resume_hosts))
        assert not dropped
        assert int(np.count_nonzero(pool[4])) == live_saved

    @pytest.mark.parametrize("save_axis,resume_axis", [(4, 2), (2, 4)])
    def test_sharded_resume_bit_identical(self, save_axis, resume_axis):
        """The REAL sharded path: a checkpoint saved under a 4-shard
        mesh resumes under 2 (and 2 under 4) with verdict AND level
        count bit-identical to the uninterrupted search — the axis
        partitions rows, it never changes the math."""
        from jepsen_tpu import parallel
        from jepsen_tpu.checker.tpu import POOL_AXIS
        p, kernel = _packed(seed=11, n=120)
        mesh_a = parallel.make_mesh(save_axis, axis=POOL_AXIS)
        mesh_b = parallel.make_mesh(resume_axis, axis=POOL_AXIS)
        kw = dict(capacity=64, window=32, expand=16)
        unint = T.check_packed_sharded(p, kernel, mesh_a,
                                       segment_iters=4, **kw)
        cps = []
        T.check_packed_sharded(p, kernel, mesh_a, segment_iters=4,
                               on_checkpoint=cps.append, **kw)
        if len(cps) < 2:
            pytest.skip("search finished inside one segment")
        cp = cps[0]
        live_saved = int(np.count_nonzero(np.asarray(cp.carry[4])))
        resumed = T.check_packed_sharded(p, kernel, mesh_b,
                                         segment_iters=4, resume=cp,
                                         **kw)
        assert resumed["valid"] == unint["valid"]
        assert resumed["levels"] == unint["levels"]
        assert live_saved == int(np.count_nonzero(
            np.asarray(cp.carry[4])))

    def test_sharded_segmented_matches_monolithic(self):
        """Small sharded segments against one monolithic segment
        covering the whole sharded search, and both against the host
        WGL engine."""
        from jepsen_tpu import parallel
        from jepsen_tpu.checker.tpu import POOL_AXIS
        p, kernel = _packed(seed=5, n=100)
        mesh = parallel.make_mesh(2, axis=POOL_AXIS)
        kw = dict(capacity=64, window=32, expand=16)
        mono = T.check_packed_sharded(
            p, kernel, mesh, segment_iters=T.DEFAULT_SEGMENT_ITERS, **kw)
        seg = T.check_packed_sharded(p, kernel, mesh,
                                     segment_iters=4, **kw)
        assert mono["segments"] == 1
        assert seg["valid"] == mono["valid"] == check_packed(
            p, kernel)["valid"]
        for k in ("levels", "rung", "max-linearized-prefix",
                  "final-states"):
            assert seg.get(k) == mono.get(k), k
        assert seg["segments"] >= -(-seg["levels"] // 4)
        assert seg["pool-sharding"] == "pool=2"

    def test_sharded_segmented_reports_peak_live_rows(self):
        from jepsen_tpu import parallel
        from jepsen_tpu.checker.tpu import POOL_AXIS
        p, kernel = _packed(seed=5, n=100)
        mesh = parallel.make_mesh(2, axis=POOL_AXIS)
        seen = []
        res = T.check_packed_sharded(
            p, kernel, mesh, capacity=64, window=32, expand=16,
            segment_iters=1, on_checkpoint=lambda cp: seen.append(
                [int(np.count_nonzero(np.asarray(cp.carry[4])[i * 32:
                                                             (i + 1) * 32]))
                 for i in range(2)]))
        bal = res["shard-balance"]
        assert bal["peak-live-rows"] == [max(s[i] for s in seen)
                                         for i in range(2)]
        assert all(pk >= lv for pk, lv in zip(bal["peak-live-rows"],
                                               bal["live-rows"]))


class TestKillSwitch:
    """JTPU_FLEET=0 (or absent) leaves single-host paths byte-identical
    — the same discipline as JTPU_TRACE / JTPU_PLAN_GATE."""

    def test_fleet_hosts_env_parsing(self, monkeypatch):
        assert T._fleet_hosts() == 0
        for off in ("0", "1", "", "  ", "nope", "-3"):
            monkeypatch.setenv("JTPU_FLEET", off)
            assert T._fleet_hosts() == 0
        monkeypatch.setenv("JTPU_FLEET", "2")
        assert T._fleet_hosts() == 2

    def test_off_and_absent_results_identical(self, monkeypatch):
        p, kernel = _packed()
        r_absent = T.check_packed_tpu(p, kernel, segment_iters=8)
        monkeypatch.setenv("JTPU_FLEET", "0")
        r_off = T.check_packed_tpu(p, kernel, segment_iters=8)

        def stable(r):
            r = dict(r)
            r.pop("device-s", None)
            return r

        assert stable(r_absent) == stable(r_off)
        assert "fleet" not in r_absent and "fleet" not in r_off

    def test_on_routes_through_fleet(self, monkeypatch):
        p, kernel = _packed()
        monkeypatch.setenv("JTPU_FLEET", "2")
        r = T.check_packed_tpu(p, kernel, segment_iters=8)
        assert r["fleet"]["hosts"] == ["host0", "host1"]
        monkeypatch.delenv("JTPU_FLEET")
        base = T.check_packed_tpu(p, kernel, segment_iters=8)
        assert r["valid"] == base["valid"]

    def test_off_leaves_history_artifact_byte_identical(
            self, monkeypatch, tmp_path):
        src = os.path.join(os.path.dirname(__file__), "fixtures",
                           "lint", "good_history.jsonl")
        art = tmp_path / "history.jsonl"
        art.write_bytes(open(src, "rb").read())
        before = art.read_bytes()
        from jepsen_tpu.history import History
        h = History.from_jsonl(art.read_text())
        v_absent = T.check_history_tpu(h, CASRegister())["valid"]
        assert art.read_bytes() == before
        monkeypatch.setenv("JTPU_FLEET", "0")
        v_off = T.check_history_tpu(h, CASRegister())["valid"]
        assert v_absent == v_off
        assert art.read_bytes() == before


class TestObsFleetDeadHosts:
    """Satellite: obs/fleet.py must render a vanished or torn host
    artifact dir as a host=dead row, never raise."""

    def _host_dir(self, tmp_path, name, level=5, hb_age=None):
        from jepsen_tpu.obs import fleet as obs_fleet
        d = tmp_path / name
        d.mkdir()
        (d / "progress.json").write_text(json.dumps(
            {"state": "searching", "level": level, "level-budget": 100,
             "ts": time.time()}))
        if hb_age is not None:
            (d / obs_fleet.HEARTBEAT_NAME).write_text(json.dumps(
                {"ts": time.time() - hb_age, "pid": 1}))
        return str(d)

    def test_vanished_dir_renders_dead_row(self, tmp_path):
        from jepsen_tpu.obs import fleet as obs_fleet
        d1 = self._host_dir(tmp_path, "h1")
        gone = str(tmp_path / "h2")     # never created
        merged = obs_fleet.merge([d1, gone])
        rows = {r["host"]: r for r in merged["summary"]}
        assert rows["h2"]["state"] == "dead"
        assert rows["h2"]["missing"] is True
        assert rows["h1"]["state"] == "searching"
        lines = obs_fleet.format_fleet(merged)
        assert any("h2: host=dead" in ln for ln in lines)

    def test_stale_heartbeat_renders_dead(self, tmp_path):
        from jepsen_tpu.obs import fleet as obs_fleet
        d1 = self._host_dir(tmp_path, "h1", hb_age=0.5)
        d2 = self._host_dir(tmp_path, "h2",
                            hb_age=obs_fleet.HEARTBEAT_DEAD_S + 60)
        merged = obs_fleet.merge([d1, d2])
        rows = {r["host"]: r for r in merged["summary"]}
        assert rows["h1"]["state"] == "searching"
        assert rows["h2"]["state"] == "dead"
        assert rows["h2"]["heartbeat-age-s"] > 60

    def test_torn_artifacts_tolerated(self, tmp_path):
        from jepsen_tpu.obs import fleet as obs_fleet
        d = tmp_path / "h1"
        d.mkdir()
        (d / "metrics.json").write_text('{"jtpu_half": {"kind"')
        (d / "progress.json").write_text('{"state": "sear')
        (d / "trace.jsonl").write_text('{"name": "x", "ts"')
        merged = obs_fleet.merge([str(d)])
        assert merged["summary"][0]["host"] == "h1"

    def test_watch_fleet_cli_tolerates_vanished_dir(self, tmp_path,
                                                    capsys):
        from jepsen_tpu import cli
        d1 = self._host_dir(tmp_path, "h1")
        # finish the run so --once exits on its own
        (tmp_path / "h1" / "progress.json").write_text(json.dumps(
            {"state": "done", "level": 9, "ts": time.time()}))
        gone = str(tmp_path / "nope")
        rc = cli.run(cli.default_commands(),
                     ["watch", "--fleet", d1, gone, "--once"])
        assert rc == cli.OK
        text = capsys.readouterr().out
        assert "host=dead" in text
        # ALL dirs missing is still a usage error
        rc = cli.run(cli.default_commands(),
                     ["watch", "--fleet", str(tmp_path / "a"),
                      str(tmp_path / "b"), "--once"])
        assert rc == cli.INVALID_ARGS

    def test_discover_hosts_survives_vanishing_root(self, tmp_path):
        from jepsen_tpu.obs import fleet as obs_fleet
        assert obs_fleet.discover_hosts(str(tmp_path / "gone")) == []


class TestImbalanceSurfacing:
    """Satellite: jtpu_shard_imbalance_ratio visible in watch / /live /
    the observatory ticker, not just bench's # search: line."""

    def test_format_status_renders_imbalance_and_fleet(self):
        from jepsen_tpu.obs import observatory
        line = observatory.format_status(
            {"state": "searching", "level": 4, "level-budget": 100,
             "imbalance": 2.5,
             "fleet": {"hosts": 3, "remeshes": 1, "steals": 2}})
        assert "imbalance 2.50x" in line
        assert "fleet 3 host(s)" in line
        assert "1 remesh(es)" in line and "2 steal(s)" in line

    def test_fleet_publishes_imbalance_to_progress(self, tmp_path,
                                                   monkeypatch):
        from jepsen_tpu.obs import observatory
        monkeypatch.setenv("JTPU_TRACE", "1")
        observatory.attach(str(tmp_path))
        try:
            p, kernel = _packed()
            check_packed_fleet(p, kernel, hosts=2, segment_iters=2)
        finally:
            observatory.detach()
        prog = json.loads((tmp_path / "progress.json").read_text())
        assert prog["state"] == "done"
        assert prog.get("imbalance") is not None
        assert prog["fleet"]["hosts"] >= 1
        # and the live gauge moved
        g = T._SHARD_IMBALANCE
        assert g.value() >= 1.0

    def test_gauge_set_each_round(self):
        p, kernel = _packed()
        T._SHARD_IMBALANCE.set(-1.0)
        check_packed_fleet(p, kernel, hosts=2, segment_iters=4)
        assert T._SHARD_IMBALANCE.value() >= 1.0


@pytest.mark.chaos
class TestProcHostWorker:
    """One real worker subprocess (the CPU-simulated DCN endpoint):
    the file protocol answers shard segments, and heartbeats flow."""

    def test_single_worker_fleet_completes(self, tmp_path):
        p, kernel = _packed(seed=3, n=100)
        h = fleet.ProcHost("w0", str(tmp_path / "w0"))
        out = check_packed_fleet(p, kernel, hosts=[h],
                                 segment_iters=16)
        assert out["valid"] == check_packed(p, kernel)["valid"]
        hb = fleet.read_heartbeat(str(tmp_path / "w0"))
        assert hb and hb.get("pid")
        assert h.state == "dead"     # stopped at run end

    def test_two_worker_spans_join_one_request_trace(self, tmp_path,
                                                     monkeypatch):
        """Request tracing across the req_N.npz seam: with an ambient
        trace context set, BOTH worker subprocesses' segment spans
        carry the request's trace id, and the stitcher renders them on
        one aligned timeline next to the leader's spans."""
        from jepsen_tpu.obs import fleet as obs_fleet
        from jepsen_tpu.obs import trace as obs_trace
        monkeypatch.delenv("JTPU_TRACE", raising=False)
        p, kernel = _packed(seed=3, n=120)
        tid = obs_trace.new_trace_id()
        tr = obs_trace.tracer()
        tr.attach(str(tmp_path / "trace.jsonl"))
        obs_trace.sync_event()
        try:
            with tr.context(tid):
                with tr.span("serve.request", id="r-fleet"):
                    hosts = [
                        fleet.ProcHost("w0", str(tmp_path / "w0")),
                        fleet.ProcHost("w1", str(tmp_path / "w1"))]
                    out = check_packed_fleet(p, kernel, hosts=hosts,
                                             segment_iters=16)
        finally:
            tr.detach()
        assert out["valid"] == check_packed(p, kernel)["valid"]
        for w in ("w0", "w1"):
            recs, stats = obs_trace.read_trace(
                str(tmp_path / w / "trace.jsonl"))
            assert stats["corrupt"] == 0
            segs = [r for r in recs
                    if r["name"] == "checker.segment"
                    and r.get("trace") == tid]
            assert segs, f"worker {w} emitted no traced segments"
            assert all(r.get("host") == w for r in segs)
            assert any(r["name"] == "trace.sync" for r in recs)
        stitched = obs_fleet.stitch_request(str(tmp_path), tid)
        assert stitched["method"] == "wall-clock"
        seen_hosts = {r.get("host") for r in stitched["records"]}
        assert {"w0", "w1"} <= seen_hosts
        names = {r["name"] for r in stitched["records"]}
        assert "serve.request" in names      # the leader's span too
