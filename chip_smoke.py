#!/usr/bin/env python
"""Bring-up smoke run of the checker's main path on the chip.

One process drives the user entry points at the north-star size
(BASELINE.json: the etcd CAS register, 10k ops; the hazelcast-style
multi-key CAS config, 50 keys x 200 ops) and holds every verdict to the
plain reference, the host C++ WGL engine (``checker/native.py``):

* ``offline`` — ``linearizable(CASRegister(), backend="tpu").check``
  on the 10k-op headline history, then on its stale-read twin;
* ``cli`` — ``cli.run(default_commands(), ["run", "--suite",
  "local-kv", "--backend", "tpu", ...])`` then ``analyze --store <run>
  --backend tpu``, in-process;
* ``keyed`` — ``check_keyed_tpu`` on 50 keys x 200 ops, every key
  decided on the device;
* ``served`` — ``serve.run_daemon`` in this process, ``POST /check`` of
  the headline history, its twin and one key's history, polled to
  verdicts, then ``POST /drain``.

``--chips 4`` runs only the cross-chip paths, each next to its one-chip
twin: the keyed batch at 256 keys x 2,000 ops on a 4-device mesh, and a
32-process wide history pool-sharded over 4 devices at rung (512, 32,
512), where every shard must hold live rows. The wide-100 history is
not used here: its only deciding rung, (512, 128, 512), sorts 66,048
rows per level on 7 sort keys and takes ~165 s to compile per
executable on the chip host, and its native reference takes minutes of
host CPU (PERF.md sections 5 and 7).

Each phase prints one ``#`` line (timings, device, rungs compiled):
bring-up observations, not benchmark results. The last line is one JSON
object, ``{"ok": true, "device": {...}}``; with no TPU, or when any
phase fails or disagrees with the reference, it says ``"ok": false`` and
the exit code is 1. Phases take their sizes as arguments so tests drive
them small on the CPU; only :func:`main` insists on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

#: Result keys (or, for the supervisor's attempt trail, values) that
#: mean a verdict did not come from the device search.
FALLBACK_MARKERS = ("fallback-from", "backend-fallback", "cpu-fallback")


class SmokeFailure(AssertionError):
    """A phase's verdict was wrong, unknown, or not the device's."""


def _expect(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _no_fallback(result, where: str) -> None:
    """Fail if ``result`` (or any result nested in it) carries a marker
    of a verdict computed off the device."""
    if isinstance(result, dict):
        for k in FALLBACK_MARKERS:
            _expect(k not in result, f"{where}: result carries {k!r}")
        for v in result.values():
            _no_fallback(v, where)
    elif isinstance(result, (list, tuple)):
        for v in result:
            _no_fallback(v, where)
    elif isinstance(result, str):
        _expect(result not in FALLBACK_MARKERS,
                f"{where}: result carries {result!r}")


def _device_label() -> str:
    import jax
    d = jax.devices()[0]
    return f"platform={d.platform} device_kind={d.device_kind!r}"


def _executed():
    from jepsen_tpu.checker import tpu as T
    return set(T._EXECUTED_SHAPES)


def _rungs(before: set) -> str:
    """The executables this process built since ``before``, as
    kind:capacity/window/expand (every shape key leads with those)."""
    from jepsen_tpu.checker import tpu as T
    new = sorted({f"{k[0]}:{k[2]}/{k[3]}/{k[4]}"
                  for k in T._EXECUTED_SHAPES - before})
    return ",".join(new) or "none"


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _line(name: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"# phase {name}: {_device_label()} {body}", flush=True)


def headline_history(n_ops: int):
    from jepsen_tpu.testing import simulate_register_history
    return simulate_register_history(n_ops, n_procs=5, n_vals=16, seed=42,
                                     crash_p=0.002)


def stale_read_twin(history, at_frac: float = 0.005):
    """``history`` with ONE completed read turned stale: from the first
    ok read at or past ``at_frac`` of the rows, its value becomes one the
    register held earlier. Candidates are tried in order until the native
    engine refutes the twin (a crashed write of that value could still
    legitimise it), so the twin is invalid by the reference's verdict.
    Early in the history, so a refutation does not need the top rung."""
    from jepsen_tpu.checker.native import check_history_native
    from jepsen_tpu.history import History
    from jepsen_tpu.models import CASRegister
    rows = list(history)
    written = []
    for i, o in enumerate(rows):
        if o.type == "ok" and o.f == "write":
            written.append(o.value)
        if (i < int(len(rows) * at_frac) or o.type != "ok"
                or o.f != "read" or o.value is None):
            continue
        for v in dict.fromkeys(reversed(written[:-1])):
            if v == o.value:
                continue
            twin = History.of(rows[:i] + [o.replace(value=v)]
                              + rows[i + 1:])
            if check_history_native(twin, CASRegister())["valid"] is False:
                return twin
    raise SmokeFailure("no stale read the reference refutes")


def phase_offline(n_ops: int = 10_000) -> None:
    from jepsen_tpu.checker.native import check_history_native
    from jepsen_tpu.checker.wgl import linearizable
    from jepsen_tpu.models import CASRegister
    h = headline_history(n_ops)
    twin = stale_read_twin(h)
    checker = linearizable(CASRegister(), backend="tpu")
    before = _executed()
    r, cold = _timed(lambda: checker.check({}, h))
    r2, warm = _timed(lambda: checker.check({}, h))
    rs, stale_s = _timed(lambda: checker.check({}, twin))
    ref = check_history_native(h, CASRegister())["valid"]
    ref_stale = check_history_native(twin, CASRegister())["valid"]
    for res, want, what in ((r, ref, "headline"), (r2, ref, "headline"),
                            (rs, ref_stale, "stale twin")):
        _no_fallback(res, f"offline {what}")
        _expect(res.get("backend") == "tpu",
                f"offline {what}: backend {res.get('backend')!r}")
        _expect(res["valid"] is want,
                f"offline {what}: device {res['valid']!r} vs native "
                f"{want!r}")
    _expect(ref is True and ref_stale is False,
            f"offline reference verdicts {ref!r}/{ref_stale!r}")
    # the opt-in run-time wedge watchdog (JTPU_SEGMENT_DEADLINE_S) stays
    # off by default: every supervised attempt ran on the default backend
    backends = sorted({a["backend"] for res in (r, r2, rs)
                       for a in res.get("attempts", [])})
    _line("offline", n_ops=n_ops, cold_s=round(cold, 3),
          warm_s=round(warm, 3), stale_s=round(stale_s, 3),
          valid=r["valid"], stale_valid=rs["valid"],
          levels=r.get("levels"), stale_levels=rs.get("levels"),
          attempt_backends=",".join(backends), rungs=_rungs(before))


def phase_cli(workdir: str, seconds: int = 3) -> None:
    import contextlib
    import io

    from jepsen_tpu import cli, store
    from jepsen_tpu.checker.native import check_history_native
    from jepsen_tpu.models import CASRegister
    before = _executed()
    cwd = os.getcwd()
    out = io.StringIO()  # the CLI's own report; its # compile: lines kept
    os.chdir(workdir)  # the run's store/ lands under workdir
    try:
        cmds = cli.default_commands()
        with contextlib.redirect_stdout(out):
            rc, run_s = _timed(lambda: cli.run(cmds, [
                "run", "--suite", "local-kv", "--backend", "tpu",
                "--time-limit", str(seconds)]))
        _expect(rc == cli.OK, f"cli run: exit code {rc}")
        run_dir = os.path.realpath(os.path.join("store", "latest"))
        with contextlib.redirect_stdout(out):
            rc2, analyze_s = _timed(lambda: cli.run(cmds, [
                "analyze", "--store", run_dir, "--backend", "tpu"]))
        _expect(rc2 == cli.OK, f"cli analyze: exit code {rc2}")
        test = store.load(run_dir)
    finally:
        os.chdir(cwd)
        for ln in out.getvalue().splitlines():
            if ln.startswith("# compile:"):
                print(f"# cli {ln[2:]}", flush=True)
    linear = (test.get("results") or {}).get("linear") or {}
    _no_fallback(test.get("results"), "cli run")
    _expect(linear.get("valid") is True and linear.get("backend") == "tpu",
            f"cli run: stored verdict {linear.get('valid')!r} from "
            f"{linear.get('backend')!r}")
    ref = check_history_native(test["history"], CASRegister())["valid"]
    _expect(ref is True, f"cli run: native verdict {ref!r}")
    _line("cli", ops=len(test["history"]) // 2, run_s=round(run_s, 3),
          analyze_s=round(analyze_s, 3), valid=linear["valid"],
          rungs=_rungs(before))


def keyed_histories(n_keys: int, n_ops: int):
    """The hazelcast-style multi-key CAS config: ``n_keys`` CAS
    registers of ``n_ops`` ops each, 5 processes, 8 values, crash
    probability 0.002."""
    from jepsen_tpu.testing import simulate_register_history
    return {k: simulate_register_history(n_ops, n_procs=5, n_vals=8,
                                         seed=1000 + k, crash_p=0.002)
            for k in range(n_keys)}


def _keyed_agree(out, ref, where: str) -> None:
    _no_fallback(out, where)
    for k, r in out["results"].items():
        _expect(r["valid"] in (True, False),
                f"{where}: key {k!r} undecided on the device ({r!r})")
        _expect(r["valid"] is ref["results"][k]["valid"],
                f"{where}: key {k!r} device {r['valid']!r} vs native "
                f"{ref['results'][k]['valid']!r}")


def phase_keyed(n_keys: int = 50, n_ops: int = 200) -> None:
    from jepsen_tpu.checker.native import check_keyed_native
    from jepsen_tpu.checker.tpu import check_keyed_tpu
    from jepsen_tpu.models import CASRegister
    keyed = keyed_histories(n_keys, n_ops)
    before = _executed()
    out, cold = _timed(lambda: check_keyed_tpu(keyed, CASRegister()))
    out2, warm = _timed(lambda: check_keyed_tpu(keyed, CASRegister()))
    ref = check_keyed_native(keyed, CASRegister())
    _keyed_agree(out, ref, "keyed")
    _keyed_agree(out2, ref, "keyed")
    _line("keyed", keys=n_keys, ops_per_key=n_ops, cold_s=round(cold, 3),
          warm_s=round(warm, 3),
          valid_keys=sum(r["valid"] is True
                         for r in out["results"].values()),
          rungs=_rungs(before))


def _http(url: str, body=None):
    import urllib.request
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
        method="GET" if body is None else "POST")
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def phase_served(root: str, n_ops: int = 10_000, keyed_ops: int = 200,
                 timeout_s: float = 600.0) -> None:
    from jepsen_tpu import serve
    from jepsen_tpu.checker.native import check_history_native
    from jepsen_tpu.models import CASRegister
    h = headline_history(n_ops)
    histories = {"headline": h, "stale": stale_read_twin(h),
                 "key": keyed_histories(1, keyed_ops)[0]}
    before = _executed()
    daemon, server = serve.run_daemon(
        serve.ServeConfig(root=root, backend="tpu"), port=0,
        store_root=os.path.join(root, "store"), quiet=True)
    url = f"http://127.0.0.1:{server.server_port}"
    try:
        t0 = time.perf_counter()
        ids = {name: _http(f"{url}/check", {
                   "tenant": "smoke", "model": "cas-register",
                   "history": [o.to_dict() for o in hist]})["id"]
               for name, hist in histories.items()}
        verdicts, latency = {}, {}
        deadline = time.monotonic() + timeout_s
        while len(verdicts) < len(ids):
            _expect(time.monotonic() < deadline,
                    f"served: no verdict for {sorted(set(ids) - set(verdicts))}"
                    f" within {timeout_s:.0f}s")
            for name, rid in ids.items():
                if name in verdicts:
                    continue
                doc = _http(f"{url}/check/{rid}")
                if doc.get("state") == "done":
                    verdicts[name] = doc["result"]
                    latency[name] = round(time.perf_counter() - t0, 3)
            time.sleep(0.05)
        drained = _http(f"{url}/drain", {})
    finally:
        server.shutdown()
        daemon.stop()
    for name, res in verdicts.items():
        want = check_history_native(histories[name], CASRegister())["valid"]
        _no_fallback(res, f"served {name}")
        _expect(res.get("valid") is want,
                f"served {name}: daemon {res.get('valid')!r} vs native "
                f"{want!r}")
    _line("served", requests=len(ids), latency_s=json.dumps(latency),
          drained=json.dumps(drained, sort_keys=True).replace(" ", ""),
          rungs=_rungs(before))


def phase_keyed_mesh(n_keys: int = 256, n_ops: int = 2000,
                     n_devices: int = 4) -> None:
    """The keyed batch (``keyed_histories``: 256 keys of 2,000 ops by
    default) on one chip and on an ``n_devices`` mesh."""
    from jepsen_tpu import parallel
    from jepsen_tpu.checker.native import check_keyed_native
    from jepsen_tpu.checker.tpu import check_keyed_tpu
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.testing import simulate_register_history
    keyed = {k: simulate_register_history(n_ops, n_procs=5, n_vals=8,
                                          seed=7000 + k, crash_p=0.001)
             for k in range(n_keys)}
    ref = check_keyed_native(keyed, CASRegister())
    mesh = parallel.make_mesh(n_devices)
    before = _executed()
    one, one_s = _timed(lambda: check_keyed_tpu(keyed, CASRegister()))
    four, four_cold = _timed(
        lambda: check_keyed_tpu(keyed, CASRegister(), mesh=mesh))
    _, four_warm = _timed(
        lambda: check_keyed_tpu(keyed, CASRegister(), mesh=mesh))
    _keyed_agree(one, ref, "keyed one-chip")
    _keyed_agree(four, ref, f"keyed {n_devices}-device mesh")
    _expect(len(four["devices"]) == n_devices,
            f"keyed mesh outputs on devices {four['devices']}, "
            f"want {n_devices} distinct")
    _line("keyed-mesh", keys=n_keys, ops_per_key=n_ops,
          one_chip_cold_s=round(one_s, 3),
          mesh_cold_s=round(four_cold, 3), mesh_warm_s=round(four_warm, 3),
          mesh_devices=four["devices"], rungs=_rungs(before))


def phase_wide_sharded(n_procs: int = 32, rounds: int = 4,
                       n_devices: int = 4, capacity: int = 512,
                       expand: int = 512, segment_iters: int = 4) -> None:
    """A wide history (every op of a round concurrent with every
    other) at one rung: pool-sharded over ``n_devices`` (checkpointed,
    so every segment barrier samples each shard's live rows) vs
    ``check_packed_tpu`` on one chip. Both verdicts must equal the
    native engine's, and every shard must have held live rows."""
    from jepsen_tpu import parallel
    from jepsen_tpu.checker.native import check_history_native
    from jepsen_tpu.checker.tpu import (POOL_AXIS, check_packed_sharded,
                                        check_packed_tpu)
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.ops.encode import pack_with_init
    from jepsen_tpu.testing import wide_history
    h = wide_history(n_procs, rounds, write_frac=0.2, seed=3)
    want = check_history_native(h, CASRegister())["valid"]
    packed, kernel = pack_with_init(h, CASRegister())
    mesh = parallel.make_mesh(n_devices, axis=POOL_AXIS)
    before = _executed()
    one, one_s = _timed(lambda: check_packed_tpu(
        packed, kernel, capacity=capacity, window=None, expand=expand))
    four, four_s = _timed(lambda: check_packed_sharded(
        packed, kernel, mesh, capacity=capacity, expand=expand,
        segment_iters=segment_iters))
    for res, what in ((one, "one chip"), (four, "sharded")):
        _no_fallback(res, f"wide {what}")
        _expect(res["valid"] is want,
                f"wide {what}: verdict {res['valid']!r} vs native {want!r}")
    balance = four.get("shard-balance") or {}
    peak = balance.get("peak-live-rows") or []
    _expect(len(peak) == n_devices and min(peak) > 0,
            f"wide sharded: per-shard peak live rows {peak}, want all of "
            f"{n_devices} shards live")
    _line("wide-sharded", procs=n_procs, rounds=rounds,
          rung=f"{capacity}/{four['rung'][1]}/{expand}",
          one_chip_s=round(one_s, 3), sharded_s=round(four_s, 3),
          one_chip_levels=one.get("levels"), sharded_levels=four.get("levels"),
          shard_balance=json.dumps(balance).replace(" ", ""),
          rungs=_rungs(before))


def run_phases(phases) -> list:
    """Run each ``(name, fn)``; return the names that failed (each
    failure's traceback goes to stderr, and later phases still run)."""
    failed = []
    for name, fn in phases:
        try:
            fn()
        except Exception:  # noqa: BLE001 — report, keep smoking
            traceback.print_exc()
            print(f"# phase {name}: FAILED", flush=True)
            failed.append(name)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip paths, each next to its "
                         "one-chip twin")
    args = ap.parse_args(argv)
    device = None
    try:
        sys.path.insert(0, REPO)
        import jax

        from jepsen_tpu.checker.engine import default_engine
        print(f"# compile cache: {default_engine().compile_cache}",
              flush=True)
        devs = jax.devices()
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
        _expect(device["platform"] == "tpu", f"no TPU: found {device}")
        _expect(len(devs) >= args.chips,
                f"--chips {args.chips} but {len(devs)} device(s)")
        from jepsen_tpu.checker.native import available
        _expect(available(), "the native reference engine did not build")
        with tempfile.TemporaryDirectory(prefix="chip_smoke.") as tmp:
            if args.chips == 4:
                phases = [("keyed-mesh", phase_keyed_mesh),
                          ("wide-sharded", phase_wide_sharded)]
            else:
                phases = [
                    ("offline", phase_offline),
                    ("cli", lambda: phase_cli(tmp)),
                    ("keyed", phase_keyed),
                    ("served", lambda: phase_served(
                        os.path.join(tmp, "serve")))]
            failed = run_phases(phases)
        _expect(not failed, f"phase(s) failed: {', '.join(failed)}")
    except Exception as e:  # noqa: BLE001 — the last line must say why
        traceback.print_exc()
        print(json.dumps({"ok": False, "device": device,
                          "error": f"{type(e).__name__}: {e}"}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
