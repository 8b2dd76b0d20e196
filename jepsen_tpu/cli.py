"""Command-line interface.

Rebuild of jepsen.cli (jepsen/src/jepsen/cli.clj): subcommand dispatch with
the reference's exit-code contract —

    0    all tests passed
    1    some test failed
    254  invalid arguments / unknown command
    255  internal error

— plus the standard test options (repeatable --node, --nodes-file,
ssh credentials folded into an 'ssh' map, '3n'-style concurrency
multipliers, --test-count loops, --time-limit) and the serve command for
the results web UI.

Suites build runners with::

    from jepsen_tpu import cli

    def my_test(opts): return {...test map...}

    if __name__ == "__main__":
        cli.main(cli.merge_commands(
            cli.single_test_cmd(my_test), cli.serve_cmd()))
"""

from __future__ import annotations

import argparse
import re
import sys
import traceback
from typing import Any, Callable, Dict, List, Optional, Sequence

DEFAULT_NODES = ["n1", "n2", "n3", "n4", "n5"]

OK = 0
TEST_FAILED = 1
INVALID_ARGS = 254
CRASHED = 255


class _ArgError(Exception):
    pass


class Parser(argparse.ArgumentParser):
    """argparse parser that raises instead of exiting, so run() owns the
    exit-code contract (cli.clj:201-276)."""

    def error(self, message):
        raise _ArgError(message)


def add_test_opts(p: argparse.ArgumentParser) -> None:
    """The standard test option spec (cli.clj:52-87)."""
    p.add_argument("-n", "--node", action="append", metavar="HOSTNAME",
                   help="node to run the test on; repeatable "
                        f"(default: {' '.join(DEFAULT_NODES)})")
    p.add_argument("--nodes-file", metavar="FILENAME",
                   help="file of node hostnames, one per line")
    p.add_argument("--username", default="root", help="ssh username")
    p.add_argument("--password", default="root", help="sudo password")
    p.add_argument("--strict-host-key-checking", action="store_true",
                   help="check ssh host keys")
    p.add_argument("--ssh-private-key", metavar="FILE",
                   help="ssh identity file")
    p.add_argument("--ssh-mode", default=None,
                   choices=[None, "ssh", "dummy", "local"],
                   help="control-plane transport (dummy = record only)")
    p.add_argument("--concurrency", default="1n",
                   help="worker count; an integer, optionally followed by n "
                        "to multiply by the node count (e.g. 3n)")
    p.add_argument("--test-count", type=int, default=1,
                   help="how many times to repeat the test")
    p.add_argument("--time-limit", type=int, default=60,
                   help="test phase duration in seconds")
    p.add_argument("--backend", default="cpu", choices=["cpu", "tpu"],
                   help="checker backend (tpu = batched device search)")
    p.add_argument("--op-timeout", type=float, default=None,
                   metavar="SECONDS",
                   help="bound each client op: a hung invoke becomes an "
                        ":info op and the process reincarnates, so one "
                        "stuck connection cannot stall the run")
    p.add_argument("--segment-iters", type=int, default=None,
                   metavar="N",
                   help="device-search iterations per checkpointed "
                        "segment (resilient execution; a positive "
                        "integer)")
    p.add_argument("--watch", action="store_true",
                   help="print a live search-progress status line "
                        "(level/frontier/ETA) to stderr while the "
                        "checker runs; `python -m jepsen_tpu watch` "
                        "follows another process's run instead")
    p.add_argument("--fleet", type=int, default=None, metavar="N",
                   help="run single-history device searches under the "
                        "elastic fleet scheduler over N (simulated on "
                        "CPU) hosts — host-loss re-meshing, "
                        "work-stealing rebalance, join admission "
                        "(equivalent to JTPU_FLEET=N; "
                        "doc/resilience.md \"Elastic fleet\")")
    p.add_argument("--profile", action="store_true",
                   help="capture a jax.profiler device trace of the "
                        "checker's searches into <run>/profile/ "
                        "(equivalent to JTPU_PROF=1); kernel spans "
                        "merge into the Perfetto export and `trace "
                        "summary` — doc/observability.md")


def parse_concurrency(c: str, n_nodes: int) -> int:
    """'3n' -> 3 * nodes; plain integer otherwise (cli.clj:123-138)."""
    m = re.fullmatch(r"(\d+)(n?)", str(c))
    if not m:
        raise _ArgError(
            f"--concurrency {c} should be an integer optionally "
            f"followed by n")
    unit = n_nodes if m.group(2) == "n" else 1
    return int(m.group(1)) * unit


def read_nodes_file(path: str) -> List[str]:
    """Node hostnames, one per line (cli.clj:174-187)."""
    with open(path) as f:
        return [line.strip() for line in f if line.strip()]


def test_opt_fn(opts: Dict[str, Any]) -> Dict[str, Any]:
    """Standard option post-processing (cli.clj:189-199): --node/
    --nodes-file -> 'nodes', ssh options -> 'ssh' map, concurrency
    parsed."""
    nodes = list(opts.pop("node", None) or [])
    nodes_file = opts.pop("nodes_file", None)
    if nodes_file:
        nodes.extend(read_nodes_file(nodes_file))
    if not nodes:
        nodes = list(DEFAULT_NODES)
    opts["nodes"] = nodes
    opts["ssh"] = {
        "username": opts.pop("username", "root"),
        "password": opts.pop("password", "root"),
        "strict-host-key-checking": opts.pop("strict_host_key_checking",
                                             False),
        "private-key-path": opts.pop("ssh_private_key", None),
        "mode": opts.pop("ssh_mode", None),
    }
    opts["concurrency"] = parse_concurrency(opts.get("concurrency", "1n"),
                                            len(nodes))
    opts["time-limit"] = opts.pop("time_limit", 60)
    opts["test-count"] = opts.pop("test_count", 1)
    opts["op-timeout"] = opts.pop("op_timeout", None)
    opts["segment-iters"] = _apply_segment_iters(
        opts.pop("segment_iters", None))
    opts["profile"] = _apply_profile(opts.pop("profile", False))
    opts["fleet"] = _apply_fleet(opts.pop("fleet", None))
    return opts


def _apply_fleet(n):
    """Deploy --fleet: the device checkers read the fleet opt-in from
    JTPU_FLEET (jepsen_tpu.fleet), so the flag exports it for every
    check this process runs."""
    if n is not None:
        import os
        os.environ["JTPU_FLEET"] = str(n)
    return n


def _apply_segment_iters(seg):
    """Deploy --segment-iters: the device checkers read the segmentation
    knob from JTPU_SEGMENT_ITERS (like the other JTPU_* tuning knobs), so
    the flag exports it for every check this process runs. A value that
    is not a positive integer raises ValueError before anything runs."""
    if seg is not None:
        import os
        if seg < 1:
            raise ValueError(
                f"--segment-iters must be a positive integer, got {seg}")
        os.environ["JTPU_SEGMENT_ITERS"] = str(seg)
    return seg


def _apply_profile(flag):
    """Deploy --profile: the device checkers read the opt-in profiling
    knob from JTPU_PROF (obs/profiler.py), so the flag exports it for
    every search this process runs."""
    if flag:
        import os
        os.environ["JTPU_PROF"] = "1"
    return bool(flag)


def _with_watch(opts: Dict[str, Any], fn: Callable[[], int]) -> int:
    """Run ``fn`` with the in-process live status printer attached when
    the user passed ``--watch`` (the observatory publishes from the
    supervised device search; the printer mirrors it to stderr)."""
    if not opts.get("watch"):
        return fn()
    from jepsen_tpu.obs import observatory
    stop = observatory.live_status_printer()
    try:
        return fn()
    finally:
        stop()


def single_test_cmd(test_fn: Callable[[dict], dict],
                    opt_spec: Optional[Callable] = None,
                    opt_fn: Optional[Callable] = None,
                    usage: Optional[str] = None) -> dict:
    """The 'test' subcommand (cli.clj:295-329): builds a test from parsed
    options via test_fn, runs it --test-count times, fails (exit 1) if any
    run is invalid."""

    def build_parser():
        p = Parser(prog="test", description=usage or "Run a test.")
        add_test_opts(p)
        if opt_spec:
            opt_spec(p)
        return p

    def run(opts) -> int:
        from jepsen_tpu import core

        def loop() -> int:
            for _ in range(opts.get("test-count", 1)):
                test = core.run(test_fn(dict(opts)))
                if test["results"].get("valid") is not True:
                    return TEST_FAILED
            return OK

        return _with_watch(opts, loop)

    return {"test": {"parser": build_parser,
                     "opt_fn": (lambda o: opt_fn(test_opt_fn(o)))
                     if opt_fn else test_opt_fn,
                     "run": run}}


def serve_cmd() -> dict:
    """The 'serve' subcommand (cli.clj:278-293): the results browser,
    plus — with ``--check-daemon`` (or ``JTPU_SERVE=1``) — the
    multi-tenant check daemon (:mod:`jepsen_tpu.serve`, doc/serve.md):
    POST /check, GET /check/<id>, /healthz, /drain mounted on the same
    server, with warm engines, an on-disk request journal, admission
    control and per-bucket circuit breakers. Without the flag the
    behavior is byte-identical to the pre-daemon serve command."""

    def build_parser():
        p = Parser(prog="serve", description="Serve the results browser "
                                             "(and, opted in, the check "
                                             "daemon).")
        p.add_argument("-b", "--host", default="0.0.0.0")
        p.add_argument("-p", "--port", type=int, default=8080)
        p.add_argument("--store-root", default="store")
        p.add_argument("--check-daemon", action="store_true",
                       help="mount the multi-tenant check daemon "
                            "(POST /check; equivalent to JTPU_SERVE=1; "
                            "doc/serve.md)")
        p.add_argument("--serve-dir", default=None, metavar="DIR",
                       help="daemon directory: request journal, result "
                            "files, heartbeat (default: "
                            "<store-root>/serve)")
        p.add_argument("--workers", type=int, default=None,
                       help="check worker threads (JTPU_SERVE_WORKERS)")
        p.add_argument("--queue-max", type=int, default=None,
                       help="bounded-queue depth past which POST /check "
                            "answers 429 (JTPU_SERVE_QUEUE)")
        p.add_argument("--tenant-max", type=int, default=None,
                       help="per-tenant queued-request quota "
                            "(JTPU_SERVE_TENANT_MAX)")
        p.add_argument("--deadline-s", type=float, default=None,
                       help="default per-request deadline; an overrun "
                            "returns :info/timeout "
                            "(JTPU_SERVE_DEADLINE_S)")
        p.add_argument("--serve-backend", default=None,
                       choices=["cpu", "tpu"],
                       help="checker backend for daemon requests "
                            "(default: tpu — the warm device path)")
        p.add_argument("--batch-max", type=int, default=None,
                       help="max same-bucket requests coalesced into "
                            "one gang-scheduled device call; 0 or 1 "
                            "disables batching (JTPU_SERVE_BATCH_MAX)")
        p.add_argument("--batch-wait-ms", type=float, default=None,
                       help="coalesce window a gang leader waits for "
                            "cohort members (JTPU_SERVE_BATCH_WAIT_MS)")
        p.add_argument("--auth-token", default=None, metavar="TOKEN",
                       help="require 'Authorization: Bearer TOKEN' on "
                            "POST /check and /drain; metrics/healthz "
                            "stay open (JTPU_SERVE_TOKEN)")
        p.add_argument("--engine-max-buckets", type=int, default=None,
                       help="LRU-evict warmed engine buckets past this "
                            "count; 0 = unbounded "
                            "(JTPU_ENGINE_MAX_BUCKETS)")
        p.add_argument("--fleet", type=int, default=None, metavar="N",
                       help="place gangs onto N fleet worker hosts "
                            "with host-loss re-meshing; 0 or 1 = "
                            "single-host dispatch (JTPU_SERVE_FLEET; "
                            "doc/serve.md 'Fleet-backed serving')")
        p.add_argument("--rate-limit", type=float, default=None,
                       metavar="R",
                       help="per-tenant POST /check token bucket: R "
                            "requests/s sustained, 429 + Retry-After "
                            "past it; 0 = off (JTPU_SERVE_RATE)")
        return p

    def run(opts) -> int:
        from jepsen_tpu import serve as serve_ns
        from jepsen_tpu import web
        if not (opts.get("check_daemon") or serve_ns.serve_enabled()):
            server = web.serve(host=opts["host"], port=opts["port"],
                               root=opts["store_root"])
            print(f"Listening on "
                  f"http://{opts['host']}:{server.server_port}/")
            try:
                server.serve_forever()
            except KeyboardInterrupt:
                pass
            return OK
        import os as _os
        cfg = serve_ns.ServeConfig(
            root=opts.get("serve_dir")
            or _os.path.join(opts["store_root"], "serve"))
        if opts.get("workers") is not None:
            cfg.workers = opts["workers"]
        if opts.get("queue_max") is not None:
            cfg.queue_max = opts["queue_max"]
        if opts.get("tenant_max") is not None:
            cfg.tenant_max = opts["tenant_max"]
        if opts.get("deadline_s") is not None:
            cfg.deadline_s = opts["deadline_s"] or None
        if opts.get("serve_backend") is not None:
            cfg.backend = opts["serve_backend"]
        if opts.get("batch_max") is not None:
            cfg.batch_max = opts["batch_max"]
            cfg.batch_enabled = opts["batch_max"] > 1
        if opts.get("batch_wait_ms") is not None:
            cfg.batch_wait_ms = opts["batch_wait_ms"]
        if opts.get("auth_token") is not None:
            cfg.auth_token = opts["auth_token"] or None
        if opts.get("engine_max_buckets") is not None:
            cfg.engine_max_buckets = opts["engine_max_buckets"]
        if opts.get("fleet") is not None:
            cfg.fleet_hosts = opts["fleet"]
        if opts.get("rate_limit") is not None:
            cfg.rate_limit = opts["rate_limit"]
        daemon, server = serve_ns.run_daemon(
            cfg, host=opts["host"], port=opts["port"],
            store_root=opts["store_root"])
        if daemon.flightrec is not None:
            import signal as _signal

            def _on_sigterm(_sig, _frm):
                # last words before an orderly kill: dump the flight
                # recorder's window, then release the drain wait below
                # (SIGKILL skips this — the flightrec-kill chaos
                # scenario asserts exactly that asymmetry)
                daemon.flightrec.dump("sigterm")
                daemon.drained.set()

            try:
                _signal.signal(_signal.SIGTERM, _on_sigterm)
            except ValueError:
                pass  # embedded off the main thread: no handler
        print(f"Listening on http://{opts['host']}:{server.server_port}/"
              f" (check daemon: POST /check, GET /check/<id>, /healthz, "
              f"/drain)", flush=True)
        try:
            # graceful drain: POST /drain finishes in-flight work,
            # leaves the queued remainder journaled, and releases this
            # wait — the daemon exits 0 (the drain contract)
            daemon.drained.wait()
        except KeyboardInterrupt:
            daemon.drain(timeout_s=30.0)
        server.shutdown()
        daemon.stop()
        return OK

    return {"serve": {"parser": build_parser, "run": run}}


def stream_cmd() -> dict:
    """The 'stream' subcommand: a reference client for the daemon's
    chunked streaming intake (doc/serve.md "Streaming API"). Reads a
    saved run (or a raw history file), opens a stream session, POSTs
    the ops as CRC-tagged sequenced chunks — honoring 429 backpressure
    (Retry-After) and resynchronizing on 409 gap responses via the
    ``need`` cursor — seals it, then polls until the online checker
    delivers the verdict. Exit codes follow the test contract."""

    def build_parser():
        p = Parser(prog="stream",
                   description="Stream a history into a check daemon's "
                               "/stream intake and await the verdict.")
        p.add_argument("--url", default="http://127.0.0.1:8080",
                       help="daemon base URL")
        p.add_argument("--store", default=None,
                       help="store directory whose history.jsonl to "
                            "stream (default: latest under ./store)")
        p.add_argument("--history", default=None, metavar="FILE",
                       help="raw history file (JSON array or JSONL of "
                            "op maps) instead of --store")
        p.add_argument("--model", default="cas-register",
                       choices=list(MODEL_CHOICES))
        p.add_argument("--tenant", default="default")
        p.add_argument("--chunk", type=int, default=1000,
                       help="ops per chunk")
        p.add_argument("--auth-token", default=None, metavar="TOKEN",
                       help="Authorization: Bearer TOKEN")
        p.add_argument("--poll", type=float, default=0.5,
                       help="verdict poll interval (seconds)")
        p.add_argument("--timeout", type=float, default=600.0,
                       help="overall client budget (seconds)")
        return p

    def run_(opts) -> int:
        import json as _json
        import time as _time
        import urllib.error
        import urllib.request

        from jepsen_tpu import stream as stream_ns

        base = opts["url"].rstrip("/")

        def call(method, path, doc=None):
            req = urllib.request.Request(
                base + path, method=method,
                data=None if doc is None
                else _json.dumps(doc).encode(),
                headers={"Content-Type": "application/json"})
            if opts.get("auth_token"):
                req.add_header("Authorization",
                               f"Bearer {opts['auth_token']}")
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    return r.status, _json.loads(r.read() or b"{}")
            except urllib.error.HTTPError as e:
                try:
                    body = _json.loads(e.read() or b"{}")
                except ValueError:
                    body = {}
                return e.code, body

        # -- load the ops -------------------------------------------------
        if opts.get("history"):
            with open(opts["history"]) as f:
                text = f.read().strip()
            if text.startswith("["):
                ops = _json.loads(text)
            else:
                ops = [_json.loads(ln) for ln in text.splitlines() if ln]
        else:
            from jepsen_tpu import repl, store
            test = (store.load(opts["store"]) if opts.get("store")
                    else repl.last_test())
            if test is None:
                print("no stored test found", file=sys.stderr)
                return INVALID_ARGS
            ops = [op.to_dict() if hasattr(op, "to_dict") else dict(op)
                   for op in (test.get("history") or [])]
        if not ops:
            print("history is empty; nothing to stream", file=sys.stderr)
            return INVALID_ARGS

        deadline = _time.monotonic() + opts["timeout"]

        def budget() -> float:
            left = deadline - _time.monotonic()
            if left <= 0:
                raise TimeoutError("stream client budget exhausted")
            return left

        # -- open ---------------------------------------------------------
        code, body = call("POST", "/stream",
                          {"tenant": opts["tenant"],
                           "model": opts["model"]})
        while code == 429:
            _time.sleep(min(float(body.get("retry-after-s") or 1.0),
                            budget()))
            code, body = call("POST", "/stream",
                              {"tenant": opts["tenant"],
                               "model": opts["model"]})
        if code != 202:
            print(f"open failed: HTTP {code} {body}", file=sys.stderr)
            return CRASHED
        sid = body["id"]
        n_chunk = max(1, opts["chunk"])
        chunks = [ops[i:i + n_chunk] for i in range(0, len(ops), n_chunk)]
        print(f"# stream: {sid} -> {base} ({len(ops)} ops in "
              f"{len(chunks)} chunk(s) of <= {n_chunk})")

        # -- append: sequenced, CRC'd, gap/backpressure aware -------------
        seq = 0
        while seq < len(chunks):
            payload = {"seq": seq, "ops": chunks[seq],
                       "crc": stream_ns.chunk_crc(chunks[seq])}
            code, body = call("POST", f"/stream/{sid}/ops", payload)
            if code == 202:
                seq += 1
            elif code == 429:
                _time.sleep(min(float(body.get("retry-after-s") or 1.0),
                                budget()))
            elif code == 409 and body.get("error") == "gap":
                # resynchronize on the server's cursor (idempotent
                # chunks make the re-send safe)
                seq = int(body["need"])
            elif code == 409 and body.get("error") == "stream-failed":
                # the online checker refuted a stable prefix mid-stream
                # (fail-fast); the verdict is already decided
                print(f"# stream: {sid} failed fast at chunk {seq}; "
                      f"awaiting verdict")
                seq = len(chunks)
                break
            else:
                print(f"chunk {seq} failed: HTTP {code} {body}",
                      file=sys.stderr)
                return CRASHED
            budget()

        # -- close + await verdict ----------------------------------------
        code, body = call("POST", f"/stream/{sid}/close",
                          {"chunks": len(chunks)})
        if code not in (200, 202) and body.get("error") != "stream-failed":
            print(f"close failed: HTTP {code} {body}", file=sys.stderr)
            return CRASHED
        while True:
            code, body = call("GET", f"/stream/{sid}")
            if code == 200 and body.get("state") == "done" \
                    and body.get("result") is not None:
                break
            _time.sleep(min(opts["poll"], budget()))
        result = body["result"]
        print(_json.dumps(result, indent=2, default=repr))
        return OK if result.get("valid") is True else TEST_FAILED

    return {"stream": {"parser": build_parser, "run": run_}}


def suite_run_cmd() -> dict:
    """The 'run' subcommand: run any registered suite by name — possible
    here because all suites live in one package (the reference spreads
    them over separate leiningen projects, each with its own -main)."""

    def build_parser():
        from jepsen_tpu import suites
        p = Parser(prog="run", description="Run a registered suite.")
        p.add_argument("--suite", required=True,
                       choices=sorted(suites.SUITES))
        add_test_opts(p)
        return p

    def run_(opts) -> int:
        from jepsen_tpu import core, suites
        # Non-strict: one broken suite module must not take down runs of
        # every OTHER suite (it warns; only the requested name matters).
        name = opts.pop("suite")
        reg = suites.registry()
        if name not in reg:
            print(f"suite {name!r} failed to load (see warning above)",
                  file=sys.stderr)
            return INVALID_ARGS
        ctor = reg[name]

        def loop() -> int:
            for _ in range(opts.get("test-count", 1)):
                test = core.run(ctor(dict(opts)))
                if test["results"].get("valid") is not True:
                    return TEST_FAILED
            return OK

        return _with_watch(opts, loop)

    return {"run": {"parser": build_parser, "opt_fn": test_opt_fn,
                    "run": run_}}


def _model_registry() -> Dict[str, Any]:
    """Model name -> constructor, shared by analyze/recover."""
    from jepsen_tpu.models import (
        CASRegister, FIFOQueue, Mutex, NoOp, SetModel, UnorderedQueue)
    return {"cas-register": CASRegister, "mutex": Mutex,
            "set": SetModel, "unordered-queue": UnorderedQueue,
            "fifo-queue": FIFOQueue, "noop": NoOp}


MODEL_CHOICES = ("cas-register", "mutex", "set", "unordered-queue",
                 "fifo-queue", "noop")


def _add_analysis_opts(p: argparse.ArgumentParser) -> None:
    """Checker options shared by the analyze and recover subcommands."""
    p.add_argument("--model", default="cas-register",
                   choices=list(MODEL_CHOICES))
    p.add_argument("--backend", default="cpu",
                   choices=["cpu", "tpu"])
    p.add_argument("--algorithm", default="auto",
                   choices=["auto", "wgl", "linear", "native",
                            "competition"])
    p.add_argument("--segment-iters", type=int, default=None,
                   metavar="N",
                   help="device-search iterations per checkpointed "
                        "segment (a positive integer)")
    p.add_argument("--profile", action="store_true",
                   help="capture a jax.profiler device trace of the "
                        "re-check into <run>/profile/ (JTPU_PROF=1)")


def _search_analytics_line(out) -> Optional[str]:
    """The ``# search:`` analytics line for analyze/recover output:
    dup-rate / prune-efficiency / frontier-area / truncation-loss from
    the counter lane the device search rolls up into the result's
    ``searchstats`` entry (doc/observability.md "Search analytics").
    None when the check ran without stats (JTPU_TRACE=0, or a backend
    that doesn't carry the lane)."""
    ss = (out or {}).get("searchstats")
    if not isinstance(ss, dict):
        return None
    return ("# search: dup-rate {dr:.0%}, prune-efficiency {pe:.0%}, "
            "frontier area {fa} (peak {fp}), truncation-losses {tr} "
            "over {lv} level(s)").format(
                dr=ss.get("dup-rate", 0.0),
                pe=ss.get("prune-efficiency", 0.0),
                fa=ss.get("frontier-area", 0),
                fp=ss.get("frontier-peak", 0),
                tr=ss.get("trunc-losses", 0),
                lv=ss.get("levels", 0))


def _print_contention_forecast(history) -> None:
    """The ``# contention:`` decomposability forecast lines
    (jepsen_tpu.analysis.contention) analyze/recover/plan print under
    the ``# plan:`` summary. Never raises."""
    from jepsen_tpu.analysis import contention
    for ln in contention.forecast_lines(contention.profile(history)):
        print(ln)


def analyze_cmd() -> dict:
    """The 'analyze' subcommand: offline re-check of a saved run — load
    a store directory's history and re-run the linearizable checker on
    any backend (the checkpoint/resume seam, repl.clj:6-13 + store
    reload; how a TPU host analyzes histories recorded elsewhere)."""

    def build_parser():
        p = Parser(prog="analyze",
                   description="Re-check a stored run offline.")
        p.add_argument("--store", default=None,
                       help="store directory (default: latest under "
                            "./store)")
        _add_analysis_opts(p)
        return p

    def run_(opts) -> int:
        import json as _json

        _apply_segment_iters(opts.pop("segment_iters", None))
        _apply_profile(opts.pop("profile", False))

        from jepsen_tpu import repl, store
        from jepsen_tpu.checker.wgl import linearizable
        models = _model_registry()
        if opts.get("store"):
            import os as _os
            if not _os.path.isdir(opts["store"]):
                # store.load tolerates missing files per-artifact; a
                # missing DIRECTORY is a typo'd path, not an empty run —
                # it must not re-check an empty history as valid
                print(f"no such store directory: {opts['store']}",
                      file=sys.stderr)
                return INVALID_ARGS
            test = store.load(opts["store"])
        else:
            test = repl.last_test()
        if test is None:
            print("no stored test found", file=sys.stderr)
            return INVALID_ARGS
        # Offline histories are arbitrary disk artifacts: surface their
        # structural lint summary (counts by rule) before re-checking,
        # so a damaged history is diagnosed here and not mid-search.
        from jepsen_tpu import analysis
        from jepsen_tpu.analysis.history_lint import lint_history
        print(analysis.summary_line(
            lint_history(test.get("history") or [])))
        # And the search-plan forecast next to it (doc/plan.md): the
        # candidate universe, the cheapest valid rung, and its
        # predicted footprint — so an offline re-check that would be
        # rejected or derated is diagnosed before the search starts.
        from jepsen_tpu.checker import plan as plan_mod
        print(plan_mod.summary_line(test.get("history") or [],
                                    models[opts["model"]]()))
        # Contention forecast (doc/perf.md): is this history
        # key-decomposable, and what speedup would decomposing buy?
        _print_contention_forecast(test.get("history") or [])
        checker = linearizable(models[opts["model"]](),
                               backend=opts["backend"],
                               algorithm=opts["algorithm"])
        # Offline re-checks are the longest searches; publish their
        # live progress to the run dir so `watch` / /live follow them
        # (and arm the device profiler for --profile re-checks).
        import time as _time

        from jepsen_tpu.checker import tpu as tpu_ns
        from jepsen_tpu.obs import observatory, profiler
        observatory.attach(test.get("store-dir"))
        profiler.attach(test.get("store-dir"))
        comp0 = tpu_ns.compile_snapshot()
        t0 = _time.perf_counter()
        try:
            out = repl.recheck(test, checker)
        finally:
            wall = _time.perf_counter() - t0
            observatory.detach()
            profiler.detach()
        # wall-clock attribution: cold-compile / execute / transfer
        # (doc/observability.md "Compile accounting")
        print(tpu_ns.compile_line(tpu_ns.compile_delta(comp0), wall))
        sline = _search_analytics_line(out)
        if sline:
            print(sline)
        # executor leakage: threads with_op_timeout abandoned (still
        # alive as daemons) in THIS process — nonzero in long soak
        # sessions that run + analyze in one interpreter, and the
        # motivation for the bounded-executor driver mode
        # (test["driver-threads"])
        from jepsen_tpu import core as core_ns
        leaked = core_ns.abandoned_threads()
        if leaked:
            print(f"# leaked-threads: {leaked} hung client-op thread(s) "
                  f"abandoned by op-timeout and still resident")
        print(_json.dumps(out, indent=2, default=repr))
        return OK if out.get("valid") is True else TEST_FAILED

    return {"analyze": {"parser": build_parser, "run": run_}}


def recover_cmd() -> dict:
    """The 'recover' subcommand: crash recovery for runs that died
    mid-flight. Scans the store for directories whose ``run.state``
    says running/analyzing but whose recording process is gone,
    reconstructs each history from its write-ahead journal
    (``history.wal``: torn-tail tolerant, dangling invokes reconciled
    to ``:info`` like worker-crash reincarnation), then feeds it
    through the same offline-analysis path as ``analyze`` so the
    crashed run still renders a verdict. Exit codes follow the test
    contract: 0 when every recovered run checks valid, 1 when a
    verdict is invalid or a recovery fails."""

    def build_parser():
        p = Parser(prog="recover",
                   description="Recover crashed runs from their "
                               "write-ahead journals and re-check them.")
        p.add_argument("--store", default=None,
                       help="a specific run directory (default: scan "
                            "--store-root for dead runs)")
        p.add_argument("--store-root", default="store",
                       help="store root to scan for dead runs")
        p.add_argument("--no-analyze", action="store_true",
                       help="reconstruct histories only; skip the "
                            "checker")
        p.add_argument("--force", action="store_true",
                       help="recover --store even if its run.state "
                            "says done or its pid looks alive")
        _add_analysis_opts(p)
        return p

    def run_(opts) -> int:
        import os as _os

        _apply_segment_iters(opts.pop("segment_iters", None))
        _apply_profile(opts.pop("profile", False))

        from jepsen_tpu import repl, store
        from jepsen_tpu.checker.wgl import linearizable
        models = _model_registry()

        if opts.get("store"):
            d = opts["store"]
            if not _os.path.isdir(d):
                print(f"no such store directory: {d}", file=sys.stderr)
                return INVALID_ARGS
            status = store.run_status(d)
            if status != "dead" and not opts.get("force"):
                print(f"# recovery: {d}: status="
                      f"{status or 'no run.state'}; nothing to recover "
                      f"(--force overrides)")
                return OK if status in ("done", "recovered") \
                    else INVALID_ARGS
            targets = [d]
        else:
            targets = store.dead_runs(opts.get("store_root") or "store")
            if not targets:
                print("# recovery: no dead runs found")
                return OK

        worst = OK
        for d in targets:
            try:
                rec = store.recover_run(d)
            except (OSError, ValueError) as e:
                print(f"# recovery: {d}: FAILED: {e}", file=sys.stderr)
                worst = TEST_FAILED
                continue
            s = rec["stats"]
            print(f"# recovery: {d}: {s['ops']} ops recovered "
                  f"({s['records']} WAL records, {s['torn']} torn, "
                  f"{s['corrupt']} corrupt, {s['reconciled']} dangling "
                  f"invoke(s) -> info)")
            # Span-trace recovery summary next to the lint/recovery
            # lines: trace.jsonl streams during the run exactly like
            # the WAL, so a killed run's timeline survives too.
            tpath = _os.path.join(d, "trace.jsonl")
            if _os.path.exists(tpath):
                from jepsen_tpu.obs import trace as trace_ns
                try:
                    trecs, tstats = trace_ns.read_trace(tpath)
                    print(f"# trace: {tstats['spans']} span(s) "
                          f"recovered from trace.jsonl "
                          f"({tstats['torn']} torn, "
                          f"{tstats['corrupt']} corrupt)")
                except OSError as e:
                    print(f"# trace: unreadable trace.jsonl: {e}",
                          file=sys.stderr)
            # Structural lint of the reconstructed history, printed
            # alongside the recovery stats; error-severity findings
            # (e.g. a corrupt WAL dropped a completion mid-stream and
            # left a process reusing itself) fail the recovery with a
            # diagnostic instead of feeding a damaged history to the
            # checker.
            from jepsen_tpu import analysis
            from jepsen_tpu.analysis import history_lint as hl
            # decode damage (corrupt/torn records) already degraded
            # gracefully inside read_wal and is reported above — the
            # gate here is about STRUCTURE the reconciler couldn't fix.
            findings = hl.lint_history(rec["history"], decode_errors=0)
            print(analysis.summary_line(findings))
            from jepsen_tpu.checker import plan as plan_mod
            print(plan_mod.summary_line(rec["history"],
                                        models[opts["model"]]()))
            _print_contention_forecast(rec["history"])
            errs = hl.errors(findings)
            if errs:
                for f in errs[:10]:
                    print(f"# lint: {d}: {f.format()}", file=sys.stderr)
                print(f"# recovery: {d}: FAILED: recovered history is "
                      f"malformed ({len(errs)} error finding(s); see "
                      f"above)", file=sys.stderr)
                worst = TEST_FAILED
                continue
            if opts.get("no_analyze"):
                continue
            test = store.load(d)
            checker = linearizable(models[opts["model"]](),
                                   backend=opts["backend"],
                                   algorithm=opts["algorithm"])
            import time as _time

            from jepsen_tpu.checker import tpu as tpu_ns
            comp0 = tpu_ns.compile_snapshot()
            t0 = _time.perf_counter()
            out = repl.recheck(test, checker)
            print(tpu_ns.compile_line(tpu_ns.compile_delta(comp0),
                                      _time.perf_counter() - t0))
            sline = _search_analytics_line(out)
            if sline:
                print(sline)
            store.write_results(d, out)
            store.write_state(d, "done", recovered=True, recovery=s)
            print(f"# recovery: {d}: verdict valid={out.get('valid')}")
            if out.get("valid") is not True:
                worst = TEST_FAILED
        return worst

    return {"recover": {"parser": build_parser, "run": run_}}


def explain_cmd() -> dict:
    """The 'explain' subcommand: why did a stored run get its verdict?
    Renders jepsen_tpu.explain's report — search-shape summary with a
    frontier sparkline for valid runs, the violating level / blocking
    ops / minimal witness region for invalid ones, and the cause chain
    (lossy truncation, window overflow, plan rejection, device faults —
    each citing its trail event) for unknowns. Torn-tolerant: a
    SIGKILLed run's partial artifacts degrade the report, they never
    crash it."""

    def build_parser():
        p = Parser(prog="explain",
                   description="Explain a stored run's verdict from "
                               "its artifacts (results, searchstats, "
                               "attempts trail).")
        p.add_argument("--store", default=None,
                       help="run directory (default: latest under "
                            "--store-root)")
        p.add_argument("--store-root", default="store")
        p.add_argument("--model", default="cas-register",
                       choices=list(MODEL_CHOICES),
                       help="model for the counterexample re-pack "
                            "(invalid verdicts only)")
        p.add_argument("--format", default="text",
                       choices=["text", "json"])
        return p

    def run_(opts) -> int:
        import json as _json
        import os as _os

        from jepsen_tpu import explain as explain_mod
        from jepsen_tpu import store
        d = opts.get("store")
        if d is None:
            t = store.latest(opts.get("store_root") or "store")
            d = t.get("store-dir") if t else None
        if not d or not _os.path.isdir(d):
            print(f"no such store directory: {d}", file=sys.stderr)
            return INVALID_ARGS
        model = _model_registry()[opts["model"]]()
        report = explain_mod.explain_report(d, model=model)
        if opts["format"] == "json":
            print(_json.dumps(report, indent=2, default=repr))
        else:
            print(explain_mod.render_text(report))
        return OK if report.get("valid") is True else TEST_FAILED

    return {"explain": {"parser": build_parser, "run": run_}}


def watch_cmd() -> dict:
    """The 'watch' subcommand: follow another process's in-flight run
    from its ``progress.json`` heartbeat (doc/observability.md). The
    supervised device search publishes level / frontier-width /
    configs-per-s / ETA after every checkpointed segment; this command
    renders that as a refreshing status line until the run's
    ``run.state`` goes terminal (done/dead/recovered). ``--once``
    prints a single line and exits (scripting / tests)."""

    def build_parser():
        p = Parser(prog="watch",
                   description="Live status line for an in-flight "
                               "run's device search.")
        p.add_argument("--store", default=None,
                       help="run directory (default: latest under "
                            "--store-root)")
        p.add_argument("--store-root", default="store")
        p.add_argument("--interval", type=float, default=1.0,
                       help="seconds between refreshes")
        p.add_argument("--once", action="store_true",
                       help="print one status line and exit")
        p.add_argument("--fleet", nargs="+", default=None,
                       metavar="HOST_DIR",
                       help="fleet mode: merge N hosts' run "
                            "directories (trace/metrics/progress) and "
                            "render per-host level, shard-imbalance "
                            "and headroom side by side "
                            "(obs/fleet.py, doc/observability.md)")
        return p

    def _watch_fleet(opts) -> int:
        import os as _os
        import time as _time

        from jepsen_tpu.obs import fleet
        dirs = list(opts["fleet"])
        # ALL dirs missing at start is a typo'd invocation; SOME
        # missing (or vanishing mid-poll) is a dead host, which the
        # fleet view renders as a host=dead row instead of exiting —
        # the whole point of watching a fleet is seeing hosts die
        if not any(_os.path.isdir(d) for d in dirs):
            print(f"no such host directory: {dirs[0]}",
                  file=sys.stderr)
            return INVALID_ARGS
        while True:
            merged = fleet.merge(dirs)
            for line in fleet.format_fleet(merged):
                print(line, flush=True)
            states = [(p or {}).get("state")
                      for p in merged["progress"].values()]
            done = all(s in (None, "done") for s in states)
            if opts.get("once") or done:
                return OK
            _time.sleep(max(opts.get("interval") or 1.0, 0.05))

    def run_(opts) -> int:
        import os as _os
        import time as _time

        from jepsen_tpu import store
        from jepsen_tpu.obs import observatory

        if opts.get("fleet"):
            return _watch_fleet(opts)
        d = opts.get("store")
        if d is None:
            t = store.latest(opts.get("store_root") or "store")
            d = t.get("store-dir") if t else None
        if not d or not _os.path.isdir(d):
            print(f"no such store directory: {d}", file=sys.stderr)
            return INVALID_ARGS
        tty = sys.stdout.isatty()
        while True:
            p = observatory.read_progress(d)
            state = store.run_status(d)
            if p is None:
                line = (f"# watch: no search progress published yet "
                        f"(state={state or 'unknown'})")
            else:
                line = observatory.format_status(p)
                if state and state != "running":
                    line += f" [{state}]"
            end = "\r" if tty else "\n"
            print(line, end=end, flush=True)
            done = (p or {}).get("state") == "done"
            if opts.get("once") or done \
                    or state in ("done", "dead", "recovered"):
                if tty:
                    print()
                return OK
            _time.sleep(max(opts.get("interval") or 1.0, 0.05))

    return {"watch": {"parser": build_parser, "run": run_}}


def top_cmd() -> dict:
    """The 'top' subcommand: one-screen live status of a serve
    directory — queue depth, fleet width, per-host frame age and
    straggler verdicts, SLO burn and the top tenant — read entirely
    from the published artifacts (``progress.json`` + the federated
    ``telemetry.frames``), so it works on a live daemon, a dead one,
    or over a copied directory (doc/observability.md "Fleet
    federation"). Pointed at a plain run directory it degrades to the
    `watch` search line."""

    def build_parser():
        p = Parser(prog="top",
                   description="One-screen live fleet/serve status "
                               "from a serve directory's published "
                               "artifacts.")
        p.add_argument("--store", default=None,
                       help="serve (or run) directory (default: "
                            "latest under --store-root)")
        p.add_argument("--store-root", default="store")
        p.add_argument("--interval", type=float, default=2.0,
                       help="seconds between refreshes")
        p.add_argument("--once", action="store_true",
                       help="print one screen and exit")
        return p

    def _screen(d: str) -> list:
        import time as _time

        from jepsen_tpu.obs import federation as obs_federation
        from jepsen_tpu.obs import observatory

        p = observatory.read_progress(d)
        when = _time.strftime("%H:%M:%S")
        lines = [f"# top: {d} at {when}"]
        if p is None:
            lines.append("# top: no progress.json yet (daemon not "
                         "started, or JTPU_TRACE=0)")
            return lines
        s = p.get("serve")
        if s is None:
            # a plain search run directory: reuse the watch line
            lines.append(observatory.format_status(p))
            return lines
        state = p.get("state") or "serving"
        lines.append(f"# top: state {state} | queue "
                     f"{s.get('queue-depth', 0)} | inflight "
                     f"{s.get('inflight', 0)} | done "
                     f"{s.get('completed', 0)} | rejected "
                     f"{s.get('rejected', 0)}")
        slo = s.get("slo")
        bits = []
        if slo is not None:
            n = slo.get("breached", 0)
            burn = slo.get("max-burn", 0)
            bits.append(f"slo BURN x{n} ({burn:g})" if n
                        else f"slo OK ({burn:g})")
        if s.get("usage-top"):
            t, dev = s["usage-top"][0], s["usage-top"][1]
            bits.append(f"top tenant {t}: {dev:g} device-s")
        if s.get("breakers-open"):
            bits.append(f"breakers-open {s['breakers-open']}")
        if bits:
            lines.append("# top: " + " | ".join(bits))
        if s.get("fleet-hosts") is not None:
            fbit = (f"fleet {s.get('fleet-live', 0)}/"
                    f"{s['fleet-hosts']} host(s)")
            if s.get("remeshes"):
                fbit += f" | remesh {s['remeshes']}"
            lines.append("# top: " + fbit)
        stragglers = set(s.get("straggler-hosts") or [])
        ages = obs_federation.fleet_ages(d)
        for host in sorted(set(ages) | stragglers):
            age = ages.get(host)
            abit = f"age {age:g}s" if age is not None else "age ?"
            sbit = "  STRAGGLER" if host in stragglers else ""
            lines.append(f"# top:   {host:<16} {abit}{sbit}")
        return lines

    def run_(opts) -> int:
        import os as _os
        import time as _time

        from jepsen_tpu import store

        d = opts.get("store")
        if d is None:
            t = store.latest(opts.get("store_root") or "store")
            d = t.get("store-dir") if t else None
        if not d or not _os.path.isdir(d):
            print(f"no such store directory: {d}", file=sys.stderr)
            return INVALID_ARGS
        while True:
            for line in _screen(d):
                print(line, flush=True)
            if opts.get("once"):
                return OK
            _time.sleep(max(opts.get("interval") or 2.0, 0.05))

    return {"top": {"parser": build_parser, "run": run_}}


def trace_cmd() -> dict:
    """The 'trace' subcommand family: read a run's ``trace.jsonl`` span
    artifact (doc/observability.md).

    * ``trace export --format chrome`` — Chrome trace-event JSON that
      loads directly in Perfetto (ui.perfetto.dev) or chrome://tracing;
      ``--format jsonl`` relays the raw records.
    * ``trace summary`` — per-span-name counts and total/max durations,
      printed as ``# trace:`` lines (plus the artifact's integrity:
      torn/corrupt line counts and distinct request trace ids).
    * ``trace request <id>`` — ONE request's distributed trace,
      stitched across the serve daemon and any fleet worker host dirs
      (doc/observability.md "Request tracing"): a text waterfall by
      default, ``--format chrome`` for Perfetto, ``--format json`` for
      the raw stitched document. ``<id>`` is a serve request id
      (resolved through serve.wal) or a literal 32-hex trace id.
    * ``trace find`` — federated trace search over a serve directory
      (doc/observability.md "Fleet federation"): filter completed
      requests by ``--tenant``, ``--min-device-s``, ``--error-class``
      and ``--host``, newest first; each hit links to
      ``trace request <id>``.

    Reading is torn-tail tolerant (the run may have been SIGKILLed
    mid-span, or still be running)."""

    def build_parser():
        p = Parser(prog="trace",
                   description="Export or summarize a run's span "
                               "trace (trace.jsonl).")
        p.add_argument("action",
                       choices=["export", "summary", "request",
                                "find"],
                       help="export: write Chrome/Perfetto (or raw "
                            "jsonl) trace; summary: per-span rollup; "
                            "request: one request's stitched "
                            "cross-process waterfall; find: federated "
                            "trace search over a serve directory")
        p.add_argument("rid", nargs="?", default=None, metavar="ID",
                       help="with `request`: the serve request id (or "
                            "32-hex trace id) to stitch")
        p.add_argument("--store", default=None,
                       help="store directory (default: latest under "
                            "./store)")
        p.add_argument("--format", default=None,
                       choices=["chrome", "jsonl", "json", "text"],
                       help="output format (default: chrome for "
                            "export, text for request; json = "
                            "machine-readable `summary`/`request` "
                            "output)")
        p.add_argument("-o", "--output", default=None, metavar="FILE",
                       help="write the export here (default: stdout)")
        p.add_argument("--top", type=int, default=None, metavar="N",
                       help="with `summary`: also print the N slowest "
                            "span names by SELF time (total minus "
                            "child spans) — the one slow span a "
                            "count-only rollup buries")
        p.add_argument("--host-dir", action="append", default=None,
                       metavar="DIR",
                       help="with `request`: extra fleet worker host "
                            "dir(s) whose trace.jsonl joins the "
                            "stitch (repeatable; host dirs under the "
                            "store dir are discovered automatically)")
        p.add_argument("--tenant", default=None,
                       help="with `find`: only this tenant's requests")
        p.add_argument("--min-device-s", type=float, default=None,
                       metavar="S",
                       help="with `find`: only requests that burned "
                            "at least S device-seconds")
        p.add_argument("--error-class", default=None, metavar="CLASS",
                       help="with `find`: only requests whose result "
                            "carries this error class")
        p.add_argument("--host", default=None,
                       help="with `find`: only requests with spans on "
                            "this fleet host")
        p.add_argument("--limit", type=int, default=50, metavar="N",
                       help="with `find`: newest N matches "
                            "(default 50)")
        return p

    def run_(opts) -> int:
        import json as _json
        import os as _os

        from jepsen_tpu import store
        from jepsen_tpu.obs import trace as trace_ns

        d = opts.get("store")
        if d is None:
            t = store.latest()
            d = t.get("store-dir") if t else None
        if not d or not _os.path.isdir(d):
            print(f"no such store directory: {d}", file=sys.stderr)
            return INVALID_ARGS
        fmt = opts.get("format") or "chrome"
        if opts["action"] == "request":
            return _trace_request(opts, d)
        if opts["action"] == "find":
            return _trace_find(opts, d)
        path = _os.path.join(d, trace_ns.TRACE_NAME)
        if not _os.path.exists(path):
            print(f"no {trace_ns.TRACE_NAME} in {d} (run predates "
                  f"tracing, or JTPU_TRACE=0)", file=sys.stderr)
            return INVALID_ARGS
        records, stats = trace_ns.read_trace(path)
        print(f"# trace: {stats['spans']} span(s) in {path} "
              f"({stats['torn']} torn, {stats['corrupt']} corrupt)",
              file=sys.stderr)
        # Device capture (opt-in --profile runs): merge the profiler's
        # kernel spans under their host spans as a device-track lane.
        # Degrades to host-only for runs without (or with a torn)
        # capture — a SIGKILL mid-capture must not break export.
        from jepsen_tpu.obs import profiler
        device = []
        if _os.path.isdir(profiler.profile_dir(d)):
            raw_dev, pstats = profiler.read_profile(d)
            device = profiler.merge_into_host(records, raw_dev)
            print(f"# trace: {len(device)} device span(s) merged from "
                  f"profile/ ({pstats['files']} file(s), "
                  f"{pstats['errors']} unreadable)", file=sys.stderr)

        if opts["action"] == "summary":
            rollup = trace_ns.summarize(records)
            kern = profiler.top_kernels(device, k=opts.get("top") or 10)
            if fmt == "json":
                print(_json.dumps({
                    "stats": stats, "summary": rollup,
                    "self-time": trace_ns.self_time_rollup(records),
                    "kernels": kern}, indent=2, default=repr))
                return OK
            # artifact integrity on STDOUT (the stderr banner is lost
            # in pipelines): torn = SIGKILL mid-write, corrupt = real
            # damage, traces = distinct request trace ids present
            print(f"# trace: integrity: {stats['torn']} torn, "
                  f"{stats['corrupt']} corrupt line(s); "
                  f"{stats['traces']} request trace id(s)")
            width = max((len(n) for n in rollup), default=4)
            print(f"# trace: {'name':<{width}}  count  total      max")
            for name, s in sorted(rollup.items(),
                                  key=lambda kv: -kv[1]["total-ns"]):
                print(f"# trace: {name:<{width}}  {s['count']:>5}  "
                      f"{s['total-ns'] / 1e9:>8.3f}s "
                      f"{s['max-ns'] / 1e9:>8.3f}s")
            if opts.get("top"):
                top = trace_ns.self_time_rollup(records)
                rows = sorted(top.items(),
                              key=lambda kv: -kv[1]["self-ns"]
                              )[:opts["top"]]
                print(f"# trace: top {len(rows)} by self-time")
                print(f"# trace: {'name':<{width}}  count  self"
                      f"       p95")
                for name, s in rows:
                    print(f"# trace: {name:<{width}}  {s['count']:>5}  "
                          f"{s['self-ns'] / 1e9:>8.3f}s "
                          f"{s['p95-ns'] / 1e9:>8.3f}s")
            if kern:
                print(f"# trace: device kernels, top {len(kern)} by "
                      f"self-time (per rung)")
                for row in kern:
                    rung = row.get("rung")
                    print(f"# trace:   {row['name'][:60]:<60} "
                          f"{row['count']:>5}  "
                          f"{row['self-ns'] / 1e9:>8.3f}s  "
                          f"rung={rung if rung else '?'}")
            return OK

        if fmt == "chrome":
            text = _json.dumps(trace_ns.to_chrome(
                records + device,
                process_name=_os.path.basename(d) or "jtpu"))
        else:
            text = "\n".join(_json.dumps(r, default=repr)
                             for r in records + device) + "\n"
        if opts.get("output"):
            with open(opts["output"], "w") as f:
                f.write(text)
            print(f"# trace: wrote {fmt} export to "
                  f"{opts['output']}", file=sys.stderr)
        else:
            print(text)
        return OK

    return {"trace": {"parser": build_parser, "run": run_}}


def _resolve_trace_id(store_dir: str, token: str):
    """A serve request id (via the daemon's serve.wal accepted
    records) or a literal 32-hex trace id -> the trace id, else
    None."""
    import os as _os

    t = (token or "").strip()
    low = t.lower()
    if len(low) == 32 and all(c in "0123456789abcdef" for c in low):
        return low
    from jepsen_tpu import journal as journal_ns
    from jepsen_tpu import serve as serve_ns
    wal = _os.path.join(store_dir, serve_ns.WAL_NAME)
    if not _os.path.exists(wal):
        return None
    try:
        records, _ = journal_ns.read_json_records(wal)
    except (OSError, ValueError):
        return None
    for r in records:
        if r.get("event") == "accepted" and r.get("id") == t:
            return r.get("trace")
    return None


def _trace_request(opts, d: str) -> int:
    """``jtpu trace request <id>`` — stitch one request's distributed
    trace across the serve daemon's trace.jsonl and any fleet worker
    host dirs, and render the single-request waterfall."""
    import json as _json

    from jepsen_tpu.obs import fleet as obs_fleet

    rid = opts.get("rid")
    if not rid:
        print("trace request needs a request id (or a 32-hex trace "
              "id): jtpu trace request <id> --store <serve-dir>",
              file=sys.stderr)
        return INVALID_ARGS
    tid = _resolve_trace_id(d, rid)
    if not tid:
        print(f"couldn't resolve {rid!r} to a trace id: no matching "
              f"accepted record in {d}/serve.wal and it is not a "
              f"32-hex trace id (JTPU_TRACE=0 at admission?)",
              file=sys.stderr)
        return INVALID_ARGS
    stitched = obs_fleet.stitch_request(d, tid,
                                        extra_dirs=opts.get("host_dir"))
    recs = stitched["records"]
    fmt = opts.get("format") or "text"
    text = None
    if fmt == "json":
        text = _json.dumps(stitched, indent=2, default=repr)
    elif fmt == "chrome":
        text = _json.dumps(obs_fleet.to_chrome(
            {"hosts": stitched["hosts"], "trace": recs}))
    elif fmt == "jsonl":
        text = "\n".join(_json.dumps(r, default=repr)
                         for r in recs) + "\n"
    if text is not None:
        if opts.get("output"):
            with open(opts["output"], "w") as f:
                f.write(text)
            print(f"# trace: wrote {fmt} request export to "
                  f"{opts['output']}", file=sys.stderr)
        else:
            print(text)
        return OK
    # the text waterfall: one aligned cross-process timeline
    hosts = stitched.get("hosts") or []
    method = stitched.get("method")
    print(f"# trace: request {rid}: trace {tid}: {len(recs)} "
          f"record(s) across {max(len(hosts), 1)} process(es)"
          + (f", clocks aligned via {method}" if method else ""))
    if not recs:
        print("# trace: no spans for this trace id (JTPU_TRACE=0, or "
              "the request has not run yet)")
        return OK
    t0 = min(int(r.get("ts", 0)) for r in recs)
    t1 = max(int(r.get("ts", 0)) + int(r.get("dur", 0) or 0)
             for r in recs)
    total = max(t1 - t0, 1)
    cols = 40
    namew = max(len(str(r.get("name", "?"))) for r in recs)
    hostw = max((len(str(r.get("host", ""))) for r in recs),
                default=0)
    for r in recs:
        ts = int(r.get("ts", 0))
        dur = int(r.get("dur", 0) or 0)
        a = (cols * (ts - t0)) // total
        b = max(a + 1, (cols * (ts - t0 + dur) + total - 1) // total)
        bar = " " * a + ("#" * (b - a) if dur else "|") \
            + " " * max(0, cols - b)
        host = str(r.get("host", ""))
        name = str(r.get("name", "?"))
        dur_bit = f"{dur / 1e9:>9.4f}s" if dur else "   instant"
        print(f"# trace: [{bar[:cols]}] {(ts - t0) / 1e9:>9.4f}s "
              f"{dur_bit}  {host:<{hostw}} {name:<{namew}}")
    return OK


def _trace_find(opts, d: str) -> int:
    """``jtpu trace find`` — federated trace search: filter a serve
    directory's completed requests by tenant / device-time / error
    class / fleet host and print one line per hit, newest first."""
    import json as _json

    from jepsen_tpu.obs import federation as obs_federation

    rows = obs_federation.trace_find(
        d,
        tenant=opts.get("tenant"),
        min_device_s=opts.get("min_device_s"),
        error_class=opts.get("error_class"),
        host=opts.get("host"),
        limit=opts.get("limit") or 50)
    fmt = opts.get("format") or "text"
    if fmt == "json":
        print(_json.dumps({"requests": rows}, indent=2, default=repr))
        return OK
    print(f"# trace: find: {len(rows)} matching request(s) in {d}")
    if not rows:
        return OK
    idw = max(len(str(r.get("id", ""))) for r in rows)
    tw = max((len(str(r.get("tenant", ""))) for r in rows), default=6)
    for r in rows:
        dev = r.get("device-s")
        hosts = " ".join(r.get("hosts") or []) or "-"
        err = r.get("error-class") or "-"
        print(f"# trace: {str(r.get('id', '')):<{idw}} "
              f"{str(r.get('tenant', '')):<{tw}} "
              f"valid={r.get('valid')} "
              f"secs={r.get('seconds') if r.get('seconds') is not None else '-'} "
              f"device-s={dev if dev is not None else '-'} "
              f"err={err} hosts={hosts}")
    print("# trace: drill in: jtpu trace request <id> --store " + d)
    return OK


def lint_cmd() -> dict:
    """The 'lint' subcommand: the seven-pass static analyzer
    (jepsen_tpu.analysis) — suite linter, history linter, JAX hazard
    pass, lockset pass, plan verification, deadlock pass,
    crash-consistency pass — gated against the committed baseline so
    CI fails on NEW findings only. See doc/lint.md for the rule
    catalog."""

    def build_parser():
        from jepsen_tpu import analysis
        p = Parser(prog="lint",
                   description="Static analysis: reject broken suites, "
                               "malformed histories, and JAX kernel "
                               "hazards before they burn device time.")
        p.add_argument("paths", nargs="*", metavar="PATH",
                       help="files to lint (.py through the code "
                            "passes, .jsonl/.wal through the history "
                            "pass); default: the whole repo at the "
                            "standard scopes")
        p.add_argument("--history", action="append", default=[],
                       metavar="FILE",
                       help="additionally lint a saved history "
                            "artifact (repeatable)")
        p.add_argument("--pass", dest="passes", action="append",
                       choices=list(analysis.PASSES), metavar="PASS",
                       help=f"run only these passes (repeatable; "
                            f"choices: {', '.join(analysis.PASSES)})")
        p.add_argument("--baseline", default=None, metavar="FILE",
                       help="baseline file (default: lint.baseline at "
                            "the repo root)")
        p.add_argument("--no-baseline", action="store_true",
                       help="ignore the baseline: report everything")
        p.add_argument("--write-baseline", action="store_true",
                       help="accept the current findings into the "
                            "baseline file (existing justifications "
                            "are preserved; new entries get a TODO "
                            "stub to fill in before committing)")
        p.add_argument("--prune-stale", action="store_true",
                       help="rewrite the baseline dropping entries "
                            "that no longer match any finding (the "
                            "accepted debt was fixed); surviving "
                            "entries keep their justifications")
        p.add_argument("--strict", action="store_true",
                       help="exit nonzero on new warnings too, not "
                            "just errors")
        p.add_argument("--format", default="text",
                       choices=["text", "json", "sarif"],
                       help="sarif: SARIF 2.1.0 of the NEW findings, "
                            "for forge PR annotation (doc/lint.md)")
        p.add_argument("--root", default=None,
                       help="repo root override (fixtures/tests)")
        return p

    def run_(opts) -> int:
        import json as _json

        from jepsen_tpu import analysis
        from jepsen_tpu.analysis import baseline as bl
        root = opts.get("root") or analysis.repo_root()
        passes = tuple(opts.get("passes") or analysis.PASSES)
        if opts["paths"]:
            findings = analysis.lint_files(
                list(opts["paths"]) + list(opts["history"]),
                passes=passes, root=root)
        else:
            findings = analysis.lint_repo(root=root, passes=passes,
                                          histories=opts["history"])

        bpath = opts.get("baseline") or bl.default_path(root)
        if opts.get("write_baseline"):
            bl.write(bpath, findings)
            print(f"# lint: baseline written to {bpath} "
                  f"({len(findings)} finding(s))")
            return OK
        if opts.get("prune_stale"):
            pruned = bl.prune(bpath, (f.key() for f in findings))
            for key in pruned:
                print(f"# lint: pruned stale baseline entry: {key}")
            print(f"# lint: {len(pruned)} stale baseline entr"
                  f"{'y' if len(pruned) == 1 else 'ies'} pruned from "
                  f"{bpath}")
            return OK
        accepted_keys = {} if opts.get("no_baseline") else bl.load(bpath)
        new, accepted = bl.split(findings, accepted_keys)

        if opts["format"] == "json":
            print(_json.dumps({
                "findings": [vars(f) for f in new],
                "accepted": [vars(f) for f in accepted],
                "counts": analysis.summarize(new),
            }, indent=2))
        elif opts["format"] == "sarif":
            from jepsen_tpu.analysis import sarif
            print(sarif.render(new), end="")
        else:
            for f in sorted(new, key=lambda x: (x.path, x.line)):
                print(f.format())
            print(analysis.summary_line(new))
            if accepted:
                print(f"# lint: {len(accepted)} finding(s) accepted "
                      f"by {bpath}")
        gate = [f for f in new
                if f.severity == "error"
                or (opts.get("strict") and f.severity == "warning")]
        if opts.get("strict") and accepted:
            # an acceptance whose justification is still the
            # --write-baseline TODO stub was never reviewed; strict
            # mode refuses to let it suppress a finding
            stub_keys = set(bl.stubbed(accepted_keys))
            unjustified = sorted({f.key() for f in accepted}
                                 & stub_keys)
            if unjustified:
                for key in unjustified:
                    print(f"# lint: --strict: baseline entry {key!r} "
                          f"still carries the stub justification "
                          f"({bl.STUB!r}); replace it with a real "
                          f"reason in {bpath}", file=sys.stderr)
                gate = gate or unjustified
        return TEST_FAILED if gate else OK

    return {"lint": {"parser": build_parser, "run": run_}}


def plan_cmd() -> dict:
    """The 'plan' subcommand: the ahead-of-time search-plan verifier
    (jepsen_tpu.checker.plan, doc/plan.md). Given a history artifact or
    bare dimensions, it enumerates the shape-bucket universe the device
    search would compile, abstract-evaluates every bucket with
    ``jax.eval_shape`` (zero XLA compiles, zero device executions),
    predicts the per-rung memory footprint and per-level cost, and
    verifies mesh divisibility and int32 encoding bounds — exiting
    nonzero on any error-severity PLAN-* finding, so admission control
    can be a shell one-liner."""

    def build_parser():
        p = Parser(prog="plan",
                   description="Verify a search plan ahead of any "
                               "device time: shape, memory, sharding "
                               "and bit-width safety.")
        p.add_argument("--history", default=None, metavar="FILE",
                       help="derive dims from a history artifact "
                            "(.jsonl)")
        p.add_argument("--dims", default=None, metavar="SPEC",
                       help="dims without a history: 'N_REQUIRED"
                            "[,N_CRASHED[,WINDOW_NEEDED[,N_EVENTS]]]' "
                            "or @file.json (keys: n_required, "
                            "n_crashed, window_needed, n_events, keys, "
                            "capacity, window, expand, mesh, "
                            "bytes_limit)")
        p.add_argument("--model", default="cas-register",
                       choices=list(MODEL_CHOICES))
        p.add_argument("--keys", type=int, default=1,
                       help="verify the keyed-batch plan for this many "
                            "independent keys")
        p.add_argument("--mesh", type=int, default=None, metavar="N",
                       help="additionally verify the pool-sharded plan "
                            "over a mesh axis of N devices")
        p.add_argument("--capacity", type=int, default=None,
                       help="pin the rung instead of the auto ladder")
        p.add_argument("--window", type=int, default=None)
        p.add_argument("--expand", type=int, default=None)
        p.add_argument("--bytes-limit", type=int, default=None,
                       help="byte budget override (default: "
                            "JTPU_PLAN_BYTES_LIMIT, else the smallest "
                            "device allocator limit, else unchecked)")
        p.add_argument("--no-trace", action="store_true",
                       help="skip jax.eval_shape abstract evaluation "
                            "(arithmetic checks only; no jax needed)")
        p.add_argument("--no-cost", action="store_true",
                       help="skip the lower()-only XLA cost analysis")
        p.add_argument("--format", default="text",
                       choices=["text", "json", "sarif"])
        return p

    def run_(opts) -> int:
        import json as _json

        from jepsen_tpu.checker import plan as plan_mod
        from jepsen_tpu.models.core import kernel_spec_for
        model = _model_registry()[opts["model"]]()
        kernel = kernel_spec_for(model)
        hist = None
        if opts.get("history"):
            import os as _os
            if not _os.path.exists(opts["history"]):
                print(f"no such history file: {opts['history']}",
                      file=sys.stderr)
                return INVALID_ARGS
            from jepsen_tpu.history import History
            with open(opts["history"], encoding="utf-8") as f:
                h = History.from_jsonl(f.read())
            hist = h
            dims = plan_mod.PlanDims.from_history(h, model)
            if dims is None:
                print(f"model {opts['model']} has no integer kernel; "
                      f"nothing to plan", file=sys.stderr)
                return INVALID_ARGS
        elif opts.get("dims"):
            spec = opts["dims"]
            if spec.startswith("@"):
                with open(spec[1:], encoding="utf-8") as f:
                    d = _json.load(f)
                dims = plan_mod.PlanDims(
                    n_required=int(d["n_required"]),
                    n_crashed=int(d.get("n_crashed", 0)),
                    window_needed=int(d.get("window_needed", 1)),
                    n_events=(int(d["n_events"])
                              if d.get("n_events") is not None else None),
                    keys=int(d.get("keys", opts.get("keys") or 1)))
                # the fixture may pin shape knobs the flags didn't
                for knob in ("capacity", "window", "expand", "mesh",
                             "bytes_limit"):
                    if opts.get(knob) is None and d.get(knob) is not None:
                        opts[knob] = int(d[knob])
            else:
                try:
                    parts = [int(x) for x in spec.split(",")]
                except ValueError:
                    print(f"--dims {spec!r}: expected comma-separated "
                          f"integers or @file.json", file=sys.stderr)
                    return INVALID_ARGS
                if not parts or len(parts) > 4:
                    print(f"--dims {spec!r}: 1-4 integers", file=sys.stderr)
                    return INVALID_ARGS
                dims = plan_mod.PlanDims(*parts,
                                         keys=opts.get("keys") or 1)
        else:
            print("pass --history FILE or --dims SPEC", file=sys.stderr)
            return INVALID_ARGS
        if (opts.get("keys") or 1) > 1 and dims.keys == 1:
            dims = plan_mod.PlanDims(dims.n_required, dims.n_crashed,
                                     dims.window_needed, dims.n_events,
                                     keys=opts["keys"])
        report = plan_mod.analyze(
            dims, kernel=kernel,
            capacity=opts.get("capacity"), window=opts.get("window"),
            expand=opts.get("expand"), mesh_axis=opts.get("mesh"),
            bytes_limit=opts.get("bytes_limit"),
            trace=not opts.get("no_trace"),
            cost=not opts.get("no_cost") and not opts.get("no_trace"))
        errors = [i for i in report["issues"]
                  if i["severity"] == "error"]
        if opts["format"] == "json":
            print(_json.dumps(report, indent=2))
        elif opts["format"] == "sarif":
            from jepsen_tpu.analysis import plan_lint, sarif
            print(sarif.render(
                plan_lint.findings_from_report(report)), end="")
        else:
            d = report["dims"]
            lim = report["bytes-limit"]
            print(f"# plan: dims n={d['n-required']}+{d['n-crashed']} "
                  f"window<={d['window-needed']} keys={d['keys']}, "
                  f"limit "
                  f"{'unchecked' if lim is None else f'{lim} B'}")
            if hist is not None:
                # --history plans also get the contention forecast:
                # whether decomposing (ROADMAP item 2) beats raising
                # the rung that this plan is about to select
                _print_contention_forecast(hist)
            for i in report["issues"]:
                if not i.get("label"):   # dims-level, not per-candidate
                    print(f"# plan: {i['severity'].upper()} "
                          f"[{i['rule']}] {i['message']}")
            for c in report["candidates"]:
                mark = "ok " if c["status"] == "ok" else "REJ"
                fp = c["footprint"]["total-bytes"]
                line = (f"# plan: {mark} {c['label']:<36} "
                        f"{fp / 1e6:9.3f} MB")
                if c.get("cost"):
                    line += (f" {c['cost']['flops'] / 1e6:10.2f} "
                             f"MFLOP/level")
                rules = sorted({i["rule"] for i in c["issues"]})
                if rules:
                    line += "  " + " ".join(rules)
                print(line)
            print(f"# plan: selected {report['selected'] or 'NONE'}; "
                  f"{len(errors)} error finding(s)")
        return TEST_FAILED if errors else OK

    return {"plan": {"parser": build_parser, "run": run_}}


def usage_cmd() -> dict:
    """The 'usage' subcommand: per-tenant usage totals for a serve
    daemon directory — device-seconds, ops checked, transfer bytes,
    gang-lane share, wall seconds, request count — recomputed straight
    from the WAL's ``done`` records (:func:`jepsen_tpu.obs.usage.
    from_wal`), so it works offline, after a SIGKILL, and always agrees
    with a live daemon's ``GET /usage`` (the meter folds the exact same
    records). Requires a daemon run with the telemetry stack on
    (JTPU_TSDB, the default)."""

    def build_parser():
        p = Parser(prog="usage",
                   description="Per-tenant usage totals from a serve "
                               "daemon's request journal.")
        p.add_argument("--serve-dir", default=None, metavar="DIR",
                       help="daemon directory (default: "
                            "<store-root>/serve)")
        p.add_argument("--store-root", default="store")
        p.add_argument("--tenant", default=None,
                       help="one tenant only (default: all)")
        p.add_argument("--json", action="store_true",
                       help="raw JSON instead of the table")
        return p

    def run_(opts) -> int:
        import json as _json
        import os as _os

        from jepsen_tpu import serve as serve_ns
        from jepsen_tpu.obs import usage as obs_usage
        d = opts.get("serve_dir") \
            or _os.path.join(opts.get("store_root") or "store", "serve")
        wal = _os.path.join(d, serve_ns.WAL_NAME)
        if not _os.path.exists(wal):
            print(f"no request journal at {wal}", file=sys.stderr)
            return INVALID_ARGS
        doc = obs_usage.from_wal(wal)
        tenant = opts.get("tenant")
        if tenant is not None:
            doc["tenants"] = {t: u for t, u in doc["tenants"].items()
                              if t == tenant}
        if opts.get("json"):
            print(_json.dumps(doc, indent=2))
            return OK
        for t in sorted(doc["tenants"]):
            u = doc["tenants"][t]
            print(f"# usage: {t}: {u['requests']} request(s), "
                  f"{u['ops']:g} op(s), {u['device-s']:g} device-s, "
                  f"{u['bytes']:g} byte(s), lane-share "
                  f"{u['lane-share']:g}, {u['seconds']:g}s wall")
        tot = doc["total"]
        print(f"# usage: total: {tot['requests']} request(s), "
              f"{tot['ops']:g} op(s), {tot['device-s']:g} device-s, "
              f"{tot['bytes']:g} byte(s), {tot['seconds']:g}s wall")
        return OK

    return {"usage": {"parser": build_parser, "run": run_}}


def flightrec_cmd() -> dict:
    """The 'flightrec' subcommand: read a serve daemon's flight-
    recorder dumps (doc/observability.md "Flight recorder"). Bare, it
    lists the ``flightrec/`` inventory newest first; with a dump name
    it summarizes that dump (reason, window, span/trace counts, the
    trigger's extra doc) or relays the raw JSON with ``--json``."""

    def build_parser():
        p = Parser(prog="flightrec",
                   description="List or show a serve daemon's "
                               "flight-recorder dumps.")
        p.add_argument("dump", nargs="?", default=None,
                       help="dump file name (default: list them)")
        p.add_argument("--serve-dir", default=None, metavar="DIR",
                       help="daemon directory (default: "
                            "<store-root>/serve)")
        p.add_argument("--store-root", default="store")
        p.add_argument("--json", action="store_true",
                       help="raw JSON instead of the summary")
        return p

    def run_(opts) -> int:
        import json as _json
        import os as _os
        import time as _time

        from jepsen_tpu.obs import flightrec as obs_flightrec
        d = opts.get("serve_dir") \
            or _os.path.join(opts.get("store_root") or "store", "serve")
        if opts.get("dump"):
            doc = obs_flightrec.load_dump(d, opts["dump"])
            if doc is None:
                print(f"no such dump: {opts['dump']}", file=sys.stderr)
                return INVALID_ARGS
            if opts.get("json"):
                print(_json.dumps(doc, indent=2))
                return OK
            when = _time.strftime(
                "%Y-%m-%d %H:%M:%S",
                _time.localtime(doc.get("wall-ts") or 0))
            print(f"# flightrec: {opts['dump']}: "
                  f"reason={doc.get('reason')} at {when}, "
                  f"window {doc.get('window-s'):g}s")
            print(f"# flightrec: {len(doc.get('spans') or [])} span(s), "
                  f"{len(doc.get('trace-ids') or [])} trace id(s), "
                  f"{len(doc.get('metrics') or {})} metric(s)")
            if doc.get("extra"):
                print(f"# flightrec: extra: "
                      f"{_json.dumps(doc['extra'], default=repr)}")
            for tid in doc.get("trace-ids") or []:
                print(f"# flightrec: trace {tid}")
            return OK
        dumps = obs_flightrec.list_dumps(d)
        if opts.get("json"):
            print(_json.dumps({"dumps": dumps}, indent=2))
            return OK
        if not dumps:
            print(f"# flightrec: no dumps under "
                  f"{_os.path.join(d, obs_flightrec.DIR_NAME)}")
            return OK
        for rec in dumps:
            when = _time.strftime(
                "%Y-%m-%d %H:%M:%S",
                _time.localtime(rec.get("wall-ts") or 0))
            print(f"# flightrec: {rec['name']}: "
                  f"reason={rec.get('reason')} at {when}, "
                  f"{rec.get('spans', 0)} span(s), "
                  f"{rec.get('trace-ids', 0)} trace(s), "
                  f"{rec.get('bytes', 0)} byte(s)")
        return OK

    return {"flightrec": {"parser": build_parser, "run": run_}}


def merge_commands(*cmds: dict) -> dict:
    out: Dict[str, dict] = {}
    for c in cmds:
        out.update(c)
    return out


def run(subcommands: Dict[str, dict], argv: Sequence[str]) -> int:
    """Dispatch a subcommand; returns the exit code (cli.clj:201-276)."""
    argv = list(argv)
    command = argv[0] if argv else None
    if command not in subcommands:
        print("Usage: COMMAND [OPTIONS ...]")
        print("Commands:", ", ".join(sorted(subcommands)))
        return INVALID_ARGS
    spec = subcommands[command]
    try:
        parser = spec["parser"]()
        try:
            ns = parser.parse_args(argv[1:])
        except _ArgError as e:
            print(str(e), file=sys.stderr)
            return INVALID_ARGS
        opts = vars(ns)
        opt_fn = spec.get("opt_fn")
        if opt_fn:
            try:
                opts = opt_fn(opts)
            except _ArgError as e:
                print(str(e), file=sys.stderr)
                return INVALID_ARGS
        return spec["run"](opts)
    except SystemExit as e:  # argparse --help exits 0
        return int(e.code or 0)
    except Exception:  # noqa: BLE001 (cli.clj:271-275)
        print("Oh jeez, I'm sorry, Jepsen broke. Here's why:",
              file=sys.stderr)
        traceback.print_exc()
        return CRASHED


def main(subcommands: Dict[str, dict],
         argv: Optional[Sequence[str]] = None) -> None:
    sys.exit(run(subcommands, argv if argv is not None else sys.argv[1:]))


def default_commands() -> dict:
    """The stock subcommand set: runner + analyzer + recovery + linter
    + plan verifier + trace tooling + live watch + fleet top + server
    + streaming client + verdict explainer + usage meter +
    flight-recorder reader (what ``python -m jepsen_tpu``
    dispatches)."""
    return merge_commands(suite_run_cmd(), analyze_cmd(), recover_cmd(),
                          lint_cmd(), plan_cmd(), trace_cmd(),
                          watch_cmd(), top_cmd(), serve_cmd(),
                          stream_cmd(), explain_cmd(), usage_cmd(),
                          flightrec_cmd())


if __name__ == "__main__":  # default main
    main(default_commands())
