"""CLI exit-code contract + option processing; web browser routes."""

import json
import os
import urllib.request

import pytest

from jepsen_tpu import cli, web
from jepsen_tpu.checker import Unbridled
from jepsen_tpu.checker.wgl import linearizable
from jepsen_tpu.models import CASRegister
from jepsen_tpu import generator as gen
from jepsen_tpu.testing import atom_test


class TestOptionProcessing:
    def test_concurrency_multiplier(self):
        assert cli.parse_concurrency("3n", 5) == 15
        assert cli.parse_concurrency("10", 5) == 10
        with pytest.raises(Exception):
            cli.parse_concurrency("3x", 5)

    def test_test_opt_fn_defaults(self):
        opts = cli.test_opt_fn({
            "node": None, "nodes_file": None, "username": "root",
            "password": "root", "strict_host_key_checking": False,
            "ssh_private_key": None, "ssh_mode": None,
            "concurrency": "1n", "test_count": 1, "time_limit": 60})
        assert opts["nodes"] == cli.DEFAULT_NODES
        assert opts["concurrency"] == 5
        assert opts["ssh"]["username"] == "root"

    def test_resilience_flags(self, monkeypatch):
        monkeypatch.delenv("JTPU_SEGMENT_ITERS", raising=False)
        p = cli.Parser(prog="t")
        cli.add_test_opts(p)
        ns = p.parse_args(["--op-timeout", "2.5", "--segment-iters",
                           "256"])
        opts = cli.test_opt_fn(vars(ns))
        assert opts["op-timeout"] == 2.5
        assert opts["segment-iters"] == 256
        # the flag deploys the device-checker knob via env (like the
        # other JTPU_* tuning knobs)
        assert os.environ["JTPU_SEGMENT_ITERS"] == "256"
        monkeypatch.delenv("JTPU_SEGMENT_ITERS", raising=False)

    @pytest.mark.parametrize("value", ["0", "-2"])
    @pytest.mark.parametrize("opts", ["test", "analysis"])
    def test_segment_iters_must_be_positive(self, monkeypatch, opts,
                                            value):
        monkeypatch.delenv("JTPU_SEGMENT_ITERS", raising=False)
        p = cli.Parser(prog="t")
        if opts == "test":
            cli.add_test_opts(p)
        else:
            cli._add_analysis_opts(p)
        ns = p.parse_args(["--segment-iters", value])
        with pytest.raises(ValueError, match=f"got {value}$"):
            if opts == "test":
                cli.test_opt_fn(vars(ns))
            else:
                cli._apply_segment_iters(ns.segment_iters)
        assert "JTPU_SEGMENT_ITERS" not in os.environ

    def test_resilience_flags_default_off(self, monkeypatch):
        monkeypatch.delenv("JTPU_SEGMENT_ITERS", raising=False)
        p = cli.Parser(prog="t")
        cli.add_test_opts(p)
        opts = cli.test_opt_fn(vars(p.parse_args([])))
        assert opts["op-timeout"] is None
        assert opts["segment-iters"] is None
        assert "JTPU_SEGMENT_ITERS" not in os.environ

    def test_nodes_file(self, tmp_path):
        f = tmp_path / "nodes"
        f.write_text("h1\nh2\n\nh3\n")
        opts = cli.test_opt_fn({"node": None, "nodes_file": str(f),
                                "concurrency": "2n"})
        assert opts["nodes"] == ["h1", "h2", "h3"]
        assert opts["concurrency"] == 6

    def test_explicit_nodes_override_default(self):
        opts = cli.test_opt_fn({"node": ["a", "b"], "concurrency": "1n"})
        assert opts["nodes"] == ["a", "b"]
        assert opts["concurrency"] == 2


class TestRunDispatch:
    def test_unknown_command_exits_254(self, capsys):
        assert cli.run({}, ["bogus"]) == cli.INVALID_ARGS
        assert cli.run({}, []) == cli.INVALID_ARGS

    def test_bad_args_exit_254(self):
        cmds = cli.single_test_cmd(lambda opts: atom_test())
        assert cli.run(cmds, ["test", "--no-such-flag"]) == cli.INVALID_ARGS
        assert cli.run(cmds, ["test", "--concurrency", "x3"]) == \
            cli.INVALID_ARGS

    def test_help_exits_0(self, capsys):
        cmds = cli.single_test_cmd(lambda opts: atom_test())
        assert cli.run(cmds, ["test", "--help"]) == cli.OK
        assert "--concurrency" in capsys.readouterr().out

    def test_crash_exits_255(self):
        def boom(opts):
            raise RuntimeError("kaboom")
        cmds = {"test": {"parser": lambda: cli.Parser(prog="t"),
                         "run": boom}}
        assert cli.run(cmds, ["test"]) == cli.CRASHED

    def _test_fn(self, valid: bool):
        def build(opts):
            t = atom_test(**{
                "nodes": opts["nodes"],
                "concurrency": opts["concurrency"],
                "store-root": opts["_root"],
            })
            t["generator"] = gen.limit(20, _cas_mix())
            t["checker"] = (linearizable(CASRegister()) if valid
                            else _AlwaysInvalid())
            return t
        return build

    def test_end_to_end_valid_run_exits_0(self, tmp_path):
        cmds = cli.single_test_cmd(
            self._test_fn(valid=True),
            opt_fn=lambda o: {**o, "_root": str(tmp_path)})
        rc = cli.run(cmds, ["test", "--ssh-mode", "dummy",
                            "--concurrency", "3"])
        assert rc == cli.OK
        # store artifacts + latest symlinks exist
        latest = tmp_path / "latest"
        assert latest.exists()
        assert (latest / "results.json").exists()
        assert (latest / "history.jsonl").exists()
        results = json.loads((latest / "results.json").read_text())
        assert results["valid"] is True

    def test_end_to_end_invalid_run_exits_1(self, tmp_path):
        cmds = cli.single_test_cmd(
            self._test_fn(valid=False),
            opt_fn=lambda o: {**o, "_root": str(tmp_path)})
        rc = cli.run(cmds, ["test", "--ssh-mode", "dummy",
                            "--concurrency", "3"])
        assert rc == cli.TEST_FAILED


class _AlwaysInvalid(Unbridled):
    def check(self, test, history, opts=None):
        return {"valid": False}


def _cas_mix():
    import random

    def next_op(test, process):
        r = random.random()
        if r < 0.4:
            return {"f": "read", "value": None}
        if r < 0.8:
            return {"f": "write", "value": random.randrange(5)}
        return {"f": "cas", "value": (random.randrange(5),
                                      random.randrange(5))}
    return next_op


@pytest.fixture()
def store_with_runs(tmp_path):
    for name, ts, valid in [("etcd-cas", "20260729T100000.000", True),
                            ("etcd-cas", "20260729T110000.000", False),
                            ("queue", "20260729T120000.000", "unknown")]:
        d = tmp_path / name / ts
        d.mkdir(parents=True)
        (d / "results.json").write_text(json.dumps({"valid": valid}))
        (d / "history.txt").write_text("0 invoke read nil\n")
        (d / "jepsen.log").write_text("hello log\n")
    return tmp_path


class TestWeb:
    def get(self, server, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.server_port}{path}") as r:
            return r.status, r.read(), r.headers

    def test_routes(self, store_with_runs):
        server = web.serve_background(root=str(store_with_runs))
        try:
            code, body, _ = self.get(server, "/")
            assert code == 200
            assert b"etcd-cas" in body and b"queue" in body
            assert web.VALID_COLORS[False].encode() in body

            code, body, _ = self.get(server, "/files/etcd-cas/")
            assert code == 200 and b"20260729T100000.000" in body

            code, body, hdrs = self.get(
                server, "/files/etcd-cas/20260729T100000.000/history.txt")
            assert code == 200 and b"invoke read" in body
            assert hdrs["Content-Type"].startswith("text/plain")

            code, body, hdrs = self.get(
                server, "/files/etcd-cas/20260729T100000.000?zip")
            assert code == 200
            assert hdrs["Content-Type"] == "application/zip"
            assert body[:2] == b"PK"
        finally:
            server.shutdown()

    def test_path_traversal_blocked(self, store_with_runs):
        server = web.serve_background(root=str(store_with_runs))
        try:
            import urllib.error
            with pytest.raises(urllib.error.HTTPError) as ei:
                self.get(server, "/files/../../../etc/passwd")
            assert ei.value.code in (403, 404)
        finally:
            server.shutdown()


class TestSuiteRunCmd:
    """The generic 'run --suite <name>' subcommand."""

    def test_registered_suites_are_choices(self, capsys):
        from jepsen_tpu import cli, suites
        rc = cli.run(cli.suite_run_cmd(), ["run", "--help"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "--suite" in out and "etcd" in out

    def test_unknown_suite_exits_254(self, capsys):
        from jepsen_tpu import cli
        rc = cli.run(cli.suite_run_cmd(), ["run", "--suite", "bogus"])
        assert rc == cli.INVALID_ARGS

    def test_default_main_lists_run_and_serve(self, capsys):
        from jepsen_tpu import cli
        rc = cli.run(cli.merge_commands(cli.suite_run_cmd(),
                                        cli.serve_cmd()), [])
        assert rc == cli.INVALID_ARGS
        out = capsys.readouterr().out
        assert "run" in out and "serve" in out


class TestAnalyzeCmd:
    """Offline re-check of a stored run ('analyze')."""

    def test_recheck_committed_examples(self, capsys):
        import os
        from jepsen_tpu import cli
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        good = os.path.join(repo, "examples", "store", "atom-cas")
        rc = cli.run(cli.analyze_cmd(), ["analyze", "--store", good])
        assert rc == cli.OK
        out = capsys.readouterr().out
        assert '"valid": true' in out
        bad = os.path.join(repo, "examples", "store",
                           "atom-cas-lost-update")
        rc = cli.run(cli.analyze_cmd(), ["analyze", "--store", bad])
        assert rc == cli.TEST_FAILED

    def test_missing_store_is_invalid_args(self, tmp_path, monkeypatch):
        from jepsen_tpu import cli
        monkeypatch.chdir(tmp_path)  # no ./store here
        rc = cli.run(cli.analyze_cmd(), ["analyze"])
        assert rc == cli.INVALID_ARGS


class TestZipStreaming:
    """The zip download must stream with bounded memory
    (web.clj:250-271 pipes the archive; an in-memory zip of a large
    store directory would balloon control-node RSS)."""

    @staticmethod
    def _rss_kb():
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
        return 0

    def test_zip_is_chunked_and_valid(self, tmp_path):
        import io
        import urllib.request
        import zipfile as zf

        run = tmp_path / "t" / "20260730T000000.000"
        run.mkdir(parents=True)
        (run / "history.txt").write_text("invoke read\n")
        (run / "results.json").write_text('{"valid": true}')
        server = web.serve_background(root=str(tmp_path))
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.server_port}"
                    f"/files/t/20260730T000000.000?zip") as r:
                assert r.headers.get("Transfer-Encoding") == "chunked"
                assert r.headers.get("Content-Length") is None
                body = r.read()
            z = zf.ZipFile(io.BytesIO(body))
            assert sorted(z.namelist()) == ["history.txt",
                                            "results.json"]
            assert z.read("history.txt") == b"invoke read\n"
            assert z.testzip() is None
        finally:
            server.shutdown()

    def test_zip_memory_stays_bounded(self, tmp_path):
        """Download a ~96 MB incompressible run dir; server+client RSS
        must not grow by anything near the archive size (the old
        BytesIO implementation grew by ~96 MB)."""
        import os as _os
        import urllib.request

        run = tmp_path / "big" / "20260730T000001.000"
        run.mkdir(parents=True)
        chunk = _os.urandom(1 << 20)
        with open(run / "data.bin", "wb") as f:
            for _ in range(96):
                f.write(chunk)
        server = web.serve_background(root=str(tmp_path))
        try:
            rss0 = self._rss_kb()
            total = 0
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.server_port}"
                    f"/files/big/20260730T000001.000?zip") as r:
                while True:
                    piece = r.read(1 << 20)
                    if not piece:
                        break
                    total += len(piece)
            grown_kb = self._rss_kb() - rss0
        finally:
            server.shutdown()
        assert total > 90 * (1 << 20)   # archive really was ~96 MB
        assert grown_kb < 32 * 1024, grown_kb  # << archive size
