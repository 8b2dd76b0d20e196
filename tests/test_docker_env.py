"""Smoke tests for the docker/ cluster environment (VERDICT r04 #7).

The reference ships docker/docker-compose.yml + up.sh for running suites
against real 5-node clusters (reference docker/README.md). This build
host has no docker daemon, so these tests validate everything that can
be validated statically — compose structure, shell syntax, Dockerfile
references — and run the real `docker compose config` / build only when
a docker binary exists.
"""

import os
import shutil
import subprocess

import pytest
import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCKER = os.path.join(REPO, "docker")


def _compose():
    with open(os.path.join(DOCKER, "docker-compose.yml")) as f:
        return yaml.safe_load(f)


class TestComposeFile:
    def test_parses_and_has_all_services(self):
        cfg = _compose()
        services = cfg["services"]
        for svc in ("control", "node", "n1", "n2", "n3", "n4", "n5"):
            assert svc in services, f"missing service {svc}"

    def test_five_nodes_extend_the_node_template(self):
        services = _compose()["services"]
        for i in range(1, 6):
            n = services[f"n{i}"]
            assert n.get("extends") in ("node", {"service": "node"}), n
            assert n["hostname"] == f"n{i}"

    def test_control_links_every_node(self):
        control = _compose()["services"]["control"]
        assert sorted(control["links"]) == ["n1", "n2", "n3", "n4", "n5"]

    def test_build_contexts_exist_with_dockerfiles(self):
        services = _compose()["services"]
        for svc in ("control", "node"):
            build = services[svc]["build"]
            ctx = build if isinstance(build, str) else build["context"]
            d = os.path.normpath(os.path.join(DOCKER, ctx))
            assert os.path.isdir(d), d
            assert os.path.isfile(os.path.join(d, "Dockerfile")), d

    def test_env_files_are_generated_by_up_sh(self):
        """The env_file entries point into ./secret, which up.sh
        creates; the script must reference every file compose needs."""
        services = _compose()["services"]
        with open(os.path.join(DOCKER, "up.sh")) as f:
            up = f.read()
        for svc in ("control", "node"):
            env = services[svc]["env_file"]
            for e in env if isinstance(env, list) else [env]:
                assert "secret/" in e, e
                assert os.path.basename(e) in up, e


class TestShellScripts:
    @pytest.mark.parametrize("script", [
        "up.sh", "control/init.sh", "node/init.sh"])
    def test_sh_syntax(self, script):
        path = os.path.join(DOCKER, script)
        assert os.path.isfile(path), path
        proc = subprocess.run(["sh", "-n", path], capture_output=True,
                              text=True)
        assert proc.returncode == 0, proc.stderr

    def test_up_sh_copies_framework_into_control_context(self):
        """The control image COPYs its build context; up.sh must stage
        the framework source there first."""
        with open(os.path.join(DOCKER, "up.sh")) as f:
            up = f.read()
        assert "cp -r ../jepsen_tpu" in up
        assert "docker compose up" in up


class TestDockerfiles:
    @pytest.mark.parametrize("ctx", ["control", "node"])
    def test_copy_sources_exist_or_are_staged(self, ctx):
        """Every COPY source must exist in the build context, or be one
        of the paths up.sh stages (control/jepsen_tpu etc.)."""
        staged = {"jepsen_tpu", "tests", "."}
        d = os.path.join(DOCKER, ctx)
        with open(os.path.join(d, "Dockerfile")) as f:
            for line in f:
                line = line.strip()
                if not line.startswith(("COPY ", "ADD ")):
                    continue
                srcs = line.split()[1:-1]
                for s in srcs:
                    if s.startswith("--"):
                        continue
                    if s in staged or s.split("/")[0] in staged:
                        continue
                    assert os.path.exists(os.path.join(d, s)), (
                        f"{ctx}/Dockerfile references missing {s}")

    @pytest.mark.parametrize("ctx,port", [("control", "8080"),
                                          ("node", "22")])
    def test_from_and_expose(self, ctx, port):
        with open(os.path.join(DOCKER, ctx, "Dockerfile")) as f:
            content = f.read()
        assert content.strip().startswith(("# ", "FROM"))
        assert "FROM " in content
        assert f"EXPOSE {port}" in content

    def test_node_image_has_the_os_layer_tools(self):
        """os/debian.py's setup path expects these on a node."""
        with open(os.path.join(DOCKER, "node", "Dockerfile")) as f:
            content = f.read()
        for tool in ("openssh-server", "sudo", "wget", "iptables",
                     "faketime", "iproute2"):
            assert tool in content, tool


needs_docker = pytest.mark.skipif(
    shutil.which("docker") is None,
    reason="no docker binary on this host (zero-egress build image)")


@needs_docker
class TestRealCompose:
    def test_compose_config_validates(self):
        """`docker compose config` fully resolves the file (extends,
        env_file presence, link graph) — the strongest check short of a
        build."""
        env = os.path.join(DOCKER, "secret")
        os.makedirs(env, exist_ok=True)
        for f in ("control.env", "node.env"):
            p = os.path.join(env, f)
            if not os.path.exists(p):
                with open(p, "w") as fh:
                    fh.write("PLACEHOLDER=1\n")
        proc = subprocess.run(["docker", "compose", "config"],
                              cwd=DOCKER, capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
