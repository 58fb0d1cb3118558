"""Service deployable: layered config, entrypoint, smoke client.

Reference: server/routerlicious/Dockerfile + config/config.json (nconf
layering) + the docker-compose single-box deployment. Docker itself is
exercised when available (CI images without a daemon skip that case and
still verify the whole path in-proc: config -> server_main -> sockets ->
smoke client -> device-served read)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from fluidframework_tpu.service.server_main import (
    DEFAULTS,
    build_server,
    load_config,
)
from fluidframework_tpu.service.smoke_client import run as smoke_run


def test_config_layering(tmp_path):
    p = tmp_path / "config.json"
    p.write_text(json.dumps({"port": 9999, "partitions": 2}))
    cfg = load_config(str(p), env={"FLUID_PARTITIONS": "8"})
    assert cfg["port"] == 9999  # file over defaults
    assert cfg["partitions"] == 8  # env over file
    assert cfg["device_backend"] is True  # defaults fill the rest
    cfg2 = load_config(str(p), env={}, overrides={"port": 1234})
    assert cfg2["port"] == 1234  # CLI overrides everything


def test_config_rejects_unknown_keys(tmp_path):
    p = tmp_path / "config.json"
    p.write_text(json.dumps({"prot": 1}))
    with pytest.raises(ValueError):
        load_config(str(p), env={})


def test_repo_config_file_is_valid():
    root = os.path.join(os.path.dirname(__file__), "..")
    cfg = load_config(os.path.join(root, "config", "config.json"), env={})
    assert set(cfg) == set(DEFAULTS)


def test_entrypoint_serves_smoke_client():
    """The deployable path in-proc: build_server from the repo config
    (ephemeral port), run the compose smoke client against it."""
    root = os.path.join(os.path.dirname(__file__), "..")
    cfg = load_config(os.path.join(root, "config", "config.json"), env={})
    cfg.update(host="127.0.0.1", port=0)  # ephemeral
    srv = build_server(cfg)
    srv.start()
    try:
        assert smoke_run("127.0.0.1", srv.port, timeout=30.0) == 0
    finally:
        srv.stop()


def test_server_main_process_starts_and_stops(tmp_path):
    """The actual CLI process comes up, prints its listening line, and
    shuts down cleanly on SIGTERM (what the container runs)."""
    p = tmp_path / "config.json"
    p.write_text(json.dumps({"host": "127.0.0.1", "port": 0}))
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..")
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    # Pin the child to the CPU through its environment, before it
    # imports JAX: tests never reach for a chip, and one process per chip.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "fluidframework_tpu.service.server_main",
         "--config", str(p)],
        stdout=subprocess.PIPE, env=env, text=True,
    )
    try:
        line = proc.stdout.readline()
        info = json.loads(line)
        assert info["event"] == "listening" and info["port"] > 0
        assert smoke_run("127.0.0.1", info["port"], timeout=30.0) == 0
        proc.terminate()
        assert proc.wait(timeout=15) == 0
    finally:
        if proc.poll() is None:
            proc.kill()


docker = shutil.which("docker")


@pytest.mark.skipif(
    docker is None, reason="docker unavailable in this environment"
)
def test_docker_compose_smoke():  # pragma: no cover - needs a daemon
    root = os.path.join(os.path.dirname(__file__), "..")
    res = subprocess.run(
        [docker, "compose", "up", "--build", "--abort-on-container-exit",
         "--exit-code-from", "smoke"],
        cwd=root, capture_output=True, timeout=900,
    )
    subprocess.run([docker, "compose", "down"], cwd=root, capture_output=True)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
