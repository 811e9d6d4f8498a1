"""The long-lived agent process (upstream cilium-agent analog): start,
serve the API, checkpoint on shutdown, restore on restart — connection
survival across restarts is the headline upstream feature this mirrors."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from cilium_tpu.runtime.api import UnixAPIClient

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every wait of this file. The spawned interpreter imports jax and builds an
# engine while the other xdist workers load the machine: alone it is up in
# ~3 s, under 24 busy loops on 8 cores it took over a minute.
SPAWN_WAIT_S = 240      # start-up, and exit after SIGTERM
CLIENT_WAIT_S = 60      # one REST call


def _spawn_agent(tmp_path, extra=(), healthz=True, poll_s=0.05):
    """Start an agent and wait for its API socket, polled every ``poll_s``,
    and (``healthz``) for the API to answer."""
    sock = str(tmp_path / "agent.sock")
    state = str(tmp_path / "state")
    cfg = {"ct_capacity": 1024, "api_socket": sock, "state_dir": state,
           "flowlog_mode": "all"}
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps(cfg))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, "-m", "cilium_tpu.cli.main", "agent", "run",
         "--config", str(cfg_path), "--fake-datapath", *extra],
        cwd=REPO_ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    deadline = time.time() + SPAWN_WAIT_S
    while not os.path.exists(sock):
        if proc.poll() is not None:
            raise AssertionError(f"agent died: {proc.stderr.read()}")
        assert time.time() < deadline, "agent never came up"
        time.sleep(poll_s)
    if not healthz:
        return proc, sock, state
    # the socket file may exist before serve_forever runs; poll healthz
    client = UnixAPIClient(sock, timeout=CLIENT_WAIT_S)
    while True:
        try:
            code, _ = client.get("/v1/healthz")
            if code == 200:
                break
        except OSError:
            pass
        assert time.time() < deadline, "api never answered"
        time.sleep(0.05)
    return proc, sock, state


class TestAgentProcess:
    def test_serve_policy_shutdown_restore(self, tmp_path):
        proc, sock, state = _spawn_agent(tmp_path)
        try:
            client = UnixAPIClient(sock, timeout=CLIENT_WAIT_S)
            code, _ = client.post("/v1/policy", [{
                "endpointSelector": {"matchLabels": {"app": "web"}},
                "ingress": [{"toPorts": [{"ports": [
                    {"port": "80", "protocol": "TCP"}]}]}]}])
            assert code == 200
            code, st = client.get("/v1/status")
            assert st["rules"] == 1
        finally:
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=SPAWN_WAIT_S) == 0, proc.stderr.read()
        # clean shutdown: socket removed, checkpoint written
        assert not os.path.exists(sock)
        assert os.path.exists(os.path.join(state, "state.json"))

        # restart restores the applied policy (upgrade-survival analog)
        proc2, sock2, _ = _spawn_agent(tmp_path)
        try:
            code, st = UnixAPIClient(
                sock2, timeout=CLIENT_WAIT_S).get("/v1/status")
            assert code == 200 and st["rules"] == 1, st
        finally:
            proc2.send_signal(signal.SIGTERM)
            assert proc2.wait(timeout=SPAWN_WAIT_S) == 0, proc2.stderr.read()

    def test_sigterm_as_soon_as_the_socket_exists_still_checkpoints(
            self, tmp_path):
        """The agent takes SIGTERM gracefully from the moment a client can
        see it: the handlers are installed before the API socket is bound,
        so a signal that races start-up still ends in the final checkpoint
        and exit 0, not in the default action's kill."""
        proc, sock, state = _spawn_agent(tmp_path, healthz=False,
                                         poll_s=0.001)
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=SPAWN_WAIT_S) == 0, proc.stderr.read()
        assert not os.path.exists(sock)
        assert os.path.exists(os.path.join(state, "state.json"))

    def test_oneshot(self, tmp_path):
        sock = str(tmp_path / "a.sock")
        state = str(tmp_path / "st")
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable, "-m", "cilium_tpu.cli.main", "agent", "run",
             "--api-socket", sock, "--state-dir", state,
             "--fake-datapath", "--oneshot"],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True,
            timeout=SPAWN_WAIT_S)
        assert out.returncode == 0, out.stderr
        assert os.path.exists(os.path.join(state, "state.json"))
        assert not os.path.exists(sock)
