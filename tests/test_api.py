"""REST API over a unix socket (SURVEY.md §1 layer 7 slim REST analog +
§3.1 "api server up (unix socket REST)") and the CLI's --api live mode."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.runtime.api import APIServer, UnixAPIClient
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr
from oracle import PacketRecord

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def live_engine(tmp_path):
    sock = str(tmp_path / "cilium-tpu.sock")
    cfg = DaemonConfig(ct_capacity=1024, auto_regen=False,
                       api_socket=sock, flowlog_mode="all")
    eng = Engine(cfg, datapath=FakeDatapath(DaemonConfig(ct_capacity=1024)))
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.add_endpoint(["k8s:role=fe"], ips=("192.168.1.30",), ep_id=3)
    eng.apply_policy([{
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "ingress": [{"fromEndpoints": [{"matchLabels": {"role": "fe"}}],
                     "toPorts": [{"ports": [
                         {"port": "443", "protocol": "TCP"}]}]}]}])
    eng.regenerate()
    # classify some traffic so ct/flows have content
    s16, _ = parse_addr("192.168.1.30")
    d16, _ = parse_addr("192.168.1.10")
    pkts = [PacketRecord(s16, d16, 40000, 443, C.PROTO_TCP, C.TCP_SYN,
                         False, 1, C.DIR_INGRESS),
            PacketRecord(s16, d16, 40001, 80, C.PROTO_TCP, C.TCP_SYN,
                         False, 1, C.DIR_INGRESS)]
    eng.classify(batch_from_records(pkts, eng.active.snapshot.ep_slot_of))
    eng.start_background()
    yield eng, sock
    eng.stop()


class TestAPIServer:
    def test_healthz_and_status(self, live_engine):
        eng, sock = live_engine
        client = UnixAPIClient(sock)
        code, doc = client.get("/v1/healthz")
        assert code == 200 and doc["status"] == "ok"
        code, st = client.get("/v1/status")
        assert code == 200
        assert st["endpoints"] == 2 and st["rules"] == 1
        assert st["conntrack"]["live"] >= 1

    def test_endpoints_and_identities(self, live_engine):
        eng, sock = live_engine
        client = UnixAPIClient(sock)
        code, eps = client.get("/v1/endpoints")
        assert code == 200 and [e["ep_id"] for e in eps] == [1, 3]
        code, one = client.get("/v1/endpoints/1")
        assert code == 200 and one["ingress"]["enforced"]
        code, _ = client.get("/v1/endpoints/99")
        assert code == 404
        code, ids = client.get("/v1/identities")
        assert code == 200 and len(ids) > 2

    def test_policy_roundtrip_and_trace(self, live_engine):
        eng, sock = live_engine
        client = UnixAPIClient(sock)
        code, rules = client.get("/v1/policy")
        assert code == 200 and len(rules) == 1
        # live apply through the API → revision bumps, verdicts change
        code, doc = client.post("/v1/policy", [{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{"toPorts": [{"ports": [
                {"port": "80", "protocol": "TCP"}]}]}]}])
        assert code == 200 and doc["revision"] > 1
        code, tr = client.post("/v1/policy/trace", {
            "ep": 1, "direction": "ingress", "remote": "192.168.1.30",
            "dport": 80, "proto": "TCP"})
        assert code == 200 and tr["verdict"] == "ALLOWED"
        code, tr = client.post("/v1/policy/trace", {
            "ep": 1, "direction": "ingress", "remote": "192.168.1.30",
            "dport": 22, "proto": "TCP"})
        assert code == 200 and tr["verdict"] == "DENIED"

    def test_ct_flows_metrics(self, live_engine):
        eng, sock = live_engine
        client = UnixAPIClient(sock)
        code, ct = client.get("/v1/ct?limit=8")
        assert code == 200 and len(ct) >= 1
        assert ct[0]["dport"] == 443
        code, flows = client.get("/v1/flows?last=10")
        assert code == 200 and len(flows) == 2
        code, text = client.get("/v1/metrics")
        assert code == 200 and "cilium_tpu" in text or "policy_revision" in text

    def test_config_patch_enforcement(self, live_engine):
        eng, sock = live_engine
        client = UnixAPIClient(sock)
        code, cfgdoc = client.get("/v1/config")
        assert code == 200 and cfgdoc["enforcement_mode"] == "default"
        code, _ = client.patch("/v1/config", {"enforcement_mode": "never"})
        assert code == 200
        assert eng.ctx.enforcement_mode == "never"
        # never-mode: previously denied traffic now allowed
        code, tr = client.post("/v1/policy/trace", {
            "ep": 1, "direction": "ingress", "remote": "192.168.1.30",
            "dport": 22})
        assert tr["verdict"] == "ALLOWED"
        code, err = client.patch("/v1/config", {"enforcement_mode": "bogus"})
        assert code == 400
        code, err = client.patch("/v1/config", {"batch_size": 1})
        assert code == 400

    def test_health_probe_route(self, live_engine):
        eng, sock = live_engine
        client = UnixAPIClient(sock)
        code, doc = client.get("/v1/health")
        assert code == 200
        assert set(doc) == {"1", "3", "engine"} or set(doc) == {1, 3, "engine"}
        assert doc["engine"]["state"] == C.HEALTH_OK

    def test_stale_socket_is_replaced(self, live_engine, tmp_path):
        eng, sock = live_engine
        eng.stop()
        assert not os.path.exists(sock)
        # a stale file at the path must not block a restart
        with open(sock, "w") as f:
            f.write("stale")
        eng2 = Engine(DaemonConfig(ct_capacity=1024, auto_regen=False,
                                   api_socket=sock),
                      datapath=FakeDatapath(DaemonConfig(ct_capacity=1024)))
        eng2.start_background()
        code, _ = UnixAPIClient(sock).get("/v1/healthz")
        assert code == 200
        eng2.stop()


class TestCLILive:
    def _run(self, argv):
        return subprocess.run(
            [sys.executable, "-m", "cilium_tpu.cli.main"] + argv,
            capture_output=True, text=True, timeout=60, cwd=REPO_ROOT,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})

    def test_cli_live_commands(self, live_engine):
        eng, sock = live_engine
        out = self._run(["status", "--api", sock])
        assert out.returncode == 0, out.stderr
        assert "Endpoints:        2" in out.stdout
        out = self._run(["endpoint", "list", "--api", sock, "-o", "json"])
        assert out.returncode == 0
        assert [e["ep_id"] for e in json.loads(out.stdout)] == [1, 3]
        out = self._run(["policy", "trace", "--api", sock, "--ep", "1",
                         "--direction", "ingress",
                         "--remote", "192.168.1.30", "--dport", "443"])
        assert out.returncode == 0 and "ALLOWED" in out.stdout
        out = self._run(["ct", "list", "--api", sock])
        assert out.returncode == 0 and "443" in out.stdout
        out = self._run(["monitor", "--api", sock, "-o", "json"])
        assert out.returncode == 0
        assert len(out.stdout.strip().splitlines()) == 2
        out = self._run(["metrics", "--api", sock])
        assert out.returncode == 0 and "policy_revision" in out.stdout

    def test_cli_requires_a_source(self, live_engine):
        out = self._run(["status"])
        assert out.returncode != 0
