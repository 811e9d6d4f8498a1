"""The XLA-as-verifier CI step (SURVEY.md §4 test/verifier analog): every
datapath shape combo must compile, and the CLI command + profiler hook
work."""

import os
import subprocess
import sys

import pytest

from cilium_tpu.compile.verifier import apply_budget, verify_configs

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sweep():
    # ONE compile sweep serves every assertion (budget checks are pure
    # post-processing of the memory stats)
    return verify_configs(batch=64, quick=True)


class TestVerifier:
    def test_all_combos_compile(self, sweep):
        assert len(sweep) >= 10
        bad = [(r.name, r.error) for r in sweep if not r.ok]
        assert not bad, bad
        names = {r.name for r in sweep}
        # the key shapes are all present
        assert "v4only+v4" in names
        assert "dual+l7+l7dict" in names
        assert "dual+addr" in names
        assert "rule-padded" in names

    def test_memory_budget_rejects(self, sweep):
        reports = apply_budget(sweep, max_hbm_bytes=1)
        assert any(not r.ok and "memory budget" in r.error for r in reports)
        # the original sweep is budget-free and still all-ok
        assert all(r.ok for r in sweep)

    def test_cli_verify(self):
        out = subprocess.run(
            [sys.executable, "-m", "cilium_tpu.cli.main", "verify",
             "--batch", "64", "--quick"],
            capture_output=True, text=True, timeout=300, cwd=REPO_ROOT,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        assert "combos verifier-accepted" in out.stdout
        assert "FAIL" not in out.stdout


class TestProfilerHook:
    def test_profile_classify_writes_trace(self, tmp_path):
        from cilium_tpu.kernels.records import batch_from_records
        from cilium_tpu.runtime.config import DaemonConfig
        from cilium_tpu.runtime.datapath import JITDatapath
        from cilium_tpu.runtime.engine import Engine
        from cilium_tpu.utils import constants as C
        from cilium_tpu.utils.ip import parse_addr
        from oracle import PacketRecord

        eng = Engine(DaemonConfig(ct_capacity=1024, auto_regen=False),
                     datapath=JITDatapath(DaemonConfig(ct_capacity=1024,
                                                       auto_regen=False)))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egress": [{"toCIDR": ["10.0.0.0/8"]}]}])
        eng.regenerate()
        s16, _ = parse_addr("192.168.1.10")
        d16, _ = parse_addr("10.1.2.3")
        batch = batch_from_records(
            [PacketRecord(s16, d16, 40000, 443, C.PROTO_TCP, C.TCP_SYN,
                          False, 1, C.DIR_EGRESS)],
            eng.active.snapshot.ep_slot_of)
        trace_dir = str(tmp_path / "xprof")
        out = eng.profile_classify(batch, trace_dir, now=1000)
        assert bool(out["allow"][0])
        # a plugin trace directory with at least one event file exists
        found = []
        for root, _dirs, files in os.walk(trace_dir):
            found.extend(files)
        assert found, "no trace files written"
