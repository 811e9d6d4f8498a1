"""Fault-injection harness + supervised degradation tests.

Covers the FaultInjector registry itself, then each injection point's
recovery contract: regen failure → DEGRADED + last-good serving,
controller/trigger backoff schedules, clustermesh peer flap + prefix
hand-off + clock skew, corrupt checkpoint → cold-start fallback, and the
hardened API socket. The end-to-end chaos scenario runs via the CLI (fast
subset on the fake datapath in tier-1; the full jit run is `slow` and is
what `make chaos` executes).
"""

import json
import os
import socket
import stat

import pytest

from cilium_tpu.cli.main import main as cli_main
from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.runtime import checkpoint as ckpt
from cilium_tpu.runtime import faults as faults_mod
from cilium_tpu.runtime.api import APIServer, UnixAPIClient
from cilium_tpu.runtime.clustermesh import ClusterMesh
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.controller import Controller, Trigger, backoff_delay
from cilium_tpu.runtime.datapath import FakeDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.runtime.faults import (FAULTS, FaultInjected, FaultInjector,
                                       FaultSpec)
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr
from oracle import PacketRecord

POLICY = [{
    "endpointSelector": {"matchLabels": {"app": "web"}},
    "egress": [{"toCIDR": ["10.0.0.0/8"],
                "toPorts": [{"ports": [{"port": "443",
                                        "protocol": "TCP"}]}]}],
}]


@pytest.fixture(autouse=True)
def _clean_faults():
    """The FAULTS singleton is process-wide state: reset around every test
    so an armed point never leaks into an unrelated test."""
    FAULTS.reset()
    yield
    FAULTS.reset()


def small_engine(**kw):
    kw.setdefault("ct_capacity", 4096)
    kw.setdefault("auto_regen", False)
    cfg = DaemonConfig(**kw)
    return Engine(cfg, datapath=FakeDatapath(cfg))


def pkt(src, dst, sp, dp, ep_id=1, direction=C.DIR_EGRESS):
    s16, sv6 = parse_addr(src)
    d16, dv6 = parse_addr(dst)
    return PacketRecord(s16, d16, sp, dp, C.PROTO_TCP, C.TCP_SYN,
                        sv6 or dv6, ep_id, direction)


def web_engine():
    eng = small_engine()
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.apply_policy(POLICY)
    return eng


def classify_allows(eng, slot_of, now=100):
    out = eng.classify(batch_from_records(
        [pkt("192.168.1.10", "10.1.2.3", 40000, 443),    # allowed
         pkt("192.168.1.10", "10.1.2.3", 40001, 80),     # denied port
         pkt("192.168.1.10", "8.8.8.8", 40002, 443)],    # denied CIDR
        slot_of), now=now)
    return [bool(a) for a in out["allow"]]


# --------------------------------------------------------------------------- #
class TestFaultInjector:
    def test_fail_n_times_then_passes(self):
        inj = FaultInjector(env={})
        inj.arm("regen.compile", mode="fail", times=2)
        for _ in range(2):
            with pytest.raises(FaultInjected):
                inj.fire("regen.compile")
        inj.fire("regen.compile")                  # spec exhausted
        st = inj.stats()["regen.compile"]
        assert st["fired"] == 3 and st["trips"] == 2 and st["armed"]

    def test_fail_forever_and_disarm(self):
        inj = FaultInjector(env={})
        inj.arm("checkpoint.write", mode="fail")   # times=None → every fire
        for _ in range(5):
            with pytest.raises(FaultInjected):
                inj.fire("checkpoint.write")
        inj.disarm("checkpoint.write")
        inj.fire("checkpoint.write")

    def test_prob_is_seed_deterministic(self):
        def pattern(seed):
            inj = FaultInjector(env={})
            inj.arm("api.handler", mode="prob", prob=0.5, seed=seed)
            out = []
            for _ in range(64):
                try:
                    inj.fire("api.handler")
                    out.append(0)
                except FaultInjected:
                    out.append(1)
            return out

        assert pattern(7) == pattern(7)            # no wall-clock anywhere
        assert pattern(7) != pattern(8)
        assert 0 < sum(pattern(7)) < 64            # actually probabilistic

    def test_delay_mode_sleeps_instead_of_raising(self, monkeypatch):
        slept = []
        monkeypatch.setattr(faults_mod.time, "sleep",
                            lambda s: slept.append(s))
        inj = FaultInjector(env={})
        inj.arm("shim.rx_ring", mode="delay", delay_s=0.25)
        inj.fire("shim.rx_ring")
        assert slept == [0.25]

    def test_unknown_point_and_bad_spec_rejected(self):
        inj = FaultInjector(env={})
        with pytest.raises(ValueError, match="unknown injection point"):
            inj.arm("no.such.point")
        with pytest.raises(ValueError):
            FaultSpec(mode="explode")
        with pytest.raises(ValueError):
            FaultSpec(mode="prob", prob=1.5)
        with pytest.raises(ValueError, match="bad fault entry"):
            inj.load_spec("regen.compile")         # no '='

    def test_env_var_grammar(self):
        inj = FaultInjector(env={
            faults_mod.ENV_VAR: "regen.compile=fail:10;"
                                "clustermesh.peer_read=prob:0.5:seed=7,"
                                "shim.rx_ring=delay:0.01"})
        armed = inj.armed()
        assert armed["regen.compile"].mode == "fail"
        assert armed["regen.compile"].times == 10
        assert armed["clustermesh.peer_read"].prob == 0.5
        assert armed["clustermesh.peer_read"].seed == 7
        assert armed["shim.rx_ring"].delay_s == 0.01

    def test_bad_multi_entry_spec_arms_nothing(self):
        """All-or-nothing arming: a 400 on entry N must not leave entries
        1..N-1 live on a production agent."""
        inj = FaultInjector(env={})
        with pytest.raises(ValueError, match="unknown injection point"):
            inj.load_spec("regen.compile=fail;no.such.point=fail")
        assert inj.armed() == {}
        with pytest.raises(ValueError, match="bad fault entry"):
            inj.load_spec("regen.compile=fail:2:bogus=1")
        assert inj.armed() == {}

    def test_inject_context_manager_restores_previous(self):
        inj = FaultInjector(env={})
        inj.arm("regen.compile", mode="fail", times=99)
        with inj.inject("regen.compile", mode="delay", delay_s=0.0):
            assert inj.armed()["regen.compile"].mode == "delay"
        assert inj.armed()["regen.compile"].mode == "fail"
        with inj.inject("api.handler", mode="fail"):
            assert "api.handler" in inj.armed()
        assert "api.handler" not in inj.armed()    # was not armed before

    def test_register_point(self):
        faults_mod.register_point("test.extra", "self-registered point")
        try:
            inj = FaultInjector(env={})
            inj.arm("test.extra", mode="fail", times=1)
            with pytest.raises(FaultInjected):
                inj.fire("test.extra")
        finally:
            faults_mod.POINTS.pop("test.extra", None)


# --------------------------------------------------------------------------- #
class TestEngineDegradation:
    def test_regen_storm_serves_last_good(self):
        """The acceptance scenario: 10 consecutive compile failures, zero
        classify errors, DEGRADED with the failure count, then recovery."""
        eng = web_engine()
        slot_of = eng.active.snapshot.ep_slot_of
        baseline = classify_allows(eng, slot_of)
        assert baseline == [True, False, False]
        assert eng.health()["state"] == C.HEALTH_OK

        FAULTS.arm("regen.compile", mode="fail", times=10)
        for i in range(10):
            eng._mark_dirty()                      # classify retries compile
            assert classify_allows(eng, slot_of, now=200 + i) == baseline
        h = eng.health()
        assert h["state"] == C.HEALTH_DEGRADED
        assert h["consecutive_regen_failures"] == 10
        assert "FaultInjected" in h["last_regen_error"]
        assert eng.metrics.counters["regen_failures_total"] == 10
        assert eng.metrics.gauges["engine_degraded"] == 1

        # 11th attempt: the fail:10 spec is exhausted → recovery
        compiled = eng.regenerate(force=True)
        assert compiled is not None
        h = eng.health()
        assert h["state"] == C.HEALTH_OK
        assert h["consecutive_regen_failures"] == 0
        assert eng.metrics.gauges["engine_degraded"] == 0
        assert classify_allows(eng, slot_of, now=300) == baseline

    def test_stale_when_policy_committed_but_uncompilable(self):
        eng = web_engine()
        _ = eng.active
        FAULTS.arm("regen.compile", mode="fail")
        # committed policy change bumps repo.revision past the active
        # snapshot: verdicts are correct for an OLDER policy world → STALE
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egress": [{"toCIDR": ["172.16.0.0/12"]}]}])
        eng.regenerate()
        h = eng.health()
        assert h["state"] == C.HEALTH_STALE
        assert h["repo_revision"] > h["active_revision"]
        FAULTS.disarm()
        eng.regenerate(force=True)
        assert eng.health()["state"] == C.HEALTH_OK

    def test_cold_start_failure_still_raises(self):
        """With no last-good snapshot there is nothing to serve: the very
        first regeneration failing must surface, not degrade silently."""
        eng = small_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        FAULTS.arm("regen.compile", mode="fail", times=1)
        with pytest.raises(FaultInjected):
            eng.regenerate(force=True)
        eng.regenerate(force=True)                 # retry succeeds
        assert eng.health()["state"] == C.HEALTH_OK

    def test_health_probe_carries_engine_state(self):
        eng = web_engine()
        _ = eng.active
        report = eng.health_probe(now=100)
        assert report["engine"]["state"] == C.HEALTH_OK
        FAULTS.arm("regen.compile", mode="fail")
        eng._mark_dirty()
        report = eng.health_probe(now=101)
        assert report["engine"]["state"] == C.HEALTH_DEGRADED
        assert report[1]["reachable"] in (True, False)   # probe still ran


# --------------------------------------------------------------------------- #
class TestBackoff:
    def test_backoff_delay_schedule(self):
        assert backoff_delay(0, 1.0, 60.0) == 0.0
        assert backoff_delay(1, 1.0, 60.0, rng=None) == 1.0
        assert backoff_delay(4, 1.0, 60.0, rng=None) == 8.0
        assert backoff_delay(50, 1.0, 60.0, rng=None) == 60.0   # capped

    def test_backoff_jitter_bounded_and_deterministic(self):
        import random
        d1 = [backoff_delay(n, 1.0, 60.0, random.Random(3))
              for n in range(1, 8)]
        d2 = [backoff_delay(n, 1.0, 60.0, random.Random(3))
              for n in range(1, 8)]
        assert d1 == d2                            # seeded → replayable
        for n, d in enumerate(d1, start=1):
            base = min(60.0, 2.0 ** (n - 1))
            assert base <= d <= base * 1.1 + 1e-9
        # the cap is a hard ceiling — jitter never pushes past it
        assert backoff_delay(50, 1.0, 60.0, random.Random(1)) == 60.0

    def test_controller_backoff_counts_and_recovery(self):
        boom = [True]

        def flaky():
            if boom[0]:
                raise RuntimeError("store down")

        c = Controller("test-ctrl", flaky, interval=5.0,
                       backoff_base=0.5, backoff_max=8.0)
        for n in range(1, 6):
            c.run_once()
            assert c.status.consecutive_failures == n
            base = min(8.0, 0.5 * (2 ** (n - 1)))
            assert base <= c.status.last_backoff_s <= base * 1.1 + 1e-9
        assert c.status.failure_count == 5
        assert "store down" in c.status.last_error
        boom[0] = False
        c.run_once()
        assert c.status.consecutive_failures == 0
        assert c.status.last_backoff_s == 5.0      # back to the interval
        assert c.status.success_count == 1

    def test_controller_schedule_is_replayable(self):
        def always_fails():
            raise RuntimeError("x")

        def schedule(name):
            c = Controller(name, always_fails, interval=1.0)
            out = []
            for _ in range(6):
                c.run_once()
                out.append(c.status.last_backoff_s)
            return out

        assert schedule("ctrl-a") == schedule("ctrl-a")   # seeded from name
        assert schedule("ctrl-a") != schedule("ctrl-b")   # de-synchronized

    def test_next_delay_peek_is_side_effect_free(self):
        def always_fails():
            raise RuntimeError("x")

        observed = Controller("peek", always_fails, interval=1.0)
        replay = Controller("peek", always_fails, interval=1.0)
        a_delays, b_delays = [], []
        for _ in range(5):
            observed.run_once()
            a_delays.append(observed.status.last_backoff_s)
            assert observed.next_delay() == observed.next_delay()  # stable
            replay.run_once()
            b_delays.append(replay.status.last_backoff_s)
        # peeking at one controller's schedule must not shift it off the
        # identical-seed replay that never peeked
        assert a_delays == b_delays

    def test_trigger_sync_failure_counting(self):
        calls = []

        def fn():
            calls.append(1)
            if len(calls) < 3:
                raise RuntimeError("regen exploded")

        t = Trigger(fn, sync=True)
        for _ in range(2):
            with pytest.raises(RuntimeError):
                t()
        assert t.consecutive_failures == 2
        assert "regen exploded" in t.last_error
        t()
        assert t.consecutive_failures == 0 and t.last_error == ""


# --------------------------------------------------------------------------- #
class TestCheckpointRobustness:
    def test_corrupt_state_falls_back_to_cold_start(self, tmp_path):
        eng = web_engine()
        _ = eng.active
        ckpt.save(eng, str(tmp_path))
        with open(tmp_path / "state.json", "r+") as f:
            f.write("{torn")
        fresh = small_engine()
        assert ckpt.restore(fresh, str(tmp_path)) is False
        assert not fresh.endpoints and len(fresh.repo) == 0   # untouched
        with pytest.raises(ckpt.CheckpointCorrupt):
            ckpt.restore(small_engine(), str(tmp_path), strict=True)

    def test_checksum_catches_field_tampering(self, tmp_path):
        eng = web_engine()
        _ = eng.active
        ckpt.save(eng, str(tmp_path))
        state = json.loads((tmp_path / "state.json").read_text())
        state["revision"] = state["revision"] + 7  # valid JSON, wrong body
        (tmp_path / "state.json").write_text(json.dumps(state))
        assert ckpt.restore(small_engine(), str(tmp_path)) is False

    def test_pre_checksum_checkpoints_still_restore(self, tmp_path):
        eng = web_engine()
        _ = eng.active
        ckpt.save(eng, str(tmp_path))
        state = json.loads((tmp_path / "state.json").read_text())
        del state["checksum"]                      # an older writer's file
        (tmp_path / "state.json").write_text(json.dumps(state))
        fresh = small_engine()
        assert ckpt.restore(fresh, str(tmp_path)) is True
        assert 1 in fresh.endpoints

    def test_corrupt_ct_drops_flows_keeps_control_plane(self, tmp_path):
        eng = web_engine()
        slot_of = eng.active.snapshot.ep_slot_of
        assert classify_allows(eng, slot_of) == [True, False, False]
        assert eng.ct_stats(now=100)["live"] == 1
        ckpt.save(eng, str(tmp_path))
        (tmp_path / "ct.npz").write_bytes(b"not a zipfile at all")
        fresh = small_engine()
        assert ckpt.restore(fresh, str(tmp_path)) is True
        assert 1 in fresh.endpoints and len(fresh.repo) == 1
        assert fresh.ct_stats(now=100)["live"] == 0    # CT was dropped
        # and the restored engine still classifies correctly
        assert classify_allows(
            fresh, fresh.active.snapshot.ep_slot_of, now=200
        ) == [True, False, False]

    def test_injected_write_fault_leaves_no_partial_state(self, tmp_path):
        eng = web_engine()
        _ = eng.active
        ckpt.save(eng, str(tmp_path))
        good = (tmp_path / "state.json").read_bytes()
        eng.classify(batch_from_records(
            [pkt("192.168.1.10", "10.1.2.3", 41000, 443)],
            eng.active.snapshot.ep_slot_of), now=150)
        FAULTS.arm("checkpoint.write", mode="fail", times=1)
        with pytest.raises(FaultInjected):
            ckpt.save(eng, str(tmp_path))
        # the old checkpoint is intact, byte for byte, and restorable
        assert (tmp_path / "state.json").read_bytes() == good
        assert not [n for n in os.listdir(tmp_path)
                    if n.startswith((".state-", ".ct-"))]   # no tmp litter
        assert ckpt.restore(small_engine(), str(tmp_path)) is True


# --------------------------------------------------------------------------- #
class TestClusterMeshRecovery:
    @staticmethod
    def write_peer(store, node, gen, entries, published_at=None):
        import time as _time
        doc = {"format_version": 1, "node": node, "generation": gen,
               "published_at": (_time.time() if published_at is None
                                else published_at),
               "entries": entries}
        tmp = os.path.join(store, f".{node}-tmp")
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, os.path.join(store, f"{node}.json"))

    def test_peer_flap_holds_state_and_converges(self, tmp_path):
        eng = web_engine()
        mesh = ClusterMesh(eng, str(tmp_path), "local", stale_after_s=300.0)
        self.write_peer(str(tmp_path), "peer1", 1,
                        {"10.99.0.5/32": {"labels": ["k8s:app=db"]}})
        mesh.sync()
        ident0 = eng.ctx.ipcache.get("10.99.0.5/32")
        assert ident0 is not None
        FAULTS.arm("clustermesh.peer_read", mode="prob", prob=0.7, seed=7)
        for gen in range(2, 14):
            self.write_peer(str(tmp_path), "peer1", gen,
                            {"10.99.0.5/32": {"labels": ["k8s:app=db"]}})
            mesh.sync()
            # a flapping read NEVER withdraws within the lease window
            assert eng.ctx.ipcache.get("10.99.0.5/32") == ident0
        FAULTS.disarm()
        mesh.sync()
        assert eng.ctx.ipcache.get("10.99.0.5/32") == ident0

    def test_prefix_handoff_survives_withdrawal_pass(self, tmp_path):
        """Regression (ADVICE round-5): a prefix claimed by two peers (pod
        move overlap) must survive the departing peer's withdrawal — the
        old code's `if prefix in held: continue` left a permanent ipcache
        hole after the hand-off."""
        eng = web_engine()
        mesh = ClusterMesh(eng, str(tmp_path), "local", stale_after_s=300.0)
        entries = {"10.99.0.7/32": {"labels": ["k8s:app=cache"]}}
        self.write_peer(str(tmp_path), "peer-a", 1, entries)
        self.write_peer(str(tmp_path), "peer-b", 1, entries)
        mesh.sync()
        ident = eng.ctx.ipcache.get("10.99.0.7/32")
        assert ident is not None
        # peer-a departs cleanly; its withdrawal pass deletes the ipcache
        # entry out from under peer-b's still-live claim
        os.unlink(tmp_path / "peer-a.json")
        mesh.sync()
        assert eng.ctx.ipcache.get("10.99.0.7/32") == ident
        # and the identity still resolves through a real LPM lookup
        assert eng.ctx.ipcache.lookup("10.99.0.7") == ident

    def test_clock_skew_does_not_withdraw_live_peer(self, tmp_path,
                                                    monkeypatch):
        """Regression (ADVICE round-5): staleness is judged from OUR lease
        clock (advanced on generation change), never from the peer-written
        published_at — a peer whose clock is behind must not be withdrawn
        while it is making progress."""
        import cilium_tpu.runtime.clustermesh as cm
        eng = web_engine()
        mesh = ClusterMesh(eng, str(tmp_path), "local", stale_after_s=60.0)
        clock = [1_000_000.0]
        monkeypatch.setattr(cm.time, "time", lambda: clock[0])
        entries = {"10.99.0.9/32": {"labels": ["k8s:app=mq"]}}
        # the peer's clock is 10 000 s behind ours — published_at looks
        # ancient on every single heartbeat
        for gen in range(1, 6):
            self.write_peer(str(tmp_path), "peer1", gen, entries,
                            published_at=clock[0] - 10_000.0)
            mesh.sync()
            assert eng.ctx.ipcache.get("10.99.0.9/32") is not None
            clock[0] += 30.0                       # under the 60 s lease
        # now the peer truly dies: generation stops advancing → the local
        # lease ages out and the state is withdrawn
        clock[0] += 120.0
        mesh.sync()
        assert eng.ctx.ipcache.get("10.99.0.9/32") is None

    def test_unreadable_file_holds_until_lease_expiry(self, tmp_path,
                                                      monkeypatch):
        import cilium_tpu.runtime.clustermesh as cm
        eng = web_engine()
        mesh = ClusterMesh(eng, str(tmp_path), "local", stale_after_s=60.0)
        clock = [1_000_000.0]
        monkeypatch.setattr(cm.time, "time", lambda: clock[0])
        self.write_peer(str(tmp_path), "peer1", 1,
                        {"10.99.0.11/32": {"labels": ["k8s:app=db"]}})
        mesh.sync()
        assert eng.ctx.ipcache.get("10.99.0.11/32") is not None
        FAULTS.arm("clustermesh.peer_read", mode="fail")   # every read fails
        clock[0] += 30.0
        mesh.sync()                                # inside lease: held
        assert eng.ctx.ipcache.get("10.99.0.11/32") is not None
        clock[0] += 60.0
        mesh.sync()                                # lease expired: withdrawn
        assert eng.ctx.ipcache.get("10.99.0.11/32") is None


# --------------------------------------------------------------------------- #
class TestAPIHardening:
    def make_server(self, tmp_path, name="api.sock"):
        sock = str(tmp_path / name)
        eng = web_engine()
        _ = eng.active
        eng.config = DaemonConfig(ct_capacity=4096, auto_regen=False,
                                  api_socket=sock)
        srv = APIServer(eng, sock)
        srv.start()
        return eng, srv, sock

    def test_socket_permissions(self, tmp_path):
        _eng, srv, sock = self.make_server(tmp_path / "sub")
        try:
            mode = stat.S_IMODE(os.stat(sock).st_mode)
            assert mode == 0o600                   # owner-only
            dmode = stat.S_IMODE(os.stat(tmp_path / "sub").st_mode)
            assert dmode & 0o027 == 0              # no group-w, no other
        finally:
            srv.stop()

    def test_live_socket_is_not_stolen(self, tmp_path):
        _eng, srv, sock = self.make_server(tmp_path)
        try:
            eng2 = web_engine()
            _ = eng2.active
            srv2 = APIServer(eng2, sock)
            with pytest.raises(RuntimeError, match="refusing to steal"):
                srv2.start()
            # the original server is untouched
            code, doc = UnixAPIClient(sock).get("/v1/healthz")
            assert code == 200 and doc["state"] == C.HEALTH_OK
        finally:
            srv.stop()

    def test_stale_socket_is_reclaimed(self, tmp_path):
        sock = str(tmp_path / "stale.sock")
        dead = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        dead.bind(sock)                            # bound but never listening
        dead.close()                               # → connect() refused
        assert os.path.exists(sock)
        _eng, srv, _ = self.make_server(tmp_path, "stale.sock")
        try:
            code, _doc = UnixAPIClient(sock).get("/v1/healthz")
            assert code == 200
        finally:
            srv.stop()

    def test_faults_routes_and_handler_fault(self, tmp_path):
        _eng, srv, sock = self.make_server(tmp_path)
        client = UnixAPIClient(sock)
        try:
            code, stats = client.get("/v1/faults")
            assert code == 200 and "regen.compile" in stats
            code, doc = client.post("/v1/faults",
                                    {"spec": "api.handler=fail"})
            assert code == 200 and doc["armed"] == 1
            code, doc = client.get("/v1/status")   # normal route: 500s now
            assert code == 500 and "FaultInjected" in doc["error"]
            # the faults route itself stays exempt so the chaos driver can
            # observe and disarm mid-storm
            code, stats = client.get("/v1/faults")
            assert code == 200 and stats["api.handler"]["armed"]
            code, _doc = client.post("/v1/faults", {"disarm": "*"})
            assert code == 200
            code, _doc = client.get("/v1/status")
            assert code == 200
            code, doc = client.post(
                "/v1/faults", {"spec": "shim.rx_ring=fail;nope=fail"})
            assert code == 400
            code, stats = client.get("/v1/faults")   # nothing half-armed
            assert code == 200 and not stats["shim.rx_ring"]["armed"]
        finally:
            srv.stop()

    def test_healthz_reports_degradation_live(self, tmp_path):
        eng, srv, sock = self.make_server(tmp_path)
        client = UnixAPIClient(sock)
        try:
            code, _doc = client.post(
                "/v1/faults", {"spec": "regen.compile=fail:3"})
            assert code == 200
            for _ in range(3):
                code, _doc = client.post("/v1/regenerate")
                assert code == 200                 # served from last-good
            code, h = client.get("/v1/healthz")
            assert code == 200
            assert h["status"] == "degraded"
            assert h["state"] == C.HEALTH_DEGRADED
            assert h["consecutive_regen_failures"] == 3
            code, _doc = client.post("/v1/regenerate")   # spec exhausted
            code, h = client.get("/v1/healthz")
            assert h["status"] == "ok" and h["state"] == C.HEALTH_OK
        finally:
            srv.stop()


# --------------------------------------------------------------------------- #
class TestShimFault:
    def test_rx_ring_fault_is_one_failed_poll(self):
        from cilium_tpu.shim.bindings import FlowShim
        try:
            shim = FlowShim(batch_size=8, timeout_us=0)
        except OSError:
            pytest.skip("shim library not built")
        try:
            FAULTS.arm("shim.rx_ring", mode="fail", times=1)
            with pytest.raises(FaultInjected):
                shim.poll_batch(now_us=1, force=True)
            # next poll drains normally — nothing was lost, nothing wedged
            assert shim.poll_batch(now_us=2, force=True) is None
        finally:
            shim.close()


# --------------------------------------------------------------------------- #
class TestChaosCLI:
    def test_faults_list(self, capsys):
        rc = cli_main(["faults", "list"])
        out = capsys.readouterr().out
        assert rc == 0
        for point in ("regen.compile", "shim.rx_ring",
                      "clustermesh.peer_read", "checkpoint.write",
                      "api.handler"):
            assert point in out

    def test_faults_list_json(self, capsys):
        rc = cli_main(["faults", "list", "-o", "json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert "regen.compile" in doc

    @pytest.fixture
    def stop_chaos_engines(self, monkeypatch):
        """`faults chaos` is a one-shot command and leaves the engines it
        builds to process exit. Here the process goes on: their pipeline
        workers and watchdogs would run beside every later test of this
        xdist worker, so the test stops them."""
        made = []
        init = Engine.__init__

        def recording_init(eng, *args, **kwargs):
            init(eng, *args, **kwargs)
            made.append(eng)

        monkeypatch.setattr(Engine, "__init__", recording_init)
        yield
        for eng in made:
            eng.stop()

    def test_chaos_scenario_fake_datapath(self, capsys, stop_chaos_engines):
        """Fast tier-1 subset of `make chaos`: the full scripted scenario
        on the oracle-backed fake datapath."""
        rc = cli_main(["faults", "chaos", "--datapath", "fake",
                       "--failures", "10", "-o", "json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0, doc
        assert doc["ok"] is True
        phases = {p["phase"]: p for p in doc["phases"]}
        assert set(phases) == {"regen-storm", "regen-recovery", "peer-flap",
                               "pipeline-storm", "stall-storm", "breaker",
                               "ct-restart", "checkpoint-corruption",
                               "qos-enqueue-failsafe", "dns-poison"}
        assert all(p["ok"] for p in doc["phases"])
        assert "0 classify errors" in phases["regen-storm"]["detail"]
        assert "0 errors, 0 verdict divergences" in \
            phases["pipeline-storm"]["detail"]
        assert "0 verdict divergences" in \
            phases["qos-enqueue-failsafe"]["detail"]
        assert "0 verdict divergences" in phases["dns-poison"]["detail"]
        # the guard phases: a watchdog restart actually happened and the
        # breaker opened within its threshold budget
        assert "watchdog restart" in phases["stall-storm"]["detail"]
        assert "3/3 post-restart submissions matched baseline" in \
            phases["stall-storm"]["detail"]
        assert "probe closed breaker" in phases["breaker"]["detail"]

    @pytest.mark.slow
    def test_chaos_scenario_jit_datapath(self, capsys, stop_chaos_engines):
        """`make chaos` equivalent: the same scenario through the real
        compiled (jit) device path under JAX_PLATFORMS=cpu."""
        rc = cli_main(["faults", "chaos", "--datapath", "jit",
                       "--failures", "10", "-o", "json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0, doc
        assert doc["ok"] is True

    def test_chaos_live_agent(self, tmp_path, capsys):
        """`faults chaos --api`: drive the storm against a live agent over
        its REST socket end-to-end."""
        sock = str(tmp_path / "agent.sock")
        eng = web_engine()
        _ = eng.active
        srv = APIServer(eng, sock)
        srv.start()
        try:
            rc = cli_main(["faults", "chaos", "--api", sock,
                           "--failures", "5", "-o", "json"])
            doc = json.loads(capsys.readouterr().out)
            assert rc == 0, doc
            assert doc["ok"] is True
            assert {p["phase"] for p in doc["phases"]} == {
                "baseline", "arm", "regen-storm", "regen-recovery"}
        finally:
            srv.stop()

    def test_faults_arm_disarm_cli(self, tmp_path, capsys):
        sock = str(tmp_path / "agent.sock")
        eng = web_engine()
        _ = eng.active
        srv = APIServer(eng, sock)
        srv.start()
        try:
            rc = cli_main(["faults", "arm", "--api", sock,
                           "regen.compile=fail:2"])
            assert rc == 0
            assert json.loads(capsys.readouterr().out)["armed"] == 1
            rc = cli_main(["faults", "list", "--api", sock, "-o", "json"])
            doc = json.loads(capsys.readouterr().out)
            assert doc["regen.compile"]["armed"] is True
            rc = cli_main(["faults", "disarm", "--api", sock])
            assert rc == 0
            capsys.readouterr()
            cli_main(["faults", "list", "--api", sock, "-o", "json"])
            doc = json.loads(capsys.readouterr().out)
            assert doc["regen.compile"]["armed"] is False
        finally:
            srv.stop()
