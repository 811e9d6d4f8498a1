"""Runtime tests: engine end-to-end (vs oracle), policy update fencing,
checkpoint/resume flow survival, config layering, controllers, metrics,
flow log."""

import json
import os
import time

import numpy as np
import pytest

from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.runtime.checkpoint import restore, save
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.controller import Controller, Trigger
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr
from oracle import Oracle, PacketRecord

POLICY = [{
    "endpointSelector": {"matchLabels": {"app": "web"}},
    "egress": [{"toCIDR": ["10.0.0.0/8"],
                "toPorts": [{"ports": [{"port": "443", "protocol": "TCP"}]}]}],
}]


def small_engine(**kw):
    kw.setdefault("ct_capacity", 4096)
    kw.setdefault("auto_regen", False)
    kw.setdefault("flowlog_mode", "all")
    return Engine(DaemonConfig(**kw))


def pkt(src, dst, sp, dp, proto=C.PROTO_TCP, flags=C.TCP_SYN, ep_id=1,
        direction=C.DIR_EGRESS, method=C.HTTP_METHOD_ANY, path=b""):
    s16, sv6 = parse_addr(src)
    d16, dv6 = parse_addr(dst)
    return PacketRecord(s16, d16, sp, dp, proto, flags, sv6 or dv6, ep_id,
                        direction, method, path)


class TestEngine:
    def test_end_to_end_matches_oracle(self):
        eng = small_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        active = eng.active
        oracle = Oracle(dict(zip(active.snapshot.ep_ids,
                                 active.snapshot.policies)),
                        eng.ctx.ipcache.snapshot())
        packets = [
            pkt("192.168.1.10", "10.1.2.3", 40000, 443),
            pkt("192.168.1.10", "10.1.2.3", 40000, 443, flags=C.TCP_ACK),
            pkt("192.168.1.10", "10.1.2.3", 40001, 80),
            pkt("192.168.1.10", "8.8.8.8", 40002, 443),
        ]
        want = oracle.classify_batch_snapshot(packets, 100)
        out = eng.classify(batch_from_records(packets, active.snapshot.ep_slot_of),
                           now=100)
        for i, v in enumerate(want):
            assert bool(out["allow"][i]) == v.allow
            assert int(out["reason"][i]) == int(v.drop_reason)
        assert eng.ct_stats(now=100)["live"] == 1
        assert eng.metrics.packets_total == 4

    def test_policy_update_revision_fence(self):
        """Snapshot swap: new rules take effect for NEW flows; established
        flows keep passing via CT (the connection-survival contract)."""
        eng = small_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        snap0 = eng.active
        slot_of = snap0.snapshot.ep_slot_of
        # establish a flow on 443
        out = eng.classify(batch_from_records(
            [pkt("192.168.1.10", "10.1.2.3", 40000, 443)], slot_of), now=100)
        assert bool(out["allow"][0])
        rev0 = snap0.revision
        # replace policy: now only port 80 is allowed
        eng.repo.clear()
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egress": [{"toCIDR": ["10.0.0.0/8"],
                        "toPorts": [{"ports": [{"port": "80", "protocol": "TCP"}]}]}],
        }])
        snap1 = eng.active
        assert snap1.revision > rev0
        # established flow still forwarded (CT bypass)
        out = eng.classify(batch_from_records(
            [pkt("192.168.1.10", "10.1.2.3", 40000, 443, flags=C.TCP_ACK)],
            slot_of), now=101)
        assert bool(out["allow"][0])
        assert int(out["status"][0]) == C.CTStatus.ESTABLISHED
        # a NEW flow to 443 now drops; to 80 passes
        out = eng.classify(batch_from_records(
            [pkt("192.168.1.10", "10.1.2.3", 41000, 443),
             pkt("192.168.1.10", "10.1.2.3", 41001, 80)], slot_of), now=102)
        assert not bool(out["allow"][0]) and bool(out["allow"][1])

    def test_sweep_controller(self):
        eng = small_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        slot_of = eng.active.snapshot.ep_slot_of
        eng.classify(batch_from_records(
            [pkt("192.168.1.10", "10.1.2.3", 40000, 443)], slot_of), now=100)
        assert eng.sweep(now=100 + C.CT_LIFETIME_SYN + 1) == 1
        assert eng.ct_stats(now=200)["live"] == 0

    def test_flowlog_and_metrics(self):
        eng = small_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        slot_of = eng.active.snapshot.ep_slot_of
        eng.classify(batch_from_records(
            [pkt("192.168.1.10", "10.1.2.3", 40000, 443),
             pkt("192.168.1.10", "10.1.2.3", 40001, 22)], slot_of), now=100)
        logs = eng.flowlog.tail()
        assert len(logs) == 2
        drop = [l for l in logs if l["verdict"] == "DROPPED"][0]
        assert drop["dst_port"] == 22 and drop["drop_reason_desc"] == "POLICY"
        text = eng.metrics.render_prometheus()
        assert 'reason="OK",direction="egress"} 1' in text
        assert 'reason="POLICY"' in text

    def test_unenforced_endpoint_allows(self):
        eng = small_engine()
        eng.add_endpoint(["k8s:app=lonely"], ips=("192.168.1.99",), ep_id=5)
        out = eng.classify(batch_from_records(
            [pkt("192.168.1.99", "8.8.8.8", 40000, 443, ep_id=5)],
            eng.active.snapshot.ep_slot_of), now=100)
        assert bool(out["allow"][0])


class TestEngineServices:
    def test_service_lb_through_engine(self):
        from cilium_tpu.model.services import Backend, Frontend, Service
        eng = small_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        eng.upsert_service(Service(
            name="api", namespace="prod",
            frontends=(Frontend("172.30.0.1", 443, C.PROTO_TCP),),
            lb_backends=(Backend("10.7.0.1", 443), Backend("10.7.0.2", 443)),
        ))
        active = eng.active
        assert active.snapshot.lb.n_frontends == 1
        out = eng.classify(batch_from_records(
            [pkt("192.168.1.10", "172.30.0.1", 40000, 443)],
            active.snapshot.ep_slot_of), now=100)
        assert bool(out["allow"][0]) and bool(out["svc"][0])
        assert int(out["nat_dport"][0]) == 443
        # deleting the service recompiles; VIP traffic now hits world/deny
        eng.delete_service("prod", "api")
        out2 = eng.classify(batch_from_records(
            [pkt("192.168.1.10", "172.30.0.1", 40001, 443)],
            eng.active.snapshot.ep_slot_of), now=101)
        assert not bool(out2["svc"][0])

    def test_rnat_stable_across_service_churn(self):
        """Rev-NAT ids are stable: adding a service that sorts earlier must
        not re-point old CT entries at the new VIP, and deleting a service
        leaves its stale CT entries failing closed (no rewrite)."""
        from cilium_tpu.model.services import Backend, Frontend, Service
        from cilium_tpu.utils.ip import words_to_addr
        eng = small_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        eng.upsert_service(Service(
            name="api", namespace="zzz",
            frontends=(Frontend("172.30.0.1", 443, C.PROTO_TCP),),
            lb_backends=(Backend("10.7.0.1", 443),)))
        slot_of = eng.active.snapshot.ep_slot_of
        out = eng.classify(batch_from_records(
            [pkt("192.168.1.10", "172.30.0.1", 40000, 443)], slot_of),
            now=100)
        assert bool(out["svc"][0])
        # a service that sorts FIRST re-orders frontend indices
        eng.upsert_service(Service(
            name="aaa", namespace="aaa",
            frontends=(Frontend("172.31.0.9", 443, C.PROTO_TCP),),
            lb_backends=(Backend("10.8.0.1", 443),)))
        reply = pkt("10.7.0.1", "192.168.1.10", 443, 40000,
                    flags=C.TCP_SYN | C.TCP_ACK, direction=C.DIR_INGRESS)
        out2 = eng.classify(batch_from_records(
            [reply], eng.active.snapshot.ep_slot_of), now=105)
        assert bool(out2["rnat"][0])
        vip16, _ = parse_addr("172.30.0.1")   # the ORIGINAL vip, not aaa's
        assert words_to_addr(out2["rnat_src"][0]) == vip16
        # delete the original service: stale CT entry → no rewrite at all
        eng.delete_service("zzz", "api")
        out3 = eng.classify(batch_from_records(
            [reply], eng.active.snapshot.ep_slot_of), now=110)
        assert int(out3["status"][0]) == C.CTStatus.REPLY
        assert not bool(out3["rnat"][0])

    def test_service_flow_survives_restart(self, tmp_path):
        from cilium_tpu.model.services import Backend, Frontend, Service
        eng = small_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        eng.upsert_service(Service(
            name="api", namespace="prod",
            frontends=(Frontend("172.30.0.1", 443, C.PROTO_TCP),),
            lb_backends=(Backend("10.7.0.1", 443),),
        ))
        slot_of = eng.active.snapshot.ep_slot_of
        out = eng.classify(batch_from_records(
            [pkt("192.168.1.10", "172.30.0.1", 40000, 443)], slot_of),
            now=100)
        assert bool(out["svc"][0])
        save(eng, str(tmp_path / "ckpt"))

        eng2 = small_engine()
        restore(eng2, str(tmp_path / "ckpt"))
        # service survives, and the reply still rev-NATs through the
        # restored CT entry (rev_nat column round-trips)
        reply = pkt("10.7.0.1", "192.168.1.10", 443, 40000,
                    flags=C.TCP_SYN | C.TCP_ACK, direction=C.DIR_INGRESS)
        out2 = eng2.classify(batch_from_records(
            [reply], eng2.active.snapshot.ep_slot_of), now=105)
        assert int(out2["status"][0]) == C.CTStatus.REPLY
        assert bool(out2["rnat"][0])
        vip16, _ = parse_addr("172.30.0.1")
        from cilium_tpu.utils.ip import words_to_addr
        assert words_to_addr(out2["rnat_src"][0]) == vip16
        assert int(out2["rnat_sport"][0]) == 443


class TestHealth:
    def test_health_probe(self):
        eng = small_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.add_endpoint(["k8s:app=db"], ips=("192.168.1.20",), ep_id=2)
        # web: unenforced ingress (no ingress rules) → reachable;
        # db: enforced ingress that does NOT allow health → unreachable
        eng.apply_policy(POLICY + [{
            "endpointSelector": {"matchLabels": {"app": "db"}},
            "ingress": [{"fromEndpoints": [{"matchLabels":
                                            {"app": "web"}}]}],
        }])
        rep = eng.health_probe(now=100)
        assert rep[1]["reachable"] is True
        assert rep[2]["reachable"] is False
        assert rep[2]["reason"] == "POLICY"
        # whitelist health → reachable (the upstream remediation)
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "db"}},
            "ingress": [{"fromEntities": ["health"]}],
        }])
        rep = eng.health_probe(now=200)
        assert rep[2]["reachable"] is True
        assert eng.metrics.gauges["health_reachable_endpoints"] == 2


class TestCheckpoint:
    def test_flows_survive_restart(self, tmp_path):
        eng = small_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        slot_of = eng.active.snapshot.ep_slot_of
        eng.classify(batch_from_records(
            [pkt("192.168.1.10", "10.1.2.3", 40000, 443)], slot_of), now=100)
        cidr_id = eng.ctx.ipcache.lookup("10.1.2.3")
        save(eng, str(tmp_path / "ckpt"))

        eng2 = small_engine()
        restore(eng2, str(tmp_path / "ckpt"))
        # identity numbering stable
        assert eng2.ctx.ipcache.lookup("10.1.2.3") == cidr_id
        # the established flow survives the "restart": ACK is ESTABLISHED,
        # not NEW (the pinned-map analog)
        out = eng2.classify(batch_from_records(
            [pkt("192.168.1.10", "10.1.2.3", 40000, 443, flags=C.TCP_ACK)],
            eng2.active.snapshot.ep_slot_of), now=105)
        assert bool(out["allow"][0])
        assert int(out["status"][0]) == C.CTStatus.ESTABLISHED

    def test_restore_requires_fresh_engine(self, tmp_path):
        eng = small_engine()
        eng.add_endpoint(["k8s:app=web"], ep_id=1)
        save(eng, str(tmp_path / "c"))
        eng2 = small_engine()
        eng2.add_endpoint(["k8s:app=other"], ep_id=9)
        with pytest.raises(ValueError):
            restore(eng2, str(tmp_path / "c"))


class TestConfig:
    def test_env_overrides_file(self, tmp_path):
        cfg_file = tmp_path / "cfg.json"
        cfg_file.write_text(json.dumps({"ct_capacity": 4096,
                                        "enforcement_mode": "default"}))
        cfg = DaemonConfig.load(
            config_file=str(cfg_file),
            env={"CILIUM_TPU_ENFORCEMENT_MODE": "always"},
            argv=["--batch-size", "128"])
        assert cfg.ct_capacity == 4096
        assert cfg.enforcement_mode == "always"
        assert cfg.batch_size == 128

    def test_rejects_unknown_keys(self, tmp_path):
        cfg_file = tmp_path / "cfg.json"
        cfg_file.write_text(json.dumps({"bogus": 1}))
        with pytest.raises(ValueError):
            DaemonConfig.load(config_file=str(cfg_file), env={})

    @pytest.mark.parametrize("how", ["constructor", "file"])
    def test_the_retired_kernel_selector_is_an_unknown_key(self, how,
                                                           tmp_path):
        """``fused_kernels`` went with the kernels it selected (PR 52): no
        alias, no shim; a deployment that still names it is told so."""
        if how == "constructor":
            with pytest.raises(TypeError, match="fused_kernels"):
                DaemonConfig(fused_kernels="auto")
        else:
            cfg_file = tmp_path / "cfg.json"
            cfg_file.write_text(json.dumps({"fused_kernels": "off"}))
            with pytest.raises(ValueError, match="fused_kernels"):
                DaemonConfig.load(config_file=str(cfg_file), env={})

    def test_validation(self):
        with pytest.raises(ValueError):
            DaemonConfig(ct_capacity=1000)
        with pytest.raises(ValueError):
            DaemonConfig(enforcement_mode="sometimes")


class TestControllers:
    def test_retry_with_backoff_counts(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise RuntimeError("boom")

        ctrl = Controller("test", flaky, interval=0.01, backoff_base=0.001)
        for _ in range(3):
            ctrl.run_once()
        assert ctrl.status.failure_count == 2
        assert ctrl.status.success_count == 1
        assert ctrl.status.consecutive_failures == 0

    def test_trigger_debounce(self):
        fired = []
        trig = Trigger(lambda: fired.append(1), min_interval=0.05)
        for _ in range(10):
            trig()
        time.sleep(0.15)
        assert len(fired) == 1
        assert trig.folds == 9


class TestFlowLogSinkCap:
    def test_sink_buf_bounded_drop_oldest(self, tmp_path, monkeypatch):
        """Without a flush controller the pending sink buffer must stay
        bounded (drop-oldest, counted) instead of growing without limit."""
        from cilium_tpu.runtime import flowlog as fl
        monkeypatch.setattr(fl, "SINK_BUF_MAX", 10)
        log = fl.FlowLog(capacity=4, mode="all",
                         sink_path=str(tmp_path / "flows.jsonl"))
        batch = {
            "src": np.zeros((3, 4), np.uint32), "dst": np.zeros((3, 4), np.uint32),
            "sport": np.zeros(3, np.uint32), "dport": np.zeros(3, np.uint32),
            "proto": np.full(3, 6, np.uint32), "direction": np.zeros(3, np.uint32),
            "ep_slot": np.zeros(3, np.uint32), "valid": np.ones(3, bool),
        }
        out = {
            "allow": np.ones(3, bool), "reason": np.zeros(3, np.uint32),
            "status": np.zeros(3, np.uint32),
            "remote_identity": np.zeros(3, np.uint32),
        }
        for t in range(8):
            log.append_batch(batch, out, now=t, ep_ids=(1,))
        assert len(log._sink_buf) <= 10
        assert log.sink_dropped == 8 * 3 - 10
        # flush drains what's left; ring tail unaffected
        assert log.flush_sink() == 10
        assert log._sink_buf == []

    @staticmethod
    def _mk_batch_out(n):
        batch = {
            "src": np.zeros((n, 4), np.uint32),
            "dst": np.zeros((n, 4), np.uint32),
            "sport": np.arange(n, dtype=np.uint32),
            "dport": np.zeros(n, np.uint32),
            "proto": np.full(n, 6, np.uint32),
            "direction": np.zeros(n, np.uint32),
            "ep_slot": np.zeros(n, np.uint32), "valid": np.ones(n, bool),
        }
        out = {
            "allow": np.ones(n, bool), "reason": np.zeros(n, np.uint32),
            "status": np.zeros(n, np.uint32),
            "remote_identity": np.zeros(n, np.uint32),
        }
        return batch, out

    def test_sink_rotation_at_rotate_bytes(self, tmp_path, monkeypatch):
        """Past SINK_ROTATE_BYTES the sink rotates to <path>.1 (keep one
        generation); new lines land in a fresh file."""
        from cilium_tpu.runtime import flowlog as fl
        monkeypatch.setattr(fl, "SINK_ROTATE_BYTES", 256)
        path = tmp_path / "flows.jsonl"
        log = fl.FlowLog(capacity=8, mode="all", sink_path=str(path))
        batch, out = self._mk_batch_out(3)
        log.append_batch(batch, out, now=1, ep_ids=(1,))
        log.flush_sink()
        assert path.stat().st_size > 256   # one flush already past the cap
        first_gen = path.read_text()
        log.append_batch(batch, out, now=2, ep_ids=(1,))
        log.flush_sink()                   # this flush must rotate first
        rotated = tmp_path / "flows.jsonl.1"
        assert rotated.exists() and rotated.read_text() == first_gen
        fresh = [json.loads(line) for line in
                 path.read_text().strip().splitlines()]
        assert len(fresh) == 3 and all(r["time"] == 2 for r in fresh)

    def test_extract_capped_keeps_newest(self, monkeypatch):
        """A drop-storm batch larger than APPEND_BATCH_MAX only extracts
        the newest rows; the shed remainder is counted, and the ring still
        sees every extracted record."""
        from cilium_tpu.runtime import flowlog as fl
        monkeypatch.setattr(fl, "APPEND_BATCH_MAX", 5)
        log = fl.FlowLog(capacity=16, mode="all")
        batch, out = self._mk_batch_out(12)
        log.append_batch(batch, out, now=1, ep_ids=(1,))
        assert log.extract_shed == 12 - 5
        assert log.total_seen == 12
        tail = log.tail()
        assert [r["src_port"] for r in tail] == list(range(7, 12))


class TestFlowLogFollowEdges:
    """Live-follow edge cases: the since() seq cursor across ring
    wraparound, and tail()/since() exact-match filter typing (int vs str
    field values must not cross-match)."""

    @staticmethod
    def _fill(log, n, start_port=0):
        batch, out = TestFlowLogSinkCap._mk_batch_out(n)
        batch["sport"] = np.arange(start_port, start_port + n,
                                   dtype=np.uint32)
        log.append_batch(batch, out, now=1, ep_ids=(1,))

    def test_since_cursor_across_wraparound(self):
        from cilium_tpu.runtime import flowlog as fl
        log = fl.FlowLog(capacity=8, mode="all")
        self._fill(log, 20)               # seqs 1..20; ring keeps 13..20
        # a cursor inside the retained range follows without loss
        got = log.since(15)
        assert [r["seq"] for r in got] == [16, 17, 18, 19, 20]
        # oldest-first ordering holds across the physical wrap point
        got = log.since(0)
        assert [r["seq"] for r in got] == list(range(13, 21))
        # a cursor that fell off the ring gets an EXPLICIT structured gap
        # marker (records 6..12 are gone), then resumes at the oldest
        # retained record — loss is a record in the stream, not an
        # inference left to seq arithmetic
        got = log.since(5)
        assert got[0] == {"gap": True, "dropped": 7, "resume_seq": 13}
        assert got[1]["seq"] == 13
        assert log.follow_gaps == 1 and log.follow_gap_records == 7
        # cursor at the head: nothing new
        assert log.since(20) == []
        # limit caps oldest-first (the poll page)
        got = log.since(0, limit=3)
        assert [r["seq"] for r in got] == [13, 14, 15]

    def test_since_filters_apply_before_limit_cursor_advances(self):
        from cilium_tpu.runtime import flowlog as fl
        log = fl.FlowLog(capacity=16, mode="all")
        self._fill(log, 10)
        got = log.since(0, src_port=7)
        assert len(got) == 1 and got[0]["src_port"] == 7
        # filtered follow: cursor from the last *returned* record still
        # sees later matches only
        assert log.since(got[0]["seq"], src_port=7) == []

    def test_tail_filter_typing_int_vs_str(self):
        from cilium_tpu.runtime import flowlog as fl
        log = fl.FlowLog(capacity=16, mode="all")
        self._fill(log, 6)
        # src_port is stored as int: an int filter matches...
        assert len(log.tail(src_port=3)) == 1
        # ...a string of the same digits must NOT (exact typed match, the
        # documented semantics — no coercion surprises for API callers)
        assert log.tail(src_port="3") == []
        # string-valued fields match strings only
        assert len(log.tail(verdict="FORWARDED")) == 6
        assert log.tail(verdict=True) == []
        # unknown filter key matches nothing rather than everything
        assert log.tail(no_such_field=1) == []
        # combined typed filters AND together
        assert len(log.tail(verdict="FORWARDED", src_port=3)) == 1

    def test_since_typed_filters_across_wrap(self):
        from cilium_tpu.runtime import flowlog as fl
        log = fl.FlowLog(capacity=4, mode="all")
        self._fill(log, 10)               # ring keeps sports 6..9
        assert [r["src_port"] for r in log.since(0, src_port=8)] == [8]
        assert log.since(0, src_port="8") == []


class TestMetricsHistogram:
    def test_observe_quantile_and_render(self):
        from cilium_tpu.runtime.metrics import Histogram, Metrics
        m = Metrics()
        h = m.histogram("pipeline_queue_wait_seconds")
        assert m.histogram("pipeline_queue_wait_seconds") is h  # idempotent
        for v in (0.0002, 0.0002, 0.003, 0.02, 7.0):
            h.observe(v)
        assert h.count == 5 and h.total == pytest.approx(7.0234)
        assert 0.0001 <= h.quantile(0.5) <= 0.005
        assert h.quantile(0.999) == h.buckets[-1]   # past last finite bound
        text = m.render_prometheus()
        assert ("# TYPE ciliumtpu_pipeline_queue_wait_seconds histogram"
                in text)
        assert 'pipeline_queue_wait_seconds_bucket{le="+Inf"} 5' in text
        assert "pipeline_queue_wait_seconds_count 5" in text
        assert Histogram().quantile(0.5) == 0.0     # empty histogram

    def test_counter_geometry_from_constants(self):
        from cilium_tpu.runtime.metrics import Metrics
        m = Metrics()
        assert m.by_reason_dir.shape == (C.DROP_REASON_BINS
                                         * C.N_DIRECTIONS,)
        bad = {"by_reason_dir": np.zeros(512 + 2, np.uint32),
               "insert_fail": np.uint32(0)}
        with pytest.raises(ValueError, match="geometry"):
            m.add_batch(bad, n_valid=0)


class TestRegenFailureVisibility:
    def test_regen_failure_logged_and_counted(self, caplog):
        """A failing auto-regen must not be silent: it logs and bumps
        regen_failures_total exactly once so operators see stale device
        state (supervised degradation: serving continues on last-good)."""
        import logging as _logging

        from cilium_tpu.runtime.faults import FAULTS
        eng = small_engine(auto_regen=True)
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        _ = eng.active                             # last-good exists
        eng._regen_trigger.cancel()                # no async timer racing us
        try:
            FAULTS.arm("regen.compile", mode="fail", times=1)
            with caplog.at_level(_logging.WARNING,
                                 logger="cilium_tpu.engine"):
                eng._mark_dirty_and_regen()
        finally:
            FAULTS.reset()
        assert eng.metrics.counters.get("regen_failures_total") == 1
        assert any("regeneration failed" in r.message
                   for r in caplog.records)
        assert "regen_failures_total 1" in eng.metrics.render_prometheus()


class TestDebugChecksHarness:
    def test_classify_under_debug_nans_and_checks(self):
        """SURVEY §5 race-detection/sanitizer row: the datapath program must
        be clean under jax_debug_nans + checking config (the eBPF-verifier
        -strictness analog for numerics) — NaN-producing ops or invalid
        indexing in the fused kernel would raise here."""
        import jax
        from cilium_tpu.kernels.records import batch_from_records
        from cilium_tpu.runtime.config import DaemonConfig
        from cilium_tpu.runtime.datapath import JITDatapath
        from cilium_tpu.runtime.engine import Engine
        from cilium_tpu.utils.ip import parse_addr
        from oracle import PacketRecord

        jax.config.update("jax_debug_nans", True)
        try:
            eng = Engine(DaemonConfig(ct_capacity=1024, auto_regen=False),
                         datapath=JITDatapath(DaemonConfig(
                             ct_capacity=1024, auto_regen=False)))
            eng.add_endpoint(["k8s:app=web"], ips=("192.168.5.10",), ep_id=1)
            eng.apply_policy([{
                "endpointSelector": {"matchLabels": {"app": "web"}},
                "egress": [{"toCIDR": ["10.0.0.0/8"],
                            "toPorts": [{"ports": [
                                {"port": "443", "protocol": "TCP"}]}]}]}])
            eng.regenerate()
            s16, _ = parse_addr("192.168.5.10")
            d16, _ = parse_addr("10.3.2.1")
            pkts = [PacketRecord(s16, d16, 40000 + i, 443, C.PROTO_TCP,
                                 C.TCP_SYN, False, 1, C.DIR_EGRESS)
                    for i in range(32)]
            out = eng.classify(batch_from_records(
                pkts, eng.active.snapshot.ep_slot_of), now=100)
            assert bool(out["allow"][0])
        finally:
            jax.config.update("jax_debug_nans", False)
