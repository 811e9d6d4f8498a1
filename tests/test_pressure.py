"""Resource pressure ledger tests (ISSUE 13: observe/pressure.py + the HBM
ledger + the trace-ring drop accounting + departed-subject gauge sweeps).

Tier-1: ledger mechanics (registration, high-water, ETA math, forecast
latching, deregistration gauge sweeps, provider isolation), the engine's
≥12-resource registration floor, CT-row-tracks-gauge exactness, the
RESOURCE_PRESSURE health detail, the overload ladder's fourth latch, the
{resource=} label families surviving concurrent render_metrics scrapes,
ledger register/deregister under engine restart, trace-ring drop
accounting, the pipeline's departed-shard gauge sweep, the verifier budget
doc, and the JIT HBM ledger.

Slow (`make chaos`): the cfg6-form storm soak — flood a tiny CT
through the live pipelined engine under the auditor, asserting the ledger's
ct_table row tracks the ct_occupancy gauge bit-for-bit every tick and the
time-to-exhaustion forecast fires before the ladder reaches SHED-NEW —
plus the 8-shard audited scrape-race soak with a mid-soak watchdog restart
(the PR 7/11 house pattern, extended to the resource_* families).
"""

import threading
import time

import numpy as np
import pytest

from cilium_tpu.observe.pressure import (GAUGE_FAMILIES, LADDER_EXCLUDE,
                                         ResourceLedger)
from cilium_tpu.observe.trace import Tracer
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.runtime.metrics import Metrics
from cilium_tpu.utils import constants as C


def _fake_engine(**kw):
    kw.setdefault("auto_regen", False)
    cfg = DaemonConfig(**kw)
    eng = Engine(cfg, datapath=FakeDatapath(cfg))
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",))
    eng.apply_policy([{"endpointSelector": {"matchLabels": {"app": "web"}},
                       "egress": [{"toCIDR": ["10.0.0.0/8"]}]}])
    eng.regenerate()
    return eng


class TestResourceLedger:
    def test_poll_derives_pressure_and_high_water(self):
        m = Metrics()
        led = ResourceLedger(metrics=m)
        occ = {"v": 25.0}
        led.register("p", lambda: {"r": (100, occ["v"])})
        rep = led.poll(now=1.0)
        row = rep["resources"]["r"]
        assert row["capacity"] == 100 and row["occupancy"] == 25
        assert row["pressure"] == 0.25
        assert row["high_water"] == 25
        occ["v"] = 60.0
        led.poll(now=2.0)
        occ["v"] = 10.0
        row = led.poll(now=3.0)["resources"]["r"]
        assert row["occupancy"] == 10 and row["high_water"] == 60
        # the label families exported under the ciliumtpu_resource_* names
        assert m.gauges['resource_high_water{resource="r"}'] == 60
        assert m.gauges['resource_pressure{resource="r"}'] == 0.1

    def test_explicit_pressure_passes_through_verbatim(self):
        led = ResourceLedger()
        led.register("p", lambda: {"ring": (256, 256, 0.0)})
        row = led.poll(now=1.0)["resources"]["ring"]
        # a wrap-by-design ring at full occupancy is NOT pressured
        assert row["occupancy"] == 256 and row["pressure"] == 0.0
        assert led.pressured() == []

    def test_eta_fires_before_exhaustion_then_freezes_on_it(self):
        events = []
        led = ResourceLedger(
            eta_warn_s=50.0, warn=0.5, crit=0.9,
            event_sink=lambda kind, **a: events.append((kind, a)))
        occ = {"v": 0.0}
        led.register("p", lambda: {"ct": (100, occ["v"])})
        # growing 10/s: at occ=60 pressure 0.6 >= warn, eta = 40/10 = 4s
        for t in range(8):
            occ["v"] = 10.0 * t
            led.poll(now=float(t))
        kinds = [k for k, _ in events]
        assert "resource-pressure" in kinds
        fc = dict(events)["resource-pressure"]
        assert fc["resource"] == "ct" and fc["eta_s"] > 0
        assert "resource-exhaustion" not in kinds   # not exhausted yet
        # one event per excursion (latched)
        assert kinds.count("resource-pressure") == 1
        # now actually exhaust: the forecast-then-exhaustion strict freeze
        occ["v"] = 100.0
        led.poll(now=8.0)
        assert [k for k, _ in events].count("resource-exhaustion") == 1
        assert led.report()["exhaustions_total"] == 1

    def test_flat_or_shrinking_resource_has_no_eta(self):
        led = ResourceLedger()
        led.register("p", lambda: {"r": (100, 50.0)})
        for t in range(4):
            led.poll(now=float(t))
        assert led.poll(now=5.0)["resources"]["r"]["eta_s"] is None

    def test_forecast_rearms_after_recovery(self):
        events = []
        led = ResourceLedger(
            eta_warn_s=100.0, warn=0.5, crit=0.99,
            event_sink=lambda kind, **a: events.append(kind))
        occ = {"v": 0.0}
        led.register("p", lambda: {"r": (100, occ["v"])})
        for t in range(7):
            occ["v"] = 10.0 * t
            led.poll(now=float(t))
        assert events.count("resource-pressure") == 1
        # recover: pressure below warn, shrinking → latch re-arms
        for t in range(7, 12):
            occ["v"] = 10.0
            led.poll(now=float(t))
        for t in range(12, 19):
            occ["v"] = 10.0 * (t - 11)
            led.poll(now=float(t))
        assert events.count("resource-pressure") == 2

    def test_deregister_sweeps_every_gauge_family(self):
        m = Metrics()
        led = ResourceLedger(metrics=m)
        led.register("p", lambda: {"a": (10, 9.0), "b": (10, 2.0)})
        led.poll(now=1.0)
        assert 'resource_occupancy{resource="a"}' in m.gauges
        gone = led.deregister("p")
        assert sorted(gone) == ["a", "b"]
        for fam in GAUGE_FAMILIES:
            for r in ("a", "b"):
                assert f'{fam}{{resource="{r}"}}' not in m.gauges
        assert led.report()["resources"] == {}

    def test_silently_departed_resource_is_swept(self):
        # a healthy provider that stops reporting a resource (pipeline
        # closed, incremental compiler discarded) must not leave its
        # frozen pressure pinned in state/gauges — only an ERRORING
        # provider's last readings stand (transient ≠ departed)
        m = Metrics()
        led = ResourceLedger(metrics=m)
        have = {"a": (10, 9.0), "b": (10, 2.0)}
        led.register("p", lambda: dict(have))
        led.poll(now=1.0)
        assert 'resource_pressure{resource="a"}' in m.gauges
        del have["a"]
        rep = led.poll(now=2.0)
        assert "a" not in rep["resources"] and "b" in rep["resources"]
        for fam in GAUGE_FAMILIES:
            assert f'{fam}{{resource="a"}}' not in m.gauges
        # an erroring provider sweeps nothing
        led.register("q", lambda: {"c": (10, 5.0)})
        led.poll(now=3.0)

        def boom():
            raise RuntimeError("transient")
        led.register("q", boom)
        rep = led.poll(now=4.0)
        assert "c" in rep["resources"]       # last good reading stands

    def test_failing_provider_is_isolated_and_counted(self):
        led = ResourceLedger()

        def bad():
            raise RuntimeError("boom")
        led.register("bad", bad)
        led.register("good", lambda: {"r": (10, 5.0)})
        rep = led.poll(now=1.0)
        assert rep["resources"]["r"]["occupancy"] == 5
        assert rep["provider_errors_total"] == 1

    def test_max_pressure_respects_exclusions(self):
        led = ResourceLedger()
        led.register("p", lambda: {"ct_table": (10, 10.0),
                                   "other": (10, 3.0)})
        led.poll(now=1.0)
        assert led.max_pressure() == 1.0
        assert led.max_pressure(exclude=LADDER_EXCLUDE) == 0.3

    def test_validation(self):
        with pytest.raises(ValueError):
            ResourceLedger(warn=0.9, crit=0.5)
        with pytest.raises(ValueError):
            ResourceLedger(window=1)
        with pytest.raises(ValueError):
            ResourceLedger(eta_warn_s=0)


class TestEngineLedger:
    def test_at_least_twelve_resources_register(self):
        # the ISSUE 13 acceptance floor — on the jax-free fake, even
        eng = _fake_engine()
        try:
            eng.start_pipeline()
            rep = eng.resource_step(now=1.0)
            assert len(rep["resources"]) >= 12, sorted(rep["resources"])
            for name in ("ct_table", "admission_queue", "flowlog_ring",
                         "trace_ring", "blackbox_events", "audit_pool",
                         "mapstate_overlay", "patch_budget"):
                assert name in rep["resources"], name
        finally:
            eng.stop()

    def test_ct_row_tracks_occupancy_gauge_exactly(self):
        eng = _fake_engine(ct_capacity=1 << 10)
        try:
            from tests.test_datapath import pkt  # house fixture helpers
            from cilium_tpu.kernels.records import batch_from_records
            recs = [pkt("192.168.0.10", f"10.0.{i >> 8}.{i & 255}",
                        40000 + i, 443, ep_id=1) for i in range(64)]
            eng.classify(batch_from_records(
                recs, eng.active.snapshot.ep_slot_of), now=1000)
            eng.sweep(now=1000)
            gauge = eng.metrics.gauges["ct_occupancy"]
            assert gauge > 0
            row = eng.resource_step(now=5.0)["resources"]["ct_table"]
            assert row["pressure"] == gauge          # bit-for-bit
            assert row["occupancy"] == gauge * (1 << 10)
        finally:
            eng.stop()

    def test_health_resource_pressure_detail_and_degrade(self):
        eng = _fake_engine()
        try:
            assert "resources" not in eng.health()
            eng.ledger.register("drill", lambda: {"drill_pool": (10, 8.0)})
            eng.resource_step(now=1.0)
            h = eng.health()
            assert h["resources"]["detail"] == C.RESOURCE_PRESSURE
            assert "drill_pool" in h["resources"]["pressured"]
            assert h["state"] == C.HEALTH_OK       # warn is attention-only
            eng.ledger.register("drill", lambda: {"drill_pool": (10, 10.0)})
            eng.resource_step(now=2.0)
            h = eng.health()
            assert h["resources"]["critical"]
            assert h["state"] == C.HEALTH_DEGRADED
            # deregistration clears the detail (and the degraded verdict)
            eng.ledger.deregister("drill")
            assert "resources" not in eng.health()
        finally:
            eng.stop()

    def test_overload_ladder_takes_resource_as_fourth_latch(self):
        eng = _fake_engine(overload_up_ticks=1)
        try:
            eng.ledger.register("drill", lambda: {"drill_pool": (10, 10.0)})
            eng.resource_step(now=1.0)
            st = eng.overload_step()
            assert st["inputs"]["resource_pressure"] == 1.0
            assert st["lit"]["resource"]
            st = eng.overload_step()
            # one lit signal holds PRESSURE, exactly like the original three
            from cilium_tpu.pipeline.guard import OVERLOAD_PRESSURE
            assert st["level"] == OVERLOAD_PRESSURE
            # excluded resources never light the latch
            eng.ledger.deregister("drill")
            eng.ledger.register(
                "drill2", lambda: {"audit_pool": (8, 8.0)})
            eng.resource_step(now=2.0)
            st = eng.overload_step()
            assert st["inputs"]["resource_pressure"] == 0.0
        finally:
            eng.stop()

    def test_past_patch_budget_consumption_is_not_standing_pressure(self):
        # a near-budget delta cycle is the LAST cycle's consumption, not a
        # standing occupancy: it must stay visible (occupancy/high-water)
        # without pinning health or the ladder's resource latch forever
        eng = _fake_engine()
        try:
            class _St:
                delta_rows = 1000       # 0.98 of the 1024 budget
                new_identities = 500
            eng._last_update_stats = _St()
            rep = eng.resource_step(now=1.0)
            row = rep["resources"]["patch_budget"]
            assert row["occupancy"] == 1000
            assert row["pressure"] == 0.0        # informational
            assert "resources" not in eng.health()
            st = eng.overload_step()
            assert st["inputs"]["resource_pressure"] == 0.0
        finally:
            eng.stop()

    def test_ladder_caps_at_shed_new_with_all_four_signals_lit(self):
        # severity can reach 4 now; the ladder must hold the top rung,
        # never step past the state table (was a KeyError crashing the
        # overload controller exactly when shedding mattered most)
        from cilium_tpu.pipeline.guard import (OVERLOAD_SHED_NEW,
                                               OverloadLadder)
        ladder = OverloadLadder(up_ticks=1)
        for _ in range(6):
            state, _ = ladder.observe(1.0, 100.0, 1.0,
                                      resource_pressure=1.0)
        assert state == OVERLOAD_SHED_NEW
        assert ladder.status()["inputs"]["severity"] == 4

    def test_wire_out_shed_on_failed_dispatch(self):
        # a fault-tripped dispatch dies between checkout and finalize:
        # the buffer sheds to the GC but the in-flight count must come
        # back down (no phantom wire_pool occupancy)
        from cilium_tpu.runtime.datapath import JITDatapath
        from cilium_tpu.runtime.faults import FAULTS
        from cilium_tpu.kernels.records import empty_batch
        cfg = DaemonConfig(auto_regen=False, ct_capacity=1 << 10)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        try:
            eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",))
            eng.regenerate()
            b = empty_batch(64)
            FAULTS.reset()
            FAULTS.load_spec("ct.insert=fail:3")
            for _ in range(3):
                with pytest.raises(Exception):
                    eng.classify(dict(b), now=1000)
            FAULTS.reset()
            assert eng.datapath.wire_pool_stats()["in_flight"] == 0
            eng.classify(dict(b), now=1000)   # healthy dispatch balances
            assert eng.datapath.wire_pool_stats()["in_flight"] == 0
        finally:
            FAULTS.reset()
            eng.stop()

    def test_wire_pool_occupancy_counts_checkouts_not_free(self):
        from cilium_tpu.runtime.datapath import JITDatapath
        cfg = DaemonConfig(auto_regen=False, ct_capacity=1 << 10)
        dp = JITDatapath(cfg)
        s = dp.wire_pool_stats()
        assert s["in_flight"] == 0               # idle pool ≠ exhausted
        with dp._pack_lock:
            buf = dp._wire_buf(256, 4)
        assert dp.wire_pool_stats()["in_flight"] == 1
        dp._wire_buf_release((256, 4), buf)
        s = dp.wire_pool_stats()
        assert s["in_flight"] == 0 and s["free"] == 1

    def test_register_deregister_under_engine_restart(self):
        eng = _fake_engine()
        eng.start_pipeline()
        eng.resource_step(now=1.0)
        fams = [g for g in eng.metrics.gauges if g.startswith("resource_")]
        assert fams
        eng.stop()
        # a stopped engine sweeps its whole exported surface
        assert not [g for g in eng.metrics.gauges
                    if g.startswith("resource_")]
        assert eng.ledger.report()["resources"] == {}
        # a fresh engine re-registers from scratch
        eng2 = _fake_engine()
        try:
            rep = eng2.resource_step(now=1.0)
            assert "ct_table" in rep["resources"]
        finally:
            eng2.stop()

    def test_resource_families_survive_concurrent_scrapes(self):
        # the PR 7/11 scrape-race house pattern on the new {resource=}
        # families: render_metrics scrapers race ledger polls AND a
        # register/deregister churn loop — no exceptions, parseable text
        eng = _fake_engine()
        eng.start_pipeline()
        errors = []
        stop = threading.Event()

        def scraper():
            try:
                while not stop.is_set():
                    text = eng.render_metrics()
                    assert "ciliumtpu_" in text
            except Exception as e:   # noqa: BLE001
                errors.append(e)

        def churn():
            try:
                i = 0
                while not stop.is_set():
                    i += 1
                    eng.ledger.register(
                        "churn", lambda: {"churn_pool": (64, 32.0)})
                    eng.resource_step(now=float(i))
                    eng.ledger.deregister("churn")
            except Exception as e:   # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=scraper) for _ in range(2)] \
            + [threading.Thread(target=churn)]
        for t in threads:
            t.start()
        time.sleep(0.8)
        stop.set()
        for t in threads:
            t.join(5)
        eng.stop()
        assert not errors

    def test_status_doc_carries_resources_and_hbm(self):
        from cilium_tpu.runtime.api import status_doc
        eng = _fake_engine()
        try:
            eng.resource_step(now=1.0)
            doc = status_doc(eng)
            assert "pressured" in doc["resources"]
            assert doc["hbm"]["ledger"] is None    # jax-free fake
            eng.note_verifier_budget({"worst_total_bytes": 123})
            assert status_doc(eng)["hbm"]["verifier"][
                "worst_total_bytes"] == 123
        finally:
            eng.stop()

    def test_resources_api_route(self, tmp_path):
        from cilium_tpu.runtime.api import APIServer, UnixAPIClient
        eng = _fake_engine()
        sock = str(tmp_path / "api.sock")
        srv = APIServer(eng, sock)
        srv.start()
        try:
            eng.resource_step(now=1.0)   # the controller's role
            status, doc = UnixAPIClient(sock).get("/v1/resources")
            assert status == 200
            assert "ct_table" in doc["resources"]
            assert doc["hbm"]["ledger"] is None
            # the route is the READ side: a scrape must not advance the
            # ledger's sampling (no resource.poll side effects)
            polls = doc["polls_total"]
            status, doc2 = UnixAPIClient(sock).get("/v1/resources")
            assert doc2["polls_total"] == polls
        finally:
            srv.stop()
            eng.stop()


class TestTraceRingDropAccounting:
    def test_overwrites_count_and_wraps(self):
        tr = Tracer(sample_rate=1.0, capacity=4)
        for i in range(10):
            tid = tr.maybe_sample()
            tr.record(tid, "s", 0.0, 0.001)
        st = tr.stats()
        assert st["spans_in_ring"] == 4
        assert st["spans_dropped_total"] == 6
        # a wrap is a completed cycle of LOSS (the initial free fill is
        # not one): 10 records = fill 4 + one full drop cycle + 2
        assert st["ring_wraps"] == 1
        tr.reset()
        st = tr.stats()
        assert st["spans_dropped_total"] == 0 and st["ring_wraps"] == 0

    def test_no_drops_while_ring_has_room(self):
        tr = Tracer(sample_rate=1.0, capacity=16)
        for _ in range(10):
            tr.record(tr.maybe_sample(), "s", 0.0, 0.001)
        st = tr.stats()
        assert st["spans_dropped_total"] == 0 and st["ring_wraps"] == 0

    def test_engine_exports_drop_counters(self):
        from cilium_tpu.observe.trace import TRACER
        eng = _fake_engine()
        try:
            TRACER.reset()
            TRACER.configure(sample_rate=1.0, capacity=4)
            for _ in range(9):
                TRACER.record(TRACER.maybe_sample(), "drill", 0.0, 0.001)
            text = eng.render_metrics()
            assert "ciliumtpu_trace_spans_dropped_total 5" in text
            assert "ciliumtpu_trace_ring_wraps_total 1" in text
        finally:
            TRACER.configure(sample_rate=0.0, capacity=4096)
            TRACER.reset()
            eng.stop()


class TestDepartedSubjectSweeps:
    def test_pipeline_close_drops_shard_gauges(self):
        from cilium_tpu.pipeline import Pipeline
        from tests.test_pipeline import EchoDispatch, sub_batch
        m = Metrics()
        echo = EchoDispatch()
        pl = Pipeline(lambda b, now, steer_rev=None: echo(b, now),
                      metrics=m, max_bucket=64,
                      min_bucket=8, n_shards=2,
                      shard_fn=lambda b: np.zeros(
                          b["valid"].shape[0], dtype=np.int64))
        pl.submit(sub_batch(8, 0)).result(timeout=10)
        assert 'pipeline_staged_rows{shard="0"}' in m.gauges
        pl.close(timeout=10)
        assert 'pipeline_staged_rows{shard="0"}' not in m.gauges
        assert 'pipeline_staged_rows{shard="1"}' not in m.gauges

    def test_mesh_withdraw_drops_peer_lag_gauges(self, tmp_path):
        eng = _fake_engine()
        eng2 = None
        try:
            mesh = eng.attach_mesh(store_dir=str(tmp_path), node_name="a")
            cfg2 = DaemonConfig(auto_regen=False)
            eng2 = Engine(cfg2, datapath=FakeDatapath(cfg2))
            eng2.add_endpoint(["k8s:app=db"], ips=("192.168.1.20",))
            eng2.regenerate()
            mesh2 = eng2.attach_mesh(store_dir=str(tmp_path),
                                     node_name="b")
            mesh2.step()
            mesh.step()
            assert 'clustermesh_peer_lag_seconds{peer="b"}' \
                in eng.metrics.gauges
            mesh.withdraw()
            assert 'clustermesh_peer_lag_seconds{peer="b"}' \
                not in eng.metrics.gauges
        finally:
            eng.stop()
            if eng2 is not None:
                eng2.stop()


class TestVerifierBudgetDoc:
    def test_budget_doc_summarizes_worst_combo(self):
        from cilium_tpu.compile.verifier import ComboReport, budget_doc
        reports = [
            ComboReport(name="a", ok=True, argument_bytes=100,
                        temp_bytes=50),
            ComboReport(name="b", ok=True, argument_bytes=400,
                        temp_bytes=100),
            ComboReport(name="c", ok=False, error="reject"),
        ]
        doc = budget_doc(reports, max_hbm_bytes=1 << 20)
        assert doc["combos"] == 3 and doc["accepted"] == 2
        assert doc["rejected"] == ["c"]
        assert doc["worst_combo"] == "b"
        assert doc["worst_total_bytes"] == 500
        assert doc["max_hbm_bytes"] == 1 << 20

    def test_memory_stats_public_name(self):
        from cilium_tpu.compile import verifier

        class FakeCompiled:
            def memory_analysis(self):
                class M:
                    argument_size_in_bytes = 10
                    temp_size_in_bytes = 20
                    output_size_in_bytes = 30
                return M()
        st = verifier.memory_stats(FakeCompiled())
        assert st == {"argument_bytes": 10, "temp_bytes": 20,
                      "output_bytes": 30}


class TestMapstateOverlayStats:
    def test_overlay_copy_updates_module_stats(self):
        from cilium_tpu.policy.mapstate import (MapState, overlay_stats)
        ms = MapState()
        clone = ms.overlay_copy()
        base = overlay_stats()
        assert base["fold_budget"] == MapState.OVERLAY_FOLD_KEYS
        clone2 = clone.overlay_copy()
        assert overlay_stats()["copies"] > base["copies"]
        assert clone2 is not clone


class TestJITHBMLedger:
    def test_place_and_patch_account_groups(self):
        from cilium_tpu.runtime.datapath import JITDatapath
        cfg = DaemonConfig(auto_regen=False, ct_capacity=1 << 10,
                           max_hbm_bytes=1 << 28)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        try:
            eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",))
            eng.apply_policy([{
                "endpointSelector": {"matchLabels": {"app": "web"}},
                "egress": [{"toCIDR": ["10.0.0.0/8"]}]}])
            eng.regenerate()
            hl = eng.datapath.hbm_ledger()
            assert hl["places_total"] == 1
            for g in ("verdict", "tries", "policy", "ct"):
                assert hl["groups"][g] > 0, g
            assert hl["device_bytes"] == sum(
                v for k, v in hl["groups"].items() if k != "wire_pool")
            # a live patch re-accounts without a full place
            eng.apply_policy([{
                "endpointSelector": {"matchLabels": {"app": "web"}},
                "egress": [{"toCIDR": ["172.16.0.0/12"]}]}])
            eng.regenerate()
            hl2 = eng.datapath.hbm_ledger()
            assert hl2["places_total"] + hl2["patches_total"] >= 2
            # the hbm resource row budgets device bytes
            row = eng.resource_step(now=1.0)["resources"]["hbm"]
            assert row["capacity"] == float(1 << 28)
            assert row["occupancy"] == hl2["device_bytes"]
        finally:
            eng.stop()


# --------------------------------------------------------------------------- #
# slow: the cfg6-form pressure soak (`make chaos`)
# --------------------------------------------------------------------------- #
class TestPressureSoak:
    @pytest.mark.parametrize("cap,rows", [
        (1 << 8, 64), pytest.param(1 << 10, 256, marks=pytest.mark.slow)])
    def test_storm_ct_row_bit_identical_and_eta_before_shed_new(self, cap,
                                                                 rows):
        """The cfg6 acceptance, in-tree: a SYN flood saturates a tiny CT
        through the live pipelined engine (auditor at 1.0); every tick the
        ledger's ct_table row must equal the ct_occupancy gauge EXACTLY,
        and the time-to-exhaustion forecast must fire before the overload
        ladder reaches SHED-NEW."""
        from cilium_tpu.pipeline.guard import OVERLOAD_SHED_NEW
        from cilium_tpu.runtime.datapath import JITDatapath
        rng = np.random.default_rng(7)
        cfg = DaemonConfig(
            ct_capacity=cap, auto_regen=False, batch_size=rows,
            pipeline_flush_ms=0.5, pipeline_queue_batches=8,
            pipeline_block_timeout_s=0.05,
            audit_enabled=True, audit_sample_rate=1.0,
            audit_pool_batches=64, flowlog_mode="none",
            ct_gc_chunk_rows=cap >> 2,
            ct_pressure_high=0.8, ct_pressure_low=0.5,
            overload_up_ticks=1, overload_down_ticks=4,
            overload_shed_rate_high=15.0, overload_shed_rate_low=2.0,
            resource_eta_warn_s=1000.0)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        eng.auditor.configure(sample_rate=1.0)
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",), ep_id=1)
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{"fromCIDR": ["10.0.0.0/8"],
                         "toPorts": [{"ports": [
                             {"port": "80", "protocol": "TCP"}]}]}]}])
        eng.regenerate()

        def flood_batch(n=rows):
            from cilium_tpu.kernels.records import empty_batch
            b = empty_batch(n)
            b["valid"][:] = True
            b["src"][:, 3] = (0x0A000000
                              + rng.integers(1, 1 << 24, n)).astype(
                                  np.uint32)
            b["dst"][:, 3] = 0xC0A8000A
            b["dst"][:, 2] = 0xFFFF
            b["src"][:, 2] = 0xFFFF
            b["sport"][:] = rng.integers(1024, 65535, n)
            b["dport"][:] = 80
            b["proto"][:] = C.PROTO_TCP
            b["tcp_flags"][:] = 0x02
            b["direction"][:] = C.DIR_INGRESS
            b["ep_slot"][:] = 0
            b["_prio"] = np.ones((n,), np.int8)
            return b

        L = 50_000
        forecast_tick = shed_new_tick = None
        mismatches = []
        try:
            for tick in range(60):
                L += 1
                for _ in range(6):
                    try:
                        eng.submit(flood_batch(), now=L, deadline_ms=200)
                    except Exception:   # noqa: BLE001 — sheds are the point
                        pass
                eng.drain(timeout=60)
                st = eng.overload_step()
                eng.sweep_step(now=L)
                eng.audit_step(budget=32)
                rep = eng.resource_step(now=float(L))
                row = rep["resources"]["ct_table"]
                gauge = float(eng.metrics.gauges.get("ct_occupancy", 0.0))
                if row["pressure"] != gauge:
                    mismatches.append((tick, row["pressure"], gauge))
                if forecast_tick is None and row["forecast"]:
                    forecast_tick = tick
                if shed_new_tick is None \
                        and st["level"] >= OVERLOAD_SHED_NEW:
                    shed_new_tick = tick
                if shed_new_tick is not None and forecast_tick is not None:
                    break
            assert not mismatches, mismatches[:4]
            assert forecast_tick is not None, \
                "time-to-exhaustion never fired for ct_table"
            if shed_new_tick is not None:
                assert forecast_tick < shed_new_tick, (forecast_tick,
                                                       shed_new_tick)
            aud = eng.auditor.stats()
            assert aud["mismatched_rows"] == 0
        finally:
            eng.stop()

    @pytest.mark.slow
    def test_8shard_audited_soak_scrape_race_with_restart(self):
        """The PR 7/11 house pattern extended to the {resource=} families:
        an 8-shard audited pipeline soak with concurrent render_metrics
        scrapers and a mid-soak watchdog restart (hang-forced), asserting
        the resource families stay scrapeable and consistent throughout
        and after the restart the per-shard staged gauges are live again."""
        from cilium_tpu.runtime.datapath import JITDatapath
        from cilium_tpu.runtime.faults import FAULTS
        from tests.test_datapath import pkt
        from cilium_tpu.kernels.records import batch_from_records
        cfg = DaemonConfig(
            n_shards=8, auto_regen=False, batch_size=512,
            ct_capacity=1 << 12, pipeline_flush_ms=0.5,
            audit_enabled=True, audit_sample_rate=1.0,
            pipeline_stall_timeout_s=1.0, pipeline_max_restarts=3,
            pipeline_restart_backoff_s=0.05)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        eng.auditor.configure(sample_rate=1.0)
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",), ep_id=1)
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egress": [{"toCIDR": ["10.0.0.0/8"],
                        "toPorts": [{"ports": [
                            {"port": "443", "protocol": "TCP"}]}]}]}])
        eng.regenerate()
        errors = []
        stop = threading.Event()

        def scraper():
            try:
                while not stop.is_set():
                    text = eng.render_metrics()
                    lines = [ln for ln in text.splitlines()
                             if ln.startswith("ciliumtpu_resource_")]
                    for ln in lines:       # every exported row parses
                        float(ln.rsplit(" ", 1)[1])
            except Exception as e:   # noqa: BLE001
                errors.append(e)
        threads = [threading.Thread(target=scraper) for _ in range(2)]
        for t in threads:
            t.start()

        def batch(i):
            recs = [pkt("192.168.0.10", f"10.0.{(i + j) % 250}.1",
                        40000 + j, 443, ep_id=1) for j in range(64)]
            return batch_from_records(recs,
                                      eng.active.snapshot.ep_slot_of)
        try:
            FAULTS.reset()
            for i in range(20):
                eng.submit(batch(i), now=1000 + i)
            assert eng.drain(timeout=120)
            eng.resource_step(now=1.0)
            # mid-soak watchdog restart: hang one dispatch past the stall
            # budget; the watchdog fences the worker and restarts
            FAULTS.load_spec("datapath.transfer=hang:4:1")
            try:
                eng.submit(batch(99), now=2000)
            except Exception:   # noqa: BLE001 — the wedged window rejects
                pass
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                ps = eng.pipeline_stats()
                if ps and ps["restarts"] >= 1 and ps["state"] == "ok":
                    break
                time.sleep(0.1)
            FAULTS.reset()
            ps = eng.pipeline_stats()
            assert ps["restarts"] >= 1
            # post-restart: serving resumes and the families still export
            for i in range(10):
                eng.submit(batch(200 + i), now=3000 + i)
            assert eng.drain(timeout=120)
            for _ in range(50):
                step = eng.audit_step(budget=128)
                if not step or (not step.get("replayed")
                                and not step.get("pending")):
                    break
            rep = eng.resource_step(now=10.0)
            assert "staging_segment_peak" in rep["resources"]
            assert len(rep["resources"]) >= 12
            assert eng.auditor.stats()["mismatched_rows"] == 0
        finally:
            stop.set()
            for t in threads:
                t.join(5)
            FAULTS.reset()
            eng.stop()
        assert not errors
