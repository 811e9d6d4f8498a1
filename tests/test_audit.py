"""Verdict-provenance tests: shadow-oracle parity audit (observe/audit.py),
the flight recorder (observe/blackbox.py), and the end-to-end latency SLO
plumbing.

Unit tests drive the auditor directly — deterministic counter sampling,
bounded capture pool with ``skipped`` accounting, the ``audit.corrupt``
fault drill (detection + health degradation + frozen bundle with the
offending rows and revision), and fault tolerance (a wedged/crashing
auditor never stalls serving). Integration tests run it against engines on
both backends, including a sharded 8-shard pipeline; the ``slow``-marked
soak (`make chaos`) pushes 10k submissions with the auditor armed
at sampling 1.0 and asserts zero mismatches, then arms ``audit.corrupt``
and asserts the corruption is detected within the sampling window.

The satellite coverage also lives here: ``quantile_from`` empty-window
sentinel, feeder-stats Prometheus families + labeled-histogram TYPE
dedupe, a concurrent ``render_metrics`` scrape racing a sharded soak, and
trace-ring wraparound with audit capture armed.
"""

import json
import math
import threading
import time

import numpy as np
import pytest

from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.observe.audit import ShadowAuditor
from cilium_tpu.observe.blackbox import FlightRecorder
from cilium_tpu.observe.trace import TRACER, Tracer
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.runtime.faults import FAULTS
from cilium_tpu.runtime.metrics import (EMPTY_QUANTILE, Histogram, Metrics,
                                        quantile_from, quantile_is_empty)
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr
from oracle import PacketRecord

from tests.test_pipeline import POLICY, fake_engine, mk_chunks, pkt


@pytest.fixture(autouse=True)
def _clean():
    FAULTS.reset()
    yield
    FAULTS.reset()
    TRACER.configure(sample_rate=0.0)
    TRACER.reset()


def audited_engine(**kw):
    kw.setdefault("audit_enabled", True)
    kw.setdefault("audit_sample_rate", 1.0)
    return fake_engine(**kw)


class ShardedFake(FakeDatapath):
    """Oracle-backed fake serving an 8-way flow mesh: the class attribute
    shadows the base property, so the engine builds the 8-segment steered
    staging ring (per-shard scatter, unsteer-on-finalize) on top of the
    oracle — the audit path then sees real steered-geometry buckets."""

    pipeline_shards = 8


def sharded_audited_engine(**kw):
    kw.setdefault("ct_capacity", 4096)
    kw.setdefault("auto_regen", False)
    kw.setdefault("batch_size", 64)
    kw.setdefault("audit_enabled", True)
    kw.setdefault("audit_sample_rate", 1.0)
    cfg = DaemonConfig(**kw)
    return Engine(cfg, datapath=ShardedFake(cfg))


def web_batch(eng, dports=(443, 80, 22)):
    slot_of = eng.active.snapshot.ep_slot_of
    recs = [pkt("192.168.1.10", "10.1.2.3", 40000 + dp, dp)
            for dp in dports]
    return batch_from_records(recs, slot_of)


def setup_web(eng):
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.apply_policy(POLICY)
    return eng


# --------------------------------------------------------------------------- #
# shadow auditor
# --------------------------------------------------------------------------- #
class TestAuditorUnit:
    def test_counter_sampling_is_deterministic(self):
        eng = setup_web(audited_engine(audit_sample_rate=0.25))
        b = web_batch(eng)
        for i in range(8):
            eng.classify(dict(b), now=100 + i)
        eng.audit_step()
        st = eng.auditor.stats()
        # every 4th finalized batch captured: batches 0 and 4
        assert st["captured_batches"] == 2
        assert st["checked_batches"] == 2
        eng.stop()

    def test_clean_engine_audits_clean(self):
        eng = setup_web(audited_engine())
        b = web_batch(eng)
        for i in range(5):
            eng.classify(dict(b), now=100 + i)   # CT revisits included
        eng.audit_step()
        st = eng.auditor.stats()
        assert st["checked_rows"] == 15 and st["mismatched_rows"] == 0
        assert eng.auditor.healthy
        assert eng.health()["state"] == C.HEALTH_OK
        # the labeled mismatch family must not exist on a clean engine
        assert not any("parity_audit_mismatched" in k
                       for k in eng.metrics.counters)
        assert eng.metrics.counters["parity_audit_checked_total"] == 15
        eng.stop()

    def test_disabled_auditor_captures_nothing(self):
        eng = setup_web(fake_engine())       # audit_enabled defaults False
        eng.classify(web_batch(eng), now=100)
        assert eng.auditor.sample_rate == 0.0
        assert eng.auditor.stats()["captured_batches"] == 0
        eng.stop()

    def test_bounded_pool_sheds_with_skipped_accounting(self):
        eng = setup_web(audited_engine(audit_pool_batches=2))
        b = web_batch(eng)
        for i in range(6):                   # no replay between captures
            eng.classify(dict(b), now=100 + i)
        st = eng.auditor.stats()
        assert st["captured_batches"] == 2 and st["skipped_batches"] == 4
        assert eng.metrics.counters["parity_audit_skipped_total"] == 4
        # the backlog replays clean once the controller catches up
        eng.audit_step()
        st = eng.auditor.stats()
        assert st["checked_batches"] == 2 and st["mismatched_rows"] == 0
        eng.stop()

    def test_corruption_drill_detects_degrades_and_freezes(self):
        """The acceptance contract: with audit.corrupt armed the auditor
        detects within the sampling window, health goes DEGRADED, and a
        flight-recorder bundle with the offending rows + revision comes
        out of the debug-bundle surface."""
        eng = setup_web(audited_engine())
        b = web_batch(eng)
        eng.classify(dict(b), now=100)
        eng.audit_step()
        assert eng.auditor.healthy
        rev = eng.active.revision
        with FAULTS.inject("audit.corrupt", mode="fail", times=1):
            eng.classify(dict(b), now=101)
        eng.classify(dict(b), now=102)       # later batches are clean again
        eng.audit_step()
        st = eng.auditor.stats()
        assert st["mismatched_batches"] == 1
        assert st["mismatched_rows"] == 3    # every flipped row caught
        assert st["last_mismatch_revision"] == rev
        h = eng.health()
        assert h["state"] == C.HEALTH_DEGRADED
        assert h["audit"]["mismatched_rows"] == 3
        key = f'parity_audit_mismatched_total{{revision="{rev}"}}'
        assert eng.metrics.counters[key] == 3
        bundle = eng.debug_bundle()
        assert bundle["frozen"] and bundle["reason"] == "parity-mismatch"
        assert bundle["detail"]["revision"] == rev
        assert bundle["detail"]["rows"], "offending rows must ride the bundle"
        assert bundle["detail"]["rows"][0]["diffs"]["allow"]
        assert bundle["engine"]["audit"]["mismatched_rows"] == 3
        json.dumps(bundle, default=str)      # exportable as-is
        eng.stop()

    def test_clear_rearms_health_and_next_mismatch_freezes_again(self):
        """The operator workflow the runbook promises: pull the bundle
        with clear=True → health returns to OK and the recorder unfreezes;
        a LATER mismatch degrades and freezes afresh."""
        eng = setup_web(audited_engine())
        b = web_batch(eng)
        with FAULTS.inject("audit.corrupt", mode="fail", times=1):
            eng.classify(dict(b), now=100)
        eng.audit_step()
        assert eng.health()["state"] == C.HEALTH_DEGRADED
        eng.debug_bundle(clear=True)         # investigated: re-arm
        assert eng.health()["state"] == C.HEALTH_OK
        assert not eng.blackbox.stats()["frozen"]
        assert eng.auditor.healthy
        # per-revision mismatch counters are history and survive re-arm
        assert any("parity_audit_mismatched" in k
                   for k in eng.metrics.counters)
        with FAULTS.inject("audit.corrupt", mode="fail", times=1):
            eng.classify(dict(b), now=200)
        eng.audit_step()
        assert eng.health()["state"] == C.HEALTH_DEGRADED
        assert eng.debug_bundle()["frozen"]
        eng.stop()

    def test_mismatch_diff_names_the_field_and_flow(self):
        eng = setup_web(audited_engine())
        with FAULTS.inject("audit.corrupt", mode="fail", times=1):
            eng.classify(web_batch(eng, dports=(443,)), now=100)
        eng.audit_step()
        (m,) = list(eng.auditor.mismatches)
        row = m["rows"][0]
        assert row["diffs"]["allow"] == {"want": True, "got": False}
        # a flipped allow on a NEW flow also tears the implied CT delta
        assert row["diffs"]["ct_delta"] == {"want": "create", "got": "none"}
        assert row["flow"]["dport"] == 443 and row["flow"]["ep_id"] == 1
        assert m["corrupt_injected"] is True
        eng.stop()

    def test_capture_crash_never_reaches_serving(self, monkeypatch):
        eng = setup_web(audited_engine())
        monkeypatch.setattr(eng.auditor, "_capture",
                            lambda *a, **k: 1 / 0)
        out = eng.classify(web_batch(eng), now=100)   # must not raise
        assert out["allow"][0]
        assert eng.auditor.stats()["capture_errors"] == 1
        assert eng.metrics.counters[
            "parity_audit_capture_errors_total"] == 1
        eng.stop()

    def test_replay_crash_is_counted_not_fatal(self, monkeypatch):
        eng = setup_web(audited_engine())
        eng.classify(web_batch(eng), now=100)
        monkeypatch.setattr(eng.auditor, "_oracle_for",
                            lambda snap: 1 / 0)
        res = eng.audit_step()               # must not raise
        assert res["replayed"] == 1
        assert eng.auditor.stats()["replay_errors"] == 1
        eng.stop()

    def test_wedged_auditor_never_stalls_serving(self):
        """A deliberately wedged replay thread: serving keeps answering
        at full function while captures overflow into `skipped` — the
        bounded-pool degradation contract."""
        eng = setup_web(audited_engine(audit_pool_batches=2))
        b = web_batch(eng)
        release = threading.Event()

        def wedged_step():
            release.wait(30)                 # the wedge
            return eng.audit_step()

        t = threading.Thread(target=wedged_step, daemon=True)
        t.start()
        outs = [eng.classify(dict(b), now=100 + i) for i in range(10)]
        assert all(bool(o["allow"][0]) for o in outs)
        st = eng.auditor.stats()
        assert st["skipped_batches"] >= 8    # pool=2, 10 batches at rate 1.0
        release.set()
        t.join(10)
        assert eng.auditor.stats()["mismatched_rows"] == 0
        eng.stop()

    def test_audit_controller_runs_in_background(self):
        eng = setup_web(audited_engine(audit_interval_s=0.05))
        eng.start_background()
        try:
            eng.classify(web_batch(eng), now=100)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if eng.auditor.stats()["checked_batches"] >= 1:
                    break
                time.sleep(0.02)
            st = eng.auditor.stats()
            assert st["checked_batches"] >= 1 and st["mismatched_rows"] == 0
        finally:
            eng.stop()

    def test_replay_against_superseded_revision(self):
        """A capture replays against the snapshot it classified under,
        even after a policy change regenerated a newer world — the
        revision fence of the audit path."""
        eng = setup_web(audited_engine())
        b = web_batch(eng)
        eng.classify(dict(b), now=100)
        old_rev = eng.active.revision
        # flip the policy so the same flow now gets the opposite verdict
        eng.replace_policy(["k8s:app=web"], [{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egressDeny": [{"toCIDR": ["10.0.0.0/8"]}]}])
        eng.regenerate(force=True)
        assert eng.active.revision > old_rev
        eng.classify(dict(b), now=101)
        eng.audit_step()
        st = eng.auditor.stats()
        assert st["checked_batches"] == 2 and st["mismatched_rows"] == 0
        eng.stop()


class TestAuditorPipelined:
    def test_pipelined_batches_audit_clean(self):
        eng = setup_web(audited_engine(pipeline_min_bucket=16))
        chunks = mk_chunks(eng.active.snapshot.ep_slot_of, n_chunks=12,
                           rows_per_chunk=8, repeats=True)
        tickets = [eng.submit(dict(ch), now=100 + i)
                   for i, ch in enumerate(chunks)]
        assert eng.drain(timeout=30)
        for t in tickets:
            t.result(timeout=5)
        while eng.audit_step()["replayed"]:
            pass
        st = eng.auditor.stats()
        assert st["checked_rows"] > 0 and st["mismatched_rows"] == 0
        eng.stop()

    def test_pipelined_corruption_detected(self):
        eng = setup_web(audited_engine(pipeline_min_bucket=16))
        chunks = mk_chunks(eng.active.snapshot.ep_slot_of, n_chunks=6,
                           rows_per_chunk=8)
        FAULTS.arm("audit.corrupt", mode="fail", times=1)
        for i, ch in enumerate(chunks):
            eng.submit(dict(ch), now=100 + i)
        assert eng.drain(timeout=30)
        FAULTS.disarm("audit.corrupt")
        while eng.audit_step()["replayed"]:
            pass
        assert eng.auditor.stats()["mismatched_rows"] > 0
        assert eng.health()["state"] == C.HEALTH_DEGRADED
        assert eng.debug_bundle()["frozen"]
        eng.stop()


# --------------------------------------------------------------------------- #
# flight recorder
# --------------------------------------------------------------------------- #
class TestFlightRecorder:
    def test_event_ring_is_bounded(self):
        fr = FlightRecorder(capacity=4, metrics=Metrics())
        for i in range(10):
            fr.record_event("regen", revision=i)
        st = fr.stats()
        assert st["events_in_ring"] == 4 and st["events_total"] == 10
        assert not st["frozen"]

    def test_first_anomaly_wins(self):
        fr = FlightRecorder(metrics=Metrics())
        fr.record_event("regen", revision=1)
        fr.record_event("watchdog", action="restart", reason="stall")
        fr.record_event("breaker", old="closed", new="open")
        st = fr.stats()
        assert st["frozen"] and st["freezes_total"] == 2
        assert st["frozen_reason"].startswith("watchdog")
        b = fr.bundle()
        kinds = [e["kind"] for e in b["events"]]
        assert kinds[0] == "regen"           # lead-up context preserved
        fr.clear()
        assert not fr.stats()["frozen"]

    def test_breaker_close_does_not_freeze(self):
        fr = FlightRecorder(metrics=Metrics())
        fr.record_event("breaker", old="open", new="half-open")
        fr.record_event("breaker", old="half-open", new="closed")
        assert not fr.stats()["frozen"]

    def test_shed_spike_freezes_single_shed_does_not(self):
        fr = FlightRecorder(shed_spike=5, shed_window_s=10.0,
                            metrics=Metrics())
        fr.record_event("shed", reason="flush")
        assert not fr.stats()["frozen"]
        for _ in range(5):
            fr.record_event("shed", reason="flush")
        st = fr.stats()
        assert st["frozen"] and st["frozen_reason"].startswith("shed-spike")

    def test_verdict_summaries_and_span_tail_ride_the_bundle(self):
        tr = Tracer(sample_rate=1.0, capacity=32)
        tid = tr.maybe_sample()
        tr.record(tid, "pipeline.dispatch", 0.0, 0.002)
        fr = FlightRecorder(metrics=Metrics(), tracer=tr)
        out = {"allow": np.array([True, False, False]),
               "reason": np.array([0, int(C.DropReason.POLICY),
                                   int(C.DropReason.POLICY)], np.int32)}
        fr.record_verdicts(out, n_valid=3, now=100)
        b = fr.freeze("parity-mismatch", detail={"revision": 7})
        (vs,) = b["verdict_summaries"]
        assert vs["allowed"] == 1 and vs["dropped"] == 2
        assert vs["top_reasons"] == {"POLICY": 2}
        assert b["spans"][0]["name"] == "pipeline.dispatch"
        assert b["detail"]["revision"] == 7

    def test_pipeline_guard_events_reach_the_recorder(self):
        """The scheduler's event_sink: a real watchdog restart (hang-wedged
        dispatch) must land in the engine's flight recorder and freeze."""
        eng = setup_web(fake_engine(pipeline_stall_timeout_s=30.0,
                                    pipeline_restart_backoff_s=0.05))
        pl = eng.start_pipeline()
        pl.set_stall_timeout_s(0.5)
        FAULTS.arm("pipeline.dispatch", mode="hang", delay_s=5.0, times=1)
        for i in range(3):
            eng.submit(web_batch(eng), now=100 + i)
        eng.drain(timeout=20)
        FAULTS.disarm("pipeline.dispatch")
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and not eng.blackbox.stats()["frozen"]:
            time.sleep(0.05)
        st = eng.blackbox.stats()
        assert st["frozen"] and st["frozen_reason"].startswith("watchdog")
        bundle = eng.debug_bundle()
        assert any(e["kind"] == "watchdog" for e in bundle["events"])
        eng.stop()


# --------------------------------------------------------------------------- #
# end-to-end latency SLO plumbing
# --------------------------------------------------------------------------- #
class TestE2ELatency:
    def test_ingest_mono_rides_the_ticket(self):
        eng = setup_web(fake_engine())
        stamp = time.monotonic() - 0.25      # harvested 250ms ago
        t = eng.submit(web_batch(eng), now=100, ingest_mono=stamp)
        t.result(timeout=10)
        assert t.ingest_mono == stamp
        t2 = eng.submit(web_batch(eng), now=101)
        t2.result(timeout=10)
        assert t2.ingest_mono is None
        eng.stop()

    def test_feeder_observes_e2e_and_burns_slo(self):
        """Drive _apply_one directly with a back-dated harvest stamp: the
        e2e histogram and the SLO burn counter must both move."""
        from cilium_tpu.shim.feeder import ShimFeeder

        class _StubShim:
            batch_size = 8

            def make_poll_buffer(self):
                from cilium_tpu.kernels.records import empty_batch
                b = empty_batch(8)
                b["_ep_raw"] = np.zeros(8, np.int64)
                return b

            def apply_verdicts(self, allow):
                pass

        class _StubTicket:
            def done(self):
                return True

            def result(self, timeout=None):
                return {"allow": np.ones(8, bool)}

        m = Metrics()
        fd = ShimFeeder(_StubShim(), engine=None, pool_batches=1,
                        slo_ms=50.0, metrics=m)
        buf = fd._free[0]
        fd._apply_one(_StubTicket(), buf,
                      ingest_mono=time.monotonic() - 0.2)
        fd._apply_one(_StubTicket(), buf,
                      ingest_mono=time.monotonic() - 0.001)
        h = m.histograms["ingest_e2e_latency_seconds"]
        assert h.count == 2
        assert m.counters["ingest_e2e_slo_burn_total"] == 1
        st = fd.stats()
        assert st["slo_burns"] == 1 and st["e2e_p99_ms"] > 50

    def test_per_shard_e2e_families_and_type_dedupe(self):
        """Sharded feeder: per-shard labeled e2e histogram families render
        with ONE TYPE line for the base metric (the satellite's labeled-
        histogram contract)."""
        from cilium_tpu.pipeline.scheduler import shard_bin_encode
        from cilium_tpu.shim.feeder import ShimFeeder

        class _StubShim:
            batch_size = 8

            def make_poll_buffer(self):
                from cilium_tpu.kernels.records import empty_batch
                b = empty_batch(8)
                b["_ep_raw"] = np.zeros(8, np.int64)
                return b

            def apply_verdicts(self, allow):
                pass

        class _StubTicket:
            def done(self):
                return True

            def result(self, timeout=None):
                return {"allow": np.ones(8, bool)}

        m = Metrics()
        fd = ShimFeeder(_StubShim(), engine=None, pool_batches=1,
                        n_shards=4, slo_ms=10.0, metrics=m)
        buf = fd._free[0]
        buf["_shard"][:] = shard_bin_encode(
            np.array([0, 0, 1, 1, 3, 3, 3, 3]), revision=1)
        # only the valid rows' bins attribute — the padding tail's
        # zeroed-row hash must not credit an idle shard
        buf["valid"][:6] = True              # shards 0, 1, 3 (3 via rows 4-5)
        fd._apply_one(_StubTicket(), buf,
                      ingest_mono=time.monotonic() - 0.1)
        assert 'ingest_e2e_latency_seconds{shard="0"}' in m.histograms
        assert 'ingest_e2e_latency_seconds{shard="3"}' in m.histograms
        assert 'ingest_e2e_latency_seconds{shard="2"}' not in m.histograms
        assert m.counters['ingest_e2e_slo_burn_total{shard="0"}'] == 1
        text = m.render_prometheus()
        type_lines = [ln for ln in text.splitlines()
                      if ln.startswith("# TYPE ciliumtpu_ingest_e2e"
                                       "_latency_seconds ")]
        assert len(type_lines) == 1          # one TYPE per base family
        assert ('ciliumtpu_ingest_e2e_latency_seconds_bucket'
                '{shard="3",le="+Inf"} 1') in text
        assert 'ciliumtpu_ingest_e2e_latency_seconds_sum{shard="3"}' in text
        # no malformed TYPE with labels anywhere
        assert not any("{" in ln for ln in text.splitlines()
                       if ln.startswith("# TYPE"))


# --------------------------------------------------------------------------- #
# satellites: metrics sentinel, feeder families, scrape races, trace ring
# --------------------------------------------------------------------------- #
class TestQuantileSentinel:
    def test_empty_window_returns_sentinel(self):
        h = Histogram()
        buckets, counts, _t, _c = h.snapshot()
        v = quantile_from(buckets, counts, 0.99)
        assert quantile_is_empty(v) and math.isnan(v)
        assert math.isnan(EMPTY_QUANTILE)

    def test_display_quantile_still_reads_zero_when_empty(self):
        assert Histogram().quantile(0.5) == 0.0

    def test_delta_window_with_counts_is_unchanged(self):
        h = Histogram()
        for v in (0.001, 0.002, 0.004):
            h.observe(v)
        b, c, _t, _n = h.snapshot()
        assert quantile_from(b, c, 0.5) > 0.0
        assert not quantile_is_empty(quantile_from(b, c, 0.5))

    def test_autotuner_skips_empty_window(self):
        """Dispatched batches but an empty queue-wait delta (histogram
        reset race): the autotuner must observe-and-skip, never compare
        against the NaN sentinel."""
        from cilium_tpu.observe.autotune import Autotuner

        class _StubPipeline:
            flush_ms = 2.0
            min_bucket = 256
            max_bucket = 8192

            def __init__(self):
                self.d = 0

            def stats(self):
                self.d += 10
                return {"fill_rows": 0, "bucket_rows": 0,
                        "dispatched_batches": self.d, "flush_reasons": {}}

            def set_flush_ms(self, v):
                raise AssertionError("must not adjust on empty window")

            def set_min_bucket(self, v):
                raise AssertionError("must not adjust on empty window")

        m = Metrics()
        m.histogram("pipeline_queue_wait_seconds")   # exists, stays empty
        at = Autotuner(_StubPipeline(), m)
        assert at.step() is None             # baseline
        # fill/bucket deltas present, queue-wait delta empty
        at.pipeline.stats = lambda: {"fill_rows": 100, "bucket_rows": 200,
                                     "dispatched_batches": 100,
                                     "flush_reasons": {}}
        at._last_fill = (0, 0)
        assert at.step() is None             # skipped, no crash, no adjust


class TestFeederMetricFamilies:
    def test_feeder_stats_exported_as_families(self):
        """render_metrics() must surface the stats-only feeder fields as
        first-class gauges (a scrape-only consumer sees liveness and pool
        occupancy without the status API)."""
        eng = setup_web(fake_engine())

        class _FakeFeeder:
            def stats(self):
                return {"alive": True, "pool_free": 3, "pending": 1,
                        "harvested_batches": 5}

        eng._feeder = _FakeFeeder()
        text = eng.render_metrics()
        assert "# TYPE ciliumtpu_feeder_alive gauge" in text
        assert "ciliumtpu_feeder_alive 1" in text
        assert "ciliumtpu_feeder_pool_free 3.0" in text \
            or "ciliumtpu_feeder_pool_free 3" in text
        assert "ciliumtpu_feeder_pending 1" in text
        eng._feeder = None
        eng.stop()


class TestScrapeRaces:
    def test_concurrent_scrape_races_sharded_soak(self):
        """A scraper hammering render_metrics() while an 8-shard pipeline
        soaks (including a mid-soak watchdog restart, whose wedged-sweep
        resets the shard gauges a fenced worker may still try to publish):
        no exceptions, every exposition parses, one TYPE line per base."""
        eng = sharded_audited_engine(pipeline_restart_backoff_s=0.05)
        setup_web(eng)
        chunks = mk_chunks(eng.active.snapshot.ep_slot_of, n_chunks=16,
                           rows_per_chunk=8)
        errors = []
        stop = threading.Event()

        def scraper():
            while not stop.is_set():
                try:
                    text = eng.render_metrics()
                    for ln in text.splitlines():
                        if ln.startswith("# TYPE"):
                            assert "{" not in ln, f"labeled TYPE: {ln}"
                except Exception as e:   # noqa: BLE001
                    errors.append(e)
                    return

        threads = [threading.Thread(target=scraper, daemon=True)
                   for _ in range(2)]
        for t in threads:
            t.start()
        try:
            pl = eng.start_pipeline()
            assert pl.stats()["n_shards"] == 8
            for round_ in range(6):
                tickets = [eng.submit(dict(ch), now=100 + i)
                           for i, ch in enumerate(chunks)]
                assert eng.drain(timeout=30)
                for t in tickets:
                    t.result(timeout=5)
                if round_ == 2:
                    # wedge → watchdog restart mid-soak (gauge publish vs
                    # fenced-worker reset is the race under test)
                    pl.set_stall_timeout_s(0.4)
                    FAULTS.arm("pipeline.dispatch", mode="hang",
                               delay_s=4.0, times=1)
                    eng.submit(dict(chunks[0]), now=500)
                    eng.drain(timeout=20)
                    FAULTS.disarm("pipeline.dispatch")
                    deadline = time.monotonic() + 10
                    while time.monotonic() < deadline and \
                            (eng.pipeline_stats() or {}).get("state") != "ok":
                        time.sleep(0.05)
                    pl.set_stall_timeout_s(30.0)
            eng.audit_step(budget=None)
            st = eng.auditor.stats()
            assert st["checked_rows"] > 0 and st["mismatched_rows"] == 0
        finally:
            stop.set()
            for t in threads:
                t.join(5)
            eng.stop()
        assert not errors, errors[:1]


class TestTraceRingWraparound:
    def test_trace_ring_wraps_with_audit_capture_armed(self):
        """Tiny span ring + full-rate tracing + full-rate audit capture:
        the ring wraps many times over while captures are in flight; spans
        stay well-formed, audit replay stays clean, and the bundle's span
        tail is the newest slice."""
        TRACER.configure(sample_rate=1.0, capacity=16)
        TRACER.reset()
        eng = setup_web(audited_engine(trace_sample_rate=1.0,
                                       trace_capacity=16))
        b = web_batch(eng)
        for i in range(40):
            eng.classify(dict(b), now=100 + i)
            if i % 8 == 0:
                eng.audit_step()
        eng.audit_step()
        st = eng.auditor.stats()
        assert st["mismatched_rows"] == 0 and st["checked_rows"] > 0
        tr = TRACER.stats()
        assert tr["spans_in_ring"] == 16     # wrapped, exactly full
        for sp in TRACER.spans(limit=100):
            assert sp["trace_id"] > 0 and sp["duration_ms"] >= 0
        bundle = eng.debug_bundle()
        assert len(bundle["spans"]) <= 16
        eng.stop()


# --------------------------------------------------------------------------- #
# export surfaces: REST route + CLI
# --------------------------------------------------------------------------- #
class TestDebugBundleSurfaces:
    @pytest.fixture
    def live(self, tmp_path):
        from cilium_tpu.runtime.api import APIServer, UnixAPIClient
        sock = str(tmp_path / "cilium-tpu.sock")
        eng = setup_web(audited_engine())
        srv = APIServer(eng, sock)
        srv.start()
        yield eng, sock, UnixAPIClient(sock)
        srv.stop()
        eng.stop()

    def test_rest_bundle_live_then_frozen_then_cleared(self, live):
        eng, _sock, client = live
        code, doc = client.get("/v1/debug/bundle")
        assert code == 200 and doc["frozen"] is False
        with FAULTS.inject("audit.corrupt", mode="fail", times=1):
            eng.classify(web_batch(eng), now=100)
        eng.audit_step()
        code, doc = client.get("/v1/debug/bundle?clear=1")
        assert code == 200 and doc["frozen"] is True
        assert doc["reason"] == "parity-mismatch"
        assert doc["engine"]["audit"]["mismatched_rows"] > 0
        assert doc["detail"]["rows"]
        code, doc = client.get("/v1/debug/bundle")   # cleared: re-armed
        assert code == 200 and doc["frozen"] is False
        # status carries the provenance counters; ?clear=1 re-armed the
        # auditor (mismatch state reset) but history persists
        code, st = client.get("/v1/status")
        assert code == 200
        assert st["audit"]["mismatched_rows"] == 0   # re-armed
        assert st["audit"]["checked_rows"] > 0
        assert st["blackbox"]["freezes_total"] >= 1

    def test_cli_debug_bundle_writes_file(self, live, tmp_path, capsys):
        eng, sock, _client = live
        with FAULTS.inject("audit.corrupt", mode="fail", times=1):
            eng.classify(web_batch(eng), now=100)
        eng.audit_step()
        out_path = tmp_path / "bundle.json"
        from cilium_tpu.cli.main import main as cli_main
        rc = cli_main(["debug-bundle", "--api", sock,
                       "--out", str(out_path), "--clear"])
        assert rc == 0
        doc = json.loads(out_path.read_text())
        assert doc["frozen"] and doc["reason"] == "parity-mismatch"
        assert "written to" in capsys.readouterr().out


# --------------------------------------------------------------------------- #
# slow: the audited soak (make chaos)
# --------------------------------------------------------------------------- #
@pytest.mark.slow
class TestAuditSoak:
    N_SUBMISSIONS = 10_000

    def test_soak_clean_then_corruption_detected(self):
        """10k pipelined submissions with the auditor armed at sampling
        1.0: zero mismatches and checked > 0 (the acceptance gate), then a
        corruption-injection phase via audit.corrupt that must be detected
        within the sampling window, degrade health, and freeze a bundle
        carrying the offending rows + revision."""
        eng = setup_web(audited_engine(
            pipeline_min_bucket=16, audit_pool_batches=64,
            audit_interval_s=0.05))
        eng.start_background()               # the real background controller
        try:
            chunks = mk_chunks(eng.active.snapshot.ep_slot_of, n_chunks=32,
                               rows_per_chunk=8, repeats=True)
            n = 0
            while n < self.N_SUBMISSIONS:
                tickets = [eng.submit(dict(ch), now=100 + n + i)
                           for i, ch in enumerate(chunks)]
                n += len(tickets)
                assert eng.drain(timeout=60)
                for t in tickets:
                    t.result(timeout=5)
            # let the controller drain the capture backlog
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline \
                    and eng.auditor.stats()["pending"] > 0:
                time.sleep(0.05)
            eng.audit_step()                 # sweep any tail
            st = eng.auditor.stats()
            assert st["checked_rows"] > 0, "auditor never checked anything"
            assert st["mismatched_rows"] == 0, list(eng.auditor.mismatches)
            assert eng.health()["state"] == C.HEALTH_OK

            # corruption-injection phase: every capture in this window is
            # corrupted; the very next sampled batch must trip
            FAULTS.arm("audit.corrupt", mode="fail", times=4)
            tickets = [eng.submit(dict(ch), now=50_000 + i)
                       for i, ch in enumerate(chunks)]
            assert eng.drain(timeout=60)
            for t in tickets:
                t.result(timeout=5)
            FAULTS.disarm("audit.corrupt")
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline \
                    and eng.auditor.stats()["mismatched_rows"] == 0:
                eng.audit_step()
                time.sleep(0.02)
            st = eng.auditor.stats()
            assert st["mismatched_rows"] > 0, \
                "corruption not detected within the sampling window"
            assert eng.health()["state"] == C.HEALTH_DEGRADED
            bundle = eng.debug_bundle()
            assert bundle["frozen"] \
                and bundle["reason"] == "parity-mismatch"
            assert bundle["detail"]["rows"]
            assert bundle["detail"]["revision"] == eng.active.revision
        finally:
            eng.stop()

    def test_sharded_soak_audits_clean(self):
        """The acceptance pin for the mesh: a clean 8-shard soak (steered
        staging, per-segment buckets, shard-attributed captures) shows
        parity_audit_mismatched_total == 0 with checked > 0."""
        eng = sharded_audited_engine(audit_pool_batches=64,
                                     audit_interval_s=0.05)
        setup_web(eng)
        eng.start_background()
        try:
            chunks = mk_chunks(eng.active.snapshot.ep_slot_of, n_chunks=32,
                               rows_per_chunk=8, repeats=True)
            n = 0
            while n < 2000:
                tickets = [eng.submit(dict(ch), now=100 + n + i)
                           for i, ch in enumerate(chunks)]
                n += len(tickets)
                assert eng.drain(timeout=60)
                for t in tickets:
                    t.result(timeout=5)
            while eng.audit_step()["replayed"]:
                pass
            st = eng.auditor.stats()
            assert st["checked_rows"] > 0, "sharded soak audited nothing"
            assert st["mismatched_rows"] == 0, list(eng.auditor.mismatches)
            assert not any("parity_audit_mismatched" in k
                           for k in eng.metrics.counters)
            assert eng.metrics.counters["parity_audit_checked_total"] > 0
        finally:
            eng.stop()

    def test_auditor_overhead_under_two_percent(self):
        """The <2% contract in the PR 3 trace-soak form: (1) the precise,
        deterministic measurement — ``maybe_capture`` per-batch cost at
        default 1/64 sampling (one counter draw + the row-copy amortized
        every 64th batch) vs disarmed, bounded under 2% of the measured
        per-submission pipeline cost; (2) an interleaved end-to-end soak
        as a loose gross-regression bound (wall-clock on a multi-threaded
        pipeline carries scheduler noise well above 2%)."""
        import gc
        eng = setup_web(audited_engine(audit_sample_rate=1 / 64,
                                       audit_pool_batches=4096,
                                       pipeline_min_bucket=16))
        snap = eng.active.snapshot
        b = web_batch(eng)
        out = eng.classify(dict(b), now=99)
        aud = eng.auditor
        chunks = mk_chunks(snap.ep_slot_of, n_chunks=16, rows_per_chunk=8)

        def one_pass(n_rounds=4):
            t0 = time.perf_counter()
            n = 0
            for _r in range(n_rounds):
                for i, ch in enumerate(chunks):
                    eng.submit(dict(ch), now=1000 + i)
                    n += 1
                assert eng.drain(timeout=60)
            return (time.perf_counter() - t0) / n

        reps = 20_000

        def micro_pass():
            t0 = time.perf_counter()
            for _ in range(reps):
                aud.maybe_capture(b, out, snap, 100)
            dt = (time.perf_counter() - t0) / reps
            aud.step()                   # drain (replay is background cost)
            return dt

        one_pass(2)                      # warmup both code paths
        gc_was = gc.isenabled()
        gc.disable()
        try:
            micro_pass()
            aud.configure(sample_rate=0.0)
            micro_off = min(micro_pass() for _ in range(5))
            aud.configure(sample_rate=1 / 64)
            micro_on = min(micro_pass() for _ in range(5))

            off, on = [], []
            for _i in range(4):          # interleaved A/B windows
                aud.configure(sample_rate=0.0)
                off.append(one_pass())
                aud.configure(sample_rate=1 / 64)
                on.append(one_pass())
                aud.step()
        finally:
            if gc_was:
                gc.enable()
        per_submit = min(off)            # best-case per-submission cost
        delta = micro_on - micro_off     # true hot-path addition per batch
        frac = delta / per_submit
        assert frac < 0.02, \
            f"1/64 audit capture adds {delta * 1e9:.0f}ns/batch = " \
            f"{frac:.2%} of the {per_submit * 1e6:.1f}us submit path " \
            f"(budget 2%)"
        assert min(on) <= min(off) * 1.15, \
            f"end-to-end regression: off={min(off) * 1e6:.1f}us " \
            f"on={min(on) * 1e6:.1f}us"
        assert aud.stats()["mismatched_rows"] == 0
        eng.stop()
