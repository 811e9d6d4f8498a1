"""Observability subsystem tests (cilium_tpu/observe/).

Unit tests cover the tracer's deterministic counter sampling + span ring,
the vectorized flow-metrics windows, and the autotuner's hysteresis /
convergence / no-oscillation contract against a stub pipeline. Integration
tests run tracing through the real Pipeline + Engine (spans appear per
stage; verdicts stay bit-identical to the serial path with sampling at
1.0 — the acceptance gate), exercise the REST routes, and pin the
``Engine._dirty`` Event semantics (a mark set mid-compile survives the
regeneration). The ``slow``-marked soak (`make chaos`) asserts
the 1/64-sampled pipeline costs <2% over tracing disabled.
"""

import os
import threading
import time

import numpy as np
import pytest

from cilium_tpu.observe.autotune import Autotuner
from cilium_tpu.observe.flowmetrics import FlowMetrics
from cilium_tpu.observe.trace import TRACER, Tracer
from cilium_tpu.runtime.metrics import Metrics, quantile_from
from tests.test_pipeline import (EchoDispatch, POLICY, _assert_parity,
                                 fake_engine, mk_chunks, pkt, sub_batch)
from tests.test_feeder import (big_shim, frames_of, manual_feeder,
                               step_until)
from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.pipeline import Pipeline
from cilium_tpu.shim.bindings import LIB_PATH


@pytest.fixture(autouse=True)
def _quiet_tracer():
    """Engines configure the process-wide TRACER from their DaemonConfig;
    leave it disabled and empty for the next test."""
    yield
    TRACER.configure(sample_rate=0.0)
    TRACER.reset()


class TestTracer:
    def test_disabled_costs_nothing_and_records_nothing(self):
        t = Tracer(sample_rate=0.0, capacity=8)
        assert not t.enabled
        assert t.maybe_sample() is None and t.force_sample() is None
        with t.span(None, "x"):
            pass
        t.record(None, "x", 0.0, 1.0)
        assert t.spans() == [] and t.summary() == {}
        assert t.event("decision") is None

    def test_counter_sampling_is_deterministic(self):
        t = Tracer(sample_rate=0.25, capacity=64)
        decisions = [t.maybe_sample() is not None for _ in range(12)]
        assert decisions == [True, False, False, False] * 3
        assert t.sampled_total == 3

    def test_rate_one_samples_everything(self):
        t = Tracer(sample_rate=1.0, capacity=64)
        assert all(t.maybe_sample() is not None for _ in range(10))

    def test_ring_keeps_newest(self):
        t = Tracer(sample_rate=1.0, capacity=4)
        for i in range(10):
            t.record(i + 1, f"s{i}", 0.0, 0.001 * i)
        names = [s["name"] for s in t.spans(limit=100)]
        assert names == ["s6", "s7", "s8", "s9"]

    def test_span_context_manager_and_summary(self):
        t = Tracer(sample_rate=1.0, capacity=64)
        tid = t.maybe_sample()
        for _ in range(5):
            with t.span(tid, "stage.a"):
                pass
        t.record(tid, "stage.b", 0.0, 0.010)
        s = t.summary()
        assert s["stage.a"]["count"] == 5
        assert s["stage.b"]["p50_ms"] == pytest.approx(10.0, rel=0.01)
        assert s["stage.a"]["p99_ms"] >= s["stage.a"]["p50_ms"]

    def test_trace_context_is_thread_local(self):
        t = Tracer(sample_rate=1.0, capacity=16)
        seen = {}
        with t.context(42):
            assert t.current() == 42

            def peek():
                seen["other"] = t.current()
            th = threading.Thread(target=peek)
            th.start()
            th.join()
            with t.context(7):
                assert t.current() == 7
            assert t.current() == 42
        assert t.current() is None and seen["other"] is None

    def test_context_propagates_across_tracer_instances(self):
        """The cross-layer seam: the datapath attaches spans via active(),
        so a Pipeline constructed with an injected tracer still gets its
        pack/transfer/compute spans recorded on THAT tracer."""
        from cilium_tpu.observe.trace import TRACER as global_tracer, active
        t1 = Tracer(sample_rate=1.0, capacity=8)
        t2 = Tracer(sample_rate=1.0, capacity=8)
        with t1.context(5):
            tr, tid = active()
            assert tr is t1 and tid == 5
            assert t2.current() == 5     # any instance reads the context
        tr, tid = active()
        assert tr is global_tracer and tid is None

    def test_event_records_with_attrs(self):
        t = Tracer(sample_rate=1 / 64, capacity=16)
        t.event("autotune.decision", knob="flush_ms", old=2.0, new=1.0)
        spans = t.spans(name="autotune.decision")
        assert len(spans) == 1
        assert spans[0]["attrs"]["knob"] == "flush_ms"

    def test_stats_shape(self):
        t = Tracer(sample_rate=0.5, capacity=8)
        tid = t.maybe_sample()
        t.record(tid, "x", 0.0, 0.001)
        st = t.stats()
        assert st["enabled"] and st["capacity"] == 8
        assert st["spans_in_ring"] == 1 and st["sample_rate"] == 0.5

    def test_forced_events_do_not_skew_sampled_total(self):
        """Coverage math (sampled_total x 1/rate ~= submissions) must not
        be inflated by always-traced regen/autotune events."""
        t = Tracer(sample_rate=0.25, capacity=16)
        for _ in range(8):
            t.maybe_sample()
        t.force_sample()
        t.event("autotune.decision", knob="flush_ms")
        st = t.stats()
        assert st["sampled_total"] == 2      # 8 events at 1/4
        assert st["forced_total"] == 2       # forced + event, separately

    def test_reconfigure_same_capacity_preserves_ring(self):
        """Constructing a second Engine (which re-states the tracer config)
        must not wipe spans another engine already recorded."""
        t = Tracer(sample_rate=1.0, capacity=8)
        t.record(t.maybe_sample(), "x", 0.0, 0.001)
        t.configure(sample_rate=1.0, capacity=8)
        assert len(t.spans()) == 1           # same capacity: ring kept
        t.configure(capacity=4)
        assert t.spans() == []               # real change: reallocated

    def test_engine_with_tracing_off_leaves_global_tracer_alone(self):
        TRACER.configure(sample_rate=1.0, capacity=32)
        tid = TRACER.maybe_sample()
        TRACER.record(tid, "pre.existing", 0.0, 0.001)
        eng = fake_engine()                  # trace_sample_rate default 0
        assert TRACER.enabled               # not silently disabled
        assert any(s["name"] == "pre.existing" for s in TRACER.spans())
        eng.stop()


class TestPipelineTracing:
    def test_stage_spans_recorded_at_rate_one(self):
        d = EchoDispatch()
        tr = Tracer(sample_rate=1.0, capacity=256)
        pl = Pipeline(d, min_bucket=4, max_bucket=16, flush_ms=1.0,
                      tracer=tr)
        try:
            t = pl.submit(sub_batch(16, start=100))    # direct path
            t.result(timeout=5)        # resolve before any rows stage
            for i in range(6):
                pl.submit(sub_batch(3, start=i * 4))   # coalesced path
            assert pl.drain(timeout=10)
            s = tr.summary()
            assert s["pipeline.admission"]["count"] == 7
            assert s["pipeline.microbatch"]["count"] == 6   # direct skips it
            assert s["pipeline.dispatch"]["count"] >= 2
            assert s["pipeline.finalize"]["count"] \
                == s["pipeline.dispatch"]["count"]
        finally:
            pl.close(timeout=5)

    def test_unsampled_pipeline_records_nothing(self):
        d = EchoDispatch()
        tr = Tracer(sample_rate=0.0, capacity=64)
        pl = Pipeline(d, min_bucket=4, max_bucket=16, flush_ms=1.0,
                      tracer=tr)
        try:
            for i in range(5):
                pl.submit(sub_batch(4, start=i * 4))
            assert pl.drain(timeout=10)
            assert tr.spans() == []
        finally:
            pl.close(timeout=5)

    def test_runtime_knob_setters_validate(self):
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=16, flush_ms=2.0)
        try:
            pl.set_flush_ms(7.5)
            assert pl.flush_ms == pytest.approx(7.5)
            pl.set_min_bucket(8)
            assert pl.min_bucket == 8
            assert pl.stats()["min_bucket"] == 8
            assert pl.stats()["flush_ms"] == pytest.approx(7.5)
            with pytest.raises(ValueError):
                pl.set_min_bucket(12)          # not a power of two
            with pytest.raises(ValueError):
                pl.set_min_bucket(32)          # > max_bucket
            with pytest.raises(ValueError):
                pl.set_flush_ms(0)
            # changed floor takes effect: an 8-row submission now rides the
            # zero-copy direct path
            t = pl.submit(sub_batch(8, start=0))
            t.result(timeout=5)
            assert pl.flush_reasons["direct"] >= 1
        finally:
            pl.close(timeout=5)

    def test_engine_parity_bit_identical_with_tracing_at_one(self):
        """The acceptance gate: full-rate tracing must not perturb a single
        verdict, counter, or CT entry vs the serial path."""
        engines = []
        for _ in range(2):
            eng = fake_engine(trace_sample_rate=1.0,
                              pipeline_min_bucket=16,
                              pipeline_flush_ms=1.0)
            eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",),
                             ep_id=1)
            eng.apply_policy(POLICY)
            engines.append(eng)
        ser, pipe = engines
        # unique flows per row: the regime where coalescing is a legal
        # scheduling choice (same contract test_pipeline pins untraced)
        chunks = mk_chunks(ser.active.snapshot.ep_slot_of, n_chunks=18,
                           rows_per_chunk=5)
        _assert_parity(ser, pipe, chunks)
        # and the pipeline stages actually traced
        names = set(TRACER.summary())
        assert {"pipeline.admission", "pipeline.dispatch",
                "pipeline.finalize", "engine.classify"} <= names
        pipe.stop()
        ser.stop()


# --------------------------------------------------------------------------- #
# One harvest, one trace: spans carry thread, parent, CPU and kind (PR 39)
# --------------------------------------------------------------------------- #
def _spin(seconds):
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        pass


class TestSpanFields:
    def test_two_draws_a_harvest_starve_the_second_and_one_does_not(self):
        """At 1/64 a harvest that draws for itself and again for its
        submission, both from the one counter, samples the harvest 313
        times in 10,000 and the submission never (every even divisor does
        it). One draw a harvest, handed on: both halves, every time."""
        t = Tracer(sample_rate=1 / 64, capacity=8)
        harvest = submission = 0
        for _ in range(10_000):
            harvest += t.maybe_sample() is not None
            submission += t.maybe_sample() is not None
        assert (harvest, submission) == (313, 0)

        tr = Tracer(sample_rate=1 / 64, capacity=4096)
        pl = Pipeline(EchoDispatch(), min_bucket=4, max_bucket=16,
                      flush_ms=1.0, queue_batches=64, tracer=tr)
        try:
            sampled = 0
            for i in range(1_000):
                tid = tr.maybe_sample()              # the feeder's draw
                ticket = pl.submit(sub_batch(16, start=i), trace_id=tid)
                assert ticket.trace_id == tid        # None stays None
                sampled += tid is not None
                if i % 32 == 31:
                    assert pl.drain(timeout=10)
            assert pl.drain(timeout=10)
            assert sampled == 16 == tr.sampled_total     # 1,000 / 64, up
            s = tr.summary()
            assert s["pipeline.admission"]["count"] == 16
            assert s["pipeline.dispatch"]["count"] == 16
            assert s["pipeline.settle"]["count"] == 16
            # a caller that drew nothing leaves the draw to the pipeline
            assert pl.submit(sub_batch(16, start=0)).trace_id is None
            assert tr.sampled_total == 16
            for _ in range(64 * 16 - 1_001):         # up to draw 1,024
                tr.maybe_sample()
            assert pl.submit(sub_batch(16, start=0)).trace_id is not None
            assert pl.drain(timeout=10)
        finally:
            pl.close(timeout=5)

    def test_parent_and_thread_of_nested_spans_on_two_threads_at_once(self):
        t = Tracer(sample_rate=1.0, capacity=64)
        inside = threading.Barrier(2, timeout=10)

        def work(outer, inner):
            tid = t.maybe_sample()
            with t.span(tid, outer):
                with t.span(tid, inner, kind="wait"):
                    inside.wait()        # both inner spans open at once
                    inside.wait()
                # recorded after the fact: its parent is the span open now
                t.record(tid, inner + ".late", time.monotonic(), 0.0)

        threads = [threading.Thread(target=work, args=a, name=n)
                   for n, a in (("feeder-x", ("shim.a", "shim.a.b")),
                                ("worker-x", ("pipeline.a", "pipeline.a.b")))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(10)
            assert not th.is_alive()
        got = {s["name"]: s for s in t.spans()}
        for thread, outer in (("feeder-x", "shim.a"),
                              ("worker-x", "pipeline.a")):
            assert got[outer]["thread"] == thread
            assert "parent" not in got[outer]
            assert got[outer]["kind"] == "work"
            assert got[outer + ".b"]["parent"] == outer
            assert got[outer + ".b"]["thread"] == thread
            assert got[outer + ".b"]["kind"] == "wait"
            assert got[outer + ".b.late"]["parent"] == outer
        assert t.summary()["shim.a"]["thread"] == "feeder-x"
        # the stacks are empty again: the next span has no parent
        with t.span(t.maybe_sample(), "after"):
            pass
        assert "parent" not in t.spans(name="after")[0]

    def test_cpu_time_is_the_threads_own_clock_read_from_another(self):
        """``thread_cpu_s``: what a thread burnt, read from the caller's
        thread: a spin burns its wall time, a sleep nothing; a thread that
        has gone, or none, reads None."""
        from cilium_tpu.observe.trace import thread_cpu_s
        go, done = threading.Event(), threading.Event()

        def work(fn):
            go.wait(10)
            fn(0.2)
            done.set()
            go.clear()
            go.wait(10)                  # stay alive to be read

        for fn, lo, hi in ((_spin, 0.1, 0.25), (time.sleep, 0.0, 0.02)):
            done.clear()
            th = threading.Thread(target=work, args=(fn,))
            th.start()
            c0 = thread_cpu_s(th)
            go.set()
            assert done.wait(10)
            burnt = thread_cpu_s(th) - c0
            go.set()
            th.join(10)
            assert not th.is_alive()
            assert lo <= burnt <= hi, (fn, burnt)
            assert thread_cpu_s(th) is None          # it has gone
        assert thread_cpu_s(None) is None
        assert thread_cpu_s(threading.Thread(target=_spin)) is None
        me = thread_cpu_s(threading.current_thread())
        assert me == pytest.approx(time.thread_time(), abs=0.05)

    def test_totals_outlive_the_ring(self):
        """The ring wraps three times; the totals hold every span."""
        t = Tracer(sample_rate=1.0, capacity=8)
        want = {}
        for i in range(8 * 4 + 3):
            name = f"s{i % 3}"
            dur = 0.001 * (i + 1)
            t.record(i + 1, name, float(i), dur)
            w = want.setdefault(name, [0, 0.0])
            w[0] += 1
            w[1] += dur
        assert t.ring_wraps == 3 and len(t.spans(limit=100)) == 8
        got = t.totals()
        assert set(got) == set(want)
        for name, (n, wall) in want.items():
            assert got[name] == [n, pytest.approx(wall)]
        assert t.summary()["s0"]["since_start"] == {
            "count": want["s0"][0],
            "total_ms": pytest.approx(want["s0"][1] * 1e3, abs=1e-3)}
        assert t.summary()["s0"]["count"] < want["s0"][0]     # the ring's
        got["s0"][0] = -1                       # a copy, not the tracer's
        assert t.totals()["s0"][0] == want["s0"][0]
        t.reset()
        assert t.totals() == {} and t.summary() == {}


HARVEST_SPANS = {
    "shim.harvest", "feeder.map", "feeder.submit", "pipeline.admission",
    "pipeline.dispatch", "datapath.pack", "pipeline.finalize",
    "datapath.unpack", "engine.account", "pipeline.settle", "feeder.apply",
    "feeder.roundtrip"}


@pytest.mark.skipif(not os.path.exists(LIB_PATH),
                    reason="libflowshim.so not built")
class TestHarvestTrace:
    def test_a_sampled_harvest_holds_its_workers_spans_at_1_64(
            self, monkeypatch):
        """A feeder over the mock rings, the pipeline and the jitted
        datapath at 1/64: one draw a harvest; every sampled harvest's
        trace id holds the spans of both threads, an unsampled one none."""
        from cilium_tpu.runtime.config import DaemonConfig
        from cilium_tpu.runtime.datapath import JITDatapath
        from cilium_tpu.runtime.engine import Engine
        from tests.test_feeder import POLICY as EGRESS
        cfg = DaemonConfig(ct_capacity=4096, auto_regen=False, device="cpu",
                           batch_size=64, pipeline_min_bucket=16,
                           pipeline_flush_ms=1.0, flowlog_mode="none",
                           trace_sample_rate=1 / 64, trace_capacity=4096)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(EGRESS)
        eng.regenerate()
        assert eng.tracer is TRACER
        TRACER.reset()
        draws = []
        drawn = TRACER.maybe_sample
        monkeypatch.setattr(
            TRACER, "maybe_sample",
            lambda: draws.append(drawn()) or draws[-1])
        shim = big_shim()
        fd = manual_feeder(shim, eng, tracer=TRACER)
        n = 130
        try:
            for k in range(n):
                for f in frames_of(10, first=10 * k):
                    assert shim.mock_rx_inject(f) == 0
                # applies harvest k-1 (it is done), then takes harvest k
                fd._step(force=True)
                assert fd.harvested_batches == k + 1
                fd._pending[-1][0].result(timeout=120)
                shim.mock_tx_drain(64)
            step_until(fd, lambda: not fd._pending, force=True)
            st_feeder, st_pipeline = fd.stats(), eng.pipeline_stats()
            assert st_feeder["span_totals"]["shim.harvest"][0] >= 3
            assert st_pipeline["span_totals"] == st_feeder["span_totals"]
        finally:
            eng.stop()
            shim.close()
        assert fd.applied_batches == n and fd.rejected_batches == 0
        # one draw a harvest (and the last, empty-handed step's)
        assert len(draws) == n + 1
        sampled = [d for d in draws[:n] if d is not None]
        assert len(sampled) == 3                     # harvests 0, 64, 128
        by_trace = {}
        for s in TRACER.spans(limit=4096):
            by_trace.setdefault(s["trace_id"], set()).add(s["name"])
        for tid in sampled:
            assert HARVEST_SPANS <= by_trace[tid], \
                HARVEST_SPANS - by_trace[tid]
        # nothing of an unsampled harvest: the only other trace is the
        # regeneration's, which is always traced
        for tid, names in by_trace.items():
            if tid not in draws:
                assert all(nm.startswith("engine.regen") for nm in names), \
                    names
        threads = {s["name"]: s["thread"] for s in TRACER.spans(limit=4096)}
        assert threads["pipeline.settle"].endswith("-worker")
        assert threads["engine.account"] == threads["datapath.unpack"] \
            == threads["pipeline.settle"]
        assert threads["feeder.apply"] == threads["shim.harvest"] \
            != threads["pipeline.settle"]
        kinds = {s["name"]: s["kind"] for s in TRACER.spans(limit=4096)}
        assert {n for n, k in kinds.items() if k == "wait"} == {
            "pipeline.admission", "datapath.compute", "feeder.roundtrip"}
        parents = {s["name"]: s.get("parent")
                   for s in TRACER.spans(limit=4096)}
        assert parents["engine.account"] == "pipeline.finalize"
        assert parents["engine.account.observe"] == "engine.account"
        assert parents["datapath.unpack"] == "pipeline.finalize"
        assert parents["feeder.apply.note"] == "feeder.apply"
        assert parents["pipeline.settle"] is None
        assert parents["feeder.roundtrip"] is None
        # each thread's CPU clock, read from here
        assert st_feeder["thread_cpu_s"] is None        # not started
        assert st_pipeline["thread_cpu_s"] > 0

    def test_a_held_back_harvest_records_one_wait(self):
        """The feeder's own thread at 1.0: a harvest held back for the
        worker is one ``feeder.wait`` span however often it slept, a wait
        on the feeder's thread, under the trace of the harvest it waited
        for."""
        from tests.test_feeder import (fake_engine as feeder_engine,
                                       inject_all, wait_verdicts)
        eng = feeder_engine(pipeline_min_bucket=16, trace_sample_rate=1.0,
                            trace_capacity=1 << 15)
        TRACER.reset()
        shim = big_shim()
        feeder = eng.start_feeder(shim)
        try:
            inject_all(shim, frames_of(600), deadline_s=60.0)
            wait_verdicts(shim, 600, deadline_s=60.0)
        finally:
            st = feeder.stats()
            eng.stop()
            shim.close()
        assert st["thread_cpu_s"] > 0          # the feeder's own clock
        waits = TRACER.spans(limit=1 << 15, name="feeder.wait")
        harvests = {s["trace_id"] for s in TRACER.spans(
            limit=1 << 15, name="shim.harvest") if s["attrs"]["rows"]}
        assert waits and len(waits) <= st["harvested_batches"]
        assert st["span_totals"]["feeder.wait"][0] == len(waits)
        for s in waits:
            assert s["kind"] == "wait" and "parent" not in s
            assert s["attrs"]["for"] in ("dispatch", "verdicts")
            assert s["thread"].endswith("-harvest")
            assert s["trace_id"] in harvests
        assert len({s["trace_id"] for s in waits}) == len(waits)

    def test_tracing_off_reads_no_cpu_clock_and_allocates_nothing(
            self, monkeypatch):
        """With the rate 0 the feeder's step, the pipeline and the
        engine's finalize make no ``time.thread_time`` call and leave no
        allocation of ``observe/trace.py``'s behind, over 1,000 steps."""
        import gc
        import tracemalloc
        from cilium_tpu.observe import trace as trace_mod
        from tests.test_feeder import fake_engine as feeder_engine
        assert not TRACER.enabled
        calls = []

        def no_cpu_clock():
            calls.append(1)
            raise AssertionError("time.thread_time read with tracing off")
        eng = feeder_engine(pipeline_min_bucket=16)
        shim = big_shim()
        fd = manual_feeder(shim, eng, tracer=TRACER)

        def run(steps, first):
            for k in range(steps):
                for f in frames_of(4, first=(first + k) % 1000):
                    assert shim.mock_rx_inject(f) == 0
                fd._step(force=True)
                fd._pending[-1][0].result(timeout=30)
                shim.mock_tx_drain(64)
            step_until(fd, lambda: not fd._pending, force=True)

        try:
            run(50, 0)                                # warm every path
            monkeypatch.setattr(time, "thread_time", no_cpu_clock)
            gc.collect()
            tracemalloc.start()
            snap1 = tracemalloc.take_snapshot()
            run(1000, 50)
            gc.collect()
            snap2 = tracemalloc.take_snapshot()
            tracemalloc.stop()
        finally:
            monkeypatch.undo()
            eng.stop()
            shim.close()
        assert fd.applied_batches >= 1050 and not calls
        flt = [tracemalloc.Filter(True, trace_mod.__file__)]
        diff = snap2.filter_traces(flt).compare_to(
            snap1.filter_traces(flt), "lineno")
        assert sum(d.size_diff for d in diff) == 0, diff[:5]
        assert TRACER.spans() == [] and TRACER.totals() == {}
        assert fd.stats()["span_totals"] is None


class TestFlowMetrics:
    @staticmethod
    def _batch_out():
        n = 8
        batch = {
            "valid": np.array([1, 1, 1, 1, 1, 1, 0, 0], bool),
            "proto": np.array([6, 6, 17, 6, 6, 1, 6, 6], np.int32),
            "dport": np.array([443, 443, 53, 80, 443, 0, 9, 9], np.int32),
        }
        out = {
            "allow": np.array([1, 1, 1, 0, 0, 1, 1, 1], bool),
            "reason": np.zeros(n, np.int32),
            "remote_identity": np.array([5, 5, 7, 5, 9, 7, 1, 1], np.int32),
        }
        out["reason"][3] = 133       # POLICY_DENIED-ish bin
        out["reason"][4] = 133
        return batch, out

    def test_vectorized_counts(self):
        fm = FlowMetrics(window_s=10, n_windows=4, top_k=3)
        batch, out = self._batch_out()
        fm.add_batch(batch, out, now=105)
        [w] = fm.series()
        assert w["window_start"] == 100
        assert w["forwarded"] == 4 and w["dropped"] == 2
        assert sum(w["drop_reasons"].values()) == 2
        assert w["protos"] == {"TCP": 4, "UDP": 1, "ICMP": 1}
        assert w["top_ports"][0] == {"port": 443, "count": 3}
        assert {d["identity"]: d["count"] for d in w["top_identities"]} \
            == {5: 3, 7: 2, 9: 1}
        # invalid rows (ports 9, identity 1) never counted
        assert all(p["port"] != 9 for p in w["top_ports"])

    def test_windows_advance_and_cap(self):
        fm = FlowMetrics(window_s=10, n_windows=3, top_k=3)
        batch, out = self._batch_out()
        for now in (5, 15, 25, 35, 45):
            fm.add_batch(batch, out, now=now)
        starts = [w["window_start"] for w in fm.series()]
        assert starts == [20, 30, 40]       # oldest windows aged out
        t = fm.totals()
        assert t["forwarded"] == 4 * 5 and t["batches"] == 5

    def test_same_window_accumulates(self):
        fm = FlowMetrics(window_s=10, n_windows=3)
        batch, out = self._batch_out()
        fm.add_batch(batch, out, now=100)
        fm.add_batch(batch, out, now=109)
        [w] = fm.series()
        assert w["forwarded"] == 8 and w["dropped"] == 4

    def test_axis_cardinality_bounded(self):
        from cilium_tpu.observe import flowmetrics as fmod
        fm = FlowMetrics(window_s=10, n_windows=2, top_k=5)
        n = fmod.AXIS_CAP + 50
        batch = {
            "valid": np.ones(n, bool),
            "proto": np.full(n, 6, np.int32),
            "dport": np.arange(n, dtype=np.int32),     # a port scan
        }
        out = {
            "allow": np.ones(n, bool),
            "reason": np.zeros(n, np.int32),
            "remote_identity": np.zeros(n, np.int32),
        }
        fm.add_batch(batch, out, now=10)
        with fm._lock:
            assert len(fm._totals.ports) <= fmod.AXIS_CAP
            total_port_counts = (sum(fm._totals.ports.values())
                                 + fm._totals.ports_other)
        assert total_port_counts == n       # nothing lost, only collapsed
        # the collapsed remainder exports as the monotone "other" series
        assert 'ciliumtpu_flow_port_total{port="other"}' \
            in fm.render_prometheus()

    def test_totals_series_stay_monotone_under_churn(self):
        """The Prometheus counter contract: once a port/identity series is
        exported from totals it never decreases and never vanishes, no
        matter how the traffic mix churns past AXIS_CAP distinct keys."""
        from cilium_tpu.observe import flowmetrics as fmod

        def parse(text):
            return {line.rpartition(" ")[0]: int(line.rpartition(" ")[2])
                    for line in text.splitlines()
                    if line.startswith("ciliumtpu_flow_port_total")}

        fm = FlowMetrics(window_s=10, n_windows=2, top_k=5)
        rng = np.random.default_rng(3)
        prev = {}
        for round_i in range(6):
            n = fmod.AXIS_CAP
            batch = {
                "valid": np.ones(n, bool),
                "proto": np.full(n, 6, np.int32),
                # shifting port population: later rounds bring new keys
                "dport": (rng.integers(0, 2 * fmod.AXIS_CAP, n)
                          + round_i * 37).astype(np.int32),
            }
            out = {"allow": np.ones(n, bool),
                   "reason": np.zeros(n, np.int32),
                   "remote_identity": np.zeros(n, np.int32)}
            fm.add_batch(batch, out, now=round_i * 10)
            cur = parse(fm.render_prometheus())
            for series, value in prev.items():
                assert series in cur, f"series vanished: {series}"
                assert cur[series] >= value, f"decreased: {series}"
            prev = cur

    def test_prometheus_render(self):
        fm = FlowMetrics(window_s=10, n_windows=2, top_k=2)
        batch, out = self._batch_out()
        fm.add_batch(batch, out, now=7)
        text = fm.render_prometheus()
        assert 'ciliumtpu_flow_verdicts_total{verdict="FORWARDED"} 4' in text
        assert 'ciliumtpu_flow_verdicts_total{verdict="DROPPED"} 2' in text
        assert 'ciliumtpu_flow_proto_total{proto="TCP"} 4' in text
        assert 'ciliumtpu_flow_port_total{port="443"} 3' in text
        # every retained entry exports (the axes are capped, not top-k'd,
        # so the series stay monotone between scrapes); nothing was pruned
        # here → no "other" series
        for ident, n in ((5, 3), (7, 2), (9, 1)):
            assert (f'ciliumtpu_flow_identity_total{{identity="{ident}"}} '
                    f"{n}") in text
        assert 'identity="other"' not in text


class _StubPipeline:
    """Duck-typed pipeline for autotuner unit tests: the test scripts the
    interval deltas (dispatches, fill, flush reasons) and the queue-wait
    observations go straight into the shared metrics histogram."""

    def __init__(self, metrics, flush_ms=2.0, min_bucket=256,
                 max_bucket=8192):
        self.metrics = metrics
        self._flush_ms = flush_ms
        self._min_bucket = min_bucket
        self._max_bucket = max_bucket
        self.dispatched = 0
        self.fill_rows = 0
        self.bucket_rows = 0
        self.reasons = {"direct": 0, "full": 0, "deadline": 0, "drain": 0}

    # the Autotuner consumer surface
    flush_ms = property(lambda self: self._flush_ms)
    min_bucket = property(lambda self: self._min_bucket)
    max_bucket = property(lambda self: self._max_bucket)

    def set_flush_ms(self, v):
        self._flush_ms = v

    def set_min_bucket(self, v):
        self._min_bucket = v

    def stats(self):
        return {"dispatched_batches": self.dispatched,
                "fill_rows": self.fill_rows,
                "bucket_rows": self.bucket_rows,
                "flush_reasons": dict(self.reasons)}

    def interval(self, batches=10, fill=0.9, wait_ms=1.0,
                 reason="full"):
        """Simulate one interval of pipeline activity."""
        h = self.metrics.histogram("pipeline_queue_wait_seconds")
        for _ in range(batches):
            h.observe(wait_ms / 1e3)
        self.dispatched += batches
        self.bucket_rows += batches * 1024
        self.fill_rows += int(batches * 1024 * fill)
        self.reasons[reason] += batches


def mk_autotuner(pl, m, **kw):
    kw.setdefault("flush_ms_min", 0.5)
    kw.setdefault("flush_ms_max", 16.0)
    kw.setdefault("min_bucket_floor", 64)
    kw.setdefault("queue_wait_p99_budget_ms", 5.0)
    kw.setdefault("hysteresis", 3)
    kw.setdefault("step_factor", 2.0)
    return Autotuner(pl, m, tracer=Tracer(sample_rate=1.0, capacity=64),
                     **kw)


class TestAutotuner:
    def test_needs_hysteresis_before_acting(self):
        m = Metrics()
        pl = _StubPipeline(m)
        at = mk_autotuner(pl, m)
        pl.interval(wait_ms=50.0)           # way over budget
        assert at.step() is None            # baseline interval
        for _ in range(2):                  # 2 more: still under hysteresis=3
            pl.interval(wait_ms=50.0)
            at.step()
        assert pl.flush_ms == 2.0
        pl.interval(wait_ms=50.0)           # 3rd consecutive over-budget
        obs = at.step()
        assert pl.flush_ms == 1.0           # one capped step down
        assert obs["adjusted"][0]["knob"] == "flush_ms"

    def test_converges_down_under_sustained_burst_and_respects_floor(self):
        m = Metrics()
        pl = _StubPipeline(m, flush_ms=8.0)
        at = mk_autotuner(pl, m)
        history = []
        for _ in range(30):
            pl.interval(wait_ms=40.0, fill=0.9)
            at.step()
            history.append(pl.flush_ms)
        assert pl.flush_ms == 0.5           # clamped at flush_ms_min
        # monotone non-increasing path down — no overshoot/oscillation
        assert all(b <= a for a, b in zip(history, history[1:]))

    def test_raises_flush_when_underfilled_and_fast(self):
        m = Metrics()
        pl = _StubPipeline(m, flush_ms=1.0)
        at = mk_autotuner(pl, m)
        for _ in range(8):
            pl.interval(wait_ms=0.5, fill=0.2, reason="deadline")
            at.step()
        assert pl.flush_ms > 1.0

    def test_dead_band_is_stable(self):
        """In-budget wait + on-target fill → zero adjustments, ever."""
        m = Metrics()
        pl = _StubPipeline(m)
        at = mk_autotuner(pl, m)
        for _ in range(12):
            pl.interval(wait_ms=1.0, fill=0.8)
            at.step()
        assert pl.flush_ms == 2.0 and not at.adjustments

    def test_alternating_load_never_oscillates(self):
        """The hysteresis contract: direction flips every interval, so the
        streak never reaches 3 and no knob ever moves."""
        m = Metrics()
        pl = _StubPipeline(m)
        at = mk_autotuner(pl, m)
        for i in range(20):
            if i % 2:
                pl.interval(wait_ms=50.0, fill=0.9)       # wants down
            else:
                pl.interval(wait_ms=0.5, fill=0.2)        # wants up
            at.step()
        assert not at.adjustments and pl.flush_ms == 2.0

    def test_bucket_floor_down_on_deadline_dominated_low_fill(self):
        m = Metrics()
        pl = _StubPipeline(m, min_bucket=1024)
        at = mk_autotuner(pl, m)
        for _ in range(8):
            pl.interval(wait_ms=1.0, fill=0.3, reason="deadline")
            at.step()
        assert pl.min_bucket < 1024
        assert pl.min_bucket >= 64          # the configured floor holds

    def test_bucket_floor_shrink_clamps_lane_bucket(self):
        """The "lane_bucket never exceeds min_bucket" invariant is
        enforced the moment the bulk arm shrinks min_bucket — the lane
        arm's own (hysteresis-gated) shrink path may take many intervals
        to fire, or never, and the lane would dispatch above the bulk
        floor meanwhile."""
        m = Metrics()
        pl = _StubPipeline(m, min_bucket=1024)
        pl.lane_bucket = 1024                # at the ceiling
        pl.set_lane_bucket = lambda v: setattr(pl, "lane_bucket", v)
        at = mk_autotuner(pl, m)
        for _ in range(8):
            pl.interval(wait_ms=1.0, fill=0.3, reason="deadline")
            at.step()
        assert pl.min_bucket < 1024
        assert pl.lane_bucket <= pl.min_bucket
        assert any(a["knob"] == "lane_bucket" for a in at.adjustments)
        m = Metrics()
        pl = _StubPipeline(m, min_bucket=256)
        at = mk_autotuner(pl, m)
        for _ in range(8):
            pl.interval(wait_ms=1.0, fill=0.97, reason="full")
            at.step()
        assert pl.min_bucket > 256

    def test_idle_interval_is_skipped(self):
        m = Metrics()
        pl = _StubPipeline(m)
        at = mk_autotuner(pl, m)
        pl.interval(wait_ms=50.0)
        at.step()                            # baseline
        assert at.step() is None             # no new dispatches → no signal
        assert pl.flush_ms == 2.0

    def test_decisions_are_traced_and_counted(self):
        m = Metrics()
        pl = _StubPipeline(m)
        at = mk_autotuner(pl, m, hysteresis=1)
        pl.interval(wait_ms=50.0)
        at.step()
        pl.interval(wait_ms=50.0)
        at.step()
        assert m.counters["autotune_adjustments_total"] >= 1
        ev = at.tracer.spans(name="autotune.decision")
        assert ev and ev[0]["attrs"]["knob"] == "flush_ms"
        st = at.status()
        assert st["adjustments_total"] == len(at.adjustments)

    def test_config_rejects_nonsense_autotune_knobs(self):
        from cilium_tpu.runtime.config import DaemonConfig
        for kw in ({"autotune_target_fill": 0.0},
                   {"autotune_target_fill": 1.5},
                   {"autotune_queue_wait_p99_ms": -1.0},
                   {"autotune_interval_s": 0.0},
                   {"trace_sample_rate": 1.5},
                   {"trace_capacity": 0},
                   {"flowmetrics_window_s": 0},
                   {"autotune_flush_ms_min": 0.0},
                   {"autotune_step_factor": 1.0}):
            with pytest.raises(ValueError):
                DaemonConfig(**kw)

    def test_quantile_from_deltas(self):
        m = Metrics()
        h = m.histogram("pipeline_queue_wait_seconds")
        for v in (0.001,) * 90 + (0.2,) * 10:
            h.observe(v)
        buckets, counts, _t, _n = h.snapshot()
        assert quantile_from(buckets, counts, 0.5) < 0.01
        assert quantile_from(buckets, counts, 0.99) > 0.05
        # the empty-window sentinel (PR 7): a delta histogram with zero
        # counts between scrapes reads NaN, not a fabricated 0.0 — the
        # autotuner and the SLO burn math both skip such intervals
        from cilium_tpu.runtime.metrics import quantile_is_empty
        assert quantile_is_empty(
            quantile_from(buckets, [0] * len(counts), 0.99))


class TestEngineIntegration:
    def test_autotune_controller_steps_through_engine(self):
        eng = fake_engine(autotune_enabled=True, pipeline_flush_ms=2.0,
                          pipeline_min_bucket=16)
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        assert eng._autotune_step() is None       # no pipeline yet
        assert eng.autotune_status() is None
        slot_of = eng.active.snapshot.ep_slot_of
        for i in range(8):
            eng.submit(batch_from_records(
                [pkt("192.168.1.10", "10.1.2.3", 40000 + i, 443)],
                slot_of), now=100 + i)
        assert eng.drain(timeout=10)
        eng._autotune_step()                      # baseline interval
        st = eng.autotune_status()
        assert st is not None
        lo, hi = st["bounds"]["flush_ms"]
        assert lo <= eng._pipeline.flush_ms <= hi
        eng.stop()

    def test_dirty_mark_during_compile_survives_regeneration(self):
        """The VERDICT weak-#6 race, pinned: an observer marking the engine
        dirty while a regeneration is compiling must not have its mark
        erased by that regeneration's completion."""
        eng = fake_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        eng.regenerate()
        assert not eng._dirty
        orig_place = eng.datapath.place

        def place_and_mark(snap):
            eng._mark_dirty()        # e.g. an ipcache upsert mid-compile
            return orig_place(snap)

        eng.datapath.place = place_and_mark
        eng.regenerate(force=True)
        assert eng._dirty            # the mid-compile mark survived
        eng.datapath.place = orig_place
        eng.regenerate()
        assert not eng._dirty
        eng.stop()

    def test_failed_regen_leaves_engine_dirty(self):
        from cilium_tpu.runtime.faults import FAULTS
        eng = fake_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        eng.regenerate()
        try:
            FAULTS.arm("regen.compile", mode="fail", times=1)
            eng._mark_dirty()
            eng.regenerate()         # supervised: serves last-good
            assert eng._dirty        # retry still owed
        finally:
            FAULTS.reset()
            eng.stop()

    def test_api_routes(self, tmp_path):
        from cilium_tpu.runtime.api import APIServer, UnixAPIClient
        eng = fake_engine(trace_sample_rate=1.0, flowlog_mode="all")
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        slot_of = eng.active.snapshot.ep_slot_of
        eng.classify(batch_from_records(
            [pkt("192.168.1.10", "10.1.2.3", 40000, 443),
             pkt("192.168.1.10", "10.1.2.3", 40001, 80)], slot_of),
            now=1000)
        sock = str(tmp_path / "api.sock")
        srv = APIServer(eng, sock)
        srv.start()
        try:
            client = UnixAPIClient(sock)
            code, doc = client.get("/v1/flows/metrics")
            assert code == 200
            assert doc["totals"]["forwarded"] == 1
            assert doc["totals"]["dropped"] == 1
            assert doc["windows"][0]["window_start"] == 1000
            code, doc = client.get("/v1/flows/metrics?last=1")
            assert code == 200 and len(doc["windows"]) == 1
            code, tr = client.get("/v1/trace?limit=5")
            assert code == 200 and tr["stats"]["enabled"]
            assert "engine.classify" in tr["summary"]
            code, tr = client.get("/v1/trace?name=engine.classify")
            assert code == 200
            assert all(s["name"] == "engine.classify" for s in tr["spans"])
            code, text = client.get("/v1/metrics")
            assert code == 200
            assert "ciliumtpu_flow_verdicts_total" in text
            code, st = client.get("/v1/status")
            assert code == 200 and st["trace"]["enabled"]
            assert st["autotune"] is None
        finally:
            srv.stop()
            eng.stop()

    def test_metrics_textfile_includes_flowmetrics(self, tmp_path):
        eng = fake_engine(metrics_path=str(tmp_path / "metrics.prom"),
                          flowlog_mode="all")
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        eng.classify(batch_from_records(
            [pkt("192.168.1.10", "10.1.2.3", 40000, 443)],
            eng.active.snapshot.ep_slot_of), now=50)
        eng.flush_observability()
        text = (tmp_path / "metrics.prom").read_text()
        assert "ciliumtpu_packets_total" in text
        assert 'ciliumtpu_flow_verdicts_total{verdict="FORWARDED"} 1' in text
        eng.stop()


@pytest.mark.slow
class TestTraceOverheadSoak:
    def test_sampled_1_64_overhead_under_2pct(self):
        """The hot-path contract behind the 1/64 default ("an unsampled
        event pays one counter"). Two measurements:

        1. The per-event sampling delta — ``maybe_sample`` at rate 0 (the
           early-out) vs 1/64 (counter + modulo, plus the full span
           recording every 64th event, i.e. the recording cost amortized
           exactly as the pipeline amortizes it) — must stay under 2% of
           the measured per-submission pipeline cost. This is the precise
           form of the claim, and it is deterministic.
        2. An end-to-end pipeline soak (interleaved off/on windows) as a
           gross-regression sanity bound; wall-clock medians on a
           multi-threaded pipeline carry scheduler noise well above 2%,
           so this bound is deliberately loose (15%) — the tight
           assertion is #1.
        """
        import gc
        d = EchoDispatch()
        tr = Tracer(sample_rate=0.0, capacity=4096)
        pl = Pipeline(d, min_bucket=64, max_bucket=256, flush_ms=0.5,
                      queue_batches=512, tracer=tr)
        batch = sub_batch(64, start=0)        # bucket-shaped: direct path

        def one_pass(n=1000):
            t0 = time.perf_counter()
            for _ in range(n):
                pl.submit(batch)
            assert pl.drain(timeout=60)
            return time.perf_counter() - t0

        reps = 100_000

        def micro_pass():
            # ~4 spans ride each sampled submission (admission, microbatch,
            # dispatch, finalize) — charge them to the sampled branch
            t0 = time.perf_counter()
            for _ in range(reps):
                tid = tr.maybe_sample()
                if tid is not None:
                    tr.record(tid, "a", 0.0, 0.0)
                    tr.record(tid, "b", 0.0, 0.0)
                    tr.record(tid, "c", 0.0, 0.0)
                    tr.record(tid, "d", 0.0, 0.0)
            return (time.perf_counter() - t0) / reps

        try:
            for _ in range(3):
                one_pass(300)                  # warmup both code paths
            gc_was = gc.isenabled()
            gc.disable()
            try:
                micro_pass()
                tr.configure(sample_rate=0.0)
                micro_off = min(micro_pass() for _ in range(5))
                tr.configure(sample_rate=1 / 64)
                micro_on = min(micro_pass() for _ in range(5))

                off, on = [], []
                for _i in range(5):            # interleaved A/B windows
                    tr.configure(sample_rate=0.0)
                    off.append(one_pass())
                    tr.configure(sample_rate=1 / 64)
                    on.append(one_pass())
            finally:
                if gc_was:
                    gc.enable()

            per_submit = min(off) / 1000       # best-case submission cost
            delta = micro_on - micro_off       # true hot-path addition
            frac = delta / per_submit
            assert frac < 0.02, \
                f"1/64 sampling adds {delta * 1e9:.0f}ns/event = " \
                f"{frac:.2%} of the {per_submit * 1e6:.1f}us submit path " \
                f"(budget 2%)"
            assert min(on) <= min(off) * 1.15, \
                f"end-to-end regression: off={min(off) * 1e3:.1f}ms " \
                f"on={min(on) * 1e3:.1f}ms"
            assert tr.sampled_total > 0        # the sampler did fire
        finally:
            pl.close(timeout=10)
