"""Pipelined ingestion scheduler tests (pipeline/scheduler.py).

Unit tests drive a raw Pipeline against a recording dispatch function
(echoing each row's sport through ``reason`` so slice plumbing is
checkable row-by-row): admission backpressure + drop accounting, deadline
vs full vs drain flushes, direct-dispatch bypass, FIFO ordering,
``pipeline.dispatch`` fault retries, supervised dispatch-error rejection,
and clean shutdown with queued work.

Integration tests go through ``Engine.submit`` and pin pipeline verdicts
bit-identical to the serial ``classify`` path on the same submissions —
the serial path is already oracle-pinned (test_parity.py), so equality
here extends the parity chain to the pipelined path. The ``slow``-marked
soak (`make chaos`) pushes 10k submissions through an engine
on FakeDatapath with ``pipeline.dispatch`` faults armed and asserts
nothing is lost or reordered.
"""

import threading
import time

import numpy as np
import pytest

from cilium_tpu.kernels.records import batch_from_records, empty_batch
from cilium_tpu.pipeline import (Pipeline, PipelineClosed, PipelineDrop,
                                 PipelineError)
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.runtime.faults import FAULTS
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
    FAULTS.reset()
    yield
    FAULTS.reset()


def sub_batch(n_rows, start, n_valid=None):
    """A submission whose rows carry ``sport = start + i`` as an identity
    tag; the first ``n_valid`` rows are valid."""
    b = empty_batch(n_rows)
    b["sport"][:] = np.arange(start, start + n_rows, dtype=np.int32)
    b["valid"][: n_rows if n_valid is None else n_valid] = True
    return b


class EchoDispatch:
    """Stands in for the datapath: records the valid-row sports of every
    dispatched batch (FIFO order proof) and echoes each row's sport back
    through ``reason`` (slice-plumbing proof)."""

    def __init__(self):
        self.batches = []            # list of [sport, ...] per dispatch
        self.gate = threading.Event()
        self.gate.set()              # clear() to stall the worker
        self.fail_next = None        # exception to raise on next call

    def __call__(self, batch, now):
        self.gate.wait(timeout=10)
        if self.fail_next is not None:
            exc, self.fail_next = self.fail_next, None
            raise exc
        valid = np.asarray(batch["valid"])
        self.batches.append(np.asarray(batch["sport"])[valid].tolist())
        out = {
            "allow": valid.copy(),
            "reason": np.asarray(batch["sport"], np.int32).copy(),
            "status": np.zeros(valid.shape[0], np.int32),
            "remote_identity": np.zeros(valid.shape[0], np.int32),
        }
        return lambda: out

    @property
    def sports_seen(self):
        return [s for b in self.batches for s in b]


class TestPipelineUnit:
    def test_direct_dispatch_bypass(self):
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=16, flush_ms=1000.0)
        try:
            t = pl.submit(sub_batch(4, start=100))
            out = t.result(timeout=5)
            assert out["reason"].tolist() == [100, 101, 102, 103]
            assert pl.flush_reasons["direct"] == 1
            assert d.batches == [[100, 101, 102, 103]]
        finally:
            pl.close(timeout=5)

    def test_coalesce_full_flush_and_slice_mapping(self):
        """Three 3-valid-row submissions into max_bucket=8: the third
        overflows the stage, forcing a 'full' flush of the first two (6
        rows → bucket 8); each ticket's rows come back in its own
        geometry."""
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=8, flush_ms=1000.0)
        try:
            t1 = pl.submit(sub_batch(5, start=10, n_valid=3))
            t2 = pl.submit(sub_batch(3, start=20))
            t3 = pl.submit(sub_batch(3, start=30))
            out1, out2 = t1.result(timeout=5), t2.result(timeout=5)
            assert pl.flush_reasons["full"] >= 1
            assert d.batches[0] == [10, 11, 12, 20, 21, 22]
            # t1: 5 rows, 3 valid — echoed on valid rows, zero elsewhere
            assert out1["reason"].tolist() == [10, 11, 12, 0, 0]
            assert out1["allow"].tolist() == [True, True, True, False, False]
            assert out2["reason"].tolist() == [20, 21, 22]
            pl.drain(timeout=5)
            assert t3.result(timeout=5)["reason"].tolist() == [30, 31, 32]
        finally:
            pl.close(timeout=5)

    def test_deadline_flush(self):
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=64, flush_ms=30.0)
        try:
            t = pl.submit(sub_batch(3, start=1))
            out = t.result(timeout=5)     # resolves via the deadline alone
            assert out["reason"].tolist() == [1, 2, 3]
            assert pl.flush_reasons["deadline"] == 1
        finally:
            pl.close(timeout=5)

    def test_drain_flushes_immediately(self):
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=64, flush_ms=60_000.0)
        try:
            t = pl.submit(sub_batch(3, start=1))
            assert pl.drain(timeout=5)
            assert t.done() and pl.flush_reasons["drain"] == 1
        finally:
            pl.close(timeout=5)

    def test_fifo_ordering_across_mixed_shapes(self):
        """Valid rows hit the dispatch function in exact submission order
        no matter how submissions coalesce, bypass, or split."""
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=16, flush_ms=1.0)
        try:
            rng = np.random.default_rng(3)
            want, start = [], 0
            for _ in range(60):
                n = int(rng.integers(1, 12))
                pl.submit(sub_batch(n, start=start))
                want.extend(range(start, start + n))
                start += n
            assert pl.drain(timeout=30)
            assert d.sports_seen == want
        finally:
            pl.close(timeout=5)

    def test_admission_drop_mode_accounts(self):
        d = EchoDispatch()
        d.gate.clear()                       # stall dispatch: queue backs up
        pl = Pipeline(d, min_bucket=4, max_bucket=4, queue_batches=2,
                      admission="drop", flush_ms=1000.0)
        try:
            tickets = [pl.submit(sub_batch(4, start=4 * i))
                       for i in range(8)]
            dropped = [t for t in tickets if t.dropped]
            assert dropped and pl.admission_drops == len(dropped)
            for t in dropped:
                with pytest.raises(PipelineDrop):
                    t.result(timeout=1)
            assert pl.metrics.counters[
                "pipeline_admission_drops_total"] == len(dropped)
            d.gate.set()
            assert pl.drain(timeout=10)
            for t in tickets:
                if not t.dropped:
                    t.result(timeout=5)
        finally:
            d.gate.set()
            pl.close(timeout=5)

    def test_admission_block_timeout_drops(self):
        d = EchoDispatch()
        d.gate.clear()
        pl = Pipeline(d, min_bucket=4, max_bucket=4, queue_batches=1,
                      admission="block", block_timeout_s=0.05,
                      flush_ms=1000.0)
        try:
            for i in range(8):
                last = pl.submit(sub_batch(4, start=4 * i))
            assert last.dropped and pl.admission_drops >= 1
        finally:
            d.gate.set()
            pl.close(timeout=5)

    def test_zero_valid_resolves_without_dispatch(self):
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=16)
        try:
            out = pl.submit(sub_batch(6, start=0, n_valid=0)).result(
                timeout=5)
            assert out["allow"].shape == (6,) and not out["allow"].any()
            assert d.batches == []
        finally:
            pl.close(timeout=5)

    def test_dispatch_fault_retried_not_lost(self):
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=16, flush_ms=1000.0)
        try:
            FAULTS.arm("pipeline.dispatch", mode="fail", times=3)
            out = pl.submit(sub_batch(4, start=7)).result(timeout=10)
            assert out["reason"].tolist() == [7, 8, 9, 10]
            assert pl.dispatch_faults == 3
            assert pl.metrics.counters["pipeline_dispatch_faults_total"] == 3
        finally:
            pl.close(timeout=5)

    def test_dispatch_error_rejects_only_affected(self):
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=16, flush_ms=1000.0)
        try:
            d.fail_next = ValueError("device fell over")
            bad = pl.submit(sub_batch(4, start=0))
            with pytest.raises(PipelineError):
                bad.result(timeout=5)
            ok = pl.submit(sub_batch(4, start=50))
            assert ok.result(timeout=5)["reason"].tolist() == [50, 51, 52, 53]
            assert pl.dispatch_errors == 1
        finally:
            pl.close(timeout=5)

    def test_close_completes_queued_work(self):
        d = EchoDispatch()
        d.gate.clear()
        pl = Pipeline(d, min_bucket=4, max_bucket=4, queue_batches=32,
                      flush_ms=1000.0)
        tickets = [pl.submit(sub_batch(4, start=4 * i)) for i in range(6)]
        d.gate.set()
        pl.close(timeout=10)
        for t in tickets:
            assert t.result(timeout=1)["allow"].all()
        with pytest.raises(PipelineClosed):
            pl.submit(sub_batch(4, start=0))
        pl.close(timeout=1)                 # idempotent

    def test_worker_crash_restarts_supervised(self):
        """A submission that crashes the worker mid-staging (malformed
        batch: missing columns) must come back rejected — not strand its
        ticket forever — and the watchdog-supervised restart keeps the
        pipeline serving (guard layer: crash → bounded restart, not a
        permanently dead pipeline)."""
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=16, flush_ms=1000.0,
                      restart_backoff_s=0.01)
        bad = {"valid": np.ones(3, bool),
               "sport": np.arange(3, dtype=np.int32)}   # not a full batch
        t = pl.submit(bad)
        with pytest.raises(PipelineError):
            t.result(timeout=5)
        assert pl.drain(timeout=5)          # outstanding went back to zero
        # supervised restart: a fresh worker picks up where the dead one
        # wedged — new submissions still serve
        ok = pl.submit(sub_batch(4, start=0))
        assert ok.result(timeout=5)["allow"].all()
        assert pl.stats()["restarts"] == 1
        pl.close(timeout=5)
        with pytest.raises(PipelineClosed):
            pl.submit(sub_batch(4, start=0))

    def test_stats_shape(self):
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=8, flush_ms=1.0)
        try:
            pl.submit(sub_batch(3, start=0))
            assert pl.drain(timeout=5)
            s = pl.stats()
            assert s["submitted"] == 1 and s["outstanding"] == 0
            assert 0 < s["fill_ratio_avg"] <= 1.0
            assert s["queue_wait_p99_ms"] >= 0.0
            text = pl.metrics.render_prometheus()
            assert "pipeline_queue_wait_seconds_bucket" in text
            assert 'le="+Inf"' in text
        finally:
            pl.close(timeout=5)


def pkt(src, dst, sp, dp, flags=C.TCP_SYN, ep_id=1):
    s16, _ = parse_addr(src)
    d16, _ = parse_addr(dst)
    return PacketRecord(s16, d16, sp, dp, C.PROTO_TCP, flags, False, ep_id,
                        C.DIR_EGRESS, C.HTTP_METHOD_ANY, b"")


def fake_engine(**kw):
    kw.setdefault("ct_capacity", 4096)
    kw.setdefault("auto_regen", False)
    kw.setdefault("batch_size", 64)
    cfg = DaemonConfig(**kw)
    return Engine(cfg, datapath=FakeDatapath(cfg))


def mk_chunks(slot_of, n_chunks, rows_per_chunk, seed=11, repeats=False):
    """An ingest stream of sub-full chunks: fresh SYNs to a mix of allowed
    (10/8:443) and denied (ports 80/22, off-prefix) destinations. With
    ``repeats`` every later chunk also revisits an early flow with an ACK,
    exercising CT continuity across batches."""
    rng = np.random.default_rng(seed)
    chunks = []
    for c in range(n_chunks):
        recs = []
        for r in range(rows_per_chunk):
            if repeats and c >= 2 and r == rows_per_chunk - 1:
                recs.append(pkt("192.168.1.10", "10.1.2.3", 41000, 443,
                                flags=C.TCP_ACK))
                continue
            dp = int(rng.choice([443, 443, 80, 22]))
            dst = f"10.{rng.integers(0, 2)}.2.{rng.integers(1, 250)}"
            sp = 42000 + c * rows_per_chunk + r
            flags = C.TCP_SYN
            if (c, r) == (0, 0):             # the flow later ACKs revisit
                sp, dp, dst = 41000, 443, "10.1.2.3"
            recs.append(pkt("192.168.1.10", dst, sp, dp, flags=flags))
        chunks.append(batch_from_records(recs, slot_of))
    return chunks


OUT_KEYS = ("allow", "reason", "status", "remote_identity", "svc",
            "nat_dst", "nat_dport", "rnat", "rnat_src", "rnat_sport")


def _mk_engine_pair(**kw):
    engines = []
    for _ in range(2):
        eng = fake_engine(**kw)
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        engines.append(eng)
    return engines


def _assert_parity(ser, pipe, chunks):
    serial_outs = [ser.classify(dict(ch), now=100 + i)
                   for i, ch in enumerate(chunks)]
    tickets = [pipe.submit(dict(ch), now=100 + i)
               for i, ch in enumerate(chunks)]
    assert pipe.drain(timeout=30)
    for i, (t, want) in enumerate(zip(tickets, serial_outs)):
        got = t.result(timeout=5)
        for k in OUT_KEYS:
            np.testing.assert_array_equal(
                got[k], want[k],
                err_msg=f"chunk {i} field {k} diverged from serial")
    # same flows, same order → identical CT occupancy and drop counters
    assert pipe.ct_stats(now=200)["live"] == ser.ct_stats(now=200)["live"]
    assert pipe.metrics.packets_total == ser.metrics.packets_total
    np.testing.assert_array_equal(pipe.metrics.by_reason_dir,
                                  ser.metrics.by_reason_dir)


class TestEnginePipelineParity:
    def test_direct_path_bit_identical_with_ct_continuity(self):
        """Bucket-shaped submissions ride the zero-copy direct path, so the
        device sees the exact same batches as the serial engine — verdicts
        must be bit-identical including established-flow CT hits spanning
        batches (the acceptance contract: same batches → same tensors)."""
        ser, pipe = _mk_engine_pair(pipeline_min_bucket=16)
        chunks = mk_chunks(ser.active.snapshot.ep_slot_of, n_chunks=12,
                           rows_per_chunk=16, repeats=True)
        _assert_parity(ser, pipe, chunks)
        stats = pipe.pipeline_stats()
        assert stats["flush_reasons"]["direct"] == len(chunks)
        pipe.stop()
        ser.stop()

    def test_coalesced_path_matches_serial(self):
        """Sub-full chunks coalesce into buckets; per-row verdicts must
        still match the serial per-chunk path. (Flows here are unique per
        row — under the kernel's CT snapshot-batch semantics that is
        exactly the regime where batch composition cannot matter, which is
        what makes coalescing a legal scheduling choice.)"""
        ser, pipe = _mk_engine_pair(pipeline_min_bucket=16,
                                    pipeline_flush_ms=1.0)
        chunks = mk_chunks(ser.active.snapshot.ep_slot_of, n_chunks=24,
                           rows_per_chunk=5)
        _assert_parity(ser, pipe, chunks)
        stats = pipe.pipeline_stats()
        assert stats["submitted"] == len(chunks)
        assert stats["dispatched_batches"] < len(chunks)   # it did coalesce
        assert ser.pipeline_stats() is None    # never started on this one
        pipe.stop()
        ser.stop()

    def test_engine_status_doc_carries_pipeline(self):
        from cilium_tpu.runtime.api import status_doc
        eng = fake_engine()
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        assert status_doc(eng)["pipeline"] is None
        eng.submit(batch_from_records(
            [pkt("192.168.1.10", "10.1.2.3", 40000, 443)],
            eng.active.snapshot.ep_slot_of), now=100)
        assert eng.drain(timeout=10)
        doc = status_doc(eng)
        assert doc["pipeline"]["submitted"] == 1
        eng.stop()
        assert eng.pipeline_stats() is None    # stop() tears the pipeline down
        with pytest.raises(PipelineClosed):    # and bars lazy resurrection
            eng.submit(batch_from_records(
                [pkt("192.168.1.10", "10.1.2.3", 40001, 443)],
                eng.active.snapshot.ep_slot_of), now=101)


@pytest.mark.slow
class TestPipelineSoak:
    def test_soak_10k_submissions_with_faults(self):
        """`make chaos` soak: 10k submissions through an engine on
        FakeDatapath with a 2% `pipeline.dispatch` fault storm armed the
        whole time — every ticket resolves, valid rows reach the datapath
        exactly once in submission order, nothing lost or reordered."""
        eng = fake_engine(pipeline_flush_ms=0.5, pipeline_queue_batches=256)
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        slot_of = eng.active.snapshot.ep_slot_of

        seen = []
        real_async = eng.datapath.classify_async

        def recording_async(placed, snap, batch, now):
            seen.extend(np.asarray(batch["sport"])
                        [np.asarray(batch["valid"])].tolist())
            return real_async(placed, snap, batch, now)

        eng.datapath.classify_async = recording_async
        FAULTS.arm("pipeline.dispatch", mode="prob", prob=0.02, seed=99)

        n_sub, want = 10_000, []
        tickets = []
        for i in range(n_sub):
            n = 1 + (i % 3)
            recs = [pkt("192.168.1.10", "10.1.2.3", 40000 + i, 443)
                    for _ in range(n)]
            b = batch_from_records(recs, slot_of)
            b["sport"][:n] = np.arange(i * 4, i * 4 + n)   # unique tags
            want.extend(range(i * 4, i * 4 + n))
            tickets.append(eng.submit(b, now=100 + i))
        assert eng.drain(timeout=120)
        unresolved = sum(1 for t in tickets if not t.done())
        assert unresolved == 0
        for t in tickets[:100] + tickets[-100:]:
            t.result(timeout=1)
        assert seen == want, "valid rows lost or reordered under faults"
        stats = eng.pipeline_stats()
        assert stats["submitted"] == n_sub
        assert stats["dispatch_faults"] > 0     # the storm actually fired
        assert stats["admission_drops"] == 0
        eng.stop()
