"""Sharded serving tests: the multi-chip zero-copy path (PR 6).

Unit tests drive a raw sharded Pipeline against an echo dispatch to pin the
steered staging ring mechanics: rows land grouped in per-shard segments,
per-ticket verdicts un-steer back to FIFO submission order, a skewed
submission sheds with ``reason="steer_overflow"`` instead of crashing the
worker, pre-binned ``_shard`` columns skip the hash, and reused segment
tails cannot leak stale rows.

Integration tests run the same submissions through 1-shard and 8-shard
JITDatapath pipelines (CPU host-platform mesh, conftest provisions the 8
fake devices) and the oracle-backed FakeDatapath serial path, asserting
bit-identical verdicts in FIFO order — including partial buckets, a
deadline-shed submission, CT continuity across drained phases (the
direction-normalized steer must keep both directions of a flow on one
shard) and a mid-soak ``place_patch``. A tracemalloc check pins the steered
staging path allocation-free in steady state, and the slow soak
(`make chaos`) pushes 10k frames through the mock-ring feeder
into an 8-shard mesh with ``shim.rx_ring`` faults armed, asserting the
steered path never fell back to an allocating pack
(``datapath_pack_fallback_total{reason="steered"} == 0``).
"""

import gc
import os
import random
import time
import tracemalloc

import numpy as np
import pytest

from cilium_tpu.kernels.records import batch_from_records, empty_batch
from cilium_tpu.pipeline import Pipeline, PipelineDeadlineExceeded, \
    PipelineDrop
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath, JITDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.runtime.faults import FAULTS
from cilium_tpu.utils import constants as C
from tests.test_datapath import FIXTURE_RULES, pkt
from tests.test_pipeline import EchoDispatch, sub_batch

#: full out geometry — comparable between two JIT backends (1-shard vs
#: 8-shard must be bit-identical in every column)
OUT_KEYS = ("allow", "reason", "status", "remote_identity", "redirect",
            "svc", "nat_dst", "nat_dport", "rnat", "rnat_src", "rnat_sport")
#: keys comparable between the JIT kernel and the oracle-backed fake (the
#: kernel reports the post-LB tuple in nat_* for non-service flows where
#: the oracle reports zeros — same convention as test_parallel's
#: TestShardedEngine)
ORACLE_KEYS = ("allow", "reason", "status", "remote_identity", "redirect",
               "svc", "rnat")


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


class ViewEchoDispatch(EchoDispatch):
    """EchoDispatch with the sharded dispatch signature (the pipeline
    passes the bucket's steer revision) that also snapshots each
    dispatched batch view (the staging buffer is recycled, so layout
    assertions need a copy)."""

    def __init__(self):
        super().__init__()
        self.views = []
        self.steer_revs = []

    def __call__(self, batch, now, steer_rev=None):
        fin = super().__call__(batch, now)
        self.views.append({k: np.asarray(v).copy()
                           for k, v in batch.items()})
        self.steer_revs.append(steer_rev)
        return fin


def shard_mod(n_shards):
    """Deterministic unit-test steering: shard by sport (the row tag the
    echo dispatch echoes back), so tests can predict each row's segment."""
    def fn(batch):
        return np.asarray(batch["sport"]) % n_shards
    return fn


def sharded_pipeline(d, n_shards=4, **kw):
    kw.setdefault("max_bucket", 16)
    kw.setdefault("min_bucket", 1)
    kw.setdefault("flush_ms", 5.0)
    kw.setdefault("shard_fn", shard_mod(n_shards))
    kw.setdefault("shard_headroom", 4)
    return Pipeline(d, n_shards=n_shards, **kw)


# --------------------------------------------------------------------------- #
# Unit: the steered staging ring
# --------------------------------------------------------------------------- #
class TestSteeredStaging:
    def test_rows_grouped_by_shard_and_fifo_unsteer(self):
        """Dispatched buckets carry rows grouped into per-shard segments;
        each ticket's verdicts come back un-steered, in submission row
        order (the slice dst_rows gather)."""
        d = ViewEchoDispatch()
        pl = sharded_pipeline(d, n_shards=4)
        try:
            seg = pl.stats()["shard_capacity"]
            t1 = pl.submit(sub_batch(6, start=100))   # sports 100..105
            t2 = pl.submit(sub_batch(5, start=200))   # sports 200..204
            assert pl.drain(timeout=10)
            # FIFO per ticket, original row order restored
            assert t1.result(timeout=5)["reason"].tolist() == \
                list(range(100, 106))
            assert t2.result(timeout=5)["reason"].tolist() == \
                list(range(200, 205))
            # one coalesced steered bucket; rows grouped by sport % 4
            assert len(d.batches) == 1
            view_sports = d.views[0]["sport"]
            view_valid = d.views[0]["valid"]
            assert view_valid.shape[0] == 4 * seg    # the full steered shape
            for row in np.nonzero(view_valid)[0]:
                assert view_sports[row] % 4 == row // seg
            # arrival order preserved inside each shard segment
            for s in range(4):
                seg_sports = view_sports[s * seg:(s + 1) * seg][
                    view_valid[s * seg:(s + 1) * seg]]
                in_100s = [x for x in seg_sports if x < 200]
                in_200s = [x for x in seg_sports if x >= 200]
                assert in_100s == sorted(in_100s)
                assert in_200s == sorted(in_200s)
                assert seg_sports.tolist() == in_100s + in_200s
        finally:
            pl.close(timeout=5)

    def test_steer_batch_out_reuse_equivalent(self):
        """steer_batch(out=) into a reused buffer is byte-identical to the
        allocating steer, including after a larger previous use (stale
        rows restored to empty-batch defaults)."""
        from cilium_tpu.kernels.records import empty_batch as eb
        from cilium_tpu.parallel.mesh import steer_batch
        big = sub_batch(16, start=100)
        small = sub_batch(4, start=200)
        buf = eb(4 * 8)
        steer_batch(big, 4, per_shard=8, out=buf)
        for b in (small, big):
            want, ws, _ = steer_batch(b, 4, per_shard=8)
            got, gs, _ = steer_batch(b, 4, per_shard=8, out=buf)
            assert got is buf
            np.testing.assert_array_equal(ws, gs)
            for k in want:
                np.testing.assert_array_equal(want[k], got[k], k)
        with pytest.raises(ValueError):
            steer_batch(big, 4, per_shard=8, out=eb(8))   # too few rows

    def test_no_direct_bypass_when_sharded(self):
        """A bucket-shaped submission still stages (its arbitrary row
        order carries no shard placement) — the 'direct' flush reason can
        never fire on a sharded pipeline."""
        d = ViewEchoDispatch()
        pl = sharded_pipeline(d, n_shards=4, max_bucket=16, min_bucket=16)
        try:
            t = pl.submit(sub_batch(16, start=300))
            assert pl.drain(timeout=10)
            assert t.result(timeout=5)["reason"].tolist() == \
                list(range(300, 316))
            assert pl.stats()["flush_reasons"]["direct"] == 0
        finally:
            pl.close(timeout=5)

    def test_steer_overflow_sheds_with_reason(self):
        """A submission more skewed than the per-shard segment capacity is
        shed with reason="steer_overflow" (PipelineDrop, retryable) — the
        old steer_batch per_shard ValueError would have crashed the worker
        into a watchdog restart. The worker survives and keeps serving."""
        d = ViewEchoDispatch()
        pl = sharded_pipeline(d, n_shards=4, max_bucket=16,
                              shard_headroom=1)
        try:
            seg = pl.stats()["shard_capacity"]
            skewed = sub_batch(16, start=400)
            skewed["sport"][:] = 400            # every row → shard 0
            assert seg < 16
            t = pl.submit(skewed)
            with pytest.raises(PipelineDrop):
                t.result(timeout=5)
            s = pl.stats()
            assert s["shed_reasons"] == {"steer_overflow": 1}
            assert pl.metrics.counters[
                'pipeline_shed_total{reason="steer_overflow"}'] == 1
            assert s["restarts"] == 0           # no watchdog involvement
            ok = pl.submit(sub_batch(4, start=500))
            assert pl.drain(timeout=10)
            assert ok.result(timeout=5)["reason"].tolist() == \
                list(range(500, 504))
        finally:
            pl.close(timeout=5)

    def test_skewed_flood_sheds_one_shard_others_keep_serving(self):
        """Adversarial skew (ISSUE 10 satellite): a flood whose flow hash
        lands predominantly in ONE shard segment sheds with
        reason="steer_overflow" FIFO-safely, while interleaved balanced
        traffic keeps serving through the other shards with verdict
        parity (the echo contract) for every surviving row."""
        d = ViewEchoDispatch()
        pl = sharded_pipeline(d, n_shards=4, max_bucket=16,
                              shard_headroom=1)
        try:
            seg = pl.stats()["shard_capacity"]
            assert seg < 16
            outcomes = []                     # (ticket, kind) in FIFO order
            for i in range(6):
                if i % 2 == 0:
                    flood = sub_batch(16, start=1000 + 100 * i)
                    flood["sport"][:] = 1000 + 100 * i   # all → one shard
                    outcomes.append((pl.submit(flood), "flood"))
                else:
                    legit = sub_batch(4, start=2000 + 100 * i)
                    outcomes.append((pl.submit(legit), "legit"))
            assert pl.drain(timeout=10)
            for t, kind in outcomes:
                if kind == "flood":
                    with pytest.raises(PipelineDrop):
                        t.result(timeout=5)
                else:
                    out = t.result(timeout=5)
                    # echo parity for survivors: each row's own sport back
                    start = int(out["reason"][0])
                    assert out["reason"].tolist() == \
                        list(range(start, start + 4))
            s = pl.stats()
            assert s["shed_reasons"] == {"steer_overflow": 3}
            assert pl.metrics.counters[
                'pipeline_shed_total{reason="steer_overflow"}'] == 3
            assert s["restarts"] == 0         # the worker never died
            # the surviving (balanced) rows actually spread across shards
            rows_total = s["shard_rows_total"]
            assert sum(rows_total) == 12 and max(rows_total) < 12
        finally:
            pl.close(timeout=5)

    def test_prebinned_shard_column_skips_hash(self):
        """A producer that pre-binned (the feeder's harvest hash) rides
        the ``_shard`` column (shard+1); shard_fn is never called."""
        d = ViewEchoDispatch()
        calls = []

        def counting_fn(batch):
            calls.append(1)
            return np.asarray(batch["sport"]) % 4

        pl = sharded_pipeline(d, n_shards=4, shard_fn=counting_fn)
        try:
            seg = pl.stats()["shard_capacity"]
            b = sub_batch(8, start=600)
            b["_shard"] = (np.arange(600, 608, dtype=np.int32) % 4) + 1
            t = pl.submit(b)
            assert pl.drain(timeout=10)
            assert t.result(timeout=5)["reason"].tolist() == \
                list(range(600, 608))
            assert not calls                    # pre-binned: no re-hash
            view = d.views[0]
            for row in np.nonzero(view["valid"])[0]:
                assert view["sport"][row] % 4 == row // seg
            # a bogus pre-bin (out-of-range shard) falls back to shard_fn
            b2 = sub_batch(4, start=700)
            b2["_shard"] = np.full(4, 99, dtype=np.int32)
            t2 = pl.submit(b2)
            assert pl.drain(timeout=10)
            assert t2.result(timeout=5)["reason"].tolist() == \
                list(range(700, 704))
            assert calls
        finally:
            pl.close(timeout=5)

    def test_prebinned_shard_revision_gate(self):
        """A pre-bin is only trusted while its binning revision is still
        active: a regen between harvest and stage-write can change the LB
        tables (and with them the post-DNAT steer hash), so a stale bin
        re-hashes through shard_fn instead of mis-steering."""
        from cilium_tpu.pipeline.scheduler import shard_bin_encode
        d = ViewEchoDispatch()
        calls = []
        rev = [7]

        def counting_fn(batch):
            calls.append(1)
            return np.asarray(batch["sport"]) % 4

        pl = sharded_pipeline(d, n_shards=4, shard_fn=counting_fn,
                              shard_rev_fn=lambda: rev[0])
        try:
            b = sub_batch(8, start=600)
            b["_shard"] = shard_bin_encode(
                np.arange(600, 608, dtype=np.int64) % 4, 7)
            t = pl.submit(b)
            assert pl.drain(timeout=10)
            t.result(timeout=5)
            assert not calls               # fresh bin: trusted
            rev[0] = 8                     # "regen" supersedes the bin
            b2 = sub_batch(4, start=700)
            b2["_shard"] = shard_bin_encode(
                np.arange(700, 704, dtype=np.int64) % 4, 7)
            t2 = pl.submit(b2)
            assert pl.drain(timeout=10)
            assert t2.result(timeout=5)["reason"].tolist() == \
                list(range(700, 704))
            assert calls                   # stale bin: re-hashed
        finally:
            pl.close(timeout=5)

    def test_steer_revision_rides_into_dispatch(self):
        """The bucket's steer revision reaches dispatch_fn: a
        single-revision bucket carries that revision, a bucket whose
        riders were steered under different revisions (a regen landed
        mid-coalesce) carries the 'mixed' sentinel — the engine re-steers
        those through the datapath instead of trusting a stale layout."""
        d = ViewEchoDispatch()
        rev = [7]
        pl = sharded_pipeline(d, n_shards=4, flush_ms=60_000.0,
                              shard_rev_fn=lambda: rev[0])
        try:
            pl.submit(sub_batch(3, start=100))
            assert pl.drain(timeout=10)
            assert d.steer_revs == [7]
            pl.submit(sub_batch(3, start=200))
            end = time.time() + 5           # rider 200 staged under rev 7
            while pl.stats()["staged_rows"] < 3 and time.time() < end:
                time.sleep(0.005)
            rev[0] = 8                      # regen between riders
            pl.submit(sub_batch(3, start=300))
            assert pl.drain(timeout=10)
            assert d.steer_revs == [7, -2]  # mixed bucket flagged
        finally:
            pl.close(timeout=5)

    def test_flush_shed_masks_steered_rows(self):
        """A staged rider whose deadline expires before the bucket
        dispatches is valid-masked out of its scattered rows; co-staged
        riders still serve in FIFO order."""
        d = ViewEchoDispatch()
        pl = sharded_pipeline(d, n_shards=4, flush_ms=60_000.0)
        try:
            doomed = pl.submit(sub_batch(3, start=10), deadline_ms=30)
            keeper = pl.submit(sub_batch(3, start=20))
            time.sleep(0.08)
            assert pl.drain(timeout=5)
            with pytest.raises(PipelineDeadlineExceeded):
                doomed.result(timeout=1)
            assert keeper.result(timeout=1)["reason"].tolist() == \
                [20, 21, 22]
            assert sorted(d.batches[0]) == [20, 21, 22]
            assert pl.stats()["shed_reasons"] == {"flush": 1}
        finally:
            pl.close(timeout=5)

    def test_segment_tails_reset_between_reuses(self):
        """A segment written full by one flush must not leak stale rows
        into a later, smaller flush from the same staging slot — the
        per-segment dirty watermark restores empty-batch defaults."""
        d = ViewEchoDispatch()
        # inflight=1 → 2 staging buffers; two drained rounds reuse slot 0
        pl = sharded_pipeline(d, n_shards=2, max_bucket=8, inflight=1)
        try:
            seg = pl.stats()["shard_capacity"]
            for start in (800, 900):            # fills both shards
                t = pl.submit(sub_batch(8, start=start))
                assert pl.drain(timeout=10)
                t.result(timeout=5)
            small = pl.submit(sub_batch(2, start=1000))
            assert pl.drain(timeout=10)
            small.result(timeout=5)
            # find the dispatch view of the small flush: exactly 2 valid
            view = d.views[-1]
            assert int(view["valid"].sum()) == 2
            # every invalid row is back at empty-batch defaults
            inv = ~view["valid"]
            assert not view["sport"][inv].any()
            assert (view["http_method"][inv] == C.HTTP_METHOD_ANY).all()
            assert view["valid"].shape[0] == 2 * seg
        finally:
            pl.close(timeout=5)


# --------------------------------------------------------------------------- #
# Integration: 1-shard vs 8-shard JIT pipelines vs the oracle-backed serial
# path — the sharded parity suite
# --------------------------------------------------------------------------- #
def jit_pipeline_engine(n_shards, **kw):
    kw.setdefault("ct_capacity", 2048)
    kw.setdefault("auto_regen", False)
    kw.setdefault("batch_size", 128)
    kw.setdefault("pipeline_flush_ms", 1.0)
    kw.setdefault("flowlog_mode", "none")
    cfg = DaemonConfig(n_shards=n_shards, **kw)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    _world(eng)
    return eng


def fake_serial_engine(**kw):
    kw.setdefault("ct_capacity", 2048)
    kw.setdefault("auto_regen", False)
    kw.setdefault("flowlog_mode", "none")
    cfg = DaemonConfig(**kw)
    eng = Engine(cfg, datapath=FakeDatapath(cfg))
    _world(eng)
    return eng


def _world(eng):
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.add_endpoint(["k8s:role=fe"], ips=("192.168.1.30",), ep_id=3)
    eng.apply_policy(FIXTURE_RULES)
    eng.regenerate()


def _mk_phase(slot_of, n_chunks, sizes, seed, revisit=None):
    """Sub-full chunks of fresh flows (unique per row — the coalescing-
    legal regime), padded with invalid tails (partial buckets). With
    ``revisit`` (list of (sport, dport, dst, flags)) the first chunk
    re-touches flows established in an earlier, drained phase — CT
    continuity across the steered path."""
    rng = np.random.default_rng(seed)
    chunks = []
    for c in range(n_chunks):
        recs = []
        if revisit and c == 0:
            recs.extend(pkt("192.168.1.10", dst, sp, dp, flags=flags)
                        for sp, dp, dst, flags in revisit)
        n = sizes[c % len(sizes)]
        for r in range(n):
            dp = int(rng.choice([443, 443, 80, 22]))
            dst = f"10.{rng.integers(0, 2)}.2.{rng.integers(1, 250)}"
            sp = 42000 + seed * 1000 + c * 64 + r
            recs.append(pkt("192.168.1.10", dst, sp, dp))
        chunks.append(batch_from_records(recs, slot_of,
                                         pad_to=len(recs) + (c % 3)))
    return chunks


def _run_phase(serial, pipes, chunks, now0):
    """Classify ``chunks`` serially (the oracle-backed truth) and submit
    them to every pipelined engine: each pipeline must match the oracle on
    ORACLE_KEYS, and the pipelines must match EACH OTHER bit-identically
    on the full out geometry (1-shard vs 8-shard). Returns the serial
    outs."""
    outs = [serial.classify(dict(ch), now=now0 + i)
            for i, ch in enumerate(chunks)]
    tickets = {id(p): [p.submit(dict(ch), now=now0 + i)
                       for i, ch in enumerate(chunks)] for p in pipes}
    got = {}
    for p in pipes:
        assert p.drain(timeout=60)
        got[id(p)] = [t.result(timeout=10) for t in tickets[id(p)]]
        for i, (g, want) in enumerate(zip(got[id(p)], outs)):
            for k in ORACLE_KEYS:
                np.testing.assert_array_equal(
                    g[k], want[k],
                    err_msg=f"chunk {i} field {k} diverged from oracle "
                            f"(shards={p.datapath.pipeline_shards})")
    ref = pipes[0]
    for p in pipes[1:]:
        for i, (g, r) in enumerate(zip(got[id(p)], got[id(ref)])):
            for k in OUT_KEYS:
                np.testing.assert_array_equal(
                    g[k], r[k],
                    err_msg=f"chunk {i} field {k}: "
                            f"{p.datapath.pipeline_shards}-shard != "
                            f"{ref.datapath.pipeline_shards}-shard")
    return outs


class TestShardedParity:
    def test_8shard_pipeline_bit_identical_to_serial(self):
        """The acceptance pin: the same submission stream through the
        1-shard and the 8-shard pipelines produces verdicts bit-identical
        to the serial single-chip path — partial buckets, a deadline-shed
        submission, CT continuity across drained phases (direction-stable
        steering), and a mid-soak place_patch included."""
        serial = fake_serial_engine()
        eng1 = jit_pipeline_engine(1)
        eng8 = jit_pipeline_engine(8)
        pipes = [eng1, eng8]
        slot_of = serial.active.snapshot.ep_slot_of
        try:
            # phase 1: fresh flows, odd sizes + invalid padding
            ch1 = _mk_phase(slot_of, 6, (1, 5, 17, 32, 9, 23), seed=1)
            _run_phase(serial, pipes, ch1, now0=1000)

            # a deadline-shed submission: both pipelines shed it, the
            # serial path simply never sees it — parity must survive
            stale = batch_from_records(
                [pkt("192.168.1.10", "10.0.2.9", 47999, 443)], slot_of)
            for p in pipes:
                t = p.submit(dict(stale), now=1100, deadline_ms=0.001)
                with pytest.raises(PipelineDeadlineExceeded):
                    t.result(timeout=10)

            # phase 2: revisit established flows in BOTH directions — the
            # direction-normalized steer must land forward and reply
            # packets on the SAME shard or the CT hit (and therefore the
            # verdict) diverges from the serial single-chip path
            est = [pkt("192.168.1.10", "10.0.2.7", 48100 + i, 443)
                   for i in range(4)]
            pre = batch_from_records(est, slot_of)
            outs = _run_phase(serial, pipes, [pre], now0=1200)
            assert outs[0]["allow"].all()
            reply = [pkt("10.0.2.7", "192.168.1.10", 443, 48100 + i,
                         flags=C.TCP_ACK, direction=C.DIR_INGRESS)
                     for i in range(4)]
            fwd_ack = [(48100 + i, 443, "10.0.2.7", C.TCP_ACK)
                       for i in range(2)]
            ch2 = [batch_from_records(reply, slot_of, pad_to=len(reply) + 2)]
            ch2 += _mk_phase(slot_of, 3, (7, 13, 2), seed=2,
                             revisit=fwd_ack)
            outs2 = _run_phase(serial, pipes, ch2, now0=1210)
            # the revisits really exercised CT: replies hit as REPLY,
            # forward ACKs as ESTABLISHED (not silently all-NEW)
            assert (np.asarray(outs2[0]["status"])[:len(reply)]
                    == int(C.CTStatus.REPLY)).all()
            assert (np.asarray(outs2[1]["status"])[:2]
                    == int(C.CTStatus.ESTABLISHED)).all()

            # mid-soak policy update through the incremental patch path
            patch_rule = [{
                "endpointSelector": {"matchLabels": {"app": "web"}},
                "egressDeny": [{"toCIDR": ["10.1.128.0/17"]}],
            }]
            for e in (serial, eng1, eng8):
                e.apply_policy(patch_rule)
                e.regenerate()

            ch3 = _mk_phase(slot_of, 4, (11, 3, 29, 6), seed=3)
            _run_phase(serial, pipes, ch3, now0=1400)

            # CT occupancy identical across all three backends
            live = serial.ct_stats(now=1500)["live"]
            assert eng1.ct_stats(now=1500)["live"] == live
            assert eng8.ct_stats(now=1500)["live"] == live

            # the steered serving path packed in place — zero allocating
            # fallbacks attributable to the sharded layout
            ps = eng8.datapath.pack_stats
            assert ps["pack_fallback_steered"] == 0
            assert ps["pack_fallback_disabled"] == 0
            assert ps["pack_inplace"] > 0
            assert eng8.pipeline_stats()["n_shards"] == 8
        finally:
            for e in (serial, eng1, eng8):
                e.stop()

    def test_4shard_pipeline_bit_identical_to_serial(self):
        """The mesh cell's width (``ct1m-50k-mesh4``; the case above runs
        eight): one stream of padded, odd-sized chunks through the 1-shard
        and the 4-shard pipelines, each equal to the oracle-backed serial
        path and the two to each other on the whole out geometry."""
        serial = fake_serial_engine()
        pipes = [jit_pipeline_engine(1), jit_pipeline_engine(4)]
        try:
            chunks = _mk_phase(serial.active.snapshot.ep_slot_of, 5,
                               (28, 37), seed=13)
            _run_phase(serial, pipes, chunks, now0=300)
            assert pipes[1].pipeline_stats()["n_shards"] == 4
        finally:
            for e in [serial] + pipes:
                e.stop()

    def test_sharded_engine_health_carries_shards(self):
        eng = jit_pipeline_engine(2)
        try:
            eng.submit(batch_from_records(
                [pkt("192.168.1.10", "10.0.2.3", 40000, 443)],
                eng.active.snapshot.ep_slot_of), now=100)
            assert eng.drain(timeout=30)
            h = eng.health()
            assert h["pipeline"]["shards"] == 2
            text = eng.render_metrics()
            assert "ciliumtpu_pipeline_mesh_shards 2" in text
            assert 'ciliumtpu_datapath_pack_fallback_total' \
                   '{reason="steered"}' not in text      # none happened
            assert "ciliumtpu_datapath_pack_inplace_total" in text
        finally:
            eng.stop()

    def test_zero_copy_disabled_still_bit_identical(self):
        """zero_copy_ingest=False falls back to the legacy dict dispatch —
        counted under reason="disabled" — with identical verdicts."""
        serial = fake_serial_engine()
        eng = jit_pipeline_engine(4, zero_copy_ingest=False)
        slot_of = serial.active.snapshot.ep_slot_of
        try:
            ch = _mk_phase(slot_of, 3, (5, 12, 3), seed=4)
            _run_phase(serial, [eng], ch, now0=2000)
            ps = eng.datapath.pack_stats
            assert ps["pack_fallback_disabled"] > 0
            assert ps["pack_inplace"] == 0
        finally:
            serial.stop()
            eng.stop()


class TestSteeredStagingAllocFree:
    def test_steered_staging_steady_state_alloc_free(self):
        """PR 5's tracemalloc contract extended to the steered path: after
        warmup, a 512-batch pipelined run through the 4-shard mesh adds no
        per-batch buffer allocations in the pack/stage/steer files (net
        growth under 64KB — temporaries are freed; what must not appear is
        a surviving allocation per batch)."""
        eng = jit_pipeline_engine(4, pipeline_flush_ms=0.5)
        slot_of = eng.active.snapshot.ep_slot_of
        chunks = _mk_phase(slot_of, 8, (9, 17, 5, 30), seed=5)
        now = [3000]

        def run(n):
            for i in range(n):
                now[0] += 1
                eng.submit(dict(chunks[i % len(chunks)]), now=now[0])
                if i % 16 == 15:
                    assert eng.drain(timeout=60)
            assert eng.drain(timeout=60)

        try:
            run(128)                    # warmup: traces, views, pools
            gc.collect()
            tracemalloc.start()
            # one full measured window FIRST, then the baseline snapshot:
            # the steered path keeps a bounded turnover footprint (the
            # most recent flush's per-ticket out dicts, the pooled wire
            # buffer) that is re-allocated rather than grown — comparing
            # two equal windows cancels it, so the assertion catches
            # exactly per-batch growth
            run(256)
            gc.collect()
            flt = [tracemalloc.Filter(True, f"*{os.sep}{name}") for name in
                   ("records.py", "scheduler.py", "datapath.py", "mesh.py")]
            snap1 = tracemalloc.take_snapshot()
            # a genuine per-batch leak grows EVERY window; a transient
            # (GC timing, another thread's allocation landing in the
            # filtered files mid-snapshot) does not — so a window over
            # budget gets exactly one fresh window before failing
            for attempt in range(2):
                run(512)
                gc.collect()
                snap2 = tracemalloc.take_snapshot()
                diff = snap2.filter_traces(flt).compare_to(
                    snap1.filter_traces(flt), "lineno")
                growth = sum(d.size_diff for d in diff)
                if growth < 64 * 1024:
                    break
                snap1 = snap2
            tracemalloc.stop()
            ps = eng.datapath.pack_stats
            assert ps["pack_inplace"] > 0
            assert ps["pack_fallback_steered"] == 0
            assert growth < 64 * 1024, \
                f"steered stage/pack path grew {growth}B:\n" + \
                "\n".join(str(d) for d in diff[:10])
        finally:
            eng.stop()


# --------------------------------------------------------------------------- #
# Slow soak (`make chaos`): the feeder → 8-shard mesh path under
# rx-ring faults
# --------------------------------------------------------------------------- #
@pytest.mark.slow
class TestShardedSoak:
    def test_soak_10k_sharded_with_rx_faults(self):
        """10k submissions through the 8-shard mesh behind one admission
        queue. With the C shim built the stream rides the mock rings +
        async feeder (harvest pre-binning) with ``shim.rx_ring`` faults
        armed; otherwise direct submissions with dispatch faults. Either
        way: every frame/submission resolves, the steered path never falls
        back to an allocating pack, and the guard never restarts."""
        from cilium_tpu.shim.bindings import LIB_PATH
        n = 10_000
        eng = jit_pipeline_engine(
            8, batch_size=256, pipeline_queue_batches=256,
            ingest_pool_batches=8, pipeline_flush_ms=0.5)
        try:
            if os.path.exists(LIB_PATH):
                from cilium_tpu.shim.bindings import FlowShim, build_frame
                shim = FlowShim(batch_size=64, timeout_us=100)
                shim.register_endpoint("192.168.1.10", 1)
                shim.mock_rings_init(ring_size=64, frame_size=2048,
                                     n_frames=64)
                feeder = eng.start_feeder(shim)
                FAULTS.arm("shim.rx_ring", mode="prob", prob=0.05, seed=31)
                end = time.time() + 300
                for i in range(n):
                    f = build_frame(
                        "192.168.1.10",
                        f"10.{i % 2}.2.{1 + i % 250}",
                        40000 + (i % 20000), 443 if i % 4 else 80)
                    while shim.mock_rx_inject(f) != 0:
                        shim.mock_tx_drain(64)
                        if time.time() > end:
                            raise TimeoutError("rx ring wedged")
                        time.sleep(0.0002)
                while time.time() < end:
                    shim.mock_tx_drain(64)
                    st = shim.stats()
                    if st["verdict_passes"] + st["verdict_drops"] \
                            + st["tx_full_drops"] >= n:
                        break
                    time.sleep(0.002)
                FAULTS.reset()
                st = shim.stats()
                fstats = feeder.stats()
                assert st["verdict_passes"] + st["verdict_drops"] \
                    + st["tx_full_drops"] >= n
                assert fstats["harvested_records"] == n
                eng.stop()
                shim.close()
            else:
                FAULTS.arm("pipeline.dispatch", mode="prob", prob=0.02,
                           seed=7)
                slot_of = eng.active.snapshot.ep_slot_of
                rng = np.random.default_rng(9)
                tickets = []
                for i in range(n):
                    m = 1 + (i % 3)
                    recs = [pkt("192.168.1.10",
                                f"10.{int(rng.integers(0, 2))}.2."
                                f"{int(rng.integers(1, 250))}",
                                40000 + (i % 20000) + r, 443)
                            for r in range(m)]
                    tickets.append(eng.submit(
                        batch_from_records(recs, slot_of), now=100 + i))
                assert eng.drain(timeout=300)
                FAULTS.reset()
                resolved = sum(1 for t in tickets if t.done())
                assert resolved == n
                eng.stop()
            ps = eng.datapath.pack_stats
            # the sharded-soak acceptance: zero steered fallbacks — the
            # serving path packed in place into pooled per-shard segments
            assert ps["pack_fallback_steered"] == 0
            assert ps["pack_inplace"] > 0
        finally:
            FAULTS.reset()
            eng.stop()


# --------------------------------------------------------------------------- #
# Degraded survivor geometry (ISSUE 19): the n-1 mesh is a first-class
# serving shape, not an error state — bit-identity to the oracle and a
# clean parity audit must hold on it from a cold CT
# --------------------------------------------------------------------------- #
class TestDegradedMeshParity:
    @pytest.mark.parametrize("n_shards,victim", [
        (4, 1),
        pytest.param(8, 5, marks=pytest.mark.slow),
    ])
    def test_n_minus_1_bit_identical_to_serial(self, n_shards, victim):
        """Shrink the mesh BEFORE any traffic (a device latched dead, one
        remesh tick onto the survivors), then run the sharded parity
        phases on the degraded geometry: fresh flows with partial
        buckets, CT continuity in BOTH directions, and the shadow
        auditor at sampling 1.0 staying clean — proving degraded serving
        is the same verdict machine, just narrower."""
        FAULTS.reset()
        serial = fake_serial_engine()
        eng = jit_pipeline_engine(n_shards, audit_enabled=True,
                                  audit_sample_rate=1.0,
                                  audit_pool_batches=64)
        eng.auditor.configure(sample_rate=1.0)
        slot_of = serial.active.snapshot.ep_slot_of
        try:
            eng.datapath.note_device_loss(victim, reason="drill")
            doc = eng.remesh_step()
            assert doc["remesh"]["from"] == n_shards
            assert doc["remesh"]["to"] == n_shards - 1
            assert victim not in \
                eng.datapath.mesh_health()["live_ordinals"]

            ch1 = _mk_phase(slot_of, 5, (1, 5, 17, 9, 23),
                            seed=60 + n_shards)
            _run_phase(serial, [eng], ch1, now0=1000)

            est = [pkt("192.168.1.10", "10.0.2.7", 49300 + i, 443)
                   for i in range(4)]
            outs = _run_phase(serial, [eng],
                              [batch_from_records(est, slot_of)],
                              now0=1200)
            assert outs[0]["allow"].all()
            reply = [pkt("10.0.2.7", "192.168.1.10", 443, 49300 + i,
                         flags=C.TCP_ACK, direction=C.DIR_INGRESS)
                     for i in range(4)]
            outs2 = _run_phase(
                serial, [eng],
                [batch_from_records(reply, slot_of, pad_to=6)],
                now0=1210)
            # the degraded steer kept both directions on one survivor
            # shard: replies really hit CT
            assert (np.asarray(outs2[0]["status"])[:4]
                    == int(C.CTStatus.REPLY)).all()

            assert eng.pipeline_stats()["n_shards"] == n_shards - 1
            for _ in range(100):
                step = eng.audit_step(budget=128)
                if not step or (not step.get("replayed")
                                and not step.get("pending")):
                    break
            st = eng.auditor.stats()
            assert st["checked_rows"] > 0
            assert st["mismatched_rows"] == 0
        finally:
            serial.stop()
            eng.stop()
