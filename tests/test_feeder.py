"""Zero-copy ingestion tests: reusable poll buffers, the async
shim→pipeline feeder (shim/feeder.py), and the steady-state zero-alloc
contract of the pack/stage path.

The FIFO proof rides frame *lengths*: mock_tx_drain returns forwarded
frames in tx-push order, and tx pushes happen in apply_verdicts order, so
injecting frames with strictly increasing payload sizes and asserting the
drained length sequence is exactly the injected one pins
harvest-order == verdict-order end to end — including under armed
``shim.rx_ring`` faults.
"""

import gc
import os
import time
import tracemalloc

import numpy as np
import pytest

from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.runtime.faults import FAULTS
from cilium_tpu.shim.bindings import LIB_PATH, FlowShim, build_frame

pytestmark = pytest.mark.skipif(
    not os.path.exists(LIB_PATH),
    reason="libflowshim.so not built (make -C cilium_tpu/shim)")

POLICY = [{
    "endpointSelector": {"matchLabels": {"app": "web"}},
    "egress": [{"toCIDR": ["10.0.0.0/8"],
                "toPorts": [{"ports": [{"port": "443",
                                        "protocol": "TCP"}]}]}],
}]

BASE_LEN = 54       # eth(14) + ipv4(20) + tcp(20): payload i → len 54+i


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def fake_engine(**kw):
    kw.setdefault("ct_capacity", 4096)
    kw.setdefault("auto_regen", False)
    kw.setdefault("batch_size", 64)
    kw.setdefault("pipeline_flush_ms", 1.0)
    cfg = DaemonConfig(**kw)
    eng = Engine(cfg, datapath=FakeDatapath(cfg))
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.apply_policy(POLICY)
    eng.regenerate()
    return eng


def mk_shim(batch_size=16, rings=True):
    shim = FlowShim(batch_size=batch_size, timeout_us=100)
    shim.register_endpoint("192.168.1.10", 1)
    if rings:
        shim.mock_rings_init(ring_size=64, frame_size=2048, n_frames=64)
    return shim


def inject_all(shim, frames, drain_to=None, deadline_s=10.0):
    """NIC-side producer: push every frame, recycling tx as needed."""
    end = time.time() + deadline_s
    for f in frames:
        while shim.mock_rx_inject(f) != 0:
            if drain_to is not None:
                drain_to.extend(shim.mock_tx_drain(64))
            else:
                shim.mock_tx_drain(64)
            if time.time() > end:
                raise TimeoutError("mock rx ring never drained")
            time.sleep(0.0005)


def wait_verdicts(shim, want, deadline_s=20.0, drain_to=None):
    end = time.time() + deadline_s
    while time.time() < end:
        if drain_to is not None:
            drain_to.extend(shim.mock_tx_drain(64))
        else:
            shim.mock_tx_drain(64)
        st = shim.stats()
        if st["verdict_passes"] + st["verdict_drops"] \
                + st["tx_full_drops"] >= want:
            return st
        time.sleep(0.005)
    raise TimeoutError(f"verdicts never reached {want}: {shim.stats()}")


class TestPollBatchOut:
    def test_out_reuse_matches_fresh_poll(self):
        """poll_batch(out=) must be column-identical to an allocating poll
        of the same frames, including the reset tail of a dirty reused
        buffer."""
        shim = mk_shim(batch_size=8, rings=False)
        frames = [build_frame("192.168.1.10", "10.0.0.1", 41000 + i, 443,
                              payload=b"x" * i) for i in range(5)]
        for f in frames:
            shim.feed_frame(f)
        fresh = shim.poll_batch(force=True)
        assert fresh is not None
        shim.apply_verdicts(np.zeros(8, bool))

        for f in frames:
            shim.feed_frame(f)
        buf = shim.make_poll_buffer()
        for col in buf.values():            # dirty the buffer thoroughly
            col[:] = np.iinfo(col.dtype).max if col.dtype != bool else True
        reused = shim.poll_batch(force=True, out=buf)
        assert reused is buf
        for k in fresh:
            if k == "_frame_idx":
                continue          # monotone across polls by design
            np.testing.assert_array_equal(
                reused[k], fresh[k], err_msg=f"column {k} diverged")
        np.testing.assert_array_equal(reused["_frame_idx"][:5],
                                      fresh["_frame_idx"][:5] + 5)
        shim.apply_verdicts(np.zeros(8, bool))
        shim.close()


class TestFeederEndToEnd:
    def test_fifo_verdict_order_mock_rings(self):
        """Frames with strictly increasing lengths, all allowed: the tx
        drain sequence must be exactly the injection sequence (verdicts
        applied FIFO, nothing lost, nothing reordered)."""
        eng = fake_engine()
        shim = mk_shim()
        eng.start_feeder(shim)
        n = 120
        frames = [build_frame("192.168.1.10", "10.1.2.3", 40000 + i, 443,
                              payload=b"p" * i) for i in range(n)]
        drained = []
        inject_all(shim, frames, drain_to=drained)
        st = wait_verdicts(shim, n, drain_to=drained)
        eng.stop()
        drained.extend(shim.mock_tx_drain(64))
        assert st["verdict_passes"] == n and st["verdict_drops"] == 0
        lens = [ln for _a, ln in drained]
        assert lens == [BASE_LEN + i for i in range(n)], \
            "forwarded frames out of order — verdict FIFO broken"
        fd_stats = eng.metrics.counters
        assert fd_stats["feeder_harvest_batches_total"] >= 1
        shim.close()

    def test_mixed_verdicts_and_counts(self):
        eng = fake_engine()
        shim = mk_shim()
        feeder = eng.start_feeder(shim)
        n = 90
        frames = [build_frame("192.168.1.10", "10.1.2.3", 42000 + i,
                              443 if i % 3 else 80) for i in range(n)]
        n_allow = sum(1 for i in range(n) if i % 3)
        inject_all(shim, frames)
        st = wait_verdicts(shim, n)
        stats = feeder.stats()
        eng.stop()
        assert st["verdict_passes"] == n_allow
        assert st["verdict_drops"] == n - n_allow
        assert stats["harvested_records"] == n
        assert stats["rejected_batches"] == 0
        shim.close()

    def test_rings_attach_with_exhausted_fill_ring(self):
        """Every umem descriptor parked in the rx ring BEFORE the
        feeder's first ring probe: the fill level reads zero exactly
        when the ring drain is most needed, and only the drain recycles
        addresses — a probe that mistook that for "no rings" deadlocked
        ingestion permanently (producer: full rx ring; harvester: never
        looks). The same race fired intermittently when a fast producer
        out-injected the feeder thread's startup."""
        eng = fake_engine()
        shim = mk_shim()                      # ring 64 / 64 umem frames
        frames = [build_frame("192.168.1.10", "10.1.2.3", 47000 + i, 443)
                  for i in range(64)]
        for f in frames:
            assert shim.mock_rx_inject(f) == 0
        assert shim.ring_fill_level() == 0    # the trap state
        eng.start_feeder(shim)
        st = wait_verdicts(shim, 64)
        eng.stop()
        assert st["verdict_passes"] == 64
        shim.close()

    def test_rx_ring_faults_tolerated(self):
        """An armed shim.rx_ring fault storm fails individual polls; the
        frames stay queued and every verdict still lands FIFO."""
        eng = fake_engine()
        shim = mk_shim()
        feeder = eng.start_feeder(shim)
        FAULTS.arm("shim.rx_ring", mode="prob", prob=0.3, seed=7)
        n = 80
        frames = [build_frame("192.168.1.10", "10.1.2.3", 43000 + i, 443,
                              payload=b"q" * i) for i in range(n)]
        drained = []
        inject_all(shim, frames, drain_to=drained)
        st = wait_verdicts(shim, n, drain_to=drained)
        FAULTS.reset()
        eng.stop()
        drained.extend(shim.mock_tx_drain(64))
        assert st["verdict_passes"] == n
        assert [ln for _a, ln in drained] == \
            [BASE_LEN + i for i in range(n)]
        assert feeder.stats()["harvest_faults"] > 0   # the storm fired
        shim.close()

    def test_pipeline_unavailable_applies_fail_closed(self):
        """When the pipeline rejects work (dispatch fault storm → breaker
        open), the feeder must still consume a verdict slot per harvested
        batch — all-drop, in FIFO position — or later verdicts would
        enforce on the wrong frames. Frames allowed BEFORE the storm must
        still come out in exact order (a rejected-at-submit batch may
        never jump the pending queue and consume an older batch's
        FrameRefs)."""
        eng = fake_engine(pipeline_breaker_threshold=2,
                          pipeline_breaker_cooldown_s=30.0)
        shim = mk_shim()
        feeder = eng.start_feeder(shim)
        n_good = 40
        good = [build_frame("192.168.1.10", "10.1.2.3", 44000 + i, 443,
                            payload=b"g" * i) for i in range(n_good)]
        drained = []
        inject_all(shim, good, drain_to=drained)
        wait_verdicts(shim, n_good, drain_to=drained)

        FAULTS.arm("pipeline.dispatch", mode="fail")
        n_bad = 48
        bad = [build_frame("192.168.1.10", "10.1.2.3", 45000 + i, 443)
               for i in range(n_bad)]
        inject_all(shim, bad, drain_to=drained)
        st = wait_verdicts(shim, n_good + n_bad, deadline_s=30.0,
                           drain_to=drained)
        FAULTS.reset()
        stats = feeder.stats()
        eng.stop()
        drained.extend(shim.mock_tx_drain(64))
        assert st["verdict_passes"] == n_good     # pre-storm traffic only
        assert st["verdict_drops"] == n_bad       # storm fail-closed
        assert [ln for _a, ln in drained] == \
            [BASE_LEN + i for i in range(n_good)], \
            "pre-storm frames reordered across the rejection boundary"
        assert stats["rejected_batches"] > 0
        assert stats["applied_batches"] == stats["harvested_batches"]
        shim.close()

    def test_oversized_shim_batch_rejected_at_start(self):
        """A harvest batch that can't fit the pipeline's largest bucket
        would fail-close 100% of traffic while looking healthy — the
        misconfig must fail fast at attach time instead."""
        eng = fake_engine(batch_size=64)
        shim = FlowShim(batch_size=128, timeout_us=100)
        try:
            with pytest.raises(ValueError, match="max bucket"):
                eng.start_feeder(shim)
        finally:
            shim.close()
            eng.stop()

    def test_sparse_ep_ids_use_dict_mapping(self, monkeypatch):
        """One huge ep_id must not make the slot LUT rebuild allocate
        id-space-sized arrays: past DENSE_LUT_MAX the mapping falls back
        to per-row dict lookups with identical verdicts."""
        from cilium_tpu.shim.feeder import ShimFeeder
        monkeypatch.setattr(ShimFeeder, "DENSE_LUT_MAX", 1024)
        eng = fake_engine()
        big_id = 1 << 16                     # far past the patched cap
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.20",),
                         ep_id=big_id)
        eng.regenerate(force=True)
        shim = mk_shim()
        shim.register_endpoint("192.168.1.20", big_id)
        feeder = eng.start_feeder(shim)
        n = 30
        frames = [build_frame("192.168.1.20", "10.1.2.3", 46000 + i,
                              443 if i % 2 else 80) for i in range(n)]
        inject_all(shim, frames)
        st = wait_verdicts(shim, n)
        eng.stop()
        assert feeder._slot_lut is None      # dict path actually taken
        assert st["verdict_passes"] == n // 2
        assert st["verdict_drops"] == n - n // 2
        shim.close()

    def test_stop_drains_pending_fifo(self):
        """stop() force-harvests what the batcher still holds and applies
        every pending verdict — no stranded FrameRefs."""
        eng = fake_engine()
        shim = mk_shim(batch_size=32)
        eng.start_feeder(shim)
        n = 11                                   # sub-batch leftovers
        frames = [build_frame("192.168.1.10", "10.1.2.3", 45000 + i, 443)
                  for i in range(n)]
        inject_all(shim, frames)
        time.sleep(0.1)
        eng.stop()                               # feeder drains through here
        st = shim.stats()
        assert st["verdict_passes"] + st["verdict_drops"] == n
        assert not shim._pending_counts          # nothing unverdicted
        shim.close()


class TestDispatchRemap:
    def test_stale_harvest_mapping_remapped_at_dispatch(self):
        """Slots are re-enumerated on regen: a batch mapped at harvest
        time can go stale in the queue. Shim-fed batches carry ``_ep_raw``
        and Engine._pipeline_dispatch re-maps them onto the snapshot it
        actually classifies with — the stale slot must not enforce another
        endpoint's policy."""
        cfg = DaemonConfig(ct_capacity=4096, auto_regen=False,
                           batch_size=64, pipeline_flush_ms=1.0)
        eng = Engine(cfg, datapath=FakeDatapath(cfg))
        eng.add_endpoint(["k8s:app=block"], ips=("192.168.1.5",), ep_id=1)
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=2)
        eng.apply_policy(POLICY + [{
            "endpointSelector": {"matchLabels": {"app": "block"}},
            "egressDeny": [{"toCIDR": ["0.0.0.0/0"]}]}])
        eng.regenerate()
        from cilium_tpu.kernels.records import batch_from_records
        from cilium_tpu.utils.ip import parse_addr
        from oracle import PacketRecord
        from cilium_tpu.utils import constants as C
        s16, _ = parse_addr("192.168.1.10")
        d16, _ = parse_addr("10.1.2.3")
        recs = [PacketRecord(s16, d16, 40000 + i, 443, C.PROTO_TCP,
                             C.TCP_SYN, False, 2, C.DIR_EGRESS)
                for i in range(4)]
        b = batch_from_records(recs, eng.active.snapshot.ep_slot_of)
        assert (b["ep_slot"][:4] == 1).all()     # web is slot 1 pre-regen
        b["_ep_raw"] = np.where(b["valid"], 2, 0).astype(np.int64)
        # endpoint 1 goes away; regen re-enumerates: web is now slot 0
        eng.remove_endpoint(1)
        eng.regenerate(force=True)
        assert eng.active.snapshot.ep_slot_of == {2: 0}
        out = eng.submit(b, now=100).result(timeout=10)
        assert out["allow"][:4].all(), \
            "stale slot survived to dispatch — wrong endpoint's policy"
        eng.stop()


class TestZeroAllocSoak:
    def test_pack_stage_path_steady_state_zero_alloc(self):
        """Acceptance pin: over >=1k pipelined batches through the JIT
        datapath, the pack/stage path (records.py, scheduler.py,
        datapath.py) shows no steady-state Python-heap growth — the wire
        rings, staging views, and upload cache make it allocation-free
        modulo transient temporaries the soak nets out to ~zero."""
        from cilium_tpu.observe.trace import TRACER
        from cilium_tpu.runtime.datapath import JITDatapath
        from cilium_tpu.kernels.records import empty_batch

        # tracemalloc counts every thread of the process, and the tracer is
        # process-wide: armed by a neighbour's engine, its ring would keep
        # this engine's spans and read as growth of the path under test
        assert not TRACER.enabled, "the process-wide tracer was left armed"
        cfg = DaemonConfig(ct_capacity=4096, auto_regen=False,
                           batch_size=64, device="cpu",
                           pipeline_flush_ms=0.5,
                           pipeline_queue_batches=256,
                           flowlog_mode="none")
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(POLICY)
        eng.regenerate()

        # one reusable sub-full chunk: submissions only read it
        chunk = empty_batch(32)
        chunk["src"][:, 2] = 0xFFFF
        chunk["src"][:, 3] = 0xC0A8010A
        chunk["dst"][:, 2] = 0xFFFF
        chunk["dst"][:, 3] = 0x0A010203
        chunk["sport"][:] = np.arange(40000, 40032)
        chunk["dport"][:] = 443
        chunk["proto"][:] = 6
        chunk["tcp_flags"][:] = 0x02
        chunk["valid"][:] = True

        def run(batches):
            for i in range(batches):
                eng.submit(chunk, now=100 + i)
                if i % 128 == 127:
                    assert eng.drain(timeout=60)
            assert eng.drain(timeout=60)

        run(128)                        # warmup: traces, views, histograms
        gc.collect()
        tracemalloc.start()
        snap1 = tracemalloc.take_snapshot()
        run(1024)
        gc.collect()
        snap2 = tracemalloc.take_snapshot()
        tracemalloc.stop()
        flt = [tracemalloc.Filter(
            True, "*" + os.path.join(os.sep, "cilium_tpu", *name))
            for name in (("kernels", "records.py"),
                         ("pipeline", "scheduler.py"),
                         ("runtime", "datapath.py"),
                         ("shim", "feeder.py"))]
        diff = snap2.filter_traces(flt).compare_to(
            snap1.filter_traces(flt), "lineno")
        growth = sum(d.size_diff for d in diff)
        stats = eng.pipeline_stats()
        eng.stop()
        assert stats["completed_batches"] >= 512   # it really coalesced
        # net growth ~0: tracemalloc bookkeeping noise only (no per-batch
        # buffer, dict, or device-destination allocation survived)
        assert growth < 64 * 1024, \
            f"pack/stage path grew {growth}B over 1k batches:\n" + \
            "\n".join(str(d) for d in diff[:10])
        assert eng.datapath.pack_stats["pack_inplace"] > 0


@pytest.mark.slow
class TestFeederSoak:
    def test_soak_10k_frames_with_faults(self):
        """`make chaos` soak: 10k frames through the mock rings
        with shim.rx_ring faults armed the whole run — every frame gets a
        verdict, forwarded frames leave in exact injection order, and the
        feeder/pipeline account for every batch.

        ct_capacity is sized ABOVE the 10k distinct flows: this soak pins
        FIFO under rx faults, not table exhaustion — at a saturated table
        the insert-when-full contract (tests/test_ctfull.py) would
        legitimately deny the overflow flows with CT_FULL."""
        eng = fake_engine(pipeline_queue_batches=256,
                          ingest_pool_batches=8,
                          ct_capacity=1 << 15)
        shim = mk_shim(batch_size=64)
        feeder = eng.start_feeder(shim)
        FAULTS.arm("shim.rx_ring", mode="prob", prob=0.05, seed=31)
        n = 10_000
        drained = []
        end = time.time() + 120
        for i in range(n):
            f = build_frame("192.168.1.10", "10.1.2.3",
                            40000 + (i % 20000), 443,
                            payload=b"s" * (i % 512))
            while shim.mock_rx_inject(f) != 0:
                drained.extend(shim.mock_tx_drain(64))
                if time.time() > end:
                    raise TimeoutError("rx ring wedged")
                time.sleep(0.0002)
        st = wait_verdicts(shim, n, deadline_s=120.0, drain_to=drained)
        FAULTS.reset()
        stats = feeder.stats()
        eng.stop()
        drained.extend(shim.mock_tx_drain(64))
        assert st["verdict_passes"] + st["tx_full_drops"] == n
        assert st["verdict_drops"] == 0
        # FIFO: drained lengths replay the injected payload cycle exactly
        lens = [ln for _a, ln in drained]
        want = [BASE_LEN + (i % 512) for i in range(n)]
        assert len(lens) == st["verdict_passes"]
        # tx_full drops (NIC backpressure) can gap the sequence; with the
        # producer draining continuously there should be none — assert the
        # strict replay when that holds, else at least monotone cycling
        if st["tx_full_drops"] == 0:
            assert lens == want, "forwarded frames out of order"
        assert stats["harvested_records"] == n
        assert stats["applied_batches"] == stats["harvested_batches"]
        assert feeder.stats()["pending"] == 0
        shim.close()


# --------------------------------------------------------------------------- #
# A harvest takes what the ring holds and waits for the worker (PR 32)
# --------------------------------------------------------------------------- #
def big_shim(batch_size=16, ring=256):
    """16-row shim batches on a 256-frame ring: with two dispatches in
    flight the ceiling is 256 / 4 = 64 rows, four polls a harvest."""
    shim = FlowShim(batch_size=batch_size, timeout_us=100)
    shim.register_endpoint("192.168.1.10", 1)
    shim.mock_rings_init(ring_size=ring, frame_size=2048, n_frames=ring)
    return shim


def manual_feeder(shim, eng, **kw):
    """A feeder whose thread is not started: the test calls ``_step``."""
    from cilium_tpu.shim.feeder import ShimFeeder
    kw.setdefault("min_bucket", 16)
    kw.setdefault("max_rows", 64)
    return ShimFeeder(shim, eng, metrics=eng.metrics, **kw)


def step_until(fd, cond, force=False, deadline_s=10.0):
    end = time.time() + deadline_s
    while not cond():
        fd._step(force=force)
        if time.time() > end:
            raise TimeoutError(f"feeder never got there: {fd.stats()}")


def frames_of(n, first=0, allow=lambda i: True):
    """Frame i is i bytes longer than the shortest and goes to the allowed
    port, or to a denied one."""
    return [build_frame("192.168.1.10", "10.1.2.3", 20000 + first + i,
                        443 if allow(first + i) else 80,
                        payload=b"h" * (first + i)) for i in range(n)]


def tx_lens(shim):
    return [ln for _a, ln in shim.mock_tx_drain(1024)]


class _Submits:
    """The engine, with every submission's row count written down and an
    outage on request (``submit`` raises while ``down``)."""

    def __init__(self, eng):
        self.eng, self.rows, self.down = eng, [], False
        self.metrics = eng.metrics

    @property
    def active(self):
        return self.eng.active

    def submit(self, batch, ingest_mono=None, trace_id=None):
        if self.down:
            raise RuntimeError("pipeline unavailable (test)")
        self.rows.append((len(batch["valid"]), int(batch["valid"].sum())))
        return self.eng.submit(batch, ingest_mono=ingest_mono,
                               trace_id=trace_id)


@pytest.mark.parametrize("ring,batch,inflight,max_rows,want", [
    (4096, 256, 2, 8192, 1024),     # every cell of the benchmark
    (4096, 256, 2, 512, 512),       # never above the largest bucket
    (4096, 256, 6, 8192, 512),      # more in flight, less a harvest
    (1024, 256, 2, 8192, 256),
    (256, 16, 2, 64, 64),
    (64, 16, 2, 64, 16),
    (100, 256, 2, 8192, 256),       # never under one shim batch
    (0, 16, 2, 64, 16),             # no rings: one shim batch
    (4096, 24, 2, 8192, 768),       # whole shim batches, a power of two
])
def test_harvest_ceiling(ring, batch, inflight, max_rows, want):
    from cilium_tpu.shim.feeder import harvest_ceiling
    assert harvest_ceiling(ring, batch, inflight, max_rows) == want


class TestHarvestTakesWhatTheRingHolds:
    @pytest.mark.parametrize("polls", [1, 2, 4])
    def test_kth_verdict_is_kth_accepted_frame(self, polls):
        """One harvest of 1, 2 and 4 shim polls (the last one partial):
        one submission, one ``apply_verdicts`` a shim batch, and the
        frames forwarded are the allowed ones in ring order."""
        eng = fake_engine(pipeline_min_bucket=16)
        shim = big_shim()
        fd = manual_feeder(shim, eng)
        assert fd.harvest_rows == 64 and fd.buckets == (16, 32, 64)
        n = 16 * polls - 5
        allow = lambda i: i % 3 != 1
        for f in frames_of(n, allow=allow):
            assert shim.mock_rx_inject(f) == 0
        step_until(fd, lambda: fd.harvested_batches == 1)
        (_t, buf, _m), = fd._pending
        assert buf.counts == [16] * (polls - 1) + [11]
        assert len(buf.view["valid"]) == 16 * polls and polls in (1, 2, 4)
        assert shim.stats()["batches_emitted"] == polls
        step_until(fd, lambda: fd.applied_batches == 1)
        st = fd.stats()
        assert (st["harvested_batches"], st["harvested_polls"],
                st["harvested_records"]) == (1, polls, n)
        assert tx_lens(shim) == [BASE_LEN + i for i in range(n) if allow(i)]
        got = shim.stats()
        assert got["verdict_passes"] + got["verdict_drops"] == n
        assert not shim._pending_counts
        c = eng.metrics.counters
        b = 16 * polls
        assert c[f'feeder_harvests_total{{bucket="{b}"}}'] == 1
        assert c[f'feeder_harvest_rows_total{{bucket="{b}"}}'] == n
        assert c[f'feeder_harvest_polls_total{{bucket="{b}"}}'] == polls
        eng.stop()
        shim.close()

    @pytest.mark.parametrize("rows,bucket", [
        (1, 16), (16, 16), (17, 32), (33, 64), (64, 64)])
    def test_partial_harvest_submits_the_smallest_bucket(self, rows, bucket):
        """The submission is the view of the buffer's first rows at the
        smallest bucket that holds the harvest, and what the harvest did
        not write there is invalid, whatever the buffer held before."""
        eng = fake_engine(pipeline_min_bucket=16)
        sub = _Submits(eng)
        shim = big_shim()
        fd = manual_feeder(shim, sub)
        for buf in fd._free:
            for k, col in buf.items():
                col[:] = True if col.dtype == bool else 7
        for f in frames_of(rows):
            assert shim.mock_rx_inject(f) == 0
        step_until(fd, lambda: fd.applied_batches == 1, force=True)
        assert sub.rows == [(bucket, rows)]
        reasons = eng.pipeline_stats()["flush_reasons"]
        assert reasons.pop("direct") == 1 and not any(reasons.values())
        assert tx_lens(shim) == [BASE_LEN + i for i in range(rows)]
        eng.stop()
        shim.close()

    def test_rejected_harvest_drops_every_shim_batch_in_fifo_position(self):
        """A submission the pipeline refuses still owes the shim one
        verdict batch per poll of its harvest, all-drop, after the harvest
        before it and before the one behind it."""
        eng = fake_engine(pipeline_min_bucket=16)
        sub = _Submits(eng)
        shim = big_shim()
        fd = manual_feeder(shim, sub)
        sizes, first = (20, 40, 30), 0        # 2, 3 and 2 polls
        for k, n in enumerate(sizes):
            for f in frames_of(n, first=first):
                assert shim.mock_rx_inject(f) == 0
            sub.down = k == 1
            step_until(fd, lambda: fd.harvested_batches == k + 1,
                       force=True)
            ticket, buf, _m = fd._pending[-1]
            assert len(buf.counts) == (2, 3, 2)[k] and sum(buf.counts) == n
            assert (ticket is None) == sub.down
            first += n
        step_until(fd, lambda: fd.applied_batches == 3)
        assert tx_lens(shim) == [BASE_LEN + i for i in range(20)] \
            + [BASE_LEN + i for i in range(60, 90)]
        st = shim.stats()
        assert (st["verdict_passes"], st["verdict_drops"]) == (50, 40)
        assert fd.stats()["rejected_batches"] == 1
        assert not shim._pending_counts
        eng.stop()
        shim.close()

    @pytest.mark.parametrize("n", [11, 50, 200])
    def test_stop_drains_multi_poll_buffers(self, n):
        """stop() takes what ring and batcher still hold, several polls a
        buffer, and applies every verdict: no stranded FrameRefs."""
        eng = fake_engine(pipeline_min_bucket=16)
        shim = big_shim()
        feeder = eng.start_feeder(shim)
        assert feeder.harvest_rows == 64
        for f in frames_of(n):
            assert shim.mock_rx_inject(f) == 0
        eng.stop()
        st = shim.stats()
        assert st["verdict_passes"] == n and st["verdict_drops"] == 0
        assert tx_lens(shim) == [BASE_LEN + i for i in range(n)]
        assert not shim._pending_counts
        fs = feeder.stats()
        assert fs["applied_batches"] == fs["harvested_batches"]
        assert fs["harvested_records"] == n and fs["pending"] == 0
        shim.close()

    @pytest.mark.parametrize("extras", [
        {}, {"n_shards": 4}, {"qos": True}, {"fqdn": True},
        {"n_shards": 4, "qos": True, "fqdn": True}])
    def test_optional_columns_have_the_buffers_length(self, extras):
        """Host-RSS, QoS and DNS columns are as long as the buffer, and a
        submission carries the view of their first rows."""
        class _Qos:
            def map_tenants(self, raw):
                return np.asarray(raw) % 3

            def name_of(self, t):
                return f"t{t}"

        class _Dns:
            payload_width = 64

            def observe_batch(self, buf, out):
                assert len(buf["_dns_len"]) == len(out["allow"])

        eng = fake_engine(pipeline_min_bucket=16)
        sub = _Submits(eng)
        shim = big_shim()
        kw = dict(n_shards=extras.get("n_shards", 1),
                  qos=_Qos() if extras.get("qos") else None,
                  fqdn=_Dns() if extras.get("fqdn") else None)
        fd = manual_feeder(shim, sub, **kw)
        want = {"_prio", "_fp"} \
            | ({"_shard"} if "n_shards" in extras else set()) \
            | ({"_tenant"} if "qos" in extras else set()) \
            | ({"_dns_payload", "_dns_len"} if "fqdn" in extras else set())
        for buf in fd._free:
            assert want <= set(buf)
            assert {k for k in buf if k.startswith("_")} \
                == want | {"_ep_raw", "_frame_idx"}
            assert all(len(col) == fd.harvest_rows == 64
                       for col in buf.values())
            for b, view in buf.views.items():
                assert set(view) == set(buf)
                assert all(len(col) == b for col in view.values())
                assert all(np.shares_memory(view[k], buf[k]) for k in buf)
        for f in frames_of(20):
            assert shim.mock_rx_inject(f) == 0
        step_until(fd, lambda: fd.harvested_batches == 1, force=True)
        (_t, buf, _m), = fd._pending
        assert all(len(col) == 32 for col in buf.view.values())
        from cilium_tpu.shim.feeder import flow_hashes
        assert buf.view["_fp"].dtype == np.uint32           # the harvest's
        np.testing.assert_array_equal(buf.view["_fp"],      # hash, tail too
                                      flow_hashes(buf.view))
        if "n_shards" in extras:
            assert (buf.view["_shard"][:20] != 0).all()     # pre-binned
        if "qos" in extras:
            assert (buf.view["_tenant"][:20] == 1).all()    # ep 1 % 3
        step_until(fd, lambda: fd.applied_batches == 1)
        assert shim.stats()["verdict_passes"] == 20
        eng.stop()
        shim.close()


    @pytest.mark.parametrize("min_bucket,reason", [
        (16, "direct"), (64, "deadline")])
    def test_a_row_is_hashed_once_a_harvest(self, monkeypatch, min_bucket,
                                            reason):
        """Harvest → apply rounds over the same flows, new and then
        established, dispatched ``direct`` and staged: ``flow_hashes`` runs
        once a harvest, on the harvest's view, and never for the feeder's
        ``note`` or the worker's; ``flow_hash_rows`` says the same on both
        threads; and both fingerprint tables are the tables that hashing
        every ``note`` would have built."""
        from cilium_tpu.shim import feeder as feeder_mod
        from cilium_tpu.shim.feeder import EstablishedFingerprints
        plain_hashes = feeder_mod.flow_hashes
        eng = fake_engine(pipeline_min_bucket=min_bucket)
        shim = big_shim()
        fd = manual_feeder(shim, eng)
        calls, notes = [], []

        def counted(b):
            calls.append(len(b["valid"]))
            return plain_hashes(b)

        def note(view, out, plain=fd._note_established):
            notes.append(({k: v.copy() for k, v in view.items()
                           if k != "_fp"},
                          {k: np.asarray(out[k]).copy()
                           for k in ("allow", "status")}))
            plain(view, out)
        monkeypatch.setattr(feeder_mod, "flow_hashes", counted)
        monkeypatch.setattr(fd, "_note_established", note)
        rounds, n = 4, 20
        for r in range(rounds):
            for f in frames_of(n, allow=lambda i: i % 4 != 3):
                assert shim.mock_rx_inject(f) == 0
            step_until(fd, lambda: fd.applied_batches == r + 1, force=True)
            tx_lens(shim)
        assert calls == [32] * rounds
        assert fd.stats()["flow_hash_rows"] == 32 * rounds
        ps = eng.pipeline_stats()
        assert ps["verdict_rows"]["flow_hash_rows"] == 0
        assert ps["verdict_rows"]["total"] == n * rounds
        assert ps["flush_reasons"][reason] == rounds
        # later rounds found the flows established and stamped them
        monkeypatch.setattr(feeder_mod, "flow_hashes", plain_hashes)
        want = EstablishedFingerprints()
        for view, out in notes:
            want.note(view, out)
        assert want.hashed_rows == 15 * (rounds - 1)
        np.testing.assert_array_equal(fd._est._tab, want._tab)
        np.testing.assert_array_equal(eng._salvage_fp._tab, want._tab)
        eng.stop()
        shim.close()


class _SlowEngine:
    """A stand-in whose worker takes its time: a submission stays
    undispatched until ``serve`` gets to it."""

    def __init__(self, eng):
        self.eng = eng
        self.metrics = eng.metrics
        self.tickets = []
        self.most_undispatched = 0

    @property
    def active(self):
        return self.eng.active

    def submit(self, batch, ingest_mono=None, trace_id=None):
        from cilium_tpu.pipeline.scheduler import Ticket
        t = Ticket(len(batch["valid"]), int(batch["valid"].sum()))
        self.tickets.append(t)
        self.most_undispatched = max(
            self.most_undispatched,
            sum(1 for x in self.tickets
                if x.dispatched_mono is None and not x.done()))
        return t

    def serve(self, stop, pace_s=0.004):
        served = 0
        while not stop.is_set() or served < len(self.tickets):
            if served == len(self.tickets):
                time.sleep(0.0005)
                continue
            t = self.tickets[served]
            time.sleep(pace_s)               # the queue
            t.dispatched_mono = time.monotonic()
            t._wake()
            time.sleep(pace_s)               # the device
            t._resolve({"allow": np.ones(t.n_rows, bool),
                        "status": np.zeros(t.n_rows, np.int32)})
            served += 1


class TestHarvestWaitsForTheWorker:
    def test_at_most_one_submission_undispatched(self):
        """With a slow worker the feeder never has two submissions
        waiting: frames wait in the ring and leave together, several polls
        a harvest, and the deferrals are counted."""
        import threading
        eng = fake_engine()
        slow = _SlowEngine(eng)
        shim = big_shim()
        fd = manual_feeder(shim, slow).start()
        stop = threading.Event()
        worker = threading.Thread(target=slow.serve, args=(stop,))
        worker.start()
        n = 600
        try:
            drained = []
            inject_all(shim, frames_of(n), drain_to=drained)
            wait_verdicts(shim, n, drain_to=drained)
        finally:
            fd.stop()
            stop.set()
            worker.join(10)
        eng.stop()
        drained.extend(shim.mock_tx_drain(1024))
        assert [ln for _a, ln in drained] == [BASE_LEN + i for i in range(n)]
        st = fd.stats()
        assert slow.most_undispatched == 1
        assert st["deferred_harvests"] >= 1
        assert eng.metrics.counters["feeder_harvest_deferred_total"] \
            == st["deferred_harvests"] <= st["harvested_batches"]
        assert st["harvested_polls"] > st["harvested_batches"]
        assert st["harvested_records"] == n
        assert st["pending"] == 0 and not shim._pending_counts
        shim.close()

    @pytest.mark.parametrize("first,regime", [(20, "verdicts"),
                                              (64, "dispatch")])
    def test_what_a_harvest_waits_for_follows_the_one_before(self, first,
                                                             regime):
        """Undispatched, the newest submission holds the next harvest back
        whatever its size. Dispatched, a full one (the ring held more than
        a harvest takes) lets the next go at once, so that host and device
        work together; a partial one (the ring ran dry) holds it until its
        verdicts are back, and the frames that came meanwhile leave in one
        piece. Each is counted by what it waited for."""
        from cilium_tpu.pipeline.scheduler import Ticket

        class _Hand:
            """Tickets the test dispatches and resolves by hand."""
            active = None
            tickets = []

            def submit(self, batch, ingest_mono=None, trace_id=None):
                self.tickets.append(
                    Ticket(len(batch["valid"]), int(batch["valid"].sum())))
                return self.tickets[-1]

        eng = fake_engine()
        hand = _Hand()
        hand.active, hand.metrics, hand.tickets = eng.active, eng.metrics, []
        shim = big_shim()
        fd = manual_feeder(shim, hand)
        for f in frames_of(first):
            assert shim.mock_rx_inject(f) == 0
        step_until(fd, lambda: fd.harvested_batches == 1)
        assert sum(fd._pending[-1][1].counts) == first
        for f in frames_of(10, first=first):      # these come meanwhile
            assert shim.mock_rx_inject(f) == 0
        t1 = hand.tickets[0]
        assert fd._held_back() == "dispatch"
        for _ in range(5):
            fd._step(force=False)
        assert fd.harvested_batches == 1          # held back
        t1.dispatched_mono = time.monotonic()
        t1._wake()
        if regime == "dispatch":
            assert fd._held_back() is None
            step_until(fd, lambda: fd.harvested_batches == 2)
            assert not t1.done()                  # two of ours in flight
        else:
            assert fd._held_back() == "verdicts"
            for _ in range(5):
                fd._step(force=False)
            assert fd.harvested_batches == 1      # still held back
        t1._resolve({"allow": np.ones(t1.n_rows, bool),
                     "status": np.zeros(t1.n_rows, np.int32)})
        step_until(fd, lambda: fd.harvested_batches == 2
                   and fd.applied_batches >= 1)
        c = eng.metrics.counters
        assert c[f'feeder_harvest_deferred_total{{for="{regime}"}}'] == 1
        assert c["feeder_harvest_deferred_total"] == 1 \
            == fd.stats()["deferred_harvests"]
        for t in hand.tickets[1:]:
            t._resolve({"allow": np.ones(t.n_rows, bool),
                        "status": np.zeros(t.n_rows, np.int32)})
        step_until(fd, lambda: not fd._pending, force=True)
        assert shim.stats()["verdict_passes"] == first + 10
        assert tx_lens(shim) == [BASE_LEN + i for i in range(first + 10)]
        eng.stop()
        shim.close()

    def test_a_harvest_that_finds_nothing_waiting_is_not_a_deferral(self):
        """Held back or not, a harvest counts as deferred only if frames
        were there when the worker let it go."""
        eng = fake_engine(pipeline_min_bucket=16)
        shim = big_shim()
        fd = manual_feeder(shim, eng)
        for k in range(5):
            for f in frames_of(10, first=10 * k):
                assert shim.mock_rx_inject(f) == 0
            step_until(fd, lambda: fd.harvested_batches == k + 1)
            # held for this one's verdicts, with an empty ring behind it
            step_until(fd, lambda: fd.applied_batches == k + 1)
            fd._step(force=False)                 # let go: nothing to take
        assert fd.stats()["deferred_harvests"] == 0
        assert "feeder_harvest_deferred_total" not in eng.metrics.counters
        eng.stop()
        shim.close()

    def test_pacing_signals_under_a_short_switch_interval(self):
        """Feeder, pipeline worker and NIC side racing with the interpreter
        switching every 10 µs: the dispatched stamp and the wake-up are
        read and written across threads, and a lost one would strand a
        harvest (frames without a verdict) or reorder them."""
        import sys
        eng = fake_engine(pipeline_min_bucket=16)
        shim = big_shim()
        feeder = eng.start_feeder(shim)
        n, drained = 1500, []          # frame i is 54 + i bytes of 2,048
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            inject_all(shim, frames_of(n), drain_to=drained,
                       deadline_s=60.0)
            wait_verdicts(shim, n, deadline_s=60.0, drain_to=drained)
        finally:
            sys.setswitchinterval(old)
            st = feeder.stats()
            eng.stop()
        drained.extend(shim.mock_tx_drain(1024))
        assert [ln for _a, ln in drained] == [BASE_LEN + i for i in range(n)]
        assert st["harvested_records"] == n and st["rejected_batches"] == 0
        assert st["harvested_polls"] >= st["harvested_batches"]
        assert not shim._pending_counts
        shim.close()

    def test_pipeline_marks_a_ticket_dispatched_and_wakes_its_producer(self):
        """The signal the feeder reads: ``dispatched_mono`` is set when the
        worker has handed the rows to the device, and the ticket's waker
        fires then and again when it resolves."""
        import threading
        eng = fake_engine()
        from cilium_tpu.kernels.records import empty_batch
        batch = empty_batch(64)
        batch["valid"][:3] = True
        batch["ep_slot"][:] = 0
        t = eng.submit(batch)
        t.waker = threading.Event()
        t.result(timeout=10)
        assert t.dispatched_mono is not None
        assert t.submitted_mono <= t.dispatched_mono <= time.monotonic()
        assert t.waker.is_set()
        none = empty_batch(64)                  # nothing valid: no dispatch
        t0 = eng.submit(none)
        t0.result(timeout=10)
        assert t0.dispatched_mono is None and t0.done()
        eng.stop()


# --------------------------------------------------------------------------- #
# Every shape the feeder can dispatch is warm before start_feeder returns
# --------------------------------------------------------------------------- #
class _Compiles:
    """Every XLA backend compile (or cache load) of this process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def __call__(self, event, _secs, **_kw):
        self.n += event == self.EVENT

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self)


@pytest.mark.parametrize("mesh", [
    {}, {"n_shards": 4, "rss_mode": "device"}],
    ids=["one-device", "device-rss-4"])
def test_no_harvest_size_compiles_after_start_feeder(mesh):
    """The jit-audited engine (parity auditor on every batch) on one device
    and on four virtual ones under device RSS: ``start_feeder`` compiles
    the ladder 16, 32, 64 on rows that open no flow; after it a harvest of
    any size from 1 to the ceiling compiles nothing, and every verdict is
    the oracle's."""
    from benchmarks.worlds import podrules
    from cilium_tpu.runtime.datapath import JITDatapath
    cfg = DaemonConfig(ct_capacity=8192, auto_regen=False, device="cpu",
                       batch_size=64, pipeline_min_bucket=16,
                       flowlog_mode="none", audit_enabled=True,
                       audit_sample_rate=1.0, audit_pool_batches=64, **mesh)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    eng.auditor.configure(sample_rate=1.0)
    world = podrules.build({"builder": "podrules", "n_ids": 16,
                            "n_rules": 64, "port_span": 32})
    world.load(eng)
    eng.regenerate()
    shim = FlowShim(batch_size=16, timeout_us=100)
    world.register(shim)
    shim.mock_rings_init(ring_size=256, frame_size=2048, n_frames=256)
    try:
        from benchmarks.frames import columns_of, frames_of as wire_frames
        rng = np.random.default_rng(3200000101)
        ceiling = 64
        n_frames = ceiling * (ceiling + 1) // 2
        flows = world.allowed_flows(rng, 64 + n_frames, 1, 2)
        flows["sport"] = (20000 + np.arange(64 + n_frames)).astype(np.int32)
        table, lens = wire_frames(flows, world.ep_v4, world.ep_v6_words)
        # some live state first: the warm-up must leave it as it is
        slot = eng.active.snapshot.ep_slot_of[world.ep_id]
        opened = columns_of({k: v[:64] for k, v in flows.items()},
                            world.ep_v4, world.ep_v6_words, slot)
        assert eng.submit(opened).result(timeout=120)["allow"].all()
        live = eng.ct_stats()["live"]
        assert live == 64
        with _Compiles() as compiles:
            feeder = eng.start_feeder(shim)
            warmed = compiles.n
            feeder.stop()          # the thread's; the test steps from here
            assert feeder.buckets == (16, 32, 64)
            assert warmed >= 2     # 64 rows was compiled by the submit
            assert eng.ct_stats()["live"] == live
            sent = 64              # the flows opened above
            for rows in range(1, ceiling + 1):
                for i in range(sent, sent + rows):
                    frame = table[i, :lens[i]].tobytes()
                    assert shim.mock_rx_inject(frame) == 0
                h = feeder.harvested_batches
                step_until(feeder, lambda: feeder.harvested_batches == h + 1,
                           force=True)
                assert sum(feeder._pending[-1][1].counts) == rows
                step_until(feeder, lambda: not feeder._pending, force=True)
                shim.mock_tx_drain(1024)
                sent += rows
            assert compiles.n == warmed, "a harvest compiled under traffic"
        st = shim.stats()
        # a flow whose probe window is full is refused a slot, and counted
        full = int(eng.metrics.insert_fail)
        assert full <= 8
        assert (st["verdict_passes"], st["verdict_drops"]) \
            == (n_frames - full, full)
        assert eng.ct_stats()["live"] == 64 + n_frames - full
        assert eng.drain(timeout=60)
        for _ in range(200):
            step = eng.audit_step(budget=128)
            if not step or (not step.get("replayed")
                            and not step.get("pending")):
                break
        audit = eng.auditor.stats()
        assert audit["checked_rows"] > 0, audit
        assert audit["mismatched_rows"] == 0, audit
        assert feeder.stats()["harvested_batches"] == ceiling
    finally:
        eng.stop()
        shim.close()
