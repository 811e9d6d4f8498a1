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
