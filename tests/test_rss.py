"""Device-side RSS tests: the in-kernel ring ppermute CT exchange
(parallel/exchange.py, ``DaemonConfig.rss_mode="device"``).

Unit tests pin the ring primitives (all-gather / reduce-scatter over
explicit ppermute hops) and the exchange's bit-identity to the steered
mesh at the raw classify-fn level — including a saturating flood where
CT_FULL fail-closed verdicts AND the tail-evict victim order must match
slot-for-slot (the gathered request set preserves global row order, and
the owner-side CT stage is classify_step's own ct_update_stage).

Integration tests run the device-RSS engine behind the pipeline against
the host-steered mesh and the oracle-backed serial path (the sharded
parity suite's acceptance bar, steering off), drive the skewed/adversarial
arrival patterns that host steering sheds or serializes on
(all-rows-one-shard, alternating-shard, a cfg6-form randomized storm)
asserting NO shed class fires and verdicts match the bounded oracle, pin
the steer-revision fence degradation (a regen between stage and dispatch
must not trip re-steer logic that no longer applies — the plain revision
stamp check / StalePlacement retry is the whole fence), and check the
operator surfaces: the ``rss_exchange`` ledger row + ``exchange`` HBM
group exist, while the steer-balance gauges and the ``steer_overflow``
shed reason are swept from the export instead of reporting frozen zeros.
"""

import os

import numpy as np
import pytest

from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.pipeline import Pipeline
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.faults import FAULTS
from cilium_tpu.utils import constants as C
from tests.test_datapath import pkt
from tests.test_sharded_pipeline import (_mk_phase, _run_phase,
                                         fake_serial_engine,
                                         jit_pipeline_engine)


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


# --------------------------------------------------------------------------- #
# Unit: the ring primitives
# --------------------------------------------------------------------------- #
class TestRingPrimitives:
    def _mesh(self, n):
        from cilium_tpu.parallel.mesh import make_mesh
        return make_mesh(n, 1)

    def test_ring_all_gather_orders_by_origin(self):
        import jax
        import jax.numpy as jnp
        try:
            from jax import shard_map
        except ImportError:
            from jax.experimental.shard_map import shard_map
        import inspect
        from jax.sharding import PartitionSpec as P
        from cilium_tpu.parallel.exchange import ring_all_gather
        n = 4
        mesh = self._mesh(n)
        kw = {("check_vma" if "check_vma"
               in inspect.signature(shard_map).parameters
               else "check_rep"): False}

        def body(x):
            return ring_all_gather(x, "flows", n)
        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("flows"),
            out_specs=P("flows"), **kw))
        x = np.arange(n * 3, dtype=np.uint32).reshape(n * 3, 1)
        out = np.asarray(fn(jnp.asarray(x)))
        # each chip's [n, L, 1] block (stacked along dim 0 by the out
        # spec) must hold ALL chips' rows indexed by origin
        out = out.reshape(n, n, 3, 1)
        for chip in range(n):
            np.testing.assert_array_equal(
                out[chip].reshape(n * 3, 1), x,
                err_msg=f"chip {chip} gathered a reordered request set")

    def test_ring_reduce_scatter_routes_chunks_home(self):
        import jax
        import jax.numpy as jnp
        try:
            from jax import shard_map
        except ImportError:
            from jax.experimental.shard_map import shard_map
        import inspect
        from jax.sharding import PartitionSpec as P
        from cilium_tpu.parallel.exchange import ring_reduce_scatter
        n = 4
        mesh = self._mesh(n)
        kw = {("check_vma" if "check_vma"
               in inspect.signature(shard_map).parameters
               else "check_rep"): False}

        def body(x):
            # every chip contributes chunk c = 1000*my + c per element;
            # chip c must end with sum over chips of (1000*chip + c)
            my = jax.lax.axis_index("flows")
            parts = (jnp.arange(n, dtype=jnp.uint32)[:, None, None]
                     + jnp.uint32(1000) * my.astype(jnp.uint32))
            parts = jnp.broadcast_to(parts, (n, 2, 1))
            return ring_reduce_scatter(parts, "flows", n)
        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("flows"), out_specs=P("flows"),
            **kw))
        out = np.asarray(fn(jnp.zeros((n * 2, 1), np.uint32)))
        out = out.reshape(n, 2, 1)
        base = 1000 * sum(range(n))
        for c in range(n):
            assert (out[c] == base + n * c).all(), \
                f"chip {c} chunk mis-routed: {out[c].ravel()}"


# --------------------------------------------------------------------------- #
# Unit: exchange vs steered bit-identity at the raw classify-fn level
# --------------------------------------------------------------------------- #
class TestExchangeBitIdentity:
    def _world(self, ct_capacity):
        from cilium_tpu.runtime.datapath import FakeDatapath
        from cilium_tpu.runtime.engine import Engine
        cfg = DaemonConfig(ct_capacity=ct_capacity, auto_regen=False,
                           flowlog_mode="none")
        eng = Engine(cfg, datapath=FakeDatapath(cfg))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egress": [{"toCIDR": ["10.0.0.0/8"]}],
        }])
        eng.regenerate()
        snap = eng.active.snapshot
        eng.stop()
        return snap

    def test_saturating_flood_ct_full_and_evict_order_identical(self):
        """The acceptance pin the steered parity suite cannot see: under
        a flood that saturates the per-shard CT tables, the exchange path
        must produce the SAME CT_FULL fail-closed verdicts, the SAME
        eviction counters, and byte-identical CT tables — the tail-evict
        victim order survives the ring exchange because the gathered
        request set preserves global row order."""
        import jax.numpy as jnp
        from cilium_tpu.compile.ct_layout import CTConfig, make_ct_arrays
        from cilium_tpu.parallel.mesh import (
            make_mesh, make_sharded_classify_fn, make_unsteered_classify_fn,
            shard_ct_arrays, steer_batch, unsteer_outputs)
        snap = self._world(ct_capacity=128)
        slot_of = snap.ep_slot_of
        n_shards = 4
        mesh = make_mesh(n_shards, 1)
        ct_host = make_ct_arrays(CTConfig(128, 8))
        shard_ct_arrays(ct_host, n_shards)
        ct_s = {k: jnp.asarray(v) for k, v in ct_host.items()}
        ct_d = {k: jnp.asarray(v) for k, v in ct_host.items()}
        tn = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
        steer_fn = make_sharded_classify_fn(mesh, donate_ct=False)
        dev_fn = make_unsteered_classify_fn(mesh, donate_ct=False)

        rows = 128
        tot_full = 0
        for i in range(6):                 # 6*128 rows >> 128 CT slots
            rng = np.random.default_rng(i)
            recs = [pkt("192.168.1.10",
                        f"10.{rng.integers(0, 200)}.{rng.integers(0, 250)}"
                        f".{rng.integers(1, 250)}",
                        int(1024 + rng.integers(0, 60000)), 443)
                    for _ in range(rows)]
            b = batch_from_records(recs, slot_of, pad_to=rows)
            now = 1000 + i
            sb, scatter, _per = steer_batch(b, n_shards, round_to_pow2=True)
            out_s, ct_s, ctr_s = steer_fn(
                tn, ct_s, {k: jnp.asarray(v) for k, v in sb.items()},
                jnp.uint32(now), jnp.int32(snap.world_index))
            out_s = unsteer_outputs(
                {k: np.asarray(v) for k, v in out_s.items()}, scatter)
            out_d, ct_d, ctr_d = dev_fn(
                tn, ct_d, {k: jnp.asarray(v) for k, v in b.items()},
                jnp.uint32(now), jnp.int32(snap.world_index))
            out_d = {k: np.asarray(v) for k, v in out_d.items()}
            v = np.asarray(b["valid"], dtype=bool)
            for k in out_s:
                np.testing.assert_array_equal(
                    out_s[k][v], out_d[k][v],
                    err_msg=f"batch {i} out[{k}] diverged")
            for k in ctr_s:
                np.testing.assert_array_equal(
                    np.asarray(ctr_s[k]), np.asarray(ctr_d[k]),
                    err_msg=f"batch {i} counter {k} diverged")
            tot_full += int(out_d["ct_full"][v].sum())
        for k in ct_s:
            np.testing.assert_array_equal(
                np.asarray(ct_s[k]), np.asarray(ct_d[k]),
                err_msg=f"CT table {k} diverged (evict order)")
        assert tot_full > 0, "flood never saturated — the pin is vacuous"


# --------------------------------------------------------------------------- #
# Integration: the device-RSS engine behind the pipeline
# --------------------------------------------------------------------------- #
class TestDeviceRSSEngine:
    def test_device_parity_vs_steered_and_oracle(self):
        """The acceptance bar: the same submission stream through the
        host-steered 4-shard mesh and the device-RSS 4-shard mesh is
        bit-identical — and both match the oracle-backed serial path —
        including CT continuity in both directions across drained
        phases."""
        serial = fake_serial_engine()
        host = jit_pipeline_engine(4)
        dev = jit_pipeline_engine(4, rss_mode="device")
        slot_of = serial.active.snapshot.ep_slot_of
        try:
            assert dev.datapath.rss_state == {
                "mode": "device", "shards": 4, "active": True}
            assert dev.datapath.pipeline_shards == 1   # no pre-steering
            ch1 = _mk_phase(slot_of, 5, (1, 5, 17, 9, 23), seed=21)
            _run_phase(serial, [host, dev], ch1, now0=1000)
            est = [pkt("192.168.1.10", "10.0.2.7", 48200 + i, 443)
                   for i in range(4)]
            _run_phase(serial, [host, dev],
                       [batch_from_records(est, slot_of)], now0=1200)
            reply = [pkt("10.0.2.7", "192.168.1.10", 443, 48200 + i,
                         flags=C.TCP_ACK, direction=C.DIR_INGRESS)
                     for i in range(4)]
            outs = _run_phase(
                serial, [host, dev],
                [batch_from_records(reply, slot_of, pad_to=6)], now0=1210)
            assert (np.asarray(outs[0]["status"])[:4]
                    == int(C.CTStatus.REPLY)).all()
            live = serial.ct_stats(now=1500)["live"]
            assert host.ct_stats(now=1500)["live"] == live
            assert dev.ct_stats(now=1500)["live"] == live
            # the device path staged unsharded, packed in place, never
            # paid an allocating steer, never shed
            ps = dev.pipeline_stats()
            assert ps["n_shards"] == 1 and ps["mesh_shards"] == 4
            assert ps["rss_mode"] == "device"
            assert ps["shed_total"] == 0
            assert dev.datapath.pack_stats["pack_fallback_steered"] == 0
            assert dev.datapath.pack_stats["pack_inplace"] > 0
        finally:
            for e in (serial, host, dev):
                e.stop()

    def test_sync_classify_pads_arbitrary_row_counts(self):
        """Control-plane entries (health probes, CLI classify) arrive at
        arbitrary sizes: the device path pads to an equal pow2 per-chip
        slice and truncates on finalize — verdicts match the oracle."""
        serial = fake_serial_engine()
        dev = jit_pipeline_engine(4, rss_mode="device")
        slot_of = serial.active.snapshot.ep_slot_of
        try:
            odd = batch_from_records(
                [pkt("192.168.1.10", f"10.1.9.{i + 1}", 51000 + i, 443)
                 for i in range(5)], slot_of)
            o1 = serial.classify(dict(odd), now=1600)
            o2 = dev.classify(dict(odd), now=1600)
            assert o2["allow"].shape[0] == 5    # padding truncated
            for k in ("allow", "reason", "status", "remote_identity"):
                np.testing.assert_array_equal(o1[k], o2[k], err_msg=k)
        finally:
            serial.stop()
            dev.stop()

    def test_skewed_and_alternating_arrivals_no_shed(self):
        """The arrival patterns host steering sheds (steer_overflow) or
        serializes on: every valid row hashing to ONE CT shard, and a
        strict alternating two-shard pattern — through the device path
        nothing sheds, no steer_overflow class exists, and verdicts match
        the bounded oracle bit-for-bit."""
        from cilium_tpu.parallel.mesh import flow_shard_of
        serial = fake_serial_engine()
        dev = jit_pipeline_engine(4, rss_mode="device")
        slot_of = serial.active.snapshot.ep_slot_of
        n_shards = 4
        try:
            # rejection-sample flows by their REAL steer hash
            by_shard = {s: [] for s in range(n_shards)}
            rng = np.random.default_rng(5)
            while min(len(v) for v in by_shard.values()) < 24:
                recs = [pkt("192.168.1.10",
                            f"10.{rng.integers(0, 2)}.2."
                            f"{rng.integers(1, 250)}",
                            int(42000 + rng.integers(0, 20000)), 443)
                        for _ in range(64)]
                b = batch_from_records(recs, slot_of)
                sh = flow_shard_of(b, n_shards)
                for i, s in enumerate(sh):
                    by_shard[int(s)].append(recs[i])
            # all-rows-one-shard x2 waves, then alternating-shard
            chunks = [batch_from_records(by_shard[0][:24], slot_of),
                      batch_from_records(by_shard[0][24:48]
                                         or by_shard[0][:24], slot_of)]
            alt = [r for pair in zip(by_shard[1][:16], by_shard[2][:16])
                   for r in pair]
            chunks.append(batch_from_records(alt, slot_of))
            _run_phase(serial, [dev], chunks, now0=3000)
            ps = dev.pipeline_stats()
            assert ps["shed_total"] == 0
            assert "steer_overflow" not in ps["shed_reasons"]
        finally:
            serial.stop()
            dev.stop()

    def test_cfg6_form_storm_matches_bounded_oracle(self):
        """A cfg6-form randomized-source SYN/junk storm through the
        device path: no shed class fires and every verdict matches the
        bounded oracle bit-for-bit (CT kept un-saturated so the
        single-table oracle and the sharded mesh agree on placement)."""
        serial = fake_serial_engine()
        dev = jit_pipeline_engine(4, rss_mode="device")
        slot_of = serial.active.snapshot.ep_slot_of
        rng = np.random.default_rng(17)
        try:
            chunks = []
            for c in range(6):
                recs = []
                for r in range(48):
                    proto = int(rng.choice(
                        [C.PROTO_TCP, C.PROTO_TCP, C.PROTO_UDP]))
                    recs.append(pkt(
                        "192.168.1.10",
                        f"10.{rng.integers(0, 2)}.{rng.integers(0, 250)}"
                        f".{rng.integers(1, 250)}",
                        int(1024 + rng.integers(0, 60000)),
                        int(rng.choice([443, 80, 53, 22])), proto=proto,
                        flags=C.TCP_SYN if proto == C.PROTO_TCP else 0))
                chunks.append(batch_from_records(recs, slot_of,
                                                 pad_to=48 + (c % 3)))
            _run_phase(serial, [dev], chunks, now0=4000)
            ps = dev.pipeline_stats()
            assert ps["shed_total"] == 0 and ps["admission_drops"] == 0
        finally:
            serial.stop()
            dev.stop()

    def test_regen_between_stage_and_dispatch_plain_stamp_check(self):
        """The steer-revision fence satellite: with device RSS active, a
        policy regen landing between stage-write and dispatch must NOT
        trip the re-steer logic (there is nothing to re-steer — rows
        carry no placement) — the fence degrades to the plain revision
        stamp check (ep-slot remap + the StalePlacement retry), and the
        batch classifies correctly under the NEW snapshot."""
        dev = jit_pipeline_engine(4, rss_mode="device",
                                  pipeline_flush_ms=250.0)
        slot_of = dev.active.snapshot.ep_slot_of
        try:
            b = batch_from_records(
                [pkt("192.168.1.10", "10.1.77.1", 45001, 443)], slot_of)
            t = dev.submit(dict(b), now=5000)     # parks in staging 250ms
            # regen lands while staged: the delta patch donates the old
            # placed handle — dispatch must retry via the stamp check,
            # never attempt a re-steer
            dev.apply_policy([{
                "endpointSelector": {"matchLabels": {"app": "web"}},
                "egressDeny": [{"toCIDR": ["10.1.77.0/24"]}],
            }])
            dev.regenerate()
            assert dev.drain(timeout=60)
            out = t.result(timeout=10)
            # the new deny applied: classified under the post-regen world
            assert not out["allow"][0]
            assert out["reason"][0] == int(C.DropReason.POLICY_DENY)
            # no steered fallback ran — there is no steering to redo
            assert dev.datapath.pack_stats["pack_fallback_steered"] == 0
        finally:
            dev.stop()

    def test_ledger_and_gauge_surfaces(self):
        """Satellites: the exchange buffers register in the resource
        ledger (+ the HBM ledger's ``exchange`` group), the unsteered
        staging ring keeps its ring row, and the steer-balance gauges /
        steer_overflow shed class are ABSENT from the export rather than
        frozen at zero."""
        dev = jit_pipeline_engine(4, rss_mode="device")
        slot_of = dev.active.snapshot.ep_slot_of
        try:
            t = dev.submit(batch_from_records(
                [pkt("192.168.1.10", "10.0.2.3", 40000, 443)], slot_of),
                now=100)
            assert dev.drain(timeout=30)
            t.result(timeout=5)
            dev.resource_step()
            rep = dev.resources()
            assert "rss_exchange" in rep["resources"]
            assert "staging_ring" in rep["resources"]
            # steered-only row must not exist on an unsharded ring
            assert "staging_segment_peak" not in rep["resources"]
            ex = dev.datapath.rss_exchange_stats()
            assert ex["in_use"] > 0 and ex["capacity"] >= ex["peak"] > 0
            assert dev.datapath.hbm_ledger()["groups"]["exchange"] > 0
            text = dev.render_metrics()
            assert "ciliumtpu_pipeline_mesh_shards 4" in text
            assert 'pipeline_staged_rows{shard=' not in text
            assert "steer_overflow" not in text
            h = dev.health()
            assert h["pipeline"]["shards"] == 4
            assert h["pipeline"]["rss_mode"] == "device"
            from cilium_tpu.runtime.api import status_doc
            assert status_doc(dev)["rss"]["mode"] == "device"
        finally:
            dev.stop()

    def test_audit_clean_at_sampling_one(self):
        """The shadow-oracle auditor at sampling 1.0 over the device
        path: every finalized batch replays clean against the oracle —
        the ISSUE's parity bar with steering off."""
        dev = jit_pipeline_engine(4, rss_mode="device",
                                  audit_enabled=True, audit_sample_rate=1.0)
        slot_of = dev.active.snapshot.ep_slot_of
        try:
            chunks = _mk_phase(slot_of, 4, (7, 13, 5, 22), seed=31)
            for i, ch in enumerate(chunks):
                dev.submit(dict(ch), now=6000 + i)
            assert dev.drain(timeout=60)
            dev.audit_step()
            st = dev.auditor.stats()
            assert st["checked_rows"] > 0
            assert st["mismatched_rows"] == 0, list(dev.auditor.mismatches)
            assert st["replay_errors"] == 0
        finally:
            dev.stop()

    def test_min_bucket_clamped_to_mesh(self):
        """Buckets must divide the mesh's flow axis: an engine configured
        with a min bucket below the shard count clamps it up."""
        dev = jit_pipeline_engine(8, rss_mode="device",
                                  pipeline_min_bucket=1)
        slot_of = dev.active.snapshot.ep_slot_of
        try:
            t = dev.submit(batch_from_records(
                [pkt("192.168.1.10", "10.0.2.3", 40001, 443)], slot_of),
                now=100)
            assert dev.drain(timeout=30)
            assert t.result(timeout=5)["allow"].shape[0] == 1
            assert dev._pipeline.min_bucket >= 8
        finally:
            dev.stop()


class TestPipelineRSSValidation:
    def test_device_mode_refuses_sharded_staging(self):
        with pytest.raises(ValueError, match="rss_mode='device'"):
            Pipeline(lambda b, n: (lambda: {}), n_shards=4,
                     shard_fn=lambda b: np.zeros(1), rss_mode="device")

    def test_bad_rss_mode_rejected(self):
        with pytest.raises(ValueError, match="bad rss_mode"):
            Pipeline(lambda b, n: (lambda: {}), rss_mode="bogus")
        with pytest.raises(ValueError, match="bad rss_mode"):
            DaemonConfig(rss_mode="bogus")


# --------------------------------------------------------------------------- #
# Slow soak (`make chaos`): 10k skewed rows through the device mesh
# --------------------------------------------------------------------------- #
@pytest.mark.slow
class TestDeviceRSSSoak:
    def test_soak_10k_skewed_device(self):
        """10k rows whose flows ALL hash to one CT shard — the storm that
        breaks host steering structurally (one segment serializes the
        mesh; past headroom it sheds steer_overflow) — through the
        device-RSS 4-shard mesh: every submission resolves, nothing
        sheds, the guard never restarts, and the CT table holds exactly
        the unique flows."""
        from cilium_tpu.parallel.mesh import flow_shard_of
        dev = jit_pipeline_engine(4, rss_mode="device", batch_size=256,
                                  ct_capacity=1 << 15,
                                  pipeline_queue_batches=256,
                                  pipeline_flush_ms=0.5)
        slot_of = dev.active.snapshot.ep_slot_of
        try:
            # build one shard-0-only pool of flows, then stream 10k rows
            pool = []
            rng = np.random.default_rng(77)
            while len(pool) < 2048:
                recs = [pkt("192.168.1.10",
                            f"10.{rng.integers(0, 2)}."
                            f"{rng.integers(0, 250)}.{rng.integers(1, 250)}",
                            int(1024 + rng.integers(0, 60000)), 443)
                        for _ in range(256)]
                b = batch_from_records(recs, slot_of)
                sh = flow_shard_of(b, 4)
                pool.extend(r for r, s in zip(recs, sh) if s == 0)
            tickets = []
            n_rows = 0
            i = 0
            while n_rows < 10_000:
                take = pool[(i * 64) % len(pool):][:64] or pool[:64]
                tickets.append(dev.submit(
                    batch_from_records(take, slot_of), now=7000 + i))
                n_rows += len(take)
                i += 1
            assert dev.drain(timeout=300)
            for t in tickets:
                t.result(timeout=10)
            ps = dev.pipeline_stats()
            assert ps["shed_total"] == 0
            assert ps["restarts"] == 0
            assert ps["state"] == "ok"
            assert dev.datapath.pack_stats["pack_fallback_steered"] == 0
        finally:
            dev.stop()


# --------------------------------------------------------------------------- #
# Degraded survivor geometry under device-side RSS (ISSUE 19): the n-1
# ring exchange is the same verdict machine, just narrower
# --------------------------------------------------------------------------- #
class TestDeviceRSSDegradedMesh:
    @pytest.mark.slow
    def test_device_rss_n_minus_1_parity_and_audit_clean(self):
        """Both rss modes shrink 4 -> 3 BEFORE any traffic; the degraded
        device-RSS mesh (ppermute ring over 3 chips) must stay
        bit-identical to the degraded host-steered mesh and to the
        oracle-backed serial path — including CT continuity in both
        directions — with the shadow auditor at sampling 1.0 clean on
        the device engine."""
        FAULTS.reset()
        serial = fake_serial_engine()
        host = jit_pipeline_engine(4)
        dev = jit_pipeline_engine(4, rss_mode="device",
                                  audit_enabled=True,
                                  audit_sample_rate=1.0,
                                  audit_pool_batches=64)
        dev.auditor.configure(sample_rate=1.0)
        slot_of = serial.active.snapshot.ep_slot_of
        try:
            for eng in (host, dev):
                eng.datapath.note_device_loss(2, reason="drill")
                doc = eng.remesh_step()
                assert doc["remesh"]["to"] == 3
            assert dev.datapath.rss_state["shards"] == 3
            assert dev.datapath.pipeline_shards == 1   # no pre-steering
            assert host.datapath.pipeline_shards == 3

            ch1 = _mk_phase(slot_of, 4, (1, 5, 17, 9), seed=91)
            _run_phase(serial, [host, dev], ch1, now0=3000)
            est = [pkt("192.168.1.10", "10.0.2.7", 49500 + i, 443)
                   for i in range(4)]
            _run_phase(serial, [host, dev],
                       [batch_from_records(est, slot_of)], now0=3200)
            reply = [pkt("10.0.2.7", "192.168.1.10", 443, 49500 + i,
                         flags=C.TCP_ACK, direction=C.DIR_INGRESS)
                     for i in range(4)]
            outs = _run_phase(
                serial, [host, dev],
                [batch_from_records(reply, slot_of, pad_to=6)],
                now0=3210)
            assert (np.asarray(outs[0]["status"])[:4]
                    == int(C.CTStatus.REPLY)).all()

            live = serial.ct_stats(now=4000)["live"]
            assert host.ct_stats(now=4000)["live"] == live
            assert dev.ct_stats(now=4000)["live"] == live
            for _ in range(100):
                step = dev.audit_step(budget=128)
                if not step or (not step.get("replayed")
                                and not step.get("pending")):
                    break
            st = dev.auditor.stats()
            assert st["checked_rows"] > 0
            assert st["mismatched_rows"] == 0
        finally:
            for eng in (serial, host, dev):
                eng.stop()
