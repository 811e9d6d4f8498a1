"""Multi-chip tests on the 8-fake-device CPU mesh (SURVEY.md §4: the standard
JAX idiom for testing shard_map without TPUs): steering invariants, DP
classify parity vs single-device, rule-axis sharding parity."""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cilium_tpu.compile.ct_layout import CTConfig, make_ct_arrays
from cilium_tpu.compile.snapshot import build_snapshot
from cilium_tpu.kernels.classify import classify_step
from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.parallel.mesh import (
    flow_shard_of, make_mesh, make_sharded_classify_fn, pad_snapshot_tensors,
    steer_batch, unsteer_outputs,
)
from cilium_tpu.utils import constants as C
from tests.test_parity import (
    build_world, extract_device_ct, oracle_live_ct, random_packet,
)
from oracle import Oracle


@pytest.fixture(scope="module")
def world():
    ctx, repo, eps = build_world()
    snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=4096))
    return ctx, snap


class TestSteering:
    def test_directions_agree(self, world):
        ctx, snap = world
        rng = random.Random(3)
        packets = [random_packet(rng, []) for _ in range(64)]
        fwd = batch_from_records(packets, snap.ep_slot_of)
        # reversed packets: swap addrs/ports, flip direction
        rev = dict(fwd)
        rev = {k: v.copy() for k, v in fwd.items()}
        rev["src"], rev["dst"] = fwd["dst"].copy(), fwd["src"].copy()
        rev["sport"], rev["dport"] = fwd["dport"].copy(), fwd["sport"].copy()
        rev["direction"] = 1 - fwd["direction"]
        np.testing.assert_array_equal(flow_shard_of(fwd, 4),
                                      flow_shard_of(rev, 4))

    @pytest.mark.parametrize("n_shards", [2, 4, 8])
    def test_uniform_flows_leave_no_shard_idle(self, world, n_shards):
        """A steering fault that parks the work on one chip hides inside
        every aggregate: over 64 distinct random flows a shard, no shard is
        idle and none carries more than three times its fair share (capped
        at 0.95 so that the check stays live on two shards)."""
        ctx, snap = world
        rng = random.Random(11)
        packets = [random_packet(rng, []) for _ in range(64 * n_shards)]
        batch = batch_from_records(packets, snap.ep_slot_of)
        rows = np.bincount(flow_shard_of(batch, n_shards)[batch["valid"]],
                           minlength=n_shards)
        assert rows.min() > 0, rows
        assert rows.max() / rows.sum() <= min(0.95, 3 / n_shards), rows

    def test_steer_roundtrip(self, world):
        ctx, snap = world
        rng = random.Random(4)
        packets = [random_packet(rng, []) for _ in range(50)]
        batch = batch_from_records(packets, snap.ep_slot_of, pad_to=64)
        steered, scatter, per = steer_batch(batch, 4)
        # every valid packet lands in its shard's region
        shard = flow_shard_of(batch, 4)
        for i in range(64):
            if batch["valid"][i]:
                assert steered["valid"][scatter[i]]
                assert scatter[i] // per == shard[i]
        # fake outputs roundtrip
        out = {"x": np.arange(steered["valid"].shape[0], dtype=np.int64)}
        back = unsteer_outputs(out, scatter)
        for i in range(64):
            if batch["valid"][i]:
                assert back["x"][i] == scatter[i]


def _run_mesh_parity(n_flow, n_rule, seed=5, n_batches=4, batch=96):
    """Sharded classify over the mesh vs the oracle."""
    rng = random.Random(seed)
    ctx, repo, eps = build_world()
    cap = 4096
    snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=cap))
    mesh = make_mesh(n_flow, n_rule)
    tensors_np = pad_snapshot_tensors(snap.tensors(), n_rule)
    tensors = {k: jnp.asarray(v) for k, v in tensors_np.items()}
    ct = {k: jnp.asarray(v) for k, v in
          make_ct_arrays(CTConfig(capacity=cap)).items()}
    fn = make_sharded_classify_fn(mesh, donate_ct=False)
    oracle = Oracle(dict(zip(snap.ep_ids, snap.policies)),
                    ctx.ipcache.snapshot())
    prior = []
    now = 1000
    for bi in range(n_batches):
        packets = [random_packet(rng, prior) for _ in range(batch)]
        want = oracle.classify_batch_snapshot(packets, now)
        raw = batch_from_records(packets, snap.ep_slot_of)
        steered, scatter, per = steer_batch(raw, n_flow, per_shard=batch)
        dev_batch = {k: jnp.asarray(v) for k, v in steered.items()}
        out, ct, counters = fn(tensors, ct, dev_batch, jnp.uint32(now),
                               jnp.int32(snap.world_index))
        out_np = unsteer_outputs({k: np.asarray(v) for k, v in out.items()},
                                 scatter)
        for i, v in enumerate(want):
            assert bool(out_np["allow"][i]) == v.allow, (n_flow, n_rule, bi, i)
            assert int(out_np["reason"][i]) == int(v.drop_reason), \
                (n_flow, n_rule, bi, i)
            assert int(out_np["status"][i]) == int(v.ct_status), \
                (n_flow, n_rule, bi, i)
        # device CT across all shards == oracle live entries
        assert extract_device_ct(ct, now) == oracle_live_ct(oracle, now)
        # counters replicated + correct total
        by = np.asarray(counters["by_reason_dir"]).reshape(256, 2)
        n_valid = sum(1 for p in packets)
        assert int(by.sum()) == n_valid
        prior.extend(p for p, v in zip(packets, want)
                     if v.allow and v.ct_status == C.CTStatus.NEW)
        prior = prior[-150:]
        now += 40


class TestMeshParity:
    def test_dp_4x1(self):
        _run_mesh_parity(4, 1)

    def test_dp_8x1(self):
        _run_mesh_parity(8, 1, seed=6)

    def test_rule_sharded_1x8(self):
        _run_mesh_parity(1, 8, seed=7)

    def test_combined_4x2(self):
        _run_mesh_parity(4, 2, seed=8)


# --------------------------------------------------------------------------- #
# Production path: Engine + JITDatapath honoring n_shards/rule_shards
# (round-4 verdict item 1: the mesh must be reachable from the Engine, not
# just the dryrun). Runs on the conftest-provisioned 8-fake-device CPU mesh.
# --------------------------------------------------------------------------- #
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath, JITDatapath
from cilium_tpu.parallel.mesh import rehash_ct_arrays
from tests.test_datapath import TRAFFIC, fixture_engine


def _sharded_cfg(**kw):
    base = dict(ct_capacity=2048, auto_regen=False, n_shards=4,
                rule_shards=2)
    base.update(kw)
    return DaemonConfig(**base)


class TestShardedEngine:
    def test_engine_sharded_parity_vs_fake(self):
        """DaemonConfig(n_shards=4, rule_shards=2) engine serves through the
        mesh and produces verdicts identical to the oracle-backed fake —
        including CT continuity across batches (flow→shard steering must be
        direction-stable)."""
        eng_mesh = fixture_engine(JITDatapath(_sharded_cfg()))
        eng_fake = fixture_engine(FakeDatapath(DaemonConfig(ct_capacity=2048)))
        slots = eng_mesh.active.snapshot.ep_slot_of
        assert slots == eng_fake.active.snapshot.ep_slot_of
        now = 1000
        for rep in range(3):          # repeats exercise ESTABLISHED via CT
            batch = batch_from_records(TRAFFIC, slots)
            out_m = eng_mesh.classify(dict(batch), now=now + rep * 5)
            out_f = eng_fake.classify(dict(batch), now=now + rep * 5)
            for k in ("allow", "reason", "status", "remote_identity",
                      "redirect", "svc", "rnat"):
                np.testing.assert_array_equal(
                    np.asarray(out_f[k]), np.asarray(out_m[k]), (rep, k))
        assert (np.asarray(out_m["status"])[0] == C.CTStatus.ESTABLISHED)
        assert eng_mesh.ct_stats(now) == eng_fake.ct_stats(now)

    def test_engine_sharded_random_traffic_parity(self):
        """Random mixed traffic (both directions, replies of prior flows)
        through the meshed engine == fake engine, multiple batches."""
        rng = random.Random(11)
        eng_mesh = fixture_engine(JITDatapath(_sharded_cfg()))
        eng_fake = fixture_engine(FakeDatapath(DaemonConfig(ct_capacity=2048)))
        slots = eng_mesh.active.snapshot.ep_slot_of
        prior = []
        now = 2000
        for bi in range(4):
            packets = [random_packet(rng, prior) for _ in range(100)]
            batch = batch_from_records(packets, slots)
            out_m = eng_mesh.classify(dict(batch), now=now)
            out_f = eng_fake.classify(dict(batch), now=now)
            for k in ("allow", "reason", "status", "remote_identity"):
                np.testing.assert_array_equal(
                    np.asarray(out_f[k]), np.asarray(out_m[k]), (bi, k))
            prior.extend(p for i, p in enumerate(packets)
                         if out_f["allow"][i]
                         and out_f["status"][i] == C.CTStatus.NEW)
            prior = prior[-120:]
            now += 30

    def test_ct_checkpoint_across_shard_layouts(self):
        """CT exported from a sharded backend restores into a single-chip
        backend and vice versa: flows stay ESTABLISHED (rehash_ct_arrays
        re-places entries for the importing geometry)."""
        eng_mesh = fixture_engine(JITDatapath(_sharded_cfg()))
        slots = eng_mesh.active.snapshot.ep_slot_of
        batch = batch_from_records(TRAFFIC, slots)
        out0 = eng_mesh.classify(dict(batch), now=1000)
        live = eng_mesh.ct_stats(1000)["live"]
        assert live > 0
        arrays = eng_mesh.ct_arrays()

        # mesh → single chip
        eng_one = fixture_engine(JITDatapath(DaemonConfig(
            ct_capacity=2048, auto_regen=False)))
        eng_one.load_ct_arrays(arrays)
        assert eng_one.ct_stats(1000)["live"] == live
        out1 = eng_one.classify(dict(batch), now=1005)
        allowed = np.asarray(out0["allow"])
        assert (np.asarray(out1["status"])[allowed]
                == C.CTStatus.ESTABLISHED).all()

        # single chip → mesh (different flow-shard count: 2)
        arrays1 = eng_one.ct_arrays()
        eng_mesh2 = fixture_engine(JITDatapath(_sharded_cfg(n_shards=2,
                                                            rule_shards=1)))
        eng_mesh2.load_ct_arrays(arrays1)
        out2 = eng_mesh2.classify(dict(batch), now=1010)
        assert (np.asarray(out2["status"])[allowed]
                == C.CTStatus.ESTABLISHED).all()

    def test_rehash_preserves_entries(self):
        """rehash round trip: every live entry survives (ample probe room)
        and lands where the importing geometry's probe expects it —
        asserted behaviorally above, structurally here."""
        rng = np.random.default_rng(5)
        from cilium_tpu.compile.ct_layout import (
            CTConfig, logical_ct_arrays, make_ct_arrays)
        arrays = logical_ct_arrays(make_ct_arrays(CTConfig(capacity=1024)))
        n = 200
        arrays["keys"][:n] = rng.integers(0, 2**32, (n, 10), dtype=np.uint32)
        arrays["keys"][:n, 9] = (arrays["keys"][:n, 9] & ~np.uint32(0xFF)) \
            | (arrays["keys"][:n, 9] & 1)          # direction ∈ {0,1}
        arrays["expiry"][:n] = 5000
        arrays["pkts_fwd"][:n] = np.arange(n)
        re4, dropped = rehash_ct_arrays(arrays, 4)
        assert dropped == 0
        assert int((re4["expiry"] > 0).sum()) == n
        # entry payloads survive keyed by key (slots differ)
        src = {tuple(arrays["keys"][i]): int(arrays["pkts_fwd"][i])
               for i in range(n)}
        for s in np.nonzero(re4["expiry"] > 0)[0]:
            assert src[tuple(re4["keys"][s])] == int(re4["pkts_fwd"][s])
