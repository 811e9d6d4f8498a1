"""Fused Pallas classify megakernel tests (kernels/fused.py, ISSUE 8).

The contract: with ``fused_kernels=on`` the Pallas interior (LPM stride
walk, fused CT probe pair, policy+L7+verdict kernel) must be bit-identical
to the jnp reference AND to the semantics oracle — outputs, CT state and
counters — in interpret mode on CPU (the tier-1 configuration; compiled
Pallas on a real TPU runs the same kernel bodies). Coverage:

- per-kernel unit parity (fused vs jnp vs the host reference walk),
  including the property-fuzz LPM suite over random v4/v6 prefix sets
  (ROADMAP item 4c seed: the 16-level v6 walk, 100k prefixes slow-marked)
  and the ROW_BLOCK grid path;
- ``ct_key_words_pair`` word-derivation identity (the shared-hashing
  satellite — it feeds the jnp fallback path too);
- the full end-to-end parity suite (tests/test_parity.run_parity) rerun
  with the fused interior, plus fused-vs-jnp bit-identity on outputs, CT
  and counters with per-stage fallback forced through the fuse_plan
  budget;
- ``make_classify_fn`` memoization (repeated snapshot placements must not
  re-trace identical static configs);
- serving integration: engine classify, pipelined submissions, a 1-shard
  vs 4-shard mesh, and the shadow-oracle auditor (PR 7) — all with
  ``fused_kernels=on`` — plus the ``datapath.compute`` span's ``fused``
  executor tag.
"""

import random
import time

import numpy as np
import pytest

import jax.numpy as jnp

from cilium_tpu.compile.ct_layout import CTConfig, make_ct_arrays
from cilium_tpu.compile.lpm import build_lpm, lpm_lookup_host
from cilium_tpu.compile.snapshot import build_snapshot
from cilium_tpu.kernels import conntrack as ctk
from cilium_tpu.kernels import fused as fk
from cilium_tpu.kernels.classify import (classify_interior_core,
                                         classify_step, make_classify_fn)
from cilium_tpu.kernels.lpm import lpm_lookup_batch
from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import (FakeDatapath, JITDatapath,
                                         resolve_fused)
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr

from tests.test_parity import build_world, random_packet, run_parity

FUSED_KW = {"fused": True, "fused_interpret": True}


def _assert_tree_equal(a, b, ctx=""):
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=f"{ctx}:{k}")


# --------------------------------------------------------------------------- #
# LPM: property-fuzz parity over random prefix sets (jnp + fused vs the
# host reference walk — which model.ipcache pins to oracle semantics)
# --------------------------------------------------------------------------- #
def _random_prefix_set(rng, n_v4, n_v6, max_ident=50):
    entries = {}
    for _ in range(n_v4):
        plen = int(rng.choice([8, 12, 16, 20, 24, 28, 32]))
        addr = rng.integers(0, 1 << 32) & ((0xFFFFFFFF << (32 - plen))
                                           & 0xFFFFFFFF)
        prefix = (f"{(addr >> 24) & 0xFF}.{(addr >> 16) & 0xFF}."
                  f"{(addr >> 8) & 0xFF}.{addr & 0xFF}/{plen}")
        entries[prefix] = int(rng.integers(1, max_ident))
    for _ in range(n_v6):
        plen = int(rng.choice([16, 32, 48, 56, 64, 96, 128]))
        words = [int(rng.integers(0, 1 << 16)) for _ in range(8)]
        addr = ":".join(f"{w:x}" for w in words)
        entries[f"{addr}/{plen}"] = int(rng.integers(1, max_ident))
    return entries


def _fuzz_addresses(rng, entries, n):
    """Half the probe addresses land inside random prefixes from the set
    (bit-match pressure on every level), half are uniform random."""
    probes = []
    keys = list(entries)
    for i in range(n):
        if keys and i % 2 == 0:
            prefix = keys[int(rng.integers(0, len(keys)))]
            addr_s, plen_s = prefix.rsplit("/", 1)
            a16, is_v6 = parse_addr(addr_s)
            raw = bytearray(a16)
            plen = int(plen_s) + (0 if is_v6 else 96)
            for bit in range(plen, 128):      # randomize the host bits
                if rng.integers(0, 2):
                    raw[bit // 8] |= 1 << (7 - bit % 8)
                else:
                    raw[bit // 8] &= ~(1 << (7 - bit % 8))
            if not is_v6:                     # keep the v4-mapped prelude
                raw[:12] = a16[:12]
            probes.append((bytes(raw), is_v6))
        else:
            is_v6 = bool(rng.integers(0, 2))
            if is_v6:
                probes.append((rng.integers(0, 256, 16, dtype=np.uint8)
                               .tobytes(), True))
            else:
                probes.append((b"\x00" * 10 + b"\xff\xff"
                               + rng.integers(0, 256, 4, dtype=np.uint8)
                               .tobytes(), False))
    return probes


def _lpm_parity(entries, probes, default_index=0):
    from cilium_tpu.compile.lpm import lpm_lookup_host_prov
    from cilium_tpu.kernels.lpm import lpm_lookup_prov_batch
    idents = sorted(set(entries.values()))
    identity_index = {i: n for n, i in enumerate(idents)}
    tables = build_lpm(entries, identity_index, default_index)
    want = np.asarray([lpm_lookup_host(tables, a, v6) for a, v6 in probes],
                      dtype=np.int32)
    want_meta = np.asarray(
        [lpm_lookup_host_prov(tables, a, v6)[1] for a, v6 in probes],
        dtype=np.int32)
    addr = np.stack([np.frombuffer(a, dtype=">u4").astype(np.uint32)
                     for a, _ in probes])
    is_v6 = np.asarray([v6 for _, v6 in probes])
    v4n, v6n = jnp.asarray(tables.v4_placed), jnp.asarray(tables.v6_placed)
    got_jnp, got_jnp_meta = lpm_lookup_prov_batch(
        v4n, v6n, jnp.asarray(addr), jnp.asarray(is_v6), default_index)
    got_fused, got_fused_meta = fk.lpm_lookup_fused(
        v4n, v6n, jnp.asarray(addr), jnp.asarray(is_v6), default_index,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got_jnp), want,
                                  "jnp walk != host walk")
    np.testing.assert_array_equal(np.asarray(got_fused), want,
                                  "fused walk != host walk")
    # match provenance ((slot<<8)|plen) rides the same walk: all three
    # executors must name the same winning prefix
    np.testing.assert_array_equal(np.asarray(got_jnp_meta), want_meta,
                                  "jnp provenance != host provenance")
    np.testing.assert_array_equal(np.asarray(got_fused_meta), want_meta,
                                  "fused provenance != host provenance")
    if not is_v6.any():
        got4, got4_meta = fk.lpm_lookup_fused(
            v4n, v6n, jnp.asarray(addr), jnp.asarray(is_v6), default_index,
            v4_only=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(got4), want,
                                      "fused v4_only != host")
        np.testing.assert_array_equal(np.asarray(got4_meta), want_meta,
                                      "fused v4_only provenance != host")


class TestLPMFuzzParity:
    @pytest.mark.parametrize("seed", range(4))
    def test_random_mixed_family_sets(self, seed):
        rng = np.random.default_rng(seed)
        entries = _random_prefix_set(rng, n_v4=120, n_v6=80)
        probes = _fuzz_addresses(rng, entries, 256)
        _lpm_parity(entries, probes, default_index=int(rng.integers(0, 5)))

    def test_v4_only_sets(self):
        rng = np.random.default_rng(9)
        entries = _random_prefix_set(rng, n_v4=200, n_v6=0)
        probes = _fuzz_addresses(
            rng, entries, 128)
        probes = [p for p in probes if not p[1]]
        _lpm_parity(entries, probes)

    def test_empty_table_resolves_default(self):
        _lpm_parity({}, _fuzz_addresses(np.random.default_rng(1), {}, 32),
                    default_index=7)

    def test_grid_block_path(self):
        """2048 probes → the ROW_BLOCK grid (2 blocks) must equal the
        single-block jnp result."""
        rng = np.random.default_rng(5)
        entries = _random_prefix_set(rng, n_v4=60, n_v6=40)
        probes = _fuzz_addresses(rng, entries, 2048)
        _lpm_parity(entries, probes)

    @pytest.mark.slow
    def test_v6_walk_at_100k_prefixes(self):
        """ROADMAP item 4c seed: the 16-level stride walk over a
        BGP-table-scale v6 set (100k distinct prefixes under a shared /32,
        bounding trie width like a real table's aggregation does)."""
        rng = np.random.default_rng(42)
        entries = {}
        while len(entries) < 100_000:
            b4, b5, b6 = (int(rng.integers(0, 256)),
                          int(rng.integers(0, 256)),
                          int(rng.integers(0, 256)))
            entries[f"2001:db8:{b4:02x}{b5:02x}:{b6:02x}00::/56"] = \
                int(rng.integers(1, 64))
        probes = _fuzz_addresses(rng, entries, 1024)
        probes = [p for p in probes if p[1]]
        _lpm_parity(entries, probes)


# --------------------------------------------------------------------------- #
# CT probe pair + key-pair derivation
# --------------------------------------------------------------------------- #
def _random_batch(rng, n, v6_frac=0.25):
    recs = []
    for i in range(n):
        v6 = rng.random() < v6_frac
        if v6:
            src, _ = parse_addr(f"2001:db8::{rng.randrange(1, 9999):x}")
            dst, _ = parse_addr(f"2001:db9::{rng.randrange(1, 9999):x}")
        else:
            src, _ = parse_addr(f"10.0.{rng.randrange(256)}.{rng.randrange(1, 255)}")
            dst, _ = parse_addr(f"10.1.{rng.randrange(256)}.{rng.randrange(1, 255)}")
        from oracle import PacketRecord
        recs.append(PacketRecord(
            src, dst, rng.randrange(1024, 65535), rng.randrange(1, 65535),
            rng.choice([C.PROTO_TCP, C.PROTO_UDP]), C.TCP_SYN, v6, 1,
            rng.choice([C.DIR_EGRESS, C.DIR_INGRESS])))
    return batch_from_records(recs, {1: 0})


class TestCtKeyPair:
    def test_pair_matches_two_sided_normalization(self):
        rng = random.Random(3)
        for trial in range(3):
            b = {k: jnp.asarray(v)
                 for k, v in _random_batch(rng, 64).items()}
            fwd, rev = ctk.ct_key_words_pair(b)
            np.testing.assert_array_equal(
                np.asarray(fwd),
                np.asarray(ctk.ct_key_words_jnp(b, reverse=False)))
            np.testing.assert_array_equal(
                np.asarray(rev),
                np.asarray(ctk.ct_key_words_jnp(b, reverse=True)))


class TestCtProbePairFused:
    def _populated_ct(self, rng, cap=1024, n_flows=300):
        ct = {k: jnp.asarray(v)
              for k, v in make_ct_arrays(CTConfig(capacity=cap)).items()}
        b = {k: jnp.asarray(v)
             for k, v in _random_batch(rng, n_flows).items()}
        keys = ctk.ct_key_words_jnp(b)
        want = jnp.ones((n_flows,), dtype=bool)
        new_keys, new_created, zero_mask, slot, _fail, _ev = ctk.ct_insert_new(
            ct, keys, want, jnp.uint32(100))
        ct = ctk.ct_apply(ct, b, slot, jnp.zeros((n_flows,), bool),
                          slot >= 0, jnp.uint32(100), new_keys=new_keys,
                          new_created=new_created, zero_mask=zero_mask)
        return ct, b

    def test_fused_pair_matches_two_probes(self):
        rng = random.Random(7)
        ct, seeded = self._populated_ct(rng)
        for trial, now in ((0, 110), (1, 10_000)):   # live + all-expired
            probe = {k: jnp.asarray(v)
                     for k, v in _random_batch(rng, 128).items()}
            # half the probe rows revisit seeded flows (hits both ways)
            mix = {k: jnp.concatenate([v[:64], seeded[k][:64]])
                   for k, v in probe.items()}
            fwd, rev = ctk.ct_key_words_pair(mix)
            want_f = ctk.ct_probe(ct, fwd, jnp.uint32(now))
            want_r = ctk.ct_probe(ct, rev, jnp.uint32(now))
            got_f, got_r = fk.ct_probe_pair_fused(
                ct, fwd, rev, jnp.uint32(now), probe_depth=8,
                interpret=True)
            np.testing.assert_array_equal(np.asarray(got_f),
                                          np.asarray(want_f), (trial, "fwd"))
            np.testing.assert_array_equal(np.asarray(got_r),
                                          np.asarray(want_r), (trial, "rev"))


# --------------------------------------------------------------------------- #
# policy + L7 + verdict kernel
# --------------------------------------------------------------------------- #
class TestPolicyVerdictFused:
    def test_kernel_matches_interior_core(self):
        rng = random.Random(11)
        ctx, repo, eps = build_world()
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
        prior = []
        for trial in range(3):
            packets = [random_packet(rng, prior) for _ in range(96)]
            b = {k: jnp.asarray(v) for k, v in
                 batch_from_records(packets, snap.ep_slot_of).items()}
            nrng = np.random.default_rng(trial)
            est = jnp.asarray(nrng.random(96) < 0.3)
            reply = jnp.asarray(~np.asarray(est)
                                & (nrng.random(96) < 0.2))
            id_idx = lpm_lookup_batch(
                tensors["lpm_v4"], tensors["lpm_v6"],
                jnp.where((b["direction"] == C.DIR_EGRESS)[:, None],
                          b["dst"], b["src"]),
                b["is_v6"], default_index=snap.world_index)
            args = (tensors, b["ep_slot"], b["direction"], id_idx,
                    b["proto"], b["dport"], b["http_method"],
                    b["http_path"], est, reply, b["valid"])
            want = classify_interior_core(*args)
            got = fk.policy_verdict_fused(*args, interpret=True)
            for name, w, g in zip(("allow", "reason", "status", "redirect",
                                   "matched_rule"),
                                  want, got):
                np.testing.assert_array_equal(np.asarray(w), np.asarray(g),
                                              (trial, name))
            prior.extend(packets)
            prior = prior[-80:]


# --------------------------------------------------------------------------- #
# full classify step: fused vs jnp vs oracle
# --------------------------------------------------------------------------- #
class TestFusedClassifyParity:
    @pytest.mark.parametrize("seed", range(2))
    def test_fused_oracle_parity(self, seed):
        """The end-to-end parity suite with the Pallas interior — verdicts,
        reasons, CT state all bit-identical to the semantics oracle."""
        run_parity(seed, n_batches=4, batch=80, classify_kwargs=FUSED_KW)

    def test_fused_vs_jnp_bit_identity(self):
        """Outputs, CT arrays AND counters bit-identical across a stateful
        multi-batch stream (v6 + L7 + CT revisits)."""
        rng = random.Random(5)
        ctx, repo, eps = build_world()
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=4096))
        tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
        mk = lambda: {k: jnp.asarray(v) for k, v in  # noqa: E731
                      make_ct_arrays(CTConfig(capacity=4096)).items()}
        ct_a, ct_b = mk(), mk()
        prior, now = [], 500
        for bi in range(4):
            packets = [random_packet(rng, prior) for _ in range(96)]
            b = {k: jnp.asarray(v) for k, v in
                 batch_from_records(packets, snap.ep_slot_of).items()}
            out_a, ct_a, cnt_a = classify_step(
                tensors, ct_a, b, jnp.uint32(now),
                world_index=snap.world_index)
            out_b, ct_b, cnt_b = classify_step(
                tensors, ct_b, b, jnp.uint32(now),
                world_index=snap.world_index, **FUSED_KW)
            _assert_tree_equal(out_a, out_b, f"out[{bi}]")
            _assert_tree_equal(ct_a, ct_b, f"ct[{bi}]")
            _assert_tree_equal(cnt_a, cnt_b, f"counters[{bi}]")
            prior.extend(packets)
            prior = prior[-100:]
            now += 40

    def test_fuse_plan_budget_gates_per_stage(self):
        """A geometry over the table budget falls back to the jnp
        reference PER STAGE (still bit-identical); the plan is a
        trace-time constant of the shapes."""
        ctx, repo, eps = build_world()
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
        ct = {k: jnp.asarray(v) for k, v in
              make_ct_arrays(CTConfig(capacity=1024)).items()}
        plan = fk.fuse_plan(tensors, ct)
        assert plan.lpm and plan.ct and plan.policy and plan.any
        tiny = fk.fuse_plan(tensors, ct, budget=1)
        assert not (tiny.lpm or tiny.ct or tiny.policy or tiny.any)
        # rule sharding pins the policy stage on the reference
        assert not fk.fuse_plan(tensors, ct, rule_axis="rules").policy
        # forced fallback still bit-identical through classify_step
        rng = random.Random(2)
        packets = [random_packet(rng, []) for _ in range(64)]
        b = {k: jnp.asarray(v) for k, v in
             batch_from_records(packets, snap.ep_slot_of).items()}
        old = fk.FUSED_TABLE_BYTES
        try:
            fk.FUSED_TABLE_BYTES = 1
            out_a, _, _ = classify_step(tensors, dict(ct), b,
                                        jnp.uint32(100),
                                        world_index=snap.world_index,
                                        **FUSED_KW)
        finally:
            fk.FUSED_TABLE_BYTES = old
        out_b, _, _ = classify_step(tensors, dict(ct), b, jnp.uint32(100),
                                    world_index=snap.world_index)
        _assert_tree_equal(out_a, out_b, "budget-fallback")


class TestMakeClassifyFnMemo:
    def test_same_static_config_shares_one_callable(self):
        a = make_classify_fn(8, False, donate_ct=False)
        assert a is make_classify_fn(8, False, donate_ct=False)
        assert a is not make_classify_fn(8, True, donate_ct=False)
        assert a is not make_classify_fn(8, False, donate_ct=False,
                                         packed=True)
        assert a is not make_classify_fn(8, False, donate_ct=False,
                                         fused=True, fused_interpret=True)
        assert a is not make_classify_fn(8, False, donate_ct=False,
                                         lb_probe_depth=4)


# --------------------------------------------------------------------------- #
# serving integration: selector, engine, pipeline, mesh, audit
# --------------------------------------------------------------------------- #
def _world(eng):
    from tests.test_datapath import FIXTURE_RULES
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.add_endpoint(["k8s:role=fe"], ips=("192.168.1.30",), ep_id=3)
    eng.apply_policy(FIXTURE_RULES)
    eng.regenerate()


def jit_engine(fused="on", **kw):
    kw.setdefault("ct_capacity", 2048)
    kw.setdefault("auto_regen", False)
    kw.setdefault("flowlog_mode", "none")
    kw.setdefault("batch_size", 128)
    kw.setdefault("pipeline_flush_ms", 1.0)
    cfg = DaemonConfig(fused_kernels=fused, **kw)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    _world(eng)
    return eng


def _chunks(eng, n_chunks=4, size=40, seed=3):
    from tests.test_sharded_pipeline import _mk_phase
    return _mk_phase(eng.active.snapshot.ep_slot_of, n_chunks,
                     (size, size + 9), seed)


class TestFusedSelector:
    def test_resolve_modes_on_cpu(self):
        assert resolve_fused(DaemonConfig(fused_kernels="off")) \
            == (False, False)
        assert resolve_fused(DaemonConfig(fused_kernels="auto")) \
            == (False, False)      # auto keeps the jnp reference off-TPU
        assert resolve_fused(DaemonConfig(fused_kernels="on")) \
            == (True, True)        # forced → interpret mode on CPU

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError):
            DaemonConfig(fused_kernels="yes")

    def test_backend_surfaces_state_and_status(self):
        eng = jit_engine("on")
        try:
            assert eng.datapath.fused_state == {
                "mode": "on", "active": True, "interpret": True,
                "plan": {"lpm": True, "ct": True, "policy": True}}
            from cilium_tpu.runtime.api import status_doc
            assert status_doc(eng)["fused_kernels"]["active"] is True
        finally:
            eng.stop()
        cfg = DaemonConfig()
        fake = Engine(cfg, datapath=FakeDatapath(cfg))
        try:
            from cilium_tpu.runtime.api import status_doc
            assert status_doc(fake)["fused_kernels"] is None
        finally:
            fake.stop()

    def test_compute_span_carries_executor_tag(self):
        eng = jit_engine("on", trace_sample_rate=1.0)
        try:
            ch = _chunks(eng, 1)[0]
            eng.classify(dict(ch), now=100)
            spans = [s for s in eng.tracer.spans(name="datapath.compute")
                     if s.get("attrs")]
            assert spans and spans[-1]["attrs"]["fused"] == 1
        finally:
            eng.stop()


class TestFusedServing:
    OUT_KEYS = ("allow", "reason", "status", "remote_identity", "redirect",
                "svc", "nat_dst", "nat_dport", "rnat", "rnat_src",
                "rnat_sport")

    def test_engine_classify_matches_reference(self):
        ref, fus = jit_engine("off"), jit_engine("on")
        try:
            for i, ch in enumerate(_chunks(ref, 5)):
                oa = ref.classify(dict(ch), now=100 + i)
                ob = fus.classify(dict(ch), now=100 + i)
                for k in self.OUT_KEYS:
                    np.testing.assert_array_equal(oa[k], ob[k], k)
        finally:
            ref.stop()
            fus.stop()

    def test_pipelined_fused_matches_pipelined_reference(self):
        """FIFO pipeline verdicts through the fused interior == the same
        submissions through the jnp-reference pipeline, bit-identical on
        every out column (zero-copy pack path included)."""
        ref, fus = jit_engine("off"), jit_engine("on")
        try:
            chunks = _chunks(ref, 6, size=30, seed=8)
            t_ref = [ref.submit(dict(ch), now=200 + i)
                     for i, ch in enumerate(chunks)]
            t_fus = [fus.submit(dict(ch), now=200 + i)
                     for i, ch in enumerate(chunks)]
            assert ref.drain(timeout=60) and fus.drain(timeout=60)
            for i, (ta, tb) in enumerate(zip(t_ref, t_fus)):
                want, got = ta.result(timeout=10), tb.result(timeout=10)
                for k in got:
                    np.testing.assert_array_equal(
                        got[k], want[k], err_msg=f"chunk {i}:{k}")
        finally:
            ref.stop()
            fus.stop()

    def test_sharded_mesh_fused_parity(self):
        """1-shard fused vs 4-shard fused pipelines bit-identical, and both
        equal to the oracle-backed serial path on the comparable keys —
        the sharded parity suite with the Pallas interior."""
        from tests.test_sharded_pipeline import (ORACLE_KEYS,
                                                 fake_serial_engine)
        serial = fake_serial_engine()
        one = jit_engine("on", n_shards=1)
        eight = jit_engine("on", n_shards=4)
        try:
            chunks = _chunks(one, 5, size=28, seed=13)
            want = [serial.classify(dict(ch), now=300 + i)
                    for i, ch in enumerate(chunks)]
            got = {}
            for eng in (one, eight):
                ts = [eng.submit(dict(ch), now=300 + i)
                      for i, ch in enumerate(chunks)]
                assert eng.drain(timeout=60)
                got[id(eng)] = [t.result(timeout=10) for t in ts]
                for i, g in enumerate(got[id(eng)]):
                    for k in ORACLE_KEYS:
                        np.testing.assert_array_equal(
                            g[k], want[i][k],
                            err_msg=f"chunk {i}:{k} vs oracle")
            for i, (a, b) in enumerate(zip(got[id(one)], got[id(eight)])):
                for k in self.OUT_KEYS:
                    np.testing.assert_array_equal(
                        a[k], b[k], err_msg=f"chunk {i}:{k} 1 vs 4 shard")
        finally:
            serial.stop()
            one.stop()
            eight.stop()

    def test_audit_clean_with_fused_interior(self):
        """The shadow-oracle auditor (PR 7) at sampling 1.0 over the fused
        path: every captured batch replays bit-identical against the
        oracle — checked > 0, zero mismatches, health stays OK."""
        eng = jit_engine("on", audit_enabled=True, audit_sample_rate=1.0)
        try:
            for i, ch in enumerate(_chunks(eng, 4, size=24, seed=21)):
                eng.classify(dict(ch), now=400 + i)
            eng.audit_step()
            st = eng.auditor.stats()
            assert st["checked_batches"] >= 4
            assert st["checked_rows"] > 0
            assert st["mismatched_rows"] == 0
            assert eng.auditor.healthy
            assert eng.health()["state"] == C.HEALTH_OK
        finally:
            eng.stop()


@pytest.mark.slow
class TestFusedSoak:
    def test_long_horizon_fused_oracle_parity(self):
        """Expiry + slot reuse + large time steps through the fused
        interior (the test_parity long-horizon case)."""
        run_parity(seed=99, n_batches=8, batch=64, time_step=90,
                   classify_kwargs=FUSED_KW)

    def test_pipelined_fused_soak(self):
        """A few hundred pipelined submissions through the fused engine
        with audit armed at 1.0: zero mismatches, no restarts."""
        eng = jit_engine("on", audit_enabled=True, audit_sample_rate=1.0,
                         audit_pool_batches=64)
        try:
            chunks = _chunks(eng, 40, size=30, seed=31)
            tickets = [eng.submit(dict(ch), now=500 + i)
                       for i, ch in enumerate(chunks)]
            assert eng.drain(timeout=120)
            for t in tickets:
                t.result(timeout=10)
            deadline = time.time() + 30
            while time.time() < deadline:
                eng.audit_step()
                if eng.auditor.stats()["checked_batches"] >= 10:
                    break
            st = eng.auditor.stats()
            assert st["checked_batches"] >= 10
            assert st["mismatched_rows"] == 0
            assert eng.health()["pipeline"]["restarts"] == 0 \
                if eng.health().get("pipeline") else True
        finally:
            eng.stop()
