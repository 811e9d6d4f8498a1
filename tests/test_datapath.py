"""The Datapath plugin boundary (SURVEY.md §1 layer 3, §4 control-plane
tests): the Engine must depend only on DatapathBackend, a fake must slot in
exactly like pkg/datapath/fake, and control-plane fixtures replayed against
the fake must produce the same verdicts the jit backend produces."""

import os
import subprocess
import sys

import numpy as np
import pytest

from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath, JITDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.utils import constants as C
from oracle import PacketRecord
from cilium_tpu.utils.ip import parse_addr

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FIXTURE_RULES = [
    {
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "egress": [
            {"toCIDRSet": [{"cidr": "10.0.0.0/8",
                            "except": ["10.96.0.0/12"]}],
             "toPorts": [{"ports": [{"port": "443", "protocol": "TCP"}]}]},
        ],
        "egressDeny": [{"toCIDR": ["10.66.0.0/16"]}],
        "ingress": [{"fromEndpoints": [{"matchLabels": {"role": "fe"}}]}],
    },
]


def fixture_engine(datapath):
    eng = Engine(DaemonConfig(ct_capacity=2048, auto_regen=False,
                              flowlog_mode="all"), datapath=datapath)
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.add_endpoint(["k8s:role=fe"], ips=("192.168.1.30",), ep_id=3)
    eng.apply_policy(FIXTURE_RULES)
    return eng


def pkt(src, dst, sp, dp, proto=C.PROTO_TCP, flags=C.TCP_SYN, ep_id=1,
        direction=C.DIR_EGRESS):
    s16, sv6 = parse_addr(src)
    d16, dv6 = parse_addr(dst)
    return PacketRecord(s16, d16, sp, dp, proto, flags, sv6 or dv6,
                        ep_id, direction)


TRAFFIC = [
    pkt("192.168.1.10", "10.1.2.3", 40000, 443),      # allow (CIDRSet)
    pkt("192.168.1.10", "10.96.0.1", 40001, 443),     # drop (except)
    pkt("192.168.1.10", "10.66.1.1", 40002, 443),     # drop (deny wins)
    pkt("192.168.1.10", "10.1.2.3", 40003, 80),       # drop (port)
    pkt("192.168.1.30", "192.168.1.10", 40004, 22,    # allow (fromEndpoints)
        ep_id=1, direction=C.DIR_INGRESS),
]


class TestFakeDatapath:
    def test_control_plane_replay_records_placements(self):
        """pkg/datapath/fake pattern: replay fixtures, assert what would be
        programmed (placed snapshot + tensor images), no device involved."""
        fake = FakeDatapath()
        eng = fixture_engine(fake)
        eng.regenerate()
        assert len(fake.placed) == 1
        snap, tensors = fake.placed[0]
        assert snap.revision == eng.active.revision
        # "map contents": the verdict image must contain at least one DENY
        # cell (the egressDeny rule) and one ALLOW cell
        decisions = tensors["verdict"] & C.VERDICT_DECISION_MASK
        assert (decisions == C.VERDICT_DENY).any()
        assert (decisions == C.VERDICT_ALLOW).any()
        # a second regenerate with a new rule records a second placement
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egress": [{"toCIDR": ["11.0.0.0/8"]}]}])
        eng.regenerate()
        assert len(fake.placed) == 2
        assert fake.placed[1][0].revision > snap.revision

    def test_fake_matches_jit_verdicts(self):
        """The two backends implement the same semantics contract: identical
        fixture + traffic → bit-identical verdict columns and CT stats."""
        eng_fake = fixture_engine(FakeDatapath(DaemonConfig(ct_capacity=2048)))
        eng_jit = fixture_engine(JITDatapath(DaemonConfig(
            ct_capacity=2048, auto_regen=False)))
        slots = eng_fake.active.snapshot.ep_slot_of
        assert slots == eng_jit.active.snapshot.ep_slot_of
        batch = batch_from_records(TRAFFIC, slots)
        now = 1000
        out_f = eng_fake.classify(dict(batch), now=now)
        out_j = eng_jit.classify(dict(batch), now=now)
        for k in ("allow", "reason", "status", "remote_identity",
                  "redirect", "svc", "rnat"):
            np.testing.assert_array_equal(
                np.asarray(out_f[k]), np.asarray(out_j[k]), k)
        # nat/rnat rewrite columns are only meaningful where svc/rnat is set
        # (device convention; see kernels/classify.py out docstring)
        svc = np.asarray(out_j["svc"])
        rnat = np.asarray(out_j["rnat"])
        np.testing.assert_array_equal(np.asarray(out_f["nat_dport"])[svc],
                                      np.asarray(out_j["nat_dport"])[svc])
        np.testing.assert_array_equal(np.asarray(out_f["rnat_sport"])[rnat],
                                      np.asarray(out_j["rnat_sport"])[rnat])
        assert eng_fake.ct_stats(now) == eng_jit.ct_stats(now)
        # established repeat flows agree too (CT persisted in both backends)
        out_f2 = eng_fake.classify(dict(batch), now=now + 5)
        out_j2 = eng_jit.classify(dict(batch), now=now + 5)
        np.testing.assert_array_equal(out_f2["status"], out_j2["status"])
        assert (np.asarray(out_f2["status"])[0]
                == C.CTStatus.ESTABLISHED)

    def test_ct_arrays_roundtrip(self):
        """Fake CT export/import preserves entries (checkpoint path)."""
        fake = FakeDatapath(DaemonConfig(ct_capacity=2048))
        eng = fixture_engine(fake)
        eng.classify(batch_from_records(
            TRAFFIC, eng.active.snapshot.ep_slot_of), now=1000)
        before = fake.ct_stats(1000)
        assert before["live"] > 0
        arrays = fake.ct_arrays()
        fake2 = FakeDatapath(DaemonConfig(ct_capacity=2048))
        fake2.load_ct_arrays(arrays)
        assert fake2.ct_stats(1000) == before
        assert fake2._ct_table.entries == fake._ct_table.entries

    def test_sweep_reclaims(self):
        fake = FakeDatapath()
        eng = fixture_engine(fake)
        eng.classify(batch_from_records(
            TRAFFIC, eng.active.snapshot.ep_slot_of), now=1000)
        assert fake.ct_stats(1000)["live"] > 0
        reclaimed = eng.sweep(now=10**9)
        assert reclaimed > 0
        assert fake.ct_stats(10**9)["live"] == 0


def jit_engine(**mesh):
    cfg = DaemonConfig(ct_capacity=2048, auto_regen=False, device="cpu",
                       **mesh)
    return fixture_engine(JITDatapath(cfg))


def table_form(dp):
    return {k: (v.shape, str(v.dtype)) for k, v in dp._ct.items()}


ONE_CHIP_AND_MESH = [pytest.param({}, id="one-chip"),
                     pytest.param({"n_shards": 4}, id="mesh4")]


class TestPlacedConntrack:
    """The device table lies as ten key planes (compile/ct_layout: the
    placed form); everything off the hot path sees ``keys [cap, 10]``."""

    @pytest.mark.parametrize("mesh", ONE_CHIP_AND_MESH)
    def test_ct_arrays_is_the_logical_view_of_the_placed_table(self, mesh):
        from cilium_tpu.compile.ct_layout import CT_PLACED_KEYS
        from cilium_tpu.runtime.datapath import CT_SCHEMA_KEYS
        eng_fake = fixture_engine(FakeDatapath(DaemonConfig(ct_capacity=2048)))
        eng = jit_engine(**mesh)
        batch = batch_from_records(TRAFFIC, eng.active.snapshot.ep_slot_of)
        eng_fake.classify(dict(batch), now=1000)
        eng.classify(dict(batch), now=1000)
        assert table_form(eng.datapath) == {
            k: ((2048,), "uint32") for k in CT_PLACED_KEYS}
        arrays = eng.ct_arrays()
        assert set(arrays) == CT_SCHEMA_KEYS
        assert arrays["keys"].shape == (2048, 10)
        assert all(arrays[k].shape == (2048,) for k in arrays if k != "keys")
        # the same flows, word for word, as the oracle's table exports
        want = eng_fake.ct_arrays()

        def entries(a):
            live = a["expiry"] > 0
            return sorted(
                (a["keys"][s].tobytes(),
                 tuple(int(a[k][s]) for k in sorted(a) if k != "keys"))
                for s in np.nonzero(live)[0])
        assert entries(arrays) == entries(want) and entries(arrays)

    @pytest.mark.parametrize("mesh", ONE_CHIP_AND_MESH)
    def test_load_ct_arrays_takes_the_logical_view_and_probes_hit(self,
                                                                  mesh):
        src = jit_engine()
        batch = batch_from_records(TRAFFIC, src.active.snapshot.ep_slot_of)
        first = src.classify(dict(batch), now=1000)
        eng = jit_engine(**mesh)
        eng.load_ct_arrays(src.ct_arrays())
        assert eng.ct_stats(1000) == src.ct_stats(1000)
        again = eng.classify(dict(batch), now=1005)
        allowed = np.asarray(first["allow"])
        assert allowed.any()
        assert (np.asarray(again["status"])[allowed]
                == C.CTStatus.ESTABLISHED).all()

    @pytest.mark.parametrize("mesh", ONE_CHIP_AND_MESH)
    def test_a_checkpoint_in_the_matrix_schema_loads_and_probes_hit(
            self, mesh, tmp_path):
        """``ct.npz`` as every build before PR 48 wrote it — the device
        table read back array for array, ``keys`` one ``[cap, 10]`` matrix
        — restores into the placed table, and its flows are found."""
        from cilium_tpu.runtime import checkpoint
        old = fixture_engine(FakeDatapath(DaemonConfig(ct_capacity=2048)))
        batch = batch_from_records(TRAFFIC, old.active.snapshot.ep_slot_of)
        first = old.classify(dict(batch), now=1000)
        checkpoint.save(old, str(tmp_path))
        with np.load(os.path.join(str(tmp_path), checkpoint.CT_FILE)) as z:
            assert z["keys"].shape == (2048, 10)
            assert z["expiry"].shape == (2048,)
        cfg = DaemonConfig(ct_capacity=2048, auto_regen=False, device="cpu",
                           **mesh)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        assert checkpoint.restore(eng, str(tmp_path))
        assert eng.ct_stats(1000) == old.ct_stats(1000)
        again = eng.classify(dict(batch), now=1005)
        allowed = np.asarray(first["allow"])
        assert (np.asarray(again["status"])[allowed]
                == C.CTStatus.ESTABLISHED).all()

    @pytest.mark.parametrize("mesh", ONE_CHIP_AND_MESH)
    def test_every_owner_hands_back_the_form_placement_made(self, mesh):
        """Classify, the GC tick and the whole-table sweep each take the
        table and return it: same keys, shapes and dtypes throughout."""
        eng = jit_engine(**mesh)
        dp = eng.datapath
        placed = table_form(dp)
        batch = batch_from_records(TRAFFIC, eng.active.snapshot.ep_slot_of)
        eng.classify(dict(batch), now=1000)
        assert table_form(dp) == placed
        live = dp.ct_stats(1000)["live"]
        assert live > 0
        for _ in range(3):
            tick = dp.sweep_step(1000, 1024)
            assert table_form(dp) == placed
        assert tick["epoch"] == 1 and tick["live"] == live
        assert dp.sweep(10**9) == live
        assert table_form(dp) == placed
        assert dp.ct_stats(10**9) == {"capacity": 2048, "live": 0,
                                      "stale": 0}
        assert not any(np.asarray(v).any() for v in dp._ct.values())

    def test_a_remesh_to_a_smaller_table_keeps_every_surviving_flow(self):
        """4 → 3 chips rehashes 4,096 slots into 3,072 through the logical
        view: the lost shard's flows go, every other flow stays, value
        columns and all, and healing back keeps them again."""
        from cilium_tpu.compile.ct_layout import (
            CT_PLACED_KEYS, CTConfig, logical_ct_arrays, make_ct_arrays)
        cfg = DaemonConfig(ct_capacity=4096, auto_regen=False, device="cpu",
                           n_shards=4)
        dp = JITDatapath(cfg)
        rng = np.random.default_rng(48)
        arrays = logical_ct_arrays(make_ct_arrays(CTConfig(4096)))
        n = 600
        arrays["keys"][:n] = rng.integers(0, 2**32, (n, 10), dtype=np.uint32)
        arrays["keys"][:n, 9] = (6 << 8) | (arrays["keys"][:n, 9] & 1)
        arrays["expiry"][:n] = 5000 + np.arange(n)
        arrays["pkts_fwd"][:n] = 1 + np.arange(n)
        dp.load_ct_arrays(arrays)

        def entries(a, slots):
            return {a["keys"][s].tobytes():
                    (int(a["expiry"][s]), int(a["pkts_fwd"][s]))
                    for s in slots}
        before = dp.ct_arrays()
        live = np.nonzero(before["expiry"] > 0)[0]
        assert len(entries(before, live)) == n
        survivors = entries(before, live[live // 1024 != 2])
        res = dp.remesh([0, 1, 3])
        assert res["ct_capacity"] == 3072
        assert res["ct_lost"] == n - len(survivors) > 0
        assert res["ct_dropped"] == 0 and res["ct_salvaged"] == len(survivors)
        assert table_form(dp) == {k: ((3072,), "uint32")
                                  for k in CT_PLACED_KEYS}
        after = dp.ct_arrays()
        assert after["keys"].shape == (3072, 10)
        assert entries(after, np.nonzero(after["expiry"] > 0)[0]) == survivors
        res = dp.remesh([0, 1, 2, 3])
        assert res["ct_capacity"] == 4096 and res["ct_lost"] == 0
        healed = dp.ct_arrays()
        assert entries(healed, np.nonzero(healed["expiry"] > 0)[0]) \
            == survivors


class TestJaxFreeBoundary:
    def test_engine_with_fake_never_imports_jax(self):
        """The boundary is real only if an Engine(FakeDatapath) session runs
        with jax imports poisoned. Subprocess because conftest pre-imports
        jax in this process."""
        code = r"""
import sys
sys.modules["jax"] = None          # any 'import jax' now raises ImportError
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.utils.ip import parse_addr
from oracle import PacketRecord

eng = Engine(DaemonConfig(ct_capacity=1024, auto_regen=False),
             datapath=FakeDatapath())
eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
eng.apply_policy([{"endpointSelector": {"matchLabels": {"app": "web"}},
                   "egress": [{"toCIDR": ["10.0.0.0/8"]}]}])
s16, _ = parse_addr("192.168.1.10")
d16, _ = parse_addr("10.1.2.3")
p = PacketRecord(s16, d16, 40000, 443, 6, 0x02, False, 1, 0)
out = eng.classify(batch_from_records([p], eng.active.snapshot.ep_slot_of),
                   now=100)
assert bool(out["allow"][0]), out
assert eng.ct_stats(100)["live"] == 1
print("JAXFREE_OK")
"""
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=120,
                              cwd=REPO_ROOT)
        assert proc.returncode == 0, proc.stderr
        assert "JAXFREE_OK" in proc.stdout


class TestWireFlagReset:
    """Satellite pin: the sticky _wire_l7/_wire_wide widening flags reset
    in place() when the NEW snapshot provably has no L7/v6 surface, so a
    transient L7/v6 burst doesn't permanently tax every future batch with
    the wide pack path — while verdicts stay correct throughout."""

    L7_POLICY = [{
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "ingress": [{"toPorts": [{
            "ports": [{"port": "80", "protocol": "TCP"}],
            "rules": {"http": [{"method": "GET", "path": "/api"}]}}]}],
        "egress": [{"toCIDR": ["10.0.0.0/8"]}],
    }]
    PLAIN_POLICY = [{
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "egress": [{"toCIDR": ["10.0.0.0/8"]}],
    }]

    def _l7_batch(self, eng):
        from cilium_tpu.kernels.records import batch_from_records
        recs = [pkt("192.168.1.30", "192.168.1.10", 50000 + i, 80,
                    direction=C.DIR_INGRESS) for i in range(4)]
        b = batch_from_records(recs, eng.active.snapshot.ep_slot_of)
        b["http_method"][:] = 0
        b["http_path"][:, :4] = np.frombuffer(b"/api", np.uint8)
        return b

    def _v4_batch(self, eng):
        from cilium_tpu.kernels.records import batch_from_records
        recs = [pkt("192.168.1.10", "10.1.2.3", 51000 + i, 443)
                for i in range(4)]
        return batch_from_records(recs, eng.active.snapshot.ep_slot_of)

    def test_l7_burst_unsticks_after_l7_free_snapshot(self):
        cfg = DaemonConfig(ct_capacity=2048, auto_regen=False,
                           device="cpu", batch_size=32)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.add_endpoint(["k8s:role=fe"], ips=("192.168.1.30",), ep_id=3)
        eng.apply_policy(self.L7_POLICY)
        eng.regenerate()
        out = eng.classify(self._l7_batch(eng), now=100)
        assert bool(out["allow"].all())
        assert eng.datapath._wire_l7          # the burst widened the wire

        # drop every L7 rule: the new snapshot has no L7 surface
        eng.repo.clear()
        eng.apply_policy(self.PLAIN_POLICY)
        eng.regenerate(force=True)
        assert not eng.datapath._wire_l7      # place() reset the flag
        assert eng.datapath.pack_stats["wire_flag_resets"] >= 1
        # subsequent traffic rides the compact wire AND verdicts stay
        # correct (allowed CIDR flow)
        out = eng.classify(self._v4_batch(eng), now=200)
        assert bool(out["allow"].all())
        assert not eng.datapath._wire_l7
        eng.stop()

    def test_v6_burst_unsticks_after_clean_run(self):
        from cilium_tpu.runtime.datapath import WIRE_RESET_CLEAN_BATCHES
        cfg = DaemonConfig(ct_capacity=2048, auto_regen=False,
                           device="cpu", batch_size=32)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(self.PLAIN_POLICY)
        eng.regenerate()
        b = self._v4_batch(eng)
        b["is_v6"][0] = True                  # one stray v6 record
        eng.classify(b, now=100)
        assert eng.datapath._wire_wide
        # a regen right after the burst must NOT narrow (hysteresis: with
        # recent wide traffic a reset would retrace on the next v6 batch)
        eng.regenerate(force=True)
        assert eng.datapath._wire_wide
        # after a clean run of v4-only batches the next regen narrows
        for i in range(WIRE_RESET_CLEAN_BATCHES):
            eng.classify(self._v4_batch(eng), now=110 + i)
        eng.regenerate(force=True)
        assert not eng.datapath._wire_wide
        assert eng.datapath.pack_stats["wire_flag_resets"] >= 1
        out = eng.classify(self._v4_batch(eng), now=300)
        assert bool(out["allow"].all())
        eng.stop()

    def test_stale_staging_tail_does_not_pin_wide(self):
        """A reused staging slot must not leak an earlier flush's v6 rows
        into later batches' wire-format probes: after one coalesced v6
        batch, subsequent v4-only coalesced batches through the SAME slot
        must advance the clean-batch counter (else the wide wire could
        never narrow on the serving path)."""
        cfg = DaemonConfig(ct_capacity=2048, auto_regen=False,
                           device="cpu", batch_size=64,
                           pipeline_flush_ms=1.0)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy(self.PLAIN_POLICY)
        eng.regenerate()
        from cilium_tpu.kernels.records import batch_from_records
        recs = [pkt("192.168.1.10", "10.1.2.3", 52000 + i, 443)
                for i in range(40)]
        big = batch_from_records(recs, eng.active.snapshot.ep_slot_of)
        big["is_v6"][:] = False
        big["is_v6"][5] = True                # one v6 row mid-batch
        eng.submit(big, now=100)              # 40 rows: coalesced path
        assert eng.drain(timeout=30)
        assert eng.datapath._wire_wide
        small = batch_from_records(recs[:8],
                                   eng.active.snapshot.ep_slot_of)
        for i in range(5):                    # 8 rows: same slots reused
            eng.submit(dict(small), now=200 + i)
            assert eng.drain(timeout=30)
        assert eng.datapath._batches_since_wide >= 5, \
            "stale staging tail re-tripped the wide probe"
        eng.stop()

    def test_tokens_without_l7_policy_never_widen(self):
        """Policy-gated L7 widening: with zero L7 rule sets, http tokens
        cannot affect verdicts — the wire stays compact under tokenized
        traffic (no per-regen reset/re-widen retrace flap), and verdicts
        still match the oracle, which does see the tokens."""
        cfg = DaemonConfig(ct_capacity=2048, auto_regen=False,
                           device="cpu", batch_size=32)
        jit = Engine(cfg, datapath=JITDatapath(cfg))
        fake = Engine(cfg, datapath=FakeDatapath(cfg))
        for eng in (jit, fake):
            eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",),
                             ep_id=1)
            eng.apply_policy(self.PLAIN_POLICY)
            eng.regenerate()
        b = self._v4_batch(jit)
        b["http_method"][:] = 0               # shim tokenizes plain HTTP
        b["http_path"][:, :4] = np.frombuffer(b"/idx", np.uint8)
        out_j = jit.classify(dict(b), now=100)
        out_f = fake.classify(dict(b), now=100)
        for k in ("allow", "reason", "status", "remote_identity"):
            np.testing.assert_array_equal(out_j[k], out_f[k])
        assert not jit.datapath._wire_l7      # tokens never widened it
        jit.stop()
        fake.stop()


# --------------------------------------------------------------------------- #
# The packed verdict slab (ISSUE 28): one crossing each way per batch
# --------------------------------------------------------------------------- #
SLAB_POLICY = [{
    "endpointSelector": {"matchLabels": {"app": "web"}},
    "egress": [{"toCIDR": ["10.0.0.0/8", "2001:db8::/32"]}],
    "egressDeny": [{"toCIDR": ["10.66.0.0/16"]}],
    "ingress": [{"fromEndpoints": [{"matchLabels": {"role": "fe"}}],
                 "toPorts": [{
                     "ports": [{"port": "80", "protocol": "TCP"}],
                     "rules": {"http": [{"method": "GET",
                                         "path": "/api"}]}}]}],
}]


class _StepRecorder:
    """Stands in for ``JITDatapath._classify``: keeps every call's
    arguments, calls through, and hands ``wrap`` the step's result."""

    def __init__(self, fn, wrap=None):
        self.fn, self.wrap, self.calls = fn, wrap, []

    def __call__(self, *args):
        self.calls.append(args)
        res = self.fn(*args)
        return self.wrap(res) if self.wrap else res


class _CountingWords:
    """Stands in for the slab's device vector: counts the read-back being
    started and the materializations (``np.asarray`` lands in
    ``__array__``), optionally failing them (``fail``: True, or the
    error's text)."""

    def __init__(self, words, fail=False):
        self.words, self.fail = words, fail
        self.started = self.materialized = 0

    def copy_to_host_async(self):
        self.started += 1
        self.words.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self.materialized += 1
        if self.fail:
            raise RuntimeError("device error (test)" if self.fail is True
                               else self.fail)
        return np.asarray(self.words)


def count_slabs(dp, fail=False):
    """Put a ``_CountingWords`` in place of the words of every slab that
    ``dp``'s step hands back; returns (the step's recorder, the stand-ins
    so far, newest last)."""
    import dataclasses
    slabs = []

    def wrap(res):
        slab, new_ct = res
        slabs.append(_CountingWords(slab.words, fail=fail))
        return dataclasses.replace(slab, words=slabs[-1]), new_ct

    dp._classify = _StepRecorder(dp._classify, wrap)
    return dp._classify, slabs


def same_columns(got, ref):
    """``got`` (numpy, off the slab) is ``ref`` (the column form's arrays)
    key for key, dtype for dtype, shape for shape, bit for bit."""
    assert list(got) == list(ref)
    for k in ref:
        want = np.asarray(ref[k])
        assert type(got[k]) is np.ndarray, k
        assert got[k].dtype == want.dtype, k
        assert got[k].shape == want.shape, k
        assert got[k].tobytes() == want.tobytes(), k


def slab_engine(ct_capacity=2048, **mesh):
    # donate_ct off: the CT state a step was handed stays readable, so the
    # per-column step can be run again on exactly the same inputs.
    # ``mesh``: n_shards / rule_shards / rss_mode / zero_copy_ingest for
    # the meshed cases (tests/test_mesh_slab.py)
    cfg = DaemonConfig(ct_capacity=ct_capacity, auto_regen=False,
                       device="cpu", batch_size=32, donate_ct=False, **mesh)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.add_endpoint(["k8s:role=fe"], ips=("192.168.1.30",), ep_id=3)
    eng.apply_policy(SLAB_POLICY)
    eng.regenerate()
    return eng


def slab_batch(eng, wire, case, base_port):
    """32 rows for one wire form: allowed, denied and (second dispatch)
    established and reply rows; ``padded`` leaves a tail of padding and a
    row of an unknown endpoint invalid."""
    n = 20 if case == "padded" else 32
    recs = []
    for i in range(n):
        if i % 7 == 3:
            recs.append(pkt("192.168.1.10", "10.66.1.1", base_port + i, 443))
        elif wire == "wide" and i % 3 == 0:
            recs.append(pkt("fd00::10", "2001:db8::%x" % (i + 1),
                            base_port + i, 443))
        elif i % 5 == 4:
            # the reverse of an allowed egress flow: a reply once that
            # flow is in the table
            recs.append(pkt("10.1.2.3", "192.168.1.10", 443,
                            base_port + i - 1, flags=C.TCP_ACK,
                            direction=C.DIR_INGRESS))
        else:
            recs.append(pkt("192.168.1.10", "10.1.2.3", base_port + i, 443))
    if case == "padded":
        recs[5] = pkt("192.168.1.99", "10.1.2.3", base_port, 443, ep_id=77)
    b = batch_from_records(recs, eng.active.snapshot.ep_slot_of, pad_to=32)
    if wire == "l7":
        for i in range(0, n, 4):
            r = batch_from_records(
                [pkt("192.168.1.30", "192.168.1.10", base_port + 100 + i,
                     80, direction=C.DIR_INGRESS)],
                eng.active.snapshot.ep_slot_of)
            for k in b:
                b[k][i] = r[k][0]
            b["http_method"][i] = 0
            b["http_path"][i, :4] = np.frombuffer(
                b"/api" if i % 8 else b"/bad", np.uint8)
    return b


class TestVerdictSlab:
    @pytest.mark.parametrize("case", ["valid", "padded", "ct_full"])
    @pytest.mark.parametrize("wire,words", [("v4", 4), ("wide", 11),
                                            ("l7", 5)])
    def test_slab_finalize_equals_column_read(self, wire, words, case):
        """What the one-chip finalize returns from the slab is, key for
        key, dtype for dtype, bit for bit, what the per-column read of the
        same jitted step returns on the same inputs and CT state."""
        from cilium_tpu.kernels.classify import make_classify_fn
        eng = slab_engine(ct_capacity=16 if case == "ct_full" else 2048)
        dp = eng.datapath
        rec = dp._classify = _StepRecorder(dp._classify)
        columns = make_classify_fn(
            probe_depth=dp.config.probe_depth, v4_only=dp.config.v4_only,
            donate_ct=False, packed=True)
        act = eng.active
        # two dispatches of the same flows: new ones, then established
        # and reply rows on the table the first one left
        for now in (1000, 1001):
            b = slab_batch(eng, wire, case, 40000)
            out, counters = dp.classify_async(
                act.tensors, act.snapshot, b, now)()
            tensors, ct, dev_batch, now_arg, wi = rec.calls[-1]
            wire_arr = dev_batch[0] if isinstance(dev_batch, tuple) \
                else dev_batch
            assert wire_arr.shape == (32, words)
            ref_out, _ct, ref_counters = columns(
                tensors, ct, dev_batch, now_arg, wi)
            same_columns(out, ref_out)
            same_columns(counters, ref_counters)
            np.testing.assert_array_equal(out["ct_state_pre"], out["status"])
            assert out["nat_dst"].shape == (32, 4)
        # the batch did what its case is there for
        assert out["allow"].any() and not out["allow"].all()
        assert (out["status"] == C.CTStatus.ESTABLISHED).any()
        if case != "ct_full":
            assert (out["status"] == C.CTStatus.REPLY).any()
        if case == "padded":
            assert not b["valid"].all()
            assert not out["allow"][~b["valid"]].any()
        if case == "ct_full":
            assert out["ct_full"].any()
            assert int(counters["insert_fail"]) == int(out["ct_full"].sum())
        if wire == "wide":
            assert out["nat_dst"][:, 0].any()    # v6 words, not v4-mapped
        if wire == "l7":
            assert out["redirect"].any() or (
                out["reason"] == C.DropReason.POLICY_L7).any()
        assert dp.pack_stats["readback_slab"] == 2
        assert "readback_columns" not in dp.pack_stats
        eng.stop()

    def test_pack_out_roundtrip_extremes(self):
        """pack_out_jnp / unpack_out on the values a layout could mangle:
        all-ones words, -1 in a signed column, a 4-word address column,
        narrow integers, scalars, and one value under two keys."""
        import jax
        from cilium_tpu.kernels.records import pack_out_jnp, unpack_out
        n, cells = 8, 14
        rule = np.full((n,), -1, np.int32)
        rule[::2] = np.iinfo(np.int32).max
        status = np.arange(n, dtype=np.int32) - 4
        out = {
            "allow": np.arange(n) % 2 == 0,
            "svc": np.ones((n,), bool),
            "rnat": np.zeros((n,), bool),
            "remote_identity": np.full((n,), 0xFFFFFFFF, np.uint32),
            "matched_rule": rule,
            "nat_dst": np.full((n, 4), 0xFFFFFFFF, np.uint32)
            - np.arange(4 * n, dtype=np.uint32).reshape(n, 4),
            "status": status,
            "narrow_s": np.array([-128, 127, -1, 0, 1, 2, 3, 4], np.int8),
            "narrow_u": np.full((n,), 0xFFFF, np.uint16),
        }
        counters = {
            "by_reason_dir": np.full((cells,), 0xFFFFFFFF, np.uint32),
            "insert_fail": np.uint32(0xFFFFFFFF),
            "ct_evicted": np.uint32(0),
        }

        def step(out, counters):
            out = dict(out, ct_state_pre=out["status"])
            return pack_out_jnp(out, counters)

        layouts = []

        def traced(out, counters):
            words, layout = step(out, counters)
            layouts.append(layout)
            return words

        words = np.asarray(jax.jit(traced)(out, counters))
        layout, = layouts
        assert words.dtype == np.uint32 and words.ndim == 1
        # one flag word per row for the three bools, status shipped once
        assert words.shape[0] == n * (1 + 1 + 1 + 4 + 1 + 1 + 1) \
            + cells + 2
        got_out, got_counters = unpack_out(words, layout)
        out["ct_state_pre"] = status
        for got, ref in ((got_out, out), (got_counters, counters)):
            assert sorted(got) == sorted(ref)
            for k in ref:
                want = np.asarray(ref[k])
                assert got[k].dtype == want.dtype, k
                assert got[k].shape == want.shape, k
                np.testing.assert_array_equal(got[k], want, k)
                assert not got[k].flags.writeable, k
        assert np.shares_memory(got_out["status"], got_out["ct_state_pre"])
        assert np.shares_memory(got_out["nat_dst"], words)
        with pytest.raises(TypeError):
            jax.jit(lambda x: pack_out_jnp({"f": x}, {})[0])(
                np.zeros((4,), np.float16))

    def test_one_crossing_each_way(self):
        """N batches through the one-chip JITDatapath: N slab read-backs
        each started at dispatch and materialized once in finalize, no
        per-column path; ``now``/``world_index`` go up as numpy scalars
        and a new ``now`` is not a new program."""
        eng = slab_engine()
        dp = eng.datapath
        rec, slabs = count_slabs(dp)
        act = eng.active
        n_batches, sizes = 5, []
        for i in range(n_batches):
            fin = dp.classify_async(act.tensors, act.snapshot,
                                    slab_batch(eng, "v4", "valid", 41000),
                                    2000 + i)
            assert (slabs[-1].started, slabs[-1].materialized) == (1, 0)
            out, counters = fin()
            assert (slabs[-1].started, slabs[-1].materialized) == (1, 1)
            assert all(type(v) is np.ndarray
                       for v in (*out.values(), *counters.values()))
            sizes.append(rec.fn._cache_size())
        assert sizes == [sizes[0]] * n_batches   # one compiled program
        for i, (_t, _ct, _b, now, wi) in enumerate(rec.calls):
            assert type(now) is np.uint32 and int(now) == 2000 + i
            assert type(wi) is np.int32
            assert int(wi) == act.snapshot.world_index
        assert dp.pack_stats["readback_slab"] == n_batches
        assert "readback_columns" not in dp.pack_stats
        assert dp._wire_out == 0
        assert len(dp._wire_pool[(32, 4)]) == 1   # released, and reused
        eng.stop()

    def test_failed_materialization_sheds_wire_buffer(self):
        """The fault path keeps its contract: a slab that fails to
        materialize sheds the wire buffer — the in-flight count comes
        down, the buffer never returns to the pool."""
        eng = slab_engine()
        dp = eng.datapath
        count_slabs(dp, fail=True)
        act = eng.active
        fin = dp.classify_async(act.tensors, act.snapshot,
                                slab_batch(eng, "v4", "valid", 42000), 3000)
        assert dp._wire_out == 1
        with pytest.raises(RuntimeError):
            fin()
        assert dp._wire_out == 0
        assert not dp._wire_pool.get((32, 4))
        assert dp.pack_stats["readback_slab"] == 0
        eng.stop()

    # -- the same on a mesh (ISSUE 30): one sharded slab a batch ----------
    @pytest.mark.parametrize("rss", ["device", "host"])
    def test_mesh_one_slab_a_batch(self, rss):
        """N batches through a 4-wide mesh, either RSS mode: N slab
        read-backs, each started at dispatch and materialized once in
        finalize, under one ``datapath.readback`` span inside the batch's
        ``datapath.compute``; the wire buffer comes back to its pool."""
        import time
        t0 = time.monotonic()       # the tracer is the process's
        eng = slab_engine(n_shards=4, rss_mode=rss, trace_sample_rate=1.0)
        dp = eng.datapath
        rec, slabs = count_slabs(dp)
        n_batches = 3
        for i in range(n_batches):
            out = eng.classify(slab_batch(eng, "v4", "valid", 43000),
                               now=4000 + i)
            assert (slabs[-1].started, slabs[-1].materialized) == (1, 1)
            assert out["allow"].shape == (32,) and out["allow"].any()
        for _t, _ct, _b, now, wi in rec.calls:
            assert type(now) is np.uint32 and type(wi) is np.int32
        assert dp.pack_stats["readback_slab"] == n_batches
        assert dp._wire_out == 0
        spans = [s for s in eng.tracer.spans(limit=1 << 12)
                 if s["start_mono"] >= t0]
        back = [s for s in spans if s["name"] == "datapath.readback"]
        assert len(back) == n_batches
        for s in back:
            assert s["attrs"] == {"arrays": 1, "shards": 4}
            assert any(o["name"] == "datapath.compute"
                       and o["trace_id"] == s["trace_id"]
                       and o["start_mono"] <= s["start_mono"]
                       and s["duration_ms"] <= o["duration_ms"]
                       for o in spans)
        names = {s["name"] for s in spans}
        if rss == "device":
            # arrival order ships as it is: pooled, released, reused,
            # and no host steering is left to pay for
            assert dp.pack_stats["pack_inplace"] == n_batches
            assert len(dp._wire_pool[(32, 4)]) == 1
            assert not names & {"pipeline.steer", "datapath.steer"}
        else:
            # the synchronous entry steers with the allocating regroup
            assert dp.pack_stats["pack_fallback_steered"] == n_batches
            assert "datapath.steer" in names
        eng.stop()

    @pytest.mark.parametrize("error,lost", [
        ("device error (test)", None),
        ("DEVICE_UNAVAILABLE: dev=2 (test)", 2)])
    @pytest.mark.parametrize("rss", ["device", "host"])
    def test_mesh_failed_materialization_sheds_and_is_triaged(
            self, rss, error, lost):
        """The meshed fault path keeps its contract under the slab: a
        failed materialization sheds the wire buffer (in-flight count
        down, buffer never re-pooled) and goes through
        ``_maybe_device_lost``: a transient error is raised as it is, a
        dead chip's signature latches the ordinal and raises DeviceLost."""
        from cilium_tpu.parallel.mesh import steer_batch
        from cilium_tpu.pipeline.guard import DeviceLost
        eng = slab_engine(n_shards=4, rss_mode=rss)
        dp = eng.datapath
        count_slabs(dp, fail=error)
        triaged = []
        triage = dp._maybe_device_lost
        dp._maybe_device_lost = lambda e: triaged.append(e) or triage(e)
        act = eng.active
        b = slab_batch(eng, "v4", "valid", 44000)
        if rss == "host":       # as the staging ring delivers it
            b, _scatter, _per = steer_batch(b, 4, per_shard=16)
        key = (int(b["valid"].shape[0]), 4)
        fin = dp.classify_async(act.tensors, act.snapshot, b, 5000,
                                pre_steered=True)
        assert dp._wire_out == 1
        with pytest.raises(DeviceLost if lost is not None
                           else RuntimeError) as raised:
            fin()
        assert len(triaged) == 1 and error in str(triaged[0])
        if lost is not None:
            assert raised.value.device == lost
            assert dp.device_health[lost]["state"] == "dead"
        else:
            assert not isinstance(raised.value, DeviceLost)
            assert not dp.device_health
        assert dp._wire_out == 0
        assert not dp._wire_pool.get(key)
        assert dp.pack_stats["readback_slab"] == 0
        eng.stop()

    def test_mesh_unpacks_with_the_dispatch_time_shard_count(self):
        """A remesh between a batch's dispatch and its finalize: the slab
        is the OLD mesh's (four segments) and is unpacked as such, not
        with the width serving now; the survivors' step is built in the
        slab form too, and reads two segments."""
        import time
        t0 = time.monotonic()       # the tracer is the process's
        eng = slab_engine(n_shards=4, rss_mode="device",
                          trace_sample_rate=1.0)
        dp = eng.datapath
        b = slab_batch(eng, "v4", "valid", 45000)
        want = eng.classify(dict(b), now=6000)      # opens the flows
        act = eng.active
        trace_id = eng.tracer.maybe_sample()
        with eng.tracer.context(trace_id):
            fin = dp.classify_async(act.tensors, act.snapshot, b, 6001)
            res = eng._remesh_to([0, 1], reason="test")
            assert (res["from"], res["to"]) == (4, 2)
            assert dp.n_flow_shards == 2
            out, counters = fin()
        # the flows the first batch opened are established, their reply
        # rows pass now, the denied stay denied: 32 rows in arrival order
        assert out["allow"].shape == (32,)
        denied = np.arange(32) % 7 == 3
        assert not out["allow"][denied].any()
        assert out["allow"][want["allow"]].all()
        assert (out["status"][want["allow"]] == C.CTStatus.ESTABLISHED).all()
        replies = out["allow"] & ~want["allow"]
        assert replies.any()
        assert (out["status"][replies] == C.CTStatus.REPLY).all()
        assert int(counters["by_reason_dir"].sum()) == int(b["valid"].sum())
        def back():
            return [s["attrs"] for s in eng.tracer.spans(limit=1 << 12)
                    if s["name"] == "datapath.readback"
                    and s["start_mono"] >= t0]

        assert back() == [{"arrays": 1, "shards": 4}] * 2
        # the survivor mesh serves in the slab form as well
        again = eng.classify(dict(b), now=6002)
        assert again["allow"].shape == (32,)
        assert again["allow"][want["allow"]].all()
        assert not again["allow"][denied].any()
        assert back()[2:] == [{"arrays": 1, "shards": 2}]
        assert dp.pack_stats["readback_slab"] == 3
        eng.stop()


class TestWireCounters:
    """PR 42: the reductions that choose the batch-wide wire also count
    what each valid row needs of it by its own class, and what went up.
    One batch of known make-up on each of the four wires; every expected
    number is written out by hand below."""

    #: 16 rows, 12 valid: (v6, carries a request, leaves the endpoint),
    #: four of each make-up the case asks for, then four invalid rows
    #: (which read as v4, no request, direction 0 = egress: the defaults)
    PLAIN, V6, REQUEST, BOTH = (0, 0), (1, 0), (0, 1), (1, 1)
    PATHS = (b"/api/one", b"/api/two")             # 8 bytes: 2 path words
    CASES = {
        # wire: (make-up of the 12 valid rows, words a row, expected
        #        wide_needed, l7_needed, needed words of the valid rows,
        #        dictionary bytes up, dictionary bytes needed)
        "narrow": ([PLAIN] * 12, 4, 0, 0, 12 * 4, 0, 0),
        "wide": ([PLAIN] * 8 + [V6] * 4, 11, 4, 0, 8 * 4 + 4 * 11, 0, 0),
        # 3 distinct paths (the empty one is the other rows') pad to 4
        # dictionary rows of 2 words; the requests' own are 2 of them
        "l7": ([PLAIN] * 8 + [REQUEST] * 4, 5, 0, 4, 8 * 4 + 4 * 5,
               4 * 2 * 4, 2 * 2 * 4),
        "full": ([PLAIN] * 4 + [V6] * 2 + [REQUEST] * 4 + [BOTH] * 2, 12,
                 4, 6, 4 * 4 + 2 * 11 + 4 * 5 + 2 * 12,
                 4 * 2 * 4, 2 * 2 * 4),
    }

    def _engine(self, **more):
        cfg = DaemonConfig(ct_capacity=2048, auto_regen=False, device="cpu",
                           batch_size=32, **more)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10", "fd00::10"),
                         ep_id=1)
        eng.apply_policy(TestWireFlagReset.L7_POLICY)
        eng.regenerate()
        return eng

    def _batch(self, eng, makeup):
        from cilium_tpu.kernels.records import empty_batch
        b = empty_batch(16)
        n = len(makeup)
        b["valid"][:n] = True
        b["ep_slot"][:] = eng.active.snapshot.ep_slot_of[1]
        b["proto"][:] = C.PROTO_TCP
        b["tcp_flags"][:] = C.TCP_SYN
        b["sport"][:] = 40000 + np.arange(16)
        b["dport"][:] = 80
        b["src"][:, 2], b["dst"][:, 2] = 0xFFFF, 0xFFFF
        b["src"][:, 3] = 0x0B000001 + np.arange(16)
        b["dst"][:, 3] = 0xC0A8010A
        # every third valid row leaves the endpoint
        b["direction"][:n] = np.where(np.arange(n) % 3 == 0, C.DIR_EGRESS,
                                      C.DIR_INGRESS)
        for i, (v6, request) in enumerate(makeup):
            b["is_v6"][i] = bool(v6)
            if request:
                b["http_method"][i] = C.HTTP_METHOD_IDS["GET"]
                b["http_path"][i, :8] = np.frombuffer(self.PATHS[i % 2],
                                                      np.uint8)
        return b

    @pytest.mark.parametrize("wire", sorted(CASES))
    def test_a_batch_of_known_make_up_counts_exactly(self, wire):
        makeup, words, wide, l7, needed_words, dict_up, dict_needed = \
            self.CASES[wire]
        eng = self._engine()
        try:
            dp = eng.datapath
            rows0, pack0 = dp.wire_stats()
            assert rows0 == {"wide_needed": 0, "l7_needed": 0, "egress": 0}
            assert pack0["wire_bytes"] == pack0["wire_bytes_needed"] == 0
            eng.classify(self._batch(eng, makeup), now=100)
            rows, pack = dp.wire_stats()
            assert (dp._wire_wide, dp._wire_l7) == (wide > 0, l7 > 0)
            assert rows == {"wide_needed": wide, "l7_needed": l7,
                            "egress": 4}
            # all 16 rows ride the batch's one layout, padding too
            assert pack["wire_bytes"] == 16 * words * 4 + dict_up
            assert pack["wire_bytes_needed"] == needed_words * 4 \
                + dict_needed
            # the choice is kept: plain rows now pay the widened wire,
            # and need what they always needed
            eng.classify(self._batch(eng, self.CASES["narrow"][0]), now=101)
            rows2, pack2 = dp.wire_stats()
            assert rows2 == {"wide_needed": wide, "l7_needed": l7,
                             "egress": 8}
            # the dictionary of a batch without a request is the empty
            # path alone: one row goes up where the first had four
            # (_l7_dict_rows only grows), none of it needed
            again_up = dict_up if l7 else 0
            assert pack2["wire_bytes"] - pack["wire_bytes"] \
                == 16 * words * 4 + again_up
            assert pack2["wire_bytes_needed"] - pack["wire_bytes_needed"] \
                == 12 * 4 * 4
        finally:
            eng.stop()

    def test_a_path_without_a_method_is_a_request_too(self):
        """``has_l7_tokens``: a method or a path byte. Two of the four
        requests lose their method (a request line whose method the
        tokenizer does not know) and still count."""
        eng = self._engine()
        try:
            b = self._batch(eng, self.CASES["l7"][0])
            b["http_method"][8:10] = C.HTTP_METHOD_ANY
            eng.classify(b, now=100)
            rows, pack = eng.datapath.wire_stats()
            assert rows["l7_needed"] == 4
            assert pack["wire_bytes_needed"] == self.CASES["l7"][4] * 4 \
                + self.CASES["l7"][6]
        finally:
            eng.stop()

    def test_the_counts_reach_pipeline_stats_and_the_pack_span(self):
        eng = self._engine(trace_sample_rate=1.0)
        try:
            def packs():
                # the tracer is process-wide: earlier cases' spans too
                return [s.get("attrs") for s in eng.tracer.spans(
                    limit=1 << 16, name="datapath.pack")]
            before = len(packs())
            b = self._batch(eng, self.CASES["full"][0])
            eng.submit(b, now=100).result(timeout=120)
            eng.drain(timeout=60)
            st = eng.pipeline_stats()
            rows, pack = st["verdict_rows"], st["pack_stats"]
            assert (rows["wide_needed"], rows["l7_needed"], rows["egress"],
                    rows["total"]) == (4, 6, 4, 12)
            assert pack["wire_bytes"] > pack["wire_bytes_needed"] > 0
            assert packs()[before:] == [
                {"wire_words": 12, "rows_wide": 4, "rows_l7": 6}]
        finally:
            eng.stop()

    def test_a_fake_datapath_states_no_wire(self):
        eng = fixture_engine(FakeDatapath(DaemonConfig(ct_capacity=2048)))
        try:
            eng.regenerate()
            b = batch_from_records(TRAFFIC, eng.active.snapshot.ep_slot_of)
            eng.submit(b, now=100).result(timeout=60)
            st = eng.pipeline_stats()
            assert "pack_stats" not in st
            assert "wide_needed" not in st["verdict_rows"]
        finally:
            eng.stop()
