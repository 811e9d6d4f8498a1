"""CT exhaustion semantics (ISSUE 10 tentpole): insert-when-full.

The contract under test, bit-identical across the jitted step and the
bounded oracle/FakeDatapath:

- a NEW allowed flow whose probe window holds no free slot tail-evicts the
  window's soonest-expiring *evictable* entry (everything except
  established TCP — SYN-stage/closing/non-TCP), ties to the earliest probe
  offset, contested victims to the lowest packet index;
- slots the batch probe-hit are protected from eviction (snapshot
  semantics);
- a flow that still cannot obtain a slot fails CLOSED: denied with the new
  ``DropReason.CT_FULL`` and the ``ct_full`` out column set, counted in
  ``insert_fail`` (``ct_evicted`` counts the evictions);
- the shadow auditor replays a saturated table's verdicts with zero
  mismatches at sampling 1.0 (``oracle.replay(ct_full=...)`` treats the
  exhaustion signal like ``status`` — externally supplied truth that can
  only EXCUSE a create the replay itself demands).
"""

import numpy as np
import pytest

from cilium_tpu.compile.ct_layout import CTConfig, make_ct_arrays
from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath, JITDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.runtime.faults import FAULTS
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr
from oracle import PacketRecord
from oracle.datapath import ConntrackTable, Oracle, _ct_expirable

#: the full comparable out surface — ct_full included (the new column)
OUT_KEYS = ("allow", "reason", "status", "ct_full", "remote_identity",
            "redirect")

CT_CAP = 256          # small enough for a test flood to saturate


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def make_engine(datapath_cls, cap=CT_CAP):
    cfg = DaemonConfig(ct_capacity=cap, auto_regen=False)
    eng = Engine(cfg, datapath=datapath_cls(cfg))
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.add_endpoint(["k8s:peer=p0", "k8s:group=g0"],
                     ips=("172.16.0.5",), ep_id=10)
    eng.apply_policy([{
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "ingress": [{"fromEndpoints": [{"matchLabels": {"group": "g0"}}],
                     "toPorts": [{"ports": [
                         {"port": "80", "protocol": "TCP"}]}]}]}])
    eng.regenerate()
    return eng


def flows(slots, sports, flags=C.TCP_SYN, dport=80):
    s16, _ = parse_addr("172.16.0.5")
    d16, _ = parse_addr("192.168.1.10")
    return batch_from_records(
        [PacketRecord(s16, d16, sp, dport, C.PROTO_TCP, flags, False, 1,
                      C.DIR_INGRESS) for sp in sports], slots)


def assert_same(a, b, msg=""):
    for k in OUT_KEYS:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      f"{msg}:{k}")


# --------------------------------------------------------------------------- #
# predicate + kernel units
# --------------------------------------------------------------------------- #
class TestEvictability:
    def test_established_tcp_protected_everything_else_fair_game(self):
        import jax.numpy as jnp
        from cilium_tpu.kernels.conntrack import ct_evictable
        proto = jnp.asarray([C.PROTO_TCP] * 4 + [C.PROTO_UDP, C.PROTO_ICMP])
        flags = jnp.asarray([
            0,                                           # SYN-stage TCP
            C.CT_FLAG_SEEN_NON_SYN,                      # established TCP
            C.CT_FLAG_SEEN_NON_SYN | C.CT_FLAG_TX_CLOSING,   # closing
            C.CT_FLAG_TX_CLOSING,                        # closing, no ack
            C.CT_FLAG_SEEN_NON_SYN,                      # UDP (flag moot)
            0,                                           # ICMP
        ], dtype=jnp.uint32)
        want = [True, False, True, True, True, True]
        assert np.asarray(ct_evictable(proto, flags)).tolist() == want
        # the oracle's host mirror agrees on every combination
        for p, f, w in zip(np.asarray(proto).tolist(),
                           np.asarray(flags).tolist(), want):
            assert _ct_expirable(int(p), int(f)) == w

    def test_insert_evicts_min_expiry_unprotected(self):
        """Direct kernel check on a tiny table: the eviction victim is the
        soonest-expiring evictable window slot, protected slots are
        skipped, and a window full of protected entries fails the
        insert."""
        import jax.numpy as jnp
        from cilium_tpu.kernels import conntrack as ctk
        cap, pd = 8, 8
        ct = {k: jnp.asarray(v) for k, v in
              make_ct_arrays(CTConfig(cap, pd)).items()}
        # fill all 8 slots with evictable SYN entries, distinct expiries
        b0 = flows({1: 0, 10: 1}, range(1000, 1012))
        bj = {k: jnp.asarray(v) for k, v in b0.items()}
        keys = ctk.ct_key_words_jnp(bj)
        nk, ncr, zm, slot, fail, _ = ctk.ct_insert_new(
            ct, keys, jnp.asarray([True] * 12), jnp.uint32(100), pd)
        ct = ctk.ct_apply(ct, bj, slot, jnp.zeros(12, bool), slot >= 0,
                          jnp.uint32(100), new_keys=nk, new_created=ncr,
                          zero_mask=zm)
        assert int((np.asarray(ct["expiry"]) > 100).sum()) == cap
        # stagger expiries so the min is unique and known
        exp = np.asarray(ct["expiry"]).copy()
        exp[:] = 200 + np.arange(cap) * 10
        ct = dict(ct)
        ct["expiry"] = jnp.asarray(exp)
        min_slot = 0                     # expiry 200 — the victim
        one = flows({1: 0, 10: 1}, [7777])
        oj = {k: jnp.asarray(v) for k, v in one.items()}
        okeys = ctk.ct_key_words_jnp(oj)
        nk, ncr, zm, slot, fail, nev = ctk.ct_insert_new(
            ct, okeys, jnp.asarray([True]), jnp.uint32(150), pd,
            evict=True)
        assert int(slot[0]) == min_slot and int(nev) == 1
        assert not bool(np.asarray(fail)[0])
        # same insert with the victim protected → next-soonest wins
        prot = jnp.zeros((cap,), bool).at[min_slot].set(True)
        nk, ncr, zm, slot, fail, nev = ctk.ct_insert_new(
            ct, okeys, jnp.asarray([True]), jnp.uint32(150), pd,
            evict=True, protected=prot)
        assert int(slot[0]) == 1 and int(nev) == 1
        # every slot protected → CT_FULL fail
        nk, ncr, zm, slot, fail, nev = ctk.ct_insert_new(
            ct, okeys, jnp.asarray([True]), jnp.uint32(150), pd,
            evict=True, protected=jnp.ones((cap,), bool))
        assert bool(np.asarray(fail)[0]) and int(nev) == 0

    def test_duplicates_adopt_evict_winner(self):
        import jax.numpy as jnp
        from cilium_tpu.kernels import conntrack as ctk
        cap, pd = 8, 8
        ct = {k: jnp.asarray(v) for k, v in
              make_ct_arrays(CTConfig(cap, pd)).items()}
        fill = flows({1: 0, 10: 1}, range(2000, 2012))
        fj = {k: jnp.asarray(v) for k, v in fill.items()}
        fkeys = ctk.ct_key_words_jnp(fj)
        nk, ncr, zm, slot, fail, _ = ctk.ct_insert_new(
            ct, fkeys, jnp.asarray([True] * 12), jnp.uint32(100), pd)
        ct = ctk.ct_apply(ct, fj, slot, jnp.zeros(12, bool), slot >= 0,
                          jnp.uint32(100), new_keys=nk, new_created=ncr,
                          zero_mask=zm)
        dup = flows({1: 0, 10: 1}, [9999, 9999, 9999])
        dj = {k: jnp.asarray(v) for k, v in dup.items()}
        dkeys = ctk.ct_key_words_jnp(dj)
        nk, ncr, zm, slot, fail, nev = ctk.ct_insert_new(
            ct, dkeys, jnp.asarray([True] * 3), jnp.uint32(150), pd,
            evict=True)
        s = np.asarray(slot)
        assert (s >= 0).all() and (s == s[0]).all()   # all adopt one slot
        assert int(nev) == 1                          # ONE eviction


# --------------------------------------------------------------------------- #
# oracle bounded-table semantics
# --------------------------------------------------------------------------- #
class TestBoundedOracle:
    def _oracle(self, cap=8, pd=4):
        return ConntrackTable(capacity=cap, probe_depth=pd)

    def _pkt(self, sport, flags=C.TCP_SYN):
        s16, _ = parse_addr("10.0.0.1")
        d16, _ = parse_addr("10.0.0.2")
        return PacketRecord(s16, d16, sport, 80, C.PROTO_TCP, flags)

    def test_create_fails_when_windows_full_of_established(self):
        tab = self._oracle(cap=4, pd=4)
        for sp in range(100, 104):
            key = tab.create(self._pkt(sp, flags=C.TCP_ACK), now=100)
            assert key is not None
        # all four entries have SEEN_NON_SYN (ACK create) → unevictable
        assert tab.create(self._pkt(999), now=150) is None
        assert tab.insert_fail == 1

    def test_create_evicts_soonest_expiring_syn(self):
        tab = self._oracle(cap=4, pd=4)
        keys = [tab.create(self._pkt(sp), now=100 + i)
                for i, sp in enumerate(range(200, 204))]
        assert all(k is not None for k in keys)
        # SYN entries: expiry 160..163; victim = the 160 one
        victim = keys[0]
        assert tab.create(self._pkt(888), now=150) is not None
        assert victim not in tab.entries
        assert tab.evicted == 1

    def test_unbounded_default_never_fails(self):
        tab = ConntrackTable()
        for sp in range(5000):
            assert tab.create(self._pkt(sp), now=100) is not None
        assert tab.insert_fail == 0

    @staticmethod
    def _open_oracle(tab=None):
        """Oracle with one unenforced endpoint (ep 0): everything allows
        at the policy layer, so CT semantics are the only variable."""
        from cilium_tpu.policy.mapstate import MapState
        from cilium_tpu.policy.repository import (DirectionPolicy,
                                                  EndpointPolicy)
        pol = EndpointPolicy(ep_id=0, identity_id=1, revision=1,
                             egress=DirectionPolicy(False, MapState()),
                             ingress=DirectionPolicy(False, MapState()))
        return Oracle({0: pol}, {}, ct=tab)

    def test_sequential_classify_emits_ct_full(self):
        """The sequential oracle's allowed-NEW flow against a saturated
        unevictable table → deny CT_FULL with ct_full set."""
        tab = self._oracle(cap=4, pd=4)
        oracle = self._open_oracle(tab)
        for sp in range(300, 304):
            v = oracle.classify(self._pkt(sp, flags=C.TCP_ACK), now=100)
            assert v.allow
        v = oracle.classify(self._pkt(777), now=150)
        assert not v.allow and not v.ct_status
        assert v.drop_reason == C.DropReason.CT_FULL and v.ct_full

    def test_replay_ct_full_only_excuses_demanded_creates(self):
        oracle = self._open_oracle()
        p = self._pkt(42)
        # demanded create + ct_full → the CT_FULL deny
        v, create = oracle.replay(p, C.CTStatus.NEW, ct_full=True)
        assert not v.allow and v.drop_reason == C.DropReason.CT_FULL
        assert not create
        # an ESTABLISHED row cannot be excused into a CT_FULL deny
        v, create = oracle.replay(p, C.CTStatus.ESTABLISHED, ct_full=True)
        assert v.allow and v.drop_reason == C.DropReason.OK


# --------------------------------------------------------------------------- #
# the bit-identity contract: the jitted step / bounded oracle
# --------------------------------------------------------------------------- #
class TestSaturationParity:
    def _run_flood(self, eng_a, eng_b, label):
        slots = eng_a.active.snapshot.ep_slot_of
        now = 1000
        # establish a protected population (ACK → SEEN_NON_SYN)
        est = flows(slots, range(30000, 30016), flags=0x10)
        assert_same(eng_a.classify(dict(est), now=now),
                    eng_b.classify(dict(est), now=now),
                    f"{label}:establish")
        # flood: distinct SYN flows, several times the table capacity —
        # saturation, tail evictions, CT_FULL fails
        for wave in range(4):
            now += 1
            fl = flows(slots, range(40000 + wave * CT_CAP,
                                    40000 + (wave + 1) * CT_CAP))
            assert_same(eng_a.classify(dict(fl), now=now),
                        eng_b.classify(dict(fl), now=now),
                        f"{label}:wave{wave}")
        # the established population survives the saturated table
        now += 1
        a = eng_a.classify(dict(est), now=now)
        b = eng_b.classify(dict(est), now=now)
        assert_same(a, b, f"{label}:revisit")
        assert (np.asarray(a["status"])[np.asarray(est["valid"])]
                == int(C.CTStatus.ESTABLISHED)).all()
        assert bool(np.asarray(a["allow"])[np.asarray(est["valid"])].all())
        # the flood actually exhausted windows on both engines, identically
        assert eng_a.metrics.insert_fail == eng_b.metrics.insert_fail
        assert eng_a.metrics.ct_evicted == eng_b.metrics.ct_evicted
        assert eng_a.metrics.ct_evicted > 0
        rendered = eng_a.render_metrics()
        assert "ciliumtpu_ct_evicted_total" in rendered
        assert "ciliumtpu_ct_insert_fail_total" in rendered

    def test_jnp_vs_bounded_oracle_bit_identical_under_saturation(self):
        eng_a = make_engine(JITDatapath)
        eng_b = make_engine(FakeDatapath)
        try:
            self._run_flood(eng_a, eng_b, "jnp")
        finally:
            eng_a.stop()
            eng_b.stop()

    def test_auditor_zero_mismatch_through_saturation(self):
        """The acceptance-criterion form: the shadow auditor at sampling
        1.0 replays a saturated table's verdicts (CT_FULL denies included)
        with zero mismatches and checked > 0."""
        eng = make_engine(JITDatapath)
        eng.auditor.configure(sample_rate=1.0)
        try:
            slots = eng.active.snapshot.ep_slot_of
            now = 1000
            eng.classify(flows(slots, range(30000, 30016), flags=0x10),
                         now=now)
            for wave in range(4):
                now += 1
                eng.classify(flows(slots,
                                   range(41000 + wave * CT_CAP,
                                         41000 + (wave + 1) * CT_CAP)),
                             now=now)
                eng.audit_step(budget=16)
            for _ in range(50):
                step = eng.audit_step(budget=64)
                if not step or not (step.get("replayed")
                                    or step.get("pending")):
                    break
            st = eng.auditor.stats()
            assert st["checked_rows"] > 0
            assert st["mismatched_rows"] == 0
            assert eng.metrics.insert_fail > 0      # genuinely saturated
        finally:
            eng.stop()


# --------------------------------------------------------------------------- #
# emergency GC
# --------------------------------------------------------------------------- #
class TestEmergencyGC:
    def test_hysteresis_latch_and_ttl_slash(self):
        """Occupancy past ct_pressure_high arms emergency mode (gauge +
        blackbox event), sweeps run full-rate with slashed TTLs and bound
        occupancy, and the latch exits below ct_pressure_low."""
        cfg = DaemonConfig(ct_capacity=256, auto_regen=False,
                           ct_gc_chunk_rows=64, ct_gc_emergency_chunks=4,
                           ct_gc_emergency_ttl_slash_s=55,
                           ct_pressure_high=0.7, ct_pressure_low=0.3)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.add_endpoint(["k8s:peer=p0", "k8s:group=g0"],
                         ips=("172.16.0.5",), ep_id=10)
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{"fromEndpoints": [
                {"matchLabels": {"group": "g0"}}],
                "toPorts": [{"ports": [
                    {"port": "80", "protocol": "TCP"}]}]}]}])
        eng.regenerate()
        try:
            slots = eng.active.snapshot.ep_slot_of
            now = 1000
            eng.classify(flows(slots, range(50000, 50224)), now=now)
            eng.sweep_step(now=now)       # enqueue
            st = eng.sweep_step(now=now)  # harvest → occupancy lands
            occ = eng.metrics.gauges["ct_occupancy"]
            assert occ >= 0.7             # a fraction, not a count
            assert eng._ct_emergency
            assert eng.metrics.gauges["ct_emergency_gc"] == 1
            assert st["emergency"] is False or st["emergency"] is True
            # SYN entries (60s life) die under the 55s slash within 6s
            for _ in range(6):
                now += 2
                st = eng.sweep_step(now=now)
                assert st["emergency"] in (True, False)
            occ = eng.metrics.gauges["ct_occupancy"]
            assert occ <= 0.3
            assert not eng._ct_emergency
            assert eng.metrics.gauges["ct_emergency_gc"] == 0
            assert eng.metrics.counters.get(
                "ct_emergency_sweeps_total", 0) > 0
            kinds = [e["kind"] for e in eng.blackbox._events]
            assert kinds.count("ct-emergency") >= 2   # enter + exit
            # commanded degradation never freezes the recorder
            assert eng.blackbox.stats()["frozen"] is False
        finally:
            eng.stop()

    def test_emergency_spares_established_flows(self):
        cfg = DaemonConfig(ct_capacity=256, auto_regen=False,
                           ct_gc_chunk_rows=256,
                           ct_gc_emergency_ttl_slash_s=55,
                           ct_pressure_high=0.5, ct_pressure_low=0.1)
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.add_endpoint(["k8s:peer=p0", "k8s:group=g0"],
                         ips=("172.16.0.5",), ep_id=10)
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{"fromEndpoints": [
                {"matchLabels": {"group": "g0"}}],
                "toPorts": [{"ports": [
                    {"port": "80", "protocol": "TCP"}]}]}]}])
        eng.regenerate()
        try:
            slots = eng.active.snapshot.ep_slot_of
            est = flows(slots, range(60000, 60032), flags=0x10)
            eng.classify(dict(est), now=1000)
            eng.classify(flows(slots, range(61000, 61160)), now=1001)
            for i in range(4):
                eng.sweep_step(now=1002 + 2 * i)
            assert eng._ct_emergency
            out = eng.classify(dict(est), now=1010)
            v = np.asarray(est["valid"])
            assert (np.asarray(out["status"])[v]
                    == int(C.CTStatus.ESTABLISHED)).all()
        finally:
            eng.stop()


# --------------------------------------------------------------------------- #
# the slow flood soak (`make chaos`)
# --------------------------------------------------------------------------- #
@pytest.mark.slow
class TestFloodSoak:
    def test_soak_saturated_table_audited_with_ct_insert_faults(self):
        """The acceptance soak: thousands of pipelined flood submissions
        saturate a small CT table with ``ct.insert`` faults armed and the
        auditor at sampling 1.0 — zero mismatches, checked > 0, evictions
        and CT_FULL fails observed, every submission resolves (classified
        or failed closed, FIFO intact)."""
        eng = make_engine(JITDatapath, cap=512)
        eng.auditor.configure(sample_rate=1.0)
        FAULTS.arm("ct.insert", mode="prob", prob=0.02, seed=11)
        try:
            slots = eng.active.snapshot.ep_slot_of
            now = 1000
            est = flows(slots, range(30000, 30032), flags=0x10)
            eng.submit(dict(est), now=now).result(timeout=120)
            rng = np.random.default_rng(3)
            tickets = []
            n_sub = 3000
            for i in range(n_sub):
                if i % 8 == 0:
                    now += 1
                sports = rng.integers(32768, 65535, 48)
                try:
                    tickets.append(eng.submit(
                        flows(slots, sports.tolist()), now=now))
                except Exception:
                    pass                      # breaker-open storms: fine
                if i % 64 == 0:
                    eng.audit_step(budget=32)
                if i % 256 == 0:
                    eng.sweep_step(now=now)
            assert eng.drain(timeout=300)
            resolved = failed = 0
            for t in tickets:
                try:
                    t.result(timeout=30)
                    resolved += 1
                except Exception:
                    failed += 1               # fail-closed is a resolution
            assert resolved + failed == len(tickets)
            assert resolved > 0
            for _ in range(200):
                step = eng.audit_step(budget=128)
                if not step or not (step.get("replayed")
                                    or step.get("pending")):
                    break
            st = eng.auditor.stats()
            assert st["checked_rows"] > 0
            assert st["mismatched_rows"] == 0
            assert eng.metrics.ct_evicted > 0
            assert eng.metrics.insert_fail > 0
            # the established population still classifies ESTABLISHED
            out = eng.submit(dict(est), now=now + 1).result(timeout=120)
            v = np.asarray(est["valid"])
            assert (np.asarray(out["status"])[v]
                    == int(C.CTStatus.ESTABLISHED)).all()
        finally:
            FAULTS.reset()
            eng.stop()
