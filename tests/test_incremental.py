"""Incremental tensor updates (SURVEY.md §3.2 hot spot, §7 step 3): after
any sequence of rule add/remove/refresh, the patched snapshot must be
semantically identical to a fresh build_snapshot — same decision and same L7
rule set for every (endpoint, direction, identity, proto, port), same
enforced flags, same mapstate lookups. Class partitions may differ (splits
are never re-merged); that is representation, not semantics, so equivalence
is asserted through the lookup surface, not array equality."""

import random

import numpy as np
import pytest

from cilium_tpu.compile.ct_layout import CTConfig
from cilium_tpu.compile.incremental import IncrementalCompiler
from cilium_tpu.compile.snapshot import build_snapshot
from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.model.endpoint import Endpoint
from cilium_tpu.model.identity import IdentityAllocator
from cilium_tpu.model.ipcache import IPCache
from cilium_tpu.model.labels import Labels
from cilium_tpu.model.rules import parse_rule
from cilium_tpu.policy import PolicyContext, Repository
from cilium_tpu.policy.selectorcache import SelectorCache
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath, JITDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.utils import constants as C
from oracle import PacketRecord
from cilium_tpu.utils.ip import parse_addr


# --------------------------------------------------------------------------- #
# world + equivalence helpers
# --------------------------------------------------------------------------- #
N_PEERS = 12


def make_world(n_eps=2, n_peers=N_PEERS):
    alloc = IdentityAllocator()
    ctx = PolicyContext(allocator=alloc,
                        selector_cache=SelectorCache(alloc),
                        ipcache=IPCache())
    repo = Repository(ctx)
    eps = []
    for e in range(n_eps):
        lbls = Labels.parse([f"k8s:app=web{e}"])
        ident = alloc.allocate(lbls)
        ctx.ipcache.upsert(f"192.168.{e}.10/32", ident.id)
        eps.append(Endpoint(ep_id=e + 1, labels=lbls, identity_id=ident.id))
    for i in range(n_peers):
        ident = alloc.allocate(Labels.parse(
            [f"k8s:peer=p{i}", f"k8s:group=g{i % 3}"]))
        ctx.ipcache.upsert(f"172.16.{i}.0/24", ident.id)
    return ctx, repo, eps


def _cell_lookup(snap, slot, d, ident_id, proto, dport):
    """Resolve one probe through a snapshot's dense tensors (host-side
    mirror of kernels/policy.policy_lookup_batch)."""
    if not snap.image.enforced[slot, d]:
        return ("unenforced",)
    idx = snap.id_classes.index_of[ident_id]
    cls = snap.id_classes.class_of[idx]
    fam = C.proto_family(proto)
    pcls = snap.port_classes.table[fam, dport]
    cell = int(snap.image.verdict[slot, d, cls, pcls])
    decision = cell & C.VERDICT_DECISION_MASK
    if decision == C.VERDICT_REDIRECT:
        l7 = snap.l7_interner.sets[(cell >> C.VERDICT_L7_SHIFT) - 1]
        return (decision, frozenset(l7))
    return (decision,)


def assert_equivalent(inc_snap, fresh_snap, probes):
    assert inc_snap.revision == fresh_snap.revision
    np.testing.assert_array_equal(inc_snap.image.enforced,
                                  fresh_snap.image.enforced)
    for slot, d, ident, proto, dport in probes:
        got = _cell_lookup(inc_snap, slot, d, ident, proto, dport)
        want = _cell_lookup(fresh_snap, slot, d, ident, proto, dport)
        assert got == want, (slot, d, ident, proto, dport, got, want)
        # the sparse (oracle-facing) mapstates must agree too
        gi = inc_snap.policies[slot].direction(d)
        fi = fresh_snap.policies[slot].direction(d)
        assert gi.enforced == fi.enforced
        ri = gi.lookup(ident, proto, dport)
        rf = fi.lookup(ident, proto, dport)
        assert ri.decision == rf.decision, (slot, d, ident, proto, dport)
        if ri.entry is not None and rf.entry is not None:
            assert (ri.entry.deny, ri.entry.l7_rules) \
                == (rf.entry.deny, rf.entry.l7_rules)


def make_probes(ctx, n_eps):
    idents = [i.id for i in ctx.allocator.all()]
    ports = [0, 1, 53, 79, 80, 81, 443, 999, 1000, 1001, 5000, 8079,
             8080, 8081, 32768, 65535]
    probes = []
    for slot in range(n_eps):
        for d in (C.DIR_EGRESS, C.DIR_INGRESS):
            for ident in idents:
                for proto in (C.PROTO_TCP, C.PROTO_UDP):
                    for p in ports:
                        probes.append((slot, d, ident, proto, p))
    return probes


def l4_rule(ep_sel, group, port, proto="TCP", deny=False, l7=None,
            direction="ingress"):
    block = {"fromEndpoints" if direction.startswith("in") else "toEndpoints":
             [{"matchLabels": {"group": f"g{group}"}}]}
    if port is not None:
        pr = {"ports": [{"port": str(port), "protocol": proto}]}
        if l7:
            pr["rules"] = {"http": l7}
        block["toPorts"] = [pr]
    key = direction if not deny else direction + "Deny"
    return parse_rule({
        "endpointSelector": {"matchLabels": {"app": ep_sel}},
        key: [block]})


# --------------------------------------------------------------------------- #
# randomized sequence parity (the round-4 "done" criterion)
# --------------------------------------------------------------------------- #
class TestRandomizedParity:
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_add_remove_refresh_sequences(self, seed):
        rng = random.Random(seed)
        ctx, repo, eps = make_world()
        # a starting rule set so the first build has real geometry
        repo.add([l4_rule("web0", 0, 80),
                  l4_rule("web0", 1, 443, deny=True),
                  l4_rule("web1", 2, None)])
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        inc = IncrementalCompiler(repo, ctx, eps, snap)
        probes = make_probes(ctx, len(eps))

        label_pool = [f"batch={b}" for b in range(6)]
        for step in range(14):
            op = rng.random()
            tag = rng.choice(label_pool)
            if op < 0.55 or len(repo) < 2:
                kind = rng.random()
                port = rng.choice([80, 81, 443, 1000, 8080, None])
                group = rng.randrange(3)
                ep_sel = rng.choice(["web0", "web1"])
                if kind < 0.25:
                    rule = l4_rule(ep_sel, group, port, deny=True)
                elif kind < 0.45 and port is not None:
                    rule = l4_rule(ep_sel, group, port,
                                   l7=[{"method": "GET",
                                        "path": f"/v{step}"}])
                elif kind < 0.6:
                    rule = l4_rule(ep_sel, group, port, proto="UDP")
                else:
                    rule = l4_rule(ep_sel, group, port)
                # tag rules so removal batches have labels to match
                object.__setattr__(rule, "labels",
                                   Labels.parse([f"k8s:{tag}"]))
                repo.add([rule])
            else:
                repo.delete_by_labels(Labels.parse([f"k8s:{tag}"]))

            result = inc.try_update(CTConfig(capacity=1024))
            assert result is not None, \
                f"unexpected fallback at step {step}: {inc.last_fallback}"
            inc_snap, patch, stats = result
            fresh = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
            assert_equivalent(inc_snap, fresh, probes)

    def test_emitted_snapshots_stay_frozen(self):
        """Revision fencing: updating must not mutate previously emitted
        snapshots (COW discipline)."""
        ctx, repo, eps = make_world()
        repo.add([l4_rule("web0", 0, 80)])
        snap0 = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        inc = IncrementalCompiler(repo, ctx, eps, snap0)
        v0 = snap0.image.verdict.copy()
        ms_len0 = len(snap0.policies[0].ingress.mapstate)

        repo.add([l4_rule("web0", 1, 443, deny=True)])
        snap1, _, _ = inc.try_update(CTConfig(capacity=1024))
        v1 = snap1.image.verdict.copy()
        ms_len1 = len(snap1.policies[0].ingress.mapstate)

        repo.add([l4_rule("web0", 2, 8080)])
        inc.try_update(CTConfig(capacity=1024))

        np.testing.assert_array_equal(snap0.image.verdict, v0)
        np.testing.assert_array_equal(snap1.image.verdict, v1)
        assert len(snap0.policies[0].ingress.mapstate) == ms_len0
        assert len(snap1.policies[0].ingress.mapstate) == ms_len1


class TestGeometryPaths:
    def test_port_class_split(self):
        """A new port that bisects an existing class appends columns, not a
        rebuild."""
        ctx, repo, eps = make_world()
        repo.add([l4_rule("web0", 0, 80)])
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        inc = IncrementalCompiler(repo, ctx, eps, snap)
        cols0 = snap.image.verdict.shape[3]
        repo.add([l4_rule("web0", 1, 5000)])   # new boundary pair
        inc_snap, patch, stats = inc.try_update(CTConfig(capacity=1024))
        assert stats.port_class_splits >= 1
        assert inc_snap.image.verdict.shape[3] > cols0
        assert "port_class" in patch.full_tensors
        fresh = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        assert_equivalent(inc_snap, fresh, make_probes(ctx, len(eps)))

    def test_identity_class_split(self):
        """A rule targeting one member of a shared class splits it (row
        append + copy), keeping every other member's verdicts intact."""
        ctx, repo, eps = make_world()
        # one rule covering the whole g0 group → its members share a class
        repo.add([l4_rule("web0", 0, 80)])
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        inc = IncrementalCompiler(repo, ctx, eps, snap)
        # now target ONE pod of g0 specifically
        rule = parse_rule({
            "endpointSelector": {"matchLabels": {"app": "web0"}},
            "ingressDeny": [{"fromEndpoints": [
                {"matchLabels": {"peer": "p0"}}]}]})
        repo.add([rule])
        inc_snap, patch, stats = inc.try_update(CTConfig(capacity=1024))
        assert stats.id_class_splits >= 1
        fresh = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        assert_equivalent(inc_snap, fresh, make_probes(ctx, len(eps)))

    def test_enforced_flip(self):
        """First rule for a direction flips enforced; removing the last rule
        flips it back — both as patches."""
        ctx, repo, eps = make_world()
        repo.add([l4_rule("web0", 0, 80)])     # ingress enforced for web0
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        inc = IncrementalCompiler(repo, ctx, eps, snap)
        rule = l4_rule("web0", 1, 443, direction="egress")
        object.__setattr__(rule, "labels", Labels.parse(["k8s:eg=1"]))
        repo.add([rule])                       # egress now enforced
        inc_snap, patch, _ = inc.try_update(CTConfig(capacity=1024))
        assert "enforced" in patch.full_tensors
        fresh = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        assert_equivalent(inc_snap, fresh, make_probes(ctx, len(eps)))
        repo.delete_by_labels(Labels.parse(["k8s:eg=1"]))
        inc_snap, patch, _ = inc.try_update(CTConfig(capacity=1024))
        fresh = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        assert_equivalent(inc_snap, fresh, make_probes(ctx, len(eps)))

    def test_identity_growth_absorbed_removal_gates(self):
        """ISSUE 12: a CIDR rule allocating NEW identities (+ ipcache
        entries) is absorbed incrementally — appended singleton classes +
        an LPM rebuild in the patch, equivalent to a fresh build. Since
        ISSUE 18, identity REMOVAL (the rule deleted, identities
        released) is ALSO absorbed: retirement tombstones the dead
        class's rows and excises the prefix in the same patch."""
        ctx, repo, eps = make_world()
        repo.add([l4_rule("web0", 0, 80)])
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        inc = IncrementalCompiler(repo, ctx, eps, snap)
        cidr = parse_rule({
            "endpointSelector": {"matchLabels": {"app": "web0"}},
            "egress": [{"toCIDR": ["10.5.0.0/16"]}]})
        repo.add([cidr])
        res = inc.try_update(CTConfig(capacity=1024))
        assert res is not None, inc.last_fallback
        inc_snap, patch, stats = res
        assert stats.new_identities == 1
        assert stats.lpm_rebuilt
        assert {"verdict", "id_class_of", "identity_ids",
                "lpm_v4", "lpm_v6"} <= patch.full_tensors
        fresh = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        assert_equivalent(inc_snap, fresh, make_probes(ctx, len(eps)))
        # the new CIDR identity resolves through the patched LPM exactly
        # like the fresh build's
        from cilium_tpu.compile.lpm import lpm_lookup_host
        a16, _ = __import__("cilium_tpu.utils.ip", fromlist=["parse_addr"]
                            ).parse_addr("10.5.1.2")
        assert lpm_lookup_host(inc_snap.lpm, a16, False) \
            == lpm_lookup_host(fresh.lpm, a16, False)
        # removal (ISSUE 18): the rule's release retires the identity on
        # the delta path — tombstoned verdict rows + an LPM rebuild in the
        # patch, still equivalent to a fresh build from the shrunk world
        repo.clear()
        res = inc.try_update(CTConfig(capacity=1024))
        assert res is not None, inc.last_fallback
        inc_snap2, _patch2, stats2 = res
        assert stats2.retired_identities == 1
        fresh2 = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        assert_equivalent(inc_snap2, fresh2, make_probes(ctx, len(eps)))


# --------------------------------------------------------------------------- #
# engine integration: the production loop actually uses the patch path
# --------------------------------------------------------------------------- #
def _mk_pkt(src, dst, sp, dp, ep_id, direction, proto=C.PROTO_TCP,
            flags=C.TCP_SYN):
    s16, _ = parse_addr(src)
    d16, _ = parse_addr(dst)
    return PacketRecord(s16, d16, sp, dp, proto, flags, False, ep_id,
                        direction)


class TestEngineIncremental:
    def _world_engine(self, datapath, incremental=True):
        eng = Engine(DaemonConfig(ct_capacity=2048, auto_regen=False,
                                  incremental=incremental),
                     datapath=datapath)
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        for i in range(6):
            eng.add_endpoint([f"k8s:peer=p{i}", f"k8s:group=g{i % 2}"],
                             ips=(f"172.16.{i}.5",), ep_id=10 + i)
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{"fromEndpoints": [{"matchLabels": {"group": "g0"}}],
                         "toPorts": [{"ports": [
                             {"port": "80", "protocol": "TCP"}]}]}]}])
        eng.regenerate()
        return eng

    def _traffic(self, slots):
        pkts = []
        for i in range(6):
            for dp in (80, 443, 8080):
                pkts.append(_mk_pkt(f"172.16.{i}.5", "192.168.1.10",
                                    30000 + i, dp, 1, C.DIR_INGRESS))
        return batch_from_records(pkts, slots)

    @pytest.mark.parametrize("backend", ["jit", "fake"])
    def test_incremental_engine_matches_full_engine(self, backend):
        def dp(inc):
            if backend == "jit":
                return JITDatapath(DaemonConfig(ct_capacity=2048,
                                                auto_regen=False))
            return FakeDatapath(DaemonConfig(ct_capacity=2048))
        eng_inc = self._world_engine(dp(True), incremental=True)
        eng_full = self._world_engine(dp(False), incremental=False)
        updates = [
            [{"endpointSelector": {"matchLabels": {"app": "web"}},
              "ingress": [{"fromEndpoints": [
                  {"matchLabels": {"group": "g1"}}],
                  "toPorts": [{"ports": [
                      {"port": "443", "protocol": "TCP"}]}]}]}],
            [{"endpointSelector": {"matchLabels": {"app": "web"}},
              "ingressDeny": [{"fromEndpoints": [
                  {"matchLabels": {"peer": "p0"}}]}]}],
            [{"endpointSelector": {"matchLabels": {"app": "web"}},
              "ingress": [{"toPorts": [{
                  "ports": [{"port": "8080", "protocol": "TCP"}],
                  "rules": {"http": [{"method": "GET",
                                      "path": "/api"}]}}]}]}],
        ]
        now = 1000
        for docs in updates:
            eng_inc.apply_policy(docs)
            eng_full.apply_policy(docs)
            eng_inc.regenerate()
            eng_full.regenerate()
            slots = eng_inc.active.snapshot.ep_slot_of
            assert slots == eng_full.active.snapshot.ep_slot_of
            batch = self._traffic(slots)
            out_i = eng_inc.classify(dict(batch), now=now)
            out_f = eng_full.classify(dict(batch), now=now)
            for k in ("allow", "reason", "status", "remote_identity",
                      "redirect"):
                np.testing.assert_array_equal(
                    np.asarray(out_f[k]), np.asarray(out_i[k]), k)
            now += 50
        # the incremental path must actually have been taken
        rendered = eng_inc.metrics.render_prometheus()
        assert "regen_incremental_total" in rendered

    @pytest.mark.parametrize("mesh", [False, True],
                             ids=["one-chip", "mesh"])
    def test_ipcache_delta_reships_the_placed_tries(self, mesh):
        """Full build and incremental patch alike place both tries in the
        placed form (``[n * 256, 3]``, a view of the host form; what the
        walk gathers from: kernels/lpm.py), one chip and mesh alike, and
        a delta that leaves the node counts as they were compiles nothing
        anew (counted on one chip, where the step is one jitted
        function)."""
        shards = dict(n_shards=2, rule_shards=2) if mesh else {}
        eng = self._world_engine(JITDatapath(DaemonConfig(
            ct_capacity=2048, auto_regen=False, **shards)))
        try:
            step = eng.datapath._classify
            before = eng.active
            batch = self._traffic(before.snapshot.ep_slot_of)
            eng.classify(dict(batch), now=1000)
            programs = None if mesh else step._cache_size()
            patched = eng.metrics.counters.get("regen_incremental_total", 0)
            # a second address of peer p0, in the node its first one made:
            # a value more, no node more
            eng.ctx.ipcache.upsert("172.16.0.6/32",
                                   eng.endpoints[10].identity_id)
            eng.regenerate()
            after = eng.active
            assert eng.metrics.counters["regen_incremental_total"] \
                == patched + 1
            assert after.snapshot.lpm.v4_nodes.shape \
                == before.snapshot.lpm.v4_nodes.shape
            for compiled in (before, after):
                lpm, host = compiled.snapshot.lpm, compiled.snapshot.tensors()
                for name, nodes in (("lpm_v4", lpm.v4_nodes),
                                    ("lpm_v6", lpm.v6_nodes)):
                    assert host[name].shape == (nodes.shape[0] * 256, 3)
                    assert np.shares_memory(host[name], nodes)
                    dev = compiled.tensors[name]
                    assert dev.shape == host[name].shape
                    np.testing.assert_array_equal(np.asarray(dev),
                                                  host[name])
            for name in ("lpm_v4", "lpm_v6"):        # re-shipped whole
                assert after.tensors[name] is not before.tensors[name]
            assert after.tensors["port_class"] is before.tensors["port_class"]
            # as many rows as the first batch: the same bucket
            pkts = [_mk_pkt(src, "192.168.1.10", 30000 + i, 80, 1,
                            C.DIR_INGRESS)
                    for i in range(6)
                    for src in ("172.16.0.6", "172.16.0.5", "172.16.0.7")]
            out = eng.classify(batch_from_records(
                pkts, after.snapshot.ep_slot_of), now=1050)
            ids = np.asarray(out["remote_identity"])[:3]
            assert ids[0] == ids[1] == eng.endpoints[10].identity_id
            assert ids[2] == C.IDENTITY_WORLD
            assert mesh or step._cache_size() == programs
        finally:
            eng.stop()

    def test_incremental_sharded_backend(self):
        """place_patch through the meshed backend: device-side row updates
        on a sharded verdict tensor."""
        eng_inc = self._world_engine(
            JITDatapath(DaemonConfig(ct_capacity=2048, auto_regen=False,
                                     n_shards=2, rule_shards=2)),
            incremental=True)
        eng_full = self._world_engine(
            FakeDatapath(DaemonConfig(ct_capacity=2048)), incremental=False)
        eng_inc.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingressDeny": [{"fromEndpoints": [
                {"matchLabels": {"peer": "p2"}}]}]}])
        eng_full.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingressDeny": [{"fromEndpoints": [
                {"matchLabels": {"peer": "p2"}}]}]}])
        eng_inc.regenerate()
        eng_full.regenerate()
        slots = eng_inc.active.snapshot.ep_slot_of
        batch = self._traffic(slots)
        out_i = eng_inc.classify(dict(batch), now=500)
        out_f = eng_full.classify(dict(batch), now=500)
        for k in ("allow", "reason", "status", "remote_identity"):
            np.testing.assert_array_equal(
                np.asarray(out_f[k]), np.asarray(out_i[k]), k)


class TestEndpointGate:
    def test_add_endpoint_falls_back_to_full_build(self):
        """Regression (round-5 review): a new endpoint reusing an existing
        identity (no ipcache change) must still invalidate the incremental
        path — the snapshot's ep_slot space changed."""
        eng = Engine(DaemonConfig(ct_capacity=1024, auto_regen=False,
                                  incremental=True),
                     datapath=FakeDatapath(DaemonConfig(ct_capacity=1024)))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{"toPorts": [{"ports": [
                {"port": "80", "protocol": "TCP"}]}]}]}])
        eng.regenerate()
        # same labels → identity refcount reuse; no IP → no ipcache bump
        eng.add_endpoint(["k8s:app=web"], ep_id=2)
        snap = eng.regenerate().snapshot
        assert 2 in snap.ep_slot_of, "new endpoint missing from snapshot"
        eng.remove_endpoint(2)
        snap = eng.regenerate().snapshot
        assert 2 not in snap.ep_slot_of


class TestMoreGates:
    def test_enforcement_mode_change_gates(self):
        """Runtime enforcement-mode change (PATCH /v1/config path) must not
        be absorbed by the incremental compiler — it rewrites every plane."""
        ctx, repo, eps = make_world()
        repo.add([l4_rule("web0", 0, 80)])
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        inc = IncrementalCompiler(repo, ctx, eps, snap)
        ctx.enforcement_mode = C.ENFORCEMENT_NEVER
        assert inc.try_update(CTConfig(capacity=1024)) is None
        assert inc.last_fallback == "enforcement-mode-changed"

    def test_endpoint_gate_via_param(self):
        """The endpoints kwarg drives the endpoint-set gate."""
        ctx, repo, eps = make_world()
        repo.add([l4_rule("web0", 0, 80)])
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        inc = IncrementalCompiler(repo, ctx, eps, snap)
        grown = list(eps) + [Endpoint(ep_id=99, labels=eps[0].labels,
                                      identity_id=eps[0].identity_id)]
        assert inc.try_update(CTConfig(capacity=1024),
                              endpoints=grown) is None
        assert inc.last_fallback == "endpoint-set-changed"
        # unchanged set still patches
        repo.add([l4_rule("web0", 1, 443)])
        assert inc.try_update(CTConfig(capacity=1024),
                              endpoints=eps) is not None
