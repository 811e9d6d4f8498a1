"""Tensor compiler tests: trie vs reference LPM, port/identity classes, and
the central property — dense verdict cells == sparse MapState ladder."""

import random

import numpy as np
import pytest

from cilium_tpu.compile.idclass import build_identity_classes
from cilium_tpu.compile.l7 import L7SetInterner, build_l7_tensors, l7_match_host
from cilium_tpu.compile.lpm import lpm_lookup_host
from cilium_tpu.compile.policy_image import build_policy_image
from cilium_tpu.compile.portclass import build_port_classes
from cilium_tpu.compile.snapshot import build_snapshot
from cilium_tpu.model.endpoint import Endpoint
from cilium_tpu.model.identity import IdentityAllocator
from cilium_tpu.model.ipcache import IPCache, lpm_lookup
from cilium_tpu.model.labels import Labels
from cilium_tpu.model.rules import HTTPRule, parse_rule
from cilium_tpu.policy import PolicyContext, Repository
from cilium_tpu.policy.mapstate import MapState, MapStateEntry, MapStateKey
from cilium_tpu.policy.selectorcache import SelectorCache
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr
from oracle.datapath import l7_match
# every table here is built by the vectorised builder AND by the loop it
# replaced, and the two compared array for array (PR 53)
from tests.test_lpm_build import checked_build_lpm as build_lpm


class TestLPM:
    def _roundtrip(self, entries, probes):
        ident_ids = sorted(set(entries.values()) | {C.IDENTITY_WORLD})
        index = {i: n for n, i in enumerate(ident_ids)}
        tables = build_lpm(entries, index, default_index=index[C.IDENTITY_WORLD])
        for addr in probes:
            want = lpm_lookup(entries, addr)
            addr16, is_v6 = parse_addr(addr)
            got_idx = lpm_lookup_host(tables, addr16, is_v6)
            assert ident_ids[got_idx] == want, f"{addr}: {ident_ids[got_idx]} != {want}"

    def test_basic_v4(self):
        entries = {"10.0.0.0/8": 100, "10.1.0.0/16": 200, "10.1.2.3/32": 300,
                   "0.0.0.0/0": 400}
        self._roundtrip(entries, ["10.1.2.3", "10.1.9.9", "10.2.0.1",
                                  "8.8.8.8", "10.1.2.4"])

    def test_miss_is_world(self):
        tables = build_lpm({"10.0.0.0/8": 100}, {100: 1, C.IDENTITY_WORLD: 0},
                           default_index=0)
        addr16, v6 = parse_addr("8.8.8.8")
        assert lpm_lookup_host(tables, addr16, v6) == 0

    def test_non_octet_prefixes(self):
        entries = {"10.0.0.0/12": 1, "10.16.0.0/12": 2, "10.0.0.0/9": 3,
                   "192.168.0.0/22": 4}
        self._roundtrip(entries, ["10.0.0.1", "10.15.255.255", "10.16.0.1",
                                  "10.31.9.9", "10.127.0.1", "10.128.0.1",
                                  "192.168.3.255", "192.168.4.0"])

    def test_v6(self):
        entries = {"2001:db8::/32": 1, "2001:db8:1::/48": 2, "::/0": 3,
                   "2001:db8:1:2::5/128": 4}
        self._roundtrip(entries, ["2001:db8::1", "2001:db8:1::9",
                                  "2001:db8:1:2::5", "fe80::1"])

    def test_family_separation(self):
        entries = {"::/0": 1, "0.0.0.0/0": 2}
        self._roundtrip(entries, ["1.2.3.4", "2001:db8::1"])

    def test_random_property(self):
        rng = random.Random(42)
        entries = {}
        for _ in range(300):
            plen = rng.choice([8, 12, 16, 20, 24, 28, 32])
            addr = f"{rng.randrange(1,224)}.{rng.randrange(256)}.{rng.randrange(256)}.{rng.randrange(256)}"
            import ipaddress
            net = str(ipaddress.ip_network(f"{addr}/{plen}", strict=False))
            entries[net] = rng.randrange(1000, 5000)
        probes = [f"{rng.randrange(1,224)}.{rng.randrange(256)}.{rng.randrange(256)}.{rng.randrange(256)}"
                  for _ in range(200)]
        self._roundtrip(entries, probes)


class TestPortClasses:
    def test_partition(self):
        t = build_port_classes({C.PROTO_FAMILY_TCP: [(80, 80), (8080, 8090)]})
        tcp = t.table[C.PROTO_FAMILY_TCP]
        assert tcp[80] != tcp[79] and tcp[80] != tcp[81]
        assert tcp[8080] == tcp[8085] == tcp[8090]
        assert tcp[8079] != tcp[8080] and tcp[8091] != tcp[8090]
        # contiguous runs between boundaries share a class
        assert tcp[0] == tcp[79] and tcp[81] == tcp[8079] and tcp[8091] == tcp[65535]
        assert tcp[79] != tcp[81]  # split at the 80 boundary

    def test_families_disjoint(self):
        t = build_port_classes({C.PROTO_FAMILY_TCP: [(80, 80)],
                                C.PROTO_FAMILY_UDP: [(53, 53)]})
        assert set(np.unique(t.table[C.PROTO_FAMILY_TCP])).isdisjoint(
            set(np.unique(t.table[C.PROTO_FAMILY_UDP])))

    def test_classes_for_range(self):
        t = build_port_classes({C.PROTO_FAMILY_TCP: [(10, 20), (15, 30)]})
        # [15,20] is exactly the overlap segment → exactly one class
        classes = t.classes_for_range(C.PROTO_FAMILY_TCP, 15, 20)
        assert len(classes) == 1
        # [10,30] spans three segments
        assert len(t.classes_for_range(C.PROTO_FAMILY_TCP, 10, 30)) == 3


class TestIdentityClasses:
    def test_same_entries_same_class(self):
        ms = MapState()
        for ident in (100, 200):
            ms.add(MapStateKey(ident, C.PROTO_TCP, 80, 80), MapStateEntry())
        ms.add(MapStateKey(300, C.PROTO_TCP, 443, 443), MapStateEntry())
        ic = build_identity_classes([2, 100, 200, 300, 400],
                                    [(0, C.DIR_INGRESS, ms)])
        cls = {i: ic.class_of[ic.index_of[i]] for i in (2, 100, 200, 300, 400)}
        assert cls[100] == cls[200]
        assert cls[300] != cls[100]
        assert cls[2] == cls[400] == 0  # untouched identities share class 0

    def test_deny_distinguishes(self):
        ms = MapState()
        ms.add(MapStateKey(100, C.PROTO_TCP, 80, 80), MapStateEntry())
        ms.add(MapStateKey(200, C.PROTO_TCP, 80, 80), MapStateEntry(deny=True))
        ic = build_identity_classes([100, 200], [(0, 0, ms)])
        assert ic.class_of[ic.index_of[100]] != ic.class_of[ic.index_of[200]]


class TestL7Tensors:
    def test_match_parity_with_oracle(self):
        interner = L7SetInterner()
        rules = frozenset({HTTPRule(method="GET", path="/api"),
                           HTTPRule(method="", path="/public")})
        sid = interner.intern(rules)
        t = build_l7_tensors(interner)
        cases = [
            (C.HTTP_METHOD_IDS["GET"], b"/api/users"),
            (C.HTTP_METHOD_IDS["POST"], b"/api"),
            (C.HTTP_METHOD_IDS["POST"], b"/public/x"),
            (C.HTTP_METHOD_IDS["GET"], b"/admin"),
            (C.HTTP_METHOD_IDS["GET"], b"/ap"),
            (C.HTTP_METHOD_IDS["GET"], b""),
        ]
        for method, path in cases:
            assert l7_match_host(t, sid, method, path) == \
                l7_match(rules, method, path), (method, path)


def _random_mapstate(rng, identities):
    ms = MapState()
    for _ in range(rng.randrange(1, 40)):
        ident = rng.choice([C.IDENTITY_ANY] + identities)
        kind = rng.random()
        if kind < 0.2:
            key = MapStateKey(ident, C.PROTO_ANY, 0, 65535)
        else:
            proto = rng.choice([C.PROTO_TCP, C.PROTO_UDP, C.PROTO_ICMP])
            if proto == C.PROTO_ICMP:
                t = rng.randrange(0, 40)
                key = MapStateKey(ident, proto, t, t)
            elif kind < 0.5:
                key = MapStateKey(ident, proto, 0, 65535)
            else:
                lo = rng.randrange(1, 65000)
                hi = min(65535, lo + rng.choice([0, 0, 0, 10, 1000]))
                key = MapStateKey(ident, proto, lo, hi)
        deny = rng.random() < 0.25
        l7 = None
        if not deny and rng.random() < 0.15:
            l7 = frozenset({HTTPRule(method="GET", path=f"/p{rng.randrange(5)}")})
        ms.add(key, MapStateEntry(deny=deny, l7_rules=l7))
    return ms


class TestDenseLadderEquivalence:
    """THE compiler property: dense verdict cell == sparse ladder, for every
    (identity, proto, port) probe."""

    @pytest.mark.parametrize("seed", range(6))
    def test_random_equivalence(self, seed):
        rng = random.Random(seed)
        identities = [100, 200, 300, 0x1000000, 0x1000001]
        ms = _random_mapstate(rng, identities)
        all_ids = identities + [C.IDENTITY_WORLD]
        ic = build_identity_classes(all_ids, [(0, C.DIR_EGRESS, ms)])
        ranges = {}
        for key, _ in ms.items():
            if key.proto == C.PROTO_ANY:
                continue
            ranges.setdefault(C.proto_family(key.proto), []).append(
                (key.port_lo, key.port_hi))
        pc = build_port_classes(ranges)
        l7 = L7SetInterner()
        from cilium_tpu.compile.policy_image import _build_plane
        plane = _build_plane(ms, ic, pc, l7, ic.n_classes, pc.n_classes)

        # probe every identity × proto × interesting ports
        probe_ports = set()
        for key, _ in ms.items():
            for p in (key.port_lo - 1, key.port_lo, key.port_hi, key.port_hi + 1):
                if 0 <= p <= 65535:
                    probe_ports.add(p)
        probe_ports |= {0, 1, 80, 443, 65535}
        for ident in all_ids:
            row = ic.class_of[ic.index_of[ident]]
            for proto in (C.PROTO_TCP, C.PROTO_UDP, C.PROTO_ICMP, C.PROTO_SCTP, 47):
                fam = C.proto_family(proto)
                for port in probe_ports:
                    col = pc.table[fam, port]
                    cell = int(plane[row, col])
                    got = cell & C.VERDICT_DECISION_MASK
                    want = ms.lookup(ident, proto, port).decision
                    assert got == want, (
                        f"seed={seed} id={ident} proto={proto} port={port}: "
                        f"dense={got} ladder={want}")


class TestSnapshot:
    def test_end_to_end_build(self):
        alloc = IdentityAllocator()
        ipc = IPCache()
        ctx = PolicyContext(allocator=alloc, selector_cache=SelectorCache(alloc),
                            ipcache=ipc)
        repo = Repository(ctx)
        lbls = Labels.parse(["k8s:app=web"])
        ident = alloc.allocate(lbls)
        ep = Endpoint(ep_id=7, labels=lbls, identity_id=ident.id)
        ipc.upsert("192.168.1.10/32", ident.id)
        repo.add([parse_rule({
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egress": [{"toCIDR": ["10.0.0.0/8"],
                        "toPorts": [{"ports": [{"port": "443", "protocol": "TCP"}]}]}],
            "ingress": [{"toPorts": [{
                "ports": [{"port": "80", "protocol": "TCP"}],
                "rules": {"http": [{"method": "GET", "path": "/api"}]}}]}],
        })])
        snap = build_snapshot(repo, ctx, [ep])
        assert snap.ep_slot_of[7] == 0
        assert snap.l7.n_sets == 1
        t = snap.tensors()
        assert t["verdict"].shape[0] == 1 and t["verdict"].shape[1] == 2
        # verdict sanity through the tensors: egress 443 to the CIDR identity
        cidr_id = ipc.lookup("10.5.5.5")
        row = snap.id_classes.class_of[snap.id_classes.index_of[cidr_id]]
        col = snap.port_classes.table[C.PROTO_FAMILY_TCP, 443]
        cell = int(t["verdict"][0, C.DIR_EGRESS, row, col])
        assert cell & C.VERDICT_DECISION_MASK == C.VERDICT_ALLOW
        # ingress 80 redirect cell carries an l7 id
        row_w = snap.id_classes.class_of[snap.id_classes.index_of[C.IDENTITY_WORLD]]
        col80 = snap.port_classes.table[C.PROTO_FAMILY_TCP, 80]
        cell80 = int(t["verdict"][0, C.DIR_INGRESS, row_w, col80])
        assert cell80 & C.VERDICT_DECISION_MASK == C.VERDICT_REDIRECT
        assert cell80 >> C.VERDICT_L7_SHIFT == 1
