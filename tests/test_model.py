"""Unit tests for the model layer (labels/selectors/rules/identity/ipcache)."""

import pytest

from cilium_tpu.model.labels import Label, Labels, parse_label
from cilium_tpu.model.selectors import EndpointSelector
from cilium_tpu.model.rules import (
    CIDRSelector, PortProtocol, RuleParseError, parse_rule, parse_rules,
)
from cilium_tpu.model.identity import IdentityAllocator, cidr_identity_labels
from cilium_tpu.model.ipcache import IPCache
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import addr_to_words, parse_addr, parse_prefix, addr_to_str


class TestLabels:
    def test_parse(self):
        lbl = parse_label("k8s:app=web")
        assert lbl == Label("k8s", "app", "web")
        assert parse_label("reserved:world") == Label("reserved", "world", "")
        assert parse_label("app=web") == Label("unspec", "app", "web")

    def test_sorted_canonical_and_hashable(self):
        a = Labels.parse(["k8s:app=web", "k8s:tier=fe"])
        b = Labels.parse(["k8s:tier=fe", "k8s:app=web"])
        assert a == b and hash(a) == hash(b)
        assert a.to_strings() == ("k8s:app=web", "k8s:tier=fe")

    def test_any_source_lookup(self):
        lbls = Labels.parse(["k8s:app=web"])
        assert lbls.get("any", "app").value == "web"
        assert lbls.get("k8s", "app").value == "web"
        assert lbls.get("reserved", "app") is None


class TestSelectors:
    def test_match_labels(self):
        sel = EndpointSelector.from_json({"matchLabels": {"app": "web"}})
        assert sel.matches(Labels.parse(["k8s:app=web"]))
        assert not sel.matches(Labels.parse(["k8s:app=db"]))

    def test_source_prefixed_key(self):
        sel = EndpointSelector.from_json({"matchLabels": {"reserved:world": ""}})
        assert sel.matches(Labels.reserved("world"))
        assert not sel.matches(Labels.parse(["k8s:world="]))

    def test_match_expressions(self):
        sel = EndpointSelector.from_json({"matchExpressions": [
            {"key": "app", "operator": "In", "values": ["web", "api"]},
            {"key": "banned", "operator": "DoesNotExist"},
        ]})
        assert sel.matches(Labels.parse(["k8s:app=api"]))
        assert not sel.matches(Labels.parse(["k8s:app=api", "k8s:banned=1"]))
        assert not sel.matches(Labels.parse(["k8s:app=db"]))

    def test_wildcard(self):
        sel = EndpointSelector.from_json({})
        assert sel.is_wildcard
        assert sel.matches(Labels())

    def test_any_source_spans_duplicate_keys(self):
        # same key under two sources: 'any' must consider all of them
        lbls = Labels.parse(["cidr:app=x", "k8s:app=web"])
        assert EndpointSelector.from_json(
            {"matchLabels": {"app": "web"}}).matches(lbls)
        assert EndpointSelector.from_json({"matchExpressions": [
            {"key": "app", "operator": "In", "values": ["web"]}]}).matches(lbls)
        assert not EndpointSelector.from_json({"matchExpressions": [
            {"key": "app", "operator": "NotIn", "values": ["web"]}]}).matches(lbls)

    def test_port_zero_with_endport_rejected(self):
        with pytest.raises(RuleParseError):
            PortProtocol(port=0, end_port=90, protocol="TCP")


class TestRules:
    def test_parse_basic_cnp(self):
        rule = parse_rule({
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"role": "fe"}}],
                "toPorts": [{"ports": [
                    {"port": "80", "protocol": "TCP"},
                    {"port": "8080", "endPort": 8090, "protocol": "TCP"},
                ]}],
            }],
        })
        assert rule.enforces_ingress and not rule.enforces_egress
        pr = rule.ingress[0].to_ports[0]
        assert pr.ports[0].port_range == (80, 80)
        assert pr.ports[1].port_range == (8080, 8090)

    def test_empty_section_flips_enforcement(self):
        rule = parse_rule({"endpointSelector": {}, "ingress": []})
        assert rule.enforces_ingress

    def test_cidrset_with_except(self):
        rule = parse_rule({
            "endpointSelector": {},
            "egress": [{"toCIDRSet": [
                {"cidr": "10.0.0.0/8", "except": ["10.1.0.0/16"]}]}],
        })
        cs = rule.egress[0].peer.cidrs[0]
        assert cs.cidr == "10.0.0.0/8" and cs.excepts == ("10.1.0.0/16",)

    def test_proto_any_expands(self):
        assert PortProtocol(port=53, protocol="ANY").protocols() == C.PORT_PROTOS

    def test_l7_http(self):
        rule = parse_rule({
            "endpointSelector": {},
            "ingress": [{"toPorts": [{
                "ports": [{"port": "80", "protocol": "TCP"}],
                "rules": {"http": [{"method": "GET", "path": "/api"}]},
            }]}],
        })
        assert rule.ingress[0].to_ports[0].http[0].method == "GET"

    def test_rejects_out_of_scope(self):
        with pytest.raises(RuleParseError):
            parse_rule({"endpointSelector": {},
                        "egress": [{"toRequires": [{}]}]})
        with pytest.raises(RuleParseError):
            parse_rule({"endpointSelector": {},
                        "ingressDeny": [{"toPorts": [{
                            "ports": [{"port": "80", "protocol": "TCP"}],
                            "rules": {"http": [{"path": "/"}]}}]}]})

    def test_entities(self):
        rule = parse_rule({"endpointSelector": {},
                           "egress": [{"toEntities": ["world", "cluster"]}]})
        assert rule.egress[0].peer.entities == ("world", "cluster")
        with pytest.raises(RuleParseError):
            parse_rule({"endpointSelector": {},
                        "egress": [{"toEntities": ["galaxy"]}]})


class TestIdentity:
    def test_reserved_preallocated(self):
        alloc = IdentityAllocator()
        assert alloc.get(C.IDENTITY_WORLD).labels == Labels.reserved("world")

    def test_idempotent_cluster_alloc(self):
        alloc = IdentityAllocator()
        a = alloc.allocate(Labels.parse(["k8s:app=web"]))
        b = alloc.allocate(Labels.parse(["k8s:app=web"]))
        assert a.id == b.id >= C.CLUSTER_IDENTITY_BASE

    def test_cidr_identity_is_local_scope(self):
        alloc = IdentityAllocator()
        ident = alloc.allocate_cidr("10.0.0.0/8")
        assert ident.id & C.LOCAL_IDENTITY_SCOPE
        assert ident.is_cidr
        # CIDR identities carry reserved:world (world-scoped)
        assert ident.labels.has("reserved", "world")

    def test_release_refcounted(self):
        alloc = IdentityAllocator()
        a = alloc.allocate(Labels.parse(["k8s:app=web"]))
        alloc.allocate(Labels.parse(["k8s:app=web"]))
        assert not alloc.release(a)
        assert alloc.release(a)
        assert alloc.get(a.id) is None

    def test_observer_notified(self):
        alloc = IdentityAllocator()
        events = []
        alloc.add_observer(lambda add, rem: events.append((len(add), len(rem))),
                           replay=False)
        ident = alloc.allocate(Labels.parse(["k8s:app=web"]))
        alloc.release(ident)
        assert events == [(1, 0), (0, 1)]

    def test_export_restore_stable(self):
        alloc = IdentityAllocator()
        a = alloc.allocate(Labels.parse(["k8s:app=web"]))
        state = alloc.export_state()
        alloc2 = IdentityAllocator()
        alloc2.restore_state(state)
        assert alloc2.lookup_by_labels(Labels.parse(["k8s:app=web"])).id == a.id
        b = alloc2.allocate(Labels.parse(["k8s:app=db"]))
        assert b.id == a.id + 1


    @pytest.mark.parametrize("prefix", ["10.0.0.0/8", "10.1.2.3/16",
                                        "2400:1234::/32", "2a00::1/128",
                                        "::ffff:10.0.0.0/104", "0.0.0.0/0"])
    def test_a_cidr_asked_for_again_is_the_same_identity_a_reference_more(
            self, prefix):
        """``allocate_cidr`` of a prefix it holds builds no label set and
        answers as it always did: the same identity, one reference more,
        released as many times as it was asked for; once gone, the prefix
        is a new identity with the same labels."""
        alloc, seen = IdentityAllocator(), []
        alloc.add_observer(lambda added, removed: seen.append(
            (len(added), len(removed))), replay=False)
        first = alloc.allocate_cidr(prefix)
        assert first.labels == cidr_identity_labels(prefix)
        assert alloc.lookup_by_labels(first.labels) is first
        for again in (prefix, prefix.upper(), first.labels and prefix):
            assert alloc.allocate_cidr(again) is first
        # ... and through the label set, as the rule materialisation may
        assert alloc.allocate(cidr_identity_labels(prefix)) is first
        assert seen == [(1, 0)]
        assert [alloc.release(first) for _ in range(5)] \
            == [False, False, False, False, True]
        assert alloc.get(first.id) is None and seen[-1] == (0, 1)
        assert not alloc.release(first)
        anew = alloc.allocate_cidr(prefix)
        assert anew.id == first.id + 1 and anew.labels == first.labels
        assert alloc.release(anew)

    def test_a_restored_allocator_answers_a_cidr_with_the_restored_identity(
            self):
        alloc = IdentityAllocator()
        a = alloc.allocate_cidr("36.0.0.0/8")
        alloc.allocate_cidr("36.0.0.0/8")
        other = IdentityAllocator()
        other.restore_state(alloc.export_state())
        b = other.allocate_cidr("36.0.0.0/8")
        assert b.id == a.id and b.labels == a.labels
        assert other.allocate_cidr("36.0.0.0/8") is b
        assert [other.release(b) for _ in range(4)] \
            == [False, False, False, True]


class TestIPCache:
    def test_lpm_most_specific_wins(self):
        cache = IPCache()
        cache.upsert("10.0.0.0/8", 100)
        cache.upsert("10.1.0.0/16", 200)
        cache.upsert("10.1.2.3/32", 300)
        assert cache.lookup("10.2.0.1") == 100
        assert cache.lookup("10.1.9.9") == 200
        assert cache.lookup("10.1.2.3") == 300

    def test_miss_is_world(self):
        cache = IPCache()
        assert cache.lookup("8.8.8.8") == C.IDENTITY_WORLD

    def test_family_separation(self):
        cache = IPCache()
        cache.upsert("::/0", 500)
        cache.upsert("0.0.0.0/0", 600)
        assert cache.lookup("1.2.3.4") == 600
        assert cache.lookup("2001:db8::1") == 500

    def test_revision_bumps(self):
        cache = IPCache()
        r0 = cache.revision
        cache.upsert("10.0.0.0/8", 1)
        assert cache.revision == r0 + 1


    TABLE = [("10.1.2.3/16", 7), ("2400:1234:5678::/40", 8),
             ("10.1.0.0/16", 9),              # the first again: the last stays
             ("0.0.0.0/1", 10), ("2A00:0:0::/12", 11), ("1.2.3.4", 12),
             ("::ffff:1.2.3.0/120", 13)]

    def test_bulk_and_single_entry_leave_the_same_cache(self):
        one, many = IPCache(), IPCache()
        for prefix, ident in self.TABLE:
            one.upsert(prefix, ident)
        assert many.upsert_many(self.TABLE) == len(self.TABLE) - 1
        assert many.snapshot() == one.snapshot() == {
            "10.1.0.0/16": 9, "2400:1234:5600::/40": 8, "0.0.0.0/1": 10,
            "2a00::/12": 11, "1.2.3.4/32": 12, "::ffff:1.2.3.0/120": 13}
        for addr in ("10.1.9.9", "2400:1234:56ff::1", "1.2.3.4", "9.9.9.9",
                     "200.1.1.1", "2a0f::1", "::ffff:1.2.3.9"):
            assert many.lookup(addr) == one.lookup(addr)
        assert many.get("10.1.77.1/16") == 9 and len(many) == len(one) == 6

    def test_a_bulk_entry_is_one_revision_and_one_call_of_the_observers(
            self):
        cache, calls = IPCache(), []
        cache.add_observer(lambda: calls.append(cache.revision))
        r0 = cache.revision
        assert cache.upsert_many(self.TABLE) == 6
        assert cache.revision == r0 + 1 and calls == [r0 + 1]
        # the same table again changes nothing: no revision, no observer
        # (a re-learnt table must not dirty the LPM), and it is counted
        assert cache.upsert_many(self.TABLE[2:]) == 0
        assert cache.upsert_many([]) == 0
        assert cache.revision == r0 + 1 and len(calls) == 1
        assert cache.bulk_upserts == 3
        # one entry of many changed: one revision
        assert cache.upsert_many([("10.1.0.0/16", 9), ("0.0.0.0/1", 77)]) \
            == 1
        assert cache.revision == r0 + 2 and cache.get("0.0.0.0/1") == 77
        # through the single door the same table costs a revision an entry
        single = IPCache()
        for prefix, ident in self.TABLE:
            single.upsert(prefix, ident)
        assert single.revision == len(self.TABLE) and single.bulk_upserts == 0

    def test_a_bulk_entry_with_a_bad_prefix_leaves_the_cache_as_it_was(self):
        cache = IPCache()
        cache.upsert("10.0.0.0/8", 1)
        r0 = cache.revision
        with pytest.raises(ValueError):
            cache.upsert_many([("11.0.0.0/8", 2), ("not-a-prefix/8", 3)])
        assert cache.snapshot() == {"10.0.0.0/8": 1}
        assert cache.revision == r0 and cache.bulk_upserts == 0

    def test_bulk_entry_is_one_regeneration_and_the_same_program_state(self):
        """For the engine a bulk entry means what the single ones mean: the
        dirty mark, one regeneration, and tries, identities and verdicts
        that are those of the same table entered prefix by prefix."""
        import numpy as np
        from cilium_tpu.runtime.config import DaemonConfig
        from cilium_tpu.runtime.datapath import FakeDatapath
        from cilium_tpu.runtime.engine import Engine
        table = [("36.1.0.0/16", "36.0.0.0/8"), ("36.1.2.0/24", "36.0.0.0/8"),
                 ("200.7.0.0/20", "200.0.0.0/8"),
                 ("2400:1234::/32", "2400::/16"),
                 ("2a00:1:2::/48", "2a00::/16")]
        doc = [{"endpointSelector": {"matchLabels": {"app": "web"}},
                "egress": [{"toCIDR": ["0.0.0.0/1", "2000::/5"]}]}]
        engines = []
        for bulk in (True, False):
            cfg = DaemonConfig(auto_regen=False, ct_capacity=1 << 10)
            eng = Engine(cfg, datapath=FakeDatapath(cfg))
            engines.append(eng)
            eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",), ep_id=1)
            eng.apply_policy(doc)
            eng.regenerate()
            placed0 = eng.datapath.placed_total
            idents = [eng.ctx.allocator.allocate_cidr(q).id
                      for _p, q in table]
            if bulk:
                eng.ctx.ipcache.upsert_many(
                    [(p, i) for (p, _q), i in zip(table, idents)])
            else:
                for (p, _q), i in zip(table, idents):
                    eng.ctx.ipcache.upsert(p, i)
            assert eng._dirty
            eng.regenerate()
            assert eng.datapath.placed_total == placed0 + 1
            assert not eng._dirty
        try:
            a, b = (e.active.snapshot for e in engines)
            assert a.ipcache == b.ipcache and a.lpm.prefixes == b.lpm.prefixes
            np.testing.assert_array_equal(a.lpm.v4_nodes, b.lpm.v4_nodes)
            np.testing.assert_array_equal(a.lpm.v6_nodes, b.lpm.v6_nodes)
            assert [(i.id, i.labels) for i in engines[0].ctx.allocator.all()] \
                == [(i.id, i.labels) for i in engines[1].ctx.allocator.all()]
            text = engines[0].render_metrics()
            assert "ciliumtpu_ipcache_bulk_upserts_total 1" in text
            assert "ipcache_bulk_upserts_total" not in \
                engines[1].render_metrics()
        finally:
            for eng in engines:
                eng.stop()


class TestIPUtils:
    @pytest.mark.parametrize("text", [
        "10.1.2.3/16", "0.0.0.0/0", "255.255.255.255/32", "1.2.3.4/31",
        "2400:1234:5678::/40", "2A00::/12", "2001:db8:0:0:1:0:0:1/128",
        "2001:0:0:1::/64", "fd00::10/128", "2000::/5", "::/0", "::1/128",
        "::ffff:1.2.3.4/128", "1.2.3.4", "fe80::1", "1.2.3.4/255.255.0.0",
        "2001:db8::/032"])
    def test_the_plain_forms_fast_path_is_ipaddress_to_the_letter(self,
                                                                   text):
        """``normalize_prefix`` / ``parse_prefix`` take plain forms through
        the C library's parser (a routing table's million prefixes) and
        everything else through ``ipaddress``: the same answer either
        way."""
        import ipaddress
        from cilium_tpu.utils.ip import (V4_MAPPED_PREFIX, _plain_prefix,
                                         normalize_prefix)
        net = ipaddress.ip_network(text, strict=False)
        assert normalize_prefix(text) == str(net)
        packed = net.network_address.packed
        assert parse_prefix(text) == (
            (V4_MAPPED_PREFIX + packed, 96 + net.prefixlen, False)
            if net.version == 4 else (packed, net.prefixlen, True))
        plain = "/" in text and "/255." not in text \
            and not text.startswith("::")
        assert (_plain_prefix(text) is not None) == plain

    @pytest.mark.parametrize("text", ["1.2.3/8", "1.2.3.4/33", "a/b",
                                      "2001:db8::/129", "1.2.3.4/-1",
                                      "fe80::1%eth0/64x", ""])
    def test_what_ipaddress_refuses_is_refused_as_before(self, text):
        from cilium_tpu.utils.ip import normalize_prefix
        with pytest.raises(ValueError):
            normalize_prefix(text)
        with pytest.raises(ValueError):
            parse_prefix(text)

    def test_v4_mapped(self):
        addr, is_v6 = parse_addr("1.2.3.4")
        assert not is_v6
        assert addr_to_str(addr) == "1.2.3.4"
        assert addr_to_words(addr) == (0, 0, 0xFFFF, 0x01020304)

    def test_prefix_normalization(self):
        net, plen, is_v6 = parse_prefix("10.1.2.3/16")
        assert plen == 96 + 16 and not is_v6
        assert addr_to_str(net) == "10.1.0.0"
