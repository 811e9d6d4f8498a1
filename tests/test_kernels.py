"""Kernel unit tests on the CPU backend: hash np/jnp agreement, LPM walk vs
host reference, L7 match vs host reference, CT probe/insert mechanics."""

import numpy as np
import pytest

import jax.numpy as jnp

from cilium_tpu.compile.ct_layout import CTConfig, make_ct_arrays
from cilium_tpu.compile.l7 import L7SetInterner, build_l7_tensors, l7_match_host
from cilium_tpu.compile.lpm import (build_lpm, lpm_lookup_host,
                                    lpm_lookup_host_prov, pack_pfx)
from cilium_tpu.kernels import conntrack as ctk
from cilium_tpu.kernels.hashing import hash_words_jnp, hash_words_np
from cilium_tpu.kernels.l7 import l7_match_batch
from cilium_tpu.kernels.lpm import lpm_lookup_batch, lpm_lookup_prov_batch
from cilium_tpu.kernels.records import (PACK4_L7_WORDS, PACK4_WORDS,
                                        PACK_L7DICT_WORDS, PACK_WORDS,
                                        _pack_path_dict, _pad_dict_rows,
                                        _path_words_of, ct_key_words,
                                        empty_batch, pack_batch,
                                        pack_batch_addrdict,
                                        pack_batch_l7dict, pack_batch_v4,
                                        unpack_batch_addrdict_jnp,
                                        unpack_batch_l7dict_jnp)
from cilium_tpu.model.rules import HTTPRule
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr
from tests.test_lpm_fuzz import _fuzz_addresses, _random_prefix_set


class TestHash:
    def test_np_jnp_agree(self):
        rng = np.random.default_rng(0)
        words = rng.integers(0, 2**32, size=(64, 10), dtype=np.uint32)
        h_np = hash_words_np(words)
        h_jnp = np.asarray(hash_words_jnp(jnp.asarray(words)))
        np.testing.assert_array_equal(h_np, h_jnp)

    def test_avalanche(self):
        words = np.zeros((2, 10), dtype=np.uint32)
        words[1, 9] = 1
        h = hash_words_np(words)
        assert h[0] != h[1]


class TestLPMKernel:
    def test_matches_host_walk(self):
        entries = {"10.0.0.0/8": 100, "10.1.0.0/16": 200, "10.1.2.3/32": 300,
                   "2001:db8::/32": 400, "::/0": 500, "0.0.0.0/0": 600}
        ids = sorted(set(entries.values()) | {C.IDENTITY_WORLD})
        index = {v: i for i, v in enumerate(ids)}
        tables = build_lpm(entries, index, default_index=index[C.IDENTITY_WORLD])
        probes = ["10.1.2.3", "10.1.9.9", "10.2.3.4", "9.9.9.9",
                  "2001:db8::1", "fe80::1"]
        addr_words = np.zeros((len(probes), 4), dtype=np.uint32)
        is_v6 = np.zeros(len(probes), dtype=bool)
        want = []
        for i, a in enumerate(probes):
            a16, v6 = parse_addr(a)
            addr_words[i] = np.frombuffer(a16, dtype=">u4")
            is_v6[i] = v6
            want.append(lpm_lookup_host(tables, a16, v6))
        got = np.asarray(lpm_lookup_batch(
            jnp.asarray(tables.v4_placed), jnp.asarray(tables.v6_placed),
            jnp.asarray(addr_words), jnp.asarray(is_v6),
            default_index=index[C.IDENTITY_WORLD]))
        np.testing.assert_array_equal(got, np.asarray(want))


#: the tries the placed form is held over (PR 44): ipcache → the node
#: counts it has to build, dead sentinel included. 2 and 11 are what
#: ``ct1m-50k`` and ``l7-http`` place; the last is of the kind
#: ``lpm100k-zipf`` and ``node-mixed`` place, a hundredth the size.
PLACED_TRIES = {
    # a /0 sits in every cell of the root; the v6 trie holds nothing
    "two-nodes": ({"0.0.0.0/0": 600}, 2, 2),
    "eleven-nodes": ({"10.1.2.3/32": 100, "10.1.9.9/32": 200,
                      "10.2.0.1/32": 300, "172.16.0.5/32": 400,
                      "10.0.0.0/8": 500, "10.1.0.0/16": 600,
                      "10.16.0.0/12": 700, "2001:db8::/32": 800,
                      "2001:db8:0:8::/61": 900}, 11, 9),
    "thousands": (_random_prefix_set(np.random.default_rng(44), 3000, 500),
                  None, None),
}


def _build(entries):
    """→ (tables, identity → index) with world as the default."""
    ids = sorted(set(entries.values()) | {C.IDENTITY_WORLD})
    index = {v: i for i, v in enumerate(ids)}
    return build_lpm(entries, index,
                     default_index=index[C.IDENTITY_WORLD]), index


def _walk_placed(tables, addrs, **kw):
    """The device walk over the placed form for ``addrs`` [(a16, is_v6)]
    → (identity index, provenance) as numpy."""
    got = lpm_lookup_prov_batch(
        jnp.asarray(tables.v4_placed), jnp.asarray(tables.v6_placed),
        jnp.asarray(np.stack([np.frombuffer(a16, dtype=">u4")
                              .astype(np.uint32) for a16, _ in addrs])),
        jnp.asarray([v6 for _, v6 in addrs]),
        default_index=tables.default_index, **kw)
    return np.asarray(got[0]), np.asarray(got[1])


def _walk_3d(nodes, data, default_index):
    """The walk by a 3-D index ``nodes[node, b]`` over the host form, a
    whole batch at a time: what the placed form's take has to equal bit
    for bit. ``data`` [N, levels] uint8."""
    dead = nodes.shape[0] - 1
    node = np.zeros(len(data), np.int64)
    best = np.full(len(data), default_index, np.int32)
    meta = np.full(len(data), -1, np.int32)
    for level in range(data.shape[1]):
        child, value, m = np.moveaxis(nodes[node, data[:, level]], 1, 0)
        best = np.where(value >= 0, value, best)
        meta = np.where(value >= 0, m, meta)
        node = np.where(child >= 0, child, dead)
    return best, meta


class TestLPMPlacedForm:
    """The walk takes its entries from the placed form ``[n * 256, 3]``
    (compile/lpm.py) and gives what the host mirror and a 3-D index over
    the host form ``[n, 256, 3]`` give: identity index and provenance, both
    families in one batch, hits, misses and paths that leave the trie."""

    @pytest.fixture(scope="class", params=sorted(PLACED_TRIES))
    def world(self, request):
        entries, n4, n6 = PLACED_TRIES[request.param]
        tables, _ = _build(entries)
        if n4 is None:
            assert tables.v4_nodes.shape[0] > 2000 \
                and tables.v6_nodes.shape[0] > 2000
        else:
            assert (tables.v4_nodes.shape[0],
                    tables.v6_nodes.shape[0]) == (n4, n6)
        # half inside the set's prefixes with random host bits (hits, and
        # paths that leave the trie below a shorter match: the chain idles
        # in the dead sentinel), half random addresses of both families
        addrs = _fuzz_addresses(np.random.default_rng(len(entries)),
                                entries, 384)
        return tables, addrs

    def test_placed_is_a_view_of_the_host_form(self, world):
        tables, _ = world
        for placed, nodes in ((tables.v4_placed, tables.v4_nodes),
                              (tables.v6_placed, tables.v6_nodes)):
            assert placed.shape == (nodes.shape[0] * 256, 3)
            assert placed.dtype == np.int32
            assert np.shares_memory(placed, nodes)
            x = nodes.shape[0] - 1
            np.testing.assert_array_equal(placed[x * 256 + 7], nodes[x, 7])
            np.testing.assert_array_equal(placed[255], nodes[0, 255])

    @pytest.mark.parametrize("v4_only", [False, True])
    def test_walk_equals_host_mirror_and_3d_index(self, world, v4_only):
        tables, addrs = world
        world_index = tables.default_index
        raw = np.stack([np.frombuffer(a16, np.uint8) for a16, _ in addrs])
        is_v6 = np.asarray([v6 for _, v6 in addrs])
        assert is_v6.any() and not is_v6.all()      # a mixed-family batch
        r4, m4 = _walk_3d(tables.v4_nodes, raw[:, 12:], world_index)
        r6, m6 = _walk_3d(tables.v6_nodes, raw, world_index)
        mirror = [lpm_lookup_host_prov(tables, a16, v6) for a16, v6 in addrs]
        sel_r, sel_m = np.where(is_v6, r6, r4), np.where(is_v6, m6, m4)
        np.testing.assert_array_equal(sel_r, [w[0] for w in mirror])
        np.testing.assert_array_equal(sel_m, [w[1] for w in mirror])
        assert (sel_m >= 0).any() and (sel_m < 0).any()   # hits and misses
        got_index, got_prov = _walk_placed(tables, addrs, v4_only=v4_only)
        # ``v4_only`` walks every row's last four bytes through the v4 trie
        want_r, want_m = (r4, m4) if v4_only else (sel_r, sel_m)
        np.testing.assert_array_equal(got_index, want_r)
        np.testing.assert_array_equal(got_prov, want_m)

    def test_named_probes(self):
        """By hand, over the eleven-node tries: the prefix that has to win
        (None: a miss, ``default_index`` and provenance -1)."""
        entries = PLACED_TRIES["eleven-nodes"][0]
        probes = [("10.1.2.3", "10.1.2.3/32"), ("10.1.2.4", "10.1.0.0/16"),
                  ("10.1.3.4", "10.1.0.0/16"), ("10.2.0.2", "10.0.0.0/8"),
                  ("10.16.0.1", "10.16.0.0/12"),
                  ("10.31.255.255", "10.16.0.0/12"),
                  ("10.32.0.0", "10.0.0.0/8"), ("172.16.0.6", None),
                  ("9.9.9.9", None), ("2001:db8:0:f::1", "2001:db8:0:8::/61"),
                  ("2001:db8:0:10::1", "2001:db8::/32"),
                  ("2001:db9::1", None), ("fe80::1", None)]
        tables, index = _build(entries)
        want = []
        for _, prefix in probes:
            if prefix is None:
                want.append((tables.default_index, -1))
                continue
            plen = int(prefix.rsplit("/", 1)[1])
            want.append((index[entries[prefix]],
                         pack_pfx(tables.pfx_slot_of[prefix],
                                  plen if ":" in prefix else plen + 96)))
        got_index, got_prov = _walk_placed(
            tables, [parse_addr(a) for a, _ in probes])
        assert list(zip(got_index.tolist(), got_prov.tolist())) == want


class TestL7Kernel:
    def test_matches_host(self):
        interner = L7SetInterner()
        s1 = interner.intern(frozenset({HTTPRule("GET", "/api"),
                                        HTTPRule("", "/pub")}))
        s2 = interner.intern(frozenset({HTTPRule("POST", "/x")}))
        t = build_l7_tensors(interner)
        cases = [(s1, 0, b"/api/v1"), (s1, 1, b"/api"), (s1, 1, b"/pub/z"),
                 (s2, 1, b"/x"), (s2, 0, b"/x"), (0, 0, b"/whatever"),
                 (s1, 0, b""), (s2, 1, b"")]
        n = len(cases)
        set_id = jnp.asarray([c[0] for c in cases], dtype=jnp.int32)
        method = jnp.asarray([c[1] for c in cases], dtype=jnp.int32)
        path = np.zeros((n, C.L7_PATH_MAXLEN), dtype=np.uint8)
        for i, (_, _, p) in enumerate(cases):
            path[i, :len(p)] = np.frombuffer(p, dtype=np.uint8)
        tensors = {"l7_methods": jnp.asarray(t.methods),
                   "l7_valid": jnp.asarray(t.valid),
                   "l7_path_len": jnp.asarray(t.path_len),
                   "l7_path": jnp.asarray(t.path)}
        got = np.asarray(l7_match_batch(tensors, set_id, method,
                                        jnp.asarray(path)))
        want = [l7_match_host(t, sid, m, p) if sid > 0 else True
                for sid, m, p in cases]
        np.testing.assert_array_equal(got, np.asarray(want))


def _mk_batch(n, tuples):
    """tuples: list of (src, dst, sport, dport, proto, dir)."""
    b = empty_batch(n)
    for i, (src, dst, sp, dp, proto, d) in enumerate(tuples):
        s16, sv6 = parse_addr(src)
        d16, dv6 = parse_addr(dst)
        b["src"][i] = np.frombuffer(s16, dtype=">u4")
        b["dst"][i] = np.frombuffer(d16, dtype=">u4")
        b["sport"][i], b["dport"][i] = sp, dp
        b["proto"][i] = proto
        b["direction"][i] = d
        b["is_v6"][i] = sv6
        b["valid"][i] = True
    return b


class TestCTKernel:
    def _jnp_ct(self, cap=1024):
        return {k: jnp.asarray(v) for k, v in
                make_ct_arrays(CTConfig(capacity=cap)).items()}

    def test_probe_miss_on_empty(self):
        ct = self._jnp_ct()
        b = _mk_batch(4, [("10.0.0.1", "10.0.0.2", 1, 2, 6, 0)] * 4)
        keys = ctk.ct_key_words_jnp({k: jnp.asarray(v) for k, v in b.items()})
        slot = ctk.ct_probe(ct, keys, jnp.uint32(100))
        assert (np.asarray(slot) == -1).all()

    def test_insert_then_probe_hits(self):
        ct = self._jnp_ct()
        b = {k: jnp.asarray(v) for k, v in _mk_batch(
            4, [("10.0.0.1", "10.0.0.2", 1000 + i, 80, 6, 0)
                for i in range(4)]).items()}
        keys = ctk.ct_key_words_jnp(b)
        want = jnp.asarray([True] * 4)
        nk, ncr, zm, slot, fail, _ev = ctk.ct_insert_new(
            ct, keys, want, jnp.uint32(100))
        assert (np.asarray(slot) >= 0).all() and not np.asarray(fail).any()
        ct2 = ctk.ct_apply(ct, b, slot, jnp.zeros(4, bool), want,
                           jnp.uint32(100), new_keys=nk,
                           new_created=ncr, zero_mask=zm)
        slot2 = ctk.ct_probe(ct2, keys, jnp.uint32(101))
        np.testing.assert_array_equal(np.asarray(slot2), np.asarray(slot))

    def test_duplicate_keys_one_slot(self):
        ct = self._jnp_ct()
        b = {k: jnp.asarray(v) for k, v in _mk_batch(
            4, [("10.0.0.1", "10.0.0.2", 7, 80, 6, 0)] * 4).items()}
        keys = ctk.ct_key_words_jnp(b)
        nk, ncr, zm, slot, fail, _ev = ctk.ct_insert_new(
            ct, keys, jnp.asarray([True] * 4), jnp.uint32(100))
        s = np.asarray(slot)
        assert (s == s[0]).all() and (s >= 0).all()
        assert int(np.asarray(zm).sum()) == 1  # exactly one slot claimed

    def test_insert_fail_when_window_full(self):
        # capacity 8 with probe depth 8: 9 distinct keys that all hash into a
        # full table → at least one fail
        ct = self._jnp_ct(cap=8)
        tuples = [("10.0.0.1", "10.0.0.2", 100 + i, 80, 6, 0) for i in range(12)]
        b = {k: jnp.asarray(v) for k, v in _mk_batch(12, tuples).items()}
        keys = ctk.ct_key_words_jnp(b)
        nk, ncr, zm, slot, fail, _ev = ctk.ct_insert_new(
            ct, keys, jnp.asarray([True] * 12), jnp.uint32(100))
        assert int(np.asarray(fail).sum()) >= 4  # 8 slots, 12 flows
        assert int(np.asarray(zm).sum()) == 8

    def test_probe_settles_a_window_of_flows_that_share_their_ports(self):
        """The probe reads the ports word of every slot of the window first
        and the rest of the key at its candidates only, in window order:
        eight flows with one port pair in one window (capacity 8) are each
        found at their own slot, a ninth with those ports and another
        address at none, a flow with other ports at none, and an expired
        candidate is no candidate. Both orientations in one probe read as
        the two probes do."""
        ct = self._jnp_ct(cap=8)
        same_ports = [(f"10.0.{i}.1", "10.9.9.9", 4242, 443, 6, 0)
                      for i in range(8)]
        b = {k: jnp.asarray(v) for k, v in
             _mk_batch(8, same_ports).items()}
        keys = ctk.ct_key_words_jnp(b)
        assert len(set(np.asarray(keys)[:, ctk.FIRST_WORD])) == 1
        want = jnp.ones(8, bool)
        nk, ncr, zm, slot, fail, _ev = ctk.ct_insert_new(
            ct, keys, want, jnp.uint32(100))
        assert sorted(np.asarray(slot)) == list(range(8))
        ct2 = ctk.ct_apply(ct, b, slot, jnp.zeros(8, bool), want,
                           jnp.uint32(100), new_keys=nk, new_created=ncr,
                           zero_mask=zm)
        np.testing.assert_array_equal(
            np.asarray(ctk.ct_probe(ct2, keys, jnp.uint32(101))),
            np.asarray(slot))
        others = {k: jnp.asarray(v) for k, v in _mk_batch(2, [
            ("10.0.8.1", "10.9.9.9", 4242, 443, 6, 0),
            ("10.0.0.1", "10.9.9.9", 4243, 443, 6, 0)]).items()}
        other_keys = ctk.ct_key_words_jnp(others)
        assert (np.asarray(ctk.ct_probe(ct2, other_keys, jnp.uint32(101)))
                == -1).all()
        # slot 3's entry expires: its flow misses, its neighbours still hit
        gone = int(np.asarray(slot)[3])
        ct3 = dict(ct2, expiry=ct2["expiry"].at[gone].set(50))
        got = np.asarray(ctk.ct_probe(ct3, keys, jnp.uint32(101)))
        assert got[3] == -1
        np.testing.assert_array_equal(np.delete(got, 3),
                                      np.delete(np.asarray(slot), 3))
        rev = ctk.reverse_key_words_jnp(keys)
        f, r = ctk.ct_probe_pair(ct2, keys, rev, jnp.uint32(101))
        np.testing.assert_array_equal(np.asarray(f), np.asarray(slot))
        assert (np.asarray(r) == -1).all()
        f, r = ctk.ct_probe_pair(ct2, rev, keys, jnp.uint32(101))
        assert (np.asarray(f) == -1).all()
        np.testing.assert_array_equal(np.asarray(r), np.asarray(slot))

    def test_sweep_reclaims(self):
        ct = self._jnp_ct()
        raw = _mk_batch(1, [("10.0.0.1", "10.0.0.2", 7, 80, 6, 0)])
        raw["tcp_flags"][0] = C.TCP_SYN  # SYN-only → 60s lifetime
        b = {k: jnp.asarray(v) for k, v in raw.items()}
        keys = ctk.ct_key_words_jnp(b)
        one = jnp.asarray([True])
        nk, ncr, zm, slot, fail, _ev = ctk.ct_insert_new(
            ct, keys, one, jnp.uint32(100))
        ct2 = ctk.ct_apply(ct, b, slot, jnp.zeros(1, bool), one,
                           jnp.uint32(100), new_keys=nk,
                           new_created=ncr, zero_mask=zm)
        ct3, n = ctk.ct_sweep(ct2, jnp.uint32(100 + C.CT_LIFETIME_SYN + 1))
        assert int(n) == 1
        assert ctk.ct_probe(ct3, keys, jnp.uint32(200))[0] == -1

    def test_key_words_np_jnp_agree(self):
        b = _mk_batch(3, [("10.0.0.1", "10.0.0.2", 5, 6, 17, 1),
                          ("2001:db8::1", "2001:db8::2", 9, 10, 6, 0),
                          ("1.1.1.1", "2.2.2.2", 0, 0, 1, 0)])
        for rev in (False, True):
            np_words = ct_key_words(b, reverse=rev)
            jnp_words = np.asarray(ctk.ct_key_words_jnp(
                {k: jnp.asarray(v) for k, v in b.items()}, reverse=rev))
            np.testing.assert_array_equal(np_words, jnp_words)

    def test_pair_matches_two_sided_normalization(self):
        """``ct_key_words_pair`` derives the reverse key as a word
        permutation of the forward one: both equal the two-sided
        normalization, on random mixed-family batches."""
        import random

        from cilium_tpu.kernels.records import batch_from_records
        from oracle import PacketRecord
        rng = random.Random(3)
        for _trial in range(3):
            recs = []
            for _ in range(64):
                v6 = rng.random() < 0.25
                net = ("2001:db8::%x", "2001:db9::%x") if v6 else \
                    ("10.0.7.%d", "10.1.9.%d")
                recs.append(PacketRecord(
                    parse_addr(net[0] % rng.randrange(1, 255))[0],
                    parse_addr(net[1] % rng.randrange(1, 255))[0],
                    rng.randrange(1024, 65535), rng.randrange(1, 65535),
                    rng.choice([C.PROTO_TCP, C.PROTO_UDP]), C.TCP_SYN, v6,
                    1, rng.choice([C.DIR_EGRESS, C.DIR_INGRESS])))
            b = {k: jnp.asarray(v)
                 for k, v in batch_from_records(recs, {1: 0}).items()}
            fwd, rev = ctk.ct_key_words_pair(b)
            np.testing.assert_array_equal(
                np.asarray(fwd),
                np.asarray(ctk.ct_key_words_jnp(b, reverse=False)))
            np.testing.assert_array_equal(
                np.asarray(rev),
                np.asarray(ctk.ct_key_words_jnp(b, reverse=True)))


def test_no_module_of_the_program_imports_pallas():
    """The classify interior is the ``jnp`` one (PR 52): a kernel comes
    back with the cell whose bottleneck it is, and through this test."""
    import ast
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = [os.path.join(root, "chip_smoke.py"),
             os.path.join(root, "__graft_entry__.py")]
    for where, _dirs, names in os.walk(os.path.join(root, "cilium_tpu")):
        files += [os.path.join(where, n) for n in names if n.endswith(".py")]
    assert len(files) > 50
    found = []
    for path in files:
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [f"{node.module}.{a.name}" for a in node.names]
            found += [(os.path.relpath(path, root), n) for n in names
                      if "pallas" in n]
    assert found == []


class TestMakeClassifyFnMemo:
    def test_same_static_config_shares_one_callable(self):
        """Repeated placements must not re-trace: one jitted callable a
        static configuration, another for each argument that differs."""
        from cilium_tpu.kernels.classify import make_classify_fn
        a = make_classify_fn(8, False, donate_ct=False)
        assert a is make_classify_fn(8, False, donate_ct=False)
        assert a is not make_classify_fn(8, True, donate_ct=False)
        assert a is not make_classify_fn(8, False, donate_ct=False,
                                         packed=True)
        assert a is not make_classify_fn(8, False, donate_ct=False,
                                         slab=True)
        assert a is not make_classify_fn(8, False, donate_ct=False,
                                         lb_probe_depth=4)


class TestPackOutVariants:
    """out= pack kernels must produce byte-identical wires to the
    allocating versions across every format, including partially-filled
    (valid-masked) buckets — the staging ring's correctness contract."""

    @staticmethod
    def _batch(n, n_valid=None, v6=False, l7=False, seed=0):
        rng = np.random.default_rng(seed)
        b = empty_batch(n)
        b["src"][:, 2] = 0xFFFF
        b["dst"][:, 2] = 0xFFFF
        b["src"][:, 3] = rng.integers(0, 2**32, n, dtype=np.uint32)
        b["dst"][:, 3] = rng.integers(0, 2**32, n, dtype=np.uint32)
        b["sport"][:] = rng.integers(0, 65536, n)
        b["dport"][:] = rng.integers(0, 65536, n)
        b["proto"][:] = rng.choice([6, 17, 1], n)
        b["tcp_flags"][:] = rng.integers(0, 256, n)
        b["ep_slot"][:] = rng.integers(0, 8, n)
        b["direction"][:] = rng.integers(0, 2, n)
        b["valid"][: n if n_valid is None else n_valid] = True
        if v6:
            b["is_v6"][::3] = True
            b["src"][::3, 0] = 0x20010DB8
        if l7:
            paths = [b"/api/v1", b"/submit", b"/", b"/static/app.js"]
            for i in range(0, n, 2):
                p = paths[i % len(paths)]
                b["http_method"][i] = i % 3
                b["http_path"][i, : len(p)] = np.frombuffer(p, np.uint8)
        return b

    def test_v4_out_bit_identical(self):
        b = self._batch(32, n_valid=20)
        want = pack_batch_v4(b)
        out = np.full((32, PACK4_WORDS), 0xDEADBEEF, dtype=np.uint32)
        got = pack_batch_v4(b, out=out)
        np.testing.assert_array_equal(got, want)
        assert got.base is out or got is out       # wrote in place

    def test_v4_out_oversized_prefix(self):
        """A max_bucket-rows ring buffer serves smaller buckets through
        its [:n] prefix."""
        b = self._batch(16)
        out = np.zeros((64, PACK4_WORDS), dtype=np.uint32)
        got = pack_batch_v4(b, out=out)
        assert got.shape == (16, PACK4_WORDS)
        np.testing.assert_array_equal(got, pack_batch_v4(b))
        np.testing.assert_array_equal(out[:16], got)

    def test_full_out_bit_identical(self):
        for v6 in (False, True):
            b = self._batch(24, n_valid=17, v6=v6, seed=3)
            want = pack_batch(b)
            got = pack_batch(b, out=np.empty((24, want.shape[1]),
                                             np.uint32))
            np.testing.assert_array_equal(got, want)

    def test_full_out_l7_path_block(self):
        b = self._batch(16, n_valid=9, l7=True, seed=4)
        want = pack_batch(b)                       # auto-detects l7
        assert want.shape[1] > PACK_WORDS
        got = pack_batch(b, out=np.empty_like(want))
        np.testing.assert_array_equal(got, want)

    def test_l7dict_out_both_variants(self):
        # compact 5-word variant
        b = self._batch(16, n_valid=11, l7=True, seed=5)
        w0, d0 = pack_batch_l7dict(b)
        assert w0.shape[1] == PACK4_L7_WORDS
        w1, d1 = pack_batch_l7dict(
            b, out=np.empty((16, PACK4_L7_WORDS), np.uint32))
        np.testing.assert_array_equal(w0, w1)
        np.testing.assert_array_equal(d0, d1)
        # full 12-word variant (force_full, as the wide sticky path does)
        w0, d0 = pack_batch_l7dict(b, force_full=True)
        assert w0.shape[1] == PACK_L7DICT_WORDS
        w1, d1 = pack_batch_l7dict(
            b, force_full=True,
            out=np.empty((16, PACK_L7DICT_WORDS), np.uint32))
        np.testing.assert_array_equal(w0, w1)
        np.testing.assert_array_equal(d0, d1)

    def test_out_mismatch_rejected(self):
        b = self._batch(8)
        with pytest.raises(ValueError):
            pack_batch_v4(b, out=np.zeros((4, PACK4_WORDS), np.uint32))
        with pytest.raises(ValueError):
            pack_batch_v4(b, out=np.zeros((8, PACK_WORDS), np.uint32))
        with pytest.raises(ValueError):
            pack_batch_v4(b, out=np.zeros((8, PACK4_WORDS), np.int32))


def _path_dict_by_fields(paths, path_words, min_rows=1):
    """The plain reference: ``_pack_path_dict`` as it stood until PR 38,
    a sort of the rows as 64 one-byte fields and the words by shifts."""
    uniq, idx = np.unique(paths, axis=0, return_inverse=True)
    if uniq.shape[0] > 65536:
        raise ValueError("path dictionary overflow (>64k unique paths)")
    if path_words is None:
        path_words = _path_words_of(uniq)
    path_words = min(path_words, C.L7_PATH_MAXLEN // 4)
    if uniq[:, 4 * path_words:].any():
        raise ValueError(f"path_words={path_words} truncates a path")
    u_pad = _pad_dict_rows(uniq.shape[0], min_rows)
    p = np.zeros((u_pad, 4 * path_words), dtype=np.uint32)
    p[:uniq.shape[0]] = uniq[:, :4 * path_words]
    p = p.reshape(u_pad, path_words, 4)
    words = ((p[:, :, 0] << 24) | (p[:, :, 1] << 16)
             | (p[:, :, 2] << 8) | p[:, :, 3])
    return words, idx.reshape(-1)


def _text_paths(n, pool, seed):
    rng = np.random.default_rng(seed)
    table = np.zeros((pool, C.L7_PATH_MAXLEN), dtype=np.uint8)
    for i in range(pool):
        p = f"/api/v{i % 200}/item/{i * 7919}".encode()
        table[i, :len(p)] = np.frombuffer(p, np.uint8)
    w = 1.0 / np.arange(1, pool + 1)
    return table[rng.choice(pool, n, p=w / w.sum())]


def _all_equal():
    return np.tile(_text_paths(1, 1, 0), (1024, 1))


def _all_distinct():
    rng = np.random.default_rng(1)
    rows = rng.integers(0, 256, (1024, C.L7_PATH_MAXLEN), dtype=np.uint8)
    rows[:, :2] = np.arange(1024, dtype=np.uint16).view(
        np.uint8).reshape(1024, 2)
    return rows[rng.permutation(1024)]


def _differ_in(byte):
    rng = np.random.default_rng(2 + byte)
    rows = np.full((256, C.L7_PATH_MAXLEN), ord("a"), dtype=np.uint8)
    rows[:, byte] = rng.integers(0, 256, 256)
    return rows


def _high_bytes():
    """0x7f / 0x80 / 0xff at the first, a middle and the last byte: a
    signed compare puts 0x80 and 0xff before 0x7f."""
    rows = np.full((27, C.L7_PATH_MAXLEN), 0x80, dtype=np.uint8)
    for k, at in enumerate((0, 31, 63)):
        rows[:, at] = np.array([0x7F, 0x80, 0xFF], np.uint8)[
            (np.arange(27) // 3 ** k) % 3]
    return rows[np.random.default_rng(3).permutation(27)]


def _zeros_among():
    rows = _text_paths(64, 40, 4)
    rows[::5] = 0
    return rows


def _column_view():
    """``http_path`` as a column of a wider row buffer (a harvest buffer's
    layout): rows 208 bytes apart, so no 64-byte item view of it exists."""
    buf = np.random.default_rng(5).integers(
        0, 256, (96, 208), dtype=np.uint8)
    buf[:, 100:164] = _text_paths(96, 30, 5)
    view = buf[:, 100:164]
    assert not view.flags["C_CONTIGUOUS"]
    return view


PATH_SETS = {
    "all-equal": _all_equal,
    "1024-distinct": _all_distinct,
    "differ-in-byte-63": lambda: _differ_in(63),
    "differ-in-byte-0": lambda: _differ_in(0),
    "bytes-from-0x80": _high_bytes,
    "zeros-among": _zeros_among,
    "column-view": _column_view,
    "zipf-1024": lambda: _text_paths(1024, 1500, 6),
    "one-row": lambda: _text_paths(1, 3, 7),
    "no-row": lambda: np.zeros((0, C.L7_PATH_MAXLEN), dtype=np.uint8),
}


class TestPathDict:
    """``_pack_path_dict`` dedups a batch's rows as 64-byte items; the
    field-by-field ``np.unique(axis=0)`` it replaced is the reference, and
    dictionary words and index have to be the reference's bit for bit."""

    @pytest.mark.parametrize("path_words", [None, 16])
    @pytest.mark.parametrize("min_rows", [1, 2, 4096])
    @pytest.mark.parametrize("name", PATH_SETS)
    def test_words_and_index_are_the_field_sorts(self, name, min_rows,
                                                 path_words):
        paths = PATH_SETS[name]()
        keep = paths.copy()
        want_words, want_idx = _path_dict_by_fields(
            paths, path_words, min_rows)
        words, idx = _pack_path_dict(paths, path_words, min_rows)
        assert words.dtype == np.uint32 and words.flags["C_CONTIGUOUS"]
        np.testing.assert_array_equal(words, want_words)
        np.testing.assert_array_equal(idx, want_idx)
        np.testing.assert_array_equal(paths, keep)     # input untouched
        distinct = len({r.tobytes() for r in paths})
        assert words.shape[0] == _pad_dict_rows(distinct, min_rows)
        if len(paths):
            # dense from 0: _pack_wire reads distinct as max index + 1
            assert int(idx.max()) + 1 == distinct
            assert np.unique(idx).size == distinct

    @pytest.mark.parametrize("name,path_words", [
        ("differ-in-byte-63", 8), ("differ-in-byte-63", 15),
        ("zipf-1024", 1), ("column-view", 2), ("bytes-from-0x80", 4)])
    def test_too_few_path_words_raise(self, name, path_words):
        paths = PATH_SETS[name]()
        for fn in (_path_dict_by_fields, _pack_path_dict):
            with pytest.raises(ValueError, match="truncates a path"):
                fn(paths, path_words)

    def test_short_paths_take_the_given_words(self):
        paths = np.zeros((8, C.L7_PATH_MAXLEN), dtype=np.uint8)
        paths[:, :3] = np.frombuffer(b"/ab", np.uint8)
        paths[3:, 3] = ord("c")
        for pw in (None, 1, 2, 16, 64):
            words, idx = _pack_path_dict(paths, pw)
            want_words, want_idx = _path_dict_by_fields(paths, pw)
            np.testing.assert_array_equal(words, want_words)
            np.testing.assert_array_equal(idx, want_idx)
            assert words.shape == (2, 1 if pw is None else min(pw, 16))
        assert words[0, 0] == 0x2F616200 and words[1, 0] == 0x2F616263

    @staticmethod
    def _batch_of(paths, seed=0):
        n = paths.shape[0]
        b = TestPackOutVariants._batch(n, seed=seed)
        b["http_path"] = paths
        b["http_method"][:] = np.random.default_rng(seed).integers(0, 9, n)
        return b

    @pytest.mark.parametrize("wire", ["l7dict-compact", "l7dict-full",
                                      "addrdict"])
    @pytest.mark.parametrize("name", PATH_SETS)
    def test_every_wire_hands_the_device_the_rows_bytes(self, name, wire):
        paths = PATH_SETS[name]()
        b = self._batch_of(paths)
        if wire == "addrdict":
            parts = pack_batch_addrdict(b, l7=True)
            assert len(parts) == 3
            unpack = unpack_batch_addrdict_jnp
        else:
            full = wire == "l7dict-full"
            parts = pack_batch_l7dict(b, force_full=full)
            assert parts[0].shape[1] == (PACK_L7DICT_WORDS if full
                                         else PACK4_L7_WORDS)
            unpack = unpack_batch_l7dict_jnp
        assert parts[0].shape[0] == len(paths)
        if not len(paths):
            # no bucket is empty, and the device's unpack takes no 0-row
            # wire: the host's side alone, one all-zero dictionary row
            assert parts[-1].shape[0] == 1 and not parts[-1].any()
            return
        got = unpack(*map(jnp.asarray, parts))
        np.testing.assert_array_equal(np.asarray(got["http_path"]), paths)
        np.testing.assert_array_equal(np.asarray(got["http_method"]),
                                      b["http_method"])
