"""The egress node behind a whole routing table of both families
(``worlds/dfz.py``: 950,000 v4 and 200,000 v6 prefixes at full size, four
flows in ten over v6) and its plain reference, at test size (``tiny-dfz``:
4,000 + 2,000 prefixes, every length of the full mix; PR 53).

(a) The world is the deployment its parameters state: counts, every length
    of both mixes, the blocks, the documents, the pools, and the nested
    prefixes with opposite verdicts, both ways, in each family.
(b) The reference against a loop over the deployment's own text, address
    by address (``ipaddress`` and ints; nothing of the world's numpy), and
    against the program's ``ipcache`` lookup of the same text: verdict and
    winning prefix.
(c) Rows through ``Engine.submit`` on the jitted datapath: allow, reason,
    status, and the winning prefix the program names (``lpm_prefix``: slot
    → text, and length) against the reference's text, over allowed, denied
    and unknown flows of both families; the walk's scopes by family are in
    the program, and its gauges, span and counter are rendered.
(d) A family never holds the other's addresses: a v4-mapped address in a
    v6 frame, and the first 32 bits of a v6 prefix as a v4 address.
(e) Frames of both families through ``frames_of``, the shim's mock rings
    and a harvest: egress, the peer as destination, 54 and 74 bytes.
(f) ``tiny-dfz.saturate`` through ``run_cell`` on the jitted datapath:
    correct, the control caught, four rows in ten on the wide wire.
(g) Parameters that leave a family without its cases are refused, and the
    reference imports nothing of the program outside ``load``.
"""

import copy
import importlib.util
import ipaddress
import json
import os
import time

import numpy as np
import pytest

from benchmarks import frames, harness, reference as ref
from benchmarks.tests.conftest import DATA, REPO
from benchmarks.tests.test_frames_direction import (
    EP_V4, EP_V6_WORDS, assert_columns, through_the_shim)
from benchmarks.worlds import cidrsvc, dfz
from cilium_tpu.utils import constants as C

CONFIG = os.path.join("tests", "data", "configs", "tiny-dfz.json")
with open(os.path.join(REPO, CONFIG)) as _f:
    TINY_CONFIG = json.load(_f)
TINY = TINY_CONFIG["world"]
with open(os.path.join(REPO, "benchmarks", "configs",
                       "dfz-dualstack.json")) as _f:
    FULL = json.load(_f)["world"]
CELL = "tiny-dfz.saturate"
REASON_OK, REASON_POLICY = 0, int(C.DropReason.POLICY)
BUCKET = 256
OUT_KEYS = ("allow", "reason", "status", "svc", "nat_dst", "lpm_prefix")


@pytest.fixture(scope="module")
def world():
    return dfz.build(TINY)


def peer_text(flows, i) -> str:
    """Flow ``i``'s destination as text, from its four words."""
    w = [int(x) for x in flows["src"][i]]
    if not flows["is_v6"][i]:
        return str(ipaddress.IPv4Address(w[3]))
    return str(ipaddress.IPv6Address(
        (w[0] << 96) | (w[1] << 64) | (w[2] << 32) | w[3]))


def the_flows(world, rng, n_allowed, n_denied, n_unknown):
    flows = frames.concat([
        world.allowed_flows(rng, n_allowed, 20000, 40000),
        world.denied_flows(rng, n_denied, 20000, 40000),
        world.unknown_flows(rng, n_unknown, 20000, 40000)])
    order = rng.permutation(flows["sport"].shape[0])
    return frames.take(flows, order)


# -- (a) the world -----------------------------------------------------------------
def test_the_test_size_is_the_full_files_world_cut_in_scale_alone():
    cut = set(TINY_CONFIG["reduced"])
    for key, value in FULL.items():
        if key in cut:
            assert TINY[key] < value, key
        else:
            assert TINY[key] == value, key
    assert set(TINY) == set(FULL)


@pytest.mark.parametrize("family", ["v4", "v6"])
def test_the_table_is_what_the_parameters_state(world, family):
    fam = getattr(world, family)
    n, mix = TINY[f"n_{family}"], TINY[f"{family}_length_mix"]
    hi = np.concatenate([fam.listed_hi, np.array(
        [p[0] for cidr, xs in fam.docs[1:] for p in (cidr, *xs)],
        np.uint64)])
    plen = np.concatenate([fam.listed_plen, np.array(
        [p[1] for cidr, xs in fam.docs[1:] for p in (cidr, *xs)],
        np.int64)])
    keep = dfz._first_distinct(hi, plen)
    hi, plen = hi[keep], plen[keep]
    assert hi.size == n                       # the listed prefixes, once each
    # every length of the mix is there, the commonest by far the mix's
    lengths, counts = np.unique(plen, return_counts=True)
    assert lengths.tolist() == sorted(int(k) for k in mix)
    top = max(mix, key=mix.get)
    assert lengths[counts.argmax()] == int(top)
    assert abs(counts.max() / n - mix[top] / sum(mix.values())) < 0.08
    # non-octet lengths in numbers: the trie's partial-byte expansion
    assert (plen % 8 != 0).sum() > n // 5
    # every prefix lies in a block, or holds blocks where it is the shorter
    block = hi & dfz._TOP[fam.block_len]
    long_enough = plen >= fam.block_len
    assert np.isin(block[long_enough], fam.blocks).all()
    assert fam.blocks.size == TINY[f"{family}_blocks"] \
        == np.unique(fam.blocks).size
    for h, p in zip(hi[~long_enough].tolist(), plen[~long_enough].tolist()):
        assert ((fam.blocks & dfz._TOP[p]) == np.uint64(h)).any()
    # a quarter were drawn inside a shorter one; more nest by chance
    parent = fam.ipcache.longest(fam.ipcache.hi,
                                 shorter_than=fam.ipcache.plen)
    inside_listed = (parent >= 0) & (fam.ipcache.plen[np.maximum(parent, 0)]
                                     > 8)
    assert inside_listed.mean() > 0.25
    if family == "v4":
        assert not np.isin((hi >> np.uint64(56)).astype(np.int64),
                           cidrsvc.KEPT_OCTETS).any()
        assert (hi & dfz.LOW32 == 0).all()
    else:
        assert np.isin(hi >> np.uint64(48), dfz.V6_TOPS).all()


@pytest.mark.parametrize("family", ["v4", "v6"])
def test_the_documents_and_the_nested_cases_both_ways(world, family):
    fam = getattr(world, family)
    assert len(fam.docs) == 1 + TINY["cidr_sets"] + TINY["admit_listed"]
    cover, sets, singles = fam.docs[0], fam.docs[1:1 + TINY["cidr_sets"]], \
        fam.docs[1 + TINY["cidr_sets"]:]
    assert dfz.text_of(fam.is_v6, *cover[0]) \
        == {"v4": "0.0.0.0/1", "v6": "2000::/5"}[family]
    assert all(1 <= len(xs) <= TINY["excepts_each"] for _c, xs in sets)
    assert sum(len(xs) for _c, xs in sets) > TINY["cidr_sets"]
    assert all(xs == () for _c, xs in singles)
    e, allowed = fam.ipcache, fam.cover > 0
    parent = e.longest(e.hi, shorter_than=e.plen)
    has = parent >= 0
    up = allowed[np.maximum(parent, 0)]
    # a refused prefix inside an admitted one, and the other way round
    assert (has & ~allowed & up).sum() >= TINY["cidr_sets"]
    assert (has & allowed & ~up).sum() >= TINY["admit_listed"] // 2
    # about half of the family's entries are admitted, by the cover
    assert 0.3 < allowed.mean() < 0.7
    # each class's heaviest ranks lie in such prefixes
    contrast = has & (allowed != up)
    for kind in (0, 1):
        hi, lo = world.pool(fam.is_v6, kind)
        cell = e.longest(hi[:8], lo[:8])
        assert contrast[cell].all() and (allowed[cell] == (kind == 0)).all()


def test_the_pools_and_the_flows_families(world):
    want = [int(round(s * TINY["pool"])) for s in TINY["pool_split"]]
    for kind in range(3):
        n4 = world.pool(False, kind)[0].size
        n6 = world.pool(True, kind)[0].size
        assert n4 + n6 == want[kind]
        assert n6 == int(round(TINY["v6_share"] * want[kind]))
        for fam, (hi, lo) in ((world.v4, world.pool(False, kind)),
                              (world.v6, world.pool(True, kind))):
            cell = fam.ipcache.longest(hi, lo)
            if kind == 2:
                assert (cell < 0).all()
            else:
                assert ((fam.cover[cell] > 0) == (kind == 0)).all()
            pairs = np.stack([hi, lo], axis=1)
            assert np.unique(pairs, axis=0).shape[0] == hi.size
    rng = np.random.default_rng(5)
    for draw, admitted in ((world.allowed_flows, True),
                           (world.denied_flows, False),
                           (world.unknown_flows, False)):
        flows = draw(rng, 4000, 1000, 2000)
        assert 0.36 < flows["is_v6"].mean() < 0.44
        assert flows["egress"].all() and (flows["proto"] == 6).all()
        assert (ref.expected_allow(world, flows) == admitted).all()
    flows = world.allowed_flows(rng, 4000, 1000, 2000)
    front = world.cells(flows) >= world.n4 + world.n6
    assert 0.04 < front.mean() < 0.08 and not flows["is_v6"][front].any()
    assert set(ref.refusal_reasons(world, flows).tolist()) == {130}
    assert not hasattr(world, "reasons")


# -- (b) the reference against a loop over the text -------------------------------
class Text:
    """The deployment as text: what ``load`` hands the program, read back
    with ``ipaddress``; nothing of the world's numpy."""

    def __init__(self, world):
        net = ipaddress.ip_network
        #: prefix → the prefix its identity is labelled for
        self.entries = {net(p): net(q) for p, q in world.listed()}
        assert len(self.entries) == len(world.listed())
        self.docs = []
        for doc in world.policy_docs():
            assert doc["endpointSelector"] == {"matchLabels": {"app": "web"}}
            (rule,) = doc["egress"]
            if "toCIDR" in rule:
                (cidr,) = rule["toCIDR"]
                self.docs.append((net(cidr), ()))
            elif "toCIDRSet" in rule:
                (s,) = rule["toCIDRSet"]
                self.docs.append((net(s["cidr"]),
                                  tuple(net(x) for x in s["except"])))
            else:
                assert rule["toServices"][0]["k8sService"] == {
                    "serviceName": "svc0", "namespace": "prod"}
        for cidr, excepts in self.docs:     # a named prefix: its own identity
            for p in (cidr, *excepts):
                assert p not in self.entries
                self.entries[p] = p
        self.backends = [ipaddress.ip_address(b.addr)
                         for s in world.services() for b in s.lb_backends]
        (svc,) = world.services()
        self.frontends = {(ipaddress.ip_address(f.addr), f.port)
                          for f in svc.frontends}
        self.by_version = {v: [(p, int(p.network_address), p.prefixlen,
                                p.max_prefixlen) for p in self.entries
                               if p.version == v] for v in (4, 6)}

    def longest(self, addr):
        """The longest prefix **of the address's family** that holds it."""
        a, best = int(addr), None
        for p, net, plen, bits in self.by_version[addr.version]:
            if a >> (bits - plen) == net >> (bits - plen) \
                    and (best is None or plen > best.prefixlen):
                best = p
        return best

    def admits(self, prefix) -> bool:
        q = self.entries[prefix]
        return any(q.version == cidr.version and q.subnet_of(cidr)
                   and not any(q.subnet_of(x) for x in excepts)
                   for cidr, excepts in self.docs)

    def judge(self, addr, dport):
        """→ (admitted, the winning prefix's text or None)."""
        if (addr, dport) in self.frontends:
            return True, None           # the one service is named
        p = self.longest(addr)
        return (p is not None and self.admits(p)), \
            (str(p) if p is not None else None)


def test_reference_agrees_with_a_loop_over_the_text(world):
    flows = the_flows(world, np.random.default_rng(11), 160, 100, 60)
    n = flows["sport"].shape[0]
    text, want = Text(world), ref.expected_allow(world, flows)
    named = world.prefix_text(flows)
    for i in range(n):
        addr = ipaddress.ip_address(peer_text(flows, i))
        assert addr.version == (6 if flows["is_v6"][i] else 4)
        admitted, prefix = text.judge(addr, int(flows["dport"][i]))
        assert admitted == want[i], (i, addr)
        assert prefix == named[i], (i, addr, prefix, named[i])
    assert 120 < want.sum() < 200 and named.count(None) > 50
    assert sum(p is not None and ":" in p for p in named) > 60


# -- (c) the program ---------------------------------------------------------------
class Served:
    def __init__(self, world):
        from cilium_tpu.runtime.config import DaemonConfig
        from cilium_tpu.runtime.engine import Engine
        self.world = world
        cfg = DaemonConfig(ct_capacity=1 << 16, batch_size=1024,
                           auto_regen=False, flowlog_mode="none",
                           trace_sample_rate=1.0)
        self.eng = Engine(cfg)
        self.revision0 = self.eng.ctx.ipcache.revision
        world.load(self.eng)
        self.eng.regenerate()
        self.snap = self.eng.active.snapshot
        self.ep_slot = self.snap.ep_slot_of[world.ep_id]

    def submit(self, flows):
        n, got = flows["sport"].shape[0], {k: [] for k in OUT_KEYS}
        for i in range(0, n, BUCKET):
            m = min(BUCKET, n - i)
            b = frames.columns_of(
                frames.take(flows, np.arange(i, i + BUCKET) % n),
                self.world.ep_v4, self.world.ep_v6_words, self.ep_slot)
            b["valid"][m:] = False
            out = self.eng.submit(b).result(timeout=300)
            for k in OUT_KEYS:
                got[k].append(np.asarray(out[k])[:m])
        assert self.eng.drain(timeout=60)
        return {k: np.concatenate(v) for k, v in got.items()}


@pytest.fixture(scope="module")
def served(world):
    s = Served(world)
    yield s
    s.eng.stop()


def assert_rows(served, flows, got, established=False):
    """The answered rows against the reference: the verdict, and the
    winning prefix by slot → text and by length."""
    w = served.world
    want, cell = ref.expected_allow(w, flows), w.cells(flows)
    np.testing.assert_array_equal(got["allow"].astype(bool), want)
    np.testing.assert_array_equal(
        got["reason"].astype(np.int64),
        np.where(want, REASON_OK, REASON_POLICY))
    status = np.where(want, C.CTStatus.ESTABLISHED, C.CTStatus.NEW) \
        if established else np.zeros(want.shape, np.int64)
    np.testing.assert_array_equal(got["status"].astype(np.int64), status)
    front = cell >= w.n4 + w.n6
    np.testing.assert_array_equal(got["svc"].astype(bool), front)
    backends = {f"{b.addr}/32" for s in w.services() for b in s.lb_backends}
    named = w.prefix_text(flows)
    for i, packed in enumerate(got["lpm_prefix"].tolist()):
        said = served.snap.lpm.describe(packed)
        if front[i]:
            assert said["prefix"] in backends and said["plen"] == 128
        elif cell[i] < 0:
            assert packed == -1 and named[i] is None
        else:
            assert said["prefix"] == named[i], (i, said, named[i])
            length = int(named[i].rsplit("/", 1)[1])
            assert said["plen"] == (length if flows["is_v6"][i]
                                    else 96 + length)
    return want, cell


@pytest.mark.parametrize("seed", [5300000101, 5300000102])
def test_rows_agree_with_the_plain_reference(served, seed):
    flows = the_flows(served.world, np.random.default_rng(seed), 600, 250,
                      150)
    want, cell = assert_rows(served, flows, served.submit(flows))
    assert_rows(served, flows, served.submit(flows), established=True)
    v6 = flows["is_v6"]
    for family in (v6, ~v6):          # every class, in each family
        assert want[family].sum() > 150 and (cell[family] < 0).sum() > 30
        assert (~want[family] & (cell[family] >= 0)).sum() > 60
    # many lengths of both mixes won some walk (the next case: every one)
    lengths = {(":" in t, int(t.rsplit("/", 1)[1]))
               for t in served.world.prefix_text(flows) if t}
    assert len({p for v, p in lengths if v}) >= 8
    assert len({p for v, p in lengths if not v}) >= 6


def test_every_length_of_both_mixes_wins_its_walk(served):
    """One address inside a prefix of every length of each family's mix,
    where that prefix is the longest that holds it."""
    w, rng = served.world, np.random.default_rng(7)
    is_v6, hi, lo, wanted = [], [], [], []
    for fam in (w.v4, w.v6):
        e = fam.ipcache
        for length in np.unique(fam.listed_plen).tolist():
            of = np.nonzero(e.plen == length)[0]
            a_hi, a_lo = fam.address_in(of, rng)
            own = np.nonzero(e.longest(a_hi, a_lo) == of)[0][:3]
            assert own.size, (fam.is_v6, length)
            is_v6 += [fam.is_v6] * own.size
            hi += a_hi[own].tolist()
            lo += a_lo[own].tolist()
            wanted += [(fam.is_v6, length)] * own.size
    assert {k for k in wanted} == {(False, int(k)) for k in
                                   TINY["v4_length_mix"]} \
        | {(True, int(k)) for k in TINY["v6_length_mix"]}
    n = len(hi)
    flows = w.flows_to(np.array(is_v6), np.array(hi, np.uint64),
                       np.array(lo, np.uint64), 30000 + np.arange(n),
                       np.full((n,), 443))
    got = served.submit(flows)
    assert_rows(served, flows, got)
    for (v6, length), packed in zip(wanted, got["lpm_prefix"].tolist()):
        assert packed & 0xFF == (length if v6 else 96 + length)


def test_the_programs_ipcache_names_the_same_prefix_for_the_same_text(
        served):
    from cilium_tpu.model.ipcache import lpm_lookup_pfx
    w = served.world
    flows = the_flows(w, np.random.default_rng(13), 40, 30, 20)
    entries = served.eng.ctx.ipcache.snapshot()
    front = w.cells(flows) >= w.n4 + w.n6
    named = w.prefix_text(flows)
    for i in np.nonzero(~front)[0].tolist():
        _ident, prefix, plen = lpm_lookup_pfx(entries, peer_text(flows, i))
        assert prefix == named[i], (i, prefix, named[i])
        if prefix is not None:
            length = int(prefix.rsplit("/", 1)[1])
            assert plen == (length if flows["is_v6"][i] else 96 + length)


def test_the_walks_scopes_the_span_the_gauges_and_the_counter(served):
    from cilium_tpu.kernels import classify, lpm
    from tests.test_lpm100k_config import lowered_text
    assert (classify.SCOPE_LPM, lpm.SCOPE_V4, lpm.SCOPE_V6) \
        == ("lpm.walk", "lpm.walk.v4", "lpm.walk.v6") \
        == (classify.SCOPE_LPM, classify.SCOPE_LPM_V4, classify.SCOPE_LPM_V6)
    w = served.world
    flows = w.allowed_flows(np.random.default_rng(1), BUCKET, 1, 2)
    text = lowered_text(served.eng, frames.columns_of(
        flows, w.ep_v4, w.ep_v6_words, served.ep_slot))
    # each family's chain under its own name inside the walk's: every
    # gather the walk's scope holds stands under one of the two
    named = [line for line in text.splitlines()
             if "/lpm.walk/" in line and "/gather" in line]
    assert any("/lpm.walk/lpm.walk.v4/gather" in g for g in named)
    assert any("/lpm.walk/lpm.walk.v6/gather" in g for g in named)
    assert all("/lpm.walk/lpm.walk.v" in g for g in named)
    # the build's span, with what it built; one bulk entry, one revision
    lpm_tables = served.snap.lpm
    spans = [s for s in served.eng.tracer.spans(limit=1 << 12)
             if s["name"] == "engine.regen.lpm"]
    assert spans and spans[-1]["attrs"] == {
        "nodes_v4": lpm_tables.v4_nodes.shape[0],
        "nodes_v6": lpm_tables.v6_nodes.shape[0],
        "prefixes": len(lpm_tables.prefixes)}
    assert spans[-1]["parent"] == "engine.regen.compile"
    lines = dict(line.rsplit(" ", 1) for line in
                 served.eng.render_metrics().splitlines()
                 if line.startswith(("ciliumtpu_lpm_", "ciliumtpu_ipcache_")))
    assert int(lines["ciliumtpu_ipcache_bulk_upserts_total"]) == 1
    for family, nodes in (("v4", lpm_tables.v4_nodes),
                          ("v6", lpm_tables.v6_nodes)):
        label = '{family="' + family + '"}'
        assert int(lines["ciliumtpu_lpm_trie_nodes" + label]) \
            == nodes.shape[0] > 200
        assert int(lines["ciliumtpu_lpm_trie_bytes" + label]) == nodes.nbytes
        # the CPU holds the placed form at its own size; a TPU pads it
        assert int(lines["ciliumtpu_lpm_trie_placed_bytes" + label]) \
            == nodes.nbytes
    assert int(lines["ciliumtpu_lpm_prefixes"]) == len(lpm_tables.prefixes) \
        >= TINY["n_v4"] + TINY["n_v6"]


def test_placed_bytes_is_what_the_device_says_it_holds():
    from cilium_tpu.runtime.datapath import placed_bytes

    class OnDevice:
        nbytes = 12

        def on_device_size_in_bytes(self):
            return 16
    assert placed_bytes(OnDevice()) == 16
    assert placed_bytes(np.zeros((4, 3), np.int32)) == 48


# -- (d) a family never holds the other's addresses --------------------------------
def test_a_family_never_holds_the_others_addresses(served):
    w, n = served.world, 64
    a_hi, _lo = w.pool(False, 0)                 # admitted v4 addresses
    v4 = (a_hi[:n] >> np.uint64(32)).astype(np.uint64)
    # ... inside a v6 frame, v4-mapped: ::ffff:a.b.c.d lies under no v6
    # prefix, whatever the v4 table says of a.b.c.d
    mapped = w.flows_to(np.ones((n,), bool), np.zeros((n,), np.uint64),
                        (np.uint64(0xFFFF) << np.uint64(32)) | v4,
                        41000 + np.arange(n), np.full((n,), 443))
    assert (mapped["src"][:, 2] == 0xFFFF).all()
    assert (w.cells(mapped) == -1).all()
    assert not ref.expected_allow(w, mapped).any()
    got = served.submit(mapped)
    assert_rows(served, mapped, got)
    assert (got["lpm_prefix"] == -1).all()
    # the same 32 bits as v4 flows are admitted
    plain = w.flows_to(np.zeros((n,), bool), a_hi[:n],
                       np.zeros((n,), np.uint64), 42000 + np.arange(n),
                       np.full((n,), 443))
    assert ref.expected_allow(w, plain).all()
    assert_rows(served, plain, served.submit(plain))
    # the reverse: the first 32 bits of an admitted v6 address as a v4
    # address is judged by the v4 table alone (0x24.., 0x26..: under the
    # cover or under a listed prefix by chance; 0x20010000: neither)
    b_hi, _lo = w.pool(True, 0)
    as_v4 = np.unique(b_hi[:4 * n] >> np.uint64(32))[:n] << np.uint64(32)
    m = as_v4.size
    flows = w.flows_to(np.zeros((m,), bool), as_v4,
                       np.zeros((m,), np.uint64), 43000 + np.arange(m),
                       np.full((m,), 443))
    cell = w.cells(flows)
    assert (cell < w.n4).all()
    np.testing.assert_array_equal(
        cell, w.v4.ipcache.longest(as_v4))
    assert_rows(served, flows, served.submit(flows))
    # a v4 flow whose words are not v4-mapped has no cell at all
    odd = dict(plain, src=plain["src"].copy())
    odd["src"][:, 2] = 0
    assert (w.cells(odd) == -1).all()


# -- (e) through the shim ---------------------------------------------------------
def test_both_families_leave_the_endpoint_in_one_harvest(world):
    assert (EP_V4, tuple(EP_V6_WORDS)) == (world.ep_v4, world.ep_v6_words)
    flows = the_flows(world, np.random.default_rng(4), 140, 70, 30)
    n = flows["sport"].shape[0]
    assert n <= 256
    table, lens = frames.frames_of(flows, EP_V4, EP_V6_WORDS)
    v6 = flows["is_v6"]
    assert table.shape[1] == frames.FRAME_STRIDE
    assert (lens[v6] == 74).all() and (lens[~v6] == 54).all()
    assert 60 <= v6.sum() <= n - 60
    want = frames.columns_of(flows, EP_V4, EP_V6_WORDS, 0)
    assert (want["direction"] == frames.DIR_EGRESS).all()
    # the peer is the destination, all four words of it over v6
    assert (want["dst"] == flows["src"]).all()
    assert (want["src"][v6] == EP_V6_WORDS).all()
    assert (want["src"][~v6, 3] == EP_V4).all()
    got = through_the_shim(flows)
    assert_columns(got, want)
    assert (got["direction"] == frames.DIR_EGRESS).all()
    assert (got["dst"][v6] == flows["src"][v6]).all()


# -- (f) the tiny cell through run_cell ---------------------------------------------
NEW_READERS = ("kernels.lpm_v6_us_per_batch", "engine.lpm_build_s")


@pytest.fixture(scope="module")
def tiny_manifest():
    """The tests' manifest with the tiny cell in it, as the README's
    "Adding things" has a later PR add one: entries appended, in memory."""
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        m = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = {e["name"]: e for e in json.load(f)["per_layer"]}
    m["configs"].append({
        "name": "tiny-dfz", "source": "test", "reduced": [], "why": "test",
        "file": CONFIG.replace(os.sep, "/")})
    m["workloads"].append({"name": CELL, "config": "tiny-dfz",
                           "traffic": "saturate", "chips": 1, "why": "test"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if "tiny-cidrsvc.saturate" in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    for name in NEW_READERS + ("datapath.wire_needed_share",
                               "datapath.wire_bytes_per_row",
                               "lb.translated_share"):
        m["per_layer"].append(dict(copy.deepcopy(real[name]),
                                   workloads=[CELL]))
    return m


@pytest.fixture(scope="module")
def tiny_run(tiny_manifest):
    from benchmarks.nic import nicgen
    nicgen.build_shim()
    nicgen.build()
    cell = harness.resolve_cell(tiny_manifest, CELL, data_root=DATA)
    return cell, harness.run_cell(cell, 3000000053, 1.5, False,
                                  time.monotonic())


def test_the_tiny_cell_is_correct_and_its_control_is_caught(tiny_run):
    cell, r = tiny_run
    n = {x["name"]: x for x in r["numbers"]}
    assert r["correct"], [x for x in r["numbers"] if not x["ok"]]
    assert r["failed"] == 0 and r["attempted"] > 1000
    for name in ("unverdicted", "prefix_excess", "passed_gap",
                 "reason_policy_gap", "probe_mismatched", "fill_table_gap",
                 "fill_denied"):
        assert n[name]["value"] == 0, name
    assert "reason_policy_l7_gap" not in n and "refused_for" not in r
    assert n["probe_rows"]["value"] >= 64
    assert r["control"]["caught"] is True
    assert r["control"]["passed_gap"] == r["control"]["frames_on_it"] >= 16
    assert r["compiles"]["in_window"] == 0
    assert set(r["metrics"]) == set(cell.e2e) \
        == {"verdicts_per_s", "setup_s"}
    assert set(NEW_READERS) <= set(cell.layers)


def test_four_rows_in_ten_ride_the_wide_wire(tiny_run):
    _cell, r = tiny_run
    also = r["also"]
    # every harvest holds rows of both families, so every row rides the
    # 44-byte wire; four in ten need it, six would do with 16 bytes:
    # (0.6 x 16 + 0.4 x 44) / 44 = 0.62
    assert 44 <= also["datapath.wire_bytes_per_row"]["value"] < 46
    assert 0.56 < also["datapath.wire_needed_share"]["value"] < 0.66
    assert 0.03 < also["lb.translated_share"]["value"] < 0.09
    # no device plane and no spans in an untraced CPU run
    assert not set(NEW_READERS) & set(also)


# -- (g) -----------------------------------------------------------------------------
def changed(**params):
    return dict(copy.deepcopy(TINY), **params)


@pytest.mark.parametrize("params", [
    changed(v6_share=0.0),
    changed(v6_share=1.0),
    changed(v6_length_mix={"48": 0.9, "72": 0.1}),
    changed(v4_length_mix={"24": 1.0}),                 # nothing can nest
    changed(v4_blocks=70000),                           # more than there are
    changed(n_v6=200, v6_length_mix={"29": 1.0}, nested_share=0.0),
    changed(cover_cidrs=["0.0.0.0/1", "2000::/65"]),
    changed(pool=1 << 22),                              # too few prefixes
    changed(services={"count": 1, "named": 2, "backends_each": 2,
                      "frontends_each": 1}),
], ids=["no-v6-flows", "no-v4-flows", "v6-length-over-64",
        "one-length", "blocks-over-the-space", "too-few-distinct",
        "cover-over-64", "pool-over-the-table", "named-over-count"])
def test_parameters_that_cannot_be_the_deployment_are_refused(params):
    with pytest.raises(ValueError):
        dfz.build(params)


def test_the_reference_imports_nothing_of_the_program():
    spec = importlib.util.find_spec("benchmarks.worlds.dfz")
    with open(spec.origin) as f:
        text = f.read()
    imports = [line.strip() for line in text.splitlines()
               if line.strip().startswith(("import ", "from "))]
    program = [i for i in imports if "cilium_tpu" in i]
    # one, inside services(), which load() calls: the service model a user
    # of the engine hands it
    assert program == [
        "from cilium_tpu.model.services import Backend, Frontend, Service"]
    assert text.index(program[0]) > text.index("    def services(self)")
    assert "upsert_many" in text and "allocate_cidr" in text
