"""A set of flows says which way each flow goes, and ``frames.py`` builds
what it says.

(a) A flow set without the new columns gives, byte for byte, what the parent
    (8632fb4) gave: digests frozen there over the two tiny worlds.
(b) Egress frames, v4 and v6, TCP and UDP, go through the shim's mock rings
    and are harvested as the columns ``columns_of`` states.
(c) The egress world's plain reference against a loop over the documents it
    hands the program, address by address.
(d) Its table against the program's oracle on the same tiny world, row for
    row.
(e) A TCP payload reaches the shim's request-line tokenizer.
"""

import hashlib
import importlib
import ipaddress
import json
import os

import numpy as np
import pytest

from benchmarks import frames, reference as ref
from benchmarks.laws import flowmix
from benchmarks.tests.conftest import DATA, tiny_config as config
from benchmarks.worlds import cidrsvc

EP_V4 = 0xC0A8000A
EP_V6 = "fd00::10"
EP_V6_WORDS = (0xFD000000, 0, 0, 0x10)
EP_ID = 7

#: sha256 over ``frames_of``'s table and lengths, and over ``columns_of``'s
#: columns, computed at 8632fb4 by ``digests`` below
FROZEN = {
    "tiny-pods": (
        "9e9c1c6dc4fab621b3ba4b0c6833161305e7ea69064414d1947a4bd572d035f9",
        "4c044738010c3141b863cbe563b8fe6054435051e2cadd2e747fed07ad3b08af"),
    "tiny-dual": (
        "0216977734c67074afaabc1533214f5966c0dda1f17b5adabebc74c28b5a3ba1",
        "921813a493f10222f2e267637ed2ff378e6870e7574665163623dbf331d6f0a4"),
}


def build(name):
    cfg = config(name)
    return importlib.import_module(
        "benchmarks.worlds." + cfg["world"]["builder"]).build(cfg["world"])


def traffic_law():
    with open(os.path.join(DATA, "traffic", "saturate.json")) as f:
        return json.load(f)["law_params"]


def sha(arrays):
    h = hashlib.sha256()
    for name, a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{name}:{a.dtype.str}:{a.shape};".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def digests(name, seed=20260928, n_frames=20000):
    w = build(name)
    mix = flowmix.generate(traffic_law(), w, np.random.default_rng(seed),
                           int(config(name)["live_flows"]), n_frames)
    tab, lens = frames.frames_of(mix["flows"], w.ep_v4, w.ep_v6_words)
    cols = frames.columns_of(mix["flows"], w.ep_v4, w.ep_v6_words, 3)
    return tab, (sha([("table", tab), ("length", lens)]),
                 sha(sorted(cols.items())))


# -- (a) ---------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(FROZEN))
def test_flow_sets_without_the_new_columns_give_the_parents_bytes(name):
    tab, got = digests(name)
    assert tab.shape[1] == frames.FRAME_STRIDE == 80
    assert got == FROZEN[name]


def test_an_all_ingress_egress_column_changes_nothing():
    w = build("tiny-dual")
    flows = w.allowed_flows(np.random.default_rng(1), 500, 20000, 40000)
    said = dict(flows, egress=np.zeros((500,), bool))
    for a, b in zip(frames.frames_of(flows, w.ep_v4, w.ep_v6_words),
                    frames.frames_of(said, w.ep_v4, w.ep_v6_words)):
        assert (a == b).all()
    a = frames.columns_of(flows, w.ep_v4, w.ep_v6_words, 3)
    b = frames.columns_of(said, w.ep_v4, w.ep_v6_words, 3)
    assert all((a[k] == b[k]).all() for k in a)


# -- (b), (e): through the shim ---------------------------------------------
def mixed_flows(n=64):
    """Both families, both protocols, both directions, every combination."""
    i = np.arange(n)
    v6 = i % 2 == 1
    peer = frames.v4_words((0x0B000000 + 257 * i).astype(np.uint32))
    peer[v6, 0] = 0x20010DB8
    peer[v6, 1] = (i[v6] * 65537).astype(np.uint32)
    peer[v6, 2] = 0
    return {"src": peer, "sport": (30000 + i).astype(np.int32),
            "dport": (80 + 7 * i).astype(np.int32),
            "proto": np.where(i // 2 % 2 == 1, frames.PROTO_UDP,
                              frames.PROTO_TCP).astype(np.int32),
            "is_v6": v6, "egress": i // 4 % 2 == 1}


def through_the_shim(flows):
    """Each flow's frame into the mock rx ring; → the harvested columns."""
    from cilium_tpu.shim.bindings import FlowShim
    table, lens = frames.frames_of(flows, EP_V4, EP_V6_WORDS)
    n = lens.shape[0]
    shim = FlowShim(batch_size=n)
    try:
        shim.register_endpoint("192.168.0.10", EP_ID)
        shim.register_endpoint(EP_V6, EP_ID)
        shim.mock_rings_init(ring_size=256, frame_size=2048, n_frames=256)
        for row, length in zip(table, lens):
            assert shim.mock_rx_inject(row[:length].tobytes()) == 0
        assert shim.afxdp_poll(n, now_us=1) == n
        got = shim.poll_batch(now_us=1, force=True)
        assert shim.last_poll_rows == n and shim.stats()["parse_errors"] == 0
        return {k: np.array(v[:n]) for k, v in got.items()}
    finally:
        shim.close()


def assert_columns(got, want):
    for k in ("src", "dst", "sport", "dport", "proto", "tcp_flags", "is_v6",
              "direction", "http_method", "http_path"):
        assert (got[k] == want[k]).all(), k
    assert (got["_ep_raw"] == EP_ID).all() and got["valid"].all()


def test_egress_frames_are_harvested_as_the_columns_state():
    flows = mixed_flows()
    assert len({(a, b, c) for a, b, c in zip(
        flows["is_v6"], flows["proto"], flows["egress"])}) == 8
    want = frames.columns_of(flows, EP_V4, EP_V6_WORDS, 0)
    out = flows["egress"]
    assert (want["direction"][out] == frames.DIR_EGRESS).all()
    assert (want["direction"][~out] == frames.DIR_INGRESS).all()
    # the endpoint is the source of a frame that leaves it, the peer's
    # address its destination; the ports are the frame's own either way
    assert (want["dst"][out] == flows["src"][out]).all()
    assert (want["src"][~out] == flows["src"][~out]).all()
    assert (want["src"][out & ~flows["is_v6"], 3] == EP_V4).all()
    assert (want["src"][out & flows["is_v6"]] == EP_V6_WORDS).all()
    assert_columns(through_the_shim(flows), want)


def test_the_programs_direction_values():
    from cilium_tpu.utils import constants as C
    assert (frames.DIR_EGRESS, frames.DIR_INGRESS) \
        == (C.DIR_EGRESS, C.DIR_INGRESS)


def test_a_tcp_payload_reaches_the_request_line_tokenizer():
    from cilium_tpu.utils import constants as C
    flows = mixed_flows(16)
    flows = frames.take(flows, flows["proto"] == frames.PROTO_TCP)
    n = flows["sport"].shape[0]
    line = b"GET /a/b HTTP/1.1\r\nHost: x\r\n\r\n"
    carries = np.arange(n) % 4 != 3                  # every fourth has none
    flows["payload"] = np.tile(np.frombuffer(line, np.uint8), (n, 1))
    flows["payload_len"] = np.where(carries, len(line), 0).astype(np.int32)
    flows["http_method"] = np.where(
        carries, C.HTTP_METHOD_IDS["GET"], C.HTTP_METHOD_ANY).astype(np.int32)
    path = np.zeros((n, C.L7_PATH_MAXLEN), np.uint8)
    path[carries, :4] = np.frombuffer(b"/a/b", np.uint8)
    flows["http_path"] = path
    table, lens = frames.frames_of(flows, EP_V4, EP_V6_WORDS)
    assert table.shape[1] == 112 and table.shape[1] >= lens.max()
    header = np.where(flows["is_v6"], 74, 54)
    assert (lens == header + flows["payload_len"]).all()
    v4 = np.nonzero(~flows["is_v6"])[0]              # IP total length
    assert ((table[v4, 16].astype(int) << 8 | table[v4, 17])
            == lens[v4] - 14).all()
    v6 = np.nonzero(flows["is_v6"])[0]               # v6 payload length
    assert ((table[v6, 18].astype(int) << 8 | table[v6, 19])
            == lens[v6] - 54).all()
    want = frames.columns_of(flows, EP_V4, EP_V6_WORDS, 0)
    assert (want["http_method"][carries] == 0).all()
    assert_columns(through_the_shim(flows), want)
    with pytest.raises(ValueError):
        frames.frames_of(dict(flows, proto=np.full(
            (n,), frames.PROTO_UDP, np.int32)), EP_V4, EP_V6_WORDS)


# -- (c): the reference against a loop over the documents --------------------
TINY = config("tiny-cidrsvc")["world"]
#: the source's shape (``build_config3``): identities by /8 block, one
#: cover for half the space, one service, every one named
BLOCKS = dict(TINY, identity_plen=8, cover_cidrs=["0.0.0.0/1"],
              services={"count": 1, "named": 1, "backends_each": 2,
                        "frontends_each": 1})
WORLDS = [pytest.param(TINY, id="own-identities"),
          pytest.param(BLOCKS, id="block-identities")]


def by_the_documents(world, addrs):
    """Per address, with ``ipaddress`` and nothing of the world's numpy:
    the longest prefix of the ipcache holding it (the listed ones, every
    prefix a document names, a named service's backends), and whether some
    document admits that prefix's identity."""
    net = ipaddress.ip_network
    ipcache = {net(p): net(q) for p, q in world.listed()}
    selectors = []
    for doc in world.policy_docs():
        for rule in doc["egress"]:
            for c in rule.get("toCIDR", ()):
                selectors.append((net(c), ()))
            for cs in rule.get("toCIDRSet", ()):
                selectors.append((net(cs["cidr"]),
                                  tuple(net(x) for x in cs["except"])))
            for ts in rule.get("toServices", ()):
                svc = next(s for s in world.services()
                           if s.name == ts["k8sService"]["serviceName"])
                selectors += [(net(b.addr + "/32"), ())
                              for b in svc.lb_backends]
    for cidr, excepts in selectors:
        for p in (cidr, *excepts):
            ipcache[p] = p
    out = []
    for a in addrs.tolist():
        ip = ipaddress.ip_address(a)
        best = None
        for p in ipcache:
            if ip in p and (best is None or p.prefixlen > best.prefixlen):
                best = p
        labelled = ipcache.get(best)
        out.append((best, best is not None and any(
            labelled.subnet_of(c) and not any(labelled.subnet_of(x)
                                              for x in xs)
            for c, xs in selectors)))
    return out


@pytest.mark.parametrize("params", WORLDS)
def test_reference_agrees_with_a_loop_over_the_documents(params):
    w = cidrsvc.build(params)
    e, rng = w.ipcache, np.random.default_rng(5)
    inside = rng.integers(0, e.addr.size, 8000)
    addrs = np.concatenate([
        e.addr[inside] | (rng.integers(0, 1 << 32, 8000)
                          & ~cidrsvc._mask(e.plen[inside])),
        rng.integers(0x01000000, 0xDF000000, 1000),
        w._pools[0][:400], w._pools[1][:400], w._pools[2][:200]])
    assert addrs.size == 10000
    flows = w._flows(addrs, np.full(addrs.shape, 30000),
                     np.full(addrs.shape, 53))
    cell = w.cells(flows)
    table, _cover = w.table()
    want = by_the_documents(w, addrs)
    for a, c, (best, admitted) in zip(addrs.tolist(), cell.tolist(), want):
        if best is None:
            assert c == -1, a
            continue
        assert (int(e.addr[c]), int(e.plen[c])) == (
            int(best.network_address), best.prefixlen), a
        assert bool(table[c]) == admitted, (a, best)
    # the cases a walk that stops early gets wrong are among them
    parent = e.longest(e.addr, shorter_than=e.plen)
    has = cell >= 0
    up = parent[cell[has]]
    differs = (up >= 0) & (table[cell[has]] != table[np.maximum(up, 0)])
    nested = int((differs & table[cell[has]]).sum())
    excepted = int((differs & ~table[cell[has]]).sum())
    assert nested >= 100 and excepted >= 100, (nested, excepted)


@pytest.mark.parametrize("params", WORLDS)
def test_every_world_holds_nested_prefixes_of_opposite_verdicts(params):
    w = cidrsvc.build(params)
    e = w.ipcache
    table, cover = w.table()
    parent = e.longest(e.addr, shorter_than=e.plen)
    up = table[np.maximum(parent, 0)]
    admitted = table[:e.addr.size]
    assert ((parent >= 0) & admitted & ~up).sum() >= 1
    assert ((parent >= 0) & ~admitted & up).sum() >= 1
    # and the traffic uses them, and rules that alone cover a cell: the
    # tiny cell's window is some 60,000 frames
    mix = flowmix.generate(traffic_law(), w, np.random.default_rng(2),
                           2000, 60000)
    cell = w.cells(mix["flows"])
    per_flow = np.bincount(mix["sched_flow"], minlength=cell.size)
    per_cell = np.bincount(cell[cell >= 0], weights=per_flow[cell >= 0],
                           minlength=table.size)
    contrast = (parent >= 0) & (admitted != up)
    on = per_cell[:e.addr.size]
    assert on[contrast & admitted].sum() >= 1000
    assert on[contrast & ~admitted].sum() >= 100
    assert ((cover == 1) & (per_cell >= 16)).sum() >= 8
    services = per_cell[e.addr.size:]
    assert (services[:w.n_named] >= 16).all()
    kinds = mix["kind"]
    want = ref.expected_allow(w, mix["flows"])
    assert want[kinds <= flowmix.KIND_NEW_ALLOWED].all()
    assert not want[kinds >= flowmix.KIND_NEW_DENIED].any()
    assert mix["flows"]["egress"].all()


def test_parameters_that_leave_no_contrast_are_refused():
    with pytest.raises(ValueError):
        cidrsvc.build(dict(TINY, nested_share=0.0))
    with pytest.raises(ValueError):
        cidrsvc.build(dict(TINY, cidr_sets=0, cover_cidrs=[]))


# -- (d): against the program's oracle, row for row ---------------------------
@pytest.mark.parametrize("params", WORLDS)
def test_table_against_the_programs_oracle(params):
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import FakeDatapath
    from cilium_tpu.runtime.engine import Engine
    w = cidrsvc.build(params)
    eng = Engine(DaemonConfig(ct_capacity=1 << 16, auto_regen=False),
                 datapath=FakeDatapath(DaemonConfig(ct_capacity=1 << 16)))
    try:
        w.load(eng)
        eng.regenerate()
        rng = np.random.default_rng(9)
        flows = frames.concat([
            w.allowed_flows(rng, 1500, 20000, 40000),
            w.denied_flows(rng, 1000, 20000, 40000),
            w.unknown_flows(rng, 300, 20000, 40000)])
        want = ref.expected_allow(w, flows)
        assert want[:1500].all() and not want[1500:].any()
        ep_slot = eng.active.snapshot.ep_slot_of[w.ep_id]
        out = eng.classify(frames.columns_of(flows, w.ep_v4, w.ep_v6_words,
                                             ep_slot))
        allow = np.asarray(out["allow"]).astype(bool)
        assert (allow == want).all(), np.nonzero(allow != want)[0][:10]
        reason = np.asarray(out["reason"])
        assert (reason[want] == ref.REASON_OK).all()
        assert (reason[~want] == ref.REASON_POLICY).all()
        # every prefix of the reference's ipcache is in the program's
        cells = w.cells(flows)
        assert np.unique(cells[cells >= 0]).size >= 100
    finally:
        eng.stop()
