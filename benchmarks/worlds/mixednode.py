"""World ``mixednode``: one dual-stack endpoint that every plane of the
datapath serves at once — BASELINE.json's ``north_star`` is one classifier
over "the PolicyRepository's SelectorCache + CIDR/L4 rules" of an endpoint,
and its ``configs[1]``, ``configs[2]`` and ``configs[3]`` are the three rule
kinds, each measured alone by a world of its own. This one puts all three on
the one endpoint: the API-gateway pod of a dual-stack cluster, which takes
east-west traffic from the cluster's pods, takes HTTP requests under
per-port allow-lists, and calls out to the Internet and to a cluster
service.

Parameters (the configuration file's ``world`` group). Three blocks, each
**the ``world`` block of the configuration that holds that plane alone**,
key for key, built by the builder the block names:
    east_west    plane A, ``worlds/groupports.py``: pod identities in label
                 groups, ingress ``fromEndpoints`` + ``toPorts`` rules over
                 TCP and UDP, a /128 beside the /32 of every ``v6_every``-th
                 pod
    egress       plane B, ``worlds/cidrsvc.py``: listed CIDR prefixes,
                 ``toCIDR`` / ``toCIDRSet`` / ``toServices`` documents,
                 service frontends, destinations by Zipf rank from a pool
    http         plane C, ``worlds/httprules.py``: a set of HTTP rules a TCP
                 port, no ``fromEndpoints``, a request line in every frame
and what no source states, for a configuration to list under ``assumed``:
    plane_shares     [A, B, C]: the plane of every flow, live or new, is
                     drawn independently by these shares
    pod_requesters   share of plane C's flows whose peer is a pod of plane A
                     that sends over v4 (the rest come from ``peer_net``)
    pod_anchor_from  the pods' addresses are consecutive and end inside the
                     first listed prefix, no document naming it, at or above
                     this address: half of its addresses hold a pod's /32
                     (at most half the pods), the rest none. ``groupports``
                     puts its pods at 172.16.0.0, which no prefix of a table
                     drawn here holds; a pod's /32 inside a routed prefix is
                     the case the joined ipcache has to get right

All three blocks' documents select the one endpoint; ``load`` hands the
program the pods' identities and addresses, the listed prefixes, the
services and then every document in one ``apply_policy``.

**The plain reference**, with numpy from the rule parameters alone (the
three worlds' own references do their planes' part; nothing of the program
is imported). A flow's cell is its plane's cell, the joined table the three
tables end to end:
    A  ingress, not to a port of C: (group of the pod whose /32 or /128 the
       source is, port, protocol); none where the source is no pod's
    B  egress: the longest prefix of the **whole** ipcache that holds the
       destination, so a pod's /32 wins over a listed prefix around it, and
       no document admits a pod's identity (it carries no ``cidr:`` label);
       a frontend's flows are judged at the backend
    C  ingress TCP to a port with a set, whoever sends: (the set, the first
       rule that admits the request); none where no rule admits
The rule ports of A, the ports of C's sets and the services' ports are
disjoint, or ``build`` raises: then no document of one plane can admit a
cell of another, and ``cover`` over the joined table is the three covers end
to end (1: the rule admits alone, the control may take it out). Cilium
wildcards an L7 rule by an L4-only rule on the same port; that is not what
this deployment is for. ``reasons``: 180 where C's port has a set, 130
elsewhere.

**Every world built here holds the cases in which planes meet** (``build``
raises otherwise); each is in ``meeting`` by name with the answer the
documents give, the admitted ones are the heaviest ranks of
``allowed_flows`` and the refused ones the first of ``denied_flows``:
    a  a pod of A sends a request to a port of C: admitted by the request
       alone, whatever its group; refused with 180
    b  a peer of C whose address lies inside a listed prefix of B (a CIDR
       identity, not world): as any peer of C
    c  a pod whose /32 lies inside a listed prefix, to a port its group is
       admitted to: admitted
    d  the address one past it (no /32: the listed prefix's CIDR identity,
       in no group) to the same port: 130
    e  an egress flow to an admitted prefix on a port an ingress rule of A
       names: admitted by B; the mirror, an ingress frame from that prefix
       to a port no ingress rule names: 130
    f  a v6 pod over a UDP rule, as ``pods10k-dualstack`` has them
    g  an egress flow to a pod's address inside an admitted region: 130 (the
       /32 wins the walk and no ``toCIDR`` selects a pod), while the address
       one past the last pod is admitted
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmarks.frames import (PROTO_TCP, PROTO_UDP, Flows, concat, take,
                               v4_words)
from benchmarks.reference import REASON_POLICY, REASON_POLICY_L7
from benchmarks.worlds import cidrsvc, groupports, httprules

EP_ID = 1
EP_V4 = 0xC0A8000A                      # 192.168.0.10
EP_V6 = "fd00::10"
EP_V6_WORDS = (0xFD000000, 0, 0, 0x10)
HTTP_METHOD_NONE = 255                  # what the tokenizer says of a frame
#                                         without a request line
FAR_PORTS = httprules.FAR_PORTS         # unknown flows' ports
STRAYS = 4096                           # sources under no prefix at all
WORLD_SEED = 0
PLANES = ("east_west", "egress", "http")
#: the planes of the heaviest ranks, in turn: under Zipf(1.0) the first ten
#: ranks carry a quarter of the live frames, and this order gives the planes
#: 0.55 / 0.28 / 0.17 of that quarter
HEAD_TURN = (0, 1, 2, 0, 1, 2, 0, 0, 1, 0)


def _parse(cidr: str) -> Tuple[int, int]:
    addr, plen = cidr.split("/")
    a, b, c, d = (int(x) for x in addr.split("."))
    return (a << 24) | (b << 16) | (c << 8) | d, int(plen)


def _dotted(addr: int) -> str:
    return f"{addr >> 24}.{(addr >> 16) & 255}.{(addr >> 8) & 255}." \
           f"{addr & 255}"


class World:
    ep_id = EP_ID
    ep_v4 = EP_V4
    ep_v6_words = EP_V6_WORDS

    def __init__(self, params: Dict):
        for name, builder in zip(PLANES, ("groupports", "cidrsvc",
                                          "httprules")):
            if params[name]["builder"] != builder:
                raise ValueError(f"world.{name} is built by {builder}")
        self.a = groupports.build(params["east_west"])
        self.b = cidrsvc.build(params["egress"])
        self.c = httprules.build(params["http"])
        self.shares = np.asarray(params["plane_shares"], np.float64)
        self.pod_requesters = float(params["pod_requesters"])
        if self.shares.shape != (3,) or abs(self.shares.sum() - 1.0) > 1e-9 \
                or (self.shares <= 0).any():
            raise ValueError("plane_shares: [A, B, C], each over 0, "
                             "summing to 1")
        if not 0.0 < self.pod_requesters < 1.0:
            raise ValueError("pod_requesters: a share of C's flows, with "
                             "both kinds of peer left")
        a, b, c = self.a, self.b, self.c
        j = np.arange(a.n_rules)
        self._rule_udp = j % 3 == 0                 # groupports' rule j
        self._rule_group = j % a.groups
        self._rule_off = j % a.port_span
        a_ports = set(range(groupports.PORT_BASE,
                            groupports.PORT_BASE + a.port_span))
        c_ports = set(range(c.first_port, c.first_port + c.n_rulesets))
        s_ports = {cidrsvc.FE_PORT_BASE + f for f in
                   range(b.frontends_each)} \
            | {cidrsvc.BE_PORT_BASE + k for k in range(b.backends_each)}
        if a_ports & c_ports or a_ports & s_ports or c_ports & s_ports \
                or max(a_ports | c_ports | s_ports) >= FAR_PORTS[0]:
            raise ValueError("the planes' rule ports overlap, or reach into "
                             "the far ports")
        self.pod_base, self.anchor = self._place_pods(
            _parse(params["pod_anchor_from"] + "/32")[0])
        tables = [w.table() for w in (a, b, c)]
        self._allowed = np.concatenate([t[0] for t in tables])
        self._cover = np.concatenate([t[1] for t in tables])
        sizes = [t[0].shape[0] for t in tables]
        self.offset = dict(zip(PLANES, np.cumsum([0] + sizes[:-1]).tolist()))
        rng = np.random.default_rng(WORLD_SEED)
        self._strays = self._draw_strays(rng)
        self.meeting = self._meeting_cases()

    # -- where the pods live ----------------------------------------------------
    def _place_pods(self, at_or_above: int) -> Tuple[int, Tuple[int, int]]:
        """→ (the first pod's address, the anchor prefix). The pods' block
        ends inside the anchor: the lowest listed prefix that no document
        names at or above ``at_or_above``."""
        listed = sorted(_parse(p) for p, _q in self.b.listed())
        anchor = next((p for p in listed if p[0] >= at_or_above), None)
        if anchor is None:
            raise ValueError("no listed prefix at or above pod_anchor_from")
        size = 1 << (32 - anchor[1])
        inside = min(self.a.n_ids // 2, size // 2)
        base = anchor[0] + inside - self.a.n_ids
        octets = {base >> 24, (base + self.a.n_ids) >> 24}
        if octets & set(cidrsvc.KEPT_OCTETS) or inside < 2 \
                or (self.b.ipcache.longest(
                    np.arange(base, base + self.a.n_ids)) < 0).any():
            raise ValueError("the pods' block reaches a kept net, or "
                             "leaves the egress documents' cover")
        return base, anchor

    def _draw_strays(self, rng) -> np.ndarray:
        """Addresses under no prefix of the ipcache: the unknown sources."""
        cand = rng.integers(0x80000000, 0xDF000000, 8 * STRAYS)
        cand = cand[~np.isin(cand >> 24, cidrsvc.KEPT_OCTETS)
                    & (self.b.ipcache.longest(cand) < 0)
                    & (self._pod_index(cand) < 0)]
        if cand.size < STRAYS // 4:
            raise ValueError("the egress documents leave too few addresses "
                             "under no prefix")
        return cand[:STRAYS]

    # -- the deployment, through the entry points a user calls --------------
    def pod_addresses(self) -> List[Tuple[int, str, str]]:
        """(pod, its /32, its /128 or '') for every pod."""
        a = self.a
        return [(i, f"{_dotted(self.pod_base + i)}/32",
                 f"2001:db8:{i >> 8:x}:{i & 0xFF:x}::1/128"
                 if i % a.v6_every == 0 else "") for i in range(a.n_ids)]

    def policy_docs(self) -> List[Dict]:
        return self.a.policy_docs() + self.b.policy_docs() \
            + self.c.policy_docs()

    def load(self, eng) -> int:
        """The endpoint, the pods, the listed prefixes, the services, and
        every document of the three planes in one call. Returns the revision
        to wait for."""
        from cilium_tpu.model.labels import Labels
        a = self.a
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10", EP_V6),
                         ep_id=EP_ID)
        for i, v4, v6 in self.pod_addresses():
            ident = eng.ctx.allocator.allocate(Labels.parse(
                [f"k8s:group=g{i % a.groups}", f"k8s:pod=p{i}"]))
            eng.ctx.ipcache.upsert(v4, ident.id)
            if v6:
                eng.ctx.ipcache.upsert(v6, ident.id)
        for prefix, labelled_for in self.b.listed():
            ident = eng.ctx.allocator.allocate_cidr(labelled_for)
            eng.ctx.ipcache.upsert(prefix, ident.id)
        for svc in self.b.services():
            eng.upsert_service(svc)
        return eng.apply_policy(self.policy_docs())

    def register(self, shim) -> None:
        shim.register_endpoint("192.168.0.10", EP_ID)
        shim.register_endpoint(EP_V6, EP_ID)

    # -- the plain reference --------------------------------------------------
    def table(self):
        """(allowed [cells] bool, cover [cells] uint8): plane A's cells,
        then B's, then C's (``offset`` says where each begins)."""
        return self._allowed, self._cover

    def _pod_index(self, addr: np.ndarray) -> np.ndarray:
        i = np.asarray(addr, np.int64) - self.pod_base
        return np.where((i >= 0) & (i < self.a.n_ids), i, -1)

    def pod_of(self, flows: Flows) -> np.ndarray:
        """The pod whose /32 or /128 each flow's peer address is, else -1."""
        a, src = self.a, flows["src"]
        v6 = flows["is_v6"].astype(bool)
        p4 = np.where(~v6 & (src[:, 2] == 0xFFFF),
                      self._pod_index(src[:, 3]), -1)
        hi = (src[:, 1] >> 16).astype(np.int64)
        lo = (src[:, 1] & 0xFFFF).astype(np.int64)
        p6 = (hi << 8) | lo
        ok6 = v6 & (src[:, 0] == 0x20010DB8) & (lo < 256) \
            & (src[:, 2] == 0) & (src[:, 3] == 1) & (p6 < a.n_ids) \
            & (p6 % a.v6_every == 0)
        return np.where(ok6, p6, p4)

    def set_of(self, flows: Flows) -> np.ndarray:
        """Plane C's rule set on each ingress TCP flow's port, else -1."""
        c = self.c
        s = flows["dport"].astype(np.int64) - c.first_port
        ok = ~flows["egress"].astype(bool) & (flows["proto"] == PROTO_TCP) \
            & (s >= 0) & (s < c.n_rulesets)
        return np.where(ok, s, -1)

    def plane_of(self, flows: Flows) -> np.ndarray:
        """0, 1, 2: the plane whose documents judge each flow."""
        return np.where(flows["egress"].astype(bool), 1,
                        np.where(self.set_of(flows) >= 0, 2, 0))

    def cells(self, flows: Flows) -> np.ndarray:
        a, out = self.a, flows["egress"].astype(bool)
        pod, s = self.pod_of(flows), self.set_of(flows)
        off = flows["dport"].astype(np.int64) - groupports.PORT_BASE
        udp = flows["proto"] == PROTO_UDP
        in_a = ~out & (s < 0) & (pod >= 0) & (off >= 0) \
            & (off < a.port_span) & (udp | (flows["proto"] == PROTO_TCP))
        cell_a = ((pod % a.groups) * a.port_span + off) * 2 + udp
        # the whole ipcache: a pod's /32 (or /128) is the longest prefix
        # that holds its address, and no egress document admits it
        cell_b = np.where(pod >= 0, -1, self.b.cells(flows))
        cell_c = self.c._admitting(s, flows["http_method"],
                                   flows["http_path"])
        return np.where(
            out, np.where(cell_b >= 0, self.offset["egress"] + cell_b, -1),
            np.where(s >= 0,
                     np.where(cell_c >= 0, self.offset["http"] + cell_c, -1),
                     np.where(in_a, self.offset["east_west"] + cell_a, -1)))

    def reasons(self, flows: Flows) -> np.ndarray:
        return np.where(self.set_of(flows) >= 0, REASON_POLICY_L7,
                        REASON_POLICY)

    def _admitted(self, flows: Flows) -> np.ndarray:
        cell = self.cells(flows)
        return np.where(cell >= 0, self._allowed[np.maximum(cell, 0)], False)

    # -- flows ----------------------------------------------------------------
    @staticmethod
    def whole(flows: Flows) -> Flows:
        """Every column a flow of any plane states, the missing ones as a
        frame without a request and an ingress flow have them."""
        n = flows["sport"].shape[0]
        blank = {"egress": np.zeros((n,), bool),
                 "payload": np.zeros((n, httprules.PAYLOAD_WIDTH), np.uint8),
                 "payload_len": np.zeros((n,), np.int32),
                 "http_method": np.full((n,), HTTP_METHOD_NONE, np.int32),
                 "http_path": np.zeros((n, httprules.PATH_CUT), np.uint8)}
        return {**blank, **flows}

    def pod_flows(self, pod, sport, dport, proto) -> Flows:
        """From pods of plane A: one with a /128 sends over v6 (every
        ``v6_every``-th), the rest over v4."""
        pod = np.asarray(pod, np.int64)
        n = pod.shape[0]
        v6 = pod % self.a.v6_every == 0
        src = v4_words((self.pod_base + pod).astype(np.uint32))
        src[v6, 0] = 0x20010DB8
        src[v6, 1] = (((pod[v6] >> 8) << 16) | (pod[v6] & 0xFF)) \
            .astype(np.uint32)
        src[v6, 2] = 0
        src[v6, 3] = 1
        return self.whole({
            "src": src, "sport": np.asarray(sport).astype(np.int32),
            "dport": np.broadcast_to(dport, (n,)).astype(np.int32),
            "proto": np.broadcast_to(proto, (n,)).astype(np.int32),
            "is_v6": v6})

    def peer_flows(self, addr, sport, dport, proto=PROTO_TCP,
                   egress=False) -> Flows:
        """v4 flows of a peer that is no pod: from it, or to it."""
        n = np.asarray(addr).shape[0]
        return self.whole({
            "src": v4_words(np.asarray(addr).astype(np.uint32)),
            "sport": np.asarray(sport).astype(np.int32),
            "dport": np.broadcast_to(dport, (n,)).astype(np.int32),
            "proto": np.full((n,), proto, np.int32),
            "is_v6": np.zeros((n,), bool),
            "egress": np.full((n,), egress, bool)})

    def _v4_pods(self, rng, n: int) -> np.ndarray:
        """Pods that send over v4, drawn evenly."""
        every = self.a.v6_every
        pod = rng.integers(0, self.a.n_ids, n)
        return np.where(pod % every == 0, (pod + 1) % self.a.n_ids, pod) \
            if every > 1 else pod

    def _from_pods(self, rng, flows: Flows) -> Flows:
        """``pod_requesters`` of plane C's flows come from pods of A."""
        n = flows["sport"].shape[0]
        swap = rng.random(n) < self.pod_requesters
        src = v4_words((self.pod_base + self._v4_pods(rng, n))
                       .astype(np.uint32))
        return dict(flows, src=np.where(swap[:, None], src, flows["src"]))

    def _draw_a(self, rng, n: int, lo: int, hi: int, admitted: bool,
                udp_share: float = 0.1) -> Flows:
        a = self.a
        sport = rng.integers(lo, hi, n)
        if admitted:            # a rule first, then a pod of its group
            udp = rng.random(n) < udp_share
            u, t = np.nonzero(self._rule_udp)[0], \
                np.nonzero(~self._rule_udp)[0]
            j = np.where(udp, u[rng.integers(0, u.size, n)],
                         t[rng.integers(0, t.size, n)])
            pod = self._rule_group[j] + a.groups * rng.integers(
                0, a.n_ids // a.groups, n)
            return self.pod_flows(
                pod, sport, groupports.PORT_BASE + self._rule_off[j],
                np.where(udp, PROTO_UDP, PROTO_TCP))
        m = 2 * n + 64
        pod = rng.integers(0, a.n_ids, m)
        off = rng.integers(0, a.port_span, m)
        udp = rng.random(m) < udp_share
        cell = ((pod % a.groups) * a.port_span + off) * 2 + udp
        keep = np.nonzero(~self._allowed[self.offset["east_west"]
                                         + cell])[0][:n]
        if keep.size < n:
            raise ValueError("the rules leave too few denied ports")
        return self.pod_flows(pod[keep], sport,
                              groupports.PORT_BASE + off[keep],
                              np.where(udp[keep], PROTO_UDP, PROTO_TCP))

    def _by_plane(self, rng, n: int, lo: int, hi: int, draw) -> Flows:
        """``n`` flows, each one's plane drawn by ``plane_shares``;
        ``draw(plane, m)`` draws ``m`` flows of one plane."""
        plane = rng.choice(3, n, p=self.shares)
        out = self.whole({"src": np.zeros((n, 4), np.uint32),
                          "sport": np.zeros((n,), np.int32),
                          "dport": np.zeros((n,), np.int32),
                          "proto": np.zeros((n,), np.int32),
                          "is_v6": np.zeros((n,), bool)})
        for k in range(3):
            at = np.nonzero(plane == k)[0]
            part = self.whole(draw(k, at.size))
            for col in out:
                out[col][at] = part[col]
        return out

    def _wanted(self, rng, n: int, lo: int, hi: int, draw, head: Flows,
                admitted: bool) -> Flows:
        """``head`` (sports drawn here), then flows by plane; those the
        joined reference does not judge as asked are left out: a pool
        address of plane B may be a pod's."""
        head = take(head, slice(0, min(head["sport"].shape[0], n // 4)))
        h = head["sport"].shape[0]
        head = dict(head, sport=rng.integers(lo, hi, h).astype(np.int32))
        rest = self._by_plane(rng, n - h + (n - h) // 32 + 16, lo, hi, draw)
        rest = take(rest, self._admitted(rest) == admitted)
        if rest["sport"].shape[0] < n - h:
            raise ValueError("too many drawn flows judged the other way")
        return concat([head, take(rest, slice(0, n - h))])

    def allowed_flows(self, rng, n: int, sport_lo: int,
                      sport_hi: int) -> Flows:
        """The admitted meeting cases first (a law that ranks flows in the
        order drawn makes them the heaviest), their planes in ``HEAD_TURN``
        order; then flows by plane, each as its plane's own world draws
        them."""
        def draw(k, m):
            if k == 0:
                return self._draw_a(rng, m, sport_lo, sport_hi, True)
            if k == 1:
                return self.b.allowed_flows(rng, m, sport_lo, sport_hi)
            return self._from_pods(rng, self.c.allowed_flows(
                rng, m, sport_lo, sport_hi))
        return self._wanted(rng, n, sport_lo, sport_hi, draw,
                            self._head(True), True)

    def denied_flows(self, rng, n: int, sport_lo: int,
                     sport_hi: int) -> Flows:
        """The refused meeting cases, then each plane's own refusals: A a
        port the pod's group is not admitted to, B a prefix no document
        admits or an ``except`` cut, C a request its set refuses (180) or a
        port past the last set (130)."""
        def draw(k, m):
            if k == 0:
                return self._draw_a(rng, m, sport_lo, sport_hi, False)
            if k == 1:
                return self.b.denied_flows(rng, m, sport_lo, sport_hi)
            return self._from_pods(rng, self.c.denied_flows(
                rng, m, sport_lo, sport_hi))
        return self._wanted(rng, n, sport_lo, sport_hi, draw,
                            self._head(False), False)

    def unknown_flows(self, rng, n: int, sport_lo: int,
                      sport_hi: int) -> Flows:
        """A and C: a source under no prefix of the ipcache, to a far port
        (C's with a request); B: a destination under no prefix."""
        def stray(m):
            return self._strays[rng.integers(0, self._strays.size, m)]

        def draw(k, m):
            if k == 0:
                return self.peer_flows(stray(m),
                                       rng.integers(sport_lo, sport_hi, m),
                                       rng.integers(*FAR_PORTS, m))
            if k == 1:
                return self.b.unknown_flows(rng, m, sport_lo, sport_hi)
            flows = self.c.unknown_flows(rng, m, sport_lo, sport_hi)
            return dict(flows, src=v4_words(stray(m).astype(np.uint32)))
        return self._by_plane(rng, n, sport_lo, sport_hi, draw)

    # -- the cases in which planes meet -------------------------------------------
    def _head(self, admitted: bool) -> Flows:
        """The meeting cases with that answer, a flow each. The admitted
        ones' planes follow ``HEAD_TURN`` for as long as every plane has a
        case left."""
        flows = concat([f for f, answer in self.meeting.values()
                        if (answer is True) == admitted])
        if not admitted:
            return flows
        left = [np.nonzero(self.plane_of(flows) == k)[0].tolist()
                for k in range(3)]
        order = []
        for k in HEAD_TURN:
            if not left[k]:
                break
            order.append(left[k].pop(0))
        return take(flows, np.array(order))

    def _meeting_cases(self) -> Dict[str, Tuple[Flows, object]]:
        """name → (flows, answer): True for admitted, else the drop reason.
        Raises where the parameters leave a case out or the reference gives
        another answer than the case is made for. The admitted cases hold
        five flows of plane A, three of B and two of C: ``HEAD_TURN``'s."""
        a, b, c = self.a, self.b, self.c
        e = b.ipcache
        allowed_b = self._allowed[self.offset["egress"]:][:e.addr.size]
        # the last three pods that send over v4 (inside the anchor by the
        # pods' placement) and the first address past the pods (inside too)
        inside_pods = [p for p in range(a.n_ids - 1, a.n_ids - 6, -1)
                       if p % a.v6_every or a.v6_every == 1][:3]
        past = self.pod_base + a.n_ids
        held_past = int(e.longest(np.array([past]))[0])

        def rule_for(pod, udp):
            hit = np.nonzero((self._rule_group == pod % a.groups)
                             & (self._rule_udp == udp))[0]
            if not hit.size:
                raise ValueError(f"no {'UDP' if udp else 'TCP'} rule for "
                                 f"group {pod % a.groups}")
            return groupports.PORT_BASE + int(self._rule_off[hit[0]])

        def unruled_for(pod):
            mine = set(self._rule_off[self._rule_group == pod % a.groups]
                       .tolist())
            return groupports.PORT_BASE + next(
                o for o in range(a.port_span) if o not in mine)

        def requests(case_names, count):
            q = np.nonzero(np.isin(c.case, case_names))[0][:count]
            if q.size < count:
                raise ValueError(f"no request of the cases {case_names}")
            return q

        rng = np.random.default_rng(WORLD_SEED + 1)
        right, wrong = requests(("right",), 1), requests(httprules.REFUSED, 2)
        # b: a peer of C inside a listed prefix of B in the peers' own net
        # where there is one, else inside whatever prefix holds the net
        in_net = np.nonzero((e.addr >= c.peer_net) & (e.plen > 8)
                            & (e.addr < c.peer_net + c.peer_span)
                            & (e.plen < 32))[0]
        peer = int(e.addr[in_net[0]]) + 9 if in_net.size else c.peer_net + 9
        if e.longest(np.array([peer]))[0] < 0:
            raise ValueError("no prefix of the egress plane holds a peer of "
                             "the HTTP plane")
        pod_peer = self.pod_base + self._v4_pods(rng, 1)
        # e: the heaviest admitted destination that no pod's /32 shadows
        pool = b._pools[0]
        dst = int(pool[np.nonzero(self._pod_index(pool) < 0)[0][0]])
        # f: two pods with a /128 and a UDP rule of their group
        v6_pods = [p for p in range(0, a.n_ids, a.v6_every)
                   if ((self._rule_group == p % a.groups)
                       & self._rule_udp).any()][:2]
        if len(inside_pods) < 3 or len(v6_pods) < 2:
            raise ValueError("too few pods inside the anchor, or with a "
                             "/128 and a UDP rule")
        ports_c = [rule_for(p, False) for p in inside_pods]
        sp = np.arange(4) + 30000
        cases = {
            "a_pod_request": (self._requests(right, pod_peer), True),
            "a_pod_request_refused": (self._requests(
                wrong, np.repeat(pod_peer, 2)), 180),
            "b_listed_peer": (self._requests(right, [peer]), True),
            "b_listed_peer_refused": (self._requests(wrong, [peer] * 2), 180),
            "c_pod_in_listed_prefix": (self.pod_flows(
                inside_pods, sp[:3], ports_c, PROTO_TCP), True),
            "d_one_past_the_pods": (self.peer_flows(
                [past], sp[:1], ports_c[0]), 130),
            "e_egress_on_an_ingress_port": (self.peer_flows(
                [dst, dst], sp[:2], [ports_c[0], groupports.PORT_BASE],
                egress=True), True),
            "e_ingress_from_an_egress_prefix": (self.peer_flows(
                [dst, dst], sp[:2],
                [groupports.PORT_BASE + a.port_span + 1, ports_c[0]]), 130),
            "f_v6_pod_udp": (self.pod_flows(
                v6_pods, sp[:2], [rule_for(p, True) for p in v6_pods],
                PROTO_UDP), True),
            "f_v6_pod_udp_refused": (self.pod_flows(
                v6_pods[:1], sp[:1], unruled_for(v6_pods[0]), PROTO_UDP),
                130),
            "g_egress_to_a_pod": (self.peer_flows(
                [self.pod_base + inside_pods[0]], sp[:1], 443, egress=True),
                130),
        }
        # g's other half, where a document admits the prefix around the
        # pods (the source's cover 0.0.0.0/1 does); else e's third port
        cases["g_egress_past_the_pods" if allowed_b[held_past]
              else "e_egress_third_port"] = (self.peer_flows(
                  [past if allowed_b[held_past] else dst], sp[:1], 443,
                  egress=True), True)
        for name, (flows, answer) in cases.items():
            got = [True if ok else int(why) for ok, why in zip(
                self._admitted(flows), self.reasons(flows))]
            if got != [answer] * len(got):
                raise ValueError(f"meeting case {name}: the reference says "
                                 f"{got}, the case is made for {answer}")
        return cases

    def _requests(self, q: np.ndarray, peers) -> Flows:
        """Requests ``q`` of plane C's catalogue, each to its own set's
        port, from the v4 addresses ``peers``."""
        flows = self.c._requests(np.random.default_rng(WORLD_SEED), q,
                                 30000, 30001)
        return self.whole(dict(flows, src=v4_words(
            np.asarray(peers).astype(np.uint32))))


def build(params: Dict) -> World:
    return World(params)
