"""World ``groupports``: one dual-stack endpoint, pod identities in label
groups, ingress port rules per group over TCP and UDP — BASELINE config
2's control plane, as ``bench.py:build_config2`` generates it (copied; the
original stays until a later PR retires ``bench.py``).

Parameters (the configuration file's ``world`` group):
    n_ids       pod identities; pod i carries ``group=g{i % groups}`` and
                ``pod=p{i}``, has 172.16.(i >> 8).(i & 255)/32 and, when
                ``i % v6_every == 0``, 2001:db8:(i >> 8):(i & 255)::1/128
    groups
    n_rules     rule j lets group ``j % groups`` reach port
                ``1000 + j % port_span``, over UDP when ``j % 3 == 0`` and
                TCP otherwise
    port_span
    v6_every

The endpoint needs an address in each family for frames to reach it: the
source gives 192.168.0.10 and no v6 address; fd00::10 is assumed.

The plain reference is ``allowed[group, port - 1000, is_udp]``, filled from
the rule parameters with numpy.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.frames import PROTO_TCP, PROTO_UDP, Flows, v4_words

EP_ID = 1
EP_V4 = 0xC0A8000A                      # 192.168.0.10
EP_V6 = "fd00::10"
EP_V6_WORDS = (0xFD000000, 0, 0, 0x10)
POD_NET = 0xAC100000                    # 172.16.0.0
UNKNOWN_NET = 0x0A090000                # 10.9.0.0/16: in no ipcache entry
PORT_BASE = 1000


class World:
    ep_id = EP_ID
    ep_v4 = EP_V4
    ep_v6_words = EP_V6_WORDS

    def __init__(self, params: Dict):
        self.n_ids = int(params["n_ids"])
        self.groups = int(params["groups"])
        self.n_rules = int(params["n_rules"])
        self.port_span = int(params["port_span"])
        self.v6_every = int(params["v6_every"])
        j = np.arange(self.n_rules)
        self._rule_udp = j % 3 == 0
        self._cell_of_rule = ((j % self.groups) * self.port_span
                              + j % self.port_span) * 2 + self._rule_udp

        self._cover = np.zeros((self.groups * self.port_span * 2,), dtype=np.uint8)
        np.add.at(self._cover, self._cell_of_rule, 1)

    # -- the deployment, through the entry points a user calls --------------
    def policy_docs(self) -> List[Dict]:
        return [{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{
                "fromEndpoints": [
                    {"matchLabels": {"group": f"g{j % self.groups}"}}],
                "toPorts": [{"ports": [{
                    "port": str(PORT_BASE + j % self.port_span),
                    "protocol": "TCP" if j % 3 else "UDP"}]}],
            }],
        } for j in range(self.n_rules)]

    def load(self, eng) -> int:
        from cilium_tpu.model.labels import Labels
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10", EP_V6),
                         ep_id=EP_ID)
        for i in range(self.n_ids):
            ident = eng.ctx.allocator.allocate(Labels.parse(
                [f"k8s:group=g{i % self.groups}", f"k8s:pod=p{i}"]))
            eng.ctx.ipcache.upsert(
                f"172.{16 + (i >> 16)}.{(i >> 8) & 0xFF}.{i & 0xFF}/32",
                ident.id)
            if i % self.v6_every == 0:
                eng.ctx.ipcache.upsert(
                    f"2001:db8:{i >> 8:x}:{i & 0xFF:x}::1/128", ident.id)
        return eng.apply_policy(self.policy_docs())

    def register(self, shim) -> None:
        shim.register_endpoint("192.168.0.10", EP_ID)
        shim.register_endpoint(EP_V6, EP_ID)

    # -- the plain reference --------------------------------------------------
    def table(self):
        """(allowed [cells] bool, cover [cells] uint8): which cells some
        rule admits, and how many rules admit each."""
        return self._cover > 0, self._cover

    def _pod_of(self, flows: Flows) -> np.ndarray:
        """Pod index of each source address, -1 where it is no pod's."""
        src = flows["src"]
        v6 = flows["is_v6"].astype(bool)
        p4 = src[:, 3].astype(np.int64) - POD_NET
        ok4 = (~v6) & (src[:, 2] == 0xFFFF) & (p4 >= 0) & (p4 < self.n_ids)
        hi = (src[:, 1] >> 16).astype(np.int64)
        lo = (src[:, 1] & 0xFFFF).astype(np.int64)
        p6 = (hi << 8) | lo
        ok6 = v6 & (src[:, 0] == 0x20010DB8) & (lo < 256) \
            & (src[:, 2] == 0) & (src[:, 3] == 1) & (p6 < self.n_ids) \
            & (p6 % self.v6_every == 0)
        return np.where(ok4, p4, np.where(ok6, p6, -1))

    def cells(self, flows: Flows) -> np.ndarray:
        pod = self._pod_of(flows)
        off = flows["dport"].astype(np.int64) - PORT_BASE
        proto = flows["proto"]
        ok = (pod >= 0) & (off >= 0) & (off < self.port_span) \
            & ((proto == PROTO_TCP) | (proto == PROTO_UDP))
        cell = ((pod % self.groups) * self.port_span + off) * 2 \
            + (proto == PROTO_UDP)
        return np.where(ok, cell, -1)

    # -- flows ----------------------------------------------------------------
    def _flows(self, pod, sport, dport, proto) -> Flows:
        """Pods with a v6 address send over v6 (a quarter of them, at
        ``v6_every`` 4), the rest over v4."""
        pod = pod.astype(np.int64)
        n = pod.shape[0]
        v6 = pod % self.v6_every == 0
        src = v4_words((POD_NET + pod).astype(np.uint32))
        src[v6, 0] = 0x20010DB8
        src[v6, 1] = (((pod[v6] >> 8) << 16) | (pod[v6] & 0xFF)) \
            .astype(np.uint32)
        src[v6, 2] = 0
        src[v6, 3] = 1
        return {"src": src, "sport": sport.astype(np.int32),
                "dport": dport.astype(np.int32),
                "proto": np.asarray(proto, np.int32) * np.ones(n, np.int32),
                "is_v6": v6}

    def allowed_flows(self, rng, n: int, sport_lo: int, sport_hi: int,
                      udp_share: float = 0.1) -> Flows:
        """A rule first (UDP for ``udp_share`` of the flows), then a pod of
        the rule's group."""
        udp_rules = np.nonzero(self._rule_udp)[0]
        tcp_rules = np.nonzero(~self._rule_udp)[0]
        udp = rng.random(n) < udp_share
        j = np.where(udp, udp_rules[rng.integers(0, udp_rules.size, n)],
                     tcp_rules[rng.integers(0, tcp_rules.size, n)])
        per_group = self.n_ids // self.groups
        pod = j % self.groups + self.groups * rng.integers(0, per_group, n)
        return self._flows(pod, rng.integers(sport_lo, sport_hi, n),
                           PORT_BASE + j % self.port_span,
                           np.where(udp, PROTO_UDP, PROTO_TCP))

    def denied_flows(self, rng, n: int, sport_lo: int, sport_hi: int,
                     udp_share: float = 0.1) -> Flows:
        allowed = self._cover > 0
        m = 2 * n + 64
        pod = rng.integers(0, self.n_ids, m)
        off = rng.integers(0, self.port_span, m)
        udp = rng.random(m) < udp_share
        cell = ((pod % self.groups) * self.port_span + off) * 2 + udp
        keep = np.nonzero(~allowed[cell])[0][:n]
        if keep.size < n:
            raise ValueError("the rules leave too few denied ports")
        return self._flows(pod[keep], rng.integers(sport_lo, sport_hi, n),
                           PORT_BASE + off[keep],
                           np.where(udp[keep], PROTO_UDP, PROTO_TCP))

    def unknown_flows(self, rng, n: int, sport_lo: int,
                      sport_hi: int) -> Flows:
        addr = (UNKNOWN_NET + rng.integers(1, 60000, n)).astype(np.uint32)
        return {"src": v4_words(addr),
                "sport": rng.integers(sport_lo, sport_hi, n)
                .astype(np.int32),
                "dport": (PORT_BASE + rng.integers(0, self.port_span, n))
                .astype(np.int32),
                "proto": np.full((n,), PROTO_TCP, np.int32),
                "is_v6": np.zeros((n,), bool)}


def build(params: Dict) -> World:
    return World(params)
