"""World ``podrules``: one endpoint, many pod identities, one ingress port
rule per (pod, port) pair — BASELINE config 5's control plane, as
``bench.py:_config5_world`` and ``chip_smoke.policy_docs`` generate it
(copied; the originals stay where they are until a later PR retires them).

Parameters (the configuration file's ``world`` group):
    n_ids       pod identities; pod i is 172.16.(i >> 8).(i & 255)/32
    n_rules     rule j lets pod ``j % n_ids`` reach TCP port
                ``1024 + j % port_span`` of the endpoint
    port_span

The plain reference is the table those rules spell out: ``allowed[pod,
port - 1024]``, filled from the rule parameters with numpy and from
nothing the program computes. A source outside the pod range has no cell:
it is the world identity, which no rule admits.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.frames import PROTO_TCP, Flows, v4_words

EP_ID = 1
EP_V4 = 0xC0A8000A                      # 192.168.0.10
EP_V6_WORDS = (0xFD000000, 0, 0, 0x10)  # unused: this world is v4-only
POD_NET = 0xAC100000                    # 172.16.0.0
UNKNOWN_NET = 0x0A090000                # 10.9.0.0/16: in no ipcache entry
PORT_BASE = 1024


class World:
    ep_id = EP_ID
    ep_v4 = EP_V4
    ep_v6_words = EP_V6_WORDS

    def __init__(self, params: Dict):
        self.n_ids = int(params["n_ids"])
        self.n_rules = int(params["n_rules"])
        self.port_span = int(params["port_span"])
        j = np.arange(self.n_rules)
        self._cell_of_rule = (j % self.n_ids) * self.port_span \
            + j % self.port_span

        self._cover = np.zeros((self.n_ids * self.port_span,), dtype=np.uint8)
        np.add.at(self._cover, self._cell_of_rule, 1)

    # -- the deployment, through the entry points a user calls --------------
    def policy_docs(self) -> List[Dict]:
        return [{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{
                "fromEndpoints": [
                    {"matchLabels": {"pod": f"p{j % self.n_ids}"}}],
                "toPorts": [{"ports": [{
                    "port": str(PORT_BASE + j % self.port_span),
                    "protocol": "TCP"}]}],
            }],
        } for j in range(self.n_rules)]

    def load(self, eng) -> int:
        """Endpoint, the remote pods as the cluster's identity sync would
        deliver them, the rule documents. Returns the revision to wait
        for."""
        from cilium_tpu.model.labels import Labels
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",),
                         ep_id=EP_ID)
        for i in range(self.n_ids):
            ident = eng.ctx.allocator.allocate(
                Labels.parse([f"k8s:pod=p{i}"]))
            eng.ctx.ipcache.upsert(f"172.16.{i >> 8}.{i & 0xFF}/32",
                                   ident.id)
        return eng.apply_policy(self.policy_docs())

    def register(self, shim) -> None:
        shim.register_endpoint("192.168.0.10", EP_ID)

    # -- the plain reference --------------------------------------------------
    def table(self):
        """(allowed [cells] bool, cover [cells] uint8): which cells some
        rule admits, and how many rules admit each."""
        return self._cover > 0, self._cover

    def cells(self, flows: Flows) -> np.ndarray:
        """Each flow's cell of the table, -1 where it has none."""
        src = flows["src"]
        pod = src[:, 3].astype(np.int64) - POD_NET
        off = flows["dport"].astype(np.int64) - PORT_BASE
        ok = (~flows["is_v6"].astype(bool)) & (src[:, 2] == 0xFFFF) \
            & (pod >= 0) & (pod < self.n_ids) \
            & (off >= 0) & (off < self.port_span) \
            & (flows["proto"] == PROTO_TCP)
        return np.where(ok, pod * self.port_span + off, -1)

    # -- flows ----------------------------------------------------------------
    def _flows(self, addr, sport, dport) -> Flows:
        n = addr.shape[0]
        return {"src": v4_words(addr.astype(np.uint32)),
                "sport": sport.astype(np.int32),
                "dport": dport.astype(np.int32),
                "proto": np.full((n,), PROTO_TCP, np.int32),
                "is_v6": np.zeros((n,), bool)}

    def allowed_flows(self, rng, n: int, sport_lo: int,
                      sport_hi: int) -> Flows:
        cells = self._cell_of_rule[rng.integers(0, self.n_rules, n)]
        return self._flows(POD_NET + cells // self.port_span,
                           rng.integers(sport_lo, sport_hi, n),
                           PORT_BASE + cells % self.port_span)

    def denied_flows(self, rng, n: int, sport_lo: int,
                     sport_hi: int) -> Flows:
        """From a pod, to a port none of its rules admit."""
        allowed = self._cover > 0
        pod = rng.integers(0, self.n_ids, 2 * n + 64)
        off = rng.integers(0, self.port_span, 2 * n + 64)
        keep = np.nonzero(~allowed[pod * self.port_span + off])[0][:n]
        if keep.size < n:
            raise ValueError("the rules leave too few denied ports")
        return self._flows(POD_NET + pod[keep],
                           rng.integers(sport_lo, sport_hi, n),
                           PORT_BASE + off[keep])

    def unknown_flows(self, rng, n: int, sport_lo: int,
                      sport_hi: int) -> Flows:
        """From an address no identity covers."""
        return self._flows(UNKNOWN_NET + rng.integers(1, 60000, n),
                           rng.integers(sport_lo, sport_hi, n),
                           PORT_BASE + rng.integers(0, self.port_span, n))


def build(params: Dict) -> World:
    return World(params)

