"""World ``svclb``: one node of a large cluster under kube-proxy replacement.
The node holds **every** service of the cluster, whatever pods it hosts, and
one local endpoint (a gateway, a batch worker fanning out) calls them by
ClusterIP — ROADMAP R4's static half: Kubernetes SIG-Scalability's
thresholds (10,000 Services, 150,000 pods, 110 pods a node) under Cilium's
documented ``bpf-lb-algorithm maglev`` / ``bpf-lb-maglev-table-size 16381``.

Parameters (the configuration file's ``world`` group):
    n_services         services; service s has the ClusterIP 10.96.0.1 + s
                       (kubeadm's service CIDR 10.96.0.0/12) and is one
                       Maglev row of the program, in s order (the names sort
                       as s does; ``row_order`` says so for the tests)
    external_services  of them selectorless, evenly spread: their backends
                       lie outside the pod CIDRs (172.20.0.0/14), under no
                       prefix of the ipcache until a document names them
    named_external     of those each named by one ``toServices``
                       ``k8sService`` document, spread evenly, so a named
                       one's neighbour among the external ones is not
    backends_mix       {"2": 0.4, ...}: share of the services with that many
                       backends, every backend of weight 1
    ports_mix          {"1": 0.7, ...}: share with that many frontends, all
                       on the service's ClusterIP, ports 80, 443, 9090
    udp_share          of the frontends are UDP
    n_groups           in-cluster service s, the i-th of them, is application
                       ``a{s}`` in label group ``g{i % n_groups}``
    target_ports       a service's backends all listen on its one target
                       port, 8000 + (i // n_groups) % target_ports (the
                       program's ``Service`` has one backend list, so every
                       frontend of a service reaches that port)
    n_rules            egress documents, each ``toEndpoints`` one group with
                       ``toPorts`` one (target port, protocol): document 0
                       is the cluster DNS's (group g0, 53/TCP); document j
                       names group j % n_groups and, with k = j // n_groups,
                       port 8000 + (3k + j % n_groups) % target_ports, UDP
                       when k % 5 == 4 and TCP otherwise
    pods_per_node      pods in a node's /24 (the nodes' /24s consecutive
                       from 10.128.0.0, clear of the service CIDR)
    service_share      of the flows go to a frontend, the rest straight to
                       a pod
    svc_zipf_s         a class's services are drawn by rank,
                       p(rank) ∝ (rank + 1)**-svc_zipf_s

The service of rank 0 is the cluster's DNS: kube-system/kube-dns, port 53
over UDP and TCP on one address, target port 53. In-cluster services'
backends are pods, spread over the nodes' slots by a fixed permutation;
each pod's /32 stands in the ipcache under its **application's** identity
(labels ``group=g…``, ``app=a…``), all pods of a service under one.

**The plain reference**, with numpy from the parameters alone (this file
imports nothing of the program outside ``load``). The program translates
before it looks up and judges, as upstream's from-container path does, so a
flow to a frontend is judged at its service's application and target port:
its cell is (group of the application, target port, protocol), admitted iff
a document names exactly that; a flow to an external service's frontend has
the service's own cell, admitted iff a ``toServices`` document names the
service (every backend then has a /32 the document admits, whatever the
port); a flow straight to a pod is judged at the pod's application and the
port it carries; anything else lies under no prefix and no document admits
it. All backends of a service share identity and target port, as pods of
one Deployment do, so the answer does not depend on which backend Maglev
picks and the reference needs no hash of the program's. A refusal's reason
is 130 throughout (no ``reasons`` method).

**Every world built here holds the cases a nearly right LB gets wrong**
(``build`` raises otherwise); each is in ``cases`` by name with the answer
the documents give, the admitted ones are the heaviest ranks of
``allowed_flows``, the refused ones the first of ``denied_flows`` and (d)
the first of ``unknown_flows``:
    a  services at neighbouring Maglev rows (s, s + 1) with opposite
       verdicts: a row index off by one, a slot that runs into the next row
    b  one ClusterIP, two ports, two verdicts: a service's TCP frontend
       admitted and its UDP frontend on another port refused
    c  one ClusterIP and port, TCP admitted and UDP refused (the DNS)
    d  a frame to a ClusterIP on a port no frontend has: not translated,
       under no prefix, 130
    e  a frame straight to a backend's address on the **frontend's** port:
       refused, where through the frontend it is admitted (policy before
       translation would admit neither or both)
    f  an external service named, and its neighbour not
    g  the largest service (most backends) and one with the fewest, both
       admitted

Source ports: within one call the flows that reach one service's backends
(through any frontend, or straight to a pod) get distinct source ports, so
no two of them can translate to one conntrack key and each hashes to its
own Maglev slot.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmarks.frames import PROTO_TCP, PROTO_UDP, Flows, concat, v4_words

EP_ID = 1
EP_V4 = 0xC0A8000A                      # 192.168.0.10
EP_V6_WORDS = (0xFD000000, 0, 0, 0x10)  # unused: this world is v4-only
SVC_NET = 0x0A600000                    # 10.96.0.0/12: ClusterIPs from .1
SVC_NET_BITS = 20                       # host bits of a /12
POD_NET = 0x0A800000                    # 10.128.0.0: a /24 a node
EXT_NET = 0xAC140000                    # 172.20.0.0/14: a /24 an external
STRAY_NET = 0xC6120000                  # 198.18.0.0/15: under no prefix
FE_PORTS = (80, 443, 9090)              # a service's f-th frontend
DNS_PORT = 53
TPORT_BASE = 8000
EXT_TPORT = 8443
NO_FRONTEND_PORTS = (10000, 20000)      # (d): ports no frontend has
DNS = ("kube-system", "kube-dns")
NAMESPACE = "prod"                      # sorts after kube-system: row = s
WORLD_SEED = 0                          # the deployment is one, whatever
#                                         the run's seed


def _dotted(addr: int) -> str:
    return f"{addr >> 24}.{(addr >> 16) & 255}.{(addr >> 8) & 255}." \
           f"{addr & 255}"


def _counts(mix: Dict[str, float], n: int, rng) -> np.ndarray:
    """[n] the mix's sizes, each by its share of ``n`` (the largest share
    takes the rounding), in an order drawn from ``rng``."""
    sizes = sorted(int(k) for k in mix)
    share = np.array([float(mix[str(k)]) for k in sizes])
    if abs(share.sum() - 1.0) > 1e-9 or (share < 0).any() or min(sizes) < 1:
        raise ValueError(f"a mix's shares sum to 1, its sizes are >= 1: "
                         f"{mix}")
    each = np.round(share * n).astype(np.int64)
    each[int(np.argmax(share))] += n - int(each.sum())
    if (each < 0).any():
        raise ValueError(f"the mix {mix} cannot be dealt over {n} services")
    return rng.permutation(np.repeat(np.array(sizes, np.int64), each))


def _spread(n: int, of: int) -> np.ndarray:
    """[of] bool: ``n`` of ``of`` places, evenly spread."""
    i = np.arange(of, dtype=np.int64)
    return (i + 1) * n // max(1, of) > i * n // max(1, of)


class World:
    ep_id = EP_ID
    ep_v4 = EP_V4
    ep_v6_words = EP_V6_WORDS

    def __init__(self, params: Dict):
        rng = np.random.default_rng(WORLD_SEED)
        S = self.n_services = int(params["n_services"])
        E = self.n_external = int(params["external_services"])
        self.n_named = int(params["named_external"])
        G = self.n_groups = int(params["n_groups"])
        self.n_rules = int(params["n_rules"])
        self.span = int(params["target_ports"])
        self.pods_per_node = int(params["pods_per_node"])
        self.service_share = float(params["service_share"])
        self.zipf_s = float(params["svc_zipf_s"])
        self.udp_share = float(params["udp_share"])
        if not (0 < 2 * self.n_named <= 2 * E <= S) or S % E \
                or S >= 1 << SVC_NET_BITS:
            raise ValueError("services: 0 < named_external <= "
                             "external_services, which divides n_services")
        if not (0 < self.n_rules // G <= self.span) or self.span % 3 == 0 \
                or self.n_rules % G or not 0 < self.pods_per_node < 255:
            raise ValueError("rules: n_rules is a multiple of n_groups, at "
                             "most target_ports a group; target_ports is "
                             "no multiple of 3")
        if not 0.0 < self.service_share < 1.0:
            raise ValueError("service_share: both kinds of flow are left")

        s = np.arange(S, dtype=np.int64)
        #: external services: the last of every S / E
        self.external = s % (S // E) == S // E - 1
        self.ext_index = np.cumsum(self.external) - 1      # where external
        self.named = np.zeros((S,), bool)
        self.named[self.external] = _spread(self.n_named, E)
        self.n_backends = _counts(params["backends_mix"], S, rng)
        n_ports = _counts(params["ports_mix"], S, rng)
        if n_ports.max() > len(FE_PORTS) or self.n_backends.max() > 254:
            raise ValueError("at most 3 frontends and 254 backends a "
                             "service")
        i = s - np.cumsum(self.external)            # among the in-cluster
        self.group = i % G
        self.tport = np.where(self.external, EXT_TPORT,
                              TPORT_BASE + (i // G) % self.span)
        # frontends: [S, 3] port and protocol, -1 where there is none
        self.fe_port = np.where(np.arange(3)[None, :] < n_ports[:, None],
                                np.array(FE_PORTS)[None, :], -1)
        self.fe_udp = (rng.random((S, 3)) < self.udp_share) \
            & (self.fe_port >= 0)
        # rank 0 is the DNS: one port, both protocols, target port 53
        if self.external[0]:
            raise ValueError("service 0 is the DNS: in-cluster")
        self.fe_port[0] = (DNS_PORT, DNS_PORT, -1)
        self.fe_udp[0] = (True, False, False)
        self.tport[0] = DNS_PORT
        self.n_frontends = int((self.fe_port >= 0).sum())

        # pods: in-cluster service s has pods [pod_from[s], pod_from[s+1]),
        # pod i in slot pod_slot[i] of the nodes' /24s
        per = np.where(self.external, 0, self.n_backends)
        self.pod_from = np.concatenate([[0], np.cumsum(per)])
        self.n_pods = int(self.pod_from[-1])
        self.pod_service = np.repeat(s, per)
        self.pod_slot = rng.permutation(self.n_pods)
        self._slot_pod = np.argsort(self.pod_slot)
        if (self.n_pods // self.pods_per_node + 1) * 256 >= 1 << 23 \
                or E * 256 > 1 << 18:
            raise ValueError("the pods pass 10.128.0.0/9, or the external "
                             "backends 172.20.0.0/14")

        # the documents: rule j → (group, port, udp); the table's cells are
        # (group, known port, protocol), then one for each external service
        j = np.arange(self.n_rules, dtype=np.int64)
        k = j // G
        self.rule_group = j % G
        self.rule_port = TPORT_BASE + (3 * k + self.rule_group) % self.span
        self.rule_udp = k % 5 == 4
        self.rule_port[0], self.rule_udp[0] = DNS_PORT, False
        self._n_l4 = G * (self.span + 1) * 2
        cover = np.zeros((self._n_l4 + E,), np.uint8)
        np.add.at(cover, self._l4_cell(self.rule_group, self.rule_port,
                                       self.rule_udp), 1)
        cover[self._n_l4:] = self.named[self.external]
        self._cover = cover

        # each class's (service, frontend) pairs, and its services by rank
        fe_cell = self._frontend_cell(s[:, None], self.fe_udp)
        self._fe_admitted = (self.fe_port >= 0) & (cover[fe_cell] > 0)
        self._fe_refused = (self.fe_port >= 0) & (cover[fe_cell] == 0)
        self.cases = self._cases()
        self._ranked = [
            self._rank(rng, self._fe_admitted.any(axis=1),
                       self._head_services(True)),
            self._rank(rng, self._fe_refused.any(axis=1),
                       self._head_services(False))]
        self._cdf = [self._rank_cdf(r.size) for r in self._ranked]
        by_group = np.argsort(np.where(self.external, G, self.group),
                              kind="stable")
        self._group_services = by_group[:S - E]     # in-cluster, by group
        self._group_from = np.searchsorted(
            self.group[self._group_services], np.arange(G + 1))
        if (np.diff(self._group_from) == 0).any():
            raise ValueError("a label group without a service")

    # -- cells -----------------------------------------------------------------
    def _l4_cell(self, group, port, udp) -> np.ndarray:
        """(group, port, protocol) → cell, -1 for a port no document could
        name (neither a target port nor 53)."""
        port = np.asarray(port, np.int64)
        idx = np.where(port == DNS_PORT, self.span, port - TPORT_BASE)
        ok = (idx >= 0) & (idx <= self.span) \
            & ((port == DNS_PORT) | (idx < self.span))
        return np.where(ok, (np.asarray(group, np.int64) * (self.span + 1)
                             + idx) * 2 + np.asarray(udp, np.int64), -1)

    def _frontend_cell(self, svc, udp) -> np.ndarray:
        """The cell a flow to a frontend of ``svc`` over that protocol is
        judged in: its application's at the target port, or the external
        service's own."""
        svc = np.asarray(svc, np.int64)
        return np.where(self.external[svc],
                        self._n_l4 + self.ext_index[svc],
                        self._l4_cell(self.group[svc], self.tport[svc], udp))

    def table(self):
        """(allowed [cells] bool, cover [cells] uint8): a cell for every
        (group, target port or 53, protocol), then one for every external
        service; which cells some document admits, and how many do."""
        return self._cover > 0, self._cover

    def pod_at(self, addr) -> np.ndarray:
        """The pod whose /32 each address is, -1 where it is no pod's."""
        off = np.asarray(addr, np.int64) - POD_NET
        node, host = off >> 8, (off & 255) - 1
        slot = node * self.pods_per_node + host
        ok = (off >= 0) & (host >= 0) & (host < self.pods_per_node) \
            & (slot < self.n_pods)
        return np.where(ok, self._slot_pod[np.where(ok, slot, 0)], -1)

    def pod_address(self, pod) -> np.ndarray:
        slot = self.pod_slot[np.asarray(pod, np.int64)]
        return POD_NET + (slot // self.pods_per_node) * 256 \
            + slot % self.pods_per_node + 1

    def backend_addresses(self, svc: int) -> np.ndarray:
        """A service's backends: its pods, or its /24 of 172.20.0.0/14."""
        if self.external[svc]:
            return EXT_NET + (int(self.ext_index[svc]) << 8) + 1 \
                + np.arange(self.n_backends[svc], dtype=np.int64)
        return self.pod_address(np.arange(self.pod_from[svc],
                                          self.pod_from[svc + 1]))

    def frontend_of(self, flows: Flows) -> Tuple[np.ndarray, np.ndarray]:
        """→ (service, frontend) of each flow's destination, both -1 where
        it is no frontend's (address, port, protocol)."""
        dst = flows["src"][:, 3].astype(np.int64)
        svc = dst - SVC_NET - 1
        v4 = ~flows["is_v6"].astype(bool) & (flows["src"][:, 2] == 0xFFFF) \
            & flows["egress"].astype(bool)
        ok = v4 & (svc >= 0) & (svc < self.n_services)
        at = np.where(ok, svc, 0)
        udp = flows["proto"] == PROTO_UDP
        l4 = udp | (flows["proto"] == PROTO_TCP)
        hit = (self.fe_port[at] == flows["dport"][:, None]) \
            & (self.fe_udp[at] == udp[:, None]) & (ok & l4)[:, None]
        f = np.where(hit.any(axis=1), hit.argmax(axis=1), -1)
        return np.where(f >= 0, svc, -1), f

    def cells(self, flows: Flows) -> np.ndarray:
        dst = flows["src"][:, 3].astype(np.int64)
        v4 = ~flows["is_v6"].astype(bool) & (flows["src"][:, 2] == 0xFFFF) \
            & flows["egress"].astype(bool)
        udp = flows["proto"] == PROTO_UDP
        l4 = udp | (flows["proto"] == PROTO_TCP)
        svc, _f = self.frontend_of(flows)
        front = self._frontend_cell(np.maximum(svc, 0), udp)
        pod = np.where(v4, self.pod_at(dst), -1)
        of = self.pod_service[np.maximum(pod, 0)]
        straight = np.where(l4, self._l4_cell(self.group[of],
                                              flows["dport"], udp), -1)
        # a named external service's backend has a /32 its document admits
        ext = (dst - EXT_NET) >> 8
        ext_svc = np.nonzero(self.external)[0][np.clip(ext, 0,
                                                       self.n_external - 1)]
        host = ((dst - EXT_NET) & 255) - 1
        at_ext = v4 & (ext >= 0) & (ext < self.n_external) & (host >= 0) \
            & (host < self.n_backends[ext_svc])
        return np.where(svc >= 0, front,
                        np.where(pod >= 0, straight,
                                 np.where(at_ext, self._n_l4 + ext, -1)))

    def _admitted(self, flows: Flows) -> np.ndarray:
        cell = self.cells(flows)
        return np.where(cell >= 0, self._cover[np.maximum(cell, 0)] > 0,
                        False)

    # -- the deployment, through the entry points a user calls --------------
    def service_name(self, svc: int) -> Tuple[str, str]:
        return DNS if svc == 0 else (NAMESPACE, f"svc{svc:06d}")

    def row_order(self) -> List[Tuple[str, str]]:
        """(namespace, name) of every service in s order. The program lays
        its Maglev rows in sorted (namespace, name) order, which is this
        order: what makes (s, s + 1) neighbouring rows."""
        names = [self.service_name(s) for s in range(self.n_services)]
        if names != sorted(names):
            raise ValueError("the services' names do not sort as s does")
        return names

    def services(self) -> List[Dict]:
        """Every service as plain data: name, namespace, frontends (addr,
        port, protocol number), backends (addr, port), whether its backends
        are pods."""
        out = []
        for svc in range(self.n_services):
            namespace, name = self.service_name(svc)
            vip = _dotted(SVC_NET + 1 + svc)
            out.append({
                "namespace": namespace, "name": name,
                "external": bool(self.external[svc]),
                "frontends": [
                    (vip, int(p), PROTO_UDP if u else PROTO_TCP)
                    for p, u in zip(self.fe_port[svc].tolist(),
                                    self.fe_udp[svc].tolist()) if p >= 0],
                "backends": [(_dotted(a), int(self.tport[svc])) for a in
                             self.backend_addresses(svc).tolist()]})
        return out

    def applications(self) -> List[Tuple[int, str, str]]:
        """(service, group label, app label) of every in-cluster one."""
        return [(s, f"g{self.group[s]}", f"a{s}")
                for s in np.nonzero(~self.external)[0].tolist()]

    def policy_docs(self) -> List[Dict]:
        select = {"matchLabels": {"app": "web"}}
        docs = [{"endpointSelector": select, "egress": [{
            "toEndpoints": [{"matchLabels": {"group": f"g{g}"}}],
            "toPorts": [{"ports": [{"port": str(p), "protocol":
                                    "UDP" if u else "TCP"}]}]}]}
                for g, p, u in zip(self.rule_group.tolist(),
                                   self.rule_port.tolist(),
                                   self.rule_udp.tolist())]
        for svc in np.nonzero(self.named)[0].tolist():
            namespace, name = self.service_name(svc)
            docs.append({"endpointSelector": select, "egress": [{
                "toServices": [{"k8sService": {
                    "serviceName": name, "namespace": namespace}}]}]})
        return docs

    def load(self, eng) -> int:
        """Endpoint, the applications' identities with their pods' /32s,
        the services, the rule documents. Returns the revision to wait
        for."""
        from cilium_tpu.model.labels import Labels
        from cilium_tpu.model.services import Backend, Frontend, Service
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",),
                         ep_id=EP_ID)
        services = self.services()
        for svc, group, app in self.applications():
            ident = eng.ctx.allocator.allocate(Labels.parse(
                [f"k8s:group={group}", f"k8s:app={app}"]))
            for addr, _port in services[svc]["backends"]:
                eng.ctx.ipcache.upsert(f"{addr}/32", ident.id)
        for svc in services:
            eng.upsert_service(Service(
                name=svc["name"], namespace=svc["namespace"],
                frontends=tuple(Frontend(a, p, proto)
                                for a, p, proto in svc["frontends"]),
                lb_backends=tuple(Backend(a, p)
                                  for a, p in svc["backends"])))
        return eng.apply_policy(self.policy_docs())

    def register(self, shim) -> None:
        shim.register_endpoint("192.168.0.10", EP_ID)

    # -- flows ----------------------------------------------------------------
    def _flows(self, dst, dport, udp) -> Flows:
        """Egress flows; the source ports are dealt by ``_with_sports``."""
        n = np.asarray(dst).shape[0]
        return {"src": v4_words(np.asarray(dst).astype(np.uint32)),
                "sport": np.zeros((n,), np.int32),
                "dport": np.broadcast_to(dport, (n,)).astype(np.int32),
                "proto": np.where(np.broadcast_to(udp, (n,)), PROTO_UDP,
                                  PROTO_TCP).astype(np.int32),
                "is_v6": np.zeros((n,), bool),
                "egress": np.ones((n,), bool)}

    def to_frontend(self, svc, f) -> Flows:
        svc, f = np.asarray(svc, np.int64), np.asarray(f, np.int64)
        return self._flows(SVC_NET + 1 + svc, self.fe_port[svc, f],
                           self.fe_udp[svc, f])

    def _with_sports(self, rng, flows: Flows, lo: int, hi: int) -> Flows:
        """Source ports from [lo, hi): the flows that reach one service's
        backends (its frontends' and those straight to its pods) each get
        their own, the rest are drawn."""
        n, span = flows["sport"].shape[0], hi - lo
        svc, _f = self.frontend_of(flows)
        pod = self.pod_at(flows["src"][:, 3])
        bucket = np.where(svc >= 0, svc, np.where(
            pod >= 0, self.pod_service[np.maximum(pod, 0)], -1))
        order = np.argsort(bucket, kind="stable")
        sb = bucket[order]
        first = np.concatenate([[True], sb[1:] != sb[:-1]])
        nth = np.arange(n) - np.maximum.accumulate(
            np.where(first, np.arange(n), 0))
        if n and nth[sb >= 0].max(initial=0) >= span:
            raise ValueError(f"more flows to one service than the "
                             f"{span} source ports of [{lo}, {hi})")
        start = rng.integers(0, span, self.n_services)
        sport = np.empty((n,), np.int64)
        sport[order] = np.where(sb >= 0, lo + (start[sb] + nth) % span,
                                rng.integers(lo, hi, n))
        return dict(flows, sport=sport.astype(np.int32))

    def _rank(self, rng, of_class: np.ndarray, head: List[int]
              ) -> np.ndarray:
        """A class's services in rank order: ``head`` first, the rest as
        ``rng`` deals them (so the heavy ranks spread over the rows)."""
        rest = rng.permutation(np.nonzero(of_class)[0])
        return np.concatenate([np.array(head, np.int64),
                               rest[~np.isin(rest, head)]])

    def _rank_cdf(self, n: int) -> np.ndarray:
        cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64)
                        ** -self.zipf_s)
        return cdf / cdf[-1]

    def _draw_frontends(self, rng, n: int, admitted: bool) -> Flows:
        """``n`` flows to frontends of the class, the service by Zipf rank,
        then one of its frontends with that answer."""
        kind = 0 if admitted else 1
        ranked = self._ranked[kind]
        svc = ranked[np.minimum(np.searchsorted(self._cdf[kind],
                                                rng.random(n)),
                                ranked.size - 1)]
        ok = (self._fe_admitted if admitted else self._fe_refused)[svc]
        # the (1 + u * count)-th frontend with the answer, in f order
        nth = (rng.random(n) * ok.sum(axis=1)).astype(np.int64)
        f = (np.cumsum(ok, axis=1) > nth[:, None]).argmax(axis=1)
        return self.to_frontend(svc, f)

    def _draw_straight(self, rng, n: int, admitted: bool) -> Flows:
        """``n`` flows straight to a pod. Admitted: a document first, then
        a pod of its group, on the document's port. Refused: any pod on a
        target or frontend port its group is not admitted to."""
        if admitted:
            j = rng.integers(0, self.n_rules, n)
            g = self.rule_group[j]
            lo, hi = self._group_from[g], self._group_from[g + 1]
            svc = self._group_services[lo + (rng.random(n) * (hi - lo))
                                       .astype(np.int64)]
            pod = self.pod_from[svc] + (rng.random(n) * self.n_backends[svc]
                                        ).astype(np.int64)
            return self._flows(self.pod_address(pod), self.rule_port[j],
                               self.rule_udp[j])
        m = 3 * n + 64
        pod = rng.integers(0, self.n_pods, m)
        ports = np.concatenate([TPORT_BASE + np.arange(self.span),
                                FE_PORTS, [DNS_PORT]])
        dport = ports[rng.integers(0, ports.size, m)]
        udp = rng.random(m) < self.udp_share
        flows = self._flows(self.pod_address(pod), dport, udp)
        keep = np.nonzero(~self._admitted(flows))[0][:n]
        if keep.size < n:
            raise ValueError("the documents leave too few refused ports")
        return {k: v[keep] for k, v in flows.items()}

    def _draw(self, rng, n: int, lo: int, hi: int, admitted: bool) -> Flows:
        head = self._head(admitted)
        h = min(head["sport"].shape[0], n // 4)
        head = {k: v[:h] for k, v in head.items()}
        to_svc = rng.random(n - h) < self.service_share
        m = int(to_svc.sum())
        parts = [self._draw_frontends(rng, m, admitted),
                 self._draw_straight(rng, n - h - m, admitted)]
        at = np.concatenate([np.nonzero(to_svc)[0], np.nonzero(~to_svc)[0]])
        rest = concat(parts)
        rest = {k: v[np.argsort(at, kind="stable")] for k, v in rest.items()}
        return self._with_sports(rng, concat([head, rest]), lo, hi)

    def allowed_flows(self, rng, n: int, sport_lo: int,
                      sport_hi: int) -> Flows:
        """The admitted cases first (a law that ranks flows in the order
        drawn makes them the heaviest), then ``service_share`` of the flows
        to an admitted frontend, the rest straight to a pod on a port a
        document admits its group to."""
        return self._draw(rng, n, sport_lo, sport_hi, True)

    def denied_flows(self, rng, n: int, sport_lo: int,
                     sport_hi: int) -> Flows:
        """The refused cases, then flows to a frontend whose application no
        document admits at its target port (or an external service none
        names), and straight to a pod on a port its group is not admitted
        to."""
        return self._draw(rng, n, sport_lo, sport_hi, False)

    def unknown_flows(self, rng, n: int, sport_lo: int,
                      sport_hi: int) -> Flows:
        """(d) first; then half to a ClusterIP on a port no frontend has,
        half to an address under no prefix."""
        head = self.cases["d_port_no_frontend_has"][0]
        h = min(head["sport"].shape[0], n)
        m = n - h
        vip = rng.random(m) < 0.5
        dst = np.where(vip, SVC_NET + 1 + rng.integers(0, self.n_services,
                                                       m),
                       STRAY_NET + rng.integers(1, 1 << 17, m))
        rest = self._flows(dst, rng.integers(*NO_FRONTEND_PORTS, m),
                           rng.random(m) < self.udp_share)
        flows = concat([{k: v[:h] for k, v in head.items()}, rest])
        return dict(flows, sport=rng.integers(sport_lo, sport_hi, n)
                    .astype(np.int32))

    # -- the cases a nearly right LB gets wrong ---------------------------------
    def _head_services(self, admitted: bool) -> List[int]:
        """The cases' services with that answer, in the cases' order, each
        once: the class's heaviest ranks (the DNS is rank 0 of both)."""
        out: List[int] = [0]
        for flows, answer in self.cases.values():
            if (answer is True) == admitted:
                svc, _f = self.frontend_of(flows)
                out += [s for s in svc.tolist() if s >= 0 and s not in out]
        return out

    def _head(self, admitted: bool) -> Flows:
        return concat([f for name, (f, answer) in self.cases.items()
                       if (answer is True) == admitted
                       and name != "d_port_no_frontend_has"])

    def _cases(self) -> Dict[str, Tuple[Flows, object]]:
        """name → (flows, answer): True for admitted, else 130. Raises
        where the parameters leave a case out, or the reference gives
        another answer than the case is made for."""
        adm, ref = self._fe_admitted, self._fe_refused
        tcp = (self.fe_port >= 0) & ~self.fe_udp
        inside = ~self.external

        def first(mask, what):
            hit = np.nonzero(mask)[0]
            if not hit.size:
                raise ValueError(f"the parameters leave no {what}")
            return int(hit[0])

        # a: rows s, s + 1, both in-cluster with a TCP frontend 0
        a = first(inside[:-1] & inside[1:] & (adm & tcp)[:-1, 0]
                  & (ref & tcp)[1:, 0] & (np.arange(self.n_services - 1)
                                          > 0),
                  "neighbouring rows with opposite verdicts")
        # b: a TCP frontend admitted and a UDP one of the same service
        # refused, on two ports
        b = first(inside & (adm & tcp).any(axis=1)
                  & (ref & self.fe_udp).any(axis=1)
                  & (np.arange(self.n_services) > 0),
                  "service with a TCP frontend admitted and a UDP one "
                  "refused")
        b_tcp, b_udp = int((adm & tcp)[b].argmax()), \
            int((ref & self.fe_udp)[b].argmax())
        # e: an admitted TCP frontend whose port no document admits its
        # group to (frontend ports are no target ports: none does)
        e = first(inside & (adm & tcp)[:, 0]
                  & ~np.isin(np.arange(self.n_services), (0, a, b)),
                  "admitted in-cluster service beside the others")
        # f: external neighbours, the first named and the second not
        ext = np.nonzero(self.external)[0]
        pair = first(self.named[ext[:-1]] & ~self.named[ext[1:]],
                     "named external service with a neighbour that is not")
        f_named, f_not = int(ext[pair]), int(ext[pair + 1])
        # g: the most and the fewest backends among the admitted
        ok = inside & (adm & tcp)[:, 0]
        most = first(ok & (self.n_backends == self.n_backends[ok].max()),
                     "admitted service")
        fewest = first(ok & (self.n_backends == self.n_backends[ok].min()),
                       "admitted service")
        backend = int(self.backend_addresses(e)[0])
        cases = {
            "a_row_s_admitted": (self.to_frontend([a], [0]), True),
            "a_row_s_plus_1_refused": (self.to_frontend([a + 1], [0]), 130),
            "b_tcp_port_admitted": (self.to_frontend([b], [b_tcp]), True),
            "b_udp_port_refused": (self.to_frontend([b], [b_udp]), 130),
            "c_dns_tcp_admitted": (self.to_frontend([0], [1]), True),
            "c_dns_udp_refused": (self.to_frontend([0], [0]), 130),
            "d_port_no_frontend_has": (self._flows(
                [SVC_NET + 1 + a, SVC_NET + 1],
                [NO_FRONTEND_PORTS[0], DNS_PORT + 1], [False, True]), 130),
            "e_through_the_frontend": (self.to_frontend([e], [0]), True),
            "e_straight_on_the_frontends_port": (self._flows(
                [backend], self.fe_port[e, 0], False), 130),
            "f_external_named": (self.to_frontend([f_named], [0]), True),
            "f_external_neighbour_not": (self.to_frontend([f_not], [0]),
                                         130),
            "g_most_backends": (self.to_frontend([most], [0]), True),
            "g_fewest_backends": (self.to_frontend([fewest], [0]), True),
        }
        for name, (flows, answer) in cases.items():
            got = [True if ok else 130 for ok in self._admitted(flows)]
            if got != [answer] * len(got):
                raise ValueError(f"case {name}: the reference says {got}, "
                                 f"the case is made for {answer}")
        return cases


def build(params: Dict) -> World:
    return World(params)
