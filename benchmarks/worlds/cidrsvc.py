"""World ``cidrsvc``: one endpoint whose flows **leave** it, towards a table
of CIDR prefixes of mixed length and towards service frontends — BASELINE
config 3's control plane ("100k CIDR prefixes (BGP full-table slice) +
ToServices rules, skewed Zipf traffic") as ``bench.py:build_config3`` read it
until PR 31 deleted it (``git show d48d000^:bench.py``): egress ``toCIDR`` and
``toServices`` rules on the one endpoint, prefixes straight into the ipcache,
destinations drawn by Zipf from a fixed pool.

Parameters (the configuration file's ``world`` group):
    n_prefixes     listed prefixes, v4, upserted into the ipcache as the
                   cluster's CIDR identities arrive
    length_mix     {"16": 0.2, "20": 0.3, "24": 0.5}: share of each length
    nested_share   share of the listed prefixes drawn inside a shorter one
    identity_plen  a listed prefix longer than this carries the CIDR
                   identity of its covering block of this length (the source
                   has 8, "to bound identity count"); 32: each its own
    cover_cidrs    CIDRs admitted as they stand, one ``toCIDR`` document
                   each (the source has ["0.0.0.0/1"]); may be empty
    admit_listed   single-prefix ``toCIDR`` documents, each naming one listed
                   prefix; nested prefixes whose parent no rule admits first
    cidr_sets      ``toCIDRSet`` documents, each naming one listed prefix
                   that holds others, with
    excepts_each   of those cut out again by ``except``
    services       {"count", "named", "backends_each", "frontends_each"}:
                   ``Engine.upsert_service`` with that many frontends (a
                   10.96/16 address and a port) and backends (10.200/16);
                   one ``toServices`` document for each of the first
                   ``named``
    pool           destinations in the pool, split by
    pool_split     [allowed, denied, unknown] shares: an address whose
                   longest prefix some document admits; one whose longest
                   prefix none admits; one under no prefix at all
    zipf_s         each class's destinations are drawn by rank,
                   p(rank) ∝ (rank + 1)**-zipf_s
    service_share  of the allowed flows go to a frontend of a service some
                   document admits (of the denied ones, to one of a service
                   none does)

A document that names a prefix puts it into the ipcache with an identity of
its own, as the program's rule materialisation does; the listed set leaves
those out, so no prefix is upserted twice.

**The plain reference** is containment, with numpy, from the prefix list and
the rule parameters alone. A flow's cell is the longest prefix of the
ipcache that holds its destination (for each length, longest first: mask,
look the masked address up among that length's prefixes). The cell is
admitted iff some document's CIDR holds the prefix's *identity* prefix and
none of its ``except`` CIDRs does: CIDR identities carry a label for every
covering prefix, so that is what a ``toCIDR`` selector matches. A flow to a
frontend has its service's cell and is judged where the program judges it,
at the backend's address: admitted iff a ``toServices`` document names the
service (every backend then has a /32 of its own that the document admits,
so the verdict does not depend on which one Maglev picks), or a document's
CIDR holds the backends' net, as a cover like 0.0.0.0/1 does.

Every world built here holds nested prefixes with opposite verdicts, both
ways (a denied prefix cut by ``except`` out of an admitted one; an admitted
prefix inside one no document admits), and each class's heaviest pool ranks
lie in such prefixes: a walk that stops at the first or the shortest match
gets the frames most often sent wrong.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from benchmarks.frames import PROTO_TCP, Flows, v4_words

EP_ID = 1
EP_V4 = 0xC0A8000A                      # 192.168.0.10
EP_V6_WORDS = (0xFD000000, 0, 0, 0x10)  # unused: this world is v4-only
VIP_NET = 0x0A600000                    # 10.96.0.0/16: frontends
BACKEND_NET = 0x0AC80000                # 10.200.0.0/16: backends
FE_PORT_BASE = 8000
BE_PORT_BASE = 9000
NAMESPACE = "prod"
#: first octets no listed prefix, cover CIDR aside, and no pool address
#: has: the frontends' and backends' net, loopback, the endpoint's own
KEPT_OCTETS = (10, 127, 192)
WORLD_SEED = 0                          # the deployment is one, whatever
#                                         the run's seed


def _mask(plen) -> np.ndarray:
    plen = np.asarray(plen, np.int64)
    return ((np.int64(0xFFFFFFFF) << (32 - plen)) & 0xFFFFFFFF)


def _parse(cidr: str) -> Tuple[int, int]:
    addr, plen = cidr.split("/")
    a, b, c, d = (int(x) for x in addr.split("."))
    plen = int(plen)
    return ((a << 24) | (b << 16) | (c << 8) | d) & int(_mask(plen)), plen


def _dotted(addr: int) -> str:
    return f"{addr >> 24}.{(addr >> 16) & 255}.{(addr >> 8) & 255}." \
           f"{addr & 255}"


def _text(addr: int, plen: int) -> str:
    return f"{_dotted(addr)}/{plen}"


class Prefixes:
    """A set of distinct v4 prefixes, looked up by containment."""

    def __init__(self, addr: np.ndarray, plen: np.ndarray):
        self.addr = np.asarray(addr, np.int64)
        self.plen = np.asarray(plen, np.int64)
        # for each length, longest first: its prefixes' addresses sorted,
        # and the index each has in the set
        self._by_len = []
        for length in sorted(set(self.plen.tolist()), reverse=True):
            idx = np.nonzero(self.plen == length)[0]
            order = np.argsort(self.addr[idx], kind="stable")
            self._by_len.append((length, self.addr[idx][order], idx[order]))

    def longest(self, addr: np.ndarray, shorter_than=None) -> np.ndarray:
        """Index of the longest prefix holding each address, -1 for none.
        ``shorter_than`` [n]: only prefixes shorter than that count."""
        addr = np.asarray(addr, np.int64)
        found = np.full(addr.shape, -1, np.int64)
        for length, sorted_addr, index in self._by_len:
            masked = addr & _mask(length)
            at = np.minimum(np.searchsorted(sorted_addr, masked),
                            sorted_addr.size - 1)
            hit = (sorted_addr[at] == masked) & (found < 0)
            if shorter_than is not None:
                hit &= length < shorter_than
            found[hit] = index[at[hit]]
        return found


def _held_by(cidr: Tuple[int, int], addr: np.ndarray,
             plen: np.ndarray) -> np.ndarray:
    """[n] bool: which of the prefixes ``addr/plen`` the CIDR holds."""
    return (plen >= cidr[1]) & ((addr & _mask(cidr[1])) == cidr[0])


class World:
    ep_id = EP_ID
    ep_v4 = EP_V4
    ep_v6_words = EP_V6_WORDS

    def __init__(self, params: Dict):
        rng = np.random.default_rng(WORLD_SEED)
        self.zipf_s = float(params["zipf_s"])
        self.service_share = float(params["service_share"])
        self.identity_plen = int(params["identity_plen"])
        svc = params["services"]
        self.n_services, self.n_named = int(svc["count"]), int(svc["named"])
        self.backends_each = int(svc["backends_each"])
        self.frontends_each = int(svc["frontends_each"])
        if not 0 <= self.n_named <= self.n_services \
                or (self.n_services and min(self.backends_each,
                                            self.frontends_each) < 1):
            raise ValueError("services: named <= count, and a service has "
                             "a frontend and a backend at least")

        listed = self._draw_listed(params, rng)
        # the documents: (cidr, excepts) each, all prefixes as (addr, plen)
        self._docs = self._choose_documents(params, listed, rng)
        named = {p for cidr, excepts in self._docs for p in (cidr, *excepts)}
        backends = [(BACKEND_NET + (s << 8) + b + 1, 32)
                    for s in range(self.n_named)
                    for b in range(self.backends_each)]
        #: what load() upserts itself; the program puts the rest in
        self._listed = [p for p in listed if p not in named]
        entries = self._listed + sorted(named) + backends
        self.ipcache = Prefixes(*zip(*entries))
        e, n = self.ipcache, len(entries)
        # the prefix each entry's identity is labelled for: a listed one's
        # may be its covering block's, every other is the entry's own
        q_plen = np.where(np.arange(n) < len(self._listed),
                          np.minimum(e.plen, self.identity_plen), e.plen)
        q_addr = e.addr & _mask(q_plen)
        cover = np.zeros((n + self.n_services,), np.uint8)
        for cidr, excepts in self._docs:
            admits = _held_by(cidr, q_addr, q_plen)
            for x in excepts:
                admits &= ~_held_by(x, q_addr, q_plen)
            cover[:n] += admits
        cover[n - len(backends):n] += 1       # its toServices document
        # a frontend's flows are judged at the backend's address: a named
        # service's by its document, any other's by whatever holds them
        for s in range(self.n_services):
            at = e.longest(BACKEND_NET + (s << 8) + 1
                           + np.arange(self.backends_each))
            held = np.where(at >= 0, cover[np.maximum(at, 0)], 0)
            if (held != held[0]).any():
                raise ValueError(f"service {s}'s backends lie under "
                                 f"prefixes that differ in what admits them")
            cover[n + s] = held[0]
        self._cover = cover
        self._services_admitted = np.nonzero(cover[n:] > 0)[0]
        self._services_denied = np.nonzero(cover[n:] == 0)[0]
        self._pools = self._draw_pools(params, rng)
        self._pool_cdf = [self._rank_cdf(p.size) for p in self._pools]

    # -- the deployment's parameters → prefixes and documents ---------------
    @staticmethod
    def _draw_listed(params: Dict, rng) -> List[Tuple[int, int]]:
        n = int(params["n_prefixes"])
        lengths = np.array(sorted(int(k) for k in params["length_mix"]))
        share = np.array([float(params["length_mix"][str(k)])
                          for k in lengths])
        n_nested = int(round(float(params["nested_share"]) * n))
        m = 2 * n + 64                    # some are drawn twice, or kept out
        top_len = rng.choice(lengths, m, p=share / share.sum())
        top = rng.integers(0x01000000, 0xDF000000, m) & _mask(top_len)
        ok = ~np.isin(top >> 24, KEPT_OCTETS)
        top, top_len = top[ok][:n - n_nested], top_len[ok][:n - n_nested]
        parents = np.nonzero(top_len < lengths[-1])[0]
        if n_nested and not parents.size:
            raise ValueError("no listed prefix is short enough to hold one")
        par = parents[rng.integers(0, max(1, parents.size), 2 * n_nested)]
        # a length longer than the parent's, by the mix's shares among those
        u = 1.0 - rng.random(par.size)                     # (0, 1]
        longer = lengths[None, :] > top_len[par][:, None]
        w = np.where(longer, share[None, :], 0.0)
        cdf = np.cumsum(w, axis=1) / w.sum(axis=1, keepdims=True)
        sub_len = lengths[np.minimum((u[:, None] > cdf).sum(axis=1),
                                     lengths.size - 1)]
        sub = (top[par] | rng.integers(0, 1 << 32, par.size)
               & ~_mask(top_len[par])) & _mask(sub_len)
        seen, out = set(), []
        for a, p in zip(np.concatenate([top, sub]).tolist(),
                        np.concatenate([top_len, sub_len]).tolist()):
            if (a, p) not in seen and len(out) < n:
                seen.add((a, p))
                out.append((a, p))
        if len(out) < n:
            raise ValueError(f"only {len(out)} distinct prefixes of {n}")
        return out

    def _choose_documents(self, params: Dict, listed, rng):
        """Which prefixes the documents admit: the cover CIDRs; then
        ``cidr_sets`` listed prefixes that hold others, some of those cut
        out; then ``admit_listed`` single prefixes, nested ones whose parent
        nothing so far admits first."""
        pre = Prefixes(*zip(*listed))
        docs = [(_parse(c), ()) for c in params["cover_cidrs"]]
        parent = pre.longest(pre.addr, shorter_than=pre.plen)
        admitted = np.zeros((len(listed),), bool)
        for cidr, _x in docs:
            admitted |= _held_by(cidr, pre.addr, pre.plen)
        holders = np.unique(parent[parent >= 0])
        holders = holders[np.argsort(admitted[holders], kind="stable")]
        x_each = int(params["excepts_each"])
        free = np.ones((len(listed),), bool)
        for h in holders[:int(params["cidr_sets"])].tolist():
            cut = np.nonzero(parent == h)[0][:x_each]
            docs.append((listed[h], tuple(listed[c] for c in cut.tolist())))
            admitted[h] = True
            free[cut] = False
        free &= ~admitted
        nested = free & (parent >= 0) & ~admitted[np.maximum(parent, 0)]
        order = rng.permutation(len(listed))
        order = order[np.argsort(~nested[order], kind="stable")]
        single = order[free[order]][:int(params["admit_listed"])]
        docs += [(listed[i], ()) for i in single.tolist()]
        return docs

    def _draw_pools(self, params: Dict, rng) -> List[np.ndarray]:
        """The pool's three classes, each in rank order. Candidates are
        drawn inside every prefix of the ipcache in turn (so the heavy ranks
        spread over the documents) and sorted by what the reference says of
        them, prefixes whose parent has the opposite verdict first."""
        e, split = self.ipcache, params["pool_split"]
        want = [int(round(float(s) * int(params["pool"]))) for s in split]
        allowed = self._cover[:e.addr.size] > 0
        parent = e.longest(e.addr, shorter_than=e.plen)
        contrast = (parent >= 0) & (allowed != allowed[np.maximum(parent, 0)])
        if not (contrast & allowed).any() or not (contrast & ~allowed).any():
            raise ValueError("the parameters leave no nested prefixes with "
                             "opposite verdicts, both ways")
        inside = np.nonzero(e.plen < 32)[0]       # not the backends' /32s
        turn = inside[rng.permutation(inside.size)]
        turn = turn[np.argsort(~contrast[turn], kind="stable")]
        per_prefix = max(want[0] / max(1, allowed[inside].sum()),
                         want[1] / max(1, (~allowed[inside]).sum()))
        of = np.tile(turn, int(2 * per_prefix) + 2)
        cand = e.addr[of] | (rng.integers(0, 1 << 32, of.size)
                             & ~_mask(e.plen[of]))
        cell = e.longest(cand)
        ok = ~np.isin(cand >> 24, KEPT_OCTETS) & (cell == of)
        cand, cell = cand[ok], cell[ok]
        stray = rng.integers(0x01000000, 0xDF000000, 8 * want[2] + 64)
        stray = stray[~np.isin(stray >> 24, KEPT_OCTETS)
                      & (e.longest(stray) < 0)]
        pools = [_first_distinct(c, n)
                 for c, n in ((cand[allowed[cell]], want[0]),
                              (cand[~allowed[cell]], want[1]),
                              (stray, want[2]))]
        for name, pool, n in zip(("allowed", "denied", "unknown"), pools,
                                 want):
            if pool.size < n:
                raise ValueError(f"only {pool.size} {name} destinations of "
                                 f"{n} wanted")
        return pools

    def _rank_cdf(self, n: int) -> np.ndarray:
        """Zipf over ranks 1..n, for drawing by inverse CDF."""
        cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64)
                        ** -self.zipf_s)
        return cdf / cdf[-1]

    # -- the deployment, through the entry points a user calls --------------
    def _service_names(self) -> List[str]:
        return [f"svc{s}" for s in range(self.n_services)]

    def services(self) -> List:
        from cilium_tpu.model.services import Backend, Frontend, Service
        return [Service(
            name=name, namespace=NAMESPACE,
            frontends=tuple(Frontend(_dotted(VIP_NET + s), FE_PORT_BASE + f)
                            for f in range(self.frontends_each)),
            lb_backends=tuple(
                Backend(_dotted(BACKEND_NET + (s << 8) + b + 1),
                        BE_PORT_BASE + b)
                for b in range(self.backends_each)))
            for s, name in enumerate(self._service_names())]

    def listed(self) -> List[Tuple[str, str]]:
        """(prefix, the prefix its CIDR identity is labelled for) of every
        listed prefix no document names."""
        out = []
        for addr, plen in self._listed:
            q = min(plen, self.identity_plen)
            out.append((_text(addr, plen), _text(addr & int(_mask(q)), q)))
        return out

    def policy_docs(self) -> List[Dict]:
        select = {"matchLabels": {"app": "web"}}
        docs = []
        for (addr, plen), excepts in self._docs:
            to = {"toCIDRSet": [{"cidr": _text(addr, plen), "except": [
                _text(*x) for x in excepts]}]} if excepts \
                else {"toCIDR": [_text(addr, plen)]}
            docs.append({"endpointSelector": select, "egress": [to]})
        for name in self._service_names()[:self.n_named]:
            docs.append({"endpointSelector": select, "egress": [{
                "toServices": [{"k8sService": {
                    "serviceName": name, "namespace": NAMESPACE}}]}]})
        return docs

    def load(self, eng) -> int:
        """Endpoint, the listed prefixes as the cluster's CIDR identities
        arrive, the services, the rule documents. Returns the revision to
        wait for."""
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",),
                         ep_id=EP_ID)
        for prefix, labelled_for in self.listed():
            ident = eng.ctx.allocator.allocate_cidr(labelled_for)
            eng.ctx.ipcache.upsert(prefix, ident.id)
        for svc in self.services():
            eng.upsert_service(svc)
        return eng.apply_policy(self.policy_docs())

    def register(self, shim) -> None:
        shim.register_endpoint("192.168.0.10", EP_ID)

    # -- the plain reference --------------------------------------------------
    def table(self):
        """(allowed [cells] bool, cover [cells] uint8): a cell for every
        prefix of the ipcache, then one for every service; which cells some
        document admits, and how many admit each."""
        return self._cover > 0, self._cover

    def cells(self, flows: Flows) -> np.ndarray:
        """Each flow's cell: its frontend's service, else the longest
        prefix holding its destination, -1 where none does."""
        dst = flows["src"][:, 3].astype(np.int64)
        v4 = ~flows["is_v6"].astype(bool) & (flows["src"][:, 2] == 0xFFFF)
        cell = np.where(v4, self.ipcache.longest(dst), -1)
        s = dst - VIP_NET
        f = flows["dport"].astype(np.int64) - FE_PORT_BASE
        front = v4 & (flows["proto"] == PROTO_TCP) \
            & (s >= 0) & (s < self.n_services) \
            & (f >= 0) & (f < self.frontends_each)
        return np.where(front, self.ipcache.addr.size + s, cell)

    # -- flows ----------------------------------------------------------------
    def _flows(self, dst, sport, dport) -> Flows:
        n = dst.shape[0]
        return {"src": v4_words(dst.astype(np.uint32)),    # the peer
                "sport": sport.astype(np.int32),
                "dport": dport.astype(np.int32),
                "proto": np.full((n,), PROTO_TCP, np.int32),
                "is_v6": np.zeros((n,), bool),
                "egress": np.ones((n,), bool)}

    def _draw(self, rng, n: int, kind: int, services: np.ndarray,
              sport_lo: int, sport_hi: int) -> Flows:
        """``n`` flows to pool ``kind`` by Zipf rank, ``service_share`` of
        them to a frontend of one of ``services`` instead."""
        pool = self._pools[kind]
        rank = np.minimum(np.searchsorted(self._pool_cdf[kind],
                                          rng.random(n)), pool.size - 1)
        dst, dport = pool[rank], rng.integers(1, 65535, n)
        if services.size:
            to_svc = rng.random(n) < self.service_share
            s = services[rng.integers(0, services.size, n)]
            f = rng.integers(0, self.frontends_each, n)
            dst = np.where(to_svc, VIP_NET + s, dst)
            dport = np.where(to_svc, FE_PORT_BASE + f, dport)
        return self._flows(dst, rng.integers(sport_lo, sport_hi, n), dport)

    def allowed_flows(self, rng, n: int, sport_lo: int,
                      sport_hi: int) -> Flows:
        return self._draw(rng, n, 0, self._services_admitted,
                          sport_lo, sport_hi)

    def denied_flows(self, rng, n: int, sport_lo: int,
                     sport_hi: int) -> Flows:
        """To a prefix no document admits, or cut out by ``except``; to a
        frontend of a service that no document admits."""
        return self._draw(rng, n, 1, self._services_denied,
                          sport_lo, sport_hi)

    def unknown_flows(self, rng, n: int, sport_lo: int,
                      sport_hi: int) -> Flows:
        """To an address under no prefix of the ipcache."""
        return self._draw(rng, n, 2, np.zeros((0,), np.int64),
                          sport_lo, sport_hi)


def _first_distinct(values: np.ndarray, n: int) -> np.ndarray:
    """The first ``n`` distinct values, in the order they stand."""
    _, first = np.unique(values, return_index=True)
    return values[np.sort(first)][:n]


def build(params: Dict) -> World:
    return World(params)
