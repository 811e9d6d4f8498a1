"""World ``dfz``: one dual-stack endpoint whose flows all **leave** it,
behind a whole routing table of both families — the Internet's default-free
zone, of which BASELINE config 3's "100k CIDR prefixes (BGP full-table
slice)" (``worlds/cidrsvc.py``) is a slice. The egress or border node of a
dual-stack cluster: its pods' connections out are judged by origin network
(``toCIDR`` / ``toCIDRSet`` lists made from BGP data), about four in ten of
them over IPv6.

No collector's dump is in the repository, so **the table is generated** from
``WORLD_SEED`` by the structure the route collectors publish (RouteViews,
RIPE RIS; the CIDR Report's counts and histogram of lengths): so many
prefixes a family, their lengths by a histogram, clustered under allocation
blocks, a share of them more-specifics of a shorter listed prefix. Every such
figure is a parameter, and the configuration lists it under ``assumed``.

Parameters (the configuration file's ``world`` group; the keys
``lpm100k-zipf`` has mean what they mean there, a family each):
    n_v4, n_v6         listed prefixes of each family, upserted into the
                       ipcache in one ``upsert_many``
    v4_length_mix, v6_length_mix
                       {"24": 0.62, ...}: share of each length (normalised
                       here); a v6 length may not pass 64
    v4_blocks          /16s in use, outside ``cidrsvc.KEPT_OCTETS``: every
                       v4 prefix of /16 or longer lies in one, a shorter one
                       holds some
    v6_blocks          /32 allocation blocks, their first sixteen bits from
                       the registries' /12s (``V6_TOPS``); likewise
    nested_share       share of each family drawn inside a shorter listed
                       prefix of the family (others nest by chance: a block
                       holds many)
    identity_plen, v6_identity_plen
                       a listed prefix longer than this carries the CIDR
                       identity of its covering block of this length
    cover_cidrs        CIDRs admitted as they stand, of either family
    cidr_sets, excepts_each, admit_listed
                       as ``cidrsvc``, **a family**: ``toCIDRSet`` documents
                       on listed prefixes that hold others, that many of
                       those cut out again; single-prefix ``toCIDR``
                       documents, nested ones whose parent nothing admits
                       first
    services           as ``cidrsvc`` (v4 frontends and backends)
    pool, pool_split   destinations, and the [allowed, denied, unknown]
                       shares of them; each class's pool is split between
                       the families by ``v6_share``
    zipf_s             a flow's destination is drawn by rank within its
                       class and family, p(rank) ∝ (rank + 1)**-zipf_s
    service_share      of the allowed v4 flows go to a frontend
    v6_share           of the flows of every class are IPv6

**The plain reference** is containment on 128 bits, with numpy, from the
prefix lists and the rule parameters alone; nothing of the program is
imported outside ``load`` and ``services``. An address is two uint64
halves; for each length of a family, longest first, the address is masked
and looked up among that length's prefixes (``Prefixes``). **A family never
matches the other's prefixes**: a v4 flow is looked up among the v4 prefixes
by its 32 bits, a v6 flow among the v6 ones by its 128, whatever they are (a
v4-mapped address in a v6 frame lies under no v6 prefix here; upstream keeps
two maps). A cell is admitted as ``cidrsvc`` says: some document's CIDR
holds the prefix's *identity* prefix and none of its ``except`` CIDRs does;
a frontend's flow has its service's cell. ``prefix_text`` states each
flow's longest prefix **by its text**, which is what the program's
``lpm_prefix`` provenance (slot → text, length) is held to. Refusals carry
130 throughout.

Every world built here holds nested prefixes with opposite verdicts, both
ways, **in each family**, and each class's heaviest pool ranks of each
family lie in such prefixes (``build`` raises otherwise).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.frames import PROTO_TCP, Flows
from benchmarks.worlds.cidrsvc import (BACKEND_NET, BE_PORT_BASE, EP_ID,
                                       EP_V4, FE_PORT_BASE, KEPT_OCTETS,
                                       NAMESPACE, VIP_NET, WORLD_SEED)

EP_V6 = "fd00::10"
EP_V6_WORDS = (0xFD000000, 0, 0, 0x10)
#: the first sixteen bits a routed v6 prefix has: 2001::/16 and the five
#: registries' /12s (APNIC 2400, ARIN 2600, LACNIC 2800, RIPE 2a00,
#: AFRINIC 2c00), a /16 of each /12's first sixteen
V6_TOPS = np.array([0x2001] + [top + i for top in (0x2400, 0x2600, 0x2800,
                                                   0x2A00, 0x2C00)
                               for i in range(256)], np.uint64)
U64 = np.uint64
LOW32 = U64(0xFFFFFFFF)
#: _TOP[p]: the top p bits of a 64-bit word
_TOP = np.array([((1 << 64) - 1) ^ ((1 << (64 - p)) - 1) for p in range(65)],
                np.uint64)

# An address is (hi, lo), two uint64. A v6 address is its 128 bits. A v4
# address a is (a << 32, 0), so that a v4 /p is the top p bits of ``hi``
# as a v6 /p is: one arithmetic for both, and two sets of prefixes.


def _top(plen) -> np.ndarray:
    return _TOP[np.asarray(plen, np.int64)]


def _parse(cidr: str) -> Tuple[bool, int, int]:
    """→ (is_v6, hi, plen) of a cover CIDR (no v6 cover is longer than
    /64: its ``lo`` is 0)."""
    addr, plen = cidr.split("/")
    plen = int(plen)
    if ":" not in addr:
        a, b, c, d = (int(x) for x in addr.split("."))
        hi = ((a << 24) | (b << 16) | (c << 8) | d) << 32
        return False, hi & int(_TOP[plen]), plen
    head, _, tail = addr.partition("::")
    groups = [int(g, 16) for g in head.split(":") if g]
    tail = [int(g, 16) for g in tail.split(":") if g]
    groups += [0] * (8 - len(groups) - len(tail)) + tail
    if plen > 64 or any(groups[4:]):
        raise ValueError(f"{cidr}: a v6 CIDR here is /64 or shorter")
    hi = (groups[0] << 48) | (groups[1] << 32) | (groups[2] << 16) | groups[3]
    return True, hi & int(_TOP[plen]), plen


def _dotted(addr: int) -> str:
    return f"{addr >> 24}.{(addr >> 16) & 255}.{(addr >> 8) & 255}." \
           f"{addr & 255}"


def text_of(is_v6: bool, hi: int, plen: int) -> str:
    """The canonical text of a prefix whose ``lo`` is 0. A v6 one: its
    last four groups are 0 and its first is not, so the run of zeros that
    ``::`` stands for is the trailing one."""
    if not is_v6:
        return f"{_dotted(hi >> 32)}/{plen}"
    groups = [(hi >> s) & 0xFFFF for s in (48, 32, 16, 0)]
    while groups[-1] == 0:
        groups.pop()
    return ":".join(f"{g:x}" for g in groups) + f"::/{plen}"


class Prefixes:
    """A set of distinct prefixes of one family, (hi, lo, plen), looked up
    by containment on 128 bits."""

    def __init__(self, hi, lo, plen):
        self.hi = np.asarray(hi, np.uint64)
        self.lo = np.asarray(lo, np.uint64)
        self.plen = np.asarray(plen, np.int64)
        self.size = self.hi.size
        # for each length, longest first: its prefixes sorted by (hi, lo),
        # and the index each has in the set
        self._by_len = []
        for length in sorted(set(self.plen.tolist()), reverse=True):
            idx = np.nonzero(self.plen == length)[0]
            order = np.lexsort((self.lo[idx], self.hi[idx]))
            self._by_len.append((length, self.hi[idx][order],
                                 self.lo[idx][order], idx[order]))

    def longest(self, hi, lo=None, shorter_than=None) -> np.ndarray:
        """Index of the longest prefix holding each address, -1 for none.
        ``lo`` None: all 0. ``shorter_than`` [n]: only prefixes shorter
        than that count."""
        hi = np.asarray(hi, np.uint64)
        lo = np.zeros(hi.shape, np.uint64) if lo is None \
            else np.asarray(lo, np.uint64)
        # sorted once: a masked address keeps its place, and a search for
        # sorted keys walks the table once
        order = np.argsort(hi, kind="stable")
        hi, lo = hi[order], lo[order]
        if shorter_than is not None:
            shorter_than = np.asarray(shorter_than)[order]
        found = np.full(hi.shape, -1, np.int64)
        for length, s_hi, s_lo, index in self._by_len:
            if length <= 64:
                # a prefix this short has no bit in lo: hi alone decides
                masked = hi & _TOP[length]
                at = np.minimum(np.searchsorted(s_hi, masked),
                                s_hi.size - 1)
                hit = s_hi[at] == masked
            else:
                at, hit = self._among_equal_hi(
                    s_hi, s_lo, hi, lo & _TOP[length - 64])
            hit &= found < 0
            if shorter_than is not None:
                hit &= length < shorter_than
            found[hit] = index[at[hit]]
        out = np.empty_like(found)
        out[order] = found
        return out

    @staticmethod
    def _among_equal_hi(s_hi, s_lo, hi, masked_lo):
        """A length over 64: the whole of hi has to be a prefix's, and the
        masked lo is then looked up among the prefixes of that hi, row by
        row (few addresses get this far)."""
        left = np.searchsorted(s_hi, hi, side="left")
        right = np.searchsorted(s_hi, hi, side="right")
        at = np.zeros(hi.shape, np.int64)
        hit = np.zeros(hi.shape, bool)
        for i in np.nonzero(right > left)[0].tolist():
            j = left[i] + int(np.searchsorted(s_lo[left[i]:right[i]],
                                              masked_lo[i]))
            if j < right[i] and s_lo[j] == masked_lo[i]:
                at[i], hit[i] = j, True
        return at, hit


def _held_by(cidr: Tuple[int, int], hi: np.ndarray,
             plen: np.ndarray) -> np.ndarray:
    """[n] bool: which of the prefixes ``hi/plen`` the CIDR (hi, plen)
    holds."""
    return (plen >= cidr[1]) & ((hi & _TOP[cidr[1]]) == U64(cidr[0]))


def _first_distinct(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Indices of the first of every distinct pair (a, b), in the order
    they stand."""
    order = np.lexsort((b, a))                # stable: ties keep their order
    x, y = a[order], b[order]
    new = np.ones(x.shape, bool)
    new[1:] = (x[1:] != x[:-1]) | (y[1:] != y[:-1])
    return np.sort(order[new])


def _distinct(values: np.ndarray) -> np.ndarray:
    """The distinct values, in the order they first stand."""
    _, first = np.unique(values, return_index=True)
    return values[np.sort(first)]


class Family:
    """One family's half of the deployment: its listed prefixes, the
    documents on them, its ipcache, what admits each entry, its pools."""

    def __init__(self, is_v6: bool, params: Dict, rng,
                 backends: Sequence[int] = ()):
        self.is_v6 = is_v6
        key = "v6" if is_v6 else "v4"
        self.n = int(params[f"n_{key}"])
        mix = params[f"{key}_length_mix"]
        self.lengths = np.array(sorted(int(k) for k in mix))
        share = np.array([float(mix[str(k)]) for k in self.lengths])
        self.share = share / share.sum()
        self.block_len = 32 if is_v6 else 16
        self.identity_plen = int(params["v6_identity_plen" if is_v6
                                        else "identity_plen"])
        if self.lengths[-1] > (64 if is_v6 else 32) or self.lengths[0] < 1:
            raise ValueError(f"{key}_length_mix: lengths of 1 to "
                             f"{64 if is_v6 else 32}")
        #: what a random 64-bit word may set of an address's hi
        self.host = U64(0xFFFFFFFFFFFFFFFF) if is_v6 else ~LOW32
        self.blocks = self._draw_blocks(int(params[f"{key}_blocks"]), rng)
        listed = self._draw_listed(float(params["nested_share"]), rng)
        covers = [(hi, plen) for v6, hi, plen in
                  map(_parse, params["cover_cidrs"]) if v6 == is_v6]
        self.docs = self._choose_documents(params, listed, covers, rng)
        named = sorted({p for cidr, excepts in self.docs
                        for p in (cidr, *excepts)})
        is_named = np.zeros((self.n,), bool)
        for hi, plen in named:
            is_named |= (listed[0] == U64(hi)) & (listed[1] == plen)
        #: what load() upserts itself; the program puts the rest in
        self.listed_hi, self.listed_plen = (x[~is_named] for x in listed)
        n_listed = self.listed_hi.size
        more = named + [(int(b) << 32, 32) for b in backends]
        hi = np.concatenate([self.listed_hi,
                             np.array([p[0] for p in more], np.uint64)])
        plen = np.concatenate([self.listed_plen,
                               np.array([p[1] for p in more], np.int64)])
        self.ipcache = Prefixes(hi, np.zeros(hi.shape, np.uint64), plen)
        # the prefix each entry's identity is labelled for: a listed one's
        # may be its covering block's, every other is the entry's own
        q_plen = np.where(np.arange(hi.size) < n_listed,
                          np.minimum(plen, self.identity_plen), plen)
        q_hi = hi & _top(q_plen)
        self.cover = np.zeros((hi.size,), np.uint8)
        for cidr, excepts in self.docs:
            admits = _held_by(cidr, q_hi, q_plen)
            for x in excepts:
                admits &= ~_held_by(x, q_hi, q_plen)
            self.cover += admits
        if backends:
            self.cover[-len(backends):] += 1      # its toServices document

    # -- the table --------------------------------------------------------------
    def _draw_blocks(self, n_blocks: int, rng) -> np.ndarray:
        """[n_blocks] hi of distinct allocation blocks."""
        if self.is_v6:
            top = V6_TOPS[rng.integers(0, V6_TOPS.size, 2 * n_blocks + 64)]
            cand = (top << U64(48)) | (rng.integers(
                0, 1 << 16, top.size).astype(np.uint64) << U64(32))
        else:
            octets = np.array([o for o in range(1, 223)
                               if o not in KEPT_OCTETS], np.uint64)
            cand = rng.permutation(
                ((octets[:, None] << U64(8)) | np.arange(256, dtype=np.uint64)
                 [None, :]).ravel()) << U64(48)
        blocks = _distinct(cand)[:n_blocks]
        if blocks.size < n_blocks:
            raise ValueError(f"only {blocks.size} blocks of {n_blocks}")
        return blocks

    def _inside(self, base_hi, base_plen, plen, rng) -> np.ndarray:
        """A prefix of ``plen`` bits drawn inside each ``base``, or the
        base's own leading bits where ``plen`` is the shorter."""
        rand = rng.integers(0, 1 << 64, base_hi.shape, dtype=np.uint64)
        return (base_hi | (rand & ~_top(base_plen))) & _top(plen)

    def _draw_listed(self, nested_share: float, rng
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """→ (hi, plen) of the ``n`` listed prefixes: of each length its
        share of the ones not nested, each in (or, if shorter, around) a
        block drawn evenly; then the nested ones, each inside a shorter
        listed prefix, its length by the mix's shares among the longer
        ones."""
        n_nested = int(round(nested_share * self.n))
        n_top = self.n - n_nested
        count = np.floor(self.share * n_top).astype(np.int64)
        count[np.argmax(count)] += n_top - int(count.sum())
        hi, plen = [], []
        for length, want in zip(self.lengths.tolist(), count.tolist()):
            m = 2 * want + 64
            block = self.blocks[rng.integers(0, self.blocks.size, m)]
            cand = _distinct(self._inside(block, self.block_len, length,
                                          rng))[:want]
            if cand.size < want:
                raise ValueError(f"only {cand.size} distinct /{length} of "
                                 f"{want}")
            hi.append(cand)
            plen.append(np.full((want,), length, np.int64))
        order = rng.permutation(n_top)
        top, top_len = np.concatenate(hi)[order], np.concatenate(plen)[order]
        parents = np.nonzero(top_len < self.lengths[-1])[0]
        if n_nested and not parents.size:
            raise ValueError("no listed prefix is short enough to hold one")
        par = parents[rng.integers(0, max(1, parents.size), 2 * n_nested)]
        # a length longer than the parent's, by the mix's shares among those
        u = 1.0 - rng.random(par.size)                     # (0, 1]
        longer = self.lengths[None, :] > top_len[par][:, None]
        w = np.where(longer, self.share[None, :], 0.0)
        cdf = np.cumsum(w, axis=1) / w.sum(axis=1, keepdims=True)
        sub_len = self.lengths[np.minimum((u[:, None] > cdf).sum(axis=1),
                                          self.lengths.size - 1)]
        # inside the parent; inside one of the blocks it holds, where the
        # parent is shorter than a block
        base, base_len = top[par], top_len[par]
        short = np.nonzero(base_len < self.block_len)[0]
        if short.size:
            blocks = np.sort(self.blocks)
            first = np.searchsorted(blocks, base[short])
            last = np.searchsorted(blocks, base[short]
                                   | ~_top(base_len[short]), side="right")
            base[short] = blocks[first + (rng.random(short.size)
                                          * (last - first)).astype(np.int64)]
            base_len[short] = self.block_len
        sub = self._inside(base, base_len, sub_len, rng)
        all_hi, all_len = np.concatenate([top, sub]), \
            np.concatenate([top_len, sub_len])
        keep = _first_distinct(all_hi, all_len)[:self.n]
        if keep.size < self.n:
            raise ValueError(f"only {keep.size} distinct prefixes of "
                             f"{self.n}")
        return all_hi[keep], all_len[keep]

    def _choose_documents(self, params: Dict, listed, covers, rng):
        """Which prefixes the documents admit, as ``cidrsvc`` chooses them:
        the cover CIDRs; then ``cidr_sets`` listed prefixes that hold
        others, some of those cut out; then ``admit_listed`` single
        prefixes, nested ones whose parent nothing so far admits first. →
        [((hi, plen), excepts)]."""
        hi, plen = listed
        pre = Prefixes(hi, np.zeros(hi.shape, np.uint64), plen)
        docs = [(c, ()) for c in covers]
        parent = pre.longest(hi, shorter_than=plen)
        admitted = np.zeros((self.n,), bool)
        for cidr, _x in docs:
            admitted |= _held_by(cidr, hi, plen)
        holders = np.unique(parent[parent >= 0])
        holders = holders[np.argsort(admitted[holders], kind="stable")]
        x_each = int(params["excepts_each"])
        free = np.ones((self.n,), bool)

        def pair(i):
            return int(hi[i]), int(plen[i])
        for h in holders[:int(params["cidr_sets"])].tolist():
            cut = np.nonzero(parent == h)[0][:x_each]
            docs.append((pair(h), tuple(pair(c) for c in cut.tolist())))
            admitted[h] = True
            free[cut] = False
        free &= ~admitted
        nested = free & (parent >= 0) & ~admitted[np.maximum(parent, 0)]
        order = rng.permutation(self.n)
        order = order[np.argsort(~nested[order], kind="stable")]
        single = order[free[order]][:int(params["admit_listed"])]
        docs += [(pair(i), ()) for i in single.tolist()]
        return docs

    # -- the pools ----------------------------------------------------------------
    def address_in(self, of: np.ndarray, rng) -> Tuple[np.ndarray,
                                                       np.ndarray]:
        """An address (hi, lo) drawn inside each entry ``of`` of the
        ipcache."""
        e = self.ipcache
        rand = rng.integers(0, 1 << 64, of.shape, dtype=np.uint64)
        hi = e.hi[of] | (rand & ~_top(e.plen[of]) & self.host)
        lo = rng.integers(0, 1 << 64, of.shape, dtype=np.uint64) \
            if self.is_v6 else np.zeros(of.shape, np.uint64)
        return hi, lo

    def strays(self, n: int, rng) -> Tuple[np.ndarray, np.ndarray]:
        """Addresses of routed space, drawn evenly, whatever holds them."""
        if self.is_v6:
            hi = (V6_TOPS[rng.integers(0, V6_TOPS.size, n)] << U64(48)) \
                | (rng.integers(0, 1 << 48, n).astype(np.uint64))
            return hi, rng.integers(0, 1 << 64, n, dtype=np.uint64)
        a = rng.integers(0x01000000, 0xDF000000, n)
        a = a[~np.isin(a >> 24, KEPT_OCTETS)].astype(np.uint64)
        return a << U64(32), np.zeros(a.shape, np.uint64)

    def draw_pools(self, want: Sequence[int], rng) -> List[Tuple]:
        """The family's three pools [(hi, lo)], each in rank order.
        Candidates are drawn inside the entries of the ipcache in turn (so
        the heavy ranks spread over the documents), those whose parent has
        the opposite verdict first, and kept where the entry is the longest
        prefix that holds them."""
        e = self.ipcache
        allowed = self.cover > 0
        parent = e.longest(e.hi, shorter_than=e.plen)
        contrast = (parent >= 0) & (allowed != allowed[np.maximum(parent, 0)])
        inside = np.nonzero(e.plen < (64 if self.is_v6 else 32))[0]
        turn = inside[rng.permutation(inside.size)]
        turn = turn[np.argsort(~contrast[turn], kind="stable")]
        pools = []
        for kind, n in zip((True, False), want[:2]):
            mine = turn[allowed[turn] == kind]
            if not (contrast[mine]).any():
                raise ValueError(
                    f"{'v6' if self.is_v6 else 'v4'}: the parameters leave "
                    f"no {'admitted' if kind else 'refused'} prefix inside "
                    f"one of the opposite verdict")
            of = np.tile(mine, int(1.5 * n / max(1, mine.size)) + 2)
            hi, lo = self.address_in(of, rng)
            ok = np.nonzero(e.longest(hi, lo) == of)[0]
            keep = ok[_first_distinct(hi[ok], lo[ok])[:n]]
            pools.append((hi[keep], lo[keep]))
        hi, lo = self.strays(8 * want[2] + 64, rng)
        ok = np.nonzero(e.longest(hi, lo) < 0)[0]
        keep = ok[_first_distinct(hi[ok], lo[ok])[:want[2]]]
        pools.append((hi[keep], lo[keep]))
        for name, pool, n in zip(("allowed", "denied", "unknown"), pools,
                                 want):
            if pool[0].size < n:
                raise ValueError(
                    f"{'v6' if self.is_v6 else 'v4'}: only {pool[0].size} "
                    f"{name} destinations of {n} wanted")
        return pools

    def entry_text(self, i: int) -> str:
        e = self.ipcache
        return text_of(self.is_v6, int(e.hi[i]), int(e.plen[i]))

    def listed(self) -> List[Tuple[str, str]]:
        """(prefix, the prefix its CIDR identity is labelled for) of every
        listed prefix no document names."""
        q = np.minimum(self.listed_plen, self.identity_plen)
        q_hi = self.listed_hi & _top(q)
        return [(text_of(self.is_v6, h, p), text_of(self.is_v6, qh, qp))
                for h, p, qh, qp in zip(self.listed_hi.tolist(),
                                        self.listed_plen.tolist(),
                                        q_hi.tolist(), q.tolist())]


class World:
    ep_id = EP_ID
    ep_v4 = EP_V4
    ep_v6_words = EP_V6_WORDS

    def __init__(self, params: Dict):
        rng = np.random.default_rng(WORLD_SEED)
        self.zipf_s = float(params["zipf_s"])
        self.service_share = float(params["service_share"])
        self.v6_share = float(params["v6_share"])
        if not 0.0 < self.v6_share < 1.0:
            raise ValueError("v6_share: a share of the flows, with both "
                             "families left")
        svc = params["services"]
        self.n_services, self.n_named = int(svc["count"]), int(svc["named"])
        self.backends_each = int(svc["backends_each"])
        self.frontends_each = int(svc["frontends_each"])
        if not 0 <= self.n_named <= self.n_services \
                or (self.n_services and min(self.backends_each,
                                            self.frontends_each) < 1):
            raise ValueError("services: named <= count, and a service has "
                             "a frontend and a backend at least")
        backends = [BACKEND_NET + (s << 8) + b + 1
                    for s in range(self.n_named)
                    for b in range(self.backends_each)]
        self.v4 = Family(False, params, rng, backends)
        self.v6 = Family(True, params, rng)
        self.n4, self.n6 = self.v4.ipcache.size, self.v6.ipcache.size
        n = self.n4 + self.n6
        # a cell for every entry of the v4 ipcache, then of the v6 one, then
        # one for every service
        cover = np.concatenate([self.v4.cover, self.v6.cover,
                                np.zeros((self.n_services,), np.uint8)])
        # a frontend's flows are judged at the backend's address: a named
        # service's by its document, any other's by whatever holds them
        for s in range(self.n_services):
            at = self.v4.ipcache.longest(
                (U64(BACKEND_NET + (s << 8) + 1)
                 + np.arange(self.backends_each, dtype=np.uint64))
                << U64(32))
            held = np.where(at >= 0, self.v4.cover[np.maximum(at, 0)], 0)
            if (held != held[0]).any():
                raise ValueError(f"service {s}'s backends lie under "
                                 f"prefixes that differ in what admits them")
            cover[n + s] = held[0]
        self._cover = cover
        self._services_admitted = np.nonzero(cover[n:] > 0)[0]
        self._services_denied = np.nonzero(cover[n:] == 0)[0]
        want = [int(round(float(s) * int(params["pool"])))
                for s in params["pool_split"]]
        want6 = [int(round(self.v6_share * w)) for w in want]
        self._pools = {False: self.v4.draw_pools(
            [w - w6 for w, w6 in zip(want, want6)], rng),
            True: self.v6.draw_pools(want6, rng)}
        self._pool_cdf = {fam: [self._rank_cdf(p[0].size) for p in pools]
                          for fam, pools in self._pools.items()}

    def _rank_cdf(self, n: int) -> np.ndarray:
        """Zipf over ranks 1..n, for drawing by inverse CDF."""
        cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64)
                        ** -self.zipf_s)
        return cdf / cdf[-1]

    def pool(self, is_v6: bool, kind: int) -> Tuple[np.ndarray, np.ndarray]:
        """(hi, lo) of one family's pool of one class (0 allowed, 1
        denied, 2 unknown), in rank order."""
        return self._pools[is_v6][kind]

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
        listed prefix of both families that no document names."""
        return self.v4.listed() + self.v6.listed()

    def policy_docs(self) -> List[Dict]:
        select = {"matchLabels": {"app": "web"}}
        docs = []
        for fam in (self.v4, self.v6):
            for (hi, plen), excepts in fam.docs:
                cidr = text_of(fam.is_v6, hi, plen)
                to = {"toCIDRSet": [{"cidr": cidr, "except": [
                    text_of(fam.is_v6, *x) for x in excepts]}]} \
                    if excepts else {"toCIDR": [cidr]}
                docs.append({"endpointSelector": select, "egress": [to]})
        for name in self._service_names()[:self.n_named]:
            docs.append({"endpointSelector": select, "egress": [{
                "toServices": [{"k8sService": {
                    "serviceName": name, "namespace": NAMESPACE}}]}]})
        return docs

    def load(self, eng) -> int:
        """The endpoint with both its addresses; the routing table as a
        node takes one: an identity a covering block, then every listed
        prefix in one ``upsert_many``; the services; the rule documents.
        Returns the revision to wait for."""
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10", EP_V6),
                         ep_id=EP_ID)
        block_id: Dict[str, int] = {}
        entries = []
        for prefix, labelled_for in self.listed():
            ident = block_id.get(labelled_for)
            if ident is None:
                ident = block_id[labelled_for] = \
                    eng.ctx.allocator.allocate_cidr(labelled_for).id
            entries.append((prefix, ident))
        eng.ctx.ipcache.upsert_many(entries)
        for svc in self.services():
            eng.upsert_service(svc)
        return eng.apply_policy(self.policy_docs())

    def register(self, shim) -> None:
        shim.register_endpoint("192.168.0.10", EP_ID)
        shim.register_endpoint(EP_V6, EP_ID)

    # -- the plain reference --------------------------------------------------
    def table(self):
        """(allowed [cells] bool, cover [cells] uint8): a cell for every
        entry of the v4 ipcache, then of the v6 one, then one for every
        service; which cells some document admits, and how many admit
        each."""
        return self._cover > 0, self._cover

    @staticmethod
    def halves(flows: Flows) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                      np.ndarray]:
        """→ (v4 [n] bool, v6 [n] bool, hi, lo) of each flow's peer. A flow
        is v4 where its frame is and its words are v4-mapped; its 32 bits
        then stand at the top of ``hi``."""
        w = flows["src"].astype(np.uint64)
        v6 = flows["is_v6"].astype(bool)
        v4 = ~v6 & (w[:, 0] == 0) & (w[:, 1] == 0) & (w[:, 2] == 0xFFFF)
        hi = np.where(v6, (w[:, 0] << U64(32)) | w[:, 1], w[:, 3] << U64(32))
        lo = np.where(v6, (w[:, 2] << U64(32)) | w[:, 3], U64(0))
        return v4, v6, hi, lo

    def cells(self, flows: Flows) -> np.ndarray:
        """Each flow's cell: its frontend's service, else the longest
        prefix **of its own family** holding its destination, -1 where none
        does."""
        v4, v6, hi, lo = self.halves(flows)
        cell = np.full(hi.shape, -1, np.int64)
        if v4.any():
            cell[v4] = self.v4.ipcache.longest(hi[v4])
        if v6.any():
            at = self.v6.ipcache.longest(hi[v6], lo[v6])
            cell[v6] = np.where(at >= 0, self.n4 + at, -1)
        s = (hi >> U64(32)).astype(np.int64) - VIP_NET
        f = flows["dport"].astype(np.int64) - FE_PORT_BASE
        front = v4 & (flows["proto"] == PROTO_TCP) \
            & (s >= 0) & (s < self.n_services) \
            & (f >= 0) & (f < self.frontends_each)
        return np.where(front, self.n4 + self.n6 + s, cell)

    def cell_text(self, cell: int) -> Optional[str]:
        """The text of the ipcache entry a cell stands for; None for no
        cell and for a service's (its flows walk to a backend's /32,
        whichever the table picks)."""
        if cell < 0 or cell >= self.n4 + self.n6:
            return None
        return self.v4.entry_text(cell) if cell < self.n4 \
            else self.v6.entry_text(cell - self.n4)

    def prefix_text(self, flows: Flows) -> List[Optional[str]]:
        """Each flow's longest prefix, by its text."""
        return [self.cell_text(c) for c in self.cells(flows).tolist()]

    # -- flows ----------------------------------------------------------------
    def _draw(self, rng, n: int, kind: int, services: np.ndarray,
              sport_lo: int, sport_hi: int) -> Flows:
        """``n`` flows of class ``kind``: ``v6_share`` of them v6; the
        destination by Zipf rank from the family's pool; ``service_share``
        of the v4 ones to a frontend of one of ``services`` instead."""
        is_v6 = rng.random(n) < self.v6_share
        u = rng.random(n)
        hi, lo = np.empty((n,), np.uint64), np.empty((n,), np.uint64)
        for fam in (False, True):
            rows = np.nonzero(is_v6 == fam)[0]
            p_hi, p_lo = self._pools[fam][kind]
            rank = np.minimum(np.searchsorted(self._pool_cdf[fam][kind],
                                              u[rows]), p_hi.size - 1)
            hi[rows], lo[rows] = p_hi[rank], p_lo[rank]
        dport = rng.integers(1, 65535, n)
        if services.size:
            to_svc = ~is_v6 & (rng.random(n) < self.service_share)
            s = services[rng.integers(0, services.size, n)]
            f = rng.integers(0, self.frontends_each, n)
            hi = np.where(to_svc, (VIP_NET + s).astype(np.uint64) << U64(32),
                          hi)
            dport = np.where(to_svc, FE_PORT_BASE + f, dport)
        return self.flows_to(is_v6, hi, lo,
                             rng.integers(sport_lo, sport_hi, n), dport)

    @staticmethod
    def flows_to(is_v6, hi, lo, sport, dport) -> Flows:
        """TCP flows that leave the endpoint for the peers (hi, lo)."""
        n = hi.shape[0]
        src = np.empty((n, 4), np.uint32)         # the peer
        src[:, 0] = np.where(is_v6, hi >> U64(32), 0)
        src[:, 1] = np.where(is_v6, hi & LOW32, 0)
        src[:, 2] = np.where(is_v6, lo >> U64(32), 0xFFFF)
        src[:, 3] = np.where(is_v6, lo & LOW32, hi >> U64(32))
        return {"src": src,
                "sport": np.asarray(sport).astype(np.int32),
                "dport": np.asarray(dport).astype(np.int32),
                "proto": np.full((n,), PROTO_TCP, np.int32),
                "is_v6": np.asarray(is_v6, bool),
                "egress": np.ones((n,), bool)}

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
        """To an address under no prefix of its family's ipcache."""
        return self._draw(rng, n, 2, np.zeros((0,), np.int64),
                          sport_lo, sport_hi)


def build(params: Dict) -> World:
    return World(params)
