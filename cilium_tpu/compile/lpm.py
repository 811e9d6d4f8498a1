"""ipcache → stride-8 multibit trie tensors (the LPM "map").

Replaces the kernel's LPM_TRIE map (upstream ``pkg/maps/ipcache``; datapath
lookup in ``bpf/lib/eps.h``) with gather-chain tables: one trie per address
family (mirroring upstream's separate v4/v6 maps), stride 8 bits, so an IPv4
lookup is 4 dependent gathers and IPv6 is 16 — cost independent of prefix
count (SURVEY.md §5: "LPM over 100k prefixes as multi-level stride tables").

Node layout, host form: ``nodes[n, 256, 3] int32`` —
  ``nodes[x, b, 0]`` = child node index, or -1 (no child);
  ``nodes[x, b, 1]`` = identity *index* decided at this byte, or -1 (inherit
  the best match seen so far along the path);
  ``nodes[x, b, 2]`` = packed match provenance ``(prefix_slot << 8) | plen``
  for the prefix that decided this value, or -1. Prefix slots enumerate the
  snapshot's canonical prefixes in sorted order (``LPMTables.prefixes``), so
  a verdict can name the exact ipcache entry that won the walk — the
  match-provenance column the observer/flowlog surfaces (ISSUE 11).
Placed form: the same entries as one 2-D table ``[n * 256, 3]``, entry
``x * 256 + b`` (``LPMTables.v4_placed`` / ``v6_placed``: a view of the host
form, nothing is copied). It is what ``PolicySnapshot.tensors()`` hands out
as ``lpm_v4`` / ``lpm_v6`` and so what the device holds; the host form stays
what ``lpm_lookup_host``, ``nbytes`` and the gauges read. Why two: a TPU
lays a 32-bit ``[rows, 3]`` table out in tiles of 4 x 128 with the rows
minor, which is the form a gather of whole entries reads, so the walk
(kernels/lpm.py) reads the trie where it lies; a 3-D ``[n, 256, 3]`` array
it lays out plane-major, and the compiled program then re-laid the whole
trie before the first level of every batch (445 us for 122 MB beside 48 us
of walking: ledger, PR 42 and 43). The tiles pad an entry's three words to
four, so a TPU holds 4/3 of ``nbytes``.
A sentinel "dead" node of all -1 lets the fixed-depth device loop run to full
depth without data-dependent control flow: after a path ends, the gather
chain idles in the dead node. Misses resolve to ``default_index``
(reserved:world) with provenance -1, matching the datapath's WORLD_ID
fallback.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Tuple

import numpy as np

from cilium_tpu.observe.trace import LPM_BUILD_SPAN, active as active_trace
from cilium_tpu.utils.ip import parse_prefix

V4_LEVELS = 4     # bytes 12..15 of the v4-mapped address
V6_LEVELS = 16

#: lpm_prefix packing: low 8 bits = canonical prefix length (0..128), the
#: rest = prefix slot. One shared constant so the kernels, the oracle and
#: the observer un-pack identically.
PFX_LEN_BITS = 8
PFX_LEN_MASK = (1 << PFX_LEN_BITS) - 1


def pack_pfx(slot: int, plen: int) -> int:
    return (slot << PFX_LEN_BITS) | (plen & PFX_LEN_MASK)


def unpack_pfx(packed: int) -> Tuple[int, int]:
    """packed lpm_prefix → (slot, plen); (-1, -1) for the miss sentinel."""
    if packed < 0:
        return -1, -1
    return packed >> PFX_LEN_BITS, packed & PFX_LEN_MASK


@dataclass(frozen=True)
class LPMTables:
    """Host-built trie tensors for one snapshot."""
    v4_nodes: np.ndarray   # [n4, 256, 3] int32
    v6_nodes: np.ndarray   # [n6, 256, 3] int32
    default_index: int     # identity index for LPM miss (world)
    # slot → canonical prefix string (sorted enumeration of the compiled
    # ipcache); the inverse map resolves oracle/observer lookups to the
    # same slot ids the device trie carries in its provenance plane
    prefixes: Tuple[str, ...] = ()
    pfx_slot_of: Dict[str, int] = field(default_factory=dict)

    @property
    def v4_placed(self) -> np.ndarray:
        """[n4 * 256, 3] int32: the placed form, a view of ``v4_nodes``."""
        return self.v4_nodes.reshape(-1, 3)

    @property
    def v6_placed(self) -> np.ndarray:
        """[n6 * 256, 3] int32: the placed form, a view of ``v6_nodes``."""
        return self.v6_nodes.reshape(-1, 3)

    @property
    def nbytes(self) -> int:
        return self.v4_nodes.nbytes + self.v6_nodes.nbytes

    def describe(self, packed: int) -> Dict:
        """Un-pack one lpm_prefix provenance value for display."""
        slot, plen = unpack_pfx(int(packed))
        if slot < 0 or slot >= len(self.prefixes):
            return {"slot": -1, "prefix": None, "plen": -1}
        return {"slot": slot, "prefix": self.prefixes[slot], "plen": plen}


def _build_trie(addr: np.ndarray, plen: np.ndarray, value: np.ndarray,
                meta: np.ndarray) -> np.ndarray:
    """One family's trie, a level at a time with numpy. ``addr`` [m, L]
    uint8 network addresses, ``plen`` [m] bits, ``value`` / ``meta`` [m]
    what the winner of a cell carries, the prefixes **in slot order**.

    The array is the one that inserting the prefixes one after the other in
    that order gives (``tests/test_lpm_build.py`` keeps that loop, word for
    word, and holds this to it array for array). A prefix of ``plen`` bits
    decides cells of the node ``depth = (plen - 1) // 8`` bytes down its
    path: the ``2 ** (8 * (depth + 1) - plen)`` byte values it covers there
    (a /12 covers 16 values of its second byte; /0 all 256 of the root),
    and the longest prefix that covers a cell wins it. Nodes are numbered
    as the loop makes them: the root 0, then in the order prefixes first
    need them, a prefix's own from the root down; the dead node last."""
    m = plen.shape[0]
    depth = np.maximum(plen - 1, 0) // 8
    # -- the nodes, a level at a time: a node is its parent and a byte --------
    at = np.zeros((m,), np.int64)       # provisional id of each prefix's node
    parent_of, byte_of, first_of, depth_of = [], [], [], []
    n_prov = 1
    for d in range(1, int(depth.max(initial=0)) + 1):
        sel = np.nonzero(depth >= d)[0]
        key = at[sel] * 256 + addr[sel, d - 1]
        uniq, first, inverse = np.unique(key, return_index=True,
                                         return_inverse=True)
        at[sel] = n_prov + inverse
        parent_of.append(uniq // 256)
        byte_of.append(uniq % 256)
        first_of.append(sel[first])     # sel ascends: the first that needs it
        depth_of.append(np.full(uniq.shape, d, np.int64))
        n_prov += uniq.size
    nodes = np.full((n_prov + 1, 256, 3), -1, dtype=np.int32)  # +1 dead node
    final = np.zeros((n_prov,), np.int64)
    if n_prov > 1:
        parent_of, byte_of, first_of, depth_of = (
            np.concatenate(x) for x in (parent_of, byte_of, first_of,
                                        depth_of))
        final[1 + np.lexsort((depth_of, first_of))] = np.arange(1, n_prov)
        nodes[final[parent_of], byte_of, 0] = final[1:]
    # -- the cells: shorter prefixes first, so that the longest stays ---------
    flat = nodes.reshape(-1, 3)
    covered = plen - 8 * depth          # bits of the node's byte: 0 for /0
    for bits in range(0, 9):
        rows = np.nonzero(covered == bits)[0]
        if not rows.size:
            continue
        span = 1 << (8 - bits)
        b0 = (addr[rows, depth[rows]].astype(np.int64) >> (8 - bits)) \
            << (8 - bits) if bits else np.zeros(rows.shape, np.int64)
        cells = ((final[at[rows]] * 256 + b0)[:, None]
                 + np.arange(span)[None, :]).ravel()
        flat[cells, 1] = np.repeat(value[rows], span)
        flat[cells, 2] = np.repeat(meta[rows], span)
    return nodes


def build_lpm(ipcache_entries: Dict[str, int],
              identity_index: Dict[int, int],
              default_index: int) -> LPMTables:
    """Build trie tensors from an ipcache snapshot.

    ``identity_index`` maps identity id → dense index (the LPM leaf payload);
    entries referencing unknown identities raise (the compiler must be handed
    a consistent snapshot). Prefix slots are assigned in sorted canonical
    order — deterministic for any snapshot content, independent of the
    ipcache dict's insertion history. Recorded as the span
    ``engine.regen.lpm`` of the regeneration it runs in (full build or
    incremental rebuild alike)."""
    tracer, trace_id = active_trace()
    with tracer.span(trace_id, LPM_BUILD_SPAN) as span:
        prefixes = tuple(sorted(ipcache_entries))
        pfx_slot_of = {p: s for s, p in enumerate(prefixes)}
        n = len(prefixes)
        packed, plen = [], np.empty((n,), np.int64)
        value, is_v6 = np.empty((n,), np.int32), np.empty((n,), bool)
        for slot, prefix in enumerate(prefixes):
            addr16, plen[slot], is_v6[slot] = parse_prefix(prefix)
            packed.append(addr16)
            value[slot] = identity_index[ipcache_entries[prefix]]
        addr = np.frombuffer(b"".join(packed), np.uint8).reshape(n, 16)
        meta = pack_pfx(np.arange(n, dtype=np.int64), plen).astype(np.int32)
        # v4: the trie is over the last 4 bytes; /96+p → p bits there
        i4, i6 = np.nonzero(~is_v6)[0], np.nonzero(is_v6)[0]
        tables = LPMTables(
            v4_nodes=_build_trie(addr[i4, 12:], plen[i4] - 96, value[i4],
                                 meta[i4]),
            v6_nodes=_build_trie(addr[i6], plen[i6], value[i6], meta[i6]),
            default_index=default_index,
            prefixes=prefixes, pfx_slot_of=pfx_slot_of)
        span.set(nodes_v4=tables.v4_nodes.shape[0],
                 nodes_v6=tables.v6_nodes.shape[0], prefixes=n)
    return tables


def lpm_lookup_host(tables: LPMTables, addr16: bytes, is_v6: bool) -> int:
    """Host-side reference walk of the trie tensors (for tests; the jnp
    kernel in kernels/lpm.py must agree with this AND with
    model.ipcache.lpm_lookup)."""
    return lpm_lookup_host_prov(tables, addr16, is_v6)[0]


def lpm_lookup_host_prov(tables: LPMTables, addr16: bytes,
                         is_v6: bool) -> Tuple[int, int]:
    """Reference walk returning (identity index, packed lpm_prefix
    provenance) — the host mirror of kernels/lpm.lpm_lookup_prov_batch."""
    nodes = tables.v6_nodes if is_v6 else tables.v4_nodes
    data = addr16 if is_v6 else addr16[12:]
    levels = V6_LEVELS if is_v6 else V4_LEVELS
    node = 0
    dead = nodes.shape[0] - 1
    best = tables.default_index
    best_meta = -1
    for level in range(levels):
        b = data[level]
        child, value, meta = nodes[node, b]
        if value >= 0:
            best = int(value)
            best_meta = int(meta)
        node = int(child) if child >= 0 else dead
    return best, best_meta
