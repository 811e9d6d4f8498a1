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
from typing import Dict, List, Tuple

import numpy as np

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


class _TrieBuilder:
    def __init__(self):
        # node 0 is the root; each node is {byte: child_idx} + per-byte value
        self.children: List[Dict[int, int]] = [{}]
        # values[node][b] = (plen_bits, identity_index, packed_provenance)
        self.values: List[Dict[int, Tuple[int, int, int]]] = [{}]

    def _new_node(self) -> int:
        self.children.append({})
        self.values.append({})
        return len(self.children) - 1

    def insert(self, addr_bytes: bytes, plen_bits: int, value: int,
               meta: int = -1) -> None:
        """Insert a prefix of ``plen_bits`` (multiple-of-8 boundary handled by
        expansion: a /12 covers 2^(16-12)=16 byte-values at level 2).
        ``meta`` is the packed provenance stored alongside the value — the
        winner of a cell carries both, so value and provenance can never
        name different prefixes."""
        node = 0
        full_bytes, rem_bits = divmod(plen_bits, 8)
        for level in range(full_bytes):
            b = addr_bytes[level]
            if level == full_bytes - 1 and rem_bits == 0:
                old = self.values[node].get(b)
                if old is None or old[0] <= plen_bits:
                    self.values[node][b] = (plen_bits, value, meta)
                return
            child = self.children[node].get(b)
            if child is None:
                child = self._new_node()
                self.children[node][b] = child
            node = child
        # partial byte: expand the remaining bits over the byte range
        b0 = addr_bytes[full_bytes] & (0xFF << (8 - rem_bits)) if rem_bits else 0
        span = 1 << (8 - rem_bits) if rem_bits else 256
        for b in range(b0, b0 + span):
            old = self.values[node].get(b)
            if old is None or old[0] <= plen_bits:
                self.values[node][b] = (plen_bits, value, meta)

    def to_array(self) -> np.ndarray:
        n = len(self.children)
        arr = np.full((n + 1, 256, 3), -1, dtype=np.int32)  # +1 dead node
        for idx in range(n):
            for b, child in self.children[idx].items():
                arr[idx, b, 0] = child
            for b, (_plen, value, meta) in self.values[idx].items():
                arr[idx, b, 1] = value
                arr[idx, b, 2] = meta
        return arr

    @property
    def dead_node(self) -> int:
        return len(self.children)


def build_lpm(ipcache_entries: Dict[str, int],
              identity_index: Dict[int, int],
              default_index: int) -> LPMTables:
    """Build trie tensors from an ipcache snapshot.

    ``identity_index`` maps identity id → dense index (the LPM leaf payload);
    entries referencing unknown identities raise (the compiler must be handed
    a consistent snapshot). Prefix slots are assigned in sorted canonical
    order — deterministic for any snapshot content, independent of the
    ipcache dict's insertion history.
    """
    b4, b6 = _TrieBuilder(), _TrieBuilder()
    prefixes = tuple(sorted(ipcache_entries))
    pfx_slot_of = {p: s for s, p in enumerate(prefixes)}
    for prefix in prefixes:
        ident = ipcache_entries[prefix]
        addr16, plen, is_v6 = parse_prefix(prefix)
        idx = identity_index[ident]
        meta = pack_pfx(pfx_slot_of[prefix], plen)
        if is_v6:
            b6.insert(addr16, plen, idx, meta)
        else:
            # v4: trie over the last 4 bytes; /96+p → p bits here
            b4.insert(addr16[12:], plen - 96, idx, meta)
    return LPMTables(v4_nodes=b4.to_array(), v6_nodes=b6.to_array(),
                     default_index=default_index,
                     prefixes=prefixes, pfx_slot_of=pfx_slot_of)


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
