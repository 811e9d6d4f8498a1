"""The LPM tries' builder (``compile/lpm.build_lpm``, vectorised in PR 53:
a level at a time with numpy) held to the loop it replaced, **array for
array**: ``v4_nodes``, ``v6_nodes`` and ``prefixes``. The loop is kept here
word for word (``_TrieBuilder``, one Python dict a node, a prefix inserted
after the other in slot order) as PR 45 kept the Maglev fill's: it is the
reference, and no served program changes while the two agree.

``checked_build_lpm`` is ``build_lpm`` with that comparison inside;
``tests/test_compile.py`` and ``tests/test_lpm_fuzz.py`` build every table
of theirs through it.
"""

from typing import Dict, List, Tuple

import numpy as np
import pytest

from cilium_tpu.compile.lpm import LPMTables, build_lpm, pack_pfx
from cilium_tpu.utils.ip import parse_prefix


# -- the loop, as compile/lpm.py had it until PR 53 ----------------------------
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


def build_lpm_by_insertion(ipcache_entries: Dict[str, int],
                           identity_index: Dict[int, int],
                           default_index: int) -> LPMTables:
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


def checked_build_lpm(ipcache_entries, identity_index, default_index):
    """``build_lpm``, held to the loop on this very table."""
    got = build_lpm(ipcache_entries, identity_index, default_index)
    want = build_lpm_by_insertion(ipcache_entries, identity_index,
                                  default_index)
    for name in ("v4_nodes", "v6_nodes"):
        a, b = getattr(got, name), getattr(want, name)
        assert a.shape == b.shape and a.dtype == b.dtype == np.int32, name
        np.testing.assert_array_equal(a, b, name)
    assert got.prefixes == want.prefixes
    assert got.pfx_slot_of == want.pfx_slot_of
    assert got.default_index == want.default_index
    return got


# -- tables of its own ---------------------------------------------------------
def text4(addr: int, plen: int) -> str:
    addr &= (0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF
    return f"{addr >> 24}.{(addr >> 16) & 255}.{(addr >> 8) & 255}." \
           f"{addr & 255}/{plen}"


def text6(addr: int, plen: int) -> str:
    addr = (addr >> (128 - plen)) << (128 - plen)
    return ":".join(f"{(addr >> (112 - 16 * g)) & 0xFFFF:x}"
                    for g in range(8)) + f"/{plen}"


def random_table(seed: int, n4: int, n6: int, nest: float = 0.5):
    """Prefixes of every length in both families, ``nest`` of them drawn
    inside an earlier one of the family (so that cells are fought over and
    paths are shared), identities from a small set."""
    rng = np.random.default_rng(seed)
    entries, drawn = {}, {4: [], 6: []}
    for family, n, bits, text in ((4, n4, 32, text4), (6, n6, 128, text6)):
        for _ in range(n):
            plen = int(rng.integers(0, bits + 1))
            addr = int.from_bytes(rng.bytes(bits // 8), "big")
            if drawn[family] and rng.random() < nest:
                base, blen = drawn[family][int(rng.integers(
                    0, len(drawn[family])))]
                if blen < bits:
                    plen = int(rng.integers(blen, bits + 1))
                    keep = ((1 << bits) - 1) >> blen
                    addr = (base & ~keep) | (addr & keep)
            drawn[family].append((addr, plen))
            entries[text(addr, plen)] = int(rng.integers(1, 40))
    return entries


def index_of(entries):
    return {i: n for n, i in enumerate(sorted(set(entries.values())))}


@pytest.mark.parametrize("seed,n4,n6", [
    (0, 300, 300), (1, 2000, 0), (2, 0, 2000), (3, 1, 1), (4, 4000, 4000),
    (5, 50, 3000), (6, 3000, 50)])
def test_the_builder_is_the_loop_array_for_array(seed, n4, n6):
    entries = random_table(seed, n4, n6)
    tables = checked_build_lpm(entries, index_of(entries), 0)
    assert tables.v4_nodes.shape[0] >= 2 and tables.v6_nodes.shape[0] >= 2


@pytest.mark.parametrize("entries", [
    {},
    {"0.0.0.0/0": 1},
    {"::/0": 1},
    {"0.0.0.0/0": 1, "::/0": 2, "128.0.0.0/1": 3, "8000::/1": 4},
    {"10.0.0.0/8": 1, "10.0.0.0/9": 2, "10.0.0.0/16": 3, "10.0.0.0/17": 4,
     "10.0.0.0/24": 5, "10.0.0.0/25": 6, "10.0.0.0/32": 7},
    {"1.2.3.4/32": 1, "1.2.3.5/32": 2, "1.2.4.0/24": 3, "1.3.0.0/16": 1},
    {"2001:db8::/32": 1, "2001:db8::/33": 2, "2001:db8::1/128": 3,
     "2001:db8::/127": 4, "::ffff:10.0.0.0/104": 5, "10.0.0.0/8": 6},
    {"2400::/12": 1, "2400:8000::/17": 2, "2400:8000::/29": 3,
     "2400:8001:200::/47": 1, "2400:8001:200::/48": 2},
], ids=["empty", "v4-default", "v6-default", "halves", "one-path",
        "siblings", "v6-and-mapped", "dfz-lengths"])
def test_the_builder_is_the_loop_on_the_edges(entries):
    checked_build_lpm(entries, index_of(entries) or {0: 0}, 0)


def test_the_order_of_the_ipcaches_dict_changes_nothing():
    entries = random_table(9, 500, 500)
    keys = list(entries)
    np.random.default_rng(1).shuffle(keys)
    a = build_lpm(entries, index_of(entries), 0)
    b = build_lpm({k: entries[k] for k in keys}, index_of(entries), 0)
    np.testing.assert_array_equal(a.v4_nodes, b.v4_nodes)
    np.testing.assert_array_equal(a.v6_nodes, b.v6_nodes)
    assert a.prefixes == b.prefixes


def test_an_identity_the_index_lacks_is_refused():
    with pytest.raises(KeyError):
        build_lpm({"10.0.0.0/8": 7}, {1: 0}, 0)
