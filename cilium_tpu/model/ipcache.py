"""Host-side IPCache (analog of upstream ``pkg/ipcache`` + ``pkg/maps/ipcache``).

Maps IP prefixes → security identity ids. This host store is the source of
truth; the compiler lowers a snapshot of it into the stride-LPM tensor
(``cilium_tpu/compile/lpm.py``). Lookup misses resolve to ``reserved:world``,
matching the datapath's behavior (eps.h: no entry → WORLD_ID).
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import normalize_prefix, parse_addr, parse_prefix


class IPCache:
    """prefix(canonical str) → identity id, with longest-prefix-match lookup."""

    def __init__(self):
        self._lock = threading.RLock()
        self._entries: Dict[str, int] = {}
        self._revision = 0
        self._observers: List[Callable[[], None]] = []
        #: calls of ``upsert_many`` so far (the engine renders it as
        #: ``ipcache_bulk_upserts_total``)
        self.bulk_upserts = 0

    def add_observer(self, obs: Callable[[], None]) -> None:
        self._observers.append(obs)

    def _changed(self) -> None:
        self._revision += 1
        for obs in list(self._observers):
            obs()

    # -- mutation ------------------------------------------------------------
    def upsert(self, prefix: str, identity_id: int) -> None:
        with self._lock:
            key = normalize_prefix(prefix)
            if self._entries.get(key) == identity_id:
                return          # no-op upserts (e.g. a DNS TTL tick
                                # re-learning the same IPs) must not dirty
                                # the LPM or trigger regeneration
            self._entries[key] = identity_id
            self._changed()

    def upsert_many(self, entries: Iterable[Tuple[str, int]]) -> int:
        """Many prefixes at once, as a routing table or a cluster's
        identity sync arrives: each text made canonical once, one hold of
        the lock, and — if any entry is new or changed — one revision and
        one call of the observers, so one regeneration. What the cache
        then holds is what ``upsert`` of each pair in turn leaves (a
        prefix named twice keeps its last identity). → prefixes that were
        new or changed."""
        fresh = {normalize_prefix(prefix): identity_id
                 for prefix, identity_id in entries}
        with self._lock:
            held = self._entries
            changed = {key: identity_id for key, identity_id in fresh.items()
                       if held.get(key) != identity_id}
            held.update(changed)
            self.bulk_upserts += 1
            if changed:
                self._changed()
            return len(changed)

    def delete(self, prefix: str) -> bool:
        with self._lock:
            ok = self._entries.pop(normalize_prefix(prefix), None) is not None
            if ok:
                self._changed()
            return ok

    # -- queries -------------------------------------------------------------
    @property
    def revision(self) -> int:
        return self._revision

    def snapshot(self) -> Dict[str, int]:
        """Copy of entries; the compiler's input."""
        with self._lock:
            return dict(self._entries)

    def get(self, prefix: str) -> Optional[int]:
        """Exact-prefix entry lookup (None if absent); NOT an LPM match."""
        with self._lock:
            return self._entries.get(normalize_prefix(prefix))

    def lookup(self, addr: str) -> int:
        """Host-side reference LPM lookup (slow; the device LPM tensor must
        agree with this exactly — the oracle uses it)."""
        with self._lock:
            return lpm_lookup(self._entries, addr)

    def __len__(self) -> int:
        return len(self._entries)


def lpm_lookup(entries: Dict[str, int], addr: str) -> int:
    """Longest-prefix-match over canonical prefix→id entries; miss → WORLD.

    IPv4 addresses only match IPv4 prefixes and IPv6 only IPv6 — upstream
    keeps two separate LPM maps (cilium_ipcache v4/v6), so ``::/0`` must not
    cover v4-mapped addresses. The device side mirrors this with two stride
    tries selected by the packet's family bit.
    """
    return lpm_lookup_pfx(entries, addr)[0]


def lpm_lookup_pfx(entries: Dict[str, int], addr: str
                   ) -> Tuple[int, Optional[str], int]:
    """LPM with match provenance: → (identity id, winning canonical prefix
    or None on miss, canonical prefix length or -1). The winning prefix is
    unique (two same-length prefixes covering one address are the same
    prefix), so this names exactly the entry whose slot the device trie's
    provenance plane carries (compile/lpm.py) — the oracle's half of the
    ``lpm_prefix`` bit-identity contract."""
    addr16, addr_is_v6 = parse_addr(addr)
    addr_int = int.from_bytes(addr16, "big")
    best_len = -1
    best_id = C.IDENTITY_WORLD
    best_pfx: Optional[str] = None
    for prefix, ident in entries.items():
        net16, plen, pfx_is_v6 = parse_prefix(prefix)
        if pfx_is_v6 != addr_is_v6:
            continue
        net_int = int.from_bytes(net16, "big")
        if plen == 0 or (addr_int >> (128 - plen)) == (net_int >> (128 - plen)):
            if plen > best_len:
                best_len = plen
                best_id = ident
                best_pfx = prefix
    return best_id, best_pfx, (best_len if best_pfx is not None else -1)
