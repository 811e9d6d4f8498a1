"""Security-identity allocation (analog of upstream ``pkg/identity`` +
``pkg/allocator``).

- Reserved identities (host/world/...) are fixed small numbers.
- Cluster-scope identities (label-derived, for pods) are allocated from
  ``CLUSTER_IDENTITY_BASE`` upward, deterministically by first-allocation
  order, and are idempotent per label set (the single-node analog of the
  kvstore/CRD global allocator — SURVEY.md §3.5).
- Node-local identities (CIDR-derived) carry ``LOCAL_IDENTITY_SCOPE``
  (upstream: identity.IdentityScopeLocal == 1<<24).

Identities are *the tensor row space*: the compiler assigns each live
identity a dense row index; observers (SelectorCache) are notified on
allocate/release so MapState can be updated incrementally.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from cilium_tpu.model.labels import Label, Labels, SOURCE_CIDR, SOURCE_RESERVED
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import normalize_prefix


@dataclass(frozen=True)
class Identity:
    id: int
    labels: Labels

    @property
    def is_reserved(self) -> bool:
        return 0 < self.id < C.CLUSTER_IDENTITY_BASE

    @property
    def is_local(self) -> bool:
        return bool(self.id & C.LOCAL_IDENTITY_SCOPE)

    @property
    def is_world(self) -> bool:
        return self.id == C.IDENTITY_WORLD

    @property
    def is_cidr(self) -> bool:
        return any(l.source == SOURCE_CIDR for l in self.labels)

    def __repr__(self) -> str:
        return f"Identity({self.id}, {','.join(self.labels.to_strings())})"


def cidr_identity_labels(prefix: str) -> Labels:
    """Labels of a CIDR-derived identity.

    Includes one ``cidr:`` label for the prefix itself AND every *parent*
    prefix, plus ``reserved:world`` (CIDR identities are world-scoped). The
    parent labels are what make CIDR policy composition work: a rule allowing
    ``10.0.0.0/8`` compiles to a selector on label ``cidr:10.0.0.0/8``, and an
    IP that LPM-resolves to a *narrower* identity (say ``10.1.0.0/16``,
    created by some other rule) still matches because the /16 identity carries
    the /8 parent label — mirroring upstream's per-prefix-length CIDR labels.
    """
    import ipaddress
    net = ipaddress.ip_network(normalize_prefix(prefix), strict=False)
    labels: List[Label] = [Label(SOURCE_RESERVED, "world")]
    for plen in range(net.prefixlen, -1, -1):
        parent = net.supernet(new_prefix=plen) if plen < net.prefixlen else net
        labels.append(Label(SOURCE_CIDR, str(parent)))
    return Labels(labels)


# Observer signature: (added: [Identity], removed: [Identity]) -> None
IdentityObserver = Callable[[List[Identity], List[Identity]], None]


class IdentityAllocator:
    """Idempotent label-set → numeric identity allocator with observers."""

    def __init__(self):
        self._lock = threading.RLock()
        self._by_labels: Dict[Labels, Identity] = {}
        self._by_id: Dict[int, Identity] = {}
        self._refcount: Dict[int, int] = {}
        self._next_cluster = C.CLUSTER_IDENTITY_BASE
        self._next_local = C.LOCAL_IDENTITY_SCOPE
        self._observers: List[IdentityObserver] = []
        # canonical prefix → the live identity ``allocate_cidr`` made for
        # it: a prefix asked for again costs no label set (a /128's is 130
        # labels through ``ipaddress``)
        self._by_cidr: Dict[str, Identity] = {}
        self._cidr_of: Dict[int, str] = {}      # its inverse, for release
        for name, num in C.RESERVED_IDENTITIES.items():
            if num == C.IDENTITY_UNKNOWN:
                continue
            ident = Identity(num, Labels.reserved(name))
            self._by_labels[ident.labels] = ident
            self._by_id[num] = ident
            self._refcount[num] = 1  # reserved identities are never released

    # -- observers ----------------------------------------------------------
    def add_observer(self, obs: IdentityObserver, replay: bool = True) -> None:
        with self._lock:
            self._observers.append(obs)
            if replay:
                obs(list(self._by_id.values()), [])

    def _notify(self, added: List[Identity], removed: List[Identity]) -> None:
        for obs in list(self._observers):
            obs(added, removed)

    # -- allocation ---------------------------------------------------------
    def allocate(self, labels: Labels) -> Identity:
        """Allocate (or ref) the identity for a label set."""
        with self._lock:
            existing = self._by_labels.get(labels)
            if existing is not None:
                self._refcount[existing.id] += 1
                return existing
            if any(l.source == SOURCE_CIDR for l in labels):
                num = self._next_local
                self._next_local += 1
            else:
                num = self._next_cluster
                self._next_cluster += 1
                if num > C.CLUSTER_IDENTITY_MAX:
                    raise RuntimeError("cluster identity space exhausted")
            ident = Identity(num, labels)
            self._by_labels[labels] = ident
            self._by_id[num] = ident
            self._refcount[num] = 1
            self._notify([ident], [])
            return ident

    def allocate_cidr(self, prefix: str) -> Identity:
        """Allocate (or ref) the identity of a CIDR prefix. The label set
        is built the first time a prefix is asked for; while that identity
        lives, asking again is a reference more on it."""
        key = normalize_prefix(prefix)
        with self._lock:
            held = self._by_cidr.get(key)
            if held is not None and self._by_id.get(held.id) is held:
                self._refcount[held.id] += 1
                return held
            ident = self.allocate(cidr_identity_labels(key))
            self._by_cidr[key] = ident
            self._cidr_of[ident.id] = key
            return ident

    def release(self, ident: Identity) -> bool:
        """Unref; returns True when the identity was fully removed."""
        with self._lock:
            if ident.id not in self._refcount or ident.is_reserved:
                return False
            self._refcount[ident.id] -= 1
            if self._refcount[ident.id] > 0:
                return False
            del self._refcount[ident.id]
            del self._by_id[ident.id]
            del self._by_labels[ident.labels]
            key = self._cidr_of.pop(ident.id, None)
            if key is not None and self._by_cidr.get(key) is ident:
                del self._by_cidr[key]
            self._notify([], [ident])
            return True

    # -- queries ------------------------------------------------------------
    def get(self, num: int) -> Optional[Identity]:
        with self._lock:
            return self._by_id.get(num)

    def lookup_by_labels(self, labels: Labels) -> Optional[Identity]:
        with self._lock:
            return self._by_labels.get(labels)

    def all(self) -> List[Identity]:
        with self._lock:
            return sorted(self._by_id.values(), key=lambda i: i.id)

    def __len__(self) -> int:
        with self._lock:
            return len(self._by_id)

    # -- persistence (checkpoint/resume: identity numbering must be stable) --
    def export_state(self) -> Dict:
        with self._lock:
            return {
                "next_cluster": self._next_cluster,
                "next_local": self._next_local,
                "identities": [
                    {"id": i.id, "labels": list(i.labels.to_strings()),
                     "refs": self._refcount[i.id]}
                    for i in self.all() if not i.is_reserved
                ],
            }

    def restore_state(self, state: Dict) -> None:
        with self._lock:
            added = []
            for ent in state["identities"]:
                labels = Labels.parse(ent["labels"])
                ident = Identity(ent["id"], labels)
                self._by_labels[labels] = ident
                self._by_id[ident.id] = ident
                self._refcount[ident.id] = ent.get("refs", 1)
                added.append(ident)
            self._next_cluster = state["next_cluster"]
            self._next_local = state["next_local"]
            if added:
                self._notify(added, [])
