"""SelectorCache: selector → live identity set, updated incrementally
(analog of upstream ``pkg/policy`` SelectorCache — SURVEY.md §2: "identity↔
selector incremental index").

Selectors are any object with ``matches(labels) -> bool`` (EndpointSelector,
the special ClusterSelector, …). The cache subscribes to the
IdentityAllocator; each registered selector keeps a materialized set of
matching identity ids, so MapState computation is a set read, not a scan.
Users subscribe per-selector to drive incremental endpoint regeneration.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Set, Tuple

from cilium_tpu.model.identity import Identity, IdentityAllocator
from cilium_tpu.model.labels import Labels, SOURCE_K8S
from cilium_tpu.model.selectors import EndpointSelector, MatchExpression
from cilium_tpu.utils import constants as C


@dataclass(frozen=True)
class ClusterSelector:
    """Matches the 'cluster' entity: any cluster-managed workload identity
    (has a k8s-source label) or cluster-infrastructure reserved identity.
    Not expressible as a single label selector, hence its own type."""

    _RESERVED = ("host", "remote-node", "health", "init", "ingress",
                 "kube-apiserver", "unmanaged")

    def matches(self, labels: Labels) -> bool:
        if any(l.source == SOURCE_K8S for l in labels):
            return True
        return any(labels.has("reserved", name) for name in self._RESERVED)

    def __str__(self) -> str:
        return "entity:cluster"


_ENTITY_SELECTOR_TABLE = {
    "all": (EndpointSelector(),),
    "world": (EndpointSelector.from_labels({"reserved:world": ""}),),
    "host": (EndpointSelector.from_labels({"reserved:host": ""}),),
    "remote-node": (EndpointSelector.from_labels({"reserved:remote-node": ""}),),
    "health": (EndpointSelector.from_labels({"reserved:health": ""}),),
    "init": (EndpointSelector.from_labels({"reserved:init": ""}),),
    "unmanaged": (EndpointSelector.from_labels({"reserved:unmanaged": ""}),),
    "kube-apiserver": (EndpointSelector.from_labels({"reserved:kube-apiserver": ""}),),
    "ingress": (EndpointSelector.from_labels({"reserved:ingress": ""}),),
    "cluster": (ClusterSelector(),),
}


def entity_selectors(name: str):
    """Expand an entity name into selector objects."""
    try:
        return _ENTITY_SELECTOR_TABLE[name]
    except KeyError:
        raise ValueError(f"unknown entity {name!r}")


def cidr_selector(cidr: str, excepts: Tuple[str, ...] = ()) -> EndpointSelector:
    """Selector matching all CIDR identities at-or-below ``cidr`` while
    excluding those at-or-below any except prefix (works because CIDR
    identities carry labels for every parent prefix)."""
    return EndpointSelector(
        match_labels=((f"cidr:{cidr}", ""),),
        match_expressions=tuple(
            MatchExpression(key=f"cidr:{e}", operator="DoesNotExist")
            for e in excepts),
    )


class CachedSelector:
    """A registered selector + its materialized identity set."""

    def __init__(self, selector, cache: "SelectorCache"):
        self.selector = selector
        self._cache = cache
        self._ids: Set[int] = set()
        self._subscribers: List[Callable[[Set[int], Set[int]], None]] = []
        self.refcount = 0

    @property
    def identities(self) -> frozenset:
        return frozenset(self._ids)

    def subscribe(self, fn: Callable[[Set[int], Set[int]], None]) -> None:
        """fn(added_ids, removed_ids) fires on incremental identity changes."""
        self._subscribers.append(fn)

    def _apply(self, added: Set[int], removed: Set[int]) -> None:
        self._ids |= added
        self._ids -= removed
        for fn in list(self._subscribers):
            fn(added, removed)


def _first_label(selector) -> Optional[Tuple[str, str]]:
    """(key, value) of a selector's first ``matchLabels`` entry, the source
    left off: an identity it matches carries a label of that key and value.
    None for a selector without one (it may match any identity)."""
    labels = getattr(selector, "match_labels", None)
    if not labels:
        return None
    key, value = labels[0]
    return key.split(":", 1)[-1], value


class SelectorCache:
    """Both sides are indexed by (label key, value), so a node that holds
    thousands of selectors and identities (one CIDR selector a backend of
    every service a ``toServices`` document names) pays for a new selector
    or identity by those that share a label with it, not by all of them."""

    def __init__(self, allocator: IdentityAllocator):
        self._lock = threading.RLock()
        self._allocator = allocator
        self._selectors: Dict[str, CachedSelector] = {}
        # (key, value) → the identities that carry such a label, by id
        self._identities_with: Dict[Tuple[str, str], Dict[int, Identity]] = {}
        # (key, value) of a selector's first matchLabels entry → selectors;
        # those without one are in _unindexed
        self._selectors_on: Dict[Tuple[str, str], Dict[str, CachedSelector]] \
            = {}
        self._unindexed: Dict[str, CachedSelector] = {}
        # replay seeds the identity index with what is allocated already
        allocator.add_observer(self._on_identities, replay=True)

    def _key(self, selector) -> str:
        return f"{type(selector).__name__}:{selector}"

    def add_selector(self, selector) -> CachedSelector:
        """Register (or ref) a selector; materializes its identity set."""
        with self._lock:
            key = self._key(selector)
            cached = self._selectors.get(key)
            if cached is None:
                cached = CachedSelector(selector, self)
                on = _first_label(selector)
                among = self._allocator.all() if on is None \
                    else self._identities_with.get(on, {}).values()
                matched = {
                    ident.id for ident in among
                    if selector.matches(ident.labels)
                }
                cached._apply(matched, set())
                self._selectors[key] = cached
                (self._unindexed if on is None
                 else self._selectors_on.setdefault(on, {}))[key] = cached
            cached.refcount += 1
            return cached

    def remove_selector(self, cached: CachedSelector) -> None:
        with self._lock:
            cached.refcount -= 1
            if cached.refcount <= 0:
                key = self._key(cached.selector)
                self._selectors.pop(key, None)
                on = _first_label(cached.selector)
                if on is None:
                    self._unindexed.pop(key, None)
                elif key in self._selectors_on.get(on, ()):
                    del self._selectors_on[on][key]
                    if not self._selectors_on[on]:
                        del self._selectors_on[on]

    def _on_identities(self, added: List[Identity], removed: List[Identity]) -> None:
        with self._lock:
            touched: Dict[str, CachedSelector] = dict(self._unindexed)
            for ident in added:
                for lbl in ident.labels:
                    on = (lbl.key, lbl.value)
                    self._identities_with.setdefault(on, {})[ident.id] = ident
                    touched.update(self._selectors_on.get(on, ()))
            for ident in removed:
                for lbl in ident.labels:
                    on = (lbl.key, lbl.value)
                    if ident.id in self._identities_with.get(on, ()):
                        del self._identities_with[on][ident.id]
                        if not self._identities_with[on]:
                            del self._identities_with[on]
                    touched.update(self._selectors_on.get(on, ()))
            for cached in touched.values():
                add_ids = {i.id for i in added if cached.selector.matches(i.labels)}
                rem_ids = {i.id for i in removed if i.id in cached._ids}
                if add_ids or rem_ids:
                    cached._apply(add_ids, rem_ids)

    def __len__(self) -> int:
        return len(self._selectors)
