"""Host-side service registry (analog of upstream ``pkg/service`` /
``pkg/loadbalancer`` + the k8s Service watchers).

Two roles:
- resolve ``toServices`` rules (BASELINE config 3) via service labels →
  backend IPs;
- describe load-balancer state (frontends → backends) that
  ``compile/lb.py`` turns into the device service/Maglev/rev-NAT tensors
  (the lbmap analog, SURVEY.md §2 "Services/LB").

A frontend is a (VIP, port, proto) the datapath DNATs (ClusterIP,
NodePort on a node IP, ExternalIP). Backends are (ip, port, weight).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from cilium_tpu.model.labels import Labels
from cilium_tpu.model.selectors import EndpointSelector

SVC_CLUSTER_IP = "ClusterIP"
SVC_NODEPORT = "NodePort"
SVC_EXTERNAL_IP = "ExternalIP"
SVC_LOADBALANCER = "LoadBalancer"
NAME_LABEL = "k8s:io.kubernetes.service.name"
NAMESPACE_LABEL = "k8s:io.kubernetes.service.namespace"


@dataclass(frozen=True)
class Frontend:
    """One DNAT'able service address: VIP:port/proto."""
    addr: str                          # v4 or v6 literal
    port: int
    proto: int = 6                     # IP protocol number (TCP)
    kind: str = SVC_CLUSTER_IP

    def __post_init__(self):
        if not (0 < self.port < 65536):
            raise ValueError(f"bad frontend port {self.port}")


@dataclass(frozen=True)
class Backend:
    addr: str
    port: int
    weight: int = 1                    # Maglev weighting (upstream lb.h)

    def __post_init__(self):
        if self.weight < 1:
            raise ValueError("backend weight must be >= 1")


@dataclass(frozen=True)
class Service:
    name: str
    namespace: str
    backends: Tuple[str, ...] = ()     # backend IPs for toServices expansion
    extra_labels: Tuple[Tuple[str, str], ...] = ()
    # Load-balancer state (empty for headless/selector-only services):
    frontends: Tuple[Frontend, ...] = ()
    lb_backends: Tuple[Backend, ...] = ()

    @property
    def backend_ips(self) -> Tuple[str, ...]:
        """IPs used for toServices rule expansion: explicit ``backends``
        else the LB backend addresses."""
        return self.backends or tuple(b.addr for b in self.lb_backends)

    @property
    def labels(self) -> Labels:
        base = {NAME_LABEL: self.name, NAMESPACE_LABEL: self.namespace}
        base.update({k: v for k, v in self.extra_labels})
        return Labels.parse([f"{k}={v}" if v else k for k, v in base.items()])


class ServiceRegistry:
    def __init__(self, lb_map_max: Optional[int] = None):
        """``lb_map_max`` (``DaemonConfig.lb_map_max``, upstream's
        ``bpf-lb-map-max``): the most frontends, and the most LB backends,
        the registry's services may have together; None for no limit."""
        self._lock = threading.RLock()
        self.lb_map_max = lb_map_max
        self._n_frontends = 0
        self._n_backends = 0
        self._services: Dict[Tuple[str, str], Service] = {}
        # services whose extra labels restate the name or the namespace
        # label: the only ones ``match`` cannot find by (namespace, name)
        self._relabelled: set = set()
        self._observers: List[Callable[[], None]] = []
        # Stable rev-NAT id per frontend (addr16, port, proto) — the analog
        # of upstream's allocated RevNatID: ids survive service churn so
        # long-lived CT entries never resolve to the wrong VIP. Ids are
        # never reused within a registry lifetime (stale CT entries could
        # otherwise rewrite replies to a NEW service's VIP).
        self._rnat_ids: Dict[Tuple[bytes, int, int], int] = {}
        self._next_rnat_id = 0
        # Frontend (addr16, port, proto) → owning (namespace, name): the
        # uniqueness index consulted at upsert time (O(frontends) per upsert,
        # not a scan of every registered service).
        self._fe_owner: Dict[Tuple[bytes, int, int], Tuple[str, str]] = {}
        self._revision = 0        # bumped on any LB-visible state change

    @property
    def revision(self) -> int:
        return self._revision

    def add_observer(self, obs: Callable[[], None]) -> None:
        self._observers.append(obs)

    def rnat_id(self, fe: Frontend) -> int:
        from cilium_tpu.utils.ip import parse_addr
        key = (parse_addr(fe.addr)[0], fe.port, fe.proto)
        with self._lock:
            rid = self._rnat_ids.get(key)
            if rid is None:
                rid = self._next_rnat_id
                self._next_rnat_id += 1
                self._rnat_ids[key] = rid
            return rid

    def export_rnat_state(self) -> Dict:
        from cilium_tpu.utils.ip import addr_to_str
        with self._lock:
            return {
                "next_id": self._next_rnat_id,
                "ids": [{"addr": addr_to_str(a), "port": p, "proto": pr,
                         "id": rid}
                        for (a, p, pr), rid in sorted(self._rnat_ids.items(),
                                                      key=lambda kv: kv[1])],
            }

    def restore_rnat_state(self, state: Dict) -> None:
        from cilium_tpu.utils.ip import parse_addr
        with self._lock:
            self._next_rnat_id = state["next_id"]
            self._rnat_ids = {
                (parse_addr(e["addr"])[0], e["port"], e["proto"]): e["id"]
                for e in state["ids"]}

    def upsert(self, svc: Service, validate: bool = True) -> None:
        """Register/replace a service. With ``validate`` (the default),
        frontend (VIP, port, proto) collisions with another service are
        rejected synchronously — deferring to snapshot-compile time would let
        the bad upsert poison every subsequent (auto-triggered) regeneration.
        ``validate=False`` is for checkpoint restore, which must accept
        whatever an older engine accepted (the conflict then surfaces at the
        next regenerate, logged + counted by the engine)."""
        from cilium_tpu.utils.ip import parse_addr
        me = (svc.namespace, svc.name)
        with self._lock:
            keys = [(parse_addr(fe.addr)[0], fe.port, fe.proto)
                    for fe in svc.frontends]
            if validate:
                seen = set()
                for key, fe in zip(keys, svc.frontends):
                    if key in seen:
                        raise ValueError(
                            f"service {svc.namespace}/{svc.name} declares "
                            f"frontend {fe.addr}:{fe.port}/{fe.proto} twice")
                    seen.add(key)
                    owner = self._fe_owner.get(key)
                    if owner is not None and owner != me:
                        raise ValueError(
                            f"frontend {fe.addr}:{fe.port}/{fe.proto} of "
                            f"service {svc.namespace}/{svc.name} conflicts "
                            f"with existing service {owner[0]}/{owner[1]}")
            old = self._services.get(me)
            if validate and self.lb_map_max is not None:
                for what, now, was, new in (
                        ("frontends", self._n_frontends,
                         old.frontends if old else (), svc.frontends),
                        ("backends", self._n_backends,
                         old.lb_backends if old else (), svc.lb_backends)):
                    if now - len(was) + len(new) > self.lb_map_max:
                        raise ValueError(
                            f"service {svc.namespace}/{svc.name} would take "
                            f"the load-balancer's {what} to "
                            f"{now - len(was) + len(new)}, past lb_map_max "
                            f"{self.lb_map_max} (bpf-lb-map-max)")
            freed = []
            if old is not None:
                for fe in old.frontends:
                    k = (parse_addr(fe.addr)[0], fe.port, fe.proto)
                    if self._fe_owner.get(k) == me and k not in keys:
                        del self._fe_owner[k]
                        freed.append(k)
            for key in keys:
                self._fe_owner.setdefault(key, me)
            for fe in svc.frontends:
                self.rnat_id(fe)      # allocate eagerly, deterministically
            self._services[me] = svc
            self._count(old, -1)
            self._count(svc, +1)
            # a key this service no longer declares may have a shadowed
            # claimant (validate=False restores): hand ownership over so a
            # later validated upsert can't create an undetected live conflict
            for k in freed:
                self._reclaim_key(k)
            self._revision += 1
        for obs in list(self._observers):
            obs()

    def delete(self, namespace: str, name: str) -> bool:
        from cilium_tpu.utils.ip import parse_addr
        with self._lock:
            svc = self._services.pop((namespace, name), None)
            ok = svc is not None
            if ok:
                self._count(svc, -1)
                for fe in svc.frontends:
                    k = (parse_addr(fe.addr)[0], fe.port, fe.proto)
                    if self._fe_owner.get(k) == (namespace, name):
                        del self._fe_owner[k]
                        self._reclaim_key(k)
                self._revision += 1
        if ok:
            for obs in list(self._observers):
                obs()
        return ok

    def _reclaim_key(self, key: Tuple[bytes, int, int]) -> None:
        """After a frontend key loses its owner, re-own it to a surviving
        service still declaring it (deterministically: first in sorted
        (namespace, name) order). Without this, a conflicting service let in
        via ``validate=False`` stays shadowed with no owner recorded, and a
        third service could later claim the key with validation passing —
        an undetected live conflict. Caller holds the lock."""
        from cilium_tpu.utils.ip import parse_addr
        for me in sorted(self._services):
            for fe in self._services[me].frontends:
                if (parse_addr(fe.addr)[0], fe.port, fe.proto) == key:
                    self._fe_owner[key] = me
                    return

    def _count(self, svc: Optional[Service], sign: int) -> None:
        """Keep the totals ``lb_map_max`` limits, and ``_relabelled``, as a
        service comes (+1) or goes (-1). Caller holds the lock."""
        if svc is None:
            return
        self._n_frontends += sign * len(svc.frontends)
        self._n_backends += sign * len(svc.lb_backends)
        if any(k in (NAME_LABEL, NAMESPACE_LABEL)
               for k, _v in svc.extra_labels):
            me = (svc.namespace, svc.name)
            (self._relabelled.add if sign > 0
             else self._relabelled.discard)(me)

    def match(self, selector: EndpointSelector) -> List[Service]:
        """Services the selector matches. One that names both the service's
        name and its namespace (the ``k8sService`` form) can match only the
        service registered under them: looked up, not scanned for."""
        want = dict(selector.match_labels)
        with self._lock:
            if NAME_LABEL in want and NAMESPACE_LABEL in want:
                keys = {(want[NAMESPACE_LABEL], want[NAME_LABEL])} \
                    | self._relabelled
                among = [self._services[k] for k in sorted(keys)
                         if k in self._services]
            else:
                among = list(self._services.values())
            return [svc for svc in among if selector.matches(svc.labels)]

    def all(self) -> List[Service]:
        with self._lock:
            return sorted(self._services.values(),
                          key=lambda s: (s.namespace, s.name))
