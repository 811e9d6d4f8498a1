"""PolicySnapshot: one immutable, device-placeable compilation of the whole
control-plane state (the output of "the loader").

A snapshot is the unit of atomicity: the runtime double-buffers snapshots
and fences batches on snapshot revision (the analog of upstream's
per-endpoint policymap atomicity + regeneration revisions — SURVEY.md §7
"revision fencing so a batch never sees a torn policy update").
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from cilium_tpu.compile.ct_layout import CTConfig
from cilium_tpu.compile.idclass import IdentityClasses, build_identity_classes
from cilium_tpu.compile.l7 import L7SetInterner, L7Tensors, build_l7_tensors
from cilium_tpu.compile.lb import LBConfig, LBTables, build_lb
from cilium_tpu.compile.lpm import LPMTables, build_lpm
from cilium_tpu.compile.policy_image import PolicyImage, build_policy_image
from cilium_tpu.compile.portclass import PortClassTable, build_port_classes
from cilium_tpu.model.endpoint import Endpoint
from cilium_tpu.policy.repository import EndpointPolicy, PolicyContext, Repository
from cilium_tpu.utils import constants as C


@dataclass(frozen=True)
class PolicySnapshot:
    revision: int
    ep_ids: Tuple[int, ...]                  # slot → endpoint id
    ep_slot_of: Dict[int, int]               # endpoint id → slot
    policies: Tuple[EndpointPolicy, ...]     # slot-aligned (host/oracle use)
    image: PolicyImage
    id_classes: IdentityClasses
    port_classes: PortClassTable
    lpm: LPMTables
    l7: L7Tensors
    lb: LBTables
    proto_family_table: np.ndarray           # [256] int32
    world_index: int
    ct_config: CTConfig
    # The ipcache state this snapshot was compiled from (prefix → identity).
    # Carried so any DatapathBackend (notably the oracle-backed fake) can
    # reconstruct the exact semantics context without reaching back into the
    # live control plane.
    ipcache: Dict[str, int] = field(default_factory=dict)
    # Compile-time context for the incremental updater's geometry gates
    # (SURVEY.md §7 step 3 "diffable"): the L7 interner that numbered the
    # verdict cells' set ids, and the revisions/modes the snapshot saw.
    l7_interner: Optional[L7SetInterner] = None
    ipcache_revision: int = -1
    services_revision: int = -1
    enforcement_mode: str = C.ENFORCEMENT_DEFAULT
    allow_localhost: bool = True

    # -- device-facing view --------------------------------------------------
    def tensors(self, only: Optional[frozenset] = None
                ) -> Dict[str, np.ndarray]:
        """The flat dict of arrays the runtime places on device. Everything
        the classify kernel reads is here; scalars live in `static_config`.

        LB tensors are included only when a frontend exists: the classify
        kernel gates the whole LB stage (frontend hash probe + Maglev +
        rev-NAT gathers) on key presence, so a service-free snapshot pays
        zero per-packet LB cost (a round-2 regression: cfg5 carried the
        full LB stage with zero services).

        ``only`` restricts the dict to the named tensors. This matters on
        the incremental fast path: a delta-emitted snapshot's dense verdict
        materializes lazily (compile/policy_image.OverlayImage), and a
        place_patch that only needs e.g. ``enforced`` must not pay an
        O(image) materialization for a tensor it never reads."""
        out: Dict[str, np.ndarray] = {}

        def want(name):
            return only is None or name in only

        if want("verdict"):
            out["verdict"] = self.image.verdict
        if want("enforced"):
            out["enforced"] = self.image.enforced
        for name, arr in (
                ("id_class_of", self.id_classes.class_of),
                ("identity_ids", self.id_classes.identity_ids),
                ("lpm_v4", self.lpm.v4_placed),
                ("lpm_v6", self.lpm.v6_placed),
                ("port_class", self.port_classes.table),
                ("proto_family", self.proto_family_table),
                ("l7_methods", self.l7.methods),
                ("l7_path", self.l7.path),
                ("l7_path_len", self.l7.path_len),
                ("l7_valid", self.l7.valid)):
            if want(name):
                out[name] = arr
        if self.lb.n_frontends:
            for name, arr in self.lb.tensors().items():
                if want(name):
                    out[name] = arr
        return out

    def static_config(self) -> Dict[str, int]:
        return {
            "world_index": self.world_index,
            "n_id_classes": self.id_classes.n_classes,
            "n_port_classes": self.port_classes.n_classes,
            "revision": self.revision,
        }

    @property
    def nbytes(self) -> int:
        # image.nbytes is computed without materializing a lazy
        # (delta-emitted) image; the rest are plain arrays
        n = self.image.nbytes
        for a in (self.id_classes.class_of, self.id_classes.identity_ids,
                  self.lpm.v4_nodes, self.lpm.v6_nodes,
                  self.port_classes.table, self.proto_family_table,
                  self.l7.methods, self.l7.path, self.l7.path_len,
                  self.l7.valid):
            n += a.nbytes
        if self.lb.n_frontends:
            n += sum(a.nbytes for a in self.lb.tensors().values())
        return n


def _proto_family_table() -> np.ndarray:
    table = np.full((256,), C.PROTO_FAMILY_OTHER, dtype=np.int32)
    for proto in range(256):
        table[proto] = C.proto_family(proto)
    return table


def build_snapshot(repo: Repository, ctx: PolicyContext,
                   endpoints: Sequence[Endpoint],
                   ct_config: Optional[CTConfig] = None,
                   lb_config: Optional[LBConfig] = None,
                   lb: Optional[LBTables] = None) -> PolicySnapshot:
    """Compile the current control-plane state for ``endpoints``. ``lb``:
    the LB tables where the caller has built them (the engine does, under a
    span of its own and with the active snapshot's rows to reuse).

    Mirrors the regeneration pipeline (SURVEY.md §3.2): resolve policy per
    endpoint → MapStates → dense tensors. Deterministic given (rules,
    identities, ipcache, endpoints).
    """
    policies = tuple(repo.resolve(ep) for ep in endpoints)
    ep_ids = tuple(ep.ep_id for ep in endpoints)
    ep_slot_of = {ep_id: slot for slot, ep_id in enumerate(ep_ids)}

    identity_ids = [ident.id for ident in ctx.allocator.all()]
    mapstates = []
    for slot, pol in enumerate(policies):
        mapstates.append((slot, C.DIR_EGRESS, pol.egress.mapstate))
        mapstates.append((slot, C.DIR_INGRESS, pol.ingress.mapstate))
    id_classes = build_identity_classes(identity_ids, mapstates)

    ranges_by_family: Dict[int, list] = {}
    for _slot, _d, ms in mapstates:
        for key, _entry in ms.items():
            if key.proto == C.PROTO_ANY:
                continue
            fam = C.proto_family(key.proto)
            ranges_by_family.setdefault(fam, []).append(
                (key.port_lo, key.port_hi))
    port_classes = build_port_classes(ranges_by_family)

    l7 = L7SetInterner()
    image = build_policy_image(list(policies), id_classes, port_classes, l7)
    l7_tensors = build_l7_tensors(l7)

    ipcache_snapshot = ctx.ipcache.snapshot()
    lpm = build_lpm(ipcache_snapshot, id_classes.index_of,
                    default_index=id_classes.index_of[C.IDENTITY_WORLD])

    if lb is None:
        lb = build_lb(ctx.services, lb_config)  # registry: stable rev-NAT ids

    return PolicySnapshot(
        revision=repo.revision,
        ep_ids=ep_ids,
        ep_slot_of=ep_slot_of,
        policies=policies,
        image=image,
        id_classes=id_classes,
        port_classes=port_classes,
        lpm=lpm,
        l7=l7_tensors,
        lb=lb,
        proto_family_table=_proto_family_table(),
        world_index=id_classes.index_of[C.IDENTITY_WORLD],
        ct_config=ct_config or CTConfig(),
        ipcache=ipcache_snapshot,
        l7_interner=l7,
        ipcache_revision=ctx.ipcache.revision,
        services_revision=ctx.services.revision,
        enforcement_mode=ctx.enforcement_mode,
        allow_localhost=ctx.allow_localhost,
    )
