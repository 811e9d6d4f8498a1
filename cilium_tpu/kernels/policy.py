"""Dense policy lookup: the whole wildcard ladder in three gathers
(upstream: bpf/lib/policy.h policy_can_access's 6-lookup ladder, resolved at
compile time by compile/policy_image.py).

``policy_lookup_batch`` is pure jnp over the snapshot's tensor dict. On
the dense (un-sharded) image every row-derived index is explicitly clipped
— the clip is jax's own out-of-bounds clamp, written down, so a garbage
row reads the cell the host ladder of tests/test_policy_ladder.py reads —
and every table is gathered from in the shape it is placed in. A flattened
take (``v.reshape(-1)[…]``) is not free on the TPU: the image's last
dimension is no multiple of the 128-lane tile, so XLA runs the reshape as a
physical copy of the whole image in every batch (the chip's trace: 1.58 ms
of every dispatch for ``ct1m-50k``'s 100 MB, PERF.md §6 PR 35).

Besides the cell, the lookup emits ``matched_rule``: the (id_class,
port_class) coordinate of the resolved verdict cell, packed
``id_cls * n_port_classes + port_cls`` — layout-independent (never an index
into the possibly rule-shard-padded image), identical across the dense
lookup, the rule-sharded mesh and the host oracle by construction. Callers
mask it to -1 where no ladder ran (invalid row or unenforced direction);
together with the endpoint slot and direction it names the exact policy-map
row that decided the verdict (the flowlog / observer provenance of
ISSUE 11).
"""

from __future__ import annotations

import jax.numpy as jnp

from cilium_tpu.utils import constants as C


def policy_lookup_batch(tensors, ep_slot, direction, id_index, proto, dport,
                        rule_axis=None):
    """→ (decision [N] int32, l7_id [N] int32, enforced [N] bool,
    matched_rule [N] int32 — the unmasked cell coordinate).

    ``rule_axis``: name of a mesh axis over which the verdict tensor's
    id-class rows are sharded (the "tensor parallelism over rule space" of
    SURVEY.md §2's parallelism table). Each shard gathers rows it owns and a
    psum combines — one XLA collective, no gather of remote rows. Rows must
    be padded to a multiple of the axis size (compile/parallel handles it).
    ``matched_rule`` uses the GLOBAL id class (id_class_of is replicated),
    so its value is identical on every shard and to the un-sharded path —
    no collective needed.
    """
    if rule_axis is None:
        n_ids = tensors["id_class_of"].shape[0]
        id_cls = tensors["id_class_of"][jnp.clip(id_index, 0, n_ids - 1)]
        fam = tensors["proto_family"][jnp.clip(proto, 0, 255)]
        n_ports = tensors["port_class"].shape[1]
        pcls = tensors["port_class"][fam, jnp.clip(dport, 0, n_ports - 1)]
        v = tensors["verdict"]
        n_eps, _, n_rows, n_cols = v.shape
        ep = jnp.clip(ep_slot, 0, n_eps - 1)
        d = jnp.clip(direction, 0, 1)
        cls = jnp.clip(id_cls, 0, n_rows - 1)
        pc = jnp.clip(pcls, 0, n_cols - 1)
        cell = v[ep, d, cls, pc].astype(jnp.int32)
        enforced = tensors["enforced"][ep, d].astype(bool)
        decision = cell & C.VERDICT_DECISION_MASK
        l7_id = cell >> C.VERDICT_L7_SHIFT
        matched_rule = (id_cls * n_cols + pcls).astype(jnp.int32)
        return decision, l7_id, enforced, matched_rule
    import jax
    id_cls = tensors["id_class_of"][id_index]
    fam = tensors["proto_family"][jnp.clip(proto, 0, 255)]
    pcls = tensors["port_class"][fam, jnp.clip(dport, 0, 65535)]
    rows_local = tensors["verdict"].shape[2]
    n_cols = tensors["verdict"].shape[3]
    ri = jax.lax.axis_index(rule_axis)
    local_idx = id_cls - ri * rows_local
    in_range = (local_idx >= 0) & (local_idx < rows_local)
    safe = jnp.clip(local_idx, 0, rows_local - 1)
    cell_local = jnp.where(
        in_range,
        tensors["verdict"][ep_slot, direction, safe, pcls].astype(jnp.int32),
        0)
    cell = jax.lax.psum(cell_local, rule_axis)
    enforced = tensors["enforced"][ep_slot, direction]
    decision = cell & C.VERDICT_DECISION_MASK
    l7_id = cell >> C.VERDICT_L7_SHIFT
    matched_rule = (id_cls * n_cols + pcls).astype(jnp.int32)
    return decision, l7_id, enforced, matched_rule
