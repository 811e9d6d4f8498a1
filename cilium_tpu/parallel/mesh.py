"""Device mesh construction + shard_map'd classify (see package docstring).

Design notes (the scaling-book recipe: pick a mesh, annotate shardings, let
XLA insert collectives):

- mesh axes: ``('flows', 'rules')`` — flows is the DP axis (batch + CT
  sharded), rules the rule-space axis (verdict rows sharded). Either may be
  size 1.
- inside the shard_map body the ONLY collectives are: one psum per counter
  (flows axis) and, when rule sharding is on, one psum for the policy cell
  (rules axis). Everything else is embarrassingly parallel — this is the
  RSS/per-CPU-map structure of the reference datapath, on ICI.
- CT sharding: the table's slot axis splits across 'flows'; each local table
  is an independent power-of-two hash table. With ``rss_mode="host"``,
  correct flow→shard placement is the HOST's job (steer_batch) — the
  direction-normalized hash guarantees a flow's forward and reply packets
  reach the same shard, so device code needs no cross-chip CT traffic at
  all. With ``rss_mode="device"`` (make_unsteered_classify_fn) rows arrive
  in plain FIFO order and the flow→shard resolution moves INTO the
  shard_map body: a ring ``ppermute`` exchange over the 'flows' axis
  (parallel/exchange.py) routes CT lookups/inserts to their owning shard —
  the host steer/scatter disappears from the hot path entirely.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from cilium_tpu.compile.ct_layout import CT_PLACED_KEYS, PROBE_DEPTH
from cilium_tpu.kernels.hashing import hash_words_np
from cilium_tpu.kernels.records import BatchArrays, ct_key_words


def make_mesh(n_flow_shards: int, n_rule_shards: int = 1, devices=None):
    import jax
    from jax.sharding import Mesh
    if devices is None:
        devices = jax.devices()
    need = n_flow_shards * n_rule_shards
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    arr = np.asarray(devices[:need]).reshape(n_flow_shards, n_rule_shards)
    return Mesh(arr, ("flows", "rules"))


# --------------------------------------------------------------------------- #
# Host-side steering (the RSS analog; the C++ shim implements the same hash)
# --------------------------------------------------------------------------- #
def flow_shard_of(batch: BatchArrays, n_shards: int,
                  lb=None) -> np.ndarray:
    """Direction-normalized shard index per packet: XOR of forward and
    reverse key hashes is symmetric, so both directions of a flow agree.

    ``lb`` (a compiled compile/lb.LBTables) translates service VIPs first —
    CT entries live under the DNAT'ed tuple, so steering must hash the
    translated tuple or a service flow's forward and reply packets would
    land on different CT shards. The C++ shim runs the same translation."""
    if lb is not None and lb.n_frontends:
        from cilium_tpu.compile.lb import lb_translate_np
        new_dst, new_dport, _rnat, _nb, _fe = lb_translate_np(lb, batch)
        batch = dict(batch)
        batch["dst"] = new_dst
        batch["dport"] = new_dport
    h = hash_words_np(ct_key_words(batch, reverse=False)) \
        ^ hash_words_np(ct_key_words(batch, reverse=True))
    return (h % np.uint32(n_shards)).astype(np.int32)


def steer_rows(shard: np.ndarray, n_shards: int, seg_cap: int,
               fills: Optional[np.ndarray] = None,
               counts: Optional[np.ndarray] = None) -> np.ndarray:
    """Destination row per packet for a segmented steered layout: packet i
    (shard ``shard[i]``) lands at ``shard[i]*seg_cap + fill + rank`` where
    ``rank`` preserves arrival order within the shard (stable sort) and
    ``fills`` are the segments' current occupancies (all-zero when absent).
    This is the scatter half of ``steer_batch``, shared with the pipeline's
    staging ring so flush-time steering is the same placement the classic
    steer produces. The caller checks capacity (``fills + counts`` must stay
    within ``seg_cap``); ``counts`` passes an already-computed
    ``bincount(shard, minlength=n_shards)`` so hot callers don't pay the
    histogram twice."""
    m = shard.shape[0]
    order = np.argsort(shard, kind="stable")
    sorted_s = shard[order]
    if counts is None:
        counts = np.bincount(shard, minlength=n_shards)
    counts = counts.astype(np.int64)
    starts = np.zeros(n_shards + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    rank = np.arange(m, dtype=np.int64) - starts[sorted_s]
    base = sorted_s * seg_cap + rank
    if fills is not None:
        base += np.asarray(fills, dtype=np.int64)[sorted_s]
    rows = np.empty(m, dtype=np.int64)
    rows[order] = base
    return rows


def steer_batch(batch: BatchArrays, n_shards: int,
                per_shard: Optional[int] = None, lb=None,
                round_to_pow2: bool = False,
                out: Optional[BatchArrays] = None
                ) -> Tuple[BatchArrays, np.ndarray, int]:
    """Regroup a batch so packets of shard s occupy rows
    [s*per_shard, (s+1)*per_shard) (invalid-padded).

    Returns (steered_batch, scatter_index, per_shard) where
    ``scatter_index[i]`` is the steered row of original packet i — use it to
    gather per-packet outputs back into original order.

    ``out=`` scatters into a caller-owned column dict (a reusable steered
    buffer) instead of allocating; its rows must cover
    ``n_shards * per_shard`` and every batch key must be present. Rows not
    written are restored to the empty-batch defaults, so a reused buffer
    cannot leak a previous batch's records into the valid mask or the
    wire-format probes. (The pipeline's staging ring does NOT come through
    here — it scatters incrementally at ingest via ``steer_rows``; this
    variant serves whole-batch callers that want to reuse one steered
    buffer across calls.)

    Fully vectorized (argsort regroup) — this is the host half of the
    production multi-chip path, so it must keep up with the device, not just
    the dryrun (round-4 finding: the per-packet Python loop capped steering
    at ~1e5 pps)."""
    from cilium_tpu.kernels.records import reset_batch_rows
    n = batch["valid"].shape[0]
    shard = flow_shard_of(batch, n_shards, lb=lb)
    validm = np.asarray(batch["valid"], dtype=bool)
    vidx = np.nonzero(validm)[0]
    s = shard[vidx]
    counts = np.bincount(s, minlength=n_shards).astype(np.int64)
    if per_shard is None:
        per_shard = int(max(1, counts.max()))
        if round_to_pow2:
            # stabilize the steered shape across batches (each distinct
            # n_shards*per_shard re-traces the jit): round up to a power of 2
            per_shard = 1 << (per_shard - 1).bit_length()
    elif counts.max() > per_shard:
        raise ValueError("per_shard too small for steering")
    rows = steer_rows(s, n_shards, per_shard, counts=counts)
    src = vidx
    total = n_shards * per_shard
    if out is None:
        out = {k: np.zeros((total,) + v.shape[1:], dtype=v.dtype)
               for k, v in batch.items()}
        out["http_method"][:] = 255
    else:
        if out["valid"].shape[0] < total:
            raise ValueError(
                f"steer out= buffer has {out['valid'].shape[0]} rows, "
                f"need {total}")
        reset_batch_rows(out, 0, total)
    scatter = np.full((n,), -1, dtype=np.int64)
    scatter[src] = rows
    for k, v in batch.items():
        out[k][rows] = np.asarray(v)[src]
    return out, scatter, per_shard


def unsteer_outputs(out: Dict[str, np.ndarray],
                    scatter: np.ndarray) -> Dict[str, np.ndarray]:
    """Map steered per-packet outputs back to original packet order.
    Packets that were invalid get zeros."""
    n = scatter.shape[0]
    result = {}
    safe = np.where(scatter >= 0, scatter, 0)
    for k, v in out.items():
        gathered = np.asarray(v)[safe]
        gathered[scatter < 0] = 0
        result[k] = gathered
    return result


# --------------------------------------------------------------------------- #
# Array preparation for the mesh
# --------------------------------------------------------------------------- #
def pad_snapshot_tensors(tensors: Dict[str, np.ndarray],
                         n_rule_shards: int) -> Dict[str, np.ndarray]:
    """Pad verdict id-class rows to a multiple of the rules axis. Padded rows
    are all-MISS (never gathered: id_class_of never points at them)."""
    if n_rule_shards <= 1:
        return tensors
    v = tensors["verdict"]
    rows = v.shape[2]
    padded = -(-rows // n_rule_shards) * n_rule_shards
    if padded != rows:
        pad = np.zeros((v.shape[0], v.shape[1], padded - rows, v.shape[3]),
                       dtype=v.dtype)
        tensors = dict(tensors)
        tensors["verdict"] = np.concatenate([v, pad], axis=2)
    return tensors


def shard_ct_arrays(ct: Dict[str, np.ndarray],
                    n_flow_shards: int) -> Dict[str, np.ndarray]:
    """Validate the CT capacity divides into power-of-two local tables."""
    cap = ct["expiry"].shape[0]
    local = cap // n_flow_shards
    if local * n_flow_shards != cap or (local & (local - 1)):
        raise ValueError(
            f"CT capacity {cap} must split into {n_flow_shards} "
            f"power-of-two shards")
    return ct


def degraded_ct_capacity(capacity: int, n_flow_shards: int) -> int:
    """The largest CT capacity <= ``capacity`` that still splits into
    ``n_flow_shards`` power-of-two local tables — the table geometry a
    remesh onto a NON-power-of-two survivor count rehashes into (e.g.
    4096 slots at 3 shards → 1024·3 = 3072). Healing back to a
    power-of-two width recovers the full configured capacity."""
    local = capacity // n_flow_shards
    if local < 1:
        raise ValueError(
            f"CT capacity {capacity} cannot split across "
            f"{n_flow_shards} shards")
    local = 1 << (local.bit_length() - 1)
    return local * n_flow_shards


def drop_ct_shard(arrays: Dict[str, np.ndarray], shard: int,
                  n_shards: int) -> int:
    """Zero one flow shard's slot range ``[shard*local, (shard+1)*local)``
    of a host-gathered CT table, in place. The honest-loss step of remesh
    salvage: on the CPU smoke rig a "killed" virtual device's shard is
    still physically gatherable, so salvage deliberately drops it — the
    lost shard's flows must cold-learn under the established-fingerprint
    grace window exactly as they would on real hardware. Returns the
    number of live entries dropped."""
    cap = arrays["expiry"].shape[0]
    local = cap // n_shards
    lo, hi = shard * local, (shard + 1) * local
    n_live = int((arrays["expiry"][lo:hi] > 0).sum())
    for k, v in arrays.items():
        v[lo:hi] = 0
    return n_live


def _reverse_key_words(keys: np.ndarray) -> np.ndarray:
    """[M,10] forward CT key words → reverse orientation (addr/port swap,
    direction flip) — the host inverse of records.ct_key_words(reverse)."""
    rev = keys.copy()
    rev[:, 0:4] = keys[:, 4:8]
    rev[:, 4:8] = keys[:, 0:4]
    rev[:, 8] = ((keys[:, 8] << np.uint32(16))
                 | (keys[:, 8] >> np.uint32(16)))
    rev[:, 9] = ((keys[:, 9] & np.uint32(0xFFFFFF00))
                 | (np.uint32(1) - (keys[:, 9] & np.uint32(0xFF))))
    return rev


def rehash_ct_arrays(arrays: Dict[str, np.ndarray], n_flow_shards: int,
                     probe_depth: int = PROBE_DEPTH,
                     capacity: Optional[int] = None
                     ) -> Tuple[Dict[str, np.ndarray], int]:
    """Re-place every live CT entry at the open-addressed position the device
    probe expects for the given shard layout (shard = direction-normalized
    hash, local slot = key hash mod the per-shard table, linear probe).

    Checkpoint portability: an exported table's slot placement is only valid
    for the geometry that wrote it (the bounded oracle-backed fake and a
    single-chip table hash over the FULL capacity; a sharded table hashes
    per shard — and legacy fake exports were dense-from-0). Rehashing on
    import makes restore correct across backends and shard counts. Returns
    (new_arrays, n_dropped) — entries whose probe window is exhausted are
    dropped (counted; a restore-time drop means the flow re-learns as NEW
    on its next packet — unlike a live insert exhaustion, which since the
    insert-when-full contract fails CLOSED with DROP ``CT_FULL``).
    ``capacity`` resizes the table while rehashing (checkpoint restored into
    a backend configured with a different ct_capacity).
    """
    cap = int(capacity or arrays["expiry"].shape[0])
    local = cap // n_flow_shards
    if local * n_flow_shards != cap or (local & (local - 1)):
        raise ValueError(
            f"CT capacity {cap} must split into {n_flow_shards} "
            f"power-of-two shards")
    live = np.nonzero(arrays["expiry"] > 0)[0]
    m = live.shape[0]
    keys = arrays["keys"][live].astype(np.uint32)
    fwd_h = hash_words_np(keys)
    shard = ((fwd_h ^ hash_words_np(_reverse_key_words(keys)))
             % np.uint32(n_flow_shards)).astype(np.int64)
    home = (fwd_h & np.uint32(local - 1)).astype(np.int64)
    base = shard * local

    new = {k: np.zeros((cap,) + v.shape[1:], dtype=v.dtype)
           for k, v in arrays.items()}
    occupied = np.zeros(cap, dtype=bool)
    placed_slot = np.full(m, -1, dtype=np.int64)
    pending = np.ones(m, dtype=bool)
    idx = np.arange(m, dtype=np.int64)
    for r in range(probe_depth):
        t = base + ((home + r) & (local - 1))
        attempt = pending & ~occupied[t]
        claim = np.full(cap + 1, m, dtype=np.int64)
        np.minimum.at(claim, np.where(attempt, t, cap), idx)
        winner = attempt & (claim[t] == idx)
        occupied[t[winner]] = True
        placed_slot[winner] = t[winner]
        pending = pending & ~winner
    ok = placed_slot >= 0
    src, dst = live[ok], placed_slot[ok]
    for k in arrays:
        new[k][dst] = arrays[k][src]
    return new, int(pending.sum())


# --------------------------------------------------------------------------- #
# The meshed classify step
# --------------------------------------------------------------------------- #
def make_sharded_classify_fn(mesh, probe_depth: int = PROBE_DEPTH,
                             v4_only: bool = False, donate_ct: bool = True,
                             slab: bool = False):
    """shard_map'd + jitted classify step over ``mesh`` ('flows','rules').

    Each shard runs the single-chip ``classify_step`` on its local arrays
    inside the shard_map body; with rule sharding the ladder's psum over
    'rules' is the body's one collective besides the counters'.

    Call with (tensors, ct, batch, now, world_index) where batch rows are
    steered (steer_batch) and verdict rows padded (pad_snapshot_tensors).

    ``batch`` may be the column dict (tests, the zero-copy-disabled path)
    OR a packed wire — a single [N, words] uint32 array or an
    ``(wire, path_dict)`` L7-dict pair (kernels/records pack formats, the
    same contiguous-buffer transfer the single-chip path ships). The wire
    rows shard over 'flows' (each chip unpacks only its own segment, fused
    into the classify pipeline); the path dict replicates. This is what
    lets the sharded serving path pack in place into one pooled buffer
    whose per-shard segments ARE the per-chip transfers.

    ``slab=True``: the step returns ``(OutSlab, new_ct)`` instead of
    ``(out, new_ct, counters)``, the return form of
    ``make_classify_fn(slab=True)`` on a mesh: each chip packs its own
    rows of every out column and its copy of the psummed counters into
    one uint32 segment (kernels/records.pack_out_jnp) as the shard_map
    body's last stage, and the segments come back as ONE vector sharded
    over 'flows'. ``records.unpack_out(np.asarray(s.words), s.layout,
    shards=mesh.shape["flows"])`` is ``(out, counters)`` again, bit for
    bit. The serving path's return form (runtime/datapath.py); the column
    form stays the default for tests, chip_smoke.py and __graft_entry__.py.
    """
    from cilium_tpu.kernels.classify import classify_step

    rule_axis = "rules" if mesh.shape["rules"] > 1 else None

    def body(tensors, ct, batch, now, world_index):
        return classify_step(
            tensors, ct, batch, now, world_index,
            probe_depth=probe_depth, v4_only=v4_only, rule_axis=rule_axis)

    return _make_meshed_classify(mesh, body, donate_ct=donate_ct, slab=slab)


def make_unsteered_classify_fn(mesh, probe_depth: int = PROBE_DEPTH,
                               v4_only: bool = False, donate_ct: bool = True,
                               slab: bool = False):
    """shard_map'd + jitted DEVICE-RSS classify step over ``mesh``
    ('flows','rules'): batch rows shard over 'flows' in plain ARRIVAL
    order — no host steering, no placement semantics in the row layout —
    and cross-shard CT lookups/inserts resolve with the ring ``ppermute``
    exchange (parallel/exchange.py) inside the shard_map body. Outputs
    come back in the same arrival row order (FIFO — no un-steer gather
    anywhere), bit-identical to what the steered path computes for the
    same rows, CT_FULL tail-evict order included (the gathered request
    set preserves global row order, and the owner-side CT stage IS the
    steered path's ct_update_stage).

    The collective set inside the body stays bounded and documented: the
    counter psum over 'flows' (+ the policy-cell psum over 'rules' when
    rule-sharded) plus the 2(n-1) ring ppermute hops of the exchange.
    The only shape contract: batch rows must divide the 'flows' axis (each
    chip takes an equal arrival-order slice).

    Accepts the same batch forms as :func:`make_sharded_classify_fn`
    (column dict, packed wire, (wire, path_dict)) and gives the same
    return forms (``slab=True``: ``(OutSlab, new_ct)``, the segments in
    shard order, which here is arrival order)."""
    from cilium_tpu.parallel.exchange import classify_step_exchange

    n_flow = mesh.shape["flows"]
    rule_axis = "rules" if mesh.shape["rules"] > 1 else None

    def body(tensors, ct, batch, now, world_index):
        return classify_step_exchange(
            tensors, ct, batch, now, world_index,
            axis_name="flows", n_shards=n_flow,
            probe_depth=probe_depth, v4_only=v4_only, rule_axis=rule_axis)

    return _make_meshed_classify(mesh, body, donate_ct=donate_ct, slab=slab)


def _make_meshed_classify(mesh, body, donate_ct: bool = True,
                          slab: bool = False):
    """The shared shard_map/jit plumbing behind both meshed classify
    variants: spec construction, the per-(tensor-key-set, batch-kind) jit
    cache, device-side wire unpack, the counter psum, and (``slab``) the
    per-chip pack of the results into one verdict slab segment."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from cilium_tpu.kernels.classify import COUNTER_KEYS, OutSlab
    from cilium_tpu.kernels.records import pack_out_jnp

    rule_sharded = mesh.shape["rules"] > 1

    def local_fn(tensors, ct, batch, now, world_index):
        out, new_ct, counters = body(tensors, ct, batch, now, world_index)
        # counters are global: reduce over 'flows' only — along 'rules' the
        # batch is replicated and every shard computes identical counts
        # (summing there would multiply by the rules-axis size)
        counters = {k: jax.lax.psum(counters[k], "flows")
                    for k in COUNTER_KEYS}
        if slab:
            # this chip's rows and its copy of the (now global) counters:
            # one segment of the mesh's slab, under one layout for all
            return OutSlab(*pack_out_jnp(out, counters)), new_ct
        return out, new_ct, counters

    verdict_spec = P(None, None, "rules", None) if rule_sharded else P()
    ct_spec = {k: P("flows") for k in CT_PLACED_KEYS}
    batch_spec = {k: P("flows") for k in
                  ("src", "dst", "sport", "dport", "proto", "tcp_flags",
                   "is_v6", "ep_slot", "direction", "http_method",
                   "http_path", "valid")}
    out_spec = {k: P("flows") for k in
                ("allow", "reason", "status", "ct_full", "remote_identity",
                 "redirect", "matched_rule", "lpm_prefix", "ct_state_pre",
                 "svc", "nat_dst", "nat_dport", "rnat",
                 "rnat_src", "rnat_sport")}
    counters_spec = {k: P() for k in COUNTER_KEYS}
    # the slab's words go out one contiguous segment a chip; the single
    # P('flows') is a prefix of the whole OutSlab (its layout is static
    # metadata of the output tree, as on one chip)
    results_spec = ((P("flows"), ct_spec) if slab
                    else (out_spec, ct_spec, counters_spec))

    def local_fn_packed(tensors, ct, wire, now, world_index):
        # device-side unpack of the local wire segment; the width dispatch
        # happens at trace time exactly like make_classify_fn(packed=True)
        from cilium_tpu.kernels.records import unpack_wire_jnp
        return local_fn(tensors, ct, unpack_wire_jnp(wire), now, world_index)

    # The snapshot's tensor key-set varies (LB tensors are elided when no
    # frontend exists), and shard_map in_specs must mirror the exact pytree —
    # so build + cache one shard_map'd jit per (key-set, batch kind).
    # Everything except the verdict is replicated (LB state included: small,
    # read-only, gathered per packet).
    jits: Dict[Any, Any] = {}

    def call(tensors, ct, batch, now, world_index):
        if isinstance(batch, dict):
            kind = "dict"
        elif isinstance(batch, (tuple, list)):
            batch = tuple(batch)
            kind = f"wire_dict{len(batch)}"
        else:
            kind = "wire"
        key = (frozenset(tensors), kind)
        fn = jits.get(key)
        if fn is None:
            tensors_spec = {k: (verdict_spec if k == "verdict" else P())
                            for k in tensors}
            if kind == "dict":
                bspec: Any = batch_spec
                body = local_fn
            else:
                # wire rows shard over 'flows'; every trailing dictionary
                # ((wire, path_dict) or (wire, addr_dict, path_dict))
                # replicates — the spec mirrors the tuple arity
                bspec = (P("flows"),) + (P(),) * (len(batch) - 1) \
                    if kind.startswith("wire_dict") else P("flows")
                body = local_fn_packed
            fn = jax.jit(shard_map(
                body, mesh=mesh,
                in_specs=(tensors_spec, ct_spec, bspec, P(), P()),
                out_specs=results_spec,
                check_vma=False,
            ), donate_argnums=(1,) if donate_ct else ())
            jits[key] = fn
        return fn(tensors, ct, batch, now, world_index)

    return call
