"""Device-side RSS: the in-kernel ICI ring ``ppermute`` CT exchange.

The steered serving path (parallel/mesh.py) pays a host tax on every
batch: rows are pre-binned in the feeder, scattered into per-shard staging
segments, and MUST land on their CT shard before dispatch — the eBPF
datapath's per-CPU RSS analog implemented in Python. This module is the
device-side alternative SURVEY §5 names: each chip classifies whatever
rows arrive on it (arrival order, no placement semantics), computes the
flow→shard hash on-device, and resolves cross-shard CT lookups/inserts
with a ring ``ppermute`` over the ``flows`` axis.

The exchange is two static ring phases around one owner-side CT stage:

1. **request gather** (``ring_all_gather``, n-1 hops): every chip's local
   request buffer — the post-DNAT forward CT keys plus the few bits the CT
   stage needs (tcp_flags, validity, the would-be allow for hit/new rows,
   the rev-NAT id to record) packed into one fixed-shape ``[L, REQ_WORDS]``
   uint32 array — rotates around the ring, so after n-1 neighbor hops every
   chip holds all n chips' requests indexed by origin. Flattened in origin
   order, the gathered rows ARE the bucket's global row order, which is
   what keeps the insert conflict/tail-evict resolution bit-identical to
   the steered path (relative order within a shard is arrival order in
   both layouts).
2. **owner-side CT stage** (``ct_exchange_serve``): each chip masks the
   gathered rows to the flows whose direction-normalized hash makes THIS
   shard their home, probes both orientations against its local table
   (the rev-CT probe rides the same exchange — each leg's key travels
   explicitly, so asymmetric DSR/NAT legs whose forward and reverse
   orientations hash to different chips are expressible by masking each
   probe by its own key's home; today's symmetric hash makes the two homes
   coincide, which is exactly what keeps device mode bit-identical to host
   steering), and runs the SAME insert-when-full + aggregate-apply stage
   (kernels/classify.ct_update_stage) the steered path runs — one source
   of the CT mutation semantics, including CT_FULL tail-evict order.
3. **reply scatter** (``ring_reduce_scatter``, n-1 hops): each owner's
   replies — est/reply/ct_full bits + the batch-start rev-NAT id, masked
   to the rows it owns — ride home as ``[n, L, REP_WORDS]`` chunks that
   accumulate around the ring (each row has exactly one owner, so the sum
   is a routing, not a reduction).

Everything else — LB/DNAT, the LPM walk, the policy ladder, L7, verdict
composition, the counters — runs locally on the arrival chip via the
shared cores in kernels/classify.py (classify_pre_ct / compose_verdict /
resolve_rev_nat), so the shard_map body's collective set stays bounded:
the existing counter/rules psums plus these 2(n-1) ring ppermute hops.
No host round-trips inside the classify step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cilium_tpu.compile.ct_layout import PROBE_DEPTH
from cilium_tpu.kernels import conntrack as ctk
from cilium_tpu.kernels.classify import (classify_pre_ct, compose_verdict,
                                         ct_update_stage, resolve_rev_nat,
                                         tally_by_reason_dir, tally_l7)
from cilium_tpu.kernels.hashing import hash_words_jnp
from cilium_tpu.utils import constants as C

#: request row layout ([L, REQ_WORDS] uint32): words 0..9 = the post-DNAT
#: forward CT key, 10 = tcp_flags, 11 = meta bits (valid | allow_if_hit<<1
#: | allow_if_new<<2), 12 = the rev-NAT id to record on a fresh insert
REQ_WORDS = 13
#: reply row layout ([L, REP_WORDS] uint32): word 0 = est | reply<<1 |
#: ct_full<<2, word 1 = the batch-start CT entry rev-NAT id at the hit slot
REP_WORDS = 2


def exchange_bytes(rows: int, n_shards: int) -> int:
    """Worst-case per-mesh bytes the exchange materializes for one
    ``rows``-row bucket: every chip holds the full gathered request set
    [n, L, REQ] plus the travelling reply chunks [n, L, REP] — the number
    the HBM ledger's ``exchange`` group and the ``rss_exchange`` resource
    row report."""
    return n_shards * rows * (REQ_WORDS + REP_WORDS) * 4


def flow_shard_of_keys(fwd_keys, rev_keys, n_shards: int):
    """Direction-normalized shard index per key pair — the device twin of
    parallel/mesh.flow_shard_of's hash (XOR of forward and reverse key
    hashes is symmetric, so both directions of a flow agree), over the
    already-DNAT-translated keys. Bit-identical to the host steer by the
    shared hash_words implementation."""
    h = hash_words_jnp(fwd_keys) ^ hash_words_jnp(rev_keys)
    return (h % jnp.uint32(n_shards)).astype(jnp.int32)


# --------------------------------------------------------------------------- #
# The ring primitives (explicit ppermute hops — the static ICI schedule)
# --------------------------------------------------------------------------- #
def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def ring_all_gather(x, axis_name: str, n: int):
    """[L, W] per chip → [n, L, W] indexed by ORIGIN chip, via n-1 ring
    ``ppermute`` hops (one neighbor hop per step). ``jax.lax.all_gather``
    would lower to the same ring on ICI; the explicit form keeps the
    collective set auditable — the shard_map body provably contains
    nothing but psums and these hops."""
    if n == 1:
        return x[None]
    my = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n)
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, x, my, 0)
    buf = x
    for t in range(1, n):
        buf = jax.lax.ppermute(buf, axis_name, perm)
        # after t forward hops this chip holds the buffer that ORIGINATED
        # t positions behind it on the ring
        out = jax.lax.dynamic_update_index_in_dim(
            out, buf, jnp.mod(my - t, n), 0)
    return out


def ring_reduce_scatter(parts, axis_name: str, n: int):
    """[n, L, W] per chip (chunk c = this chip's contribution to chip c's
    rows) → [L, W]: chunk c starts at chip c+1, accumulates every chip's
    contribution over n-1 ring hops, and arrives home summed. With each
    row owned by exactly one shard (the exchange's reply masking) the sum
    is pure routing — disjoint writers, no actual reduction."""
    if n == 1:
        return parts[0]
    my = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n)
    acc = jax.lax.dynamic_index_in_dim(parts, jnp.mod(my - 1, n), 0,
                                       keepdims=False)
    for t in range(1, n):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + jax.lax.dynamic_index_in_dim(
            parts, jnp.mod(my - 1 - t, n), 0, keepdims=False)
    return acc


# --------------------------------------------------------------------------- #
# Exchange buffer packing (fixed shapes → static collective schedule)
# --------------------------------------------------------------------------- #
def pack_requests(fwd_keys, tcp_flags, valid, allow_if_hit, allow_if_new,
                  rev_nat_vals):
    """→ [L, REQ_WORDS] uint32 (layout at the module constants)."""
    meta = (valid.astype(jnp.uint32)
            | (allow_if_hit.astype(jnp.uint32) << jnp.uint32(1))
            | (allow_if_new.astype(jnp.uint32) << jnp.uint32(2)))
    return jnp.concatenate([
        fwd_keys.astype(jnp.uint32),
        tcp_flags.astype(jnp.uint32)[:, None],
        meta[:, None],
        rev_nat_vals.astype(jnp.uint32)[:, None],
    ], axis=-1)


def unpack_requests(req):
    fwd_keys = req[:, :10]
    tcp_flags = req[:, 10].astype(jnp.int32)
    meta = req[:, 11]
    valid = (meta & jnp.uint32(1)) != 0
    allow_if_hit = (meta & jnp.uint32(2)) != 0
    allow_if_new = (meta & jnp.uint32(4)) != 0
    rev_nat_vals = req[:, 12].astype(jnp.int32)
    return fwd_keys, tcp_flags, valid, allow_if_hit, allow_if_new, \
        rev_nat_vals


def pack_replies(est, reply, ct_full, entry_rnat, mine):
    """→ [G, REP_WORDS] uint32, masked to the rows THIS shard owns so the
    homeward reduce-scatter has exactly one writer per row."""
    flags = (est.astype(jnp.uint32)
             | (reply.astype(jnp.uint32) << jnp.uint32(1))
             | (ct_full.astype(jnp.uint32) << jnp.uint32(2)))
    rnat = jnp.where(mine, entry_rnat.astype(jnp.uint32), jnp.uint32(0))
    return jnp.stack([flags, rnat], axis=-1)


def unpack_replies(rep):
    flags = rep[:, 0]
    est = (flags & jnp.uint32(1)) != 0
    reply = (flags & jnp.uint32(2)) != 0
    ct_full = (flags & jnp.uint32(4)) != 0
    entry_rnat = rep[:, 1].astype(jnp.int32)
    return est, reply, ct_full, entry_rnat


# --------------------------------------------------------------------------- #
# The owner-side CT stage
# --------------------------------------------------------------------------- #
def ct_exchange_serve(ct, req_flat, axis_name: str, n_shards: int, now,
                      probe_depth: int = PROBE_DEPTH):
    """Serve the gathered request set against THIS chip's local CT shard:
    probe pair → est/reply/new → insert-when-full → aggregate apply →
    batch-start rev-NAT read — the exact CT stage classify_step runs,
    over exactly the rows whose flow hash homes here, in global bucket
    row order (origin-major). Foreign rows are valid-masked out; their
    keys can never match this shard's entries anyway (flows only insert
    at their home), so hit sets, protected slots and eviction victims are
    identical to the steered layout's.

    → (rep [G, REP_WORDS] uint32 — replies masked to owned rows,
    new_ct, insert_fail uint32 scalar, n_evicted uint32 scalar)."""
    fwd_keys, tcp_flags, valid, allow_if_hit, allow_if_new, rev_nat_vals = \
        unpack_requests(req_flat)
    rev_keys = ctk.reverse_key_words_jnp(fwd_keys)
    my = jax.lax.axis_index(axis_name)
    # each probe leg routes by its own key pair's home; the symmetric hash
    # makes the forward and reverse orientations agree, so one mask serves
    # both probes (an asymmetric DSR hash would split this into per-leg
    # masks — the schedule would not change)
    mine = flow_shard_of_keys(fwd_keys, rev_keys, n_shards) == my
    valid = valid & mine

    fwd_slot, rev_slot = ctk.ct_probe_pair(ct, fwd_keys, rev_keys, now,
                                           probe_depth)
    est = valid & (fwd_slot >= 0)
    reply = valid & ~est & (rev_slot >= 0)
    new = valid & ~est & ~reply
    hit = est | reply
    hit_slot = jnp.where(est, fwd_slot, jnp.where(reply, rev_slot, 0))
    # the would-be allow the origin chip composed without est/reply: pick
    # the branch the probe resolved (foreign rows are gated by new=False /
    # hit=False, so their value is irrelevant)
    allow = jnp.where(hit, allow_if_hit, allow_if_new)

    proto = (fwd_keys[:, 9] >> jnp.uint32(8)).astype(jnp.int32)
    new_ct, ct_full, entry_rnat, n_evicted = ct_update_stage(
        ct, fwd_keys, proto, tcp_flags, hit, hit_slot, reply, new, allow,
        rev_nat_vals, now, probe_depth)
    rep = pack_replies(est, reply, ct_full, entry_rnat, mine)
    return rep, new_ct, ct_full.sum().astype(jnp.uint32), n_evicted


# --------------------------------------------------------------------------- #
# The unsteered classify step (runs inside the shard_map body)
# --------------------------------------------------------------------------- #
def classify_step_exchange(tensors, ct, batch, now, world_index=0, *,
                           axis_name: str = "flows", n_shards: int,
                           probe_depth: int = PROBE_DEPTH,
                           v4_only: bool = False, rule_axis=None,
                           lb_probe_depth: int = 8):
    """→ (out, new_ct, counters) — the device-RSS twin of
    kernels/classify.classify_step over THIS chip's arrival-order rows.

    Structure: the shared pre-CT stage (LB → LPM → ladder + L7) runs
    locally, the CT stage resolves through the ring ppermute exchange
    (module docstring), and the verdict composes locally from the replies
    — every semantic block is the same function the steered path runs,
    so bit-identity holds by construction."""
    pre = classify_pre_ct(tensors, batch, world_index, v4_only=v4_only,
                          rule_axis=rule_axis, lb_probe_depth=lb_probe_depth)
    b = pre["batch"]
    valid = pre["valid"]
    direction = b["direction"]
    no_backend = pre["no_backend"]

    # the would-be allow for each probe outcome, composed through the one
    # shared compose_verdict (est/reply pinned) so the owner's insert
    # decision can never drift from the verdict the origin composes later
    ones = jnp.ones_like(valid)
    zeros = jnp.zeros_like(valid)
    allow_if_hit = compose_verdict(
        pre["decision"], pre["enforced"], pre["cell_redirect"],
        pre["l7_fail"], ones, zeros, valid)[0]
    allow_if_new = compose_verdict(
        pre["decision"], pre["enforced"], pre["cell_redirect"],
        pre["l7_fail"], zeros, zeros, valid)[0]

    req = pack_requests(pre["fwd_keys"], b["tcp_flags"], valid,
                        allow_if_hit, allow_if_new, pre["rev_nat"])
    local_rows = req.shape[0]
    # the three phases carry names into the compiled program's op metadata
    # (mesh programs only), so a device trace can tell them apart
    with jax.named_scope("rss.request_gather"):
        gathered = ring_all_gather(req, axis_name, n_shards)
    with jax.named_scope("rss.owner_ct"):
        rep_all, new_ct, insert_fail, n_evicted = ct_exchange_serve(
            ct, gathered.reshape(n_shards * local_rows, REQ_WORDS),
            axis_name, n_shards, now, probe_depth)
    with jax.named_scope("rss.reply_scatter"):
        rep = ring_reduce_scatter(
            rep_all.reshape(n_shards, local_rows, REP_WORDS), axis_name,
            n_shards)
    est, reply, ct_full, entry_rnat = unpack_replies(rep)

    # local verdict composition from the replies — the same 3-5 → 6b → 7
    # tail classify_step runs
    allow, reason, status, redirect = compose_verdict(
        pre["decision"], pre["enforced"], pre["cell_redirect"],
        pre["l7_fail"], est, reply, valid)
    matched_rule = jnp.where(valid & pre["enforced"], pre["mrule"],
                             jnp.int32(-1)).astype(jnp.int32)
    l7_tally = tally_l7(b["http_method"], b["http_path"], valid, redirect,
                        reason)
    reason = jnp.where(no_backend, int(C.DropReason.NO_SERVICE), reason)
    allow = allow & ~ct_full
    reason = jnp.where(ct_full, int(C.DropReason.CT_FULL), reason)
    rnat, rnat_src, rnat_sport = resolve_rev_nat(
        tensors, entry_rnat, reply, b["src"], b["sport"])
    counted = valid | no_backend
    by_reason_dir = tally_by_reason_dir(reason, direction, counted)
    counters = {
        "by_reason_dir": by_reason_dir,
        # owner-side totals: each chip counts the gathered rows IT served;
        # the caller's psum over 'flows' yields the same global totals the
        # steered layout's per-chip sums produce
        "insert_fail": insert_fail,
        "ct_evicted": n_evicted,
        **pre["tally"],
        **l7_tally,
    }
    out = {
        "allow": allow,
        "reason": reason,
        "status": status,
        "ct_full": ct_full,
        "remote_identity": pre["remote_identity"],
        "redirect": redirect,
        "matched_rule": matched_rule,
        "lpm_prefix": pre["lpm_prefix"],
        "ct_state_pre": status,
        "svc": pre["svc"] & valid,
        "nat_dst": b["dst"],
        "nat_dport": b["dport"].astype(jnp.int32),
        "rnat": rnat,
        "rnat_src": rnat_src,
        "rnat_sport": rnat_sport,
    }
    return out, new_ct, counters
