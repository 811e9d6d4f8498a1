"""The fused classify step — one jitted program per batch (the analog of one
eBPF datapath run over a batch of packets; SURVEY.md §3.3: "TPU equivalent:
one fused kernel: gather(ipcache-LPM) → conntrack probe → policy
wildcard-ladder as masked [compile-time] resolution → verdict + CT update,
batched over N headers").

Branch-free: every packet takes every path, masks select. XLA fuses the
elementwise pipeline between the gathers; the scatters at the end form the
CT write phase.

The interior (LPM walk → CT probe pair → policy ladder + L7 + verdict
composition) and the CT insert/apply phase are plain ``jnp`` operations:
one program, the one every chip has served, held to the host oracle by
tests/test_parity.py. The deterministic-winner semantics of the CT write
phase live in kernels/conntrack.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any

import jax
import jax.numpy as jnp

from cilium_tpu.compile.ct_layout import PROBE_DEPTH
from cilium_tpu.compile.lpm import PFX_LEN_MASK
from cilium_tpu.kernels import conntrack as ctk
from cilium_tpu.kernels.l7 import l7_match_batch
from cilium_tpu.kernels.lb import lb_step
from cilium_tpu.kernels.lpm import (SCOPE_V4 as SCOPE_LPM_V4,  # noqa: F401
                                    SCOPE_V6 as SCOPE_LPM_V6,  # noqa: F401
                                    lpm_lookup_prov_batch)
from cilium_tpu.kernels.policy import policy_lookup_batch
from cilium_tpu.utils import constants as C

N_REASON_BINS = C.DROP_REASON_BINS   # counter-tensor geometry (one source)

#: the two pre-CT kernels' names in every program's op metadata, so that a
#: profiler trace's device time can be read by kernel (the trace's readers
#: look for these strings; a program loaded from a compile cache written
#: before the scopes existed carries none, the cache key leaves metadata out)
SCOPE_LB = "lb.step"
SCOPE_LPM = "lpm.walk"
#: ... and inside it ``SCOPE_LPM_V4`` / ``SCOPE_LPM_V6`` ("lpm.walk.v4",
#: "lpm.walk.v6": kernels/lpm.py, which names each family's chain)
#: ... and of the three counters of that stage, so that what they cost the
#: device can be read the same way
SCOPE_TALLY = "pre_ct.tally"
#: the L7 lane's match (``kernels/records.py`` names the path dictionary's
#: unpacking ``l7.unpack``), in a program whose snapshot holds an L7 set
SCOPE_L7 = "l7.match"

#: every key of the step's ``counters`` group (the meshed step psums and
#: specs them by this list)
COUNTER_KEYS = ("by_reason_dir", "insert_fail", "ct_evicted",
                "lb_translated", "lb_no_backend", "lpm_rows",
                "l7_checked", "l7_refused")


def compose_verdict(decision, enforced, cell_redirect, l7_fail,
                    est, reply, valid):
    """Step 5 of the datapath: (decision, l7_fail) → allow/reason/status/
    redirect. The single source of the composition semantics: the step
    composes once after its probe, the device-RSS exchange
    (parallel/exchange.py) before the hop, to know what to insert, and
    again after it; the L7-gating inputs (``cell_redirect``/``l7_fail``)
    come from :func:`interior_pre_core`."""
    hit = est | reply
    new_allow = jnp.where(
        decision == C.VERDICT_DENY, False,
        jnp.where(decision == C.VERDICT_MISS, ~enforced,
                  ~l7_fail))  # ALLOW always passes; REDIRECT unless l7_fail
    allow = jnp.where(hit, ~l7_fail, new_allow) & valid
    reason = jnp.where(
        hit,
        jnp.where(l7_fail, int(C.DropReason.POLICY_L7), int(C.DropReason.OK)),
        jnp.where(
            decision == C.VERDICT_DENY, int(C.DropReason.POLICY_DENY),
            jnp.where(decision == C.VERDICT_MISS,
                      jnp.where(enforced, int(C.DropReason.POLICY),
                                int(C.DropReason.OK)),
                      jnp.where(l7_fail, int(C.DropReason.POLICY_L7),
                                int(C.DropReason.OK)))),
    ).astype(jnp.int32)
    status = jnp.where(est, int(C.CTStatus.ESTABLISHED),
                       jnp.where(reply, int(C.CTStatus.REPLY),
                                 int(C.CTStatus.NEW))).astype(jnp.int32)
    redirect = valid & cell_redirect
    return allow, reason, status, redirect


def interior_pre_core(tensors, ep_slot, direction, id_idx, proto,
                      dport, http_method, http_path, rule_axis=None):
    """The CT-independent half of the classify interior: policy ladder +
    L7 token match → (decision, enforced, cell_redirect, l7_fail, mrule).
    Nothing here depends on the CT probe result — only the final
    ``compose_verdict`` does — which is exactly what lets the device-RSS
    exchange path (parallel/exchange.py) run this BEFORE the ring
    ``ppermute`` CT hop and compose after the replies land. One source of
    the ladder/L7 semantics for every step (:func:`classify_pre_ct` calls
    it)."""
    decision, l7_cell, enforced, mrule = policy_lookup_batch(
        tensors, ep_slot, direction, id_idx, proto, dport,
        rule_axis=rule_axis)
    # L7-lite: the CURRENT policy cell's rules apply to every packet with
    # tokens — new and established flows alike (the per-request proxy
    # semantics; CT entries carry no L7 state, so policy swaps need no
    # remap)
    has_tokens = has_l7_tokens(http_method, http_path)
    cell_redirect = decision == C.VERDICT_REDIRECT
    set_to_check = jnp.where(cell_redirect, l7_cell, 0)
    # set 0 is "none": a snapshot with no L7 set has a one-row rule tensor
    # and nothing to name (as a program with no frontend has no lb.step)
    with jax.named_scope(SCOPE_L7) if tensors["l7_methods"].shape[0] > 1 \
            else contextlib.nullcontext():
        l7_ok = l7_match_batch(tensors, set_to_check, http_method, http_path)
    l7_fail = has_tokens & (set_to_check > 0) & ~l7_ok
    return decision, enforced, cell_redirect, l7_fail, mrule


def has_l7_tokens(http_method, http_path):
    """[N] bool: the row carries a request (a method or a path byte)."""
    return (http_method != C.HTTP_METHOD_ANY) | (http_path != 0).any(axis=-1)


def tally_l7(http_method, http_path, valid, redirect, reason):
    """Rows the L7 lane judged, once a batch, from the composed verdict's
    own columns: ``l7_checked``, the valid rows that carry a request
    and whose cell redirects to a rule set (``redirect`` is ``valid &
    cell_redirect``, and a REDIRECT cell always names a set: ids are
    1-based), and ``l7_refused``, those of them the set refused
    (``l7_fail`` is the one way to reason POLICY_L7)."""
    return {
        "l7_checked": (redirect & has_l7_tokens(http_method, http_path)
                       ).sum().astype(jnp.uint32),
        "l7_refused": (valid & (reason == int(C.DropReason.POLICY_L7))
                       ).sum().astype(jnp.uint32),
    }


def tally_pre_ct(valid0, translated, no_backend, pfx_meta):
    """Rows the two pre-CT kernels answered, once a batch: ``lb_translated``
    (DNAT'd to a backend), ``lb_no_backend`` (a frontend with none:
    NO_SERVICE) and ``lpm_rows`` [C.LPM_PLEN_BINS], the rows valid at ingest
    by the length of the prefix their walk matched (``C.LPM_MISS_BIN``: none
    held the address, the world fallback)."""
    with jax.named_scope(SCOPE_TALLY):
        plen_bin = jnp.where(pfx_meta < 0, C.LPM_MISS_BIN,
                             pfx_meta & PFX_LEN_MASK)
        scat = jnp.where(valid0, plen_bin, C.LPM_PLEN_BINS)
        return {
            "lb_translated": translated.sum().astype(jnp.uint32),
            "lb_no_backend": no_backend.sum().astype(jnp.uint32),
            "lpm_rows": jnp.zeros((C.LPM_PLEN_BINS,), dtype=jnp.uint32).at[
                scat].add(jnp.uint32(1), mode="drop"),
        }


def classify_pre_ct(tensors, batch, world_index, *, v4_only: bool = False,
                    rule_axis=None, lb_probe_depth: int = 8):
    """Steps 0-1 of the datapath (service LB → ipcache LPM), the CT key
    derivation and the CT-independent half of steps 3-5 (ladder + L7,
    :func:`interior_pre_core`), as one pure function shared by
    :func:`classify_step` and the device-RSS exchange path
    (parallel/exchange.py) — the single source of everything that happens
    BEFORE the conntrack stage.

    Returns a dict:
      ``batch``  — the post-DNAT column dict (dst/dport rewritten),
      ``valid``  — post-LB validity (``valid & ~no_backend``),
      ``svc`` / ``rev_nat`` / ``no_backend`` — the LB columns,
      ``id_idx`` / ``remote_identity`` / ``lpm_prefix`` — the LPM result
      + provenance (masked by the ORIGINAL valid, like classify_step),
      ``fwd_keys`` / ``rev_keys`` — the post-DNAT CT key pair,
      ``tally`` — :func:`tally_pre_ct`'s three counters of this stage,
      ``decision`` / ``enforced`` / ``cell_redirect`` / ``l7_fail`` /
      ``mrule`` — the ladder and the L7 match, not yet composed: est/reply
      exist only after the probe (after the ppermute hop, on the exchange
      path)."""
    valid0 = batch["valid"]
    direction = batch["direction"]
    # 0. service LB (bpf/lib/lb.h analog): frontend match → Maglev backend
    # → DNAT. Everything downstream (LPM, CT, policy) sees the translated
    # tuple, exactly like the upstream from-container path.
    has_lb = "lb_tab_keys" in tensors
    if has_lb:
        with jax.named_scope(SCOPE_LB):
            new_dst, new_dport, rev_nat, no_backend = lb_step(
                tensors, batch, probe_depth=lb_probe_depth)
        svc = rev_nat > 0
        batch = dict(batch)
        batch["dst"] = new_dst
        batch["dport"] = new_dport
        valid = valid0 & ~no_backend
    else:
        n = valid0.shape[0]
        rev_nat = jnp.zeros((n,), dtype=jnp.int32)
        svc = jnp.zeros((n,), dtype=bool)
        no_backend = jnp.zeros((n,), dtype=bool)
        valid = valid0

    # 1. ipcache LPM: remote = dst on egress, src on ingress. The walk
    # resolves the identity index AND the winning prefix provenance
    # ((slot << 8) | plen, -1 on miss) in the same register chain
    remote_words = jnp.where((direction == C.DIR_EGRESS)[:, None],
                             batch["dst"], batch["src"])
    with jax.named_scope(SCOPE_LPM):
        id_idx, pfx_meta = lpm_lookup_prov_batch(
            tensors["lpm_v4"], tensors["lpm_v6"], remote_words,
            batch["is_v6"], default_index=world_index, v4_only=v4_only)
    remote_identity = tensors["identity_ids"][id_idx].astype(jnp.uint32)
    # provenance masking follows the same truth the columns they explain
    # use: lpm_prefix for every row that was valid at ingest (NO_SERVICE
    # rows keep their VIP-resolved identity AND its prefix), -1 otherwise
    lpm_prefix = jnp.where(valid0, pfx_meta,
                           jnp.int32(-1)).astype(jnp.int32)

    # CT key pair (post-DNAT): the reverse key is a word permutation of
    # the forward key — normalized once, derived twice
    fwd_keys, rev_keys = ctk.ct_key_words_pair(batch)

    tally = tally_pre_ct(valid0, svc & valid, no_backend, pfx_meta)
    decision, enforced, cell_redirect, l7_fail, mrule = interior_pre_core(
        tensors, batch["ep_slot"], direction, id_idx, batch["proto"],
        batch["dport"], batch["http_method"], batch["http_path"],
        rule_axis=rule_axis)
    return {
        "batch": batch, "valid": valid, "svc": svc, "rev_nat": rev_nat,
        "no_backend": no_backend, "id_idx": id_idx,
        "remote_identity": remote_identity, "lpm_prefix": lpm_prefix,
        "fwd_keys": fwd_keys, "rev_keys": rev_keys, "tally": tally,
        "decision": decision, "enforced": enforced,
        "cell_redirect": cell_redirect, "l7_fail": l7_fail, "mrule": mrule,
    }


def ct_update_stage(ct, fwd_keys, proto, tcp_flags, hit, hit_slot, reply,
                    new, allow, rev_nat_vals, now,
                    probe_depth: int = PROBE_DEPTH):
    """Step 6 (+ the 6b batch-start rev-NAT read) of the datapath: the CT
    insert-when-full + aggregate apply, shared verbatim by
    :func:`classify_step` (local rows) and the device-RSS exchange's
    owner-side stage (gathered rows) — the single source of the CT
    mutation semantics, including the tail-evict victim order. Slots this
    batch probe-hit are protected from eviction (snapshot semantics), and
    a flow whose window stays exhausted even after evicting fails CLOSED
    (``ct_full``, the CT_FULL drop the caller composes in).

    → (new_ct, ct_full [N] bool, entry_rnat [N] int32 — the batch-start
    ``rev_nat`` read at each row's hit slot, garbage where ``~hit`` and
    discarded by the caller's reply mask — and n_evicted uint32)."""
    want_insert = new & allow
    cap = ct["expiry"].shape[0]
    protected = jnp.zeros((cap,), dtype=bool).at[
        jnp.where(hit, hit_slot, cap)].set(True, mode="drop")
    new_keys, new_created, zero_mask, slot_new, fail, n_evicted = \
        ctk.ct_insert_new(ct, fwd_keys, want_insert, now, probe_depth,
                          evict=True, protected=protected)
    ct_full = fail                       # fail ⊆ want_insert ⊆ new & allow
    allow = allow & ~ct_full
    slot = jnp.where(hit, hit_slot, slot_new)
    contrib = allow & (jnp.where(hit, True, slot_new >= 0))
    new_ct = ctk.ct_apply(ct, {"proto": proto, "tcp_flags": tcp_flags},
                          slot, reply, contrib, now,
                          new_keys=new_keys, new_created=new_created,
                          zero_mask=zero_mask, rev_nat_vals=rev_nat_vals)
    # 6b read half (lb4_rev_nat analog): the CT entry's stable rev-NAT id
    # as-of the batch start — reads the PRE-apply table, exactly like
    # classify_step always did
    slot_safe = jnp.where(hit_slot >= 0, hit_slot, 0)
    entry_rnat = ct["rev_nat"][slot_safe].astype(jnp.int32)
    return new_ct, ct_full, entry_rnat, n_evicted


def resolve_rev_nat(tensors, entry_rnat, reply, src, sport):
    """Step 6b resolution: a reply on a service flow carries the CT
    entry's stable rev-NAT id → rewrite src back to the VIP. Ids whose
    service is gone resolve to an invalid row → no rewrite (fail closed;
    never another service's VIP). Shared by classify_step and the
    exchange path — the replicated ``lb_rnat_*`` tensors make this a
    purely local lookup once ``entry_rnat`` rode the reply buffer home."""
    if "lb_rnat_valid" not in tensors:
        n = reply.shape[0]
        rnat = jnp.zeros((n,), dtype=bool)
        return rnat, src, sport.astype(jnp.int32)
    n_rnat = tensors["lb_rnat_valid"].shape[0]
    rid = entry_rnat - 1
    known = (rid >= 0) & (rid < n_rnat)
    rid_safe = jnp.where(known, rid, 0)
    rnat = reply & known & tensors["lb_rnat_valid"][rid_safe]
    rnat_src = jnp.where(rnat[:, None], tensors["lb_rnat_addr"][rid_safe],
                         src)
    rnat_sport = jnp.where(rnat, tensors["lb_rnat_port"][rid_safe],
                           sport).astype(jnp.int32)
    return rnat, rnat_src, rnat_sport


def tally_by_reason_dir(reason, direction, counted):
    """Step 7: the per-reason × direction counter tensor (metricsmap
    analog) — one scatter-add, shared by every classify executor."""
    bin_idx = reason * 2 + direction
    scat = jnp.where(counted, bin_idx, N_REASON_BINS * 2)
    return jnp.zeros((N_REASON_BINS * 2,), dtype=jnp.uint32).at[scat].add(
        jnp.uint32(1), mode="drop")


def classify_step(tensors, ct, batch, now, world_index=0, *,
                  probe_depth: int = PROBE_DEPTH, v4_only: bool = False,
                  rule_axis=None, lb_probe_depth: int = 8):
    # ``world_index`` is a traced scalar (not static): it changes whenever the
    # identity table grows, and baking it in would force a re-jit per snapshot.
    # ``rule_axis`` names a mesh axis for rule-space (verdict-row) sharding.
    """→ (out, new_ct, counters).

    out: allow [N] bool, reason [N] int32 (DropReason), status [N] int32
    (CTStatus), ct_full [N] bool (new flow denied because its CT probe
    window stayed exhausted after the eviction round), remote_identity [N]
    uint32, redirect [N] bool, the provenance columns matched_rule /
    lpm_prefix / ct_state_pre [N] int32 (see the out dict below), plus the
    NAT rewrite columns the shim applies: svc [N] bool, nat_dst [N,4] uint32, nat_dport [N] int32
    (forward DNAT) and rnat [N] bool, rnat_src [N,4] uint32,
    rnat_sport [N] int32 (reply un-DNAT).
    counters: by_reason_dir [COUNTER_CELLS] uint32 (reasons x directions),
    insert_fail uint32 scalar, ct_evicted uint32 scalar (live entries
    tail-evicted by saturated inserts), :func:`tally_pre_ct`'s three and
    :func:`tally_l7`'s two."""
    # 0-1. service LB + ipcache LPM + CT key derivation, and the ladder
    # and L7 match of 3-5 — the shared pre-CT stage (classify_pre_ct; also
    # the device-RSS exchange's local prologue)
    pre = classify_pre_ct(tensors, batch, world_index, v4_only=v4_only,
                          rule_axis=rule_axis, lb_probe_depth=lb_probe_depth)
    batch = pre["batch"]
    valid = pre["valid"]
    direction = batch["direction"]
    no_backend = pre["no_backend"]
    svc = pre["svc"]
    fwd_keys, rev_keys = pre["fwd_keys"], pre["rev_keys"]

    # 2. conntrack probe (batch-start snapshot)
    fwd_slot, rev_slot = ctk.ct_probe_pair(ct, fwd_keys, rev_keys, now,
                                           probe_depth)
    est = valid & (fwd_slot >= 0)
    reply = valid & ~est & (rev_slot >= 0)
    new = valid & ~est & ~reply
    hit = est | reply
    hit_slot = jnp.where(est, fwd_slot, jnp.where(reply, rev_slot, 0))

    # 3-5. verdict composition over the ladder's and the L7 match's
    # answers. ``matched_rule`` is the ladder's provenance column
    # (kernels/policy.py): the resolved cell coordinate where a ladder
    # actually ran (valid row, enforced direction), -1 otherwise
    allow, reason, status, redirect = compose_verdict(
        pre["decision"], pre["enforced"], pre["cell_redirect"],
        pre["l7_fail"], est, reply, valid)
    matched_rule = jnp.where(valid & pre["enforced"], pre["mrule"],
                             jnp.int32(-1)).astype(jnp.int32)
    l7_tally = tally_l7(batch["http_method"], batch["http_path"], valid,
                        redirect, reason)
    reason = jnp.where(no_backend, int(C.DropReason.NO_SERVICE), reason)

    # 6 + 6b-read. CT insert-when-full + aggregate apply + the batch-start
    # rev-NAT read (ct_update_stage — shared with the exchange path's
    # owner-side stage, so the tail-evict order has one source)
    new_ct, ct_full, entry_rnat, n_evicted = ct_update_stage(
        ct, fwd_keys, batch["proto"], batch["tcp_flags"], hit, hit_slot,
        reply, new, allow, pre["rev_nat"], now, probe_depth)
    allow = allow & ~ct_full
    reason = jnp.where(ct_full, int(C.DropReason.CT_FULL), reason)

    # 6b. reply un-DNAT resolution against the replicated lb_rnat_* planes
    rnat, rnat_src, rnat_sport = resolve_rev_nat(
        tensors, entry_rnat, reply, batch["src"], batch["sport"])

    # 7. counters (metricsmap analog: per reason × direction); no_backend
    # drops count under NO_SERVICE even though they are datapath-invalid
    counted = valid | no_backend
    by_reason_dir = tally_by_reason_dir(reason, direction, counted)
    counters = {
        "by_reason_dir": by_reason_dir,
        "insert_fail": ct_full.sum().astype(jnp.uint32),
        "ct_evicted": n_evicted,
        **pre["tally"],
        **l7_tally,
    }
    remote_identity = pre["remote_identity"]
    lpm_prefix = pre["lpm_prefix"]

    out = {
        "allow": allow,
        "reason": reason,
        "status": status,
        # the CT-exhaustion signal (same truth class as ``status``: a
        # datapath-internal probe/insert fact as-of classification) — the
        # shadow auditor captures it so oracle.replay can re-derive the
        # CT_FULL deny without modeling the live table's occupancy
        "ct_full": ct_full,
        "remote_identity": remote_identity,
        "redirect": redirect,
        # match provenance (ISSUE 11): the evidence behind the verdict —
        # which policy cell the ladder resolved (matched_rule), which
        # ipcache prefix won the LPM walk (lpm_prefix, (slot<<8)|plen),
        # and the CT probe class as-of classification (ct_state_pre; an
        # explicit alias of ``status``, pinned as its own column so the
        # provenance contract survives any future post-mutation semantics
        # of status). Bit-identical to the oracle's.
        "matched_rule": matched_rule,
        "lpm_prefix": lpm_prefix,
        "ct_state_pre": status,
        "svc": svc & valid,
        "nat_dst": batch["dst"],
        "nat_dport": batch["dport"].astype(jnp.int32),
        "rnat": rnat,
        "rnat_src": rnat_src,
        "rnat_sport": rnat_sport,
    }
    return out, new_ct, counters


#: make_classify_fn memo: repeated snapshot placements / engine restarts
#: previously built a FRESH closure (and so a fresh jit cache) per call —
#: every placement re-traced shapes the daemon had already compiled. One
#: jitted fn per static-config key; jax's own cache then dedupes per shape.
#:
#: LRU-bounded: a long-lived daemon cycling many distinct static configs
#: (probe depths, lb depths, return forms across restarts/tests) must not
#: grow the memo — and the jit caches it pins — without bound. Cap
#: overridable via CILIUM_TPU_CLASSIFY_FN_CACHE; evictions are counted and
#: exported by Engine.render_metrics (classify_fn_cache_evictions_total).
import collections
import os as _os

FN_CACHE_CAP = max(1, int(_os.environ.get(
    "CILIUM_TPU_CLASSIFY_FN_CACHE", "64")))
_FN_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_FN_LOCK = threading.Lock()
_FN_EVICTIONS = [0]


def fn_cache_stats() -> dict:
    """Memo-cache observability: current size, cap, eviction count."""
    with _FN_LOCK:
        return {"size": len(_FN_CACHE), "cap": FN_CACHE_CAP,
                "evictions": _FN_EVICTIONS[0]}


@dataclasses.dataclass(frozen=True)
class OutSlab:
    """The slab return form of the jitted step: every ``out`` column and
    counter in one flat uint32 device vector (``words``), plus the layout
    kernels/records.pack_out_jnp derived for it at trace time. The layout
    is static pytree metadata — it rides the jit's output treedef, so the
    host gets it back with every call at no transfer."""
    words: Any
    layout: tuple


jax.tree_util.register_dataclass(OutSlab, data_fields=["words"],
                                 meta_fields=["layout"])


def make_classify_fn(probe_depth: int = PROBE_DEPTH, v4_only: bool = False,
                     donate_ct: bool = True, packed: bool = False,
                     lb_probe_depth: int = 8, slab: bool = False):
    """jit-compiled classify step. CT buffers are donated (in-place update,
    no double allocation); re-traces only when array shapes change.

    Memoized on the full static-argument key — callers that rebuild their
    datapath (engine restarts, repeated placements, tests) share one jitted
    callable and therefore one trace cache instead of re-tracing identical
    shapes per closure.

    ``packed=True``: the batch argument is the single contiguous uint32 wire
    array (kernels/records.pack_batch) — one host→device transfer instead of
    twelve; unpacking happens on device and fuses into the pipeline. This is
    the transfer-bound production path; the dict path stays for tests. The
    wire width selects the variant at trace time: 4 words = compact v4
    (pack_batch_v4), otherwise the full/L7 layout.

    ``slab=True``: the step returns ``(OutSlab, new_ct)`` instead of
    ``(out, new_ct, counters)`` — a last stage inside the same jit packs
    every out column and counter into one uint32 vector
    (kernels/records.pack_out_jnp), so the host reads one batch's results
    back in one transfer; ``records.unpack_out(np.asarray(s.words),
    s.layout)`` is ``(out, counters)`` again, bit for bit. The one-chip
    serving path's return form; the column form stays for tests."""
    key = (probe_depth, v4_only, donate_ct, packed, lb_probe_depth, slab)
    with _FN_LOCK:
        fn = _FN_CACHE.get(key)
        if fn is not None:
            _FN_CACHE.move_to_end(key)     # LRU touch
            return fn

    def fn(tensors, ct, batch, now, world_index):
        if packed:
            from cilium_tpu.kernels.records import unpack_wire_jnp
            batch = unpack_wire_jnp(batch)
        out, new_ct, counters = classify_step(
            tensors, ct, batch, now, world_index,
            probe_depth=probe_depth, v4_only=v4_only,
            lb_probe_depth=lb_probe_depth)
        if slab:
            from cilium_tpu.kernels.records import pack_out_jnp
            return OutSlab(*pack_out_jnp(out, counters)), new_ct
        return out, new_ct, counters
    fn = jax.jit(fn, donate_argnums=(1,) if donate_ct else ())
    with _FN_LOCK:
        cached = _FN_CACHE.get(key)
        if cached is not None:             # lost the build race: reuse
            _FN_CACHE.move_to_end(key)
            return cached
        _FN_CACHE[key] = fn
        while len(_FN_CACHE) > FN_CACHE_CAP:
            _FN_CACHE.popitem(last=False)  # evict least-recently-used
            _FN_EVICTIONS[0] += 1
        return fn
