"""Device conntrack: batched probe, deterministic parallel insert, aggregate
update, epoch sweep (upstream: bpf/lib/conntrack.h + pkg/maps/ctmap GC).

Design (SURVEY.md §7 "Hash tables on TPU"): fixed-capacity open addressing,
``PROBE_DEPTH`` linear probe slots, structure-of-arrays (compile/ct_layout).
All updates follow the *snapshot* batch semantics the oracle defines
(oracle/datapath.py classify_batch_snapshot): verdicts read the batch-start
state; effects are applied as an order-independent aggregate (flag-bit OR via
per-bit scatter-max, counter scatter-adds, expiry recomputed from aggregated
flags). Inserts resolve conflicts deterministically: per probe round, the
lowest packet index wins a free slot (scatter-min claim), duplicates of an
inserted key adopt the entry on the next round's check.

Insert-when-full (the adversarial-load contract, shared bit-for-bit with
oracle.ConntrackTable's bounded mode): a new flow whose probe window holds
no free slot performs ONE tail-eviction round — its victim is the window
slot with the smallest expiry among *evictable* entries (``ct_evictable``:
everything except established TCP — SYN-stage, closing, and non-TCP entries
are fair game, so a SYN flood churns among its own entries while
established flows survive), excluding slots claimed this batch and slots
any packet of this batch probe-hit (snapshot semantics: a slot being
updated by the batch is not evictable by the batch). Ties break to the
earliest probe offset; contested victims go to the lowest packet index.
Flows that still cannot obtain a slot fail the insert: counted
(``insert_fail``) and classified DROP ``CT_FULL`` by the caller — under
table exhaustion tracking fails CLOSED, the one place policy alone cannot
answer (an untracked "established-looking" flow would bypass the ladder
forever).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cilium_tpu.compile.ct_layout import KEY_PLANES, PROBE_DEPTH, key_planes
from cilium_tpu.kernels.hashing import hash_words_jnp
from cilium_tpu.kernels.records import ct_key_words_generic
from cilium_tpu.utils import constants as C


def ct_key_words_jnp(batch, reverse: bool = False):
    return ct_key_words_generic(jnp, batch, reverse)


def reverse_key_words_jnp(fwd_keys):
    """[N,10] forward CT key words → reverse orientation, derived by word
    ops instead of re-normalizing the tuple columns (the device twin of
    parallel/mesh._reverse_key_words): addr blocks swap, the port word
    rotates by 16 (sport<<16|dport → dport<<16|sport), and the direction
    byte flips (0 ↔ 1). Bit-identical to ``ct_key_words_jnp(reverse=True)``
    for any batch whose direction column is 0/1 — which the wire formats
    guarantee (direction rides a single bit)."""
    w8 = fwd_keys[:, 8]
    w9 = fwd_keys[:, 9]
    return jnp.concatenate([
        fwd_keys[:, 4:8], fwd_keys[:, 0:4],
        ((w8 << jnp.uint32(16)) | (w8 >> jnp.uint32(16)))[:, None],
        ((w9 & jnp.uint32(0xFFFFFF00))
         | (jnp.uint32(1) - (w9 & jnp.uint32(0xFF))))[:, None],
    ], axis=-1)


def ct_key_words_pair(batch):
    """→ (fwd_keys, rev_keys), both [N,10] uint32, sharing one pass over
    the tuple columns. ``classify_step`` previously normalized the same
    src/dst/port/proto fields twice (forward + reverse stacks); the reverse
    orientation is a cheap word permutation of the forward words."""
    fwd = ct_key_words_jnp(batch, reverse=False)
    return fwd, reverse_key_words_jnp(fwd)


#: the key word the probe reads at every slot of its window before any
#: other: ``sport << 16 | dport``, the word two flows of one window least
#: often share
FIRST_WORD = 8


def ct_probe(ct, keys, now, probe_depth: int = PROBE_DEPTH):
    """Find each key's live slot, the first of its window that holds the
    key → slot [N] int32 (-1 = miss), over the table's placed form (the
    ten key planes, each [cap] uint32; expiry [cap] uint32).

    Two stages, because a word gathered off a plane is what the probe
    costs (≈8 µs a plane and 1,024 rows on a v5e, PERF.md §6 "PR 48"):
    the window's *candidates* are its live slots whose ``FIRST_WORD`` is
    the key's (one gather of that plane and one of ``expiry`` a slot);
    then each row's candidates are settled in window order, the other nine
    words read at one slot a row a round, until every row has found its
    slot or has no candidate left. A hit takes one round, a miss none; a
    window of flows that share their ports takes as many rounds as it has
    such slots, ``probe_depth`` at most, which is what every probe took
    before."""
    planes, expiry = key_planes(ct), ct["expiry"]
    cap = expiry.shape[0]
    mask = cap - 1
    base = (hash_words_jnp(keys) & jnp.uint32(mask)).astype(jnp.int32)
    offsets = jnp.arange(probe_depth, dtype=jnp.int32)
    cand = []
    for i in range(probe_depth):
        s = (base + i) & mask
        cand.append((planes[FIRST_WORD][s] == keys[:, FIRST_WORD])
                    & (expiry[s] > now))

    def settle(state):
        cand, found = state
        first = jnp.argmax(cand, axis=1).astype(jnp.int32)
        s = (base + first) & mask
        eq = jnp.any(cand, axis=1)
        for w, plane in enumerate(planes):
            if w != FIRST_WORD:
                eq = eq & (plane[s] == keys[:, w])
        # a row that found its slot is settled; another loses this candidate
        cand = cand & ~eq[:, None] & (offsets[None, :] != first[:, None])
        return cand, jnp.where(eq, s, found)

    _, found = jax.lax.while_loop(
        lambda state: jnp.any(state[0]), settle,
        (jnp.stack(cand, axis=1), jnp.full(base.shape, -1, dtype=jnp.int32)))
    return found


def ct_probe_pair(ct, fwd_keys, rev_keys, now,
                  probe_depth: int = PROBE_DEPTH):
    """Both orientations in one probe of 2N keys (a gather's cost is mostly
    the gather's own, not its rows') → (fwd_slot, rev_slot), each [N]."""
    n = fwd_keys.shape[0]
    slot = ct_probe(ct, jnp.concatenate([fwd_keys, rev_keys]), now,
                    probe_depth)
    return slot[:n], slot[n:]


def _flag_delta(proto, tcp_flags, is_reply):
    """Vectorized mirror of oracle._flag_delta."""
    is_tcp = proto == C.PROTO_TCP
    fin_rst = (tcp_flags & (C.TCP_FIN | C.TCP_RST)) != 0
    rst = (tcp_flags & C.TCP_RST) != 0
    non_syn = (tcp_flags & C.TCP_SYN) == 0
    close_self = jnp.where(is_reply, C.CT_FLAG_RX_CLOSING, C.CT_FLAG_TX_CLOSING)
    delta = jnp.where(fin_rst, close_self, 0)
    delta = jnp.where(rst, delta | C.CT_FLAG_TX_CLOSING | C.CT_FLAG_RX_CLOSING,
                      delta)
    delta = jnp.where(non_syn, delta | C.CT_FLAG_SEEN_NON_SYN, delta)
    return jnp.where(is_tcp, delta, 0).astype(jnp.uint32)


def _lifetime(proto, flags):
    """Vectorized mirror of oracle lifetime rules. proto [M], flags [M]."""
    is_tcp = proto == C.PROTO_TCP
    closing = (flags & (C.CT_FLAG_TX_CLOSING | C.CT_FLAG_RX_CLOSING)) != 0
    non_syn = (flags & C.CT_FLAG_SEEN_NON_SYN) != 0
    tcp_life = jnp.where(closing, C.CT_LIFETIME_CLOSE,
                         jnp.where(non_syn, C.CT_LIFETIME_TCP,
                                   C.CT_LIFETIME_SYN))
    return jnp.where(is_tcp, tcp_life, C.CT_LIFETIME_NONTCP).astype(jnp.uint32)


def _slot_proto(planes):
    """Every slot's protocol [cap] int32: the top of key word 9, read off
    its own plane in order."""
    return (planes[9] >> jnp.uint32(8)).astype(jnp.int32)


def ct_evictable(slot_proto, flags):
    """Which live entries an exhausted insert may tail-evict: everything
    whose current lifetime class is NOT the established-TCP one — i.e.
    TCP entries still in the handshake (no SEEN_NON_SYN) or closing, and
    all non-TCP entries. One predicate, two executors (this jnp form and
    the oracle's ``_ct_expirable``), so the protected class can never
    drift."""
    is_tcp = slot_proto == C.PROTO_TCP
    non_syn = (flags & jnp.uint32(C.CT_FLAG_SEEN_NON_SYN)) != 0
    closing = (flags & jnp.uint32(C.CT_FLAG_TX_CLOSING
                                  | C.CT_FLAG_RX_CLOSING)) != 0
    return ~(is_tcp & non_syn & ~closing)


def ct_insert_new(ct, keys, want_insert, now,
                  probe_depth: int = PROBE_DEPTH,
                  evict: bool = False, protected=None):
    """Deterministic parallel insert of new flows.

    Returns (new_keys, new_created, zero_mask, slot_of, fail, n_evicted):
    - ``new_keys`` the ten key planes with the winners' keys written;
    - ``zero_mask`` [cap] marks freshly-claimed slots whose value arrays
      (flags/counters) must be reset before aggregation;
    - ``slot_of`` [N] is the entry slot for every packet whose flow now has
      one (winner or adopted duplicate), else -1;
    - ``fail`` [N] marks flows that exhausted their probe window (with
      ``evict``: even after the eviction round);
    - ``n_evicted`` uint32 scalar: live entries tail-evicted this batch.

    ``evict`` arms the insert-when-full tail eviction (module docstring);
    ``protected`` [cap] bool marks slots the batch probe-hit (never
    evicted — snapshot semantics demand a slot being updated by this batch
    stays this batch's).

    A slot claimed in this batch is known by its ``owner`` (the winning
    packet's index; ``n`` = unclaimed), and a claimed slot's key is its
    owner's: the adoption checks compare the batch's own key rows, and the
    table's planes are written once, after the last round, each winner's
    ten words and its ``created``."""
    cap = ct["expiry"].shape[0]
    mask = cap - 1
    n = keys.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    base = (hash_words_jnp(keys) & jnp.uint32(mask)).astype(jnp.int32)

    owner = jnp.full((cap + 1,), n, dtype=jnp.int32)
    slot_of = jnp.full((n,), -1, dtype=jnp.int32)
    won_at = jnp.full((n,), cap, dtype=jnp.int32)
    pending = want_insert

    def adopt(s, o, slot_of, pending):
        # slot s, claimed by packet o, may hold my key, inserted by a
        # lower-indexed duplicate of my flow (o = n, unclaimed, reads the
        # clamped last row: excluded by o < n)
        adopted = pending & (o < n) & jnp.all(keys[o] == keys, axis=-1)
        return jnp.where(adopted, s, slot_of), pending & ~adopted

    def claim(target, attempt, owner, slot_of, won_at, pending):
        # lowest packet index wins each contested slot
        owner = owner.at[jnp.where(attempt, target, cap)].min(idx)
        now_owned_by = owner[target]
        winner = attempt & (now_owned_by == idx)
        return (owner, jnp.where(winner, target, slot_of),
                jnp.where(winner, target, won_at), pending & ~winner,
                now_owned_by, winner)

    for r in range(probe_depth):
        s = (base + r) & mask
        if r > 0:
            # adoption at my previous round's target, as that round left it
            slot_of, pending = adopt(sprev, now_owned_by, slot_of, pending)
        free = (ct["expiry"][s] <= now) & (owner[s] == n)
        owner, slot_of, won_at, pending, now_owned_by, _ = claim(
            s, pending & free, owner, slot_of, won_at, pending)
        sprev = s

    def stragglers(state):
        """What is left for the rows the rounds did not settle: a last
        adoption sweep, then the eviction round. Most batches have no such
        row and skip it."""
        owner, slot_of, won_at, pending = state
        # final adoption sweep: stragglers whose duplicate won at a slot
        # they already passed
        for r in range(probe_depth):
            s = (base + r) & mask
            slot_of, pending = adopt(s, owner[s], slot_of, pending)
        if not evict:
            return (owner, slot_of, won_at, pending), jnp.uint32(0)
        # tail-eviction round (batch-start state throughout): victim =
        # the window slot with the smallest expiry among live, evictable,
        # unclaimed, unprotected entries; ties break to the earliest probe
        # offset (strict <), contested victims to the lowest packet index
        exp0 = ct["expiry"]
        candidate = (exp0 > now) & ct_evictable(
            _slot_proto(key_planes(ct)), ct["flags"])
        if protected is not None:
            candidate = candidate & ~protected
        best_s = jnp.full((n,), -1, dtype=jnp.int32)
        best_e = jnp.full((n,), 0xFFFFFFFF, dtype=jnp.uint32)
        for r in range(probe_depth):
            s = (base + r) & mask
            cand = pending & candidate[s] & (owner[s] == n)
            e = exp0[s]
            better = cand & ((best_s < 0) | (e < best_e))
            best_s = jnp.where(better, s, best_s)
            best_e = jnp.where(better, e, best_e)
        owner, slot_of, won_at, pending, _, winner = claim(
            jnp.where(best_s >= 0, best_s, 0), pending & (best_s >= 0),
            owner, slot_of, won_at, pending)
        # adoption: duplicates of an evict-winner's key ride its new slot
        for r in range(probe_depth):
            s = (base + r) & mask
            slot_of, pending = adopt(s, owner[s], slot_of, pending)
        return ((owner, slot_of, won_at, pending),
                winner.sum().astype(jnp.uint32))

    (owner, slot_of, won_at, pending), n_evicted = jax.lax.cond(
        jnp.any(pending), stragglers,
        lambda state: (state, jnp.uint32(0)),
        (owner, slot_of, won_at, pending))

    new_keys = tuple(p.at[won_at].set(keys[:, w], mode="drop")
                     for w, p in enumerate(key_planes(ct)))
    new_created = ct["created"].at[won_at].set(now, mode="drop")
    return new_keys, new_created, owner[:cap] < n, slot_of, pending, n_evicted


def ct_apply(ct, batch, slot, is_reply, contrib, now,
             new_keys=None, new_created=None, zero_mask=None,
             rev_nat_vals=None):
    """Aggregate all allowed packets' effects into the table (snapshot
    semantics). ``slot`` [N] (-1 = none), ``contrib`` [N] bool.
    ``rev_nat_vals`` [N] int32: per-packet rev-NAT id to record for freshly
    created entries (0 = none; duplicates of one flow carry the same value,
    so the scatter-max is deterministic).

    Returns the new ct pytree.
    """
    cap = ct["expiry"].shape[0]
    planes = new_keys if new_keys is not None else key_planes(ct)
    created_arr = new_created if new_created is not None else ct["created"]
    flags = ct["flags"]
    fwd = ct["pkts_fwd"]
    rev = ct["pkts_rev"]
    rnat = ct["rev_nat"]
    if zero_mask is not None:
        zero32 = jnp.uint32(0)
        flags = jnp.where(zero_mask, zero32, flags)
        fwd = jnp.where(zero_mask, zero32, fwd)
        rev = jnp.where(zero_mask, zero32, rev)
        rnat = jnp.where(zero_mask, zero32, rnat)
    if rev_nat_vals is not None and zero_mask is not None:
        # only freshly created entries record a rev-NAT id (create-time
        # semantics, like upstream ct_create4's rev_nat_index)
        fresh = contrib & (slot >= 0) & zero_mask[jnp.where(slot >= 0, slot, 0)]
        rnat = rnat.at[jnp.where(fresh, slot, cap)].max(
            rev_nat_vals.astype(jnp.uint32), mode="drop")

    scat = jnp.where(contrib, slot, cap)  # OOB → dropped
    delta = _flag_delta(batch["proto"], batch["tcp_flags"], is_reply)
    # OR-accumulate flag bits: scatter-max each bit plane separately (a max
    # on the full word would clobber unrelated bits), then recombine
    acc = jnp.zeros_like(flags)
    for bit in (C.CT_FLAG_SEEN_NON_SYN, C.CT_FLAG_TX_CLOSING,
                C.CT_FLAG_RX_CLOSING):
        plane = flags & jnp.uint32(bit)
        has = ((delta & jnp.uint32(bit)) != 0).astype(jnp.uint32) * jnp.uint32(bit)
        plane = plane.at[scat].max(has, mode="drop")
        acc = acc | plane
    flags = acc
    one = jnp.ones_like(scat, dtype=jnp.uint32)
    fwd = fwd.at[jnp.where(contrib & ~is_reply, slot, cap)].add(one, mode="drop")
    rev = rev.at[jnp.where(contrib & is_reply, slot, cap)].add(one, mode="drop")

    touched = jnp.zeros((cap,), dtype=bool).at[scat].set(True, mode="drop")
    new_expiry = now + _lifetime(_slot_proto(planes), flags)
    expiry = jnp.where(touched, new_expiry, ct["expiry"])

    return {
        **dict(zip(KEY_PLANES, planes)),
        "expiry": expiry,
        "created": created_arr,
        "flags": flags,
        "pkts_fwd": fwd,
        "pkts_rev": rev,
        "rev_nat": rnat,
    }


def _sweep_mask(ct, dead):
    """Clear every entry under ``dead`` [cap] bool → new ct pytree (shared
    by the whole-table sweep and the chunked epoch sweep)."""
    zero32 = jnp.uint32(0)
    return {k: jnp.where(dead, zero32, v) for k, v in ct.items()}


def ct_sweep(ct, now):
    """Epoch GC: clear expired entries (upstream ctmap GC — SURVEY.md §2
    "Pipelined device-side epoch sweep"). Returns (new_ct, n_reclaimed)."""
    dead = (ct["expiry"] <= now) & (ct["expiry"] != 0)
    return _sweep_mask(ct, dead), dead.sum()


def ct_sweep_chunk(ct, now, start, chunk_rows: int, count_now=None):
    """One chunk of the overlapped device-side epoch sweep: clear expired
    entries whose slot lies in ``[start, start + chunk_rows)`` (mod cap —
    the window wraps so a cursor can advance forever) and count the whole
    table's live occupancy in the same program.

    ``count_now`` (default: ``now``) is the clock the occupancy count
    uses. Emergency GC sweeps with a slashed clock (``now`` pushed into
    the future so short-TTL entries die early) but must keep MEASURING
    with the real clock — a slashed count would exclude genuinely-live
    entries the sweep has not reached, read artificially low, and flap
    the pressure latch's exit hysteresis.

    ``chunk_rows`` is trace-time static; ``start`` is traced, so one jitted
    program serves every cursor position. Semantics-free by construction:
    probes and inserts already treat ``expiry <= now`` slots as
    dead/claimable, so *when* a slot is physically cleared can never change
    a verdict — which is exactly what lets the GC overlap live classify
    steps instead of stopping the world.

    Returns (new_ct, n_reclaimed [uint32 scalar], n_live [uint32 scalar]).
    Both scalars are device values: the caller is expected NOT to block on
    them in the enqueue path (the double-buffered harvest reads them a tick
    later, when they are long since resolved)."""
    cap = ct["expiry"].shape[0]
    idx = jnp.arange(cap, dtype=jnp.uint32)
    off = (idx - start.astype(jnp.uint32)) % jnp.uint32(cap)
    in_win = off < jnp.uint32(min(chunk_rows, cap))
    expiry = ct["expiry"]
    dead = in_win & (expiry <= now) & (expiry != 0)
    live = (expiry > (now if count_now is None else count_now)) \
        .sum().astype(jnp.uint32)
    return _sweep_mask(ct, dead), dead.sum().astype(jnp.uint32), live
