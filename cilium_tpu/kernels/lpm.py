"""Batched LPM trie walk (the device half of compile/lpm.py).

Fixed-depth gather chain, no data-dependent control flow: dead paths idle in
the sentinel node. A mixed-family batch walks both tries and selects by the
family bit (mirroring upstream's two LPM maps); ``v4_only=True`` (static)
skips the 16-level v6 walk for pure-IPv4 workloads (BASELINE config 1).

``lpm_lookup_prov_batch`` returns BOTH the identity index and the packed
match provenance ``(prefix_slot << 8) | plen`` carried in the trie's third
plane (compile/lpm.py): the walk that resolves the identity IS the walk that
names the winning prefix, so the two can never disagree.
``lpm_lookup_batch`` keeps the index-only contract for callers that do not
need provenance.

The tries arrive in their placed form, one 2-D table ``[n * 256, 3]`` a
family (compile/lpm.py), and a level takes its entry with a single-axis
``take`` (node * 256 + byte) from the placed table itself: no reshape stands
between the parameter and its gather. A TPU lays that table out as a gather
of whole entries reads it (tiles of 4 x 128, rows minor), so the walk reads
the trie where it lies; handed ``[n, 256, 3]`` and flattening it here, the
compiled program re-laid the whole trie into that form before the first
level of every batch (tests/test_tpu_compile.py holds the compiled program
to the form). A gather of one word a plane from a plane-major table copies
nothing either and costs three to five times the walking (11-15 us a gather
of 1,024 words whatever the table's size). In-range indices make the take
bit-identical to a 3-D index ``nodes[node, byte]`` (node is always a real
node or the dead sentinel, byte is masked to 0..255).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cilium_tpu.compile.lpm import V4_LEVELS, V6_LEVELS

#: each family's chain by name in the program's op metadata, inside the
#: caller's ``lpm.walk`` (kernels/classify.py), so that a profiler trace's
#: device time under the walk reads by family. Names only: the
#: instructions are what they were
SCOPE_V4 = "lpm.walk.v4"
SCOPE_V6 = "lpm.walk.v6"


def _walk(flat, addr_words, byte_index, levels, default_index):
    """flat [n*256,3] int32; addr_words [N,4] uint32; byte_index(l) gives the
    byte position 0..15 in the 16-byte address for level l. ``node``,
    ``best`` and ``best_meta`` live in registers across the whole chain —
    nothing but the node-triple gather touches memory per level. Returns
    (best identity index [N], best packed provenance [N], -1 on miss)."""
    dead = flat.shape[0] // 256 - 1
    n = addr_words.shape[0]
    node = jnp.zeros((n,), dtype=jnp.int32)
    # default_index may be a traced scalar (snapshot-dependent) — broadcast,
    # don't bake
    best = jnp.broadcast_to(jnp.asarray(default_index, jnp.int32), (n,))
    best_meta = jnp.full((n,), -1, dtype=jnp.int32)
    for level in range(levels):
        pos = byte_index(level)
        word = addr_words[:, pos // 4]
        b = ((word >> jnp.uint32(8 * (3 - pos % 4))) & jnp.uint32(0xFF)
             ).astype(jnp.int32)
        triple = flat[node * 256 + b]             # [N, 3]
        child, value, meta = triple[:, 0], triple[:, 1], triple[:, 2]
        hit = value >= 0
        best = jnp.where(hit, value, best)
        best_meta = jnp.where(hit, meta, best_meta)
        node = jnp.where(child >= 0, child, dead)
    return best, best_meta


def lpm_lookup_prov_batch(lpm_v4, lpm_v6, addr_words, is_v6, default_index,
                          v4_only: bool = False):
    """addr_words [N,4] uint32 (16-byte normalized, v4-mapped) → (identity
    index [N] int32, packed lpm_prefix provenance [N] int32, -1 on miss).
    ``default_index`` may be a traced scalar. ``v4_only`` (static) elides
    the 16-level v6 chain."""
    with jax.named_scope(SCOPE_V4):
        r4, m4 = _walk(lpm_v4, addr_words, lambda l: 12 + l, V4_LEVELS,
                       default_index)
    if v4_only:
        return r4, m4
    with jax.named_scope(SCOPE_V6):
        r6, m6 = _walk(lpm_v6, addr_words, lambda l: l, V6_LEVELS,
                       default_index)
    v6 = is_v6.astype(bool)
    return jnp.where(v6, r6, r4), jnp.where(v6, m6, m4)


def lpm_lookup_batch(lpm_v4, lpm_v6, addr_words, is_v6, default_index: int,
                     v4_only: bool = False):
    """addr_words [N,4] uint32 (16-byte normalized, v4-mapped) → identity
    index [N] int32."""
    return lpm_lookup_prov_batch(lpm_v4, lpm_v6, addr_words, is_v6,
                                 default_index, v4_only=v4_only)[0]
