"""Packet batch layout: the device-facing form of the shim's 64B records.

A batch is a dict-of-arrays pytree with a fixed size N (padded; ``valid``
masks real packets). The C++ shim emits exactly these columns (shim/ record
layout doc); tests build batches from oracle PacketRecords.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from cilium_tpu.utils import constants as C

BatchArrays = Dict[str, np.ndarray]


def empty_batch(n: int) -> BatchArrays:
    return {
        "src": np.zeros((n, 4), dtype=np.uint32),
        "dst": np.zeros((n, 4), dtype=np.uint32),
        "sport": np.zeros((n,), dtype=np.int32),
        "dport": np.zeros((n,), dtype=np.int32),
        "proto": np.zeros((n,), dtype=np.int32),
        "tcp_flags": np.zeros((n,), dtype=np.int32),
        "is_v6": np.zeros((n,), dtype=bool),
        "ep_slot": np.zeros((n,), dtype=np.int32),
        "direction": np.zeros((n,), dtype=np.int32),
        "http_method": np.full((n,), C.HTTP_METHOD_ANY, dtype=np.int32),
        "http_path": np.zeros((n, C.L7_PATH_MAXLEN), dtype=np.uint8),
        "valid": np.zeros((n,), dtype=bool),
    }


def reset_batch_rows(b: BatchArrays, start: int, stop: int) -> None:
    """Restore rows [start:stop] of a reused batch dict to the
    ``empty_batch`` defaults (optional shim-side ``_*`` columns included).
    Every buffer-reuse site (staging-ring flush tails, reusable poll
    buffers) goes through here so a future column with a non-zero default
    cannot silently diverge between them — stale rows leaking into the
    wire-format probes is exactly the bug class this prevents."""
    for k, col in b.items():
        if k == "valid":
            col[start:stop] = False
        elif k == "http_method":
            col[start:stop] = C.HTTP_METHOD_ANY
        else:
            col[start:stop] = 0


def _addr_words(addr16: bytes) -> np.ndarray:
    return np.frombuffer(addr16, dtype=">u4").astype(np.uint32)


def batch_from_records(records: Sequence, ep_slot_of: Dict[int, int],
                       pad_to: int = 0) -> BatchArrays:
    """Build a batch from oracle PacketRecords (tests / pcap replay).

    Records for endpoints unknown to the snapshot fail closed: they are left
    ``valid=False`` (never forwarded, no CT effects) — the batch-level analog
    of the oracle's INVALID_IDENTITY drop for unknown endpoints.
    """
    n = max(len(records), pad_to)
    b = empty_batch(n)
    for i, p in enumerate(records):
        b["src"][i] = _addr_words(p.src_addr)
        b["dst"][i] = _addr_words(p.dst_addr)
        b["sport"][i] = p.src_port
        b["dport"][i] = p.dst_port
        b["proto"][i] = p.proto
        b["tcp_flags"][i] = p.tcp_flags
        b["is_v6"][i] = p.is_ipv6
        slot = ep_slot_of.get(p.ep_id)
        if slot is None:
            continue  # fail closed: stays invalid
        b["ep_slot"][i] = slot
        b["direction"][i] = p.direction
        b["http_method"][i] = p.http_method
        pb = p.http_path[:C.L7_PATH_MAXLEN]
        if pb:
            b["http_path"][i, :len(pb)] = np.frombuffer(pb, dtype=np.uint8)
        b["valid"][i] = True
    return b


def ct_key_words_generic(xp, batch: Dict, reverse: bool = False):
    """[N, 10] uint32 conntrack key (see compile/ct_layout.py), forward or
    reverse orientation. One definition, two executors (xp = np on host,
    jnp on device) so the key layout cannot silently diverge between the
    device table and host checkpoint/export."""
    src, dst = ((batch["dst"], batch["src"]) if reverse
                else (batch["src"], batch["dst"]))
    sport, dport = ((batch["dport"], batch["sport"]) if reverse
                    else (batch["sport"], batch["dport"]))
    direction = (1 - batch["direction"]) if reverse else batch["direction"]
    words = [
        src[:, 0], src[:, 1], src[:, 2], src[:, 3],
        dst[:, 0], dst[:, 1], dst[:, 2], dst[:, 3],
        (sport.astype(xp.uint32) << xp.uint32(16)) | dport.astype(xp.uint32),
        (batch["proto"].astype(xp.uint32) << xp.uint32(8))
        | direction.astype(xp.uint32),
    ]
    return xp.stack(words, axis=-1)


def ct_key_words(batch: BatchArrays, reverse: bool = False) -> np.ndarray:
    return ct_key_words_generic(np, batch, reverse)


# --------------------------------------------------------------------------- #
# Packed wire format: ONE contiguous uint32 array per batch.
#
# One host→device transfer per batch instead of twelve (each transfer pays
# a fixed overhead): the runtime packs on the host (vectorized numpy) and
# unpacks on device inside the jit (bit ops that XLA fuses into the
# pipeline). The C++ shim can emit this layout directly.
#
# Word layout per record:
#   0-3   src words          4-7  dst words
#   8     sport<<16 | dport
#   9     proto<<24 | tcp_flags<<16 | http_method<<8 | is_v6<<2|dir<<1|valid
#   10    ep_slot
#   11+   (L7 variant only) http_path as 16 big-endian uint32 words
# --------------------------------------------------------------------------- #
PACK_WORDS = 11
PACK_WORDS_L7 = PACK_WORDS + C.L7_PATH_MAXLEN // 4


def _out_view(out: Optional[np.ndarray], n: int, words: int) -> np.ndarray:
    """Resolve the ``out=`` contract shared by every pack kernel: a
    caller-owned uint32 buffer with >= n rows of exactly ``words`` columns
    (the staging ring preallocates at max_bucket rows and packs into the
    [:n] prefix). Returns the [:n] view to fill, or a fresh allocation when
    the caller passed none."""
    if out is None:
        return np.empty((n, words), dtype=np.uint32)
    if (out.dtype != np.uint32 or out.ndim != 2 or out.shape[0] < n
            or out.shape[1] != words):
        raise ValueError(
            f"pack out= buffer mismatch: need uint32 [>={n}, {words}], "
            f"got {out.dtype} {out.shape}")
    return out[:n]


def _path_words_of(paths: np.ndarray) -> int:
    """Smallest power-of-two word count covering the longest path in
    ``paths`` [N, 64]. L7 throughput is transfer-bound and most HTTP paths
    are short: shipping only the occupied prefix (rounded to a power of two
    so trace shapes stay few) cuts the wire size ~2-4x vs the fixed 64-byte
    block."""
    nz = np.nonzero(paths.any(axis=0))[0]
    maxlen = int(nz[-1]) + 1 if nz.size else 1
    words = -(-maxlen // 4)
    return min(1 << (words - 1).bit_length(), C.L7_PATH_MAXLEN // 4)


def _path_words_for(b: BatchArrays) -> int:
    return _path_words_of(b["http_path"])


def pack_batch(b: BatchArrays, l7: Optional[bool] = None,
               path_words: Optional[int] = None,
               out: Optional[np.ndarray] = None) -> np.ndarray:
    """Pack a batch dict → [N, 11] (or [N, 11+path_words] when l7) uint32.
    ``l7=None`` auto-detects: include the path block iff any record carries
    L7 tokens. ``path_words`` (power of two ≤ 16) sizes the path block;
    default = smallest power of two covering the batch's longest path.
    ``out=`` writes into a caller-owned buffer (see ``_out_view``) instead
    of allocating — the staging ring's steady-state zero-alloc path; the
    wire is bit-identical either way."""
    if l7 is None:
        l7 = bool((b["http_method"] != C.HTTP_METHOD_ANY).any()
                  or b["http_path"].any())
    if l7:
        if path_words is None:
            path_words = _path_words_for(b)
        path_words = min(path_words, C.L7_PATH_MAXLEN // 4)
        if b["http_path"][:, 4 * path_words:].any():
            raise ValueError(f"path_words={path_words} truncates a path")
    else:
        path_words = 0
    n = b["valid"].shape[0]
    out = _out_view(out, n, PACK_WORDS + path_words)
    out[:, 0:4] = b["src"]
    out[:, 4:8] = b["dst"]
    out[:, 8] = (b["sport"].astype(np.uint32) << 16) \
        | b["dport"].astype(np.uint32)
    out[:, 9] = (b["proto"].astype(np.uint32) << 24) \
        | (b["tcp_flags"].astype(np.uint32) << 16) \
        | (b["http_method"].astype(np.uint32) << 8) \
        | (b["is_v6"].astype(np.uint32) << 2) \
        | (b["direction"].astype(np.uint32) << 1) \
        | b["valid"].astype(np.uint32)
    out[:, 10] = b["ep_slot"].astype(np.uint32)
    if l7:
        p = b["http_path"][:, :4 * path_words].reshape(
            n, path_words, 4).astype(np.uint32)
        out[:, PACK_WORDS:] = ((p[:, :, 0] << 24) | (p[:, :, 1] << 16)
                               | (p[:, :, 2] << 8) | p[:, :, 3])
    return out


# Compact v4 variant: 4 words (16B) per record — for the v4-only, L7-free
# hot path (configs 1/2/3/5 traffic). The classify pipeline is transfer-
# bound, so bytes/record is the throughput knob: 16B vs 44B is ~2.7x more
# records per second through the same link.
#   0  src v4   1  dst v4   2  sport<<16|dport
#   3  proto<<24 | tcp_flags<<16 | ep_slot<<2 | dir<<1 | valid
# ep_slot therefore caps at 14 bits (16383 endpoints/node) in this format;
# the full format carries 32-bit slots.
PACK4_WORDS = 4
PACK4_EP_SLOT_MAX = (1 << 14) - 1


def pack_batch_v4(b: BatchArrays,
                  out: Optional[np.ndarray] = None) -> np.ndarray:
    """Pack a v4-only, L7-free batch dict → [N, 4] uint32. ``out=`` fills
    a caller-owned buffer in place (bit-identical wire, no allocation)."""
    if b["is_v6"].any():
        raise ValueError("pack_batch_v4: batch contains v6 records")
    if (b["ep_slot"] > PACK4_EP_SLOT_MAX).any():
        raise ValueError("pack_batch_v4: ep_slot exceeds 14-bit compact cap")
    n = b["valid"].shape[0]
    out = _out_view(out, n, PACK4_WORDS)
    out[:, 0] = b["src"][:, 3]
    out[:, 1] = b["dst"][:, 3]
    out[:, 2] = (b["sport"].astype(np.uint32) << 16) \
        | b["dport"].astype(np.uint32)
    out[:, 3] = (b["proto"].astype(np.uint32) << 24) \
        | (b["tcp_flags"].astype(np.uint32) << 16) \
        | (b["ep_slot"].astype(np.uint32) << 2) \
        | (b["direction"].astype(np.uint32) << 1) \
        | b["valid"].astype(np.uint32)
    return out


# --------------------------------------------------------------------------- #
# L7 dictionary wire format: (wire, path_dict) — real HTTP traffic repeats a
# small set of paths, so shipping the 64B path block per record wastes ~80%
# of the link. Instead: unique paths once per batch ([U, P] packed words,
# U padded to a power of two for trace stability) + a 16-bit dictionary
# index per record; the device gathers the path bytes back during unpack.
# cfg4 measurement (round 5): the L7 kernel runs >100M flows/s compute-only;
# the fixed-block wire capped it at ~1.3M. 20B/record vs 76-108B.
#
# v4-compact variant ([N, 5]): PACK4 words 0-3 + word 4 = method<<24|path_idx.
# full variant ([N, 12]): PACK words 0-10 + word 11 = path_idx (method is
# already in word 9).
# --------------------------------------------------------------------------- #
PACK4_L7_WORDS = PACK4_WORDS + 1
PACK_L7DICT_WORDS = PACK_WORDS + 1


def _pad_dict_rows(count: int, min_rows: int) -> int:
    """Dictionary row padding: next power of two ≥ max(count, min_rows) —
    shared by the path and address dictionaries so trace-shape policy can't
    silently diverge between them."""
    return 1 << max(0, (max(count, min_rows) - 1)).bit_length()


def _pack_path_dict(paths: np.ndarray, path_words: Optional[int],
                    min_rows: int = 1) -> Tuple[np.ndarray, np.ndarray]:
    """[N, 64] uint8 → (dict_words [U_pow2, P] uint32, index [N] int64).
    The distinct rows in ascending byte order, the index dense from 0.
    ``min_rows`` floors the padded row count (callers pin it grow-only so
    serving doesn't retrace when per-batch path diversity fluctuates)."""
    n, width = paths.shape
    # a row is ONE 64-byte item to the sort: memcmp order over the item is
    # the lexicographic order of its unsigned bytes, so dictionary and
    # index are what np.unique(axis=0) gives, which sorts a structured view
    # of 64 one-byte fields, field by field, at ~20x the time. The view
    # needs whole rows in memory: a column view of a wider harvest buffer
    # is copied (64 KB), never reinterpreted
    rows = np.ascontiguousarray(paths, dtype=np.uint8)
    items, idx = np.unique(
        rows.view(np.dtype((np.void, width))).reshape(n),
        return_inverse=True)
    uniq = items.view(np.uint8).reshape(-1, width)
    if uniq.shape[0] > 65536:
        raise ValueError("path dictionary overflow (>64k unique paths)")
    if path_words is None:
        path_words = _path_words_of(uniq)
    path_words = min(path_words, C.L7_PATH_MAXLEN // 4)
    if uniq[:, 4 * path_words:].any():
        raise ValueError(f"path_words={path_words} truncates a path")
    words = np.zeros((_pad_dict_rows(uniq.shape[0], min_rows), path_words),
                     dtype=np.uint32)
    # b0<<24 | b1<<16 | b2<<8 | b3 is the big-endian uint32 of the four
    words[:uniq.shape[0]] = uniq.view(">u4")[:, :path_words]
    return words, idx


def pack_batch_l7dict(b: BatchArrays, path_words: Optional[int] = None,
                      min_rows: int = 1, force_full: bool = False,
                      out: Optional[np.ndarray] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Pack an L7 batch as (wire, path_dict). Picks the 5-word v4-compact
    wire when the batch qualifies, else the 12-word full wire
    (``force_full`` pins the full wire so serving paths don't flap formats
    batch-to-batch). ``out=`` fills a caller-owned wire buffer in place
    (its width must match the variant this batch selects; the path dict is
    a fresh array every batch, and the upload layer dedups re-transfers by
    content instead)."""
    dict_words, idx = _pack_path_dict(b["http_path"], path_words, min_rows)
    n = b["valid"].shape[0]
    if not force_full and not b["is_v6"].any() \
            and not (b["ep_slot"] > PACK4_EP_SLOT_MAX).any():
        wire = _out_view(out, n, PACK4_L7_WORDS)
        wire[:, 0] = b["src"][:, 3]
        wire[:, 1] = b["dst"][:, 3]
        wire[:, 2] = (b["sport"].astype(np.uint32) << 16) \
            | b["dport"].astype(np.uint32)
        wire[:, 3] = (b["proto"].astype(np.uint32) << 24) \
            | (b["tcp_flags"].astype(np.uint32) << 16) \
            | (b["ep_slot"].astype(np.uint32) << 2) \
            | (b["direction"].astype(np.uint32) << 1) \
            | b["valid"].astype(np.uint32)
        wire[:, 4] = (b["http_method"].astype(np.uint32) << 24) \
            | idx.astype(np.uint32)
        return wire, dict_words
    wire = _out_view(out, n, PACK_L7DICT_WORDS)
    pack_batch(b, l7=False, out=wire[:, :PACK_WORDS])
    wire[:, PACK_WORDS] = idx.astype(np.uint32)
    return wire, dict_words


#: the path dictionary's unpacking in a program's op metadata, so that a
#: profiler trace's device time can be read for the L7 lane (the match is
#: ``l7.match``, kernels/classify.py); every dictionary wire's path arm
#: (l7-dict, address-dict) goes through the one function
SCOPE_L7_UNPACK = "l7.unpack"


def _unpack_dict_paths_jnp(dict_words, idx):
    import jax
    import jax.numpy as jnp
    with jax.named_scope(SCOPE_L7_UNPACK):
        words = dict_words[idx]                            # [N, P]
        n = words.shape[0]
        path = jnp.stack([(words >> 24) & 0xFF, (words >> 16) & 0xFF,
                          (words >> 8) & 0xFF, words & 0xFF],
                         axis=-1).reshape(n, -1).astype(jnp.uint8)
        pad = C.L7_PATH_MAXLEN - path.shape[1]
        if pad > 0:
            path = jnp.pad(path, ((0, 0), (0, pad)))
        return path


def unpack_batch_l7dict_jnp(wire, dict_words):
    """Device-side unpack of either l7-dict wire variant."""
    import jax.numpy as jnp
    if wire.shape[1] == PACK4_L7_WORDS:
        b = unpack_batch_v4_jnp(wire[:, :PACK4_WORDS])
        w4 = wire[:, 4]
        b["http_method"] = (w4 >> 24).astype(jnp.int32)
        b["http_path"] = _unpack_dict_paths_jnp(
            dict_words, (w4 & 0xFFFF).astype(jnp.int32))
        return b
    b = unpack_batch_jnp(wire[:, :PACK_WORDS])
    b["http_path"] = _unpack_dict_paths_jnp(
        dict_words, (wire[:, PACK_WORDS] & 0xFFFF).astype(jnp.int32))
    return b


# --------------------------------------------------------------------------- #
# Address-dictionary wire: (wire [N,3 or 4], addr_dict [U,4] [, path_dict]) —
# pod traffic repeats addresses heavily (a 65k-record cfg5 batch carries
# ~2000 distinct addresses), so shipping each unique 16B normalized address
# once and 16-bit indexes per record beats even the compact v4 wire
# (12B/record vs 16B) and beats the full wire ~3x for mixed v4/v6. Handles
# both families uniformly (the dict rows are v4-mapped/16B).
#
#   w0 = src_idx<<16 | dst_idx          (indexes into addr_dict)
#   w1 = sport<<16 | dport
#   w2 = proto<<24 | tcp_flags<<16 | ep_slot<<3 | is_v6<<2 | dir<<1 | valid
#   w3 = http_method<<16 | path_idx     (L7 variant only)
# --------------------------------------------------------------------------- #
PACKA_WORDS = 3
PACKA_L7_WORDS = 4
PACKA_EP_SLOT_MAX = (1 << 13) - 1


def addr_dict_ratio(b: BatchArrays) -> float:
    """Unique-address fraction of a batch (selection heuristic: the addr
    dict wins when addresses repeat; random-scan traffic where every
    record brings fresh addresses packs better with the flat formats)."""
    n = b["valid"].shape[0]
    if n == 0:
        return 1.0
    uniq = np.unique(np.concatenate([b["src"], b["dst"]]), axis=0)
    return uniq.shape[0] / (2 * n)


def pack_batch_addrdict(b: BatchArrays, l7: Optional[bool] = None,
                        min_addr_rows: int = 1,
                        path_words: Optional[int] = None,
                        min_path_rows: int = 1):
    """Pack a batch in the address-dictionary wire. Returns
    (wire [N,3], addr_dict) or, with L7 tokens, (wire [N,4], addr_dict,
    path_dict). ``min_*_rows`` floor the padded dictionary sizes (grow-only
    pinning for serving paths)."""
    n = b["valid"].shape[0]
    if (b["ep_slot"] > PACKA_EP_SLOT_MAX).any():
        raise ValueError("pack_batch_addrdict: ep_slot exceeds 13-bit cap")
    uniq, inv = np.unique(np.concatenate([b["src"], b["dst"]]), axis=0,
                          return_inverse=True)
    if uniq.shape[0] > 65536:
        raise ValueError("address dictionary overflow (>64k unique)")
    u_pad = _pad_dict_rows(uniq.shape[0], min_addr_rows)
    addr_dict = np.zeros((u_pad, 4), dtype=np.uint32)
    addr_dict[:uniq.shape[0]] = uniq
    src_idx = inv[:n].astype(np.uint32)
    dst_idx = inv[n:].astype(np.uint32)
    if l7 is None:
        l7 = bool((b["http_method"] != C.HTTP_METHOD_ANY).any()
                  or b["http_path"].any())
    wire = np.empty((n, PACKA_L7_WORDS if l7 else PACKA_WORDS),
                    dtype=np.uint32)
    wire[:, 0] = (src_idx << 16) | dst_idx
    wire[:, 1] = (b["sport"].astype(np.uint32) << 16) \
        | b["dport"].astype(np.uint32)
    wire[:, 2] = (b["proto"].astype(np.uint32) << 24) \
        | (b["tcp_flags"].astype(np.uint32) << 16) \
        | (b["ep_slot"].astype(np.uint32) << 3) \
        | (b["is_v6"].astype(np.uint32) << 2) \
        | (b["direction"].astype(np.uint32) << 1) \
        | b["valid"].astype(np.uint32)
    if not l7:
        return wire, addr_dict
    path_dict, path_idx = _pack_path_dict(b["http_path"], path_words,
                                          min_path_rows)
    wire[:, 3] = (b["http_method"].astype(np.uint32) << 16) \
        | path_idx.astype(np.uint32)
    return wire, addr_dict, path_dict


def unpack_batch_addrdict_jnp(wire, addr_dict, path_dict=None):
    """Device-side unpack of the address-dictionary wire."""
    import jax.numpy as jnp
    n = wire.shape[0]
    w2 = wire[:, 2]
    b = {
        "src": addr_dict[(wire[:, 0] >> 16).astype(jnp.int32)],
        "dst": addr_dict[(wire[:, 0] & 0xFFFF).astype(jnp.int32)],
        "sport": (wire[:, 1] >> 16).astype(jnp.int32),
        "dport": (wire[:, 1] & 0xFFFF).astype(jnp.int32),
        "proto": (w2 >> 24).astype(jnp.int32),
        "tcp_flags": ((w2 >> 16) & 0xFF).astype(jnp.int32),
        "ep_slot": ((w2 >> 3) & PACKA_EP_SLOT_MAX).astype(jnp.int32),
        "is_v6": ((w2 >> 2) & 1).astype(bool),
        "direction": ((w2 >> 1) & 1).astype(jnp.int32),
        "valid": (w2 & 1).astype(bool),
    }
    if wire.shape[1] == PACKA_L7_WORDS and path_dict is not None:
        w3 = wire[:, 3]
        b["http_method"] = ((w3 >> 16) & 0xFF).astype(jnp.int32)
        b["http_path"] = _unpack_dict_paths_jnp(
            path_dict, (w3 & 0xFFFF).astype(jnp.int32))
    else:
        b["http_method"] = jnp.full((n,), C.HTTP_METHOD_ANY,
                                    dtype=jnp.int32)
        b["http_path"] = jnp.zeros((n, C.L7_PATH_MAXLEN), dtype=jnp.uint8)
    return b


def unpack_batch_v4_jnp(packed):
    """Device-side unpack of the compact v4 format → standard batch dict
    (v4-mapped addresses: words [0, 0, 0xFFFF, addr])."""
    import jax.numpy as jnp
    n = packed.shape[0]
    w3 = packed[:, 3]
    zeros = jnp.zeros((n,), dtype=jnp.uint32)
    ffff = jnp.full((n,), 0xFFFF, dtype=jnp.uint32)
    return {
        "src": jnp.stack([zeros, zeros, ffff, packed[:, 0]], axis=-1),
        "dst": jnp.stack([zeros, zeros, ffff, packed[:, 1]], axis=-1),
        "sport": (packed[:, 2] >> 16).astype(jnp.int32),
        "dport": (packed[:, 2] & 0xFFFF).astype(jnp.int32),
        "proto": (w3 >> 24).astype(jnp.int32),
        "tcp_flags": ((w3 >> 16) & 0xFF).astype(jnp.int32),
        "http_method": jnp.full((n,), C.HTTP_METHOD_ANY, dtype=jnp.int32),
        "http_path": jnp.zeros((n, C.L7_PATH_MAXLEN), dtype=jnp.uint8),
        "is_v6": jnp.zeros((n,), dtype=bool),
        "direction": ((w3 >> 1) & 1).astype(jnp.int32),
        "valid": (w3 & 1).astype(bool),
        "ep_slot": ((w3 >> 2) & PACK4_EP_SLOT_MAX).astype(jnp.int32),
    }


def wire_words_for(use_l7: bool, use_wide: bool) -> int:
    """Wire width (uint32 words/record) the serving path ships for a given
    sticky-format decision — one ladder shared by the single-chip and the
    sharded pack paths so the format choice cannot diverge between them."""
    if use_l7:
        return PACK_L7DICT_WORDS if use_wide else PACK4_L7_WORDS
    return PACK_WORDS if use_wide else PACK4_WORDS


def unpack_wire_jnp(batch):
    """Device-side unpack of ANY packed wire form → the standard batch
    dict, dispatching on the wire's static width/pytree at trace time:
    tuple → dictionary wires (address or L7-path), [N,4] → compact v4,
    otherwise the full layout. Shared by the single-chip jit and the
    per-shard body of the meshed classify."""
    if isinstance(batch, (tuple, list)):
        wire = batch[0]
        if wire.shape[1] in (PACKA_WORDS, PACKA_L7_WORDS):
            # (wire, addr_dict[, path_dict]): address-dictionary wire
            return unpack_batch_addrdict_jnp(*batch)
        # (wire, path_dict): the L7 path-dictionary wire
        return unpack_batch_l7dict_jnp(*batch)
    if batch.shape[1] == PACK4_WORDS:
        return unpack_batch_v4_jnp(batch)
    return unpack_batch_jnp(batch)


def unpack_batch_jnp(packed):
    """Device-side unpack (inside jit) → the standard batch dict. The L7
    path block is reconstructed when present (static via array width)."""
    import jax.numpy as jnp
    n = packed.shape[0]
    w9 = packed[:, 9]
    b = {
        "src": packed[:, 0:4],
        "dst": packed[:, 4:8],
        "sport": (packed[:, 8] >> 16).astype(jnp.int32),
        "dport": (packed[:, 8] & 0xFFFF).astype(jnp.int32),
        "proto": (w9 >> 24).astype(jnp.int32),
        "tcp_flags": ((w9 >> 16) & 0xFF).astype(jnp.int32),
        "http_method": ((w9 >> 8) & 0xFF).astype(jnp.int32),
        "is_v6": ((w9 >> 2) & 1).astype(bool),
        "direction": ((w9 >> 1) & 1).astype(jnp.int32),
        "valid": (w9 & 1).astype(bool),
        "ep_slot": packed[:, 10].astype(jnp.int32),
    }
    if packed.shape[1] > PACK_WORDS:
        words = packed[:, PACK_WORDS:]
        path = jnp.stack([(words >> 24) & 0xFF, (words >> 16) & 0xFF,
                          (words >> 8) & 0xFF, words & 0xFF],
                         axis=-1).reshape(n, -1).astype(jnp.uint8)
        pad = C.L7_PATH_MAXLEN - path.shape[1]
        if pad > 0:        # variable-width wire: restore the full 64B block
            path = jnp.pad(path, ((0, 0), (0, pad)))
        b["http_path"] = path
    else:
        b["http_path"] = jnp.zeros((n, C.L7_PATH_MAXLEN), dtype=jnp.uint8)
    return b


# --------------------------------------------------------------------------- #
# Packed verdict slab: the down-wire, ONE flat uint32 vector per batch.
#
# The mirror of the packed wire above: one device→host transfer per batch
# instead of one per ``out`` column and counter (each read-back pays a
# fixed cost that dwarfs 256 rows of payload). The jitted step packs on
# device as its last stage (pack_out_jnp); finalize views the one
# materialized vector back into the same keys, dtypes and shapes
# (unpack_out). Nothing is dropped or made lazy — every column crosses for
# every batch.
#
# On a mesh (parallel/mesh.py, ``slab=True``) every chip packs its own
# rows and its copy of the psummed counters inside the shard_map body, so
# the vector is ``n`` equal per-chip segments under ONE layout (whose
# shapes are a chip's), sharded over 'flows' and still one transfer. An
# ``out`` column is then the segments' pieces in shard order (arrival
# order under device RSS, the steered geometry under host RSS: what the
# per-column read of the sharded array gives), a counter is shard 0's
# copy. One chip is the case n = 1.
#
# The layout is DERIVED at trace time from the columns' own shapes and
# dtypes, in sorted key order (the order a jit hands a dict back in):
#   bool column      → one bit of a shared flag segment (one word per
#                      element, up to 32 same-sized columns a segment)
#   32-bit integer   → its words bitcast, flattened row-major (so a
#                      [N, 4] address column is 4N contiguous words)
#   narrower integer → widened to 32 bits, narrowed again on the host
# and a column that IS another column (the same traced value under two
# keys — ``ct_state_pre`` and ``status``) ships once: both keys point at
# the one segment. A layout is a tuple of fields
#   (group, key, dtype, offset, shape, bit)        bit < 0: word segment
# — static and hashable, so it can ride a jit's output treedef.
# --------------------------------------------------------------------------- #
OUT_GROUPS = ("out", "counters")
OutLayout = Tuple[Tuple[str, str, str, int, Tuple[int, ...], int], ...]


def pack_out_jnp(out: Dict, counters: Dict):
    """Device-side pack (inside jit) of the classify step's results →
    (words [L] uint32, layout). ``unpack_out`` is the numpy twin."""
    import jax
    import jax.numpy as jnp
    segs: List = []             # flat uint32 segments, in slab order
    fields: List = []
    shipped: Dict[int, Tuple[int, int]] = {}   # id(column) → (offset, bit)
    open_flags: Dict[int, List[int]] = {}      # size → [seg, offset, bits]
    end = 0
    for group, cols in zip(OUT_GROUPS, (out, counters)):
        for key in sorted(cols):
            col = cols[key]
            dt = np.dtype(col.dtype)
            at = shipped.get(id(col))
            if at is None:
                flat = col.reshape(-1)
                if dt == np.bool_:
                    flags = open_flags.get(flat.shape[0])
                    if flags is None or flags[2] == 32:
                        flags = open_flags[flat.shape[0]] = [
                            len(segs), end, 0]
                        segs.append(jnp.zeros(flat.shape, jnp.uint32))
                        end += flat.shape[0]
                    seg, offset, bit = flags
                    segs[seg] = segs[seg] | (
                        flat.astype(jnp.uint32) << jnp.uint32(bit))
                    flags[2] += 1
                    at = (offset, bit)
                else:
                    if dt.kind not in "iu" or dt.itemsize > 4:
                        raise TypeError(
                            f"pack_out_jnp: {group}[{key!r}] is {dt}; the "
                            f"slab carries bools and integers of at most "
                            f"32 bits")
                    if dt.itemsize < 4:
                        flat = flat.astype(
                            jnp.int32 if dt.kind == "i" else jnp.uint32)
                    if flat.dtype != jnp.uint32:
                        flat = jax.lax.bitcast_convert_type(flat, jnp.uint32)
                    segs.append(flat)
                    at = (end, -1)
                    end += flat.shape[0]
                shipped[id(col)] = at
            fields.append((group, key, dt.name, at[0],
                           tuple(int(d) for d in col.shape), at[1]))
    return jnp.concatenate(segs), tuple(fields)


def unpack_out(words: np.ndarray, layout: OutLayout, shards: int = 1
               ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """Host twin of :func:`pack_out_jnp`: the materialized slab →
    (out_np, counters_np), key for key, dtype for dtype, shape for shape
    what the per-column read of the same step returns. ``shards`` is the
    number of equal per-chip segments ``words`` holds under the one
    ``layout`` (1 on a chip; the 'flows' width the batch was dispatched
    with on a mesh). On one segment 32-bit columns are views of ``words``
    (no copy); bools, narrow integers and a column gathered from several
    segments are copies. Like a device read-back, nothing returned is
    writable."""
    groups: Dict[str, Dict[str, np.ndarray]] = {g: {} for g in OUT_GROUPS}
    slabs = words.reshape(shards, -1)
    for group, key, dtype, offset, shape, bit in layout:
        dt = np.dtype(dtype)
        seg = slabs[:, offset:offset + math.prod(shape)]
        if group == "counters":
            seg = seg[:1]              # replicated: shard 0's copy
        else:
            shape = (shards * shape[0],) + shape[1:]
        if bit >= 0:
            col = (seg & np.uint32(1 << bit)) != 0
        elif dt.itemsize == 4:
            col = seg.view(dt)
        else:
            col = seg.view(np.int32 if dt.kind == "i"
                           else np.uint32).astype(dt)
        col = col.reshape(shape)
        col.flags.writeable = False
        groups[group][key] = col
    return groups["out"], groups["counters"]
