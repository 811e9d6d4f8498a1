"""Fixed-capacity conntrack table layout (analog of upstream
``pkg/maps/ctmap`` — SURVEY.md §2: "Becomes fixed-capacity device hash table").

Structure-of-arrays layout, power-of-two capacity, open addressing with
bounded linear probing (PROBE_DEPTH slots). No dynamic memory on device —
a saturated probe window first tail-evicts its soonest-expiring evictable
occupant (kernels/conntrack.ct_evictable: established TCP is protected),
then fails the insert: counted, and the new flow classifies DROP CT_FULL
(fail closed — exhaustion must not mint untrackable flows). A device-side
epoch sweep (kernels/conntrack.py) reclaims expired slots.

Key: 10 uint32 words — src[4] + dst[4] (16-byte normalized addresses) +
(sport<<16|dport) + (proto<<8|open_dir). An all-zero key with expiry 0 marks
an empty slot; real keys always have a nonzero proto word.

Two forms of one table:

- The **placed** form (``make_ct_arrays``, ``place_ct_arrays``) is what
  lies on the device and what every dispatched program takes and returns:
  the key as ten word planes ``key0`` … ``key9``, each ``u32[cap]``,
  beside the six value columns, all of one shape. A program gathers and
  scatters a plane as it does ``expiry``, and the shape alone fixes how
  each lies, so no program can hand the next one another layout. The
  ``[cap, 10]`` matrix this replaces had no such form on the TPU: at 2^18
  the compiler placed it column-major and every batch re-laid it into a
  lane-padded row-major copy of 134 MB before the probe's row gathers and
  back after the insert's scatters (0.385 ms a dispatch whatever its
  rows), and at 2^21 it stayed column-major and the two reads of every
  slot's protocol word (``keys[:, 9]``) strode through all 84 MB of it.
  Plane 9 is that word, 8 MB read in order (PERF.md §6 "PR 48";
  tests/test_tpu_compile.py holds every owner of the table to the form).
- The **logical** form (``logical_ct_arrays``) is the interface of
  everything off the hot path — checkpoints, ``ct_arrays()`` /
  ``load_ct_arrays()``, the mesh's rehash, the API's listing, the
  oracle's comparison: ``keys`` ``u32[cap, 10]`` beside the same value
  columns. Conversion is a host copy at those edges, never part of a
  dispatched program.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict

import numpy as np

KEY_WORDS = 10
PROBE_DEPTH = 8

KEY_PLANES = tuple(f"key{w}" for w in range(KEY_WORDS))
# service rev-NAT (``rev_nat``): stable rev-NAT id + 1 of the DNAT applied at
# create time (see compile/lb.LBTables — stable ids are why stale CT entries
# fail closed instead of rewriting to another service's VIP), 0 = none
# (upstream: CtEntry.rev_nat_index)
VALUE_COLUMNS = ("expiry", "created", "flags", "pkts_fwd", "pkts_rev",
                 "rev_nat")
CT_PLACED_KEYS = KEY_PLANES + VALUE_COLUMNS


@dataclass
class CTConfig:
    capacity: int = 1 << 20          # 1M flows (BASELINE config 5)
    probe_depth: int = PROBE_DEPTH

    def __post_init__(self):
        if self.capacity & (self.capacity - 1):
            raise ValueError("CT capacity must be a power of two")


def make_ct_arrays(cfg: CTConfig) -> Dict[str, np.ndarray]:
    """Fresh empty table in the placed form. Kept as a dict-of-arrays
    pytree so jit donation and shard_map partitioning apply uniformly."""
    return {k: np.zeros((cfg.capacity,), dtype=np.uint32)
            for k in CT_PLACED_KEYS}


def key_planes(ct) -> tuple:
    """The ten key planes of a placed table, word 0 first."""
    return tuple(ct[k] for k in KEY_PLANES)


def place_ct_arrays(arrays: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Logical form → placed form (host copy; each plane contiguous)."""
    keys = np.asarray(arrays["keys"], dtype=np.uint32)
    placed = {k: np.ascontiguousarray(keys[:, w])
              for w, k in enumerate(KEY_PLANES)}
    placed.update((k, np.asarray(arrays[k])) for k in VALUE_COLUMNS)
    return placed


def logical_ct_arrays(ct) -> Dict[str, np.ndarray]:
    """Placed form (host or device arrays) → logical form, a host copy."""
    arrays = {"keys": np.stack([np.asarray(p) for p in key_planes(ct)],
                               axis=1)}
    arrays.update((k, np.array(ct[k])) for k in VALUE_COLUMNS)
    return arrays
