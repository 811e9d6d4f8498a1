"""Pallas TPU megakernels for the classify interior (ROADMAP item 2:
"close the compute ceilings with fused Pallas kernels").

Why: the classify interior is a chain of gathers — the LPM walk (4-level
v4 / 16-level v6, each level a separate XLA gather materializing [N]
node/best intermediates in HBM), the policy ladder's gather→select→gather
round trips, and the double CT probe. SURVEY §7 step 4 prescribes
"jnp-first, Pallas only where fusion wins are proven"; whether fusing wins
has not been measured on a chip (ROADMAP S7).

Three kernels, each wrapping a *shared core* (the same jnp function the
reference path executes — kernels/lpm.lpm_walk_core,
kernels/conntrack.ct_probe_core, kernels/classify.classify_interior_core):

- ``lpm_lookup_fused``: the whole stride walk in one grid kernel. The node
  tables are kernel-resident; ``node``/``best`` stay in registers across
  all 4 (v4) / 16 (v6) levels and both families resolve in one launch —
  no [N] intermediates ever reach HBM.
- ``ct_probe_pair_fused``: forward and reverse probes share one residency
  of the CT key/expiry tables; both orientations' bucket loads and key
  compares run in a single kernel emitting ``(fwd_slot, rev_slot)``.
- ``policy_verdict_fused``: policy ladder gathers + L7 token matcher +
  verdict composition — ``decision → l7_cell → l7_match → allow/reason``
  never round-trips through HBM.

Because the kernel bodies call the *same* core functions the jnp reference
path runs, bit-identity between executors holds by construction; the
parity/fuzz suites (tests/test_fused.py) and the shadow-oracle auditor
(observe/audit.py, PR 7) enforce it continuously anyway. On CPU the
kernels run under ``interpret=True`` (the Pallas interpreter evaluates the
same jnp ops), which is how tier-1 CI pins the fused path without TPU
hardware.

On the TPU none of the three is selected: Mosaic refuses every body (see
``TPU_COMPILED_STAGES`` for the compiler's words), so ``fuse_plan`` drops
them statically and the XLA reference serves. There is no interpret mode
on the chip and no fallback around ``pallas_call``.

Geometry gates: a stage only fuses when its tables fit the kernel-resident
budget (``fuse_plan``) — a 1M-entry CT table or a BGP-scale trie stays on
the XLA reference, which is semantically identical. The budget is
trace-time static (array shapes), so the plan can never flap per batch.
"""

from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cilium_tpu.compile.ct_layout import key_planes
from cilium_tpu.kernels.classify import classify_interior_core
from cilium_tpu.kernels.conntrack import ct_probe_core
from cilium_tpu.kernels.lpm import lpm_walk_prov_core

#: per-stage kernel-resident table budget (bytes). ~VMEM-scale by default;
#: raise on hardware with the headroom, lower to force the jnp reference.
FUSED_TABLE_BYTES = int(os.environ.get(
    "CILIUM_TPU_FUSED_TABLE_BYTES", str(12 << 20)))

#: row-block size for the kernel grids (pow2; batches whose row count
#: divides evenly grid over blocks, anything else runs one block)
ROW_BLOCK = 1024

#: the snapshot tensors the verdict kernel keeps resident
POLICY_TENSOR_KEYS = ("id_class_of", "proto_family", "port_class",
                      "verdict", "enforced", "l7_methods", "l7_valid",
                      "l7_path_len", "l7_path")


class FusePlan(NamedTuple):
    """Per-stage fuse decision for one (snapshot, ct) geometry — computed
    from static shapes at trace time, so the executor choice is a property
    of the compiled program, never of batch contents."""
    lpm: bool
    ct: bool
    policy: bool

    @property
    def any(self) -> bool:
        return self.lpm or self.ct or self.policy


def _nbytes(a) -> int:
    return int(a.size) * a.dtype.itemsize


#: The stages whose kernel Mosaic compiles for the TPU: none. Measured on a
#: TPU v5e (jax/jaxlib 0.9.0, libtpu 0.0.34; chip_smoke.py phase
#: ``kernels`` re-checks it on every chip run). Each body indexes a
#: VMEM-resident table with a *vector* of row indices — a general gather —
#: and the TPU lowering accepts only the same-shape 2-D ``take_along_axis``
#: form. The compiler's words, per stage:
#:   lpm:    ValueError: Shape mismatch in input, indices and output
#:   ct:     NotImplementedError: Only 2D gather is supported
#:   policy: NotImplementedError: Only 2D gather is supported
#: The rank of a body's gathers does not change the refusal
#: (kernels/policy.py).
#: Until a body is rewritten (ROADMAP S7/D3 decide repair or deletion) the
#: kernels run only under the Pallas interpreter, i.e. ``fused_kernels="on"``
#: off-TPU — the CI configuration.
TPU_COMPILED_STAGES = FusePlan(lpm=False, ct=False, policy=False)


def fuse_plan(tensors, ct, v4_only: bool = False, rule_axis=None,
              budget: int = 0, compiled: bool = False) -> FusePlan:
    """Which stages of this geometry fit the fused kernels. ``rule_axis``
    disables the verdict kernel (the rule-sharded ladder needs a psum that
    must stay in the surrounding shard_map body). ``compiled`` (the kernels
    will be compiled by Mosaic, not interpreted) keeps only the stages in
    ``TPU_COMPILED_STAGES``."""
    budget = budget or FUSED_TABLE_BYTES
    lpm_bytes = _nbytes(tensors["lpm_v4"]) \
        + (0 if v4_only else _nbytes(tensors["lpm_v6"]))
    ct_bytes = sum(map(_nbytes, key_planes(ct))) + _nbytes(ct["expiry"])
    policy_bytes = sum(_nbytes(tensors[k]) for k in POLICY_TENSOR_KEYS)
    can = TPU_COMPILED_STAGES if compiled else FusePlan(True, True, True)
    return FusePlan(
        lpm=can.lpm and lpm_bytes <= budget,
        ct=can.ct and ct_bytes <= budget,
        policy=can.policy and rule_axis is None and policy_bytes <= budget,
    )


def _row_grid(n: int):
    """(block_rows, n_blocks): grid over ROW_BLOCK-row blocks when the
    batch divides evenly (the pow2 serving shapes), else one block."""
    if n > ROW_BLOCK and n % ROW_BLOCK == 0:
        return ROW_BLOCK, n // ROW_BLOCK
    return n, 1


def _full(shape):
    """BlockSpec for a kernel-resident table: every grid step sees the
    whole array (block index 0 on every axis)."""
    nd = len(shape)
    return pl.BlockSpec(shape, lambda i, _nd=nd: (0,) * _nd)


def _rows(blk, trailing=()):
    """BlockSpec for a per-row array blocked along axis 0."""
    shape = (blk,) + tuple(trailing)
    pad = (0,) * len(trailing)
    return pl.BlockSpec(shape, lambda i, _p=pad: (i,) + _p)


def _smem_scalar():
    return pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM)


# --------------------------------------------------------------------------- #
# (a) LPM stride walk
# --------------------------------------------------------------------------- #
def lpm_lookup_fused(lpm_v4, lpm_v6, addr_words, is_v6, default_index,
                     v4_only: bool = False, interpret: bool = False):
    """One grid kernel over row blocks: both families' stride walks with
    ``node``/``best``/``best_meta`` held in registers (see
    lpm.lpm_walk_prov_core — the same function the jnp reference runs) →
    (identity index [N] int32, packed lpm_prefix provenance [N] int32).
    ``default_index`` may be a traced scalar (it is the snapshot's world
    index); it rides in SMEM."""
    n = addr_words.shape[0]
    blk, grid = _row_grid(n)

    if v4_only:
        def kernel(default_ref, v4_ref, addr_ref, idx_ref, meta_ref):
            idx, meta = lpm_walk_prov_core(
                v4_ref[...], None, addr_ref[...], None, default_ref[0],
                v4_only=True)
            idx_ref[...] = idx
            meta_ref[...] = meta
        in_specs = [_smem_scalar(), _full(lpm_v4.shape), _rows(blk, (4,))]
        args = (jnp.asarray(default_index, jnp.int32).reshape(1),
                lpm_v4, addr_words)
    else:
        def kernel(default_ref, v4_ref, v6_ref, addr_ref, isv6_ref,
                   idx_ref, meta_ref):
            idx, meta = lpm_walk_prov_core(
                v4_ref[...], v6_ref[...], addr_ref[...], isv6_ref[...],
                default_ref[0], v4_only=False)
            idx_ref[...] = idx
            meta_ref[...] = meta
        in_specs = [_smem_scalar(), _full(lpm_v4.shape), _full(lpm_v6.shape),
                    _rows(blk, (4,)), _rows(blk)]
        args = (jnp.asarray(default_index, jnp.int32).reshape(1),
                lpm_v4, lpm_v6, addr_words, is_v6.astype(jnp.int32))

    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[_rows(blk), _rows(blk)],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32),
                   jax.ShapeDtypeStruct((n,), jnp.int32)],
        interpret=interpret,
    )(*args)
    return out[0], out[1]


# --------------------------------------------------------------------------- #
# (b) fused CT probe pair
# --------------------------------------------------------------------------- #
def ct_probe_pair_fused(ct, fwd_keys, rev_keys, now, probe_depth: int,
                        interpret: bool = False):
    """Forward + reverse probes over one residency of the CT key/expiry
    tables → (fwd_slot, rev_slot), each [N] int32 (-1 = miss). The probe
    loop is conntrack.ct_probe_core — identical to the reference."""
    n = fwd_keys.shape[0]
    blk, grid = _row_grid(n)
    # the kernel takes the key table as one [cap, 10] operand: stacked here,
    # at its edge, from the placed planes (compile/ct_layout)
    tab_keys, expiry = jnp.stack(key_planes(ct), axis=1), ct["expiry"]

    def kernel(now_ref, tab_ref, exp_ref, fwd_ref, rev_ref,
               fwd_out, rev_out):
        tab = tab_ref[...]
        tab = tuple(tab[:, w] for w in range(tab.shape[1]))
        exp = exp_ref[...]
        now_s = now_ref[0]
        fwd_out[...] = ct_probe_core(tab, exp, fwd_ref[...], now_s,
                                     probe_depth)
        rev_out[...] = ct_probe_core(tab, exp, rev_ref[...], now_s,
                                     probe_depth)

    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[_smem_scalar(), _full(tab_keys.shape), _full(expiry.shape),
                  _rows(blk, (10,)), _rows(blk, (10,))],
        out_specs=[_rows(blk), _rows(blk)],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32),
                   jax.ShapeDtypeStruct((n,), jnp.int32)],
        interpret=interpret,
    )(jnp.asarray(now, jnp.uint32).reshape(1), tab_keys, expiry,
      fwd_keys, rev_keys)
    return out[0], out[1]


# --------------------------------------------------------------------------- #
# (c) policy ladder + L7 matcher + verdict composition
# --------------------------------------------------------------------------- #
def policy_verdict_fused(tensors, ep_slot, direction, id_idx, proto, dport,
                         http_method, http_path, est, reply, valid,
                         interpret: bool = False):
    """Steps 3-5 of classify_step in one kernel (the body IS
    classify.classify_interior_core over VMEM-resident tables) →
    (allow [N] bool, reason [N] int32, status [N] int32,
    redirect [N] bool, matched_rule [N] int32)."""
    n = valid.shape[0]
    blk, grid = _row_grid(n)
    # bool tables ride as uint8 (TPU-friendly); the core casts back — the
    # reference path sees real bools either way, so this is bit-neutral
    tabs = {
        "id_class_of": tensors["id_class_of"],
        "proto_family": tensors["proto_family"],
        "port_class": tensors["port_class"],
        "verdict": tensors["verdict"],
        "enforced": tensors["enforced"].astype(jnp.uint8),
        "l7_methods": tensors["l7_methods"],
        "l7_valid": tensors["l7_valid"].astype(jnp.uint8),
        "l7_path_len": tensors["l7_path_len"],
        "l7_path": tensors["l7_path"],
    }
    tab_names = tuple(tabs)

    def kernel(*refs):
        row_refs = refs[:10]
        tab_refs = refs[10:10 + len(tab_names)]
        (allow_ref, reason_ref, status_ref, redirect_ref,
         mrule_ref) = refs[10 + len(tab_names):]
        t = {name: ref[...] for name, ref in zip(tab_names, tab_refs)}
        t["enforced"] = t["enforced"].astype(bool)
        t["l7_valid"] = t["l7_valid"].astype(bool)
        (ep_r, dir_r, id_r, proto_r, dport_r, meth_r, path_r, est_r,
         reply_r, valid_r) = row_refs
        allow, reason, status, redirect, mrule = classify_interior_core(
            t, ep_r[...], dir_r[...], id_r[...], proto_r[...], dport_r[...],
            meth_r[...], path_r[...], est_r[...].astype(bool),
            reply_r[...].astype(bool), valid_r[...].astype(bool))
        allow_ref[...] = allow.astype(jnp.int32)
        reason_ref[...] = reason
        status_ref[...] = status
        redirect_ref[...] = redirect.astype(jnp.int32)
        mrule_ref[...] = mrule

    # row-arg order matches the kernel's unpacking above: ep, dir, id,
    # proto, dport, method, path, est, reply, valid
    row_args = (ep_slot, direction, id_idx, proto, dport, http_method,
                http_path, est.astype(jnp.int32), reply.astype(jnp.int32),
                valid.astype(jnp.int32))
    row_specs = [_rows(blk)] * 6 + [_rows(blk, (http_path.shape[1],))] \
        + [_rows(blk)] * 3
    tab_specs = [_full(tabs[k].shape) for k in tab_names]

    allow, reason, status, redirect, mrule = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=row_specs + tab_specs,
        out_specs=[_rows(blk)] * 5,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)] * 5,
        interpret=interpret,
    )(*row_args, *(tabs[k] for k in tab_names))
    return allow.astype(bool), reason, status, redirect.astype(bool), mrule
