"""The packed verdict slab on a mesh (ISSUE 30): both meshed steps
(``make_sharded_classify_fn``: host RSS, ``make_unsteered_classify_fn``:
device RSS) with ``slab=True`` return one uint32 vector of ``n`` equal
per-chip segments under one layout, and ``records.unpack_out(words,
layout, n)`` of it is the column form of the same step key for key, dtype
for dtype, shape for shape, value for value, with the same ``new_ct``.
On the CPU's virtual devices, through ``JITDatapath`` as it packs and
dispatches each wire, so that what the meshed finalizers return (padding
cut, rows un-steered) is held to the column read too."""

import numpy as np
import pytest

from cilium_tpu.kernels.records import unpack_out
from cilium_tpu.parallel.mesh import (
    make_sharded_classify_fn, make_unsteered_classify_fn, steer_batch,
    unsteer_outputs)
from cilium_tpu.utils import constants as C
from tests.test_datapath import (
    _StepRecorder, same_columns as same, slab_batch, slab_engine)

# wire → (slab_batch's traffic, words a row; None: the column dict)
WIRES = {"v4": ("v4", 4), "wide": ("wide", 11), "l7": ("l7", 5),
         "dict": ("v4", None)}
# harvest: a pow2 serving batch (pre-steered under host RSS, as the
# pipeline's staging ring delivers it); control: 20 rows through the
# synchronous entry (padded to the mesh and cut back under device RSS,
# steered and un-steered under host RSS)
CASES = [(rss, wire, kind, (4, 1))
         for rss in ("device", "host") for wire in WIRES
         for kind in ("harvest", "control")]
# a 'rules' axis over 1: 2x2 under both RSS modes, and rules alone
CASES += [("device", "v4", "harvest", (2, 2)),
          ("host", "v4", "control", (2, 2)),
          ("host", "wide", "harvest", (1, 2))]


@pytest.mark.parametrize(
    "rss,wire,kind,mesh", CASES,
    ids=["-".join((r, w, k, "%dx%d" % m)) for r, w, k, m in CASES])
def test_sharded_slab_equals_column_read(rss, wire, kind, mesh):
    n, n_rules = mesh
    traffic, words = WIRES[wire]
    eng = slab_engine(n_shards=n, rule_shards=n_rules, rss_mode=rss,
                      zero_copy_ingest=words is not None)
    dp = eng.datapath
    assert dp._rss_device == (rss == "device" and n > 1)
    results = []
    rec = dp._classify = _StepRecorder(
        dp._classify, lambda res: results.append(res) or res)
    make_fn = (make_unsteered_classify_fn if dp._rss_device
               else make_sharded_classify_fn)
    columns = make_fn(dp._mesh, probe_depth=dp.config.probe_depth,
                      v4_only=dp.config.v4_only, donate_ct=False)
    act = eng.active
    # two dispatches of the same flows: new ones, then established and
    # reply rows on the table the first one left
    for now in (1000, 1001):
        b = slab_batch(eng, traffic, "padded" if kind == "control"
                       else "valid", 40000)
        scatter, pre = None, False
        if kind == "control":
            b = {k: v[:20] for k, v in b.items()}
            if not dp._rss_device and n > 1:
                scatter = steer_batch(b, n, round_to_pow2=True)[1]
        elif not dp._rss_device and n > 1:
            b, pre_scatter, _per = steer_batch(b, n, per_shard=16)
            pre = True
        out, counters = dp.classify_async(
            act.tensors, act.snapshot, b, now, pre_steered=pre)()
        tensors, ct, dev_batch, now_arg, wi = rec.calls[-1]
        slab, new_ct = results[-1]
        if words is None:
            assert isinstance(dev_batch, dict)
        else:
            wire_arr = dev_batch[0] if isinstance(dev_batch, tuple) \
                else dev_batch
            assert wire_arr.shape[1] == words
            assert isinstance(dev_batch, tuple) == (wire == "l7")
        ref_out, ref_ct, ref_counters = columns(
            tensors, ct, dev_batch, now_arg, wi)
        # the slab: n equal segments in one array sharded over 'flows'
        assert slab.words.ndim == 1 and slab.words.shape[0] % n == 0
        if n > 1:
            assert slab.words.sharding.spec == ("flows",)
        got_out, got_counters = unpack_out(
            np.asarray(slab.words), slab.layout, n)
        same(got_out, ref_out)
        same(got_counters, ref_counters)
        assert not any(v.flags.writeable for v in got_out.values())
        assert sorted(new_ct) == sorted(ref_ct)
        for k in ref_ct:
            assert np.asarray(new_ct[k]).tobytes() \
                == np.asarray(ref_ct[k]).tobytes(), k
        # ct_state_pre is status, shipped once
        lay = {key: (offset, bit) for _g, key, _d, offset, _s, bit
               in slab.layout}
        assert lay["ct_state_pre"] == lay["status"]
        # what finalize hands on: the same columns, padding cut (device
        # RSS) or rows un-steered (host RSS), counters as they are
        ref_np = {k: np.asarray(v) for k, v in ref_out.items()}
        rows = int(b["valid"].shape[0])
        if scatter is not None:
            ref_np = unsteer_outputs(ref_np, scatter)
        elif dp._rss_device:
            ref_np = {k: v[:rows] for k, v in ref_np.items()}
        same(out, ref_np)
        same(counters, ref_counters)
        assert out["allow"].shape == (rows,)
        assert out["nat_dst"].shape == (rows, 4)
        if pre:
            out = unsteer_outputs(out, pre_scatter)
    # the batch did what its case is there for
    assert out["allow"].any() and not out["allow"].all()
    assert (out["status"] == C.CTStatus.ESTABLISHED).any()
    assert (out["status"] == C.CTStatus.REPLY).any()
    if wire == "wide":
        assert out["nat_dst"][:, 0].any()        # v6 words, not v4-mapped
    if wire == "l7":
        assert out["redirect"].any() or (
            out["reason"] == C.DropReason.POLICY_L7).any()
    assert dp.pack_stats["readback_slab"] == 2
    assert "readback_columns" not in dp.pack_stats
    eng.stop()
