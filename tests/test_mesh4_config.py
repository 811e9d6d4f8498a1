"""The four-chip deployment of the benchmark (``ct1m-50k-mesh4``:
conntrack sharded over a 4x1 'flows' mesh, device-side RSS) at a tiny
size on four virtual CPU devices, held to the benchmark's **plain
reference** (``benchmarks/worlds/podrules.py``: the table the rule
documents spell out, built with numpy from the rule parameters).

(a) rows through ``Engine(DaemonConfig(n_shards=4, rss_mode="device"))``
    agree with the reference row for row (allow, drop reason, conntrack
    status) as first packets, as established flows and in the reply
    direction, the two directions of a flow arriving in different chips'
    slices of the batch;
(b) one home: every admitted flow has exactly one conntrack entry in the
    whole mesh, on the shard ``flow_shard_of`` names, and the shards' live
    counts sum to the reference's admitted flows;
(c) the exchange's cumulative counters add ``exchange_bytes(rows, 4)`` a
    batch, and each batch leaves one ``datapath.readback`` span (its one
    sharded verdict slab, ``readback_slab``);
(d) the configuration file is ``ct1m-50k``'s deployment but for the mesh,
    and the benchmark's byte function is the program's.
"""

import json
import os
import time

import numpy as np
import pytest

from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.utils import constants as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_SHARDS = 4
SEEDS = (2900000101, 2900000102, 2900000103)
ROWS = (4, 256, 1000)
CASES = [(s, r) for s in SEEDS for r in ROWS]
WORLD = {"builder": "podrules", "n_ids": 64, "n_rules": 512,
         "port_span": 250}
REASON_OK, REASON_POLICY = 0, int(C.DropReason.POLICY)


def bucket_of(rows: int) -> int:
    """The pow2 bucket the pipeline dispatches ``rows`` rows in (the
    engine clamps its smallest bucket to the mesh)."""
    return max(N_SHARDS, 1 << (rows - 1).bit_length())


class Mesh:
    """One engine for the module; every case brings flows of its own."""

    def __init__(self):
        from benchmarks.worlds import podrules
        from cilium_tpu.runtime.engine import Engine
        self.world = podrules.build(WORLD)
        self.eng = Engine(DaemonConfig(
            n_shards=N_SHARDS, rss_mode="device", ct_capacity=65536,
            batch_size=1024, pipeline_min_bucket=N_SHARDS,
            auto_regen=False, flowlog_mode="none", trace_sample_rate=1.0,
            trace_capacity=1 << 14))
        self.world.load(self.eng)
        self.eng.regenerate()
        self.ep_slot = self.eng.active.snapshot.ep_slot_of[self.world.ep_id]
        self.cases = {}
        self.admitted = 0

    def columns(self, flows):
        from benchmarks.frames import columns_of
        return columns_of(flows, self.world.ep_v4, self.world.ep_v6_words,
                          self.ep_slot)

    def serve(self, batch):
        out = self.eng.submit(batch).result(timeout=300)
        assert self.eng.drain(timeout=60)
        return {k: np.asarray(out[k]) for k in
                ("allow", "reason", "status", "ct_full")}

    def case(self, seed: int, rows: int):
        """New → established → reply for ``rows`` flows of this case's
        own, each phase one batch. Run once, kept for (a), (b) and (c)."""
        key = (seed, rows)
        if key in self.cases:
            return self.cases[key]
        from benchmarks import reference as ref
        from benchmarks.frames import concat
        rng = np.random.default_rng(seed)
        n_den, n_unk = rows // 5, rows // 20
        n_ok = rows - n_den - n_unk
        w = self.world
        flows = concat([w.allowed_flows(rng, n_ok, 1, 2),
                        w.denied_flows(rng, n_den, 1, 2),
                        w.unknown_flows(rng, n_unk, 1, 2)])
        order = rng.permutation(rows)
        flows = {k: v[order] for k, v in flows.items()}
        # a source port of its own for every row of every case: no two
        # rows are one flow
        flows["sport"] = (2000 + CASES.index(key) * 2048
                          + np.arange(rows)).astype(np.int32)
        want = ref.expected_allow(w, flows)
        fwd = self.columns(flows)
        # the reply direction: the endpoint answers the pod, moved one
        # chip's slice along, so that a flow's two directions arrive on
        # different chips; only admitted flows answer
        shift = bucket_of(rows) // N_SHARDS
        at = (np.arange(rows) + shift) % rows
        rev = self.columns(flows)
        for a, b in (("src", "dst"), ("sport", "dport")):
            rev[a][at], rev[b][at] = fwd[b], fwd[a]
        rev["direction"][:] = C.DIR_EGRESS
        rev["valid"][at] = want
        t0 = time.monotonic()
        ex0 = self.eng.datapath.rss_exchange_stats()
        got = {"new": self.serve(dict(fwd)),
               "established": self.serve(dict(fwd)),
               "reply": self.serve(dict(rev))}
        self.admitted += int(want.sum())
        self.cases[key] = dict(
            flows=flows, want=want, fwd=fwd, at=at, shift=shift, got=got,
            t0=t0, t1=time.monotonic(), ex0=ex0,
            ex1=self.eng.datapath.rss_exchange_stats(),
            live=self.eng.ct_stats()["live"], admitted=self.admitted)
        return self.cases[key]


@pytest.fixture(scope="module")
def mesh():
    m = Mesh()
    yield m
    m.eng.stop()


@pytest.mark.parametrize("seed,rows", CASES)
def test_rows_agree_with_the_plain_reference(mesh, seed, rows):
    c = mesh.case(seed, rows)
    want, got = c["want"], c["got"]
    assert 0 < want.sum() < rows or rows < 8
    reason = np.where(want, REASON_OK, REASON_POLICY)
    for phase, status in (
            ("new", np.zeros(rows, np.int64)),
            # a denied flow left no state: it reads NEW again
            ("established", np.where(want, C.CTStatus.ESTABLISHED,
                                     C.CTStatus.NEW))):
        g = got[phase]
        assert not g["ct_full"].any()
        np.testing.assert_array_equal(g["allow"].astype(bool), want, phase)
        np.testing.assert_array_equal(g["reason"].astype(np.int64), reason,
                                      phase)
        np.testing.assert_array_equal(g["status"].astype(np.int64), status,
                                      phase)
    # the two directions of every flow sat in different chips' slices
    per_chip = bucket_of(rows) // N_SHARDS
    assert (np.arange(rows) // per_chip != c["at"] // per_chip).all()
    g, at = got["reply"], c["at"][want]
    assert g["allow"][at].astype(bool).all()
    assert (g["reason"][at] == REASON_OK).all()
    assert (g["status"][at] == C.CTStatus.REPLY).all()


@pytest.mark.parametrize("seed,rows", CASES)
def test_one_home_per_flow(mesh, seed, rows):
    from cilium_tpu.kernels.records import ct_key_words
    from cilium_tpu.parallel.mesh import flow_shard_of
    c = mesh.case(seed, rows)
    # the replies opened nothing, the denied left nothing: the mesh holds
    # the reference's admitted flows of the cases so far, and no more
    assert c["live"] == c["admitted"]
    ct = mesh.eng.ct_arrays()
    live = np.nonzero(ct["expiry"] > 0)[0]
    per_shard = ct["expiry"].shape[0] // N_SHARDS
    table = {ct["keys"][s].tobytes(): [] for s in live}
    for s in live:
        table[ct["keys"][s].tobytes()].append(int(s) // per_shard)
    keys = ct_key_words(c["fwd"])
    home = flow_shard_of(c["fwd"], N_SHARDS)
    for i in np.nonzero(c["want"])[0]:
        assert table.get(keys[i].tobytes()) == [int(home[i])], i
    for i in np.nonzero(~c["want"])[0]:
        assert keys[i].tobytes() not in table, i
    counts = np.bincount(live // per_shard, minlength=N_SHARDS)
    assert counts.sum() == mesh.admitted and (counts > 0).all()


@pytest.mark.parametrize("seed,rows", CASES)
def test_exchange_counters_and_one_readback_span_a_batch(mesh, seed, rows):
    from cilium_tpu.parallel.exchange import exchange_bytes
    c = mesh.case(seed, rows)
    ex0, ex1 = c["ex0"], c["ex1"]
    assert ex1["exchange_batches_total"] - ex0["exchange_batches_total"] == 3
    assert ex1["exchange_bytes_total"] - ex0["exchange_bytes_total"] \
        == 3 * exchange_bytes(bucket_of(rows), N_SHARDS)
    assert ex1["in_use"] == exchange_bytes(bucket_of(rows), N_SHARDS)
    spans = [s for s in mesh.eng.tracer.spans(limit=1 << 14)
             if c["t0"] <= s["start_mono"] < c["t1"]]
    back = [s for s in spans if s["name"] == "datapath.readback"]
    assert len(back) == 3
    for s in back:
        # one sharded slab a batch, not a read per column
        assert s["attrs"] == {"arrays": 1, "shards": N_SHARDS}
        # inside its batch's `datapath.compute`
        assert any(o["name"] == "datapath.compute"
                   and o["trace_id"] == s["trace_id"]
                   and o["start_mono"] <= s["start_mono"]
                   and s["duration_ms"] <= o["duration_ms"] for o in spans)
    text = mesh.eng.render_metrics()
    now = mesh.eng.datapath.rss_exchange_stats()
    assert f"ciliumtpu_rss_exchange_bytes_total " \
        f"{now['exchange_bytes_total']}" in text
    assert f"ciliumtpu_rss_exchange_batches_total " \
        f"{now['exchange_batches_total']}" in text
    ps = mesh.eng.datapath.pack_stats
    assert ps["readback_slab"] == now["exchange_batches_total"]
    assert ps["pack_fallback_steered"] == 0
    assert "readback_columns" not in ps
    assert "ciliumtpu_datapath_readback_slab_total " \
        f"{ps['readback_slab']}" in text


# -- (d) the configuration file and the byte function -------------------------
def config(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("key", ["world", "rings", "live_flows", "shim"])
def test_mesh4_is_the_one_chip_deployment(key):
    assert config("ct1m-50k-mesh4")[key] == config("ct1m-50k")[key]


def test_mesh4_states_its_mesh_its_cut_and_its_guarantees():
    one, four = config("ct1m-50k"), config("ct1m-50k-mesh4")
    assert four["chips"] == 4 and four["reduced"] == ["chips"]
    assert four["reduced_from"]["chips"]["published"] == 8
    assert four["daemon"] == {"ct_capacity": one["daemon"]["ct_capacity"],
                              "n_shards": 4, "rss_mode": "device"}
    DaemonConfig(**four["daemon"])              # fields the program has
    assert four["guarantees"][:len(one["guarantees"])] == one["guarantees"]
    assert len(four["guarantees"]) == len(one["guarantees"]) + 2
    assert set(one["fixes"]) <= set(four["fixes"])
    assert set(one["assumed"]) <= set(four["assumed"])


@pytest.mark.parametrize("rows,n", [(256, 4), (8192, 4), (4, 4), (1024, 8),
                                    (256, 1)])
def test_benchmark_byte_function_is_the_programs(rows, n):
    from benchmarks.mesh import exchange_bytes as eb
    from cilium_tpu.parallel import exchange as ex
    assert eb.materialized_bytes(rows, n) == ex.exchange_bytes(rows, n)
    assert (eb.REQUEST_WORDS, eb.REPLY_WORDS) == (ex.REQ_WORDS, ex.REP_WORDS)
    # what a chip sends: n-1 hops of its [L, 13] and n-1 of an [L, 2]
    L = rows // n
    assert eb.sent_bytes_per_chip(rows, n) \
        == (n - 1) * (L * ex.REQ_WORDS + L * ex.REP_WORDS) * 4
