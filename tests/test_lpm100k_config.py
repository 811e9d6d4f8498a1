"""The egress deployment of the benchmark (``lpm100k-zipf``: 100,000 CIDR
prefixes behind one endpoint whose flows leave it, and a service
frontend), its configuration file and, at a tiny size on the CPU, the
program under it held to the benchmark's **plain reference**
(``benchmarks/worlds/cidrsvc.py``: containment with numpy from the prefix
list and the rule parameters).

(a) the configuration file states the source's numbers
    (``bench.py:build_config3`` at ``d48d000^``), lists every other key
    under ``assumed``, cuts nothing, and the cell's mix is ``saturate``'s
    law at 1% first packets;
(b) the file's world with ``n_prefixes``, ``pool``, ``live_flows`` and
    ``ct_capacity`` cut to test size, through ``Engine.submit`` on the
    jitted datapath, agrees with ``World.table()`` / ``cells()`` row for
    row: allow, drop reason, ``svc``, and the NAT destination of a flow to
    the frontend (one of its service's backends);
(c) ... and with the program's oracle (``FakeDatapath``), column for
    column, the three counters among them;
(d) ``lpm.walk`` and ``lb.step`` are in the lowered text of the
    datapath's program for this world; ``tiny-pods`` has no service, so
    its program has no LB step to name (``kernels/classify.py``:
    ``has_lb``) and carries ``lpm.walk`` alone, and both once a service is
    upserted beside it; and in a program whose world has a policy image
    the ladder's three placed tables (``verdict``, ``port_class``,
    ``enforced``) reach their gather as the parameters they are placed
    as (``kernels/policy.py``; PR 35: the flat take cost the chip a
    copy of the whole image in every batch), and so do the two tries,
    placed as one table of entries each (``kernels/lpm.py``; PR 44: the
    reshape in the walk cost the chip a copy of the whole trie);
(e) the counters ``ciliumtpu_lb_translated_rows_total``,
    ``ciliumtpu_lb_no_backend_rows_total`` and
    ``ciliumtpu_lpm_rows_total{plen}`` add up to the rows submitted, bin
    for bin what the reference's longest prefix gives, and the placement
    gauges are the tries';
(f) the benchmark's byte count of a walk (``benchmarks/lpm/
    walk_bytes.py``, which imports nothing of the program) against a hand
    count and against the node layout ``compile/lpm.py`` builds.
"""

import copy
import json
import os
import re

import numpy as np
import pytest

from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.utils import constants as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmarks", "configs", "lpm100k-zipf.json")
MIX = os.path.join(REPO, "benchmarks", "traffic", "saturate-longflows.json")
CELL = "lpm100k-zipf.saturate-longflows"

#: what bench.py:build_config3 fixed at its full preset
SOURCE_WORLD = {
    "builder": "cidrsvc",
    "n_prefixes": 100000,
    "length_mix": {"16": 0.2, "20": 0.3, "24": 0.5},
    "identity_plen": 8,
    "cover_cidrs": ["0.0.0.0/1"],
    "services": {"count": 1, "named": 1, "backends_each": 2,
                 "frontends_each": 1},
    "pool": 65536,
    "zipf_s": 1.1,
}
#: what the source does not state, and the file has to own up to
ASSUMED_WORLD = {"nested_share": 0.25, "admit_listed": 64, "cidr_sets": 32,
                 "excepts_each": 2, "pool_split": [0.5, 0.3, 0.2],
                 "service_share": 0.1}
#: the cut to test size: scale only, every shape and share as the file's
TEST_SIZE = {"n_prefixes": 3000, "pool": 4096}
TEST_CT = 1 << 16
BUCKET = 256
SEEDS = (3400000101, 3400000102, 3400000103)
REASON_OK, REASON_POLICY = 0, int(C.DropReason.POLICY)
OUT_KEYS = ("allow", "reason", "status", "svc", "nat_dst", "nat_dport",
            "lpm_prefix")


def load(path):
    with open(path) as f:
        return json.load(f)


# -- (a) the file -------------------------------------------------------------
@pytest.mark.parametrize("key", sorted(SOURCE_WORLD))
def test_the_file_states_the_sources_numbers(key):
    assert load(CONFIG)["world"][key] == SOURCE_WORLD[key]


@pytest.mark.parametrize("key", sorted(ASSUMED_WORLD))
def test_every_other_world_key_is_listed_as_assumed(key):
    cfg = load(CONFIG)
    assert cfg["world"][key] == ASSUMED_WORLD[key]
    assert key in cfg["assumed"] and len(cfg["assumed"][key]) > 40


def test_the_file_cuts_nothing_and_names_no_other_field():
    cfg = load(CONFIG)
    assert set(cfg["world"]) == set(SOURCE_WORLD) | set(ASSUMED_WORLD)
    assert cfg["daemon"] == {"ct_capacity": 262144}      # the source's 2^18
    assert cfg["shim"] == {} and cfg["reduced"] == [] and cfg["chips"] == 1
    assert cfg["rings"] == {"ring_size": 4096, "frame_size": 2048,
                            "n_frames": 4096}
    assert cfg["live_flows"] == 100000
    assert {"live set", "rings"} <= set(cfg["assumed"])
    pods = load(os.path.join(REPO, "benchmarks", "configs",
                             "pods10k-dualstack.json"))
    assert set(pods["guarantees"]) <= set(cfg["guarantees"])
    assert len(cfg["guarantees"]) == len(pods["guarantees"]) + 2
    for field in ("source", "deployment", "fixes", "assumed"):
        assert cfg[field]
    assert "build_config3" in cfg["source"] and "d48d000^" in cfg["source"]


def test_the_cell_and_its_mix_in_the_manifest():
    manifest = load(os.path.join(REPO, "BENCHMARK.json"))
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lpm100k-zipf", "saturate-longflows", 1)
    entry = {c["name"]: c for c in manifest["configs"]}["lpm100k-zipf"]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert "configs[2]" in entry["source"] \
        and "build_config3" in entry["source"] \
        and "d48d000^" in entry["source"]
    mix, sat = load(MIX), load(os.path.join(REPO, "benchmarks", "traffic",
                                            "saturate.json"))
    assert mix["law_params"] == dict(sat["law_params"], live_share=0.99)
    assert (mix["loop"], mix["law"], mix["warmup_s"]) == (
        "saturate", "flowmix", 3.0)
    assert mix["schedule_frames_per_s"] == 300000
    reads = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
             if CELL in m.get("workloads", [CELL])}
    assert {"verdicts_per_s", "setup_s", "kernels.lpm_us_per_batch",
            "kernels.lpm_hbm_share", "kernels.lb_us_per_batch",
            "lb.translated_share", "kernels.device_ns_per_row"} <= reads


# -- the deployment at test size ------------------------------------------------
def world_params():
    return dict(copy.deepcopy(load(CONFIG)["world"]), **TEST_SIZE)


def new_engine(fake: bool):
    from cilium_tpu.runtime.datapath import FakeDatapath
    from cilium_tpu.runtime.engine import Engine
    cfg = DaemonConfig(ct_capacity=TEST_CT, batch_size=1024,
                       auto_regen=False, flowlog_mode="none")
    return Engine(cfg, datapath=FakeDatapath(cfg) if fake else None)


class Served:
    """The world once, an engine on the jitted datapath and one on the
    oracle; every case brings flows of its own and is run once."""

    def __init__(self):
        from benchmarks.worlds import cidrsvc
        self.world = cidrsvc.build(world_params())
        self.jit, self.fake = new_engine(False), new_engine(True)
        for eng in (self.jit, self.fake):
            self.world.load(eng)
            eng.regenerate()
        self.ep_slot = self.jit.active.snapshot.ep_slot_of[self.world.ep_id]
        self.cases = {}

    def stop(self):
        self.jit.stop()
        self.fake.stop()

    def submit(self, eng, flows):
        """``flows`` through ``Engine.submit`` in BUCKET-row batches, the
        last padded with invalid rows → the answered columns."""
        from benchmarks.frames import columns_of, take
        n, got = flows["sport"].shape[0], {k: [] for k in OUT_KEYS}
        for i in range(0, n, BUCKET):
            m = min(BUCKET, n - i)
            idx = np.arange(i, i + BUCKET) % n
            b = columns_of(take(flows, idx), self.world.ep_v4,
                           self.world.ep_v6_words, self.ep_slot)
            b["valid"][m:] = False
            out = eng.submit(b).result(timeout=300)
            for k in OUT_KEYS:
                got[k].append(np.asarray(out[k])[:m])
        assert eng.drain(timeout=60)
        return {k: np.concatenate(v) for k, v in got.items()}

    def case(self, seed: int):
        if seed in self.cases:
            return self.cases[seed]
        from benchmarks import reference as ref
        from benchmarks.frames import concat
        rng = np.random.default_rng(seed)
        w, k = self.world, SEEDS.index(seed)
        lo, hi = 20000 + k * 10000, 30000 + k * 10000
        flows = concat([w.allowed_flows(rng, 700, lo, hi),
                        w.denied_flows(rng, 200, lo, hi),
                        w.unknown_flows(rng, 100, lo, hi)])
        order = rng.permutation(1000)
        flows = {key: v[order] for key, v in flows.items()}
        from benchmarks.frames import take
        rows0 = {name: eng.metrics.verdict_rows() for name, eng in
                 (("jit", self.jit), ("fake", self.fake))}
        lpm0 = self.jit.metrics.lpm_rows.copy()
        # the oracle walks its tries row by row in Python: one bucket
        c = dict(flows=flows, want=ref.expected_allow(w, flows),
                 cell=w.cells(flows),
                 new=self.submit(self.jit, flows),
                 established=self.submit(self.jit, flows),
                 oracle=self.submit(self.fake,
                                    take(flows, slice(0, BUCKET))))
        c["rows"] = {name: {key: eng.metrics.verdict_rows()[key] - v
                            for key, v in rows0[name].items()}
                     for name, eng in (("jit", self.jit),
                                       ("fake", self.fake))}
        c["lpm_rows"] = self.jit.metrics.lpm_rows - lpm0
        self.cases[seed] = c
        return c


@pytest.fixture(scope="module")
def served():
    s = Served()
    yield s
    s.stop()


# -- (b) ---------------------------------------------------------------------
@pytest.mark.parametrize("phase", ("new", "established"))
@pytest.mark.parametrize("seed", SEEDS)
def test_rows_agree_with_the_plain_reference(served, seed, phase):
    from benchmarks.worlds import cidrsvc
    c, w = served.case(seed), served.world
    want, cell, got = c["want"], c["cell"], c[phase]
    n_prefix_cells = w.ipcache.addr.size
    front = cell >= n_prefix_cells
    assert 600 < want.sum() < 800 and 30 < front.sum() < 150
    np.testing.assert_array_equal(got["allow"].astype(bool), want)
    np.testing.assert_array_equal(
        got["reason"].astype(np.int64),
        np.where(want, REASON_OK, REASON_POLICY))
    status = np.where(want, C.CTStatus.ESTABLISHED, C.CTStatus.NEW) \
        if phase == "established" else np.zeros(want.shape, np.int64)
    np.testing.assert_array_equal(got["status"].astype(np.int64), status)
    # the one service is named: every flow to its frontend is translated,
    # and judged and tracked at a backend's address
    np.testing.assert_array_equal(got["svc"].astype(bool), front)
    assert want[front].all()
    s = cell[front] - n_prefix_cells
    b = got["nat_dst"][front][:, 3].astype(np.int64) \
        - (cidrsvc.BACKEND_NET + (s << 8) + 1)
    assert ((b >= 0) & (b < w.backends_each)).all()
    np.testing.assert_array_equal(got["nat_dport"][front],
                                  cidrsvc.BE_PORT_BASE + b)
    # every other flow keeps its destination
    np.testing.assert_array_equal(got["nat_dst"][~front][:, 3],
                                  c["flows"]["src"][~front][:, 3])
    # the longest prefix holding the destination, by its length
    plen = got["lpm_prefix"] & 0xFF
    has = ~front & (cell >= 0)
    np.testing.assert_array_equal(plen[has],
                                  96 + w.ipcache.plen[cell[has]])
    assert (got["lpm_prefix"][~front & (cell < 0)] == -1).all()
    assert (plen[front] == 128).all()


# -- (c) ---------------------------------------------------------------------
@pytest.mark.parametrize("seed", SEEDS)
def test_rows_agree_with_the_programs_oracle(served, seed):
    c = served.case(seed)
    new = {key: v[:BUCKET] for key, v in c["new"].items()}
    for key in ("allow", "reason", "svc"):
        np.testing.assert_array_equal(new[key], c["oracle"][key], key)
    # the oracle states a NAT destination for translated rows alone, and
    # picks the backend the program's Maglev row picks
    svc = new["svc"].astype(bool)
    assert svc.any()
    for key in ("nat_dst", "nat_dport"):
        np.testing.assert_array_equal(new[key][svc],
                                      c["oracle"][key][svc], key)
    # provenance: the same prefix length (slots are numbered by each build)
    np.testing.assert_array_equal(
        np.where(new["lpm_prefix"] < 0, -1, new["lpm_prefix"] & 0xFF),
        np.where(c["oracle"]["lpm_prefix"] < 0, -1,
                 c["oracle"]["lpm_prefix"] & 0xFF))
    # ... and counts its rows as the program's counters do
    cell = c["cell"][:BUCKET]
    assert c["rows"]["fake"] == {
        "total": BUCKET, "lpm_walked": BUCKET, "lb_no_backend": 0,
        "lb_translated": int((cell >= served.world.ipcache.addr.size).sum()),
        "lpm_missed": int((cell < 0).sum()),
        # no document of this world has rules.http: the L7 lane checks none
        "l7_checked": 0, "l7_refused": 0}


# -- (d) ---------------------------------------------------------------------
def lowered_text(eng, batch) -> str:
    """The StableHLO text, locations kept, of the program the datapath
    dispatches ``batch`` with: the jitted step lowered again over the
    shapes of one real call."""
    import jax
    dp, seen = eng.datapath, []
    step = dp._classify

    def spy(*args):
        seen.append(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), args))
        return step(*args)
    dp._classify = spy
    try:
        eng.submit(batch).result(timeout=300)
        assert eng.drain(timeout=60)
    finally:
        dp._classify = step
    return step.lower(*seen[-1]).as_text(debug_info=True)


@pytest.fixture(scope="module")
def programs(served):
    """Lowered text by deployment: this world's; ``tiny-pods`` as it
    stands; ``tiny-pods`` with one service upserted beside it."""
    from benchmarks.frames import columns_of
    from benchmarks.worlds import podrules
    out = {}
    w = served.world
    flows = w.allowed_flows(np.random.default_rng(1), BUCKET, 1, 2)
    out["lpm100k-zipf"] = lowered_text(served.jit, columns_of(
        flows, w.ep_v4, w.ep_v6_words, served.ep_slot))
    pods = podrules.build(load(os.path.join(
        REPO, "benchmarks", "tests", "data", "configs",
        "tiny-pods.json"))["world"])
    eng = new_engine(False)
    try:
        pods.load(eng)
        for name in ("tiny-pods", "tiny-pods+service"):
            if name.endswith("service"):
                eng.upsert_service(w.services()[0])
            eng.regenerate()
            flows = pods.allowed_flows(np.random.default_rng(1), BUCKET,
                                       1, 2)
            out[name] = lowered_text(eng, columns_of(
                flows, pods.ep_v4, pods.ep_v6_words,
                eng.active.snapshot.ep_slot_of[pods.ep_id]))
    finally:
        eng.stop()
    return out


@pytest.mark.parametrize("deployment,scope,carried", [
    ("lpm100k-zipf", "lpm.walk", True),
    ("lpm100k-zipf", "lb.step", True),
    ("tiny-pods", "lpm.walk", True),
    # no frontend, so no LB tensors and no LB step in its program
    ("tiny-pods", "lb.step", False),
    ("tiny-pods+service", "lpm.walk", True),
    ("tiny-pods+service", "lb.step", True),
])
def test_the_programs_carry_the_kernels_names(programs, deployment, scope,
                                              carried):
    from cilium_tpu.kernels import classify
    assert {classify.SCOPE_LPM, classify.SCOPE_LB} == {"lpm.walk", "lb.step"}
    text = programs[deployment]
    assert (f"/{scope}/" in text) == carried
    if carried:
        # a gather under it: the walk's node reads, the LB table's probes
        assert any(f"/{scope}/" in line and "gather" in line
                   for line in text.splitlines())


def uses_of_parameter(text, table):
    """The name of the parameter ``tensors[table]`` is placed as, and every
    line of ``@main`` that names it."""
    main = text.split("func.func public @main", 1)[1]
    head, body = main.split("\n", 1)
    body = body.split("func.func", 1)[0]
    arg, = re.findall(
        r"(%arg\d+): tensor<[^>]*> loc\(\"tensors\['" + table + r"'\]\"\)",
        head)
    return arg, [line.strip() for line in body.splitlines()
                 if re.search(re.escape(arg) + r"\b", line)]


def is_a_gather_of(arg, line):
    return re.search(r'= "stablehlo\.gather"\(' + re.escape(arg) + ",", line)


@pytest.mark.parametrize("table", ["verdict", "port_class", "enforced"])
def test_the_ladder_gathers_from_the_placed_table_itself(programs, table):
    """Every use of the table's parameter in ``@main`` is a
    ``stablehlo.gather`` with the parameter as its operand: no reshape,
    transpose, copy or call stands between the placed table and its
    gather."""
    arg, uses = uses_of_parameter(programs["tiny-pods"], table)
    assert uses, table
    for line in uses:
        assert is_a_gather_of(arg, line), (table, line[:200])


@pytest.mark.parametrize("deployment", ["lpm100k-zipf", "tiny-pods"])
@pytest.mark.parametrize("trie,levels", [("lpm_v4", 4), ("lpm_v6", 16)])
def test_the_walk_gathers_from_the_placed_trie_itself(programs, deployment,
                                                      trie, levels):
    """The same for the two tries (PR 44): every use of a placed trie in
    ``@main`` is a gather of whole entries ``[1, 3]`` from the 2-D
    parameter, one a level. A reshape between the placed trie and its read
    is what made the TPU's compiler re-lay the whole table in every
    batch."""
    arg, uses = uses_of_parameter(programs[deployment], trie)
    assert len(uses) == levels, (trie, len(uses))
    for line in uses:
        assert is_a_gather_of(arg, line), (trie, line[:200])
        assert "slice_sizes = array<i64: 1, 3>" in line \
            and re.search(r"\(tensor<\d+x3xi32>,", line), line[:400]


# -- (e) ---------------------------------------------------------------------
@pytest.mark.parametrize("seed", SEEDS)
def test_the_counters_add_up_to_the_rows_submitted(served, seed):
    c, w = served.case(seed), served.world
    cell, n = c["cell"], c["cell"].shape[0]
    front = cell >= w.ipcache.addr.size
    rows = c["rows"]["jit"]                  # two passes over the flows
    assert rows["total"] == rows["lpm_walked"] == 2 * n
    assert rows["lb_translated"] == 2 * int(front.sum())
    assert rows["lb_no_backend"] == 0
    assert rows["lpm_missed"] == 2 * int((cell < 0).sum())
    # bin for bin: a frontend's flow is walked at its backend's /32
    bins = np.where(front, 128, np.where(
        cell < 0, C.LPM_MISS_BIN,
        96 + w.ipcache.plen[np.clip(cell, 0, w.ipcache.addr.size - 1)]))
    np.testing.assert_array_equal(
        c["lpm_rows"], 2 * np.bincount(bins, minlength=C.LPM_PLEN_BINS))
    assert set(np.nonzero(c["lpm_rows"])[0]) >= {112, 116, 120, 128}


def test_the_counters_and_gauges_are_rendered(served):
    for seed in SEEDS:
        served.case(seed)
    from benchmarks.lpm import walk_bytes
    m, lpm = served.jit.metrics, served.jit.active.snapshot.lpm
    lines = dict(line.rsplit(" ", 1) for line in
                 m.render_prometheus().splitlines()
                 if line.startswith(("ciliumtpu_lb_", "ciliumtpu_lpm_")))
    assert int(lines["ciliumtpu_lb_translated_rows_total"]) \
        == m.lb_translated > 0
    assert int(lines["ciliumtpu_lb_no_backend_rows_total"]) == 0
    by_plen = {k: int(v) for k, v in lines.items()
               if k.startswith("ciliumtpu_lpm_rows_total{")}
    assert sum(by_plen.values()) == int(m.lpm_rows.sum()) == m.packets_total
    assert 'ciliumtpu_lpm_rows_total{plen="miss"}' in by_plen
    assert 'ciliumtpu_lpm_rows_total{plen="120"}' in by_plen
    nodes = int(lines['ciliumtpu_lpm_trie_nodes{family="v4"}'])
    assert nodes == lpm.v4_nodes.shape[0] > 1000
    assert int(lines['ciliumtpu_lpm_trie_bytes{family="v4"}']) \
        == nodes * walk_bytes.node_bytes() == lpm.v4_nodes.nbytes
    assert int(lines['ciliumtpu_lpm_trie_nodes{family="v6"}']) \
        == lpm.v6_nodes.shape[0]
    assert int(lines["ciliumtpu_lpm_prefixes"]) == len(lpm.prefixes) \
        >= TEST_SIZE["n_prefixes"]
    assert int(lines["ciliumtpu_lb_frontends"]) == 1
    assert int(lines["ciliumtpu_lb_backends"]) == 2
    stats = served.jit.pipeline_stats()["verdict_rows"]
    assert stats["total"] == m.packets_total


def test_a_frontend_without_backends_is_counted(served):
    """NO_SERVICE rows: a service whose backends went away."""
    from benchmarks.frames import columns_of
    from cilium_tpu.model.services import Service
    w = served.world
    for fake in (False, True):
        eng = new_engine(fake)
        try:
            w.load(eng)
            svc = w.services()[0]
            eng.upsert_service(Service(
                name=svc.name, namespace=svc.namespace,
                frontends=svc.frontends, lb_backends=()))
            eng.regenerate()
            flows = w.allowed_flows(np.random.default_rng(3), BUCKET, 1, 2)
            front = w.cells(flows) >= w.ipcache.addr.size
            out = eng.submit(columns_of(
                flows, w.ep_v4, w.ep_v6_words,
                eng.active.snapshot.ep_slot_of[w.ep_id])).result(timeout=300)
            assert eng.drain(timeout=60)
            rows = eng.metrics.verdict_rows()
            assert rows["lb_no_backend"] == int(front.sum()) > 0
            assert rows["lb_translated"] == 0
            np.testing.assert_array_equal(
                np.asarray(out["reason"])[front],
                int(C.DropReason.NO_SERVICE))
        finally:
            eng.stop()


# -- (f) ---------------------------------------------------------------------
@pytest.mark.parametrize("rows_v4,rows_v6,want", [
    (1, 0, 48),                  # 4 levels x one 12-byte entry
    (1024, 0, 49152),            # a full harvest of the cell
    (0, 1, 192),                 # 16 levels
    (1000, 24, 1000 * 48 + 24 * 192),
])
def test_walk_bytes_against_a_hand_count(rows_v4, rows_v6, want):
    from benchmarks.lpm import walk_bytes
    assert walk_bytes.walk_bytes(rows_v4, rows_v6) == want


def test_walk_bytes_layout_is_the_programs():
    from benchmarks.lpm import walk_bytes
    from cilium_tpu.compile import lpm
    built = lpm.build_lpm({"10.0.0.0/8": 1, "10.1.0.0/16": 2,
                           "fd00::/64": 3}, {1: 0, 2: 1, 3: 2}, 0)
    for nodes, levels in ((built.v4_nodes, lpm.V4_LEVELS),
                          (built.v6_nodes, lpm.V6_LEVELS)):
        assert nodes.shape[1:] == (1 << walk_bytes.STRIDE_BITS,
                                   walk_bytes.ENTRY_WORDS)
        assert nodes.dtype.itemsize == walk_bytes.WORD_BYTES
        assert nodes[0].nbytes == walk_bytes.node_bytes() == 3072
    assert (walk_bytes.V4_LEVELS, walk_bytes.V6_LEVELS) \
        == (lpm.V4_LEVELS, lpm.V6_LEVELS) == (4, 16)
