"""The HTTP deployment of the benchmark (``l7-http``: one ingress endpoint
behind 200 sets of HTTP rules, one set a TCP port, every frame carrying its
request line), its configuration file and, at a tiny size on the CPU, the
program under it held to the benchmark's **plain reference**
(``benchmarks/worlds/httprules.py``: byte prefixes with numpy from the rule
parameters).

(a) the configuration file states the source's numbers
    (``bench.py:build_config4`` at ``d48d000^``), lists every other key
    under ``assumed``, cuts nothing, and the cell and its mix are in the
    manifest;
(b) the file's world with ``n_rulesets``, ``live_flows`` and
    ``ct_capacity`` cut to test size, through ``Engine.submit`` on the
    jitted datapath with seeded flows, agrees with the reference row for
    row: allow, the drop reason against ``reasons(flows)``, the CT status,
    on first packets and again on the established flows' requests;
(c) ... and with the program's oracle (``FakeDatapath``), column for
    column, the two counters among them;
(d) ``l7.unpack`` and ``l7.match`` are in the lowered text of the
    datapath's program for this world, each over a gather; ``tiny-pods``
    has no L7 set and no request in any frame, so its program carries
    neither (``kernels/classify.py``: a one-row rule tensor has nothing to
    name; ``kernels/records.py``: no dictionary wire);
(e) the counters ``ciliumtpu_l7_checked_rows_total`` (the rows whose port
    has a set) and ``ciliumtpu_l7_refused_rows_total`` (the reference's
    rows refused under 180) add up over the batches, are rendered under
    those names, and the span ``datapath.pack.l7dict`` states each batch's
    distinct paths, its dictionary's rows and the second upload's bytes;
(f) the benchmark's byte count of a match (``benchmarks/l7/
    match_bytes.py``, which imports nothing of the program) against a hand
    count and against the shapes ``compile/l7.py`` builds.
"""

import copy
import os

import numpy as np
import pytest

from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.utils import constants as C
from tests.test_lpm100k_config import load, lowered_text

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmarks", "configs", "l7-http.json")
MIX = os.path.join(REPO, "benchmarks", "traffic", "saturate-longflows.json")
CELL = "l7-http.saturate-longflows"

#: what bench.py:build_config4 fixed at its full preset
SOURCE_WORLD = {
    "builder": "httprules",
    "n_rulesets": 200,
    "first_port": 80,
    "rules": [{"method": "GET", "path": "/api/v{i}"},
              {"method": "POST", "path": "/submit/{i}"},
              {"path": "/public/{i}"}],
    "peer_net": "11.0.0.0/8",
}
#: what the source does not state, and the file has to own up to
ASSUMED_WORLD = {"live_requests": [0.7, 0.15, 0.15],
                 "denied_split": [0.7, 0.3], "long_path_share": 0.05}
L7_METRICS = ("datapath.l7_dict_us_per_batch", "kernels.l7_us_per_batch",
              "kernels.l7_hbm_share", "l7.checked_share")
#: the cut to test size: scale only, every shape and share as the file's
TEST_SIZE = {"n_rulesets": 24}
TEST_CT = 1 << 14
BUCKET = 256
N_FLOWS = 3 * BUCKET
SEEDS = (3700000101, 3700000102, 3700000103)
REASON_OK, REASON_POLICY, REASON_L7 = 0, int(C.DropReason.POLICY), \
    int(C.DropReason.POLICY_L7)
OUT_KEYS = ("allow", "reason", "status", "redirect")
DICT_SPAN = "datapath.pack.l7dict"


# -- (a) the file -------------------------------------------------------------
@pytest.mark.parametrize("key", sorted(SOURCE_WORLD))
def test_the_file_states_the_sources_numbers(key):
    assert load(CONFIG)["world"][key] == SOURCE_WORLD[key]


@pytest.mark.parametrize("key", sorted(ASSUMED_WORLD))
def test_every_other_world_key_is_listed_as_assumed(key):
    cfg = load(CONFIG)
    assert cfg["world"][key] == ASSUMED_WORLD[key]
    assert key in cfg["assumed"] and len(cfg["assumed"][key]) > 40
    # ... as the test-size file argues them
    tiny = load(os.path.join(REPO, "benchmarks", "tests", "data", "configs",
                             "tiny-l7.json"))
    assert tiny["world"][key] == ASSUMED_WORLD[key]


def test_the_file_cuts_nothing_and_names_no_other_field():
    cfg = load(CONFIG)
    assert set(cfg["world"]) == set(SOURCE_WORLD) | set(ASSUMED_WORLD)
    assert cfg["daemon"] == {"ct_capacity": 65536}       # the source's 2^16
    assert cfg["shim"] == {} and cfg["reduced"] == [] and cfg["chips"] == 1
    main = load(os.path.join(REPO, "benchmarks", "configs", "ct1m-50k.json"))
    assert cfg["rings"] == main["rings"] == {
        "ring_size": 4096, "frame_size": 2048, "n_frames": 4096}
    assert cfg["live_flows"] == 8192
    assert {"live set", "rings", "requests a flow", "address family"} \
        <= set(cfg["assumed"])
    # the four guarantees of ct1m-50k, and the two of the L7 lane
    assert cfg["guarantees"][:4] == main["guarantees"]
    assert len(cfg["guarantees"]) == 6
    match, every_frame = cfg["guarantees"][4:]
    assert "byte prefix" in match and "64 bytes" in match \
        and "180" in match and "130" in match
    assert "established" in every_frame and "new flow" in every_frame
    for field in ("source", "deployment", "fixes", "assumed"):
        assert cfg[field]
    assert "build_config4" in cfg["source"] and "d48d000^" in cfg["source"]
    assert "small by nature" in cfg["deployment"]


def test_the_cell_and_its_mix_in_the_manifest():
    manifest = load(os.path.join(REPO, "BENCHMARK.json"))
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "l7-http", "saturate-longflows", 1)
    assert "small by nature" in cell["why"] and "8,192" in cell["why"]
    entry = {c["name"]: c for c in manifest["configs"]}["l7-http"]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmarks/configs/l7-http.json"
    assert "configs[3]" in entry["source"] \
        and "build_config4" in entry["source"] \
        and "d48d000^" in entry["source"]
    mix = load(MIX)
    assert (mix["loop"], mix["law"], mix["warmup_s"]) == (
        "saturate", "flowmix", 3.0)
    assert mix["law_params"] == {
        "live_share": 0.99, "zipf_s": 1.0, "new_allowed": 0.78,
        "new_denied": 0.18, "new_unknown": 0.04}
    assert mix["schedule_frames_per_s"] == 300000
    reads = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
             if CELL in m.get("workloads", [CELL])}
    assert reads == {"verdicts_per_s", "setup_s", "feeder.rows_per_harvest",
                     "pipeline.fill_ratio", "datapath.host_us_per_batch",
                     "kernels.device_ns_per_row",
                     "startup.compiles_in_window", *L7_METRICS,
                     # every saturate cell's, from the host's spans and
                     # thread clocks (PR 39)
                     "pipeline.finalize_own_us_per_batch",
                     "feeder.apply_us_per_batch", "feeder.map_us_per_batch",
                     "host.cpu_us_per_row",
                     # and how often a row is hashed (PR 41)
                     "host.flow_hashes_per_row",
                     # the one-plane wires beside the mixed node's (PR 42)
                     "datapath.wire_bytes_per_row"}
    for name in L7_METRICS:
        m = next(m for m in manifest["per_layer"] if m["name"] == name)
        # the cell first; the mixed node (PR 42) after it where the
        # reader's premises hold there
        assert m["workloads"][0] == CELL and m["moves"] == "verdicts_per_s"
        assert set(m["workloads"]) <= {CELL, "node-mixed.saturate-longflows"}
        assert os.path.exists(os.path.join(REPO, "benchmarks", "layers",
                                           name + ".py"))


# -- the deployment at test size ------------------------------------------------
def world_params():
    return dict(copy.deepcopy(load(CONFIG)["world"]), **TEST_SIZE)


def new_engine(fake: bool, **more):
    from cilium_tpu.runtime.datapath import FakeDatapath
    from cilium_tpu.runtime.engine import Engine
    cfg = DaemonConfig(ct_capacity=TEST_CT, batch_size=1024,
                       auto_regen=False, flowlog_mode="none", **more)
    return Engine(cfg, datapath=FakeDatapath(cfg) if fake else None)


def dict_spans(eng):
    return eng.tracer.spans(limit=1 << 16, name=DICT_SPAN)


class Served:
    """The world once, an engine on the jitted datapath (every submission
    traced, for the span) and one on the oracle; every case brings flows
    of its own and is run once."""

    def __init__(self):
        from benchmarks.worlds import httprules
        self.world = httprules.build(world_params())
        self.jit = new_engine(False, trace_sample_rate=1.0,
                              trace_capacity=1 << 16)
        self.fake = new_engine(True)
        for eng in (self.jit, self.fake):
            self.world.load(eng)
            eng.regenerate()
        self.ep_slot = self.jit.active.snapshot.ep_slot_of[self.world.ep_id]
        self.cases = {}

    def stop(self):
        self.jit.stop()
        self.fake.stop()

    def batches(self, flows):
        """``flows`` in BUCKET-row batches as ``Engine.submit`` takes them,
        the last padded with invalid rows."""
        from benchmarks.frames import columns_of, take
        n = flows["sport"].shape[0]
        for i in range(0, n, BUCKET):
            m = min(BUCKET, n - i)
            b = columns_of(take(flows, np.arange(i, i + BUCKET) % n),
                           self.world.ep_v4, self.world.ep_v6_words,
                           self.ep_slot)
            b["valid"][m:] = False
            yield m, b

    def submit(self, eng, flows):
        got = {k: [] for k in OUT_KEYS}
        for m, b in self.batches(flows):
            out = eng.submit(b).result(timeout=300)
            for k in OUT_KEYS:
                got[k].append(np.asarray(out[k])[:m])
        assert eng.drain(timeout=60)
        return {k: np.concatenate(v) for k, v in got.items()}

    def case(self, seed: int):
        if seed in self.cases:
            return self.cases[seed]
        from benchmarks import reference as ref
        from benchmarks.frames import concat, take
        rng = np.random.default_rng(seed)
        w, k = self.world, SEEDS.index(seed)
        lo, hi = 20000 + k * 10000, 30000 + k * 10000
        flows = concat([w.allowed_flows(rng, 540, lo, hi),
                        w.denied_flows(rng, 180, lo, hi),
                        w.unknown_flows(rng, 48, lo, hi)])
        order = rng.permutation(N_FLOWS)
        flows = {key: v[order] for key, v in flows.items()}
        rows0 = {name: eng.metrics.verdict_rows() for name, eng in
                 (("jit", self.jit), ("fake", self.fake))}
        spans0 = len(dict_spans(self.jit))
        c = dict(flows=flows, want=ref.expected_allow(w, flows),
                 why=ref.refusal_reasons(w, flows),
                 new=self.submit(self.jit, flows),
                 established=self.submit(self.jit, flows),
                 # the oracle judges row by row in Python: one bucket
                 oracle=self.submit(self.fake,
                                    take(flows, slice(0, BUCKET))))
        c["rows"] = {name: {key: eng.metrics.verdict_rows()[key] - v
                            for key, v in rows0[name].items()}
                     for name, eng in (("jit", self.jit),
                                       ("fake", self.fake))}
        c["spans"] = dict_spans(self.jit)[spans0:]
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
    c = served.case(seed)
    want, why, got = c["want"], c["why"], c[phase]
    refused = ~want
    assert 500 < want.sum() < 600
    assert (why[refused] == REASON_L7).sum() > 80 \
        and (why[refused] == REASON_POLICY).sum() > 60
    np.testing.assert_array_equal(got["allow"].astype(bool), want)
    # a refusal carries the reason the world states of its flow: 180 from
    # its port's set, 130 where no document names the port
    np.testing.assert_array_equal(got["reason"].astype(np.int64),
                                  np.where(want, REASON_OK, why))
    # a refused request leaves no state; an admitted flow's next request
    # finds its entry and is held to the rules in force all the same
    status = np.where(want, C.CTStatus.ESTABLISHED, C.CTStatus.NEW) \
        if phase == "established" else np.zeros(want.shape, np.int64)
    np.testing.assert_array_equal(got["status"].astype(np.int64), status)
    # the cell redirects to its set wherever the port has one
    np.testing.assert_array_equal(got["redirect"].astype(bool),
                                  why == REASON_L7)


# -- (c) ---------------------------------------------------------------------
@pytest.mark.parametrize("seed", SEEDS)
def test_rows_agree_with_the_programs_oracle(served, seed):
    c = served.case(seed)
    for key in OUT_KEYS:
        np.testing.assert_array_equal(c["new"][key][:BUCKET],
                                      c["oracle"][key], key)
    # ... and the oracle counts its rows as the program's counters do
    want, why = c["want"][:BUCKET], c["why"][:BUCKET]
    checked = int((why == REASON_L7).sum())
    refused = int((~want & (why == REASON_L7)).sum())
    assert 0 < refused < checked < BUCKET
    fake = c["rows"]["fake"]
    assert (fake["total"], fake["l7_checked"], fake["l7_refused"]) \
        == (BUCKET, checked, refused)


# -- (d) ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def programs(served):
    """Lowered text by deployment (``lowered_text`` lowers the jitted step
    again over the shapes of one real call, the dictionary wire's two
    arrays among them): this world's; ``tiny-pods`` as it stands."""
    from benchmarks.frames import columns_of
    from benchmarks.worlds import podrules
    w = served.world
    flows = w.allowed_flows(np.random.default_rng(1), BUCKET, 1, 2)
    out = {"l7-http": lowered_text(served.jit, columns_of(
        flows, w.ep_v4, w.ep_v6_words, served.ep_slot))}
    pods = podrules.build(load(os.path.join(
        REPO, "benchmarks", "tests", "data", "configs",
        "tiny-pods.json"))["world"])
    eng = new_engine(False)
    try:
        pods.load(eng)
        eng.regenerate()
        flows = pods.allowed_flows(np.random.default_rng(1), BUCKET, 1, 2)
        out["tiny-pods"] = lowered_text(eng, columns_of(
            flows, pods.ep_v4, pods.ep_v6_words,
            eng.active.snapshot.ep_slot_of[pods.ep_id]))
    finally:
        eng.stop()
    return out


@pytest.mark.parametrize("deployment,scope,carried", [
    ("l7-http", "l7.unpack", True),
    ("l7-http", "l7.match", True),
    # no L7 set and no request in any frame: the narrow wire, a one-row
    # rule tensor, and neither name
    ("tiny-pods", "l7.unpack", False),
    ("tiny-pods", "l7.match", False),
])
def test_the_programs_carry_the_lanes_names(programs, deployment, scope,
                                            carried):
    from cilium_tpu.kernels import classify, records
    assert (records.SCOPE_L7_UNPACK, classify.SCOPE_L7) \
        == ("l7.unpack", "l7.match")
    text = programs[deployment]
    assert (f"/{scope}/" in text) == carried
    if carried:
        # a gather under it: a row's words from the dictionary, a set's
        # rules from the rule tensors
        assert any(f"/{scope}/" in line and "gather" in line
                   for line in text.splitlines())


def test_the_other_kernels_names_stand_beside_them(programs):
    for text in programs.values():
        assert "/lpm.walk/" in text and "/lb.step/" not in text


# -- (e) ---------------------------------------------------------------------
@pytest.mark.parametrize("seed", SEEDS)
def test_the_counters_add_up_over_the_batches(served, seed):
    c = served.case(seed)
    want, why = c["want"], c["why"]
    rows = c["rows"]["jit"]                  # two passes, three batches each
    assert rows["total"] == 2 * N_FLOWS
    # checked: every row whose port has a set, first packet or established
    assert rows["l7_checked"] == 2 * int((why == REASON_L7).sum())
    # refused: the reference's refused-under-180 rows, both times round
    assert rows["l7_refused"] == 2 * int((~want & (why == REASON_L7)).sum())
    assert 0 < rows["l7_refused"] < rows["l7_checked"] < rows["total"]
    # the pre-CT counters beside them, unmoved: no frontend, no prefix
    assert rows["lpm_walked"] == rows["lpm_missed"] == rows["total"]
    assert rows["lb_translated"] == rows["lb_no_backend"] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_span_states_each_batchs_dictionary(served, seed):
    c = served.case(seed)
    spans = c["spans"]
    batches = [b for _m, b in served.batches(c["flows"])] * 2
    assert len(spans) == len(batches) == 6
    rows_before = 1
    for span, b in zip(spans, batches):
        a = span["attrs"]
        distinct = np.unique(b["http_path"], axis=0).shape[0]
        assert a["rows"] == BUCKET and a["distinct"] == distinct > 50
        # grow-only, a power of two that holds the batch's paths, in the
        # full 16 words once a path of over 60 bytes has been seen
        assert a["dict_rows"] >= max(distinct, rows_before) \
            and a["dict_rows"] & (a["dict_rows"] - 1) == 0
        assert a["bytes"] == a["dict_rows"] * 16 * 4
        assert span["duration_ms"] > 0
        rows_before = a["dict_rows"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_span_counts_the_references_distinct_requests(served, seed):
    """``distinct`` against the request lines themselves: each flow's path
    read back from its frame's payload and cut to 64 bytes, counted as
    Python ``bytes`` the way the plain reference holds a path; no sort of
    the program's, no numpy ``unique``."""
    from benchmarks.worlds.httprules import PATH_CUT
    c = served.case(seed)
    flows = c["flows"]
    lines = [bytes(p[:n]) for p, n in zip(flows["payload"],
                                          flows["payload_len"])]
    paths = [line.split(b" ")[1][:PATH_CUT] for line in lines]
    assert len(paths) == N_FLOWS and len(set(paths)) > 150
    want = [len(set(paths[i:i + BUCKET]))
            for i in range(0, N_FLOWS, BUCKET)] * 2
    assert [s["attrs"]["distinct"] for s in c["spans"]] == want
    # ... which are the paths the columns state, as the reference reads them
    stated = np.ascontiguousarray(flows["http_path"]).view(
        f"S{PATH_CUT}").reshape(-1)
    assert [p.rstrip(b"\0") for p in paths] == stated.tolist()


def test_the_counters_and_gauges_are_rendered(served):
    for seed in SEEDS:
        served.case(seed)
    m = served.jit.metrics
    lines = dict(line.rsplit(" ", 1) for line in
                 served.jit.render_metrics().splitlines()
                 if line.startswith("ciliumtpu_l7_"))
    assert int(lines["ciliumtpu_l7_checked_rows_total"]) == m.l7_checked > 0
    assert int(lines["ciliumtpu_l7_refused_rows_total"]) == m.l7_refused > 0
    spans = dict_spans(served.jit)
    assert int(lines["ciliumtpu_l7_dict_paths_total"]) \
        == sum(s["attrs"]["distinct"] for s in spans)
    # a batch seen again finds its dictionary on the device: what went up
    # is at most what the spans state, and at least the first of each
    up = int(lines["ciliumtpu_l7_dict_upload_bytes_total"])
    assert 0 < up <= sum(s["attrs"]["bytes"] for s in spans)
    wire = served.jit.datapath.l7_wire_stats()
    assert int(lines["ciliumtpu_l7_path_words"]) == wire["path_words"] == 16
    assert int(lines["ciliumtpu_l7_dict_rows"]) == wire["dict_rows"] \
        == max(s["attrs"]["dict_rows"] for s in spans)
    stats = served.jit.pipeline_stats()["verdict_rows"]
    assert (stats["total"], stats["l7_checked"], stats["l7_refused"]) \
        == (m.packets_total, m.l7_checked, m.l7_refused)
    # the oracle's engine renders the two counters, and has no wire
    fake = served.fake.render_metrics()
    assert "ciliumtpu_l7_checked_rows_total" in fake
    assert "ciliumtpu_l7_dict_paths_total" not in fake


# -- (f) ---------------------------------------------------------------------
@pytest.mark.parametrize("rows,batches,n_sets,rules,want", [
    # one request against its set: 64 + 4 + 4, and three rules of 70
    (1, 1, 200, 3, 72 + 210),
    # a full harvest of the cell: its rows' gathers would read 215,040
    # bytes of rules, more than the 42,210 the tensors hold
    (1024, 1, 200, 3, 1024 * 72 + 201 * 3 * 70),
    # the same rows in four dispatches read the tensors four times
    (1024, 4, 200, 3, 1024 * 72 + 4 * 201 * 3 * 70),
    # few rows: what they name, not the whole tensors
    (100, 1, 200, 3, 100 * 72 + 100 * 210),
    (0, 0, 200, 3, 0),
])
def test_match_bytes_against_a_hand_count(rows, batches, n_sets, rules,
                                          want):
    from benchmarks.l7 import match_bytes
    assert match_bytes.match_bytes(rows, batches, n_sets, rules) == want


def test_match_bytes_layout_is_the_programs(served):
    from benchmarks.l7 import match_bytes
    l7 = served.jit.active.snapshot.l7
    n_sets, rules = TEST_SIZE["n_rulesets"], len(SOURCE_WORLD["rules"])
    assert (l7.n_sets, l7.max_rules) == (n_sets, rules)
    tensors = (l7.path, l7.methods, l7.path_len, l7.valid)
    assert l7.path.shape == (n_sets + 1, rules, match_bytes.PATH_BYTES)
    assert match_bytes.PATH_BYTES == C.L7_PATH_MAXLEN
    for t in tensors[1:]:
        assert t.shape == (n_sets + 1, rules)
    # a rule: 64 path bytes, a method byte, a length word, a valid byte
    assert [t.dtype.itemsize for t in tensors] == [1, 1, 4, 1]
    assert sum(t[0, 0].nbytes for t in tensors) == match_bytes.RULE_BYTES \
        == 70
    assert sum(t.nbytes for t in tensors) \
        == match_bytes.rule_tensor_bytes(n_sets, rules)
    # a row: the path as cut, and two words on the device
    flows = served.world.allowed_flows(np.random.default_rng(2), 4, 1, 2)
    assert flows["http_path"].shape[1] == match_bytes.PATH_BYTES
    assert match_bytes.ROW_BYTES == 72
