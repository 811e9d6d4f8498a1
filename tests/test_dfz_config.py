"""The egress node behind a whole routing table, in the benchmark
(``dfz-dualstack``, PR 53): its configuration file, its cell in the
manifest, its two readers, and the world at the configuration's counts.

(a) the file states the deployment (950,000 + 200,000 prefixes, the two
    histograms, the blocks, the tries that come of them), cuts nothing,
    lists every world key no source fixes under ``assumed`` with its reason
    ("from memory" where it is one), and its guarantees are
    ``lpm100k-zipf``'s six and a seventh;
(b) the cell and the metrics it reports: in the lists whose readers'
    premises hold here, and off the ones whose premise fails, each with its
    reader's own words;
(c) the two readers this PR brings return None, and do not raise, over a
    run of a program without the name, and read a run that has it;
(d) the world **at the configuration's counts** (reference and loop only:
    no engine at full size here): counts, lengths, the tries' node counts
    by a count of its own with numpy from the prefixes alone against what
    the file states, pools of the wanted sizes, the reference against a
    loop over the text on a few hundred addresses, the cell's traffic.

The world's own tests, at test size, are ``tests/test_dfz.py``.
"""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmarks import harness
from benchmarks import reference as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmarks", "configs")
NAME = "dfz-dualstack"
CELL = "dfz-dualstack.saturate-longflows"
NEW = ("kernels.lpm_v6_us_per_batch", "engine.lpm_build_s")
#: every list of the manifest the cell's name was appended to
REPORTS = ("verdicts_per_s", "feeder.rows_per_harvest", "pipeline.fill_ratio",
           "datapath.host_us_per_batch", "kernels.device_ns_per_row",
           "kernels.lpm_us_per_batch", "kernels.lpm_dualstack_hbm_share",
           "kernels.lb_us_per_batch", "lb.translated_share",
           "pipeline.finalize_own_us_per_batch", "feeder.apply_us_per_batch",
           "feeder.map_us_per_batch", "host.cpu_us_per_row",
           "host.flow_hashes_per_row", "datapath.wire_bytes_per_row",
           "datapath.wire_needed_share") + NEW
#: ... and those whose readers' premises fail here, each with the words of
#: its reader that say so
LEFT_OFF = {
    # it counts every row as a v4 address, four levels: four rows in ten
    # here walk sixteen
    "kernels.lpm_hbm_share": "the cell's deployment is v4 only",
    # one frontend of one service: 256 bytes a translated row, a twentieth
    # of the rows; the share is svc10k-maglev's
    "kernels.lb_hbm_share": "10,000 Maglev rows of 16,381",
    # no document of this world has rules.http
    "kernels.l7_hbm_share": 'world["n_rulesets"]',
    "kernels.l7_us_per_batch": "l7.match",
    "l7.checked_share": "carries a request",
    "datapath.l7_dict_us_per_batch": "datapath.pack.l7dict",
}
#: what the file's ``fixes`` says the program's tries hold; the world's own
#: table accounts for all but the endpoint's and the health prober's entries
NODES_V4, NODES_V6 = 40228, 188035
OWN_V4, OWN_V6 = 4, 15


def load(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# -- (a) the file -----------------------------------------------------------------
def test_the_file_states_the_deployment_and_cuts_nothing():
    cfg = load(NAME)
    assert cfg["reduced"] == [] and cfg["chips"] == 1 and cfg["shim"] == {}
    assert cfg["architecture"] is None and len(cfg["source"]) <= 200
    for part in ("configs[2]", "without the slice", "RouteViews", "RIPE RIS",
                 "950k v4", "200k v6", "CIDR Report", "generated from seed",
                 "dual-stack"):
        assert part in cfg["source"], part
    # no DaemonConfig field is added for it
    assert cfg["daemon"] == {"ct_capacity": 262144} \
        == load("lpm100k-zipf")["daemon"]
    assert cfg["rings"] == load("ct1m-50k")["rings"]
    assert cfg["live_flows"] == 100000 == load("lpm100k-zipf")["live_flows"]
    for field in ("deployment", "fixes"):
        assert cfg[field]
    tries = cfg["fixes"]["tries"]
    for part in (f"{NODES_V4:,}", f"{NODES_V6:,}", "1,150,007"):
        assert part in tries, part
    placed = (NODES_V4 + NODES_V6) * 256 * 16
    assert f"{placed:,} B on the chip" in cfg["deployment"]
    assert f"{placed // 4 * 3:,} B in the host form" in cfg["deployment"]
    assert 0.9e9 < placed < 1.1e9


def test_the_world_block_is_the_issues_and_lpm100k_zipfs_where_it_can_be():
    world, small = load(NAME)["world"], load("lpm100k-zipf")["world"]
    assert world["builder"] == "dfz"
    assert (world["n_v4"], world["n_v6"]) == (950000, 200000)
    assert (world["v4_blocks"], world["v6_blocks"]) == (40000, 30000)
    assert world["v4_length_mix"] == {
        "24": 0.62, "23": 0.10, "22": 0.11, "21": 0.052, "20": 0.045,
        "19": 0.025, "18": 0.0145, "17": 0.0085, "16": 0.014, "15": 0.0021,
        "14": 0.0012, "13": 0.0006, "12": 0.0005}
    assert world["v6_length_mix"] == {
        "48": 0.54, "32": 0.12, "40": 0.08, "44": 0.08, "36": 0.05,
        "29": 0.03, "46": 0.02, "47": 0.02, "33": 0.01, "34": 0.01,
        "35": 0.01, "38": 0.01, "42": 0.01, "45": 0.01}
    assert sum(world["v4_length_mix"].values()) == pytest.approx(1.0, abs=0.01)
    assert sum(world["v6_length_mix"].values()) == pytest.approx(1.0)
    assert world["cover_cidrs"] == ["0.0.0.0/1", "2000::/5"]
    assert (world["pool"], world["v6_share"], world["v6_identity_plen"]) \
        == (1048576, 0.4, 16)
    # key for key lpm100k-zipf's wherever the full table changes no number
    for key in ("nested_share", "identity_plen", "admit_listed", "cidr_sets",
                "excepts_each", "services", "pool_split", "zipf_s",
                "service_share"):
        assert world[key] == small[key], key
    assert set(world) == (set(small) - {"n_prefixes", "length_mix"}) | {
        "n_v4", "n_v6", "v4_length_mix", "v6_length_mix", "v4_blocks",
        "v6_blocks", "v6_identity_plen", "v6_share"}


@pytest.mark.parametrize("key", [
    "n_v4", "n_v6", "v4_length_mix", "v6_length_mix", "v4_blocks",
    "v6_blocks", "nested_share", "v6_identity_plen", "cover_cidrs",
    "admit_listed", "cidr_sets", "excepts_each", "pool", "pool_split",
    "service_share", "v6_share", "live set", "from memory",
    "generated, not a collector's dump", "everything else"])
def test_every_figure_no_source_fixes_is_listed_as_assumed(key):
    cfg = load(NAME)
    assert len(cfg["assumed"][key]) > 60, key
    if key in ("n_v4", "n_v6", "v4_length_mix", "v6_length_mix", "v4_blocks",
               "v6_blocks", "nested_share", "v6_share",
               "generated, not a collector's dump"):
        assert "from memory" in cfg["assumed"][key], key


def test_what_is_not_assumed_is_the_sources():
    cfg = load(NAME)
    stated = set(cfg["world"]) - set(cfg["assumed"])
    # the source's own: the /8 identity blocks, the service, Zipf(1.1)
    assert stated == {"builder", "identity_plen", "services", "zipf_s"}
    assert "512,000" in cfg["assumed"]["generated, not a collector's dump"]
    assert cfg["assumed"]["rings"] == "as ct1m-50k"


def test_the_guarantees_are_lpm100k_zipfs_and_a_seventh():
    mine, theirs = load(NAME)["guarantees"], load("lpm100k-zipf")["guarantees"]
    assert mine[:6] == theirs and len(theirs) == 6 and len(mine) == 7
    assert mine[6] == "a prefix of one family never holds an address of " \
                      "the other"


def test_the_daemon_block_is_what_the_program_takes():
    from cilium_tpu.runtime.config import DaemonConfig
    cfg = DaemonConfig(**load(NAME)["daemon"])
    assert cfg.ct_capacity == 1 << 18 and not cfg.v4_only


# -- (b) the manifest ----------------------------------------------------------------
def test_the_configuration_and_the_cell_in_the_manifest(manifest):
    entry = manifest["configs"][-1]
    assert entry["name"] == NAME and set(entry) == {
        "name", "source", "file", "reduced", "why"}
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert entry["source"] == load(NAME)["source"]
    assert entry["file"] == "benchmarks/configs/dfz-dualstack.json"
    cell = manifest["workloads"][-1]
    assert cell == {"name": CELL, "config": NAME,
                    "traffic": "saturate-longflows", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for part in ("54/74-byte", "0.6/0.4", "100,000", "4 levels", "165 MB",
                 "16 of a 770 MB", "the host sets the rate", "kernels.lpm_"):
        assert part in cell["why"], part
    assert len(manifest["workloads"]) == 9 and len(manifest["configs"]) == 8
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert [c for c in manifest["configs"] if c["file"] == entry["file"]] \
        == [entry]
    # the mix is the file as it stands: lpm100k-zipf's cell has it too
    assert {w["traffic"] for w in manifest["workloads"]
            if w["config"] in (NAME, "lpm100k-zipf")} \
        == {"saturate-longflows"}


@pytest.mark.parametrize("metric", REPORTS)
def test_the_cell_reports(manifest, metric):
    entry = {m["name"]: m for m in manifest["end_to_end"]
             + manifest["per_layer"]}[metric]
    if metric in NEW:
        assert entry["workloads"] == [CELL]
    else:
        # appended: every cell that was on the list stands before it
        assert entry["workloads"][-1] == CELL and len(entry["workloads"]) > 1
        assert entry.get("moves", "verdicts_per_s") == "verdicts_per_s"
    assert os.path.exists(os.path.join(
        REPO, "benchmarks", "layers" if "moves" in entry else "e2e",
        metric + ".py"))


def test_the_two_new_metrics(manifest):
    assert [m["name"] for m in manifest["per_layer"][-2:]] == list(NEW)
    by = {m["name"]: m for m in manifest["per_layer"]}
    assert by[NEW[0]] == {
        "name": NEW[0], "unit": "us", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "verdicts_per_s", "workloads": [CELL]}
    assert by[NEW[1]] == {
        "name": NEW[1], "unit": "s", "better": "lower",
        "source": "program_span", "layer": "engine", "moves": "setup_s",
        "workloads": [CELL]}
    # units and layers follow the metrics they stand beside
    for new, old in ((NEW[0], "kernels.lpm_us_per_batch"),
                     (NEW[1], "engine.lb_build_s")):
        assert {k: by[new][k] for k in ("unit", "better", "source", "layer",
                                        "moves")} \
            == {k: by[old][k] for k in ("unit", "better", "source", "layer",
                                        "moves")}
    cell = harness.resolve_cell(manifest, CELL)
    assert set(cell.e2e) == {"verdicts_per_s", "setup_s"}
    assert set(REPORTS[1:]) | {"startup.compiles_in_window"} \
        == set(cell.layers)


@pytest.mark.parametrize("metric", sorted(LEFT_OFF))
def test_the_cell_stays_off_a_list_whose_premise_fails(manifest, metric):
    entry = {m["name"]: m for m in manifest["per_layer"]}[metric]
    assert CELL not in entry["workloads"]
    with open(os.path.join(REPO, "benchmarks", "layers",
                           metric + ".py")) as f:
        text = " ".join(f.read().split())
    assert LEFT_OFF[metric] in text, metric


def test_the_premises_of_the_lists_it_is_on():
    """By the readers' own docstrings: the roofline share that splits the
    rows by the window's own v6 share is a dual-stack deployment's, and
    the wire's needed share has something to compare where rows of two
    classes ride one batch."""
    for metric, words in (
            ("kernels.lpm_dualstack_hbm_share",
             "in a deployment of both families"),
            ("datapath.wire_needed_share", "rows of several classes"),
            ("kernels.lb_us_per_batch", "service frontend"),
            ("kernels.lpm_v6_us_per_batch", "lpm.walk.v6"),
            ("engine.lpm_build_s", "engine.regen.lpm")):
        with open(os.path.join(REPO, "benchmarks", "layers",
                               metric + ".py")) as f:
            assert words in " ".join(f.read().split()), metric
    world = load(NAME)["world"]
    assert 0 < world["v6_share"] < 1 and world["services"]["count"] == 1
    assert "n_rulesets" not in world


# -- (c) the readers --------------------------------------------------------------
def parent_shaped_run():
    rows = {"total": 0, "lb_translated": 0, "lb_no_backend": 0,
            "lpm_walked": 0, "lpm_missed": 0}
    return types.SimpleNamespace(
        stats0={"pipeline": {"verdict_rows": dict(rows)}, "feeder": {}},
        stats1={"pipeline": {"verdict_rows": dict(rows, total=4096000)},
                "feeder": {}},
        trace=None, spans=[("engine.regen.compile", -50.0, 12.0),
                           ("engine.regen.lb", -49.0, 0.2),
                           ("datapath.pack", 1.0, 0.0001)],
        w0=0.0, w1=40.0, info={}, verdicts_by=lambda t: 0)


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_finds_nothing_at_a_program_without_the_name(metric):
    read = harness.load_reader("layers", metric).read
    assert read(parent_shaped_run()) is None
    empty = parent_shaped_run()
    empty.stats0, empty.stats1, empty.spans = {}, {}, []
    assert read(empty) is None


def test_the_build_span_is_read_whole_and_the_longest():
    read = harness.load_reader("layers", NEW[1]).read
    run = parent_shaped_run()
    run.spans += [("engine.regen.lpm", -49.0, 6.5),      # before the window
                  ("engine.regen.lpm", 12.0, 0.5)]
    assert read(run) == 6.5


def test_the_v6_walk_over_a_recorded_trace(tmp_path, monkeypatch):
    """Over the trace recorded on the chip for ``lpm100k-zipf`` before this
    PR, whose program names ``lpm.walk`` and ``lb.step`` and neither
    family: nothing to read. Told to look for that trace's own pair of
    names, the same reader reads what ``kernels.lpm_us_per_batch`` reads:
    it maps any pair of scope names through ``lpm/trace.py``."""
    from benchmarks.lpm import trace as T
    from benchmarks.tests.test_lpm_trace import reader, recorded_run
    run_, batches = recorded_run(tmp_path, "lpm100k.xplane.pb",
                                 "lpm100k.spans.json", monkeypatch)
    module = harness.load_reader("layers", NEW[0])
    assert module.SCOPES == ("lpm.walk.v6", "lpm.walk.v4")
    assert T.scoped(run_) is not None and module.read(run_) is None
    module.SCOPES = (T.SCOPE_LPM, T.SCOPE_LB)
    got = module.read(run_)
    assert got == pytest.approx(reader("kernels.lpm_us_per_batch")(run_))
    assert got == pytest.approx(T.scoped(run_)["lpm_s"] / batches * 1e6) \
        and got > 10


# -- (d) the world at the configuration's counts ----------------------------------
@pytest.fixture(scope="module")
def full_world():
    from benchmarks.worlds import dfz
    t0 = time.monotonic()
    world = dfz.build(load(NAME)["world"])
    world.built_s = time.monotonic() - t0
    return world


def nodes_by_level(hi, plen):
    """Nodes of a stride-8 trie over the prefixes, by depth, from the
    prefixes alone: a prefix of p bits decides cells of the node its first
    (p - 1) // 8 bytes lead to, and needs every node on the way."""
    depth = np.maximum(plen - 1, 0) // 8
    return [1] + [int(np.unique(hi[depth >= d]
                                >> np.uint64(64 - 8 * d)).size)
                  for d in range(1, int(depth.max()) + 1)]


def test_the_world_builds_at_the_configurations_counts(full_world):
    w, world = full_world, load(NAME)["world"]
    # its own time limit: set-up's budget on the chip is 90 s for all of it
    assert w.built_s < 60, w.built_s
    named = 2 * (1 + 32 * 3 + 64) - 2        # both families' documents'
    # the listed ones, the two covers, the service's two backends
    assert w.n4 + w.n6 == 950000 + 200000 + 2 + 2
    assert w.v4.listed_hi.size + w.v6.listed_hi.size \
        >= 1150000 - named
    for fam, key, lengths in ((w.v4, "v4", range(12, 25)),
                              (w.v6, "v6", (29, 32, 33, 34, 35, 36, 38, 40,
                                            42, 44, 45, 46, 47, 48))):
        got, counts = np.unique(fam.listed_plen, return_counts=True)
        assert got.tolist() == list(lengths)
        mix = world[f"{key}_length_mix"]
        top = max(mix, key=mix.get)
        assert got[counts.argmax()] == int(top)
        assert abs(counts.max() / fam.n - mix[top]) < 0.06
        assert fam.blocks.size == world[f"{key}_blocks"]
        assert len(fam.docs) == 1 + 32 + 64
    assert len(w.policy_docs()) == 195
    listed = w.listed()
    assert len(listed) == w.v4.listed_hi.size + w.v6.listed_hi.size
    blocks = {q for _p, q in listed}
    assert len(blocks) <= 220 + 1281 and len(blocks) > 1400
    assert all(q.endswith("/8") or q.endswith("::/16") for q in blocks)
    allowed, cover = w.table()
    assert allowed.size == w.n4 + w.n6 + 1 and cover.max() <= 3
    assert 0.4 < allowed[:w.n4].mean() < 0.65
    assert 0.3 < allowed[w.n4:-1].mean() < 0.55


def test_the_tries_node_counts_are_the_files(full_world):
    w = full_world
    v4 = nodes_by_level(w.v4.ipcache.hi, w.v4.ipcache.plen)
    v6 = nodes_by_level(w.v6.ipcache.hi, w.v6.ipcache.plen)
    assert v4 == [1, 220, 40001, 1]
    assert v6 == [1, 6, 1281, 28402, 29729, 128600]
    # ... and the dead node, and the program's own entries' nodes
    assert sum(v4) + 1 + OWN_V4 == NODES_V4
    assert sum(v6) + 1 + OWN_V6 == NODES_V6
    tries = load(NAME)["fixes"]["tries"]
    for n in [v4[1], 40000] + v6[1:]:
        assert f"{n:,}" in tries, n
    # node * 256 + byte stays under 2^31, and a slot in 23 bits
    assert NODES_V6 * 256 < 1 << 31 and w.n4 + w.n6 + 3 < 1 << 23


def test_the_pools_are_of_the_wanted_sizes(full_world):
    w, world = full_world, load(NAME)["world"]
    want = [int(round(s * world["pool"])) for s in world["pool_split"]]
    assert want == [524288, 314573, 209715]
    for kind in range(3):
        n4, n6 = (w.pool(fam, kind)[0].size for fam in (False, True))
        assert n4 + n6 == want[kind]
        assert n6 == int(round(0.4 * want[kind]))
    assert sum(w.pool(False, k)[0].size for k in range(3)) == 629146
    assert sum(w.pool(True, k)[0].size for k in range(3)) == 419430


def test_the_reference_agrees_with_the_loop_at_full_size(full_world):
    """A few hundred addresses, each against every prefix of its family's
    text (ints, no numpy): the longest that holds it, and what the
    documents say of that one."""
    import ipaddress
    from tests.test_dfz import peer_text, the_flows
    w = full_world
    flows = the_flows(w, np.random.default_rng(11), 120, 80, 40)
    n = flows["sport"].shape[0]
    want, named = ref.expected_allow(w, flows), w.prefix_text(flows)
    front = w.cells(flows) >= w.n4 + w.n6
    entries = {False: [], True: []}
    for fam in (w.v4, w.v6):
        e, bits = fam.ipcache, 128 if fam.is_v6 else 32
        for i, (hi, plen) in enumerate(zip(e.hi.tolist(), e.plen.tolist())):
            net = (hi << 64) if fam.is_v6 else (hi >> 32)
            entries[fam.is_v6].append((net >> (bits - plen), bits - plen, i))
    for i in range(n):
        if front[i]:
            continue
        v6 = bool(flows["is_v6"][i])
        addr = int(ipaddress.ip_address(peer_text(flows, i)))
        best, best_plen = None, -1
        for net, host, at in entries[v6]:
            if addr >> host == net and (128 if v6 else 32) - host > best_plen:
                best, best_plen = at, (128 if v6 else 32) - host
        fam = w.v6 if v6 else w.v4
        assert (fam.entry_text(best) if best is not None else None) \
            == named[i], (i, named[i])
        assert (best is not None and fam.cover[best] > 0) == want[i]
    assert front.sum() >= 3 and want.sum() == 120


def test_the_traffic_at_full_size(full_world):
    from benchmarks.laws import flowmix
    w = full_world
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "saturate-longflows.json")) as f:
        law = json.load(f)["law_params"]
    mix = flowmix.generate(law, w, np.random.default_rng(7), 100000, 2000000)
    flows = mix["flows"]
    want = ref.expected_allow(w, flows)
    assert want[mix["kind"] <= flowmix.KIND_NEW_ALLOWED].all()
    assert not want[mix["kind"] >= flowmix.KIND_NEW_DENIED].any()
    per_flow = np.bincount(mix["sched_flow"], minlength=want.size)
    v6 = flows["is_v6"]
    assert 0.36 < v6.mean() < 0.44
    # by frames the heavy ranks decide: the share swings with which
    # family the heaviest flows drew
    assert 0.25 < per_flow[v6].sum() / per_flow.sum() < 0.55
    front = w.cells(flows) >= w.n4 + w.n6
    assert 0.03 < per_flow[front].sum() / per_flow.sum() < 0.12
    # the live flows reach tens of thousands of prefixes of both families
    live = np.arange(want.size) < 100000
    cell = w.cells(flows)
    reached = np.unique(cell[live & ~front])
    assert (reached < w.n4).sum() > 10000 and (reached >= w.n4).sum() > 5000
    assert set(ref.refusal_reasons(w, flows)[~want].tolist()) == {130}
    # a wrong table is there to be caught: rules that alone admit a cell
    # the traffic exercised
    wrong, dropped = ref.wrong_table(w, flows, per_flow.astype(np.float64),
                                     np.random.default_rng(8))
    assert wrong is not None and w.table()[0][dropped] and not wrong[dropped]
