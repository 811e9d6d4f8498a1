"""The mixed node of the benchmark (``node-mixed``: every plane of the
datapath on one endpoint, PR 42): its configuration file, its cell in the
manifest, and its three readers.

(a) the file's three ``world`` blocks are, key for key, the ``world``
    blocks of the configurations that hold each plane alone; nothing is
    cut; every size no source fixes is under ``assumed`` with its reason;
    the guarantees are the three files' and one more;
(b) the cell and the metrics it reports: in the lists whose readers'
    premises hold here, and in neither of the two whose premise fails;
(c) the three readers this PR brings return None, and do not raise, over
    a run of a program without the counters (the parent, with these files
    laid over it, has to run the cell);
(d) the world builds at the sources' numbers, holds every meeting case,
    and its traffic has the planes' shares.

The world's own tests, at test size, are ``tests/test_mixednode.py``.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmarks import harness
from benchmarks import reference as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmarks", "configs")
CELL = "node-mixed.saturate-longflows"
PLANES = {"east_west": "pods10k-dualstack", "egress": "lpm100k-zipf",
          "http": "l7-http"}
NEW = ("datapath.wire_bytes_per_row", "datapath.wire_needed_share",
       "kernels.lpm_dualstack_hbm_share")
#: every list of the manifest the cell's name was appended to
REPORTS = ("verdicts_per_s", "feeder.rows_per_harvest", "pipeline.fill_ratio",
           "datapath.host_us_per_batch", "kernels.device_ns_per_row",
           "kernels.lpm_us_per_batch", "kernels.lb_us_per_batch",
           "lb.translated_share", "datapath.l7_dict_us_per_batch",
           "kernels.l7_us_per_batch", "l7.checked_share",
           "pipeline.finalize_own_us_per_batch", "feeder.apply_us_per_batch",
           "feeder.map_us_per_batch", "host.cpu_us_per_row",
           "host.flow_hashes_per_row") + NEW
#: ... and the two whose readers' premises fail here: the first counts four
#: trie levels a row ("the cell's deployment is v4 only"), the second reads
#: ``n_rulesets`` and ``rules`` at the top of the configuration's ``world``
LEFT_OFF = ("kernels.lpm_hbm_share", "kernels.l7_hbm_share")


def load(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# -- (a) the file -----------------------------------------------------------------
@pytest.mark.parametrize("block", sorted(PLANES))
def test_a_planes_block_is_its_own_configurations(block):
    mine, theirs = load("node-mixed")["world"][block], \
        load(PLANES[block])["world"]
    assert mine == theirs and list(mine) == list(theirs)


def test_nothing_is_cut_and_the_rest_is_assumed():
    cfg = load("node-mixed")
    assert cfg["reduced"] == [] and cfg["chips"] == 1 and cfg["shim"] == {}
    assert cfg["architecture"] is None
    assert cfg["daemon"] == {"ct_capacity": 262144} \
        == load("pods10k-dualstack")["daemon"] == load("lpm100k-zipf")["daemon"]
    assert cfg["rings"] == load("ct1m-50k")["rings"]
    assert cfg["live_flows"] == 100000 == load("lpm100k-zipf")["live_flows"]
    world = cfg["world"]
    own = set(world) - set(PLANES) - {"builder"}
    assert own == {"plane_shares", "pod_requesters", "pod_anchor_from"}
    assert world["builder"] == "mixednode"
    assert world["plane_shares"] == [0.5, 0.3, 0.2]
    assert world["pod_requesters"] == 0.25
    for key in own | {"disjoint ports", "live set", "requests a flow",
                      "within the planes", "rings"}:
        assert key in cfg["assumed"], key
    for key in own | {"disjoint ports", "live set"}:
        assert len(cfg["assumed"][key]) > 80, key
    for field in ("source", "deployment", "fixes"):
        assert cfg[field]
    assert "small by nature" in cfg["deployment"]


def test_the_guarantees_are_the_three_files_and_one_more():
    mine = load("node-mixed")["guarantees"]
    union = []
    for name in PLANES.values():
        union += [g for g in load(name)["guarantees"] if g not in union]
    assert mine[:-1] == union and len(union) == 8
    assert "whatever else rides its batch" in mine[-1]


# -- (b) the manifest ----------------------------------------------------------------
def test_the_configuration_and_the_cell_in_the_manifest(manifest):
    names = [c["name"] for c in manifest["configs"]]
    entry = manifest["configs"][names.index("node-mixed")]
    # after the three configurations that hold its planes alone
    assert all(names.index(p) < names.index("node-mixed")
               for p in PLANES.values())
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert entry["source"] == load("node-mixed")["source"]
    for part in ("north_star", "configs[1]", "configs[2]", "configs[3]"):
        assert part in entry["source"]
    assert entry["file"] == "benchmarks/configs/node-mixed.json"
    cells = [w["name"] for w in manifest["workloads"]]
    cell = manifest["workloads"][cells.index(CELL)]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "node-mixed", "saturate-longflows", 1)
    # after the cells of the configurations that hold its planes alone
    assert all(cells.index(c) < cells.index(CELL) for c in (
        "pods10k-dualstack.steady80", "lpm100k-zipf.saturate-longflows",
        "l7-http.saturate-longflows"))
    assert len(cell["why"]) <= 200
    for part in ("54-74", "75-180", "0.5 / 0.3 / 0.2", "100,000",
                 "gateway"):
        assert part in cell["why"], part
    assert len(manifest["configs"]) >= 6 and len(manifest["workloads"]) >= 7
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)


@pytest.mark.parametrize("metric", REPORTS)
def test_the_cell_reports(manifest, metric):
    entry = {m["name"]: m for m in manifest["end_to_end"]
             + manifest["per_layer"]}[metric]
    # appended: the cells of the one-plane configurations stand before it
    # on every list that held one when it came
    on = entry["workloads"]
    assert CELL in on
    assert metric in NEW or all(
        on.index(c) < on.index(CELL) for c in (
            "ct1m-50k.saturate", "lpm100k-zipf.saturate-longflows",
            "l7-http.saturate-longflows") if c in on)
    assert entry.get("moves", "verdicts_per_s") == "verdicts_per_s"
    if metric in NEW:
        # the three came together, in this order, after every metric the
        # manifest held before them
        at = [m["name"] for m in manifest["per_layer"]]
        first = at.index(NEW[0])
        assert at[first:first + 3] == list(NEW)
        assert at.index("host.flow_hashes_per_row") < first
    assert os.path.exists(os.path.join(
        REPO, "benchmarks", "layers" if "moves" in entry else "e2e",
        metric + ".py"))


@pytest.mark.parametrize("metric", LEFT_OFF)
def test_the_cell_stays_off_a_list_whose_premise_fails(manifest, metric):
    entry = {m["name"]: m for m in manifest["per_layer"]}[metric]
    assert CELL not in entry["workloads"]
    with open(os.path.join(REPO, "benchmarks", "layers",
                           metric + ".py")) as f:
        text = f.read()
    premise = {"kernels.lpm_hbm_share": "the cell's deployment is v4 only",
               "kernels.l7_hbm_share": 'world["n_rulesets"]'}[metric]
    assert premise in " ".join(text.split())
    world = load("node-mixed")["world"]
    assert "n_rulesets" not in world and world["east_west"]["v6_every"] == 4


def test_the_one_plane_wires_stand_beside_it(manifest):
    entry = {m["name"]: m for m in manifest["per_layer"]}[NEW[0]]
    # the four it came with, in the order it gave them (later cells after)
    assert entry["workloads"][:4] == [CELL, "ct1m-50k.saturate",
                                      "lpm100k-zipf.saturate-longflows",
                                      "l7-http.saturate-longflows"]
    # the two it alone reported when it came; a later dual-stack cell after
    for name in NEW[1:]:
        assert {m["name"]: m for m in manifest["per_layer"]}[name][
            "workloads"][0] == CELL
    cell = harness.resolve_cell(manifest, CELL)
    assert set(cell.e2e) == {"verdicts_per_s", "setup_s"}
    assert set(REPORTS[1:]) | {"startup.compiles_in_window"} \
        == set(cell.layers)


# -- (c) the readers over a program without the counters -----------------------------
def parent_shaped_run():
    """What the harness hands a reader after a run of the parent: the
    counters it had, none of this PR's."""
    rows = {"total": 0, "lb_translated": 0, "lb_no_backend": 0,
            "lpm_walked": 0, "lpm_missed": 0, "l7_checked": 0,
            "l7_refused": 0, "flow_hash_rows": 0}
    return types.SimpleNamespace(
        stats0={"pipeline": {"verdict_rows": dict(rows)}, "feeder": {}},
        stats1={"pipeline": {"verdict_rows": dict(rows, total=4096000)},
                "feeder": {}},
        trace=None, spans=[], w0=0.0, w1=40.0, info={},
        verdicts_by=lambda t: 0)


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_finds_nothing_at_the_parent(metric):
    read = harness.load_reader("layers", metric).read
    assert read(parent_shaped_run()) is None
    empty = parent_shaped_run()
    empty.stats0, empty.stats1 = {}, {}              # not even a pipeline
    assert read(empty) is None


def test_the_counter_readers_read_a_program_with_them():
    run = parent_shaped_run()
    run.stats0["pipeline"]["pack_stats"] = {"wire_bytes": 1000,
                                            "wire_bytes_needed": 400}
    run.stats1["pipeline"]["pack_stats"] = {
        "wire_bytes": 1000 + 4096000 * 50, "wire_bytes_needed": 400
        + 4096000 * 20}
    per_row = harness.load_reader("layers", NEW[0]).read(run)
    share = harness.load_reader("layers", NEW[1]).read(run)
    assert per_row == pytest.approx(50.0) and share == pytest.approx(0.4)
    run.stats1["pipeline"]["pack_stats"]["wire_bytes"] = 1000   # no batch
    assert harness.load_reader("layers", NEW[1]).read(run) is None


# -- (d) the world at the sources' numbers -------------------------------------------
def test_the_world_builds_at_full_size_with_every_meeting_case():
    from benchmarks.laws import flowmix
    from benchmarks.worlds import mixednode
    w = mixednode.build(load("node-mixed")["world"])
    assert (w.a.n_ids, w.a.n_rules, w.c.n_rulesets) == (10000, 5000, 200)
    assert len(w.b.listed()) + len({p for d in w.b._docs
                                    for p in (d[0], *d[1])}) >= 100000
    assert len(w.meeting) == 12
    # half the pods lie inside the anchor, a listed prefix under the cover
    lo, plen = w.anchor
    assert lo >> 24 == 100 and plen in (16, 20, 24)
    inside = ((w.pod_base + np.arange(w.a.n_ids)) >> (32 - plen)) \
        == lo >> (32 - plen)
    assert inside.sum() == min(5000, 1 << (31 - plen))
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "saturate-longflows.json")) as f:
        law = json.load(f)["law_params"]
    mix = flowmix.generate(law, w, np.random.default_rng(7), 20000, 400000)
    flows = mix["flows"]
    want = ref.expected_allow(w, flows)
    assert want[mix["kind"] <= flowmix.KIND_NEW_ALLOWED].all()
    assert not want[mix["kind"] >= flowmix.KIND_NEW_DENIED].any()
    plane = w.plane_of(flows)
    per_flow = np.bincount(mix["sched_flow"], minlength=plane.size)
    share = np.array([per_flow[plane == k].sum() for k in range(3)]) \
        / per_flow.sum()
    assert np.abs(share - [0.5, 0.3, 0.2]).max() < 0.03, share
    assert set(ref.refusal_reasons(w, flows)[~want].tolist()) == {130, 180}
