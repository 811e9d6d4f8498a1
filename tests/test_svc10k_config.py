"""The node that holds a cluster's services, in the benchmark
(``svc10k-maglev``, PR 45): its configuration file, its cell in the manifest,
its two readers and the yardstick's count of the LB step's bytes.

(a) the file states the deployment (10,000 services, M = 16,381, the two
    mixes, ``lb_map_max``), cuts nothing, lists what no source fixes under
    ``assumed`` with its reason, and its guarantees are ``lpm100k-zipf``'s
    six and two more;
(b) the cell and the metrics it reports: in the lists whose readers'
    premises hold here, and off the ones whose premise fails;
(c) the two readers this PR brings return None, and do not raise, over a
    run of a program without the span (the parent fails the cell before
    any run, but a traced run of another cell hands them such a run), and
    read a run that has something to read;
(d) ``lb/step_bytes.py`` against the shapes ``compile/lb.py`` builds;
(e) the world at the configuration's counts: the counts, every case, and
    the reference against the loop over the deployment's text on 10,000
    flows and more (reference and loop only: no engine at full size here).

The world's own tests, at test size, are ``tests/test_svclb.py``.
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
NAME = "svc10k-maglev"
CELL = "svc10k-maglev.saturate-longflows"
NEW = ("kernels.lb_hbm_share", "engine.lb_build_s")
#: every list of the manifest the cell's name was appended to
REPORTS = ("verdicts_per_s", "feeder.rows_per_harvest", "pipeline.fill_ratio",
           "datapath.host_us_per_batch", "kernels.device_ns_per_row",
           "kernels.lpm_us_per_batch", "kernels.lb_us_per_batch",
           "lb.translated_share", "pipeline.finalize_own_us_per_batch",
           "feeder.apply_us_per_batch", "feeder.map_us_per_batch",
           "host.cpu_us_per_row", "host.flow_hashes_per_row",
           "datapath.wire_bytes_per_row") + NEW
#: ... and those whose readers' premises fail here, each with the words of
#: its reader that say so
LEFT_OFF = {
    # its count of the walk's bytes is set against "a table far larger than
    # any on-chip memory": this world's trie is 1,305 nodes, 4 MB
    "kernels.lpm_hbm_share": "a table far larger than any on-chip memory",
    "kernels.lpm_dualstack_hbm_share": "a deployment of both families",
    # no document of this world has rules.http
    "kernels.l7_hbm_share": 'world["n_rulesets"]',
    "kernels.l7_us_per_batch": "l7.match",
    "l7.checked_share": "carries a request",
    "datapath.l7_dict_us_per_batch": "datapath.pack.l7dict",
    # every row of this world rides the narrow wire: nothing to compare
    "datapath.wire_needed_share": "wire_bytes_needed",
}


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
    for part in ("10,000 Services", "150,000 pods", "110 pods a node",
                 "maglev", "16381", "bpf-lb-map-max"):
        assert part in cfg["source"], part
    assert cfg["daemon"] == {"ct_capacity": 262144, "maglev_m": 16381,
                             "lb_map_max": 262144}
    assert cfg["rings"] == load("ct1m-50k")["rings"]
    assert cfg["live_flows"] == 100000 == load("lpm100k-zipf")["live_flows"]
    world = cfg["world"]
    assert world == {
        "builder": "svclb", "n_services": 10000, "external_services": 500,
        "named_external": 250,
        "backends_mix": {"2": 0.40, "5": 0.30, "15": 0.20, "60": 0.08,
                         "250": 0.02},
        "ports_mix": {"1": 0.7, "2": 0.2, "3": 0.1}, "udp_share": 0.1,
        "n_groups": 200, "target_ports": 16, "n_rules": 2000,
        "pods_per_node": 110, "service_share": 0.9, "svc_zipf_s": 1.0}
    mean = sum(int(k) * v for k, v in world["backends_mix"].items())
    assert mean == pytest.approx(15.1)
    for key in ("from memory", "backends_mix", "ports_mix", "udp_share",
                "in-cluster / external", "groups and rules", "service_share",
                "svc_zipf_s", "lb_map_max", "ct_capacity", "live set",
                "address family", "one target port a service",
                "no service without backends"):
        assert len(cfg["assumed"][key]) > 60, key
    assert cfg["assumed"]["rings"] == "as ct1m-50k"
    for field in ("deployment", "fixes"):
        assert cfg[field]
    assert "655,240,000" in cfg["deployment"]


def test_the_guarantees_are_lpm100k_zipfs_and_two_more():
    mine, theirs = load(NAME)["guarantees"], load("lpm100k-zipf")["guarantees"]
    assert mine[:6] == theirs and len(theirs) == 6 and len(mine) == 8
    assert "Maglev over 16,381 slots" in mine[6] \
        and "address and port" in mine[6]
    assert "keeps the backend" in mine[7] and "probe_mismatched" in mine[7]


def test_the_daemon_block_is_what_the_program_takes():
    from cilium_tpu.runtime.config import DaemonConfig
    cfg = DaemonConfig(**load(NAME)["daemon"])
    assert (cfg.maglev_m, cfg.lb_map_max) == (16381, 262144)
    assert DaemonConfig().lb_map_max == 65536 < 151000 < cfg.lb_map_max


# -- (b) the manifest ----------------------------------------------------------------
def test_the_configuration_and_the_cell_in_the_manifest(manifest):
    entry = {c["name"]: c for c in manifest["configs"]}[NAME]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert entry["source"] == load(NAME)["source"]
    assert entry["file"] == "benchmarks/configs/svc10k-maglev.json"
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "saturate-longflows", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for part in ("54/42-byte", "100,000", "0.9", "32,768", "10,000",
                 "655 MB"):
        assert part in cell["why"], part
    # it came after every cell that was there, and no cell left
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index("node-mixed.saturate-longflows")
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert [c for c in manifest["configs"] if c["file"] == entry["file"]] \
        == [entry]


@pytest.mark.parametrize("metric", REPORTS)
def test_the_cell_reports(manifest, metric):
    entry = {m["name"]: m for m in manifest["end_to_end"]
             + manifest["per_layer"]}[metric]
    assert CELL in entry["workloads"]
    if metric in NEW:
        assert entry["workloads"] == [CELL]
    else:
        # appended: every cell that was on the list stands before it, a
        # later one after
        on = entry["workloads"]
        assert on.index("node-mixed.saturate-longflows") < on.index(CELL) \
            or on.index("lpm100k-zipf.saturate-longflows") < on.index(CELL)
        assert entry.get("moves", "verdicts_per_s") == "verdicts_per_s"
    assert os.path.exists(os.path.join(
        REPO, "benchmarks", "layers" if "moves" in entry else "e2e",
        metric + ".py"))


def test_the_two_new_metrics(manifest):
    by = {m["name"]: m for m in manifest["per_layer"]}
    assert by[NEW[0]] == {
        "name": NEW[0], "unit": "ratio", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "verdicts_per_s", "workloads": [CELL]}
    assert by[NEW[1]] == {
        "name": NEW[1], "unit": "s", "better": "lower",
        "source": "program_span", "layer": "engine", "moves": "setup_s",
        "workloads": [CELL]}
    # the unit and the name follow kernels.lpm_hbm_share's
    assert by["kernels.lpm_hbm_share"]["unit"] == "ratio"
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


def test_what_the_world_states_of_those_premises():
    from benchmarks.worlds import svclb
    world = load(NAME)["world"]
    assert "n_rulesets" not in world and "v6_every" not in world
    w = svclb.build(world)
    # a /24 a node: a root, a node for 10, one for each second octet and
    # one for each node
    assert w.n_pods // 110 + 1 == 1303
    assert not any("rules" in rule.get("toPorts", [{}])[0]
                   for doc in w.policy_docs() for rule in doc["egress"])


# -- (c) the readers --------------------------------------------------------------
def parent_shaped_run():
    rows = {"total": 0, "lb_translated": 0, "lb_no_backend": 0,
            "lpm_walked": 0, "lpm_missed": 0}
    return types.SimpleNamespace(
        stats0={"pipeline": {"verdict_rows": dict(rows)}, "feeder": {}},
        stats1={"pipeline": {"verdict_rows": dict(rows, total=4096000)},
                "feeder": {}},
        trace=None, spans=[("engine.regen.compile", -50.0, 12.0),
                           ("datapath.pack", 1.0, 0.0001)],
        w0=0.0, w1=40.0, info={}, verdicts_by=lambda t: 0)


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_finds_nothing_at_a_program_without_the_span(metric):
    read = harness.load_reader("layers", metric).read
    assert read(parent_shaped_run()) is None
    empty = parent_shaped_run()
    empty.stats0, empty.stats1, empty.spans = {}, {}, []
    assert read(empty) is None


def test_the_build_span_is_read_whole_and_the_longest():
    read = harness.load_reader("layers", NEW[1]).read
    run = parent_shaped_run()
    run.spans += [("engine.regen.lb", -49.0, 7.25),     # before the window
                  ("engine.regen.lb", 12.0, 0.5)]
    assert read(run) == 7.25


def test_the_steps_share_over_a_recorded_trace(tmp_path, monkeypatch):
    """``kernels.lb_hbm_share`` over the trace recorded on the chip for
    ``lpm100k-zipf`` (its program names ``lb.step``): 256 bytes a row over
    the scope's device time at the chip's peak."""
    from benchmarks.lb import step_bytes
    from benchmarks.lpm import trace as T
    from benchmarks.tests.test_lpm_trace import reader, recorded_run
    run_, batches = recorded_run(tmp_path, "lpm100k.xplane.pb",
                                 "lpm100k.spans.json", monkeypatch)
    share = reader(NEW[0])(run_)
    sc = T.scoped(run_)
    assert sc["has_lb"] and sc["lb_s"] > 0
    assert share == pytest.approx(
        step_bytes.step_bytes(1024 * batches) / (sc["lb_s"] * 819e9))
    assert 0 < share < 0.05
    assert share / reader("kernels.lpm_hbm_share")(run_) == pytest.approx(
        256 / 48 * sc["lpm_s"] / sc["lb_s"])


# -- (d) the yardstick's bytes against the program's layouts -------------------------
def test_step_bytes_is_what_the_tables_rows_hold():
    from benchmarks.lb import step_bytes
    from benchmarks.worlds import svclb
    from cilium_tpu.compile.lb import LBConfig, build_lb
    from cilium_tpu.model.services import Backend, Frontend, Service
    with open(os.path.join(REPO, "tests", "data", "configs",
                           "tiny-svclb.json")) as f:
        w = svclb.build(json.load(f)["world"])
    lb = build_lb([Service(
        name=s["name"], namespace=s["namespace"],
        frontends=tuple(Frontend(*fe) for fe in s["frontends"]),
        lb_backends=tuple(Backend(*b) for b in s["backends"]))
        for s in w.services()], LBConfig(maglev_m=251))
    t = lb.tensors()
    per_row = lb.probe_depth * (t["lb_tab_keys"][0].nbytes
                                + t["lb_tab_val"][0].nbytes) \
        + t["lb_fe_service"][0].nbytes + t["lb_fe_rnat_id"][0].nbytes \
        + t["lb_maglev"][0, 0].nbytes \
        + t["lb_be_addr"][0].nbytes + t["lb_be_port"][0].nbytes
    assert step_bytes.row_bytes() == per_row == 256
    assert step_bytes.PROBE_DEPTH == lb.probe_depth
    assert step_bytes.step_bytes(1024) == 262144
    assert step_bytes.table_bytes(lb.n_services, 251, lb.n_frontends,
                                  len(lb.backends)) == {
        "maglev": t["lb_maglev"].nbytes,
        "frontend_table": t["lb_tab_keys"].nbytes + t["lb_tab_val"].nbytes,
        "frontends": t["lb_fe_service"].nbytes + t["lb_fe_rnat_id"].nbytes,
        "backends": t["lb_be_addr"].nbytes + t["lb_be_port"].nbytes}
    full = step_bytes.table_bytes(10000, 16381, 14001, 151000)
    assert full["maglev"] == 655_240_000
    assert full["frontend_table"] == 32768 * 28
    assert sum(full.values()) < 660_000_000
    with open(os.path.join(REPO, "benchmarks", "lb", "step_bytes.py")) as f:
        assert "import" not in f.read().split('"""', 2)[2]


# -- (e) the world at the configuration's counts ----------------------------------
@pytest.fixture(scope="module")
def full_world():
    from benchmarks.worlds import svclb
    return svclb.build(load(NAME)["world"])


def test_the_world_builds_at_the_configurations_counts(full_world):
    w = full_world
    assert (w.n_services, w.n_external, int(w.named.sum())) \
        == (10000, 500, 250)
    assert w.n_frontends == 14001 and w.n_backends.sum() == 151000
    assert w.n_pods == 151000 - w.n_backends[w.external].sum() == 143260
    assert w.n_backends.max() == 250 and w.n_backends.min() == 2
    sizes, counts = np.unique(w.n_backends, return_counts=True)
    assert dict(zip(sizes.tolist(), counts.tolist())) == {
        2: 4000, 5: 3000, 15: 2000, 60: 800, 250: 200}
    assert len(w.cases) == 13
    most = w.frontend_of(w.cases["g_most_backends"][0])[0][0]
    fewest = w.frontend_of(w.cases["g_fewest_backends"][0])[0][0]
    assert (w.n_backends[most], w.n_backends[fewest]) == (250, 2)
    assert len(w.policy_docs()) == 2000 + 250
    assert w.row_order()[0] == ("kube-system", "kube-dns")
    allowed, cover = w.table()
    assert allowed.size == 200 * 17 * 2 + 500 and cover.max() == 1
    assert allowed[:-500].sum() == 2000 and allowed[-500:].sum() == 250
    # about half the frontends are admitted
    assert 0.4 < w._fe_admitted.sum() / w.n_frontends < 0.55


def test_the_reference_agrees_with_the_loop_at_full_size(full_world):
    from tests.test_svclb import Documents, judged, the_flows
    w = full_world
    flows = the_flows(w, np.random.default_rng(11), 5000, 3000, 800, 600)
    n = flows["sport"].shape[0]
    assert n >= 10000
    got, docs = judged(w, flows), Documents(w)
    for backend in (0, 3):
        want = docs.judge(flows, backend)
        wrong = [i for i in range(n) if got[i] != want[i]]
        assert not wrong, [(i, got[i], want[i]) for i in wrong[:10]]
    assert got.count(True) >= 4000 and got.count(130) >= 4000


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
    svc, _f = w.frontend_of(flows)
    per_flow = np.bincount(mix["sched_flow"], minlength=svc.size)
    assert 0.88 < per_flow[svc >= 0].sum() / per_flow.sum() < 0.93
    live = np.arange(svc.size) < 100000
    # the live flows reach thousands of services, the DNS most of all
    reached = np.bincount(svc[live & (svc >= 0)], minlength=w.n_services)
    assert (reached > 0).sum() > 3000 and reached.argmax() == 0
    assert 8000 < reached[0] < 16000
    assert set(ref.refusal_reasons(w, flows)[~want].tolist()) == {130}
