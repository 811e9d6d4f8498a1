"""The four-chip cell's own pieces: the harness end to end on the 4-wide
virtual mesh under device-side RSS (rows in arrival order, the conntrack
exchange inside the program), and the three ``mesh.*`` readers over a
small trace recorded on four TPU v5e chips by this benchmark
(``data/mesh4.xplane.pb`` with the program's spans of the same interval in
``data/mesh4.spans.json``: a few batches of ``ct1m-50k-mesh4.saturate``,
PERF.md PR 29; cut from the run's trace by ``tests/cut_trace.py``).

The manifest is composed here from the tiny one: ``data/BENCHMARK.json``
stays as it is.
"""

import json
import os
import shutil
import time
import types

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.tests.conftest import DATA

CELL = "tiny-pods-mesh4-dev.saturate"
MESH_METRICS = (("mesh.exchange_us_per_batch", "us", "lower", "device_trace"),
                ("mesh.exchange_ici_share", "ratio", "higher",
                 "device_trace"),
                ("mesh.readback_us_per_batch", "us", "lower", "program_span"))


@pytest.fixture(scope="module")
def dev_manifest(tiny_manifest):
    m = json.loads(json.dumps(tiny_manifest))
    m["configs"].append({
        "name": "tiny-pods-mesh4-dev", "source": "test",
        "file": "benchmarks/tests/data/configs/tiny-pods-mesh4-dev.json",
        "reduced": [], "why": "test"})
    m["workloads"].append({"name": CELL, "config": "tiny-pods-mesh4-dev",
                           "traffic": "saturate", "chips": 4, "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "tiny-pods-mesh4.saturate" in e.get("workloads", ()):
            e["workloads"].append(CELL)
    for name, unit, better, source in MESH_METRICS:
        m["per_layer"].append({
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "mesh", "moves": "verdicts_per_s", "workloads": [CELL]})
    return m


def run(manifest, seed, traced=False, **kw):
    cell = harness.resolve_cell(manifest, CELL, data_root=DATA)
    return cell, harness.run_cell(cell, seed, 1.5, traced, time.monotonic(),
                                  **kw)


def numbers(result):
    return {n["name"]: n for n in result["numbers"]}


@pytest.fixture(scope="module")
def runs(dev_manifest):
    return {traced: run(dev_manifest, seed, traced)
            for seed, traced in ((2900000011, False), (2900000012, True))}


@pytest.mark.parametrize("traced", [False, True])
def test_device_rss_cell_is_correct_and_fifo(runs, traced):
    cell, r = runs[traced]
    assert cell.config["daemon"]["rss_mode"] == "device"
    assert cell.chips == 4 and r["device"]["count"] == 4
    n = numbers(r)
    assert r["correct"], [x for x in r["numbers"] if not x["ok"]]
    assert r["failed"] == 0 and r["attempted"] > 1000
    for name in ("unverdicted", "prefix_excess", "passed_gap",
                 "probe_mismatched", "fill_table_gap", "pipeline_faults",
                 "feeder_faults"):
        assert n[name]["value"] == 0, name
    assert n["stable_points"]["value"] >= 100
    c = r["control"]
    assert c["caught"] is True and c["frames_on_it"] >= 16
    assert c["prefix_excess"] > 0 and c["passed_gap"] > 0
    assert r["compiles"]["in_window"] == 0
    want = set(cell.layers if traced else cell.e2e)
    assert set(r["metrics"]) <= want
    if not traced:
        assert set(r["metrics"]) == want
        assert r["metrics"]["verdicts_per_s"]["value"] > 0


def test_traced_run_reads_the_readback_span_and_no_device_plane(runs):
    cell, r = runs[True]
    assert {m[0] for m in MESH_METRICS} <= set(cell.layers)
    m = r["metrics"]
    # one `datapath.readback` span a batch, inside `datapath.compute`
    assert 0 < m["mesh.readback_us_per_batch"]["value"] < 1e6
    assert m["mesh.readback_us_per_batch"]["unit"] == "us"
    # no device plane in a CPU trace: the trace readers find nothing to
    # read and the line leaves their metrics out
    assert "mesh.exchange_us_per_batch" not in m
    assert "mesh.exchange_ici_share" not in m
    # device RSS stages in arrival order: nothing is steered on the host
    assert m["datapath.host_us_per_batch"]["value"] > 0
    assert "mesh.readback_us_per_batch" not in runs[False][1]["also"]


def test_flipped_verdict_is_seen(dev_manifest):
    def break_path(eng, shim):
        sound, seen = shim.apply_verdicts, [0]

        def apply(allow):
            allow = np.array(allow, dtype=bool)
            seen[0] += 1
            if seen[0] % 40 == 0 and allow.size:
                allow[0] = ~allow[0]
            sound(allow)
        shim.apply_verdicts = apply

    _cell, r = run(dev_manifest, 2900000013, break_path=break_path)
    n = numbers(r)
    assert not r["correct"]
    assert n["prefix_excess"]["value"] > 0 and not n["prefix_excess"]["ok"]
    assert n["unverdicted"]["value"] == 0


# -- the readers over a trace recorded on the chips ---------------------------
def reader(name):
    return harness.load_reader("layers", name).read


def recorded_run(tmp_path, trace_name, spans_name, monkeypatch):
    """What the harness hands the readers after a traced run, from the
    recorded files: the trace where ``_profile`` leaves it, the reduced
    trace, the spans, and pipeline counters that say 256 rows a dispatch."""
    from benchmarks.reduce import xplane
    where = tmp_path / "plugins" / "profile" / "recorded"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, trace_name), where / "t.xplane.pb")
    spans = []
    if spans_name:
        with open(os.path.join(DATA, spans_name)) as f:
            spans = [tuple(s) for s in json.load(f)["spans"]]
    trace = xplane.reduce_file(str(where / "t.xplane.pb"), spans)
    monkeypatch.setattr(harness, "describe_device", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4})
    w0, w1 = trace["window_mono_s"]
    return types.SimpleNamespace(
        info={"trace_dir": str(tmp_path)}, trace=trace, spans=spans,
        w0=w0, w1=w1,
        stats0={"pipeline": {"dispatched_batches": 0, "bucket_rows": 0}},
        stats1={"pipeline": {"dispatched_batches": 10,
                             "bucket_rows": 2560}})


def test_readers_over_the_recorded_four_chip_trace(tmp_path, monkeypatch):
    run_ = recorded_run(tmp_path, "mesh4.xplane.pb", "mesh4.spans.json",
                        monkeypatch)
    assert os.path.getsize(os.path.join(DATA, "mesh4.xplane.pb")) < 300_000
    assert len(run_.trace["busy_s_per_chip"]) == 4
    us = reader("mesh.exchange_us_per_batch")(run_)
    share = reader("mesh.exchange_ici_share")(run_)
    back = reader("mesh.readback_us_per_batch")(run_)
    assert all(np.isfinite(v) and v > 0 for v in (us, share, back))
    from benchmarks.mesh import exchange_bytes, trace
    ex = trace.exchange(run_)
    assert len(ex["chips"]) == 4 and ex["batches"] >= 2
    for c in ex["chips"].values():
        # whole hops only, six to a batch, and a hop's own time holds the
        # time its two events held the line
        assert c["hops"] % 6 == 0 and c["hops"] >= 12
        assert 0 < c["exposed_s"] < c["hop_s"]
    # the line is held microseconds a batch; the hops take tens of them
    assert 1 < us < 100
    assert us == pytest.approx(trace.mean_over_chips(ex, "exposed_s")
                               / ex["batches"] * 1e6)
    # a share of a peak: over 105% the bytes are counted too high or the
    # time leaves out part of the work. 3,328 and 512 bytes in
    # microseconds: latency, far under 1%
    assert 0 < share < 0.01 <= 1.05
    per_hop = exchange_bytes.sent_bytes_per_chip(256, 4) / 6
    assert per_hop == (3 * 3328 + 3 * 512) / 6
    assert share == pytest.approx(np.mean(
        [c["hops"] * per_hop / (c["hop_s"] * 200e9)
         for c in ex["chips"].values()]))
    # 18 reads of arrays sharded four ways: milliseconds a batch
    assert 1e3 < back < 1e5


def test_readers_find_nothing_in_a_one_chip_trace(tmp_path, monkeypatch):
    run_ = recorded_run(tmp_path, "small.xplane.pb", None, monkeypatch)
    assert list(run_.trace["busy_s_per_chip"]) == ["/device:TPU:0"]
    for name, *_ in MESH_METRICS:
        assert reader(name)(run_) is None, name


def test_readers_find_nothing_in_an_untraced_run():
    run_ = types.SimpleNamespace(info={}, trace=None, spans=[], w0=0.0,
                                 w1=1.0, stats0={}, stats1={})
    for name, *_ in MESH_METRICS:
        assert reader(name)(run_) is None, name


def test_byte_function_and_peak():
    from benchmarks.mesh import exchange_bytes as eb
    assert eb.hop_bytes(256, 4) == (64 * 13 * 4, 64 * 2 * 4)
    assert eb.sent_bytes_per_chip(256, 4) == 3 * 64 * 15 * 4 == 11520
    assert eb.materialized_bytes(256, 4) == 4 * 256 * 15 * 4
    assert eb.sent_bytes_per_chip(256, 1) == 0
    peaks = harness.load_json(harness.BENCH_DIR, "peaks_ici.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["ici_bytes_per_s"] == 200e9 and v5e["source"]
    assert "ici_bytes_per_s" in v5e["assumed"]
    # the same figure the accepted table holds, in bytes
    assert harness.chip_peaks("TPU v5 lite")["ici_bits_per_s"] / 8 \
        == v5e["ici_bytes_per_s"]
