"""The readers of the five per-layer metrics PR 39 reads from the program's
spans and thread clocks (``benchmarks/host/spans.py`` and five files under
``layers/``):

(a) over a run written out by hand (the tracer's totals and the two
    threads' CPU clocks at the window's two ends as ``Pipeline.stats()``
    and ``ShimFeeder.stats()`` hand them out, and ``run.spans`` for the
    round trip): each reads what the sums give by hand, by the window's
    two ends;
(b) where there is nothing to read they return None and never 0: a
    program before PR 39 (no ``span_totals`` or ``thread_cpu_s`` key, the
    old spans alone), a run with tracing off (``span_totals`` None), a
    window in which a span was not recorded, a worker restarted inside the
    window (its clock starts anew);
(c) the manifest lists them in the cells the issue names, each moving an
    end-to-end metric its cells report;
(d) a cell at test size through ``run_cell`` on the CPU, traced, on one
    device and on the four-device mesh: all five read numbers, the CPU a
    row under the wall a row, and the facts script's tables add up.
"""

import json
import os
import time
import types

import pytest

from benchmarks import harness
from benchmarks.host import spans

SATURATE = ["ct1m-50k.saturate", "ct1m-50k-mesh4.saturate",
            "lpm100k-zipf.saturate-longflows", "l7-http.saturate-longflows"]
STEADY = ["pods10k-dualstack.steady80", "ct1m-50k.steady80"]
CELLS = {"pipeline.finalize_own_us_per_batch": SATURATE,
         "feeder.apply_us_per_batch": SATURATE,
         "feeder.map_us_per_batch": SATURATE,
         "host.cpu_us_per_row": SATURATE,
         "feeder.roundtrip_ms": STEADY}


def reader(name):
    return harness.load_reader("layers", name).read


# the tracer's totals at the window's start and end: name -> [count,
# wall_s]. One tracer serves both threads, so both stats() hold every name
START = {
    "shim.harvest": [10, 0.010], "feeder.map": [10, 0.010],
    "feeder.submit": [10, 0.002], "feeder.apply": [9, 0.018],
    "feeder.wait": [9, 0.040],
    "pipeline.dispatch": [10, 0.025], "pipeline.finalize": [9, 0.072],
    "pipeline.settle": [9, 0.0018], "datapath.pack": [10, 0.004],
    "datapath.compute": [9, 0.045], "datapath.unpack": [9, 0.0027],
    "engine.account": [9, 0.018],
}
# 100 batches of 1,024 rows in the window
WINDOW = {
    "shim.harvest": (100, 0.100), "feeder.map": (100, 0.090),
    "feeder.submit": (100, 0.015), "feeder.apply": (100, 0.220),
    "feeder.wait": (100, 0.450),
    "pipeline.dispatch": (100, 0.245), "pipeline.finalize": (100, 0.830),
    "pipeline.settle": (100, 0.020), "datapath.pack": (100, 0.045),
    "datapath.compute": (100, 0.569), "datapath.unpack": (100, 0.030),
    "engine.account": (100, 0.210),
}
END = {n: [START[n][0] + c, START[n][1] + w] for n, (c, w) in WINDOW.items()}
# the two threads' CPU clocks: what each burnt before and in the window
CPU0 = {"feeder": 0.5, "pipeline": 0.9}
CPU_IN = {"feeder": 0.300, "pipeline": 0.422}


def written_run(start=START, end=END, rows=102_400, spans_=(),
                cpu0=CPU0, cpu_in=CPU_IN):
    def stats(totals, total_rows, cpu):
        return {"pipeline": {"span_totals": totals,
                             "thread_cpu_s": cpu["pipeline"],
                             "verdict_rows": {"total": total_rows},
                             "fill_rows": total_rows},
                "feeder": {"span_totals": totals,
                           "thread_cpu_s": cpu["feeder"]}}
    cpu1 = {k: None if cpu0[k] is None else cpu0[k] + cpu_in[k]
            for k in cpu0}
    return types.SimpleNamespace(
        w0=100.0, w1=140.0, stats0=stats(start, 10_240, cpu0),
        stats1=stats(end, 10_240 + rows, cpu1), spans=list(spans_))


# -- (a) ---------------------------------------------------------------------
def test_each_reads_the_windows_sums():
    run = written_run()
    t = spans.window_totals(run)
    assert set(t) == set(WINDOW)
    for name, (c, w) in WINDOW.items():
        assert t[name][0] == c
        assert t[name][1] == pytest.approx(w)
    assert reader("pipeline.finalize_own_us_per_batch")(run) \
        == pytest.approx((0.030 + 0.210 + 0.020) / 100 * 1e6)      # 2,600
    assert reader("feeder.apply_us_per_batch")(run) == pytest.approx(2200.0)
    assert reader("feeder.map_us_per_batch")(run) == pytest.approx(1050.0)
    # both threads' own clocks over the rows verdicted
    assert reader("host.cpu_us_per_row")(run) \
        == pytest.approx((0.300 + 0.422) / 102_400 * 1e6)          # 7.05


def test_one_tracer_or_two_every_name_is_read():
    # two tracers: each stats() holds its own thread's names alone
    run = written_run()
    for st, end in ((run.stats0, START), (run.stats1, END)):
        st["feeder"] = {"span_totals": {
            n: v for n, v in end.items()
            if n.startswith(("shim.", "feeder."))}}
        st["pipeline"]["span_totals"] = {
            n: v for n, v in end.items()
            if not n.startswith(("shim.", "feeder."))}
    assert set(spans.window_totals(run)) == set(WINDOW)
    assert reader("feeder.apply_us_per_batch")(run) == pytest.approx(2200.0)
    assert reader("pipeline.finalize_own_us_per_batch")(run) \
        == pytest.approx(2600.0)


def test_rows_are_fill_rows_where_the_program_counts_no_verdict_rows():
    run, half = written_run(), written_run(rows=51_200)
    for st in (half.stats0, half.stats1):
        del st["pipeline"]["verdict_rows"]
    assert reader("host.cpu_us_per_row")(half) \
        == pytest.approx(2 * reader("host.cpu_us_per_row")(run))


def test_the_round_trip_is_the_median_of_the_windows_spans():
    sp = [("feeder.roundtrip", 99.0, 0.500),                 # before it
          ("feeder.roundtrip", 101.0, 0.0071),
          ("feeder.roundtrip", 102.0, 0.0069),
          ("feeder.roundtrip", 103.0, 0.0090),
          ("feeder.apply", 103.0, 0.0022),
          ("feeder.roundtrip", 140.0, 0.500)]                # after it
    assert reader("feeder.roundtrip_ms")(written_run(spans_=sp)) \
        == pytest.approx(7.1)


# -- (b) ---------------------------------------------------------------------
OLD_SPANS = [("shim.harvest", 101.0, 0.001),
             ("pipeline.admission", 101.0, 0.006),
             ("pipeline.dispatch", 101.0, 0.0025),
             ("datapath.pack", 101.0, 0.0004),
             ("datapath.transfer", 101.0, 0.002),
             ("pipeline.finalize", 101.0, 0.008),
             ("datapath.compute", 101.0, 0.0057)]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_nothing_to_read_is_none_and_never_zero(name):
    read = reader(name)
    # a program before PR 39: no totals, the spans it had
    parent = types.SimpleNamespace(
        w0=100.0, w1=140.0, spans=OLD_SPANS,
        stats0={"pipeline": {"fill_rows": 0}, "feeder": {}},
        stats1={"pipeline": {"fill_rows": 1024}, "feeder": {}})
    assert read(parent) is None
    # no pipeline or feeder at all
    bare = types.SimpleNamespace(w0=0.0, w1=1.0, spans=[],
                                 stats0={"pipeline": None, "feeder": None},
                                 stats1={"pipeline": None, "feeder": None})
    assert read(bare) is None
    no_clock = {"feeder": None, "pipeline": None}
    if name == "host.cpu_us_per_row":
        # it reads the two threads' clocks alone: with tracing off too
        assert read(written_run(start=None, end=None)) \
            == pytest.approx(7.05, abs=0.01)
        assert read(written_run(cpu0=no_clock, cpu_in=no_clock)) is None
        # a worker restarted inside the window: its clock started anew
        assert read(written_run(cpu0={"feeder": 0.5, "pipeline": 9.0},
                                cpu_in={"feeder": 0.3,
                                        "pipeline": -8.9})) is None
        return
    # tracing off: the key is there and holds None
    assert read(written_run(start=None, end=None)) is None
    # the old names alone in the totals (the parent's spans, counted)
    old = {n: v for n, v in END.items() if n in (
        "shim.harvest", "pipeline.dispatch", "pipeline.finalize",
        "datapath.pack", "datapath.compute")}
    assert read(written_run(start={}, end=old, spans_=OLD_SPANS)) is None
    # a window in which nothing was recorded
    assert read(written_run(start=END, end=END)) is None


def test_no_rows_verdicted_is_none():
    assert reader("host.cpu_us_per_row")(written_run(rows=0)) is None


# -- (c) ---------------------------------------------------------------------
def test_the_manifest_lists_them_in_the_issues_cells():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by = {m["name"]: m for m in manifest["per_layer"]}
    assert list(by)[-5:] == [
        "pipeline.finalize_own_us_per_batch", "feeder.apply_us_per_batch",
        "feeder.map_us_per_batch", "host.cpu_us_per_row",
        "feeder.roundtrip_ms"]
    # no share of the interpreter lock's wait: the chip machine's clocks
    # cannot tell it from the host's time to run a woken thread (PERF.md)
    assert "host.lock_wait_share" not in by
    for name, cells in CELLS.items():
        m = by[name]
        assert m["workloads"] == cells
        # the CPU a row is the two thread clocks' count, not a span's
        assert m["source"] == ("program_counter"
                               if name == "host.cpu_us_per_row"
                               else "program_span")
        assert m["better"] == "lower"
        assert m["moves"] == ("verdict_p50_ms" if cells is STEADY
                              else "verdicts_per_s")
        for cell in cells:
            assert name in harness.resolve_cell(manifest, cell).layers
    assert by["feeder.roundtrip_ms"]["unit"] == "ms"


# -- (d) ---------------------------------------------------------------------
def test_a_cell_at_test_size_reads_all_five(tiny_manifest_cell):
    cell = tiny_manifest_cell
    kept = {}
    from benchmarks.tests import host_facts

    sound_check = harness.check

    def check(sv, tr, run, *a, **kw):
        kept.update(run=run, spans=sv.eng.tracer.spans(limit=1 << 18))
        return sound_check(sv, tr, run, *a, **kw)
    harness.check = check
    try:
        r = harness.run_cell(cell, 3900000101, 1.5, True, time.monotonic())
    finally:
        harness.check = sound_check
    assert r["correct"] and r["control"]["caught"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(CELLS) <= set(m)
    assert m["host.cpu_us_per_row"] > 0
    assert m["pipeline.finalize_own_us_per_batch"] > 0
    assert m["feeder.apply_us_per_batch"] > 0
    assert m["feeder.map_us_per_batch"] > 0
    run = kept["run"]
    # the exact interval where the histogram's reader has a bucket
    lat = sorted(d for n, t0, d in run.spans
                 if n == "feeder.roundtrip" and run.w0 <= t0 < run.w1)
    assert lat[0] * 1e3 <= m["feeder.roundtrip_ms"] <= lat[-1] * 1e3
    # the CPU both threads burnt cannot pass two threads' wall
    rows = run.stats1["pipeline"]["verdict_rows"]["total"] \
        - run.stats0["pipeline"]["verdict_rows"]["total"]
    assert m["host.cpu_us_per_row"] * rows / 1e6 < 2 * (run.w1 - run.w0)
    # the facts script's table over the same spans
    window = [s for s in kept["spans"]
              if run.w0 <= s["start_mono"] < run.w1]
    table = host_facts.by_name(window)
    fin = table["pipeline.finalize"]
    assert fin["thread"].endswith("-worker") and fin["kind"] == "work"
    assert fin["self_wall_us"] <= fin["wall_us"]
    assert table["engine.account"]["parent"] == "pipeline.finalize"
    assert table["datapath.compute"]["kind"] == "wait"
    assert table["feeder.apply"]["thread"] == table["shim.harvest"]["thread"]
    th = host_facts.threads(run, window)
    feeder, worker = th[table["shim.harvest"]["thread"]], th[fin["thread"]]
    for row in (feeder, worker):
        assert row["working_us"] > 0 and row["thread_cpu_us"] > 0
    # the worker's working time leaves the wait for the device out and
    # holds what else it opens at the top (the mesh's steer and staging)
    batches = table["pipeline.dispatch"]["n"]
    outer = sum(table[n]["wall_us"] * table[n]["n"] for n in (
        "pipeline.dispatch", "pipeline.finalize", "pipeline.settle"))
    least = (outer - table["datapath.compute"]["wall_us"]
             * table["datapath.compute"]["n"]) / batches
    assert least * 0.999 <= worker["working_us"] \
        <= (run.w1 - run.w0) / batches * 1e6
    assert all("thread_cpu_us" not in row for name, row in th.items()
               if row is not feeder and row is not worker)
    parts = sum(table[n]["wall_us"] * table[n]["n"] for n in (
        "datapath.compute", "datapath.unpack", "engine.account"))
    assert parts <= fin["wall_us"] * fin["n"] * 1.001
    cyc = host_facts.cycle(table, run.w1 - run.w0)
    assert cyc["compute+unpack+account_us"] <= cyc["finalize_us"] * 1.001


@pytest.fixture(params=["tiny-pods.saturate", "tiny-pods-mesh4.saturate"])
def tiny_manifest_cell(request):
    """A ``saturate`` cell of the test-size manifest, on one device and on
    the four-device mesh (whose slab read has a ``datapath.unpack`` of its
    own), reading the five new metrics beside its own (the manifest at test
    size is the parent's file and lists none of them)."""
    from benchmarks.tests.conftest import DATA
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = harness.resolve_cell(manifest, request.param, data_root=DATA)
    cell.layers = list(cell.layers) + sorted(CELLS)
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        units = {m["name"]: m["unit"] for m in json.load(f)["per_layer"]}
    cell.units = {**cell.units, **{n: units[n] for n in CELLS}}
    return cell
