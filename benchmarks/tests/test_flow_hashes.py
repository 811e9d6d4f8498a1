"""The reader of ``host.flow_hashes_per_row`` (PR 41): rows the host's two
threads ran through ``flow_hashes`` per verdicted row, from the counters
``flow_hash_rows`` of ``ShimFeeder.stats()`` and of
``pipeline_stats()["verdict_rows"]`` at the window's two ends.

(a) over a run written out by hand it reads (Δ feeder + Δ worker) / Δ rows;
(b) where there is nothing to read it returns None and never 0: a program
    before PR 41 (no such key on either side, or on one), no feeder or no
    pipeline, a window in which no row was verdicted;
(c) the manifest lists it in the four ``saturate`` cells, moving
    ``verdicts_per_s`` (found by name: a later PR appends behind it);
(d) a ``saturate`` cell at test size through ``run_cell`` on the CPU, on
    one device and on the four-device mesh: a row is hashed once, and the
    worker's thread hashes none.
"""

import json
import os
import time
import types

import pytest

from benchmarks import harness

NAME = "host.flow_hashes_per_row"
SATURATE = ["ct1m-50k.saturate", "ct1m-50k-mesh4.saturate",
            "lpm100k-zipf.saturate-longflows", "l7-http.saturate-longflows"]
read = harness.load_reader("layers", NAME).read


def written_run(feeder=(2_048, 104_448), worker=(512, 512),
                rows=(2_000, 102_000)):
    """The two counters and the rows verdicted at the window's two ends
    (a counter given as None is a program that has no such key)."""
    def stats(i):
        vr = {"total": rows[i]}
        if worker is not None:
            vr["flow_hash_rows"] = worker[i]
        fd = {"harvested_records": rows[i]}
        if feeder is not None:
            fd["flow_hash_rows"] = feeder[i]
        return {"pipeline": {"verdict_rows": vr, "fill_rows": rows[i]},
                "feeder": fd}
    return types.SimpleNamespace(w0=100.0, w1=140.0, spans=[],
                                 stats0=stats(0), stats1=stats(1))


# -- (a) ---------------------------------------------------------------------
@pytest.mark.parametrize("feeder,worker,want", [
    ((2_048, 104_448), (512, 512), 1.024),       # once, at harvest
    ((2_048, 194_048), (512, 90_512), 2.82),     # and again by each note
    ((0, 0), (0, 100_000), 1.0),                 # an Engine.submit producer
])
def test_it_reads_both_threads_hashes_over_the_rows(feeder, worker, want):
    assert read(written_run(feeder, worker)) == pytest.approx(want)


# -- (b) ---------------------------------------------------------------------
@pytest.mark.parametrize("missing", ["both", "feeder", "worker"])
def test_a_program_without_the_counters_is_none(missing):
    run = written_run(feeder=None if missing != "worker" else (0, 9),
                      worker=None if missing != "feeder" else (0, 9))
    assert read(run) is None


def test_nothing_to_read_is_none_and_never_zero():
    bare = types.SimpleNamespace(w0=0.0, w1=1.0, spans=[],
                                 stats0={"pipeline": None, "feeder": None},
                                 stats1={"pipeline": None, "feeder": None})
    assert read(bare) is None
    for who in ("pipeline", "feeder"):
        run = written_run()
        run.stats1[who] = None
        assert read(run) is None
    # a program before PR 39 had no ``verdict_rows`` either
    run = written_run()
    for st in (run.stats0, run.stats1):
        del st["pipeline"]["verdict_rows"]
    assert read(run) is None
    assert read(written_run(rows=(2_000, 2_000))) is None


# -- (c) ---------------------------------------------------------------------
def test_the_manifest_lists_it_in_the_saturate_cells():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by = {m["name"]: m for m in manifest["per_layer"]}
    assert by[NAME] == {
        "name": NAME, "unit": "hashes/row", "better": "lower",
        "source": "program_counter", "layer": "host threads",
        "moves": "verdicts_per_s", "workloads": SATURATE}
    assert by["host.cpu_us_per_row"]["layer"] == by[NAME]["layer"]
    for w in manifest["workloads"]:
        cell = harness.resolve_cell(manifest, w["name"])
        assert (NAME in cell.layers) == (w["name"] in SATURATE)


# -- (d) ---------------------------------------------------------------------
@pytest.mark.parametrize("name", ["tiny-pods.saturate",
                                  "tiny-pods-mesh4.saturate"])
def test_a_cell_at_test_size_hashes_a_row_once(name, monkeypatch):
    from benchmarks.tests.conftest import DATA
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        cell = harness.resolve_cell(json.load(f), name, data_root=DATA)
    cell.layers = list(cell.layers) + [NAME]
    cell.units = {**cell.units, NAME: "hashes/row"}
    kept = {}
    sound_check = harness.check

    def check(sv, tr, run, *a, **kw):
        kept["run"] = run
        return sound_check(sv, tr, run, *a, **kw)
    monkeypatch.setattr(harness, "check", check)
    # untraced: a counter needs no profile, the reader's value rides
    # ``also``, and the trace directory, which is the cell's by name, is
    # left to the traced cases of test_host_spans.py, which tier-1 runs
    # beside this file in another worker
    r = harness.run_cell(cell, 3900000141, 1.5, False, time.monotonic())
    assert r["correct"]
    run = kept["run"]
    a, b = run.stats0, run.stats1
    rows = b["pipeline"]["verdict_rows"]["total"] \
        - a["pipeline"]["verdict_rows"]["total"]
    hashed = b["feeder"]["flow_hash_rows"] - a["feeder"]["flow_hash_rows"]
    assert rows > 0 and hashed >= rows * 0.98
    # the harness's probe is an Engine.submit producer, before the window:
    # its batches carry no column and are hashed on the worker, counted
    assert a["pipeline"]["verdict_rows"]["flow_hash_rows"] > 0
    # in the window every batch is a feeder's: the worker hashes none
    assert b["pipeline"]["verdict_rows"]["flow_hash_rows"] \
        == a["pipeline"]["verdict_rows"]["flow_hash_rows"]
    got = r["also"][NAME]
    assert got["unit"] == "hashes/row"
    assert got["value"] == pytest.approx(hashed / rows)
    # once a row, plus the invalid tails of the views that were not full;
    # twice the established share more where a note still hashed
    assert 0.98 <= got["value"] < 1.6
