"""Tier-1's hold on the L7 cell's readers (PR 37).

``benchmarks/tests/test_l7_trace.py`` holds the readers of
``l7-http.saturate-longflows``'s four per-layer metrics over a trace
recorded on the chip, the one exception ``benchmarks/l7/trace.py`` makes
to the scope rule, what they return where there is nothing to read, and
the cell at test size through ``run_cell``: 14 cases. Tier-1 collects
``tests/`` only; this file brings them under it as they stand, case for
case, as ``tests/test_frames_direction.py`` and ``tests/
test_httprules.py`` do for theirs. The benchmark's conftest is not loaded
here, only imported for its helpers; the native libraries its session
fixture builds are built by ``tests/conftest.py`` and on first use by
``harness.serve``.

One case runs here with twice its schedule. The benchmark's
``test_the_cell_at_test_size_reads_the_hosts_two`` prepares 80,000 frames
for a second of run; alone on this CPU the program took 130,000–132,000 of
its 161,024 until PR 41 and takes them all since (a ``saturate`` run that
uses its whole schedule fails and says so), so under tier-1 the case
passed only while five other workers kept the cores busy. The file is the
benchmark's and a PR may not edit it (PERF.md §7); the case below is that
one, word for word, over a schedule with room.

A second case is held here in its own words. The benchmark's
``test_the_cell_reads_the_four_metrics`` pins each metric's ``workloads``
to ``[CELL]`` and has no other cell read one; since PR 42 the mixed node's
cell (``node-mixed.saturate-longflows``: the same lane, beside the other
planes) reads the three whose readers' premises hold there, its name
appended. The case below holds what that one holds, the cell first in each
list and the mixed node the only other reader; the benchmark's own is for
a ``benchmark`` PR to loosen (PERF.md §7).
"""

from benchmarks import harness
from benchmarks.tests import test_l7_trace as _theirs
from benchmarks.tests.test_l7_trace import *  # noqa: F401,F403


def test_the_cell_at_test_size_reads_the_hosts_two(monkeypatch):  # noqa: F811
    plain = harness.schedule_frames
    monkeypatch.setattr(
        harness, "schedule_frames",
        lambda cell, rate, seconds: 2 * plain(cell, rate, seconds))
    _theirs.test_the_cell_at_test_size_reads_the_hosts_two(monkeypatch)


def test_the_cell_reads_the_four_metrics():  # noqa: F811
    m = _theirs.manifest()
    cell_name, metrics = _theirs.CELL, _theirs.L7_METRICS
    mixed = "node-mixed.saturate-longflows"
    cell = harness.resolve_cell(m, cell_name)
    assert set(metrics) <= set(cell.layers)
    assert cell.e2e == ["verdicts_per_s", "setup_s"]
    layer_of = {"datapath.l7_dict_us_per_batch": "datapath host"}
    for name in metrics:
        entry = next(e for e in m["per_layer"] if e["name"] == name)
        assert entry["workloads"] in ([cell_name], [cell_name, mixed])
        assert entry["moves"] == "verdicts_per_s"
        assert entry["layer"] == layer_of.get(name, "kernels")
    # no other one-plane cell reads them, and this one reads no other
    # kernel's
    for w in m["workloads"]:
        if w["name"] not in (cell_name, mixed):
            assert not set(metrics) & set(
                harness.resolve_cell(m, w["name"]).layers)
    assert not {"kernels.lpm_us_per_batch", "kernels.lb_us_per_batch",
                "lb.translated_share"} & set(cell.layers)
