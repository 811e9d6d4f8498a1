"""Tier-1's hold on the reader of ``host.flow_hashes_per_row`` (PR 41).

``benchmarks/tests/test_flow_hashes.py`` holds the reader over a run
written out by hand, what it returns where there is nothing to read, its
entry in the manifest, and a ``saturate`` cell at test size through
``run_cell`` on one device and on the four-device mesh. Tier-1 collects
``tests/`` only; this file brings them under it as they stand, case for
case, as ``tests/test_host_spans.py`` does for its file.

One case is held here in its own words, as that file holds one. The
benchmark's ``test_the_manifest_lists_it_in_the_saturate_cells`` pins the
metric's ``workloads`` to the four ``saturate*`` cells of PR 41, letter for
letter, and a later cell appends its name there (PR 42:
``node-mixed.saturate-longflows``) and may not edit a file the benchmark
has. The case below holds what that one holds, with PR 41's four at the
head of the list in their order; the benchmark's own is for a ``benchmark``
PR to loosen (PERF.md §7).
"""

import json
import os

from benchmarks.tests import test_flow_hashes as _theirs
from benchmarks.tests.test_flow_hashes import *  # noqa: F401,F403


def test_the_manifest_lists_it_in_the_saturate_cells():  # noqa: F811
    harness, name, saturate = _theirs.harness, _theirs.NAME, _theirs.SATURATE
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by = {m["name"]: m for m in manifest["per_layer"]}
    listed = by[name]["workloads"]
    assert dict(by[name], workloads=None) == {
        "name": name, "unit": "hashes/row", "better": "lower",
        "source": "program_counter", "layer": "host threads",
        "moves": "verdicts_per_s", "workloads": None}
    assert listed[:len(saturate)] == saturate
    assert by["host.cpu_us_per_row"]["layer"] == by[name]["layer"]
    for w in manifest["workloads"]:
        cell = harness.resolve_cell(manifest, w["name"])
        assert (name in cell.layers) == (w["name"] in listed)
        if w["name"] in listed:             # closed on ring space, all
            assert w["traffic"].startswith("saturate")
