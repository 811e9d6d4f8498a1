"""Tier-1's hold on the readers of the host's spans (PR 39).

``benchmarks/tests/test_host_spans.py`` holds the five per-layer metrics
read from the tracer's totals and the ``feeder.roundtrip`` spans over a
run written out by hand, what they return where there is nothing to read,
their entries in the manifest, and a cell at test size through
``run_cell`` with the facts script's table over it. Tier-1 collects
``tests/`` only; this file brings them under it as they stand, case for
case, as ``tests/test_l7_trace.py`` does for its file.

One case is held here in its own words. The benchmark's
``test_the_manifest_lists_them_in_the_issues_cells`` pins the five to the
END of ``per_layer`` (``list(by)[-5:]``), and a PR appends its metrics
there (PR 41: ``host.flow_hashes_per_row``) and may not edit a file the
benchmark has. The case below holds what that one holds, with the five
found where they stand, in PR 39's order and side by side; the benchmark's
own is for a ``benchmark`` PR to loosen (PERF.md §7). It pins each one's
``workloads`` letter for letter as well, and a later cell appends its name
(PR 42: ``node-mixed.saturate-longflows``): here PR 39's cells stand at the
head of each list, in their order.
"""

import json
import os

from benchmarks.tests import test_host_spans as _theirs
from benchmarks.tests.test_host_spans import *  # noqa: F401,F403


def test_the_manifest_lists_them_in_the_issues_cells():  # noqa: F811
    harness, cells = _theirs.harness, _theirs.CELLS
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by = {m["name"]: m for m in manifest["per_layer"]}
    names = list(by)
    at = names.index("pipeline.finalize_own_us_per_batch")
    assert names[at:at + 5] == [
        "pipeline.finalize_own_us_per_batch", "feeder.apply_us_per_batch",
        "feeder.map_us_per_batch", "host.cpu_us_per_row",
        "feeder.roundtrip_ms"]
    assert "host.lock_wait_share" not in by
    for name, want in cells.items():
        m = by[name]
        assert m["workloads"][:len(want)] == want
        assert m["source"] == ("program_counter"
                               if name == "host.cpu_us_per_row"
                               else "program_span")
        assert m["better"] == "lower"
        assert m["moves"] == ("verdict_p50_ms" if want is _theirs.STEADY
                              else "verdicts_per_s")
        for cell in m["workloads"]:
            assert name in harness.resolve_cell(manifest, cell).layers
    assert by["feeder.roundtrip_ms"]["unit"] == "ms"
