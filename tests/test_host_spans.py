"""Tier-1's hold on the readers of the host's spans (PR 39).

``benchmarks/tests/test_host_spans.py`` holds the five per-layer metrics
read from the tracer's totals and the ``feeder.roundtrip`` spans over a
run written out by hand, what they return where there is nothing to read,
their entries in the manifest, and a cell at test size through
``run_cell`` with the facts script's table over it. Tier-1 collects
``tests/`` only; this file brings them under it as they stand, case for
case, as ``tests/test_l7_trace.py`` does for its file.
"""

from benchmarks.tests.test_host_spans import *  # noqa: F401,F403
