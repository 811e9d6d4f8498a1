"""Tier-1's hold on the reader of ``host.flow_hashes_per_row`` (PR 41).

``benchmarks/tests/test_flow_hashes.py`` holds the reader over a run
written out by hand, what it returns where there is nothing to read, its
entry in the manifest, and a ``saturate`` cell at test size through
``run_cell`` on one device and on the four-device mesh. Tier-1 collects
``tests/`` only; this file brings them under it as they stand, case for
case, as ``tests/test_host_spans.py`` does for its file.
"""

from benchmarks.tests.test_flow_hashes import *  # noqa: F401,F403
