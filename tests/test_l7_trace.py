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
"""

from benchmarks.tests.test_l7_trace import *  # noqa: F401,F403
