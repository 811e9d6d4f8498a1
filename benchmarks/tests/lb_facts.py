#!/usr/bin/env python3
"""A run of a service cell with the facts the result line leaves out.

    python3 benchmarks/tests/lb_facts.py --workload <cell> --seed <n>
                        --seconds <s> [--trace 1] [--out chiprun_out/x]

``lpm_facts.py`` over a world of ``worlds/svclb.py``: the same run and the
same ``[facts]`` line (the gauges the LB tables were placed with, among
them ``lb_services``, ``lb_frontends``, ``lb_backends`` and
``lb_maglev_bytes``; the device-memory ledger by group; the program's
``verdict_rows`` at both ends of the window; in a traced run the device
seconds under ``lb.step`` with its longest operations), with the one thing
that tool reads of ``cidrsvc``'s world taken from this one: the share of the
window's verdicted frames whose flow goes to a frontend
(``World.frontend_of``), which is what ``lb.translated_share`` has to read.
A driver for a builder's chip call; not a part of the benchmark's command.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.tests import lpm_facts  # noqa: E402


def service_share(world, tr, run) -> float:
    """Of the accepted frames verdicted inside the window, the share whose
    flow's destination is a frontend."""
    import numpy as np
    flow_of = tr.sched[run.accepted_idx]
    inside = (run.verdict_t >= run.w0) & (run.verdict_t < run.w1)
    to_frontend = world.frontend_of(tr.flows)[0] >= 0
    return float(np.mean(to_frontend[flow_of[inside]]))


if __name__ == "__main__":
    lpm_facts.service_share = service_share
    sys.exit(lpm_facts.main())
