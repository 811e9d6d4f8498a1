"""Mesh: the conntrack exchange's share of its roofline. The bytes a chip
sends in the ring hops of the traced interval (``benchmarks/mesh/
exchange_bytes.py``: from the row layouts and the rows a dispatch carried)
over what the interconnect could have moved in the hops' own time, each
from its start's beginning to its done's end (``benchmarks/mesh/
trace.py``), at the peak in ``benchmarks/peaks_ici.json`` (the chip's
published total, so a per-link peak could only read higher); mean over
the chips. At 64 rows a chip a hop carries 3,328 or 512 bytes and takes
microseconds: bound by latency, and the share reads far under 1%."""

import numpy as np

from benchmarks import harness
from benchmarks.mesh import exchange_bytes, trace


def read(run):
    ex = trace.exchange(run)
    if ex is None:
        return None
    a, b = run.stats0["pipeline"], run.stats1["pipeline"]
    dispatches = b["dispatched_batches"] - a["dispatched_batches"]
    if dispatches <= 0:
        return None
    rows = int(round((b["bucket_rows"] - a["bucket_rows"]) / dispatches))
    n = len(ex["chips"])
    # a batch is 2 (n - 1) hops; the traced interval cuts whole hops
    sent_a_hop = exchange_bytes.sent_bytes_per_chip(rows, n) / (2 * (n - 1))
    peaks = harness.load_json(harness.BENCH_DIR, "peaks_ici.json")
    peak = peaks[harness.describe_device()["kind"]]["ici_bytes_per_s"]
    return float(np.mean([c["hops"] * sent_a_hop / (c["hop_s"] * peak)
                          for c in ex["chips"].values()]))
