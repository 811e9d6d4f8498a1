"""90th percentile of due time → verdict applied, over every frame due in
the window: the tail that stands under a bound on a machine whose host
stops now and then (PERF.md §2); the 99th is read beside it, unbounded."""

import numpy as np

from benchmarks.e2e.latency import window_latencies_ms


def read(run):
    lat = window_latencies_ms(run)
    return None if lat is None else float(np.percentile(lat, 90))
