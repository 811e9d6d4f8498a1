"""Load generator: 99th percentile of due time → verdict applied, over
every frame due in the window. It stands here without a bound: on a host
that stops for a tenth of a second now and then it counts the stops, and
reads in as many modes as a window can hold stops (PERF.md §2). The
bounded tail is ``verdict_p90_ms``."""

import numpy as np

from benchmarks.e2e.latency import window_latencies_ms


def read(run):
    lat = window_latencies_ms(run)
    return None if lat is None else float(np.percentile(lat, 99))
