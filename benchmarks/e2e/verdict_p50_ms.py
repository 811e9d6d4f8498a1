"""Median of due time → verdict applied, per frame."""

import numpy as np

from benchmarks.e2e.latency import window_latencies_ms


def read(run):
    lat = window_latencies_ms(run)
    return None if lat is None else float(np.percentile(lat, 50))
