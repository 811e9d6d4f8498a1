"""Per-frame latency in an open loop: the time the verdict count first
reached k+1 minus the time frame k was DUE, over every frame due inside
the window that the ring accepted (a refused frame is counted in
``failed``). Shared by the percentile readers."""

import numpy as np


def window_latencies_ms(run):
    if run.due is None or run.accepted_idx is None:
        return None
    due = run.due[run.accepted_idx]
    m = (due >= run.w0) & (due < run.w1) & np.isfinite(run.verdict_t)
    if not m.any():
        return None
    run.info["latency_samples"] = int(m.sum())
    return (run.verdict_t[m] - due[m]) * 1e3
