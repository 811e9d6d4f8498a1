"""Feeder: median of the span ``feeder.roundtrip`` over the window: a
harvest's stamp → its verdicts applied, the interval the
``ingest_e2e_latency_seconds`` histogram observes, one exact sample a
harvest (``feeder.harvest_to_apply_ms`` reads that histogram's bucket).
None where the program records no such span (before PR 39)."""

import numpy as np


def read(run):
    d = [dur for name, t0, dur in run.spans
         if name == "feeder.roundtrip" and run.w0 <= t0 < run.w1]
    return float(np.percentile(d, 50) * 1e3) if d else None
