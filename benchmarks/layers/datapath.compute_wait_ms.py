"""Datapath host: median of the span ``datapath.compute`` over the window:
from the moment finalize asks for the verdict columns to the moment they
are on the host."""

import numpy as np


def read(run):
    d = [dur for name, t0, dur in run.spans
         if name == "datapath.compute" and run.w0 <= t0 < run.w1]
    return float(np.percentile(d, 50) * 1e3) if d else None
