"""Load generator: 99th percentile of (time a frame entered the ring − time
it was due), over the window's accepted frames. A starved generator must
not read as a fast server."""

import numpy as np


def read(run):
    if run.due is None:
        return None
    inj = run.nic["inject_t"]
    due = run.due[:inj.shape[0]]
    m = (inj >= 0) & (due >= run.w0) & (due < run.w1)
    if not m.any():
        return None
    return float(np.percentile(inj[m] - due[m], 99) * 1e3)
