"""Mesh: self time of the span ``datapath.readback`` (the meshed
finalizers' device→host reads of a batch's verdict columns and counters,
first ``np.asarray`` to last) per batch, over the window. None where the
program has no such span (one chip reads a slab; a program before PR 29)."""


def read(run):
    spans = [dur for name, t0, dur in run.spans
             if name == "datapath.readback" and run.w0 <= t0 < run.w1]
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e6
