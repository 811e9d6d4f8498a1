"""Mesh: self time of the span ``datapath.readback`` per batch, over the
window: the meshed finalizers' one ``np.asarray`` of a batch's verdict slab
(four per-chip segments in one sharded array, since PR 30). Since PR 32 the
eager finalize enters that read while the chips still work, so the span
holds the wait for the chips as well as the copy (PERF.md §3). None where
the program has no such span (one chip; a program before PR 29)."""


def read(run):
    spans = [dur for name, t0, dur in run.spans
             if name == "datapath.readback" and run.w0 <= t0 < run.w1]
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e6
