"""Datapath host: time of the span ``datapath.pack.l7dict`` (the path
dictionary's build for a batch on the L7 wire: one ``np.unique`` over the
batch's 64-byte paths, the dictionary's words, the wire's columns) per
dispatched batch, over the window (traced run: the harness sets
``trace_sample_rate`` to 1). The span lies inside ``datapath.pack``, so
this is a part of ``datapath.host_us_per_batch``. None where the program
records no such span: a program before PR 37, or a cell whose batches
carry no request."""

SPAN = "datapath.pack.l7dict"
BATCH_SPAN = "datapath.pack"


def read(run):
    total, batches, found = 0.0, 0, False
    for name, t0, dur in run.spans:
        if run.w0 <= t0 < run.w1:
            if name == SPAN:
                total += dur
                found = True
            batches += name == BATCH_SPAN
    if not found or not batches:
        return None
    return total / batches * 1e6
