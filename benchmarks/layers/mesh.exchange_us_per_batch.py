"""Mesh: time the conntrack exchange's ring hops hold a chip's operation
stream (the collective-permute start and done events on the ``XLA Ops``
line of the profiler trace: issuing a hop, and waiting for one that has
not come), mean over the chips, per batch dispatched in the traced
interval. ``benchmarks/mesh/trace.py`` says what the events are called."""

from benchmarks.mesh import trace


def read(run):
    ex = trace.exchange(run)
    if ex is None:
        return None
    return trace.mean_over_chips(ex, "exposed_s") / ex["batches"] * 1e6
