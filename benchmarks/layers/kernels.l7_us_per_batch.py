"""Kernels: device time of the L7 lane (the events that stand under the
program's ``l7.unpack`` scope, its ``l7.match`` scope or both: the path
dictionary unpacked on the device, every row's request held to its cell's
rule set) per batch dispatched in the traced interval, mean over the
chips. ``benchmarks/l7/trace.py`` says how an event is tied to a scope.
None where no traced program names either (a program before PR 37, or one
loaded from a compile cache written before it)."""

from benchmarks.l7 import trace


def read(run):
    sc = trace.scoped(run)
    if sc is None:
        return None
    return sc["l7_s"] / sc["batches"] * 1e6
