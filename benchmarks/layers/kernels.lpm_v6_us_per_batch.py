"""Kernels: device time under the program's ``lpm.walk.v6`` scope (the
sixteen dependent gathers of the v6 trie's chain, inside ``lpm.walk``) per
batch dispatched in the traced interval, mean over the chips.
``benchmarks/lpm/trace.py`` ties an event of the profiler trace to a pair
of scope names, here the two families' (``lpm.walk.v6``, ``lpm.walk.v4``):
an event whose fusion holds instructions of both chains counts as mixed
and is left out (the select by family after the two walks is one; PERF.md
reports it), so with ``kernels.lpm_us_per_batch`` beside it the rest is
the v4 chain and that select. None where no traced program names either
family's scope: a program before PR 53, one loaded from a compile cache
written before it, or a run that is not traced."""

from benchmarks.lpm import trace
from benchmarks.reduce import xplane

SCOPES = ("lpm.walk.v6", "lpm.walk.v4")


def read(run):
    sc = trace.scoped(run)
    if sc is None:
        return None
    path = trace.trace_file(run)
    marks = xplane.read_planes(path)["marks"]
    by = trace.seconds_by_scope(trace.read_trace(path),
                                marks[xplane.MARK_START][0],
                                marks[xplane.MARK_END][0], SCOPES)
    if by is None or SCOPES[0] not in by["named"]:
        return None
    chips = list(by["chips"].values())
    return sum(c["first"] for c in chips) / len(chips) / sc["batches"] * 1e6
