"""Kernels: device time under the program's ``lpm.walk`` scope (the
ipcache's trie walk, both families' chains) per batch dispatched in the
traced interval, mean over the chips. ``benchmarks/lpm/trace.py`` says how
an event of the profiler trace is tied to the scope; an event whose fusion
holds ``lb.step`` as well counts as mixed and is left out here (``mixed_s``
of ``trace.scoped``, which PERF.md reports). None where no traced program
names the scope (a program before PR 34, or one loaded
from a compile cache written before it)."""

from benchmarks.lpm import trace


def read(run):
    sc = trace.scoped(run)
    if sc is None:
        return None
    return sc["lpm_s"] / sc["batches"] * 1e6
