"""Kernels: device time under the program's ``lb.step`` scope (service
frontend probe, Maglev row, DNAT) per batch dispatched in the traced
interval, mean over the chips; ``benchmarks/lpm/trace.py`` ties events to
the scope. None where no traced program names it: a deployment with no
service frontend has no LB step in its program, and a program before
PR 34 has no scope."""

from benchmarks.lpm import trace


def read(run):
    sc = trace.scoped(run)
    if sc is None or not sc["has_lb"]:
        return None
    return sc["lb_s"] / sc["batches"] * 1e6
