"""Feeder: wall time of the span ``feeder.apply`` (a harvest's verdicts
onto its frames: the established filter's note, one ``apply_verdicts`` a
shim batch, the latency observation) per applied harvest over the window,
from the tracer's totals at the window's two ends
(``benchmarks/host/spans.py``). None where the program records no such
span (before PR 39)."""

from benchmarks.host import spans


def read(run):
    return spans.wall_us_per(run, ("feeder.apply",), "feeder.apply")
