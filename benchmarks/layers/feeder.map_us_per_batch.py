"""Feeder: what a harvest costs its thread between the shim and the
pipeline's queue, per harvest that returned rows, over the window: wall
time of the spans ``feeder.map`` (slot mapping, the flow hash and the
established filter, the shed check) and ``feeder.submit`` (the
``engine.submit`` call) per ``feeder.map`` span, from the tracer's totals
at the window's two ends (``benchmarks/host/spans.py``). None where the
program records no such span (before PR 39)."""

from benchmarks.host import spans


def read(run):
    return spans.wall_us_per(run, ("feeder.map", "feeder.submit"),
                             "feeder.map")
