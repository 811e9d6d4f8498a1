"""Pipeline: what a finalize costs the worker after the device's answer is
on the host, per finalized batch over the window: wall time of the spans
``datapath.unpack`` (buffer release, ``unpack_out``), ``engine.account``
(metrics, flow log, flow metrics, the observers, the salvage window) and
``pipeline.settle`` (the outcomes, the recycle, the tickets) per
``pipeline.finalize`` span, from the tracer's totals at the window's two
ends (``benchmarks/host/spans.py``). None where the program records no
such span (before PR 39)."""

from benchmarks.host import spans


def read(run):
    return spans.wall_us_per(
        run, ("datapath.unpack", "engine.account", "pipeline.settle"),
        "pipeline.finalize")
