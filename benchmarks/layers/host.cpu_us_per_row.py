"""Host threads: CPU microseconds the feeder's and the worker's threads
burn a verdicted row, over the window: each thread's own CPU clock
(``thread_cpu_s`` of ``ShimFeeder.stats()`` and ``Pipeline.stats()``: a
counter the program hands out, read from the harness's thread at the
window's two ends, at no cost to either thread) over the rows verdicted in
it (``pipeline_stats()["verdict_rows"]["total"]``, else ``fill_rows``).

It counts all a thread burns, not the work of its spans alone: the
feeder's polling while a harvest is held back, each thread's loop between
spans and what its waits burn are in it, so it reads above the Python a
row costs inside the spans (by a third in ``ct1m-50k.saturate``: PERF.md
§5 item 1). On a host whose CPU clock ticks coarsely (gVisor: 10 ms) it is
a count of ticks, ≈3,000 a thread a 40 s window; what that clock reads
for known work: ``benchmarks/tests/host_facts.py --clock``. None where the
program hands out no such clock (before PR 39)."""

from benchmarks.host import spans


def rows_verdicted(run):
    a, b = run.stats0.get("pipeline") or {}, run.stats1.get("pipeline") or {}
    if a.get("verdict_rows") and b.get("verdict_rows"):
        return b["verdict_rows"]["total"] - a["verdict_rows"]["total"]
    return b.get("fill_rows", 0) - a.get("fill_rows", 0)


def read(run):
    feeder = spans.thread_cpu_s(run, "feeder")
    worker = spans.thread_cpu_s(run, "pipeline")
    rows = rows_verdicted(run)
    if feeder is None or worker is None or rows <= 0:
        return None
    return (feeder + worker) / rows * 1e6
