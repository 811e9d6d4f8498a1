"""Kernels: the LPM walk's share of its memory roofline. The bytes the
walks of the traced interval's rows have to read (``benchmarks/lpm/
walk_bytes.py``: one 12-byte trie entry a level a row, 4 levels for a v4
address; the cell's deployment is v4 only) over what the chip's memory
could have moved in the device time under ``lpm.walk`` (``benchmarks/lpm/
trace.py``), at ``hbm_bytes_per_s`` of ``benchmarks/peaks.json``. The walk
is a chain of dependent gathers of 12 bytes each into a table far larger
than any on-chip memory, so it is bound by the latency of a read and not
by bandwidth: the share reads far under 1%, and what would raise it is
fewer, wider or overlapped reads, not a faster memory."""

from benchmarks import harness
from benchmarks.lpm import trace, walk_bytes


def read(run):
    sc = trace.scoped(run)
    if sc is None or sc["lpm_s"] <= 0:
        return None
    m0, m1 = run.trace["window_mono_s"]
    rows = run.verdicts_by(m1) - run.verdicts_by(m0)
    if rows <= 0:
        return None
    peak = harness.chip_peaks(
        harness.describe_device()["kind"])["hbm_bytes_per_s"]
    # a chip walks its own rows: on a mesh, its share of the batch
    return walk_bytes.walk_bytes(rows) / sc["chips"] / (sc["lpm_s"] * peak)
