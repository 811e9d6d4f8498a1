"""Kernels: the LB step's share of its memory roofline. The bytes the steps
of the traced interval's rows have to read (``benchmarks/lb/step_bytes.py``:
a row's probe window of eight 28-byte slots, its frontend's service and
rev-NAT id, one Maglev entry, its backend's address and port: 256 bytes)
over what the chip's memory could have moved in the device time under
``lb.step`` (``benchmarks/lpm/trace.py``), at ``hbm_bytes_per_s`` of
``benchmarks/peaks.json``. The step is a chain of dependent gathers of a few
words each, the longest of them one word out of 10,000 Maglev rows of 16,381
that no on-chip memory holds, so it is bound by the latency of a read and
not by bandwidth: the share reads far under 1%, and what would raise it is
fewer, wider or overlapped reads, not a faster memory. None where no traced
program names the scope (a program before PR 34, a deployment without a
frontend)."""

from benchmarks import harness
from benchmarks.lb import step_bytes
from benchmarks.lpm import trace


def read(run):
    sc = trace.scoped(run)
    if sc is None or not sc["has_lb"] or sc["lb_s"] <= 0:
        return None
    m0, m1 = run.trace["window_mono_s"]
    rows = run.verdicts_by(m1) - run.verdicts_by(m0)
    if rows <= 0:
        return None
    peak = harness.chip_peaks(
        harness.describe_device()["kind"])["hbm_bytes_per_s"]
    # a chip translates its own rows: on a mesh, its share of the batch
    return step_bytes.step_bytes(rows) / sc["chips"] / (sc["lb_s"] * peak)
