"""Kernels: the L7 lane's share of its memory roofline. The bytes the
matches of the traced interval's rows have to read (``benchmarks/l7/
match_bytes.py``: a row's 64 path bytes, its method and set words, and a
batch the rules its rows name, the whole rule tensors at most; the set
count and the rules a set are the configuration's) over what the chip's
memory could have moved in the device time under ``l7.unpack`` and
``l7.match`` (``benchmarks/l7/trace.py``), at ``hbm_bytes_per_s`` of
``benchmarks/peaks.json``. A batch has about 116 KB to read, a seventh of
a microsecond of the chip's memory, so the share says how far the lane is
from a copy: it reads a few per cent at most, and what would raise it is
fewer and wider operations over the rows, not a faster memory. None where
no traced program names the scopes."""

from benchmarks import harness
from benchmarks.l7 import match_bytes, trace


def read(run):
    sc = trace.scoped(run)
    if sc is None or sc["l7_s"] <= 0:
        return None
    m0, m1 = run.trace["window_mono_s"]
    rows = run.verdicts_by(m1) - run.verdicts_by(m0)
    if rows <= 0:
        return None
    world = run.cell.config["world"]
    peak = harness.chip_peaks(
        harness.describe_device()["kind"])["hbm_bytes_per_s"]
    # a chip matches its own rows: on a mesh, its share of each batch
    need = match_bytes.match_bytes(
        rows / sc["chips"], sc["batches"], int(world["n_rulesets"]),
        len(world["rules"]))
    return need / (sc["l7_s"] * peak)
