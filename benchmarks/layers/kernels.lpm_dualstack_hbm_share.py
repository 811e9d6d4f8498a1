"""Kernels: the LPM walk's share of its memory roofline in a deployment of
both families. As ``kernels.lpm_hbm_share``, whose count takes every row
for a v4 address (four levels): here the traced interval's rows are split
by the window's own share of v6 rows (``verdict_rows["wide_needed"]`` over
``verdict_rows["total"]`` at the window's two ends: valid rows that are v6,
or whose ``ep_slot`` passes the compact cap, which no cell's does), a v4
row reading 4 trie entries of 12 bytes and a v6 row 16
(``benchmarks/lpm/walk_bytes.py``, from the node layout alone), over what
the chip's memory could have moved in the device time under ``lpm.walk``
(``benchmarks/lpm/trace.py``) at ``hbm_bytes_per_s`` of
``benchmarks/peaks.json``. The program walks both tries for every row and
selects by family, so it reads more than this counts and the share is the
lower for it; a chain of dependent 12-byte gathers is bound by latency, so
it reads far under 1%. None where no traced program names the scope, and at
a program without the counter (before PR 42)."""

from benchmarks import harness
from benchmarks.lpm import trace, walk_bytes


def read(run):
    a = (run.stats0.get("pipeline") or {}).get("verdict_rows") or {}
    b = (run.stats1.get("pipeline") or {}).get("verdict_rows") or {}
    if "wide_needed" not in a or "wide_needed" not in b \
            or b["total"] <= a["total"]:
        return None
    sc = trace.scoped(run)
    if sc is None or sc["lpm_s"] <= 0:
        return None
    m0, m1 = run.trace["window_mono_s"]
    rows = run.verdicts_by(m1) - run.verdicts_by(m0)
    if rows <= 0:
        return None
    v6 = rows * (b["wide_needed"] - a["wide_needed"]) \
        / (b["total"] - a["total"])
    peak = harness.chip_peaks(
        harness.describe_device()["kind"])["hbm_bytes_per_s"]
    # a chip walks its own rows: on a mesh, its share of the batch
    return walk_bytes.walk_bytes(rows - v6, v6) / sc["chips"] \
        / (sc["lpm_s"] * peak)
