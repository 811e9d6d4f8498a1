"""Datapath host: the share of the wire's bytes that the window's rows
needed, each on the layout of its own class:
``pack_stats["wire_bytes_needed"]`` over ``pack_stats["wire_bytes"]``, both
from ``pipeline_stats()`` at the window's two ends.

``wire_bytes`` is what went up (``datapath.wire_bytes_per_row``): every row
of a batch on the one layout the batch-wide, sticky choice gives, padding
rows too. ``wire_bytes_needed`` counts each valid row at
``wire_words_for`` of its **own** two flags (wide: v6 or an ``ep_slot``
past the compact cap; L7: it carries a request under a snapshot with rule
sets) and the dictionary at the distinct paths of the L7 rows alone. 1.0
where every row needs the layout its batch rides (a one-plane cell with
full buckets); what a dispatch split by wire class would raise where rows
of several classes ride one batch. None at a program without the counters
(before PR 42)."""


def read(run):
    ends = []
    for st in (run.stats0, run.stats1):
        pack = (st.get("pipeline") or {}).get("pack_stats") or {}
        if "wire_bytes" not in pack or "wire_bytes_needed" not in pack:
            return None
        ends.append((pack["wire_bytes_needed"], pack["wire_bytes"]))
    (n0, b0), (n1, b1) = ends
    if b1 <= b0:
        return None
    return (n1 - n0) / (b1 - b0)
