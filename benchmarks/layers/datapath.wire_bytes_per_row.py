"""Datapath host: bytes the program put on the device for its batches' wire
per verdicted row, over the window: ``pack_stats["wire_bytes"]`` (every
batch's wire as ``_pack_wire`` shipped it, rows × the batch-wide layout's
words × 4, and the path dictionary where ``_upload_path_dict`` uploaded
one; a content-cache hit ships none) at the window's two ends, over the
rows verdicted in it (``verdict_rows["total"]``), both from
``pipeline_stats()``.

The layout is chosen for the whole batch and the choice is kept: 16 bytes a
row on the narrow wire (``ct1m-50k``, ``lpm100k-zipf``), 20 and a
dictionary on the L7 wire (``l7-http``), 44 on the wide one, and 48 and a
dictionary where rows of every kind ride one batch (``node-mixed``). None
at a program without the counter (before PR 42)."""


def read(run):
    ends = []
    for st in (run.stats0, run.stats1):
        pipeline = st.get("pipeline") or {}
        pack = pipeline.get("pack_stats") or {}
        rows = pipeline.get("verdict_rows") or {}
        if "wire_bytes" not in pack or "total" not in rows:
            return None
        ends.append((pack["wire_bytes"], rows["total"]))
    (b0, r0), (b1, r1) = ends
    if r1 <= r0:
        return None
    return (b1 - b0) / (r1 - r0)
