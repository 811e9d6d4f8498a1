"""Host threads: rows the feeder's and the worker's threads ran through
``flow_hashes`` (the direction-normalised flow fingerprint: two ten-word
murmur passes, ≈250 short numpy calls a batch) per verdicted row, over the
window: ``flow_hash_rows`` of ``ShimFeeder.stats()`` and of
``pipeline_stats()["verdict_rows"]`` (plain counters the program adds to
where it makes the call, PR 41) at the window's two ends, over the rows
verdicted in it (``verdict_rows["total"]``).

1.0 where a row is hashed once, at harvest, and the hash rides the batch
to the feeder's ``note`` and the worker's salvage ``note`` (a little over,
since a view's invalid tail is hashed with it); 1 + twice the established
share where each ``note`` hashes its rows again, as before PR 41. Anything
above 1.0 names a path that still hashes. None where the program counts no
such rows (before PR 41)."""


def read(run):
    ends = []
    for st in (run.stats0, run.stats1):
        feeder = (st.get("feeder") or {}).get("flow_hash_rows")
        rows = (st.get("pipeline") or {}).get("verdict_rows") or {}
        if feeder is None or "flow_hash_rows" not in rows:
            return None
        ends.append((feeder + rows["flow_hash_rows"], rows["total"]))
    (h0, r0), (h1, r1) = ends
    if r1 <= r0:
        return None
    return (h1 - h0) / (r1 - r0)
