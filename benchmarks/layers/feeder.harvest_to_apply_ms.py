"""Feeder: median of harvest → verdict apply, one sample per batch, from
the window's part of the ``ingest_e2e_latency_seconds`` histogram (linear
inside the winning bucket, as the histogram's own quantile reads it)."""


def quantile(buckets, counts, q):
    total = sum(counts)
    if total == 0:
        return None
    rank, seen = q * total, 0
    for i, c in enumerate(counts):
        if c and seen + c >= rank:
            if i >= len(buckets):
                return buckets[-1]
            lo = buckets[i - 1] if i else 0.0
            return lo + (buckets[i] - lo) * (rank - seen) / c
        seen += c
    return buckets[-1]


def read(run):
    h0, h1 = run.stats0["e2e_hist"], run.stats1["e2e_hist"]
    if h1 is None:
        return None
    buckets, c1 = h1[0], h1[1]
    c0 = h0[1] if h0 is not None else [0] * len(c1)
    q = quantile(buckets, [b - a for a, b in zip(c0, c1)], 0.5)
    return None if q is None else q * 1e3
