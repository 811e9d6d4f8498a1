"""Pipeline: share of the window's dispatches that left on the coalescing
deadline (``pipeline_stats()["flush_reasons"]``)."""


def read(run):
    a = run.stats0["pipeline"]["flush_reasons"]
    b = run.stats1["pipeline"]["flush_reasons"]
    delta = {k: b[k] - a.get(k, 0) for k in b}
    total = sum(delta.values())
    if total <= 0:
        return None
    return delta.get("deadline", 0) / total
