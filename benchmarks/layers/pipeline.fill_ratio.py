"""Pipeline: valid rows over dispatched bucket rows in the window
(``pipeline_stats()``: fill_rows / bucket_rows)."""


def read(run):
    a, b = run.stats0["pipeline"], run.stats1["pipeline"]
    rows = b["bucket_rows"] - a["bucket_rows"]
    if rows <= 0:
        return None
    return (b["fill_rows"] - a["fill_rows"]) / rows
