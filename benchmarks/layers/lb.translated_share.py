"""Kernels: the share of the window's verdicted rows that the LB step sent
on to a backend (``pipeline_stats()["verdict_rows"]``: the program's
``ciliumtpu_lb_translated_rows_total`` over its rows verdicted, both read
at one instant at either end of the window). It is the share of the
frames whose flow goes to a service frontend: a tenth of the live flows
do, and the frames' share follows the ranks those flows drew. None where
the program has no such counter (before PR 34)."""


def read(run):
    a = run.stats0["pipeline"].get("verdict_rows")
    b = run.stats1["pipeline"].get("verdict_rows")
    if a is None or b is None or b["total"] <= a["total"]:
        return None
    return (b["lb_translated"] - a["lb_translated"]) \
        / (b["total"] - a["total"])
