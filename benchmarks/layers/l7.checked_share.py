"""Kernels: the share of the window's verdicted rows that the L7 lane held
to a rule set (``pipeline_stats()["verdict_rows"]``: the program's
``ciliumtpu_l7_checked_rows_total`` over its rows verdicted, both read at
one instant at either end of the window). A row is checked when it is
valid, carries a request and its policy cell redirects to a set, whether
its flow is new or established; so this is the share of the frames that
go to a port some document names, which the plain reference states of the
same frames. None where the program has no such counter (before PR 37)."""


def read(run):
    a = run.stats0["pipeline"].get("verdict_rows")
    b = run.stats1["pipeline"].get("verdict_rows")
    if a is None or b is None or "l7_checked" not in b \
            or b["total"] <= a["total"]:
        return None
    return (b["l7_checked"] - a["l7_checked"]) / (b["total"] - a["total"])
