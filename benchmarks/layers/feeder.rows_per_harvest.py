"""Feeder: valid rows per harvested batch over the window
(``ShimFeeder.stats()``: harvested_records / harvested_batches)."""


def read(run):
    a, b = run.stats0["feeder"], run.stats1["feeder"]
    batches = b["harvested_batches"] - a["harvested_batches"]
    if batches <= 0:
        return None
    return (b["harvested_records"] - a["harvested_records"]) / batches
