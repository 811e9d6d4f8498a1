"""Datapath host: self time of the spans ``datapath.pack`` and
``datapath.transfer`` per dispatched batch, over the window (traced run:
the harness sets ``trace_sample_rate`` to 1). ``datapath.steer`` runs
inside ``datapath.pack`` and is taken out of it."""


def read(run):
    tot = {"datapath.pack": 0.0, "datapath.transfer": 0.0,
           "datapath.steer": 0.0}
    batches = 0
    for name, t0, dur in run.spans:
        if name in tot and run.w0 <= t0 < run.w1:
            tot[name] += dur
            batches += name == "datapath.pack"
    if not batches:
        return None
    return (tot["datapath.pack"] - tot["datapath.steer"]
            + tot["datapath.transfer"]) / batches * 1e6
