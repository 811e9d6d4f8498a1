"""Engine: seconds of the span ``engine.regen.lb``, the build of the
load-balancer's tables inside a regeneration (every service's Maglev row,
the frontend probe table, the backend arrays), read from the program's
spans in the traced run. The regeneration that counts is set-up's, which
starts before the window: a regeneration is always sampled, and the
tracer's ring of 2^18 spans still holds it after the window's. The longest
where there are several (a later policy-only regeneration reuses every
row). None where the program records no such span: a program before PR 45,
or a run that is not traced."""

SPAN = "engine.regen.lb"


def read(run):
    found = [dur for name, _t0, dur in run.spans if name == SPAN]
    return max(found) if found else None
