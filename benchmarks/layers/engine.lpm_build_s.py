"""Engine: seconds of the span ``engine.regen.lpm``, the build of the two
LPM tries from the ipcache inside a regeneration (every prefix parsed, the
nodes a level at a time, full build or incremental rebuild alike), read
from the program's spans in the traced run. The regeneration that counts
is set-up's, which starts before the window: a regeneration is always
sampled, and the tracer's ring of 2^18 spans still holds it after the
window's. The longest where there are several. None where the program
records no such span: a program before PR 53, or a run that is not
traced."""

SPAN = "engine.regen.lpm"


def read(run):
    found = [dur for name, _t0, dur in run.spans if name == SPAN]
    return max(found) if found else None
