"""Load generator: frames the ring refused inside a stop episode (opened
when the generator itself comes to a frame over 1 ms late; nic/nicgen.cc
has the rule). They are the host's loss and are left out of ``failed``,
which keeps the refusals that found the generator on time."""


def read(run):
    if run.due is None:
        return None
    return run.nic["n_refused_in_stop"]
