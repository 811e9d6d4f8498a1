"""Process start → window start: imports, build of the two native
libraries, policy build and placement, the traffic from the seed, the live
set opened, compilation (or the compile cache), and the ring warm-up."""


def read(run):
    return run.info["setup_s"]
