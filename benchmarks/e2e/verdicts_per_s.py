"""Verdicts applied on ring frames inside the window, over the window's
length: all the work and all the time of the window, read from nicgen's
log of the shim's verdict counters."""


def read(run):
    return (run.verdicts_by(run.w1) - run.verdicts_by(run.w0)) \
        / (run.w1 - run.w0)
