"""Kernels: device busy time (union of the device-operation intervals of
the profiler trace, summed over the chips) per ring frame that got its
verdict inside the traced interval."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    m0, m1 = tr["window_mono_s"]
    rows = run.verdicts_by(m1) - run.verdicts_by(m0)
    if rows <= 0:
        return None
    return sum(tr["busy_s_per_chip"].values()) * 1e9 / rows
