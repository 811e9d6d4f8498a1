"""What the program says of the host's two threads over the window
(PR 39): the tracer's totals by span name, ``[count, wall_s]`` of every
span recorded since its start, and each thread's CPU seconds by its own
CPU clock, as ``Pipeline.stats()`` and ``ShimFeeder.stats()`` hand them
out under ``span_totals`` and ``thread_cpu_s``; the harness takes both at
the window's two ends (``run.stats0``, ``run.stats1``), so a window's sums
are differences and the span ring need not hold the run.
"""

from typing import Dict, Optional, Sequence, Tuple

Totals = Dict[str, Tuple[int, float]]


def _totals_at(stats) -> Optional[dict]:
    """One end's totals: the feeder's and the pipeline's tracer are one
    in a serving process, so either's hold every name."""
    found = [t for t in ((stats.get(who) or {}).get("span_totals")
                         for who in ("feeder", "pipeline")) if t is not None]
    return {k: v for t in found for k, v in t.items()} if found else None


def window_totals(run) -> Optional[Totals]:
    """name → (count, wall_s) over the window, for every name recorded in
    it. None where the program hands out no totals: a program before
    PR 39, or tracing off."""
    b = _totals_at(run.stats1)
    if b is None:
        return None
    a = _totals_at(run.stats0) or {}
    out: Totals = {}
    for name, (n1, w1) in b.items():
        n0, w0 = a.get(name, (0, 0.0))
        if n1 > n0:
            out[name] = (n1 - n0, w1 - w0)
    return out


def thread_cpu_s(run, who: str) -> Optional[float]:
    """CPU seconds the feeder's (``who="feeder"``) or the worker's
    (``"pipeline"``) thread burnt in the window. None where the program
    hands out no such clock, or the thread was not the same at both ends
    (a restarted worker's clock starts anew)."""
    a = (run.stats0.get(who) or {}).get("thread_cpu_s")
    b = (run.stats1.get(who) or {}).get("thread_cpu_s")
    if a is None or b is None or b < a:
        return None
    return b - a


def wall_us_per(run, names: Sequence[str], per: str) -> Optional[float]:
    """Wall µs of the spans ``names`` per span ``per`` over the window.
    None where the program recorded none of ``names[0]`` or of ``per``."""
    t = window_totals(run)
    if not t or names[0] not in t or per not in t:
        return None
    return sum(t[n][1] for n in names if n in t) / t[per][0] * 1e6
