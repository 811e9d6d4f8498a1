"""Profiler trace (``.xplane.pb``) → device busy time, top device
operations, and idle gaps named by what the host was doing.

The yardstick lives here, not in the program: every PR reduces its trace
with this code. It needs nothing but JAX's own reader
(``jax.profiler.ProfileData``).

What a trace holds (looked at by hand on a TPU v5e trace, PERF.md PR 23):
one plane per chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` has one
event per executed HLO operation, with start and duration in nanoseconds
from the start of the trace; and ``/host:CPU`` with one line per host
thread, holding the ``TraceAnnotation`` events. The harness brackets the
traced interval with two annotations, ``bench.window.start`` and
``bench.window.end``, each carrying ``t_mono_ns`` — the host's monotonic
clock at that moment — which ties the trace's clock to the clock of the
program's spans.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MARK_START = "bench.window.start"
MARK_END = "bench.window.end"

Interval = Tuple[float, float]            # (start_ns, end_ns)


def union_ns(starts: np.ndarray, ends: np.ndarray) -> Tuple[float,
                                                            List[Interval]]:
    """Length and merged pieces of the union of [start, end) intervals."""
    if starts.size == 0:
        return 0.0, []
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    # a new piece starts wherever an interval begins past everything before
    new = np.ones(s.shape, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.nonzero(new)[0]
    piece_s = s[first]
    piece_e = np.maximum.reduceat(e, first)
    return float((piece_e - piece_s).sum()), \
        list(zip(piece_s.tolist(), piece_e.tolist()))


_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")


def short_op(name: str) -> str:
    """An operation's event name is its whole HLO line; keep the
    instruction's own name and its opcode (``%while.1 while``)."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:120]
    m = _OPCODE.search(" " + rhs)
    return (lhs + (" " + m.group(1) if m else ""))[:120]


def read_planes(path: str) -> Dict:
    """→ {"devices": {plane: {"ops": [(name, start_ns, dur_ns)],
    "all": [...]}}, "marks": {name: (start_ns, t_mono_ns)}}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    marks: Dict[str, Tuple[float, float]] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            d = devices.setdefault(plane.name, {"ops": [], "lines": []})
            for line in plane.lines:
                d["lines"].append(line.name)
                if line.name == OPS_LINE:
                    d["ops"] = [(short_op(e.name), float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (MARK_START, MARK_END):
                        stats = dict(e.stats)
                        if "t_mono_ns" in stats:
                            marks[e.name] = (float(e.start_ns),
                                             float(stats["t_mono_ns"]))
    return {"devices": devices, "marks": marks}


def reduce_planes(planes: Dict, spans: Sequence[Tuple[str, float, float]] = (),
                  top: int = 10) -> Optional[Dict]:
    """→ busy seconds per chip and averaged, the traced window, the ``top``
    device operations by total time (over all chips) and the idle time of
    the busiest chip split by the program span open during it.

    ``spans`` are the program's own (name, start_monotonic_s, duration_s).
    Returns None when the trace has no device plane or no window marks:
    there is nothing to read."""
    marks, devices = planes["marks"], planes["devices"]
    if MARK_START not in marks or MARK_END not in marks or not devices:
        return None
    w0, mono0 = marks[MARK_START]
    w1, _ = marks[MARK_END]
    if w1 <= w0:
        return None
    offset_ns = mono0 - w0               # monotonic = trace + offset
    per_chip: Dict[str, float] = {}
    pieces_of: Dict[str, List[Interval]] = {}
    op_time: Dict[str, float] = {}
    for name, d in sorted(devices.items()):
        ops = d["ops"]
        if not ops:
            per_chip[name] = 0.0
            pieces_of[name] = []
            continue
        s = np.array([o[1] for o in ops])
        e = s + np.array([o[2] for o in ops])
        cs, ce = np.clip(s, w0, w1), np.clip(e, w0, w1)
        keep = ce > cs
        busy, pieces = union_ns(cs[keep], ce[keep])
        per_chip[name] = busy / 1e9
        pieces_of[name] = pieces
        for (op, _s, _d), c0, c1, k in zip(ops, cs, ce, keep):
            if k:
                op_time[op] = op_time.get(op, 0.0) + (c1 - c0) / 1e9
    busiest = max(per_chip, key=per_chip.get)
    gaps = _gaps(pieces_of[busiest], w0, w1)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": float(np.mean(list(per_chip.values()))),
        "busy_s_per_chip": per_chip,
        "device_ops": [[n, t] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": _name_gaps(gaps, spans, offset_ns, top),
        "n_gaps": len(gaps),
        "longest_gap_s": max((g1 - g0 for g0, g1 in gaps), default=0.0) / 1e9,
        "window_mono_s": ((w0 + offset_ns) / 1e9, (w1 + offset_ns) / 1e9),
    }


def _gaps(pieces: List[Interval], w0: float, w1: float) -> List[Interval]:
    gaps, at = [], w0
    for s, e in pieces:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if w1 > at:
        gaps.append((at, w1))
    return gaps


def _name_gaps(gaps: List[Interval],
               spans: Sequence[Tuple[str, float, float]],
               offset_ns: float, top: int) -> List[List]:
    """Idle seconds by the program span that was open: each gap is cut at
    the span boundaries inside it, and each piece goes to the innermost
    (latest-started) span covering it, or to ``no span open``."""
    if not gaps:
        return []
    sp = sorted(((s * 1e9 - offset_ns, (s + d) * 1e9 - offset_ns, n)
                 for n, s, d in spans), key=lambda x: x[0])
    starts = np.array([x[0] for x in sp]) if sp else np.zeros((0,))
    total: Dict[str, float] = {}
    for g0, g1 in gaps:
        cuts = {g0, g1}
        lo = int(np.searchsorted(starts, g1)) if sp else 0
        near = [x for x in sp[max(0, lo - 256):lo] if x[1] > g0]
        for s, e, _n in near:
            if g0 < s < g1:
                cuts.add(s)
            if g0 < e < g1:
                cuts.add(e)
        edges = sorted(cuts)
        for a, b in zip(edges[:-1], edges[1:]):
            mid = (a + b) / 2
            open_ = [x for x in near if x[0] <= mid < x[1]]
            name = max(open_, key=lambda x: x[0])[2] if open_ \
                else "no span open"
            total[name] = total.get(name, 0.0) + (b - a) / 1e9
    return [[n, t] for n, t in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:top]]


def reduce_file(path: str, spans: Sequence[Tuple[str, float, float]] = (),
                top: int = 10) -> Optional[Dict]:
    return reduce_planes(read_planes(path), spans, top)
