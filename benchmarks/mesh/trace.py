"""The conntrack exchange in a run's profiler trace: what both
``mesh.exchange_*`` readers read.

What the events are called (looked at by hand in a TPU v5e trace of
``ct1m-50k-mesh4.saturate`` on four chips, PERF.md PR 29). The six ring
hops of a batch are twelve events on each chip's ``XLA Ops`` line, in
program order: ``%collective-permute-start = (u32[64,13]…) collective-
permute-start(…), channel_id=1, source_target_pairs={{0,1},{1,2},{2,3},
{3,0}}`` and its ``%collective-permute-done``, then ``.1`` and ``.2``
(the request gather, 3,328 bytes a hop), then ``.3``, ``.4``, ``.5`` on
``u32[64,2]`` (the reply scatter, 512 bytes a hop). A start or a done
holds the line for 0.005–3 µs: issuing, or waiting for what has not
come. Between them the line runs other operations, so a hop's own time
is from its start's beginning to its done's end: 3–4 µs for a hop whose
neighbour is ready, 23–34 µs for the first hop of either phase, which
waits for a neighbour that the host launched later. The ``Async XLA Ops``
line holds exactly that interval as one event a hop, but only on chip 0
of the trace; the pairs on ``XLA Ops`` give it on every chip. The scopes
``rss.request_gather`` / ``rss.owner_ct`` / ``rss.reply_scatter`` are in
the program's op metadata, not in these event names.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.reduce import xplane

#: an exchange hop's two events: ``%collective-permute-start[.k]`` and
#: ``%collective-permute-done[.k]`` (``short_op`` keeps the instruction's
#: name first)
START = "%collective-permute-start"
DONE = "%collective-permute-done"
#: the program's span that opens one dispatched batch
BATCH_SPAN = "datapath.pack"


def trace_file(run) -> Optional[str]:
    trace_dir = run.info.get("trace_dir")
    if not trace_dir:
        return None
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def hops_of(ops: List[Tuple[str, float, float]], w0: float, w1: float
            ) -> Tuple[float, float, int]:
    """One chip's ``XLA Ops`` events → (ns its line spent in the hops'
    start and done events, ns from each hop's start to its done's end,
    hops), over the hops that lie whole inside [w0, w1]."""
    open_: Dict[str, Tuple[float, float]] = {}  # ".k" → its start event
    exposed = hop = 0.0
    n = 0
    for name, s, dur in sorted(ops, key=lambda o: o[1]):
        inst = name.split(" ", 1)[0]
        if inst.startswith(START) and s >= w0:
            open_[inst[len(START):]] = (s, dur)
        elif inst.startswith(DONE) and s + dur <= w1:
            start = open_.pop(inst[len(DONE):], None)
            if start is not None:
                exposed += start[1] + dur
                hop += s + dur - start[0]
                n += 1
    return exposed, hop, n


def exchange(run) -> Optional[Dict]:
    """→ {"batches": batches dispatched in the traced interval, "chips":
    {plane: {"exposed_s", "hop_s", "hops"}}} for this run, read once and
    kept on ``run.info``; None where there is nothing to read: no trace,
    one chip, a program with no exchange."""
    if "mesh_exchange" in run.info:
        return run.info["mesh_exchange"]
    out = None
    path = trace_file(run) if run.trace is not None else None
    if path is not None:
        planes = xplane.read_planes(path)
        w0 = planes["marks"][xplane.MARK_START][0]
        w1 = planes["marks"][xplane.MARK_END][0]
        chips = {}
        for name, d in planes["devices"].items():
            exposed, hop, n = hops_of(d["ops"], w0, w1)
            if n:
                chips[name] = {"exposed_s": exposed / 1e9,
                               "hop_s": hop / 1e9, "hops": n}
        m0, m1 = run.trace["window_mono_s"]
        batches = sum(1 for name, t0, _d in run.spans
                      if name == BATCH_SPAN and m0 <= t0 < m1)
        if chips and batches:
            out = {"batches": batches, "chips": chips}
    run.info["mesh_exchange"] = out
    return out


def mean_over_chips(ex: Dict, key: str) -> float:
    return float(np.mean([c[key] for c in ex["chips"].values()]))
