#!/usr/bin/env python3
"""A run of an L7 cell with the facts the result line leaves out.

    python3 benchmarks/tests/l7_facts.py --workload <cell> --seed <n>
                        --seconds <s> [--trace 1] [--out chiprun_out/x]

Runs the cell as ``run.py`` does (same harness, same result line) and
prints beside it, as one ``[facts]`` JSON line: the share of the window's
verdicted frames whose port has a rule set by the plain reference
(``World.reasons()``), which is what ``l7.checked_share`` has to read, and
the share the reference refuses under 180; the program's ``verdict_rows``
at both ends of the window; the dictionary wire's totals and geometry
(``JITDatapath.l7_wire_stats``) and the upload cache's hits and misses;
the attributes of the ``datapath.pack.l7dict`` spans (rows, distinct,
dict_rows, bytes: mean, least, most) and every program span's count, mean
and median over the window; and, in a traced run, the device seconds
``benchmarks/l7/trace.py`` finds under ``l7.unpack``, ``l7.match``, both
and neither beside the busy union, with the ten longest operations of
each kind, and the same by the scope rule as it stands (conntrack's
scatters that hold the match's merged ``True`` among the match's). With ``--out`` a traced run also leaves there the trace (gzip)
and the program's spans of the traced interval, for ``tests/
cut_trace.py`` and ``tests/keep_programs.py --scopes l7.unpack,l7.match``.
A driver for a builder's chip call, as ``lpm_facts.py`` is; not a part of
the benchmark's command.
"""

import argparse
import collections
import glob
import gzip
import json
import os
import shutil
import statistics
import sys
import time

T_PROC0 = time.monotonic()
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DICT_SPAN = "datapath.pack.l7dict"


def reference_shares(world, tr, run) -> dict:
    """Of the accepted frames verdicted inside the window: the share whose
    port has a rule set, and the share the reference refuses under 180."""
    import numpy as np
    from benchmarks import reference as ref
    flow_of = tr.sched[run.accepted_idx]
    inside = (run.verdict_t >= run.w0) & (run.verdict_t < run.w1)
    has_set = world.reasons(tr.flows) == ref.REASON_POLICY_L7
    frames = flow_of[inside]
    return {"checked_share_ref": float(np.mean(has_set[frames])),
            "refused_share_ref": float(np.mean(
                has_set[frames] & ~tr.want_flow[frames])),
            "frames": int(frames.size)}


def span_attrs(eng, w0: float, w1: float) -> dict:
    spans = [s for s in eng.tracer.spans(limit=1 << 18, name=DICT_SPAN)
             if w0 <= s["start_mono"] < w1 and "attrs" in s]
    out = {"spans": len(spans)}
    for key in ("rows", "distinct", "dict_rows", "bytes"):
        v = [s["attrs"][key] for s in spans]
        if v:
            out[key] = {"mean": sum(v) / len(v), "min": min(v),
                        "max": max(v)}
    return out


def spans_by_name(spans, w0: float, w1: float) -> dict:
    by = collections.defaultdict(list)
    for name, t0, dur in spans:
        if w0 <= t0 < w1:
            by[name].append(dur * 1e3)
    return {name: {"n": len(v), "mean_ms": sum(v) / len(v),
                   "p50_ms": statistics.median(v)}
            for name, v in sorted(by.items())}


def ops_by_kind(path: str, as_it_stands: bool, top: int = 10) -> dict:
    """The traced interval's device seconds by scope, and the longest
    operations under each: by ``l7/trace.py``'s reading (a merged
    constant's name names no scope), or by ``lpm/trace.py``'s rule as it
    stands."""
    from benchmarks.l7 import trace as L7
    from benchmarks.lpm import trace as T
    from benchmarks.reduce import xplane
    marks = xplane.read_planes(path)["marks"]
    w0, w1 = marks[xplane.MARK_START][0], marks[xplane.MARK_END][0]
    tr = T.read_trace(path)
    if not as_it_stands:
        tr["programs"] = {pid: L7.without_merged_constants(proto)
                          for pid, proto in tr["programs"].items()}
    of = T.scopes_of_events(tr, L7.SCOPES) or {}
    out = collections.defaultdict(lambda: collections.defaultdict(float))
    for plane, events in tr["chips"].items():
        meta, found_of = tr["metadata"][plane], of.get(plane, {})
        for ident, start, dur in events:
            cut = min(start + dur, w1) - max(start, w0)
            if cut > 0:
                kind = "+".join(sorted(found_of.get(ident, ()))) or "unnamed"
                out[kind][xplane.short_op(meta[ident][0])] += cut / 1e9
    return {kind: {"seconds": sum(ops.values()),
                   "top": sorted(ops.items(), key=lambda kv: -kv[1])[:top]}
            for kind, ops in out.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmarks import harness
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = harness.resolve_cell(manifest, args.workload)
    harness.say("device", **harness.require_device(cell.chips))
    kept = {}
    sound_check = harness.check

    def check(sv, tr, run, *a, **kw):
        dp = sv.eng.datapath
        wire = getattr(dp, "l7_wire_stats", None)
        kept.update(run=run, facts={
            **reference_shares(sv.world, tr, run),
            "verdict_rows": [run.stats0["pipeline"].get("verdict_rows"),
                             run.stats1["pipeline"].get("verdict_rows")],
            "l7_wire": wire() if wire is not None else None,
            "pack_stats": dict(dp.pack_stats),
            "dict_span": span_attrs(sv.eng, run.w0, run.w1),
            "hbm": dp.hbm_ledger()["groups"],
            "ct": sv.eng.ct_stats()})
        return sound_check(sv, tr, run, *a, **kw)
    harness.check = check
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROC0)
    run, facts = kept["run"], kept["facts"]
    facts["spans"] = spans_by_name(run.spans, run.w0, run.w1)
    path = None
    if run.trace is not None:
        path = sorted(glob.glob(os.path.join(
            run.info["trace_dir"], "plugins", "profile", "*",
            "*.xplane.pb")))[-1]
        facts["scoped"] = run.info.get("l7_scoped")
        facts["busy_s"] = run.trace["busy_s"]
        facts["by_kind"] = ops_by_kind(path, as_it_stands=False)
        facts["by_kind_as_the_rule_stands"] = ops_by_kind(
            path, as_it_stands=True, top=14)
    print("[facts] " + json.dumps(facts), flush=True)
    if args.out and path is not None:
        os.makedirs(args.out, exist_ok=True)
        tag = os.path.join(args.out, cell.name)
        with open(path, "rb") as src, gzip.open(
                tag + ".xplane.pb.gz", "wb", compresslevel=6) as dst:
            shutil.copyfileobj(src, dst)
        m0, m1 = run.trace["window_mono_s"]
        with open(tag + ".spans.json", "w") as f:
            json.dump({"window_mono_s": [m0, m1], "spans": [
                s for s in run.spans if m0 - 0.05 <= s[1] < m1 + 0.05]}, f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
