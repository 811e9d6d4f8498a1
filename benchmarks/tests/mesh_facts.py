#!/usr/bin/env python3
"""A traced run of a meshed cell with the facts the result line leaves out.

    python3 benchmarks/tests/mesh_facts.py --workload <cell> --seed <n>
                        --seconds <s> [--trace 1] [--out chiprun_out/x]

Runs the cell as ``run.py`` does (same harness, same result line) and
prints beside it, as one ``[facts]`` JSON line: how the window's batches
left the pipeline (``flush_reasons``, dispatches, rows), how the datapath
packed and read them back (``pack_stats``, the exchange's cumulative
counters) and how many ``datapath.readback`` spans the window holds. With
``--out`` a traced run also leaves there the trace (gzip), the program's
spans of the traced interval, and both planes by event name inside the
interval: the by-hand look PERF.md §5 quotes. A driver for a builder's
chip call, as ``stops.py`` is; not a part of the benchmark's command.
"""

import argparse
import collections
import glob
import gzip
import json
import os
import shutil
import sys
import time

T_PROC0 = time.monotonic()
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def delta(a, b):
    return {k: b[k] - a.get(k, 0) for k in b if b[k] - a.get(k, 0)}


def facts_of(eng, run) -> dict:
    p0, p1 = run.stats0["pipeline"], run.stats1["pipeline"]
    dp = eng.datapath
    rs = getattr(dp, "rss_exchange_stats", lambda: None)()
    return {
        "window": {
            "flush_reasons": delta(p0["flush_reasons"], p1["flush_reasons"]),
            "dispatched_batches": p1["dispatched_batches"]
            - p0["dispatched_batches"],
            "bucket_rows": p1["bucket_rows"] - p0["bucket_rows"],
            "fill_rows": p1["fill_rows"] - p0["fill_rows"],
            "inflight_at_end": p1["inflight"],
            "staged_rows_at_end": p1["staged_rows"],
        },
        # since the process started: set-up's fill and the warm-up included
        "pack_stats": dict(dp.pack_stats),
        "batches_total": int(eng.metrics.batches_total),
        "rss_exchange": rs,
        "pipeline": {k: p1[k] for k in ("n_shards", "mesh_shards",
                                        "rss_mode", "min_bucket")},
    }


def planes_by_name(path: str, top: int = 40) -> str:
    """Both planes by event name inside the marked interval: count and
    summed duration, per device line and per host thread group."""
    from jax.profiler import ProfileData
    from benchmarks.reduce import xplane
    data = ProfileData.from_file(path)
    marks = xplane.read_planes(path)["marks"]
    w0, w1 = marks[xplane.MARK_START][0], marks[xplane.MARK_END][0]
    out = [f"# {path}: interval {(w1 - w0) / 1e9:.3f} s"]
    for plane in data.planes:
        dev = plane.name.startswith(xplane.DEVICE_PLANE_PREFIX)
        if not dev and not plane.name.startswith("/host:CPU"):
            continue
        by = collections.defaultdict(lambda: [0, 0.0])
        for line in plane.lines:
            for e in line.events:
                if not w0 <= e.start_ns < w1:
                    continue
                key = (line.name if dev else "host",
                       xplane.short_op(e.name))
                by[key][0] += 1
                by[key][1] += e.duration_ns / 1e9
        out.append(f"## {plane.name} lines="
                   f"{[line.name for line in plane.lines] if dev else 'host threads'}")
        rows = sorted(by.items(), key=lambda kv: -kv[1][1])
        for (line, name), (n, secs) in rows[:top]:
            out.append(f"{line:16s} {n:7d} {secs:10.6f} s  {name}")
        for (line, name), (n, secs) in rows[top:]:
            if "collective" in name or "permute" in name:
                out.append(f"{line:16s} {n:7d} {secs:10.6f} s  {name}")
    return "\n".join(out) + "\n"


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
        kept.update(run=run, facts=facts_of(sv.eng, run))
        return sound_check(sv, tr, run, *a, **kw)
    harness.check = check
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROC0)
    run, facts = kept["run"], kept["facts"]
    facts["readback_spans_in_window"] = sum(
        1 for n, t0, _d in run.spans
        if n == "datapath.readback" and run.w0 <= t0 < run.w1)
    print("[facts] " + json.dumps(facts), flush=True)
    if args.out and run.trace is not None:
        os.makedirs(args.out, exist_ok=True)
        path = sorted(glob.glob(os.path.join(
            run.info["trace_dir"], "plugins", "profile", "*",
            "*.xplane.pb")))[-1]
        tag = os.path.join(args.out, cell.name)
        with open(path, "rb") as src, gzip.open(
                tag + ".xplane.pb.gz", "wb", compresslevel=6) as dst:
            shutil.copyfileobj(src, dst)
        m0, m1 = run.trace["window_mono_s"]
        with open(tag + ".spans.json", "w") as f:
            json.dump({"window_mono_s": [m0, m1], "spans": [
                s for s in run.spans if m0 - 0.05 <= s[1] < m1 + 0.05]}, f)
        with open(tag + ".planes.txt", "w") as f:
            f.write(planes_by_name(path))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
