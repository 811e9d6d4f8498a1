#!/usr/bin/env python3
"""The knee sweep: one set-up, a ladder of offered rates, a few seconds
each.

    python3 benchmarks/sweep.py --config <name> --rates 40000,60000,...
                                [--seconds 4] [--seed 1] [--traffic steady80]

The knee of a configuration is the highest rate it sustains with zero ring
refusals and no growing backlog. It is found once, on the chip, with this
tool; the result goes into ``knees/<config>.json`` by hand, its table into
PERF.md. Each step is an open loop of exponential arrivals at the step's
rate, the mix of the traffic file, on the one deployment that stays up
(its conntrack table keeps the flows of the earlier steps, as a running
node's would). A step reads:

    refused      frames the ring would not take when they were offered, the
                 sum of the next two (nic/nicgen.cc has the rule)
    refused_in_stop  of those, in a stop of the host: the generator itself
                 came to them late. They say nothing about the rate
    refused_on_time  the others: the generator was on time and the ring was
                 full. "Zero ring refusals" below means these
    backlog_mid  accepted frames still without a verdict, half way through
    backlog_end  the same when injection stops
    p50/p99_ms   due → verdict, over the step's second half
    late_p99_ms  how late the generator ran

A backlog that is larger at the end than half way, or a p99 of the order
of the step's length, says the rate is over the knee even where the ring
(4096 deep) has not refused yet.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def sweep(cell, rates, seconds: float, seed: int, settle_s: float = 1.0):
    from benchmarks import harness as h
    rng = np.random.default_rng(seed)
    counts = [int(r * seconds * 1.05) + 1024 for r in rates]
    rows = []
    with h.serve(cell, traced=False) as sv:
        tr = h.make_traffic(cell, sv.world, rng, sum(counts))
        h.open_live_set(sv, tr, [])
        sv.eng.start_background()
        sv.eng.start_feeder(sv.shim)
        lo = 0
        for rate, n in zip(rates, counts):
            t_start = time.monotonic() + 0.25
            due = t_start + np.cumsum(rng.exponential(1.0 / rate, n))
            t_mid, t_stop = t_start + seconds / 2, t_start + seconds
            log = h.ring_phase(sv, tr, lo, lo + n, due, t_stop, rate=rate)
            lo += n
            inj = log["inject_t"]
            ok = inj >= 0
            vt = h.verdict_times(log)
            d = due[:inj.shape[0]][ok]
            late = inj[ok] - d
            half = (d >= t_mid) & np.isfinite(vt)

            def backlog(t):
                return int((inj[ok] <= t).sum()) - h.verdicts_by(log, t)
            row = {
                "rate": rate, "offered": log["n_offered"],
                "refused": log["n_refused"],
                "refused_in_stop": log["n_refused_in_stop"],
                "refused_on_time": log["n_refused_on_time"],
                "backlog_mid": backlog(t_mid), "backlog_end": backlog(t_stop),
                "p50_ms": float(np.percentile((vt - d)[half], 50) * 1e3)
                if half.any() else None,
                "p99_ms": float(np.percentile((vt - d)[half], 99) * 1e3)
                if half.any() else None,
                "late_p99_ms": float(np.percentile(late, 99) * 1e3)
                if late.size else None,
                "delivered_per_s": (h.verdicts_by(log, t_stop)
                                    - h.verdicts_by(log, t_mid))
                / (t_stop - t_mid),
                "ct_live": sv.eng.ct_stats()["live"],
            }
            rows.append(row)
            h.say("step", **row)
            time.sleep(settle_s)
        ps = sv.eng.pipeline_stats()
        h.say("pipeline", flush_reasons=ps["flush_reasons"],
              fill_ratio_avg=ps["fill_ratio_avg"], restarts=ps["restarts"],
              shed_total=ps["shed_total"])
        device = h.describe_device()
    return {"config": cell.config_name, "traffic": cell.traffic_name,
            "seconds": seconds, "seed": seed, "device": device,
            "steps": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="steady80")
    ap.add_argument("--rates", required=True,
                    help="comma-separated frames/s, in the order to try")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from benchmarks import harness as h
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    # any cell of the configuration gives its files; the rates are ours
    name = next((w["name"] for w in manifest["workloads"]
                 if w["config"] == args.config
                 and w["traffic"] == args.traffic), None)
    if name is None:
        name = next(w["name"] for w in manifest["workloads"]
                    if w["config"] == args.config)
    cell = h.resolve_cell(manifest, name)
    if cell.traffic_name != args.traffic:
        cell.traffic = h.load_json(h.BENCH_DIR, "traffic",
                                   args.traffic + ".json")
        cell.traffic_name = args.traffic
    h.say("device", **h.require_device(cell.chips))
    rates = [float(r) for r in args.rates.split(",")]
    print(json.dumps(sweep(cell, rates, args.seconds, args.seed)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
