#!/usr/bin/env python3
"""A run of an LPM-and-service cell with the facts the result line leaves out.

    python3 benchmarks/tests/lpm_facts.py --workload <cell> --seed <n>
                        --seconds <s> [--trace 1] [--out chiprun_out/x]

Runs the cell as ``run.py`` does (same harness, same result line) and
prints beside it, as one ``[facts]`` JSON line: the share of the window's
verdicted frames whose flow the plain reference (``World.cells()``) puts
in a service's cell, which is what ``lb.translated_share`` has to read;
the program's ``verdict_rows`` at both ends of the window; the gauges the
tries and the LB tables were placed with; and, in a traced run, the device
seconds ``benchmarks/lpm/trace.py`` finds under ``lpm.walk``, ``lb.step``,
both and neither beside the busy union, with the ten longest operations
of each kind (the counters' own scope, ``pre_ct.tally``, told apart there:
what they cost the device) and ``host_cost_us``: what one batch's
``unpack_out`` and ``Metrics.add_batch`` take on this host with the three
counters and without. With ``--out`` a traced run also leaves there the trace
(gzip) and the program's spans of the traced interval, for
``tests/cut_trace.py``. A driver for a builder's chip call, as
``mesh_facts.py`` is; not a part of the benchmark's command.
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


#: the two kernels' scopes (``benchmarks/lpm/trace.py``) and the counters'
#: own (``kernels/classify.py:SCOPE_TALLY``)
SCOPES = ("lpm.walk", "lb.step", "pre_ct.tally")


def host_cost_us(rounds: int = 2000) -> dict:
    """Microseconds of host time a 1,024-row batch's read-back pays for
    the three pre-CT counters: ``unpack_out`` of a slab and ``add_batch``
    with them and without, a mean over ``rounds``."""
    import jax.numpy as jnp
    import numpy as np
    from cilium_tpu.kernels import records
    from cilium_tpu.runtime.metrics import Metrics
    from cilium_tpu.utils import constants as C
    out = {"allow": jnp.zeros((1024,), bool),
           "reason": jnp.zeros((1024,), jnp.int32)}
    without = {"by_reason_dir": jnp.zeros((C.COUNTER_CELLS,), jnp.uint32),
               "insert_fail": jnp.uint32(0), "ct_evicted": jnp.uint32(0)}
    with_ = dict(without, lb_translated=jnp.uint32(0),
                 lb_no_backend=jnp.uint32(0),
                 lpm_rows=jnp.zeros((C.LPM_PLEN_BINS,), jnp.uint32))
    cost = {}
    for name, counters in (("without", without), ("with", with_)):
        words, layout = records.pack_out_jnp(out, counters)
        words, metrics = np.asarray(words), Metrics()
        t0 = time.perf_counter()
        for _ in range(rounds):
            _out, read = records.unpack_out(words, layout)
            metrics.add_batch(read, 1024)
        cost[name] = (time.perf_counter() - t0) / rounds * 1e6
    cost["counters"] = cost["with"] - cost["without"]
    return cost


def service_share(world, tr, run) -> float:
    """Of the accepted frames verdicted inside the window, the share whose
    flow's cell is a service's (the cells after the ipcache's prefixes)."""
    import numpy as np
    flow_of = tr.sched[run.accepted_idx]
    t = run.verdict_t
    inside = (t >= run.w0) & (t < run.w1)
    n_prefix_cells = world.ipcache.addr.size
    to_service = world.cells(tr.flows) >= n_prefix_cells
    return float(np.mean(to_service[flow_of[inside]]))


def ops_by_kind(path: str, top: int = 10) -> dict:
    """The traced interval's device seconds by scope, and the longest
    operations under each."""
    from benchmarks.lpm import trace as T
    from benchmarks.reduce import xplane
    marks = xplane.read_planes(path)["marks"]
    w0, w1 = marks[xplane.MARK_START][0], marks[xplane.MARK_END][0]
    tr = T.read_trace(path)
    of = T.scopes_of_events(tr, SCOPES) or {}
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
        gauges = dict(sv.eng.metrics.gauges)
        kept.update(run=run, facts={
            "service_share_ref": service_share(sv.world, tr, run),
            "verdict_rows": [run.stats0["pipeline"].get("verdict_rows"),
                             run.stats1["pipeline"].get("verdict_rows")],
            "placed": {k: v for k, v in gauges.items()
                       if k.startswith(("lpm_", "lb_"))},
            "hbm": sv.eng.datapath.hbm_ledger()["groups"],
            "ct": sv.eng.ct_stats()})
        return sound_check(sv, tr, run, *a, **kw)
    harness.check = check
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROC0)
    run, facts = kept["run"], kept["facts"]
    path = None
    if run.trace is not None:
        path = sorted(glob.glob(os.path.join(
            run.info["trace_dir"], "plugins", "profile", "*",
            "*.xplane.pb")))[-1]
        facts["scoped"] = run.info.get("lpm_scoped")
        facts["busy_s"] = run.trace["busy_s"]
        facts["by_kind"] = ops_by_kind(path)
    facts["host_cost_us"] = host_cost_us()
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
