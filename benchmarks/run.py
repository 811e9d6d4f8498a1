#!/usr/bin/env python3
"""One process, one cell, once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s>
                              --trace <0|1>

Builds the deployment and its traffic from ``--seed``, starts
``Engine(DaemonConfig(...))`` through the entry points a user calls, opens
the live set, warms up, drives the NIC's side of the rings for
``--seconds``, checks what came back against the plain reference, and
prints one JSON object as the last line of stdout. With ``--trace 0`` the
metrics are the cell's end-to-end metrics; with ``--trace 1`` its per-layer
metrics, from the program's spans and counters and a profiler trace of a
slice of the window.

It exits non-zero, and prints no result line, when JAX's first device is
not a TPU, when the cell's chips are not there, and in a directory that
lacks the program.
"""

import time

T_PROC0 = time.monotonic()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "cilium_tpu")):
        raise SystemExit("benchmarks/run.py: no cilium_tpu/ beside "
                         "benchmarks/: there is no program to measure")
    from benchmarks import harness
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = harness.resolve_cell(manifest, args.workload)
    device = harness.require_device(cell.chips)
    harness.say("device", **device)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROC0)
    print(json.dumps(result), flush=True)
    for n in result["numbers"]:
        print("[compare] " + " ".join(f"{k}={v}" for k, v in n.items()),
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
