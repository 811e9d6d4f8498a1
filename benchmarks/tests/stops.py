#!/usr/bin/env python3
"""Stops made on purpose, to show whose loss `failed` counts (PERF.md §2).

    python3 benchmarks/tests/stops.py server  --workload <cell> --seed <n>
                        --seconds <s> [--at 8] [--for 2]
    python3 benchmarks/tests/stops.py process  (the same arguments)

``server`` holds the server alone: the feeder's thread sleeps inside one
verdict apply, ``--at`` seconds into the window, while the generator keeps
its schedule. The ring fills, on-time frames are refused, and they count as
``failed``. ``process`` stops the whole process from outside (SIGSTOP, then
SIGCONT after ``--for`` seconds): the generator comes to the frames late,
and what the ring cannot hold of them is ``refused_in_stop``, not
``failed``. Both need the chip, as ``run.py`` does; this is a driver for a
builder's chip call and for the tests beside it, not a part of the
benchmark's command.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

T_PROC0 = time.monotonic()
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

def hold_server(after_s: float, for_s: float):
    """A ``break_path`` for ``harness.run_cell``: the first verdict apply
    that comes ``after_s`` after the ring phase is armed sleeps ``for_s``
    on the feeder's thread first. No verdict is changed or lost."""
    def break_path(eng, shim):
        sound, t_arm, held = shim.apply_verdicts, time.monotonic(), []

        def apply(allow):
            if not held and time.monotonic() - t_arm >= after_s:
                held.append(time.monotonic())
                time.sleep(for_s)
            sound(allow)
        shim.apply_verdicts = apply
    return break_path


def window_offset_s(cell) -> float:
    """Seconds from `break_path` (and the `rings_at_s` line) to the
    window's start."""
    from benchmarks import harness
    return harness.START_DELAY_S + float(cell.traffic["warmup_s"])


def stop_process(args, cell) -> int:
    """``run.py`` as a child (this process never touches JAX, so the chip
    is the child's), stopped and continued by signal."""
    cmd = [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
           "--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", "0"]
    child = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                             cwd=REPO)
    stopped = {}

    def stop_at(t: float) -> None:
        time.sleep(max(0.0, t - time.monotonic()))
        if child.poll() is None:
            t0 = time.monotonic()
            child.send_signal(signal.SIGSTOP)
            time.sleep(args.hold)
            child.send_signal(signal.SIGCONT)
            stopped.update(at_s=t0 - t, for_s=time.monotonic() - t0)

    stopper, last = None, ""
    try:
        for line in child.stdout:
            line = line.rstrip("\n")
            if line.startswith("{"):
                last = line
            else:
                print(line, flush=True)
            if stopper is None and "rings_at_s=" in line:
                stopper = threading.Thread(target=stop_at, args=(
                    time.monotonic() + window_offset_s(cell) + args.at,))
                stopper.start()
        rc = child.wait()
    finally:
        if child.poll() is None:
            child.send_signal(signal.SIGCONT)
            child.kill()
            child.wait()
        if stopper is not None:
            stopper.join()
    if rc != 0 or not last:
        return rc or 1
    result = json.loads(last)
    result["stop"] = dict(kind="process", **stopped)
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("kind", choices=("server", "process"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--at", type=float, default=8.0,
                    help="seconds into the window")
    ap.add_argument("--for", dest="hold", type=float, default=2.0)
    args = ap.parse_args(argv)
    from benchmarks import harness
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = harness.resolve_cell(manifest, args.workload)
    if args.kind == "process":
        return stop_process(args, cell)
    harness.say("device", **harness.require_device(cell.chips))
    result = harness.run_cell(
        cell, args.seed, args.seconds, False, T_PROC0,
        break_path=hold_server(window_offset_s(cell) + args.at, args.hold))
    result["stop"] = {"kind": "server", "at_s": args.at, "for_s": args.hold}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
