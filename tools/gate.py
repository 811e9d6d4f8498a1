#!/usr/bin/env python3
"""The hand-in gate: runs of benchmark cells on two trees, one line a run.

    python3 tools/gate.py --tree parent=<dir> --tree change=<dir> \\
        --out chiprun_out/<dir> [--cache-root <dir>] [--seconds 40] \\
        [--budget-s 3000] SIDE:CELL:SEED:TRACE:CACHE ...

Each run is ``python3 benchmarks/run.py --workload CELL --seed SEED
--seconds S --trace TRACE`` from the root of SIDE's tree, in the order
given, with ``JAX_COMPILATION_CACHE_DIR=<cache root>/<CACHE>`` (the root is
``<out>/cache`` unless given; a chip call keeps it out of what it brings
back): runs that name one CACHE share compiled programs, so a run of the
change after the parent's on the parent's cache shows by its hits and
misses whether its programs are the parent's. A run's whole output is
kept in ``<out>/<nn>_….out``; its row goes to stdout and to
``<out>/gate.jsonl``: ``correct``, ``failed``, the end-to-end metrics,
``compiles.cache``, ``compiles.in_window`` and every compared number as
``value/limit``.

Exit code 1 if a run of the tree named ``change`` gave no result line, was
not ``correct`` or lost a frame; what another tree's runs did is in their
rows and changes no exit code. Runs not started within ``--budget-s`` are
skipped and say so (exit code 1 if one was the change's).

This process never imports JAX: each run is the only holder of the chip.
It reads result lines only (``benchmarks/README.md`` names their keys).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

CHANGE = "change"


def read_result(text: str):
    """The result object of a run's output: its last line that is a JSON
    object with a ``correct`` key, or None."""
    for line in reversed(text.splitlines()):
        if line.startswith("{") and '"correct"' in line:
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


def row_of(run: dict, rc, result) -> dict:
    """One run's row: ``run`` (side, cell, seed, trace, cache) beside what
    its result line says. ``ok`` is the gate's own reading: a result line,
    ``correct``, no frame ``failed``."""
    row = dict(run, rc=rc)
    if result is None:
        return dict(row, ok=False, correct=None, failed=None)
    values = {name: m["value"] for group in ("also", "metrics")
              for name, m in (result.get(group) or {}).items()}
    compiles = result.get("compiles") or {}
    nic = result.get("nic") or {}
    row.update(
        ok=bool(result["correct"]) and result["failed"] == 0,
        correct=result["correct"], failed=result["failed"],
        attempted=result.get("attempted"),
        device=(result.get("device") or {}).get("kind"),
        # the end-to-end metrics carry no layer's prefix
        e2e={k: v for k, v in values.items() if "." not in k},
        layers={k: v for k, v in values.items() if "." in k},
        cache=compiles.get("cache"), in_window=compiles.get("in_window"),
        compiles=compiles.get("total"),
        nic={k: nic.get(k) for k in ("refused_on_time", "refused_in_stop",
                                     "max_gap_ms", "stop_s")},
        numbers={n["name"]: f"{n['value']}/{n['limit']}"
                 for n in result.get("numbers") or []},
        not_ok=[n["name"] for n in result.get("numbers") or []
                if not n.get("ok", True)])
    return row


def verdict(rows) -> int:
    """0 unless a run of the change is not ``ok``."""
    return int(any(r["side"] == CHANGE and not r["ok"] for r in rows))


def parse_run(text: str) -> dict:
    side, cell, seed, trace, cache = text.split(":")
    return {"side": side, "cell": cell, "seed": int(seed),
            "trace": int(trace), "cache_name": cache}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", action="append", required=True,
                    metavar="SIDE=DIR")
    ap.add_argument("--out", required=True)
    ap.add_argument("--cache-root", help="default: <out>/cache")
    ap.add_argument("--seconds", type=int, default=40)
    ap.add_argument("--timeout", type=int, default=420,
                    help="seconds one run may take")
    ap.add_argument("--budget-s", type=float, default=float("inf"),
                    help="start no run later than this after the first")
    ap.add_argument("runs", nargs="+", metavar="SIDE:CELL:SEED:TRACE:CACHE")
    args = ap.parse_args(argv)
    trees = {side: os.path.abspath(path) for side, path in
             (t.split("=", 1) for t in args.tree)}
    runs = [parse_run(r) for r in args.runs]
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    cache_root = os.path.abspath(args.cache_root
                                 or os.path.join(out, "cache"))
    t0, rows = time.monotonic(), []
    with open(os.path.join(out, "gate.jsonl"), "a") as book:
        for n, run in enumerate(runs, 1):
            log = os.path.join(out, "%02d_%s_%s_s%d_t%d.out" % (
                n, run["side"], run["cell"], run["seed"], run["trace"]))
            if time.monotonic() - t0 > args.budget_s:
                row = dict(row_of(run, None, None), skipped=True)
            else:
                cache = os.path.join(cache_root, run["cache_name"])
                os.makedirs(cache, exist_ok=True)
                with open(log, "w") as f:
                    try:
                        rc = subprocess.run(
                            [sys.executable, "benchmarks/run.py",
                             "--workload", run["cell"],
                             "--seed", str(run["seed"]),
                             "--seconds", str(args.seconds),
                             "--trace", str(run["trace"])],
                            cwd=trees[run["side"]], stdout=f,
                            stderr=subprocess.STDOUT, timeout=args.timeout,
                            env=dict(os.environ,
                                     JAX_COMPILATION_CACHE_DIR=cache),
                        ).returncode
                    except subprocess.TimeoutExpired:
                        rc = 124
                with open(log, errors="replace") as f:
                    row = row_of(run, rc, read_result(f.read()))
            row["t_s"] = round(time.monotonic() - t0, 1)
            rows.append(row)
            line = json.dumps(row)
            print(line, flush=True)
            book.write(line + "\n")
            book.flush()
    return verdict(rows)


if __name__ == "__main__":
    sys.exit(main())
