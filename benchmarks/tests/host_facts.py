#!/usr/bin/env python3
"""A traced run of a cell with what its spans say of the host's two
threads (PR 39).

    python3 benchmarks/tests/host_facts.py --workload <cell> --seed <n>
                        --seconds <s> [--out chiprun_out/x]
    python3 benchmarks/tests/host_facts.py --clock

Runs the cell as ``run.py --trace 1`` does (same harness, same result
line) and prints beside it, as one ``[facts]`` JSON line:

- ``spans``: every span name of the window with its thread, kind and
  parent, its count, and per span the mean wall microseconds and the self
  time (wall less the spans that name it as their parent);
- ``threads``: for each thread, per dispatched batch: the working time
  (the thread's outermost ``work`` spans less the ``wait`` spans inside
  them) and, for the feeder and the worker, the CPU time by the thread's
  own clock over the window, which counts all the thread burns, between
  its spans and in its waits too;
- ``cycle``: the worker's cycle (window seconds per ``pipeline.dispatch``)
  beside the sum of its three outermost spans, and ``pipeline.finalize``
  beside the sum of its three parts;
- ``idle_gaps``: the device's idle time in the traced interval by the span
  open during it: as the reducer names it (every span, whatever its
  thread) and by each thread's spans alone (the span's ``thread``), each
  through ``reduce/xplane.py`` as it stands;
- ``tracer``: the ring's occupancy and what it dropped.

``--clock`` (alone, or before a cell) prints as a ``[clock]`` line what the
per-thread CPU clock reads on this host for work whose CPU time is known
(``clock_facts``).

With ``--out`` the traced interval's spans, fields and all, are left there
as ``<cell>.host_spans.json``. A driver for a builder's chip call, as
``l7_facts.py`` is; not a part of the benchmark's command.
"""

import argparse
import collections
import glob
import json
import os
import sys
import time

T_PROC0 = time.monotonic()
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def by_name(spans) -> dict:
    """``Tracer.spans()`` dicts of one window → the docstring's table."""
    groups = collections.defaultdict(list)
    child_wall = collections.Counter()
    for s in spans:
        groups[s["name"]].append(s)
        if s.get("parent"):
            child_wall[(s["thread"], s["parent"])] += s["duration_ms"]
    out = {}
    for name, g in sorted(groups.items()):
        n = len(g)
        wall = sum(s["duration_ms"] for s in g) * 1e3 / n
        kids = sum(child_wall[(t, name)]
                   for t in {s["thread"] for s in g}) * 1e3 / n
        out[name] = {"thread": g[-1]["thread"], "kind": g[-1]["kind"],
                     "parent": collections.Counter(
                         s.get("parent") for s in g).most_common(1)[0][0],
                     "n": n, "wall_us": wall, "self_wall_us": wall - kids}
    return out


def threads(run, spans) -> dict:
    """For each thread that recorded spans in the window, microseconds a
    dispatched batch: its working time (its outermost ``work`` spans less
    the ``wait`` spans opened inside a work span) and, for the feeder and
    the worker, the CPU time by the thread's own clock over the window
    (``stats()["thread_cpu_s"]``: all the thread burnt, between its spans
    and in its waits too, so the two are not one extent and their
    difference is no wait for the interpreter lock)."""
    from benchmarks.host.spans import thread_cpu_s
    kind = {s["name"]: s["kind"] for s in spans}
    batches = sum(s["name"] == "pipeline.dispatch" for s in spans)
    if not batches:
        return {}
    working = collections.Counter()
    for s in spans:
        if s["kind"] == "work" and s.get("parent") is None:
            working[s["thread"]] += s["duration_ms"] * 1e3
        elif s["kind"] == "wait" and kind.get(s.get("parent")) == "work":
            working[s["thread"]] -= s["duration_ms"] * 1e3
    out = {}
    for thread, us in sorted(working.items()):
        row = out[thread] = {"working_us": us / batches}
        # the threads are named where they are started: shim/feeder.py
        # ("<name>-harvest"), pipeline/scheduler.py ("<name>-worker")
        who = "feeder" if thread.endswith("-harvest") else \
            "pipeline" if "-worker" in thread else None
        cpu = who and thread_cpu_s(run, who)
        if cpu is not None:
            row["thread_cpu_us"] = cpu / batches * 1e6
    return out


def cycle(table: dict, window_s: float) -> dict:
    def wall(*names):
        return sum(table[n]["wall_us"] for n in names if n in table)
    if "pipeline.dispatch" not in table:
        return {}
    return {
        "cycle_us": window_s / table["pipeline.dispatch"]["n"] * 1e6,
        "dispatch+finalize+settle_us": wall(
            "pipeline.dispatch", "pipeline.finalize", "pipeline.settle"),
        "finalize_us": wall("pipeline.finalize"),
        "compute+unpack+account_us": wall(
            "datapath.compute", "datapath.unpack", "engine.account")}


def gaps_by_thread(path: str, spans) -> dict:
    """The traced interval's idle gaps named by every span (``all``) and
    by each thread's spans alone."""
    from benchmarks.reduce import xplane
    planes = xplane.read_planes(path)
    out = {}
    for who in ["all"] + sorted({s["thread"] for s in spans}):
        part = [(s["name"], s["start_mono"], s["duration_ms"] / 1e3)
                for s in spans if who in ("all", s["thread"])]
        red = xplane.reduce_planes(planes, part, top=16)
        out[who] = red["idle_gaps"] if red else None
    return out


def clock_facts(chunks: int = 1000) -> dict:
    """What this host's per-thread CPU clock reads for work whose CPU time
    is known, whole-thread (``thread_cpu_s``, read from this thread, as
    ``stats()`` reads it) and summed over short pieces
    (``time.thread_time`` at each piece's two ends, as a span would read
    it):

    - ``spin`` / ``sleep``: one thread, 2 s of either: 2 and 0 are true;
    - ``two_spinners``: two threads spin for 2 s: the interpreter lock
      gives them 2 s between them;
    - ``pieces_alone``: ``chunks`` pieces of a fixed Python loop (about
      1 ms when run back to back) that lets the lock go four times (a
      numpy copy, which sleeps nowhere), 1 ms of sleep between pieces:
      nobody else wants the lock, so on a host that runs a woken thread at
      once the pieces' wall time is their CPU time, and
      (wall − CPU) / wall of the pieces is 0;
    - ``pieces_contended``: the same pieces beside a thread that takes the
      lock in 1 ms bursts: ``lock_share_read`` = (wall − CPU read) / wall
      stands beside ``lock_share_true`` = (wall − wall alone) / wall.

    On the chip's machine (gVisor, PR 39) ``pieces_alone`` reads 0.39 and
    0.40 where 0 is true: which is why no span reads a CPU clock and the
    benchmark has no share of the lock's wait."""
    import threading
    from cilium_tpu.observe.trace import thread_cpu_s

    def spin(s):
        end = time.monotonic() + s
        while time.monotonic() < end:
            pass

    import numpy as np
    src, dst = np.arange(4096), np.empty(4096, np.int64)

    def piece(k):
        for _ in range(4):
            for _ in range(k):
                pass
            np.copyto(dst, src)         # lets the lock go, sleeps nowhere

    # a piece of about 1 ms alone
    k = 1000
    t0 = time.monotonic()
    for _ in range(50):
        piece(k)
    k = max(1, int(k * 1e-3 / ((time.monotonic() - t0) / 50)))

    def pieces(res):
        wall = cpu = 0.0
        for _ in range(chunks):
            c0, w0 = time.thread_time(), time.monotonic()
            piece(k)
            wall += time.monotonic() - w0
            cpu += time.thread_time() - c0
            time.sleep(1e-3)
        res.update(pieces_wall_s=wall, pieces_cpu_s=cpu)

    def bursts(stop):
        while not stop.is_set():
            for _ in range(4 * k):      # about 1 ms of CPU, lock held
                pass
            time.sleep(1e-3)

    def case(*bodies):
        go, release = threading.Event(), threading.Event()
        rows = []

        def run(body, row, done):
            go.wait()
            c0 = time.thread_time()
            body(row)
            row["inside_cpu_s"] = time.thread_time() - c0
            done.set()
            release.wait()
        for body in bodies:
            row, done = {}, threading.Event()
            th = threading.Thread(target=run, args=(body, row, done),
                                  daemon=True)
            th.start()
            rows.append((th, row, done))
        time.sleep(0.05)
        before = [thread_cpu_s(th) for th, _, _ in rows]
        w0 = time.monotonic()
        go.set()
        for _, _, done in rows:
            done.wait()
        wall = time.monotonic() - w0
        for (th, row, _), c0 in zip(rows, before):
            c1 = thread_cpu_s(th)
            row["outside_cpu_s"] = None if c0 is None or c1 is None \
                else c1 - c0
        release.set()
        return {"wall_s": wall, "threads": [row for _, row, _ in rows]}

    out = {"loop_iterations_a_piece": k,
           "spin": case(lambda r: spin(2.0)),
           "sleep": case(lambda r: time.sleep(2.0)),
           "two_spinners": case(lambda r: spin(2.0), lambda r: spin(2.0)),
           "pieces_alone": case(pieces)}
    stop = threading.Event()
    other = threading.Thread(target=bursts, args=(stop,), daemon=True)
    other.start()
    out["pieces_contended"] = case(pieces)
    stop.set()
    other.join()
    alone = out["pieces_alone"]["threads"][0]
    cont = out["pieces_contended"]["threads"][0]
    w = cont["pieces_wall_s"]
    out["alone_share_read"] = 1 - alone["pieces_cpu_s"] \
        / alone["pieces_wall_s"]
    out["lock_share_read"] = (w - cont["pieces_cpu_s"]) / w
    out["lock_share_true"] = (w - alone["pieces_wall_s"]) / w
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=None)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--clock", action="store_true",
                    help="print what the per-thread CPU clock reads for "
                         "known work on this host, as a [clock] line")
    args = ap.parse_args(argv)
    if args.clock:
        print("[clock] " + json.dumps(clock_facts()), flush=True)
    if args.workload is None:
        return 0
    from benchmarks import harness
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = harness.resolve_cell(manifest, args.workload)
    harness.say("device", **harness.require_device(cell.chips))
    kept = {}
    sound_check = harness.check

    def check(sv, tr, run, *a, **kw):
        # before the check's own probe submissions add their spans
        kept.update(run=run, tracer=sv.eng.tracer.stats(),
                    spans=sv.eng.tracer.spans(limit=1 << 18))
        return sound_check(sv, tr, run, *a, **kw)
    harness.check = check
    result = harness.run_cell(cell, args.seed, args.seconds, True, T_PROC0)
    run = kept["run"]
    window = [s for s in kept["spans"] if run.w0 <= s["start_mono"] < run.w1]
    table = by_name(window)
    facts = {"window_s": run.w1 - run.w0, "tracer": kept["tracer"],
             "spans": table, "threads": threads(run, window),
             "cycle": cycle(table, run.w1 - run.w0)}
    traced = []
    if run.trace is not None:
        path = sorted(glob.glob(os.path.join(
            run.info["trace_dir"], "plugins", "profile", "*",
            "*.xplane.pb")))[-1]
        m0, m1 = run.trace["window_mono_s"]
        traced = [s for s in kept["spans"]
                  if m0 - 0.1 <= s["start_mono"] < m1 + 0.1]
        facts["idle_gaps"] = gaps_by_thread(path, traced)
    print("[facts] " + json.dumps(facts), flush=True)
    if args.out and traced:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, cell.name + ".host_spans.json"),
                  "w") as f:
            json.dump({"window_mono_s": [m0, m1], "spans": traced}, f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
