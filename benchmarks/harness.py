"""One run of one cell: set-up, the ring phase, the check, the numbers.

Nothing here names a cell, a configuration, a traffic mix or a metric.
Each of those is a file that ``BENCHMARK.json`` names:

    configs/<config>.json     the deployment (+ worlds/<builder>.py)
    traffic/<traffic>.json    the mix and its loop (+ laws/<law>.py)
    knees/<config>.json       that configuration's knee, from sweep.py
    e2e/<metric>.py           reader of one end-to-end metric
    layers/<metric>.py        reader of one per-layer metric

The timed path is the served path and nothing beside it:
``Engine(DaemonConfig(...))`` → ``start_feeder(FlowShim)`` → pipeline →
``JITDatapath`` → verdicts applied on the rings, driven from the NIC's side
of the rings by nicgen. A ``DaemonConfig`` or ``FlowShim`` field that the
configuration file does not name stays at the program's default.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import importlib.util
import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)

#: the traced run profiles this slice of the window (seconds from its
#: start, length): traces are large and tracing slows the host
TRACE_OFFSET_S = 1.0
TRACE_LENGTH_S = 2.0
PROBE_ROWS = 1024
ARRIVALS_SEED = 1
#: the schedule starts this long after the ring phase is armed
START_DELAY_S = 0.25


def say(what: str, **fields) -> None:
    print(f"[{what}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_reader(kind: str, name: str):
    """``<kind>/<name>.py`` → its module (a metric's name may hold dots,
    so the file is loaded by path)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict
    traffic: Dict
    knee: Optional[Dict]            # knees/<config>.json, open loops only
    e2e: List[str]                  # end-to-end metrics this cell reports
    layers: List[str]               # per-layer metrics read in this cell
    units: Dict[str, str]           # the manifest's unit of each metric


def resolve_cell(manifest: Dict, name: str,
                 data_root: str = BENCH_DIR) -> Cell:
    """The cell's files, found by the names in the manifest. ``data_root``
    holds ``traffic/`` and ``knees/`` (the tests bring their own)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(os.path.join(REPO, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json(data_root, "traffic", w["traffic"] + ".json")
    knee_file = os.path.join(data_root, "knees", w["config"] + ".json")
    knee = load_json(knee_file) if os.path.exists(knee_file) else None

    def here(metric: Dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]
    e2e = [m["name"] for m in manifest["end_to_end"] if here(m)]
    moved = set(e2e)
    layers = [m["name"] for m in manifest["per_layer"]
              if here(m) and m["moves"] in moved]
    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    return Cell(name, w["config"], w["traffic"], int(w["chips"]), config,
                traffic, knee, e2e, layers, units)


def require_device(chips: int) -> Dict:
    """The measurement path has no CPU mode: no TPU, or fewer chips than
    the cell asks for, ends the run with no result line."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"benchmarks/run.py: JAX found no TPU (platform {d.platform!r}, "
            f"{d.device_kind}, {len(devs)} device(s)); a rate from anything "
            f"else is not reported")
    if len(devs) < chips:
        raise SystemExit(f"benchmarks/run.py: the cell needs {chips} chips, "
                         f"JAX has {len(devs)}")
    chip_peaks(d.device_kind)
    return describe_device()


def chip_peaks(device_kind: str) -> Dict:
    """The chip's published peaks. A device the table lacks is an error,
    never a default."""
    peaks = load_json(BENCH_DIR, "peaks.json")
    if device_kind not in peaks:
        raise SystemExit(f"benchmarks/peaks.json has no entry for "
                         f"{device_kind!r}: add its published peaks, with "
                         f"their source")
    return peaks[device_kind]


def describe_device() -> Dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


class CompileWatch:
    """Every XLA backend compile (or cache load) of this process, with the
    host's monotonic clock at the moment it ended."""

    def __init__(self):
        self.events: List[tuple] = []       # (t_end_mono, fun_name, secs)
        self.cache: Dict[str, int] = {}

    def install(self) -> "CompileWatch":
        import jax.monitoring as mon

        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.events.append((time.monotonic(),
                                    kw.get("fun_name", "?"), float(secs)))

        def on_event(event, **_kw):
            if event.startswith("/jax/compilation_cache/cache_"):
                key = event.rsplit("/", 1)[1]
                self.cache[key] = self.cache.get(key, 0) + 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        return self

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _f, _s in self.events if t0 <= t <= t1)


def wait_active(eng, revision: int, deadline_s: float = 600.0) -> None:
    """With ``auto_regen`` at its default the engine compiles a repository
    change on its own debounced trigger; wait until it serves it."""
    end = time.monotonic() + deadline_s
    while eng.active.revision < revision:
        if time.monotonic() > end:
            raise RuntimeError(f"revision {revision} not active after "
                               f"{deadline_s:.0f}s: {eng.health()}")
        time.sleep(0.005)


def reason_counts(eng) -> np.ndarray:
    """Verdicts so far by drop reason ([256] int64)."""
    from cilium_tpu.utils import constants as C
    return eng.metrics.by_reason_dir.reshape(
        C.DROP_REASON_BINS, C.N_DIRECTIONS).sum(axis=1).astype(np.int64)


def snapshot_stats(eng) -> Dict:
    hist = eng.metrics.histograms.get("ingest_e2e_latency_seconds")
    return {
        "pipeline": eng.pipeline_stats(),
        "feeder": eng.feeder_stats(),
        "e2e_hist": hist.snapshot() if hist is not None else None,
    }


def submit_flows(eng, world, flows, bucket: int, window: int = 8) -> Dict:
    """``flows`` through ``Engine.submit`` in ``bucket``-row batches (the
    last one padded with invalid rows, so every batch has the one shape),
    at most ``window`` in flight. → the answered columns, concatenated."""
    from benchmarks.frames import columns_of, take
    n = flows["sport"].shape[0]
    ep_slot = eng.active.snapshot.ep_slot_of[world.ep_id]
    keys = ("allow", "reason", "status", "ct_full")
    got = {k: [] for k in keys}
    tickets: List = []

    def settle(ticket, m):
        out = ticket.result(timeout=300)
        for k in keys:
            got[k].append(np.asarray(out[k])[:m])

    for i in range(0, n, bucket):
        m = min(bucket, n - i)
        part = take(flows, slice(i, i + m))
        if m < bucket:
            pad = take(flows, np.zeros(bucket - m, np.int64))
            part = {k: np.concatenate([part[k], pad[k]]) for k in part}
        b = columns_of(part, world.ep_v4, world.ep_v6_words, ep_slot)
        b["valid"][m:] = False
        tickets.append((eng.submit(b), m))
        if len(tickets) > window:
            settle(*tickets.pop(0))
    for t in tickets:
        settle(*t)
    return {k: np.concatenate(v) if v else np.zeros((0,))
            for k, v in got.items()}


def resolve_rate(cell: Cell) -> Optional[float]:
    """The open loop's fixed rate: the traffic file's share of the
    configuration's knee (``knees/<config>.json``, found once by
    ``sweep.py`` on the chip). None for a loop closed on ring space."""
    t = cell.traffic
    if t["loop"] == "saturate":
        return None
    if t["loop"] != "open":
        raise ValueError(f"traffic loop {t['loop']!r}: saturate or open")
    if cell.knee is None:
        raise SystemExit(f"knees/{cell.config_name}.json is missing: run "
                         f"sweep.py for the configuration on the chip")
    return float(t["knee_share"]) * float(cell.knee["knee_frames_per_s"])


def stop_cap_s(cell: Cell, rate: float) -> float:
    """How long after the open loop was last late a stop episode may last
    (nic/nicgen.cc): the time a program that just holds the
    knee it was measured at, ``rate / knee_share``, needs to drain a full
    ring under the offered load. After it the program is behind by its own
    doing, and what the ring refuses is the program's loss again."""
    spare = rate * (1.0 / float(cell.traffic["knee_share"]) - 1.0)
    return ring_frames(cell) / spare


def ring_frames(cell: Cell) -> int:
    rings = cell.config["rings"]
    return min(int(rings["ring_size"]), int(rings["n_frames"]))


def schedule_frames(cell: Cell, rate: Optional[float],
                    seconds: float) -> int:
    span = float(cell.traffic["warmup_s"]) + seconds
    per_s = rate if rate is not None \
        else float(cell.traffic["schedule_frames_per_s"])
    return int(per_s * span * (1.02 if rate is not None else 1.0)) + 1024


@dataclasses.dataclass
class Run:
    """What a run hands its metric readers."""
    cell: Cell
    seed: int
    seconds: float
    traced: bool
    t_proc0: float
    w0: float = 0.0                 # window, monotonic seconds
    w1: float = 0.0
    rate: Optional[float] = None
    nic: Dict = dataclasses.field(default_factory=dict)
    due: Optional[np.ndarray] = None
    verdict_t: Optional[np.ndarray] = None    # per accepted frame
    accepted_idx: Optional[np.ndarray] = None  # schedule index of each
    stats0: Dict = dataclasses.field(default_factory=dict)
    stats1: Dict = dataclasses.field(default_factory=dict)
    spans: List = dataclasses.field(default_factory=list)
    trace: Optional[Dict] = None
    compiles: Optional[CompileWatch] = None
    numbers: List[Dict] = dataclasses.field(default_factory=list)
    info: Dict = dataclasses.field(default_factory=dict)

    def verdicts_by(self, t: float) -> int:
        """Verdicts applied on ring frames by monotonic time ``t``."""
        return verdicts_by(self.nic, t)


def verdicts_by(log: Dict, t: float) -> int:
    i = int(np.searchsorted(log["log_t"], t, side="right")) - 1
    if i < 0:
        return 0
    return int(log["log_drops"][i] + log["log_passes"][i]
               + log["log_txfull"][i]) - log["base_verdicts"]


def verdict_times(log: Dict) -> np.ndarray:
    """[n_accepted] the time the verdict count first reached k+1: verdicts
    are applied in ring order, so that is frame k's verdict."""
    done = log["log_drops"] + log["log_passes"] + log["log_txfull"] \
        - log["base_verdicts"]
    n = log["n_accepted"]
    idx = np.searchsorted(done, np.arange(1, n + 1), side="left")
    t = np.full((n,), np.nan)
    have = idx < done.shape[0]
    t[have] = log["log_t"][idx[have]]
    return t


@dataclasses.dataclass
class Served:
    """The system under test, up and serving, and the NIC's library."""
    cell: Cell
    world: object
    eng: object
    shim: object
    lib: object
    compiles: CompileWatch


@contextlib.contextmanager
def serve(cell: Cell, traced: bool):
    """The deployment through the entry points a user calls: engine,
    endpoint, identities and rule documents, the shim with its mock rings.
    Stops the engine and frees the shim on the way out."""
    from benchmarks.nic import nicgen
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.engine import Engine
    from cilium_tpu.shim.bindings import FlowShim
    from cilium_tpu.utils.compile_cache import enable_compile_cache
    cfg = cell.config
    cache_dir = enable_compile_cache()
    compiles = CompileWatch().install()
    t_libs = time.monotonic()
    nicgen.build_shim()
    lib = nicgen.build()
    t0 = time.monotonic()
    world = importlib.import_module(
        "benchmarks.worlds." + cfg["world"]["builder"]).build(cfg["world"])
    daemon = dict(cfg.get("daemon", {}))
    if traced:
        # the program's own spans, every submission, in the traced run only
        daemon.update(trace_sample_rate=1.0, trace_capacity=1 << 18)
    eng = Engine(DaemonConfig(**daemon))
    shim = None
    try:
        wait_active(eng, world.load(eng))
        shim = FlowShim(**cfg.get("shim", {}))
        world.register(shim)
        shim.mock_rings_init(**cfg["rings"])
        say("setup", built_s=round(t0 - t_libs, 2),
            load_s=round(time.monotonic() - t0, 2), compile_cache=cache_dir)
        yield Served(cell, world, eng, shim, lib, compiles)
    finally:
        eng.stop()
        if shim is not None:
            shim.close()


@dataclasses.dataclass
class Traffic:
    flows: Dict[str, np.ndarray]    # live set first, in rank order
    n_live: int
    sched: np.ndarray               # [n_frames] flow of each frame
    table: np.ndarray               # [n_flows, stride] one frame a flow
    lens: np.ndarray
    want_flow: np.ndarray           # the plain reference's answer per flow
    reason_flow: np.ndarray         # and its reason for a refusal of each


def make_traffic(cell: Cell, world, rng, n_frames: int) -> Traffic:
    from benchmarks import reference as ref
    from benchmarks.frames import frames_of
    t0 = time.monotonic()
    law = importlib.import_module("benchmarks.laws." + cell.traffic["law"])
    mix = law.generate(cell.traffic["law_params"], world, rng,
                       int(cell.config["live_flows"]), n_frames)
    table, lens = frames_of(mix["flows"], world.ep_v4, world.ep_v6_words)
    want = ref.expected_allow(world, mix["flows"])
    say("setup", traffic_s=round(time.monotonic() - t0, 2),
        flows=int(want.shape[0]), schedule=n_frames)
    return Traffic(mix["flows"], mix["n_live"], mix["sched_flow"], table,
                   lens, want, ref.refusal_reasons(world, mix["flows"]))


def open_live_set(sv: Served, tr: Traffic, numbers: List[Dict]
                  ) -> np.ndarray:
    """The live set through ``Engine.submit`` in full buckets, heaviest
    flows first (they meet an empty table); then the shapes the window
    uses and no others: the harvest-sized bucket the feeder's batches
    dispatch at (the fill warmed the full one) and the GC tick's program,
    which compiles on its first call. → which live flows the table refused
    a slot."""
    from benchmarks import reference as ref
    from benchmarks.frames import take
    eng, t0 = sv.eng, time.monotonic()
    evicted0 = eng.metrics.ct_evicted
    fill = submit_flows(eng, sv.world, take(tr.flows, slice(0, tr.n_live)),
                        eng.config.batch_size)
    refused = fill["ct_full"].astype(bool)
    # a flow whose probe window is full takes the slot of a live entry that
    # is not established TCP (the program's insert-when-full contract), so
    # a crowded table with UDP flows in it holds fewer than were admitted
    evicted = eng.metrics.ct_evicted - evicted0
    held = eng.ct_stats()["live"]
    say("setup", fill_s=round(time.monotonic() - t0, 2), live=tr.n_live,
        refused=int(refused.sum()), evicted=evicted, table_holds=held)
    numbers.append(ref.compare(
        "fill_table_gap",
        abs(held - (tr.n_live - int(refused.sum()) - evicted)), 0))
    numbers.append(ref.compare(
        "fill_denied",
        int((~fill["allow"].astype(bool) & ~refused).sum()), 0))
    t0 = time.monotonic()
    submit_flows(eng, sv.world, take(tr.flows, slice(0, sv.shim.batch_size)),
                 sv.shim.batch_size)
    t1 = time.monotonic()
    eng.sweep_step()
    say("setup", warm_harvest_bucket_s=round(t1 - t0, 2),
        warm_gc_s=round(time.monotonic() - t1, 2))
    return refused


def ring_phase(sv: Served, tr: Traffic, lo: int, hi: int,
               due: Optional[np.ndarray], t_stop: float,
               at: Sequence = (), rate: Optional[float] = None) -> Dict:
    """nicgen sends schedule entries [lo, hi) until ``t_stop``, then waits
    for the last verdicts. ``at``: (monotonic time, callable) pairs run
    from this thread meanwhile, in order. ``rate``: the open loop's, which
    sets how long a stop episode may last. → nicgen's log."""
    from benchmarks.nic import nicgen
    stops = {} if due is None else {
        "ring_frames": ring_frames(sv.cell),
        "stop_cap_s": stop_cap_s(sv.cell, rate)}
    nic = nicgen.Nic(sv.lib, sv.shim, tr.table, tr.lens, tr.sched[lo:hi],
                     due, t_stop_s=t_stop, **stops).start()
    for t, fn in at:
        _sleep_until(t)
        fn()
    log = nic.join(timeout=max(0.0, t_stop - time.monotonic()) + 120.0)
    sv.eng.drain(timeout=60)
    say("ring", offered=log["n_offered"], accepted=log["n_accepted"],
        refused=log["n_refused"], **refusal_split(log),
        stalls_over_1ms=log["n_stalls"],
        tx_drained=log["n_tx_drained"],
        samples=log["n_samples"], gaps_over_50us=log["n_gaps_over_50us"],
        max_gap_ms=round(log["max_gap_s"] * 1e3, 3),
        log_entries=log["log_t"].shape[0])
    return log


def refusal_split(log: Dict) -> Dict:
    """Whose loss the ring's refusals were, as nicgen labelled them."""
    return {"refused_in_stop": log["n_refused_in_stop"],
            "refused_on_time": log["n_refused_on_time"],
            "refused_aftermath": log["n_refused_aftermath"],
            "stop_episodes": log["n_stop_episodes"],
            "stop_s": log["stop_s"]}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_proc0: float, *, break_path: Optional[Callable] = None
             ) -> Dict:
    """Set up, drive the rings for ``warmup_s + seconds``, check, reduce.
    → the result line's object. ``break_path(eng, shim)`` is for the test
    that breaks the timed path underneath and must see ``correct`` false."""
    run = Run(cell, seed, seconds, traced, t_proc0)
    rng = np.random.default_rng(seed)
    with serve(cell, traced) as sv:
        eng, shim = sv.eng, sv.shim
        run.compiles = sv.compiles
        rate = run.rate = resolve_rate(cell)
        n_frames = schedule_frames(cell, rate, seconds)
        say("setup", serving_at_s=round(time.monotonic() - t_proc0, 2))
        tr = make_traffic(cell, sv.world, rng, n_frames)
        fill_refused = open_live_set(sv, tr, run.numbers)
        # the controllers a running agent has, then the feeder
        eng.start_background()
        eng.start_feeder(shim)
        say("setup", rings_at_s=round(time.monotonic() - t_proc0, 2))
        if break_path is not None:
            break_path(eng, shim)

        # -- the ring phase --------------------------------------------------
        before = {"shim": shim.stats(), "reasons": reason_counts(eng),
                  "refused": eng.metrics.insert_fail}
        warmup = float(cell.traffic["warmup_s"])
        t_start = time.monotonic() + START_DELAY_S
        run.w0, run.w1 = t_start + warmup, t_start + warmup + seconds
        if rate is not None:
            # every seed gets the same inter-arrival gaps, in another order
            gaps = np.random.default_rng(ARRIVALS_SEED).exponential(
                1.0 / rate, n_frames)
            run.due = t_start + np.cumsum(rng.permutation(gaps))
        at = [(run.w0, lambda: run.stats0.update(snapshot_stats(eng)))]
        if traced:
            at.append((run.w0, lambda: run.info.update(
                trace_dir=_profile(run))))
        at.append((run.w1, lambda: run.stats1.update(
            snapshot_stats(eng))))
        log = run.nic = ring_phase(sv, tr, 0, n_frames, run.due, run.w1, at,
                                   rate)
        run.info["setup_s"] = run.w0 - t_proc0
        if cell.traffic["loop"] == "saturate" \
                and log["n_offered"] >= n_frames:
            raise RuntimeError(
                f"the schedule's {n_frames} frames ran out before the "
                f"window closed: raise schedule_frames_per_s in "
                f"traffic/{cell.traffic_name}.json")

        # -- the check, outside the timed region ------------------------------
        t0 = time.monotonic()
        run.accepted_idx = np.nonzero(log["inject_t"] >= 0)[0]
        run.verdict_t = verdict_times(log)
        correct = check(sv, tr, run, before, fill_refused, rng)
        say("check", seconds=round(time.monotonic() - t0, 2),
            correct=correct)

        # -- the numbers -----------------------------------------------------
        if traced:
            run.spans = [(s["name"], s["start_mono"],
                          s["duration_ms"] / 1e3)
                         for s in eng.tracer.spans(limit=1 << 18)]
            run.trace = _reduce_trace(run)
        metrics = read_metrics(run, traced)
        device = describe_device()
        device["memory_peak_bytes"] = memory_peak_bytes()
        if device["platform"] == "tpu":
            device["memory_peak_share"] = device["memory_peak_bytes"] \
                / chip_peaks(device["kind"])["hbm_bytes"]
        unverdicted = next(n["value"] for n in run.numbers
                           if n["name"] == "unverdicted")
        result = {
            "correct": bool(correct),
            "attempted": int(log["n_offered"]),
            # the program's loss: frames the ring refused with the
            # generator on time, and accepted frames left without a verdict.
            # What the ring refused in a stop of the host is under `nic`
            "failed": int(log["n_refused_on_time"] + unverdicted),
            "metrics": metrics,
            "device": device,
        }
        if traced and run.trace is not None:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            result["breakdown"] = {
                "device_ops": run.trace["device_ops"],
                "idle_gaps": run.trace["idle_gaps"]}
        # beyond the contract's keys: what the builder reads
        result["compiles"] = {
            "total": len(sv.compiles.events),
            "seconds": sum(s for _t, _f, s in sv.compiles.events),
            "in_window": sv.compiles.between(run.w0, run.w1),
            "cache": dict(sv.compiles.cache)}
        # the other kind's readers, where they find something to read: the
        # traced run's end-to-end numbers show what tracing costs
        result["also"] = read_metrics(run, not traced)
        if not traced:
            result["window_prefixes"] = window_prefixes(run)
        if "refused_for" in run.info:
            result["refused_for"] = run.info["refused_for"]
        result["control"] = run.info.get("control")
        result["latency_samples"] = run.info.get("latency_samples")
        result["nic"] = {
            "rate": rate, **refusal_split(log),
            "stalls_over_1ms": log["n_stalls"],
            "gaps_over_50us": log["n_gaps_over_50us"],
            "max_gap_ms": log["max_gap_s"] * 1e3,
            "samples": log["n_samples"]}
        # last, so that the end of a line that was cut still holds them
        result["numbers"] = run.numbers
        return result


def read_metrics(run: Run, per_layer: bool) -> Dict[str, Dict]:
    """The cell's per-layer or end-to-end metrics, each by its own reader;
    a reader that finds nothing to read leaves its metric out."""
    kind, names = ("layers", run.cell.layers) if per_layer \
        else ("e2e", run.cell.e2e)
    out: Dict[str, Dict] = {}
    for name in names:
        value = load_reader(kind, name).read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": run.cell.units[name]}
    return out


def window_prefixes(run: Run, shares: Sequence[float] = (0.25, 0.5)) -> Dict:
    """The end-to-end readers over the first quarter and half of the
    window: how a metric's run-to-run spread falls with the window's
    length can then be read from one set of runs."""
    out = {}
    for share in shares:
        part = dataclasses.replace(run, w1=run.w0 + share * run.seconds,
                                   info=dict(run.info))
        out[str(share)] = {k: v["value"]
                           for k, v in read_metrics(part, False).items()}
    return out


def check(sv: Served, tr: Traffic, run: Run, before: Dict,
          fill_refused: np.ndarray, rng) -> bool:
    """The comparisons of benchmarks/reference.py over this run, each
    number printed beside its limit, and the control beside them."""
    from benchmarks import reference as ref
    from benchmarks.frames import take
    eng, shim, log = sv.eng, sv.shim, run.nic
    acc, base = run.accepted_idx, before["shim"]
    flow_of = tr.sched[acc]
    ct_full = eng.metrics.insert_fail - before["refused"]
    reasons = reason_counts(eng) - before["reasons"]
    end = shim.stats()
    n_acc = log["n_accepted"]
    done_end = sum(end[k] - base[k] for k in
                   ("verdict_passes", "verdict_drops", "tx_full_drops"))
    passed_end = end["verdict_passes"] + end["tx_full_drops"] \
        - base["verdict_passes"] - base["tx_full_drops"]
    allow_acc = tr.want_flow[flow_of]
    admitted = int(allow_acc.sum())
    pc = ref.prefix_check(allow_acc, log, base, ct_full)
    ps, fs = eng.pipeline_stats(), eng.feeder_stats()
    N, C = run.numbers, ref.compare
    N.append(C("unverdicted", n_acc - done_end, 0, "eq"))
    N.append(C("log_overflow", int(log["log_overflow"]), 0))
    N.append(C("stable_points", pc["stable_points"], 16, "min"))
    N.append(C("prefix_excess", pc["prefix_excess"], 0))
    N.append(C("passed_gap", abs(admitted - ct_full - passed_end), 0))
    N.append(C("reason_ok_gap",
               abs(int(reasons[ref.REASON_OK]) - passed_end), 0))
    # a refusal's reason is the world's to state: REASON_POLICY's total
    # always, every other reason's where the world states it of some flow
    why_refused = tr.reason_flow[flow_of][~allow_acc]
    refused_for = {}
    for reason, name in ref.REFUSAL_GAPS.items():
        if reason == ref.REASON_POLICY or (tr.reason_flow == reason).any():
            refused_for[name] = int((why_refused == reason).sum())
            N.append(C(name, abs(int(reasons[reason]) - refused_for[name]),
                       0))
    if len(refused_for) > 1:
        # the frames each of those gaps is taken over
        run.info["refused_for"] = refused_for
    N.append(C("reason_ct_full_gap",
               abs(int(reasons[ref.REASON_CT_FULL]) - ct_full), 0))
    N.append(C("ct_full_share", ct_full / max(1, admitted),
               ref.CT_FULL_SHARE_LIMIT))
    N.append(C("pipeline_faults", sum(int(ps[k]) for k in (
        "restarts", "dispatch_errors", "dispatch_faults", "shed_total",
        "admission_drops", "unavailable_total")), 0))
    N.append(C("feeder_faults", sum(int(fs[k]) for k in (
        "rejected_batches", "errors", "prio_shed_rows",
        "harvest_faults")), 0))

    # the probe: a seeded sample of the ring's own flows, row for row
    sent_flow = np.zeros(tr.want_flow.shape, bool)
    sent_flow[flow_of] = True
    pick = np.unique(flow_of[rng.integers(0, acc.size, PROBE_ROWS)]) \
        if acc.size else np.zeros((0,), np.int64)
    refused_of = np.zeros(tr.want_flow.shape, bool)
    refused_of[:tr.n_live] = fill_refused
    probe_flows = take(tr.flows, pick)
    out = submit_flows(eng, sv.world, probe_flows, shim.batch_size)
    probe = (sv.world, probe_flows, pick < tr.n_live, sent_flow[pick],
             refused_of[pick], out)
    pr = ref.probe_check(*probe)
    few = max(4, pr["probe_rows"] // 50)
    N.append(C("probe_rows", pr["probe_rows"], min(64, acc.size), "min"))
    N.append(C("probe_mismatched", pr["probe_mismatched"], 0))
    N.append(C("probe_refused_now", pr["probe_refused_now"], few))
    N.append(C("probe_reopened", pr["probe_reopened"], min(ct_full, few)))
    for n in N:
        say("compare", **n)

    # the control: the same comparisons against a table with one exercised
    # rule taken out. It has to fail.
    per_flow = np.bincount(flow_of, minlength=tr.want_flow.shape[0])
    wrong, dropped = ref.wrong_table(
        sv.world, tr.flows, per_flow.astype(np.float64),
        np.random.default_rng(run.seed + 1))
    if wrong is None:
        run.info["control"] = {"caught": None,
                               "note": "no exercised single-cover rule"}
    else:
        w_acc = ref.expected_allow(sv.world, tr.flows, wrong)[flow_of]
        wpc = ref.prefix_check(w_acc, log, base, ct_full)
        w_gap = abs(int(w_acc.sum()) - ct_full - passed_end)
        run.info["control"] = {
            "cell_dropped": dropped,
            "frames_on_it": admitted - int(w_acc.sum()),
            "prefix_excess": wpc["prefix_excess"], "passed_gap": w_gap,
            "probe_mismatched": ref.probe_check(
                *probe, wrong)["probe_mismatched"],
            "caught": bool(wpc["prefix_excess"] > 0 or w_gap > 0)}
    say("control", **run.info["control"])
    return ref.verdict(N)


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))


def _profile(run: Run) -> str:
    """Profile [w0 + offset, + length] of the window with the Python
    tracer off (it would record every call of the program's threads)."""
    import jax
    from benchmarks.reduce.xplane import MARK_END, MARK_START
    trace_dir = os.path.join(BENCH_DIR, ".build", "trace", run.cell.name)
    for old in glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*")):
        os.remove(old)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    length = min(TRACE_LENGTH_S,
                 max(0.05, run.seconds - 2 * TRACE_OFFSET_S))
    _sleep_until(run.w0 + min(TRACE_OFFSET_S, run.seconds / 4))
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(
                MARK_START, t_mono_ns=int(time.monotonic() * 1e9)):
            pass
        time.sleep(length)
        with jax.profiler.TraceAnnotation(
                MARK_END, t_mono_ns=int(time.monotonic() * 1e9)):
            pass
    finally:
        jax.profiler.stop_trace()
    return trace_dir


def _reduce_trace(run: Run) -> Optional[Dict]:
    from benchmarks.reduce.xplane import reduce_file
    files = sorted(glob.glob(os.path.join(
        run.info["trace_dir"], "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    return reduce_file(files[-1], run.spans)
