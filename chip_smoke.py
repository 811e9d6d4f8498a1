#!/usr/bin/env python3
"""chip_smoke.py — the served path, end to end, on the TPU.

    python chip_smoke.py [--seed N] [--shards N] [--rule-shards N]
                         [--rss host|device]

One process drives rx ring → shim harvest → feeder → pipeline →
JITDatapath → verdict apply at the north-star deployment (BASELINE config 5
at its ``full`` preset: 2,000 pod identities, 50,000 ingress port rules on
one endpoint, a 2^21-slot conntrack table, every other ``DaemonConfig``
field at its default, parity auditor armed at sampling 1.0), through the
entry points a user calls. It is a pass/fail check that the product still
starts and answers correctly on the chip; it measures nothing. Any time it
prints is a smoke reading on a shared host and belongs under no metric.

Phases, in order; each one raises on its own failure and nothing catches
it, so the run exits non-zero naming the phase:

  device   JAX's first device is a TPU (no accelerator → exit, no result)
  build    libflowshim.so built from flowshim.cc by the shim's Makefile
  load     policy build + place; engine, controllers, shim, feeder up
  serve1   >=100k frames of the cfg5 mix; every frame gets a verdict
  serve2   the same flows again: ESTABLISHED / REPLY off the device CT
  update   one rule added then removed under traffic (donated scatter)
  sweep    one whole-table device sweep
  parity   auditor clean with rows audited; 8192-row batch == oracle twin
           (the twin answers every 8th row)
  health   pipeline counters, engine health, the packed verdict slab

The last line of stdout is one JSON object, ``{"ok": true, "device":
{"platform": ..., "kind": ..., "count": ...}}``; the line before it
(``[report] json=...``) carries the run's smoke readings.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

WEB_IP = "192.168.0.10"
WEB_IP_WORD = 0xC0A8000A
WEB_EP = 1
POD_NET = 0xAC100000            # 172.16.0.0: pod i is 172.16.(i>>8).(i&255)
UNKNOWN_NET = 0x0A090000        # 10.9.0.0/16: in no ipcache entry → world
TCP_ACK = 0x10
FRAME_LEN = 54                  # eth 14 + ipv4 20 + tcp 20, no payload
LIVE_LABEL = "k8s:smoke=live"
TWIN_STRIDE = 8                 # the oracle twin answers every 8th parity row


@dataclasses.dataclass(frozen=True)
class World:
    """The deployment: BASELINE config 5 at its published size (the
    documents ``benchmarks/worlds/`` builds for ``ct1m-50k``, in a copy of
    this file's own: ROADMAP D12). Defaults are the real size;
    tests/test_chip_smoke.py shrinks it for the CPU."""
    n_ids: int = 2000
    n_rules: int = 50_000
    port_span: int = 25_000
    ct_capacity: int = 1 << 21
    batch_size: int = 8192          # the DaemonConfig default
    n_frames: int = 120_000         # serve pass 1
    collide_windows: int = 48       # crafted CT probe-window pile-ups
    parity_rows: int = 8192


@dataclasses.dataclass
class Run:
    """What the phases hand on to each other."""
    world: World
    seed: int
    t_start: float = dataclasses.field(default_factory=time.monotonic)
    n_shards: int = 1
    rule_shards: int = 1
    rss_mode: str = "host"
    eng: object = None
    shim: object = None
    allowed: Optional[np.ndarray] = None      # [n_ids, port_span] bool
    flows: Optional[Dict[str, np.ndarray]] = None
    established: Optional[np.ndarray] = None  # per flow, after serve1
    ct_full: int = 0
    collide_homes: Optional[np.ndarray] = None
    compiles: List = dataclasses.field(default_factory=list)
    cache_events: Dict[str, int] = dataclasses.field(default_factory=dict)
    report: Dict = dataclasses.field(default_factory=dict)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def need(cond, phase: str, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: phase {phase} FAILED: {what}")


# --------------------------------------------------------------------------- #
# the world: documents, identities, traffic
# --------------------------------------------------------------------------- #
def policy_docs(w: World) -> List[Dict]:
    return [{
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "ingress": [{
            "fromEndpoints": [{"matchLabels": {"pod": f"p{j % w.n_ids}"}}],
            "toPorts": [{"ports": [{"port": str(1024 + j % w.port_span),
                                    "protocol": "TCP"}]}],
        }],
    } for j in range(w.n_rules)]


def allowed_matrix(w: World) -> np.ndarray:
    """allowed[pod, port - 1024]: what the documents above permit."""
    j = np.arange(w.n_rules)
    m = np.zeros((w.n_ids, w.port_span), dtype=bool)
    m[j % w.n_ids, j % w.port_span] = True
    return m


def load_world(eng, w: World) -> None:
    """One endpoint, the remote pods as the cluster's identity sync would
    deliver them (runtime/clustermesh.py: allocate + ipcache upsert), and
    the rule documents through ``apply_policy``."""
    from cilium_tpu.model.labels import Labels
    eng.add_endpoint(["k8s:app=web"], ips=(WEB_IP,), ep_id=WEB_EP)
    for i in range(w.n_ids):
        ident = eng.ctx.allocator.allocate(Labels.parse([f"k8s:pod=p{i}"]))
        eng.ctx.ipcache.upsert(f"172.16.{i >> 8}.{i & 0xFF}/32", ident.id)
    wait_active(eng, eng.apply_policy(policy_docs(w)), "load", 600.0)


def columns(src_ip, sport, dport, reply=None) -> Dict[str, np.ndarray]:
    """Flows → the kernels/records column batch the shim would parse them
    into: ingress to the web endpoint, or (``reply`` rows) the endpoint's
    answer on the reversed tuple."""
    from cilium_tpu.kernels.records import empty_batch
    from cilium_tpu.utils import constants as C
    n = len(src_ip)
    rep = np.zeros(n, bool) if reply is None else np.asarray(reply, bool)
    b = empty_batch(n)
    b["src"][:, 2] = b["dst"][:, 2] = 0xFFFF          # v4-mapped
    b["src"][:, 3] = np.where(rep, WEB_IP_WORD, src_ip)
    b["dst"][:, 3] = np.where(rep, src_ip, WEB_IP_WORD)
    b["sport"][:] = np.where(rep, dport, sport)
    b["dport"][:] = np.where(rep, sport, dport)
    b["proto"][:] = C.PROTO_TCP
    b["tcp_flags"][:] = TCP_ACK
    b["direction"][:] = np.where(rep, C.DIR_EGRESS, C.DIR_INGRESS)
    b["valid"][:] = True
    return b


def frames_of(b: Dict[str, np.ndarray]) -> List[bytes]:
    """Column batch → Ethernet/IPv4/TCP frames (bindings.build_frame's
    layout, the fields patched in bulk at their protocol offsets)."""
    from cilium_tpu.shim.bindings import build_frame
    n = b["valid"].shape[0]
    tmpl = np.frombuffer(build_frame("1.1.1.1", "2.2.2.2", 1, 2,
                                     tcp_flags=TCP_ACK), dtype=np.uint8)
    need(tmpl.size == FRAME_LEN, "serve", "frame template is not 54 bytes")
    f = np.tile(tmpl, (n, 1))
    f[:, 26:30] = b["src"][:, 3].astype(">u4").view(np.uint8).reshape(n, 4)
    f[:, 30:34] = b["dst"][:, 3].astype(">u4").view(np.uint8).reshape(n, 4)
    f[:, 34:36] = b["sport"].astype(">u2").view(np.uint8).reshape(n, 2)
    f[:, 36:38] = b["dport"].astype(">u2").view(np.uint8).reshape(n, 2)
    raw = f.tobytes()
    return [raw[i * FRAME_LEN:(i + 1) * FRAME_LEN] for i in range(n)]


def home_slots(b: Dict[str, np.ndarray], capacity: int,
               n_shards: int) -> np.ndarray:
    """First probe slot of each flow in the device table's layout: one
    table, or ``n_shards`` local tables chosen by the direction-normalized
    hash (parallel/mesh.rehash_ct_arrays places entries the same way)."""
    from cilium_tpu.kernels.hashing import hash_words_np
    from cilium_tpu.kernels.records import ct_key_words
    from cilium_tpu.parallel.mesh import flow_shard_of
    h = hash_words_np(ct_key_words(b)).astype(np.int64)
    local = capacity // n_shards
    home = h & (local - 1)
    if n_shards > 1:
        home += flow_shard_of(b, n_shards).astype(np.int64) * local
    return home


def pod_ip(pod: np.ndarray) -> np.ndarray:
    return (POD_NET + pod).astype(np.uint32)


def flow_ids(src_ip, sport, dport) -> np.ndarray:
    """One int64 per (src, sport, dport), for deduplication."""
    return (src_ip.astype(np.int64) << 32) | (sport.astype(np.int64) << 16) \
        | dport.astype(np.int64)


def allowed_flows(rng, allowed: np.ndarray, n: int, sport_lo: int,
                  sport_hi: int):
    """``n`` draws of (pod, sport, dport) with dport in the pod's allowed
    set."""
    pods, offs = np.nonzero(allowed)
    pick = rng.integers(0, pods.size, n)
    return (pods[pick], rng.integers(sport_lo, sport_hi, n),
            1024 + offs[pick])


def make_flows(run: Run) -> Dict[str, np.ndarray]:
    """Serve-pass traffic, the cfg5 mix: TCP to the web endpoint from the
    pods on allowed and denied ports, some from an address no identity
    covers, and one contiguous burst of new flows crafted to pile into the
    same CT probe windows. Every flow is distinct."""
    w, rng = run.world, np.random.default_rng(run.seed)
    allowed = run.allowed
    n_ok = int(w.n_frames * 0.78)
    n_deny = int(w.n_frames * 0.18)
    n_unknown = int(w.n_frames * 0.03)

    pod_a, sport_a, dport_a = allowed_flows(rng, allowed, n_ok, 20000, 40000)
    pod_d = rng.integers(0, w.n_ids, 2 * n_deny)
    off_d = rng.integers(0, w.port_span, 2 * n_deny)
    keep = np.nonzero(~allowed[pod_d, off_d])[0][:n_deny]
    pod_d, off_d = pod_d[keep], off_d[keep]

    # the pile-up: many candidate new flows, hashed on the host; keep 16 of
    # those whose first probe slot falls into one aligned 8-slot window,
    # for each of a few windows. 16 flows reach at most 15 slots, so every
    # window must refuse someone (CT_FULL) and contends on every round.
    pod_c, sport_c, dport_c = allowed_flows(rng, allowed,
                                            2 * w.ct_capacity, 40000, 60000)
    _, uniq = np.unique(flow_ids(pod_ip(pod_c), sport_c, dport_c),
                        return_index=True)
    pod_c, sport_c, dport_c = pod_c[uniq], sport_c[uniq], dport_c[uniq]
    home = home_slots(columns(pod_ip(pod_c), sport_c, dport_c),
                      w.ct_capacity, run.n_shards)
    win, inv, counts = np.unique(home >> 3, return_inverse=True,
                                 return_counts=True)
    crowded = np.nonzero(counts >= 16)[0][:w.collide_windows]
    need(crowded.size > 0, "serve1", "no crowded CT window to craft from")
    rank = np.argsort(inv, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pick = np.concatenate([rank[starts[c]:starts[c] + 16] for c in crowded])
    run.collide_homes = win[crowded] << 3

    parts = [
        (pod_ip(pod_a), sport_a, dport_a, True, False),
        (pod_ip(pod_d), rng.integers(20000, 40000, pod_d.size),
         1024 + off_d, False, False),
        ((UNKNOWN_NET + rng.integers(1, 60000, n_unknown)).astype(np.uint32),
         rng.integers(20000, 40000, n_unknown),
         1024 + rng.integers(0, w.port_span, n_unknown), False, False),
    ]
    src = np.concatenate([p[0] for p in parts])
    sport = np.concatenate([p[1] for p in parts])
    dport = np.concatenate([p[2] for p in parts])
    want = np.concatenate([np.full(p[0].size, p[3]) for p in parts])
    # distinct flows only, in a shuffled order
    _, first = np.unique(flow_ids(src, sport, dport), return_index=True)
    first = rng.permutation(first)
    src, sport, dport, want = src[first], sport[first], dport[first], \
        want[first]
    # the crafted burst goes in the middle, contiguous
    mid = src.size // 2
    ins = lambda a, c: np.concatenate([a[:mid], c, a[mid:]])  # noqa: E731
    flows = {
        "src": ins(src, pod_ip(pod_c[pick])),
        "sport": ins(sport, sport_c[pick]),
        "dport": ins(dport, dport_c[pick]),
        "want_allow": ins(want, np.ones(pick.size, bool)),
        "crafted": ins(np.zeros(src.size, bool), np.ones(pick.size, bool)),
    }
    return flows


# --------------------------------------------------------------------------- #
# the NIC side of the mock rings
# --------------------------------------------------------------------------- #
def verdict_count(st: Dict[str, int]) -> int:
    return st["verdict_passes"] + st["verdict_drops"] + st["tx_full_drops"]


def nic_serve(run: Run, frames: List[bytes], phase: str,
              deadline_s: float = 600.0) -> Dict[str, int]:
    """Offer ``frames`` to the rx ring, take forwarded frames off the tx
    ring, and return once every frame has its verdict. Returns the shim
    counter deltas. A frame without a verdict at the deadline fails the
    phase."""
    shim = run.shim
    base = shim.stats()
    end = time.monotonic() + deadline_s
    for i, f in enumerate(frames):
        while shim.mock_rx_inject(f) != 0:
            shim.mock_tx_drain(256)
            need(time.monotonic() < end, phase,
                 f"rx ring never drained ({i}/{len(frames)} injected)")
            time.sleep(0.0002)
        if i % 128 == 0:
            shim.mock_tx_drain(256)
            if "setup_s" not in run.report \
                    and verdict_count(shim.stats()) > 0:
                # process start → first verdict: engine up, policy placed,
                # the first bucket shape compiled (or loaded from cache)
                run.report["setup_s"] = round(
                    time.monotonic() - run.t_start, 2)
    while True:
        shim.mock_tx_drain(256)
        st = shim.stats()
        if verdict_count(st) - verdict_count(base) >= len(frames):
            break
        need(time.monotonic() < end, phase,
             f"{len(frames) - verdict_count(st) + verdict_count(base)} of "
             f"{len(frames)} frames got no verdict in {deadline_s:.0f}s; "
             f"pipeline={run.eng.pipeline_stats()}")
        time.sleep(0.002)
    delta = {k: st[k] - base[k] for k in st}
    need(verdict_count(delta) == len(frames), phase,
         f"verdicts {verdict_count(delta)} != injected {len(frames)}")
    return delta


def reason_counts(eng) -> Dict[str, int]:
    """Verdicts by drop reason so far (read between passes, drained)."""
    from cilium_tpu.utils import constants as C
    arr = eng.metrics.by_reason_dir.reshape(
        C.DROP_REASON_BINS, C.N_DIRECTIONS).sum(axis=1)
    return {r.name: int(arr[int(r)]) for r in C.DropReason}


def live_table(eng):
    ct = eng.ct_arrays()
    live = ct["expiry"] > 0
    return {k: v[live] for k, v in ct.items()}


def drain_audit(eng) -> Dict:
    """Replay what the shadow auditor has captured so far, so the next
    phase's batches find room in its bounded pool (8 batches; at sampling
    1.0 the rest of a burst is counted ``skipped``). Returns its counters.
    The replay is the Python oracle — tens of milliseconds a row at this
    world — which is why the smoke replays at phase ends and does not
    start the engine's background controllers: their parity-audit timer
    would replay all the while and hold the interpreter lock against the
    feeder."""
    for _ in range(1000):
        step = eng.audit_step(budget=128)
        if not step or (not step.get("replayed") and not step.get("pending")):
            break
    return eng.auditor.stats()


def key_rows(keys: np.ndarray) -> np.ndarray:
    """[N, 10] uint32 CT keys → [N] opaque rows comparable with isin."""
    k = np.ascontiguousarray(keys, dtype=np.uint32)
    return k.view([("k", np.void, k.shape[1] * 4)]).reshape(-1)


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #
def phase_device(run: Run) -> Dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: phase device FAILED: JAX found no TPU — platform "
            f"{d.platform!r} ({d.device_kind}, {len(devs)} device(s)); this "
            f"script has no CPU mode")
    need(len(devs) >= run.n_shards * run.rule_shards, "device",
         f"mesh {run.n_shards}x{run.rule_shards} needs more than the "
         f"{len(devs)} device(s) JAX has")
    from cilium_tpu.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    say("device", platform=d.platform, device_kind=repr(d.device_kind),
        count=len(devs), jax=jax.__version__, compile_cache=cache,
        cache_entries=len(os.listdir(cache)) if os.path.isdir(cache) else 0)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def watch_compiles(run: Run) -> None:
    """Every XLA backend compile (or cache load) of this process, with its
    seconds — the longest one is what the pipeline's stall watchdog has to
    outlast."""
    import jax.monitoring as mon

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            run.compiles.append((kw.get("fun_name", "?"), float(secs)))

    def on_event(event, **_kw):
        if event.startswith("/jax/compilation_cache/cache_"):
            key = event.rsplit("/", 1)[1]
            run.cache_events[key] = run.cache_events.get(key, 0) + 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)


def phase_build(run: Run) -> None:
    shim_dir = os.path.join(HERE, "cilium_tpu", "shim")
    subprocess.run(["make", "-C", shim_dir, "libflowshim.so"], check=True,
                   stdout=subprocess.DEVNULL)
    from cilium_tpu.shim.bindings import LIB_PATH
    need(os.path.getmtime(LIB_PATH)
         >= os.path.getmtime(os.path.join(shim_dir, "flowshim.cc")),
         "build", f"{LIB_PATH} is older than flowshim.cc after make")
    say("build", lib=LIB_PATH)


def make_config(run: Run):
    from cilium_tpu.runtime.config import DaemonConfig
    w = run.world
    return DaemonConfig(ct_capacity=w.ct_capacity, batch_size=w.batch_size,
                        audit_enabled=True, audit_sample_rate=1.0,
                        n_shards=run.n_shards, rule_shards=run.rule_shards,
                        rss_mode=run.rss_mode)


def phase_load(run: Run) -> None:
    import jax
    from cilium_tpu.runtime.engine import Engine
    from cilium_tpu.shim.bindings import FlowShim
    t0 = time.monotonic()
    eng = run.eng = Engine(make_config(run))
    load_world(eng, run.world)
    load_s = time.monotonic() - t0
    spans = eng.metrics.spans
    mem = jax.devices()[0].memory_stats() or {}
    say("load", wall_s=round(load_s, 2),
        host_compile_s=round(spans["snapshot_compile"].total_s, 2),
        place_s=round(spans["device_place"].total_s, 2),
        revision=eng.active.revision,
        peak_bytes_in_use=mem.get("peak_bytes_in_use", "not reported"))
    say("load", hbm=json.dumps(eng.hbm_status()["ledger"]["groups"]))
    if run.n_shards * run.rule_shards > 1:
        check_placement(run, "load")
    shim = run.shim = FlowShim()
    shim.register_endpoint(WEB_IP, WEB_EP)
    shim.mock_rings_init(ring_size=4096, frame_size=2048, n_frames=4096)
    eng.start_feeder(shim)
    run.allowed = allowed_matrix(run.world)
    run.report["load_s"] = round(load_s, 2)


def check_placement(run: Run, phase: str) -> None:
    """On a mesh: CT shards on distinct devices, one per flow shard — at
    load, and again after every batch has been donated through it; a wire
    batch put down the way dispatch does it lands one segment a device; the
    verdict image over the rules axis; everything else replicated on every
    device of the mesh (not parked on device 0)."""
    import jax
    dp = run.eng.datapath
    n_dev = run.n_shards * run.rule_shards
    mesh_devs = {d.id for d in np.asarray(dp._mesh.devices).reshape(-1)}
    need(len(mesh_devs) == n_dev, phase, f"mesh has {mesh_devs}")
    ct_devs = {d.id for d in dp._ct["expiry"].sharding.device_set}
    need(ct_devs == mesh_devs, phase, f"CT on {ct_devs}, mesh {mesh_devs}")
    shard_rows = {s.data.shape[0] for s in
                  dp._ct["expiry"].addressable_shards}
    need(shard_rows == {run.world.ct_capacity // run.n_shards}, phase,
         f"CT shard rows {shard_rows}")
    wire = jax.device_put(np.zeros((run.world.batch_size, 4), np.uint32),
                          dp._batch_sharding)
    wire_devs = {s.device.id for s in wire.addressable_shards}
    seg_rows = {s.data.shape[0] for s in wire.addressable_shards}
    need(wire_devs == mesh_devs
         and seg_rows == {run.world.batch_size // run.n_shards}, phase,
         f"a wire batch lands on {wire_devs} in segments of {seg_rows} rows")
    placed = run.eng.active.tensors
    for name, arr in placed.items():
        devs = {d.id for d in arr.sharding.device_set}
        need(devs == mesh_devs, phase,
             f"tensor {name} on {devs}, mesh {mesh_devs}")
        if name != "verdict":
            need(arr.sharding.is_fully_replicated, phase,
                 f"tensor {name} is not replicated")
    say(phase, mesh=f"{run.n_shards}x{run.rule_shards}",
        rss=run.rss_mode, ct_devices=sorted(ct_devs),
        wire_devices=sorted(wire_devs), wire_segment_rows=sorted(seg_rows),
        verdict_sharding=str(placed["verdict"].sharding.spec))


def phase_serve1(run: Run) -> None:
    from cilium_tpu.kernels.records import ct_key_words
    eng, w = run.eng, run.world
    flows = run.flows = make_flows(run)
    n = flows["src"].size
    need(n >= 0.95 * w.n_frames, "serve1", f"only {n} distinct flows")
    cols = columns(flows["src"], flows["sport"], flows["dport"])
    r0, fail0 = reason_counts(eng), eng.metrics.insert_fail
    t0 = time.monotonic()
    d = nic_serve(run, frames_of(cols), "serve1")
    wall = time.monotonic() - t0
    need(eng.drain(timeout=60), "serve1", "pipeline did not drain")
    r1 = reason_counts(eng)
    reasons = {k: r1[k] - r0[k] for k in r1 if r1[k] != r0[k]}
    ct_full = run.ct_full = eng.metrics.insert_fail - fail0
    allowed = d["verdict_passes"] + d["tx_full_drops"]
    want = int(flows["want_allow"].sum())
    say("serve1", frames=n, passes=d["verdict_passes"],
        drops=d["verdict_drops"], tx_full=d["tx_full_drops"],
        reasons=json.dumps(reasons), wall_s=round(wall, 2),
        smoke_reading_frames_per_s=int(n / wall))
    need(reasons.get("OK", 0) == allowed, "serve1",
         f"OK verdicts {reasons.get('OK')} != frames passed {allowed}")
    need(reasons.get("POLICY", 0) == n - want, "serve1",
         f"POLICY drops {reasons.get('POLICY')} != {n - want} denied flows")
    need(reasons.get("CT_FULL", 0) == ct_full == want - allowed, "serve1",
         f"CT_FULL {reasons.get('CT_FULL')} / insert_fail {ct_full} != "
         f"{want - allowed} allowed flows refused")
    need(ct_full > 0, "serve1", "the crafted CT windows refused nobody")
    # the device table holds exactly the flows that were let in
    tab = live_table(eng)
    est = run.established = np.isin(key_rows(ct_key_words(cols)),
                                    key_rows(tab["keys"]))
    need(int(est.sum()) == allowed == tab["keys"].shape[0], "serve1",
         f"device CT holds {tab['keys'].shape[0]} entries, {int(est.sum())} "
         f"of them ours, {allowed} flows allowed")
    need(not (est & ~flows["want_allow"]).any(), "serve1",
         "a denied flow has a CT entry")
    refused = flows["want_allow"] & ~est
    far = np.abs(home_slots({k: v[refused] for k, v in cols.items()},
                            w.ct_capacity, run.n_shards)[:, None]
                 - run.collide_homes[None, :]).min(axis=1) > 16
    need(not far.any(), "serve1",
         f"{int(far.sum())} flows away from the crafted windows were "
         f"refused a CT slot")
    say("serve1", ct_live=int(est.sum()), ct_full=ct_full,
        crafted=int(flows["crafted"].sum()),
        audited_rows=drain_audit(eng)["checked_rows"])


def phase_serve2(run: Run) -> None:
    """The same flows again. What pass 1 let in must now be ESTABLISHED —
    or REPLY, for the quarter of them sent as the endpoint's answer — off
    the CT that lives on the device and was donated through every batch:
    no new entry, and every entry has seen exactly two packets."""
    eng, flows, est = run.eng, run.flows, run.established
    n = flows["src"].size
    reply = est & (np.arange(n) % 4 == 0)
    cols = columns(flows["src"], flows["sport"], flows["dport"], reply=reply)
    r0, fail0 = reason_counts(eng), eng.metrics.insert_fail
    d = nic_serve(run, frames_of(cols), "serve2")
    need(eng.drain(timeout=60), "serve2", "pipeline did not drain")
    r1 = reason_counts(eng)
    reasons = {k: r1[k] - r0[k] for k in r1 if r1[k] != r0[k]}
    allowed = d["verdict_passes"] + d["tx_full_drops"]
    need(allowed == int(est.sum()), "serve2",
         f"{allowed} frames passed, {int(est.sum())} flows established")
    need(eng.metrics.insert_fail - fail0 == run.ct_full, "serve2",
         "the refused flows were not refused again")
    need(reasons.get("POLICY", 0)
         == n - int(flows["want_allow"].sum()), "serve2", "POLICY drops")
    tab = live_table(eng)
    need(tab["keys"].shape[0] == int(est.sum()), "serve2",
         f"CT grew to {tab['keys'].shape[0]} entries")
    seen = tab["pkts_fwd"].astype(np.int64) + tab["pkts_rev"]
    need((seen == 2).all(), "serve2",
         f"{int((seen != 2).sum())} CT entries did not see 2 packets")
    need(int(tab["pkts_rev"].sum()) == int(reply.sum()), "serve2",
         f"REPLY packets {int(tab['pkts_rev'].sum())} != {int(reply.sum())}")
    say("serve2", frames=n, passes=d["verdict_passes"],
        established=int(est.sum()) - int(reply.sum()),
        reply=int(reply.sum()), reasons=json.dumps(reasons),
        audited_rows=drain_audit(eng)["checked_rows"])


def wait_active(eng, revision: int, phase: str,
                deadline_s: float = 120.0) -> None:
    """Block until the engine serves policy ``revision``. With
    ``auto_regen`` at its default a repository change is compiled by the
    engine's own debounced trigger, not by the caller."""
    end = time.monotonic() + deadline_s
    while eng.active.revision < revision:
        need(time.monotonic() < end, phase,
             f"revision {revision} not active after {deadline_s:.0f}s "
             f"(serving {eng.active.revision}, health {eng.health()})")
        time.sleep(0.005)


def probe(run: Run, pod: int, sport: int, dport: int) -> Dict:
    """One flow through the pipeline (``Engine.submit``), its verdict."""
    b = columns(pod_ip(np.array([pod])), np.array([sport]),
                np.array([dport]))
    b["ep_slot"][:] = run.eng.active.snapshot.ep_slot_of[WEB_EP]
    out = run.eng.submit(b).result(timeout=120)
    return {k: int(out[k][0]) for k in ("allow", "reason", "status")}


def phase_update(run: Run) -> None:
    """One rule added, then removed, while established traffic keeps
    arriving through the rings — twice. The flow the rule decides flips
    and flips back (a fresh source port each time: an entry, once made,
    outlives the rule by design). The first add after a full build splits
    the pod's identity class and re-uploads the verdict image; from then
    on every update must ride the donated delta scatter, which is what the
    second round checks. On the chip a donated buffer that is read again
    raises, so a hole in the StalePlacement fence shows here as a dispatch
    error."""
    from cilium_tpu.utils import constants as C
    eng, flows, est = run.eng, run.flows, run.established
    pod = 7 % run.world.n_ids
    dport = 1024 + int(np.nonzero(~run.allowed[pod])[0][0])
    doc = [{"endpointSelector": {"matchLabels": {"app": "web"}},
            "labels": [LIVE_LABEL],
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"pod": f"p{pod}"}}],
                "toPorts": [{"ports": [{"port": str(dport),
                                        "protocol": "TCP"}]}]}]}]
    background = frames_of(columns(flows["src"][est], flows["sport"][est],
                                   flows["dport"][est]))
    stop = threading.Event()
    sent = [0]
    failure: List[BaseException] = []

    def traffic():
        try:
            while not stop.is_set():
                i = sent[0] % len(background)
                chunk = background[i:i + 2048]
                nic_serve(run, chunk, "update")
                sent[0] += len(chunk)
        except BaseException as e:   # noqa: BLE001 - re-raised by the phase
            failure.append(e)

    deny = {"allow": 0, "reason": int(C.DropReason.POLICY),
            "status": int(C.CTStatus.NEW)}
    allow = {"allow": 1, "reason": int(C.DropReason.OK),
             "status": int(C.CTStatus.NEW)}
    th = threading.Thread(target=traffic, name="smoke-nic", daemon=True)
    th.start()
    try:
        for rnd in range(2):
            p0 = dict(eng.datapath.patch_stats)
            sport = 10001 + 3 * rnd
            before = probe(run, pod, sport, dport)
            wait_active(eng, eng.apply_policy(doc), "update")
            during = probe(run, pod, sport + 1, dport)
            wait_active(eng, eng.replace_policy([LIVE_LABEL], []), "update")
            after = probe(run, pod, sport + 2, dport)
            patch = {k: v - p0[k]
                     for k, v in eng.datapath.patch_stats.items()}
            say("update", round=rnd, pod=pod, dport=dport, before=before,
                during=during, after=after, patch=json.dumps(patch))
            need(before == deny and after == deny, "update",
                 f"the flow should be denied without the rule: "
                 f"{before} {after}")
            need(during == allow, "update",
                 f"the flow should be allowed under the rule: {during}")
    finally:
        stop.set()
        th.join(timeout=700)
    need(not th.is_alive(), "update", "traffic thread did not stop")
    if failure:
        raise failure[0]
    need(sent[0] > 0, "update", "no traffic ran during the updates")
    say("update", frames_meanwhile=sent[0])
    need(patch["patch_delta"] == 2 and patch["patch_full"] == 0
         and patch["patch_scatter_errors"] == 0, "update",
         f"round 1's add and remove did not both ride the donated delta "
         f"scatter: {patch}")
    drain_audit(eng)


def phase_sweep(run: Run) -> None:
    eng = run.eng
    live0 = eng.ct_stats()["live"]
    reclaimed = eng.sweep()
    live1 = eng.ct_stats()["live"]
    say("sweep", reclaimed=reclaimed, live_before=live0, live_after=live1)
    need(reclaimed == 0 and live1 == live0, "sweep",
         "a fresh table lost entries to the sweep")


def phase_parity(run: Run) -> None:
    """Two independent judges. The shadow auditor replayed, through the
    oracle, rows sampled from served batches of every phase. And a
    jax-free twin engine (FakeDatapath: the oracle behind the same Engine)
    built from the same documents answers a fresh batch twice — NEW, then
    ESTABLISHED / REPLY — and must agree exactly. The chip classifies all
    ``parity_rows`` rows in one batch; the twin, at tens of milliseconds a
    row, answers every ``TWIN_STRIDE``-th of them."""
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import FakeDatapath
    from cilium_tpu.runtime.engine import Engine
    eng, w = run.eng, run.world
    a = drain_audit(eng)
    say("parity", audit=json.dumps({k: a[k] for k in (
        "captured_batches", "checked_batches", "checked_rows",
        "mismatched_rows", "skipped_batches", "capture_errors",
        "replay_errors", "pending")}))
    need(a["mismatched_rows"] == 0 and a["checked_rows"] > 0
         and a["capture_errors"] == 0 and a["replay_errors"] == 0
         and a["pending"] == 0, "parity", f"auditor: {a}")

    cfg = DaemonConfig(ct_capacity=w.ct_capacity, auto_regen=False)
    twin = Engine(cfg, datapath=FakeDatapath(cfg))
    load_world(twin, w)
    rng = np.random.default_rng(run.seed + 1)
    n = w.parity_rows
    n_ok = n // 2
    pod_a, sport_a, dport_a = allowed_flows(rng, run.allowed, 2 * n_ok,
                                            10010, 20000)
    # the device table is deliberately full around the crafted windows and
    # the twin's is empty: fresh flows that would probe there are left out
    home = home_slots(columns(pod_ip(pod_a), sport_a, dport_a),
                      w.ct_capacity, run.n_shards)
    clear = np.nonzero(np.abs(
        home[:, None] - run.collide_homes[None, :]).min(axis=1) > 32)[0]
    _, uniq = np.unique(flow_ids(pod_a, sport_a, dport_a)[clear],
                        return_index=True)
    clear = clear[np.sort(uniq)][:n_ok]
    need(clear.size == n_ok, "parity", "too few fresh flows")
    n_rest = n - n_ok
    order = rng.permutation(n)
    src = np.concatenate([
        pod_ip(pod_a[clear]),
        pod_ip(rng.integers(0, w.n_ids, n_rest // 2)),
        (UNKNOWN_NET + rng.integers(1, 60000, n_rest - n_rest // 2))
        .astype(np.uint32)])[order]
    sport = np.concatenate([sport_a[clear],
                            rng.integers(10010, 20000, n_rest)])[order]
    dport = np.concatenate([
        dport_a[clear],
        1024 + rng.integers(0, w.port_span, n_rest)])[order]
    sub = np.arange(0, n, TWIN_STRIDE)
    now = int(time.time())
    reply = np.zeros(n, bool)
    for rnd in range(2):
        b = columns(src, sport, dport, reply=reply)
        b["ep_slot"][:] = eng.active.snapshot.ep_slot_of[WEB_EP]
        chip = eng.classify(b, now=now + rnd)
        tb = {k: v[sub] for k, v in b.items()}
        tb["ep_slot"][:] = twin.active.snapshot.ep_slot_of[WEB_EP]
        ref = twin.classify(tb, now=now + rnd)
        for k in ("allow", "reason", "status", "remote_identity",
                  "ct_full"):
            got = np.asarray(chip[k])[sub].astype(np.int64)
            want = np.asarray(ref[k]).astype(np.int64)
            bad = np.nonzero(got != want)[0]
            need(bad.size == 0, "parity",
                 f"round {rnd}: column {k} differs from the oracle twin on "
                 f"{bad.size} of {sub.size} rows, first at row "
                 f"{sub[bad[:1]]}: chip {got[bad[:1]]} twin {want[bad[:1]]}")
        st = np.bincount(np.asarray(chip["status"]), minlength=3)
        allowed = np.asarray(chip["allow"]).astype(bool)
        say("parity", round=rnd, rows=n, twin_rows=sub.size,
            allowed=int(allowed.sum()), new=int(st[0]),
            established=int(st[1]), reply=int(st[2]))
        need(int(allowed.sum()) >= n_ok, "parity",
             "the fresh allowed flows were not all allowed")
        # round 1: what round 0 let in comes back, a third of it as the
        # endpoint's reply
        reply = allowed & (np.arange(n) % 3 == 0)
    need(st[1] > 0 and st[2] > 0, "parity",
         "round 1 saw no ESTABLISHED or no REPLY rows")
    twin.stop()


def phase_health(run: Run) -> None:
    from cilium_tpu.utils import constants as C
    eng = run.eng
    ps = eng.pipeline_stats()
    fs = eng.feeder_stats()
    h = eng.health()
    cold = sorted(run.compiles, key=lambda c: -c[1])[:3]
    say("health", pipeline=json.dumps({k: ps[k] for k in (
        "state", "restarts", "dispatch_errors", "dispatch_faults",
        "shed_total", "admission_drops", "unavailable_total",
        "dispatched_batches", "flush_reasons", "fill_ratio_avg")}),
        breaker=ps["breaker"]["state"])
    say("health", feeder=json.dumps({k: fs[k] for k in (
        "harvested_batches", "applied_batches", "rejected_batches",
        "errors", "harvest_faults", "prio_shed_rows")}))
    say("health", engine=h["state"], pack=json.dumps(eng.datapath.pack_stats))
    say("health", compiles=len(run.compiles),
        compile_s_total=round(sum(c[1] for c in run.compiles), 2),
        longest=json.dumps([(n, round(s, 2)) for n, s in cold]),
        stall_timeout_s=ps["stall_timeout_s"],
        cache=json.dumps(run.cache_events),
        setup_s=run.report.get("setup_s"))
    need(ps["state"] == "ok" and ps["breaker"]["state"] == "closed"
         and ps["restarts"] == 0 and ps["dispatch_errors"] == 0
         and ps["dispatch_faults"] == 0 and ps["shed_total"] == 0
         and ps["admission_drops"] == 0 and ps["unavailable_total"] == 0,
         "health", f"pipeline: {ps}")
    need(fs["rejected_batches"] == 0 and fs["errors"] == 0
         and fs["prio_shed_rows"] == 0 and fs["alive"], "health",
         f"feeder: {fs}")
    need(h["state"] == C.HEALTH_OK, "health", f"engine health: {h}")
    if run.n_shards * run.rule_shards > 1:
        check_placement(run, "health")
    # one chip or a mesh: batches came back in one packed verdict slab
    pack = eng.datapath.pack_stats
    need(pack["readback_slab"] > 0, "health",
         f"no batch read back through the verdict slab: {pack}")
    run.report["longest_compile_s"] = round(cold[0][1], 2) if cold else 0.0
    run.report["compile_s_total"] = round(sum(c[1] for c in run.compiles), 2)
    run.report["compiles"] = len(run.compiles)
    run.report["cache"] = dict(run.cache_events)


def shutdown(run: Run) -> None:
    if run.eng is not None:
        run.eng.stop()
    if run.shim is not None:
        run.shim.close()


PHASES = (("load", phase_load), ("serve1", phase_serve1),
          ("serve2", phase_serve2), ("update", phase_update),
          ("sweep", phase_sweep), ("parity", phase_parity),
          ("health", phase_health))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=1,
                    help="flow shards (DaemonConfig.n_shards); default one "
                         "chip")
    ap.add_argument("--rule-shards", type=int, default=1)
    ap.add_argument("--rss", default="host", choices=["host", "device"])
    args = ap.parse_args(argv)
    run = Run(world=World(), seed=args.seed, n_shards=args.shards,
              rule_shards=args.rule_shards, rss_mode=args.rss)
    device = phase_device(run)
    watch_compiles(run)
    phase_build(run)
    try:
        for name, fn in PHASES:
            t1 = time.monotonic()
            fn(run)
            say(name, phase_s=round(time.monotonic() - t1, 2))
    finally:
        shutdown(run)
    say("report", json=json.dumps({
        "mesh": {"shards": args.shards, "rule_shards": args.rule_shards,
                 "rss": args.rss},
        "seed": args.seed, **run.report,
        "wall_s": round(time.monotonic() - run.t_start, 2)}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
