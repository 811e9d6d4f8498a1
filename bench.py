#!/usr/bin/env python
"""Benchmarks: the five BASELINE.md configs.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} for the
headline config (5: conntrack churn — 50k-rule policy, 1M-flow CT, 10%
new-flow rate, single chip), plus per-batch latency percentiles
("p50_batch_ms"/"p99_batch_ms", BASELINE metric: "+ p99 batch latency") and
a "configs" sub-object with every config's throughput + latency so
round-over-round visibility covers the LPM-heavy and L7 shapes too.
``vs_baseline`` normalizes against the driver-set north star — 10M flows/sec
on a v5e-8 (8 chips) → 1.25M flows/sec/chip; there are no reference-published
numbers (BASELINE.json.published == {}, see BASELINE.md provenance note).

Usage:
  python bench.py [--config 1..5] [--preset full|smoke]
                  [--batch N] [--batches K] [--only]

The mesh is made of the devices JAX has; a virtual CPU mesh is asked for
from outside (JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_
device_count=N, as every Makefile target does). Every artifact names the
platform it ran on; a number from a CPU run is not a device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

PER_CHIP_TARGET = 10e6 / 8  # north-star flows/sec per chip

# Watchdog: a device op that never completes freezes the process in a
# futex wait. A hung benchmark reports nothing — worse than a partial
# report. The watchdog emits the best-effort JSON line from whatever
# completed and exits non-zero.
WATCHDOG_DEADLINE_S = float(os.environ.get(
    "CILIUM_TPU_BENCH_DEADLINE_S", 2400))
_progress: dict = {"headline": None, "configs": {}}


def _start_watchdog(headline_metric: str) -> None:
    if WATCHDOG_DEADLINE_S <= 0:
        return                          # 0/negative = watchdog disabled

    def fire():
        time.sleep(WATCHDOG_DEADLINE_S)
        doc = _progress["headline"] or {
            "metric": f"flow_classify_throughput_{headline_metric}",
            "value": 0, "unit": "flows/sec/chip", "vs_baseline": 0,
        }
        doc = dict(doc)
        doc["watchdog_timeout"] = True
        doc["error"] = (f"bench stalled past {WATCHDOG_DEADLINE_S:.0f}s; "
                        "partial results reported")
        if _progress["configs"]:
            doc["configs"] = _progress["configs"]
        print(json.dumps(doc), flush=True)
        os._exit(3)
    threading.Thread(target=fire, daemon=True,
                     name="bench-watchdog").start()


# --------------------------------------------------------------------------- #
# artifact provenance + regression compare
# --------------------------------------------------------------------------- #
def _provenance(argv=None, platform=None):
    """Artifact provenance: enough to answer "what produced this number"
    months later — the git revision (``unknown`` outside a checkout), the
    jax stack and the platform that served, and a hash of the bench's
    whole config surface (argv + every CILIUM_TPU_* env knob, the things
    that silently change reference numbers between runs). ``platform``
    names it for runs whose engines live in other processes (--cluster):
    this process then never touches a device."""
    import hashlib
    import subprocess
    rev = "unknown"
    try:
        r = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                           cwd=os.path.dirname(os.path.abspath(__file__)),
                           capture_output=True, text=True, timeout=10)
        if r.returncode == 0 and r.stdout.strip():
            rev = r.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass
    import jax
    jax_version = jax.__version__
    if platform is None:
        platform = jax.devices()[0].platform
    cfg = {"argv": list(sys.argv[1:] if argv is None else argv),
           "env": {k: v for k, v in sorted(os.environ.items())
                   if k.startswith("CILIUM_TPU_")}}
    doc = {
        "git_rev": rev,
        "jax_version": jax_version,
        "platform": platform,
        "config_hash": hashlib.sha256(
            json.dumps(cfg, sort_keys=True).encode()).hexdigest()[:12],
        "config": cfg,
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if _HBM_REPORT["budget"] is not None:
        # offline verifier HBM budget (cilium-tpu verify --report FILE,
        # embedded via --hbm-report): the artifact cites the same numbers
        # the --max-hbm-bytes gate judged and the live ledger exports
        doc["hbm_budget"] = _HBM_REPORT["budget"]
    return doc


#: `--hbm-report FILE` payload (the budget summary of a `cilium-tpu verify
#: --report` sweep), stamped into every artifact's provenance when given
_HBM_REPORT = {"budget": None}


#: fields --compare judges, with direction: +1 higher-is-better
#: (throughput), -1 lower-is-better (latency)
COMPARE_FIELDS = (
    ("value", +1),
    ("compute_only", +1),
    ("speedup_vs_serial", +1),
    ("e2e_p50_ms", -1),
    ("e2e_p99_ms", -1),
    ("pack_p50_ms", -1),
    # --ddos artifacts: adversarial-load survival
    ("survival_rate", +1),
    ("legit_e2e_p99_ms", -1),
    # --tenants artifacts: multi-tenant isolation (lower flooder share =
    # better confined to its weight)
    ("victim_survival_min", +1),
    ("lane_e2e_p99_ms", -1),
    ("flood_admitted_share", -1),
    # --fqdn artifacts: DNS-churn policy refresh on the delta path
    ("refresh_p50_ms", -1),
    ("refresh_p99_ms", -1),
    ("established_survival", +1),
    # --update-storm artifacts: live-patch latency under pipelined traffic
    ("rule_add_ms", -1),
    ("rule_add_p99_ms", -1),
    ("device_apply_p50_ms", -1),
    # --kernels artifacts: per-kernel compute-only latency
    ("kernel_lpm_p50_ms", -1),
    ("kernel_ct_probe_p50_ms", -1),
    ("kernel_policy_l7_p50_ms", -1),
    ("kernel_full_step_p50_ms", -1),
)

#: max tolerated regression ratio for --compare (generalizes the PR 6
#: --shards 1 gate to ANY prior artifact; deliberately generous — the gate
#: catches wholesale regressions, not jitter)
BENCH_COMPARE_FACTOR = float(os.environ.get(
    "CILIUM_TPU_BENCH_COMPARE_FACTOR", "1.75"))


def _metric_surface(doc: dict) -> dict:
    """The comparable numbers of one artifact, flattened (pack p50 lives
    in the stage/trace span split depending on the mode; per-kernel p50s
    come from the --kernels artifact's ``kernels`` block)."""
    out = {}
    for key, _d in COMPARE_FIELDS:
        v = doc.get(key)
        if isinstance(v, (int, float)):
            out[key] = v
    spans = doc.get("stage_split") or doc.get("trace_spans") or {}
    p = (spans.get("datapath.pack") or {}).get("p50_ms")
    if p is not None:
        out["pack_p50_ms"] = p
    for kname, kdoc in (doc.get("kernels") or {}).items():
        p = kdoc.get("p50_ms")
        if isinstance(p, (int, float)):
            out[f"kernel_{kname}_p50_ms"] = p
    return out


def _compare_artifacts(new_doc: dict, old_path: str,
                       factor: float = BENCH_COMPARE_FACTOR) -> dict:
    """Diff this run against a prior JSON artifact: every comparable field
    present in BOTH is ratio-checked against ``factor`` in its
    direction. ``failed`` fails the artifact (exit 4 from main) — the
    round-over-round regression gate."""
    with open(old_path) as f:
        old_doc = json.load(f)
    new_m, old_m = _metric_surface(new_doc), _metric_surface(old_doc)
    checked, regressions = {}, []
    for key, direction in COMPARE_FIELDS:
        old_v, new_v = old_m.get(key), new_m.get(key)
        if old_v is None or new_v is None or old_v <= 0:
            continue
        ratio = new_v / old_v
        checked[key] = {"old": old_v, "new": new_v,
                        "ratio": round(ratio, 4)}
        if direction > 0 and ratio < 1.0 / factor:
            regressions.append(
                f"{key}: {new_v} < {old_v}/{factor} (ratio {ratio:.3f})")
        elif direction < 0 and ratio > factor:
            regressions.append(
                f"{key}: {new_v} > {old_v}*{factor} (ratio {ratio:.3f})")
    return {
        "baseline": old_path,
        "baseline_rev": (old_doc.get("provenance") or {}).get("git_rev"),
        "factor": factor,
        "checked": checked,
        # steered and unsteered sharded artifacts are deliberately
        # comparable (same metric surface; the span-attribution contract
        # lives in each artifact's own schema_check, not here) — the
        # annotation makes a cross-mode diff visible in the artifact
        **({"rss": {"old": old_doc.get("rss", "host"),
                    "new": new_doc.get("rss", "host")}}
           if (new_doc.get("rss") or old_doc.get("rss")) else {}),
        "failed": bool(regressions),
        **({"regressions": regressions} if regressions else {}),
    }


# --------------------------------------------------------------------------- #
# world builders (one per config)
# --------------------------------------------------------------------------- #
def _ctx_repo():
    from cilium_tpu.model.identity import IdentityAllocator
    from cilium_tpu.model.ipcache import IPCache
    from cilium_tpu.policy import PolicyContext, Repository
    from cilium_tpu.policy.selectorcache import SelectorCache
    alloc = IdentityAllocator()
    ctx = PolicyContext(allocator=alloc,
                        selector_cache=SelectorCache(alloc),
                        ipcache=IPCache())
    return ctx, Repository(ctx)


def _add_web_ep(ctx, ip="192.168.0.10"):
    from cilium_tpu.model.endpoint import Endpoint
    from cilium_tpu.model.labels import Labels
    lbls = Labels.parse(["k8s:app=web"])
    ident = ctx.allocator.allocate(lbls)
    ctx.ipcache.upsert(f"{ip}/32", ident.id)
    return Endpoint(ep_id=1, labels=lbls, identity_id=ident.id)


def _compile(ctx, repo, eps, ct_capacity):
    from cilium_tpu.compile.ct_layout import CTConfig
    from cilium_tpu.compile.snapshot import build_snapshot
    return build_snapshot(repo, ctx, eps, CTConfig(capacity=ct_capacity))


def build_config1(preset):
    """1k static CIDR allow/deny rules, single endpoint, IPv4 only."""
    from cilium_tpu.model.rules import parse_rule
    ctx, repo = _ctx_repo()
    ep = _add_web_ep(ctx)
    n_rules = 1000
    rules = []
    for i in range(n_rules):
        a, b = 1 + (i % 200), (i * 7) % 256
        block = {"toCIDR": [f"{a}.{b}.0.0/16"]}
        if i % 3 == 2:
            rules.append(parse_rule({
                "endpointSelector": {"matchLabels": {"app": "web"}},
                "egressDeny": [block]}))
        else:
            rules.append(parse_rule({
                "endpointSelector": {"matchLabels": {"app": "web"}},
                "egress": [block]}))
    repo.add(rules)
    snap = _compile(ctx, repo, [ep], 1 << (14 if preset == "smoke" else 18))

    def gen(rng, n):
        b = _base_batch(n)
        b["dst"][:, 3] = ((rng.integers(1, 220, n) << 24)
                          + rng.integers(0, 1 << 24, n)).astype(np.uint32)
        b["dport"][:] = rng.integers(1, 65535, n)
        return b

    def pcap_replay(batch, count):
        """BASELINE cfg1 'IPv4-only 5-tuple pcap replay': frames through the
        C++ parser/batcher (the AF_XDP ingest path), not a numpy generator.
        Returns None (→ numpy fallback) if the shim isn't built."""
        import os
        import tempfile
        from cilium_tpu.shim.bindings import LIB_PATH
        if not os.path.exists(LIB_PATH):
            return None
        from cilium_tpu.shim.bindings import FlowShim
        from cilium_tpu.shim.pcap import replay_pcap, synthesize_pcap
        fd, path = tempfile.mkstemp(suffix=".pcap")
        os.close(fd)
        try:
            synthesize_pcap(path, batch * count)
            shim = FlowShim(batch_size=batch, timeout_us=0)
            shim.register_endpoint("192.168.0.10", 1)
            batches = replay_pcap(shim, path, batch, max_batches=count)
            shim.close()
        finally:
            os.unlink(path)
        for b in batches:
            raw = b.pop("_ep_raw")
            b.pop("_frame_idx")
            b["ep_slot"][:] = 0              # single endpoint at slot 0
            b["valid"] = raw != 0
        return batches

    gen.pcap_replay = pcap_replay
    return snap, gen, True  # v4_only


def build_config2(preset):
    """10k pod identities, 5k CNP port rules, mixed v4/v6 traffic."""
    from cilium_tpu.model.labels import Labels
    from cilium_tpu.model.rules import parse_rule
    ctx, repo = _ctx_repo()
    ep = _add_web_ep(ctx)
    n_ids = 2000 if preset == "smoke" else 10000
    n_rules = 1000 if preset == "smoke" else 5000
    groups = 200
    for i in range(n_ids):
        ident = ctx.allocator.allocate(
            Labels.parse([f"k8s:group=g{i % groups}", f"k8s:pod=p{i}"]))
        ctx.ipcache.upsert(f"172.{16 + (i >> 16)}.{(i >> 8) & 0xFF}.{i & 0xFF}/32",
                           ident.id)
        if i % 4 == 0:
            ctx.ipcache.upsert(f"2001:db8:{i >> 8:x}:{i & 0xFF:x}::1/128",
                               ident.id)
    rules = []
    for j in range(n_rules):
        rules.append(parse_rule({
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"group": f"g{j % groups}"}}],
                "toPorts": [{"ports": [
                    {"port": str(1000 + (j % 4000)), "protocol":
                     "TCP" if j % 3 else "UDP"}]}],
            }],
        }))
    repo.add(rules)
    snap = _compile(ctx, repo, [ep], 1 << (14 if preset == "smoke" else 18))

    def gen(rng, n):
        b = _base_batch(n, direction=1)
        i = rng.integers(0, n_ids, n)
        b["src"][:, 3] = (0xAC100000 + ((16 + (i >> 16)) - 16 << 24)
                          + ((i >> 8) & 0xFF) * 256 + (i & 0xFF)).astype(np.uint32)
        # real mixed v4/v6 (BASELINE config 2): identities with a v6 /128
        # (every 4th) send over v6 — ~25% of traffic walks the 16-level v6
        # LPM; the kernel compiles with v4_only=False
        v6 = (i % 4 == 0)
        b["is_v6"][v6] = True
        b["src"][v6, 0] = 0x20010DB8
        b["src"][v6, 1] = (((i[v6] >> 8) << 16) | (i[v6] & 0xFF)).astype(np.uint32)
        b["src"][v6, 2] = 0
        b["src"][v6, 3] = 1
        b["dst"][:, 3] = 0xC0A8000A
        b["sport"][:] = rng.integers(20000, 60000, n)
        # ~70% aimed at a port the identity's group actually allows
        # (group g allows ports {1000 + j%4000 : j ≡ g mod groups})
        k = rng.integers(0, max(1, n_rules // groups), n)
        aligned = 1000 + ((i % groups) + groups * k) % 4000
        b["dport"][:] = np.where(rng.random(n) < 0.7, aligned,
                                 rng.integers(1000, 5000, n))
        b["proto"][:] = np.where(rng.random(n) < 0.9, 6, 17)
        return b
    return snap, gen, False


def build_config3(preset):
    """100k CIDR prefixes (BGP-table-like) + ToServices, Zipf traffic."""
    from cilium_tpu.model.rules import parse_rule
    from cilium_tpu.model.services import Service
    ctx, repo = _ctx_repo()
    ep = _add_web_ep(ctx)
    n_prefix = 20000 if preset == "smoke" else 100000
    rng0 = np.random.default_rng(0)
    # one covering allow for half the space + direct ipcache prefix churn
    repo.add([parse_rule({
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "egress": [{"toCIDR": ["0.0.0.0/1"]}]})])
    ctx.services.upsert(Service(name="api", namespace="prod",
                                backends=("10.200.0.1", "10.200.0.2")))
    repo.add([parse_rule({
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "egress": [{"toServices": [{"k8sService": {
            "serviceName": "api", "namespace": "prod"}}]}]})])
    # the BGP-slice: prefixes straight into the ipcache (identity per /16
    # block to bound identity count)
    from cilium_tpu.model.identity import cidr_identity_labels
    for i in range(n_prefix):
        plen = int(rng0.choice([16, 20, 24], p=[0.2, 0.3, 0.5]))
        addr = int(rng0.integers(0x01000000, 0xDF000000)) & (0xFFFFFFFF << (32 - plen))
        prefix = f"{addr >> 24}.{(addr >> 16) & 0xFF}.{(addr >> 8) & 0xFF}.{addr & 0xFF}/{plen}"
        ident = ctx.allocator.allocate_cidr(f"{addr >> 24}.0.0.0/8")
        ctx.ipcache.upsert(prefix, ident.id)
    snap = _compile(ctx, repo, [ep], 1 << (14 if preset == "smoke" else 18))

    # Zipf-skewed destination pool
    pool_n = 1 << 16
    pool = ((rng0.integers(1, 220, pool_n) << 24)
            + rng0.integers(0, 1 << 24, pool_n)).astype(np.uint32)
    zipf_w = 1.0 / np.arange(1, pool_n + 1) ** 1.1
    zipf_p = zipf_w / zipf_w.sum()

    def gen(rng, n):
        b = _base_batch(n)
        b["dst"][:, 3] = rng.choice(pool, size=n, p=zipf_p)
        b["dport"][:] = rng.integers(1, 65535, n)
        return b
    return snap, gen, True


def build_config4(preset):
    """L7-lite: HTTP method/path-prefix matching via token tensors."""
    from cilium_tpu.model.rules import parse_rule
    ctx, repo = _ctx_repo()
    ep = _add_web_ep(ctx)
    n_rulesets = 50 if preset == "smoke" else 200
    rules = []
    for i in range(n_rulesets):
        rules.append(parse_rule({
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{"toPorts": [{
                "ports": [{"port": str(80 + i), "protocol": "TCP"}],
                "rules": {"http": [
                    {"method": "GET", "path": f"/api/v{i}"},
                    {"method": "POST", "path": f"/submit/{i}"},
                    {"path": f"/public/{i}"},
                ]},
            }]}],
        }))
    repo.add(rules)
    snap = _compile(ctx, repo, [ep], 1 << (14 if preset == "smoke" else 16))
    paths = [f"/api/v{i}/x".encode() for i in range(n_rulesets)] + \
            [b"/forbidden/zone", b"/public/7/asset.js"]
    path_arr = np.zeros((len(paths), 64), dtype=np.uint8)
    for i, p in enumerate(paths):
        path_arr[i, :len(p)] = np.frombuffer(p[:64], dtype=np.uint8)

    def gen(rng, n):
        b = _base_batch(n, direction=1)
        b["src"][:, 3] = rng.integers(0x0B000000, 0x0BFFFFFF, n).astype(np.uint32)
        b["dst"][:, 3] = 0xC0A8000A
        port_idx = rng.integers(0, n_rulesets, n)
        b["dport"][:] = 80 + port_idx
        b["tcp_flags"][:] = 0x10
        # ~70% requests aligned with their port's ruleset (GET /api/v{i});
        # the rest random (exercise the drop path)
        aligned = rng.random(n) < 0.7
        pi = np.where(aligned, port_idx, rng.integers(0, len(paths), n))
        b["http_method"][:] = np.where(aligned, 0, rng.integers(0, 2, n))
        b["http_path"][:] = path_arr[pi]
        return b
    return snap, gen, True


def _config5_world(preset):
    """The cfg5 control plane (50k-rule policy over 2k pod identities) —
    shared by the throughput bench and the update-latency bench."""
    from cilium_tpu.model.labels import Labels
    from cilium_tpu.model.rules import parse_rule
    ctx, repo = _ctx_repo()
    ep = _add_web_ep(ctx)
    n_ids = 500 if preset == "smoke" else 2000
    n_rules = 5000 if preset == "smoke" else 50000
    for i in range(n_ids):
        ident = ctx.allocator.allocate(Labels.parse([f"k8s:pod=p{i}"]))
        ctx.ipcache.upsert(f"172.{16 + (i >> 16)}.{(i >> 8) & 0xFF}.{i & 0xFF}/32",
                           ident.id)
    rules = []
    for j in range(n_rules):
        rules.append(parse_rule({
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"pod": f"p{j % n_ids}"}}],
                "toPorts": [{"ports": [
                    {"port": str(1024 + (j % 25000)), "protocol": "TCP"}]}],
            }],
        }))
    repo.add(rules)
    return ctx, repo, ep, n_ids, n_rules


def build_config5(preset):
    """Conntrack churn: 50k-rule policy, 1M concurrent flows, 10% new rate."""
    ctx, repo, ep, n_ids, n_rules = _config5_world(preset)
    cap = 1 << (16 if preset == "smoke" else 21)
    snap = _compile(ctx, repo, [ep], cap)

    n_flows = (1 << 14) if preset == "smoke" else 1_000_000
    rng0 = np.random.default_rng(1)
    flow_src = rng0.integers(0, n_ids, n_flows).astype(np.int64)
    flow_sport = rng0.integers(20000, 60000, n_flows).astype(np.int32)
    # dports drawn from the flow's identity's ALLOWED set so flows actually
    # establish and churn the CT (pod i allows {1024 + (i + n_ids*k) % 25000})
    k0 = rng0.integers(0, max(1, n_rules // n_ids), n_flows)
    flow_dport = (1024 + (flow_src + n_ids * k0) % 25000).astype(np.int32)

    def gen(rng, n):
        # 90% existing flows, 10% replaced with fresh ones (the churn)
        idx = rng.integers(0, n_flows, n)
        n_new = n // 10
        repl = idx[:n_new]
        flow_sport[repl] = rng.integers(20000, 60000, n_new)
        b = _base_batch(n, direction=1)
        i = flow_src[idx]
        b["src"][:, 3] = (0xAC100000 + ((i >> 8) & 0xFF) * 256
                          + (i & 0xFF)).astype(np.uint32)
        b["dst"][:, 3] = 0xC0A8000A
        b["sport"][:] = flow_sport[idx]
        b["dport"][:] = flow_dport[idx]
        b["tcp_flags"][:] = 0x10
        return b
    return snap, gen, True


def _base_batch(n, direction=0):
    from cilium_tpu.kernels.records import empty_batch
    b = empty_batch(n)
    b["src"][:, 2] = 0xFFFF
    b["dst"][:, 2] = 0xFFFF
    b["src"][:, 3] = 0xC0A8000A
    b["sport"][:] = 40000
    b["dport"][:] = 443
    b["proto"][:] = 6
    b["tcp_flags"][:] = 0x02
    b["direction"][:] = direction
    b["valid"][:] = True
    return b


def update_latency_bench(preset):
    """1-rule policy-update latency on the cfg5 world: full rebuild vs the
    incremental patch path (round-4 verdict item 2's 'done' metric; upstream
    analog: incremental policymap diffs vs endpoint regeneration)."""
    from cilium_tpu.compile.ct_layout import CTConfig
    from cilium_tpu.compile.incremental import IncrementalCompiler
    from cilium_tpu.compile.snapshot import build_snapshot
    from cilium_tpu.model.labels import Labels
    from cilium_tpu.model.rules import parse_rule

    ctx, repo, ep, n_ids, _n_rules = _config5_world(preset)
    ct_cfg = CTConfig(capacity=1 << 14)

    t0 = time.time()
    snap = build_snapshot(repo, ctx, [ep], ct_cfg)
    full_s = time.time() - t0
    t0 = time.time()
    inc = IncrementalCompiler(repo, ctx, [ep], snap)
    seed_s = time.time() - t0

    one = parse_rule({
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "ingress": [{
            "fromEndpoints": [{"matchLabels": {"pod": "p7"}}],
            "toPorts": [{"ports": [{"port": "4242", "protocol": "TCP"}]}]}]})
    object.__setattr__(one, "labels", Labels.parse(["k8s:bench=u1"]))

    t0 = time.time()
    repo.add([one])
    res = inc.try_update(ct_cfg)
    assert res is not None, f"update fell back: {inc.last_fallback}"
    add_s = time.time() - t0

    t0 = time.time()
    repo.delete_by_labels(Labels.parse(["k8s:bench=u1"]))
    res = inc.try_update(ct_cfg)
    assert res is not None, f"remove fell back: {inc.last_fallback}"
    remove_s = time.time() - t0

    return {
        "full_rebuild_ms": round(full_s * 1e3, 1),
        "incremental_seed_ms": round(seed_s * 1e3, 1),
        "rule_add_ms": round(add_s * 1e3, 2),
        "rule_remove_ms": round(remove_s * 1e3, 2),
        "speedup_vs_full": round(full_s / max(add_s, 1e-9), 1),
    }


#: BENCH_r05-era incremental-update reference (full cfg5 world, host
#: COW-copy path): what the ≥50x acceptance gate for the delta-patch
#: path is judged against. Override when re-baselining on other hardware.
REF_RULE_ADD_MS = float(os.environ.get(
    "CILIUM_TPU_BENCH_REF_RULE_ADD_MS", "619.5"))


def update_storm_bench(preset: str, updates: int = 0, traffic_batch: int = 512,
                       verbose: bool = False):
    """Live policy patching under pipelined traffic (ROADMAP item 3a).

    Builds the cfg5 control plane INSIDE an Engine (JITDatapath,
    incremental + delta-patch on, shadow auditor armed at sampling 1.0),
    keeps a feeder thread pushing conntrack-churn traffic through the
    ingestion pipeline the whole time, and storms rule adds/removes
    against warm geometry — the long-lived-daemon steady state where every
    update rides the sparse-delta scatter-apply path.

    Reported: ``rule_add_ms``/``rule_remove_ms`` p50+p99 (the full
    regenerate() wall time per update, host compile + device apply),
    the span split (``engine.regen.patch`` host compile,
    ``datapath.patch.apply`` device scatter enqueue) and
    ``device_ready_p50_ms`` (block-until-ready on the patched verdict
    under load). Parity: the auditor replays every finalized batch against
    the exact revision it classified under — ``audit.mismatched_rows``
    must be 0 (no batch classified under a torn update). A second phase
    re-runs the cfg5 churn loop with the overlapped device-side CT GC off
    vs armed and gates the throughput ratio.
    """
    import jax
    from cilium_tpu.model.labels import Labels
    from cilium_tpu.observe.trace import (CT_GC_SPAN, PATCH_APPLY_SPAN,
                                          TRACER)
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.engine import Engine

    if updates <= 0:
        updates = 40 if preset == "smoke" else 120
    n_ids = 500 if preset == "smoke" else 2000
    n_rules = 5000 if preset == "smoke" else 50000
    storm_pods = 8                     # warm split set the storm cycles
    TRACER.configure(sample_rate=1.0, capacity=1 << 16)
    TRACER.reset()

    cfg = DaemonConfig(ct_capacity=1 << 14, auto_regen=False,
                       batch_size=traffic_batch,
                       pipeline_flush_ms=1.0,
                       # one epoch ≈ 8 ticks: the production shape (chunks
                       # small relative to the table), scaled to the
                       # bench's CT capacity
                       ct_gc_chunk_rows=1 << 11,
                       audit_enabled=True, audit_sample_rate=1.0,
                       audit_pool_batches=64, flowlog_mode="none",
                       trace_sample_rate=1.0)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    eng.auditor.configure(sample_rate=1.0)

    # -- the cfg5 world, engine-resident ------------------------------------
    t0 = time.time()
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",), ep_id=1)
    for i in range(n_ids):
        ident = eng.ctx.allocator.allocate(
            Labels.parse([f"k8s:pod=p{i}"]))
        eng.ctx.ipcache.upsert(
            f"172.{16 + (i >> 16)}.{(i >> 8) & 0xFF}.{i & 0xFF}/32",
            ident.id)
    from cilium_tpu.model.rules import parse_rule
    base_rules = []
    for j in range(n_rules):
        base_rules.append(parse_rule({
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"pod": f"p{j % n_ids}"}}],
                "toPorts": [{"ports": [
                    {"port": str(1024 + (j % 25000)), "protocol": "TCP"}]}],
            }]}))
    eng.repo.add(base_rules)
    eng.regenerate()
    world_s = time.time() - t0

    def storm_docs(pod: int, port: int, label: str):
        return [{"endpointSelector": {"matchLabels": {"app": "web"}},
                 "labels": [label],
                 "ingress": [{
                     "fromEndpoints": [{"matchLabels":
                                        {"pod": f"p{pod}"}}],
                     "toPorts": [{"ports": [{"port": str(port),
                                             "protocol": "TCP"}]}]}]}]

    # warm: split each storm pod's class once (ports reuse existing
    # boundaries so no port-class splits ride along)
    storm_ports = [1024 + 7 * k for k in range(storm_pods)]
    for k in range(storm_pods):
        eng.replace_policy([f"k8s:storm=w{k}"],
                           storm_docs(k, storm_ports[k],
                                      f"k8s:storm=w{k}"))
        eng.regenerate()
    patch_base = dict(eng.datapath.patch_stats)

    # -- live traffic (the cfg5 churn stream through the pipeline) ----------
    rng = np.random.default_rng(9)

    def churn_batch(n):
        b = _base_batch(n, direction=1)
        i = rng.integers(0, n_ids, n)
        b["src"][:, 3] = (0xAC100000 + ((i >> 8) & 0xFF) * 256
                          + (i & 0xFF)).astype(np.uint32)
        b["dst"][:, 3] = 0xC0A8000A
        b["sport"][:] = rng.integers(20000, 60000, n)
        b["dport"][:] = (1024 + i % 25000).astype(np.int32)
        b["tcp_flags"][:] = 0x10
        return b

    stop_traffic = threading.Event()
    traffic_sent = [0]
    traffic_errors = [0]
    traffic_now = [50_000]

    def feeder():
        while not stop_traffic.is_set():
            traffic_now[0] += 1
            try:
                eng.submit(churn_batch(traffic_batch),
                           now=traffic_now[0], deadline_ms=0)
                traffic_sent[0] += 1
            except Exception:
                # counted AND gated below: a feeder that stops feeding
                # would make this an idle-engine benchmark lying about
                # "under live traffic"
                traffic_errors[0] += 1
                time.sleep(0.005)

    # warm the pipeline's device shapes before timing updates
    eng.submit(churn_batch(traffic_batch), now=traffic_now[0]).result(
        timeout=120)
    th = threading.Thread(target=feeder, daemon=True, name="storm-feeder")
    th.start()

    # -- the storm ----------------------------------------------------------
    add_ms, remove_ms, ready_ms = [], [], []
    try:
        for u in range(updates):
            k = u % storm_pods
            label = f"k8s:storm=w{k}"
            adding = (u // storm_pods) % 2 == 1
            body = storm_docs(k, storm_ports[k], label) if adding else None
            t1 = time.time()
            eng.replace_policy([label], body)
            eng.regenerate()
            dt = (time.time() - t1) * 1e3
            (add_ms if adding else remove_ms).append(dt)
            if u % 8 == 0:
                t2 = time.time()
                jax.block_until_ready(eng.active.tensors["verdict"])
                ready_ms.append((time.time() - t2) * 1e3)
    finally:
        stop_traffic.set()
        th.join(timeout=10)
    drained = eng.drain(timeout=300)

    # -- parity: drain the audit pool at sampling 1.0 -----------------------
    for _ in range(400):
        step = eng.audit_step(budget=128)
        if not step or (not step.get("replayed")
                        and not step.get("pending")):
            break
    audit = eng.auditor.stats()
    patch_stats = {k: v - patch_base.get(k, 0)
                   for k, v in eng.datapath.patch_stats.items()}

    spans = TRACER.summary()
    span_keys = ("engine.regen.patch", "engine.regen.place",
                 PATCH_APPLY_SPAN)
    stage_split = {k: spans[k] for k in span_keys if k in spans}

    def _p(vals, q):
        return round(float(np.percentile(np.asarray(vals), q)), 3) \
            if vals else 0.0

    # -- phase 2: overlapped CT GC on/off over the churn stream -------------
    # cadence: one chunk tick per 16 buckets ≈ 40ms of traffic on this rig —
    # still ~50x the production duty cycle (ct_gc_interval_s=2.0), so the
    # measured overhead upper-bounds the real one. The sweep program is
    # warmed first: its one-time jit compile is not a per-tick cost.
    gc_doc = {}
    gc_batches = 32 if preset == "smoke" else 64
    eng.sweep_step(now=traffic_now[0])      # warm the chunk-sweep jit
    eng.sweep_step(now=traffic_now[0])
    for mode in ("off", "on"):
        tps = []
        for _w in range(3):
            t1 = time.time()
            for i in range(gc_batches):
                traffic_now[0] += 1
                eng.submit(churn_batch(traffic_batch),
                           now=traffic_now[0])
                if mode == "on" and i % 16 == 0:
                    eng.sweep_step(now=traffic_now[0])
            eng.drain(timeout=300)
            tps.append(gc_batches * traffic_batch
                       / max(time.time() - t1, 1e-9))
        gc_doc[f"gc_{mode}_flows_per_sec"] = round(
            float(np.percentile(tps, 50)), 1)
    gc_ratio = gc_doc["gc_on_flows_per_sec"] \
        / max(gc_doc["gc_off_flows_per_sec"], 1e-9)
    gc_doc.update({
        "gc_on_vs_off_ratio": round(gc_ratio, 4),
        "reclaimed_total": getattr(eng.datapath, "_gc_reclaimed_total", 0),
        "gc_span": TRACER.summary().get(CT_GC_SPAN),
    })

    eng.stop()

    rule_add_p50 = _p(add_ms, 50)
    apply_span = stage_split.get(PATCH_APPLY_SPAN, {})
    gate_reasons = []
    if audit["mismatched_rows"]:
        gate_reasons.append(
            f"parity: {audit['mismatched_rows']} mismatched rows at "
            "sampling 1.0")
    if patch_stats.get("patch_delta", 0) < updates // 4:
        gate_reasons.append(
            f"delta path underused: {patch_stats.get('patch_delta', 0)} "
            f"delta patches over {updates} updates")
    if audit["checked_rows"] == 0:
        gate_reasons.append("auditor checked nothing")
    if traffic_sent[0] < max(4, updates // 4):
        gate_reasons.append(
            f"live-traffic floor missed: only {traffic_sent[0]} batches "
            f"fed during {updates} updates ({traffic_errors[0]} submit "
            "errors) — the storm measured an idle engine")
    if gc_ratio < 1.0 / BENCH_NOISE_FACTOR:
        gate_reasons.append(
            f"CT GC regressed churn throughput: ratio {gc_ratio:.3f}")
    if not add_ms:
        gate_reasons.append(
            f"no rule adds measured over {updates} updates (the headline "
            "metric never ran — raise --updates)")
    elif REF_RULE_ADD_MS / rule_add_p50 < 50:
        gate_reasons.append(
            f"rule_add_ms {rule_add_p50} not ≥50x under the "
            f"{REF_RULE_ADD_MS}ms reference")
    if patch_stats.get("patch_scatter_errors", 0):
        gate_reasons.append(
            f"{patch_stats['patch_scatter_errors']} scatter failures "
            "self-healed by full uploads during the storm")

    if verbose:
        print(f"# update-storm preset={preset} updates={updates} "
              f"world={world_s:.1f}s traffic_batches={traffic_sent[0]} "
              f"add p50={rule_add_p50}ms device-apply "
              f"p50={apply_span.get('p50_ms')}ms "
              f"audit checked={audit['checked_rows']} "
              f"mism={audit['mismatched_rows']} gc_ratio={gc_ratio:.3f}",
              file=sys.stderr)

    return {
        "metric": "live_update_storm_cfg5",
        "value": rule_add_p50,
        "unit": "ms",
        # higher-is-better speedup vs the BENCH_r05-era reference
        "vs_baseline": round(REF_RULE_ADD_MS / rule_add_p50, 1)
        if add_ms else 0.0,
        "baseline_rule_add_ms": REF_RULE_ADD_MS,
        "rule_add_ms": rule_add_p50,
        "rule_add_p99_ms": _p(add_ms, 99),
        "rule_remove_ms": _p(remove_ms, 50),
        "rule_remove_p99_ms": _p(remove_ms, 99),
        "device_apply_p50_ms": apply_span.get("p50_ms", 0.0),
        "device_apply_p99_ms": apply_span.get("p99_ms", 0.0),
        "device_ready_p50_ms": _p(ready_ms, 50),
        "updates": updates,
        "traffic_batches": traffic_sent[0],
        "traffic_errors": traffic_errors[0],
        "traffic_batch": traffic_batch,
        "drained": bool(drained),
        "preset": preset,
        "stage_split": stage_split,
        "patch_stats": patch_stats,
        "audit": {
            "checked_rows": audit["checked_rows"],
            "checked_batches": audit["checked_batches"],
            "mismatched_rows": audit["mismatched_rows"],
            "skipped_batches": audit["skipped_batches"],
        },
        "ct_gc": gc_doc,
        "storm_gate": {
            "failed": bool(gate_reasons),
            **({"reasons": gate_reasons} if gate_reasons else {}),
        },
    }


def ddos_bench(preset: str, verbose: bool = False, batch: int = 256):
    """cfg6: adversarial drop-storm survival over the live pipelined
    engine (ROADMAP item 4d — the ``bpf_xdp.c`` mitigation role with real
    drop-heavy traffic, not fault-injected hangs).

    A flood of randomized-source SYNs ramps against a small CT table: a
    40% junk slice (unknown identities → POLICY drops, the drop storm) and
    a 60% allowed-SYN slice (an open port reachable from a /8 — the CT
    filler that saturates the table), while a fixed population of
    established legitimate flows keeps serving through the same pipeline.
    The bench plays the shim feeder's role at "harvest": flood batches
    carry ``_prio=1``, legit batches ``_prio=0``, and once the overload
    ladder commands SHED-NEW the flood is dropped at harvest
    (shim/feeder.shed_new_rows) without ever being submitted. Logical time
    drives the engine's overload and ct-gc controllers deterministically
    (manual ``overload_step``/``sweep_step`` ticks — no wall-clock
    flakiness), with the parity auditor armed at sampling 1.0 throughout.

    Reported: established-flow survival rate, legit-slice e2e p50/p99,
    the CT occupancy trajectory (saturation → emergency-GC-bounded plateau
    → post-storm recovery), ladder state dwell times, eviction/insert-fail
    counters, and pre/storm/post throughput. ``ddos_gate`` fails the
    artifact (exit 4) on: survival < 99%, any parity mismatch (or nothing
    checked), the ladder never reaching SHED-NEW, occupancy never
    pressuring / not stabilizing below 1.0 / not recovering below
    ``ct_pressure_low``, no evictions (the table never actually
    saturated), or post-storm throughput collapsing past 20% of
    pre-storm."""
    from cilium_tpu.pipeline.guard import OVERLOAD_SHED_NEW
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.engine import Engine
    from cilium_tpu.shim.feeder import shed_new_rows

    smoke = preset == "smoke"
    flood_per_iter = 11 if smoke else 16
    hold_iters = 8 if smoke else 20        # iters to hold after SHED-NEW
    max_iters = 48 if smoke else 120
    n_legit = batch                        # one direct-dispatch bucket
    cap = 1 << 13
    cfg = DaemonConfig(
        ct_capacity=cap, auto_regen=False, batch_size=batch,
        pipeline_flush_ms=0.5, pipeline_queue_batches=16,
        pipeline_block_timeout_s=0.05,
        audit_enabled=True, audit_sample_rate=1.0, audit_pool_batches=64,
        flowlog_mode="none",
        ct_gc_chunk_rows=1 << 10, ct_gc_emergency_chunks=8,
        ct_gc_emergency_ttl_slash_s=56,
        ct_pressure_high=0.8, ct_pressure_low=0.5,
        overload_up_ticks=1, overload_down_ticks=4,
        # the bench's iteration cadence is wall-fast (logical seconds tick
        # faster than real ones): judge the shed rate against a threshold
        # the flood's admission-drop + deadline-shed stream actually
        # crosses on this rig
        overload_shed_rate_high=15.0, overload_shed_rate_low=2.0,
        overload_interval_s=0.1)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    eng.auditor.configure(sample_rate=1.0)
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",), ep_id=1)
    # the cfg6 policy world: legit clients (172.16/16) on 443, an open
    # port 80 reachable from 10/8 (the flood's CT-filler surface), ingress
    # enforced — every other source drops (the storm)
    eng.apply_policy([
        {"endpointSelector": {"matchLabels": {"app": "web"}},
         "ingress": [{"fromCIDR": ["172.16.0.0/16"],
                      "toPorts": [{"ports": [
                          {"port": "443", "protocol": "TCP"}]}]}]},
        {"endpointSelector": {"matchLabels": {"app": "web"}},
         "ingress": [{"fromCIDR": ["10.0.0.0/8"],
                      "toPorts": [{"ports": [
                          {"port": "80", "protocol": "TCP"}]}]}]},
    ])
    eng.regenerate()

    class _BenchHarvester:
        """The shim feeder's role, played by the bench: carries the
        harvest-shed counter the overload controller folds into its shed
        signal, and receives the ladder state like the real feeder."""
        prio_shed_rows = 0
        prio_shed_batches = 0
        level = 0

        def set_overload_state(self, level):
            self.level = int(level)

        def stats(self):
            return {"alive": True, "pending": 0, "pool_free": 0,
                    "prio_shed_rows": self.prio_shed_rows,
                    "prio_shed_batches": self.prio_shed_batches,
                    "overload_level": self.level}

        def stop(self, timeout=0.0):
            pass

    harvester = _BenchHarvester()
    eng._feeder = harvester

    rng = np.random.default_rng(5)

    def legit_batch():
        b = _base_batch(n_legit, direction=1)
        b["src"][:, 3] = (0xAC100000
                          + np.arange(n_legit) % 250 + 1
                          + ((np.arange(n_legit) // 250) << 8)
                          ).astype(np.uint32)
        b["dst"][:, 3] = 0xC0A8000A
        b["sport"][:] = 40000 + np.arange(n_legit)
        b["dport"][:] = 443
        b["tcp_flags"][:] = 0x10     # ACK → SEEN_NON_SYN → protected class
        b["_prio"] = np.zeros((n_legit,), np.int8)
        return b

    def flood_batch():
        b = _base_batch(batch, direction=1)
        junk = rng.random(batch) < 0.4
        b["src"][:, 3] = np.where(
            junk,
            0xCB000000 + rng.integers(0, 1 << 20, batch),   # 203.x → world
            0x0A000000 + rng.integers(1, 1 << 24, batch),   # 10/8 → open 80
        ).astype(np.uint32)
        b["dst"][:, 3] = 0xC0A8000A
        b["sport"][:] = rng.integers(1024, 65535, batch)
        b["dport"][:] = np.where(junk, rng.integers(1, 65535, batch), 80)
        b["tcp_flags"][:] = 0x02                            # SYN storm
        b["_prio"] = np.ones((batch,), np.int8)
        return b

    L = [50_000]                      # logical clock (seconds)
    survival = {"rows": 0, "allowed": 0}
    legit_lat_ms: list = []
    pending_legit: list = []

    def submit_legit():
        t0 = time.monotonic()
        try:
            pending_legit.append((eng.submit(legit_batch(), now=L[0]), t0))
        except Exception:
            survival["rows"] += n_legit       # whole batch lost = 0 allowed

    def pump_legit(block_s=None):
        """Account resolved legit tickets; ``block_s`` resolves everything
        (end of a phase), None sweeps only already-done tickets — the
        storm loop must never serialize behind its own victims."""
        rest = []
        for tk, t0 in pending_legit:
            if block_s is None and not tk.done():
                rest.append((tk, t0))
                continue
            try:
                out = tk.result(timeout=block_s if block_s is not None
                                else 0)
                survival["allowed"] += int(np.asarray(out["allow"]).sum())
            except Exception:
                pass
            survival["rows"] += n_legit
            legit_lat_ms.append((time.monotonic() - t0) * 1e3)
        pending_legit[:] = rest

    def run_legit(count, timeout=120.0):
        for _ in range(count):
            submit_legit()
        pump_legit(block_s=timeout)

    def fps_of(count):
        t0 = time.monotonic()
        run_legit(count)
        return count * n_legit / max(time.monotonic() - t0, 1e-9)

    # -- phase 0: establish + pre-storm throughput --------------------------
    run_legit(2)                      # warm/compile + create entries
    L[0] += 1
    run_legit(2)                      # revisit: flows now ESTABLISHED
    pre_rows0 = survival["rows"]
    legit_lat_ms.clear()              # cold-compile warmup is not latency
    pre_fps = fps_of(12 if smoke else 24)
    eng.overload_step()

    # -- phase 0b: ledger-overhead attestation (the PR 3 form) --------------
    # D/A/D/A interleaved windows (disarmed / armed-with-polling) for the
    # fps evidence, with the GATED number measured directly: wall time
    # spent inside resource_step as a fraction of the armed windows'
    # serving time. The armed cadence — one full ledger sweep per
    # dozen-batch window, the storm loop's own per-iteration rhythm — is
    # still ~250x denser per served row than the production controller's
    # resource_interval_s, so a pass bounds the real overhead from far
    # above. (The fps delta alone flakes: window-to-window variance on a
    # shared CPU rig is several percent, an order above the poll cost —
    # the ratio-of-measured-times form is what "<2% of armed serving
    # time" actually states.)
    att_w = 12 if smoke else 24
    att_fps = {"off": [], "on": []}
    att_poll_s = att_armed_s = 0.0
    for mode in ("off", "on", "off", "on"):
        t0 = time.monotonic()
        for i in range(att_w):
            run_legit(1)
            if mode == "on" and i % 12 == 11:
                p0 = time.monotonic()
                eng.resource_step(now=float(L[0]))
                att_poll_s += time.monotonic() - p0
        dt = max(time.monotonic() - t0, 1e-9)
        att_fps[mode].append(att_w * n_legit / dt)
        if mode == "on":
            att_armed_s += dt
    att_off = sum(att_fps["off"]) / len(att_fps["off"])
    att_on = sum(att_fps["on"]) / len(att_fps["on"])
    att_overhead_pct = 100.0 * att_poll_s / max(att_armed_s, 1e-9)
    pressure_attestation = {
        "fps_disarmed": round(att_off, 1),
        "fps_armed": round(att_on, 1),
        "fps_delta_pct": round(
            max(0.0, (1.0 - att_on / max(att_off, 1e-9)) * 100), 2),
        "poll_s": round(att_poll_s, 4),
        "armed_serving_s": round(att_armed_s, 4),
        "overhead_pct": round(att_overhead_pct, 2),
        "budget_pct": 2.0,
        "ok": att_overhead_pct < 2.0,
    }

    # per-iteration ledger polling through the storm (logical clock →
    # deterministic ETA math): the cfg6 acceptance gates — the CT resource
    # row must track the ct_occupancy gauge EXACTLY, and the
    # time-to-exhaustion forecast must fire before the ladder reaches
    # SHED-NEW (forecast-then-shed is the ledger doing its job; shed
    # without forecast means the forecast is useless under attack)
    ct_track_mismatches = 0
    forecast_iter = shed_new_iter = None

    def poll_ledger(it_now: int):
        nonlocal ct_track_mismatches, forecast_iter
        rep = eng.resource_step(now=float(L[0]))
        row = rep["resources"].get("ct_table")
        gauge = float(eng.metrics.gauges.get("ct_occupancy", 0.0))
        if row is None or row["pressure"] != gauge:
            ct_track_mismatches += 1
        if forecast_iter is None and row is not None and row["forecast"]:
            forecast_iter = it_now
        return rep

    # -- phase 1a: CT saturation burst --------------------------------------
    # the flood fully processed (drained per iteration): the table fills
    # past ct_pressure_high, emergency GC arms and bounds occupancy, tail
    # evictions + CT_FULL fails happen under the auditor — the
    # table-exhaustion half of the scenario, before admission pressure
    # starts refusing the flood at the door
    occ_trajectory = []
    flood_sent = flood_dropped = flood_harvest_shed = 0
    max_level = 0
    it = 0
    storm_t0 = time.monotonic()
    storm_rows = 0
    sat_hold = 0
    while it < max_iters // 2 and sat_hold < 4:
        it += 1
        L[0] += 1
        for _ in range(flood_per_iter):
            try:
                tk = eng.submit(flood_batch(), now=L[0], deadline_ms=0)
                flood_sent += 1
            except Exception:
                flood_dropped += 1
            storm_rows += batch
        run_legit(1, timeout=120.0)   # drain: device-bound, not ingest-bound
        storm_rows += n_legit
        st = eng.overload_step()
        max_level = max(max_level, st["level"])
        eng.sweep_step(now=L[0])
        eng.audit_step(budget=16)
        poll_ledger(it)
        occ = float(eng.metrics.gauges.get("ct_occupancy", 0.0))
        occ_trajectory.append((it, occ))
        if occ >= cfg.ct_pressure_high:
            sat_hold += 1             # hold a few iters at the plateau

    # -- phase 1b: the ingest storm -----------------------------------------
    # flood submitted faster than the device drains: queue + shed signals
    # light, the ladder escalates PRESSURE → OVERLOAD → SHED-NEW, and the
    # bench plays the feeder's harvest-time SHED-NEW once commanded
    shed_new_iters = 0
    while it < max_iters and shed_new_iters < hold_iters:
        it += 1
        L[0] += 1
        level = harvester.level
        max_level = max(max_level, level)
        for _ in range(flood_per_iter):
            fb = flood_batch()
            storm_rows += batch
            if level >= OVERLOAD_SHED_NEW:
                # the feeder's SHED-NEW behavior: drop verdicts at
                # harvest, nothing submitted — rx-ring relief
                shed = shed_new_rows(fb)
                harvester.prio_shed_rows += shed
                harvester.prio_shed_batches += 1
                flood_harvest_shed += shed
                continue
            try:
                tk = eng.submit(fb, now=L[0], deadline_ms=200)
                if tk.dropped:
                    flood_dropped += 1
                else:
                    flood_sent += 1
            except Exception:
                flood_dropped += 1
        submit_legit()
        pump_legit()                  # non-blocking: backlog must build
        storm_rows += n_legit
        st = eng.overload_step()
        if st["level"] >= OVERLOAD_SHED_NEW:
            shed_new_iters += 1
            if shed_new_iter is None:
                shed_new_iter = it
        eng.sweep_step(now=L[0])
        eng.audit_step(budget=16)
        poll_ledger(it)
        occ_trajectory.append(
            (it, float(eng.metrics.gauges.get("ct_occupancy", 0.0))))
    pump_legit(block_s=120.0)         # storm stragglers resolve now
    storm_s = max(time.monotonic() - storm_t0, 1e-9)
    storm_fps = storm_rows / storm_s
    occ_peak = max((o for _i, o in occ_trajectory), default=0.0)
    occ_late = occ_trajectory[-1][1] if occ_trajectory else 0.0

    # -- phase 2: recovery --------------------------------------------------
    recovered_level = None
    for _r in range(80):
        L[0] += 2
        run_legit(1, timeout=60.0)
        st = eng.overload_step()
        eng.sweep_step(now=L[0])
        recovered_level = st["level"]
        occ = float(eng.metrics.gauges.get("ct_occupancy", 0.0))
        if recovered_level == 0 and occ <= cfg.ct_pressure_low:
            break
    occ_final = float(eng.metrics.gauges.get("ct_occupancy", 0.0))
    post_fps = fps_of(12 if smoke else 24)
    ladder = eng.overload_status() or {}
    # final ledger sweep: the artifact carries every resource's high-water
    # through the storm + the device-memory ledger (ROADMAP item 6's
    # hardware-truth landing zone — re-baselined per-group on a real v5e)
    final_rep = eng.resource_step(now=float(L[0]))
    resource_high_water = {
        r: d["high_water"] for r, d in final_rep["resources"].items()}
    hbm_ledger = eng.datapath.hbm_ledger() \
        if hasattr(eng.datapath, "hbm_ledger") else None

    # -- drain + audit ------------------------------------------------------
    drained = eng.drain(timeout=120)
    for _ in range(200):
        step = eng.audit_step(budget=128)
        if not step or (not step.get("replayed")
                        and not step.get("pending")):
            break
    audit = eng.auditor.stats()
    evicted = eng.metrics.ct_evicted
    insert_fail = eng.metrics.insert_fail
    by = eng.metrics.by_reason_dir.reshape(256, 2)
    eng._feeder = None                # the harvester is not a real feeder
    eng.stop()

    survival_rate = survival["allowed"] / max(1, survival["rows"])
    legit_p50 = round(float(np.percentile(legit_lat_ms, 50)), 3) \
        if legit_lat_ms else 0.0
    legit_p99 = round(float(np.percentile(legit_lat_ms, 99)), 3) \
        if legit_lat_ms else 0.0
    post_ratio = post_fps / max(pre_fps, 1e-9)

    gate_reasons = []
    if survival_rate < 0.99:
        gate_reasons.append(
            f"established-flow survival {survival_rate:.4f} < 0.99")
    if audit["mismatched_rows"]:
        gate_reasons.append(
            f"parity: {audit['mismatched_rows']} mismatched rows at "
            "sampling 1.0")
    if audit["checked_rows"] == 0:
        gate_reasons.append("auditor checked nothing")
    if max_level < OVERLOAD_SHED_NEW:
        gate_reasons.append(
            f"ladder never reached SHED-NEW (max level {max_level})")
    if occ_peak < cfg.ct_pressure_high:
        gate_reasons.append(
            f"flood never pressured the CT (peak occupancy {occ_peak:.3f} "
            f"< {cfg.ct_pressure_high})")
    if occ_late >= 0.995:
        gate_reasons.append(
            f"emergency GC failed to bound occupancy ({occ_late:.3f} at "
            "storm end)")
    if occ_final > cfg.ct_pressure_low:
        gate_reasons.append(
            f"occupancy did not recover below ct_pressure_low "
            f"({occ_final:.3f} > {cfg.ct_pressure_low})")
    if not evicted:
        gate_reasons.append("no CT tail-evictions — the table never "
                            "actually saturated")
    if post_ratio < 1.0 / 1.2:
        gate_reasons.append(
            f"post-storm throughput collapsed: {post_fps:.0f} vs "
            f"pre-storm {pre_fps:.0f} (ratio {post_ratio:.3f} < 1/1.2)")
    if ct_track_mismatches:
        gate_reasons.append(
            f"resource ledger: ct_table pressure diverged from the "
            f"ct_occupancy gauge on {ct_track_mismatches} poll(s)")
    if forecast_iter is None:
        gate_reasons.append(
            "resource ledger: time-to-exhaustion never fired for ct_table")
    elif shed_new_iter is not None and forecast_iter >= shed_new_iter:
        gate_reasons.append(
            f"resource ledger: forecast fired at iter {forecast_iter}, "
            f"after SHED-NEW at iter {shed_new_iter}")
    if not pressure_attestation["ok"]:
        gate_reasons.append(
            f"ledger polling overhead {att_overhead_pct:.2f}% > 2% of "
            "armed serving time")

    if verbose:
        print(f"# ddos preset={preset} iters={it} survival="
              f"{survival_rate:.4f} max_level={max_level} "
              f"occ peak/late/final={occ_peak:.3f}/{occ_late:.3f}/"
              f"{occ_final:.3f} evicted={evicted} ct_full_fails="
              f"{insert_fail} audit={audit['checked_rows']}/"
              f"{audit['mismatched_rows']} fps pre/storm/post="
              f"{pre_fps:.0f}/{storm_fps:.0f}/{post_fps:.0f}",
              file=sys.stderr)

    return {
        "metric": "ddos_drop_storm_cfg6",
        "value": round(survival_rate, 6),
        "unit": "established_flow_survival",
        "vs_baseline": round(survival_rate / 0.99, 4),
        "survival_rate": round(survival_rate, 6),
        "legit_rows": survival["rows"],
        "legit_allowed": survival["allowed"],
        "legit_e2e_p50_ms": legit_p50,
        "legit_e2e_p99_ms": legit_p99,
        "preset": preset,
        "batch": batch,
        "storm_iters": it,
        "flood": {
            "batches_submitted": flood_sent,
            "batches_rejected": flood_dropped,
            "rows_harvest_shed": flood_harvest_shed,
            "per_iter": flood_per_iter,
        },
        "ladder": {
            "max_level": max_level,
            "recovered_level": recovered_level,
            "dwell_s": ladder.get("dwell_s"),
            "transitions": ladder.get("transitions"),
            "trail": (ladder.get("trail") or [])[-8:],
        },
        "ct": {
            "capacity": cap,
            "occupancy_peak": round(occ_peak, 4),
            "occupancy_storm_end": round(occ_late, 4),
            "occupancy_final": round(occ_final, 4),
            "evicted_total": int(evicted),
            "insert_fail_total": int(insert_fail),
            "trajectory": [(i, round(o, 4)) for i, o in
                           occ_trajectory[:: max(1, len(occ_trajectory)
                                                 // 32)]],
        },
        "drops_by_reason": {
            str(int(r)): int(by[r].sum())
            for r in np.nonzero(by.sum(1))[0] if r != 0},
        "throughput": {
            "pre_storm_fps": round(pre_fps, 1),
            "storm_fps": round(storm_fps, 1),
            "post_storm_fps": round(post_fps, 1),
            "post_vs_pre_ratio": round(post_ratio, 4),
        },
        "audit": {
            "checked_rows": audit["checked_rows"],
            "checked_batches": audit["checked_batches"],
            "mismatched_rows": audit["mismatched_rows"],
            "skipped_batches": audit["skipped_batches"],
        },
        "pre_storm_rows": pre_rows0,
        "drained": bool(drained),
        "resources": {
            "registered": len(final_rep["resources"]),
            "high_water": resource_high_water,
            "ct_trajectory_exact": ct_track_mismatches == 0,
            "forecast_iter": forecast_iter,
            "shed_new_iter": shed_new_iter,
            "forecasts_total": final_rep["forecasts_total"],
            "exhaustions_total": final_rep["exhaustions_total"],
        },
        "hbm_ledger": hbm_ledger,
        "pressure_attestation": pressure_attestation,
        "ddos_gate": {
            "failed": bool(gate_reasons),
            **({"reasons": gate_reasons} if gate_reasons else {}),
        },
    }


def tenants_bench(preset: str, verbose: bool = False, batch: int = 256):
    """cfg8: mixed-tenant isolation under a noisy neighbor (ROADMAP item
    4 — multi-tenant QoS over the live pipelined engine).

    Three tenants share one pipeline: ``gold`` (weight 4, latency lane),
    ``silver`` (weight 2), and ``bulk`` (weight 1, occupancy-capped) —
    the noisy neighbor, replaying cfg6's randomized-source SYN storm
    with ``_tenant`` stamped at "harvest" the way the shim feeder's
    compiled LUT would. Three phases:

    - **lane baseline**: unloaded gold lane probes (small always-armed
      bucket, bypassing deadline microbatching) establish the e2e p99
      the loaded gate is judged against.
    - **isolation**: bulk floods at cfg6 rates while gold (lane probes)
      and silver (steady established-flow batches) keep serving.
      Victims must survive >= 99% and the loaded lane p99 must stay
      within 2x the unloaded baseline plus a head-of-line allowance for
      the committed bulk units a lane batch cannot preempt (the
      in-flight dispatches plus the staged-ahead batch, each costed at
      2x its unloaded round-trip for load inflation — µs of slack on a
      real TPU, the dominant term on the CPU smoke rig), with a small
      absolute floor against scheduler jitter.
    - **share convergence**: all three tenants push saturating backlogs
      through the admission queue for a wall-clock window; the DRR
      scheduler's per-tenant admitted-row shares must converge to the
      4:2:1 weights — the flooder confined to within [0.5x, 1.5x] of
      its 1/7 share.

    The parity auditor rides at sampling 1.0 throughout (QoS reorders
    batches, never rows — verdicts stay bit-identical). ``qos_gate``
    fails the artifact (exit 4) on: victim survival < 99%, lane p99
    past budget, the flooder's share escaping its weight band, any
    parity mismatch (or nothing checked), or an unclean drain."""
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.engine import Engine

    smoke = preset == "smoke"
    lane_rows = 32                      # well under the lane bucket (64)
    flood_per_iter = 6 if smoke else 10
    iso_iters = 24 if smoke else 60
    share_window_s = 3.0 if smoke else 8.0
    lane_floor_ms = 2.0                 # absolute floor on the lane budget
    cfg = DaemonConfig(
        ct_capacity=1 << 13, auto_regen=False, batch_size=batch,
        # generous flush deadline: bulk microbatching coalesces while the
        # lane's immediate flush is what keeps gold fast — the contrast
        # the lane gate actually measures
        pipeline_flush_ms=5.0, pipeline_queue_batches=16,
        pipeline_block_timeout_s=0.05,
        # latency-biased serving profile: one batch in flight keeps the
        # lane's head-of-line wait to a single bulk dispatch — the profile
        # a lane tenant's SLO would be sold against
        pipeline_inflight=1,
        audit_enabled=True, audit_sample_rate=1.0, audit_pool_batches=64,
        flowlog_mode="none",
        qos_enabled=True,
        # the flooder is capped below the queue so victims always have
        # admission headroom — the occupancy-cap half of isolation
        qos_tenants="gold=4:lane,silver=2,bulk=1:cap=10",
        qos_lane_bucket=64,
        overload_interval_s=0.1)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    eng.auditor.configure(sample_rate=1.0)
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",), ep_id=1)
    # the cfg6 policy world: victims (172.16/16) on 443, an open port 80
    # reachable from 10/8 (the flood's allowed slice), ingress enforced
    eng.apply_policy([
        {"endpointSelector": {"matchLabels": {"app": "web"}},
         "ingress": [{"fromCIDR": ["172.16.0.0/16"],
                      "toPorts": [{"ports": [
                          {"port": "443", "protocol": "TCP"}]}]}]},
        {"endpointSelector": {"matchLabels": {"app": "web"}},
         "ingress": [{"fromCIDR": ["10.0.0.0/8"],
                      "toPorts": [{"ports": [
                          {"port": "80", "protocol": "TCP"}]}]}]},
    ])
    eng.regenerate()
    pl = eng.start_pipeline()
    tid_of = {name: tid for tid, name in eng.qos.tenants().items()}

    rng = np.random.default_rng(8)

    def victim_batch(tenant, n, sport_base):
        b = _base_batch(n, direction=1)
        b["src"][:, 3] = (0xAC100000
                          + np.arange(n) % 250 + 1
                          + ((np.arange(n) // 250) << 8)
                          ).astype(np.uint32)
        b["dst"][:, 3] = 0xC0A8000A
        b["sport"][:] = sport_base + np.arange(n)
        b["dport"][:] = 443
        b["tcp_flags"][:] = 0x10     # ACK → SEEN_NON_SYN → protected class
        b["_prio"] = np.zeros((n,), np.int8)
        b["_tenant"] = np.full((n,), tid_of[tenant], np.int32)
        return b

    def flood_batch(tenant="bulk"):
        b = _base_batch(batch, direction=1)
        junk = rng.random(batch) < 0.4
        b["src"][:, 3] = np.where(
            junk,
            0xCB000000 + rng.integers(0, 1 << 20, batch),   # 203.x → world
            0x0A000000 + rng.integers(1, 1 << 24, batch),   # 10/8 → open 80
        ).astype(np.uint32)
        b["dst"][:, 3] = 0xC0A8000A
        b["sport"][:] = rng.integers(1024, 65535, batch)
        b["dport"][:] = np.where(junk, rng.integers(1, 65535, batch), 80)
        b["tcp_flags"][:] = 0x02                            # SYN storm
        b["_prio"] = np.ones((batch,), np.int8)
        b["_tenant"] = np.full((batch,), tid_of[tenant], np.int32)
        return b

    L = [50_000]                      # logical clock (seconds)
    survival = {"gold": {"rows": 0, "allowed": 0},
                "silver": {"rows": 0, "allowed": 0}}
    pending: list = []                # (ticket, tenant, rows)

    def pump(block_s=None):
        rest = []
        for tk, tenant, rows in pending:
            if block_s is None and not tk.done():
                rest.append((tk, tenant, rows))
                continue
            try:
                out = tk.result(timeout=block_s if block_s is not None
                                else 0)
                survival[tenant]["allowed"] += \
                    int(np.asarray(out["allow"]).sum())
            except Exception:
                pass
            survival[tenant]["rows"] += rows
        pending[:] = rest

    def submit_victim(tenant, n, sport_base):
        try:
            pending.append((eng.submit(victim_batch(tenant, n, sport_base),
                                       now=L[0]), tenant, n))
        except Exception:
            survival[tenant]["rows"] += n     # whole batch lost

    def lane_probe(record):
        """One blocking gold lane round-trip: small batch → immediate
        lane flush → result. The victim's latency-sensitive traffic."""
        t0 = time.monotonic()
        try:
            tk = eng.submit(victim_batch("gold", lane_rows, 30000),
                            now=L[0])
            out = tk.result(timeout=60.0)
            record.append((time.monotonic() - t0) * 1e3)
            survival["gold"]["allowed"] += \
                int(np.asarray(out["allow"]).sum())
        except Exception:
            pass
        survival["gold"]["rows"] += lane_rows

    # -- phase 0: establish + unloaded lane baseline ------------------------
    # warm both dispatch shapes (the lane bucket AND the full bucket) and
    # revisit so victim flows are ESTABLISHED before anything is timed
    for _r in range(2):
        lane_probe([])                # cold-compile warmup is not latency
        submit_victim("silver", batch, 40000)
        pump(block_s=120.0)
        L[0] += 1
    survival = {"gold": {"rows": 0, "allowed": 0},
                "silver": {"rows": 0, "allowed": 0}}     # warmup not scored
    lane_base_ms: list = []
    for _p in range(12 if smoke else 32):
        lane_probe(lane_base_ms)
        L[0] += 1
    lane_base_p99 = float(np.percentile(lane_base_ms, 99)) \
        if lane_base_ms else 0.0
    # unloaded full-bucket round-trip: the indivisible head-of-line unit.
    # Dispatches are not preempted, so a lane batch can land behind
    # every committed bulk unit — one per inflight slot plus the
    # staged-ahead batch — each up to ~2x its unloaded cost on a
    # contended rig. The lane budget allows those on top of the
    # 2x-baseline contract: µs of slack on a real TPU, the dominant
    # term on the CPU smoke rig where a dispatch is ms-scale
    bulk_ms: list = []
    for _p in range(6 if smoke else 12):
        t0 = time.monotonic()
        try:
            tk = eng.submit(victim_batch("silver", batch, 40000), now=L[0])
            out = tk.result(timeout=60.0)
            bulk_ms.append((time.monotonic() - t0) * 1e3)
            survival["silver"]["allowed"] += \
                int(np.asarray(out["allow"]).sum())
        except Exception:
            pass
        survival["silver"]["rows"] += batch
        L[0] += 1
    bulk_p50 = float(np.percentile(bulk_ms, 50)) if bulk_ms else 0.0

    # -- phase 1: isolation — bulk floods, gold + silver keep serving -------
    lane_loaded_ms: list = []
    flood_sent = flood_rejected = 0
    for _it in range(iso_iters):
        L[0] += 1
        for _f in range(flood_per_iter):
            try:
                tk = eng.submit(flood_batch(), now=L[0], deadline_ms=0)
                if tk.dropped:
                    flood_rejected += 1
                else:
                    flood_sent += 1
            except Exception:
                flood_rejected += 1
        submit_victim("silver", batch, 40000)
        pump()                        # non-blocking: backlog must build
        lane_probe(lane_loaded_ms)
        eng.overload_step()
        eng.sweep_step(now=L[0])
        eng.audit_step(budget=16)
    pump(block_s=120.0)
    lane_loaded_p99 = float(np.percentile(lane_loaded_ms, 99)) \
        if lane_loaded_ms else 0.0
    hol_units = 2 * (cfg.pipeline_inflight + 1)
    lane_budget_ms = max(2.0 * lane_base_p99,
                         lane_base_p99 + hol_units * bulk_p50,
                         lane_floor_ms)

    surv_rate = {
        t: s["allowed"] / max(1, s["rows"]) for t, s in survival.items()}
    victim_survival_min = min(surv_rate.values())

    # -- phase 2: DRR share convergence under saturating backlogs -----------
    # every tenant pushes as hard as admission lets it for a wall-clock
    # window; admitted_rows (counted at DRR pop) must split ~4:2:1. The
    # snapshot is taken at window end, BEFORE the drain — residual queue
    # rows (<= queue_batches) are noise against hundreds of pops
    shares0 = {n: d["admitted_rows"]
               for n, d in pl.stats()["tenants"].items()}
    share_sent = {"gold": 0, "silver": 0, "bulk": 0}
    share_rejected = {"gold": 0, "silver": 0, "bulk": 0}
    # pre-built batch pools: submission must outrun dispatch or the
    # queue never saturates and "shares" degenerate to arrival order.
    # (No audit_step in the loop either — replay is a second classify
    # per batch and would pace submissions to the drain rate; the pool
    # overflows into skipped_batches, which the gate ignores.)
    pool = {n: [flood_batch(n) for _ in range(8)]
            for n in ("gold", "silver", "bulk")}
    t_end = time.monotonic() + share_window_s
    k = 0
    while time.monotonic() < t_end:
        L[0] += 1
        k += 1
        for name in ("gold", "silver", "bulk"):
            for _r in range(2):
                try:
                    tk = eng.submit(pool[name][(k + _r) % 8], now=L[0],
                                    deadline_ms=0)
                    if tk.dropped:
                        share_rejected[name] += 1
                    else:
                        share_sent[name] += 1
                except Exception:
                    share_rejected[name] += 1
    shares1 = {n: d["admitted_rows"]
               for n, d in pl.stats()["tenants"].items()}
    share_rows = {n: shares1.get(n, 0) - shares0.get(n, 0)
                  for n in shares1}
    share_total = max(1, sum(share_rows.values()))
    admitted_share = {n: r / share_total for n, r in share_rows.items()}
    flood_admitted_share = admitted_share.get("bulk", 0.0)
    w_share = 1.0 / 7.0               # bulk's weight share of 4+2+1

    # -- drain + audit ------------------------------------------------------
    drained = eng.drain(timeout=120)
    pump(block_s=120.0)
    for _ in range(200):
        step = eng.audit_step(budget=128)
        if not step or (not step.get("replayed")
                        and not step.get("pending")):
            break
    audit = eng.auditor.stats()
    qos_stats = eng.qos_status() or {}
    eng.stop()

    gate_reasons = []
    if victim_survival_min < 0.99:
        gate_reasons.append(
            f"victim survival {victim_survival_min:.4f} < 0.99 "
            f"(gold {surv_rate['gold']:.4f}, "
            f"silver {surv_rate['silver']:.4f})")
    if lane_loaded_p99 > lane_budget_ms:
        gate_reasons.append(
            f"lane p99 under flood {lane_loaded_p99:.3f}ms > budget "
            f"{lane_budget_ms:.3f}ms (2x unloaded baseline "
            f"{lane_base_p99:.3f}ms / head-of-line allowance of "
            f"{hol_units} full-bucket dispatch units at "
            f"{bulk_p50:.3f}ms, floor {lane_floor_ms}ms)")
    if not w_share * 0.5 <= flood_admitted_share <= w_share * 1.5:
        gate_reasons.append(
            f"flooder admitted share {flood_admitted_share:.4f} outside "
            f"[{w_share * 0.5:.4f}, {w_share * 1.5:.4f}] — DRR did not "
            "confine it to its 1/7 weight")
    if audit["mismatched_rows"]:
        gate_reasons.append(
            f"parity: {audit['mismatched_rows']} mismatched rows at "
            "sampling 1.0 with QoS armed")
    if audit["checked_rows"] == 0:
        gate_reasons.append("auditor checked nothing")
    if not drained:
        gate_reasons.append("pipeline did not drain clean")

    if verbose:
        print(f"# tenants preset={preset} survival gold/silver="
              f"{surv_rate['gold']:.4f}/{surv_rate['silver']:.4f} "
              f"lane p99 base/loaded={lane_base_p99:.3f}/"
              f"{lane_loaded_p99:.3f}ms shares="
              f"{ {n: round(s, 3) for n, s in admitted_share.items()} } "
              f"flood sent/rejected={flood_sent}/{flood_rejected} "
              f"audit={audit['checked_rows']}/{audit['mismatched_rows']}",
              file=sys.stderr)

    return {
        "metric": "qos_mixed_tenant_cfg8",
        "value": round(victim_survival_min, 6),
        "unit": "victim_flow_survival",
        "vs_baseline": round(victim_survival_min / 0.99, 4),
        "preset": preset,
        "batch": batch,
        "victim_survival_min": round(victim_survival_min, 6),
        "lane_base_p99_ms": round(lane_base_p99, 3),
        "lane_e2e_p99_ms": round(lane_loaded_p99, 3),
        "flood_admitted_share": round(flood_admitted_share, 4),
        "survival": {t: {"rows": s["rows"], "allowed": s["allowed"],
                         "rate": round(surv_rate[t], 6)}
                     for t, s in survival.items()},
        "lane": {
            "rows": lane_rows,
            "probes_base": len(lane_base_ms),
            "probes_loaded": len(lane_loaded_ms),
            "base_p50_ms": round(float(np.percentile(lane_base_ms, 50)), 3)
            if lane_base_ms else 0.0,
            "loaded_p50_ms":
            round(float(np.percentile(lane_loaded_ms, 50)), 3)
            if lane_loaded_ms else 0.0,
            "bulk_dispatch_p50_ms": round(bulk_p50, 3),
            "budget_ms": round(lane_budget_ms, 3),
        },
        "flood": {
            "batches_submitted": flood_sent,
            "batches_rejected": flood_rejected,
            "per_iter": flood_per_iter,
            "iso_iters": iso_iters,
        },
        "shares": {
            "weights": {"gold": 4, "silver": 2, "bulk": 1},
            "window_s": share_window_s,
            "admitted_rows": share_rows,
            "admitted_share": {n: round(s, 4)
                               for n, s in admitted_share.items()},
            "submitted": share_sent,
            "rejected": share_rejected,
        },
        "tenants": qos_stats.get("tenants"),
        "audit": {
            "checked_rows": audit["checked_rows"],
            "checked_batches": audit["checked_batches"],
            "mismatched_rows": audit["mismatched_rows"],
            "skipped_batches": audit["skipped_batches"],
        },
        "drained": bool(drained),
        "qos_gate": {
            "failed": bool(gate_reasons),
            **({"reasons": gate_reasons} if gate_reasons else {}),
        },
    }


def fqdn_bench(preset: str, verbose: bool = False, batch: int = 256):
    """cfg9: toFQDNs policy under DNS churn at storm rates (ROADMAP item
    1b — the in-band DNS plane over the live pipelined engine).

    One endpoint serves an egress ``toFQDNs`` world: a matchPattern rule
    (``*.svc.example.com``, toPorts 443) plus the DNS L7 redirect class
    (UDP/53 to the resolver). Learning rides the WIRE shape: every tick
    submits a DNS batch through the pipeline, the verdict output marks
    the redirect rows, and the proxy tap (fqdn/proxy.observe_batch —
    the exact call the shim feeder makes at verdict-apply) decodes the
    harvested response payloads into the FQDN cache.

    Churn model, all on the cache's logical clock:

    - **stable names** re-resolve every tick with a long TTL — their
      identities must never flap; established flows to them are the
      survival population.
    - **churn names** arrive fresh every tick with a short TTL and die
      two ticks later through the fqdn-gc expiry — a steady
      grow-and-retire stream the delta path must absorb: every refresh
      is a coalesced rule refresh + identity growth + identity
      retirement through ``place_patch``, NEVER a full rebuild.

    The parity auditor rides at sampling 1.0 (retirement tombstones must
    be bit-identical to a fresh build under the oracle). ``fqdn_gate``
    fails the artifact (exit 4) on: any parity mismatch (or nothing
    checked), established survival < 99%, any full rebuild during
    steady churn, refresh p99 past the delta-path budget
    (max(25ms, 0.5x the measured full-build p50) — the patch path must
    beat half a rebuild or it isn't earning its complexity), zero
    learned/retired identities (the churn never actually exercised the
    plane), or an unclean drain."""
    from cilium_tpu.fqdn.dnsparse import encode_response
    from cilium_tpu.fqdn.proxy import DNSProxy
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.engine import Engine

    smoke = preset == "smoke"
    ticks = 16 if smoke else 48
    churn_per_tick = 3 if smoke else 8
    n_stable = 6
    stable_ttl, churn_ttl, tick_s = 10_000, 15, 7     # churn lives 2 ticks
    payload_w = 512
    cfg = DaemonConfig(
        ct_capacity=1 << 13, auto_regen=False, batch_size=batch,
        pipeline_flush_ms=5.0, pipeline_queue_batches=16,
        pipeline_block_timeout_s=0.05,
        audit_enabled=True, audit_sample_rate=1.0, audit_pool_batches=64,
        flowlog_mode="none",
        fqdn_proxy_enabled=True, fqdn_min_ttl=0)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    eng.auditor.configure(sample_rate=1.0)
    L = [50_000]                       # logical clock (seconds)
    eng.ctx.fqdn_cache.clock = lambda: L[0]
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",), ep_id=1)
    eng.apply_policy([{
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "egress": [
            # the DNS L7 redirect class: queries to the resolver carry
            # VERDICT_REDIRECT (allow-all L7 set — replies always flow)
            {"toCIDR": ["8.8.8.8/32"],
             "toPorts": [{"ports": [{"port": "53", "protocol": "UDP"}],
                          "rules": {"http": [{}]}}]},
            {"toFQDNs": [{"matchPattern": "*.svc.example.com"}],
             "toPorts": [{"ports": [{"port": "443",
                                     "protocol": "TCP"}]}]},
        ]}])
    eng.regenerate()
    eng.start_pipeline()
    proxy = DNSProxy(eng.ctx.fqdn_cache, metrics=eng.metrics,
                     min_ttl=cfg.fqdn_min_ttl, port=cfg.fqdn_proxy_port,
                     payload_width=payload_w)

    stable_ip = {i: f"20.0.{i}.1" for i in range(n_stable)}

    def dns_batch(answers):
        """One DNS exchange batch: egress UDP/53 query rows to the
        resolver, the harvested response payload riding the poll-buffer
        columns — the wire shape the feeder tap sees."""
        n = len(answers)
        b = _base_batch(n, direction=0)
        b["dst"][:, 3] = 0x08080808
        b["sport"][:] = 30000 + np.arange(n)
        b["dport"][:] = 53
        b["proto"][:] = 17
        b["tcp_flags"][:] = 0
        b["_dns_payload"] = np.zeros((n, payload_w), np.uint8)
        b["_dns_len"] = np.zeros((n,), np.int32)
        for i, (name, ip, ttl) in enumerate(answers):
            wire = encode_response(name, [ip], ttl=ttl)
            w = min(len(wire), payload_w)
            b["_dns_payload"][i, :w] = np.frombuffer(wire[:w], np.uint8)
            b["_dns_len"][i] = w
        return b

    def traffic_batch(n, syn):
        """Established-population flows to the STABLE learned IPs."""
        b = _base_batch(n, direction=0)
        idx = np.arange(n) % n_stable
        b["dst"][:, 3] = (0x14000001 + (idx << 8)).astype(np.uint32)
        b["sport"][:] = 41000 + np.arange(n) % 256
        b["dport"][:] = 443
        b["tcp_flags"][:] = 0x02 if syn else 0x10
        return b

    def learn(answers):
        """DNS batch through the pipeline; tap the verdict output."""
        b = dns_batch(answers)
        tk = eng.submit(b, now=L[0])
        out = tk.result(timeout=60.0)
        n_red = int(np.asarray(out["redirect"]).sum())
        proxy.observe_batch(b, out)
        return n_red

    # -- phase 0: seed + full-build baseline --------------------------------
    # learn the stable names, establish the survival flows, then measure
    # what a FULL rebuild of this world costs — the delta-path budget's
    # denominator
    for i in range(n_stable):
        learn([(f"s{i}.svc.example.com", stable_ip[i], stable_ttl)])
    eng.regenerate()
    tb = traffic_batch(min(batch, 128), syn=True)
    eng.submit(tb, now=L[0]).result(timeout=60.0)      # CT establishment
    full_ms = []
    for _ in range(3):
        t0 = time.monotonic()
        eng.regenerate(force=True)
        full_ms.append((time.monotonic() - t0) * 1e3)
    full_p50 = float(np.percentile(full_ms, 50))
    refresh_budget_ms = max(25.0, 0.5 * full_p50)
    eng.regenerate()                   # settle; re-seed the delta path

    # -- phase 1: steady churn ----------------------------------------------
    fulls0 = eng.metrics.counters.get("regen_full_total", 0)
    retired0 = eng.metrics.counters.get("fqdn_identities_retired_total", 0)
    created0 = eng.repo.fqdn_identities_created
    refresh_samples = []
    surv_rows = surv_allowed = 0
    dns_rows = redirect_rows = 0
    pending = []
    for tick in range(ticks):
        L[0] += tick_s
        # the tick's DNS storm: stable refreshes + fresh churn names
        answers = [(f"s{i}.svc.example.com", stable_ip[i], stable_ttl)
                   for i in range(n_stable)]
        for j in range(churn_per_tick):
            answers.append((f"c{tick}-{j}.svc.example.com",
                            f"20.1.{tick % 200}.{j + 1}", churn_ttl))
        redirect_rows += learn(answers)
        dns_rows += len(answers)
        # expiry: churn names from two ticks ago die here (fqdn-gc tick)
        eng.ctx.fqdn_cache.expire(L[0])
        # the refresh the gate times: coalesced flush + identity growth
        # AND retirement through the delta path, in one cycle
        t0 = time.monotonic()
        eng.regenerate()
        refresh_samples.append((time.monotonic() - t0) * 1e3)
        # established flows to stable names keep serving THROUGH the churn
        n = min(batch, 128)
        try:
            pending.append((eng.submit(traffic_batch(n, syn=False),
                                       now=L[0]), n))
        except Exception:
            surv_rows += n             # whole batch lost
        done = []
        for tk, rows in pending:
            if tk.done():
                done.append((tk, rows))
        for tk, rows in done:
            pending.remove((tk, rows))
            try:
                out = tk.result(timeout=0)
                surv_allowed += int(np.asarray(out["allow"]).sum())
            except Exception:
                pass
            surv_rows += rows
        eng.audit_step(budget=16)
    for tk, rows in pending:
        try:
            out = tk.result(timeout=60.0)
            surv_allowed += int(np.asarray(out["allow"]).sum())
        except Exception:
            pass
        surv_rows += rows

    # -- drain + audit ------------------------------------------------------
    drained = eng.drain(timeout=120)
    for _ in range(200):
        step = eng.audit_step(budget=128)
        if not step or (not step.get("replayed")
                        and not step.get("pending")):
            break
    audit = eng.auditor.stats()
    fulls_delta = eng.metrics.counters.get("regen_full_total", 0) - fulls0
    retired = eng.metrics.counters.get(
        "fqdn_identities_retired_total", 0) - retired0
    created = eng.repo.fqdn_identities_created - created0
    coalesced = eng.repo.fqdn_refresh_coalesced
    fqdn_doc = eng.fqdn_status()
    eng.stop()

    survival = surv_allowed / max(1, surv_rows)
    refresh_p50 = float(np.percentile(refresh_samples, 50))
    refresh_p99 = float(np.percentile(refresh_samples, 99))

    gate_reasons = []
    if audit["mismatched_rows"]:
        gate_reasons.append(
            f"parity: {audit['mismatched_rows']} mismatched rows at "
            "sampling 1.0 under FQDN churn")
    if audit["checked_rows"] == 0:
        gate_reasons.append("auditor checked nothing")
    if survival < 0.99:
        gate_reasons.append(
            f"established survival {survival:.4f} < 0.99 — stable-name "
            "flows lost verdicts during churn refreshes")
    if fulls_delta:
        gate_reasons.append(
            f"{fulls_delta} full rebuild(s) during steady churn — the "
            "delta path fell back")
    if refresh_p99 > refresh_budget_ms:
        gate_reasons.append(
            f"refresh p99 {refresh_p99:.3f}ms > delta budget "
            f"{refresh_budget_ms:.3f}ms (full build p50 {full_p50:.3f}ms)")
    if created == 0 or retired == 0:
        gate_reasons.append(
            f"churn exercised nothing (created={created} "
            f"retired={retired})")
    if redirect_rows == 0:
        gate_reasons.append("no DNS row ever carried the redirect class")
    if not drained:
        gate_reasons.append("pipeline did not drain clean")

    if verbose:
        print(f"# fqdn preset={preset} survival={survival:.4f} refresh "
              f"p50/p99={refresh_p50:.3f}/{refresh_p99:.3f}ms (budget "
              f"{refresh_budget_ms:.3f}ms, full {full_p50:.3f}ms) "
              f"created/retired={created}/{retired} fulls={fulls_delta} "
              f"audit={audit['checked_rows']}/{audit['mismatched_rows']}",
              file=sys.stderr)

    return {
        "metric": "fqdn_churn_cfg9",
        "value": round(refresh_p99, 3),
        "unit": "refresh_p99_ms",
        "vs_baseline": round(refresh_p99 / max(1e-9, refresh_budget_ms), 4),
        "preset": preset,
        "batch": batch,
        "refresh_p50_ms": round(refresh_p50, 3),
        "refresh_p99_ms": round(refresh_p99, 3),
        "established_survival": round(survival, 6),
        "refresh": {
            "samples": len(refresh_samples),
            "budget_ms": round(refresh_budget_ms, 3),
            "full_build_p50_ms": round(full_p50, 3),
            "full_rebuilds_in_churn": fulls_delta,
        },
        "churn": {
            "ticks": ticks,
            "names_per_tick": churn_per_tick,
            "stable_names": n_stable,
            "dns_rows": dns_rows,
            "redirect_rows": redirect_rows,
            "identities_created": created,
            "identities_retired": retired,
            "refreshes_coalesced": coalesced,
        },
        "survival": {"rows": surv_rows, "allowed": surv_allowed},
        "fqdn": fqdn_doc,
        "audit": {
            "checked_rows": audit["checked_rows"],
            "checked_batches": audit["checked_batches"],
            "mismatched_rows": audit["mismatched_rows"],
            "skipped_batches": audit["skipped_batches"],
        },
        "drained": bool(drained),
        "fqdn_gate": {
            "failed": bool(gate_reasons),
            **({"reasons": gate_reasons} if gate_reasons else {}),
        },
    }


def chiploss_bench(preset: str, verbose: bool = False, batch: int = 256,
                   shards: int = 4):
    """cfg10: chip-loss self-healing over the live pipelined engine
    (ISSUE 19 — the robustness counterpart to cfg9's control-plane
    churn).

    A ``shards``-device mesh serves a CT-gated reply world: the
    endpoint's egress policy allows the forward direction, ingress is
    enforced with nothing matching the servers — so a REPLY row passes
    ONLY on a conntrack hit. The established population is the survival
    metric: every reply verdict is a direct probe of CT continuity
    through the loss.

    Phases: establish + warm (the warm replies also stamp the
    established-fingerprint filter the grace window consults) → CT
    archive snapshot (the salvage floor) → baseline reply storm (fps
    denominator) → arm ``device.fail`` on one ordinal mid-storm → the
    dispatch error latches DEVICE_LOST and parks the pipeline → one
    ``remesh_step`` fences the wedged generation and re-meshes onto the
    survivors with CT salvage (surviving shards' entries re-steered into
    the n-1 geometry; the lost shard's flows ride the bounded grace
    window until forward traffic cold-learns them back) → degraded
    reply storm (fps numerator + survival) → disarm + heal re-mesh back
    to full width → healed storm.

    The parity auditor rides at sampling 1.0 the whole way — the grace
    flip is applied AFTER capture, so raw verdicts replay exactly and
    the oracle takes the captured CT status as table truth.
    ``chiploss_gate`` fails the artifact (exit 4) on: established
    survival < 99% over resolved post-loss replies (pipeline rejects in
    the loss window are sheds, not denials), any parity mismatch (or
    nothing checked), degraded throughput under 0.7x the ideal (n-1)/n
    scaling, anything but exactly one re-mesh in each direction, a
    grace window that never fired (the loss exercised nothing), a final
    mesh narrower than configured, or an unclean drain."""
    import shutil
    import tempfile

    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.engine import Engine
    from cilium_tpu.runtime.faults import FAULTS
    from cilium_tpu.utils import constants as C

    smoke = preset == "smoke"
    n = max(2, shards)
    victim = 1 % n
    n_flows = 384 if smoke else 1536
    ticks = 4 if smoke else 12          # storm ticks per measured phase
    snap_dir = tempfile.mkdtemp(prefix="cilium-tpu-ct-archive-")
    cfg = DaemonConfig(
        n_shards=n, ct_capacity=1 << 13, auto_regen=False,
        batch_size=batch, pipeline_flush_ms=5.0,
        pipeline_queue_batches=16, pipeline_block_timeout_s=0.05,
        audit_enabled=True, audit_sample_rate=1.0, audit_pool_batches=64,
        flowlog_mode="none",
        remesh_heal_hysteresis_s=0.0,   # the bench drives the heal tick
        remesh_grace_s=120.0,           # survives a slow smoke rig
        ct_snapshot_dir=snap_dir, checkpoint_max_age_s=300.0)
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    eng.auditor.configure(sample_rate=1.0)
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",), ep_id=1)
    eng.apply_policy([{
        "endpointSelector": {"matchLabels": {"app": "web"}},
        # forward direction: allowed by policy — the cold-learn path
        # that re-creates CT on the survivor mesh after the loss
        "egress": [{"toCIDR": ["10.0.0.0/8"],
                    "toPorts": [{"ports": [{"port": "443",
                                            "protocol": "TCP"}]}]}],
        # ingress ENFORCED with nothing matching the servers: replies
        # pass only on a CT hit — each one probes CT continuity
        "ingress": [{"fromEndpoints": [
            {"matchLabels": {"role": "backoffice"}}]}],
    }])
    eng.regenerate()
    eng.start_pipeline()

    flow_ids = np.arange(n_flows)
    chunks = [flow_ids[i:i + batch] for i in range(0, n_flows, batch)]
    shed_rows = 0

    def fwd_batch(idx, flags):
        b = _base_batch(len(idx), direction=C.DIR_EGRESS)
        b["dst"][:, 3] = (0x0A000100 + idx).astype(np.uint32)
        b["sport"][:] = 20000 + idx
        b["tcp_flags"][:] = flags
        return b

    def rep_batch(idx):
        b = _base_batch(len(idx), direction=C.DIR_INGRESS)
        b["src"][:, 3] = (0x0A000100 + idx).astype(np.uint32)
        b["dst"][:, 3] = 0xC0A8000A
        b["sport"][:] = 443
        b["dport"][:] = 20000 + idx
        b["tcp_flags"][:] = C.TCP_ACK
        return b

    def pump(mk, count=None):
        """Submit every chunk, resolve every ticket. Submission or
        resolution failures (queue overflow while parked, the fenced
        wedged window) are capacity sheds, never denials — they leave
        the survival denominator."""
        nonlocal shed_rows
        tickets = []
        for idx in chunks:
            try:
                tickets.append((eng.submit(mk(idx)), len(idx)))
            except Exception:
                shed_rows += len(idx)
        for tk, rows in tickets:
            try:
                out = tk.result(timeout=60.0)
            except Exception:
                shed_rows += rows
                continue
            if count is not None:
                count["rows"] += rows
                count["allowed"] += int(np.asarray(out["allow"]).sum())

    def storm(n_ticks, count):
        """Forward-ACK + reply sweeps over the whole population; only
        the reply verdicts feed survival, both directions feed fps."""
        t0 = time.monotonic()
        rows = 0
        for _ in range(n_ticks):
            pump(lambda idx: fwd_batch(idx, C.TCP_ACK))
            pump(rep_batch, count=count)
            rows += 2 * n_flows
            eng.audit_step(budget=32)
        eng.drain(timeout=120)
        return rows / max(1e-9, time.monotonic() - t0)

    # -- phase 0: establish + warm ------------------------------------------
    pump(lambda idx: fwd_batch(idx, C.TCP_SYN))
    assert eng.drain(timeout=120)
    warm = {"rows": 0, "allowed": 0}
    pump(rep_batch, count=warm)        # stamps the fingerprint filter
    eng.drain(timeout=120)
    eng.ct_snapshot_step()             # the archive salvage floor
    warm_surv = warm["allowed"] / max(1, warm["rows"])

    # -- phase 1: baseline storm --------------------------------------------
    base = {"rows": 0, "allowed": 0}
    baseline_fps = storm(ticks, base)

    # -- phase 2: loss, detection, fenced re-mesh ---------------------------
    FAULTS.arm("device.fail", mode="fail", message=f"dev={victim}")
    t_loss0 = time.monotonic()
    deg = {"rows": 0, "allowed": 0}
    trip = []
    try:
        trip.append((eng.submit(rep_batch(chunks[0])), len(chunks[0])))
    except Exception:
        shed_rows += len(chunks[0])
    deadline = time.monotonic() + 60
    while (eng.pipeline_stats() or {}).get("state") != "device-lost" \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    detect_ms = (time.monotonic() - t_loss0) * 1e3
    down = eng.remesh_step() or {}
    down_ms = (time.monotonic() - t_loss0) * 1e3
    for tk, rows in trip:
        try:
            out = tk.result(timeout=30.0)
            deg["rows"] += rows        # raced the fence and resolved
            deg["allowed"] += int(np.asarray(out["allow"]).sum())
        except Exception:
            shed_rows += rows          # the fenced wedged window

    # -- phase 3: degraded storm --------------------------------------------
    grace0 = eng.metrics.counters.get("ct_salvage_grace_hits_total", 0)
    # first reply sweep BEFORE any forward traffic: the lost shard's
    # flows must ride the grace window (fingerprint hits) — the
    # forward ACKs of the storm then cold-learn their CT entries back
    pump(rep_batch, count=deg)
    degraded_fps = storm(ticks, deg)
    grace_hits = eng.metrics.counters.get(
        "ct_salvage_grace_hits_total", 0) - grace0

    # -- phase 4: heal ------------------------------------------------------
    FAULTS.disarm("device.fail")
    t_up0 = time.monotonic()
    up = eng.remesh_step() or {}
    up_ms = (time.monotonic() - t_up0) * 1e3
    healed = {"rows": 0, "allowed": 0}
    healed_fps = storm(max(1, ticks // 2), healed)

    # -- drain + audit ------------------------------------------------------
    drained = eng.drain(timeout=120)
    for _ in range(200):
        step = eng.audit_step(budget=128)
        if not step or (not step.get("replayed")
                        and not step.get("pending")):
            break
    audit = eng.auditor.stats()
    status = eng.remesh_status()
    ctr = eng.metrics.counters
    downs = ctr.get(f'datapath_remesh_total{{from="{n}",to="{n - 1}"}}', 0)
    ups = ctr.get(f'datapath_remesh_total{{from="{n - 1}",to="{n}"}}', 0)
    eng.stop()
    shutil.rmtree(snap_dir, ignore_errors=True)

    survival = (deg["allowed"] + healed["allowed"]) \
        / max(1, deg["rows"] + healed["rows"])
    ratio = degraded_fps / max(1e-9, baseline_fps)
    ideal = (n - 1) / n
    floor = 0.7 * ideal
    mesh = status.get("mesh") or {}

    gate_reasons = []
    if warm_surv < 0.999 or base["allowed"] < base["rows"]:
        gate_reasons.append(
            f"baseline replies leaked before any loss (warm "
            f"{warm_surv:.4f}, storm {base['allowed']}/{base['rows']}) — "
            "the CT-gated world is broken, survival would be vacuous")
    if survival < 0.99:
        gate_reasons.append(
            f"established survival {survival:.4f} < 0.99 — flows lost "
            "verdicts through the loss/heal cycle")
    if audit["mismatched_rows"]:
        gate_reasons.append(
            f"parity: {audit['mismatched_rows']} mismatched rows at "
            "sampling 1.0 across the re-mesh")
    if audit["checked_rows"] == 0:
        gate_reasons.append("auditor checked nothing")
    if ratio < floor:
        gate_reasons.append(
            f"degraded throughput {ratio:.3f}x baseline < "
            f"{floor:.3f}x (0.7 * ideal {ideal:.3f} for {n}->{n - 1})")
    if downs != 1:
        gate_reasons.append(
            f"{downs} loss re-mesh(es) {n}->{n - 1} — expected exactly 1")
    if ups != 1:
        gate_reasons.append(
            f"{ups} heal re-mesh(es) {n - 1}->{n} — expected exactly 1")
    if grace_hits == 0:
        gate_reasons.append(
            "the salvage grace window never fired — the loss exercised "
            "nothing (no lost-shard flow ever needed it)")
    if mesh.get("live") != mesh.get("configured"):
        gate_reasons.append(
            f"final mesh {mesh.get('live')}/{mesh.get('configured')} — "
            "the healed device never re-admitted")
    if not drained:
        gate_reasons.append("pipeline did not drain clean")

    if verbose:
        print(f"# chiploss preset={preset} shards={n} victim={victim} "
              f"survival={survival:.4f} fps base/deg/heal="
              f"{baseline_fps:.0f}/{degraded_fps:.0f}/{healed_fps:.0f} "
              f"detect={detect_ms:.1f}ms down={down_ms:.1f}ms "
              f"up={up_ms:.1f}ms grace={grace_hits} shed={shed_rows} "
              f"audit={audit['checked_rows']}/{audit['mismatched_rows']}",
              file=sys.stderr)

    return {
        "metric": "chiploss_recovery_cfg10",
        "value": round(ratio, 4),
        "unit": "degraded_fps_ratio",
        "vs_baseline": round(ratio / max(1e-9, ideal), 4),
        "preset": preset,
        "batch": batch,
        "shards": n,
        "victim": victim,
        "established_survival": round(survival, 6),
        "throughput": {
            "baseline_fps": round(baseline_fps, 1),
            "degraded_fps": round(degraded_fps, 1),
            "healed_fps": round(healed_fps, 1),
            "ideal_ratio": round(ideal, 4),
            "floor_ratio": round(floor, 4),
        },
        "loss": {
            "detect_ms": round(detect_ms, 3),
            "down_ms": round(down_ms, 3),
            "remesh": down.get("remesh"),
        },
        "heal": {
            "up_ms": round(up_ms, 3),
            "remesh": up.get("remesh"),
        },
        "salvage": {
            "grace_hits": grace_hits,
            "shed_rows": shed_rows,
        },
        "survival": {"warm": warm, "baseline": base, "degraded": deg,
                     "healed": healed},
        "mesh": status,
        "audit": {
            "checked_rows": audit["checked_rows"],
            "checked_batches": audit["checked_batches"],
            "mismatched_rows": audit["mismatched_rows"],
            "skipped_batches": audit["skipped_batches"],
        },
        "drained": bool(drained),
        "chiploss_gate": {
            "failed": bool(gate_reasons),
            **({"reasons": gate_reasons} if gate_reasons else {}),
        },
    }


def cluster_bench(n_nodes: int, preset: str, verbose: bool = False):
    """cfg7: multi-host serving over the clustermesh store (ISSUE 12 /
    ROADMAP item 3 — the horizontal-scale counterpart to cfg6's
    single-host overload ladder). N engine PROCESSES (runtime/cluster.py,
    spawn — real per-host isolation: own jax, own FAULTS, own identity
    numbering) share one store directory; each publishes its endpoints'
    (prefix, labels) and ingests its peers', so ordinary label policy
    selects remote pods.

    Phases: (1) converge — every node's remote view matches the union of
    its peers' ledgers, with the post-seed ingest riding the PR 9
    delta-patch path (``regen_incremental_total`` must move); (2) serve —
    cross-boundary traffic on every node, aggregate fps + per-node
    replication-lag p99, with the parity auditor armed at sampling 1.0
    (the oracle replay IS "the merged world" check); (3) chaos — store
    partition on one node (``clustermesh.store_list``: last-good serving,
    MESH_STALE past the budget, heal), peer kill + lease-expiry withdrawal
    + restart + re-convergence, conflicting prefix claims resolved
    identically on every observer (n >= 3), and a skewed publisher clock
    (entries survive, lag clamps at zero); (4) relay fan-in — every node's
    flowlog JSONL tailed into one FlowRelay, every node visible in the
    merged stream. ``cluster_gate`` fails the artifact (exit 4) on any
    violation: non-convergence, parity mismatches, fail-closed remote
    flows during partition, MESH_STALE missing/sticky, observer
    disagreement on a conflicting claim, a node missing from the relay."""
    import shutil
    import tempfile

    from cilium_tpu.observe.relay import FlowRelay, JsonlTailObserver
    from cilium_tpu.runtime.cluster import ClusterSupervisor

    smoke = preset == "smoke"
    datapath = os.environ.get("CILIUM_TPU_CLUSTER_DATAPATH", "jit")
    serve_batches = 20 if smoke else 80
    stale_after_s = 2.0
    staleness_budget_s = 1.0
    gate_reasons = []
    phases = {}

    def note(phase, **kw):
        phases[phase] = kw
        if verbose:
            print(f"# cluster phase {phase}: {kw}", file=sys.stderr)

    def gate(ok, reason):
        if not ok:
            gate_reasons.append(reason)
        return ok

    names = [f"node-{i}" for i in range(n_nodes)]
    work = tempfile.mkdtemp(prefix="cilium-tpu-cluster-")
    store = os.path.join(work, "store")
    flows_dir = os.path.join(work, "flows")
    os.makedirs(flows_dir)
    overrides = {
        name: {"cluster_stale_after_s": stale_after_s,
               "cluster_staleness_budget_s": staleness_budget_s,
               "flowlog_path": os.path.join(flows_dir, f"{name}.jsonl")}
        for name in names}

    def node_ip(i):
        return f"10.{i + 1}.0.10"

    def setup_node(sup, i):
        name = names[i]
        sup.add_endpoint(name, ["k8s:cluster=mesh", f"k8s:app=svc{i}"],
                         [node_ip(i)], ep_id=1)
        sup.nodes[name].call("policy", docs=[{
            "endpointSelector": {"matchLabels": {"app": f"svc{i}"}},
            "ingress": [{"fromEndpoints": [
                {"matchLabels": {"cluster": "mesh"}}],
                "toPorts": [{"ports": [
                    {"port": "8080", "protocol": "TCP"}]}]}]}])
        sup.nodes[name].call("regen")   # seed the incremental compiler
                                        # BEFORE remote entries arrive

    def cross_flows(i, sport0=41000):
        """Flows node i serves: one allowed cross-boundary flow per peer
        (remote pod ip → local pod, the mesh-selected port) + junk drops
        (unknown world sources)."""
        flows = []
        for j in range(n_nodes):
            if j == i:
                continue
            flows.append({"src": node_ip(j), "dst": node_ip(i),
                          "sport": sport0 + j, "dport": 8080, "ep_id": 1})
        flows.append({"src": "203.0.113.9", "dst": node_ip(i),
                      "sport": sport0 + 99, "dport": 8080, "ep_id": 1})
        flows.append({"src": node_ip(i - 1 if i else n_nodes - 1),
                      "dst": node_ip(i), "sport": sport0 + 98,
                      "dport": 23, "ep_id": 1})   # wrong port → drop
        return flows

    def expect_cross(out, i):
        """allowed cross flows per peer, junk + wrong-port denied."""
        want = [True] * (n_nodes - 1) + [False, False]
        return list(out["allow"]) == want

    sup = ClusterSupervisor(store, names, overrides=overrides,
                            datapath=datapath)
    t_bench0 = time.monotonic()
    try:
        # -- phase 1: boot + converge (delta-patch ingest) ------------------
        for i in range(n_nodes):
            setup_node(sup, i)
        rounds = sup.converge(max_rounds=3 + n_nodes)
        statuses = sup.broadcast("status")
        delta_used = {n: statuses[n]["counters"].get(
            "regen_incremental_total", 0) for n in names}
        gate(all(v >= 1 for v in delta_used.values()),
             f"remote ingest did not ride the delta-patch path on every "
             f"node (regen_incremental_total={delta_used})")
        note("converge", rounds=rounds, delta_used=delta_used)

        # -- phase 2: serve + cross-boundary verdict spot-audit -------------
        per_node = {}
        for i, name in enumerate(names):
            res = sup.nodes[name].call(
                "serve", flows=cross_flows(i), batches=serve_batches,
                now=5000, timeout=600.0)
            per_node[name] = res
        agg_fps = sum(r["fps"] for r in per_node.values())
        spot_ok = {}
        for i, name in enumerate(names):
            out = sup.nodes[name].call("classify",
                                       flows=cross_flows(i, sport0=45000),
                                       now=6000)
            spot_ok[name] = expect_cross(out, i)
        gate(all(spot_ok.values()),
             f"cross-boundary verdict spot-audit failed: {spot_ok}")
        # flush every node's flowlog sink NOW: the kill phase below takes a
        # node down hard, and the relay must still see its served flows
        sup.broadcast("flush")
        note("serve", aggregate_fps=round(agg_fps, 1),
             per_node_fps={n: round(r["fps"], 1)
                           for n, r in per_node.items()})

        # -- phase 3a: store partition on node-0 ----------------------------
        victim = names[0]
        sup.nodes[victim].call("arm", point="clustermesh.store_list",
                               spec={"mode": "fail"})
        during = []
        for _ in range(3):
            sup.broadcast("step")
            out = sup.nodes[victim].call("classify",
                                         flows=cross_flows(0, 46000),
                                         now=7000)
            during.append(expect_cross(out, 0))
            time.sleep(0.45)
        gate(all(during),
             "partitioned node failed closed on established remote flows")
        st = sup.nodes[victim].call("status")
        gate(st["mesh"]["state"] == "MESH_STALE",
             f"partitioned node never reported MESH_STALE past the "
             f"{staleness_budget_s}s budget (state={st['mesh']['state']})")
        gate(st["health"]["state"] == "DEGRADED",
             f"health did not degrade on MESH_STALE "
             f"(state={st['health']['state']})")
        sup.nodes[victim].call("disarm", point="clustermesh.store_list")
        sup.broadcast("step")
        st = sup.nodes[victim].call("status")
        gate(st["mesh"]["state"] == "OK",
             f"MESH_STALE did not clear after heal "
             f"(state={st['mesh']['state']})")
        rounds_heal = sup.converge(max_rounds=4)
        note("partition", during_partition_served=all(during),
             healed_rounds=rounds_heal)

        # -- phase 3b: peer kill → lease expiry → restart → re-converge -----
        dead = names[-1]
        dead_idx = n_nodes - 1
        sup.nodes[dead].kill()
        survivors = names[:-1]
        dead_prefix = f"{node_ip(dead_idx)}/32"
        # detection latency is [stale_after, 2*stale_after): a survivor
        # that cached generation G-1 observes the dead node's final G on
        # its first post-kill sync as "progress" and renews the lease once
        # — withdrawal lands within one more lease window
        withdrawn = False
        expiry_deadline = time.monotonic() + 2 * stale_after_s + 2.0
        while not withdrawn and time.monotonic() < expiry_deadline:
            time.sleep(stale_after_s * 0.6)
            sup.broadcast("step", only=survivors)
            views = sup.views(only=survivors)
            withdrawn = all(dead_prefix not in views[n] for n in survivors)
        gate(withdrawn,
             f"dead peer's prefix {dead_prefix} not withdrawn after lease "
             f"expiry")
        # the withdrawn identity fails closed for NEW flows (stale IP must
        # not keep the old pod's permissions)
        out = sup.nodes[names[0]].call("classify", flows=[
            {"src": node_ip(dead_idx), "dst": node_ip(0),
             "sport": 47001, "dport": 8080, "ep_id": 1}], now=8000)
        gate(not out["allow"][0],
             "withdrawn remote identity still allowed after lease expiry")
        sup.restart(dead)
        setup_node(sup, dead_idx)
        rounds_back = sup.converge(max_rounds=4 + n_nodes)
        out = sup.nodes[names[0]].call("classify", flows=[
            {"src": node_ip(dead_idx), "dst": node_ip(0),
             "sport": 47002, "dport": 8080, "ep_id": 1}], now=8100)
        gate(bool(out["allow"][0]),
             "restarted peer's pod not re-admitted after re-convergence")
        # the restarted node serves again (feeds its auditor + flowlog —
        # the relay below must span the RESTARTED mesh, not just the
        # pre-kill one)
        sup.nodes[dead].call("serve", flows=cross_flows(dead_idx, 48000),
                             batches=max(5, serve_batches // 4), now=8200,
                             timeout=600.0)
        sup.nodes[dead].call("flush")
        note("kill_restart", withdrawn=withdrawn,
             reconverged_rounds=rounds_back)

        # -- phase 3c: conflicting claims (needs a third observer) ----------
        if n_nodes >= 3:
            cprefix = "10.77.0.7/32"
            sup.add_endpoint(names[0], ["k8s:app=moving"], ["10.77.0.7"],
                             ep_id=7)
            sup.add_endpoint(names[1], ["k8s:app=moving"], ["10.77.0.7"],
                             ep_id=7)
            for _ in range(2):
                sup.broadcast("step")
            observers = names[2:]
            winners = {}
            for name in observers:
                st = sup.nodes[name].call("status")
                conf = st["mesh"]["conflicts"].get(cprefix)
                winners[name] = conf["winner"] if conf else None
                gate(any(k.startswith("clustermesh_conflicts_total")
                         for k in st["counters"]),
                     f"{name}: conflicting claim not counted")
            gate(len(set(winners.values())) == 1
                 and None not in winners.values(),
                 f"observers disagree on the conflict winner: {winners}")
            # every observer ingested the prefix under exactly one claim
            views = sup.views(only=observers)
            gate(all(cprefix in views[n] for n in observers),
                 f"conflicted prefix not served by observers: "
                 f"{ {n: cprefix in views[n] for n in observers} }")
            sup.remove_endpoint(names[0], 7, ips=["10.77.0.7"])
            sup.remove_endpoint(names[1], 7, ips=["10.77.0.7"])
            rounds_conf = sup.converge(max_rounds=4)
            note("conflict", winners=winners, resolved_rounds=rounds_conf)
        else:
            note("conflict", skipped=f"needs >= 3 nodes, ran {n_nodes}")

        # -- phase 3d: skewed publisher clock -------------------------------
        skewed = names[1]
        sup.nodes[skewed].call("skew", seconds=3600.0)
        for _ in range(2):
            sup.broadcast("step")
        views = sup.views()
        skew_prefix = f"{node_ip(1)}/32"
        holders = [n for n in names if n != skewed]
        skew_ok = all(skew_prefix in views[n] for n in holders)
        gate(skew_ok, f"peers dropped a live publisher whose clock is "
                      f"3600s ahead (views={ {n: skew_prefix in views[n] for n in holders} })")
        lags = {n: sup.nodes[n].call("status")["mesh"]
                ["replication_lag_p99_s"] for n in holders}
        gate(all(v >= 0 for v in lags.values()),
             f"replication lag went negative under clock skew: {lags}")
        sup.nodes[skewed].call("skew", seconds=0.0)
        note("skewed_clock", entries_survive=skew_ok, lag_p99=lags)

        # -- phase 4: relay fan-in over the nodes' flowlog sinks ------------
        sup.broadcast("flush")
        relay = FlowRelay({name: JsonlTailObserver(
            os.path.join(flows_dir, f"{name}.jsonl")) for name in names})
        merged = relay.poll(limit=100_000)
        seen_nodes = {r.get("node") for r in merged["flows"]
                      if not r.get("gap")}
        gate(seen_nodes == set(names),
             f"relay fan-in missing nodes: saw {sorted(seen_nodes)} of "
             f"{names}")
        note("relay", merged_flows=len(merged["flows"]),
             nodes=sorted(seen_nodes),
             lag=merged["lag"], gaps=len(merged["gaps"]))

        # -- phase 5: final parity audit + lag p99 --------------------------
        audits = sup.broadcast("audit")
        mismatched = {n: a["mismatched_rows"] for n, a in audits.items()}
        checked = {n: a["checked_rows"] for n, a in audits.items()}
        gate(all(v == 0 for v in mismatched.values()),
             f"parity mismatches at sampling 1.0: {mismatched}")
        gate(all(v > 0 for v in checked.values()),
             f"auditor checked nothing on some node: {checked}")
        statuses = sup.broadcast("status")
        lag_p99 = {n: statuses[n]["mesh"]["replication_lag_p99_s"]
                   for n in names}
        note("audit", checked=checked, mismatched=mismatched)
    finally:
        try:
            sup.stop_all()
        finally:
            shutil.rmtree(work, ignore_errors=True)
    elapsed = time.monotonic() - t_bench0

    if verbose:
        print(f"# cluster n={n_nodes} preset={preset} agg_fps={agg_fps:.0f}"
              f" lag_p99={max(lag_p99.values()):.4f}s gate_reasons="
              f"{gate_reasons}", file=sys.stderr)

    return {
        "metric": f"cluster_mesh_serving_n{n_nodes}_cfg7",
        "value": round(agg_fps, 1),
        "unit": "aggregate_flows/sec",
        "vs_baseline": round(agg_fps / (PER_CHIP_TARGET * n_nodes), 6),
        "nodes": n_nodes,
        "preset": preset,
        "datapath": datapath,
        "elapsed_s": round(elapsed, 1),
        "aggregate_fps": round(agg_fps, 1),
        "per_node_fps": {n: round(r["fps"], 1)
                         for n, r in per_node.items()},
        "replication_lag_p99_s": lag_p99,
        "replication_lag_p99_max_s": max(lag_p99.values()),
        "audit": {"checked_rows": checked, "mismatched_rows": mismatched},
        "phases": phases,
        "cluster_gate": {
            "failed": bool(gate_reasons),
            **({"reasons": gate_reasons} if gate_reasons else {}),
        },
    }


BUILDERS = {1: build_config1, 2: build_config2, 3: build_config3,
            4: build_config4, 5: build_config5}
METRIC_NAMES = {
    1: "cfg1_l3_cidr_1k_rules",
    2: "cfg2_multi_identity_l3l4",
    3: "cfg3_lpm_heavy",
    4: "cfg4_l7_lite",
    5: "cfg5_conntrack_churn_50k_rules",
}


# --------------------------------------------------------------------------- #
# runner
# --------------------------------------------------------------------------- #
def run_bench(config: int, preset: str, batch: int, batches: int,
              verbose: bool = False, windows: int = 5,
              shards: int = 1, rule_shards: int = 1,
              profile_dir: str = ""):
    """One config → throughput dict.

    Pipeline modeled: packed wire batches (kernels/records.pack_batch — the
    single-buffer format the C++ shim emits) are device_put with one-batch
    prefetch (the next transfer overlaps the current classify), then the
    fused classify step runs with donated CT buffers. Transfers ARE included
    in the headline timing.

    Statistics (round-4 verdict item 3: the harness must detect its own
    noise): ``windows`` (>=5) timing windows run per mode, each calibrated
    to span >=~0.3s (short windows measure dispatch granularity — the
    kernel clears 65k records in ~100us), and the MEDIAN is reported with
    the IQR alongside — never best-of. Three numbers are measured:
    - ``value``: sustained transfer-included median (what a long-running
      AF_XDP pipeline sees);
    - ``burst``: the transfer-included rate of the first pass;
    - ``compute_only``: device-resident batches — the kernels without the
      host↔device link. If ``value`` moves between runs but
      ``compute_only`` doesn't, the link moved, not the code.

    ``shards``/``rule_shards`` > 1 route the run through the production mesh
    path (parallel/mesh.make_sharded_classify_fn over a ('flows','rules')
    mesh): batches host-steered by flow hash, CT sharded per chip, verdict
    rows sharded + psum. Requires shards*rule_shards visible devices
    (JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=N
    for a virtual mesh on a 1-chip rig).
    """
    import jax
    import jax.numpy as jnp
    from cilium_tpu.compile.ct_layout import make_ct_arrays
    from cilium_tpu.kernels.classify import make_classify_fn
    from cilium_tpu.kernels.records import pack_batch

    t0 = time.time()
    snap, gen, v4_only = BUILDERS[config](preset)
    compile_s = time.time() - t0

    rng = np.random.default_rng(7)
    wi = jnp.int32(snap.world_index)
    sharded = shards * rule_shards > 1

    # pre-generate host batches (generation excluded from the timed loop —
    # the shim does it in C++; transfer included, it is part of the real
    # pipeline). One packed width per config so a single jit serves.
    # Configs with a pcap source replay it through the shim ingest instead.
    host_dicts = None
    pcap_fn = getattr(gen, "pcap_replay", None)
    if pcap_fn is not None:
        host_dicts = pcap_fn(batch, min(batches, 16))
    if host_dicts is None:
        host_dicts = [gen(rng, batch) for _ in range(min(batches, 16))]
    from cilium_tpu.utils import constants as C
    from cilium_tpu.kernels.records import pack_batch_v4

    if sharded:
        from cilium_tpu.parallel.mesh import (
            flow_shard_of, make_mesh, make_sharded_classify_fn,
            pad_snapshot_tensors, shard_ct_arrays, steer_batch)
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = make_mesh(shards, rule_shards)
        tensors_np = pad_snapshot_tensors(snap.tensors(), rule_shards)
        vspec = NamedSharding(mesh, P(None, None, "rules", None))
        repl = NamedSharding(mesh, P())
        tensors = {k: jax.device_put(v, vspec if k == "verdict" else repl)
                   for k, v in tensors_np.items()}
        ct_host = shard_ct_arrays(
            make_ct_arrays(snap.ct_config), shards)
        ct_sharding = NamedSharding(mesh, P("flows"))
        ct = {k: jax.device_put(v, ct_sharding) for k, v in ct_host.items()}
        fn = make_sharded_classify_fn(mesh, v4_only=v4_only, donate_ct=True)
        # pre-steer (the C++ shim's flow_shard does this in production);
        # one uniform per-shard size across batches → single trace
        lb = snap.lb if snap.lb.n_frontends else None
        per = max(int(np.bincount(
            flow_shard_of(hb, shards, lb=lb), minlength=shards).max())
            for hb in host_dicts)
        per = 1 << (per - 1).bit_length()
        host_batches = [steer_batch(hb, shards, per_shard=per, lb=lb)[0]
                        for hb in host_dicts]
    else:
        tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
        ct = {k: jnp.asarray(v)
              for k, v in make_ct_arrays(snap.ct_config).items()}
        fn = make_classify_fn(v4_only=v4_only, donate_ct=True, packed=True)
        # L7 presence must be decided across ALL pre-generated batches:
        # deciding from the first alone silently drops later batches'
        # http_path data (changing measured verdicts) whenever the first
        # happens to be L7-free.
        has_l7 = any(bool((hb["http_method"] != C.HTTP_METHOD_ANY).any()
                          or hb["http_path"].any()) for hb in host_dicts)
        has_v6 = any(bool(hb["is_v6"].any()) for hb in host_dicts)
        from cilium_tpu.kernels.records import (
            PACKA_EP_SLOT_MAX, _pad_dict_rows, pack_batch_addrdict)
        # addr-dict selection by BYTE COST vs the wire it would displace
        # (16B/record v4, or 44B/record full for v6): the dict only wins
        # when addresses repeat enough to pay for the dict rows
        u_max = 0 if has_l7 else max(
            np.unique(np.concatenate([hb["src"], hb["dst"]]),
                      axis=0).shape[0] for hb in host_dicts)
        u_pad = _pad_dict_rows(u_max, 1)
        addr_bytes = 12 * batch + 16 * u_pad
        alt_bytes = (44 if has_v6 else 16) * batch
        addr_ok = (not has_l7 and 0 < u_max <= 65536
                   and addr_bytes < alt_bytes
                   and all(not (hb["ep_slot"] > PACKA_EP_SLOT_MAX).any()
                           for hb in host_dicts))
        if addr_ok:
            # one dict row count across batches keeps a single trace
            host_batches = [pack_batch_addrdict(hb, min_addr_rows=u_pad)
                            for hb in host_dicts]
        elif not has_l7 and not has_v6:
            # compact 16B/record wire format — the transfer-bound fast path
            host_batches = [pack_batch_v4(hb) for hb in host_dicts]
        elif has_l7:
            # L7 dictionary wire: unique paths shipped once, 16-bit index
            # per record (~20B/record instead of 76-108B; the L7 path is
            # transfer-bound — compute-only runs >100M flows/s)
            from cilium_tpu.kernels.records import (
                _path_words_for, pack_batch_l7dict)
            pw = max(_path_words_for(hb) for hb in host_dicts)
            host_batches = [pack_batch_l7dict(hb, path_words=pw)
                            for hb in host_dicts]
        else:
            host_batches = [pack_batch(hb) for hb in host_dicts]

    # warmup / compile
    now = 10_000
    out, ct, counters = fn(tensors, ct,
                           jax.device_put(host_batches[0]),
                           jnp.uint32(now), wi)
    jax.block_until_ready(out)
    trace_s = time.time() - t0 - compile_s

    eff_batch = batch          # valid records per batch (steered pads aren't)

    # -- mode 1: transfer-included (headline) ------------------------------- #
    if profile_dir:
        # one profiled steady-state window → XProf trace (SURVEY §5)
        with jax.profiler.trace(profile_dir):
            for i in range(min(batches, 8)):
                now += 1
                out, ct, counters = fn(
                    tensors, ct, jax.device_put(host_batches[i % len(host_batches)]),
                    jnp.uint32(now), wi)
            jax.block_until_ready(out)
        print(f"# profiler trace written to {profile_dir}", file=sys.stderr)

    def _xfer_pass():
        nonlocal now, ct, out, counters
        nxt = jax.device_put(host_batches[0])
        for i in range(batches):
            cur = nxt
            nxt = jax.device_put(host_batches[(i + 1) % len(host_batches)])
            now += 1
            out, ct, counters = fn(tensors, ct, cur, jnp.uint32(now), wi)
        jax.block_until_ready(out)

    # calibration: the fused kernel clears 65k records in ~100us, so a
    # fixed-batch window can be milliseconds — measuring dispatch
    # granularity and single jitter bursts, not steady state (the round-4
    # "2.9x swing on identical code" failure). Repeat each window's pass
    # until it spans >= ~0.3s.
    t1 = time.time()
    _xfer_pass()
    first_pass_s = max(time.time() - t1, 1e-4)
    # the calibration pass doubles as the BURST rate probe: `value` reports
    # the sustained median, `burst` the first measured pass after warmup.
    # Compute-only separates the kernels from the link entirely.
    burst_tp = batches * eff_batch / first_pass_s
    xfer_reps = max(1, min(50, int(0.3 / first_pass_s)))
    xfer_tp = []
    for _w in range(windows):
        t1 = time.time()
        for _r in range(xfer_reps):
            _xfer_pass()
        xfer_tp.append(xfer_reps * batches * eff_batch / (time.time() - t1))

    # -- mode 2: compute-only (device-resident batches) --------------------- #
    if sharded:
        # pre-shard onto the mesh: a plain device_put would commit to one
        # device and every call would re-distribute (still transfer-bound)
        batch_sharding = NamedSharding(mesh, P("flows"))
        dev_batches = [jax.device_put(hb, batch_sharding)
                       for hb in host_batches[:4]]
    else:
        dev_batches = [jax.device_put(hb) for hb in host_batches[:4]]
    jax.block_until_ready(dev_batches)

    def _comp_pass():
        nonlocal now, ct, out, counters
        for i in range(batches):
            now += 1
            out, ct, counters = fn(tensors, ct,
                                   dev_batches[i % len(dev_batches)],
                                   jnp.uint32(now), wi)
        jax.block_until_ready(out)

    t1 = time.time()
    _comp_pass()
    comp_reps = max(1, min(200, int(0.3 / max(time.time() - t1, 1e-4))))
    comp_tp = []
    for _w in range(windows):
        t1 = time.time()
        for _r in range(comp_reps):
            _comp_pass()
        comp_tp.append(comp_reps * batches * eff_batch / (time.time() - t1))

    def _stats(vals):
        v = np.asarray(vals, dtype=np.float64)
        q1, med, q3 = np.percentile(v, [25, 50, 75])
        return float(med), float(q3 - q1)

    xfer_med, xfer_iqr = _stats(xfer_tp)
    comp_med, comp_iqr = _stats(comp_tp)

    # per-batch latency distribution: synchronous dispatch (transfer +
    # classify + result fence per batch) — the per-batch time an enforcing
    # shim would wait for a verdict bitmap, deliberately unpipelined.
    lat_n = max(20, min(batches, 50))
    lat_ms = np.empty(lat_n)
    for i in range(lat_n):
        now += 1
        t1 = time.time()
        cur = jax.device_put(host_batches[i % len(host_batches)])
        out, ct, counters = fn(tensors, ct, cur, jnp.uint32(now), wi)
        jax.block_until_ready(out["allow"])
        lat_ms[i] = (time.time() - t1) * 1e3
    p50_ms = float(np.percentile(lat_ms, 50))
    p99_ms = float(np.percentile(lat_ms, 99))

    if verbose:
        by = np.asarray(counters["by_reason_dir"]).reshape(256, 2)
        print(f"# config={config} preset={preset} platform="
              f"{jax.devices()[0].platform} batch={batch} batches={batches}"
              f" windows={windows} shards={shards}x{rule_shards}\n"
              f"# compile={compile_s:.1f}s trace={trace_s:.1f}s\n"
              f"# transfer-incl windows (Mfl/s): "
              f"{[round(x / 1e6, 1) for x in xfer_tp]}\n"
              f"# compute-only windows (Mfl/s): "
              f"{[round(x / 1e6, 1) for x in comp_tp]}\n"
              f"# sync batch latency p50={p50_ms:.2f}ms p99={p99_ms:.2f}ms"
              f" last-batch reasons={ {int(r): int(by[r].sum()) for r in np.nonzero(by.sum(1))[0]} }",
              file=sys.stderr)
    n_chips = shards * rule_shards
    return {
        "metric": f"flow_classify_throughput_{METRIC_NAMES[config]}",
        # sharded runs measure the whole mesh: report honestly per chip
        "value": round(xfer_med / n_chips, 1),
        "unit": "flows/sec/chip",
        "vs_baseline": round(xfer_med / n_chips / PER_CHIP_TARGET, 4),
        "iqr": round(xfer_iqr / n_chips, 1),
        "burst": round(burst_tp / n_chips, 1),
        "compute_only": round(comp_med / n_chips, 1),
        "compute_only_iqr": round(comp_iqr / n_chips, 1),
        "windows": windows,
        "p50_batch_ms": round(p50_ms, 3),
        "p99_batch_ms": round(p99_ms, 3),
        "batch": batch,
        "preset": preset,
        **({"shards": shards, "rule_shards": rule_shards,
            "mesh_total": round(xfer_med, 1)} if sharded else {}),
    }


def _bench_bucket(cfg, batch: int, shards: int, mode: str) -> int:
    """Dispatch-shape parity between the RSS modes: a steered flush
    always ships the FULL n_shards*seg_cap layout (= batch * headroom
    rows, mostly valid under balanced traffic), so the unsteered ring
    sizes its bucket to the same aggregate rows — equal rows-per-dispatch
    and equal staging memory; anything else compares dispatch-overhead
    amortization, not steering."""
    if shards > 1 and mode == "device":
        return batch * cfg.pipeline_shard_headroom
    return batch


def _bench_pipeline(dispatch_fn, met, cfg, batch: int, shards: int,
                    mode: str, shard_fn=None):
    """The bench's serving Pipeline — ONE construction shared by the
    primary pipeline_bench measurement and the rss A/B, so the two sides
    of the steered-vs-unsteered comparison can never drift into
    differently configured pipelines. min_bucket == max_bucket: every
    coalesced dispatch is the one device-optimal shape (no trace
    proliferation); stall_timeout wide — a cold-shape XLA compile must
    not look like a device stall to the watchdog."""
    from cilium_tpu.pipeline import Pipeline
    sharded = shards > 1
    steered = sharded and mode == "host"
    bucket = _bench_bucket(cfg, batch, shards, mode)
    return Pipeline(dispatch_fn, metrics=met, max_bucket=bucket,
                    min_bucket=bucket,
                    queue_batches=max(64, cfg.pipeline_queue_batches),
                    admission="block", block_timeout_s=60.0,
                    flush_ms=cfg.pipeline_flush_ms,
                    inflight=cfg.pipeline_inflight,
                    stall_timeout_s=300.0,
                    n_shards=shards if steered else 1,
                    shard_fn=shard_fn if steered else None,
                    shard_headroom=cfg.pipeline_shard_headroom,
                    mesh_shards=shards if sharded else 0,
                    rss_mode=mode if sharded else "host")


def pipeline_bench(config: int, preset: str, batch: int, batches: int,
                   windows: int = 3, verbose: bool = False,
                   trace: bool = False, shards: int = 1,
                   rss: str = "host"):
    """Serial vs pipelined ingestion on one config, through the real
    ``DatapathBackend`` boundary (JITDatapath behind the Pipeline
    scheduler), over the same ingest stream: the shim's rx polls deliver
    sub-full chunks (``batch // 8`` records — an AF_XDP poll budget), and

    - **serial** classifies each chunk as it arrives with a blocking wait
      (today's per-poll serving path: build → transfer → classify →
      verdict fence, strictly sequential);
    - **pipelined** submits the same chunks to the scheduler, which
      coalesces them into full ``batch``-row buckets and keeps
      ``pipeline_inflight`` dispatches in flight via ``classify_async`` —
      host staging/transfer overlapped with the previous bucket's device
      compute, one device shape, 8x fewer dispatches.

    Same flows, same CT geometry, same kernel — the delta is scheduling.

    ``shards`` > 1 routes both modes through the flow-sharded mesh (one
    admission queue, steered staging, per-shard wire segments): serial
    classifies through the sync sharded path (steer at classify time),
    pipelined through the pre-steered staging ring. Requires ``shards``
    visible devices; tracing auto-enables so the artifact always carries
    the steer/scatter span split.

    ``rss="device"`` (with ``shards`` > 1) measures the device-side RSS
    path instead — arrival-order staging, the in-kernel ring ppermute CT
    exchange, no host steer/scatter anywhere (the schema check asserts
    those spans are ABSENT) — and appends a steered-vs-unsteered A/B
    (``rss_ab``): balanced traffic plus a skewed stream whose flows all
    hash to one CT shard, where the device path's win is structural
    (one segment serializes the steered mesh) rather than incremental.
    The ``rss_gate`` (exit 4) always arms the structural half — skew
    immunity (the steered path must degrade under skew by
    CILIUM_TPU_BENCH_RSS_SKEW_IMMUNITY_MIN more than the device path)
    plus zero device sheds — and arms the absolute throughput
    comparison (balanced within CILIUM_TPU_BENCH_RSS_AB_SLACK, strict
    win on skew) on TPU only: the CPU virtual mesh serializes the
    chips onto a couple of host cores, which inflates the exchange's
    per-chip CT redundancy ~n× in a way real hardware never sees (the
    same rig-unmeasurable-by-construction split as the --kernels
    fused gate).
    """
    from cilium_tpu.observe.trace import TRACER
    from cilium_tpu.pipeline import Pipeline
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.metrics import Metrics

    sharded = shards > 1
    device_rss = sharded and rss == "device"
    trace = trace or sharded
    if trace:
        # --trace: sample every submission so the per-stage summary in the
        # JSON artifact covers the whole run (admission/microbatch/dispatch/
        # finalize + the datapath's pack/transfer/compute split). This is
        # the diagnostic mode — production sampling is 1/64-style.
        TRACER.configure(sample_rate=1.0, capacity=65536)
        TRACER.reset()
    t0 = time.time()
    snap, gen, v4_only = BUILDERS[config](preset)
    compile_s = time.time() - t0
    cfg = DaemonConfig(ct_capacity=snap.ct_config.capacity,
                       probe_depth=snap.ct_config.probe_depth,
                       v4_only=v4_only, batch_size=batch, n_shards=shards,
                       rss_mode=rss if sharded else "host")
    dp = JITDatapath(cfg)
    placed = dp.place(snap)
    rng = np.random.default_rng(7)
    chunk = max(64, batch // 8)
    chunks = []
    for _ in range(min(batches, 8)):
        full = gen(rng, batch)
        chunks.extend({k: v[j:j + chunk] for k, v in full.items()}
                      for j in range(0, batch, chunk))
    now = [20_000]

    # warmup both device shapes (chunk for serial, full bucket for pipelined)
    dp.classify(placed, snap, dict(chunks[0]), now[0])
    dp.classify(placed, snap, gen(rng, batch), now[0])

    def serial_pass():
        for i in range(batches * (batch // chunk)):
            now[0] += 1
            dp.classify(placed, snap, chunks[i % len(chunks)], now[0])

    lb = snap.lb if snap.lb.n_frontends else None

    def shard_fn(b):
        from cilium_tpu.parallel.mesh import flow_shard_of
        return flow_shard_of(b, shards, lb=lb)

    def make_pipeline(met):
        mode = "device" if device_rss else "host"
        steered = sharded and not device_rss

        def dispatch_fn(b, n, steer_rev=None):
            # fixed snapshot for the whole run: a pre-steered bucket can
            # never be stale, whatever revision it was steered under
            fin = dp.classify_async(placed, snap, b, n,
                                    pre_steered=steered)
            return lambda: fin()[0]
        return _bench_pipeline(dispatch_fn, met, cfg, batch, shards, mode,
                               shard_fn=shard_fn)

    met = Metrics()
    pl = make_pipeline(met)        # long-lived, like a serving daemon's
    # pack attribution for the PIPELINED passes only — the serial
    # comparison mode classifies through the sync path, whose allocating
    # steer is counted "steered" by design and must not pollute the
    # steered-staging acceptance numbers
    pack_pipe = {k: 0 for k in dp.pack_stats}

    def pipe_pass():
        base = dict(dp.pack_stats)
        for i in range(batches * (batch // chunk)):
            now[0] += 1
            pl.submit(chunks[i % len(chunks)], now=now[0])
        assert pl.drain(timeout=600), "pipeline drain timed out"
        for k in pack_pipe:
            pack_pipe[k] += dp.pack_stats[k] - base.get(k, 0)

    serial_pass()                   # calibrate both modes on a warm link
    pipe_pass()
    serial_tp, pipe_tp = [], []
    for _w in range(windows):
        # alternate which mode runs first so CT-occupancy / link drift
        # across the run cannot systematically favor one mode
        order = ((serial_pass, serial_tp), (pipe_pass, pipe_tp))
        if _w % 2:
            order = order[::-1]
        for fn, acc in order:
            t1 = time.time()
            fn()
            acc.append(batches * batch / (time.time() - t1))

    def _med(vals):
        return float(np.percentile(np.asarray(vals, np.float64), 50))

    serial_med, pipe_med = _med(serial_tp), _med(pipe_tp)
    qw = met.histograms.get("pipeline_queue_wait_seconds")
    bl = met.histograms.get("pipeline_batch_latency_seconds")
    stats = pl.stats()
    pl.close(timeout=30)
    if verbose:
        print(f"# pipeline bench config={config} preset={preset} "
              f"batch={batch} batches={batches} compile={compile_s:.1f}s\n"
              f"# serial windows (Mfl/s): "
              f"{[round(x / 1e6, 1) for x in serial_tp]}\n"
              f"# pipelined windows (Mfl/s): "
              f"{[round(x / 1e6, 1) for x in pipe_tp]}", file=sys.stderr)
    doc = {
        "metric": f"pipeline_ingestion_{METRIC_NAMES[config]}",
        "value": round(pipe_med, 1),
        "unit": "flows/sec",
        "vs_baseline": round(pipe_med / PER_CHIP_TARGET, 4),
        "serial_flows_per_sec": round(serial_med, 1),
        "pipelined_flows_per_sec": round(pipe_med, 1),
        "speedup_vs_serial": round(pipe_med / max(serial_med, 1e-9), 3),
        "queue_wait_p50_ms": round(qw.quantile(0.5) * 1e3, 3) if qw else 0.0,
        "queue_wait_p99_ms": round(qw.quantile(0.99) * 1e3, 3) if qw else 0.0,
        "batch_latency_p50_ms": round(bl.quantile(0.5) * 1e3, 3)
        if bl else 0.0,
        "fill_ratio": stats["fill_ratio_avg"],
        "flush_reasons": stats["flush_reasons"],
        # guard-layer counters: overload/degradation behavior belongs in
        # the artifact (a healthy run shows zeros; a shedding or
        # breaker-tripping run is visibly not a clean number)
        "shed_total": stats.get("shed_total", 0),
        "shed_reasons": stats.get("shed_reasons", {}),
        "admission_drops": stats.get("admission_drops", 0),
        "breaker": stats.get("breaker", {}),
        "restarts": stats.get("restarts", 0),
        "pipeline_state": stats.get("state", "ok"),
        "inflight": cfg.pipeline_inflight,
        "ingest_chunk": chunk,
        "windows": windows,
        "batch": batch,
        "batches": batches,
        "preset": preset,
        # --trace: per-stage span summary (p50/p99/max per stage, ms)
        **({"trace_spans": TRACER.summary(),
            "trace_stats": TRACER.stats()} if trace else {}),
    }
    if sharded:
        doc.update({
            "shards": shards,
            "rss": "device" if device_rss else "host",
            "aggregate_flows_per_sec": round(pipe_med, 1),
            "per_chip_flows_per_sec": round(pipe_med / shards, 1),
            "vs_baseline": round(pipe_med / shards / PER_CHIP_TARGET, 4),
            "pack_stats": pack_pipe,
            "pack_stats_total": dict(dp.pack_stats),
            **({"shard_fill": stats.get("shard_fill"),
                "shard_rows_total": stats.get("shard_rows_total"),
                "shard_capacity": stats.get("shard_capacity")}
               if not device_rss else
               {"rss_exchange": dp.rss_exchange_stats()}),
        })
        spans = doc.get("trace_spans", {})
        doc["steer_split"] = {k: spans[k] for k in
                              ("pipeline.steer", "pipeline.stage_write",
                               "datapath.pack", "datapath.steer")
                              if k in spans}
        if device_rss:
            doc["rss_ab"] = _rss_ab(
                pipe_med, chunks, gen, snap, lb, cfg, batch, batches,
                chunk, shards, now, _med, verbose=verbose)
            import jax
            doc["rss_gate"] = _rss_gate(doc["rss_ab"],
                                        jax.devices()[0].platform)
        doc.update(_sharded_schema_check(doc, shards))
    return doc


def _rss_ab(device_balanced_fps, chunks, gen, snap, lb, cfg, batch,
            batches, chunk, shards, now, med, verbose=False):
    """The steered-vs-unsteered A/B the device-RSS artifact carries: the
    same balanced chunk stream through a HOST-steered mesh, plus a skewed
    stream — every flow hashing to ONE CT shard (rejection-sampled
    through the real steer hash) — through both modes. On skewed traffic
    the device path's win is structural: classify work spreads by arrival
    while host steering serializes the whole mesh behind one segment."""
    import time as _time
    from cilium_tpu.parallel.mesh import flow_shard_of
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.metrics import Metrics

    def skewed_stream(n_chunks):
        rng = np.random.default_rng(1123)
        need = n_chunks * chunk
        cols, got = None, 0
        while got < need:
            full = gen(rng, batch)
            sh = flow_shard_of(full, shards, lb=lb)
            keep = (sh == 0) & np.asarray(full["valid"], dtype=bool)
            if cols is None:
                cols = {k: [] for k in full}
            for k, v in full.items():
                cols[k].append(np.asarray(v)[keep])
            got += int(keep.sum())
        cat = {k: np.concatenate(v)[:need] for k, v in cols.items()}
        return [{k: v[j:j + chunk] for k, v in cat.items()}
                for j in range(0, need, chunk)]

    def build(mode):
        steered = mode == "host"
        cfg_m = DaemonConfig(ct_capacity=snap.ct_config.capacity,
                             probe_depth=snap.ct_config.probe_depth,
                             v4_only=cfg.v4_only,
                             batch_size=_bench_bucket(cfg, batch, shards,
                                                      mode),
                             n_shards=shards, rss_mode=mode)
        dp_m = JITDatapath(cfg_m)
        placed_m = dp_m.place(snap)

        def dispatch_fn(b, n, steer_rev=None):
            fin = dp_m.classify_async(placed_m, snap, b, n,
                                      pre_steered=steered)
            return lambda: fin()[0]
        return _bench_pipeline(
            dispatch_fn, Metrics(), cfg, batch, shards, mode,
            shard_fn=lambda b: flow_shard_of(b, shards, lb=lb))

    def one_pass(pl_m, chunk_list):
        for i in range(batches * (batch // chunk)):
            now[0] += 1
            pl_m.submit(chunk_list[i % len(chunk_list)], now=now[0])
        assert pl_m.drain(timeout=600), "rss A/B drain timed out"

    def measure_pair(chunk_list, n_windows=3):
        """Both modes over the same stream, windows INTERLEAVED with
        alternating order — rig drift (CPU freq, background load, CT
        aging) hits both modes instead of whichever ran second."""
        pls = {m: build(m) for m in ("host", "device")}
        for pl_m in pls.values():
            one_pass(pl_m, chunk_list)       # warm: traces + pools
        fps = {"host": [], "device": []}
        for w in range(n_windows):
            order = ("host", "device") if w % 2 == 0 else ("device", "host")
            for m in order:
                t1 = _time.time()
                one_pass(pls[m], chunk_list)
                fps[m].append(batches * batch / (_time.time() - t1))
        stats_pair = {m: pls[m].stats() for m in pls}
        for pl_m in pls.values():
            pl_m.close(timeout=30)
        return {m: med(v) for m, v in fps.items()}, stats_pair

    skewed = skewed_stream(max(4, min(8, len(chunks))))
    bal, _bal_st = measure_pair(chunks)
    sk, sk_st = measure_pair(skewed)
    if verbose:
        print(f"# rss A/B: balanced host={bal['host'] / 1e6:.2f} "
              f"device={bal['device'] / 1e6:.2f} Mfl/s "
              f"(primary device run: {device_balanced_fps / 1e6:.2f}); "
              f"skewed host={sk['host'] / 1e6:.2f} "
              f"device={sk['device'] / 1e6:.2f}", file=sys.stderr)
    return {
        "balanced": {
            "host_flows_per_sec": round(bal["host"], 1),
            "device_flows_per_sec": round(bal["device"], 1),
            "device_over_host": round(
                bal["device"] / max(bal["host"], 1e-9), 3),
        },
        "skewed": {
            "host_flows_per_sec": round(sk["host"], 1),
            "device_flows_per_sec": round(sk["device"], 1),
            "device_over_host": round(
                sk["device"] / max(sk["host"], 1e-9), 3),
            # the failure mode the device path retires: a steered mesh
            # under all-one-shard traffic sheds (steer_overflow) or
            # serializes — either shows here
            "host_shed_total": sk_st["host"].get("shed_total", 0),
            "device_shed_total": sk_st["device"].get("shed_total", 0),
        },
    }


#: balanced-traffic slack for the rss_gate's TPU-armed absolute half:
#: device mode must hold >= host/slack on balanced traffic and win
#: strictly on skew
RSS_AB_SLACK = float(os.environ.get("CILIUM_TPU_BENCH_RSS_AB_SLACK", "1.1"))
#: the always-armed structural gate: under the all-one-shard stream the
#: steered path must degrade at least this factor MORE than the device
#: path does (host_bal/host_sk vs dev_bal/dev_sk) — the skewed-flood
#: imbalance failure mode the exchange exists to retire, measurable on
#: any rig because it is a ratio of ratios
RSS_SKEW_IMMUNITY_MIN = float(os.environ.get(
    "CILIUM_TPU_BENCH_RSS_SKEW_IMMUNITY_MIN", "1.3"))


def _rss_gate(ab: dict, platform: str) -> dict:
    """Two-tier gate, mirroring the --kernels fused gate's platform
    split: the ABSOLUTE throughput comparison (device >= host/slack on
    balanced, strictly > on skew) arms only on TPU — on the CPU smoke
    rig the virtual mesh serializes every chip's work onto a couple of
    host cores, so the exchange's per-chip CT redundancy (the price of
    shedless skew tolerance with static shapes) inflates ~n_shards×
    in wall clock in a way n real chips never see; gating fps there
    measures the rig, not the code. The STRUCTURAL half — skew
    immunity + zero device sheds — is a ratio of ratios and always
    arms: steered throughput must collapse under the all-one-shard
    stream while the device path holds, or the whole point of the
    mode is missing."""
    reasons = []
    bal, sk = ab["balanced"], ab["skewed"]
    eps = 1e-9
    host_deg = bal["host_flows_per_sec"] / max(sk["host_flows_per_sec"],
                                               eps)
    dev_deg = bal["device_flows_per_sec"] / max(
        sk["device_flows_per_sec"], eps)
    immunity = host_deg / max(dev_deg, eps)
    if immunity < RSS_SKEW_IMMUNITY_MIN:
        reasons.append(
            f"skew immunity {immunity:.2f} < {RSS_SKEW_IMMUNITY_MIN}: "
            f"steered degrades {host_deg:.2f}x under skew vs device "
            f"{dev_deg:.2f}x — the structural win is missing")
    if sk["device_shed_total"]:
        reasons.append(
            f"skewed: device path shed {sk['device_shed_total']} "
            "submissions (no shed class should exist without steering)")
    throughput_armed = platform == "tpu"
    if throughput_armed:
        if bal["device_over_host"] < 1.0 / RSS_AB_SLACK:
            reasons.append(
                f"balanced: device {bal['device_flows_per_sec']} < host "
                f"{bal['host_flows_per_sec']}/{RSS_AB_SLACK}")
        if sk["device_over_host"] <= 1.0:
            reasons.append(
                f"skewed: device {sk['device_flows_per_sec']} <= host "
                f"{sk['host_flows_per_sec']}")
    return {
        "failed": bool(reasons),
        "slack": RSS_AB_SLACK,
        "skew_immunity_min": RSS_SKEW_IMMUNITY_MIN,
        "host_skew_degradation": round(host_deg, 3),
        "device_skew_degradation": round(dev_deg, 3),
        "skew_immunity_ratio": round(immunity, 3),
        # False = this artifact came from a rig whose absolute fps
        # comparison is unmeasurable by construction (see docstring);
        # the ROADMAP item-6 v5e pass arms it
        "throughput_gate_armed": throughput_armed,
        **({"reasons": reasons} if reasons else {}),
    }


#: max tolerated per-shard traffic skew, expressed as a multiple of the
#: fair share (1/shards of all rows) one shard may carry before the
#: artifact is failed — a healthy flow hash over uniform traffic sits
#: near 1x; one saturated shard means the mesh throughput number is a lie
SHARD_SKEW_LIMIT = float(os.environ.get(
    "CILIUM_TPU_BENCH_SHARD_SKEW_LIMIT", "3"))


def _sharded_schema_check(doc: dict, shards: int) -> dict:
    """Artifact self-check for sharded runs: the per-chip/aggregate fields
    must be present, the steer/scatter attribution must be in the split,
    the steered path must not have fallen back to allocating packs, and —
    the real balance check — every flow shard must actually have carried
    traffic within SHARD_SKEW_LIMIT of the mean (`shard_rows_total` is
    counted independently at ingest, so a steering bug that parks the work
    on one chip fails the artifact loudly instead of hiding inside an
    aggregate headline).

    Device-RSS artifacts (``doc["rss"] == "device"``) invert the span
    contract: the host ``pipeline.steer``/``datapath.steer`` spans must
    be ABSENT (their presence means the host tax the mode exists to
    delete is still being paid), and the per-shard balance check does
    not apply (rows never group by shard on the host — that is the
    point). This is what keeps steered and unsteered artifacts
    comparable under ``--compare`` without tripping the
    span-attribution gate."""
    problems = []
    rss = doc.get("rss", "host")
    if doc.get("aggregate_flows_per_sec", 0) <= 0 \
            or doc.get("per_chip_flows_per_sec", 0) <= 0:
        problems.append("missing per-chip/aggregate throughput")
    spans = {}
    spans.update(doc.get("stage_split") or {})
    spans.update(doc.get("steer_split") or {})
    spans.update(doc.get("trace_spans") or {})
    if rss == "device":
        for sp in ("pipeline.steer", "datapath.steer"):
            if sp in spans:
                problems.append(
                    f"{sp} span present in a device-RSS artifact "
                    "(host steering still running)")
    elif "pipeline.steer" not in doc.get("steer_split", {}) \
            and "pipeline.steer" not in doc.get("stage_split", {}):
        problems.append("steer span missing from the stage split")
    pack = doc.get("pack_stats") or {}
    if pack.get("pack_fallback_steered", 0):
        problems.append(
            f'pack_fallback{{reason="steered"}} = '
            f'{pack["pack_fallback_steered"]} on the steered path')
    rows = doc.get("shard_rows_total")
    if rss == "device":
        pass            # no host-side per-shard grouping exists to judge
    elif not rows or len(rows) != shards:
        problems.append("shard_rows_total missing from pipeline stats")
    elif sum(rows) >= 64 * shards:       # enough traffic to judge balance
        total = sum(rows)
        # judged as max SHARE of total vs the fair share 1/shards: the
        # max-share threshold is capped at 0.95 so the check stays live
        # for every mesh size (a max/mean formulation is mathematically
        # dead whenever the limit reaches the shard count — a 2-shard
        # mesh can never exceed 2x its mean)
        share_limit = min(0.95, SHARD_SKEW_LIMIT / shards)
        max_share = max(rows) / total
        if min(rows) == 0:
            problems.append(f"idle shard(s): shard_rows_total={rows}")
        elif max_share > share_limit:
            problems.append(
                f"shard skew: one shard carries {max_share:.0%} of rows "
                f"(> {share_limit:.0%} = {SHARD_SKEW_LIMIT}x fair share): "
                f"shard_rows_total={rows}")
    return {"schema_check": "ok" if not problems else "failed",
            **({"schema_check_problems": problems} if problems else {})}


#: BENCH_r05 reference points for the single-chip regression gate (the
#: CPU smoke rig numbers the zero-copy PR shipped with); override via env
#: when re-baselining on different hardware. NOISE_FACTOR is deliberately
#: generous — the gate exists to catch the steered-staging refactor
#: regressing the single-shard path wholesale, not 5% jitter.
REF_PACK_P50_MS = float(os.environ.get(
    "CILIUM_TPU_BENCH_REF_PACK_P50_MS", "0.116"))
REF_INGEST_FPS = float(os.environ.get(
    "CILIUM_TPU_BENCH_REF_INGEST_FPS", "0"))       # 0 = unknown, skip
BENCH_NOISE_FACTOR = float(os.environ.get(
    "CILIUM_TPU_BENCH_NOISE_FACTOR", "1.75"))


def _single_chip_regression_gate(spans: dict, fps: float) -> dict:
    """--shards 1 gate: the steered-staging refactor must not tax the
    single-chip path — fail the artifact when pack p50 (or, with a known
    reference, end-to-end fps) regresses beyond noise vs BENCH_r05."""
    gate = {
        "pack_p50_ms": spans.get("datapath.pack", {}).get("p50_ms"),
        "ref_pack_p50_ms": REF_PACK_P50_MS,
        "steer_p50_ms": spans.get("pipeline.steer", {}).get("p50_ms", 0.0),
        "fps": round(fps, 1),
        "ref_fps": REF_INGEST_FPS or None,
        "noise_factor": BENCH_NOISE_FACTOR,
        # the default reference is the BENCH_r05 CPU smoke rig: a `failed`
        # verdict from a different-speed machine with no env-pinned
        # baseline is a rig mismatch, not a regression — consumers can
        # tell from this field
        "ref_source": "env" if "CILIUM_TPU_BENCH_REF_PACK_P50_MS"
                      in os.environ else "BENCH_r05-default",
    }
    reasons = []
    p50 = gate["pack_p50_ms"]
    if p50 is not None and REF_PACK_P50_MS > 0 \
            and p50 > REF_PACK_P50_MS * BENCH_NOISE_FACTOR:
        reasons.append(f"pack p50 {p50}ms > "
                       f"{REF_PACK_P50_MS}*{BENCH_NOISE_FACTOR}ms")
    if REF_INGEST_FPS > 0 and fps < REF_INGEST_FPS / BENCH_NOISE_FACTOR:
        reasons.append(f"fps {fps:.0f} < "
                       f"{REF_INGEST_FPS}/{BENCH_NOISE_FACTOR}")
    gate["failed"] = bool(reasons)
    if reasons:
        gate["reasons"] = reasons
    return gate


def ingest_bench(preset: str, batch: int, n_frames: int = 0,
                 verbose: bool = False, shards: int = 1,
                 observer: bool = False, rss: str = "host"):
    """Shim→verdict end-to-end over the mock rings: frames are injected
    NIC-side into the rx ring, the async feeder (shim/feeder.py) harvests
    on a budget into reusable poll buffers, the pipeline coalesces and
    dispatches through ``classify_async`` with in-place pack + pinned
    staging, and verdicts apply FIFO back into the shim (forwarded frames
    drain from the tx ring). Tracing runs at sampling 1.0 so the JSON
    artifact carries the full harvest/stage/pack/transfer/compute split
    plus staging-ring occupancy — where the remaining gap lives."""
    from cilium_tpu.observe.trace import TRACER
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import JITDatapath
    from cilium_tpu.runtime.engine import Engine
    from cilium_tpu.shim.bindings import LIB_PATH, FlowShim, build_frame

    if not os.path.exists(LIB_PATH):
        raise SystemExit(f"bench --ingest: {LIB_PATH} not built "
                         "(make -C cilium_tpu/shim)")
    if n_frames <= 0:
        n_frames = 10_000 if preset == "smoke" else 100_000
    TRACER.configure(sample_rate=1.0, capacity=65536)
    TRACER.reset()
    from cilium_tpu.model.rules import parse_rule
    cfg = DaemonConfig(ct_capacity=1 << (14 if preset == "smoke" else 18),
                       auto_regen=False, batch_size=batch,
                       pipeline_flush_ms=1.0, pipeline_queue_batches=256,
                       ingest_pool_batches=8,
                       # the observer A/B soak needs the columnar ring
                       # armed in BOTH windows (the flowlog predates this
                       # bench; what's measured is the observe machinery)
                       flowlog_mode="all" if observer else "none",
                       n_shards=shards,
                       rss_mode=rss if shards > 1 else "host")
    eng = Engine(cfg, datapath=JITDatapath(cfg))
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    # a non-trivial ruleset so classification isn't a no-op: cfg1-style
    # CIDR allow/deny slice
    rules = []
    for i in range(200):
        a, b = 1 + (i % 200), (i * 7) % 256
        block = {"toCIDR": [f"{a}.{b}.0.0/16"]}
        key = "egressDeny" if i % 3 == 2 else "egress"
        rules.append(parse_rule({
            "endpointSelector": {"matchLabels": {"app": "web"}},
            key: [block]}))
    eng.repo.add(rules)
    eng.apply_policy([{
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "egress": [{"toCIDR": ["10.0.0.0/8"],
                    "toPorts": [{"ports": [{"port": "443",
                                            "protocol": "TCP"}]}]}]}])
    eng.regenerate()

    shim_batch = min(256, batch)
    shim = FlowShim(batch_size=shim_batch, timeout_us=200)
    shim.register_endpoint("192.168.1.10", 1)
    shim.mock_rings_init(ring_size=256, frame_size=2048, n_frames=256)
    feeder = eng.start_feeder(shim)

    # pre-build the frame set (frame crafting is not the measured path)
    rng = np.random.default_rng(11)
    pool = [build_frame("192.168.1.10",
                        f"10.{rng.integers(0, 4)}.2.{rng.integers(1, 250)}"
                        if i % 4 else f"{1 + i % 200}.9.9.9",
                        40000 + (i % 20000),
                        443 if i % 4 else 80)
            for i in range(512)]
    # warmup: the first dispatches JIT-compile the classify shapes
    for f in pool[:64]:
        shim.mock_rx_inject(f)
    deadline = time.time() + 120
    while True:
        shim.mock_tx_drain(256)
        st = shim.stats()
        if st["verdict_passes"] + st["verdict_drops"] >= 64:
            break
        if time.time() > deadline:
            raise SystemExit(
                "bench --ingest: the 64 warm-up frames got no verdict in "
                f"120s (shim {st}, pipeline {eng.pipeline_stats()})")
        time.sleep(0.005)
    base = shim.stats()
    done_base = base["verdict_passes"] + base["verdict_drops"] \
        + base["tx_full_drops"]
    TRACER.reset()     # drop warmup spans (cold XLA compile) from the split
    # e2e baseline for the same reason: the p50/p99 split is computed from
    # the DELTA bucket counts over the measured window, so the cold-compile
    # warmup batches can't dominate the tail
    _e2e = eng.metrics.histograms.get("ingest_e2e_latency_seconds")
    e2e_base = list(_e2e.snapshot()[1]) if _e2e is not None else None
    slo_base = feeder.slo_burns          # same window discipline for burns

    t0 = time.time()
    injected = 0
    stalls = 0
    deadline = time.time() + 600
    while injected < n_frames and time.time() < deadline:
        if shim.mock_rx_inject(pool[injected % len(pool)]) == 0:
            injected += 1
        else:
            shim.mock_tx_drain(256)
            stalls += 1
            if stalls % 64 == 0:
                time.sleep(0.0002)
    timed_out = True
    while time.time() < deadline:
        shim.mock_tx_drain(256)
        st = shim.stats()
        if st["verdict_passes"] + st["verdict_drops"] \
                + st["tx_full_drops"] - done_base >= injected:
            timed_out = False
            break
        time.sleep(0.002)
    elapsed = time.time() - t0
    fps = injected / max(elapsed, 1e-9)

    pstats = eng.pipeline_stats() or {}
    fstats = feeder.stats()
    pack_stats = dict(eng.datapath.pack_stats)
    # measured-window e2e split (delta bucket counts vs the post-warmup
    # baseline; EMPTY_QUANTILE → 0.0 when nothing applied in the window)
    from cilium_tpu.runtime.metrics import quantile_from, quantile_is_empty
    e2e_p50_ms = e2e_p99_ms = 0.0
    hist = eng.metrics.histograms.get("ingest_e2e_latency_seconds")
    if hist is not None:
        hb, hc, _ht, _hn = hist.snapshot()
        if e2e_base is not None:
            hc = [a - b for a, b in zip(hc, e2e_base)]
        p50, p99 = quantile_from(hb, hc, 0.5), quantile_from(hb, hc, 0.99)
        if not quantile_is_empty(p50):
            e2e_p50_ms = round(p50 * 1e3, 3)
            e2e_p99_ms = round(p99 * 1e3, 3)
    spans = TRACER.summary()
    keep = ("shim.harvest", "pipeline.steer", "pipeline.stage_write",
            "pipeline.microbatch", "pipeline.dispatch", "pipeline.finalize",
            "datapath.pack", "datapath.steer", "datapath.transfer",
            "datapath.compute")

    # -- observer overhead attestation (ISSUE 11 acceptance): D/A/D/A
    # windows over the warm engine — disarmed vs a live follow-mode
    # observer polling every 5ms with a compound filter armed (verdict +
    # port + CIDR; selective, so matched rows are payload, not noise).
    # Best-of-two per arm absorbs rig noise; the <2% budget is recorded
    # (and gated by `make observe-smoke`) in the artifact.
    observer_doc = None
    if observer:
        import threading as _threading

        from cilium_tpu.observe.observer import (FlowFilter, FlowObserver,
                                                 FollowCursor)
        obs_filters = [FlowFilter(verdict="DROPPED", dports=(9999,),
                                  dst_cidrs=("10.0.0.0/8",))]

        def _window(n, armed):
            stop_evt = _threading.Event()
            fstat = {"polls": 0, "matched": 0, "gaps": 0, "dropped": 0,
                     "poll_busy_s": 0.0}

            samples = []

            def _follow():
                cur = FollowCursor(FlowObserver(eng.flowlog),
                                   allow=obs_filters)
                # Per-poll durations are sampled and summarized as
                # median x count: a raw wall-time sum would bill GIL /
                # scheduler descheduling (10ms quanta) to a ~20us poll,
                # and thread_time's granularity is coarser than the polls
                # themselves. 5ms cadence is already 60x the CLI
                # follower's 300ms poll; per-tick cost scales with
                # throughput (records since last tick), not cadence.
                while not stop_evt.is_set():
                    p_t0 = time.perf_counter()
                    for r in cur.poll(limit=8192):
                        if r.get("gap"):
                            fstat["gaps"] += 1
                            fstat["dropped"] += r["dropped"]
                        else:
                            fstat["matched"] += 1
                    samples.append(time.perf_counter() - p_t0)
                    fstat["polls"] += 1
                    time.sleep(0.005)

            th = None
            if armed:
                th = _threading.Thread(target=_follow, daemon=True)
                th.start()
            st0 = shim.stats()
            done0 = st0["verdict_passes"] + st0["verdict_drops"] \
                + st0["tx_full_drops"]
            w_t0 = time.time()
            inj = stl = 0
            w_dl = time.time() + 240
            while inj < n and time.time() < w_dl:
                if shim.mock_rx_inject(pool[inj % len(pool)]) == 0:
                    inj += 1
                else:
                    shim.mock_tx_drain(256)
                    stl += 1
                    if stl % 64 == 0:
                        time.sleep(0.0002)
            while time.time() < w_dl:
                shim.mock_tx_drain(256)
                s = shim.stats()
                if s["verdict_passes"] + s["verdict_drops"] \
                        + s["tx_full_drops"] - done0 >= inj:
                    break
                time.sleep(0.002)
            w_elapsed = max(time.time() - w_t0, 1e-9)
            if th is not None:
                stop_evt.set()
                th.join(5)
            if samples:
                med = sorted(samples)[len(samples) // 2]
                fstat["poll_p50_us"] = round(med * 1e6, 1)
                fstat["poll_busy_s"] = med * fstat["polls"]
            fstat["elapsed_s"] = round(w_elapsed, 4)
            fstat["poll_busy_s"] = round(fstat["poll_busy_s"], 5)
            return inj / w_elapsed, fstat

        # The GATED overhead is the observer's measured serving-time share
        # during the armed windows (summed in-poll time / window time):
        # deterministic where wall-clock fps windows on a shared CPU rig
        # swing 2-3x from CT drift / GC ticks / scheduler noise — far
        # above a 2% signal. The D/A fps windows still ride the artifact
        # as context (best-of per arm), with a loose 25% sanity ratio.
        w_n = max(1500, n_frames // 8)
        _window(w_n, False)              # warmup (not recorded)
        # calibrate the per-poll cost synchronously on the LIVE ring (a
        # representative 64-record backlog, filters armed): in-window
        # wall samples bill GIL handoffs — time the pipeline is actually
        # serving — to the observer, so the attested overhead is
        # calibrated-cost x observed polls over armed serving time (the
        # audit-smoke attestation form, executed in the bench)
        cal = FollowCursor(FlowObserver(eng.flowlog), allow=obs_filters)
        cal_newest = eng.flowlog.newest_seq
        cal_durs = []
        for _ in range(200):
            cal.cursor = max(0, cal_newest - 64)
            c_t0 = time.perf_counter()
            cal.poll(limit=8192)
            cal_durs.append(time.perf_counter() - c_t0)
        per_poll_s = sorted(cal_durs)[len(cal_durs) // 2]
        obs_runs = []
        for armed in (False, True) * 4:
            w_fps, fstat = _window(w_n, armed)
            obs_runs.append({"armed": armed, "fps": round(w_fps, 1),
                             **(fstat if armed else {})})
        fps_dis = max(r["fps"] for r in obs_runs if not r["armed"])
        fps_arm = max(r["fps"] for r in obs_runs if r["armed"])
        polls_total = sum(r.get("polls", 0) for r in obs_runs)
        span = sum(r["elapsed_s"] for r in obs_runs if r["armed"])
        busy = per_poll_s * polls_total
        ovh = busy / max(span, 1e-9)
        fps_ratio = fps_arm / max(fps_dis, 1e-9)
        observer_doc = {
            "windows": obs_runs, "frames_per_window": w_n,
            "fps_armed": fps_arm, "fps_disarmed": fps_dis,
            "fps_ratio": round(fps_ratio, 4),
            "calibrated_poll_us": round(per_poll_s * 1e6, 1),
            "polls": polls_total,
            "poll_busy_s": round(busy, 5),
            "armed_elapsed_s": round(span, 4),
            "overhead_pct": round(ovh * 100, 2),
            "budget_pct": 2.0,
            # the gate: calibrated observer cost share < 2%, plus a
            # catastrophic-only fps guard — best-of-4 windows on a shared
            # rig still swing ~30% from CT drift and scheduler noise, so
            # anything tighter than 2x would gate on the rig, not the code
            "ok": bool(ovh < 0.02 and fps_ratio > 0.5),
        }
    eng.stop()
    st = shim.stats()
    shim.close()
    if verbose:
        print(f"# ingest bench preset={preset} frames={injected} "
              f"elapsed={elapsed:.2f}s fps={fps / 1e6:.3f}M "
              f"passes={st['verdict_passes']} drops={st['verdict_drops']} "
              f"tx_full={st['tx_full_drops']}", file=sys.stderr)
    doc = {
        "metric": "ingest_shim_to_verdict",
        "value": round(fps, 1),
        "unit": "frames/sec",
        "vs_baseline": round(fps / PER_CHIP_TARGET, 4),
        "frames": injected,
        "elapsed_s": round(elapsed, 3),
        # a wedged pipeline must be distinguishable from a clean run —
        # with this set, `value` is a floor, not a measurement
        **({"timed_out": True} if timed_out else {}),
        "verdict_passes": int(st["verdict_passes"]),
        "verdict_drops": int(st["verdict_drops"]),
        "tx_full_drops": int(st["tx_full_drops"]),
        "shim_batch": shim_batch,
        "batch": batch,
        "preset": preset,
        # the per-stage attribution the issue asks for: where host time
        # goes between the rx ring and the verdict bitmap
        "stage_split": {k: spans[k] for k in keep if k in spans},
        # the TRUE ingest→verdict split (harvest stamp → verdict apply,
        # through queue + staging + device + FIFO head-of-line): per-stage
        # spans above attribute it, these two numbers ARE it — computed
        # over the measured window only (warmup-compile batches excluded)
        "e2e_p50_ms": e2e_p50_ms,
        "e2e_p99_ms": e2e_p99_ms,
        "slo_burns": fstats.get("slo_burns", 0) - slo_base,
        "staging_free": pstats.get("staging_free"),
        "staging_slots": pstats.get("staging_slots"),
        "fill_ratio": pstats.get("fill_ratio_avg"),
        "flush_reasons": pstats.get("flush_reasons"),
        "shed_reasons": pstats.get("shed_reasons"),
        "pack_stats": pack_stats,
        "feeder": fstats,
        **({"observer_soak": observer_doc} if observer_doc else {}),
    }
    if shards > 1:
        doc.update({
            "shards": shards,
            "rss": rss,
            "aggregate_frames_per_sec": round(fps, 1),
            "per_chip_frames_per_sec": round(fps / shards, 1),
            "aggregate_flows_per_sec": round(fps, 1),
            "per_chip_flows_per_sec": round(fps / shards, 1),
            **({"shard_fill": pstats.get("shard_fill"),
                "shard_rows_total": pstats.get("shard_rows_total"),
                "shard_capacity": pstats.get("shard_capacity")}
               if rss != "device" else {}),
        })
        doc.update(_sharded_schema_check(doc, shards))
    else:
        # satellite gate: the refactored (shard-capable) staging path must
        # stay within noise of BENCH_r05 on the single-chip configuration
        doc["regression_gate"] = _single_chip_regression_gate(
            doc["stage_split"], fps)
    return doc


def kernels_bench(config: int, preset: str, batch: int, batches: int,
                  verbose: bool = False, fused_mode: str = "auto"):
    """Per-kernel compute-only microbench of the classify interior
    (ROADMAP item 2 attribution): the LPM stride walk, the CT probe pair,
    the policy ladder + L7 matcher + verdict composition, and the full
    classify step — each as its own jitted program over device-resident
    batches, timed through the observe tracer's per-kernel span names
    (``datapath.kernel.*``) so the artifact's p50/p99 flow through the same
    machinery as the serving-path stage split.

    Executors: the jnp reference always runs. The fused Pallas path
    (kernels/fused.py) is timed only where it actually compiles —
    ``fused_mode`` resolved exactly like the serving selector
    (``DaemonConfig.fused_kernels``) — because interpret-mode wall time
    measures the Pallas *interpreter*, not the kernel. Off-TPU the fused
    path is instead PARITY-checked in interpret mode (bit-identical outputs
    + CT against the jnp reference over every pre-generated batch), so the
    artifact still proves the fused interior before a TPU ever runs it.
    On the TPU no fused stage compiles today (kernels/fused.
    TPU_COMPILED_STAGES), so only the jnp reference is timed there;
    whether fusing wins is ROADMAP S7's question.
    """
    import jax
    import jax.numpy as jnp
    from cilium_tpu.compile.ct_layout import make_ct_arrays
    from cilium_tpu.kernels import conntrack as ctk
    from cilium_tpu.kernels import fused as fk
    from cilium_tpu.kernels.classify import (classify_interior_core,
                                             classify_step)
    from cilium_tpu.kernels.lpm import lpm_lookup_batch
    from cilium_tpu.observe.trace import (KERNEL_SPAN_CT_PROBE,
                                          KERNEL_SPAN_FULL, KERNEL_SPAN_LPM,
                                          KERNEL_SPAN_POLICY_L7, Tracer)
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.datapath import resolve_fused
    from cilium_tpu.utils import constants as C

    t0 = time.time()
    snap, gen, v4_only = BUILDERS[config](preset)
    compile_s = time.time() - t0
    tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
    make_ct = lambda: {k: jnp.asarray(v)  # noqa: E731
                       for k, v in make_ct_arrays(snap.ct_config).items()}
    ct = make_ct()
    rng = np.random.default_rng(7)
    host = [gen(rng, batch) for _ in range(min(batches, 8))]
    dev = [{k: jnp.asarray(v) for k, v in hb.items()} for hb in host]
    jax.block_until_ready(dev)
    wi = jnp.int32(snap.world_index)

    fused_active, interpret = resolve_fused(
        DaemonConfig(fused_kernels=fused_mode))
    plan = fk.fuse_plan(tensors, ct, v4_only=v4_only,
                        compiled=not interpret)
    time_fused = fused_active and not interpret   # compiled Pallas only

    def _stage_fns(use_fused):
        """One jitted program per interior stage; ``use_fused`` swaps the
        executor, nothing else. The fuse_plan geometry gate applies per
        stage exactly as classify_step applies it in serving — a gated
        stage times its real executor (the jnp reference), never a
        kernel the serving path would refuse."""
        def lpm_fn(tensors, b, wi):
            rw = jnp.where((b["direction"] == C.DIR_EGRESS)[:, None],
                           b["dst"], b["src"])
            if use_fused and plan.lpm:
                return fk.lpm_lookup_fused(
                    tensors["lpm_v4"], tensors["lpm_v6"], rw, b["is_v6"],
                    wi, v4_only=v4_only, interpret=interpret)
            return lpm_lookup_batch(tensors["lpm_v4"], tensors["lpm_v6"],
                                    rw, b["is_v6"], default_index=wi,
                                    v4_only=v4_only)

        def ct_fn(ct, b, now):
            fwd, rev = ctk.ct_key_words_pair(b)
            if use_fused and plan.ct:
                return fk.ct_probe_pair_fused(
                    ct, fwd, rev, now, snap.ct_config.probe_depth,
                    interpret=interpret)
            return (ctk.ct_probe(ct, fwd, now, snap.ct_config.probe_depth),
                    ctk.ct_probe(ct, rev, now, snap.ct_config.probe_depth))

        def pol_fn(tensors, b, id_idx, est, reply):
            args = (tensors, b["ep_slot"], b["direction"], id_idx,
                    b["proto"], b["dport"], b["http_method"],
                    b["http_path"], est, reply, b["valid"])
            if use_fused and plan.policy:
                return fk.policy_verdict_fused(*args, interpret=interpret)
            return classify_interior_core(*args)

        def full_fn(tensors, ct, b, now, wi):
            return classify_step(tensors, ct, b, now, wi,
                                 probe_depth=snap.ct_config.probe_depth,
                                 v4_only=v4_only, fused=use_fused,
                                 fused_interpret=interpret)
        return {
            KERNEL_SPAN_LPM: jax.jit(lpm_fn),
            KERNEL_SPAN_CT_PROBE: jax.jit(ct_fn),
            KERNEL_SPAN_POLICY_L7: jax.jit(pol_fn),
            KERNEL_SPAN_FULL: jax.jit(full_fn, donate_argnums=(1,)),
        }

    # staged inputs shared by the lpm/ct/policy micro-stages: id_idx from a
    # reference LPM pass; est/reply against the empty table (all-new flows
    # — the ladder cost is est-independent, it is branch-free)
    ref = _stage_fns(False)
    id_idx0 = [ref[KERNEL_SPAN_LPM](tensors, b, wi) for b in dev]
    n = batch
    false_col = jnp.zeros((n,), dtype=bool)
    jax.block_until_ready(id_idx0)

    tracer = Tracer(sample_rate=1.0, capacity=1 << 14)
    now_ctr = [20_000]

    def _run(span_name, fns, reps):
        """Time one stage ``reps`` times through the tracer (span per
        call, device-fenced). The full step threads donated CT."""
        nonlocal ct
        calls = {
            KERNEL_SPAN_LPM:
                lambda i: fns[KERNEL_SPAN_LPM](
                    tensors, dev[i % len(dev)], wi),
            KERNEL_SPAN_CT_PROBE:
                lambda i: fns[KERNEL_SPAN_CT_PROBE](
                    ct, dev[i % len(dev)], jnp.uint32(now_ctr[0])),
            KERNEL_SPAN_POLICY_L7:
                lambda i: fns[KERNEL_SPAN_POLICY_L7](
                    tensors, dev[i % len(dev)], id_idx0[i % len(dev)],
                    false_col, false_col),
        }
        if span_name == KERNEL_SPAN_FULL:
            def call(i):
                nonlocal ct
                now_ctr[0] += 1
                out, ct, _ = fns[KERNEL_SPAN_FULL](
                    tensors, ct, dev[i % len(dev)],
                    jnp.uint32(now_ctr[0]), wi)
                return out
        else:
            call = calls[span_name]
        jax.block_until_ready(call(0))               # warmup/compile
        for r in range(reps):
            tid = tracer.maybe_sample()
            with tracer.span(tid, span_name):
                jax.block_until_ready(call(r))

    reps = max(8, min(100, batches * 4))
    stage_names = (KERNEL_SPAN_LPM, KERNEL_SPAN_CT_PROBE,
                   KERNEL_SPAN_POLICY_L7, KERNEL_SPAN_FULL)
    for name in stage_names:
        _run(name, ref, reps)
    jnp_summary = tracer.summary()

    fused_summary = None
    if time_fused:
        tracer.reset()
        tracer.configure(sample_rate=1.0)
        ct = make_ct()
        fus = _stage_fns(True)
        for name in stage_names:
            _run(name, fus, reps)
        fused_summary = tracer.summary()

    def _stage_doc(summary):
        out = {}
        for name in stage_names:
            s = summary.get(name)
            if s is None:
                continue
            key = name.rsplit(".", 1)[1]
            out[key] = {
                "p50_ms": s["p50_ms"], "p99_ms": s["p99_ms"],
                "flows_per_s": round(batch / (s["p50_ms"] / 1e3), 1),
            }
        return out

    # interpret-mode parity: the CPU-CI proof that the fused interior is
    # bit-identical (outputs + CT + counters) to the jnp reference
    parity = None
    if fused_active and interpret:
        ct_a, ct_b = make_ct(), make_ct()
        rows = 0
        for i, b in enumerate(dev):
            now = jnp.uint32(30_000 + i)
            out_a, ct_a, cnt_a = classify_step(
                tensors, ct_a, b, now, wi, v4_only=v4_only)
            out_b, ct_b, cnt_b = classify_step(
                tensors, ct_b, b, now, wi, v4_only=v4_only,
                fused=True, fused_interpret=True)
            for k in out_a:
                np.testing.assert_array_equal(
                    np.asarray(out_a[k]), np.asarray(out_b[k]), k)
            for k in ct_a:
                np.testing.assert_array_equal(
                    np.asarray(ct_a[k]), np.asarray(ct_b[k]), k)
            for k in cnt_a:
                np.testing.assert_array_equal(
                    np.asarray(cnt_a[k]), np.asarray(cnt_b[k]), k)
            rows += int(np.asarray(b["valid"]).shape[0])
        parity = {"ok": True, "batches": len(dev), "rows": rows}

    kernels = _stage_doc(jnp_summary)
    full = kernels.get("full_step", {})
    result = {
        "metric": f"kernel_compute_only_{METRIC_NAMES[config]}",
        "value": full.get("flows_per_s", 0.0),
        "unit": "flows/sec/chip",
        "vs_baseline": round(full.get("flows_per_s", 0.0)
                             / PER_CHIP_TARGET, 4),
        "compute_only": full.get("flows_per_s", 0.0),
        "batch": batch,
        "preset": preset,
        "reps": reps,
        "compile_s": round(compile_s, 1),
        "kernels": kernels,
        "fused": {
            "mode": fused_mode,
            "active": fused_active,
            "interpret": interpret,
            "plan": {"lpm": plan.lpm, "ct": plan.ct, "policy": plan.policy},
            **({"interpret_parity": parity} if parity is not None else {}),
        },
    }
    if fused_summary is not None:
        fdoc = _stage_doc(fused_summary)
        result["kernels_fused"] = fdoc
        # the no-regression gate: a compiled fused kernel slower than the
        # reference it replaces fails the artifact (main exits 4)
        gate = {}
        regressions = []
        for key, ref_doc in kernels.items():
            fd = fdoc.get(key)
            if fd is None or ref_doc["p50_ms"] <= 0:
                continue
            ratio = fd["p50_ms"] / ref_doc["p50_ms"]
            gate[key] = round(ratio, 4)
            if ratio > 1.05:
                regressions.append(
                    f"{key}: fused p50 {fd['p50_ms']}ms > jnp "
                    f"{ref_doc['p50_ms']}ms")
        result["fused_gate"] = {
            "p50_ratio_fused_over_jnp": gate,
            "failed": bool(regressions),
            **({"regressions": regressions} if regressions else {}),
        }
    if verbose:
        print(f"# kernels config={config} preset={preset} batch={batch} "
              f"reps={reps} fused_active={fused_active} "
              f"interpret={interpret} plan={plan}", file=sys.stderr)
        for key, d in kernels.items():
            print(f"#   {key}: p50={d['p50_ms']}ms p99={d['p99_ms']}ms "
                  f"({d['flows_per_s'] / 1e6:.1f} Mfl/s)", file=sys.stderr)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, default=5, choices=sorted(BUILDERS))
    ap.add_argument("--preset", default="full", choices=["smoke", "full"],
                    help="world size: 'full' is the BASELINE size; 'smoke' "
                         "is the small world the CPU gates ask for")
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--batches", type=int, default=0)
    ap.add_argument("--only", action="store_true",
                    help="run just --config (default: all five, with "
                         "--config as the headline metric)")
    ap.add_argument("--pipeline", action="store_true",
                    help="pipelined-ingestion mode: serial vs overlapped "
                         "(pipeline/scheduler.py) throughput on --config, "
                         "one JSON line with queue-wait and fill-ratio")
    ap.add_argument("--trace", action="store_true",
                    help="with --pipeline: record observe/trace spans at "
                         "sampling 1.0 and emit the per-stage p50/p99 "
                         "summary in the JSON artifact")
    ap.add_argument("--ingest", action="store_true",
                    help="shim→verdict end-to-end over mock rings through "
                         "the async feeder + pipeline (shim/feeder.py): "
                         "one JSON line with the harvest/stage/pack/"
                         "transfer/compute split and staging-ring "
                         "occupancy")
    ap.add_argument("--frames", type=int, default=0,
                    help="with --ingest: frames to push (default "
                         "10k smoke / 100k full)")
    ap.add_argument("--observer", action="store_true",
                    help="with --ingest: append a D/A/D/A observer "
                         "overhead soak (flowlog armed, a 5ms-cadence "
                         "follow observer with compound filters vs "
                         "disarmed) and record the <2%% attestation in "
                         "the artifact as `observer_soak`")
    ap.add_argument("--update-storm", action="store_true",
                    help="live policy patching under pipelined traffic: "
                         "rule add/remove p50/p99 with the host/device "
                         "span split, parity-audited at sampling 1.0, "
                         "plus the overlapped-CT-GC on/off churn "
                         "comparison; gate failures exit 4")
    ap.add_argument("--updates", type=int, default=0,
                    help="with --update-storm: rule toggles to time "
                         "(default 40 smoke / 120 full)")
    ap.add_argument("--ddos", action="store_true",
                    help="cfg6 adversarial drop-storm: a randomized-source "
                         "SYN flood saturates a small CT table over the "
                         "live pipelined engine while established flows "
                         "keep serving — reports survival rate, legit e2e "
                         "p99, CT occupancy trajectory, overload-ladder "
                         "dwell times; auditor at sampling 1.0; gate "
                         "failures exit 4")
    ap.add_argument("--tenants", action="store_true",
                    help="cfg8 mixed-tenant QoS isolation: gold (lane) + "
                         "silver victims keep serving while a weight-1 "
                         "bulk tenant replays the cfg6 SYN storm through "
                         "the same pipeline — reports victim survival, "
                         "lane e2e p99 vs unloaded baseline, and the DRR "
                         "admitted-row shares vs the 4:2:1 weights; "
                         "auditor at sampling 1.0; gate failures exit 4")
    ap.add_argument("--fqdn", action="store_true",
                    help="cfg9 FQDN churn: toFQDNs policy under a DNS "
                         "storm on the pipelined engine — stable names "
                         "keep their established flows serving while "
                         "short-TTL churn names grow AND retire "
                         "identities through the delta path every tick; "
                         "reports refresh p50/p99 vs the delta budget, "
                         "established survival, full-rebuild count "
                         "(must be 0); auditor at sampling 1.0; gate "
                         "failures exit 4")
    ap.add_argument("--chiploss", action="store_true",
                    help="cfg10 chip-loss: kill one mesh device mid-"
                         "storm, fenced re-mesh onto survivors with CT "
                         "salvage + grace window, then heal back to "
                         "full width (gated by chiploss_gate, exit 4; "
                         "--shards picks the mesh width, default 4)")
    ap.add_argument("--cluster", type=int, default=0, metavar="N",
                    help="cfg7 multi-host serving: N engine PROCESSES over "
                         "one clustermesh store (runtime/cluster.py) — "
                         "converge (delta-patch ingest), cross-boundary "
                         "serve with the auditor at 1.0, chaos (store "
                         "partition / peer kill+restart / conflicting "
                         "claims / skewed clock), relay fan-in over the "
                         "nodes' flowlogs; reports aggregate fps + "
                         "replication-lag p99; gate failures exit 4")
    ap.add_argument("--kernels", action="store_true",
                    help="per-kernel compute-only microbench of the "
                         "classify interior (lpm / ct_probe / policy_l7 / "
                         "full_step p50+p99 via the datapath.kernel.* "
                         "spans); times the fused Pallas path where it "
                         "compiles and parity-checks it in interpret mode "
                         "elsewhere")
    ap.add_argument("--fused", default="auto", choices=["auto", "on", "off"],
                    help="with --kernels: fused-kernel selector resolved "
                         "exactly like DaemonConfig.fused_kernels")
    ap.add_argument("--hbm-report", metavar="VERIFY.json",
                    help="embed a `cilium-tpu verify --report` sweep's HBM "
                         "budget summary into the artifact's provenance "
                         "(offline --max-hbm-bytes verification and the "
                         "live HBM ledger citing the same numbers)")
    ap.add_argument("--compare", metavar="OLD.json",
                    help="diff this run against a prior JSON artifact "
                         "(pack/fps/e2e ratio-checked against "
                         "CILIUM_TPU_BENCH_COMPARE_FACTOR, default 1.75); "
                         "a regression past the factor fails the run "
                         "(exit 4)")
    ap.add_argument("--shards", type=int, default=1,
                    help="flow shards (data-parallel mesh axis); >1 routes "
                         "through the production multi-chip path — with "
                         "--pipeline/--ingest: steered staging + per-shard "
                         "wire segments behind one admission queue, "
                         "reporting per-chip AND aggregate flows/s plus "
                         "the steer/scatter span split")
    ap.add_argument("--rule-shards", type=int, default=1,
                    help="verdict-row shards (rule-space mesh axis)")
    ap.add_argument("--rss", default="host", choices=["host", "device"],
                    help="with --shards > 1: where flow→shard resolution "
                         "runs — 'host' = the steered staging path, "
                         "'device' = the in-kernel ring ppermute CT "
                         "exchange (no host steer/scatter; with "
                         "--pipeline the artifact carries a "
                         "steered-vs-unsteered A/B incl. a skewed-"
                         "traffic case, gated by rss_gate)")
    ap.add_argument("--windows", type=int, default=5,
                    help="timing windows per mode (median+IQR reported)")
    ap.add_argument("--profile", default="", metavar="DIR",
                    help="write an XProf trace of one steady-state window "
                         "to DIR (jax.profiler.trace)")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)
    if args.hbm_report:
        with open(args.hbm_report) as f:
            _HBM_REPORT["budget"] = json.load(f).get("budget")

    if args.chiploss and args.shards <= 1:
        args.shards = 4                # the cfg10 default mesh width
    preset = args.preset
    # run_bench / --kernels jit without a JITDatapath: place the compile
    # cache here, before any mode's first compile
    from cilium_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    # 64k records ≈ 2.9MB packed — big enough to amortize dispatch, small
    # enough to stay under the transport's fast-path transfer size
    batch = args.batch or (4096 if preset == "smoke" else 65536)
    batches = args.batches or (10 if preset == "smoke" else 40)

    def _finish(result) -> None:
        """Shared artifact tail: provenance stamp, optional --compare gate
        (exit 4 on regression past the factor), one JSON line. Device-RSS
        A/B deltas ride into the provenance block so a later --compare
        against this artifact carries the steered-vs-unsteered evidence."""
        result["provenance"] = _provenance(argv)
        if result.get("rss_ab"):
            result["provenance"]["rss_ab"] = result["rss_ab"]
        rc = 0
        if args.compare:
            result["compare"] = _compare_artifacts(result, args.compare)
            if result["compare"]["failed"]:
                rc = 4
        if result.get("rss_gate", {}).get("failed"):
            rc = 4
        if result.get("timed_out"):
            rc = 5                     # frames left without a verdict
        _progress["headline"] = result
        print(json.dumps(result))
        if rc:
            sys.exit(rc)

    _start_watchdog(METRIC_NAMES[args.config])
    if args.cluster:
        if args.cluster < 2:
            ap.error("--cluster needs N >= 2")
        # the engines are the N node processes, and those are CPU
        # processes (runtime/cluster.py); this one stays off every device
        result = cluster_bench(args.cluster, preset, verbose=args.verbose)
        result["provenance"] = _provenance(argv, platform="cpu")
        rc = 0
        if args.compare:
            result["compare"] = _compare_artifacts(result, args.compare)
            if result["compare"]["failed"]:
                rc = 4
        if result.get("cluster_gate", {}).get("failed"):
            rc = 4
        _progress["headline"] = result
        print(json.dumps(result))
        if rc:
            sys.exit(rc)
        return
    if args.kernels:
        result = kernels_bench(args.config, preset, batch, batches,
                               verbose=args.verbose, fused_mode=args.fused)
        result["provenance"] = _provenance(argv)
        rc = 0
        if args.compare:
            result["compare"] = _compare_artifacts(result, args.compare)
            if result["compare"]["failed"]:
                rc = 4
        if result.get("fused_gate", {}).get("failed"):
            rc = 4
        _progress["headline"] = result
        print(json.dumps(result))
        if rc:
            sys.exit(rc)
        return
    if args.update_storm:
        result = update_storm_bench(preset, updates=args.updates,
                                    verbose=args.verbose)
        result["provenance"] = _provenance(argv)
        rc = 0
        if args.compare:
            result["compare"] = _compare_artifacts(result, args.compare)
            if result["compare"]["failed"]:
                rc = 4
        if result.get("storm_gate", {}).get("failed"):
            rc = 4
        _progress["headline"] = result
        print(json.dumps(result))
        if rc:
            sys.exit(rc)
        return
    if args.tenants:
        result = tenants_bench(preset, verbose=args.verbose,
                               batch=min(batch, 256))
        result["provenance"] = _provenance(argv)
        rc = 0
        if args.compare:
            result["compare"] = _compare_artifacts(result, args.compare)
            if result["compare"]["failed"]:
                rc = 4
        if result.get("qos_gate", {}).get("failed"):
            rc = 4
        _progress["headline"] = result
        print(json.dumps(result))
        if rc:
            sys.exit(rc)
        return
    if args.fqdn:
        result = fqdn_bench(preset, verbose=args.verbose,
                            batch=min(batch, 256))
        result["provenance"] = _provenance(argv)
        rc = 0
        if args.compare:
            result["compare"] = _compare_artifacts(result, args.compare)
            if result["compare"]["failed"]:
                rc = 4
        if result.get("fqdn_gate", {}).get("failed"):
            rc = 4
        _progress["headline"] = result
        print(json.dumps(result))
        if rc:
            sys.exit(rc)
        return
    if args.chiploss:
        result = chiploss_bench(preset, verbose=args.verbose,
                                batch=min(batch, 256), shards=args.shards)
        result["provenance"] = _provenance(argv)
        rc = 0
        if args.compare:
            result["compare"] = _compare_artifacts(result, args.compare)
            if result["compare"]["failed"]:
                rc = 4
        if result.get("chiploss_gate", {}).get("failed"):
            rc = 4
        _progress["headline"] = result
        print(json.dumps(result))
        if rc:
            sys.exit(rc)
        return
    if args.ddos:
        result = ddos_bench(preset, verbose=args.verbose,
                            batch=min(batch, 256))
        result["provenance"] = _provenance(argv)
        rc = 0
        if args.compare:
            result["compare"] = _compare_artifacts(result, args.compare)
            if result["compare"]["failed"]:
                rc = 4
        if result.get("ddos_gate", {}).get("failed"):
            rc = 4
        _progress["headline"] = result
        print(json.dumps(result))
        if rc:
            sys.exit(rc)
        return
    if args.ingest:
        result = ingest_bench(preset, batch, n_frames=args.frames,
                              verbose=args.verbose, shards=args.shards,
                              observer=args.observer, rss=args.rss)
        _finish(result)
        return
    if args.pipeline:
        result = pipeline_bench(args.config, preset, batch, batches,
                                windows=max(3, args.windows - 2),
                                verbose=args.verbose, trace=args.trace,
                                shards=args.shards, rss=args.rss)
        _finish(result)
        return
    result = run_bench(args.config, preset, batch, batches,
                       verbose=args.verbose, windows=args.windows,
                       shards=args.shards, rule_shards=args.rule_shards,
                       profile_dir=args.profile)
    _progress["headline"] = result
    if args.shards * args.rule_shards > 1:
        args.only = True       # the sweep is a single-chip comparison series
    if not args.only:
        configs = {METRIC_NAMES[args.config]: {
            "value": result["value"], "vs_baseline": result["vs_baseline"],
            "p50_batch_ms": result["p50_batch_ms"],
            "p99_batch_ms": result["p99_batch_ms"]}}
        for cfg in sorted(BUILDERS):
            if cfg == args.config:
                continue
            # non-headline configs: fewer timed batches and windows
            # (visibility, not the headline number) — bounds the sweep
            res = run_bench(cfg, preset, batch, max(10, batches // 2),
                            verbose=args.verbose,
                            windows=max(3, args.windows - 2))
            print(json.dumps(res), file=sys.stderr)
            configs[METRIC_NAMES[cfg]] = {
                "value": res["value"], "vs_baseline": res["vs_baseline"],
                "p50_batch_ms": res["p50_batch_ms"],
                "p99_batch_ms": res["p99_batch_ms"]}
            _progress["configs"] = configs
        result["configs"] = configs
        result["update_latency"] = update_latency_bench(preset)
    _finish(result)


if __name__ == "__main__":
    main()
