"""END-TO-END PARITY: the fused device kernel vs the semantics oracle.

This is the build's core obligation (SURVEY.md §4 "Parity testing"): for
randomized (rules × packet streams), the jitted classify step must produce
verdicts bit-identical to the oracle's snapshot batch mode, and the device
CT table must hold exactly the oracle's live entries (flags, expiry,
counters). Batch-size-1 equals the sequential (eBPF-equivalent) mode, which
the oracle test suite separately pins to snapshot mode.
"""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from cilium_tpu.compile.ct_layout import (CTConfig, logical_ct_arrays,
                                          make_ct_arrays)
from cilium_tpu.compile.snapshot import build_snapshot
from cilium_tpu.kernels.classify import classify_step
from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.model.endpoint import Endpoint
from cilium_tpu.model.identity import IdentityAllocator
from cilium_tpu.model.ipcache import IPCache
from cilium_tpu.model.labels import Labels
from cilium_tpu.model.rules import parse_rules
from cilium_tpu.policy import PolicyContext, Repository
from cilium_tpu.policy.selectorcache import SelectorCache
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr, words_to_addr
from oracle import ConntrackTable, Oracle, PacketRecord

RULES = [
    {   # web: egress to 10/8 except 10.96/12 on 443+8080-8090; ingress l7 80
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "egress": [
            {"toCIDRSet": [{"cidr": "10.0.0.0/8", "except": ["10.96.0.0/12"]}],
             "toPorts": [{"ports": [{"port": "443", "protocol": "TCP"},
                                    {"port": "8080", "endPort": 8090,
                                     "protocol": "TCP"}]}]},
            {"toEntities": ["world"],
             "toPorts": [{"ports": [{"port": "53", "protocol": "ANY"}]}]},
            {"toCIDR": ["2001:db8::/32"],
             "toPorts": [{"ports": [{"port": "443", "protocol": "TCP"}]}]},
            {"toCIDR": ["10.200.0.0/16"],
             "icmps": [{"fields": [{"type": 8, "family": "IPv4"}]}]},
        ],
        "egressDeny": [
            {"toCIDR": ["10.66.0.0/16"]},
        ],
        "ingress": [
            {"toPorts": [{"ports": [{"port": "80", "protocol": "TCP"}],
                          "rules": {"http": [{"method": "GET", "path": "/api"},
                                             {"path": "/public"}]}}]},
            {"fromEndpoints": [{"matchLabels": {"role": "fe"}}]},
        ],
    },
    {   # db: ingress only from web pods on 5432
        "endpointSelector": {"matchLabels": {"app": "db"}},
        "ingress": [{"fromEndpoints": [{"matchLabels": {"app": "web"}}],
                     "toPorts": [{"ports": [{"port": "5432", "protocol": "TCP"}]}]}],
    },
]


def build_world():
    alloc = IdentityAllocator()
    ipc = IPCache()
    ctx = PolicyContext(allocator=alloc, selector_cache=SelectorCache(alloc),
                        ipcache=ipc)
    repo = Repository(ctx)
    eps = []
    for ep_id, (labels, ip) in enumerate(
            [(("k8s:app=web",), "192.168.1.10"),
             (("k8s:app=db",), "192.168.1.20"),
             (("k8s:role=fe",), "192.168.1.30")], start=1):
        lbls = Labels.parse(labels)
        ident = alloc.allocate(lbls)
        ep = Endpoint(ep_id=ep_id, labels=lbls, identity_id=ident.id, ips=(ip,))
        ipc.upsert(f"{ip}/32", ident.id)
        eps.append(ep)
    repo.add(parse_rules(RULES))
    return ctx, repo, eps


DST_POOL = [
    "10.1.2.3", "10.5.5.5", "10.96.0.1", "10.100.3.9", "10.66.1.1",
    "10.200.1.1", "8.8.8.8", "1.1.1.1", "192.168.1.20", "192.168.1.30",
    "2001:db8::77", "2001:db9::1",
]
PORT_POOL = [443, 8080, 8085, 8090, 8091, 80, 53, 5432, 22, 0]
PATHS = [b"/api/users", b"/public/x", b"/admin", b"/ap", b""]


def random_packet(rng, prior):
    """Either a brand-new random flow, a repeat, or a reply of a prior one."""
    r = rng.random()
    if prior and r < 0.30:
        p = rng.choice(prior)     # repeat (established)
        flags = rng.choice([C.TCP_ACK, C.TCP_ACK | C.TCP_PSH, C.TCP_FIN,
                            C.TCP_RST]) if p.proto == C.PROTO_TCP else 0
        return PacketRecord(p.src_addr, p.dst_addr, p.src_port, p.dst_port,
                            p.proto, flags, p.is_ipv6, p.ep_id, p.direction,
                            p.http_method, p.http_path)
    if prior and r < 0.45:
        p = rng.choice(prior)     # reply
        flags = (C.TCP_SYN | C.TCP_ACK) if p.proto == C.PROTO_TCP else 0
        return PacketRecord(p.dst_addr, p.src_addr, p.dst_port, p.src_port,
                            p.proto, flags, p.is_ipv6, p.ep_id,
                            1 - p.direction, C.HTTP_METHOD_ANY, b"")
    ep_id = rng.choice([1, 1, 1, 2, 3])
    direction = rng.choice([C.DIR_EGRESS, C.DIR_EGRESS, C.DIR_INGRESS])
    dst = rng.choice(DST_POOL)
    src_ip = {1: "192.168.1.10", 2: "192.168.1.20", 3: "192.168.1.30"}[ep_id]
    if direction == C.DIR_INGRESS:
        src, dstip = dst, src_ip
    else:
        src, dstip = src_ip, dst
    s16, sv6 = parse_addr(src)
    d16, dv6 = parse_addr(dstip)
    proto = rng.choice([C.PROTO_TCP] * 5 + [C.PROTO_UDP, C.PROTO_ICMP])
    if proto == C.PROTO_ICMP:
        sport, dport, flags = 0, rng.choice([0, 8]), 0
    else:
        sport = rng.randrange(30000, 60000)
        dport = rng.choice(PORT_POOL)
        flags = C.TCP_SYN if proto == C.PROTO_TCP else 0
    method, path = C.HTTP_METHOD_ANY, b""
    if proto == C.PROTO_TCP and dport == 80 and rng.random() < 0.5:
        method = rng.choice([C.HTTP_METHOD_IDS["GET"], C.HTTP_METHOD_IDS["POST"]])
        path = rng.choice(PATHS)
        flags = C.TCP_ACK
    return PacketRecord(s16, d16, sport, dport, proto, flags, sv6 or dv6,
                        ep_id, direction, method, path)


def extract_device_ct(ct_dev, now):
    """Device table → {CTKey: (flags, expiry, pkts_fwd, pkts_rev)} for live
    entries."""
    keys = logical_ct_arrays(ct_dev)["keys"]
    expiry = np.asarray(ct_dev["expiry"])
    flags = np.asarray(ct_dev["flags"])
    fwd = np.asarray(ct_dev["pkts_fwd"])
    rev = np.asarray(ct_dev["pkts_rev"])
    rnat = np.asarray(ct_dev["rev_nat"])
    out = {}
    for slot in np.nonzero(expiry > now)[0]:
        w = keys[slot]
        src = words_to_addr(w[0:4])
        dst = words_to_addr(w[4:8])
        sport = int(w[8]) >> 16
        dport = int(w[8]) & 0xFFFF
        proto = int(w[9]) >> 8
        d = int(w[9]) & 0xFF
        key = (src, dst, sport, dport, proto, d)
        out[key] = (int(flags[slot]), int(expiry[slot]),
                    int(fwd[slot]), int(rev[slot]), int(rnat[slot]))
    return out


def oracle_live_ct(oracle, now):
    out = {}
    for key, e in oracle.ct.entries.items():
        if e.expiry > now:
            out[key] = (e.flags, e.expiry, e.pkts_fwd, e.pkts_rev, e.rev_nat)
    return out


def test_packed_path_bit_identical():
    """The packed wire format (single uint32 array) must produce the exact
    same outputs and CT state as the dict path — it is the production
    transfer path (bench + shim)."""
    import jax
    from cilium_tpu.kernels.classify import make_classify_fn
    from cilium_tpu.kernels.records import pack_batch, unpack_batch_jnp

    rng = random.Random(11)
    ctx, repo, eps = build_world()
    snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=4096))
    tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
    make_ct = lambda: {k: jnp.asarray(v) for k, v in  # noqa: E731
                       make_ct_arrays(CTConfig(capacity=4096)).items()}
    ct_a, ct_b = make_ct(), make_ct()
    fn_dict = make_classify_fn(donate_ct=False)
    fn_packed = make_classify_fn(donate_ct=False, packed=True)
    prior = []
    now = 500
    for bi in range(3):
        packets = [random_packet(rng, prior) for _ in range(64)]
        raw = batch_from_records(packets, snap.ep_slot_of)
        # roundtrip: pack → device unpack reproduces every column
        unpacked = unpack_batch_jnp(jnp.asarray(pack_batch(raw, l7=True)))
        for k in raw:
            np.testing.assert_array_equal(
                np.asarray(unpacked[k]).astype(raw[k].dtype), raw[k], k)
        out_a, ct_a, ca = fn_dict(
            tensors, ct_a, {k: jnp.asarray(v) for k, v in raw.items()},
            jnp.uint32(now), jnp.int32(snap.world_index))
        out_b, ct_b, cb = fn_packed(
            tensors, ct_b, jnp.asarray(pack_batch(raw)),
            jnp.uint32(now), jnp.int32(snap.world_index))
        for k in out_a:
            np.testing.assert_array_equal(np.asarray(out_a[k]),
                                          np.asarray(out_b[k]), k)
        for k in ct_a:
            np.testing.assert_array_equal(np.asarray(ct_a[k]),
                                          np.asarray(ct_b[k]), k)
        prior.extend(packets)
        prior = prior[-100:]
        now += 40


def run_parity(seed, n_batches=6, batch=96, cap=4096, time_step=40):
    rng = random.Random(seed)
    ctx, repo, eps = build_world()
    snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=cap))
    tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
    ct_dev = {k: jnp.asarray(v) for k, v in
              make_ct_arrays(CTConfig(capacity=cap)).items()}
    # for_snapshot wires the provenance tables — the matched_rule /
    # lpm_prefix / ct_state_pre columns below are part of the parity
    # contract (ISSUE 11), pinned against the oracle like the verdicts
    oracle = Oracle.for_snapshot(snap)
    prior = []
    now = 1000
    for bi in range(n_batches):
        packets = [random_packet(rng, prior) for _ in range(batch)]
        want = oracle.classify_batch_snapshot(packets, now)
        b = {k: jnp.asarray(v) for k, v in
             batch_from_records(packets, snap.ep_slot_of).items()}
        out, ct_dev, counters = classify_step(
            tensors, ct_dev, b, jnp.uint32(now),
            world_index=snap.world_index)
        got_allow = np.asarray(out["allow"])
        got_reason = np.asarray(out["reason"])
        got_status = np.asarray(out["status"])
        got_rid = np.asarray(out["remote_identity"])
        got_rule = np.asarray(out["matched_rule"])
        got_pfx = np.asarray(out["lpm_prefix"])
        got_pre = np.asarray(out["ct_state_pre"])
        for i, (p, v) in enumerate(zip(packets, want)):
            assert bool(got_allow[i]) == v.allow, \
                f"seed={seed} batch={bi} pkt={i}: allow {bool(got_allow[i])} != {v.allow} ({p})"
            assert int(got_reason[i]) == int(v.drop_reason), \
                f"seed={seed} batch={bi} pkt={i}: reason {int(got_reason[i])} != {int(v.drop_reason)} ({p})"
            assert int(got_status[i]) == int(v.ct_status), \
                f"seed={seed} batch={bi} pkt={i}: status {int(got_status[i])} != {int(v.ct_status)} ({p})"
            assert int(got_rid[i]) == v.remote_identity, \
                f"seed={seed} batch={bi} pkt={i}: rid {int(got_rid[i])} != {v.remote_identity}"
            assert int(got_rule[i]) == v.matched_rule, \
                f"seed={seed} batch={bi} pkt={i}: matched_rule " \
                f"{int(got_rule[i])} != {v.matched_rule} ({p})"
            assert int(got_pfx[i]) == v.lpm_prefix, \
                f"seed={seed} batch={bi} pkt={i}: lpm_prefix " \
                f"{int(got_pfx[i])} != {v.lpm_prefix} ({p})"
            assert int(got_pre[i]) == int(v.ct_status), \
                f"seed={seed} batch={bi} pkt={i}: ct_state_pre " \
                f"{int(got_pre[i])} != {int(v.ct_status)} ({p})"
        dev_ct = extract_device_ct(ct_dev, now)
        ora_ct = oracle_live_ct(oracle, now)
        assert dev_ct == ora_ct, (
            f"seed={seed} batch={bi}: CT divergence\n"
            f"only-device: { {k: v for k, v in dev_ct.items() if ora_ct.get(k) != v} }\n"
            f"only-oracle: { {k: v for k, v in ora_ct.items() if dev_ct.get(k) != v} }")
        prior.extend(p for p, v in zip(packets, want)
                     if v.allow and v.ct_status == C.CTStatus.NEW)
        prior = prior[-200:]
        now += time_step


class TestKernelOracleParity:
    @pytest.mark.parametrize("seed", range(4))
    def test_randomized_multibatch(self, seed):
        run_parity(seed)

    @pytest.mark.parametrize("mode", [C.ENFORCEMENT_NEVER, C.ENFORCEMENT_ALWAYS])
    def test_enforcement_modes(self, mode):
        """Regression: unenforced directions must bypass DENY/REDIRECT cells
        on the device path exactly as the oracle skips the ladder."""
        rng = random.Random(11)
        ctx, repo, eps = build_world()
        ctx.enforcement_mode = mode
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=2048))
        tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
        ct_dev = {k: jnp.asarray(v) for k, v in
                  make_ct_arrays(CTConfig(capacity=2048)).items()}
        oracle = Oracle(dict(zip(snap.ep_ids, snap.policies)),
                        ctx.ipcache.snapshot())
        prior = []
        now = 100
        for bi in range(3):
            packets = [random_packet(rng, prior) for _ in range(64)]
            want = oracle.classify_batch_snapshot(packets, now)
            b = {k: jnp.asarray(v) for k, v in
                 batch_from_records(packets, snap.ep_slot_of).items()}
            out, ct_dev, _ = classify_step(tensors, ct_dev, b, jnp.uint32(now),
                                           world_index=snap.world_index)
            for i, v in enumerate(want):
                assert bool(np.asarray(out["allow"])[i]) == v.allow, (mode, bi, i)
                assert int(np.asarray(out["reason"])[i]) == int(v.drop_reason), \
                    (mode, bi, i)
            assert extract_device_ct(ct_dev, now) == oracle_live_ct(oracle, now)
            prior.extend(p for p, v in zip(packets, want)
                         if v.allow and v.ct_status == C.CTStatus.NEW)
            now += 40

    def test_per_endpoint_enforcement_override(self):
        ctx, repo, eps = build_world()
        eps[2].enforcement = C.ENFORCEMENT_ALWAYS  # fe endpoint: default-deny
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
        ct_dev = {k: jnp.asarray(v) for k, v in
                  make_ct_arrays(CTConfig(capacity=1024)).items()}
        oracle = Oracle(dict(zip(snap.ep_ids, snap.policies)),
                        ctx.ipcache.snapshot())
        s16, _ = parse_addr("192.168.1.30")
        d16, _ = parse_addr("8.8.8.8")
        p = PacketRecord(s16, d16, 40000, 443, C.PROTO_TCP, C.TCP_SYN,
                         False, 3, C.DIR_EGRESS)
        v = oracle.classify(p, 100)
        b = {k: jnp.asarray(a) for k, a in
             batch_from_records([p], snap.ep_slot_of).items()}
        out, ct_dev, _ = classify_step(tensors, ct_dev, b, jnp.uint32(100),
                                       world_index=snap.world_index)
        assert not v.allow  # always-mode, no rules for fe → default deny
        assert bool(np.asarray(out["allow"])[0]) == v.allow
        assert int(np.asarray(out["reason"])[0]) == int(v.drop_reason)

    def test_long_horizon_with_expiry(self):
        # large time steps force SYN-timeout expiries and slot reuse
        run_parity(seed=99, n_batches=8, batch=64, time_step=90)

    def test_batch_of_one_matches_sequential(self):
        rng = random.Random(7)
        ctx, repo, eps = build_world()
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
        ct_dev = {k: jnp.asarray(v) for k, v in
                  make_ct_arrays(CTConfig(capacity=1024)).items()}
        oracle = Oracle(dict(zip(snap.ep_ids, snap.policies)),
                        ctx.ipcache.snapshot())
        prior = []
        now = 500
        for i in range(40):
            p = random_packet(rng, prior)
            v = oracle.classify(p, now)          # SEQUENTIAL mode
            b = {k: jnp.asarray(a) for k, a in
                 batch_from_records([p], snap.ep_slot_of).items()}
            out, ct_dev, _ = classify_step(tensors, ct_dev, b, jnp.uint32(now),
                                           world_index=snap.world_index)
            assert bool(np.asarray(out["allow"])[0]) == v.allow, (i, p)
            assert int(np.asarray(out["reason"])[0]) == int(v.drop_reason), (i, p)
            assert int(np.asarray(out["status"])[0]) == int(v.ct_status), (i, p)
            if v.allow and v.ct_status == C.CTStatus.NEW:
                prior.append(p)
            now += 13
        assert extract_device_ct(ct_dev, now) == oracle_live_ct(oracle, now)


def test_addrdict_wire_bit_identical():
    """The address-dictionary wire (12B/record + shared unique-address
    table) must match the dict path exactly — outputs and CT state — for
    mixed v4/v6 and for L7-token traffic (the 4-word variant)."""
    from cilium_tpu.kernels.classify import make_classify_fn
    from cilium_tpu.kernels.records import (
        pack_batch_addrdict, unpack_batch_addrdict_jnp)

    rng = random.Random(12)
    ctx, repo, eps = build_world()
    snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=4096))
    tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
    make_ct = lambda: {k: jnp.asarray(v) for k, v in  # noqa: E731
                       make_ct_arrays(CTConfig(capacity=4096)).items()}
    ct_a, ct_b = make_ct(), make_ct()
    fn_dict = make_classify_fn(donate_ct=False)
    fn_packed = make_classify_fn(donate_ct=False, packed=True)
    prior = []
    now = 700
    for bi in range(3):
        packets = [random_packet(rng, prior) for _ in range(64)]
        raw = batch_from_records(packets, snap.ep_slot_of)
        # roundtrip incl. L7 variant
        parts = pack_batch_addrdict(raw, l7=True)
        unpacked = unpack_batch_addrdict_jnp(
            *(jnp.asarray(p) for p in parts))
        for k in raw:
            np.testing.assert_array_equal(
                np.asarray(unpacked[k]).astype(raw[k].dtype), raw[k], k)
        out_a, ct_a, _ = fn_dict(
            tensors, ct_a, {k: jnp.asarray(v) for k, v in raw.items()},
            jnp.uint32(now), jnp.int32(snap.world_index))
        wire = pack_batch_addrdict(raw)
        out_b, ct_b, _ = fn_packed(
            tensors, ct_b, tuple(jnp.asarray(p) for p in wire),
            jnp.uint32(now), jnp.int32(snap.world_index))
        for k in out_a:
            np.testing.assert_array_equal(np.asarray(out_a[k]),
                                          np.asarray(out_b[k]), k)
        for k in ct_a:
            np.testing.assert_array_equal(np.asarray(ct_a[k]),
                                          np.asarray(ct_b[k]), k)
        prior.extend(packets)
        prior = prior[-100:]
        now += 40
