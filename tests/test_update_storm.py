"""Live-state fast paths (ROADMAP item 3): sparse delta patching of the
device-resident policy image, the StalePlacement donation fence, the
overlapped device-side CT GC, conntrack survival across restart, and the
bounded classify-fn memo.

The contract under test: a live rule add/remove updates the placed verdict
image in place (donated scatter-apply) behind a revision fence — no batch
ever classifies under a torn update — and stays bit-identical to both a
fresh full compile and the semantics oracle at every revision; the chunked
epoch GC is semantics-free (probes already ignore expired slots) and never
stalls classify.
"""

import os

import numpy as np
import pytest

from cilium_tpu.compile.ct_layout import CTConfig, make_ct_arrays
from cilium_tpu.compile.incremental import IncrementalCompiler
from cilium_tpu.compile.snapshot import build_snapshot
from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.runtime import checkpoint as ckpt
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import (CT_FORMAT_VERSION, FakeDatapath,
                                         JITDatapath, StalePlacement)
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.runtime.faults import FAULTS, FaultInjected
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr
from oracle import PacketRecord


# --------------------------------------------------------------------------- #
# world helpers
# --------------------------------------------------------------------------- #
N_PEERS = 6

OUT_KEYS = ("allow", "reason", "status", "remote_identity", "redirect")


def peer_rule_docs(i, port=80, deny=False, label=None):
    """One labeled per-peer rule document (labels make replace_policy
    toggles work — the storm's add/remove primitive)."""
    key = "ingressDeny" if deny else "ingress"
    block = {"fromEndpoints": [{"matchLabels": {"peer": f"p{i}"}}]}
    if not deny:
        block["toPorts"] = [{"ports": [{"port": str(port),
                                        "protocol": "TCP"}]}]
    return [{"endpointSelector": {"matchLabels": {"app": "web"}},
             "labels": [label or f"k8s:storm=r{i}-{port}-{int(deny)}"],
             key: [block]}]


def make_engine(datapath, n_peers=N_PEERS, **cfg_kw):
    cfg = DaemonConfig(ct_capacity=2048, auto_regen=False, **cfg_kw)
    eng = Engine(cfg, datapath=datapath)
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    for i in range(n_peers):
        eng.add_endpoint([f"k8s:peer=p{i}", f"k8s:group=g{i % 2}"],
                         ips=(f"172.16.{i}.5",), ep_id=10 + i)
    eng.apply_policy([{
        "endpointSelector": {"matchLabels": {"app": "web"}},
        "ingress": [{"fromEndpoints": [{"matchLabels": {"group": "g0"}}],
                     "toPorts": [{"ports": [
                         {"port": "80", "protocol": "TCP"}]}]}]}])
    eng.regenerate()
    return eng


def jit_engine(**kw):
    cfg = DaemonConfig(ct_capacity=2048, auto_regen=False, **kw)
    return make_engine(JITDatapath(cfg), **kw)


def fake_engine(**kw):
    cfg = DaemonConfig(ct_capacity=2048, auto_regen=False, **kw)
    return make_engine(FakeDatapath(cfg), **kw)


def traffic(slots, n_peers=N_PEERS, flags=C.TCP_SYN, sport0=30000):
    pkts = []
    for i in range(n_peers):
        for dp in (80, 443, 8080):
            s16, _ = parse_addr(f"172.16.{i}.5")
            d16, _ = parse_addr("192.168.1.10")
            pkts.append(PacketRecord(s16, d16, sport0 + i, dp, C.PROTO_TCP,
                                     flags, False, 1, C.DIR_INGRESS))
    return batch_from_records(pkts, slots)


def warm_geometry(*engines, ports=(443, 8080)):
    """Split every peer's identity class and every port boundary once, so
    subsequent churn rides the pure delta path (the long-lived-daemon
    steady state)."""
    for i in range(N_PEERS):
        for p in ports:
            for e in engines:
                e.replace_policy([f"k8s:warm=w{i}-{p}"],
                                 peer_rule_docs(i, p,
                                                label=f"k8s:warm=w{i}-{p}"))
                e.regenerate()


def assert_same_verdicts(a, b, msg=""):
    for k in OUT_KEYS:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      f"{msg}:{k}")


# --------------------------------------------------------------------------- #
# the delta-patch fast path
# --------------------------------------------------------------------------- #
class TestDeltaPatch:
    def test_warm_churn_rides_the_delta_path_bit_identical(self):
        """Steady-state rule toggles on warm geometry must (a) actually
        take the scatter-apply path and (b) stay bit-identical to the
        oracle-backed fake at every revision."""
        eng, ref = jit_engine(), fake_engine()
        warm_geometry(eng, ref)
        base = dict(eng.datapath.patch_stats)
        now = 1000
        for step in range(10):
            i, p = step % N_PEERS, (443, 8080)[step % 2]
            label = f"k8s:warm=w{i}-{p}"
            body = None if step % 3 == 2 else peer_rule_docs(i, p,
                                                             label=label)
            for e in (eng, ref):
                e.replace_policy([label], body)
                e.regenerate()
            b = traffic(eng.active.snapshot.ep_slot_of)
            assert_same_verdicts(eng.classify(dict(b), now=now),
                                 ref.classify(dict(b), now=now),
                                 f"step{step}")
            now += 10
        ps = eng.datapath.patch_stats
        assert ps["patch_delta"] - base["patch_delta"] >= 5, ps
        # patches carried their sparse payloads, not whole-plane uploads
        assert ps["patch_rows"] > base["patch_rows"]

    def test_delta_patched_image_equals_full_place(self):
        """After a run of in-place scatter patches the device-resident
        verdict must equal what a from-scratch placement of the same
        snapshot would hold (no drift, ever)."""
        eng = jit_engine()
        warm_geometry(eng)
        for step in range(6):
            label = f"k8s:warm=w{step % N_PEERS}-443"
            eng.replace_policy(
                [label],
                None if step % 2 else peer_rule_docs(step % N_PEERS, 443,
                                                     label=label))
            eng.regenerate()
        assert eng.datapath.patch_stats["patch_delta"] >= 3
        snap = eng.active.snapshot
        fresh = eng.datapath.place(snap)
        np.testing.assert_array_equal(
            np.asarray(eng.active.tensors["verdict"]),
            np.asarray(fresh["verdict"]))

    def test_stale_placement_fence_and_engine_retry(self):
        """A handle captured before a delta patch and enqueued after must
        raise StalePlacement (never read a donated buffer); the engine's
        retry classifies against the patched snapshot."""
        eng = jit_engine()
        warm_geometry(eng)
        # ensure the toggled rule exists so the next replace is a delta
        eng.replace_policy(["k8s:warm=w0-443"],
                           peer_rule_docs(0, 443, label="k8s:warm=w0-443"))
        eng.regenerate()
        old = eng.active
        before = eng.datapath.patch_stats["patch_delta"]
        eng.replace_policy(["k8s:warm=w0-443"], None)
        eng.regenerate()
        assert eng.datapath.patch_stats["patch_delta"] == before + 1
        b = traffic(old.snapshot.ep_slot_of)
        with pytest.raises(StalePlacement):
            eng.datapath.classify(old.tensors, old.snapshot, dict(b), 500)
        assert eng.datapath.patch_stats["patch_stale_fences"] >= 1
        # the engine-level path retries transparently
        out = eng.classify(traffic(eng.active.snapshot.ep_slot_of), now=600)
        assert out["allow"].shape[0] > 0

    def test_delta_budget_gate_falls_back_to_full_upload(self):
        """A patch past the delta budget ships as a whole-plane upload
        (full_tensors), not a sparse payload."""
        ctx_eng = jit_engine(patch_delta_rows=1)
        warm_geometry(ctx_eng)
        inc = ctx_eng._inc
        assert inc is not None and inc.delta_budget_rows == 1
        # a group rule touches every member's class → > 1 row
        ctx_eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "labels": ["k8s:storm=wide"],
            "ingressDeny": [{"fromEndpoints": [
                {"matchLabels": {"group": "g1"}}]}]}])
        before = dict(ctx_eng.datapath.patch_stats)
        ctx_eng.regenerate()
        ps = ctx_eng.datapath.patch_stats
        assert ps["patch_delta"] == before["patch_delta"]
        assert ps["patch_full"] == before["patch_full"] + 1

    def test_scatter_failure_self_heals_with_full_upload(self):
        """A scatter that fails AFTER the donation must not pin a dead
        handle on the engine's serve-last-good path: place_patch recovers
        with a full verdict upload of the new snapshot."""
        eng = jit_engine()
        warm_geometry(eng)
        eng.replace_policy(["k8s:warm=w2-443"],
                           peer_rule_docs(2, 443, label="k8s:warm=w2-443"))
        eng.regenerate()
        dp = eng.datapath

        calls = {"n": 0}
        real = dp._scatter_rows

        def flaky(verdict, rows, vals):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected scatter failure")
            return real(verdict, rows, vals)

        dp._scatter_rows = flaky
        try:
            before = dp.patch_stats["patch_scatter_errors"]
            eng.replace_policy(["k8s:warm=w2-443"], None)
            eng.regenerate()              # must NOT raise
            assert dp.patch_stats["patch_scatter_errors"] == before + 1
            # the healed image serves and equals a fresh placement
            snap = eng.active.snapshot
            np.testing.assert_array_equal(
                np.asarray(eng.active.tensors["verdict"]),
                np.asarray(dp.place(snap)["verdict"]))
            out = eng.classify(traffic(eng.active.snapshot.ep_slot_of),
                               now=900)
            assert out["allow"].shape[0] > 0
        finally:
            dp._scatter_rows = real

    def test_compiler_emits_sparse_payload(self):
        """Unit: the incremental compiler's patch carries rows+values
        matching the emitted snapshot's own cells."""
        eng = fake_engine()
        warm_geometry(eng)
        inc = eng._inc
        eng.replace_policy(["k8s:warm=w1-443"], None)
        eps = sorted(eng.endpoints.values(), key=lambda e: e.ep_id)
        res = inc.try_update(CTConfig(capacity=2048), endpoints=eps)
        assert res is not None
        snap, patch, stats = res
        assert patch.is_delta and stats.delta_rows == patch.delta_rows.shape[0]
        dense = snap.image.verdict        # lazy materialization
        r = patch.delta_rows
        np.testing.assert_array_equal(
            dense[r[:, 0], r[:, 1], r[:, 2]], patch.delta_vals)

    def test_sharded_delta_patch_parity(self):
        """Scatter-apply onto the meshed (flows×rules) verdict: delta
        churn through a 2x2 backend matches the fake."""
        cfg = DaemonConfig(ct_capacity=2048, auto_regen=False,
                           n_shards=2, rule_shards=2)
        eng = make_engine(JITDatapath(cfg))
        ref = fake_engine()
        warm_geometry(eng, ref)
        base = eng.datapath.patch_stats["patch_delta"]
        now = 700
        for step in range(6):
            label = f"k8s:warm=w{step % N_PEERS}-8080"
            body = None if step % 2 else peer_rule_docs(
                step % N_PEERS, 8080, label=label)
            for e in (eng, ref):
                e.replace_policy([label], body)
                e.regenerate()
            b = traffic(eng.active.snapshot.ep_slot_of)
            assert_same_verdicts(eng.classify(dict(b), now=now),
                                 ref.classify(dict(b), now=now),
                                 f"sharded-step{step}")
            now += 10
        assert eng.datapath.patch_stats["patch_delta"] > base


# --------------------------------------------------------------------------- #
# overlay emission invariants
# --------------------------------------------------------------------------- #
class TestOverlayEmission:
    def _world(self):
        from cilium_tpu.model.identity import IdentityAllocator
        from cilium_tpu.model.ipcache import IPCache
        from cilium_tpu.model.labels import Labels
        from cilium_tpu.model.endpoint import Endpoint
        from cilium_tpu.policy import PolicyContext, Repository
        from cilium_tpu.policy.selectorcache import SelectorCache
        alloc = IdentityAllocator()
        ctx = PolicyContext(allocator=alloc,
                            selector_cache=SelectorCache(alloc),
                            ipcache=IPCache())
        repo = Repository(ctx)
        lbls = Labels.parse(["k8s:app=web0"])
        ident = alloc.allocate(lbls)
        ctx.ipcache.upsert("192.168.0.10/32", ident.id)
        eps = [Endpoint(ep_id=1, labels=lbls, identity_id=ident.id)]
        for i in range(4):
            pid = alloc.allocate(Labels.parse([f"k8s:peer=q{i}"]))
            ctx.ipcache.upsert(f"172.17.{i}.0/24", pid.id)
        return ctx, repo, eps

    def _rule(self, i, port, tag):
        from cilium_tpu.model.rules import parse_rule
        return parse_rule({
            "endpointSelector": {"matchLabels": {"app": "web0"}},
            "labels": [f"k8s:t={tag}"],
            "ingress": [{"fromEndpoints": [
                {"matchLabels": {"peer": f"q{i}"}}],
                "toPorts": [{"ports": [{"port": str(port),
                                        "protocol": "TCP"}]}]}]})

    def test_tiny_rebase_budget_keeps_equivalence_and_frozen_snapshots(self):
        """With rebase_rows=1 every emission rebases; with a large budget
        the overlay accumulates — both must stay semantically identical to
        a fresh build and previously emitted snapshots must stay frozen."""
        for rebase in (1, 10_000):
            ctx, repo, eps = self._world()
            repo.add([self._rule(0, 80, "seed")])
            snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
            inc = IncrementalCompiler(repo, ctx, eps, snap,
                                      rebase_rows=rebase)
            emitted = []
            for step in range(8):
                i = step % 4
                if step % 3 == 2:
                    repo.delete_by_labels(
                        __import__("cilium_tpu.model.labels",
                                   fromlist=["Labels"]).Labels.parse(
                            [f"k8s:t=s{step - 2}"]))
                else:
                    repo.add([self._rule(i, 80, f"s{step}")])
                res = inc.try_update(CTConfig(capacity=1024))
                assert res is not None, inc.last_fallback
                s, patch, _ = res
                emitted.append((s, s.image.verdict.copy()))
                fresh = build_snapshot(repo, ctx, eps,
                                       CTConfig(capacity=1024))
                # dense lookups agree cell-for-cell where geometry matches
                for ident in [i.id for i in ctx.allocator.all()]:
                    idx_s = s.id_classes.index_of.get(ident)
                    idx_f = fresh.id_classes.index_of.get(ident)
                    if idx_s is None or idx_f is None:
                        continue
                    cs = s.id_classes.class_of[idx_s]
                    cf = fresh.id_classes.class_of[idx_f]
                    for port in (79, 80, 81, 443):
                        ps = s.port_classes.table[0, port]
                        pf = fresh.port_classes.table[0, port]
                        assert (int(s.image.verdict[0, 1, cs, ps])
                                & C.VERDICT_DECISION_MASK) == \
                               (int(fresh.image.verdict[0, 1, cf, pf])
                                & C.VERDICT_DECISION_MASK), \
                            (rebase, step, ident, port)
            # revision fencing: every emitted image unchanged
            for s, frozen in emitted:
                np.testing.assert_array_equal(s.image.verdict, frozen)

    def test_overlay_image_nbytes_without_materialization(self):
        ctx, repo, eps = self._world()
        repo.add([self._rule(0, 80, "seed")])
        snap = build_snapshot(repo, ctx, eps, CTConfig(capacity=1024))
        inc = IncrementalCompiler(repo, ctx, eps, snap)
        repo.add([self._rule(0, 80, "x")])
        res = inc.try_update(CTConfig(capacity=1024))
        assert res is not None
        s, patch, _ = res
        from cilium_tpu.compile.policy_image import OverlayImage
        if isinstance(s.image, OverlayImage):
            assert s.image._dense is None
            assert s.nbytes > 0                 # no materialization
            assert s.image._dense is None
            _ = s.image.verdict                 # now materialize
            assert s.image._dense is not None


# --------------------------------------------------------------------------- #
# randomized storm: rule add/remove + endpoint churn, engine-level
# --------------------------------------------------------------------------- #
class TestRandomStorm:
    @pytest.mark.parametrize("seed", [3, 11])
    def test_engine_storm_matches_oracle(self, seed):
        """Property storm: random rule toggles (delta path) interleaved
        with endpoint adds/removes (full-build gate) — the JIT engine must
        stay bit-identical to the oracle-backed fake at every revision."""
        import random
        rng = random.Random(seed)
        eng, ref = jit_engine(), fake_engine()
        warm_geometry(eng, ref)
        next_ep = [100]
        added_eps = []
        now = 2000
        for step in range(14):
            op = rng.random()
            if op < 0.7:
                i, p = rng.randrange(N_PEERS), rng.choice((443, 8080))
                label = f"k8s:warm=w{i}-{p}"
                body = None if rng.random() < 0.4 else peer_rule_docs(
                    i, p, deny=rng.random() < 0.3, label=label)
                for e in (eng, ref):
                    e.replace_policy([label], body)
            elif op < 0.85 or not added_eps:
                ep_id = next_ep[0]
                next_ep[0] += 1
                added_eps.append(ep_id)
                for e in (eng, ref):
                    e.add_endpoint([f"k8s:peer=px{ep_id}"],
                                   ips=(f"172.18.{ep_id % 250}.9",),
                                   ep_id=ep_id)
            else:
                ep_id = added_eps.pop(rng.randrange(len(added_eps)))
                for e in (eng, ref):
                    e.remove_endpoint(ep_id)
            for e in (eng, ref):
                e.regenerate()
            assert eng.active.revision == ref.active.revision
            b = traffic(eng.active.snapshot.ep_slot_of)
            assert_same_verdicts(eng.classify(dict(b), now=now),
                                 ref.classify(dict(b), now=now),
                                 f"storm{seed}-{step}")
            now += 7
        assert eng.datapath.patch_stats["patch_delta"] >= 1


# --------------------------------------------------------------------------- #
# overlapped device-side CT GC
# --------------------------------------------------------------------------- #
class TestOverlappedCTGC:
    def _ct_with_expiries(self, cap=1024):
        import jax.numpy as jnp
        ct = make_ct_arrays(CTConfig(capacity=cap, probe_depth=4))
        rng = np.random.default_rng(5)
        n = cap // 2
        slots = rng.choice(cap, size=n, replace=False)
        ct["expiry"][slots] = rng.integers(1, 200, n).astype(np.uint32)
        ct["key0"][slots] = np.arange(n, dtype=np.uint32) + 1
        return {k: jnp.asarray(v) for k, v in ct.items()}

    def test_chunked_epoch_equals_whole_table_sweep(self):
        """One full epoch of chunk sweeps == one whole-table sweep: same
        final table, same total reclaimed."""
        import jax.numpy as jnp
        from cilium_tpu.kernels.conntrack import ct_sweep, ct_sweep_chunk
        cap, chunk = 1024, 128
        ct_a = self._ct_with_expiries(cap)
        ct_b = {k: v + 0 for k, v in ct_a.items()}   # independent copy
        now = jnp.uint32(100)
        swept, n_full = ct_sweep(ct_a, now)
        total = 0
        for start in range(0, cap, chunk):
            ct_b, n, live = ct_sweep_chunk(ct_b, now, jnp.uint32(start),
                                           chunk)
            total += int(n)
        assert total == int(n_full)
        for k in swept:
            np.testing.assert_array_equal(np.asarray(swept[k]),
                                          np.asarray(ct_b[k]), k)

    def test_chunk_window_wraps(self):
        import jax.numpy as jnp
        from cilium_tpu.kernels.conntrack import ct_sweep_chunk
        cap, chunk = 256, 128
        ct = self._ct_with_expiries(cap)
        # start near the end: window covers [192, 256) ∪ [0, 64)
        new_ct, n, _ = ct_sweep_chunk(ct, jnp.uint32(100),
                                      jnp.uint32(192), chunk)
        exp_old = np.asarray(ct["expiry"])
        exp_new = np.asarray(new_ct["expiry"])
        in_win = np.r_[np.arange(192, 256), np.arange(0, 64)]
        out_win = np.arange(64, 192)
        dead = (exp_old[in_win] > 0) & (exp_old[in_win] <= 100)
        assert (exp_new[in_win][dead] == 0).all()
        np.testing.assert_array_equal(exp_new[out_win], exp_old[out_win])
        assert int(n) == int(dead.sum())

    def test_sweep_step_overlap_and_metrics(self):
        """Engine.sweep_step drives the double-buffered sweep: reclaimed
        counts harvest one tick late, the counter/gauge families export,
        and live flows survive while expired ones are reclaimed."""
        eng = jit_engine(ct_gc_chunk_rows=256)
        slots = eng.active.snapshot.ep_slot_of
        # establish allowed flows at t=1000 (peers in g0 on port 80)
        eng.classify(traffic(slots), now=1000)
        live0 = eng.datapath.ct_stats(1001)["live"]
        assert live0 > 0
        # run one full epoch well past expiry: every entry reclaims
        ticks = (2048 // 256) + 2
        total = 0
        st = None
        for _ in range(ticks + 1):      # +1: the last tick's harvest
            st = eng.sweep_step(now=1_000_000)   # far past every expiry
            total += st["reclaimed"]
        assert total >= live0, (total, live0)
        assert st["epoch"] >= 1
        rendered = eng.render_metrics()
        assert "ct_gc_reclaimed_total" in rendered
        assert "ct_occupancy" in rendered

    def test_gc_is_semantics_free_under_traffic(self):
        """Interleaving chunk sweeps with classify must not change any
        verdict: a live flow stays ESTABLISHED, an expired one re-learns
        as NEW — identical to an engine that never sweeps."""
        eng_gc, eng_ref = jit_engine(), jit_engine()
        slots = eng_gc.active.snapshot.ep_slot_of
        for e in (eng_gc, eng_ref):
            e.classify(traffic(slots), now=1000)      # SYN: establish
        out = []
        for step in range(6):
            now = 1005 + step
            eng_gc.sweep_step(now=now)
            a = eng_gc.classify(traffic(slots, flags=0x10), now=now)
            b = eng_ref.classify(traffic(slots, flags=0x10), now=now)
            assert_same_verdicts(a, b, f"gc-step{step}")
            out.append(a)
        est = np.asarray(out[-1]["status"])
        assert (est == int(C.CTStatus.ESTABLISHED)).any()

    def test_ct_gc_fault_point(self):
        eng = jit_engine()
        FAULTS.arm("ct.gc", mode="fail", times=1)
        try:
            with pytest.raises(FaultInjected):
                eng.sweep_step()
        finally:
            FAULTS.disarm("ct.gc")
        # next tick proceeds normally
        st = eng.sweep_step()
        assert st["chunk_rows"] == eng.config.ct_gc_chunk_rows

    def test_controller_selection(self):
        """Overlap-capable backend at ct_gc_interval_s; the fake keeps the
        host sweep. Neither start crashes; both register ct-gc."""
        for eng in (jit_engine(), fake_engine()):
            try:
                eng.start_background()
                assert "ct-gc" in getattr(eng.controllers, "_controllers",
                                          {"ct-gc": None})
            finally:
                eng.stop()

    def test_host_sweep_exports_counters_too(self):
        eng = fake_engine()
        slots = eng.active.snapshot.ep_slot_of
        eng.classify(traffic(slots), now=1000)
        reclaimed = eng.sweep(now=10_000_000)
        rendered = eng.render_metrics()
        assert "ct_occupancy" in rendered
        if reclaimed:
            assert "ct_gc_reclaimed_total" in rendered


# --------------------------------------------------------------------------- #
# bounded classify-fn memo
# --------------------------------------------------------------------------- #
class TestClassifyFnCacheLRU:
    def test_lru_cap_and_eviction_counter(self, monkeypatch):
        from cilium_tpu.kernels import classify as ck
        monkeypatch.setattr(ck, "FN_CACHE_CAP", 4)
        ck._FN_CACHE.clear()
        ev0 = ck._FN_EVICTIONS[0]
        fns = [ck.make_classify_fn(lb_probe_depth=8 + i) for i in range(6)]
        st = ck.fn_cache_stats()
        assert st["size"] <= 4
        assert ck._FN_EVICTIONS[0] == ev0 + 2
        # the most-recent entries survive; hits touch LRU order
        assert ck.make_classify_fn(lb_probe_depth=13) is fns[5]
        # an evicted key rebuilds without growing past the cap
        ck.make_classify_fn(lb_probe_depth=8)
        assert ck.fn_cache_stats()["size"] <= 4

    def test_memo_hit_returns_same_fn(self):
        from cilium_tpu.kernels import classify as ck
        a = ck.make_classify_fn(probe_depth=8, packed=True)
        b = ck.make_classify_fn(probe_depth=8, packed=True)
        assert a is b


# --------------------------------------------------------------------------- #
# conntrack survival across restart (ROADMAP 3b)
# --------------------------------------------------------------------------- #
def _flow_pkt(flags):
    s16, _ = parse_addr("172.16.0.5")
    d16, _ = parse_addr("192.168.1.10")
    return PacketRecord(s16, d16, 33333, 80, C.PROTO_TCP, flags, False, 1,
                        C.DIR_INGRESS)


class TestCTRestart:
    @pytest.mark.parametrize("backend", ["fake", "jit"])
    def test_established_flows_survive_restart(self, tmp_path, backend):
        def dp():
            cfg = DaemonConfig(ct_capacity=2048, auto_regen=False)
            return (JITDatapath(cfg) if backend == "jit"
                    else FakeDatapath(cfg))
        eng = make_engine(dp())
        slots = eng.active.snapshot.ep_slot_of
        b = batch_from_records([_flow_pkt(C.TCP_SYN)], slots)
        out = eng.classify(b, now=1000)
        assert bool(out["allow"][0])
        path = str(tmp_path / "ckpt")
        ckpt.save(eng, path)
        eng.stop()

        # restart: restored CT → the non-SYN packet is ESTABLISHED
        eng2 = Engine(DaemonConfig(ct_capacity=2048, auto_regen=False),
                      datapath=dp())
        assert ckpt.restore(eng2, path) is True
        b2 = batch_from_records(
            [_flow_pkt(0x10)], eng2.active.snapshot.ep_slot_of)
        out2 = eng2.classify(b2, now=1005)
        assert bool(out2["allow"][0])
        assert int(out2["status"][0]) == int(C.CTStatus.ESTABLISHED)
        eng2.stop()

        # control: a cold engine sees the same packet as NEW
        eng3 = make_engine(dp())
        out3 = eng3.classify(
            batch_from_records([_flow_pkt(0x10)],
                               eng3.active.snapshot.ep_slot_of), now=1005)
        assert int(out3["status"][0]) == int(C.CTStatus.NEW)

    def test_ct_archive_is_versioned(self, tmp_path):
        eng = fake_engine()
        eng.classify(batch_from_records(
            [_flow_pkt(C.TCP_SYN)], eng.active.snapshot.ep_slot_of),
            now=1000)
        path = str(tmp_path / "ckpt")
        ckpt.save(eng, path)
        with np.load(os.path.join(path, "ct.npz")) as npz:
            assert "__ct_format__" in npz.files
            assert int(npz["__ct_format__"]) == CT_FORMAT_VERSION
        state = ckpt._read_state(path)
        assert state["ct_format"] == CT_FORMAT_VERSION
        # a FUTURE-format archive is dropped (flows re-learn), control
        # plane restores fine
        arrays = ckpt._read_ct(path)
        np.savez(os.path.join(path, "ct.npz"),
                 __ct_format__=np.int32(CT_FORMAT_VERSION + 1), **arrays)
        # the sha no longer matches either way; _read_ct's version check
        # fires first when loaded directly
        assert ckpt._read_ct(path) is None

    @pytest.mark.slow
    def test_restart_mid_soak_keeps_verdicts(self, tmp_path):
        """The chaos-adjacent soak: pipelined traffic, daemon restarts
        mid-soak (save → stop → fresh engine → restore), established flows
        keep their verdicts through the reloaded CT."""
        eng = jit_engine()
        slots = eng.active.snapshot.ep_slot_of
        n_flows = 48
        # all flows from p0 (group g0 — the allowed ingress peer): a
        # denied flow never establishes, so it cannot test CT survival
        syn = [PacketRecord(parse_addr("172.16.0.5")[0],
                            parse_addr("192.168.1.10")[0],
                            40000 + i, 80, C.PROTO_TCP, C.TCP_SYN, False,
                            1, C.DIR_INGRESS) for i in range(n_flows)]
        ack = [PacketRecord(p.src_addr, p.dst_addr, p.src_port, p.dst_port,
                            p.proto, 0x10, False, p.ep_id, p.direction)
               for p in syn]
        for chunk in range(0, n_flows, 16):
            t = eng.submit(batch_from_records(syn[chunk:chunk + 16], slots),
                           now=3000 + chunk)
            t.result(timeout=30)
        # upgrade past the SYN lifetime (SEEN_NON_SYN → full TCP lifetime)
        for chunk in range(0, n_flows, 16):
            eng.submit(batch_from_records(ack[chunk:chunk + 16], slots),
                       now=3050).result(timeout=30)
        assert eng.drain(timeout=30)
        path = str(tmp_path / "soak-ckpt")
        ckpt.save(eng, path)
        eng.stop()

        eng2 = Engine(DaemonConfig(ct_capacity=2048, auto_regen=False),
                      datapath=JITDatapath(
                          DaemonConfig(ct_capacity=2048, auto_regen=False)))
        assert ckpt.restore(eng2, path) is True
        slots2 = eng2.active.snapshot.ep_slot_of
        est = 0
        for chunk in range(0, n_flows, 16):
            out = eng2.submit(
                batch_from_records(ack[chunk:chunk + 16], slots2),
                now=3100 + chunk).result(timeout=30)
            est += int((np.asarray(out["status"])
                        == int(C.CTStatus.ESTABLISHED)).sum())
        eng2.stop()
        assert est == n_flows, f"only {est}/{n_flows} flows survived"


# --------------------------------------------------------------------------- #
# the storm soak with the parity auditor at sampling 1.0
# --------------------------------------------------------------------------- #
class TestStormAudit:
    @pytest.mark.parametrize("steps", [
        18, pytest.param(60, marks=pytest.mark.slow)])
    def test_policy_storm_audited_at_full_sampling(self, steps):
        """Pipelined traffic under continuous rule churn with the shadow
        auditor at sampling 1.0: zero parity mismatches, and the churn
        actually exercised the delta-patch path (no batch classified under
        a torn revision — the auditor replays each batch against the exact
        revision it classified under), with no scatter healed by a full
        upload on the way."""
        eng = jit_engine(audit_enabled=True, audit_sample_rate=1.0,
                         audit_pool_batches=64, audit_max_rows=512)
        eng.auditor.configure(sample_rate=1.0)
        warm_geometry(eng)
        slots = eng.active.snapshot.ep_slot_of
        now = 5000
        tickets = []
        base = dict(eng.datapath.patch_stats)
        for step in range(steps):
            if step % 3 == 0:
                i, p = step % N_PEERS, (443, 8080)[step % 2]
                label = f"k8s:warm=w{i}-{p}"
                body = None if step % 6 else peer_rule_docs(i, p,
                                                            label=label)
                eng.replace_policy([label], body)
                eng.regenerate()
            tickets.append(eng.submit(traffic(slots), now=now))
            now += 1
        assert eng.drain(timeout=120)
        for t in tickets:
            t.result(timeout=10)
        # drain the audit pool completely
        for _ in range(200):
            step = eng.audit_step(budget=64)
            if not step or (not step.get("replayed")
                            and not step.get("pending")):
                break
        st = eng.auditor.stats()
        assert st["checked_rows"] > 0, st
        assert st["mismatched_rows"] == 0, st
        ps = eng.datapath.patch_stats
        assert ps["patch_delta"] - base["patch_delta"] >= 1, ps
        assert ps["patch_scatter_errors"] == base["patch_scatter_errors"]
        eng.stop()
