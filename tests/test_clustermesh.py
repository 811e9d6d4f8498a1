"""Multi-host control-plane sync (SURVEY.md §5 distributed backend; upstream
pkg/clustermesh): two engines share a store directory; each publishes its
endpoints' (prefix, labels) and ingests the other's, allocating LOCAL
identities for remote label sets — so ordinary label policy selects remote
pods, verdicts included."""

import json
import os
import time

import numpy as np
import pytest

from cilium_tpu.kernels.records import batch_from_records
from cilium_tpu.runtime.clustermesh import ClusterMesh
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.runtime.faults import FAULTS
from cilium_tpu.utils import constants as C
from cilium_tpu.utils.ip import parse_addr
from oracle import PacketRecord


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def write_peer(store, node, gen, entries, published_at=None,
               claimed_node=None):
    """Write a peer file the way publish() would (atomic rename)."""
    os.makedirs(store, exist_ok=True)
    doc = {"format_version": 1, "node": claimed_node or node,
           "generation": gen,
           "published_at": time.time() if published_at is None
           else published_at,
           "entries": entries}
    path = os.path.join(store, f"{node}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(doc, f)
    os.replace(path + ".tmp", path)


def _node(tmp_path, name, node=True):
    cfg = DaemonConfig(ct_capacity=1024, auto_regen=False,
                       cluster_store=str(tmp_path / "store") if node else "",
                       node_name=name if node else "")
    return Engine(cfg, datapath=FakeDatapath(DaemonConfig(ct_capacity=1024)))


def _pkt(src, dst, sp, dp, ep_id, d=C.DIR_INGRESS):
    s16, _ = parse_addr(src)
    d16, _ = parse_addr(dst)
    return PacketRecord(s16, d16, sp, dp, C.PROTO_TCP, C.TCP_SYN, False,
                        ep_id, d)


class TestClusterMesh:
    def test_cross_node_policy_by_labels(self, tmp_path):
        """Node B's policy 'allow from role=backup' matches node A's pod via
        the mesh: A publishes (ip, labels); B allocates a local identity for
        those labels; B's selector picks it up; classify allows."""
        a = _node(tmp_path, "node-a")
        b = _node(tmp_path, "node-b")
        a.add_endpoint(["k8s:role=backup"], ips=("10.1.0.5",), ep_id=1)
        b.add_endpoint(["k8s:app=db"], ips=("10.2.0.9",), ep_id=1)
        b.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "db"}},
            "ingress": [{"fromEndpoints": [
                {"matchLabels": {"role": "backup"}}],
                "toPorts": [{"ports": [
                    {"port": "5432", "protocol": "TCP"}]}]}]}])

        mesh_a = ClusterMesh(a, str(tmp_path / "store"), "node-a")
        mesh_b = ClusterMesh(b, str(tmp_path / "store"), "node-b")
        mesh_a.step()
        mesh_b.step()
        b.regenerate()

        slots = b.active.snapshot.ep_slot_of
        batch = batch_from_records(
            [_pkt("10.1.0.5", "10.2.0.9", 40000, 5432, 1),   # remote backup
             _pkt("10.9.9.9", "10.2.0.9", 40001, 5432, 1)],  # unknown world
            slots)
        out = b.classify(dict(batch), now=100)
        assert bool(out["allow"][0]), "remote pod not selected by policy"
        assert not bool(out["allow"][1])
        # the remote identity resolved is a real local allocation with the
        # peer's labels
        rid = int(out["remote_identity"][0])
        ident = b.ctx.allocator.get(rid)
        assert ident is not None
        assert "k8s:role=backup" in ident.labels.to_strings()

    def test_withdrawal_and_stale_peer(self, tmp_path, monkeypatch):
        a = _node(tmp_path, "node-a")
        b = _node(tmp_path, "node-b")
        a.add_endpoint(["k8s:role=backup"], ips=("10.1.0.5",), ep_id=1)
        mesh_a = ClusterMesh(a, str(tmp_path / "store"), "node-a")
        mesh_b = ClusterMesh(b, str(tmp_path / "store"), "node-b",
                             stale_after_s=60)
        mesh_a.step()
        mesh_b.sync()
        assert "10.1.0.5/32" in b.ctx.ipcache.snapshot()

        # endpoint removed on A → withdrawn on B at the next round trip
        a.remove_endpoint(1)
        mesh_a.publish()
        mesh_b.sync()
        assert "10.1.0.5/32" not in b.ctx.ipcache.snapshot()

        # stale peer (lease expiry): state withdrawn even with no explicit
        # removal. Staleness is judged from B's OWN lease clock, renewed
        # only on generation progress (never from the peer-written
        # published_at, which a skewed peer clock would poison) — so the
        # stall is simulated by freezing A's generation and advancing B's
        # clock past the lease.
        a.add_endpoint(["k8s:role=backup"], ips=("10.1.0.6",), ep_id=2)
        mesh_a.publish()
        mesh_b.sync()
        assert "10.1.0.6/32" in b.ctx.ipcache.snapshot()
        import cilium_tpu.runtime.clustermesh as cm
        real_time = time.time
        monkeypatch.setattr(cm.time, "time", lambda: real_time() + 3600)
        mesh_b.sync()
        assert "10.1.0.6/32" not in b.ctx.ipcache.snapshot()

    def test_label_change_reallocates(self, tmp_path):
        a = _node(tmp_path, "node-a")
        b = _node(tmp_path, "node-b")
        a.add_endpoint(["k8s:role=backup"], ips=("10.1.0.5",), ep_id=1)
        mesh_a = ClusterMesh(a, str(tmp_path / "store"), "node-a")
        mesh_b = ClusterMesh(b, str(tmp_path / "store"), "node-b")
        mesh_a.step()
        mesh_b.sync()
        id1 = b.ctx.ipcache.snapshot()["10.1.0.5/32"]
        # relabel the pod on A → B must re-ingest under a new identity
        a.remove_endpoint(1)
        a.add_endpoint(["k8s:role=primary"], ips=("10.1.0.5",), ep_id=2)
        mesh_a.publish()
        mesh_b.sync()
        id2 = b.ctx.ipcache.snapshot()["10.1.0.5/32"]
        assert id1 != id2
        ident = b.ctx.allocator.get(id2)
        assert "k8s:role=primary" in ident.labels.to_strings()

    def test_handoff_rides_delta_patch_path(self, tmp_path):
        """ISSUE 12 datapath consequence: remote entries arriving AFTER the
        incremental compiler is seeded ride the PR 9 delta path (identity
        growth + LPM rebuild), not a full rebuild — and the verdict matches
        what a fresh compile of the merged world produces."""
        a = _node(tmp_path, "node-a")
        b = _node(tmp_path, "node-b")
        a.add_endpoint(["k8s:role=backup"], ips=("10.1.0.5",), ep_id=1)
        b.add_endpoint(["k8s:app=db"], ips=("10.2.0.9",), ep_id=1)
        b.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "db"}},
            "ingress": [{"fromEndpoints": [
                {"matchLabels": {"role": "backup"}}],
                "toPorts": [{"ports": [
                    {"port": "5432", "protocol": "TCP"}]}]}]}])
        b.regenerate()                 # seed BEFORE remote entries arrive
        full_before = b.metrics.counters.get("regen_full_total", 0)

        ClusterMesh(a, str(tmp_path / "store"), "node-a").step()
        ClusterMesh(b, str(tmp_path / "store"), "node-b").step()
        b.regenerate()
        assert b.metrics.counters.get("regen_incremental_total", 0) >= 1
        assert b.metrics.counters.get("regen_full_total", 0) == full_before

        batch = batch_from_records(
            [_pkt("10.1.0.5", "10.2.0.9", 40000, 5432, 1)],
            b.active.snapshot.ep_slot_of)
        out = b.classify(dict(batch), now=100)
        assert bool(out["allow"][0])

    def test_engine_lifecycle_integration(self, tmp_path):
        """start_background wires the controller; stop withdraws the node
        file; corrupt peer files are skipped without failing the sync."""
        a = _node(tmp_path, "node-a")
        a.add_endpoint(["k8s:x=1"], ips=("10.1.0.7",), ep_id=1)
        a.config.cluster_sync_interval_s = 0.05
        a.start_background()
        store = tmp_path / "store"
        deadline = time.time() + 5
        while not (store / "node-a.json").exists():
            assert time.time() < deadline, "publish never happened"
            time.sleep(0.02)
        # garbage peer file must not break the loop
        (store / "node-bad.json").write_text("{not json")
        time.sleep(0.1)
        assert (store / "node-a.json").exists()
        a.stop()
        assert not (store / "node-a.json").exists()


class _Clock:
    """Mutable test clock handed to ClusterMesh(clock=...)."""

    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _mesh(engine, tmp_path, name, clock, stale_after_s=60.0,
          staleness_budget_s=15.0):
    m = ClusterMesh(engine, str(tmp_path / "store"), name,
                    stale_after_s=stale_after_s,
                    staleness_budget_s=staleness_budget_s, clock=clock)
    engine._mesh = m               # health() folds the mesh detail in
    return m


class TestPartitionContract:
    """ISSUE 12 (a): store partition — last-good serving, MESH_STALE past
    the budget, never fail closed on established remote flows."""

    def test_partition_serves_last_good_then_mesh_stale(self, tmp_path):
        clk = _Clock()
        b = _node(tmp_path, "node-b")
        mesh = _mesh(b, tmp_path, "node-b", clk, staleness_budget_s=5.0)
        write_peer(str(tmp_path / "store"), "node-a", 1,
                   {"10.1.0.5/32": {"labels": ["k8s:role=backup"]}})
        mesh.sync()
        assert b.ctx.ipcache.get("10.1.0.5/32") is not None

        FAULTS.arm("clustermesh.store_list", mode="fail")
        clk.t += 2.0
        mesh.sync()
        # inside the budget: stale not yet declared, state held
        assert b.ctx.ipcache.get("10.1.0.5/32") is not None
        assert not mesh.is_stale()
        assert mesh.status()["state"] == C.HEALTH_OK
        assert not mesh.status()["store_ok"]

        clk.t += 10.0                 # budget spent
        mesh.sync()
        st = mesh.status()
        assert mesh.is_stale()
        assert st["state"] == C.MESH_STALE
        # last-good remote state still serves: partition is a control-plane
        # outage, never a data-plane one
        assert b.ctx.ipcache.get("10.1.0.5/32") is not None
        health = b.health()
        assert health["state"] == C.HEALTH_DEGRADED
        assert health["mesh"]["state"] == C.MESH_STALE

        FAULTS.disarm("clustermesh.store_list")
        mesh.sync()                   # heal: next good pass clears it
        assert not mesh.is_stale()
        assert mesh.status()["state"] == C.HEALTH_OK
        assert b.health()["state"] == C.HEALTH_OK
        assert b.ctx.ipcache.get("10.1.0.5/32") is not None

    def test_lease_never_expires_during_partition(self, tmp_path):
        """A peer lease must only age out under a HEALTHY listing: during
        a partition no heartbeat is observable at all, and expiring then
        would turn the control-plane outage into a data-plane one. After
        heal, a peer whose generation did not progress expires on the
        first good pass."""
        clk = _Clock()
        b = _node(tmp_path, "node-b")
        mesh = _mesh(b, tmp_path, "node-b", clk, stale_after_s=30.0)
        write_peer(str(tmp_path / "store"), "node-a", 1,
                   {"10.1.0.5/32": {"labels": ["k8s:role=backup"]}})
        mesh.sync()
        assert b.ctx.ipcache.get("10.1.0.5/32") is not None

        FAULTS.arm("clustermesh.store_list", mode="fail")
        clk.t += 300.0                # way past the lease, store dark
        mesh.sync()
        assert b.ctx.ipcache.get("10.1.0.5/32") is not None, \
            "lease expired during a partition"

        FAULTS.disarm("clustermesh.store_list")
        mesh.sync()                   # heal: gen 1 never progressed
        assert b.ctx.ipcache.get("10.1.0.5/32") is None

    def test_unreadable_peer_file_holds_last_good(self, tmp_path):
        clk = _Clock()
        b = _node(tmp_path, "node-b")
        mesh = _mesh(b, tmp_path, "node-b", clk)
        store = str(tmp_path / "store")
        write_peer(store, "node-a", 1,
                   {"10.1.0.5/32": {"labels": ["k8s:role=backup"]}})
        mesh.sync()
        (tmp_path / "store" / "node-a.json").write_text("{torn")
        clk.t += 5.0
        mesh.sync()                   # single-file flake: state held
        assert b.ctx.ipcache.get("10.1.0.5/32") is not None
        # explicit deletion from a HEALTHY store is a clean withdraw
        os.unlink(os.path.join(store, "node-a.json"))
        mesh.sync()
        assert b.ctx.ipcache.get("10.1.0.5/32") is None

    def test_dead_peers_file_cannot_resurrect_it(self, tmp_path):
        """A crashed peer's file lingers in the store. After its lease
        expires the generation is tombstoned: only real progress (the node
        restarting and publishing anew) revives the peer."""
        clk = _Clock()
        b = _node(tmp_path, "node-b")
        mesh = _mesh(b, tmp_path, "node-b", clk, stale_after_s=30.0)
        store = str(tmp_path / "store")
        write_peer(store, "node-a", 7,
                   {"10.1.0.5/32": {"labels": ["k8s:role=backup"]}})
        mesh.sync()
        clk.t += 31.0
        mesh.sync()                   # lease expired, file still present
        assert b.ctx.ipcache.get("10.1.0.5/32") is None
        for _ in range(3):            # the lingering file must stay dead
            clk.t += 1.0
            mesh.sync()
            assert b.ctx.ipcache.get("10.1.0.5/32") is None
        write_peer(store, "node-a", 8,
                   {"10.1.0.5/32": {"labels": ["k8s:role=backup"]}})
        mesh.sync()                   # generation progressed: resurrected
        assert b.ctx.ipcache.get("10.1.0.5/32") is not None


class TestConflictContract:
    """ISSUE 12 (b): conflicting prefix claims resolve deterministically —
    highest generation, then lexicographically-first node name — and
    identically regardless of the order claims were observed."""

    PREFIX = "10.77.0.7/32"

    def _claims(self, store, order):
        docs = {
            "node-a": (4, {self.PREFIX: {"labels": ["k8s:app=a"]}}),
            "node-b": (9, {self.PREFIX: {"labels": ["k8s:app=b"]}}),
        }
        for node in order:
            gen, entries = docs[node]
            write_peer(store, node, gen, entries)

    def _winner_labels(self, engine):
        ident = engine.ctx.allocator.get(
            engine.ctx.ipcache.get(self.PREFIX))
        return tuple(sorted(ident.labels.to_strings()))

    @pytest.mark.parametrize("order", [("node-a", "node-b"),
                                       ("node-b", "node-a")])
    def test_winner_identical_for_both_ingest_orders(self, tmp_path, order):
        """Acceptance: run BOTH ingest orders — the first claim lands and
        is ingested alone, then the second arrives; the final owner is the
        same either way (node-b: generation 9 beats 4), the loser's claim
        withdrawn rather than split-brained."""
        clk = _Clock()
        c = _node(tmp_path, "node-c")
        mesh = _mesh(c, tmp_path, "node-c", clk)
        store = str(tmp_path / "store")
        first, second = order
        self._claims(store, [first])
        mesh.sync()                   # first claim alone: ingested as-is
        assert self._winner_labels(c) == (f"k8s:app={first[-1]}",)
        self._claims(store, [second])
        clk.t += 1.0
        mesh.sync()                   # conflict: deterministic resolution
        assert self._winner_labels(c) == ("k8s:app=b",)
        st = mesh.status()
        assert st["conflicts"][self.PREFIX]["winner"] == "node-b"
        assert st["conflicts"][self.PREFIX]["losers"] == ["node-a"]
        assert c.metrics.counters.get(
            'clustermesh_conflicts_total{prefix_winner="node-b"}', 0) >= 1
        view = mesh.remote_view()
        assert view[self.PREFIX]["peer"] == "node-b"

    def test_generation_tie_breaks_on_node_name(self, tmp_path):
        clk = _Clock()
        c = _node(tmp_path, "node-c")
        mesh = _mesh(c, tmp_path, "node-c", clk)
        store = str(tmp_path / "store")
        write_peer(store, "node-b", 5,
                   {self.PREFIX: {"labels": ["k8s:app=b"]}})
        write_peer(store, "node-a", 5,
                   {self.PREFIX: {"labels": ["k8s:app=a"]}})
        mesh.sync()
        assert mesh.status()["conflicts"][self.PREFIX]["winner"] == "node-a"
        assert self._winner_labels(c) == ("k8s:app=a",)

    def test_local_prefix_beats_any_remote_claim(self, tmp_path):
        clk = _Clock()
        c = _node(tmp_path, "node-c")
        c.add_endpoint(["k8s:app=local"], ips=("10.77.0.7",), ep_id=1)
        local_id = c.ctx.ipcache.get(self.PREFIX)
        mesh = _mesh(c, tmp_path, "node-c", clk)
        write_peer(str(tmp_path / "store"), "node-b", 999,
                   {self.PREFIX: {"labels": ["k8s:app=b"]}})
        mesh.sync()
        assert c.ctx.ipcache.get(self.PREFIX) == local_id
        st = mesh.status()
        assert st["conflicts"][self.PREFIX]["winner"] == "node-c"


class TestStoreHygiene:
    """Satellites: spoofed peer files, tmp litter, loud withdraw."""

    def test_spoofed_peer_file_ignored(self, tmp_path):
        """A peer file whose doc claims another node must not be ingested
        under the filename's ledger — and must not displace the real
        peer's last-good state (spoofed withdrawal on the next sync)."""
        clk = _Clock()
        b = _node(tmp_path, "node-b")
        mesh = _mesh(b, tmp_path, "node-b", clk)
        store = str(tmp_path / "store")
        write_peer(store, "node-a", 1,
                   {"10.1.0.5/32": {"labels": ["k8s:role=backup"]}})
        mesh.sync()
        assert b.ctx.ipcache.get("10.1.0.5/32") is not None
        # node-a's file now impersonates node-z (carrying no entries —
        # the spoofed-withdrawal shape)
        write_peer(store, "node-a", 2, {}, claimed_node="node-z")
        clk.t += 1.0
        mesh.sync()
        assert b.ctx.ipcache.get("10.1.0.5/32") is not None, \
            "spoofed file displaced the real peer's state"
        assert b.metrics.counters.get(
            "clustermesh_spoofed_peer_files_total", 0) >= 1
        assert "node-z" not in mesh.status()["peers"]

    def test_publish_failure_leaves_no_tmp_litter(self, tmp_path,
                                                  monkeypatch):
        a = _node(tmp_path, "node-a")
        mesh = ClusterMesh(a, str(tmp_path / "store"), "node-a")
        import cilium_tpu.runtime.clustermesh as cm

        def boom(*args, **kw):
            raise OSError("disk full")
        monkeypatch.setattr(cm.json, "dump", boom)
        with pytest.raises(OSError):
            mesh.publish()
        litter = [n for n in os.listdir(str(tmp_path / "store"))
                  if n.startswith(".")]
        assert litter == []

    def test_startup_sweeps_tmp_litter(self, tmp_path):
        store = tmp_path / "store"
        store.mkdir()
        own = store / ".node-a-deadbeef"
        own.write_text("{}")          # our own crash litter: always swept
        old = store / ".node-b-cafe"
        old.write_text("{}")          # another writer's, long-dead
        os.utime(old, (time.time() - 3600, time.time() - 3600))
        fresh = store / ".node-c-beef"
        fresh.write_text("{}")        # another writer mid-rename: kept
        a = _node(tmp_path, "node-a")
        ClusterMesh(a, str(store), "node-a")
        assert not own.exists()
        assert not old.exists()
        assert fresh.exists()
        assert a.metrics.counters.get("clustermesh_tmp_swept_total") == 2

    def test_withdraw_failure_is_counted(self, tmp_path, monkeypatch):
        """Satellite: a node that cannot cleanly withdraw looks identical
        to one that did, for the whole lease — so the failure is loud."""
        a = _node(tmp_path, "node-a")
        mesh = ClusterMesh(a, str(tmp_path / "store"), "node-a")
        mesh.publish()
        import cilium_tpu.runtime.clustermesh as cm

        def boom(path):
            raise PermissionError(13, "read-only store")
        monkeypatch.setattr(cm.os, "unlink", boom)
        mesh.withdraw()               # must not raise
        assert a.metrics.counters.get(
            "clustermesh_withdraw_errors_total") == 1
        # FileNotFoundError stays silent: never published is not an error
        monkeypatch.setattr(
            cm.os, "unlink",
            lambda p: (_ for _ in ()).throw(FileNotFoundError(p)))
        mesh.withdraw()
        assert a.metrics.counters.get(
            "clustermesh_withdraw_errors_total") == 1


class TestHandoffRace:
    """Satellite: prefix hand-off racing lease expiry — the pod moves
    peers while the departing peer's file is unreadable. The re-upsert
    path and the lease-withdrawal path must compose without a permanent
    ipcache hole."""

    PREFIX = "10.1.0.5/32"
    LABELS = ["k8s:role=backup"]

    def test_handoff_while_departing_file_unreadable(self, tmp_path):
        clk = _Clock()
        c = _node(tmp_path, "node-c")
        mesh = _mesh(c, tmp_path, "node-c", clk, stale_after_s=30.0)
        store = str(tmp_path / "store")
        write_peer(store, "node-a", 10, {self.PREFIX:
                                         {"labels": self.LABELS}})
        mesh.sync()
        id_before = c.ctx.ipcache.get(self.PREFIX)
        assert id_before is not None

        # the pod moves a → b (same labels, b publishes a higher claim);
        # a's file turns to garbage at the same moment (crashed writer)
        (tmp_path / "store" / "node-a.json").write_text("{torn")
        write_peer(store, "node-b", 11, {self.PREFIX:
                                         {"labels": self.LABELS}})
        for _ in range(3):            # race window: every sync must serve
            clk.t += 1.0
            mesh.sync()
            assert c.ctx.ipcache.get(self.PREFIX) is not None, \
                "ipcache hole during hand-off"
        # same labels ⇒ the hand-off re-referenced the same identity
        # (deferred release), not a new number
        assert c.ctx.ipcache.get(self.PREFIX) == id_before
        assert mesh.remote_view()[self.PREFIX]["peer"] == "node-b"

        # now a's lease expires while its file is STILL unreadable: the
        # withdrawal pass must not punch a hole under b's live claim
        # (b is alive, so its generation keeps progressing)
        clk.t += 31.0
        write_peer(store, "node-b", 12, {self.PREFIX:
                                         {"labels": self.LABELS}})
        mesh.sync()
        assert c.ctx.ipcache.get(self.PREFIX) is not None
        assert "node-a" not in mesh.status()["peers"]
        assert mesh.remote_view()[self.PREFIX]["peer"] == "node-b"

    def test_remote_to_local_handoff_keeps_local_entry(self, tmp_path):
        """The pod moves from a remote peer TO THIS node: the old remote
        mapping's withdrawal must not delete the live local endpoint's
        ipcache entry (local prefixes are claims too, even though
        _resolve_claims strips them from every peer's effective map)."""
        clk = _Clock()
        c = _node(tmp_path, "node-c")
        mesh = _mesh(c, tmp_path, "node-c", clk)
        store = str(tmp_path / "store")
        write_peer(store, "node-b", 1, {self.PREFIX:
                                        {"labels": self.LABELS}})
        mesh.sync()
        assert mesh.remote_view()[self.PREFIX]["peer"] == "node-b"

        # the pod lands locally; b withdraws its claim
        c.add_endpoint(self.LABELS, ips=("10.1.0.5",), ep_id=1)
        local_id = c.ctx.ipcache.get(self.PREFIX)
        write_peer(store, "node-b", 2, {})
        clk.t += 1.0
        mesh.sync()
        assert c.ctx.ipcache.get(self.PREFIX) == local_id, \
            "remote withdrawal deleted the local endpoint's entry"
        assert self.PREFIX not in mesh.remote_view()
        # same outcome when b never withdraws (local always wins): the
        # conflict path must not punch the hole either
        write_peer(store, "node-b", 3, {self.PREFIX:
                                        {"labels": self.LABELS}})
        clk.t += 1.0
        mesh.sync()
        assert c.ctx.ipcache.get(self.PREFIX) == local_id

    def test_reupsert_heals_external_deletion(self, tmp_path):
        """The re-upsert branch directly: an ipcache entry deleted out
        from under a still-live claim (the departing-peer/hand-off
        composition) is restored on the next sync instead of
        short-circuiting into a permanent hole."""
        clk = _Clock()
        c = _node(tmp_path, "node-c")
        mesh = _mesh(c, tmp_path, "node-c", clk)
        write_peer(str(tmp_path / "store"), "node-b", 1,
                   {self.PREFIX: {"labels": self.LABELS}})
        mesh.sync()
        assert c.ctx.ipcache.get(self.PREFIX) is not None
        c.ctx.ipcache.delete(self.PREFIX)
        clk.t += 1.0
        mesh.sync()
        assert c.ctx.ipcache.get(self.PREFIX) is not None


class TestLagMetrics:
    """ISSUE 12 (c): per-peer lag gauges + replication-lag p99, clamped
    at zero under publisher clock skew."""

    def test_replication_lag_sampled_and_clamped(self, tmp_path):
        clk = _Clock(1000.0)
        b = _node(tmp_path, "node-b")
        mesh = _mesh(b, tmp_path, "node-b", clk)
        store = str(tmp_path / "store")
        # gen 1 published 2s ago on our clock: a real 2s lag sample
        write_peer(store, "node-a", 1,
                   {"10.1.0.5/32": {"labels": ["k8s:x=1"]}},
                   published_at=998.0)
        mesh.sync()
        # gen 2 published "in the future" (peer clock 1h ahead): clamped
        write_peer(store, "node-a", 2,
                   {"10.1.0.5/32": {"labels": ["k8s:x=1"]}},
                   published_at=clk.t + 3600.0)
        clk.t += 1.0
        mesh.sync()
        assert b.ctx.ipcache.get("10.1.0.5/32") is not None, \
            "live publisher dropped for running a fast clock"
        assert list(mesh._repl_lag) == [2.0, 0.0]
        assert mesh.replication_lag_p99() <= 2.0
        assert mesh.replication_lag_p99() >= 0.0
        st = mesh.status()
        assert st["replication_lag_p99_s"] >= 0.0
        assert st["peers"]["node-a"]["lag_s"] >= 0.0

    def test_peer_lag_gauge_tracks_generation_stall(self, tmp_path):
        clk = _Clock()
        b = _node(tmp_path, "node-b")
        mesh = _mesh(b, tmp_path, "node-b", clk, stale_after_s=1000.0)
        write_peer(str(tmp_path / "store"), "node-a", 1,
                   {"10.1.0.5/32": {"labels": ["k8s:x=1"]}})
        mesh.sync()
        assert mesh.status()["peers"]["node-a"]["lag_s"] == 0.0
        clk.t += 12.0                 # generation frozen: lag accrues
        mesh.sync()
        assert mesh.status()["peers"]["node-a"]["lag_s"] == 12.0
        assert b.metrics.gauges.get(
            'clustermesh_peer_lag_seconds{peer="node-a"}') == 12.0
        # a departed peer's gauge goes with it — a frozen last value
        # would read as a small, healthy lag for a dead peer forever
        os.unlink(str(tmp_path / "store" / "node-a.json"))
        mesh.sync()
        assert 'clustermesh_peer_lag_seconds{peer="node-a"}' \
            not in b.metrics.gauges


@pytest.mark.slow
class TestClusterSoak:
    """Satellite (CI wiring): the 2-proc partition/heal soak `make
    chaos` runs — real spawned engine processes over one store,
    with `clustermesh.peer_read` and `clustermesh.store_list` faults
    armed through partition phases, gating on convergence-after-heal and
    zero parity mismatches."""

    def test_two_proc_partition_heal_soak(self, tmp_path):
        from cilium_tpu.runtime.cluster import ClusterSupervisor

        store = str(tmp_path / "store")
        names = ["node-0", "node-1"]
        overrides = {n: {"cluster_stale_after_s": 30.0,
                         "cluster_staleness_budget_s": 5.0}
                     for n in names}
        sup = ClusterSupervisor(store, names, overrides=overrides,
                                datapath="fake")
        try:
            for i, name in enumerate(names):
                sup.add_endpoint(name,
                                 ["k8s:cluster=mesh", f"k8s:app=svc{i}"],
                                 [f"10.{i + 1}.0.10"], ep_id=1)
                sup.nodes[name].call("policy", docs=[{
                    "endpointSelector": {"matchLabels":
                                         {"app": f"svc{i}"}},
                    "ingress": [{"fromEndpoints": [
                        {"matchLabels": {"cluster": "mesh"}}],
                        "toPorts": [{"ports": [
                            {"port": "8080", "protocol": "TCP"}]}]}]}])
                sup.nodes[name].call("regen")
            sup.converge()

            flows = [{"src": "10.2.0.10", "dst": "10.1.0.10",
                      "sport": 41000, "dport": 8080, "ep_id": 1}]
            rev_flows = [{"src": "10.1.0.10", "dst": "10.2.0.10",
                          "sport": 41001, "dport": 8080, "ep_id": 1}]
            out = sup.nodes["node-0"].call("classify", flows=flows,
                                           now=100)
            assert out["allow"] == [True]
            out = sup.nodes["node-1"].call("classify", flows=rev_flows,
                                           now=100)
            assert out["allow"] == [True]

            # soak: alternate store partitions and single-file flakes on
            # node-0; the cross-boundary flow must keep serving from
            # last-good state the whole time
            for rnd in range(6):
                point = ("clustermesh.store_list" if rnd % 2 == 0
                         else "clustermesh.peer_read")
                sup.nodes["node-0"].call("arm", point=point,
                                         spec={"mode": "fail"})
                for step in range(3):
                    sup.broadcast("step")
                    now = 200 + rnd * 10 + step
                    out = sup.nodes["node-0"].call(
                        "classify", flows=flows, now=now)
                    assert out["allow"] == [True], \
                        f"failed closed during {point} round {rnd}"
                    out = sup.nodes["node-1"].call(
                        "classify", flows=rev_flows, now=now)
                    assert out["allow"] == [True], \
                        f"healthy peer failed closed during {point} " \
                        f"round {rnd}"
                sup.nodes["node-0"].call("disarm", point=point)
            rounds = sup.converge()
            assert rounds >= 1

            # post-heal: both nodes OK, zero parity mismatches at 1.0
            for name in names:
                st = sup.nodes[name].call("status")
                assert st["mesh"]["state"] == "OK"
                audit = sup.nodes[name].call("audit")
                assert audit["mismatched_rows"] == 0
                assert audit["checked_rows"] > 0
        finally:
            sup.stop_all()
