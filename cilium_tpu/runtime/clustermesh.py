"""Multi-host control-plane sync — the clustermesh analog (SURVEY.md §5
distributed backend: "DCN/host network carries control-plane sync (policy
snapshots to peer hosts, the etcd analog — keep it a simple gRPC/file
protocol)"; upstream: ``pkg/clustermesh`` syncing ipcache/identities between
clusters through etcd).

Protocol: a shared store directory (NFS/object-store mount — the DCN-visible
rendezvous; explicitly NOT a reimplementation of etcd, per SURVEY §5's
non-goal). Each node atomically publishes ``<store>/<node>.json``:

    {"node", "generation", "published_at",
     "entries": {prefix: {"labels": [...]}}}

carrying its local endpoints' IP prefixes with their LABEL SETS — labels,
not numeric identities, cross the wire, exactly like upstream clustermesh:
identity numbering is node-local, so the receiver allocates its own identity
for each remote label set (same labels ⇒ same identity ⇒ remote pods are
selectable by normal fromEndpoints/toEndpoints policy).

Each node polls peers' files (a controller with backoff — the watch analog)
and reconciles: new prefixes allocate+upsert, withdrawn prefixes release;
a peer whose file goes stale (no heartbeat within ``stale_after_s``) is
treated as failed and its state withdrawn (upstream: etcd lease expiry).

Partition / conflict contract (ISSUE 12 — the serving-tier semantics):

* **Store partition** (``clustermesh.store_list`` / ``clustermesh.peer_read``
  faults, a dead NFS mount): the node serves its LAST-GOOD remote state —
  established remote flows never fail closed because the control plane went
  away. Past ``staleness_budget_s`` without a successful store pass the mesh
  reports :data:`~cilium_tpu.utils.constants.MESH_STALE` and
  ``Engine.health()`` degrades; heal clears it on the next good pass.
* **Conflicting prefix claims** (two live peers claiming one prefix — a pod
  mid-move, a misconfigured node): resolved DETERMINISTICALLY everywhere by
  highest ``generation``, ties broken by lexicographically-first node name;
  the losing claim is not ingested anywhere (withdrawn if previously held),
  so the mesh converges to one owner instead of split-braining per node.
  Losers count into ``clustermesh_conflicts_total{prefix_winner=...}``.
  A prefix owned LOCALLY (one of this node's own endpoints) always beats
  any remote claim.
* **Lagging peer**: ``clustermesh_peer_lag_seconds{peer=...}`` gauges the
  time since a peer's generation last progressed (judged on OUR clock —
  skew-immune), and every observed generation step samples
  ``clustermesh_replication_lag_seconds`` (publish→ingest delay, clamped at
  zero — a peer whose wall clock runs ahead must not read negative);
  :meth:`status` surfaces the windowed p99.
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
import time
from collections import deque
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Tuple

import numpy as np

from cilium_tpu.model.labels import Labels
from cilium_tpu.runtime.faults import FAULTS, FaultInjected
from cilium_tpu.utils import constants as C

if TYPE_CHECKING:
    from cilium_tpu.runtime.engine import Engine

log = logging.getLogger("cilium_tpu.clustermesh")

FORMAT_VERSION = 1

#: replication-lag samples retained for the windowed p99 in :meth:`status`
LAG_WINDOW = 512


class ClusterMesh:
    """Publishes this node's endpoint map and ingests peers' into the local
    identity allocator + ipcache. Owned by the Engine; driven by the
    ``clustermesh-sync`` controller."""

    def __init__(self, engine: "Engine", store_dir: str, node_name: str,
                 stale_after_s: float = 60.0,
                 staleness_budget_s: float = 15.0,
                 clock: Optional[Callable[[], float]] = None):
        if not node_name or "/" in node_name or node_name.startswith("."):
            raise ValueError(f"bad node name {node_name!r}")
        self.engine = engine
        self.store_dir = store_dir
        self.node_name = node_name
        self.stale_after_s = stale_after_s
        self.staleness_budget_s = staleness_budget_s
        # test/chaos hooks: ``clock`` replaces the wall clock for EVERY
        # mesh judgment (leases, staleness, publish stamps);
        # ``publish_skew_s`` skews only the published_at stamp — the
        # cross-node wall-clock-skew drill (leases stay on the local
        # clock, which is the design's whole skew defense)
        self._clock = clock
        self.publish_skew_s = 0.0
        self._generation = 0
        # peer → {prefix: (identity, labels_key)} we ingested (for release)
        self._ingested: Dict[str, Dict[str, object]] = {}
        # peer → (doc, lease_ts): a transiently unreadable file (NFS
        # hiccup) must NOT read as departure — the lease (stale_after_s),
        # not one failed read, decides withdrawal. lease_ts is OUR clock,
        # advanced only when the peer's generation changes: judging
        # staleness from the peer-written published_at would withdraw a
        # live peer whose clock is skewed behind ours (etcd leases are
        # likewise granted on the server's clock, not the client's).
        self._last_good: Dict[str, Tuple[Dict, float]] = {}
        # prefix → (winner_node, losers): currently-observed conflicting
        # claims, so each distinct conflict counts once, not once per sync
        self._conflicts: Dict[str, Tuple[str, Tuple[str, ...]]] = {}
        # node → generation its lease EXPIRED at: a crashed peer's file
        # stays in the store, and without this memory the next sync would
        # re-cache it as "fresh" (lease renewed) and resurrect the dead
        # peer every other pass — only real generation PROGRESS (the node
        # actually restarting/publishing) clears the tombstone
        self._expired: Dict[str, object] = {}
        self._store_ok = True          # last listing attempt succeeded
        self._last_pass_ok: float = self._now()   # last good store pass
        self._repl_lag = deque(maxlen=LAG_WINDOW)  # publish→ingest seconds
        os.makedirs(store_dir, exist_ok=True)
        self._sweep_tmp_litter()

    def _now(self) -> float:
        # late-bound so tests monkeypatching time.time still work
        return self._clock() if self._clock is not None else time.time()

    def _drop_peer_gauge(self, node: str) -> None:
        # a departed peer's frozen lag gauge would keep exporting a small,
        # healthy-looking value forever — remove it with the peer
        self.engine.metrics.drop_gauge(
            f'clustermesh_peer_lag_seconds{{peer="{node}"}}')

    # -- publish ------------------------------------------------------------
    def _own_entries(self) -> Dict[str, Dict]:
        entries: Dict[str, Dict] = {}
        for ep in self.engine.endpoints.values():
            labels = list(ep.labels.to_strings())
            for ip in ep.ips:
                prefix = f"{ip}/128" if ":" in ip else f"{ip}/32"
                entries[prefix] = {"labels": labels}
        return entries

    def _sweep_tmp_litter(self) -> None:
        """Startup hygiene: a writer that crashed between ``mkstemp`` and
        ``os.replace`` leaves a ``.``-prefixed tmp file behind forever (the
        store is append-only otherwise). Sweep OUR OWN litter
        unconditionally (we are this node's only writer) and other nodes'
        only once it is old enough that no live publish can still be
        racing its rename window."""
        try:
            names = os.listdir(self.store_dir)
        except OSError:
            return                     # store unreachable: sync() will say so
        now = time.time()              # mtimes are real fs time, not _clock
        own_prefix = f".{self.node_name}-"
        swept = 0
        for name in names:
            if not name.startswith("."):
                continue
            path = os.path.join(self.store_dir, name)
            try:
                old = now - os.path.getmtime(path) > max(
                    self.stale_after_s, 60.0)
                if name.startswith(own_prefix) or old:
                    os.unlink(path)
                    swept += 1
            except OSError:
                continue               # already gone / unreadable: not ours
        if swept:
            log.info("clustermesh: swept %d stale tmp file(s) from %s",
                     swept, self.store_dir)
            self.engine.metrics.inc_counter(
                "clustermesh_tmp_swept_total", swept)

    def publish(self) -> None:
        """Write this node's state atomically (tmp + rename — readers never
        see a torn file; the single-file-per-writer layout makes the store
        safely multi-writer without locks). A failed write never leaves
        tmp litter behind."""
        self._generation += 1
        doc = {
            "format_version": FORMAT_VERSION,
            "node": self.node_name,
            "generation": self._generation,
            "published_at": self._now() + self.publish_skew_s,
            "entries": self._own_entries(),
        }
        fd, tmp = tempfile.mkstemp(dir=self.store_dir,
                                   prefix=f".{self.node_name}-")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, os.path.join(self.store_dir,
                                         f"{self.node_name}.json"))
        except BaseException:
            # json.dump / replace failed: the doc never landed — remove the
            # tmp so a crash-looping publisher cannot fill the store with
            # litter (the startup sweep is the backstop, not the plan)
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # -- ingest -------------------------------------------------------------
    def _read_peers(self) -> Dict[str, Dict]:
        peers: Dict[str, Dict] = {}
        now = self._now()
        listing_ok = True
        try:
            FAULTS.fire("clustermesh.store_list")
            names = os.listdir(self.store_dir)
        except (OSError, FaultInjected) as e:
            # whole store unreachable (partition): hold last-good state —
            # established remote flows must keep classifying; status()
            # reports MESH_STALE once the staleness budget is spent
            log.warning("clustermesh: store unreachable (%s); holding "
                        "last-known peer state", e)
            names = []
            listing_ok = False
        seen = set()
        for name in names:
            if not name.endswith(".json") or name.startswith("."):
                continue
            node = name[: -len(".json")]
            if node == self.node_name:
                continue
            seen.add(node)
            path = os.path.join(self.store_dir, name)
            try:
                FAULTS.fire("clustermesh.peer_read")
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, json.JSONDecodeError, FaultInjected) as e:
                log.warning("clustermesh: unreadable peer file %s: %s "
                            "(holding last-known state)", name, e)
                doc = None
            if doc is not None and doc.get("node") != node:
                # the doc's self-declared node MUST match the filename stem:
                # a file claiming to be another node would otherwise be
                # ingested under the wrong peer's ledger — and withdrawn
                # wholesale on the next sync as a spoofed withdrawal
                log.warning("clustermesh: peer file %s claims node %r — "
                            "spoofed or misplaced, ignored (holding "
                            "last-known state)", name, doc.get("node"))
                self.engine.metrics.inc_counter(
                    "clustermesh_spoofed_peer_files_total")
                doc = None
            if doc is not None:
                if doc.get("format_version") != FORMAT_VERSION:
                    log.warning("clustermesh: peer %s speaks format %r, "
                                "skipped", node, doc.get("format_version"))
                    # a real doc in an unknown format supersedes anything
                    # cached — keeping serving the old doc would pin stale
                    # identities for the lease duration
                    self._last_good.pop(node, None)
                    self._drop_peer_gauge(node)
                    continue
                if node in self._expired \
                        and doc.get("generation") == self._expired[node]:
                    continue           # tombstoned: the file is a dead
                                       # peer's last word, not a heartbeat
                self._expired.pop(node, None)
                cached = self._last_good.get(node)
                if (cached is None
                        or doc.get("generation") != cached[0].get("generation")):
                    ts = now               # progress observed: renew lease
                    # replication lag: publish→ingest delay for this
                    # generation step. Clamped at zero — a peer whose wall
                    # clock runs AHEAD of ours must not produce negative
                    # samples (leases are already skew-immune; this metric
                    # is best-effort wall truth)
                    lag = max(0.0, now - float(doc.get("published_at", now)))
                    self._repl_lag.append(lag)
                    self.engine.metrics.histogram(
                        "clustermesh_replication_lag_seconds").observe(lag)
                else:
                    ts = cached[1]         # unchanged generation: lease ages
                self._last_good[node] = (doc, ts)
        for node, (doc, ts) in list(self._last_good.items()):
            if listing_ok and node not in seen:
                # file explicitly gone from a healthy store: the peer's
                # clean withdraw() — immediate removal (etcd delete analog)
                del self._last_good[node]
                self._expired.pop(node, None)
                self._drop_peer_gauge(node)
                continue
            if listing_ok and now - ts > self.stale_after_s:
                # expired lease: treated as withdrawn — and tombstoned at
                # this generation, so the lingering file of a crashed peer
                # cannot resurrect it (only generation progress can).
                # Expiry requires a HEALTHY listing: while the store is
                # partitioned no heartbeat is observable at all, and
                # expiring peers then would turn a control-plane outage
                # into a data-plane one (established remote flows failing
                # closed — the exact thing the partition contract forbids).
                # After heal, a peer whose generation did not progress
                # expires on the first good pass.
                self._expired[node] = doc.get("generation")
                del self._last_good[node]
                self._drop_peer_gauge(node)
                continue
            peers[node] = doc
            self.engine.metrics.set_gauge(
                f'clustermesh_peer_lag_seconds{{peer="{node}"}}',
                round(max(0.0, now - ts), 3))
        if listing_ok:
            self._last_pass_ok = now
        self._store_ok = listing_ok
        self.engine.metrics.set_gauge("clustermesh_store_ok",
                                      1 if listing_ok else 0)
        return peers

    # -- conflict resolution -------------------------------------------------
    def _resolve_claims(self, peers: Dict[str, Dict]
                        ) -> Dict[str, Dict[str, Dict]]:
        """Peers' raw docs → per-peer EFFECTIVE entry maps with conflicting
        prefix claims resolved deterministically: highest generation wins,
        ties broken by lexicographically-first node name — the same answer
        on every node of the mesh, so a losing claim is withdrawn
        everywhere rather than split-brained per node. Prefixes this node
        itself publishes (live local endpoints) always beat remote claims.
        New conflicts count into
        ``clustermesh_conflicts_total{prefix_winner=...}`` once per
        distinct (prefix, winner, losers) observation."""
        local = set(self._own_entries())
        effective: Dict[str, Dict[str, Dict]] = {n: {} for n in peers}
        conflicts_now: Dict[str, Tuple[str, Tuple[str, ...]]] = {}
        claims: Dict[str, List[Tuple[int, str, Dict]]] = {}
        for node, doc in peers.items():
            for prefix, entry in doc.get("entries", {}).items():
                claims.setdefault(prefix, []).append(
                    (int(doc.get("generation", 0)), node, entry))
        for prefix, cs in claims.items():
            if prefix in local:
                # local endpoints own their prefixes unconditionally: a
                # remote claim is a conflict we lose nothing to
                conflicts_now[prefix] = (
                    self.node_name, tuple(sorted(n for _g, n, _e in cs)))
                continue
            if len(cs) == 1:
                _g, node, entry = cs[0]
                effective[node][prefix] = entry
                continue
            cs_sorted = sorted(cs, key=lambda c: (-c[0], c[1]))
            _g, winner, entry = cs_sorted[0]
            effective[winner][prefix] = entry
            conflicts_now[prefix] = (
                winner, tuple(sorted(n for _g2, n, _e2 in cs_sorted[1:])))
        for prefix, (winner, losers) in conflicts_now.items():
            if self._conflicts.get(prefix) == (winner, losers):
                continue               # already counted this exact conflict
            log.warning("clustermesh: conflicting claims on %s: winner=%s "
                        "losers=%s (highest-generation-then-node-name)",
                        prefix, winner, ",".join(losers))
            self.engine.metrics.inc_counter(
                f'clustermesh_conflicts_total{{prefix_winner="{winner}"}}',
                len(losers))
        self._conflicts = conflicts_now
        return effective

    def sync(self) -> Tuple[int, int]:
        """One reconcile pass: ingest peers, withdraw the departed.
        Returns (n_added, n_removed) ipcache entries."""
        ctx = self.engine.ctx
        peers = self._read_peers()
        effective = self._resolve_claims(peers)
        # prefix → claiming node across ALL effective peers, PLUS this
        # node's own endpoints: a withdrawal must not punch an ipcache
        # hole under a prefix another peer still (or newly) claims — the
        # hand-off case — and a remote→LOCAL hand-off (the pod moved to
        # us: _resolve_claims strips local prefixes from every peer's
        # effective map, so without the local set the old remote mapping's
        # withdrawal would delete the live local endpoint's entry)
        claimed = {p for entries in effective.values() for p in entries}
        claimed |= set(self._own_entries())
        added = removed = 0
        deferred_release = []
        with self.engine._lock:            # noqa: SLF001 — same lifecycle
            # withdrawals: peers gone/stale, entries they dropped, or
            # claims they just LOST to a higher-generation peer (the
            # conflict loser is withdrawn everywhere, not split-brained)
            for node in list(self._ingested):
                peer_entries = effective.get(node, {})
                held = self._ingested[node]
                for prefix in list(held):
                    new = peer_entries.get(prefix)
                    old_ident, old_labels = held[prefix]
                    if new is not None \
                            and tuple(sorted(new["labels"])) == old_labels:
                        continue
                    # the prefix no longer belongs to this peer's pod:
                    # remove the mapping unless another live claim covers
                    # it (a stale IP mapping would grant the old pod's
                    # permissions to whoever reuses the address). The
                    # identity release is DEFERRED past the additions pass
                    # so a hand-off (same labels, new peer) re-refs the
                    # same identity instead of minting a new number.
                    deferred_release.append(old_ident)
                    if prefix not in claimed:
                        ctx.ipcache.delete(prefix)
                    del held[prefix]
                    removed += 1
                if not held:
                    del self._ingested[node]
            # additions/updates
            for node in sorted(effective):
                held = self._ingested.setdefault(node, {})
                for prefix, entry in effective[node].items():
                    key = tuple(sorted(entry["labels"]))
                    if prefix in held:
                        # unchanged claim (label mismatches were removed
                        # above) — but on a prefix hand-off (pod moved
                        # between peers) the departing peer's withdrawal
                        # pass just deleted the ipcache entry out from
                        # under our still-live claim. Re-upsert when the
                        # entry is missing (upsert is idempotent) instead
                        # of short-circuiting into a permanent hole.
                        ident, _key = held[prefix]
                        if ctx.ipcache.get(prefix) is None:
                            ctx.ipcache.upsert(prefix, ident.id)
                            added += 1
                        continue
                    ident = ctx.allocator.allocate(Labels.parse(
                        list(entry["labels"])))
                    ctx.ipcache.upsert(prefix, ident.id)
                    held[prefix] = (ident, key)
                    added += 1
                if not self._ingested[node]:
                    del self._ingested[node]
            for ident in deferred_release:
                ctx.allocator.release(ident)
        if added or removed:
            self.engine.metrics.set_gauge(
                "clustermesh_remote_entries",
                sum(len(h) for h in self._ingested.values()))
        self.engine.metrics.set_gauge("clustermesh_peers",
                                      len(self._ingested))
        self.engine.metrics.set_gauge(
            "clustermesh_mesh_stale", 1 if self.is_stale() else 0)
        return added, removed

    def step(self) -> None:
        """One controller tick: publish our state, ingest everyone else's."""
        self.publish()
        self.sync()

    # -- introspection -------------------------------------------------------
    def is_stale(self) -> bool:
        """True once the staleness budget is spent without a good store
        pass — the MESH_STALE health detail. Last-good remote state keeps
        serving regardless (never fail closed on established remote
        flows); stale only says the view may be behind the mesh."""
        return self._now() - self._last_pass_ok > self.staleness_budget_s

    def replication_lag_p99(self) -> float:
        """Windowed p99 of observed publish→ingest replication lag."""
        # list(deque) is a single C-level copy (GIL-atomic) — safe against
        # the sync thread appending concurrently
        samples = list(self._repl_lag)
        if not samples:
            return 0.0
        return float(np.percentile(np.asarray(samples), 99))

    def status(self) -> Dict:
        """The mesh health/lag surface (folded into ``Engine.health()`` and
        ``/v1/status``): per-peer generation + lag, store reachability,
        staleness verdict, conflict map, replication-lag p99.

        Called from the API/health threads while the ``clustermesh-sync``
        controller mutates peer state — every shared dict is read through
        one C-level (GIL-atomic) copy, never iterated live: individual
        values are immutable once stored (docs are never mutated in place,
        ``_conflicts`` is replaced wholesale), so the copy is a consistent
        snapshot without taking the engine lock on a path that must stay
        responsive while the store hangs."""
        now = self._now()
        stale = self.is_stale()
        peers = {}
        for node, (doc, ts) in dict(self._last_good).items():
            peers[node] = {
                "generation": int(doc.get("generation", 0)),
                "entries": len(doc.get("entries", {})),
                "lag_s": round(max(0.0, now - ts), 3),
            }
        conflicts = self._conflicts
        return {
            "state": C.MESH_STALE if stale else C.HEALTH_OK,
            "node": self.node_name,
            "generation": self._generation,
            "store_ok": self._store_ok,
            "last_good_pass_age_s": round(max(0.0, now - self._last_pass_ok),
                                          3),
            "staleness_budget_s": self.staleness_budget_s,
            "peers": peers,
            "remote_entries": sum(len(h)
                                  for h in list(self._ingested.values())),
            "conflicts": {p: {"winner": w, "losers": list(ls)}
                          for p, (w, ls) in sorted(conflicts.items())},
            "replication_lag_p99_s": round(self.replication_lag_p99(), 6),
        }

    def remote_view(self) -> Dict[str, Dict]:
        """The ingested remote world, keyed by prefix — identity numbers
        are node-local, so cross-node convergence is judged on (peer,
        labels), which this view carries (the tests' convergence
        probe). Reads GIL-atomic copies, same as :meth:`status`."""
        out: Dict[str, Dict] = {}
        for node, held in dict(self._ingested).items():
            for prefix, (ident, labels_key) in dict(held).items():
                out[prefix] = {"peer": node, "labels": list(labels_key),
                               "identity": ident.id}
        return out

    def withdraw(self) -> None:
        """Remove this node's published state (clean shutdown). A failed
        unlink is LOUD: a node that cannot withdraw looks exactly like one
        that did to every peer — until the lease expires — so the failure
        is logged and counted instead of silently swallowed."""
        # departed-subject gauge sweep (ISSUE 13): detaching the mesh
        # deregisters every peer this node was tracking — their lag gauges
        # must go with them (expiry/tombstone paths already sweep their own
        # peer; a withdraw mid-tracking would otherwise pin every live
        # peer's last lag forever)
        for node in list(self._last_good) + list(self._ingested):
            self._drop_peer_gauge(node)
        path = os.path.join(self.store_dir, f"{self.node_name}.json")
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass                       # never published / already withdrawn
        except OSError as e:
            log.warning(
                "clustermesh: withdraw failed for %s: %s — peers will keep "
                "serving this node's last claims for up to the full lease "
                "(%.0fs)", path, e, self.stale_after_s)
            self.engine.metrics.inc_counter(
                "clustermesh_withdraw_errors_total")
