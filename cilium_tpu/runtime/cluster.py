"""Multi-host serving harness: N engine processes over one clustermesh
store (ISSUE 12 / ROADMAP item 3 — the "millions of users" horizontal
axis). Used by the cluster chaos tests (tests/test_clustermesh.py).

Each node is a real OS process (``multiprocessing`` *spawn* — a fresh
interpreter per node, so jax state, the FAULTS singleton, identity
numbering and the engine lock are all genuinely per-host) running a full
Engine with its own datapath, mesh, auditor and flowlog. The supervisor
drives them over pipes with a tiny command protocol; faults are armed
*inside* a node (each process owns its own injector), which is exactly the
partition topology a real deployment has — one node's dead NFS mount is
invisible to the others.

The harness is deterministic by construction: nothing ticks on wall-clock
controllers — the driver commands every ``mesh.step()`` and regeneration
explicitly, so a chaos sequence (partition N syncs, kill a peer, conflict
two claims) replays identically.

Cluster nodes are CPU processes. A chip belongs to one process at a time,
so N engine processes on one host cannot each hold it; every node pins
``JAX_PLATFORMS=cpu`` before it imports jax, whatever the machine has and
whatever the parent did. What this harness establishes is convergence,
parity and failure handling — counts, never device rates."""

from __future__ import annotations

import multiprocessing as mp
import os
import time
import traceback
from typing import Dict, List, Optional, Tuple


# --------------------------------------------------------------------------- #
# the node worker (runs in a spawned child process)
# --------------------------------------------------------------------------- #
def _flow_records(flows: List[Dict]):
    from cilium_tpu.utils import constants as C
    from cilium_tpu.utils.ip import parse_addr
    from oracle import PacketRecord
    recs = []
    for f in flows:
        s16, _ = parse_addr(f["src"])
        d16, v6 = parse_addr(f["dst"])
        recs.append(PacketRecord(
            s16, d16, int(f.get("sport", 40000)), int(f["dport"]),
            int(f.get("proto", C.PROTO_TCP)),
            int(f.get("flags", C.TCP_SYN)), v6, int(f["ep_id"]),
            C.DIR_INGRESS if f.get("direction", "ingress") == "ingress"
            else C.DIR_EGRESS))
    return recs


def _node_worker(conn, node_name: str, store_dir: str,
                 overrides: Optional[Dict], datapath: str) -> None:
    """One mesh node: build the engine, answer supervisor commands until
    ``stop`` (clean shutdown + withdraw) or ``exit_dirty`` (simulated
    crash: the published file stays behind for the lease to expire)."""
    os.environ["JAX_PLATFORMS"] = "cpu"     # see the module docstring
    import numpy as np

    from cilium_tpu.kernels.records import batch_from_records
    from cilium_tpu.runtime.config import DaemonConfig
    from cilium_tpu.runtime.engine import Engine
    from cilium_tpu.runtime.faults import FAULTS

    kw = dict(ct_capacity=1 << 13, auto_regen=False,
              cluster_store=store_dir, node_name=node_name,
              cluster_stale_after_s=60.0, cluster_staleness_budget_s=15.0,
              audit_enabled=True, audit_sample_rate=1.0,
              audit_pool_batches=64, flowlog_mode="all")
    kw.update(overrides or {})
    cfg = DaemonConfig(**kw)
    if datapath == "fake":
        from cilium_tpu.runtime.datapath import FakeDatapath
        eng = Engine(cfg, datapath=FakeDatapath(cfg))
    else:
        eng = Engine(cfg)              # JITDatapath
    eng.auditor.configure(sample_rate=1.0)
    mesh = eng.attach_mesh()

    def classify(flows: List[Dict], now: int) -> Dict:
        batch = batch_from_records(_flow_records(flows),
                                   eng.active.snapshot.ep_slot_of)
        out = eng.classify(batch, now=now)
        return {k: np.asarray(out[k]).tolist()
                for k in ("allow", "reason", "remote_identity",
                          "matched_rule")}

    def drain_audit() -> Dict:
        for _ in range(200):
            step = eng.audit_step(budget=128)
            if not step or (not step.get("replayed")
                            and not step.get("pending")):
                break
        return eng.auditor.stats()

    running = True
    while running:
        try:
            cmd, args = conn.recv()
        except (EOFError, OSError):
            break
        try:
            if cmd == "ping":
                res = {"node": node_name, "pid": os.getpid()}
            elif cmd == "add_ep":
                ep = eng.add_endpoint(args["labels"],
                                      ips=tuple(args.get("ips", ())),
                                      ep_id=args.get("ep_id"))
                res = {"ep_id": ep.ep_id, "identity": ep.identity_id}
            elif cmd == "remove_ep":
                res = {"removed": eng.remove_endpoint(args["ep_id"])}
            elif cmd == "policy":
                res = {"revision": eng.apply_policy(args["docs"])}
            elif cmd == "step":
                # publish + ingest + regenerate: one full control-plane
                # tick, reporting whether the regen took the delta path
                added = removed = 0
                for _ in range(int(args.get("n", 1))):
                    mesh.publish()
                    a, r = mesh.sync()
                    added += a
                    removed += r
                eng.regenerate()
                res = {"added": added, "removed": removed,
                       "regen_incremental": eng.metrics.counters.get(
                           "regen_incremental_total", 0),
                       "regen_full": eng.metrics.counters.get(
                           "regen_full_total", 0)}
            elif cmd == "regen":
                # explicit regeneration (warm/seed the incremental
                # compiler BEFORE remote entries arrive, so a later step's
                # ingest provably rides the delta-patch path)
                compiled = eng.regenerate(force=bool(args.get("force")))
                res = {"revision": compiled.revision}
            elif cmd == "classify":
                res = classify(args["flows"], int(args.get("now", 1000)))
            elif cmd == "serve":
                flows = args["flows"]
                batches = int(args.get("batches", 20))
                now = int(args.get("now", 1000))
                classify(flows, now - 1)   # warmup: XLA compile is not fps
                allowed = rows = 0
                t0 = time.monotonic()
                for i in range(batches):
                    out = classify(flows, now + i)
                    allowed += sum(out["allow"])
                    rows += len(flows)
                dt = max(time.monotonic() - t0, 1e-9)
                res = {"rows": rows, "allowed": allowed,
                       "elapsed_s": dt, "fps": rows / dt}
            elif cmd == "audit":
                res = drain_audit()
            elif cmd == "status":
                res = {"health": eng.health(),
                       "mesh": mesh.status(),
                       "remote_view": mesh.remote_view(),
                       "counters": {
                           k: v for k, v in eng.metrics.counters.items()
                           if k.startswith(("regen_", "clustermesh_"))},
                       "audit": eng.auditor.stats()}
            elif cmd == "arm":
                FAULTS.arm(args["point"], **args.get("spec", {}))
                res = {"armed": args["point"]}
            elif cmd == "disarm":
                FAULTS.disarm(args.get("point"))
                res = {"disarmed": args.get("point")}
            elif cmd == "skew":
                # cross-node wall-clock skew drill: only the published_at
                # stamp moves — leases stay on each node's local clock
                mesh.publish_skew_s = float(args["seconds"])
                res = {"publish_skew_s": mesh.publish_skew_s}
            elif cmd == "flush":
                eng.flush_observability()
                res = {"flushed": True}
            elif cmd == "stop":
                eng.stop()             # clean: withdraws the node file
                res = {"stopped": True}
                running = False
            elif cmd == "exit_dirty":
                res = {"exited": True}  # crash: no withdraw, file stays
                running = False
            else:
                raise ValueError(f"unknown command {cmd!r}")
            conn.send(("ok", res))
        except Exception:
            conn.send(("err", traceback.format_exc()))
    conn.close()


# --------------------------------------------------------------------------- #
# supervisor side
# --------------------------------------------------------------------------- #
class ClusterNode:
    """Handle on one spawned node process."""

    def __init__(self, name: str, store_dir: str,
                 overrides: Optional[Dict] = None, datapath: str = "jit",
                 ctx: Optional[mp.context.BaseContext] = None):
        self.name = name
        self.store_dir = store_dir
        self.overrides = dict(overrides or {})
        self.datapath = datapath
        ctx = ctx or mp.get_context("spawn")
        self._conn, child = ctx.Pipe()
        self.proc = ctx.Process(
            target=_node_worker,
            args=(child, name, store_dir, self.overrides, datapath),
            daemon=True, name=f"cluster-node-{name}")
        self.proc.start()
        child.close()

    def call(self, cmd: str, timeout: float = 300.0, **args):
        """One command round-trip; raises on worker error or timeout (a
        dead/hung node must fail the driver loudly, not hang it)."""
        self._conn.send((cmd, args))
        if not self._conn.poll(timeout):
            raise TimeoutError(
                f"node {self.name}: no reply to {cmd!r} in {timeout}s")
        status, payload = self._conn.recv()
        if status != "ok":
            raise RuntimeError(f"node {self.name} {cmd!r} failed:\n{payload}")
        return payload

    @property
    def alive(self) -> bool:
        return self.proc.is_alive()

    def kill(self) -> None:
        """Hard-kill (peer-crash drill): no withdraw, the published file
        stays until the peers' leases expire."""
        self.proc.terminate()
        self.proc.join(timeout=10)
        self._conn.close()

    def stop(self, timeout: float = 60.0) -> None:
        try:
            self.call("stop", timeout=timeout)
        except Exception:   # noqa: BLE001 — best-effort stop RPC; terminate() below is the backstop
            pass
        self.proc.join(timeout=timeout)
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(timeout=10)
        self._conn.close()


class ClusterSupervisor:
    """Owns N node processes + the ledger of what each node published, so
    convergence ("every node's remote view equals the union of its peers'
    local entries") is checkable without trusting the thing under test."""

    def __init__(self, store_dir: str, node_names: List[str],
                 overrides: Optional[Dict[str, Dict]] = None,
                 datapath: str = "jit"):
        self.store_dir = store_dir
        self.datapath = datapath
        self.overrides = overrides or {}
        self._ctx = mp.get_context("spawn")
        self.nodes: Dict[str, ClusterNode] = {}
        # node → {prefix: sorted labels}: the supervisor's own truth of
        # what each node publishes (fed by add_endpoint/remove_endpoint)
        self.ledger: Dict[str, Dict[str, Tuple[str, ...]]] = {
            n: {} for n in node_names}
        for n in node_names:
            self.nodes[n] = self._spawn(n)

    def _spawn(self, name: str) -> ClusterNode:
        return ClusterNode(name, self.store_dir,
                           overrides=self.overrides.get(name),
                           datapath=self.datapath, ctx=self._ctx)

    # -- cluster-wide ops ---------------------------------------------------
    def add_endpoint(self, node: str, labels: List[str], ips: List[str],
                     ep_id: Optional[int] = None) -> Dict:
        res = self.nodes[node].call("add_ep", labels=labels, ips=ips,
                                    ep_id=ep_id)
        for ip in ips:
            prefix = f"{ip}/128" if ":" in ip else f"{ip}/32"
            self.ledger[node][prefix] = tuple(sorted(labels))
        return res

    def remove_endpoint(self, node: str, ep_id: int,
                        ips: List[str] = ()) -> Dict:
        res = self.nodes[node].call("remove_ep", ep_id=ep_id)
        for ip in ips:
            prefix = f"{ip}/128" if ":" in ip else f"{ip}/32"
            self.ledger[node].pop(prefix, None)
        return res

    def broadcast(self, cmd: str, timeout: float = 300.0,
                  only: Optional[List[str]] = None, **args) -> Dict:
        out = {}
        for name, node in self.nodes.items():
            if only is not None and name not in only:
                continue
            if not node.alive:
                continue
            out[name] = node.call(cmd, timeout=timeout, **args)
        return out

    def expected_remote(self, node: str,
                        exclude: Tuple[str, ...] = ()) -> Dict:
        """What ``node`` should see once converged: the union of every
        OTHER live node's ledger (conflicting claims excluded — they are
        judged separately, by the deterministic-winner rule)."""
        want: Dict[str, Tuple[str, ...]] = {}
        for peer, entries in self.ledger.items():
            if peer == node or peer in exclude:
                continue
            if not self.nodes[peer].alive:
                continue
            want.update(entries)
        return want

    def views(self, only: Optional[List[str]] = None) -> Dict[str, Dict]:
        """node → {prefix: labels tuple} as actually ingested."""
        out = {}
        for name, res in self.broadcast("status", only=only).items():
            out[name] = {p: tuple(v["labels"])
                         for p, v in res["remote_view"].items()}
        return out

    def converge(self, max_rounds: int = 12,
                 exclude: Tuple[str, ...] = ()) -> int:
        """Step every live node until each one's remote view matches the
        supervisor's ledger (or the round budget runs out). Returns the
        number of rounds taken; raises on non-convergence."""
        live = [n for n, node in self.nodes.items()
                if node.alive and n not in exclude]
        for rnd in range(1, max_rounds + 1):
            self.broadcast("step", only=live)
            views = self.views(only=live)
            if all(views[n] == self.expected_remote(n, exclude=exclude)
                   for n in live):
                return rnd
        raise AssertionError(
            f"mesh did not converge in {max_rounds} rounds: "
            f"{ {n: sorted(views[n]) for n in live} } vs expected "
            f"{ {n: sorted(self.expected_remote(n, exclude=exclude)) for n in live} }")

    def restart(self, name: str) -> ClusterNode:
        """Replace a (killed) node with a fresh process under the same
        node name — the peer-restart drill. The ledger keeps the node's
        entries only if the caller re-adds its endpoints."""
        old = self.nodes.get(name)
        if old is not None and old.alive:
            old.kill()
        self.ledger[name] = {}         # fresh process = empty endpoint set
        self.nodes[name] = self._spawn(name)
        return self.nodes[name]

    def stop_all(self) -> None:
        for node in self.nodes.values():
            if node.alive:
                node.stop()
            else:
                try:
                    node._conn.close()
                except Exception:   # noqa: BLE001 — closing a pipe to a dead node; nothing to account
                    pass
