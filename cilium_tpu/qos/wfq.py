"""Per-tenant weighted-fair admission queue (deficit round robin).

Drop-in replacement for the scheduler's single FIFO ``deque``: it keeps
one FIFO deque *per tenant* and serves them in deficit-round-robin order,
with each tenant's per-round credit proportional to its configured
weight. The public surface is deque-compatible (``append`` / ``popleft``
/ ``remove`` / ``clear`` / ``len`` / iteration) so every scheduler sweep
path — wedged-work collection, victim removal, drain — works unchanged;
only the *order* ``popleft`` returns differs, and with a single tenant
even that collapses to FIFO (credit is always sufficient, so pops come
straight off the one deque in arrival order).

All methods are called under the pipeline lock; this class does no
locking of its own (the :class:`~cilium_tpu.qos.tenancy.TenantTable` it
consults is a leaf lock).
"""

from __future__ import annotations

import math
from collections import deque
from typing import Dict, Iterator, Optional, Tuple

from cilium_tpu.qos.tenancy import TENANT_DEFAULT, WEIGHT_FLOOR_ROWS

#: guard against a zero/negative weight making a tenant's queue-share
#: denominator vanish — weights below this are floored for share math.
_MIN_WEIGHT = 1e-6


class TenantQueues:
    """DRR scheduler state: per-tenant FIFOs + an active-tenant ring.

    ``quantum_rows`` is the per-round credit a weight-1.0 tenant earns
    (the pipeline passes its max bucket, so any single batch is
    affordable within one round at weight >= 1). A zero-weight tenant
    still earns :data:`WEIGHT_FLOOR_ROWS` per round — service is slow
    but guaranteed (the starvation floor).
    """

    def __init__(self, table, quantum_rows: int = 512,
                 lane_rows: int = 0):
        self.table = table
        self._qrows = max(1, int(quantum_rows))
        #: latency-lane bypass threshold (the pipeline's lane bucket):
        #: a lane tenant whose HEAD submission is at most this many rows
        #: is served ahead of the DRR ring. 0 disables the bypass.
        self.lane_rows = max(0, int(lane_rows))
        self._queues: Dict[int, deque] = {}
        self._order: deque = deque()          # tenants with queued work
        self._deficit: Dict[int, float] = {}
        # tenants already granted their quantum this visit — DRR grants
        # ONCE per turn, serves while the deficit lasts, then rotates;
        # topping up on every pop would let the head tenant starve the
        # ring (it could always afford its own next batch)
        self._granted: set = set()
        # rows served via the lane bypass but not yet paid for by a ring
        # quantum — the starvation bound: bypass is allowed only while
        # the debt stays under one quantum, and ring grants pay the debt
        # before banking deficit
        self._lane_debt: Dict[int, float] = {}
        self._len = 0
        # lifetime service accounting (rows/batches the DRR actually
        # handed to the dispatcher) — tests/test_qos.py judges the DRR's
        # shares on these, so they must reflect pop order, not arrivals
        self.admitted_rows: Dict[int, int] = {}
        self.admitted_batches: Dict[int, int] = {}

    # -- deque-compatible surface -------------------------------------------
    def __len__(self) -> int:
        return self._len

    def __bool__(self) -> bool:
        return self._len > 0

    def __iter__(self) -> Iterator:
        for tid in list(self._order):
            q = self._queues.get(tid)
            if q:
                yield from q

    def append(self, sub) -> None:
        tid = getattr(sub, "tenant", TENANT_DEFAULT)
        q = self._queues.get(tid)
        if q is None:
            q = deque()
            self._queues[tid] = q
            self._order.append(tid)
            self._deficit[tid] = 0.0
        q.append(sub)
        self._len += 1

    def popleft(self):
        """DRR dequeue with a latency-lane fast path.

        Lane bypass first: a lane tenant whose head submission fits the
        lane bucket is served ahead of the ring — FIFO within the tenant
        holds (it is still that tenant's own head), and the rows are
        charged as lane debt so sustained lane traffic cannot starve the
        ring: once a tenant owes a full quantum it falls back to its
        ring turn, and ring grants pay the debt before banking deficit.

        Otherwise standard DRR: serve the head of the active ring while
        its deficit covers the head batch's row cost; top up one quantum
        and rotate when spent. Guaranteed to terminate in O(tenants) loop
        iterations — a full rotation in which nobody could afford its
        head fast-forwards the number of empty rounds the cheapest
        unblock needs in one step, instead of rotating once per
        :data:`WEIGHT_FLOOR_ROWS` row (a zero-weight tenant's big batch
        would otherwise cost hundreds of ring spins under the pipeline
        lock)."""
        if not self._len:
            raise IndexError("pop from an empty TenantQueues")
        if self.lane_rows:
            for tid in list(self._order):
                q = self._queues.get(tid)
                if (q and self.table.is_lane(tid)
                        and q[0].ticket.n_valid <= self.lane_rows
                        and self._lane_debt.get(tid, 0.0)
                        < self._quantum(tid)):
                    sub = q.popleft()
                    cost = max(1, sub.ticket.n_valid)
                    self._lane_debt[tid] = \
                        self._lane_debt.get(tid, 0.0) + cost
                    return self._served(tid, sub, q, cost)
        spins = 0
        while True:
            tid = self._order[0]
            q = self._queues.get(tid)
            if not q:
                # defensive: an empty per-tenant deque should have been
                # retired at pop/remove time
                self._retire_locked(tid)
                continue
            if tid not in self._granted:
                grant = self._quantum(tid)
                debt = self._lane_debt.get(tid, 0.0)
                pay = min(grant, debt)
                if pay:
                    self._lane_debt[tid] = debt - pay
                self._deficit[tid] += grant - pay
                self._granted.add(tid)
            cost = max(1, q[0].ticket.n_valid)
            if self._deficit[tid] < cost:
                # this tenant's turn is spent: next tenant (it keeps the
                # accrued deficit and earns a fresh quantum next round)
                self._granted.discard(tid)
                self._order.rotate(-1)
                spins += 1
                if spins >= len(self._order):
                    # a whole rotation granted everyone a quantum and
                    # served nobody: replay the empty rounds in bulk
                    self._fast_forward()
                    spins = 0
                continue
            sub = q.popleft()
            self._deficit[tid] -= cost
            return self._served(tid, sub, q, cost)

    def _served(self, tid: int, sub, q: deque, cost: int):
        self._len -= 1
        self.admitted_rows[tid] = self.admitted_rows.get(tid, 0) + cost
        self.admitted_batches[tid] = self.admitted_batches.get(tid, 0) + 1
        if not q:
            # idle tenants bank no credit (standard DRR)
            self._retire_locked(tid)
        return sub

    def remove(self, sub) -> None:
        tid = getattr(sub, "tenant", TENANT_DEFAULT)
        q = self._queues.get(tid)
        if q is None:
            raise ValueError("TenantQueues.remove(x): x not in queue")
        q.remove(sub)                      # ValueError if absent, like deque
        self._len -= 1
        if not q:
            self._retire_locked(tid)

    def clear(self) -> None:
        self._queues.clear()
        self._order.clear()
        self._deficit.clear()
        self._granted.clear()
        self._lane_debt.clear()
        self._len = 0

    # -- internals -----------------------------------------------------------
    def _quantum(self, tid: int) -> float:
        return max(float(WEIGHT_FLOOR_ROWS),
                   self.table.weight_of(tid) * self._qrows)

    def _fast_forward(self) -> None:
        """Credit every queued tenant the smallest whole number of DRR
        rounds after which at least one of them can afford its head
        batch — the deterministic equivalent of that many empty ring
        rotations (each round's grant still pays lane debt before
        banking deficit), collapsed into one O(tenants) pass."""
        rounds: Optional[int] = None
        for tid in self._order:
            q = self._queues.get(tid)
            if not q:
                continue
            need = (max(1, q[0].ticket.n_valid)
                    + self._lane_debt.get(tid, 0.0) - self._deficit[tid])
            k = max(1, int(math.ceil(need / self._quantum(tid))))
            rounds = k if rounds is None else min(rounds, k)
        if not rounds:
            return
        for tid in self._order:
            if not self._queues.get(tid):
                continue
            total = rounds * self._quantum(tid)
            debt = self._lane_debt.get(tid, 0.0)
            pay = min(total, debt)
            if pay:
                self._lane_debt[tid] = debt - pay
            self._deficit[tid] += total - pay

    def _retire_locked(self, tid: int) -> None:
        try:
            self._order.remove(tid)
        except ValueError:
            pass
        self._queues.pop(tid, None)
        self._deficit.pop(tid, None)
        self._granted.discard(tid)
        # lane debt SURVIVES per-tenant retirement, unlike credit: a lane
        # tenant whose queue drains on every pop (arrival rate ~ service
        # rate, one batch queued at a time) retires here after every
        # single popleft, and forgiving the debt with the credit would
        # reset the "bypass only while debt < one quantum" starvation
        # bound each time — the ring would never get a turn. The debt is
        # owed TO the tenants still queued behind the bypass, though, so
        # when the LAST queue drains the creditors no longer exist and
        # all debt is forgiven (otherwise debt banked against an idle
        # ring — e.g. sparse probes on an unloaded system — would deny
        # the bypass at the start of the next busy period and show up as
        # a lane-latency spike that repays nobody). Zeroed entries are
        # dropped so a departed tenant does not leak a dict slot.
        if self._len == 0:
            self._lane_debt.clear()
        elif not self._lane_debt.get(tid):
            self._lane_debt.pop(tid, None)

    # -- admission policy (scheduler hooks) ----------------------------------
    def occupancy(self, tid: int) -> int:
        q = self._queues.get(tid)
        return len(q) if q else 0

    def over_cap(self, tid: int) -> bool:
        """True when the tenant is at its own occupancy cap (batches).
        Cap 0 means uncapped — only the global queue bound applies."""
        cap = self.table.cap_of(tid)
        return cap > 0 and self.occupancy(tid) >= cap

    def over_share(self, tid: int) -> bool:
        """True when the tenant's share of the queued batches meets or
        exceeds its weight share among the tenants currently competing
        (queued tenants plus the incoming one). Used to scope the
        OVERLOAD fail-fast: only over-share tenants are instant-rejected;
        a within-budget tenant still gets to wait/displace. With a single
        tenant this is always True — the old unconditional reject."""
        total = self._len
        if total == 0:
            return False
        tids = set(self._queues)
        tids.add(tid)
        wsum = 0.0
        for t in tids:
            wsum += max(self.table.weight_of(t), _MIN_WEIGHT)
        wshare = max(self.table.weight_of(tid), _MIN_WEIGHT) / wsum
        return (self.occupancy(tid) / total) >= wshare

    def pressure_of(self, tid: int) -> float:
        """Queue pressure normalized by weight — the shed-ordering key
        (worst-pressure tenant sheds first)."""
        occ = self.occupancy(tid)
        if not occ:
            return 0.0
        return occ / max(self.table.weight_of(tid), _MIN_WEIGHT)

    def priority_victim(self, incoming_prio: int, incoming_tid: int):
        """Tenant-scoped displacement under PRESSURE. Scans tenants from
        worst weight-normalized pressure down; within the incoming
        tenant the old contract holds (only a strictly worse class is
        displaced — established CT still outranks new flows *within* a
        tenant); across tenants an equal-or-worse class may be displaced
        but only from a tenant under strictly more pressure than the
        submitter's (the worst-pressure tenant sheds first)."""
        inc_pressure = self.pressure_of(incoming_tid)
        for tid in sorted(self._queues, key=self.pressure_of, reverse=True):
            q = self._queues.get(tid)
            if not q:
                continue
            worst = None
            for sub in q:                      # newest of the worst class
                if worst is None or sub.prio >= worst.prio:
                    worst = sub
            if worst is None:
                continue
            if tid == incoming_tid:
                if worst.prio > incoming_prio:
                    return worst
            elif (worst.prio >= incoming_prio
                    and self.pressure_of(tid) > inc_pressure):
                return worst
        return None

    # -- introspection -------------------------------------------------------
    def occupancy_by_name(self) -> Dict[str, Tuple[int, int]]:
        """``{tenant_name: (cap_batches, queued_batches)}`` for the
        resource ledger (active tenants only — a departed/idle tenant
        stops reporting and the ledger's staleness sweep drops its
        gauges, the PR 13 departed-subject discipline)."""
        names = self.table.tenants()
        out: Dict[str, Tuple[int, int]] = {}
        for tid, q in self._queues.items():
            name = names.get(tid, str(tid))
            cap = self.table.cap_of(tid)
            out[name] = (cap, len(q))
        return out

    def stats(self) -> Dict[str, Dict[str, object]]:
        names = self.table.tenants()
        out: Dict[str, Dict[str, object]] = {}
        tids = set(names) | set(self._queues) | set(self.admitted_batches)
        for tid in sorted(tids):
            name = names.get(tid, str(tid))
            out[name] = {
                "depth": self.occupancy(tid),
                "weight": self.table.weight_of(tid),
                "cap": self.table.cap_of(tid),
                "lane": self.table.is_lane(tid),
                "admitted_rows": self.admitted_rows.get(tid, 0),
                "admitted_batches": self.admitted_batches.get(tid, 0),
            }
        return out
