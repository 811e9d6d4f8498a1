"""Multi-tenant QoS tests (cilium_tpu/qos + the weighted-fair admission
path through pipeline/scheduler.py and the engine).

Tier-1: tenant spec parsing + the compiled ep→tenant LUT (fail-open),
DRR weight-share dequeue with FIFO-within-tenant and the zero-weight
starvation floor, single-tenant degeneracy to plain FIFO (QoS armed but
order bit-identical), per-tenant cap sheds (:class:`PipelineTenantCap`
with ``{reason=,tenant=}`` counters), tenant-scoped OVERLOAD fail-fast
(over-share tenant rejected, within-budget tenant displaces), the
latency lane's immediate flush at the lane bucket, the ``qos.enqueue``
fail-closed path, and engine parity with the auditor at sampling 1.0
with QoS armed.

Slow (`make chaos`): the 8-shard audited soak with two concurrent
``render_metrics`` scrapers and a mid-soak watchdog restart (the PR
7/11/13 house race pattern, extended to the ``{tenant=}`` label
families and the ``qos_tenant_queue_*`` resource rows).
"""

import threading
import time
from collections import Counter

import numpy as np
import pytest

from cilium_tpu.kernels.records import batch_from_records, empty_batch
from cilium_tpu.pipeline import (Pipeline, PipelineDrop, PipelineTenantCap)
from cilium_tpu.pipeline.guard import (OVERLOAD_OVERLOAD, OVERLOAD_PRESSURE,
                                       PRIO_ESTABLISHED, PRIO_NEW)
from cilium_tpu.qos import (TENANT_DEFAULT, TenantQueues, TenantSpecError,
                            TenantTable, parse_assign_spec,
                            parse_tenant_spec)
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.datapath import FakeDatapath
from cilium_tpu.runtime.engine import Engine
from cilium_tpu.runtime.faults import FAULTS
from cilium_tpu.utils.ip import parse_addr
from oracle import PacketRecord
from cilium_tpu.utils import constants as C

POLICY = [{
    "endpointSelector": {"matchLabels": {"app": "web"}},
    "egress": [{"toCIDR": ["10.0.0.0/8"],
                "toPorts": [{"ports": [{"port": "443",
                                        "protocol": "TCP"}]}]}],
}]

SPEC = "gold=4:lane,silver=2,bulk=1"


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


# --------------------------------------------------------------------------- #
# tenant table / spec parsing
# --------------------------------------------------------------------------- #
class TestTenantTable:
    def test_spec_parse(self):
        got = list(parse_tenant_spec("gold=4:lane, silver=2, bulk=1:cap=8"))
        assert got == [("gold", 4.0, True, 0),
                       ("silver", 2.0, False, 0),
                       ("bulk", 1.0, False, 8)]
        assert parse_assign_spec("1=gold, 7=bulk") == {1: "gold", 7: "bulk"}

    @pytest.mark.parametrize("bad", [
        "gold", "gold=x", "gold=-1", "gold=1:warp", "gold=1:cap=x",
        "gold=1:cap=-2", "=3", "b@d=1"])
    def test_spec_rejects(self, bad):
        with pytest.raises(TenantSpecError):
            list(parse_tenant_spec(bad))

    @pytest.mark.parametrize("bad", ["gold", "x=gold", "0=gold", "3="])
    def test_assign_rejects(self, bad):
        with pytest.raises(TenantSpecError):
            parse_assign_spec(bad)

    def test_from_spec_and_lookups(self):
        tbl = TenantTable.from_spec(SPEC, assign="1=gold,2=silver")
        tids = {v: k for k, v in tbl.tenants().items()}
        assert tids["default"] == TENANT_DEFAULT
        assert tbl.weight_of(tids["gold"]) == 4.0
        assert tbl.is_lane(tids["gold"]) and not tbl.is_lane(tids["bulk"])
        assert tbl.tenant_of_ep(1) == tids["gold"]
        assert tbl.tenant_of_ep(99) == TENANT_DEFAULT   # fail-open

    def test_map_tenants_vectorized_fail_open(self):
        tbl = TenantTable.from_spec(SPEC, assign="1=gold,5=bulk")
        tids = {v: k for k, v in tbl.tenants().items()}
        eps = np.array([1, 5, 2, -3, 10_000], dtype=np.int32)
        got = tbl.map_tenants(eps)
        assert got.tolist() == [tids["gold"], tids["bulk"], 0, 0, 0]
        # LUT is cached on the revision counter: same object until a change
        assert tbl.lut() is tbl.lut()
        tbl.assign(2, "silver")
        assert tbl.map_tenants(eps)[2] == tids["silver"]

    def test_remove_retires_tenant(self):
        tbl = TenantTable.from_spec(SPEC, assign="1=gold")
        tids = {v: k for k, v in tbl.tenants().items()}
        tbl.remove("gold")
        # endpoints fall back to default; the retired id keeps a safe name
        assert tbl.tenant_of_ep(1) == TENANT_DEFAULT
        assert tbl.name_of(tids["gold"]) == "default"
        with pytest.raises(ValueError):
            tbl.remove("default")


# --------------------------------------------------------------------------- #
# DRR queue mechanics
# --------------------------------------------------------------------------- #
class _FakeTicket:
    def __init__(self, n_valid):
        self.n_valid = n_valid


class _FakeSub:
    def __init__(self, tenant, n_valid=1, prio=PRIO_NEW, tag=None):
        self.tenant = tenant
        self.prio = prio
        self.tag = tag
        self.ticket = _FakeTicket(n_valid)


class TestTenantQueues:
    def _mk(self, spec=SPEC, quantum_rows=1):
        tbl = TenantTable.from_spec(spec)
        tids = {v: k for k, v in tbl.tenants().items()}
        return TenantQueues(tbl, quantum_rows=quantum_rows), tids

    def test_drr_weight_share(self):
        """Under contention the dequeue order converges to the 4:2:1
        weight ratio — the first full round serves exactly one quantum
        per tenant."""
        qs, tids = self._mk()
        for i in range(12):
            for name in ("bulk", "silver", "gold"):   # bulk enqueues FIRST
                qs.append(_FakeSub(tids[name], tag=f"{name}{i}"))
        first_round = [qs.popleft().tenant for _ in range(7)]
        assert Counter(first_round) == {tids["gold"]: 4, tids["silver"]: 2,
                                        tids["bulk"]: 1}
        # and it keeps that ratio over many rounds
        more = Counter(qs.popleft().tenant for _ in range(14))
        assert more == {tids["gold"]: 8, tids["silver"]: 4, tids["bulk"]: 2}

    def test_fifo_within_tenant_and_single_tenant_fifo(self):
        qs, tids = self._mk()
        for i in range(10):
            qs.append(_FakeSub(tids["gold"], tag=i))
        assert [qs.popleft().tag for _ in range(10)] == list(range(10))
        assert len(qs) == 0 and not qs

    def test_remove_clear_iter(self):
        qs, tids = self._mk()
        subs = [_FakeSub(tids["gold"], tag=0), _FakeSub(tids["bulk"], tag=1)]
        for s in subs:
            qs.append(s)
        assert set(s.tag for s in qs) == {0, 1}
        qs.remove(subs[0])
        assert len(qs) == 1
        with pytest.raises(ValueError):
            qs.remove(subs[0])
        qs.clear()
        assert len(qs) == 0

    def test_zero_weight_starvation_floor(self):
        """A zero-weight tenant still gets served: every full DRR round
        banks WEIGHT_FLOOR_ROWS of credit, so its head batch is reachable
        in a bounded number of pops."""
        qs, tids = self._mk()
        zero = qs.table.register("zero", weight=0.0)
        for i in range(64):
            qs.append(_FakeSub(tids["gold"], n_valid=1, tag=f"g{i}"))
        qs.append(_FakeSub(zero, n_valid=1, tag="starved"))
        served = [qs.popleft().tag for _ in range(len(qs))]
        assert "starved" in served

    def test_over_cap_over_share(self):
        tbl = TenantTable.from_spec("gold=4,bulk=1:cap=2")
        tids = {v: k for k, v in tbl.tenants().items()}
        qs = TenantQueues(tbl, quantum_rows=1)
        assert not qs.over_cap(tids["bulk"])
        qs.append(_FakeSub(tids["bulk"]))
        qs.append(_FakeSub(tids["bulk"]))
        assert qs.over_cap(tids["bulk"])
        assert not qs.over_cap(tids["gold"])        # cap 0 = uncapped
        # bulk holds 100% of the queue >> its 1/5 weight share vs gold
        assert qs.over_share(tids["bulk"])
        assert not qs.over_share(tids["gold"])
        # single-tenant world: over_share is always True (old behavior)
        qs2 = TenantQueues(TenantTable(), quantum_rows=1)
        qs2.append(_FakeSub(TENANT_DEFAULT))
        assert qs2.over_share(TENANT_DEFAULT)

    def test_priority_victim_tenant_scoped(self):
        qs, tids = self._mk()
        est = _FakeSub(tids["gold"], prio=PRIO_ESTABLISHED, tag="g-est")
        new = _FakeSub(tids["gold"], prio=PRIO_NEW, tag="g-new")
        flood = _FakeSub(tids["bulk"], prio=PRIO_NEW, tag="b-new")
        for s in (est, new, flood):
            qs.append(s)
        qs.append(_FakeSub(tids["bulk"], prio=PRIO_NEW, tag="b-new2"))
        # within gold: only a strictly worse class is displaced
        v = qs.priority_victim(PRIO_ESTABLISHED, tids["gold"])
        assert v is not None and v.tag in ("b-new2", "b-new", "g-new")
        # bulk is the worst-pressure tenant (2 queued over weight 1):
        # a same-class submission from silver displaces from bulk, never
        # from gold (gold's pressure 2/4 < bulk's 2/1)
        v = qs.priority_victim(PRIO_NEW, tids["silver"])
        assert v is not None and v.tag == "b-new2"   # newest of worst class
        # an established sub within bulk itself displaces its own NEW first
        v = qs.priority_victim(PRIO_ESTABLISHED, tids["bulk"])
        assert v is not None and v.tag == "b-new2"

    def test_stats_and_occupancy_by_name(self):
        qs, tids = self._mk()
        qs.append(_FakeSub(tids["gold"], n_valid=64))
        st = qs.stats()
        assert st["gold"]["depth"] == 1
        # admitted_* count service (DRR pops), not arrivals — the share
        # gate must see dequeue order, not whatever was accepted
        assert st["gold"]["admitted_rows"] == 0
        assert st["gold"]["lane"] is True
        assert st["bulk"]["depth"] == 0
        occ = qs.occupancy_by_name()
        assert occ == {"gold": (0, 1)}               # active tenants only
        qs.popleft()
        assert qs.stats()["gold"]["admitted_rows"] == 64

    def test_lane_bypass_priority_and_debt_bound(self):
        """A lane tenant's lane-sized head jumps the DRR ring, but only
        until it owes a full quantum — sustained lane traffic falls back
        to its ring turn (the starvation bound), and ring grants pay the
        debt before banking deficit."""
        tbl = TenantTable.from_spec(SPEC)
        tids = {v: k for k, v in tbl.tenants().items()}
        qs = TenantQueues(tbl, quantum_rows=4, lane_rows=8)
        # bulk and silver enqueue FIRST; gold's small sub still pops first
        qs.append(_FakeSub(tids["bulk"], n_valid=4, tag="b0"))
        qs.append(_FakeSub(tids["silver"], n_valid=4, tag="s0"))
        qs.append(_FakeSub(tids["gold"], n_valid=4, tag="g0"))
        assert qs.popleft().tag == "g0"
        # an over-lane-size gold sub does NOT bypass (bulk-shaped work
        # from a lane tenant waits its ring turn like everyone else)
        qs.append(_FakeSub(tids["gold"], n_valid=9, tag="gbig"))
        assert qs.popleft().tag == "b0"              # ring head, not gold
        # debt bound: gold's quantum is 4*4=16 rows; after 4 bypassed
        # 4-row subs the debt is at the quantum and the 5th waits for
        # the ring (which still owes silver its turn first)
        qs, tids = TenantQueues(tbl, quantum_rows=4, lane_rows=8), tids
        qs.append(_FakeSub(tids["silver"], n_valid=4, tag="s0"))
        for i in range(5):
            qs.append(_FakeSub(tids["gold"], n_valid=4, tag=f"g{i}"))
        got = [qs.popleft().tag for _ in range(4)]
        assert got == ["g0", "g1", "g2", "g3"]       # bypass while affordable
        assert qs.popleft().tag == "s0"              # debt cap: ring resumes

    def test_lane_debt_survives_queue_drain(self):
        """A lane tenant that keeps exactly ONE batch queued at a time
        (arrival rate ~ service rate) drains its queue — and is retired
        from the ring — on every single pop. Its lane debt must survive
        that retirement: forgiving it with the credit would reset the
        "bypass only while debt < one quantum" starvation bound on every
        popleft and the ring (bulk tenants) would be starved forever."""
        tbl = TenantTable.from_spec(SPEC)
        tids = {v: k for k, v in tbl.tenants().items()}
        qs = TenantQueues(tbl, quantum_rows=4, lane_rows=8)
        for i in range(6):
            qs.append(_FakeSub(tids["bulk"], n_valid=4, tag=f"b{i}"))
        got = []
        for i in range(5):
            qs.append(_FakeSub(tids["gold"], n_valid=4, tag=f"g{i}"))
            got.append(qs.popleft().tag)
        # gold's quantum is 4*4=16 rows: four bypassed 4-row pops bank a
        # full quantum of debt even though gold's queue drained after
        # each one — the 5th pop falls back to the ring and bulk is
        # finally served
        assert got == ["g0", "g1", "g2", "g3", "b0"]
        assert qs.popleft().tag == "b1"   # ring grant pays the debt down
        assert qs.popleft().tag == "g4"   # ...and the bypass re-arms

    def test_lane_debt_forgiven_when_ring_fully_drains(self):
        """Lane debt is owed to the tenants queued behind the bypass —
        when the LAST queue drains there is nobody left to repay, and
        carrying the debt into the next busy period would deny the lane
        bypass to the first probes after an idle gap (a latency spike
        that repays no one). Debt banked by sparse probes on an idle
        ring must NOT outlive a full drain."""
        tbl = TenantTable.from_spec(SPEC)
        tids = {v: k for k, v in tbl.tenants().items()}
        qs = TenantQueues(tbl, quantum_rows=4, lane_rows=8)
        # unloaded phase: sparse gold probes, one at a time, bank a full
        # quantum (4 * 4 rows >= quantum 16) of debt against an idle ring
        for i in range(4):
            qs.append(_FakeSub(tids["gold"], n_valid=4, tag=f"p{i}"))
            assert qs.popleft().tag == f"p{i}"
        assert len(qs) == 0               # ring fully drained -> debt gone
        # busy period starts: bulk floods, then a gold probe arrives —
        # the bypass must be armed (with stale debt it would queue
        # behind both bulk batches)
        qs.append(_FakeSub(tids["bulk"], n_valid=4, tag="b0"))
        qs.append(_FakeSub(tids["bulk"], n_valid=4, tag="b1"))
        qs.append(_FakeSub(tids["gold"], n_valid=4, tag="g0"))
        assert qs.popleft().tag == "g0"

    def test_zero_weight_big_batch_fast_forwards(self):
        """Two zero-weight tenants with max-bucket-sized heads: the floor
        quantum is 1 row, so reaching a 512-row head used to take 512
        full ring rotations under the pipeline lock — the fruitless-
        rotation fast-forward credits those rounds in one O(tenants)
        pass, and service order is unchanged (first-enqueued first)."""
        tbl = TenantTable.from_spec(SPEC)
        za = tbl.register("za", weight=0.0)
        zb = tbl.register("zb", weight=0.0)
        qs = TenantQueues(tbl, quantum_rows=1)
        qs.append(_FakeSub(za, n_valid=512, tag="a"))
        qs.append(_FakeSub(zb, n_valid=512, tag="b"))
        assert [qs.popleft().tag for _ in range(2)] == ["a", "b"]
        assert len(qs) == 0


# --------------------------------------------------------------------------- #
# pipeline-level QoS (raw Pipeline against an echo dispatch)
# --------------------------------------------------------------------------- #
class EchoDispatch:
    """Records the valid-row sports of every dispatched batch and echoes
    them through ``reason``; ``gate.clear()`` stalls the worker."""

    def __init__(self):
        self.batches = []
        self.gate = threading.Event()
        self.gate.set()

    def __call__(self, batch, now):
        self.gate.wait(timeout=10)
        valid = np.asarray(batch["valid"])
        self.batches.append(np.asarray(batch["sport"])[valid].tolist())
        out = {
            "allow": valid.copy(),
            "reason": np.asarray(batch["sport"], np.int32).copy(),
            "status": np.zeros(valid.shape[0], np.int32),
            "remote_identity": np.zeros(valid.shape[0], np.int32),
        }
        return lambda: out


def tagged_batch(n_rows, start, tenant=0):
    b = empty_batch(n_rows)
    b["sport"][:] = np.arange(start, start + n_rows, dtype=np.int32)
    b["valid"][:] = True
    b["_tenant"] = np.full((n_rows,), tenant, dtype=np.int32)
    return b


class TestQosPipeline:
    def _mk(self, spec=SPEC, **kw):
        tbl = TenantTable.from_spec(spec)
        tids = {v: k for k, v in tbl.tenants().items()}
        d = EchoDispatch()
        kw.setdefault("min_bucket", 4)
        kw.setdefault("max_bucket", 4)
        kw.setdefault("flush_ms", 1000.0)
        pl = Pipeline(d, qos=tbl, **kw)
        return pl, d, tids

    def test_drr_dispatch_order_under_contention(self):
        """Back the queue up behind a gated dispatch, release, and check
        the weighted interleave: the first contended round serves
        4 gold : 2 silver : 1 bulk (quantum = max_bucket rows)."""
        pl, d, tids = self._mk(inflight=1, queue_batches=64)
        try:
            d.gate.clear()
            pl.submit(tagged_batch(4, start=0, tenant=tids["bulk"]))
            time.sleep(0.1)          # the worker pops this one pre-gate
            tickets = []
            for i in range(8):
                for name in ("bulk", "silver", "gold"):
                    tickets.append(pl.submit(tagged_batch(
                        4, start=100 * (tids[name]) + 4 * i,
                        tenant=tids[name])))
            d.gate.set()
            assert pl.drain(timeout=20)
            served = [b[0] // 100 for b in d.batches[1:]]
            first = Counter(served[:7])
            assert first == {tids["gold"]: 4, tids["silver"]: 2,
                             tids["bulk"]: 1}
            for t in tickets:
                t.result(timeout=5)
        finally:
            pl.close(timeout=5)

    def test_single_tenant_degenerates_to_fifo(self):
        """QoS armed but one tenant submitting: dispatch order is exactly
        submission order — bit-identical to the FIFO world."""
        pl, d, _tids = self._mk(inflight=1, queue_batches=64)
        try:
            d.gate.clear()
            pl.submit(tagged_batch(4, start=0))
            time.sleep(0.1)
            for i in range(1, 12):
                pl.submit(tagged_batch(4, start=4 * i))
            d.gate.set()
            assert pl.drain(timeout=20)
            assert [b[0] for b in d.batches] == [4 * i for i in range(12)]
        finally:
            pl.close(timeout=5)

    def test_tenant_cap_shed(self):
        """A capped tenant sheds against its OWN budget while the shared
        queue still has room: PipelineTenantCap (a PipelineDrop) plus the
        {reason=,tenant=} counter."""
        pl, d, tids = self._mk(spec="gold=4,bulk=1:cap=1",
                               admission="drop", inflight=1,
                               queue_batches=32)
        try:
            d.gate.clear()
            pl.submit(tagged_batch(4, start=0, tenant=tids["bulk"]))
            time.sleep(0.1)
            pl.submit(tagged_batch(4, start=4, tenant=tids["bulk"]))
            t = pl.submit(tagged_batch(4, start=8, tenant=tids["bulk"]))
            assert t.dropped
            with pytest.raises(PipelineTenantCap):
                t.result(timeout=1)
            # gold rides free: the shared queue has room
            tg = pl.submit(tagged_batch(4, start=12, tenant=tids["gold"]))
            assert not tg.dropped
            key = 'pipeline_shed_total{reason="tenant_cap",tenant="bulk"}'
            assert pl.metrics.counters.get(key) == 1
            # the labeled family rides ALONGSIDE the pre-QoS reason-only
            # family, never instead of it — dashboards watching the bare
            # family must keep counting with QoS armed
            assert pl.metrics.counters.get(
                'pipeline_shed_total{reason="tenant_cap"}') == 1
            assert pl.shed_reasons.get("tenant_cap") == 1
            d.gate.set()
            assert pl.drain(timeout=10)
            tg.result(timeout=5)
        finally:
            pl.close(timeout=5)

    def test_overload_fail_fast_is_tenant_scoped(self):
        """At OVERLOAD with a full queue, the over-share tenant is
        instant-rejected while a within-budget tenant displaces the
        flooder's newest batch and gets served."""
        pl, d, tids = self._mk(inflight=1, queue_batches=2,
                               block_timeout_s=5.0)
        try:
            d.gate.clear()
            pl.submit(tagged_batch(4, start=0, tenant=tids["bulk"]))
            time.sleep(0.1)
            q1 = pl.submit(tagged_batch(4, start=4, tenant=tids["bulk"]))
            q2 = pl.submit(tagged_batch(4, start=8, tenant=tids["bulk"]))
            pl.set_overload_state(OVERLOAD_OVERLOAD)
            t0 = time.monotonic()
            tb = pl.submit(tagged_batch(4, start=12, tenant=tids["bulk"]))
            assert tb.dropped                     # over-share: fail fast
            assert time.monotonic() - t0 < 1.0    # no blocking wait burned
            tg = pl.submit(tagged_batch(4, start=16, tenant=tids["gold"]))
            assert not tg.dropped                 # displaced q2 (newest bulk)
            assert q2.dropped
            with pytest.raises(PipelineDrop):
                q2.result(timeout=1)
            d.gate.set()
            assert pl.drain(timeout=10)
            assert not q1.dropped
            tg.result(timeout=5)
        finally:
            pl.close(timeout=5)

    def test_pressure_at_cap_never_strands_a_victim(self):
        """A submitter over its OWN cap gains nothing from displacing a
        cross-tenant victim, so under PRESSURE no victim may be removed
        for it: a removed-but-never-settled victim would leave its
        producer blocked forever in result() and wedge drain()/close().
        Setup: bulk (high weight, cap 1) already holds its cap, gold
        (low weight → worst pressure) holds the rest of a full queue;
        bulk submits again with admission=drop under PRESSURE."""
        pl, d, tids = self._mk(spec="bulk=4:cap=1,gold=0.5",
                               admission="drop", inflight=1,
                               queue_batches=2)
        try:
            d.gate.clear()
            pl.submit(tagged_batch(4, start=0, tenant=tids["bulk"]))
            time.sleep(0.1)          # the worker pops this one pre-gate
            b1 = pl.submit(tagged_batch(4, start=4, tenant=tids["bulk"]))
            g0 = pl.submit(tagged_batch(4, start=8, tenant=tids["gold"]))
            assert not b1.dropped and not g0.dropped   # queue now full
            pl.set_overload_state(OVERLOAD_PRESSURE)
            t = pl.submit(tagged_batch(4, start=12, tenant=tids["bulk"]))
            assert t.dropped         # rejected against its own budget
            # the drop counts in BOTH admission families (aggregate and
            # tenant-labeled) and g0 was NOT displaced for a submission
            # that could never be admitted
            assert pl.metrics.counters.get(
                "pipeline_admission_drops_total") == 1
            assert pl.metrics.counters.get(
                'pipeline_admission_drops_total{tenant="bulk"}') == 1
            assert not g0.dropped
            d.gate.set()
            # the wedge the stranded victim used to cause: drain() hung
            # forever because _outstanding never drained
            assert pl.drain(timeout=10)
            b1.result(timeout=5)
            g0.result(timeout=5)
        finally:
            pl.close(timeout=5)

    def test_lane_bypasses_microbatching(self):
        """A lane tenant's small batch dispatches immediately at the lane
        bucket; an identical bulk batch waits for the coalescing deadline."""
        pl, d, tids = self._mk(min_bucket=64, max_bucket=64, lane_bucket=8,
                               flush_ms=60_000.0, inflight=2,
                               queue_batches=32)
        try:
            tg = pl.submit(tagged_batch(5, start=0, tenant=tids["gold"]))
            out = tg.result(timeout=5)            # flushed at once: lane
            assert out["reason"].tolist() == list(range(5))
            assert pl.flush_reasons["lane"] >= 1
            assert d.batches[0] == list(range(5))
            st = pl.stats()
            assert st["lane_bucket"] == 8
            assert st["lane_fill_rows"] >= 5
            assert st["lane_bucket_rows"] >= 8     # padded to the lane shape
            assert "pipeline_lane_wait_seconds" in pl.metrics.histograms
            # a lane batch at the lane bucket takes the DIRECT zero-copy
            # path — the bypass floor is the lane bucket, not min_bucket
            pl.submit(tagged_batch(8, start=50,
                                   tenant=tids["gold"])).result(timeout=5)
            assert pl.flush_reasons["direct"] >= 1
            # bulk: same shape, stays staged until an explicit drain
            tb = pl.submit(tagged_batch(8, start=100, tenant=tids["bulk"]))
            time.sleep(0.2)
            assert not tb.done()
            assert pl.drain(timeout=10)
            tb.result(timeout=5)
            assert pl.flush_reasons["drain"] >= 1
        finally:
            pl.close(timeout=5)

    def test_set_lane_bucket_bounds(self):
        pl, _d, _tids = self._mk(min_bucket=16, max_bucket=64,
                                 lane_bucket=16)
        try:
            pl.set_lane_bucket(8)
            assert pl.lane_bucket == 8
            with pytest.raises(ValueError):
                pl.set_lane_bucket(6)             # not a power of two
            with pytest.raises(ValueError):
                pl.set_lane_bucket(128)           # > max_bucket
        finally:
            pl.close(timeout=5)

    def test_qos_enqueue_fault_fails_closed(self):
        """Classification faulting at admission lands the ticket on the
        default tenant's FIFO budget — served, never dropped."""
        pl, d, tids = self._mk()
        try:
            FAULTS.arm("qos.enqueue", mode="fail", times=1)
            t = pl.submit(tagged_batch(4, start=0, tenant=tids["gold"]))
            assert t.tenant == "default"
            t.result(timeout=5)
            assert pl.metrics.counters.get(
                "qos_enqueue_failsafe_total") == 1
            t2 = pl.submit(tagged_batch(4, start=4, tenant=tids["gold"]))
            assert t2.tenant == "gold"
            t2.result(timeout=5)
        finally:
            FAULTS.reset()
            pl.close(timeout=5)

    def test_qos_off_surface_unchanged(self):
        """Without qos the stats/metric surfaces are byte-identical to the
        pre-QoS shapes: no tenants key, unlabeled counter names."""
        d = EchoDispatch()
        pl = Pipeline(d, min_bucket=4, max_bucket=4, admission="drop",
                      queue_batches=1, inflight=1, flush_ms=1000.0)
        try:
            d.gate.clear()
            pl.submit(tagged_batch(4, start=0))
            time.sleep(0.1)
            pl.submit(tagged_batch(4, start=4))
            t = pl.submit(tagged_batch(4, start=8))
            assert t.dropped and t.tenant is None
            st = pl.stats()
            assert "tenants" not in st and "lane_bucket" not in st
            assert "pipeline_admission_drops_total" in pl.metrics.counters
            assert not any("tenant=" in k for k in pl.metrics.counters)
            assert pl.lane_bucket == 0
            d.gate.set()
        finally:
            pl.close(timeout=5)


# --------------------------------------------------------------------------- #
# engine integration
# --------------------------------------------------------------------------- #
def _qos_engine(**kw):
    kw.setdefault("auto_regen", False)
    kw.setdefault("qos_enabled", True)
    kw.setdefault("qos_tenants", SPEC)
    kw.setdefault("qos_assign", "1=gold")
    cfg = DaemonConfig(**kw)
    eng = Engine(cfg, datapath=FakeDatapath(cfg))
    eng.add_endpoint(["k8s:app=web"], ips=("192.168.1.10",), ep_id=1)
    eng.apply_policy(POLICY)
    eng.regenerate()
    return eng


def _mk_batch(eng, tenant=0, n=3):
    s16, _ = parse_addr("192.168.1.10")
    recs = []
    for j in range(n):
        d16, _ = parse_addr(f"10.1.2.{3 + j}")
        recs.append(PacketRecord(s16, d16, 40000 + j, 443, C.PROTO_TCP,
                                 C.TCP_SYN, False, 1, C.DIR_EGRESS))
    b = batch_from_records(recs, eng.active.snapshot.ep_slot_of)
    b["_tenant"] = np.full(b["valid"].shape, tenant, dtype=np.int32)
    return b


class TestQosEngine:
    def test_parity_with_auditor_qos_armed(self):
        """Pipeline verdicts stay bit-identical to the serial classify
        path with QoS armed, and the parity auditor at sampling 1.0 sees
        zero mismatched rows."""
        eng = _qos_engine(audit_enabled=True, audit_sample_rate=1.0)
        eng.auditor.configure(sample_rate=1.0)
        try:
            tids = {v: k for k, v in eng.qos.tenants().items()}
            base = eng.classify(_mk_batch(eng), now=100)
            baseline = [bool(a) for a in base["allow"]]
            tickets = [eng.submit(_mk_batch(eng, tenant=tids[name]),
                                  now=200 + i)
                       for i, name in enumerate(
                           ["gold", "silver", "bulk", "default"] * 6)]
            assert eng.drain(timeout=60)
            for t in tickets:
                out = t.result(timeout=5)
                assert [bool(a) for a in out["allow"]] == baseline
            for _ in range(50):
                step = eng.audit_step(budget=128)
                if not step or (not step.get("replayed")
                                and not step.get("pending")):
                    break
            assert eng.auditor.stats()["mismatched_rows"] == 0
        finally:
            eng.stop()

    def test_status_doc_and_ledger_rows(self):
        """The status document carries the qos row, per-tenant queue
        resources register in the ledger, and the global overload ladder
        never reads them."""
        from cilium_tpu.runtime.api import status_doc
        eng = _qos_engine(qos_tenant_cap_batches=0)
        try:
            tids = {v: k for k, v in eng.qos.tenants().items()}
            eng.submit(_mk_batch(eng, tenant=tids["gold"]), now=100)
            assert eng.drain(timeout=30)
            doc = status_doc(eng)
            assert doc["qos"] is not None
            assert doc["qos"]["tenants"]["gold"]["weight"] == 4.0
            assert doc["qos"]["tenants"]["gold"]["admitted_batches"] >= 1
            assert doc["qos"]["lane_bucket"] >= 1
            # ledger rows appear while a tenant has queued work; with the
            # queue drained they are swept (departed-subject discipline)
            rep = eng.resource_step(now=1.0)
            assert not any(r.startswith("qos_tenant_queue_")
                           for r in rep["resources"])
            st = eng.overload_step()
            assert st is not None
        finally:
            eng.stop()

    def test_qos_off_engine_unchanged(self):
        eng = Engine(DaemonConfig(auto_regen=False),
                     datapath=FakeDatapath(DaemonConfig(auto_regen=False)))
        try:
            assert eng.qos is None
            assert eng.qos_status() is None
        finally:
            eng.stop()

    @pytest.mark.slow
    def test_8shard_qos_soak_scrape_race_with_restart(self):
        """The PR 7/11/13 house race pattern extended to the {tenant=}
        families: an 8-shard audited QoS soak with concurrent
        render_metrics scrapers and a mid-soak watchdog restart, asserting
        every {tenant=}-labeled row and qos_tenant_queue_* resource row
        stays parseable throughout and parity holds after the restart."""
        from cilium_tpu.runtime.datapath import JITDatapath
        from tests.test_datapath import pkt
        # stall timeout stays wide through warmup: the QoS lane adds a
        # SECOND dispatch shape (the lane bucket) whose cold JIT compile
        # lands after the generation's one cold-dispatch grace window —
        # the drill shrinks the timeout only once the shapes are warm
        # (the chaos-CLI discipline)
        cfg = DaemonConfig(
            n_shards=8, auto_regen=False, batch_size=512,
            ct_capacity=1 << 12, pipeline_flush_ms=0.5,
            audit_enabled=True, audit_sample_rate=1.0,
            pipeline_max_restarts=3,
            pipeline_restart_backoff_s=0.05,
            qos_enabled=True, qos_tenants=SPEC,
            qos_assign="1=gold")
        eng = Engine(cfg, datapath=JITDatapath(cfg))
        eng.auditor.configure(sample_rate=1.0)
        eng.add_endpoint(["k8s:app=web"], ips=("192.168.0.10",), ep_id=1)
        eng.apply_policy([{
            "endpointSelector": {"matchLabels": {"app": "web"}},
            "egress": [{"toCIDR": ["10.0.0.0/8"],
                        "toPorts": [{"ports": [
                            {"port": "443", "protocol": "TCP"}]}]}]}])
        eng.regenerate()
        tids = {v: k for k, v in eng.qos.tenants().items()}
        errors = []
        stop = threading.Event()

        def scraper():
            try:
                while not stop.is_set():
                    text = eng.render_metrics()
                    for ln in text.splitlines():
                        if ln.startswith("#"):
                            continue
                        if 'tenant="' in ln or "qos_tenant_queue_" in ln:
                            float(ln.rsplit(" ", 1)[1])
            except Exception as e:   # noqa: BLE001
                errors.append(e)
        threads = [threading.Thread(target=scraper) for _ in range(2)]
        for t in threads:
            t.start()

        def batch(i, name):
            recs = [pkt("192.168.0.10", f"10.0.{(i + j) % 250}.1",
                        40000 + j, 443, ep_id=1) for j in range(64)]
            b = batch_from_records(recs, eng.active.snapshot.ep_slot_of)
            b["_tenant"] = np.full(b["valid"].shape, tids[name],
                                   dtype=np.int32)
            return b
        names = ["gold", "silver", "bulk"]
        try:
            FAULTS.reset()
            for i in range(20):
                eng.submit(batch(i, names[i % 3]), now=1000 + i)
            assert eng.drain(timeout=120)
            eng.resource_step(now=1.0)
            # shapes are warm: stall fast, then hang one dispatch past it
            eng.start_pipeline().set_stall_timeout_s(1.0)
            FAULTS.load_spec("datapath.transfer=hang:delay_s=4:times=1")
            try:
                eng.submit(batch(99, "bulk"), now=2000)
            except Exception:   # noqa: BLE001 — the wedged window rejects
                pass
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                ps = eng.pipeline_stats()
                if ps and ps["restarts"] >= 1 and ps["state"] == "ok":
                    break
                time.sleep(0.1)
            FAULTS.reset()
            ps = eng.pipeline_stats()
            assert ps["restarts"] >= 1
            for i in range(10):
                eng.submit(batch(200 + i, names[i % 3]), now=3000 + i)
            assert eng.drain(timeout=120)
            for _ in range(50):
                step = eng.audit_step(budget=128)
                if not step or (not step.get("replayed")
                                and not step.get("pending")):
                    break
            assert eng.auditor.stats()["mismatched_rows"] == 0
            st = eng.pipeline_stats()
            assert st["tenants"]["gold"]["admitted_batches"] >= 1
        finally:
            stop.set()
            for t in threads:
                t.join(5)
            FAULTS.reset()
            eng.stop()
        assert not errors
