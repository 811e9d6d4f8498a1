"""Async shim→pipeline feeder: the harvest half of zero-copy ingestion.

Before this module the shim path was synchronous per poll: poll a batch,
classify it with a blocking wait, apply verdicts, repeat — the device idles
during every harvest and the host idles during every classify. The feeder
replaces that loop with a harvest thread that

- harvests at the pipeline worker's pace. **A harvest takes what the ring
  holds**: as many rounds of ``afxdp_poll`` + ``poll_batch`` as there are
  frames waiting (AF_XDP rings and the heap-mocked rings drain through
  ``afxdp_poll``; the plain mock batcher through ``poll_batch`` alone),
  each round one shim batch, all into ONE reusable harvest buffer
  (:class:`HarvestBuffer` — no per-poll column dict), up to
  :func:`harvest_ceiling` rows. **A harvest waits for the worker**: none
  opens while a submission of this feeder's is still undispatched (in the
  pipeline's queue or a staged microbatch) — those frames wait in the
  ring, where the next harvest takes them together, instead of in the
  queue as a batch of their own. What comes after the dispatch follows
  what the harvest found (``_held_back``): a full one means a backlog, and
  the next opens at once, so that the worker dispatches it while the
  device has the one before; a partial one means the ring ran dry, and the
  next waits until the verdicts of everything out are back — a second
  submission in flight would carry a handful of rows at a whole
  dispatch's cost and put a dispatch and a finalize between every frame
  and its verdict,
- maps shim endpoint ids onto the active snapshot's slots (vectorized,
  lookup table cached per snapshot; unknown endpoints fail closed),
- makes ONE submission of the harvest to the engine's ingestion pipeline:
  the view of the buffer's first rows at the smallest power-of-two bucket
  (from ``min_bucket`` up) that holds them, so it dispatches ``direct`` —
  rows a dispatch follow the load — and
- applies verdicts **FIFO** as tickets resolve, one ``apply_verdicts`` per
  shim batch the harvest took, in poll order — the C++ shim holds one
  FrameRef per emitted record, so verdict order must equal harvest order;
  a rejected/shed/timed-out ticket is applied as all-drop (fail closed)
  for every shim batch of its harvest rather than skipped, which would
  desync frames from verdicts.

Buffer lifecycle: a harvest buffer stays owned by the pipeline from submit
until its ticket resolves (the scheduler stages from it asynchronously),
so the pool bounds feeder in-flight harvests; it is a ceiling the pacing
keeps the feeder under (two dispatches in flight, one being finalized, one
undispatched). When every buffer is busy all the same, the feeder blocks on
the oldest ticket — backpressure from the device straight back to the rx
ring (frames simply wait in the ring).

Fault tolerance: the ``shim.rx_ring`` injection point fires inside both
poll entry points; a trip is one failed poll — frames stay queued and
drain on the next poll. ``stop()`` drains: remaining rx frames are
force-harvested, submitted, and every pending verdict applied in order.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from cilium_tpu.kernels.records import reset_batch_rows
from cilium_tpu.observe.trace import TRACER, WAIT, Tracer, thread_cpu_s
from cilium_tpu.runtime.faults import FaultInjected
from cilium_tpu.runtime.metrics import Metrics
from cilium_tpu.shim.bindings import MAX_UNVERDICTED_BATCHES, FlowShim
from cilium_tpu.utils import constants as C

log = logging.getLogger("cilium_tpu.feeder")

#: dense-LUT cap: one sparse/huge ep_id must not turn the per-snapshot
#: LUT rebuild into a multi-GB allocation — fall back to dict lookups
DENSE_LUT_MAX = 1 << 20

#: established-flow filter geometry (pow2 slots): a direct-mapped
#: fingerprint table of recently-established flow hashes — the harvest-time
#: priority heuristic, NOT semantics (a collision merely promotes a flood
#: flow's priority class; verdicts are untouched)
EST_FILTER_SLOTS = 1 << 16


def flow_hashes(b: Dict[str, np.ndarray]) -> np.ndarray:
    """Direction-normalized flow hash per row (fwd XOR rev key hash — both
    directions of a flow agree). The UNtranslated tuple, deliberately:
    priority classing is a heuristic and must only be self-consistent
    between its update (verdict apply) and lookup (harvest) sides; the
    steering path keeps its own LB-translated hash."""
    from cilium_tpu.kernels.hashing import hash_words_np
    from cilium_tpu.kernels.records import ct_key_words
    return (hash_words_np(ct_key_words(b))
            ^ hash_words_np(ct_key_words(b, reverse=True)))


class EstablishedFingerprints:
    """Direct-mapped fingerprint table of flows observed allowed-
    ESTABLISHED/REPLY (pow2 slots; slot ``h & mask`` holds ``h | 1`` so an
    empty slot can never read as a hit for hash 0). Two consumers share
    the exact same update/lookup discipline:

    - the feeder's harvest-time priority classing (a hit ranks the row
      PRIO_ESTABLISHED — heuristic only, a collision merely promotes a
      colliding flow's class);
    - the engine's post-remesh CT-salvage grace window (ISSUE 19): a
      denied row whose fingerprint was established before the device loss
      rides through while the survivor mesh's CT cold-learns — there a
      collision admits one flow for a bounded window, which is exactly
      the documented grace contract, never a policy bypass outside it.

    They share one hash too. A batch that carries a ``_fp`` column (the
    feeder's harvest buffers: :func:`flow_hashes` of the view, written once
    a harvest by ``ShimFeeder._map_slots``, row-aligned through staging)
    is looked up and stamped from it; a batch without one (``Engine.submit``
    producers, batches built by hand) is hashed here. Slot and stamp have
    the one definition either way, so a table fed carried hashes is bit for
    bit the table fed by hashing. ``hashed_rows`` counts the rows this
    table's owner ran through :func:`flow_hashes`, in either place.

    ``note`` never raises (both call sites are verdict hot paths)."""

    def __init__(self, slots: int = EST_FILTER_SLOTS):
        if slots < 1 or slots & (slots - 1):
            raise ValueError("fingerprint slots must be a power of two")
        self._tab = np.zeros((slots,), dtype=np.uint32)
        self._mask = np.uint32(slots - 1)
        self.hashed_rows = 0     # single writer: the owner's thread

    def hash_rows(self, b: Dict[str, np.ndarray]) -> np.ndarray:
        """:func:`flow_hashes` of ``b``'s rows, counted."""
        h = flow_hashes(b)
        self.hashed_rows += len(h)
        return h

    def note(self, buf: Dict[str, np.ndarray],
             out: Dict[str, np.ndarray]) -> None:
        """Stamp fingerprints for rows applied allowed-ESTABLISHED/REPLY."""
        try:
            st = np.asarray(out["status"])
            m = (np.asarray(out["allow"])
                 & ((st == int(C.CTStatus.ESTABLISHED))
                    | (st == int(C.CTStatus.REPLY)))
                 & np.asarray(buf["valid"]))
            if not m.any():
                return
            h = buf.get("_fp")
            if h is not None:
                h = np.asarray(h)[m]
            else:
                h = self.hash_rows(
                    {k: np.asarray(buf[k])[m]
                     for k in ("src", "dst", "sport", "dport", "proto",
                               "direction")})
            self._tab[h & self._mask] = h | np.uint32(1)
        except Exception:   # noqa: BLE001 — heuristic, never load-bearing
            log.exception("established-fingerprint update failed")

    def hits(self, buf: Dict[str, np.ndarray]) -> np.ndarray:
        """[N] bool: rows whose direction-normalized fingerprint is
        stamped. Row-aligned with ``buf``; validity is the caller's mask."""
        h = buf.get("_fp")
        h = self.hash_rows(buf) if h is None else np.asarray(h)
        return self._tab[h & self._mask] == (h | np.uint32(1))


def shed_new_rows(b: Dict[str, np.ndarray]) -> int:
    """The SHED-NEW harvest-time shed (the feeder's, and a test's that
    plays the feeder): invalidate every valid row whose ``_prio``
    class is worse than established — those frames get their drop verdict
    at apply time without EVER being submitted (rx-ring backpressure
    relief), while established-class rows ride on. Returns rows shed."""
    from cilium_tpu.pipeline.guard import PRIO_ESTABLISHED
    v = b["valid"]
    m = v & (np.asarray(b["_prio"]) > PRIO_ESTABLISHED)
    n = int(m.sum())
    if n:
        v[m] = False                    # in place: poll buffers are pooled
    return n


def build_slot_lut(slot_of: Dict[int, int],
                   dense_max: int = DENSE_LUT_MAX
                   ) -> Optional[np.ndarray]:
    """ep_id → slot lookup array for one snapshot (None when the id space
    is too sparse to densify — callers fall back to dict lookups)."""
    size = max(slot_of, default=0) + 1
    if size > dense_max:
        return None
    lut = np.full((size,), -1, dtype=np.int32)
    for ep_id, slot in slot_of.items():
        if 0 <= ep_id < size:
            lut[ep_id] = slot
    return lut


def map_raw_slots(raw: np.ndarray, slot_of: Dict[int, int],
                  lut: Optional[np.ndarray]) -> np.ndarray:
    """[N] raw shim ep ids → [N] snapshot slots; -1 for unknown ids AND
    for raw == 0 ("no id"). Vectorized through the dense LUT when one
    exists, per-row dict lookups otherwise. Shared by the feeder's
    harvest-time mapping and the engine's dispatch-time re-mapping so the
    fail-closed semantics cannot diverge."""
    if lut is not None:
        slots = lut[np.clip(raw, 0, lut.size - 1)]
        # out-of-range ids INCLUDING negatives fail closed (a negative
        # would otherwise wrap-index the LUT and steal another
        # endpoint's slot); 0 means "no id"
        return np.where((raw >= lut.size) | (raw <= 0),
                        np.int32(-1), slots)
    return np.fromiter(
        (slot_of.get(int(e), -1) if e else -1 for e in raw),
        dtype=np.int32, count=raw.shape[0])


def harvest_ceiling(ring_frames: int, shim_batch: int, inflight: int,
                    max_rows: int) -> int:
    """Rows one harvest may take: the largest power-of-two number of shim
    batches that leaves the NIC room while ``inflight`` dispatches are in
    flight and one more harvest is being taken — a ``1 / (inflight + 2)``
    share of the frames the NIC can have outstanding (``ring_frames``: the
    smaller of rx ring and umem, the shim's to know) — never above the
    pipeline's largest bucket ``max_rows`` and never under one shim batch.
    4,096 frames, 256-row shim batches, two in flight: 1,024. Without rings
    (the plain mock batcher) a harvest is one shim batch."""
    polls = max(1, min(ring_frames // (inflight + 2), max_rows)
                // shim_batch)
    return shim_batch * (1 << (polls.bit_length() - 1))


class HarvestBuffer(dict):
    """One reusable harvest buffer: the column dict, ``harvest_ceiling``
    rows long, with its views cut once. ``segments[k]`` is rows
    [k·batch, (k+1)·batch): what the harvest's k-th poll writes.
    ``views[rows]`` is the first ``rows`` rows for every bucket a
    submission can have. ``view``, ``counts`` and ``trace_id`` describe the
    harvest the buffer holds: the view submitted, the records of each shim
    batch polled into it, in poll order (all but the last are whole
    batches, so batch k starts at row k·batch), and its trace id (None:
    unsampled).

    Its columns: what the shim's ``make_poll_buffer`` gives (the record's
    fields, ``_ep_raw``, ``_frame_idx``), and the feeder's own, each as
    long as the buffer: ``_prio`` and ``_fp`` always (the row's priority
    class and its flow fingerprint, both written by ``_map_slots``),
    ``_tenant``, ``_dns_payload`` + ``_dns_len`` and ``_shard`` where QoS,
    the DNS proxy and host RSS are armed."""

    __slots__ = ("segments", "views", "view", "counts", "trace_id")

    def cut(self, shim_batch: int, buckets: Tuple[int, ...]) -> None:
        """(Re)cut the views — after the last optional column was added."""
        rows = int(self["valid"].shape[0])
        self.segments = [{k: v[i:i + shim_batch] for k, v in self.items()}
                         for i in range(0, rows, shim_batch)]
        self.views = {b: {k: v[:b] for k, v in self.items()}
                      for b in buckets}
        self.view = self.views[buckets[-1]]
        self.counts: List[int] = []
        self.trace_id: Optional[int] = None


class ShimFeeder:
    """Harvest thread feeding one ``FlowShim`` into one engine's pipeline.

    A harvest is every shim batch the ring and the batcher give up in one
    go (up to ``harvest_rows``), in one buffer, as one submission shaped at
    the smallest of ``buckets`` that holds it; and it opens only once the
    worker has dispatched the harvest before — after a partial one, only
    once its verdicts are back (module docstring, ``_held_back``).
    ``pool_batches`` and ``poll_budget`` are ceilings, not the pace: the
    buffers that may be out at once, and the rx descriptors one round
    drains. ``inflight``, ``min_bucket`` and ``max_rows`` are the
    pipeline's (``pipeline_inflight``, its smallest and largest dispatch
    shapes); ``harvest_rows`` follows from them and the shim's rings as
    they are when the feeder is built.

    ``engine`` needs ``submit(batch, ingest_mono=..., trace_id=...) ->
    Ticket`` (the harvest's stamp and its sampling decision) and
    ``active.snapshot`` (slot mapping) — the real Engine, or any
    duck-typed stand-in in tests. A ticket without ``dispatched_mono``
    (a stand-in's) never holds a harvest back."""

    def __init__(self, shim: FlowShim, engine, *,
                 pool_batches: int = 4,
                 poll_budget: int = 256,
                 idle_sleep_s: float = 0.0005,
                 inflight: int = 2,
                 min_bucket: int = 256,
                 max_rows: Optional[int] = None,
                 n_shards: int = 1,
                 slo_ms: float = 0.0,
                 metrics: Optional[Metrics] = None,
                 tracer: Optional[Tracer] = None,
                 event_sink=None,
                 qos=None,
                 fqdn=None,
                 name: str = "feeder"):
        if poll_budget < 1:
            raise ValueError("poll_budget must be >= 1")
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if inflight < 1 or min_bucket < 1:
            raise ValueError("inflight and min_bucket must be >= 1")
        self.shim = shim
        self.engine = engine
        self.metrics = metrics if metrics is not None else Metrics()
        self.tracer = tracer if tracer is not None else TRACER
        self._poll_budget = poll_budget
        self._idle_sleep_s = idle_sleep_s
        self._n_shards = n_shards
        self._name = name
        bs = shim.batch_size
        #: rows one harvest may take, and one buffer holds
        self.harvest_rows = harvest_ceiling(
            int(getattr(shim, "ring_frames", 0)), bs, inflight,
            max(bs, max_rows if max_rows is not None else bs))
        polls = self.harvest_rows // bs
        if not 1 <= pool_batches * polls <= MAX_UNVERDICTED_BATCHES:
            raise ValueError(
                f"pool_batches x polls a harvest ({pool_batches} x {polls}) "
                f"must be in [1, {MAX_UNVERDICTED_BATCHES}] (the shim ages "
                "out unverdicted batches past that)")
        #: the row counts a submission can have, ascending: the powers of
        #: two from the pipeline's smallest bucket (or one shim batch, if
        #: that is larger) up to the ceiling. A buffer shorter than the
        #: smallest bucket is submitted whole, and the pipeline stages it
        lo = max(bs, min_bucket)
        self.buckets: Tuple[int, ...] = tuple(
            b for b in (lo << i for i in range(32))
            if b <= self.harvest_rows and b % bs == 0) \
            or (self.harvest_rows,)
        self._by_bucket = {b: tuple(
            f'feeder_{what}_total{{bucket="{b}"}}'
            for what in ("harvests", "harvest_rows", "harvest_polls"))
            for b in self.buckets}
        # guard-event sink (the flight recorder): SHED-NEW harvest drops
        # are narrated as kind="shed" reason="shed-new" events — the
        # relaxed spike class (observe/blackbox.RELAXED_SHED_REASONS).
        # Fired outside any lock, exceptions swallowed.
        self._event_sink = event_sink
        # end-to-end latency SLO: harvest stamp → verdict apply, the TRUE
        # ingest→verdict number (queue wait + staging + dispatch + device +
        # FIFO head-of-line wait). slo_ms > 0 arms the burn counters.
        self._slo_s = slo_ms / 1e3 if slo_ms > 0 else 0.0

        rows = self.harvest_rows
        # (a stand-in shim's make_poll_buffer may take no length: it is
        # only ever asked for its own batch)
        self._free: deque = deque(
            HarvestBuffer(shim.make_poll_buffer() if rows == bs
                          else shim.make_poll_buffer(rows))
            for _ in range(pool_batches))
        # overload-ladder level (set by the engine's overload controller);
        # >= SHED_NEW arms the harvest-time priority shed
        self._overload_level = 0
        # priority classing: every harvest buffer carries a ``_prio``
        # column (pipeline/guard.PRIO_*) the admission queue ranks batches
        # by; the established-flow filter below feeds class 0. Like every
        # optional column it is as long as the buffer, and a submission
        # carries the view of its first rows
        from cilium_tpu.pipeline.guard import PRIO_NEW
        for buf in self._free:
            buf["_prio"] = np.full((rows,), PRIO_NEW, dtype=np.int8)
            # the row's flow fingerprint (flow_hashes), hashed once a
            # harvest for the classing lookup and carried for every later
            # ``note``: ours at apply, the engine's salvage filter's at
            # finalize (EstablishedFingerprints)
            buf["_fp"] = np.zeros((rows,), dtype=np.uint32)
        self._est = EstablishedFingerprints()
        # multi-tenant QoS (cilium_tpu/qos): with a TenantTable armed,
        # every harvest buffer carries a ``_tenant`` column stamped at
        # harvest time from the endpoint→tenant LUT (same compiled-LUT
        # discipline as the ep-slot map below) — the admission queue's
        # weighted-fair scheduling and the per-tenant e2e/SLO families
        # key on it. QoS off: no column, zero extra work per harvest.
        self._qos = qos
        if qos is not None:
            for buf in self._free:
                buf["_tenant"] = np.zeros((rows,), dtype=np.int32)
        # in-band DNS plane (cilium_tpu/fqdn): with a DNSProxy armed,
        # every harvest buffer carries the harvested DNS response payload
        # (``_dns_payload`` [rows, W] uint8, ``_dns_len`` [rows] int32)
        # the verdict-apply tap parses into the FQDN cache. The native
        # C++ shim has no payload channel and never writes either column
        # (the tap sees len==0 everywhere and no-ops); DNS-capable shim
        # stand-ins fill both during poll_batch. Proxy off: no columns,
        # zero extra work per harvest.
        self._fqdn = fqdn
        if fqdn is not None:
            w = int(getattr(fqdn, "payload_width", 512))
            for buf in self._free:
                buf["_dns_payload"] = np.zeros((rows, w), dtype=np.uint8)
                buf["_dns_len"] = np.zeros((rows,), dtype=np.int32)
        if n_shards > 1:
            # software RSS (SURVEY §2), HOST steering mode only: harvest
            # pre-bins each record by the direction-normalized flow hash
            # so the pipeline's flush-time scatter is a plain copy, never
            # a re-hash. With ``rss_mode="device"`` the engine passes
            # n_shards=1 (the datapath's ``pipeline_shards``) and this
            # whole block — the harvest-side half of the host RSS tax —
            # disappears: the in-kernel ppermute exchange owns flow→shard
            # resolution. The column carries the SHARD_BIN encoding —
            # shard+1 in the low bits (0 = "not binned", the staging
            # ring's convention for optional ``_*`` columns) and the
            # binning policy revision above, so a bin hashed under a
            # superseded LB table is re-hashed at stage-write instead of
            # stranding a service flow's CT entry on the wrong shard —
            # and rides the same reusable harvest buffers.
            for buf in self._free:
                buf["_shard"] = np.zeros((rows,), dtype=np.int64)
        for buf in self._free:
            buf.cut(bs, self.buckets)
        self._pending: deque = deque()     # (ticket, buf) in harvest order
        self._zeros = np.zeros((bs,), dtype=bool)
        # set by the pipeline when a ticket of ours is dispatched or
        # resolves (Ticket.waker): what a held-back harvest sleeps on
        self._wake = threading.Event()
        self._held: Optional[str] = None    # what a harvest is held for
        self._hold: Optional[Tuple[int, float]] = None  # its trace, since
        self._last_full = False             # the last harvest hit the ceiling
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._rings: Optional[bool] = None  # afxdp/mock rings attached?
        self._snap = None                   # slot-lookup cache key
        self._slot_lut = np.full((1,), -1, dtype=np.int32)

        # stats (single-writer: the feeder thread; read via stats())
        self.harvested_batches = 0         # harvests, one submission each
        self.harvested_polls = 0           # shim batches they took
        self.harvested_records = 0
        self.deferred_harvests = 0         # held back for the worker
        self.applied_batches = 0
        self.rejected_batches = 0          # applied fail-closed
        self.harvest_faults = 0
        self.errors = 0                    # unexpected step failures
        self.slo_burns = 0                 # applied batches past the SLO
        self.prio_shed_rows = 0            # SHED-NEW harvest-time drops
        self.prio_shed_batches = 0         # batches never submitted at all
        self._submit_rejects = 0           # log-throttle counter

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "ShimFeeder":
        if self._thread is not None:
            return self
        self._stop.clear()      # restart after a clean stop() must harvest
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"{self._name}-harvest")
        self._thread.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        """Stop harvesting and drain: force-poll what the batcher still
        holds, then apply every pending verdict FIFO (fail closed on
        tickets that cannot resolve within ``timeout``)."""
        self._stop.set()
        self._wake.set()         # a held-back harvest sleeps on this one
        t = self._thread
        if t is not None:
            t.join(timeout)
            if t.is_alive():
                # keep the thread ref: nulling it would let a later
                # start() spawn a SECOND harvester interleaving
                # poll/apply on the same shim — frame/verdict desync
                log.warning("feeder thread did not stop within %.1fs; "
                            "keeping it registered", timeout)
                return
            self._thread = None

    def set_overload_state(self, level: int) -> None:
        """Propagate the overload-ladder level (engine's overload
        controller); >= SHED-NEW arms the harvest-time priority shed."""
        self._overload_level = int(level)

    def stats(self) -> Dict:
        t = self._thread
        e2e = self.metrics.histograms.get("ingest_e2e_latency_seconds")
        return {
            "harvested_batches": self.harvested_batches,
            "harvested_polls": self.harvested_polls,
            "harvested_records": self.harvested_records,
            "deferred_harvests": self.deferred_harvests,
            "harvest_rows": self.harvest_rows,
            "applied_batches": self.applied_batches,
            "rejected_batches": self.rejected_batches,
            "harvest_faults": self.harvest_faults,
            "errors": self.errors,
            "overload_level": self._overload_level,
            "prio_shed_rows": self.prio_shed_rows,
            "prio_shed_batches": self.prio_shed_batches,
            # rows this thread ran through flow_hashes: one a harvested row
            # where every batch carries its ``_fp``
            "flow_hash_rows": self._est.hashed_rows,
            "alive": bool(t is not None and t.is_alive()),
            "pending": len(self._pending),
            "pool_free": len(self._free),
            "slo_ms": round(self._slo_s * 1e3, 3),
            "slo_burns": self.slo_burns,
            "e2e_p50_ms": round(e2e.quantile(0.5) * 1e3, 3) if e2e else 0.0,
            "e2e_p99_ms": round(e2e.quantile(0.99) * 1e3, 3) if e2e else 0.0,
            # name -> [count, wall_s] of every span recorded since the
            # tracer's start (None with tracing off), and the CPU seconds
            # this thread has burnt (its own clock, read from here)
            "span_totals": self.tracer.totals()
            if self.tracer.enabled else None,
            "thread_cpu_s": thread_cpu_s(t),
        }

    # -- harvest loop ---------------------------------------------------------
    def _run(self) -> None:
        # supervised degradation: a failing step (e.g. engine.active
        # raising through a regen failure storm) must not kill ingestion
        # for the daemon's lifetime — count, log (throttled), retry
        while not self._stop.is_set():
            try:
                progressed = self._step(force=False)
            except Exception:   # noqa: BLE001 — keep harvesting
                progressed = False
                self._count_error("harvest step failed")
            if not progressed and not self._stop.is_set():
                if self._idle_sleep_s:
                    self._stop.wait(self._idle_sleep_s)
        try:
            self._drain()
        except Exception:   # noqa: BLE001
            log.exception("feeder drain failed")

    def _count_error(self, what: str) -> None:
        self.errors += 1
        self.metrics.inc_counter("feeder_errors_total")
        if self.errors <= 3 or self.errors % 100 == 0:
            log.exception("feeder %s (error %d); retrying", what,
                          self.errors)

    def _step(self, force: bool) -> bool:
        """One harvest iteration. Returns True when any work happened."""
        progressed = self._apply_ready(block=False)
        held = None if force else self._held_back()
        if held is not None:
            # frames wait in the ring, where this harvest will take them
            # together, and not in the queue as a batch of their own
            self._wait_for_worker(held)
            return True
        buf = self._acquire_buffer()
        if buf is None:
            return progressed            # pool exhausted and head not done
        # a harvest that was held back has coalesced in the ring for as
        # long as the worker took: it leaves with what is there instead of
        # waiting out the batcher's timeout on top
        paced, self._held = self._held, None
        if paced is not None and self._hold is not None:
            tid, t0 = self._hold
            self.tracer.record(tid, "feeder.wait", t0,
                               time.monotonic() - t0, {"for": paced},
                               kind=WAIT)
        now_us = int(time.monotonic() * 1e6)
        if self._fqdn is not None:
            # reset_batch_rows zeroes only the TAIL of optional columns and
            # the native shim never writes them: a reused buffer would
            # otherwise replay the PREVIOUS harvest's DNS payload for head
            # rows. len==0 makes stale payload bytes unreachable.
            buf["_dns_len"][:] = 0
        # one sampling decision a harvest: the id rides the submission into
        # the pipeline, so the worker's spans of it join this trace
        tid = buf.trace_id = self.tracer.maybe_sample()
        t0 = time.monotonic()
        try:
            b = self._harvest(buf, now_us, force or paced is not None)
        except FaultInjected:
            # one failed poll: frames stay queued in the ring and drain on
            # the next poll — the supervised-degradation contract
            self._count_fault()
            b = None
        except Exception:   # noqa: BLE001 — buffer must return to the pool
            self._count_error("poll failed")
            b = None
        rows, polls = sum(buf.counts), len(buf.counts)
        if tid is not None:
            self.tracer.record(
                tid, "shim.harvest", t0, time.monotonic() - t0,
                {"force": force, "rows": rows, "polls": polls,
                 "bucket": len(b["valid"]) if b else 0})
        if b is None:
            self._free.append(buf)
            return progressed
        self.harvested_batches += 1
        self.harvested_polls += polls
        self._last_full = rows >= self.harvest_rows
        m = self.metrics
        if paced is not None:
            # frames were there when the worker let this harvest go
            self.deferred_harvests += 1
            m.inc_counter("feeder_harvest_deferred_total")
            m.inc_counter(f'feeder_harvest_deferred_total{{for="{paced}"}}')
        m.inc_counter("feeder_harvest_batches_total")
        m.inc_counter("feeder_harvest_polls_total", polls)
        # how often each dispatch shape engages, and how full it leaves
        by_bucket = self._by_bucket[len(b["valid"])]
        m.inc_counter(by_bucket[0])
        m.inc_counter(by_bucket[1], rows)
        m.inc_counter(by_bucket[2], polls)
        ticket = None
        try:
            with self.tracer.span(tid, "feeder.map"):
                n_valid = self._map_slots(b)
                self.harvested_records += n_valid
                self.metrics.inc_counter("feeder_harvest_records_total",
                                         n_valid)
                from cilium_tpu.pipeline.guard import OVERLOAD_SHED_NEW
                submit = True
                if self._overload_level >= OVERLOAD_SHED_NEW:
                    # the ladder's terminal rung: only established-class
                    # rows are submitted; everything else gets its drop
                    # verdict at apply time without touching the pipeline
                    # — the rx ring's real backpressure relief. A batch
                    # shed whole rides the pending queue as the all-drop
                    # sentinel (FIFO-safe).
                    if self._shed_new(b) and not bool(b["valid"].any()):
                        submit = False
                        self.prio_shed_batches += 1
                        self.metrics.inc_counter(
                            "feeder_prio_shed_batches_total")
            # the harvest stamp rides the ticket (true ingest→verdict
            # latency; monotonic — same clock as now_us above)
            if submit:
                with self.tracer.span(tid, "feeder.submit"):
                    ticket = self.engine.submit(
                        b, ingest_mono=now_us / 1e6, trace_id=tid)
                if hasattr(ticket, "waker"):
                    ticket.waker = self._wake
        except Exception as e:   # noqa: BLE001 — unavailable/closed/
            # regen-storm engine.active/... : the shim already holds this
            # harvest's FrameRefs, so a verdict MUST be consumed for every
            # shim batch of it — but strictly AFTER the batches harvested
            # before it (apply_verdicts always consumes the OLDEST batch).
            # The rejection rides the pending queue as a ``None`` sentinel
            # and is applied all-drop in FIFO position, never out of order.
            self._submit_rejects += 1
            if self._submit_rejects <= 3 or self._submit_rejects % 100 == 0:
                # throttled: a breaker-open storm rejects at harvest rate
                log.warning("feeder submit rejected (%d), queueing "
                            "fail-closed drop verdicts: %s",
                            self._submit_rejects, e)
        self._pending.append((ticket, buf, now_us / 1e6))
        self.metrics.set_gauge("feeder_pending", len(self._pending))
        return True

    def _harvest(self, buf: HarvestBuffer, now_us: int,
                 force: bool) -> Optional[Dict[str, np.ndarray]]:
        """Take what the ring and the batcher hold into ``buf``: one round
        of ``afxdp_poll`` + ``poll_batch`` per segment, until a round
        leaves with less than a whole shim batch (the ring ran dry) or the
        buffer is full. The first round follows the batcher's own rule
        (full, timed out, or ``force``); once the harvest holds rows it
        takes the batcher's tail with it. → the submission: the view of
        the smallest bucket that holds the rows, its unpolled segments
        reset; None when nothing was ready. ``buf.counts`` has each shim
        batch's records. A poll that fails after the first round ends the
        harvest there: the shim already holds the earlier rounds'
        FrameRefs, so they leave as they are."""
        shim, counts = self.shim, buf.counts
        counts.clear()
        rings = self._rings_attached()
        for seg in buf.segments:
            try:
                if rings:
                    rc = shim.afxdp_poll(self._poll_budget, now_us=now_us)
                    if rc < 0:
                        log.debug("afxdp_poll -> %d", rc)
                got = shim.poll_batch(now_us=now_us,
                                      force=force or bool(counts), out=seg)
            except Exception as e:   # noqa: BLE001
                if not counts:
                    raise            # nothing taken yet: _step's to count
                if isinstance(e, FaultInjected):
                    self._count_fault()
                else:
                    self._count_error("poll failed mid-harvest")
                break
            if got is None:
                break
            counts.append(int(shim.last_poll_rows))
            if counts[-1] < shim.batch_size:
                break
        if not counts:
            return None
        rows = sum(counts)
        view = next((buf.views[b] for b in self.buckets if b >= rows),
                    buf.view)
        buf.view = view
        polled = len(counts) * shim.batch_size
        if polled < len(view["valid"]):
            # segments this harvest did not reach still hold an older one
            reset_batch_rows(view, polled, len(view["valid"]))
        return view

    def _held_back(self) -> Optional[str]:
        """What the next harvest has to wait for, or None. ``"dispatch"``:
        our newest submission is still undispatched (in the pipeline's
        queue, or staged and waiting for its flush; only the newest can
        be, since none is made while one is). Past that it depends on
        what the last harvest found. A full one says the ring holds more
        than a harvest can take: the next opens at once, so that the
        worker dispatches it while the device has the one before. A
        partial one says the ring ran dry, so nothing is gained by a
        second submission in flight, and it costs a dispatch of its own:
        the next waits for ``"verdicts"``, those of every harvest still
        out, and takes what came in meanwhile in one piece."""
        if not self._pending:
            return None
        t = self._pending[-1][0]
        if t is not None and not t.done() \
                and getattr(t, "dispatched_mono", 0.0) is None:
            return "dispatch"
        return None if self._last_full else "verdicts"

    def _wait_for_worker(self, what: str) -> None:
        """Sleep until the pipeline wakes us — our newest ticket dispatched,
        or one of ours resolved, whose verdicts the next step applies at
        once — or an idle-sleep has passed (the net under a wake-up that
        ``stop`` or a stand-in's ticket never sends)."""
        if self._held is None:
            # a hold begins. It is many short sleeps with the head's apply
            # between them, and one span: recorded when the harvest is
            # let go, under the trace of the harvest it waited for
            tid = self._pending[-1][1].trace_id
            self._hold = None if tid is None else (tid, time.monotonic())
        self._held = what
        # clear, look again, then sleep: a wake-up sent after the clear is
        # seen by the wait, one sent before it by the second look
        self._wake.clear()
        if self._stop.is_set() or self._held_back() != what \
                or self._pending[0][0] is None \
                or self._pending[0][0].done():
            return
        self._wake.wait(self._idle_sleep_s or 0.0005)

    def _count_fault(self) -> None:
        self.harvest_faults += 1
        self.metrics.inc_counter("feeder_harvest_faults_total")

    def _acquire_buffer(self):
        if self._free:
            return self._free.popleft()
        # pool exhausted: backpressure — block on the OLDEST ticket (FIFO),
        # bounded so stop() stays responsive
        if self._apply_ready(block=True, block_timeout=0.05):
            if self._free:
                return self._free.popleft()
        return None

    def _rings_attached(self) -> bool:
        """Whether rx/fill rings exist (AF_XDP bind or mocked); without
        them the plain feed_frame→batcher path needs no ring drain.
        Prefer the shim's own ``rings_ready`` flag: probing the fill
        LEVEL can read zero with every umem descriptor parked in the rx
        ring — exactly the state where the drain is most needed — and
        since only the drain recycles addresses, mistaking that for "no
        rings" wedges ingestion permanently (the producer sees a full rx
        ring, the harvester never looks at it). The level probe remains
        as a fallback for shim stand-ins without the flag; only a
        positive probe is cached, so rings initialized after start still
        attach."""
        if self._rings:
            return True
        ready = getattr(self.shim, "rings_ready", None)
        self._rings = bool(ready) if ready is not None \
            else self.shim.ring_fill_level() > 0
        return bool(self._rings)

    #: class-level alias (tests monkeypatch it to force the sparse path)
    DENSE_LUT_MAX = DENSE_LUT_MAX

    # -- slot mapping ---------------------------------------------------------
    def _map_slots(self, b: Dict[str, np.ndarray]) -> int:
        """Shim-ep-id → snapshot-slot mapping, in place (build_slot_lut /
        map_raw_slots — shared with the engine's dispatch-time re-map).
        Records for endpoints the snapshot doesn't know go invalid (fail
        closed). Returns the surviving valid count."""
        snap = self.engine.active.snapshot
        if snap is not self._snap:
            self._slot_lut = build_slot_lut(snap.ep_slot_of,
                                            self.DENSE_LUT_MAX)
            self._snap = snap
        slots = map_raw_slots(b["_ep_raw"], snap.ep_slot_of,
                              self._slot_lut)
        unknown = slots < 0
        b["ep_slot"][:] = np.where(unknown, 0, slots)
        b["valid"] &= ~unknown
        if "_prio" in b:
            # priority classing while the columns are hot: flows in the
            # established filter outrank new flows outrank
            # unknown-endpoint traffic (pipeline/guard.PRIO_*) — what the
            # admission queue ranks batches by under PRESSURE and the
            # SHED-NEW harvest shed keys on
            from cilium_tpu.pipeline.guard import (PRIO_ESTABLISHED,
                                                   PRIO_NEW, PRIO_UNKNOWN)
            if "_fp" in b:
                # the harvest's one hash: the lookup below reads it, and
                # so does every ``note`` of these rows, here and on the
                # worker (nothing between harvest and apply writes the
                # tuple columns)
                b["_fp"][:] = self._est.hash_rows(b)
            hit = self._est.hits(b)
            pr = np.where(hit, PRIO_ESTABLISHED, PRIO_NEW).astype(np.int8)
            pr[unknown] = PRIO_UNKNOWN
            b["_prio"][:] = pr
        if self._qos is not None and "_tenant" in b:
            # tenant identity while the ep ids are hot: endpoint → tenant
            # via the TenantTable's compiled LUT (cached on its revision
            # counter inside map_tenants — same rebuild-on-change
            # discipline as the ep-slot LUT above). Unknown endpoints
            # land on the default tenant: they must still be served,
            # they just ride the shared budget.
            b["_tenant"][:] = self._qos.map_tenants(b["_ep_raw"])
        if self._n_shards > 1:
            # pre-bin while the columns are already hot in cache: the same
            # direction-normalized hash (post-DNAT tuple) the datapath and
            # the staging ring use, revision-stamped so a regen between
            # harvest and stage-write invalidates the bin rather than
            # mis-steering it
            from cilium_tpu.parallel.mesh import flow_shard_of
            from cilium_tpu.pipeline.scheduler import shard_bin_encode
            lb = snap.lb if snap.lb.n_frontends else None
            b["_shard"][:] = shard_bin_encode(
                flow_shard_of(b, self._n_shards, lb=lb), snap.revision)
        return int(b["valid"].sum())

    def _shed_new(self, b: Dict[str, np.ndarray]) -> int:
        """SHED-NEW shed of one harvested batch (see shed_new_rows), with
        per-class attribution counters — the ``{class=...}`` label family
        operators alert on."""
        from cilium_tpu.pipeline.guard import PRIO_NEW, PRIO_UNKNOWN
        pr = np.asarray(b["_prio"])
        v = np.asarray(b["valid"])
        n_new = int((v & (pr == PRIO_NEW)).sum())
        n_unk = int((v & (pr >= PRIO_UNKNOWN)).sum())
        shed = shed_new_rows(b)
        if shed:
            self.prio_shed_rows += shed
            if n_new:
                self.metrics.inc_counter(
                    'feeder_prio_shed_rows_total{class="new"}', n_new)
            if n_unk:
                self.metrics.inc_counter(
                    'feeder_prio_shed_rows_total{class="unknown"}', n_unk)
            if self._event_sink is not None:
                try:
                    self._event_sink("shed", reason="shed-new", rows=shed)
                except Exception:   # noqa: BLE001 — observability only
                    log.exception("feeder event sink failed")
        return shed

    def _note_established(self, buf, out) -> None:
        """Feed the established-flow filter from applied verdicts: flows
        observed allowed-ESTABLISHED/REPLY stamp their fingerprint, so the
        NEXT harvest ranks them class 0 (EstablishedFingerprints — shared
        with the engine's CT-salvage grace window, which needs the exact
        same update/lookup discipline). The view carries the hashes its
        harvest computed (``_fp``), so this is a mask and a scatter. Never
        raises."""
        self._est.note(buf, out)

    # -- verdict application (FIFO) -------------------------------------------
    def _apply_ready(self, block: bool,
                     block_timeout: float = 0.0) -> bool:
        """Apply verdicts for resolved head tickets, strictly FIFO. With
        ``block`` the head ticket is awaited up to ``block_timeout``."""
        did = False
        while self._pending:
            ticket, buf, ingest_mono = self._pending[0]
            if ticket is not None and not ticket.done():
                if not block:
                    break
                try:
                    ticket.result(timeout=block_timeout)
                except TimeoutError:
                    break
                except Exception:   # noqa: BLE001 — applied below
                    pass
                block = False        # at most one blocking wait per call
            self._pending.popleft()
            self._apply_one(ticket, buf, ingest_mono=ingest_mono)
            did = True
        self.metrics.set_gauge("feeder_pending", len(self._pending))
        return did

    def _apply_one(self, ticket, buf, recycle: bool = True,
                   ingest_mono: Optional[float] = None) -> None:
        """Apply one harvest's verdicts: one ``apply_verdicts`` per shim
        batch it took, in poll order, each with its own rows
        (``ticket is None``: the rejected-at-submit sentinel — all-drop for
        every one of them, fail closed). ``recycle=False`` sheds the buffer
        instead of pooling it — for tickets that did NOT resolve: the
        pipeline may still stage from the buffer later."""
        tracer, tid = self.tracer, buf.trace_id
        with tracer.span(tid, "feeder.apply", rows=sum(buf.counts),
                         polls=len(buf.counts)):
            lat_s = self._apply_verdicts(ticket, buf, ingest_mono)
        if lat_s is not None:
            # harvest stamp -> verdicts applied, exactly what the e2e
            # histogram observed: a wait as the frames see it, whose parts
            # are the harvest's other spans
            tracer.record(tid, "feeder.roundtrip", ingest_mono, lat_s,
                          kind=WAIT)
        if recycle:
            self._free.append(buf)

    def _apply_verdicts(self, ticket, buf,
                        ingest_mono: Optional[float]) -> Optional[float]:
        """:meth:`_apply_one`'s work. → the harvest → apply latency it
        observed, None for a rejected batch or one without a stamp."""
        tid = buf.trace_id
        rejected = True
        allow = None
        lat_s = None
        view = buf.view
        if ticket is not None:
            try:
                out = ticket.result(timeout=0)
                allow = np.asarray(out["allow"])
                rejected = False
                with self.tracer.span(tid, "feeder.apply.note"):
                    self._note_established(view, out)
                if self._fqdn is not None:
                    # in-band DNS learning tap: rows whose verdict carried
                    # the DNS L7 redirect get their response payload parsed
                    # into the FQDN cache. Strictly after the verdict is
                    # computed and strictly before apply_verdicts — the
                    # proxy never raises and never touches ``allow``, so a
                    # broken parser can only lose learning, never the reply
                    # (the fail-open contract; fault point ``fqdn.parse``).
                    self._fqdn.observe_batch(view, out)
            except Exception:   # noqa: BLE001 — drop/shed/unavailable
                pass
        bs = self.shim.batch_size
        for k, n in enumerate(buf.counts):
            try:
                self.shim.apply_verdicts(
                    self._zeros if allow is None
                    else allow[k * bs:k * bs + n])
            except Exception:   # noqa: BLE001
                log.exception("apply_verdicts failed; frame/verdict FIFO "
                              "may be desynced")
        if not rejected and ingest_mono is not None:
            # verdict-apply is the END of the serving path for this batch:
            # harvest stamp → here is the true ingest→verdict latency
            lat_s = time.monotonic() - ingest_mono
            self._observe_e2e(lat_s, view)
        if rejected:
            self.rejected_batches += 1
            self.metrics.inc_counter("feeder_rejected_batches_total")
        self.applied_batches += 1
        self.metrics.inc_counter("feeder_applied_batches_total")
        return lat_s

    def _observe_e2e(self, lat_s: float, buf: Dict[str, np.ndarray]) -> None:
        """One applied batch's ingest→verdict latency into the e2e SLO
        surface: the ``ingest_e2e_latency_seconds`` histogram (plus a
        per-shard labeled family when the batch pre-binned onto a mesh) and
        the SLO burn counters when a threshold is armed.

        Attribution is BATCH-granular: every row in the batch experienced
        the same harvest→apply latency, so the batch's latency is observed
        once into each shard family that had valid rows (the latency shard
        N's rows truly saw). Under uniformly mixed harvest batches the
        per-shard series therefore move together; they become differential
        exactly when the mesh degrades asymmetrically — only the batches
        carrying the slow shard's rows stall, and that shard's family (and
        burn counter) pulls away from the rest. The unlabeled family/burn
        counts each batch once and stays the aggregate truth. Never raises
        — this rides the verdict-apply hot path."""
        try:
            self.metrics.histogram("ingest_e2e_latency_seconds").observe(
                lat_s)
            shards = ()
            if self._n_shards > 1 and "_shard" in buf:
                from cilium_tpu.pipeline.scheduler import SHARD_BIN_MASK
                # valid-masked: the buffer's padding tail carries the
                # zeroed-row flow hash, which would attribute every batch
                # to one deterministic shard that carried no traffic
                bins = (np.asarray(buf["_shard"]) & SHARD_BIN_MASK) - 1
                bins = bins[np.asarray(buf["valid"])]
                shards = np.unique(bins[(bins >= 0)
                                        & (bins < self._n_shards)])
                for s in shards:
                    self.metrics.histogram(
                        f'ingest_e2e_latency_seconds{{shard="{int(s)}"}}'
                    ).observe(lat_s)
            tnames = ()
            if self._qos is not None and "_tenant" in buf:
                # per-tenant SLO accounting, batch-granular like the
                # shard families: the batch's latency is observed once
                # into each tenant family that had valid rows — the
                # per-tenant p99 the isolation contract is gated on
                tids = np.unique(
                    np.asarray(buf["_tenant"])[np.asarray(buf["valid"])])
                tnames = [self._qos.name_of(int(t)) for t in tids]
                for tn in tnames:
                    self.metrics.histogram(
                        f'ingest_e2e_latency_seconds{{tenant="{tn}"}}'
                    ).observe(lat_s)
            if self._slo_s and lat_s > self._slo_s:
                self.metrics.inc_counter("ingest_e2e_slo_burn_total")
                for s in shards:
                    self.metrics.inc_counter(
                        f'ingest_e2e_slo_burn_total{{shard="{int(s)}"}}')
                for tn in tnames:
                    self.metrics.inc_counter(
                        f'ingest_e2e_slo_burn_total{{tenant="{tn}"}}')
                self.slo_burns += 1
        except Exception:   # noqa: BLE001
            log.exception("e2e latency observation failed")

    def _drain(self) -> None:
        """Stop-path drain: alternate force-harvesting what the batcher
        still holds with resolving + applying pending verdicts FIFO, until
        neither makes progress — a busy device can exhaust the pool
        mid-drain, so harvesting must resume after the pending sweep frees
        buffers (bounded: the producer has stopped injecting)."""
        for _round in range(2 * MAX_UNVERDICTED_BATCHES):
            harvested = False
            for _ in range(MAX_UNVERDICTED_BATCHES):
                if not self._step(force=True):
                    break
                harvested = True
            if not self._pending and not harvested:
                break
            while self._pending:
                ticket, buf, ingest_mono = self._pending.popleft()
                resolved = True
                if ticket is not None:
                    try:
                        ticket.result(timeout=10.0)
                    except TimeoutError:
                        # still owned by the (possibly wedged) pipeline:
                        # apply fail-closed for FIFO, but NEVER pool the
                        # buffer — a later stage could read it rewritten
                        resolved = False
                    except Exception:   # noqa: BLE001 — fail-closed below
                        pass
                self._apply_one(ticket, buf, recycle=resolved,
                                ingest_mono=ingest_mono)
        self.metrics.set_gauge("feeder_pending", len(self._pending))
