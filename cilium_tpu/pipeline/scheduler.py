"""Pipelined ingestion scheduler: overlapped host→device batching runtime.

BENCH_r05 showed the serving path ~30-60x off its own compute ceiling:
``compute_only`` runs ~300M flows/sec/chip while the end-to-end path sits at
6-9M, because a batch is built, transferred, and classified strictly
serially. This subsystem is the continuous-batching layer between the shim
and the datapath that closes that shape problem:

- **Admission with backpressure** (``submit``): a bounded multi-producer
  queue. When full, producers either block up to a timeout or shed
  immediately (``admission="drop"``) — never unbounded blocking, and every
  shed submission is accounted (``pipeline_admission_drops_total``).
- **Deadline-based microbatching**: sub-full submissions coalesce in a host
  staging buffer until either the buffer fills or the oldest submission's
  deadline (``flush_ms``) expires. Dispatch shapes are drawn from a small
  set of power-of-two buckets in ``[min_bucket, max_bucket]`` so the device
  sees a handful of stable shapes (no recompile storms). A submission that
  already *is* a bucket shape bypasses staging entirely (zero-copy
  ``direct`` dispatch).
- **Overlap** (double/ring-buffered staging): dispatch goes through
  ``DatapathBackend.classify_async`` — the JIT backend enqueues pack +
  transfer + XLA dispatch and returns a finalize callable, so the worker
  stages and transfers batch *i+1* while the device still computes batch
  *i* (up to ``inflight`` batches in flight; CT buffer donation sequences
  the steps on-device). On FakeDatapath classify_async is synchronous — a
  plain queue, same semantics, no overlap.
- **Ordering**: one worker drains the queue FIFO and finalizes in-flight
  batches FIFO, so CT mutation order == submission order and every ticket
  resolves in order. This is what makes pipeline verdicts bit-identical to
  the serial ``classify`` path on the same submissions.
- **Telemetry**: queue depth / inflight gauges, admission drops, flush
  reasons, fill ratio, and ``pipeline_queue_wait_seconds`` /
  ``pipeline_batch_latency_seconds`` histograms through ``Metrics``.

Overload protection & self-healing (the guard layer, ``pipeline/guard.py``):

- **Per-submission deadlines**: ``submit(deadline_ms=...)`` rides the
  ticket; the worker sheds already-stale work at ingest and at flush time
  (rejected with :class:`PipelineDeadlineExceeded`, counted per reason in
  ``pipeline_shed_total{reason}``) so a backlog never burns device time on
  answers nobody is waiting for.
- **Priority shedding** (the overload ladder's PRESSURE behavior,
  ``pipeline/guard.OverloadLadder`` — armed via
  :meth:`Pipeline.set_overload_state`): with the queue full, a submission
  that outranks the worst-priority queued one displaces it
  (``pipeline_shed_total{reason="priority"}``, FIFO-safe for everything
  that survives) — established-flow batches are never stuck behind a
  flood. Rank comes from the producer's ``_prio`` column (the shim
  feeder's established/new/unknown classes); same-class traffic keeps
  plain FIFO admission. At OVERLOAD the full queue additionally rejects
  instantly instead of blocking producers.
- **Circuit breaker**: consecutive dispatch/finalize failures past
  ``breaker_threshold`` open the breaker — submissions fail fast with
  :class:`PipelineUnavailable` instead of burning the per-submission retry
  cap against a sick backend; after ``breaker_cooldown_s`` a half-open
  probe dispatch closes it again.
- **Watchdog-supervised restart**: worker heartbeats are armed around each
  blocking dispatch/finalize call; a beat armed past ``stall_timeout_s``
  (device stall) — or a worker crash — triggers the restart protocol: the
  wedged in-flight window is rejected, the stuck thread is abandoned
  behind a generation fence (it can never touch live state again), and a
  fresh worker starts on a fresh staging ring. Queued-but-uningested
  submissions survive a restart, preserving the FIFO/bit-identical
  contract for everything that still resolves. Restarts are bounded with
  capped backoff; past ``max_restarts`` the pipeline goes *hard-failed*
  and every submission is rejected fast.
- **State**: ``stats()["state"]`` ∈ ok / breaker-open / restarting /
  failed / closed folds into ``Engine.health()``, ``healthz`` and the
  ``pipeline_state`` gauge.

Fault injection: every dispatch fires the ``pipeline.dispatch`` point and
every finalize fires ``pipeline.finalize``. ``FaultInjected`` dispatch
trips are retried with a capped backoff (counted in
``pipeline_dispatch_faults_total``) until the breaker opens — an armed
chaos scenario delays batches but never loses or reorders them. Non-fault
dispatch errors reject only the affected tickets; the pipeline keeps
serving (supervised degradation, same philosophy as the engine's regen
path). The ``hang`` fault mode stalls cooperatively inside the point —
the scenario ``make chaos`` uses to force a watchdog restart.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from cilium_tpu.kernels.records import empty_batch, reset_batch_rows
from cilium_tpu.observe.trace import (TRACER, UNDECIDED, WAIT, Tracer,
                                      thread_cpu_s)
from cilium_tpu.parallel.mesh import steer_rows
from cilium_tpu.pipeline.guard import (OVERLOAD_OVERLOAD, OVERLOAD_PRESSURE,
                                       PIPELINE_STATES, PRIO_NEW,
                                       CircuitBreaker, DeviceLost,
                                       PipelineClosed,
                                       PipelineDeadlineExceeded,
                                       PipelineDrop, PipelineError,
                                       PipelineTenantCap,
                                       PipelineUnavailable, Watchdog)
from cilium_tpu.runtime.faults import FAULTS, FaultInjected
from cilium_tpu.runtime.metrics import Metrics

log = logging.getLogger("cilium_tpu.pipeline")

#: retry caps for FaultInjected dispatch trips (the closing cap bounds
#: shutdown time when a fail-always fault is armed; the breaker usually
#: opens long before either cap is reached)
MAX_DISPATCH_RETRIES = 1000
MAX_DISPATCH_RETRIES_CLOSING = 25

#: backoff cap between watchdog restarts (seconds)
MAX_RESTART_BACKOFF_S = 5.0

#: the restart budget is a flap-stopper, not a lifetime kill switch: after
#: this long without a restart the spent budget is forgiven, so isolated
#: stalls weeks apart on a long-lived daemon never accumulate into a
#: hard-fail — only `max_restarts` restarts *within one window* do
RESTART_BUDGET_WINDOW_S = 300.0

#: the first dispatch of a worker generation may run a cold-shape XLA
#: compile inside dispatch_fn — give its heartbeat this multiple of the
#: stall timeout before the watchdog calls it a device stall, so a healthy
#: daemon's warmup can never restart-loop into hard-fail
COLD_DISPATCH_GRACE = 4

#: pre-binned ``_shard`` column encoding (written by the shim feeder, read
#: by the sharded staging ring): low bits carry shard+1 (0 = not binned),
#: high bits the policy revision the bin was hashed under — a bin from a
#: superseded revision is re-hashed at stage-write, because an LB-table
#: change moves service flows' post-DNAT steer hash (the same
#: harvest-vs-dispatch staleness class the dispatch-time ep-slot remap
#: exists for)
SHARD_BIN_SHIFT = 16
SHARD_BIN_MASK = (1 << SHARD_BIN_SHIFT) - 1
SHARD_BIN_REV_MASK = (1 << 31) - 1      # revision bits (int64 column)


def shard_bin_encode(shard: np.ndarray, revision: int) -> np.ndarray:
    """Producer-side ``_shard`` column encoding (int64): shard+1 in the
    low bits, the binning policy revision above — one definition shared
    with the feeder so writer and reader cannot drift."""
    return (np.int64((revision & SHARD_BIN_REV_MASK) << SHARD_BIN_SHIFT)
            | (shard.astype(np.int64) + 1))

# canonical out columns (the DatapathBackend.classify contract) — used to
# resolve all-invalid submissions without a device round trip
_OUT_SPEC: Tuple[Tuple[str, type, Tuple[int, ...]], ...] = (
    ("allow", bool, ()), ("reason", np.int32, ()), ("status", np.int32, ()),
    ("ct_full", bool, ()),
    ("remote_identity", np.int32, ()), ("redirect", bool, ()),
    ("svc", bool, ()), ("nat_dst", np.uint32, (4,)),
    ("nat_dport", np.int32, ()), ("rnat", bool, ()),
    ("rnat_src", np.uint32, (4,)), ("rnat_sport", np.int32, ()),
)


def _zero_out(n: int) -> Dict[str, np.ndarray]:
    return {k: np.zeros((n,) + shape, dtype=dt) for k, dt, shape in _OUT_SPEC}


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class _Superseded(BaseException):
    """Internal unwind signal: this worker's generation was replaced (the
    watchdog restarted the pipeline around it, or close() fenced it off).
    A BaseException so the supervised ``except Exception`` paths in the
    worker cannot swallow it; ``_run`` catches it and exits silently —
    the replacement already owns all state, nothing to clean up."""


class Ticket:
    """Handle for one submission. ``result()`` blocks until the pipeline
    resolved this submission's rows and returns the out dict (same row
    geometry as the submitted batch; invalid rows zero-filled, exactly like
    the serial classify path)."""

    __slots__ = ("seq", "n_rows", "n_valid", "submitted_mono", "trace_id",
                 "deadline_mono", "ingest_mono", "tenant", "dispatched_mono",
                 "waker", "_event", "_out", "_exc")

    def __init__(self, n_rows: int, n_valid: int):
        self.seq = -1                      # assigned at admission
        self.n_rows = n_rows
        self.n_valid = n_valid
        self.trace_id = None               # observe/trace sampling decision
        # tenant NAME (QoS armed only; None otherwise) — rides the ticket
        # so sheds can carry a {tenant=} label without a table lookup
        self.tenant: Optional[str] = None
        self.submitted_mono = time.monotonic()
        # when the rows actually entered the host (the shim feeder's
        # harvest stamp, monotonic seconds) — what true ingest→verdict
        # latency is measured from; None for producers that submit the
        # instant they build the batch (submitted_mono is then the truth)
        self.ingest_mono: Optional[float] = None
        self.deadline_mono: Optional[float] = None   # shed-after fence
        # when the worker handed this submission's rows to the device
        # (monotonic seconds; None while it waits in the queue or in a
        # staged microbatch). A producer that paces itself on the worker
        # (the shim feeder) reads it, and may leave an Event in ``waker``
        # that is set at that moment and again when the ticket resolves
        self.dispatched_mono: Optional[float] = None
        self.waker: Optional[threading.Event] = None
        self._event = threading.Event()
        self._out: Optional[Dict[str, np.ndarray]] = None
        self._exc: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    @property
    def dropped(self) -> bool:
        return isinstance(self._exc, PipelineDrop)

    def result(self, timeout: Optional[float] = None) -> Dict[str, np.ndarray]:
        if not self._event.wait(timeout):
            raise TimeoutError(f"pipeline ticket seq={self.seq} not resolved "
                               f"within {timeout}s")
        if self._exc is not None:
            raise self._exc
        return self._out

    # -- pipeline-internal ---------------------------------------------------
    def _resolve(self, out: Dict[str, np.ndarray]) -> None:
        self._out = out
        self._event.set()
        self._wake()

    def _reject(self, exc: BaseException) -> None:
        self._exc = exc
        self._event.set()
        self._wake()

    def _wake(self) -> None:
        w = self.waker
        if w is not None:
            w.set()


def _batch_prio(batch: Dict[str, np.ndarray]) -> int:
    """A submission's priority class: the BEST (minimum) ``_prio`` among
    its valid rows — one established-flow row is enough to outrank a
    flood batch, because shedding the batch would shed that flow with it.
    Producers without the column (control plane, tests) rank as new-flow
    traffic."""
    col = batch.get("_prio")
    if col is None:
        return PRIO_NEW
    p = np.asarray(col)[np.asarray(batch["valid"], dtype=bool)]
    return int(p.min()) if p.size else PRIO_NEW


def _batch_tenant(batch: Dict[str, np.ndarray]) -> int:
    """A submission's tenant: the DOMINANT ``_tenant`` id among its valid
    rows — a couple of stray rows must not reclassify a whole harvest
    batch onto another tenant's budget. Producers without the column
    (control plane, tests, QoS-off feeders) land on the default tenant."""
    col = batch.get("_tenant")
    if col is None:
        return 0
    t = np.asarray(col)[np.asarray(batch["valid"], dtype=bool)]
    if not t.size:
        return 0
    vals, counts = np.unique(t, return_counts=True)
    return int(vals[int(np.argmax(counts))])


class _Sub:
    """One admitted submission riding the queue. ``valid_idx`` is computed
    lazily on the worker — the direct-dispatch fast path never needs it."""

    __slots__ = ("ticket", "batch", "now", "prio", "tenant")

    def __init__(self, ticket: Ticket, batch: Dict[str, np.ndarray],
                 now: Optional[int], prio: int = PRIO_NEW, tenant: int = 0):
        self.ticket = ticket
        self.batch = batch
        self.now = now
        self.prio = prio
        self.tenant = tenant


class _Slice:
    """A submission's rows inside one dispatched bucket. ``valid_idx`` is
    None for a direct (zero-copy) dispatch: the out arrays already have the
    submission's row geometry. ``dst_rows`` (sharded staging only) lists
    the bucket rows this submission's valid rows were steered into, in
    submission order — gathering outputs through it at finalize IS the
    un-steer that keeps per-ticket verdicts in FIFO row order; unsharded
    staging packs rows contiguously from ``dst_start`` instead."""

    __slots__ = ("ticket", "valid_idx", "dst_start", "dst_rows")

    def __init__(self, ticket: Ticket, valid_idx: Optional[np.ndarray],
                 dst_start: int, dst_rows: Optional[np.ndarray] = None):
        self.ticket = ticket
        self.valid_idx = valid_idx
        self.dst_start = dst_start
        self.dst_rows = dst_rows


class _Inflight:
    __slots__ = ("finalize", "slices", "t_dispatch", "buf_idx")

    def __init__(self, finalize, slices, t_dispatch, buf_idx):
        self.finalize = finalize
        self.slices = slices
        self.t_dispatch = t_dispatch
        self.buf_idx = buf_idx


class _StageBuf:
    """One staging-ring slot: a preallocated column batch plus cached
    per-bucket prefix views, so a steady-state flush allocates nothing —
    neither columns nor the view dict handed to dispatch (the view dict
    for each power-of-two bucket is built once per buffer and reused; a
    buffer is never rewritten while its views are in flight, which is
    exactly the ring's recycle discipline).

    Sharded pipelines size the slot as ``n_shards`` per-shard segments
    (``rows = n_shards * seg_cap``): ingest scatters each valid row
    straight into its flow shard's segment, so the flushed view is already
    the steered layout the mesh wants. ``dirty`` tracks each segment's
    content high-water mark across reuses — flush restores empty-batch
    defaults only on [fill, dirty), not the whole tail, so segment resets
    stay proportional to actual traffic.

    Beside the record's columns a slot has two optional shim-side ones.
    ``_ep_raw``: the raw endpoint ids that let the dispatch-time slot
    re-mapping survive coalescing; 0 is "no raw id", so a rider without
    the column stages as 0. ``_fp``: the flow fingerprints a feeder hashed
    at harvest (shim/feeder.py), for the engine's salvage filter to read
    instead of hashing again. There every value is a real hash, so absence
    cannot be a value: ``fp_whole`` says whether every rider staged since
    the slot was opened brought the column, and the view handed to dispatch
    holds ``_fp`` only then."""

    __slots__ = ("cols", "dirty", "fp_whole", "_views")

    def __init__(self, rows: int, n_shards: int = 1):
        self.cols = empty_batch(rows)
        self.cols["_ep_raw"] = np.zeros((rows,), dtype=np.int64)
        self.cols["_fp"] = np.zeros((rows,), dtype=np.uint32)
        self.dirty: Optional[List[int]] = [0] * n_shards \
            if n_shards > 1 else None
        self.fp_whole = True
        self._views: Dict[Tuple[int, bool], Dict[str, np.ndarray]] = {}

    def view(self, bucket: int) -> Dict[str, np.ndarray]:
        fp = self.fp_whole
        v = self._views.get((bucket, fp))
        if v is None:
            v = {k: col[:bucket] for k, col in self.cols.items()
                 if fp or k != "_fp"}
            self._views[(bucket, fp)] = v
        return v


class Pipeline:
    """The scheduler. ``dispatch_fn(batch, now)`` must enqueue one batch and
    return a zero-arg finalize callable yielding the out dict — the Engine
    provides a closure over ``DatapathBackend.classify_async`` that also
    feeds metrics and the flow log.

    Producers call :meth:`submit` from any thread; one worker thread owns
    staging, dispatch, and finalization, which is what guarantees CT-order
    == submission-order. A watchdog thread supervises the worker (see the
    module docstring's guard-layer section)."""

    def __init__(self, dispatch_fn: Callable, *,
                 metrics: Optional[Metrics] = None,
                 max_bucket: int = 8192, min_bucket: int = 256,
                 queue_batches: int = 64, admission: str = "block",
                 block_timeout_s: float = 1.0, flush_ms: float = 2.0,
                 inflight: int = 2, name: str = "pipeline",
                 tracer: Optional[Tracer] = None,
                 deadline_ms: float = 0.0,
                 breaker_threshold: int = 20,
                 breaker_cooldown_s: float = 5.0,
                 stall_timeout_s: float = 30.0,
                 max_restarts: int = 3,
                 restart_backoff_s: float = 0.2,
                 n_shards: int = 1,
                 shard_fn: Optional[Callable] = None,
                 shard_headroom: int = 4,
                 shard_rev_fn: Optional[Callable[[], int]] = None,
                 mesh_shards: int = 0,
                 rss_mode: str = "host",
                 event_sink: Optional[Callable] = None,
                 qos=None,
                 lane_bucket: int = 0,
                 on_device_loss: Optional[Callable] = None):
        if max_bucket & (max_bucket - 1) or max_bucket <= 0:
            raise ValueError("max_bucket must be a power of two")
        if min_bucket & (min_bucket - 1) or not 0 < min_bucket <= max_bucket:
            raise ValueError("min_bucket must be a power of two "
                             "<= max_bucket")
        if lane_bucket and (lane_bucket & (lane_bucket - 1)
                            or not 0 < lane_bucket <= max_bucket):
            raise ValueError("lane_bucket must be 0 (lane off) or a power "
                             "of two <= max_bucket")
        if admission not in ("block", "drop"):
            raise ValueError(f"bad admission mode {admission!r}")
        if inflight < 1 or queue_batches < 1:
            raise ValueError("inflight and queue_batches must be >= 1")
        if deadline_ms < 0:
            raise ValueError("deadline_ms must be >= 0 (0 = no deadline)")
        if max_restarts < 0 or restart_backoff_s <= 0:
            raise ValueError("max_restarts must be >= 0 and "
                             "restart_backoff_s > 0")
        if n_shards < 1:
            # any positive count is a valid geometry: flow steering is
            # modulo (parallel/mesh.flow_shard_of), and a fenced re-mesh
            # leaves the serving set at n-1 survivors — a pipeline built
            # lazily (or restarted) against a degraded datapath must come
            # up at that same non-pow2 width remesh() would have adopted
            raise ValueError("n_shards must be >= 1")
        if shard_headroom < 1 or shard_headroom & (shard_headroom - 1):
            raise ValueError("shard_headroom must be a power of two >= 1")
        if n_shards > 1 and shard_fn is None:
            raise ValueError("a sharded pipeline needs shard_fn "
                             "(per-row flow-shard ids)")
        if rss_mode not in ("host", "device"):
            raise ValueError(f"bad rss_mode {rss_mode!r} (host | device)")
        if rss_mode == "device" and n_shards > 1:
            # device RSS deletes host steering by definition: steered
            # (per-shard-segment) staging under it would reintroduce the
            # very scatter the ppermute exchange retires
            raise ValueError("rss_mode='device' stages unsharded "
                             "(n_shards must be 1; pass the mesh size via "
                             "mesh_shards)")
        self._dispatch_fn = dispatch_fn
        # the serving mesh behind this pipeline, for the per-mesh guard
        # surface: with device-side RSS the staging ring is UNSHARDED
        # (n_shards == 1 — row order carries no placement semantics) but
        # one watchdog/breaker generation still fences mesh_shards chips
        self._mesh_shards = mesh_shards if mesh_shards > 0 else n_shards
        self._rss_mode = rss_mode
        # sharded staging (the software-RSS half of the multi-chip path):
        # each staging slot holds n_shards per-shard segments of seg_cap
        # rows; ingest steers rows into their segment, flush dispatches the
        # ONE steered shape [n_shards * seg_cap] every time (a single XLA
        # trace per wire format — sharded serving trades padded transfer
        # bytes for zero recompile storms). seg_cap carries
        # `shard_headroom`x the
        # even-split share so hash skew doesn't force tiny aggregates; a
        # submission more skewed than that is shed ("steer_overflow"),
        # never a worker-killing error.
        self._n_shards = n_shards
        self._shard_fn = shard_fn
        self._shard_rev_fn = shard_rev_fn
        # kept as an attr (unlike the other ctor-only sizing inputs):
        # remesh() recomputes seg_cap/stage_rows for the survivor count
        self._shard_headroom = shard_headroom
        # mesh self-healing (ISSUE 19): a DeviceLost dispatch parks this
        # worker (queue survives) and notifies the engine via the callback;
        # Pipeline.remesh() is the fenced geometry swap that un-parks. With
        # no handler wired (bare pipelines, tests) DeviceLost degrades to
        # the generic dispatch-error path — behavior identical to pre-19.
        self._on_device_loss = on_device_loss
        self._device_lost: Optional[int] = None
        # a freshly restarted/re-meshed generation proves the device path
        # with a 1-row synthetic dispatch before serving real traffic
        self._canary_pending = False
        if n_shards > 1:
            self._seg_cap = min(max_bucket, _next_pow2(
                max(1, max_bucket // n_shards) * shard_headroom))
            self._stage_rows = n_shards * self._seg_cap
        else:
            self._seg_cap = 0
            self._stage_rows = max_bucket
        self._shard_fill: List[int] = [0] * n_shards
        # lifetime per-shard ingest totals: the steering-balance surface
        # (tests and operators read skew from here)
        self._shard_rows_total: List[int] = [0] * n_shards
        # the policy revision the staged bucket was steered under (-2 =
        # riders steered under different revisions): rides into
        # dispatch_fn so the engine can detect a regen landing between
        # stage-write and dispatch and have the datapath RE-steer under
        # the snapshot it actually classifies with — an LB change moves
        # service flows' post-DNAT hash, and dispatching a stale steer
        # would strand their CT entries on the wrong shard
        self._stage_steer_rev: Optional[int] = None
        self._shard_gauge_names = [
            f'pipeline_staged_rows{{shard="{s}"}}'
            for s in range(n_shards)] if n_shards > 1 else []
        self.metrics = metrics if metrics is not None else Metrics()
        self.tracer = tracer if tracer is not None else TRACER
        # guard-event sink (the flight recorder, observe/blackbox.py):
        # breaker transitions, watchdog restarts and sheds are narrated to
        # it so an anomaly freezes with its lead-up intact. Fired outside
        # the pipeline lock, exceptions swallowed — a broken recorder can
        # never take the worker down
        self._event_sink = event_sink
        self._max_bucket = max_bucket
        self._min_bucket = min_bucket
        self._queue_max = queue_batches
        self._admission = admission
        self._block_timeout_s = block_timeout_s
        self._flush_s = flush_ms / 1e3
        self._inflight_max = inflight
        self._default_deadline_s = deadline_ms / 1e3 if deadline_ms else None
        self._name = name

        # overload-ladder level (pipeline/guard.OverloadLadder, propagated
        # by the engine's overload controller; plain-int writes are atomic
        # under the GIL). >= PRESSURE arms priority shedding at admission;
        # >= OVERLOAD additionally fails admission fast (no blocking waits
        # — a saturated queue under overload must push backpressure to the
        # producer immediately, not park its threads)
        self._overload_level = 0

        # multi-tenant QoS (cilium_tpu/qos): when a TenantTable is passed
        # the admission queue becomes per-tenant weighted-fair (DRR); with
        # qos=None the queue is the plain FIFO deque — byte-identical to
        # the pre-QoS pipeline, which is what keeps the default-off
        # contract trivially true
        self._qos = qos
        self._lane_bucket = lane_bucket if qos is not None else 0

        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        if qos is not None:
            from cilium_tpu.qos.wfq import TenantQueues
            self._queue = TenantQueues(qos, quantum_rows=max_bucket,
                                       lane_rows=self._lane_bucket)
        else:
            self._queue = deque()
        self._outstanding = 0            # accepted tickets not yet resolved
        self._drain_req = 0
        self._closing = False
        self._closed = False
        self._next_seq = 0

        # guard state (generation fence + restart budget)
        self._gen = 0                    # current worker generation
        self._worker_gen = 0             # generation self._worker runs
        self._restarts = 0
        self._last_restart_mono = 0.0
        self._max_restarts = max_restarts
        self._restart_backoff_s = restart_backoff_s
        self._restarting = False
        self._failed = False             # hard-failed: restart budget spent
        self._cold_dispatch = True       # this gen has not dispatched yet
        #: armed heartbeat: (armed_mono, label, gen, stall multiplier)
        self._hb: Optional[Tuple[float, str, int, int]] = None

        # worker-owned (no lock): staging ring + inflight window
        self._buffers = [_StageBuf(self._stage_rows, n_shards)
                         for _ in range(inflight + 1)]
        self._free_bufs: List[int] = list(range(len(self._buffers)))
        self._stage_buf: Optional[int] = None
        self._staged_rows = 0
        self._staged_slices: List[_Slice] = []
        self._stage_deadline = 0.0
        self._stage_now: Optional[int] = None
        self._inflight: deque = deque()
        self._current: Optional[_Sub] = None   # popped, mid-_ingest
        self._dispatching: List[_Slice] = []   # handed to _dispatch, not
        self._finalizing: Optional[_Inflight] = None   # ... yet inflight

        # stats. submitted/admission_drops/shed mutate under self._lock;
        # the worker-owned counters are mirrored into the _pub snapshot
        # (also under the lock) so stats() never does a cross-thread
        # unsynchronized read of in-flux worker state
        self.submitted = 0
        self.admission_drops = 0
        self.dispatched_batches = 0
        self.completed_batches = 0
        self.dispatch_faults = 0
        self.dispatch_errors = 0
        self.shed_total = 0
        self.shed_reasons: Dict[str, int] = {}
        self.unavailable_total = 0
        self.flush_reasons: Dict[str, int] = {
            "direct": 0, "full": 0, "deadline": 0, "drain": 0, "lane": 0}
        self._fill_rows = 0
        self._bucket_rows = 0
        # latency-lane fill accounting (reason="lane" dispatches only) —
        # the autotuner's lane/bulk arbitration signal
        self._lane_fill_rows = 0
        self._lane_bucket_rows = 0
        self._pub: Dict = {}             # worker-published stats snapshot

        if self._mesh_shards > 1:
            # the guard runs per-mesh: one breaker/watchdog generation
            # fences ALL shards together (a wedged shard must never yield
            # half-mesh verdicts), and the gauge says how many chips one
            # restart takes down — true for steered AND device-RSS meshes
            # (device mode stages unsharded but one dispatch still covers
            # every chip)
            self.metrics.set_gauge("pipeline_mesh_shards",
                                   self._mesh_shards)
            self._hb_dispatch_label = f"dispatch[mesh={self._mesh_shards}]"
            self._hb_finalize_label = f"finalize[mesh={self._mesh_shards}]"
        else:
            self._hb_dispatch_label = "dispatch"
            self._hb_finalize_label = "finalize"
        self.breaker = CircuitBreaker(
            breaker_threshold, breaker_cooldown_s, metrics=self.metrics,
            tracer=self.tracer, name=name,
            on_transition=self._on_breaker_transition)
        self._watchdog = Watchdog(
            stall_timeout_s=stall_timeout_s,
            heartbeat=lambda: self._hb,
            on_stall=self._restart_worker,
            should_stop=lambda: self._closed or self._failed,
            name=name)

        self._worker = threading.Thread(target=self._run, args=(0,),
                                        daemon=True, name=f"{name}-worker")
        self._worker.start()
        self._watchdog.start()

    # -- producer side -------------------------------------------------------
    def submit(self, batch: Dict[str, np.ndarray],
               now: Optional[int] = None,
               timeout: Optional[float] = None,
               deadline_ms: Optional[float] = None,
               ingest_mono: Optional[float] = None,
               trace_id: Optional[int] = UNDECIDED) -> Ticket:
        """Admit one batch (records layout, ``valid``-masked). Returns a
        :class:`Ticket` immediately; with ``admission="drop"`` (or a blocked
        admission that times out) the ticket comes back already rejected
        with :class:`PipelineDrop` — check ``ticket.dropped``.

        ``deadline_ms`` (default: the pipeline-wide ``deadline_ms``, 0 =
        none) bounds how stale this submission may get: work the worker
        cannot reach/dispatch before the deadline is shed with
        :class:`PipelineDeadlineExceeded` instead of burning device time.
        Raises :class:`PipelineUnavailable` (fail fast, no queueing) while
        the circuit breaker is open or the pipeline is hard-failed.
        ``trace_id``: a producer that has drawn its own sampling decision
        (the feeder, once a harvest) hands it in, an id or None for "not
        sampled", and the submission's spans join that trace; left
        ``UNDECIDED`` the decision is drawn here.

        The caller must not mutate ``batch`` until the ticket resolves (the
        staging copy happens on the worker; a direct-dispatch batch is read
        by the flow log at finalize time)."""
        valid = np.asarray(batch["valid"])
        n_valid = int(valid.sum())
        if n_valid > self._max_bucket:
            raise ValueError(
                f"submission has {n_valid} valid rows > max_bucket "
                f"{self._max_bucket}; split it or raise batch_size")
        if self._failed:
            self._count_unavailable()
            raise PipelineUnavailable(
                f"pipeline hard-failed after {self._restarts} worker "
                "restarts; no new submissions")
        if not self.breaker.admit():
            self._count_unavailable()
            raise PipelineUnavailable(
                "circuit breaker open after consecutive dispatch failures; "
                f"retry in {self.breaker.stats().get('retry_in_s', 0.0)}s")
        ticket = Ticket(n_rows=int(valid.shape[0]), n_valid=n_valid)
        # the harvest stamp rides the ticket so verdict-apply can compute
        # TRUE ingest→verdict latency (queue wait alone measures only the
        # pipeline's share of the 30-60x compute-vs-end-to-end gap)
        ticket.ingest_mono = ingest_mono
        dl = self._default_deadline_s if deadline_ms is None \
            else (deadline_ms / 1e3 if deadline_ms > 0 else None)
        if dl is not None:
            ticket.deadline_mono = ticket.submitted_mono + dl
        # the sampling decision is made once per submission and rides the
        # ticket; unsampled submissions pay exactly one counter draw, here
        # or at the producer that handed its own in
        ticket.trace_id = self.tracer.maybe_sample() \
            if trace_id == UNDECIDED else trace_id
        deadline = time.monotonic() + (
            self._block_timeout_s if timeout is None else timeout)
        prio = _batch_prio(batch)
        tenant = 0
        if self._qos is not None:
            # classify-time tenant derivation is a guarded shed path
            # (fault point "qos.enqueue"): if it faults, the ticket fails
            # CLOSED onto the default-tenant FIFO class — served, just
            # without a private budget — and the producer thread survives
            try:
                FAULTS.fire("qos.enqueue")
                tenant = _batch_tenant(batch)
            except FaultInjected:
                self.metrics.inc_counter("qos_enqueue_failsafe_total")
                tenant = 0
            ticket.tenant = self._qos.name_of(tenant)
        victim: Optional[_Sub] = None
        try:
            with self._lock:
                if self._closing or self._closed:
                    raise PipelineClosed("pipeline is closed")
                if self._failed:
                    # re-check under the lock: a hard-fail landing between
                    # the unlocked check above and here must not enqueue a
                    # ticket nothing will ever serve
                    self._count_unavailable_locked()
                    raise PipelineUnavailable(
                        f"pipeline hard-failed after {self._restarts} "
                        "worker restarts; no new submissions")
                qs = self._queue if self._qos is not None else None
                while True:
                    qfull = len(self._queue) >= self._queue_max
                    # per-tenant occupancy cap (QoS only): the tenant is
                    # at its OWN budget even if the shared queue has room
                    # — it waits/sheds against that budget, never spending
                    # the other tenants' headroom
                    tcap = qs is not None and qs.over_cap(tenant)
                    if not qfull and not tcap:
                        break
                    if qfull and not tcap and victim is None \
                            and self._overload_level >= OVERLOAD_PRESSURE:
                        # priority shedding (the degradation ladder's
                        # PRESSURE behavior): a full queue sheds its
                        # WORST-ranked submission in favor of a
                        # better-ranked newcomer — established-flow
                        # batches displace flood batches instead of
                        # queueing behind them. Same-class traffic keeps
                        # the plain FIFO admission below. With QoS armed
                        # the scan is tenant-scoped: the worst-PRESSURE
                        # tenant (queue depth over weight) sheds first,
                        # and within the submitter's own tenant the old
                        # strictly-worse-class contract still holds. The
                        # scan is gated on `not tcap`: a submitter at its
                        # own cap gains nothing from displacing someone
                        # else, so no victim is removed it cannot use —
                        # and once one IS removed we break unconditionally
                        # (the lock is held throughout, so the just-
                        # checked cap cannot have changed) straight to
                        # the enqueue below: no loop exit can strand an
                        # already-removed victim.
                        victim = (self._queue.priority_victim(prio, tenant)
                                  if qs is not None
                                  else self._priority_victim_locked(prio))
                        if victim is not None:
                            self._queue.remove(victim)
                            self.metrics.set_gauge("pipeline_queue_depth",
                                                   len(self._queue))
                            break
                    remaining = deadline - time.monotonic()
                    # OVERLOAD fail-fast is tenant-scoped under QoS: only
                    # a tenant at-or-over its weight share of the queue is
                    # instant-rejected; a within-budget tenant still gets
                    # the blocking wait (its backlog is someone else's
                    # flood)
                    fail_fast = self._overload_level >= OVERLOAD_OVERLOAD \
                        and (qs is None or qs.over_share(tenant))
                    if self._admission == "drop" or remaining <= 0 \
                            or fail_fast:
                        if tcap and not qfull:
                            # the tenant's own cap is the binding
                            # constraint: this is a shed against its
                            # private budget, not a shared-queue
                            # admission drop
                            self.shed_total += 1
                            self.shed_reasons["tenant_cap"] = \
                                self.shed_reasons.get("tenant_cap", 0) + 1
                            self.metrics.inc_counter(
                                'pipeline_shed_total'
                                '{reason="tenant_cap"}')
                            self.metrics.inc_counter(
                                f'pipeline_shed_total{{reason="tenant_cap"'
                                f',tenant="{ticket.tenant}"}}')
                            ticket._reject(PipelineTenantCap(
                                f"tenant {ticket.tenant!r} at its "
                                f"occupancy cap "
                                f"({qs.table.cap_of(tenant)} batches); "
                                f"admission={self._admission}"))
                            return ticket
                        self.admission_drops += 1
                        # the unlabeled family counts EVERY drop — QoS on
                        # or off — so pre-QoS dashboards/alerts keep
                        # working when QoS is armed; the tenant-labeled
                        # family rides alongside it (the shard-metrics
                        # discipline), never instead of it
                        self.metrics.inc_counter(
                            "pipeline_admission_drops_total")
                        if ticket.tenant is not None:
                            self.metrics.inc_counter(
                                f'pipeline_admission_drops_total'
                                f'{{tenant="{ticket.tenant}"}}')
                        ticket._reject(PipelineDrop(
                            f"queue full ({self._queue_max} batches); "
                            f"admission={self._admission}"
                            + (", overload fail-fast"
                               if fail_fast else "")))
                        return ticket
                    self._cond.wait(min(remaining, 0.05))
                    if self._closing or self._closed:
                        raise PipelineClosed("pipeline closed while "
                                             "blocked at admission")
                    if self._failed:
                        # hard-fail swept the queue out from under us; the
                        # freed capacity must not admit work nothing will
                        # serve
                        self._count_unavailable_locked()
                        raise PipelineUnavailable(
                            "pipeline hard-failed while blocked at "
                            "admission")
                ticket.seq = self._next_seq
                self._next_seq += 1
                self._queue.append(_Sub(ticket, batch, now, prio=prio,
                                        tenant=tenant))
                self.submitted += 1
                self._outstanding += 1
                self.metrics.set_gauge("pipeline_queue_depth",
                                       len(self._queue))
                self._cond.notify_all()
        finally:
            if victim is not None:
                # settle OUTSIDE the lock (_shed takes it; the `with`
                # block has exited by the time `finally` runs). A removed
                # victim settles on EVERY exit path — the normal enqueue,
                # the reject returns, and the closed/hard-fail raises —
                # or its producer would block forever on a ticket nothing
                # owns and _outstanding would never drain. A racing sweep
                # dedupes through ticket.done().
                self._shed(victim.ticket, "priority", PipelineDrop(
                    f"priority shed: displaced by a class-{prio} "
                    f"submission under overload state "
                    f"{self._overload_level} "
                    f"(seq={victim.ticket.seq}, class={victim.prio})"))
        return ticket

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every accepted submission so far has resolved
        (flushes any staged microbatch immediately — ``drain`` flush
        reason). Returns False on timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            self._drain_req += 1
            self._cond.notify_all()
            try:
                while self._outstanding > 0:
                    remaining = None if deadline is None else \
                        deadline - time.monotonic()
                    if remaining is not None and remaining <= 0:
                        return False
                    self._cond.wait(remaining if remaining is None
                                    else min(remaining, 0.1))
            finally:
                self._drain_req -= 1
                self._cond.notify_all()
        return True

    def close(self, timeout: Optional[float] = None) -> None:
        """Clean shutdown: stop admitting, process everything already
        queued/staged/in flight, then stop the worker. If the worker does
        not stop within ``timeout`` (wedged in a device call) it is fenced
        off behind a generation bump and every outstanding ticket is
        swept and rejected — close() never strands a waiter. Idempotent."""
        with self._lock:
            if self._closed and not self._worker.is_alive():
                return
            self._closing = True
            self._cond.notify_all()
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._lock:
                if self._closed:
                    break       # the watchdog's shutdown sweep beat us
                if self._failed or self._worker_gen != self._gen:
                    # the current worker object is fenced (hard-fail, or a
                    # restart aborted mid-backoff): it will never drain —
                    # stop waiting and let the sweep below settle leftovers
                    break
                worker = self._worker
            # lap-join, never an unbounded join: a worker wedged in a
            # device call would otherwise block close(timeout=None)
            # forever — the watchdog fences it at stall_timeout and sets
            # _closed, which the lap re-check above observes
            remaining = None if deadline is None else \
                max(0.0, deadline - time.monotonic())
            worker.join(0.2 if remaining is None else min(0.2, remaining))
            with self._lock:
                if not worker.is_alive() and worker is self._worker:
                    break       # clean exit, no restart swapped it
            if deadline is not None and time.monotonic() >= deadline:
                break           # out of budget; sweep below
        stranded: List[Ticket] = []
        with self._lock:
            self._closed = True
            wedged = self._worker.is_alive()
            if wedged or self._outstanding > 0:
                # the worker is stuck in a device call (or a restart was
                # aborted mid-backoff with work still queued): fence it off
                # and sweep — a fenced worker that later wakes sees a stale
                # generation and exits without touching live state
                self._gen += 1
                stranded = self._collect_wedged_locked(include_queue=True)
            self._cond.notify_all()
        if stranded:
            log.warning(
                "pipeline close: worker %s; rejecting %d outstanding "
                "ticket(s)", "did not stop within timeout" if wedged
                else "already gone with work queued", len(stranded))
            self._settle([(t, None, PipelineError(
                "pipeline closed before this submission resolved"))
                for t in stranded])
        # departed-subject gauge sweep (ISSUE 13): a closed pipeline's
        # per-shard staged-rows series would otherwise export their last
        # fills forever — and after a mesh resize (engine restarted onto a
        # different shard count) the old shard labels would pin a gauge no
        # live structure backs. Same drop_gauge sweep departed clustermesh
        # peers and deregistered ledger resources get.
        for name in self._shard_gauge_names:
            self.metrics.drop_gauge(name)

    # -- runtime-tunable knobs (observe/autotune.py + chaos consumers) --------
    @property
    def flush_ms(self) -> float:
        return self._flush_s * 1e3

    @property
    def min_bucket(self) -> int:
        return self._min_bucket

    @property
    def max_bucket(self) -> int:
        return self._max_bucket

    @property
    def stall_timeout_s(self) -> float:
        return self._watchdog.stall_timeout_s

    def set_flush_ms(self, flush_ms: float) -> None:
        """Retarget the microbatch coalesce deadline (applies to the next
        staged submission; an already-armed deadline keeps its anchor)."""
        if flush_ms <= 0:
            raise ValueError("flush_ms must be > 0")
        with self._lock:
            self._flush_s = flush_ms / 1e3
            self._cond.notify_all()     # re-evaluate a parked deadline wait

    def set_min_bucket(self, min_bucket: int) -> None:
        """Move the smallest dispatch shape (the bucket-set floor)."""
        if min_bucket & (min_bucket - 1) or \
                not 0 < min_bucket <= self._max_bucket:
            raise ValueError("min_bucket must be a power of two "
                             "<= max_bucket")
        with self._lock:
            self._min_bucket = min_bucket

    @property
    def lane_bucket(self) -> int:
        return self._lane_bucket

    def set_lane_bucket(self, lane_bucket: int) -> None:
        """Move the latency lane's dispatch shape (the always-armed small
        bucket lane-tenant submissions flush at). 0 disarms the lane;
        the autotuner arbitrates it within [its floor, min_bucket]."""
        if lane_bucket and (lane_bucket & (lane_bucket - 1)
                            or not 0 < lane_bucket <= self._max_bucket):
            raise ValueError("lane_bucket must be 0 or a power of two "
                             "<= max_bucket")
        with self._lock:
            self._lane_bucket = lane_bucket if self._qos is not None else 0
            if self._qos is not None:
                # keep the DRR's lane-bypass threshold in lockstep with
                # the lane's dispatch shape
                self._queue.lane_rows = self._lane_bucket

    def set_stall_timeout_s(self, stall_timeout_s: float) -> None:
        """Retarget the watchdog's stall budget (e.g. widen it before a
        cold dispatch that will JIT-compile, shrink it in chaos drills)."""
        if stall_timeout_s <= 0:
            raise ValueError("stall_timeout_s must be > 0")
        self._watchdog.stall_timeout_s = stall_timeout_s

    # -- introspection --------------------------------------------------------
    def state(self) -> str:
        with self._lock:
            return self._state_locked()

    def _state_locked(self) -> str:
        if self._failed:
            return "failed"
        if self._closed or self._closing:
            return "closed"
        if self._restarting:
            return "restarting"
        if self._device_lost is not None:
            return "device-lost"
        if self.breaker.state != "closed":
            return "breaker-open"
        return "ok"

    def occupancy_stats(self) -> Dict:
        """The bounded-structure subset of :meth:`stats` for the resource
        ledger's per-poll sweep — no histogram quantile math, one lock
        acquisition (the <2% ledger-polling attestation is the budget)."""
        with self._lock:
            pub = self._pub
            return {
                "queue_depth": len(self._queue),
                "queue_max": self._queue_max,
                "n_shards": self._n_shards,
                "mesh_shards": self._mesh_shards,
                "rss_mode": self._rss_mode,
                # aggregate staging rows: n_shards * seg_cap when sharded
                # (seg_cap carries headroom, so this exceeds max_bucket)
                "stage_rows": self._stage_rows,
                "shard_capacity": self._seg_cap,
                "shard_fill": list(pub.get("shard_fill",
                                           [0] * self._n_shards)),
                "staged_rows": pub.get("staged_rows", 0),
                "staging_free": pub.get("staging_free",
                                        self._inflight_max + 1),
                "staging_slots": pub.get("staging_slots",
                                         self._inflight_max + 1),
                # active per-tenant queue occupancy (QoS armed only):
                # {name: (cap_batches, queued_batches)} for the ledger's
                # qos_tenant_queue_* rows
                **({"tenants": self._queue.occupancy_by_name()}
                   if self._qos is not None else {}),
            }

    def stats(self) -> Dict:
        with self._lock:
            queue_depth = len(self._queue)
            outstanding = self._outstanding
            pub = dict(self._pub)
            state = self._state_locked()
            restarts = self._restarts
            submitted = self.submitted
            admission_drops = self.admission_drops
            shed_total = self.shed_total
            shed_reasons = dict(self.shed_reasons)
            unavailable = self.unavailable_total
            tenants = (self._queue.stats() if self._qos is not None
                       else None)
        qw = self.metrics.histograms.get("pipeline_queue_wait_seconds")
        flush_reasons = pub.get("flush_reasons") or dict(self.flush_reasons)
        fill_rows = pub.get("fill_rows", 0)
        bucket_rows = pub.get("bucket_rows", 0)
        return {
            "state": state,
            "submitted": submitted,
            "outstanding": outstanding,
            "queue_depth": queue_depth,
            "queue_max": self._queue_max,
            "overload_level": self._overload_level,
            "n_shards": self._n_shards,
            # the mesh behind this pipeline + where RSS runs: with
            # rss_mode="device" n_shards is 1 (unsharded staging) while
            # mesh_shards still names the chips one guard fence covers
            "mesh_shards": self._mesh_shards,
            "rss_mode": self._rss_mode,
            **({"shard_capacity": self._seg_cap,
                "shard_fill": pub.get("shard_fill",
                                      [0] * self._n_shards),
                "shard_rows_total": pub.get("shard_rows_total",
                                            [0] * self._n_shards)}
               if self._n_shards > 1 else {}),
            "staged_rows": pub.get("staged_rows", 0),
            "inflight": pub.get("inflight", 0),
            "staging_free": pub.get("staging_free",
                                    self._inflight_max + 1),
            "staging_slots": pub.get("staging_slots",
                                     self._inflight_max + 1),
            "admission_drops": admission_drops,
            "dispatched_batches": pub.get("dispatched_batches",
                                          self.dispatched_batches),
            "completed_batches": pub.get("completed_batches",
                                         self.completed_batches),
            # monotone ints bumped mid-retry-loop: the attr is always
            # current, the published snapshot only moves on batch
            # boundaries — read the live value
            "dispatch_faults": self.dispatch_faults,
            "dispatch_errors": self.dispatch_errors,
            "flush_reasons": flush_reasons,
            "fill_rows": fill_rows,
            "bucket_rows": bucket_rows,
            # rows whose verdicts are back (every finalize folds its batch
            # into the shared registry), by what LB and LPM made of them
            "verdict_rows": self.metrics.verdict_rows(),
            # name -> [count, wall_s] of every span recorded since the
            # tracer's start (None with tracing off), and the CPU seconds
            # the worker's thread has burnt (its own clock, read from
            # here): what a reader takes at a window's two ends
            "span_totals": self.tracer.totals()
            if self.tracer.enabled else None,
            "thread_cpu_s": thread_cpu_s(self._worker),
            "shed_total": shed_total,
            "shed_reasons": shed_reasons,
            "unavailable_total": unavailable,
            "restarts": restarts,
            "max_restarts": self._max_restarts,
            "stall_timeout_s": self._watchdog.stall_timeout_s,
            "breaker": self.breaker.stats(),
            "flush_ms": self.flush_ms,
            "min_bucket": self._min_bucket,
            # multi-tenant QoS surface (absent when QoS is off, so the
            # QoS-off stats doc is byte-identical to the pre-QoS one)
            **({"tenants": tenants,
                "lane_bucket": self._lane_bucket,
                "lane_fill_rows": pub.get("lane_fill_rows", 0),
                "lane_bucket_rows": pub.get("lane_bucket_rows", 0)}
               if tenants is not None else {}),
            "fill_ratio_avg": round(fill_rows / max(1, bucket_rows), 4),
            "queue_wait_p50_ms": round(qw.quantile(0.5) * 1e3, 3)
            if qw else 0.0,
            "queue_wait_p99_ms": round(qw.quantile(0.99) * 1e3, 3)
            if qw else 0.0,
            "closed": self._closed or self._closing,
        }

    # -- guard plumbing -------------------------------------------------------
    def set_overload_state(self, level: int) -> None:
        """Propagate the overload-ladder level (engine's overload
        controller). Level semantics live in pipeline/guard.py."""
        self._overload_level = int(level)
        with self._lock:
            self._cond.notify_all()   # blocked producers re-evaluate

    def _priority_victim_locked(self, incoming_prio: int) -> Optional[_Sub]:
        """Lock held: the queued submission a better-ranked newcomer may
        displace — the worst priority class in the queue, newest first
        (shedding the freshest flood batch preserves the most FIFO
        history). None when nothing ranks strictly worse than the
        newcomer."""
        worst: Optional[_Sub] = None
        for sub in self._queue:
            if worst is None or sub.prio >= worst.prio:
                worst = sub
        if worst is not None and worst.prio > incoming_prio:
            return worst
        return None

    def _count_unavailable(self) -> None:
        with self._lock:
            self._count_unavailable_locked()

    def _count_unavailable_locked(self) -> None:
        self.unavailable_total += 1
        self.metrics.inc_counter("pipeline_unavailable_total")

    def _emit(self, kind: str, **attrs) -> None:
        sink = self._event_sink
        if sink is None:
            return
        try:
            sink(kind, **attrs)
        except Exception:   # noqa: BLE001 — the sink is observability-only
            log.exception("pipeline event sink failed for %r", kind)

    def _on_breaker_transition(self, old: str, new: str) -> None:
        self._set_state_gauge()
        self._emit("breaker", old=old, new=new)

    def _set_state_gauge(self) -> None:
        self.metrics.set_gauge("pipeline_state",
                               PIPELINE_STATES.get(self.state(), -1))

    def _hb_arm(self, label: str, gen: int, grace: int = 1) -> None:
        # tuple assignment is atomic under the GIL; the watchdog reads it
        self._hb = (time.monotonic(), label, gen, grace)

    def _hb_clear(self, gen: int) -> None:
        # gen-checked: a fenced-off worker waking from a stall must not
        # clear the REPLACEMENT worker's armed heartbeat
        hb = self._hb
        if hb is not None and hb[2] == gen:
            self._hb = None

    def _stale(self, gen: int) -> bool:
        return self._gen != gen

    def _check_gen(self, gen: int) -> None:
        """Raise the unwind signal when this worker has been superseded.
        Called after every return from a blocking call — a fenced-off
        worker must never touch live scheduler state again."""
        if self._gen != gen:
            raise _Superseded()

    def _settle(self, outcomes) -> None:
        """The single resolution path: ``outcomes`` is a sequence of
        ``(ticket, out_or_None, exc_or_None)``. Settles each not-yet-done
        ticket and adjusts ``_outstanding`` for exactly the tickets that
        transitioned — under the lock, so a watchdog sweep racing a waking
        worker can never double-resolve or double-count."""
        with self._lock:
            n = 0
            for ticket, out, exc in outcomes:
                if ticket.done():
                    continue
                if exc is not None:
                    ticket._reject(exc)
                else:
                    ticket._resolve(out)
                n += 1
            self._outstanding -= n
            # drain waiters only care about reaching zero; producers are
            # woken by the queue pop — skip the per-batch thundering herd
            if self._outstanding == 0 or self._closing:
                self._cond.notify_all()

    def _collect_wedged_locked(self, include_queue: bool) -> List[Ticket]:
        """Lock held. Gather every ticket the (dead/wedged) worker owned —
        mid-ingest sub, staged slices, a dispatch/finalize in progress, the
        whole in-flight window, optionally the queue — and reset the
        worker-owned state to a fresh staging ring."""
        # read registries in DATA-FLOW order (current -> staged ->
        # dispatching -> inflight -> finalizing): every worker hand-off
        # adds to the destination before removing from the source, so a
        # ticket mid-hand-off is seen in the source, the destination, or
        # both — never in neither. (queue->_current happens under this
        # lock, so reading the queue last is safe.)
        wedged: List[Ticket] = []
        if self._current is not None:
            wedged.append(self._current.ticket)
            self._current = None
        wedged.extend(sl.ticket for sl in self._staged_slices)
        wedged.extend(sl.ticket for sl in self._dispatching)
        for inf in self._inflight:
            wedged.extend(sl.ticket for sl in inf.slices)
        if self._finalizing is not None:
            wedged.extend(sl.ticket for sl in self._finalizing.slices)
        if include_queue:
            wedged.extend(s.ticket for s in self._queue)
            self._queue.clear()
            self.metrics.set_gauge("pipeline_queue_depth", 0)
        # fresh staging ring: the old buffers may still be referenced by
        # the fenced-off worker — never reuse them
        self._buffers = [_StageBuf(self._stage_rows, self._n_shards)
                         for _ in range(self._inflight_max + 1)]
        self._free_bufs = list(range(len(self._buffers)))
        self._shard_fill = [0] * self._n_shards
        # the gauge is otherwise only touched in acquire/recycle: without
        # this it would report the wedged worker's last value (usually 0)
        # through the whole recovery window
        self.metrics.set_gauge("pipeline_staging_free",
                               len(self._free_bufs))
        for name in self._shard_gauge_names:
            self.metrics.set_gauge(name, 0)   # fresh ring: empty segments
        self._stage_buf = None
        self._staged_rows = 0
        self._staged_slices = []
        self._stage_now = None
        self._dispatching = []
        self._finalizing = None
        self._inflight = deque()
        self._hb = None
        self._pub = {}
        return wedged

    def _restart_worker(self, gen: int, reason: str) -> None:
        """The restart protocol (watchdog thread, or the dying worker
        itself on a crash). Generation-fenced: a stale ``gen`` is a no-op,
        so a watchdog firing while a crash restart is already underway
        cannot double-restart."""
        with self._lock:
            if gen != self._gen or self._closed or self._failed:
                return
            if self._closing:
                # shutdown is in flight: no replacement worker — fence the
                # wedged one and sweep so close()/waiters unblock instead
                # of waiting on a thread that will never return
                self._gen += 1
                stranded = self._collect_wedged_locked(include_queue=True)
                self._closed = True
                self._cond.notify_all()
                shutdown_sweep = True
            else:
                shutdown_sweep = False
                now = time.monotonic()
                if self._restarts and \
                        now - self._last_restart_mono > \
                        RESTART_BUDGET_WINDOW_S:
                    self._restarts = 0       # healthy interval: forgive
                self._last_restart_mono = now
                self._gen += 1
                new_gen = self._gen
                self._restarts += 1
            if not shutdown_sweep:
                restarts = self._restarts
                self._restarting = True
                wedged = self._collect_wedged_locked(
                    include_queue=restarts > self._max_restarts)
                hard_fail = restarts > self._max_restarts
                if hard_fail:
                    self._failed = True
                self._cond.notify_all()
        if shutdown_sweep:
            log.warning("pipeline worker wedged during shutdown (%s); "
                        "rejecting %d outstanding ticket(s)",
                        reason, len(stranded))
            self._settle([(t, None, PipelineError(
                "pipeline closed before this submission resolved "
                f"({reason})")) for t in stranded])
            return
        if hard_fail:
            exc: PipelineError = PipelineUnavailable(
                f"pipeline hard-failed after {restarts - 1} restarts "
                f"({reason}); submission rejected")
            self.metrics.inc_counter("pipeline_hard_failures_total")
        else:
            exc = PipelineError(
                f"pipeline worker restarted ({reason}); in-flight window "
                "rejected")
        self.metrics.inc_counter("pipeline_restarts_total")
        self._set_state_gauge()
        self.tracer.event("pipeline.watchdog",
                          action="hard-fail" if hard_fail else "restart",
                          reason=reason, restarts=restarts,
                          rejected=len(wedged))
        self._emit("watchdog",
                   action="hard-fail" if hard_fail else "restart",
                   reason=reason, restarts=restarts, rejected=len(wedged))
        log.warning("pipeline %s (restart %d/%d): %s; rejecting %d wedged "
                    "ticket(s)",
                    "HARD-FAILED" if hard_fail else "worker restarting",
                    restarts, self._max_restarts, reason, len(wedged))
        self._settle([(t, None, exc) for t in wedged])
        if hard_fail:
            with self._lock:
                self._restarting = False
                self._cond.notify_all()
            self._set_state_gauge()
            return
        # capped exponential backoff between restarts: a persistently
        # stalling backend gets breathing room instead of a restart storm
        time.sleep(min(self._restart_backoff_s * (1 << (restarts - 1)),
                       MAX_RESTART_BACKOFF_S))
        with self._lock:
            if self._closing or self._closed or self._gen != new_gen:
                self._restarting = False
                self._cond.notify_all()
                return
            self._worker = threading.Thread(
                target=self._run, args=(new_gen,), daemon=True,
                name=f"{self._name}-worker-g{new_gen}")
            self._worker_gen = new_gen
            self._cold_dispatch = True   # fresh gen: next dispatch is cold
            # satellite (b): recovery is DECLARED only after the new
            # worker's synthetic canary dispatch survives the real device
            # path — not merely after a thread started
            self._canary_pending = True
            self._worker.start()
            self._restarting = False
            self._cond.notify_all()
        self._set_state_gauge()

    def _on_worker_crash(self, gen: int) -> None:
        """The dying worker's own exit path (crash, not stall)."""
        with self._lock:
            if gen != self._gen:
                return               # a restart already superseded us
            shutting_down = self._closing or self._closed
        if shutting_down:
            # no restart during shutdown: sweep and mark closed so close()
            # and every waiter unblock
            stranded: List[Ticket] = []
            with self._lock:
                self._gen += 1
                stranded = self._collect_wedged_locked(include_queue=True)
                self._closed = True
                self._cond.notify_all()
            self._settle([(t, None, PipelineError(
                "pipeline worker crashed during shutdown"))
                for t in stranded])
            return
        self._restart_worker(gen, "worker crashed")

    # -- mesh self-healing (ISSUE 19) -----------------------------------------
    def _handle_device_lost(self, exc: DeviceLost,
                            slices: Sequence[_Slice],
                            buf_idx: Optional[int]) -> None:
        """A dispatch/finalize failed with a dead-accelerator signature.
        This is NOT breaker territory (retrying cannot resurrect a chip)
        and NOT watchdog territory (a restart would re-dispatch onto the
        same dead mesh): reject only the failing window's slices, PARK the
        worker — the queue and future submissions survive — and notify the
        engine, whose fenced :meth:`remesh` swaps the geometry under a
        fresh generation. Without a handler wired (bare pipelines) degrade
        to the generic dispatch-error path: breaker math still bounds the
        damage, and nothing ever parks waiting for a re-mesh that will
        never come."""
        self.dispatch_errors += 1
        self.metrics.inc_counter("pipeline_dispatch_errors_total")
        self.metrics.inc_counter(
            f'pipeline_device_lost_total{{device="{exc.device}"}}')
        cb = self._on_device_loss
        if cb is None:
            self.breaker.record_failure()
            log.warning("pipeline dispatch lost device %d with no re-mesh "
                        "handler wired; rejecting %d submission(s): %s",
                        exc.device, len(slices), exc)
            self._reject_slices(slices, exc, buf_idx)
            return
        with self._lock:
            self._device_lost = exc.device
        self._set_state_gauge()
        self.tracer.event("pipeline.device-loss", device=exc.device)
        self._emit("device-loss", device=exc.device, reason=str(exc))
        log.error("pipeline: device %d LOST (%s); worker parked pending "
                  "re-mesh, %d in-flight submission(s) rejected",
                  exc.device, exc, len(slices))
        self._reject_slices(slices, exc, buf_idx)
        try:
            cb(exc.device, str(exc))
        except Exception:   # noqa: BLE001 — a broken handler must not
            log.exception("on_device_loss handler failed")   # kill the worker

    def remesh(self, rebuild: Callable[[], Dict],
               reason: str = "device-loss") -> Dict:
        """The fenced re-mesh protocol. Fences the current generation and
        rejects ONLY the wedged in-flight window — queued submissions
        survive — then runs ``rebuild()`` (the engine's closure: re-mesh
        the datapath onto the survivor device set and re-place the active
        snapshot) and adopts the geometry it returns (``n_shards``,
        ``mesh_shards``, ``min_bucket``): seg_cap/stage_rows recomputed, a
        fresh staging ring allocated at the new shape, per-shard gauges
        swapped, and a new worker generation started with the canary
        dispatch pending.

        Unlike the watchdog protocol this NEVER spends restart budget — a
        commanded geometry change is not a crash. If ``rebuild()`` raises,
        the old geometry stands and a fresh worker restarts on it (the
        engine owns retrying); the exception propagates to the caller.
        Returns the adopted geometry dict."""
        with self._lock:
            if self._closed or self._closing:
                raise PipelineClosed("pipeline is closing; remesh refused")
            if self._failed:
                raise PipelineUnavailable(
                    "pipeline hard-failed; remesh refused")
            self._gen += 1
            new_gen = self._gen
            self._restarting = True
            self._device_lost = None
            wedged = self._collect_wedged_locked(include_queue=False)
        self.metrics.inc_counter("pipeline_remesh_total")
        self._set_state_gauge()
        self.tracer.event("pipeline.remesh", reason=reason,
                          rejected=len(wedged))
        self._settle([(t, None, PipelineError(
            f"mesh re-meshed ({reason}); in-flight window rejected"))
            for t in wedged])
        try:
            geom = rebuild() or {}
        except BaseException:
            # geometry unchanged: restart a worker on the OLD shape so
            # queued submissions are served (or fail back into the park
            # path if the mesh really is dead — the engine retries)
            self._start_generation(new_gen)
            self._emit("remesh", reason=reason, ok=False,
                       rejected=len(wedged))
            raise
        with self._lock:
            n_shards = int(geom.get("n_shards", self._n_shards))
            mesh_shards = int(geom.get("mesh_shards", n_shards))
            min_bucket = _next_pow2(
                int(geom.get("min_bucket", self._min_bucket)))
            self._n_shards = n_shards
            self._mesh_shards = mesh_shards if mesh_shards > 0 else n_shards
            self._min_bucket = min(min_bucket, self._max_bucket)
            if n_shards > 1:
                self._seg_cap = min(self._max_bucket, _next_pow2(
                    max(1, self._max_bucket // n_shards)
                    * self._shard_headroom))
                self._stage_rows = n_shards * self._seg_cap
            else:
                self._seg_cap = 0
                self._stage_rows = self._max_bucket
            old_gauges = self._shard_gauge_names
            self._shard_gauge_names = [
                f'pipeline_staged_rows{{shard="{s}"}}'
                for s in range(n_shards)] if n_shards > 1 else []
            self._shard_fill = [0] * n_shards
            self._shard_rows_total = [0] * n_shards
            self._stage_steer_rev = None
            # fresh ring at the NEW geometry (the wedged-collect above
            # already re-allocated one, but at the old shape)
            self._buffers = [_StageBuf(self._stage_rows, n_shards)
                             for _ in range(self._inflight_max + 1)]
            self._free_bufs = list(range(len(self._buffers)))
            self.metrics.set_gauge("pipeline_staging_free",
                                   len(self._free_bufs))
            if self._mesh_shards > 1:
                self.metrics.set_gauge("pipeline_mesh_shards",
                                       self._mesh_shards)
        # departed-shard gauge sweep: a 4→3 remesh must not leave
        # shard="3" pinned at its last fill forever
        for name in old_gauges:
            if name not in self._shard_gauge_names:
                self.metrics.drop_gauge(name)
        self._start_generation(new_gen)
        self._emit("remesh", reason=reason, ok=True, n_shards=n_shards,
                   mesh_shards=self._mesh_shards, rejected=len(wedged))
        log.warning("pipeline re-meshed (%s): n_shards=%d mesh_shards=%d "
                    "min_bucket=%d; %d wedged ticket(s) rejected",
                    reason, n_shards, self._mesh_shards, self._min_bucket,
                    len(wedged))
        return {"n_shards": self._n_shards,
                "mesh_shards": self._mesh_shards,
                "min_bucket": self._min_bucket,
                "rejected": len(wedged)}

    def _start_generation(self, new_gen: int) -> None:
        """Start a fresh worker for ``new_gen`` (remesh path — no restart
        budget, no backoff) with the canary dispatch pending; clears
        ``_restarting`` either way."""
        with self._lock:
            if not (self._closing or self._closed or self._gen != new_gen):
                self._worker = threading.Thread(
                    target=self._run, args=(new_gen,), daemon=True,
                    name=f"{self._name}-worker-g{new_gen}")
                self._worker_gen = new_gen
                self._cold_dispatch = True
                self._canary_pending = True
                self._worker.start()
            self._restarting = False
            self._cond.notify_all()
        self._set_state_gauge()

    def _maybe_canary(self, gen: int) -> None:
        """A restarted/re-meshed worker's first act: prove the device path
        with a synthetic all-invalid dispatch BEFORE serving traffic — a
        recovery that immediately wedges again must never eat a real
        submission to find out. The batch carries a ``_canary`` marker
        column so the engine's dispatch closure skips its observers (flow
        log, parity auditor, CT fingerprints). Success closes the half-open
        breaker the same way a real dispatch would; failure feeds the
        breaker — or the device-loss park path — with zero tickets harmed.
        The canary does not count as a dispatched/completed batch."""
        with self._lock:
            if not self._canary_pending or gen != self._gen:
                return
            self._canary_pending = False
        rows = self._n_shards if self._n_shards > 1 else 1
        batch = empty_batch(rows)
        batch["_canary"] = np.ones(rows, dtype=np.uint8)
        now = int(time.time())
        try:
            self._hb_arm("canary", gen, grace=COLD_DISPATCH_GRACE)
            self._check_gen(gen)
            if self._n_shards > 1:
                finalize = self._dispatch_fn(batch, now, None)
            else:
                finalize = self._dispatch_fn(batch, now)
            finalize()
            self._hb_clear(gen)
            self._check_gen(gen)
        except _Superseded:
            raise
        except DeviceLost as e:
            self._hb_clear(gen)
            self._check_gen(gen)
            self.metrics.inc_counter("pipeline_canary_failed_total")
            log.warning("pipeline canary (gen %d) lost device %d: %s",
                        gen, e.device, e)
            self._handle_device_lost(e, (), None)
            return
        except Exception as e:   # noqa: BLE001 — counted; breaker owns it
            self._hb_clear(gen)
            self._check_gen(gen)
            self.metrics.inc_counter("pipeline_canary_failed_total")
            self.breaker.record_failure()
            log.warning("pipeline canary (gen %d) failed: %s", gen, e)
            return
        self.metrics.inc_counter("pipeline_canary_ok_total")
        if self.breaker.state != "closed":
            self.breaker.record_success()
        self._cold_dispatch = False

    def _shed(self, ticket: Ticket, reason: str,
              exc: Optional[BaseException] = None) -> None:
        """Shed one submission without computing it (deadline passed, or a
        steer-overflow batch no shard segment can hold). Counted per shed
        point in ``pipeline_shed_total``; default rejection is the deadline
        error, ``exc`` overrides (steer overflow rejects with
        :class:`PipelineDrop` — overload shed, retryable)."""
        with self._lock:
            self.shed_total += 1
            self.shed_reasons[reason] = self.shed_reasons.get(reason, 0) + 1
        # the reason-only family counts every shed, QoS on or off, so
        # pre-QoS dashboards/alerts keep working when QoS is armed; with
        # QoS the shed is ALSO attributed to the ticket's tenant (the
        # name rode the ticket from admission, no table lookup here) in a
        # labeled family alongside it, never instead of it
        self.metrics.inc_counter(
            f'pipeline_shed_total{{reason="{reason}"}}')
        if ticket.tenant is not None:
            self.metrics.inc_counter(
                f'pipeline_shed_total{{reason="{reason}",'
                f'tenant="{ticket.tenant}"}}')
        self.tracer.record(ticket.trace_id, "pipeline.shed",
                           ticket.submitted_mono,
                           time.monotonic() - ticket.submitted_mono,
                           {"reason": reason})
        self._emit("shed", reason=reason, seq=ticket.seq)
        if exc is None:
            exc = PipelineDeadlineExceeded(
                f"deadline exceeded before {reason} (seq={ticket.seq}, "
                f"waited "
                f"{(time.monotonic() - ticket.submitted_mono) * 1e3:.1f}ms)")
        self._settle([(ticket, None, exc)])

    # -- worker side ----------------------------------------------------------
    def _run(self, gen: int) -> None:
        try:
            self._run_inner(gen)
        except _Superseded:
            return                       # fenced off; replacement owns state
        except BaseException:            # noqa: BLE001 — never strand tickets
            log.exception("pipeline worker (gen %d) died", gen)
            self._on_worker_crash(gen)

    def _run_inner(self, gen: int) -> None:
        self._maybe_canary(gen)
        while True:
            sub = None
            action = None
            with self._lock:
                while True:
                    if gen != self._gen or self._closed:
                        return
                    if self._device_lost is not None and not self._closing:
                        # device-lost park: do NOT pop the queue — queued
                        # submissions must survive until Pipeline.remesh()
                        # supersedes this generation and a fresh worker
                        # serves them on the survivor mesh. (During close
                        # we fall through so shutdown can still sweep.)
                        self._cond.wait(0.25)
                        continue
                    if self._queue:
                        sub = self._queue.popleft()
                        # hand-off under the lock: the sub must never be
                        # in neither the queue nor _current when a
                        # close/watchdog sweep runs
                        self._current = sub
                        depth = len(self._queue)
                        self.metrics.set_gauge("pipeline_queue_depth", depth)
                        if depth >= self._queue_max - 1:
                            self._cond.notify_all()   # wake blocked producers
                        action = "ingest"
                        break
                    if self._staged_slices and (
                            self._drain_req or self._closing
                            or time.monotonic() >= self._stage_deadline):
                        action = ("drain" if (self._drain_req
                                              or self._closing)
                                  else "deadline")
                        break
                    if self._inflight:
                        # idle with work in flight: finalize eagerly so a
                        # lone submission never waits for a successor
                        action = "finalize"
                        break
                    if self._closing:
                        return
                    wait = None
                    if self._staged_slices:
                        wait = max(0.0, self._stage_deadline
                                   - time.monotonic())
                    self._cond.wait(wait)
            if action == "ingest":
                self._ingest(sub, gen)     # _current was set at the pop
                self._current = None
            elif action == "finalize":
                self._finalize_oldest(gen)
            else:
                self._flush(action, gen)

    def _ingest(self, sub: _Sub, gen: int) -> None:
        t = sub.ticket
        if t.deadline_mono is not None \
                and time.monotonic() > t.deadline_mono:
            self._shed(t, "ingest")
            return
        m = t.n_valid
        if m == 0:
            # nothing to classify: resolve without a device round trip
            wait = time.monotonic() - t.submitted_mono
            self.metrics.histogram("pipeline_queue_wait_seconds").observe(
                wait)
            self.tracer.record(t.trace_id, "pipeline.admission",
                               t.submitted_mono, wait, kind=WAIT)
            self._settle([(t, _zero_out(t.n_rows), None)])
            return
        # latency lane: a lane-tagged tenant's submission never waits out
        # the coalesce deadline — it dispatches the moment it stages (at
        # the small always-armed lane bucket), taking any staged bulk
        # riders along. Bulk tenants keep the deadline microbatching.
        lane = bool(self._lane_bucket) and self._qos is not None \
            and self._qos.is_lane(sub.tenant)
        if self._n_shards > 1:
            # sharded staging: every row must land in its flow shard's
            # segment, so even bucket-shaped submissions stage (no direct
            # bypass — an arbitrary row order carries no shard placement)
            self._ingest_sharded(sub, gen, lane=lane)
            return
        rows = t.n_rows
        if (self._staged_rows == 0
                and (self._lane_bucket if lane
                     else self._min_bucket) <= rows <= self._max_bucket
                and rows & (rows - 1) == 0):
            # already bucket-shaped: zero-copy direct dispatch (_current
            # stays set across the hand-off into _dispatching — a ticket
            # is always visible in at least one sweep registry)
            self._dispatch(sub.batch, sub.now,
                           [_Slice(t, None, 0)], rows, m, "direct", None,
                           gen)
            return
        if self._staged_rows + m > self._max_bucket:
            self._flush("full", gen)
        if self._stage_buf is None:
            self._stage_buf = self._acquire_buffer(gen)
            # the deadline is anchored to the oldest rider's SUBMIT time so
            # backlogged submissions flush immediately instead of waiting
            # another full window
            self._stage_deadline = t.submitted_mono + self._flush_s
            self._stage_now = None
        valid_idx = np.nonzero(np.asarray(sub.batch["valid"]))[0]
        stage = self._buffers[self._stage_buf]
        if "_fp" not in sub.batch:
            stage.fp_whole = False       # the bucket is hashed downstream
        buf = stage.cols
        pos = self._staged_rows
        with self.tracer.span(t.trace_id, "pipeline.microbatch", rows=m):
            # pipeline.stage_write: just the column writes into the pinned
            # staging slot — the per-stage attribution point
            # (microbatch additionally covers valid_idx/admin)
            with self.tracer.span(t.trace_id, "pipeline.stage_write",
                                  rows=m, slot=self._stage_buf):
                for k, col in buf.items():
                    if k.startswith("_"):
                        # optional shim-side column: absent in non-shim
                        # submissions → 0 ("no raw id"; for ``_fp``, whose
                        # 0 is a hash like any other, ``fp_whole`` above)
                        src = sub.batch.get(k)
                        if src is None:
                            col[pos:pos + m] = 0
                            continue
                    else:
                        src = sub.batch[k]   # required: missing → crash →
                        #                      supervised reject (pinned)
                    col[pos:pos + m] = np.asarray(src)[valid_idx]
        if self._stage_now is None:
            self._stage_now = sub.now
        self._staged_slices.append(_Slice(t, valid_idx, pos))
        self._staged_rows += m
        self._publish(gen)
        if lane:
            self._flush("lane", gen)
        elif self._staged_rows >= self._max_bucket:
            self._flush("full", gen)

    def _shards_for(self, batch: Dict[str, np.ndarray],
                    valid_idx: np.ndarray, rev: int) -> np.ndarray:
        """Flow-shard id per valid row. A producer that already hashed
        (the shim feeder's harvest pre-binning — the SHARD_BIN encoding:
        low bits shard+1, 0 = not binned; high bits the policy revision
        the bin was hashed under) skips the hash entirely — but ONLY when
        the bin's revision matches ``rev``, the revision the caller read
        BEFORE steering and will stamp the bucket with: a regen between
        harvest and stage-write can change the LB tables and with them a
        service flow's post-DNAT steer hash, and a stale bin would strand
        its CT entry on the wrong shard. (Reading the revision once,
        up-front, also means a regen landing DURING this call can at worst
        stamp the bucket with the older revision — forcing a dispatch-time
        re-steer — never accept stale rows under a fresh stamp.) Anything
        else goes through ``shard_fn`` (the engine's direction-normalized
        flow hash over the active snapshot's LB tables)."""
        col = batch.get("_shard")
        if col is not None:
            raw = np.asarray(col)[valid_idx].astype(np.int64)
            pre = (raw & SHARD_BIN_MASK) - 1
            if pre.size and pre.min() >= 0 \
                    and pre.max() < self._n_shards \
                    and (self._shard_rev_fn is None
                         or bool((raw >> SHARD_BIN_SHIFT
                                  == (rev & SHARD_BIN_REV_MASK)).all())):
                return pre
        shard = np.asarray(self._shard_fn(batch), dtype=np.int64)
        return shard[valid_idx]

    def _ingest_sharded(self, sub: _Sub, gen: int,
                        lane: bool = False) -> None:
        """Steered staging (the software-RSS half of the multi-chip path):
        each valid row is scattered directly into its flow shard's column
        segment, so flush hands the datapath an already-steered batch and
        the per-batch steer→allocate→pack chain never runs. Placement is
        ``steer_rows`` — byte-identical to what ``steer_batch`` would
        produce for the same arrival order, which is what makes 8-shard
        pipeline verdicts bit-identical to the single-chip path."""
        t = sub.ticket
        m = t.n_valid
        valid_idx = np.nonzero(np.asarray(sub.batch["valid"]))[0]
        # the bucket's steer-revision stamp is read BEFORE hashing: a
        # regen landing mid-steer then stamps the bucket with the OLDER
        # revision (dispatch re-steers), never blesses stale rows
        rev = self._shard_rev_fn() if self._shard_rev_fn is not None else 0
        with self.tracer.span(t.trace_id, "pipeline.steer", rows=m):
            shard = self._shards_for(sub.batch, valid_idx, rev)
            counts = np.bincount(shard, minlength=self._n_shards)
        if int(counts.max()) > self._seg_cap:
            # one pathologically skewed submission can never fit a shard
            # segment: shed with an attributable reason instead of letting
            # the old per_shard ValueError crash the worker into a
            # watchdog restart
            self._shed(t, "steer_overflow", PipelineDrop(
                f"steer overflow: {int(counts.max())} rows for one flow "
                f"shard exceed the per-shard segment capacity "
                f"{self._seg_cap} (seq={t.seq})"))
            return
        if self._staged_slices and bool(
                (np.asarray(self._shard_fill) + counts
                 > self._seg_cap).any()):
            self._flush("full", gen)
        if self._stage_buf is None:
            self._stage_buf = self._acquire_buffer(gen)
            self._stage_deadline = t.submitted_mono + self._flush_s
            self._stage_now = None
            self._stage_steer_rev = rev
        elif self._stage_steer_rev != rev:
            self._stage_steer_rev = -2       # mixed: dispatch must re-steer
        stage = self._buffers[self._stage_buf]
        if "_fp" not in sub.batch:
            stage.fp_whole = False       # the bucket is hashed downstream
        buf = stage.cols
        fills = self._shard_fill
        with self.tracer.span(t.trace_id, "pipeline.microbatch", rows=m):
            with self.tracer.span(t.trace_id, "pipeline.stage_write",
                                  rows=m, slot=self._stage_buf):
                dst_rows = steer_rows(shard, self._n_shards, self._seg_cap,
                                      fills, counts=counts)
                for k, col in buf.items():
                    if k.startswith("_"):
                        src = sub.batch.get(k)
                        if src is None:
                            col[dst_rows] = 0
                            continue
                    else:
                        src = sub.batch[k]
                    col[dst_rows] = np.asarray(src)[valid_idx]
        for s in range(self._n_shards):
            c = int(counts[s])
            if c:
                fills[s] += c
                self._shard_rows_total[s] += c
                stage.dirty[s] = max(stage.dirty[s], fills[s])
        if self._stage_now is None:
            self._stage_now = sub.now
        self._staged_slices.append(_Slice(t, valid_idx, 0,
                                          dst_rows=dst_rows))
        self._staged_rows += m
        self._publish(gen)
        if lane:
            # the sharded dispatch shape is the fixed steered layout, so
            # the lane here only skips the coalesce deadline — no shape
            # change, no extra XLA traces
            self._flush("lane", gen)
        elif max(fills) >= self._seg_cap:
            self._flush("full", gen)

    def _flush(self, reason: str, gen: int) -> None:
        if not self._staged_slices:
            return
        buf_idx = self._stage_buf
        stage = self._buffers[buf_idx]
        buf = stage.cols
        rows = self._staged_rows
        slices = self._staged_slices
        now = self._stage_now
        sharded = self._n_shards > 1
        steer_rev = self._stage_steer_rev
        self._stage_steer_rev = None
        if sharded:
            fills = self._shard_fill
            self._shard_fill = [0] * self._n_shards
        # hand-off ordering: into _dispatching BEFORE leaving the staged
        # registry, so a concurrent sweep always sees every ticket
        self._dispatching = slices
        self._stage_buf = None
        self._staged_rows = 0
        self._staged_slices = []
        self._stage_now = None
        # deadline shed at flush time: riders whose deadline passed while
        # coalescing are masked out of the bucket and rejected — the
        # device never spends a cycle on them
        now_mono = time.monotonic()
        live: List[_Slice] = []
        for sl in slices:
            dl = sl.ticket.deadline_mono
            if dl is not None and now_mono > dl:
                if sl.dst_rows is not None:
                    buf["valid"][sl.dst_rows] = False
                else:
                    n = len(sl.valid_idx)
                    buf["valid"][sl.dst_start:sl.dst_start + n] = False
                self._shed(sl.ticket, "flush")
            else:
                live.append(sl)
        if not live:
            self._dispatching = []       # every slice settled by _shed
            self._recycle(buf_idx)
            self._publish(gen)
            return
        n_valid = sum(len(sl.valid_idx) for sl in live)
        if sharded:
            # restore empty-batch defaults on each segment's stale tail
            # (rows a previous, fuller use of this buffer wrote past the
            # current fill) — same wire-format-probe poisoning guard as
            # the unsharded tail reset, segment by segment. The dispatch
            # shape is always the full steered layout: one trace per wire
            # format, padded tails are valid-masked.
            for s in range(self._n_shards):
                base = s * self._seg_cap
                if fills[s] < stage.dirty[s]:
                    reset_batch_rows(buf, base + fills[s],
                                     base + stage.dirty[s])
                    stage.dirty[s] = fills[s]
            bucket = self._stage_rows
        else:
            # lane flushes dispatch at the (smaller) lane floor — padding
            # a 4-row lane batch to min_bucket would spend the latency
            # budget the lane exists to protect
            floor = (self._lane_bucket if reason == "lane"
                     and self._lane_bucket else self._min_bucket)
            bucket = max(floor, _next_pow2(rows))
            if rows < bucket:
                # reused buffer: restore the empty-batch defaults on the
                # tail, not just the valid mask — stale v6/L7/_ep_raw
                # content from an earlier, larger flush would otherwise
                # poison the datapath's wire-format probes (sticking the
                # wide wire forever) and trip the strict v6 check in the
                # compact pack kernel
                reset_batch_rows(buf, rows, bucket)
        self._dispatch(stage.view(bucket), now, live, bucket, n_valid,
                       reason, buf_idx, gen, steer_rev=steer_rev)

    def _dispatch(self, batch: Dict[str, np.ndarray], now: Optional[int],
                  slices: List[_Slice], bucket_rows: int, n_valid: int,
                  reason: str, buf_idx: Optional[int], gen: int,
                  steer_rev: Optional[int] = None) -> None:
        # hand-off ordering invariant: these slices are in _dispatching
        # from before they leave any upstream registry until after they
        # are settled or appended to _inflight — a concurrent sweep can
        # never catch a ticket in no registry at all
        self._dispatching = slices
        if now is None:
            now = int(time.time())
        if self.breaker.state == "open":
            # opened while this batch staged/queued: reject fast rather
            # than hammering the sick backend with its rows
            self._count_unavailable()
            self._reject_slices(slices, PipelineUnavailable(
                "circuit breaker open; dispatch suppressed"), buf_idx)
            self._dispatching = []
            return
        self.flush_reasons[reason] = self.flush_reasons.get(reason, 0) + 1
        self.metrics.inc_counter(f"pipeline_flush_{reason}_total")
        self._fill_rows += n_valid
        self._bucket_rows += bucket_rows
        if reason == "lane":
            # lane-only fill accounting: the autotuner's lane/bulk
            # arbitration reads padding waste from these, separately from
            # the aggregate fill ratio the bulk knobs are tuned by
            self._lane_fill_rows += n_valid
            self._lane_bucket_rows += bucket_rows
        self.metrics.set_gauge("pipeline_fill_ratio",
                               round(n_valid / bucket_rows, 4))
        t0 = time.monotonic()
        qw = self.metrics.histogram("pipeline_queue_wait_seconds")
        lw = (self.metrics.histogram("pipeline_lane_wait_seconds")
              if reason == "lane" else None)
        for sl in slices:
            qw.observe(t0 - sl.ticket.submitted_mono)
            if lw is not None:
                lw.observe(t0 - sl.ticket.submitted_mono)
            self.tracer.record(sl.ticket.trace_id, "pipeline.admission",
                               sl.ticket.submitted_mono,
                               t0 - sl.ticket.submitted_mono, kind=WAIT)
        # the batch-level spans ride the first sampled rider's trace; the
        # trace context makes the datapath's pack/transfer/compute split
        # attach to the same trace id across the backend boundary
        tid = next((sl.ticket.trace_id for sl in slices
                    if sl.ticket.trace_id is not None), None)

        attempts = 0
        while True:
            try:
                self._hb_arm(self._hb_dispatch_label, gen,
                             grace=COLD_DISPATCH_GRACE
                             if self._cold_dispatch else 1)
                FAULTS.fire("pipeline.dispatch")
                # a fenced-off worker released from a hang-mode stall must
                # not dispatch: its window was already rejected — reaching
                # the datapath now would mutate CT for nobody
                self._check_gen(gen)
                with self.tracer.context(tid), \
                        self.tracer.span(tid, "pipeline.dispatch",
                                         bucket=bucket_rows,
                                         n_valid=n_valid, reason=reason):
                    if self._n_shards > 1:
                        # sharded dispatch_fns take the steer revision so
                        # the backend can detect a regen landing between
                        # stage-write and here and re-steer under the
                        # snapshot it classifies with
                        finalize = self._dispatch_fn(batch, now, steer_rev)
                    else:
                        finalize = self._dispatch_fn(batch, now)
                self._hb_clear(gen)
                self._check_gen(gen)
                break
            except FaultInjected as e:
                self._hb_clear(gen)
                self._check_gen(gen)
                self.dispatch_faults += 1
                self.metrics.inc_counter("pipeline_dispatch_faults_total")
                attempts += 1
                if self.breaker.record_failure():
                    # the breaker opened: stop burning the retry budget
                    # against a backend that is failing every attempt
                    self._count_unavailable()
                    self._reject_slices(slices, PipelineUnavailable(
                        f"circuit breaker opened after {attempts} dispatch "
                        f"attempts: {e}"), buf_idx)
                    self._dispatching = []
                    return
                cap = (MAX_DISPATCH_RETRIES_CLOSING if self._closing
                       else MAX_DISPATCH_RETRIES)
                if attempts >= cap:
                    self._reject_slices(slices, e, buf_idx)
                    self._dispatching = []
                    return
                time.sleep(min(0.05, 0.0005 * (1 << min(attempts, 7))))
            except DeviceLost as e:
                self._hb_clear(gen)
                self._check_gen(gen)
                self._handle_device_lost(e, slices, buf_idx)
                self._dispatching = []
                return
            except Exception as e:   # noqa: BLE001 — supervised degradation
                self._hb_clear(gen)
                self._check_gen(gen)
                self.dispatch_errors += 1
                self.metrics.inc_counter("pipeline_dispatch_errors_total")
                self.breaker.record_failure()
                log.warning("pipeline dispatch failed, rejecting %d "
                            "submission(s): %s", len(slices), e)
                self._reject_slices(slices, e, buf_idx)
                self._dispatching = []
                return
        # a successful dispatch is only an *enqueue* — the failure streak
        # resets on finalize (the device actually answering). The
        # exception is the half-open probe: its dispatch succeeding is the
        # close signal (the issue's "half-open probe dispatches close it")
        if self.breaker.state != "closed":
            self.breaker.record_success()
        self._cold_dispatch = False      # this generation is warm now
        self.dispatched_batches += 1
        self._inflight.append(_Inflight(finalize, slices, t0, buf_idx))
        self._dispatching = []           # now visible in _inflight
        t_dev = time.monotonic()
        for sl in slices:
            # the rows are the device's now: a producer pacing itself on
            # the worker may harvest its next batch
            sl.ticket.dispatched_mono = t_dev
            sl.ticket._wake()
        self.metrics.set_gauge("pipeline_inflight", len(self._inflight))
        self._publish(gen)
        # keep at most ``inflight`` batches genuinely in flight; the ring
        # has inflight+1 staging buffers so the next microbatch can stage
        # while the window is full
        while len(self._inflight) > self._inflight_max:
            self._finalize_oldest(gen)

    def _finalize_oldest(self, gen: int) -> None:
        if not self._inflight:
            return
        # hand-off ordering: into _finalizing BEFORE leaving _inflight
        inf: _Inflight = self._inflight[0]
        self._finalizing = inf
        self._inflight.popleft()
        tid = next((sl.ticket.trace_id for sl in inf.slices
                    if sl.ticket.trace_id is not None), None)
        try:
            self._hb_arm(self._hb_finalize_label, gen)
            FAULTS.fire("pipeline.finalize")
            self._check_gen(gen)     # hang-released fence: do not finalize
            with self.tracer.context(tid), \
                    self.tracer.span(tid, "pipeline.finalize"):
                out = inf.finalize()
            self._hb_clear(gen)
        except DeviceLost as e:
            self._hb_clear(gen)
            self._check_gen(gen)
            self._handle_device_lost(e, inf.slices, inf.buf_idx)
            self._finalizing = None      # settled above
            return
        except Exception as e:   # noqa: BLE001 — incl. injected trips
            self._hb_clear(gen)
            self._check_gen(gen)
            self.dispatch_errors += 1
            self.metrics.inc_counter("pipeline_dispatch_errors_total")
            self.breaker.record_failure()
            log.warning("pipeline finalize failed, rejecting %d "
                        "submission(s): %s", len(inf.slices), e)
            self._reject_slices(inf.slices, e, inf.buf_idx)
            self._finalizing = None      # settled above
            return
        self._check_gen(gen)
        self.breaker.record_success()
        self.metrics.histogram("pipeline_batch_latency_seconds").observe(
            time.monotonic() - inf.t_dispatch)
        with self.tracer.span(tid, "pipeline.settle"):
            self._settle_finalized(inf, out, gen)
        self._finalizing = None          # settled above

    def _settle_finalized(self, inf: _Inflight, out: Dict[str, np.ndarray],
                          gen: int) -> None:
        """Hand a finalized batch's verdicts to its tickets: each slice's
        rows back in its submission's geometry, the staging buffer
        recycled, the stats published, the tickets resolved."""
        outcomes = []
        for sl in inf.slices:
            if sl.valid_idx is None:        # direct: geometry already matches
                outcomes.append((sl.ticket, out, None))
                continue
            n = len(sl.valid_idx)
            tout = _zero_out(sl.ticket.n_rows)
            for k, arr in out.items():
                if k not in tout:
                    tout[k] = np.zeros((sl.ticket.n_rows,) + arr.shape[1:],
                                       dtype=arr.dtype)
                # steered buckets: gathering through dst_rows un-steers
                # this ticket's verdicts back into submission row order
                if sl.dst_rows is not None:
                    tout[k][sl.valid_idx] = arr[sl.dst_rows]
                else:
                    tout[k][sl.valid_idx] = arr[sl.dst_start:
                                                sl.dst_start + n]
            outcomes.append((sl.ticket, tout, None))
        self.completed_batches += 1
        self._recycle(inf.buf_idx)
        self.metrics.set_gauge("pipeline_inflight", len(self._inflight))
        self._publish(gen)
        self._settle(outcomes)

    # -- small helpers ---------------------------------------------------------
    def _publish(self, gen: int) -> None:
        """Worker-side: publish a consistent snapshot of the worker-owned
        stats under the lock (what ``stats()`` reads instead of racing the
        worker's in-flux fields)."""
        snapshot = {
            "staged_rows": self._staged_rows,
            "flush_reasons": dict(self.flush_reasons),
            "fill_rows": self._fill_rows,
            "bucket_rows": self._bucket_rows,
            "inflight": len(self._inflight),
            "staging_free": len(self._free_bufs),
            "staging_slots": len(self._buffers),
            "dispatched_batches": self.dispatched_batches,
            "completed_batches": self.completed_batches,
        }
        if self._qos is not None:
            snapshot["lane_fill_rows"] = self._lane_fill_rows
            snapshot["lane_bucket_rows"] = self._lane_bucket_rows
        if self._n_shards > 1:
            snapshot["shard_fill"] = list(self._shard_fill)
            snapshot["shard_rows_total"] = list(self._shard_rows_total)
        with self._lock:
            if gen != self._gen:         # a fenced worker must not publish
                return
            self._pub = snapshot
            # shard-labeled staging occupancy: which segment is the
            # skew/backpressure hotspot (the per-mesh guard surface).
            # Inside the gen-checked lock so a fenced worker can never
            # overwrite the restart sweep's gauge reset with stale fills
            # (metrics locks are leaves — same nesting as the sweep's own
            # gauge writes); names precomputed, once per ingest.
            for name, f in zip(self._shard_gauge_names,
                               snapshot.get("shard_fill", ())):
                self.metrics.set_gauge(name, f)

    def _acquire_buffer(self, gen: int) -> int:
        while not self._free_bufs:
            self._check_gen(gen)
            self._finalize_oldest(gen)
        idx = self._free_bufs.pop()
        self._buffers[idx].fp_whole = True     # no rider yet
        # staging-ring occupancy: free slots left after this acquire (0 =
        # every slot staged or in flight — the host is the bottleneck)
        self.metrics.set_gauge("pipeline_staging_free", len(self._free_bufs))
        return idx

    def _recycle(self, buf_idx: Optional[int]) -> None:
        if buf_idx is not None:
            self._free_bufs.append(buf_idx)
            self.metrics.set_gauge("pipeline_staging_free",
                                   len(self._free_bufs))

    def _reject_slices(self, slices: Sequence[_Slice], exc: BaseException,
                       buf_idx: Optional[int]) -> None:
        wrapped = exc if isinstance(exc, PipelineError) else \
            PipelineError(f"dispatch failed: {type(exc).__name__}: {exc}")
        wrapped.__cause__ = exc
        self._recycle(buf_idx)
        self._settle([(sl.ticket, None, wrapped) for sl in slices])
