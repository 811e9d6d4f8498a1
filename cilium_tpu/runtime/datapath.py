"""The Datapath plugin boundary (upstream: ``pkg/datapath/types``'s
``Datapath``/``Loader`` interfaces; the fake mirrors ``pkg/datapath/fake``).

SURVEY.md §1 layer 3: "the Datapath/Loader Go interfaces — this is the
plugin boundary the TPU backend targets", and §4: control-plane tests
"replay recorded fixtures into a daemon with fake datapath" — the TPU
backend slots in exactly like that fake. Concretely: the Engine owns the
control plane (rules, identities, ipcache, endpoints) and compiles
``PolicySnapshot``s; everything device- or semantics-executing sits behind
``DatapathBackend``:

- ``JITDatapath`` — the production backend: snapshots placed as jax device
  arrays, batches classified by the fused jit kernel, conntrack as donated
  device buffers.
- ``FakeDatapath`` — jax-free. Records every placed snapshot (what upstream
  tests assert map/table contents against) and classifies via the semantics
  oracle, so control-plane tests exercise the full rules → verdict contract
  with no device, no XLA, no jit cache.

The Engine never imports jax when constructed with a FakeDatapath — that is
the test that the boundary is real.
"""

from __future__ import annotations

import abc
import logging
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from cilium_tpu.compile.ct_layout import (CTConfig, logical_ct_arrays,
                                          make_ct_arrays, place_ct_arrays)
from cilium_tpu.compile.lpm import PFX_LEN_MASK
from cilium_tpu.compile.snapshot import PolicySnapshot
from cilium_tpu.kernels.records import unpack_out
from cilium_tpu.observe.trace import (CT_GC_SPAN, L7_DICT_SPAN,
                                      PATCH_APPLY_SPAN, WAIT,
                                      active as active_trace)
from cilium_tpu.pipeline.guard import DeviceLost
from cilium_tpu.runtime.config import DaemonConfig
from cilium_tpu.runtime.faults import FAULTS
from cilium_tpu.utils import constants as C

OutArrays = Dict[str, np.ndarray]

#: clean (non-v6, compact-slot) batches required before place() may narrow
#: a widened wire format: narrowing under sustained wide traffic would
#: retrace every regen (the shape-flapping the sticky flags exist to stop)
WIRE_RESET_CLEAN_BATCHES = 64

CT_SCHEMA_KEYS = frozenset(
    ("keys", "expiry", "created", "flags", "pkts_fwd", "pkts_rev", "rev_nat"))

#: ct.npz schema version written by checkpoints; normalize_ct_arrays
#: upgrades anything older it still understands (v1 lacked rev_nat)
CT_FORMAT_VERSION = 2


class StalePlacement(RuntimeError):
    """The placed handle's device buffers were donated away by a later
    ``place_patch`` (the sub-ms delta path updates the device-resident
    policy image in place). Raised from the classify enqueue path when a
    caller captured the old handle before the patch landed but enqueued
    after — the revision fence that guarantees no batch ever classifies
    against a torn or deleted image. Callers retry with the engine's
    current active snapshot; semantically identical to having dispatched a
    moment later."""


#: substrings that mark a dispatch exception as a DEAD-ACCELERATOR failure
#: rather than the transient dispatch errors the breaker/backoff machinery
#: owns. The first entry is the chaos drill (runtime/faults.py
#: ``device.fail``); the rest are the signatures real runtimes emit when a
#: chip drops off the bus (PJRT/XLA status strings, the ICI-link variants a
#: pod slice reports when a neighbor dies). Matching is case-sensitive on
#: purpose: these are literal runtime status tokens, and loosening the
#: match risks classifying a user exception that merely *mentions* devices.
_DEAD_DEVICE_MARKERS = (
    "device.fail",
    "DEVICE_UNAVAILABLE",
    "device unavailable",
    "Device or resource busy",
    "hardware failure",
    "data transfer failed between devices",
    "chip has been disabled",
)

#: ``dev=K`` riding in the exception text attributes the loss to a flow-
#: shard ordinal (the fault drill arms it via message=dev=K; a real
#: runtime's status may or may not name the chip)
_DEAD_DEVICE_ORDINAL = re.compile(r"\bdev=(\d+)\b")


def dead_device_of(exc: BaseException) -> Optional[int]:
    """Classify a dispatch exception: ``None`` means transient (breaker /
    retry territory — NOT a device loss), an int means a dead-accelerator
    signature. The int is the flow-shard ordinal the failure names, or -1
    when the signature carries no attribution (the caller then treats the
    whole mesh generation as suspect and probes)."""
    text = f"{type(exc).__name__}: {exc}"
    if not any(m in text for m in _DEAD_DEVICE_MARKERS):
        return None
    m = _DEAD_DEVICE_ORDINAL.search(text)
    return int(m.group(1)) if m else -1


class PlacedTensors(dict):
    """A placed snapshot handle: the device-tensor dict plus the donation
    fence. ``dead`` flips (under the datapath's classify lock) the moment a
    delta patch donates this handle's verdict buffer into its successor —
    enqueueing against a dead handle raises :class:`StalePlacement` instead
    of reading a deleted buffer."""
    __slots__ = ("dead",)

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.dead = False


def placed_bytes(arr) -> int:
    """Bytes one device holds of a placed array: its tiled, padded size
    where the backend states one (a TPU holds a 32-bit ``[rows, 3]`` table
    at 16 bytes a row), else the array's own ``nbytes`` (numpy, the CPU)."""
    on_device = getattr(arr, "on_device_size_in_bytes", None)
    return int(on_device()) if on_device is not None else int(arr.nbytes)


def normalize_ct_arrays(arrays: Dict[str, np.ndarray]
                        ) -> Dict[str, np.ndarray]:
    """Validate/upgrade a ct_layout checkpoint to the current schema —
    backend-independent (the schema belongs to the checkpoint format, not to
    any one backend). Strips the embedded format-version stamp, backfills
    the rev_nat column for checkpoints written before service rev-NAT
    existed (format 1), and raises on any other mismatch — including a
    format stamp NEWER than this build understands (restoring a
    future-format CT would silently mis-read columns; dropping it loudly
    is the checkpoint path's fail-closed)."""
    if "__ct_format__" in arrays:
        arrays = dict(arrays)
        fmt = int(np.asarray(arrays.pop("__ct_format__")).reshape(-1)[0])
        if fmt > CT_FORMAT_VERSION:
            raise ValueError(
                f"CT checkpoint format {fmt} is newer than this build's "
                f"{CT_FORMAT_VERSION}")
    if "rev_nat" not in arrays and "expiry" in arrays:
        arrays = dict(arrays)
        arrays["rev_nat"] = np.zeros_like(arrays["expiry"])
    if set(arrays.keys()) != CT_SCHEMA_KEYS:
        raise ValueError(f"CT arrays mismatch: {sorted(arrays)} != "
                         f"{sorted(CT_SCHEMA_KEYS)}")
    return arrays


def _records_from_batch(b: Dict[str, np.ndarray], ep_ids) -> list:
    """Batch dict → oracle PacketRecords (inverse of batch_from_records);
    invalid rows become None so callers keep indices aligned. Lives here —
    not in kernels/ — because only the oracle-backed fake needs it and
    kernels/ must stay importable without the oracle package."""
    from oracle import PacketRecord
    n = b["valid"].shape[0]
    out: list = []
    for i in range(n):
        if not b["valid"][i]:
            out.append(None)
            continue
        slot = int(b["ep_slot"][i])
        path = bytes(b["http_path"][i])
        path = path[:path.index(0)] if 0 in path else path
        out.append(PacketRecord(
            b["src"][i].astype(">u4").tobytes(),
            b["dst"][i].astype(">u4").tobytes(),
            int(b["sport"][i]), int(b["dport"][i]), int(b["proto"][i]),
            int(b["tcp_flags"][i]), bool(b["is_v6"][i]),
            ep_ids[slot] if slot < len(ep_ids) else -1,
            int(b["direction"][i]), int(b["http_method"][i]), path))
    return out


class DatapathBackend(abc.ABC):
    """What the Engine needs from a datapath. The backend owns conntrack
    state (the device-side analog of pinned BPF maps): it survives snapshot
    swaps and is exportable/restorable for checkpointing."""

    @abc.abstractmethod
    def place(self, snap: PolicySnapshot) -> Any:
        """Materialize a compiled snapshot for classification; returns an
        opaque placed handle the Engine passes back to classify()."""

    def place_patch(self, placed: Any, snap: PolicySnapshot,
                    patch) -> Any:
        """Materialize an incrementally-updated snapshot given the previous
        placed handle and a compile.incremental.SnapshotPatch. Default: full
        re-place (semantically always correct); the JIT backend overrides
        with device-side index updates."""
        return self.place(snap)

    @abc.abstractmethod
    def classify(self, placed: Any, snap: PolicySnapshot,
                 batch: Dict[str, np.ndarray], now: int
                 ) -> Tuple[OutArrays, OutArrays]:
        """Classify one batch against a placed snapshot. Returns
        (out, counters) as numpy: out has at least allow/reason/status/
        remote_identity; counters has by_reason_dir [C.COUNTER_CELLS]
        (reasons x directions) + insert_fail."""

    @property
    def pipeline_shards(self) -> int:
        """Flow-shard count the ingestion pipeline should steer for: > 1
        means the backend serves a flow-sharded mesh and wants pipeline
        batches delivered pre-steered (rows grouped into equal per-shard
        segments along dim 0). 1 = no steering (the default; FakeDatapath
        and single-chip JIT)."""
        return 1

    def classify_async(self, placed: Any, snap: PolicySnapshot,
                       batch: Dict[str, np.ndarray], now: int,
                       pre_steered: bool = False):
        """Enqueue one batch and return a zero-argument *finalize* callable
        that blocks until the verdicts are ready and returns the same
        (out, counters) tuple ``classify`` would.

        The contract the pipeline scheduler builds on: everything ordering-
        sensitive (CT mutation order) happens before this returns, so the
        caller may stage/pack/transfer the NEXT batch while the device is
        still computing this one. The default runs the backend's synchronous
        ``classify`` eagerly (FakeDatapath: a plain queue — no device, no
        overlap to win); the JIT backend overrides it with real async
        dispatch.

        ``pre_steered``: the caller already grouped rows into
        ``pipeline_shards`` equal segments by flow-shard (the sharded
        staging ring); outputs come back in the same steered row geometry —
        the caller owns un-steering. Meaningless (and ignored) on backends
        with ``pipeline_shards == 1``, where row order carries no placement
        semantics."""
        res = self.classify(placed, snap, batch, now)
        return lambda: res

    @abc.abstractmethod
    def sweep(self, now: int) -> int:
        """Conntrack GC; returns reclaimed entry count."""

    @abc.abstractmethod
    def ct_stats(self, now: int) -> Dict[str, int]: ...

    @abc.abstractmethod
    def ct_arrays(self) -> Dict[str, np.ndarray]:
        """Host copy of the CT table in the ct_layout schema."""

    @abc.abstractmethod
    def load_ct_arrays(self, arrays: Dict[str, np.ndarray]) -> None: ...


class JITDatapath(DatapathBackend):
    """Production backend: XLA-compiled fused classify over device arrays.

    With ``DaemonConfig.n_shards``/``rule_shards`` > 1 the backend serves
    through a ('flows','rules') device mesh (SURVEY.md §2 parallelism rows
    1-2): batches are steered on the host by the direction-normalized flow
    hash (the RSS analog, vectorized), the conntrack table lives sharded
    along its slot axis (one independent power-of-two table per flow shard),
    and verdict id-class rows are sharded over the rules axis with one psum
    combining (kernels/policy.py). Checkpoint export/import transparently
    rehashes entries into the active shard layout (parallel/mesh.py
    rehash_ct_arrays), so a single-chip checkpoint restores onto a mesh and
    vice versa."""

    def __init__(self, config: Optional[DaemonConfig] = None):
        self.config = config or DaemonConfig()
        if self.config.device == "cpu":
            import os
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax
        import jax.numpy as jnp
        from cilium_tpu.utils.compile_cache import enable_compile_cache
        self.compile_cache_dir = enable_compile_cache()
        self._jnp = jnp
        # which device serves: "tpu"/"cpu" are requirements, never
        # preferences — an engine configured for the chip must not serve
        # from a CPU without a word; "auto" takes what JAX has and says so
        # (here, in device_state and in `cilium-tpu status`)
        backend = jax.default_backend()
        if self.config.device not in ("auto", backend):
            raise RuntimeError(
                f"DaemonConfig.device={self.config.device!r} but JAX's "
                f"default backend is {backend!r} "
                f"({jax.devices()[0].device_kind}); the platform is chosen "
                f"before the first jax import (JAX_PLATFORMS)")
        logging.getLogger("cilium_tpu.datapath").info(
            "serving on %s (%s x%d), device=%s, compile cache %s", backend,
            jax.devices()[0].device_kind, len(jax.devices()),
            self.config.device, self.compile_cache_dir)
        self.n_flow_shards = max(1, self.config.n_shards)
        self.n_rule_shards = max(1, self.config.rule_shards)
        self._sharded = self.n_flow_shards * self.n_rule_shards > 1
        ct_host = make_ct_arrays(CTConfig(self.config.ct_capacity,
                                          self.config.probe_depth))
        # device-side RSS (rss_mode="device", parallel/exchange.py): rows
        # arrive on chips in plain FIFO order and cross-shard CT resolves
        # with the in-kernel ring ppermute exchange — no host steering, no
        # pre-binning, no un-steer. Host mode keeps the classic steered
        # path. Only meaningful on a flow-sharded mesh.
        self._rss_device = (self.config.rss_mode == "device"
                            and self.n_flow_shards > 1)
        if self._sharded:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from cilium_tpu.parallel.mesh import (
                make_mesh, make_sharded_classify_fn,
                make_unsteered_classify_fn, shard_ct_arrays)
            if self.n_flow_shards & (self.n_flow_shards - 1):
                raise ValueError("n_shards must be a power of two (each CT "
                                 "shard is a power-of-two hash table)")
            self._mesh = make_mesh(self.n_flow_shards, self.n_rule_shards)
            # mesh self-healing (ISSUE 19): flow shard i serves on row i of
            # this CONFIGURED device grid for the life of the process —
            # remesh() re-derives survivor meshes from it and device-health
            # ordinals index into it. The grid never shrinks; only
            # _live_ordinals (the serving subset) does.
            self._configured_devices = [
                list(row) for row in np.asarray(self._mesh.devices).reshape(
                    self.n_flow_shards, self.n_rule_shards)]
            self._live_ordinals: List[int] = list(range(self.n_flow_shards))
            self._ct_sharding = NamedSharding(self._mesh, P("flows"))
            self._repl_sharding = NamedSharding(self._mesh, P())
            # packed wire rows shard over 'flows': each chip receives only
            # its own segment of the pooled wire buffer
            self._batch_sharding = NamedSharding(self._mesh, P("flows"))
            self._verdict_sharding = NamedSharding(
                self._mesh, P(None, None, "rules", None))
            shard_ct_arrays(ct_host, self.n_flow_shards)
            self._ct = {k: jax.device_put(v, self._ct_sharding)
                        for k, v in ct_host.items()}
            make_fn = (make_unsteered_classify_fn if self._rss_device
                       else make_sharded_classify_fn)
            self._classify = make_fn(
                self._mesh,
                probe_depth=self.config.probe_depth,
                v4_only=self.config.v4_only,
                donate_ct=self.config.donate_ct,
                # one packed verdict slab a batch, a segment a chip, as
                # on one chip below (remesh builds its survivors alike)
                slab=True)
            # per-survivor-set geometry cache: healing back onto a device
            # set the process already served reuses that set's mesh +
            # jitted classify — re-tracing on every down/up flap would
            # stall serving for seconds each transition
            self._mesh_cache: Dict[Tuple[int, ...], tuple] = {
                tuple(self._live_ordinals): (
                    self._mesh, self._ct_sharding, self._repl_sharding,
                    self._batch_sharding, self._verdict_sharding,
                    self._classify)}
        else:
            from cilium_tpu.kernels.classify import make_classify_fn
            self._ct = {k: jnp.asarray(v) for k, v in ct_host.items()}
            # production single-chip path is transfer-bound: ship batches in
            # the packed wire format (one contiguous buffer, not 12 arrays)
            self._classify = make_classify_fn(
                probe_depth=self.config.probe_depth,
                v4_only=self.config.v4_only,
                donate_ct=self.config.donate_ct,
                packed=True,
                # ...and read-back-bound the other way: every out column
                # and counter comes back in one packed slab, not 18 reads
                slab=True)
            self._configured_devices = None
            self._live_ordinals = [0]
            self._mesh_cache = {}
        # the flow-shard width the operator CONFIGURED; n_flow_shards is
        # the width currently SERVING (remesh shrinks/restores it)
        self._configured_flow_shards = self.n_flow_shards
        # per-ordinal health records latched by the dead-device classifier
        # and cleared on heal — the engine folds these into health() and
        # the mesh_width ledger row
        self.device_health: Dict[int, Dict[str, Any]] = {}
        # CT capacity currently on device: remesh clamps it to the largest
        # per-shard power of two the survivor count divides into
        # (parallel/mesh.degraded_ct_capacity) — sweep cursors and restores
        # must use THIS, not config.ct_capacity
        self._ct_capacity = int(self.config.ct_capacity)
        self.remesh_stats: Dict[str, int] = {
            "remesh_total": 0,
            "remesh_ct_salvaged": 0,     # live entries carried across
            "remesh_ct_lost": 0,         # live entries on lost shards
            "remesh_ct_dropped": 0,      # rehash probe-window casualties
            "remesh_gather_failures": 0,  # device.collective / gather died
        }
        # donated CT buffers make concurrent classify a use-after-donate;
        # serialize the device step (host-side controllers may call in)
        self._ct_lock = threading.Lock()
        # wire-format stickiness: each (format, shape) is a separate XLA
        # trace (seconds), so per-batch content must not flap the choice —
        # once L7/v6 traffic is seen the wider format stays, and L7 dict
        # geometry (path words, dict rows) only grows. place() resets the
        # flags when a NEW snapshot provably has no L7/v6 surface, so a
        # transient burst doesn't tax every future batch forever.
        self._wire_l7 = False
        self._wire_wide = False        # v6 or >14-bit ep_slot seen
        self._l7_path_words = 1
        self._l7_dict_rows = 1
        # what the L7 path-dictionary wire carried so far (pack lock):
        # distinct paths over every batch's dictionary, and the bytes of
        # the dictionaries that went up (a content-cache hit uploads none)
        self.l7_stats: Dict[str, int] = {"dict_paths": 0,
                                         "dict_upload_bytes": 0}
        # zero-copy staging: a checkout/return pool of wire buffers the
        # pack kernels fill in place, keyed by (rows, words). A buffer may
        # be aliased by the backend until its batch finalizes
        # (device_put/asarray can be zero-copy or async on some backends),
        # so a buffer returns to the pool only in ITS OWN batch's finalize
        # — never by rotation, which dispatch retries could wrap early. A
        # pool miss allocates (steady state refills the pool; fault storms
        # just shed buffers to the GC); only power-of-two row counts (the
        # serving shapes) are pooled, so arbitrary control-plane batch
        # sizes (health probes, pcap tails) can't grow it unboundedly.
        self._pack_lock = threading.Lock()
        self._wire_pool_cap = max(4, self.config.pipeline_inflight + 2)
        self._wire_pool: Dict[Tuple[int, int], list] = {}
        # poolable buffers currently checked out with in-flight batches
        # (the resource ledger's wire_pool occupancy; a lazily-filling
        # pool makes cap-minus-free overstate wildly at startup)
        self._wire_out = 0
        # batches since the last v6/wide-slot batch: the place() narrowing
        # only fires after a clean run, so steady v6 traffic can never
        # reset-flap the wire shape across regens
        self._batches_since_wide = 0
        # L7 path-dict upload cache: real traffic repeats the same path set
        # batch after batch — an unchanged dict is never re-transferred
        self._path_dict_host: Optional[np.ndarray] = None
        self._path_dict_dev = None
        # attribution counters (Engine renders them as labeled Prometheus
        # counters). The fallback split is the answer to "why did this
        # batch allocate": ``disabled`` = zero_copy_ingest off, ``steered``
        # = a sharded batch arrived un-steered (the sync/control-plane
        # entry, which steers with an allocating regroup), ``shape`` = a
        # non-power-of-two row count the pool refuses to hold. The serving
        # path — pipelined, pre-steered, pow2 buckets — must show only
        # ``pack_inplace``; the sharded soak asserts ``steered`` stays 0.
        self.pack_stats: Dict[str, int] = {
            "pack_inplace": 0,            # packed into a pooled wire buffer
            "pack_fallback_disabled": 0,  # zero-copy ingest turned off
            "pack_fallback_steered": 0,   # un-steered sharded batch
            "pack_fallback_shape": 0,     # unpoolable (non-pow2) row count
            "upload_cache_hits": 0,       # path dict served from device cache
            "upload_cache_misses": 0,
            "wire_flag_resets": 0,        # place() narrowed the wire format
            # device→host crossings: batches finalized through one packed
            # verdict slab read in one transfer — every batch, on one chip
            # and on a mesh (there one segment a chip, one sharded array)
            "readback_slab": 0,
            # bytes put on the device for the wire (and the path dictionary
            # where one went up), and what the same valid rows would ship
            # each on its own class's layout (_pack_wire)
            "wire_bytes": 0,
            "wire_bytes_needed": 0,
        }
        # valid rows through _pack_wire by what their OWN class needs of
        # the wire (the choice itself is batch-wide and sticky), and those
        # that leave the endpoint (pack lock)
        self.wire_rows: Dict[str, int] = {"wide_needed": 0, "l7_needed": 0,
                                          "egress": 0}
        # live-patch attribution: how each place_patch applied (delta =
        # donated device scatter; full = whole-tensor re-upload) and how
        # often the StalePlacement fence actually fired (a caller captured
        # the pre-patch handle and enqueued post-donation — rare, retried)
        self.patch_stats: Dict[str, int] = {
            "patch_delta": 0,
            "patch_full": 0,
            "patch_rows": 0,              # rows scatter-applied, cumulative
            "patch_stale_fences": 0,
            "patch_scatter_errors": 0,    # failed scatters self-healed by
                                          # a full verdict re-upload
        }
        # device-memory ledger (ROADMAP item 6 groundwork / ISSUE 13): the
        # live-placement half of the HBM truth the offline verifier's
        # memory_analysis() budgets describe. Bytes per placed tensor
        # group, re-accounted on every place/place_patch (reading .nbytes
        # off a dozen device arrays — no transfers), CT at construction/
        # restore, the wire pool on demand. hbm_ledger() is the export.
        self._hbm_lock = threading.Lock()
        self._hbm_groups: Dict[str, int] = {}
        self._hbm_places = 0
        self._hbm_patches = 0
        # device-RSS exchange accounting: the ring ppermute's gathered
        # request/reply buffers are transient per-dispatch device tensors;
        # the ledger's ``exchange`` group carries the PEAK bytes any
        # dispatched bucket materialized (the budget-relevant number),
        # rss_exchange_stats() the last/peak occupancy pair and the
        # cumulative bytes/batches every dispatched bucket added
        self._exchange_last_bytes = 0
        self._exchange_peak_bytes = 0
        self._exchange_bytes_total = 0
        self._exchange_batches_total = 0
        self._account_ct_hbm()
        self._scatter_fn = None            # jitted donated row scatter
        # overlapped CT GC (kernels/conntrack.ct_sweep_chunk): cursor into
        # the slot space + the previous tick's un-materialized device
        # scalars (the double buffer — harvested one tick later so the
        # enqueue path never blocks on the device)
        self._gc_fn = None
        self._gc_chunk = 0
        self._gc_cursor = 0
        self._gc_epoch = 0
        self._gc_pending = None            # (reclaimed_dev, live_dev)
        self._gc_reclaimed_total = 0
        self._gc_last_live = -1

    @property
    def pipeline_shards(self) -> int:
        """The ingestion pipeline steers for the flow axis only — rule
        shards replicate the batch, so a rules-only mesh needs no row
        grouping at all. With device-side RSS (rss_mode="device") the
        answer is 1: row order carries NO placement semantics — the
        pipeline stages contiguously, the feeder skips pre-binning, and
        the shard_map body's ppermute exchange owns flow→shard
        resolution."""
        if self._rss_device:
            return 1
        return self.n_flow_shards if self._sharded else 1

    @property
    def rss_state(self) -> Dict[str, Any]:
        """Operator-facing RSS-mode surface: where flow→shard resolution
        runs ("host" steering vs the "device" ppermute exchange), the
        mesh's flow-axis size, and whether device mode is actually active
        (it needs a flow-sharded mesh)."""
        return {
            "mode": "device" if self._rss_device else "host",
            "shards": self.n_flow_shards if self._sharded else 1,
            "active": self._rss_device,
        }

    def rss_exchange_stats(self) -> Optional[Dict[str, int]]:
        """Exchange-buffer occupancy for the resource ledger (device RSS
        only): bytes the last dispatched bucket's gathered request/reply
        buffers materialized across the mesh against the worst case at
        ``batch_size`` — the device-transient twin of the wire pool's
        host-side row — and, cumulative over every dispatched bucket,
        ``exchange_bytes_total`` / ``exchange_batches_total`` (Engine folds
        them into ``rss_exchange_{bytes,batches}_total``)."""
        if not self._rss_device:
            return None
        from cilium_tpu.parallel.exchange import exchange_bytes
        cap = exchange_bytes(self.config.batch_size, self.n_flow_shards)
        with self._hbm_lock:
            # capacity tracks the largest bucket actually dispatched when
            # a caller runs bigger-than-batch_size buckets — occupancy
            # must never exceed capacity
            return {"capacity": max(cap, self._exchange_peak_bytes),
                    "in_use": self._exchange_last_bytes,
                    "peak": self._exchange_peak_bytes,
                    "exchange_bytes_total": self._exchange_bytes_total,
                    "exchange_batches_total": self._exchange_batches_total}

    @property
    def device_state(self) -> Dict[str, Any]:
        """Which device serves: what the config asked for and what JAX
        has (platform, kind, count), plus how many of them this backend's
        mesh uses."""
        import jax
        devs = jax.devices()
        return {
            "configured": self.config.device,
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs),
            "serving": self.n_flow_shards * self.n_rule_shards,
            "compile_cache_dir": self.compile_cache_dir,
        }

    def _maybe_reset_wire_flags(self, snap: PolicySnapshot) -> None:
        """Un-stick the widened wire formats when the NEW snapshot provably
        has no surface that needs them: with zero L7 rule sets, http tokens
        cannot affect any verdict (no mapstate cell references an L7 set —
        and widening is policy-gated, so the flag cannot re-stick while the
        surface stays empty), and with no v6 prefixes + all ep slots under
        the compact cap, the 4-word v4 wire is sufficient. The wide reset
        additionally requires WIRE_RESET_CLEAN_BATCHES batches without v6
        traffic, so sustained v6 flows can never reset-flap the shape
        across regens — only a genuinely transient burst un-sticks."""
        from cilium_tpu.kernels.records import PACK4_EP_SLOT_MAX
        # under the pack lock: a concurrent classify_async reads/widens the
        # same flags there — a reset landing between its widen and its
        # format choice would mispack the in-flight batch
        with self._pack_lock:
            reset = False
            if self._wire_l7 and snap.l7.n_sets == 0:
                self._wire_l7 = False
                self._l7_path_words = 1
                self._l7_dict_rows = 1
                self._path_dict_host = None
                self._path_dict_dev = None
                reset = True
            # slots run 0..len-1, so the compact wire fits through
            # len == PACK4_EP_SLOT_MAX + 1 inclusive
            if self._wire_wide \
                    and len(snap.ep_ids) - 1 <= PACK4_EP_SLOT_MAX \
                    and self._batches_since_wide >= WIRE_RESET_CLEAN_BATCHES \
                    and not any(":" in p for p in snap.ipcache):
                self._wire_wide = False
                reset = True
            if reset:
                self.pack_stats["wire_flag_resets"] += 1

    # -- HBM ledger (ISSUE 13: the live half of the verifier's offline
    # memory_analysis() budgets; ROADMAP item 6's hardware-truth landing
    # zone) -------------------------------------------------------------------
    @staticmethod
    def _hbm_group(name: str) -> str:
        """Placed-tensor name → ledger group. The groups mirror how an
        operator reasons about device memory: the verdict image (the thing
        place_patch scatters), the LPM tries (the IPv6-at-scale risk), the
        load-balancer's tables (a Maglev row of M a service: the largest
        group of a node that holds a cluster's services), and the remaining
        policy planes."""
        if name == "verdict":
            return "verdict"
        if name.startswith("lpm"):
            return "tries"
        if name.startswith("lb_"):
            return "lb"
        return "policy"

    def _account_placed(self, placed: Dict, patched: bool) -> None:
        groups = {"verdict": 0, "tries": 0, "lb": 0, "policy": 0}
        for k, v in placed.items():
            groups[self._hbm_group(k)] += int(getattr(v, "nbytes", 0))
        with self._hbm_lock:
            self._hbm_groups.update(groups)
            if patched:
                self._hbm_patches += 1
            else:
                self._hbm_places += 1

    def _account_ct_hbm(self) -> None:
        n = sum(int(getattr(v, "nbytes", 0)) for v in self._ct.values())
        with self._hbm_lock:
            self._hbm_groups["ct"] = n

    def hbm_ledger(self) -> Dict[str, Any]:
        """Bytes per placed tensor group, live: verdict image / LPM tries /
        policy planes / CT table (device-resident) plus the pooled wire
        staging buffers (host-pinned; flagged so the two kinds are never
        summed into one misleading number). Re-accounted per
        place/place_patch — this is the number ``--max-hbm-bytes`` budgets
        and the resource ledger's ``hbm`` row cite."""
        with self._pack_lock:
            wire = sum(b.nbytes for pool in self._wire_pool.values()
                       for b in pool)
            wire_keys = len(self._wire_pool)
        with self._hbm_lock:
            groups = dict(self._hbm_groups)
            places, patches = self._hbm_places, self._hbm_patches
        device_total = sum(groups.values())
        groups["wire_pool"] = wire
        return {
            "groups": groups,
            "device_bytes": device_total,
            "host_pool_bytes": wire,
            "wire_pool_keys": wire_keys,
            "places_total": places,
            "patches_total": patches,
        }

    def wire_pool_stats(self) -> Dict[str, int]:
        """Pool occupancy for the resource ledger: buffers currently
        checked out (in flight with a dispatched batch — counted at
        checkout/release, since cap-minus-free overstates on a lazily
        filled pool) against the pool's total slots across active
        (rows, words) keys."""
        with self._pack_lock:
            keys = max(1, len(self._wire_pool))
            free = sum(len(p) for p in self._wire_pool.values())
            out = self._wire_out
        cap = self._wire_pool_cap * keys
        return {"capacity": max(cap, out), "free": free,
                "in_flight": out, "keys": keys}

    def place(self, snap: PolicySnapshot) -> Dict:
        jnp = self._jnp
        self._maybe_reset_wire_flags(snap)
        if not self._sharded:
            placed = PlacedTensors(
                {k: jnp.asarray(v) for k, v in snap.tensors().items()})
            self._account_placed(placed, patched=False)
            return placed
        import jax
        from cilium_tpu.parallel.mesh import pad_snapshot_tensors
        tensors = pad_snapshot_tensors(snap.tensors(), self.n_rule_shards)
        placed = PlacedTensors({k: jax.device_put(
            v, self._verdict_sharding if k == "verdict"
            else self._repl_sharding) for k, v in tensors.items()})
        self._account_placed(placed, patched=False)
        return placed

    def _put_tensor(self, name, v):
        if not self._sharded:
            return self._jnp.asarray(v)
        import jax
        return jax.device_put(
            v, self._verdict_sharding if name == "verdict"
            else self._repl_sharding)

    def _scatter_rows(self, verdict, rows, vals):
        """Donated scatter-apply of a sparse verdict delta: the jit is
        created once; jax's shape cache keys the (Kp, n_cols) buckets.
        Padded rows carry an out-of-range slot index and drop."""
        if self._scatter_fn is None:
            import jax

            def _apply(v, r, x):
                return v.at[r[:, 0], r[:, 1], r[:, 2]].set(x, mode="drop")
            self._scatter_fn = jax.jit(_apply, donate_argnums=(0,))
        return self._scatter_fn(verdict, rows, vals)

    #: delta row-count buckets are padded to powers of two so a policy
    #: storm's varying patch sizes reuse a handful of scatter traces
    #: instead of compiling one program per distinct K
    _PATCH_PAD_SLOT = 1 << 30          # OOB slot index → mode="drop"

    def place_patch(self, placed, snap: PolicySnapshot, patch) -> Dict:
        """Incremental device update (SURVEY.md §7 step 3 / ROADMAP item 3a):
        re-upload only tensors the patch names; when the compiler shipped a
        sparse (rows, values) delta, scatter-apply it onto the
        device-resident verdict image with a DONATED buffer — the policy
        image mutates in place on device, no host round trip of any plane.

        Donation is fenced: under the classify lock the old handle is
        marked dead before its verdict buffer is donated, so a concurrent
        classify that captured the old handle either enqueued before the
        patch (XLA's buffer usage-holds sequence its reads ahead of the
        donated write) or observes ``dead`` and raises
        :class:`StalePlacement` for the caller to retry against the new
        active snapshot — no batch can ever classify against a torn or
        deleted image."""
        jnp = self._jnp
        self._maybe_reset_wire_flags(snap)
        tracer, trace_id = active_trace()

        new_placed = PlacedTensors(placed)
        if patch.full_tensors:
            # selective host materialization: only the named tensors are
            # read (a delta-emitted snapshot's dense image stays lazy)
            tensors = snap.tensors(only=frozenset(patch.full_tensors))
            if self._sharded and "verdict" in tensors:
                from cilium_tpu.parallel.mesh import pad_snapshot_tensors
                tensors = pad_snapshot_tensors(tensors, self.n_rule_shards)
            for name in patch.full_tensors:
                if name in tensors:
                    new_placed[name] = self._put_tensor(name, tensors[name])

        if patch.verdict_rows and "verdict" not in patch.full_tensors:
            use_delta = (self.config.delta_patch
                         and patch.delta_rows is not None)
            if use_delta:
                rows_np, vals_np = patch.delta_rows, patch.delta_vals
                k = rows_np.shape[0]
                # pow2 padding: a storm's varying patch sizes reuse a few
                # scatter traces; padded rows carry an OOB slot and drop
                kp = 1 << (k - 1).bit_length() if k > 1 else 1
                if kp != k:
                    pad_rows = np.zeros((kp - k, 3), dtype=np.int32)
                    pad_rows[:, 0] = self._PATCH_PAD_SLOT
                    rows_np = np.concatenate([rows_np, pad_rows])
                    vals_np = np.concatenate(
                        [vals_np, np.zeros((kp - k,) + vals_np.shape[1:],
                                           dtype=vals_np.dtype)])
                with tracer.span(trace_id, PATCH_APPLY_SPAN, rows=k):
                    if self._sharded:
                        import jax
                        rows_dev = jax.device_put(rows_np,
                                                  self._repl_sharding)
                        vals_dev = jax.device_put(vals_np,
                                                  self._repl_sharding)
                    else:
                        rows_dev = jnp.asarray(rows_np)
                        vals_dev = jnp.asarray(vals_np)
                    scatter_failed = False
                    with self._ct_lock:
                        # the fence: dead flips atomically with the
                        # donation — any enqueue that comes later sees it
                        # and retries against the new active snapshot
                        if isinstance(placed, PlacedTensors):
                            placed.dead = True
                        try:
                            new_placed["verdict"] = self._scatter_rows(
                                placed["verdict"], rows_dev, vals_dev)
                        except Exception:   # noqa: BLE001 — accounted below
                            # the donation may already have consumed the
                            # old buffer AND the handle is marked dead: a
                            # raise here would leave regenerate()'s
                            # serve-last-good degradation pinned on a
                            # handle every classify refuses. Self-heal
                            # with a full verdict upload of the NEW
                            # snapshot (outside the lock) instead.
                            scatter_failed = True
                    if scatter_failed:
                        # attribution: the healed patch COUNTS AS FULL —
                        # patch_delta must only ever mean "the donated
                        # scatter actually ran" (tests/test_update_storm.py
                        # reads it as exactly that)
                        self.patch_stats["patch_scatter_errors"] += 1
                        self.patch_stats["patch_full"] += 1
                        v = snap.tensors(only=frozenset(("verdict",)))
                        if self._sharded:
                            from cilium_tpu.parallel.mesh import \
                                pad_snapshot_tensors
                            v = pad_snapshot_tensors(v, self.n_rule_shards)
                        new_placed["verdict"] = self._put_tensor(
                            "verdict", v["verdict"])
                    else:
                        self.patch_stats["patch_delta"] += 1
                        self.patch_stats["patch_rows"] += k
            else:
                # legacy path (delta_patch off, or a patch without the
                # payload): functional row update from host-gathered
                # values — no donation, the old handle stays live
                rows = np.asarray(patch.verdict_rows, dtype=np.int32)
                vals = snap.tensors(only=frozenset(("verdict",)))[
                    "verdict"][rows[:, 0], rows[:, 1], rows[:, 2]]
                new_placed["verdict"] = placed["verdict"].at[
                    rows[:, 0], rows[:, 1], rows[:, 2]].set(
                        jnp.asarray(vals))
                self.patch_stats["patch_full"] += 1
        else:
            self.patch_stats["patch_full"] += 1
        self._account_placed(new_placed, patched=True)
        return new_placed

    def classify(self, placed, snap, batch, now):
        return self.classify_async(placed, snap, batch, now)()

    #: the exact key-set the sharded dict dispatch ships — shard_map
    #: in_specs mirror this pytree, so staging-ring extras (``_ep_raw``)
    #: must be filtered out before the call
    _BATCH_KEYS = ("src", "dst", "sport", "dport", "proto", "tcp_flags",
                   "is_v6", "ep_slot", "direction", "http_method",
                   "http_path", "valid")

    def _pack_wire(self, b, snap, pooled: bool, fallback_reason: str,
                   span=None):
        """The shared zero-copy pack: widen-then-choose the sticky wire
        format under the pack lock, check out a pooled wire buffer, pack in
        place. Returns (wire, path_dict_or_None, wire_key, wire_buf,
        dict_needed) — wire_key is None when the pack allocated (the buffer
        then just sheds to the GC instead of returning to the pool);
        dict_needed is for ``_upload_path_dict``: the bytes of the
        dictionary that are paths of rows that carry a request.

        The lock covers only widen-then-choose + the pool checkout (a
        concurrent place() reset can only land before or after this batch's
        whole format decision, never between); the column writes themselves
        run outside it — they touch only the private wire_buf, and
        serializing them would double pack latency whenever a control-plane
        classify (health probe, CLI) overlaps the pipeline worker. L7
        widening is POLICY-gated: with zero L7 rule sets, tokens cannot
        affect any verdict — shipping them is pure wire waste, and skipping
        them keeps tokenized traffic under an L7-free policy on the compact
        wire permanently (no reset/re-widen retrace flap across regens).

        ``pooled=False`` skips the pool entirely and counts the batch under
        ``pack_fallback_{fallback_reason}`` — for paths whose zero-copy
        chain already broke upstream (the allocating steer of an un-steered
        sharded batch).

        The reductions that choose the wire also count what each valid row
        needs of it by its own class (``wire_rows``, ``wire_bytes`` /
        ``wire_bytes_needed`` of ``pack_stats``, and ``wire_words`` /
        ``rows_wide`` / ``rows_l7`` on ``span``, the caller's
        ``datapath.pack``): a per-row mask is built only for a class the
        batch holds."""
        from cilium_tpu.kernels.records import (
            PACK4_EP_SLOT_MAX, _path_words_of, pack_batch, pack_batch_l7dict,
            pack_batch_v4, wire_words_for)
        valid = b["valid"]
        has_method = b["http_method"] != C.HTTP_METHOD_ANY
        batch_l7 = bool(has_method.any() or b["http_path"].any())
        slot_wide = int(b["ep_slot"].max(initial=0)) > PACK4_EP_SLOT_MAX
        batch_wide = bool(b["is_v6"].any() or slot_wide)
        n_valid = int(np.count_nonzero(valid))
        n_egress = int(np.count_nonzero(
            valid & (b["direction"] == C.DIR_EGRESS)))
        rows_wide = rows_l7 = rows_both = 0
        if batch_wide:
            wide = valid & b["is_v6"]
            if slot_wide:
                wide |= valid & (b["ep_slot"] > PACK4_EP_SLOT_MAX)
            rows_wide = int(np.count_nonzero(wide))
        if batch_l7 and snap.l7.n_sets > 0:
            # a row carries a request by its method or, where the
            # tokenizer knew none, by a path byte (has_l7_tokens). The
            # rows without a method are swept as one block: only where one
            # of them holds a path does the batch pay a reduction a row
            carries = valid & has_method
            bare = valid & ~has_method
            if bare.any() and b["http_path"].compress(bare, axis=0).any():
                carries |= bare & b["http_path"].any(axis=1)
            rows_l7 = int(np.count_nonzero(carries))
            if rows_wide:
                rows_both = int(np.count_nonzero(carries & wide))
        needed = 4 * (
            (n_valid - rows_wide - rows_l7 + rows_both)
            * wire_words_for(False, False)
            + (rows_wide - rows_both) * wire_words_for(False, True)
            + (rows_l7 - rows_both) * wire_words_for(True, False)
            + rows_both * wire_words_for(True, True))
        path_dict, dict_needed = None, 0
        n_rows = int(valid.shape[0])
        zero_copy = self.config.zero_copy_ingest and pooled
        with self._pack_lock:
            if snap.l7.n_sets > 0:
                self._wire_l7 |= batch_l7
            self._wire_wide |= batch_wide
            self._batches_since_wide = 0 if batch_wide \
                else self._batches_since_wide + 1
            use_l7, use_wide = self._wire_l7, self._wire_wide
            if use_l7:
                self._l7_path_words = max(self._l7_path_words,
                                          _path_words_of(b["http_path"]))
                l7_path_words = self._l7_path_words
                l7_min_rows = self._l7_dict_rows
            words = wire_words_for(use_l7, use_wide)
            self.wire_rows["wide_needed"] += rows_wide
            self.wire_rows["l7_needed"] += rows_l7
            self.wire_rows["egress"] += n_egress
            self.pack_stats["wire_bytes"] += 4 * n_rows * words
            self.pack_stats["wire_bytes_needed"] += needed
            wire_buf = self._wire_buf(n_rows, words) if zero_copy else None
            wire_key = (n_rows, words) if wire_buf is not None else None
            if wire_buf is not None:
                self.pack_stats["pack_inplace"] += 1
            elif not self.config.zero_copy_ingest:
                self.pack_stats["pack_fallback_disabled"] += 1
            else:
                self.pack_stats[
                    f"pack_fallback_{fallback_reason}"] += 1
        if span is not None:
            span.set(wire_words=words, rows_wide=rows_wide, rows_l7=rows_l7)
        try:
            if use_l7:
                t0 = time.monotonic()
                wire, path_dict = pack_batch_l7dict(
                    b, path_words=l7_path_words, min_rows=l7_min_rows,
                    force_full=use_wide, out=wire_buf)
                took = time.monotonic() - t0
                # either wire variant keeps a row's dictionary index in the
                # low half of its last word, and the index is dense from 0
                distinct = int((wire[:, -1] & 0xFFFF).max(initial=0)) + 1 \
                    if n_rows else 0
                # what the rows that carry a request put in the dictionary:
                # its paths that are not empty. The rows stand in ascending
                # byte order, so only the first can be the empty one (the
                # other rows'), and past ``distinct`` all is padding
                dict_needed = 4 * int(path_dict.shape[1]) * max(
                    0, distinct - (0 if path_dict[0].any() else 1))
                with self._pack_lock:       # dict geometry stays grow-only
                    self._l7_dict_rows = max(self._l7_dict_rows,
                                             path_dict.shape[0])
                    self.l7_stats["dict_paths"] += distinct
                # the dictionary's build (one sort of the batch's paths as
                # 64-byte items) and the wire's columns, inside the caller's
                # datapath.pack
                tracer, trace_id = active_trace()
                tracer.record(trace_id, L7_DICT_SPAN, t0, took, {
                    "rows": n_rows, "distinct": distinct,
                    "dict_rows": int(path_dict.shape[0]),
                    "bytes": int(path_dict.nbytes)})
            elif not use_wide:
                wire = pack_batch_v4(b, out=wire_buf)
            else:
                wire = pack_batch(b, l7=False, out=wire_buf)
        except BaseException:
            # the checkout already counted this buffer in flight; a pack
            # that dies here never reaches a finalize to release it
            self._wire_buf_shed(wire_key)
            raise
        return wire, path_dict, wire_key, wire_buf, dict_needed

    @staticmethod
    def _columnar(batch):
        """Already-columnar staged batches (the pipeline's staging ring,
        the shim feeder's harvest buffers) skip the per-batch dict copy;
        only mixed/jax-array pytrees still pay the conversion."""
        if all(type(v) is np.ndarray for v in batch.values()):
            return batch
        return {k: np.asarray(v) for k, v in batch.items()}

    def classify_async(self, placed, snap, batch, now, pre_steered=False):
        """Async dispatch (SURVEY.md §5 / the pipeline's overlap stage):
        host packing + transfer + XLA enqueue happen here, synchronously and
        in CT order, and the read-back of the results is STARTED here too
        (``copy_to_host_async`` right after the enqueue). On one chip the
        step hands back one packed verdict slab (kernels/records
        pack_out_jnp: every out column and the three counters in one
        uint32 vector), so the returned finalize blocks on exactly one
        device→host materialization — that is where the host actually
        waits on the device — and views the slab back into the same
        ``(out, counters)`` keys, dtypes and shapes a per-column read
        gives. A mesh does the same with one segment of the slab a chip, in
        one array sharded over 'flows' (``_read_slab``). One crossing
        each way per batch: the wire up, ``now``/``world_index`` riding
        the call's own argument transfer as numpy scalars, the slab down.
        Only the donated CT buffers need the lock — the slab is a fresh
        (non-donated) device array, safe to read after the lock is
        released, and XLA sequences the donated-CT dependency chain across
        in-flight steps by itself."""
        jnp = self._jnp
        if self._sharded:
            if self._rss_device:
                # device-side RSS: no steering anywhere — rows ship in
                # arrival order and the shard_map body's ppermute exchange
                # resolves CT ownership (pre_steered is meaningless)
                return self._classify_async_device(placed, snap, batch, now)
            return self._classify_async_sharded(placed, snap, batch, now,
                                                pre_steered=pre_steered)
        # observe/trace: the pack/transfer/compute split attaches to the
        # caller's current trace context (pipeline worker or
        # Engine.classify), whichever tracer instance set it
        tracer, trace_id = active_trace()
        with tracer.span(trace_id, "datapath.pack") as pack_span:
            b = self._columnar(batch)
            wire, path_dict, wire_key, wire_buf, dict_needed = \
                self._pack_wire(b, snap, pooled=True,
                                fallback_reason="shape", span=pack_span)
        try:
            with tracer.span(trace_id, "datapath.transfer",
                             bytes=int(wire.nbytes)):
                # chaos points: a wedged/failed host→device link (hang mode
                # is what the pipeline watchdog drill stalls on), and the CT
                # insert phase of this dispatch (a trip rejects the batch —
                # tickets fail closed, FIFO intact — the ddos-smoke drill)
                FAULTS.fire("datapath.transfer")
                FAULTS.fire("ct.insert")
                if path_dict is not None:
                    dev_batch = (jnp.asarray(wire),
                                 self._upload_path_dict(
                                     path_dict, dict_needed))
                else:
                    dev_batch = jnp.asarray(wire)
                with self._ct_lock:
                    self._check_placed(placed)
                    # a PlacedTensors handle is a dict SUBCLASS (not a
                    # registered pytree): hand jit the plain-dict view.
                    # now/world_index go up as numpy scalars (uint32[] /
                    # int32[], not weak-typed: one trace for every value)
                    # inside the call's own argument transfer — a
                    # jnp.uint32(now) is a device program of its own
                    slab, new_ct = self._classify(
                        dict(placed), self._ct, dev_batch, np.uint32(now),
                        np.int32(snap.world_index))
                    self._ct = new_ct
                # outside the CT lock: the slab is not donated. The copy
                # back starts behind the step now, so finalize finds it
                # done (or waits for one transfer, never eighteen)
                slab.words.copy_to_host_async()
        except BaseException:
            self._wire_buf_shed(wire_key)    # finalize will never run
            raise

        def finalize():
            # stages inside one jit are not separately timeable from the
            # host, so there is no per-kernel span
            try:
                with tracer.span(trace_id, "datapath.compute", WAIT):
                    words = np.asarray(slab.words)
            except BaseException:
                # a failed materialization (device error) never releases:
                # shed the checkout count, the buffer goes to the GC
                self._wire_buf_shed(wire_key)
                raise
            with tracer.span(trace_id, "datapath.unpack"):
                if wire_key is not None:
                    # the device is provably done with this batch (the
                    # slab is materialized): the wire buffer is safe to
                    # reuse now — and ONLY now (a dispatch that never
                    # finalizes simply sheds its buffer to the GC)
                    self._wire_buf_release(wire_key, wire_buf)
                with self._pack_lock:
                    self.pack_stats["readback_slab"] += 1
                return unpack_out(words, slab.layout)
        return finalize

    def _wire_buf(self, rows: int, words: int) -> Optional[np.ndarray]:
        """Checkout a pooled wire buffer (pack lock held); it returns to
        the pool in its batch's finalize. Pool misses allocate (the pool
        refills in steady state). Non-power-of-two row counts — rare
        control-plane batches (health probes, pcap tails) — return None:
        pooling every distinct size ever seen would grow without bound."""
        if rows & (rows - 1):
            return None
        self._wire_out += 1
        pool = self._wire_pool.get((rows, words))
        if pool:
            return pool.pop()
        return np.empty((rows, words), dtype=np.uint32)

    def _wire_buf_release(self, key: Tuple[int, int],
                          buf: np.ndarray) -> None:
        with self._pack_lock:
            self._wire_out = max(0, self._wire_out - 1)
            pool = self._wire_pool.setdefault(key, [])
            if len(pool) < self._wire_pool_cap:
                pool.append(buf)

    def _wire_buf_shed(self, wire_key) -> None:
        """A dispatch died between checkout and finalize (fault trip,
        transfer failure): the buffer itself sheds to the GC — it may be
        aliased by an aborted transfer, never re-pool it — but the
        in-flight count must come back down or the wire_pool ledger row
        reports phantom occupancy forever."""
        if wire_key is None:
            return
        with self._pack_lock:
            self._wire_out = max(0, self._wire_out - 1)

    def l7_wire_stats(self) -> Dict[str, int]:
        """The L7 dictionary wire's two totals and its grow-only geometry
        (what the serving programs are traced at), read at one instant."""
        with self._pack_lock:
            return dict(self.l7_stats, path_words=self._l7_path_words,
                        dict_rows=self._l7_dict_rows)

    def wire_stats(self) -> Tuple[Dict[str, int], Dict[str, int]]:
        """(``wire_rows``, ``pack_stats``) read at one instant."""
        with self._pack_lock:
            return dict(self.wire_rows), dict(self.pack_stats)

    def _upload_path_dict(self, path_dict: np.ndarray, needed: int):
        """Device copy of the L7 path dict, cached by content: serving
        traffic repeats a small stable path set, so in steady state the
        dict (host-compared — same cost as one pack column) is uploaded
        once and every later batch reuses the device array."""
        with self._pack_lock:
            cached_host = self._path_dict_host
            cached_dev = self._path_dict_dev
        # compare + upload OUTSIDE the lock: array_equal is O(dict) and
        # the upload is a host→device transfer — neither may serialize
        # concurrent pack work (concurrent misses just race to fill the
        # cache; last write wins, both uploads are correct)
        if (cached_dev is not None and cached_host is not None
                and cached_host.shape == path_dict.shape
                and np.array_equal(cached_host, path_dict)):
            with self._pack_lock:
                self.pack_stats["upload_cache_hits"] += 1
            return cached_dev
        if self._sharded:
            import jax
            dev = jax.device_put(path_dict, self._repl_sharding)
        else:
            dev = self._jnp.asarray(path_dict)
        with self._pack_lock:
            self.pack_stats["upload_cache_misses"] += 1
            self.l7_stats["dict_upload_bytes"] += int(path_dict.nbytes)
            # the second upload is the wire's too; ``needed`` of it is
            # the paths of the rows that carry a request (_pack_wire)
            self.pack_stats["wire_bytes"] += int(path_dict.nbytes)
            self.pack_stats["wire_bytes_needed"] += needed
            # the dict is a fresh array every batch (never pool-aliased):
            # safe to retain as the comparison baseline without a copy
            self._path_dict_host = path_dict
            self._path_dict_dev = dev
        return dev

    def _read_slab(self, tracer, trace_id, slab, shards, wire_key,
                   wire_buf):
        """The meshed finalizers' device→host crossing: the one verdict
        slab of the batch, ``shards`` equal per-chip segments in one array
        sharded over 'flows' whose copy back started at dispatch, read in
        one ``np.asarray`` and viewed back into ``(out, counters)``.
        ``shards`` is the width the batch was DISPATCHED with (a remesh
        may have come since). ``datapath.readback`` spans that read,
        inside ``datapath.compute``; the wire buffer is released only once
        the slab is on the host (the device is then done with the batch),
        and a failed materialization sheds it and is checked for a dead
        chip's signature."""
        try:
            with tracer.span(trace_id, "datapath.compute", WAIT):
                with tracer.span(trace_id, "datapath.readback", WAIT,
                                 arrays=1, shards=shards):
                    words = np.asarray(slab.words)
        except BaseException as e:
            self._wire_buf_shed(wire_key)      # failed materialization
            self._maybe_device_lost(e)
            raise
        with tracer.span(trace_id, "datapath.unpack"):
            if wire_key is not None:
                self._wire_buf_release(wire_key, wire_buf)
            with self._pack_lock:
                self.pack_stats["readback_slab"] += 1
            return unpack_out(words, slab.layout, shards)

    def _classify_async_sharded(self, placed, snap, batch, now,
                                pre_steered=False):
        """The meshed overlap stage. Pre-steered batches (the pipeline's
        sharded staging ring delivers rows already grouped into equal
        per-shard segments) pack IN PLACE into one pooled wire buffer whose
        segments are exactly the per-chip transfers (P('flows') splits dim
        0 on the segment boundaries) — the per-batch steer→allocate→pack
        chain of the pre-PR-6 path is gone, and finalize returns outputs in
        the steered geometry (the caller un-steers; the pipeline does it
        per-slice while gathering ticket rows, which IS the
        unsteer-on-finalize that keeps FIFO verdicts bit-identical).

        Un-steered batches (the synchronous control-plane entry: health
        probes, CLI classify) steer here with the classic allocating
        regroup — counted ``pack_fallback_steered`` so a residual allocating
        dispatch on the serving path is attributable — and finalize
        un-steers back to the caller's row order. A rules-only mesh
        (n_flow_shards == 1) needs no row grouping at all: every batch
        counts as pre-steered."""
        import jax
        from cilium_tpu.parallel.mesh import steer_batch, unsteer_outputs
        tracer, trace_id = active_trace()
        n = self.n_flow_shards      # the width this batch is dispatched at
        pre = pre_steered or n == 1
        scatter = None
        with tracer.span(trace_id, "datapath.pack", shards=n) as pack_span:
            b = self._columnar(batch)
            if not pre:
                # steering must hash the post-DNAT tuple (service flows' CT
                # entries live under the translated tuple) — the same
                # translation the shim/feeder runs when it pre-bins
                lb = snap.lb if snap.lb.n_frontends else None
                with tracer.span(trace_id, "datapath.steer"):
                    b, scatter, _per = steer_batch(
                        b, n, lb=lb, round_to_pow2=True)
            n_rows = int(b["valid"].shape[0])
            if n_rows % n:
                raise ValueError(
                    f"pre-steered batch rows ({n_rows}) must divide into "
                    f"{n} flow shards")
            if not self.config.zero_copy_ingest:
                # legacy dict dispatch (12 P('flows') column transfers);
                # shard_map in_specs mirror the exact key-set, so staging
                # extras must not ride along
                with self._pack_lock:
                    self.pack_stats["pack_fallback_disabled"] += 1
                wire = path_dict = None
                wire_key = wire_buf = None
                dict_batch = {k: b[k] for k in self._BATCH_KEYS}
                nbytes = sum(v.nbytes for v in dict_batch.values())
            else:
                dict_batch = None
                # attribution: a pre-steered batch that still allocates can
                # only do so for a pool-unfriendly shape; only the
                # allocating-regroup path above earns the "steered" label
                wire, path_dict, wire_key, wire_buf, dict_needed = \
                    self._pack_wire(
                        b, snap, pooled=pre,
                        fallback_reason="shape" if pre else "steered",
                        span=pack_span)
                nbytes = int(wire.nbytes)
        try:
            with tracer.span(trace_id, "datapath.transfer", bytes=nbytes,
                             shards=n):
                FAULTS.fire("datapath.transfer")
                FAULTS.fire("ct.insert")
                self._fire_device_fault()
                if dict_batch is not None:
                    dev_batch = dict_batch   # the jit shards the columns
                elif path_dict is not None:
                    dev_batch = (jax.device_put(wire, self._batch_sharding),
                                 self._upload_path_dict(
                                     path_dict, dict_needed))
                else:
                    dev_batch = jax.device_put(wire, self._batch_sharding)
                with self._ct_lock:
                    self._check_placed(placed)
                    slab, new_ct = self._classify(
                        dict(placed), self._ct, dev_batch, np.uint32(now),
                        np.int32(snap.world_index))
                    self._ct = new_ct
                # outside the CT lock (the slab is not donated): every
                # chip's segment starts its way back behind the step
                slab.words.copy_to_host_async()
        except BaseException as e:
            self._wire_buf_shed(wire_key)    # finalize will never run
            self._maybe_device_lost(e)       # dead-chip signature? reclassify
            raise

        def finalize():
            out_np, counters_np = self._read_slab(
                tracer, trace_id, slab, n, wire_key, wire_buf)
            if scatter is not None:
                out_np = unsteer_outputs(out_np, scatter)
            return out_np, counters_np
        return finalize

    def _classify_async_device(self, placed, snap, batch, now):
        """The device-RSS overlap stage: the batch ships in plain ARRIVAL
        order — packed in place into one pooled wire buffer whose equal
        per-chip slices are the transfers (P('flows') splits dim 0) — and
        flow→shard resolution happens inside the shard_map body via the
        ring ppermute CT exchange (parallel/exchange.py). There is no
        steer span, no scatter, and no un-steer: outputs come back in the
        same FIFO row order they were submitted in.

        The only shape contract is divisibility: each chip takes an equal
        pow2 arrival-order slice, so arbitrary-size control-plane batches
        (health probes, CLI classify) pad to the next pow2 multiple of
        the mesh with invalid rows (mirroring the steered path's
        round_to_pow2 trace discipline) and finalize truncates the
        padding. Pipeline buckets are pow2 >= the mesh by construction
        (the engine clamps min_bucket) and ship unpadded."""
        import jax
        from cilium_tpu.parallel.exchange import exchange_bytes
        tracer, trace_id = active_trace()
        n = self.n_flow_shards
        with tracer.span(trace_id, "datapath.pack",
                         shards=n, rss="device") as pack_span:
            b = self._columnar(batch)
            orig_rows = int(b["valid"].shape[0])
            rows = orig_rows
            if rows % n or rows & (rows - 1):
                from cilium_tpu.kernels.records import empty_batch
                per = 1 << max(0, (-(-rows // n) - 1).bit_length())
                rows = per * n
                pb = empty_batch(rows)
                for k, col in pb.items():
                    col[:orig_rows] = b[k]
                b = pb
            if not self.config.zero_copy_ingest:
                with self._pack_lock:
                    self.pack_stats["pack_fallback_disabled"] += 1
                wire = path_dict = None
                wire_key = wire_buf = None
                dict_batch = {k: b[k] for k in self._BATCH_KEYS}
                nbytes = sum(v.nbytes for v in dict_batch.values())
            else:
                dict_batch = None
                wire, path_dict, wire_key, wire_buf, dict_needed = \
                    self._pack_wire(b, snap, pooled=True,
                                    fallback_reason="shape", span=pack_span)
                nbytes = int(wire.nbytes)
        # exchange-buffer accounting (the HBM ledger's ``exchange`` group):
        # per-mesh bytes the ring materializes for this bucket shape
        ex_bytes = exchange_bytes(rows, n)
        with self._hbm_lock:
            self._exchange_last_bytes = ex_bytes
            self._exchange_bytes_total += ex_bytes
            self._exchange_batches_total += 1
            if ex_bytes > self._exchange_peak_bytes:
                self._exchange_peak_bytes = ex_bytes
                self._hbm_groups["exchange"] = ex_bytes
        try:
            with tracer.span(trace_id, "datapath.transfer", bytes=nbytes,
                             shards=n):
                FAULTS.fire("datapath.transfer")
                FAULTS.fire("ct.insert")
                self._fire_device_fault()
                if dict_batch is not None:
                    dev_batch = dict_batch   # the jit shards the columns
                elif path_dict is not None:
                    dev_batch = (jax.device_put(wire, self._batch_sharding),
                                 self._upload_path_dict(
                                     path_dict, dict_needed))
                else:
                    dev_batch = jax.device_put(wire, self._batch_sharding)
                with self._ct_lock:
                    self._check_placed(placed)
                    slab, new_ct = self._classify(
                        dict(placed), self._ct, dev_batch, np.uint32(now),
                        np.int32(snap.world_index))
                    self._ct = new_ct
                # outside the CT lock (the slab is not donated): every
                # chip's segment starts its way back behind the step
                slab.words.copy_to_host_async()
        except BaseException as e:
            self._wire_buf_shed(wire_key)    # finalize will never run
            self._maybe_device_lost(e)       # dead-chip signature? reclassify
            raise

        def finalize():
            out_np, counters_np = self._read_slab(
                tracer, trace_id, slab, n, wire_key, wire_buf)
            if orig_rows != rows:
                # padded control-plane batch: outputs are already FIFO —
                # dropping the invalid tail is the whole "un-steer"
                out_np = {k: v[:orig_rows] for k, v in out_np.items()}
            return out_np, counters_np
        return finalize

    def _check_placed(self, placed) -> None:
        """Classify-lock-held donation fence: refuse to enqueue against a
        handle whose buffers a delta patch donated away."""
        if isinstance(placed, PlacedTensors) and placed.dead:
            self.patch_stats["patch_stale_fences"] += 1
            raise StalePlacement(
                "placed snapshot was delta-patched in place; re-capture "
                "the active snapshot and retry")

    def sweep(self, now: int) -> int:
        from cilium_tpu.kernels import conntrack as ctk
        with self._ct_lock:
            new_ct, n = ctk.ct_sweep(self._ct, self._jnp.uint32(now))
            self._ct = new_ct
        return int(n)

    def sweep_step(self, now: int, chunk_rows: int,
                   ttl_slash_s: int = 0) -> Dict[str, int]:
        """One tick of the overlapped device-side epoch GC (SURVEY.md §2
        "pipelined device-side epoch sweep"; ROADMAP item 3c).

        Each tick enqueues a donated chunk sweep over
        ``[cursor, cursor + chunk_rows)`` of the slot space — interleaving
        with classify steps under the same lock discipline as the classify
        dispatch itself (the enqueue is microseconds; XLA sequences the
        donated-CT dependency chain) — and *harvests the previous tick's*
        reclaimed/occupancy scalars, which resolved on-device while traffic
        ran. That one-tick-late readback is the double buffer: the host
        never blocks on sweep compute inside the enqueue path, and the
        whole-table stop-the-world sync of the old host-driven
        ``sweep()`` is gone.

        ``ttl_slash_s`` (emergency GC) pushes the SWEEP clock that far
        into the future — entries within that many seconds of expiry are
        reclaimed early — while the occupancy count stays on the real
        clock (a slashed count would read low and flap the pressure
        latch's exit hysteresis).

        Returns {"reclaimed", "live", "cursor", "epoch", "chunk_rows"};
        ``live`` is -1 until the first harvest lands."""
        import functools
        jnp = self._jnp
        if self._gc_fn is None or self._gc_chunk != chunk_rows:
            import jax
            from cilium_tpu.kernels.conntrack import ct_sweep_chunk
            self._gc_chunk = chunk_rows
            self._gc_fn = jax.jit(
                functools.partial(ct_sweep_chunk, chunk_rows=chunk_rows),
                donate_argnums=(0,))
        # harvest the previous tick's scalars (long since resolved)
        if self._gc_pending is not None:
            n_dev, live_dev = self._gc_pending
            self._gc_reclaimed_total += int(n_dev)
            self._gc_last_live = int(live_dev)
            reclaimed = int(n_dev)
            self._gc_pending = None
        else:
            reclaimed = 0
        # the LIVE capacity: a remesh clamps the table to the survivor
        # count's power-of-two geometry, and a cursor wrapping on the
        # configured size would sweep past the end of the shrunken table
        cap = int(self._ct_capacity)
        tracer, trace_id = active_trace()
        with tracer.span(trace_id, CT_GC_SPAN,
                         cursor=self._gc_cursor, chunk=chunk_rows):
            with self._ct_lock:
                new_ct, n_dev, live_dev = self._gc_fn(
                    self._ct, jnp.uint32(now + ttl_slash_s),
                    jnp.uint32(self._gc_cursor),
                    count_now=jnp.uint32(now))
                self._ct = new_ct
        self._gc_pending = (n_dev, live_dev)
        cursor = self._gc_cursor
        self._gc_cursor = (self._gc_cursor + chunk_rows) % cap
        if self._gc_cursor <= cursor:
            self._gc_epoch += 1            # wrapped: one full epoch swept
        return {
            "reclaimed": reclaimed,
            "reclaimed_total": self._gc_reclaimed_total,
            "live": self._gc_last_live,
            "cursor": cursor,
            "epoch": self._gc_epoch,
            "chunk_rows": chunk_rows,
        }

    def ct_stats(self, now: int) -> Dict[str, int]:
        # _ct buffers are donated into classify/sweep: reading outside the
        # lock can observe deleted device arrays mid-swap. Copy inside.
        with self._ct_lock:
            expiry = np.asarray(self._ct["expiry"])
        return {
            "capacity": int(expiry.shape[0]),
            "live": int((expiry > now).sum()),
            "stale": int(((expiry > 0) & (expiry <= now)).sum()),
        }

    def ct_arrays(self) -> Dict[str, np.ndarray]:
        # copy the arrays out under the lock (they are donated into the next
        # step); re-join the planes as rows outside it
        with self._ct_lock:
            host = {k: np.array(v) for k, v in self._ct.items()}
        return logical_ct_arrays(host)

    def _place_ct(self, arrays: Dict[str, np.ndarray]) -> None:
        """Logical host arrays → the device table in its placed form (ct
        lock held): the one way a table reaches the device after start."""
        placed = place_ct_arrays(arrays)
        if self._sharded:
            import jax
            self._ct = {k: jax.device_put(v, self._ct_sharding)
                        for k, v in placed.items()}
        else:
            self._ct = {k: self._jnp.asarray(v) for k, v in placed.items()}

    def load_ct_arrays(self, arrays: Dict[str, np.ndarray]) -> None:
        import logging
        from cilium_tpu.parallel.mesh import rehash_ct_arrays
        arrays = normalize_ct_arrays(arrays)
        # re-place entries for THIS backend's probe geometry: imported tables
        # may come from a different shard count or the dense fake export
        arrays, dropped = rehash_ct_arrays(
            arrays, self.n_flow_shards, self.config.probe_depth,
            capacity=self._ct_capacity)
        if dropped:
            logging.getLogger("cilium_tpu.datapath").warning(
                "load_ct_arrays: %d entries dropped (probe window exhausted "
                "during rehash into %d shard(s))", dropped,
                self.n_flow_shards)
        with self._ct_lock:
            self._place_ct(arrays)
        self._account_ct_hbm()

    # -- mesh self-healing (ISSUE 19: device loss → fenced re-mesh onto
    # survivors → CT salvage → hysteretic re-admission) ----------------------
    def _fire_device_fault(self) -> None:
        """The ``device.fail`` drill, fired on every sharded dispatch. A
        trip naming an ordinal that already LEFT the serving mesh is
        swallowed: the dead chip cannot hurt a mesh it is no longer part
        of — that swallow is what lets degraded serving run with the fault
        still armed (disarming the point is the drill's heal signal). Trips
        naming a live ordinal, or naming none, propagate into the dispatch
        failure path where :meth:`_maybe_device_lost` reclassifies them."""
        try:
            FAULTS.fire("device.fail")
        except BaseException as e:
            dev = dead_device_of(e)
            if dev is not None and dev >= 0 \
                    and dev not in self._live_ordinals:
                return
            raise

    def _maybe_device_lost(self, exc: BaseException) -> None:
        """Dispatch-failure triage (the detection half of ISSUE 19):
        transient errors return untouched — the caller's breaker/backoff
        machinery owns those — while a dead-accelerator signature latches
        the per-device health record and re-raises as
        :class:`~cilium_tpu.pipeline.guard.DeviceLost`, the signal the
        pipeline parks its worker on and the engine re-meshes from."""
        if isinstance(exc, DeviceLost):
            raise exc
        dev = dead_device_of(exc)
        if dev is None:
            return
        self.note_device_loss(dev, reason=str(exc))
        raise DeviceLost(
            f"dead-device signature in sharded dispatch: {exc}",
            device=dev) from exc

    def note_device_loss(self, ordinal: int, reason: str = "") -> None:
        """Latch a per-device health record. The FIRST loss's evidence is
        kept (a storm of failures off one dead chip must not churn the
        record the debug bundle will cite); an unattributed loss (-1)
        records nothing — the engine's probe pass owns attribution then."""
        if ordinal < 0 or ordinal >= self._configured_flow_shards:
            return
        rec = self.device_health.get(ordinal)
        if rec is not None and rec.get("state") == "dead":
            return
        self.device_health[ordinal] = {
            "state": "dead", "since": time.time(),
            "reason": str(reason)[:200]}

    def note_device_healed(self, ordinal: int) -> None:
        rec = self.device_health.get(ordinal)
        if rec is not None:
            rec.update(state="live", since=time.time(), reason="")

    def probe_device(self, ordinal: int) -> bool:
        """Heal canary for one CONFIGURED chip: the chaos drill first (an
        armed ``device.fail`` naming this ordinal — or attributing to no
        ordinal at all — means still dead), then a real host→device round
        trip against the chip itself. Never raises: a failed probe IS the
        answer."""
        try:
            FAULTS.fire("device.fail")
        except BaseException as e:   # noqa: BLE001 — trip text is the verdict
            dev = dead_device_of(e)
            if dev is None or dev < 0 or dev == ordinal:
                return False
        if self._configured_devices is None:
            return True
        try:
            import jax
            dev0 = self._configured_devices[ordinal][0]
            np.asarray(jax.device_put(np.ones(8, np.uint8), dev0))
        except Exception:   # noqa: BLE001 — a failed probe IS the answer
            return False
        return True

    def mesh_health(self) -> Dict[str, Any]:
        """Operator-facing mesh-width surface: configured vs currently
        SERVING flow shards, which ordinals serve, and the per-device
        health records — what ``Engine.health()`` and the ``mesh_width``
        resource-ledger row render."""
        dead = sorted(o for o, r in self.device_health.items()
                      if r.get("state") == "dead")
        return {
            "configured": self._configured_flow_shards,
            "live": self.n_flow_shards if self._sharded else 1,
            "live_ordinals": list(self._live_ordinals),
            "dead_ordinals": dead,
            "devices": {int(k): dict(v)
                        for k, v in self.device_health.items()},
        }

    def remesh(self, live_ordinals, fence_handle=None,
               salvage_floor: Optional[Dict[str, np.ndarray]] = None
               ) -> Dict[str, Any]:
        """Shrink (or re-grow) the serving mesh to exactly the given
        CONFIGURED flow-shard ordinals, salvaging the conntrack table
        across the transition. Runs under the classify lock — the caller
        (Engine._remesh_to) has already fenced the pipeline generation, so
        nothing is dispatching concurrently; a racing CONTROL-PLANE
        classify that captured the pre-remesh placed handle hits the
        ``fence_handle.dead`` flip and retries via StalePlacement.

        CT salvage order (each fallback counted in ``remesh_stats``):
        device gather (``device.collective`` is the chaos point) → the
        caller's ``salvage_floor`` archive (the ct-snapshot controller's
        bounded-staleness npz) → a cold table. On a successful gather the
        LOST shards' slots are zeroed first: on real hardware that state
        died with the chip, and the CPU rig must not get a free pass the
        grace window was built to cover.

        The new table's capacity is ``degraded_ct_capacity`` — the largest
        per-shard power of two the survivor count divides into — and
        surviving entries rehash into it (probe-window casualties counted
        ``remesh_ct_dropped``). The caller re-places the active snapshot
        onto the new mesh afterwards."""
        if not self._sharded or self._configured_devices is None:
            raise ValueError("remesh requires a flow-sharded mesh")
        live = sorted({int(o) for o in live_ordinals})
        if not live:
            raise ValueError("remesh needs at least one surviving shard")
        bad = [o for o in live
               if not 0 <= o < self._configured_flow_shards]
        if bad:
            raise ValueError(f"remesh ordinals out of range: {bad}")
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from cilium_tpu.parallel.mesh import (
            degraded_ct_capacity, drop_ct_shard, make_mesh,
            make_sharded_classify_fn, make_unsteered_classify_fn,
            rehash_ct_arrays, shard_ct_arrays)
        n_new = len(live)
        started = time.monotonic()
        with self._ct_lock:
            old_live = list(self._live_ordinals)
            old_n = len(old_live)
            if live == old_live:
                return {"from": old_n, "to": n_new, "noop": True,
                        "live_ordinals": live}
            # 1) salvage: gather the current table to host. The gather is
            # itself a collective over a possibly-degraded mesh — its
            # failure (chaos: device.collective) falls back to the archive
            # floor, then cold.
            source = "device"
            arrays: Optional[Dict[str, np.ndarray]] = None
            try:
                FAULTS.fire("device.collective")
                # a host copy (the gather view off a jax buffer is
                # read-only, and the lost-shard zeroing below mutates)
                arrays = logical_ct_arrays(self._ct)
            except Exception:   # noqa: BLE001 — counted, archive/cold floor
                self.remesh_stats["remesh_gather_failures"] += 1
            lost = 0
            if arrays is not None:
                for pos, o in enumerate(old_live):
                    if o not in live:
                        lost += drop_ct_shard(arrays, pos, old_n)
            elif salvage_floor is not None:
                source = "archive"
                arrays = {k: np.array(v) for k, v in salvage_floor.items()}
            else:
                source = "cold"
                arrays = logical_ct_arrays(make_ct_arrays(
                    CTConfig(self.config.ct_capacity,
                             self.config.probe_depth)))
            new_cap = degraded_ct_capacity(self.config.ct_capacity, n_new)
            arrays, dropped = rehash_ct_arrays(
                arrays, n_new, self.config.probe_depth, capacity=new_cap)
            salvaged = int((arrays["expiry"] > 0).sum())
            # 2) survivor geometry, cached per exact device set: healing
            # back onto a set that served before reuses its jitted classify
            key = tuple(live)
            cached = self._mesh_cache.get(key)
            if cached is None:
                devices = [d for o in live
                           for d in self._configured_devices[o]]
                mesh = make_mesh(n_new, self.n_rule_shards, devices=devices)
                make_fn = (make_unsteered_classify_fn if self._rss_device
                           else make_sharded_classify_fn)
                cached = (
                    mesh,
                    NamedSharding(mesh, P("flows")),
                    NamedSharding(mesh, P()),
                    NamedSharding(mesh, P("flows")),
                    NamedSharding(mesh, P(None, None, "rules", None)),
                    make_fn(mesh,
                            probe_depth=self.config.probe_depth,
                            v4_only=self.config.v4_only,
                            donate_ct=self.config.donate_ct,
                            slab=True))
                self._mesh_cache[key] = cached
            (self._mesh, self._ct_sharding, self._repl_sharding,
             self._batch_sharding, self._verdict_sharding,
             self._classify) = cached
            shard_ct_arrays(arrays, n_new)    # divisibility fail-fast
            self._place_ct(arrays)
            self.n_flow_shards = n_new
            self._live_ordinals = live
            self._ct_capacity = new_cap
            # the overlapped GC's jitted chunk sweep and the donated
            # verdict scatter both baked the OLD geometry — drop them and
            # restart the sweep cursor in the new slot space
            self._gc_fn = None
            self._gc_cursor = 0
            self._gc_pending = None
            self._scatter_fn = None
            self.remesh_stats["remesh_total"] += 1
            self.remesh_stats["remesh_ct_salvaged"] += salvaged
            self.remesh_stats["remesh_ct_lost"] += lost
            self.remesh_stats["remesh_ct_dropped"] += dropped
            # fence: flips atomically with the geometry swap, so a control-
            # plane classify holding the old handle can never enqueue
            # against the old mesh's shardings
            if isinstance(fence_handle, PlacedTensors):
                fence_handle.dead = True
        self._account_ct_hbm()
        return {"from": old_n, "to": n_new, "live_ordinals": live,
                "ct_capacity": new_cap, "ct_salvaged": salvaged,
                "ct_lost": lost, "ct_dropped": dropped,
                "salvage_source": source,
                "took_s": round(time.monotonic() - started, 3)}


class FakeDatapath(DatapathBackend):
    """Jax-free backend for control-plane tests (pkg/datapath/fake analog).

    ``place`` records the snapshot + its would-be device images (numpy) in
    ``self.placed`` so tests can assert "map contents" exactly like upstream
    control-plane tests assert policymap/lbmap state. ``classify`` runs the
    semantics oracle over the same snapshot, so verdicts follow the real
    contract — a second, independent implementation behind the same
    boundary. Conntrack is the oracle's exact table; the array view is
    reconstructed on demand in the ct_layout schema."""

    PLACED_KEEP = 64                     # placement history cap (memory bound)

    def __init__(self, config: Optional[DaemonConfig] = None):
        from oracle import ConntrackTable
        self.config = config or DaemonConfig()
        # [(snapshot, tensors_np)], in order; a long-lived engine with
        # auto-regen would otherwise grow this without bound — keep the most
        # recent PLACED_KEEP (tests only assert against recent placements)
        self.placed = []
        self.placed_total = 0            # placements ever (incl. evicted)
        # BOUNDED oracle table (device hash, same probe window) so the
        # fake exhibits the device's exact CT-exhaustion semantics — at the
        # configured capacity a saturating test sees the same tail
        # evictions and CT_FULL denies the jnp kernel computes, slot for
        # slot (single-chip layout; the sharded mesh's per-shard tables
        # hash differently and are out of the fake's scope)
        self._ct_table = ConntrackTable(capacity=self.config.ct_capacity,
                                        probe_depth=self.config.probe_depth)
        self._oracle = None
        self._oracle_snap = None         # snapshot the cached oracle is for
        self.ct_export_truncated = 0     # entries dropped by ct_arrays()
        self._lock = threading.Lock()

    # -- helpers -------------------------------------------------------------
    def _oracle_for(self, snap: PolicySnapshot):
        """Oracle for EXACTLY ``snap`` — cached by snapshot identity, so a
        batch is always evaluated against the snapshot revision the Engine
        captured (revision fencing: a concurrent place() of a newer snapshot
        must not retarget an in-flight batch)."""
        from oracle import Oracle
        if self._oracle is None or self._oracle_snap is not snap:
            # CT persists across snapshot swaps: the table, not the oracle,
            # owns connection state
            oracle = Oracle.for_snapshot(snap, ct=self._ct_table)
            self._oracle, self._oracle_snap = oracle, snap
        return self._oracle

    # -- DatapathBackend -----------------------------------------------------
    def place(self, snap: PolicySnapshot) -> Dict:
        tensors = snap.tensors()         # numpy, no device
        with self._lock:
            self.placed.append((snap, tensors))
            self.placed_total += 1
            if len(self.placed) > self.PLACED_KEEP:
                del self.placed[:-self.PLACED_KEEP]
        return tensors

    def classify(self, placed, snap, batch, now):
        FAULTS.fire("ct.insert")      # same drill point as the JIT path
        with self._lock:
            oracle = self._oracle_for(snap)
            records = _records_from_batch(batch, snap.ep_ids)
            live = [p for p in records if p is not None]
            # counter baselines BEFORE the classify mutates the table
            evicted0 = self._ct_table.evicted
            fail0 = self._ct_table.insert_fail
            verdicts = iter(oracle.classify_batch_snapshot(live, now))
            n = len(records)
            out = {
                "allow": np.zeros(n, bool),
                "reason": np.zeros(n, np.int32),
                "status": np.zeros(n, np.int32),
                "ct_full": np.zeros(n, bool),
                "remote_identity": np.zeros(n, np.int32),
                "redirect": np.zeros(n, bool),
                # provenance columns (invalid rows: -1 like the device's
                # valid mask; ct_state_pre 0 == CTStatus.NEW, matching the
                # kernel's valid-masked est/reply)
                "matched_rule": np.full(n, -1, np.int32),
                "lpm_prefix": np.full(n, -1, np.int32),
                "ct_state_pre": np.zeros(n, np.int32),
                "svc": np.zeros(n, bool),
                "nat_dst": np.zeros((n, 4), np.uint32),
                "nat_dport": np.zeros(n, np.int32),
                "rnat": np.zeros(n, bool),
                "rnat_src": np.zeros((n, 4), np.uint32),
                "rnat_sport": np.zeros(n, np.int32),
            }
            counters = {"by_reason_dir": np.zeros(C.COUNTER_CELLS, np.uint32),
                        "insert_fail": np.uint32(0)}
            for i, p in enumerate(records):
                if p is None:
                    continue
                v = next(verdicts)
                out["allow"][i] = v.allow
                out["reason"][i] = v.drop_reason
                out["status"][i] = v.ct_status
                out["ct_full"][i] = v.ct_full
                out["remote_identity"][i] = v.remote_identity
                out["redirect"][i] = v.redirect
                out["matched_rule"][i] = v.matched_rule
                out["lpm_prefix"][i] = v.lpm_prefix
                out["ct_state_pre"][i] = v.ct_status
                out["svc"][i] = v.svc
                if v.nat_dst:
                    out["nat_dst"][i] = np.frombuffer(v.nat_dst, dtype=">u4")
                out["nat_dport"][i] = v.nat_dport
                out["rnat"][i] = v.rnat
                if v.rnat_src:
                    out["rnat_src"][i] = np.frombuffer(v.rnat_src, dtype=">u4")
                out["rnat_sport"][i] = v.rnat_sport
                counters["by_reason_dir"][int(v.drop_reason) * 2
                                          + p.direction] += 1
            counters["insert_fail"] = np.uint32(
                self._ct_table.insert_fail - fail0)
            counters["ct_evicted"] = np.uint32(
                self._ct_table.evicted - evicted0)
            # the pre-CT kernels' rows, as kernels/classify.tally_pre_ct
            # counts them, from the oracle's own columns
            valid0 = np.asarray(batch["valid"], bool)
            meta = out["lpm_prefix"][valid0]
            counters["lb_translated"] = np.uint32(out["svc"].sum())
            counters["lb_no_backend"] = np.uint32(
                (out["reason"][valid0] == int(C.DropReason.NO_SERVICE)).sum())
            counters["lpm_rows"] = np.bincount(
                np.where(meta < 0, C.LPM_MISS_BIN, meta & PFX_LEN_MASK),
                minlength=C.LPM_PLEN_BINS).astype(np.uint32)
            # ... and the L7 lane's, as kernels/classify.tally_l7 does
            tokens = (np.asarray(batch["http_method"])
                      != C.HTTP_METHOD_ANY) \
                | np.asarray(batch["http_path"]).any(axis=-1)
            counters["l7_checked"] = np.uint32(
                (out["redirect"] & tokens & valid0).sum())
            counters["l7_refused"] = np.uint32(
                (out["reason"][valid0] == int(C.DropReason.POLICY_L7)).sum())
            return out, counters

    def sweep(self, now: int) -> int:
        with self._lock:
            return self._ct_table.sweep(now)

    def ct_stats(self, now: int) -> Dict[str, int]:
        with self._lock:
            live = sum(1 for e in self._ct_table.entries.values()
                       if e.expiry > now)
            return {
                "capacity": self.config.ct_capacity,
                "live": live,
                "stale": len(self._ct_table.entries) - live,
            }

    def ct_arrays(self) -> Dict[str, np.ndarray]:
        """Oracle CT → ct_layout arrays. Bounded tables (the default)
        export each entry at its REAL hash slot — the same placement the
        single-chip device computes; entries without one (legacy restores)
        fall back to the old dense-from-0 layout."""
        import logging
        from cilium_tpu.kernels.records import ct_key_words
        cap = self.config.ct_capacity
        arrays = logical_ct_arrays(
            make_ct_arrays(CTConfig(cap, self.config.probe_depth)))
        with self._lock:
            items = list(self._ct_table.entries.items())
            overflow = len(items) - cap
            if overflow > 0:
                # a bounded table can never overflow; an unbounded legacy
                # dict can — never lose flows silently, and when forced
                # to, drop the soonest-to-expire (deterministic)
                self.ct_export_truncated += overflow
                logging.getLogger("cilium_tpu.datapath").warning(
                    "FakeDatapath.ct_arrays: %d CT entries exceed "
                    "ct_capacity=%d; dropping the soonest-expiring from "
                    "the export", overflow, cap)
                items.sort(key=lambda kv: kv[1].expiry, reverse=True)
                items = items[:cap]
        hash_slots = all(0 <= e.slot < cap for _k, e in items)
        for dense, (key, e) in enumerate(items):
            slot = e.slot if hash_slots else dense
            src, dst, sport, dport, proto, d = key
            one = {
                "src": np.frombuffer(src, dtype=">u4").reshape(1, 4),
                "dst": np.frombuffer(dst, dtype=">u4").reshape(1, 4),
                "sport": np.array([sport]), "dport": np.array([dport]),
                "proto": np.array([proto]), "direction": np.array([d]),
            }
            arrays["keys"][slot] = ct_key_words(one)[0]
            arrays["expiry"][slot] = e.expiry
            arrays["created"][slot] = e.created
            arrays["flags"][slot] = e.flags
            arrays["pkts_fwd"][slot] = e.pkts_fwd
            arrays["pkts_rev"][slot] = e.pkts_rev
            arrays["rev_nat"][slot] = e.rev_nat
        return arrays

    def load_ct_arrays(self, arrays: Dict[str, np.ndarray]) -> None:
        """ct_layout arrays → oracle CT entries (inverse of ct_arrays).
        Entries re-place into this table's hash layout (the imported
        arrays may come from a different shard count or a dense legacy
        export); entries whose probe window is already full drop with a
        warning — the same contract as the JIT backend's rehash."""
        import logging
        from oracle import ConntrackTable, CTEntry
        from cilium_tpu.utils.ip import words_to_addr
        arrays = normalize_ct_arrays(arrays)   # validate BEFORE clearing
        cap = self.config.ct_capacity
        pd = self.config.probe_depth
        with self._lock:
            table = ConntrackTable(capacity=cap, probe_depth=pd)
            expiry = arrays["expiry"]
            loaded = []
            for slot in np.nonzero(expiry > 0)[0]:
                w = arrays["keys"][slot]
                key = (words_to_addr(w[0:4]), words_to_addr(w[4:8]),
                       int(w[8]) >> 16, int(w[8]) & 0xFFFF,
                       int(w[9]) >> 8, int(w[9]) & 0xFF)
                loaded.append((key, CTEntry(
                    expiry=int(expiry[slot]),
                    created=int(arrays["created"][slot]),
                    flags=int(arrays["flags"][slot]),
                    pkts_fwd=int(arrays["pkts_fwd"][slot]),
                    pkts_rev=int(arrays["pkts_rev"][slot]),
                    rev_nat=int(arrays["rev_nat"][slot]))))
            dropped = 0
            if loaded:
                bases = table.base_slots([k for k, _e in loaded])
                for (key, entry), base in zip(loaded, bases):
                    placed = False
                    for r in range(pd):
                        s = (int(base) + r) % cap
                        if table._slots[s] is None:     # noqa: SLF001
                            table.install(key, entry, s)
                            placed = True
                            break
                    if not placed:
                        dropped += 1
            self._ct_table = table
            # the cached oracle closed over the old table object
            self._oracle = None
            self._oracle_snap = None
            if dropped:
                logging.getLogger("cilium_tpu.datapath").warning(
                    "FakeDatapath.load_ct_arrays: %d entries dropped "
                    "(probe window exhausted during re-place)", dropped)
