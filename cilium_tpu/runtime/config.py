"""Daemon configuration (analog of upstream ``pkg/option.DaemonConfig`` +
``pkg/defaults`` — one frozen dataclass, sourced file < env < CLI flags,
with the runtime-mutable subset limited to what upstream allows at runtime
(policy enforcement mode)."""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Dict, Optional

from cilium_tpu.utils import constants as C

ENV_PREFIX = "CILIUM_TPU_"


@dataclass
class DaemonConfig:
    # --- policy semantics (part of the parity contract) ---
    enforcement_mode: str = C.ENFORCEMENT_DEFAULT
    allow_localhost: bool = True
    # --- datapath geometry ---
    ct_capacity: int = 1 << 20
    probe_depth: int = 8
    batch_size: int = 8192
    v4_only: bool = False
    maglev_m: int = 251            # Maglev table size (prime); upstream's
    #                                default 16381 runs in the benchmark's
    #                                svc10k-maglev, 10,000 rows of it
    # upstream's bpf-lb-map-max at its default: the most service frontends,
    # and the most LB backends, the registry takes (an upsert past it is
    # refused as upstream's map insert fails)
    lb_map_max: int = 65536
    # --- device/runtime ---
    # "tpu"/"cpu" are requirements (JITDatapath refuses to start on
    # anything else); "auto" serves on what JAX has and reports it
    device: str = "auto"           # auto | cpu | tpu
    n_shards: int = 1              # data-parallel flow shards (mesh size)
    rule_shards: int = 1           # rule-space (verdict-row) shards
    # flow-to-shard resolution for the sharded mesh (n_shards > 1):
    # "host" = the classic steered path (feeder pre-binning + staging-ring
    # scatter; a flow MUST land on its CT shard before dispatch);
    # "device" = device-side RSS — each chip classifies whatever rows
    # arrive on it and cross-shard CT lookups/inserts resolve with a ring
    # ppermute exchange inside the shard_map body (parallel/exchange.py).
    # Device mode deletes the host steer/scatter from the hot path, the
    # steer_overflow shed class, and the skewed-flood imbalance failure
    # mode; verdicts stay bit-identical to the steered path.
    rss_mode: str = "host"         # host | device
    donate_ct: bool = True
    # --- lifecycle ---
    state_dir: str = "/var/run/cilium-tpu"
    sweep_interval_s: float = 30.0
    regen_debounce_s: float = 0.1
    auto_regen: bool = True
    # incremental regeneration: patch the compiled snapshot through the
    # repository changelog instead of full recompiles (geometry changes
    # still fall back to a full build — compile/incremental.py gates)
    incremental: bool = True
    # --- live policy patching (the sub-ms device-resident fast path) ---
    # delta_patch: scatter-apply sparse (rows, values) verdict deltas onto
    # the device-resident image with donated buffers (JITDatapath) instead
    # of round-tripping whole planes through device_put; False restores the
    # whole-tensor re-place. patch_delta_rows gates a single patch (more
    # touched rows → full verdict upload); patch_rebase_rows bounds the
    # host-side row overlay before the incremental compiler folds it into
    # a fresh dense base (one amortized O(image) copy).
    delta_patch: bool = True
    patch_delta_rows: int = 1024
    patch_rebase_rows: int = 4096
    # --- overlapped device-side CT GC (kernels/conntrack.ct_sweep_chunk) ---
    # ct_gc_overlap: the ct-gc controller drives a double-buffered chunked
    # epoch sweep that interleaves with classify steps (enqueue under the
    # classify lock, reclaim counts harvested a tick later) instead of the
    # host-blocking whole-table sweep; chunk_rows per tick (pow2), at
    # ct_gc_interval_s cadence. Backends without device sweeps (the fake)
    # keep the host sweep at sweep_interval_s.
    ct_gc_overlap: bool = True
    ct_gc_chunk_rows: int = 1 << 16
    ct_gc_interval_s: float = 2.0
    # --- CT pressure / emergency GC (adversarial-load survival) ---
    # occupancy (live/capacity) above ct_pressure_high arms EMERGENCY GC:
    # each ct-gc tick runs ct_gc_emergency_chunks chunk sweeps with the
    # effective TTL shortened by ct_gc_emergency_ttl_slash_s (entries
    # within that many seconds of expiry are reclaimed early — a SYN
    # flood's 60s entries die fast, established 21600s flows are
    # untouched); hysteresis exits below ct_pressure_low. The same
    # thresholds feed the overload ladder's CT signal.
    ct_pressure_high: float = 0.85
    ct_pressure_low: float = 0.6
    ct_gc_emergency_chunks: int = 8
    ct_gc_emergency_ttl_slash_s: int = 45
    # --- zero-copy ingestion (kernels/records.py out= + shim/feeder.py) ---
    # in-place pack into preallocated wire rings + L7 path-dict upload
    # cache (JITDatapath); False restores per-batch allocation
    zero_copy_ingest: bool = True
    # ceilings, not the pace: a harvest takes what the ring holds (up to
    # shim/feeder.harvest_ceiling, from the ring's size and
    # pipeline_inflight) and opens once the worker dispatched the one
    # before (after a partial one: once its verdicts are back)
    ingest_pool_batches: int = 4        # feeder harvest buffers, at most
    ingest_poll_budget: int = 256       # rx descriptors per afxdp_poll round
    ingest_idle_sleep_s: float = 0.0005  # feeder park when rings are empty
    # --- ingestion pipeline (pipeline/scheduler.py) ---
    pipeline_queue_batches: int = 64    # bounded submission queue (batches)
    pipeline_admission: str = "block"   # block (up to timeout) | drop
    pipeline_block_timeout_s: float = 1.0
    pipeline_flush_ms: float = 2.0      # microbatch coalesce deadline
    pipeline_min_bucket: int = 256      # smallest dispatch shape (pow2)
    pipeline_inflight: int = 2          # overlapped batches in flight
    # sharded staging (n_shards > 1): per-shard segment capacity =
    # pow2(batch_size / n_shards) * headroom — slack for flow-hash skew
    # before a submission sheds with reason="steer_overflow" (pow2)
    pipeline_shard_headroom: int = 4
    # --- pipeline guard (pipeline/guard.py): overload + self-healing ---
    pipeline_deadline_ms: float = 0.0   # per-submission deadline (0 = none)
    pipeline_request_timeout_s: float = 10.0  # REST/CLI Ticket.result bound
    pipeline_breaker_threshold: int = 20  # consecutive failures → open
    pipeline_breaker_cooldown_s: float = 5.0  # open → half-open probe delay
    # heartbeat age → watchdog restart; a generation's FIRST dispatch gets
    # 4x this budget (COLD_DISPATCH_GRACE) so a cold-shape XLA compile can
    # never look like a device stall and restart-loop a healthy daemon
    pipeline_stall_timeout_s: float = 30.0
    pipeline_max_restarts: int = 3      # restart budget, then hard-failed
    pipeline_restart_backoff_s: float = 0.2  # base (capped exponential)
    # --- overload ladder (pipeline/guard.OverloadLadder; the supervised
    # degradation state machine OK → PRESSURE → OVERLOAD → SHED-NEW) ---
    # the `overload` controller folds queue occupancy, shed rate and CT
    # occupancy into the ladder each interval and propagates the state to
    # the admission queue (priority shedding) and the shim feeder
    # (harvest-time SHED-NEW). Signals latch with per-signal hysteresis
    # (high to light, low to clear); the ladder climbs one rung per
    # up_ticks pressured intervals and descends one per down_ticks calm.
    overload_enabled: bool = True
    overload_interval_s: float = 0.5
    overload_queue_high: float = 0.75   # queue_depth/queue_batches to light
    overload_queue_low: float = 0.25
    overload_shed_rate_high: float = 50.0   # sheds+admission drops per sec
    overload_shed_rate_low: float = 5.0
    overload_up_ticks: int = 2
    overload_down_ticks: int = 6
    # --- api ---
    api_socket: str = ""           # unix-socket REST path ("" = disabled)
    # --- multi-host sync (clustermesh analog; runtime/clustermesh.py) ---
    cluster_store: str = ""        # shared store dir ("" = single-host)
    node_name: str = ""            # this node's name in the store
    cluster_sync_interval_s: float = 5.0
    # peer lease: a peer whose generation stops progressing for this long
    # (judged on OUR clock — skew-immune) is withdrawn (etcd lease analog)
    cluster_stale_after_s: float = 60.0
    # store-partition budget: no successful store pass for this long →
    # health() degrades with the MESH_STALE detail (last-good remote state
    # keeps serving throughout — partition never fails closed)
    cluster_staleness_budget_s: float = 15.0
    # --- observability ---
    flowlog_capacity: int = 16384
    flowlog_mode: str = "drops"    # all | drops | none
    flowlog_path: str = ""         # JSONL sink ("" = in-memory ring only)
    metrics_path: str = ""         # Prometheus text file ("" = disabled)
    obs_flush_interval_s: float = 5.0
    # capped {rule=} label cardinality for policy_rule_{hits,drops}_total:
    # a 50k-rule world must not mint 50k Prometheus series; coordinates
    # past the cap aggregate under rule="other" (0 disables the family)
    rule_metrics_max: int = 128
    # --- observe/: tracing, flow metrics, autotune ---
    trace_sample_rate: float = 0.0   # 0 off; 1/64 samples every 64th event
    trace_capacity: int = 4096       # span ring size
    flowmetrics_window_s: int = 10   # flow-metrics aggregation window
    flowmetrics_windows: int = 60    # retained windows (10min at 10s)
    flowmetrics_top_k: int = 10      # ports/identities reported per window
    autotune_enabled: bool = False   # closed-loop pipeline tuning (opt-in)
    autotune_interval_s: float = 5.0
    autotune_flush_ms_min: float = 0.5
    autotune_flush_ms_max: float = 20.0
    autotune_target_fill: float = 0.7
    autotune_queue_wait_p99_ms: float = 10.0   # p99 queue-wait budget
    autotune_hysteresis: int = 3     # consecutive intervals before a step
    autotune_step_factor: float = 1.5  # capped multiplicative step
    # --- observe/: shadow-oracle parity audit (observe/audit.py) ---
    audit_enabled: bool = False      # background parity-audit controller
    audit_sample_rate: float = 0.015625  # 1/64 of finalized batches captured
    audit_pool_batches: int = 8      # bounded capture pool (overflow=skipped)
    audit_max_rows: int = 512        # rows captured per sampled batch
    audit_interval_s: float = 1.0    # parity-audit controller interval
    # --- observe/: flight recorder (observe/blackbox.py; always on) ---
    blackbox_events: int = 256       # guard/regen/audit event ring
    blackbox_verdicts: int = 64      # last-N per-batch verdict summaries
    blackbox_shed_spike: int = 64    # sheds within the window that freeze
    blackbox_shed_window_s: float = 5.0
    # deliberate-overload sheds (priority eviction, SHED-NEW, stale-at-
    # ingest) fire at storm rate BY DESIGN: they get this relaxed spike
    # threshold so a commanded SHED-NEW storm cannot freeze the recorder
    # every window, while flush/steer_overflow keep the strict one above
    blackbox_shed_spike_relaxed: int = 4096
    # --- resource pressure ledger (observe/pressure.py; ISSUE 13) ---
    # every bounded structure registers (capacity, occupancy, high_water)
    # with the central ledger; the resource-ledger controller polls at
    # resource_interval_s, exporting the labeled resource_* gauge families
    # and a windowed time-to-exhaustion forecast per resource. warn feeds
    # the RESOURCE_PRESSURE health detail (and the forecast gate); crit
    # degrades health(); an ETA under resource_eta_warn_s fires the
    # resource-pressure flight-recorder event (strict freeze only on
    # forecast-then-exhaustion). overload_resource_{high,low} is the
    # ladder's fourth latch signal (max non-CT pressure).
    resource_ledger_enabled: bool = True
    resource_interval_s: float = 2.0
    resource_pressure_warn: float = 0.8
    resource_pressure_crit: float = 0.95
    resource_eta_window: int = 16        # (t, occupancy) samples per ETA fit
    resource_eta_warn_s: float = 120.0   # forecast threshold (seconds)
    overload_resource_high: float = 0.9
    overload_resource_low: float = 0.7
    # device-memory budget for the live HBM ledger's `hbm` resource row
    # (JIT backends only; 0 = report without a budget). The OFFLINE check
    # stays `cilium-tpu verify --max-hbm-bytes` — same machinery, one
    # number (compile/verifier.py budget_doc).
    max_hbm_bytes: int = 0
    # --- end-to-end latency SLO (shim harvest → verdict apply) ---
    # burn threshold for ingest_e2e_slo_burn_total (+{shard=...}); 0 keeps
    # the e2e histograms exporting but disables burn counting
    slo_e2e_ms: float = 0.0
    # --- multi-tenant QoS (cilium_tpu/qos; weighted-fair admission) ---
    # default-off: with qos_enabled=False the admission queue is the
    # plain FIFO deque, byte-identical to the pre-QoS pipeline. Armed,
    # the feeder stamps a per-row tenant id (endpoint → tenant LUT), the
    # admission queue goes per-tenant deficit-round-robin (weights from
    # qos_tenants), and lane-tagged tenants bypass deadline microbatching
    # at the qos_lane_bucket dispatch shape.
    qos_enabled: bool = False
    # tenant spec: "name=weight[:lane][:cap=N],..." — e.g.
    # "gold=4:lane,silver=2,bulk=1:cap=8" (cap in queue batches)
    qos_tenants: str = ""
    # static endpoint→tenant assignment: "ep_id=tenant,..." (dynamic
    # assignment goes through Engine.qos.assign at runtime)
    qos_assign: str = ""
    qos_default_weight: float = 1.0  # weight of the default tenant
    qos_lane_bucket: int = 64        # latency-lane dispatch shape (pow2)
    # per-tenant queue occupancy cap in batches for tenants without an
    # explicit :cap= (0 = uncapped; the global queue bound still applies)
    qos_tenant_cap_batches: int = 0
    # --- in-band DNS plane (cilium_tpu/fqdn; ISSUE 18) ---
    # fqdn_proxy_enabled arms the feeder's verdict-apply DNS tap: rows
    # whose verdict carries the DNS L7 redirect class get their harvested
    # response payloads (_dns_payload/_dns_len poll-buffer columns)
    # parsed and fed to the FQDN cache. Fail-open by construction — a
    # parse failure (or the armed fqdn.parse fault) loses learning, never
    # the reply. Off: the feeder allocates no payload columns and the
    # serving path is byte-identical to pre-ISSUE-18.
    fqdn_proxy_enabled: bool = False
    fqdn_proxy_port: int = 53        # the redirect class's DNS port
    # min-TTL floor (upstream tofqdns-min-ttl): short-TTL records are
    # clamped so churn-happy names don't thrash rule re-materialization
    fqdn_min_ttl: int = 0
    # FQDNCache bounds (upstream tofqdns-endpoint-max-ip-per-hostname
    # class): oldest-expiry eviction past either cap; 0 = unbounded
    fqdn_max_names: int = 4096
    fqdn_max_ips_per_name: int = 64
    # --- mesh self-healing (ISSUE 19; runtime/datapath.remesh + the
    # engine's mesh-heal controller) ---
    # remesh_enabled arms the recovery path on sharded JIT backends: a
    # dead-device dispatch signature (DeviceLost) fences the pipeline
    # generation, shrinks the mesh to the surviving devices, salvages the
    # surviving shards' CT, and resumes degraded — and a healed device
    # (probe canary passing for remesh_heal_hysteresis_s) re-meshes back
    # to full width. Off: device loss stays breaker/restart territory.
    remesh_enabled: bool = True
    remesh_interval_s: float = 0.5      # mesh-heal controller poll
    # bounded established-fingerprint grace window after a LOSS remesh:
    # the lost shard's flows classify NEW on-device (their CT is gone)
    # but recently-applied established verdicts flip back to allow at
    # verdict-apply, counted ct_salvage_grace_hits_total, until the
    # window closes and cold-learned CT has taken over
    remesh_grace_s: float = 30.0
    # a healed device must hold a passing probe canary this long before
    # the reverse remesh (anti-flap hysteresis); each failed probe resets
    remesh_heal_hysteresis_s: float = 10.0
    # --- ct-snapshot controller (bounded-staleness CT archive: the
    # salvage floor when the remesh gather itself fails) ---
    ct_snapshot_dir: str = ""           # archive directory ("" = disabled)
    ct_snapshot_interval_s: float = 30.0
    ct_snapshot_keep: int = 2           # newest archives retained
    # checkpoint freshness budget: newest CT archive older than this →
    # checkpoint_age_seconds gauge + CHECKPOINT_STALE health detail
    # (0 = no freshness contract)
    checkpoint_max_age_s: float = 0.0

    def __post_init__(self):
        if self.enforcement_mode not in C.ENFORCEMENT_MODES:
            raise ValueError(f"bad enforcement mode {self.enforcement_mode!r}")
        if self.ct_capacity & (self.ct_capacity - 1):
            raise ValueError("ct_capacity must be a power of two")
        if self.flowlog_mode not in ("all", "drops", "none"):
            raise ValueError(f"bad flowlog mode {self.flowlog_mode!r}")
        if self.device not in ("auto", "cpu", "tpu"):
            raise ValueError(
                f"bad device {self.device!r} (auto | cpu | tpu)")
        if self.rss_mode not in ("host", "device"):
            raise ValueError(
                f"bad rss_mode {self.rss_mode!r} (host | device)")
        if self.pipeline_admission not in ("block", "drop"):
            raise ValueError(
                f"bad pipeline admission {self.pipeline_admission!r}")
        if (self.pipeline_min_bucket <= 0
                or self.pipeline_min_bucket & (self.pipeline_min_bucket - 1)):
            raise ValueError("pipeline_min_bucket must be a power of two")
        if self.pipeline_inflight < 1 or self.pipeline_queue_batches < 1:
            raise ValueError(
                "pipeline_inflight and pipeline_queue_batches must be >= 1")
        if (self.pipeline_shard_headroom < 1
                or self.pipeline_shard_headroom
                & (self.pipeline_shard_headroom - 1)):
            raise ValueError(
                "pipeline_shard_headroom must be a power of two >= 1")
        if self.ingest_pool_batches < 1 or self.ingest_poll_budget < 1:
            raise ValueError(
                "ingest_pool_batches and ingest_poll_budget must be >= 1")
        if self.ingest_idle_sleep_s < 0:
            raise ValueError("ingest_idle_sleep_s must be >= 0")
        if self.pipeline_deadline_ms < 0:
            raise ValueError("pipeline_deadline_ms must be >= 0 (0 = none)")
        if self.pipeline_request_timeout_s <= 0:
            raise ValueError("pipeline_request_timeout_s must be > 0")
        if self.pipeline_breaker_threshold < 1:
            raise ValueError("pipeline_breaker_threshold must be >= 1")
        if self.pipeline_breaker_cooldown_s <= 0 \
                or self.pipeline_stall_timeout_s <= 0:
            raise ValueError("pipeline_breaker_cooldown_s and "
                             "pipeline_stall_timeout_s must be > 0")
        if self.pipeline_max_restarts < 0 \
                or self.pipeline_restart_backoff_s <= 0:
            raise ValueError("pipeline_max_restarts must be >= 0 and "
                             "pipeline_restart_backoff_s > 0")
        if self.patch_delta_rows < 1 or self.patch_rebase_rows < 1:
            raise ValueError(
                "patch_delta_rows and patch_rebase_rows must be >= 1")
        if (self.ct_gc_chunk_rows < 1
                or self.ct_gc_chunk_rows & (self.ct_gc_chunk_rows - 1)):
            raise ValueError("ct_gc_chunk_rows must be a power of two")
        if self.ct_gc_interval_s <= 0:
            raise ValueError("ct_gc_interval_s must be > 0")
        if not 0.0 <= self.ct_pressure_low < self.ct_pressure_high <= 1.0:
            raise ValueError(
                "need 0 <= ct_pressure_low < ct_pressure_high <= 1")
        if self.ct_gc_emergency_chunks < 1 \
                or self.ct_gc_emergency_ttl_slash_s < 0:
            raise ValueError("ct_gc_emergency_chunks must be >= 1 and "
                             "ct_gc_emergency_ttl_slash_s >= 0")
        if self.overload_interval_s <= 0:
            raise ValueError("overload_interval_s must be > 0")
        if not 0.0 <= self.overload_queue_low \
                < self.overload_queue_high <= 1.0:
            raise ValueError(
                "need 0 <= overload_queue_low < overload_queue_high <= 1")
        if not 0.0 <= self.overload_shed_rate_low \
                < self.overload_shed_rate_high:
            raise ValueError("need 0 <= overload_shed_rate_low < "
                             "overload_shed_rate_high")
        if self.overload_up_ticks < 1 or self.overload_down_ticks < 1:
            raise ValueError(
                "overload_up_ticks and overload_down_ticks must be >= 1")
        if self.blackbox_shed_spike_relaxed < 1:
            raise ValueError("blackbox_shed_spike_relaxed must be >= 1")
        if not 0.0 <= self.trace_sample_rate <= 1.0:
            raise ValueError("trace_sample_rate must be in [0, 1]")
        if self.trace_capacity < 1:
            raise ValueError("trace_capacity must be >= 1")
        if self.flowmetrics_window_s < 1 or self.flowmetrics_windows < 1:
            raise ValueError(
                "flowmetrics_window_s and flowmetrics_windows must be >= 1")
        if not 0 < self.autotune_flush_ms_min <= self.autotune_flush_ms_max:
            raise ValueError(
                "need 0 < autotune_flush_ms_min <= autotune_flush_ms_max")
        if self.autotune_hysteresis < 1 or self.autotune_step_factor <= 1.0:
            raise ValueError("autotune_hysteresis must be >= 1 and "
                             "autotune_step_factor > 1")
        if not 0.0 < self.autotune_target_fill <= 1.0:
            raise ValueError("autotune_target_fill must be in (0, 1]")
        if self.autotune_queue_wait_p99_ms <= 0:
            raise ValueError("autotune_queue_wait_p99_ms must be > 0")
        if self.autotune_interval_s <= 0:
            raise ValueError("autotune_interval_s must be > 0")
        if not 0.0 <= self.audit_sample_rate <= 1.0:
            raise ValueError("audit_sample_rate must be in [0, 1]")
        if self.audit_pool_batches < 1 or self.audit_max_rows < 1:
            raise ValueError(
                "audit_pool_batches and audit_max_rows must be >= 1")
        if self.audit_interval_s <= 0:
            raise ValueError("audit_interval_s must be > 0")
        if self.blackbox_events < 1 or self.blackbox_verdicts < 1 \
                or self.blackbox_shed_spike < 1:
            raise ValueError("blackbox_events, blackbox_verdicts and "
                             "blackbox_shed_spike must be >= 1")
        if self.blackbox_shed_window_s <= 0:
            raise ValueError("blackbox_shed_window_s must be > 0")
        if self.resource_interval_s <= 0:
            raise ValueError("resource_interval_s must be > 0")
        if not 0.0 < self.resource_pressure_warn \
                < self.resource_pressure_crit <= 1.0:
            raise ValueError("need 0 < resource_pressure_warn < "
                             "resource_pressure_crit <= 1")
        if self.resource_eta_window < 2:
            raise ValueError("resource_eta_window must be >= 2")
        if self.resource_eta_warn_s <= 0:
            raise ValueError("resource_eta_warn_s must be > 0")
        if not 0.0 <= self.overload_resource_low \
                < self.overload_resource_high <= 1.0:
            raise ValueError("need 0 <= overload_resource_low < "
                             "overload_resource_high <= 1")
        if self.max_hbm_bytes < 0:
            raise ValueError("max_hbm_bytes must be >= 0 (0 = no budget)")
        if self.slo_e2e_ms < 0:
            raise ValueError("slo_e2e_ms must be >= 0 (0 = no burn "
                             "counting)")
        if self.cluster_stale_after_s <= 0 \
                or self.cluster_staleness_budget_s <= 0:
            raise ValueError("cluster_stale_after_s and "
                             "cluster_staleness_budget_s must be > 0")
        if self.qos_lane_bucket <= 0 \
                or self.qos_lane_bucket & (self.qos_lane_bucket - 1):
            raise ValueError("qos_lane_bucket must be a power of two")
        if self.qos_default_weight < 0:
            raise ValueError("qos_default_weight must be >= 0")
        if self.qos_tenant_cap_batches < 0:
            raise ValueError("qos_tenant_cap_batches must be >= 0 "
                             "(0 = uncapped)")
        if not 0 < self.fqdn_proxy_port < 65536:
            raise ValueError("fqdn_proxy_port must be in [1, 65535]")
        if self.fqdn_min_ttl < 0:
            raise ValueError("fqdn_min_ttl must be >= 0")
        if self.fqdn_max_names < 0 or self.fqdn_max_ips_per_name < 0:
            raise ValueError("fqdn_max_names and fqdn_max_ips_per_name "
                             "must be >= 0 (0 = unbounded)")
        if self.remesh_interval_s <= 0:
            raise ValueError("remesh_interval_s must be > 0")
        if self.remesh_grace_s < 0:
            raise ValueError("remesh_grace_s must be >= 0 (0 = no grace "
                             "window)")
        if self.remesh_heal_hysteresis_s < 0:
            raise ValueError("remesh_heal_hysteresis_s must be >= 0")
        if self.ct_snapshot_interval_s <= 0:
            raise ValueError("ct_snapshot_interval_s must be > 0")
        if self.ct_snapshot_keep < 1:
            raise ValueError("ct_snapshot_keep must be >= 1")
        if self.checkpoint_max_age_s < 0:
            raise ValueError("checkpoint_max_age_s must be >= 0 "
                             "(0 = no freshness contract)")
        if self.qos_enabled or self.qos_tenants or self.qos_assign:
            # parse eagerly so a malformed spec fails at config load, not
            # mid-flood inside the admission path
            from cilium_tpu.qos.tenancy import (parse_assign_spec,
                                                parse_tenant_spec)
            parse_tenant_spec(self.qos_tenants)
            parse_assign_spec(self.qos_assign)

    # -- sources -------------------------------------------------------------
    @classmethod
    def load(cls, config_file: Optional[str] = None,
             env: Optional[Dict[str, str]] = None,
             argv: Optional[list] = None) -> "DaemonConfig":
        """file < env < flags, like upstream's viper layering."""
        values: Dict = {}
        if config_file:
            with open(config_file) as f:
                values.update(json.load(f))
        env = os.environ if env is None else env
        for f_ in dataclasses.fields(cls):
            key = ENV_PREFIX + f_.name.upper()
            if key in env:
                values[f_.name] = _coerce(f_.type, env[key])
        if argv is not None:
            parser = argparse.ArgumentParser(prog="cilium-tpu-agent")
            for f_ in dataclasses.fields(cls):
                parser.add_argument(f"--{f_.name.replace('_', '-')}",
                                    dest=f_.name, default=None)
            ns = parser.parse_args(argv)
            for f_ in dataclasses.fields(cls):
                v = getattr(ns, f_.name)
                if v is not None:
                    values[f_.name] = _coerce(f_.type, v)
        known = {f_.name for f_ in dataclasses.fields(cls)}
        unknown = set(values) - known
        if unknown:
            raise ValueError(f"unknown config keys: {sorted(unknown)}")
        return cls(**values)


def _coerce(typ, raw):
    if isinstance(raw, str):
        t = str(typ)
        if "bool" in t:
            return raw.lower() in ("1", "true", "yes", "on")
        if "int" in t:
            return int(raw)
        if "float" in t:
            return float(raw)
    return raw
