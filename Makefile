# Development entry points.
#
# Tests and the *-smoke gates run on the CPU: JAX_PLATFORMS=cpu with eight
# host devices (SURVEY.md §4), small worlds (--preset smoke). What they
# print is a CPU reading, never a device number. The chip is reached only
# through the chip tool, one process per chip, and the first command to
# run there is `python chip_smoke.py` (see README "Quick start").

PYTEST_ENV = env JAX_PLATFORMS=cpu \
             XLA_FLAGS="--xla_force_host_platform_device_count=8"

.PHONY: test test-fast chaos chaos-pipeline pipeline-smoke observe-smoke \
        ingest-smoke multichip-smoke audit-smoke kernel-smoke update-smoke \
        ddos-smoke cluster-smoke pressure-smoke rss-smoke qos-smoke \
        fqdn-smoke chiploss-smoke lint-serving shim bench clean

test:
	$(PYTEST_ENV) python -m pytest tests/ -q

test-fast:
	$(PYTEST_ENV) python -m pytest tests/ -q -x -m "not slow"

# Pipeline-guard gate (pipeline/guard.py): the fast, tier-1-safe stall +
# breaker + watchdog-restart subset — deadline shed, circuit-breaker
# open/probe/close, hang-forced restart parity, close-timeout sweep,
# drain-vs-close races. Wired into `make chaos` below.
chaos-pipeline:
	$(PYTEST_ENV) python -m pytest tests/test_pipeline_guard.py -q -m "not slow"

# Scripted fault-injection scenario (runtime/faults.py): regen failure storm
# → last-good serving + DEGRADED, clustermesh peer flap → ipcache
# convergence, pipeline dispatch storm + stall-storm (watchdog restart) +
# circuit breaker open/probe/close, corrupt checkpoint → cold-start
# fallback. Runs the scenario through the real jit datapath twice: directly
# via the CLI (prints the verdict-continuity report) and as the slow-marked
# pytest, plus the slow-marked 10k-submission watchdog soak. A fast subset
# on the fake datapath runs in tier-1 (tests/test_faults.py,
# tests/test_pipeline_guard.py via chaos-pipeline).
# Multi-chip serving gate (parallel/mesh.py + the sharded staging ring):
# the host-platform 8-device tier-1 subset — steering invariants, mesh
# parity, the sharded-pipeline parity suite (1-shard vs 8-shard
# bit-identical, steered staging mechanics, steer-overflow shed,
# alloc-free steered staging) — plus the slow-marked 10k-submission
# sharded soak with `shim.rx_ring` faults armed, which asserts
# `datapath_pack_fallback_total{reason="steered"}` stays 0 (the steered
# serving path packs in place into pooled per-shard wire segments).
multichip-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_parallel.py tests/test_sharded_pipeline.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_sharded_pipeline.py -q -m slow

# Verdict-provenance gate (observe/audit.py + observe/blackbox.py): the
# tier-1 audit subset — deterministic capture sampling, bounded-pool
# skipped accounting, the audit.corrupt detection drill (health DEGRADED +
# frozen debug bundle with the offending rows/revision), wedged-auditor
# serving survival, e2e SLO plumbing, scrape-race + trace-wraparound
# satellites — plus the slow-marked 10k-submission soak with the auditor
# armed at sampling 1.0 (zero mismatches, checked > 0, then a
# corruption-injection phase) and the <2%-overhead attestation.
audit-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_audit.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_audit.py -q -m slow

# Fused-megakernel gate (kernels/fused.py): the tier-1 kernel/parity
# subset — per-kernel fused-vs-jnp-vs-host parity (LPM fuzz incl. the
# grid path, CT probe pair, policy+L7+verdict), the fused end-to-end
# oracle parity suite, selector/memoization units, fused pipeline +
# 4-shard mesh + audit integration — plus the slow-marked soaks (100k-
# prefix v6 walk, long-horizon fused parity, audited pipeline soak) and a
# `bench.py --kernels` round with interpret-mode parity asserted and a
# second round --compare'd against the first (the per-kernel regression
# gate). Tier-1 already runs the fused path in interpret mode via
# tests/test_fused.py, so no PR can land a divergent kernel.
kernel-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_fused.py tests/test_kernels.py tests/test_parity.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_fused.py -q -m slow
	$(PYTEST_ENV) python bench.py --preset smoke --kernels --config 3 --batch 1024 --batches 4 --fused on > /tmp/cilium_tpu_kernels_gate.json
	$(PYTEST_ENV) python bench.py --preset smoke --kernels --config 3 --batch 1024 --batches 4 --fused on --compare /tmp/cilium_tpu_kernels_gate.json > /dev/null

# Live-update gate (compile/incremental delta path + runtime/datapath
# scatter-apply + overlapped CT GC): the tier-1 subset — delta-patch
# bit-identity vs the oracle on warm geometry, the StalePlacement donation
# fence + engine retry, sharded scatter parity, chunk-sweep == whole-table
# sweep, CT restart survival, the bounded classify-fn memo — plus the
# slow-marked soaks (restart-mid-soak, the policy storm audited at
# sampling 1.0) and a `bench.py --update-storm` round whose artifact gate
# (parity mismatches, delta-path usage, GC churn ratio, the ≥50x rule-add
# bar) exits 4 on failure, --compare'd against itself for the
# round-over-round surface.
update-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_update_storm.py tests/test_incremental.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_update_storm.py -q -m slow
	$(PYTEST_ENV) python bench.py --update-storm --preset smoke > /tmp/cilium_tpu_update_gate.json
	$(PYTEST_ENV) python bench.py --update-storm --preset smoke --compare /tmp/cilium_tpu_update_gate.json > /dev/null

# Adversarial-load gate (ISSUE 10: CT exhaustion + the degradation ladder):
# the tier-1 overload-ladder + CT-full subset — insert-when-full tail
# eviction bit-identical across jnp/fused-interpret/bounded-oracle,
# CT_FULL fail-closed verdicts, emergency GC hysteresis, ladder state
# machine + priority shed + SHED-NEW harvest shed + blackbox shed split +
# the labeled-scrape race — plus the slow flood soak (thousands of
# pipelined submissions saturating a tiny CT with `ct.insert` faults armed
# and the auditor at sampling 1.0: zero mismatches, checked > 0), and a
# `bench.py --ddos` round whose gate (≥99% established-flow survival,
# SHED-NEW reached, occupancy bounded + recovered, no post-storm
# throughput collapse, zero parity mismatches) exits 4 on failure,
# --compare'd against itself for the round-over-round surface.
ddos-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_overload.py tests/test_ctfull.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_ctfull.py -q -m slow
	$(PYTEST_ENV) python bench.py --preset smoke --ddos > /tmp/cilium_tpu_ddos_gate.json
	$(PYTEST_ENV) python bench.py --preset smoke --ddos --compare /tmp/cilium_tpu_ddos_gate.json > /dev/null

# Multi-host serving gate (ISSUE 12: runtime/clustermesh.py +
# runtime/cluster.py): the tier-1 clustermesh subset — the partition
# contract (last-good serving, MESH_STALE past the staleness budget,
# lease expiry only under a healthy listing, dead-peer tombstones),
# deterministic conflict resolution pinned on BOTH ingest orders, store
# hygiene (spoofed peer files, tmp-litter sweep, loud withdraw), the
# prefix hand-off racing lease expiry, replication-lag clamping — plus
# the slow-marked 2-proc partition/heal soak (real spawned engine
# processes over one store, `clustermesh.peer_read` +
# `clustermesh.store_list` faults armed through six partition rounds,
# gating on convergence-after-heal and zero parity mismatches at
# sampling 1.0), and a `bench.py --cluster 3` round whose artifact gate
# (convergence via the delta-patch path, cross-boundary verdict
# spot-audit, partition / peer-kill+restart / conflicting-claims /
# skewed-clock chaos, relay fan-in spanning every node, zero audit
# mismatches) exits 4 on failure.
cluster-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_clustermesh.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_clustermesh.py -q -m slow
	$(PYTEST_ENV) env CILIUM_TPU_CLUSTER_DATAPATH=fake python bench.py --cluster 3 --preset smoke > /tmp/cilium_tpu_cluster_gate.json

# Resource-pressure gate (ISSUE 13: observe/pressure.py ledger + the HBM
# ledger): the tier-1 ledger subset — registration floor (≥12 resources),
# CT-row-tracks-gauge exactness, ETA/forecast latching, RESOURCE_PRESSURE
# health detail, the ladder's fourth latch, {resource=} scrape races,
# register/deregister under engine restart, trace-ring drop accounting,
# departed-shard/peer gauge sweeps, verifier budget doc, JIT HBM groups —
# plus the slow-marked soaks: the cfg6-form storm (ct_table row bit-
# identical to ct_occupancy every tick, time-to-exhaustion fired before
# SHED-NEW, auditor clean at 1.0) and the 8-shard audited scrape-race soak
# with a mid-soak watchdog restart (the PR 7/11 house pattern on the new
# families). The full-scale acceptance rides `bench.py --ddos` (ddos-smoke
# above), whose artifact now gates trajectory exactness, forecast-before-
# SHED-NEW, and the <2% ledger-polling attestation.
pressure-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_pressure.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_pressure.py -q -m slow

# Device-side RSS gate (parallel/exchange.py + rss_mode="device"): the
# tier-1 device-RSS subset — ring-primitive units, exchange-vs-steered
# bit-identity through a saturating flood (CT_FULL + tail-evict order),
# the device parity suite vs the steered mesh and the oracle, the
# skewed/alternating/cfg6-storm arrival patterns with zero sheds, the
# degraded steer-revision fence, the rss_exchange ledger row + swept
# steer gauges, and the auditor at sampling 1.0 — plus the slow-marked
# 10k-row all-one-shard skewed soak host steering cannot survive
# shed-free, and a steered-vs-unsteered `bench.py --rss device` A/B
# round (cfg1: the policy/LPM-weighted workload where the steered
# path's skew collapse is visible) whose rss_gate exits 4 on failure —
# skew immunity + zero device sheds always; the absolute fps
# comparison arms on TPU (CPU-unmeasurable by construction, like the
# --kernels fused gate).
rss-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_rss.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_rss.py -q -m slow
	$(PYTEST_ENV) python bench.py --pipeline --config 1 --shards 4 --rss device --preset smoke > /tmp/cilium_tpu_rss_gate.json

# Multi-tenant QoS gate (cilium_tpu/qos): the tier-1 QoS subset — tenant
# spec/LUT mechanics, DRR weight shares + FIFO-within-tenant + the
# zero-weight starvation floor + the lane bypass debt bound, tenant-scoped
# caps / over-share fail-fast / priority displacement, the `qos.enqueue`
# fail-closed fault, the QoS-off byte-identical surface, engine parity
# with the auditor at 1.0 while QoS is armed — plus the slow-marked
# 8-shard mixed-tenant soak (concurrent `{tenant=}` metric scrapes racing
# a mid-soak watchdog restart), and a `bench.py --tenants` cfg8 round
# whose gate (victim survival ≥99%, lane p99 within budget under the
# flood, the flooder's DRR share confined to its 1/7 weight band, zero
# parity mismatches) exits 4 on failure, --compare'd against itself for
# the round-over-round per-tenant surface.
qos-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_qos.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_qos.py -q -m slow
	$(PYTEST_ENV) python bench.py --preset smoke --tenants > /tmp/cilium_tpu_qos_gate.json
	$(PYTEST_ENV) python bench.py --preset smoke --tenants --compare /tmp/cilium_tpu_qos_gate.json > /dev/null

# In-band DNS plane gate (fqdn/ + the delta-path identity retirement in
# compile/incremental.py): the tier-1 FQDN subset (parser edge cases,
# proxy fail-open, refresh coalescing, retirement/fresh-rebuild parity,
# the wire-path feeder tap) plus the cfg9 churn workload behind its
# exit-4 gate (zero oracle mismatches at sampling 1.0, established
# survival >= 0.99, zero full rebuilds in steady churn, refresh p99
# inside the delta budget) — run twice to prove --compare regression
# detection stays wired.
fqdn-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_fqdn.py tests/test_fqdn_plane.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_fqdn_plane.py -q -m slow
	$(PYTEST_ENV) python bench.py --preset smoke --fqdn > /tmp/cilium_tpu_fqdn_gate.json
	$(PYTEST_ENV) python bench.py --preset smoke --fqdn --compare /tmp/cilium_tpu_fqdn_gate.json > /dev/null

# Mesh self-healing gate (ISSUE 19: runtime/datapath.remesh +
# Pipeline.remesh + the engine's mesh-heal / ct-snapshot controllers):
# the serving-path exception-hygiene lint (a swallowed broad catch eats
# exactly the dispatch evidence device-loss detection runs on), the
# tier-1 chip-loss subset — dead-device triage, fenced re-mesh geometry
# + queued-submission survival, CT salvage/archive/grace mechanics,
# probe-canary heal with hysteresis, degraded n-1 parity — plus the
# cfg10 chip-loss workload behind its exit-4 gate (established survival
# >= 0.99 through loss+heal, zero oracle mismatches at sampling 1.0,
# degraded fps >= 0.7x the ideal (n-1)/n, exactly one re-mesh each
# direction, the grace window actually fired, full width restored) —
# run twice to prove --compare regression detection stays wired.
lint-serving:
	python tools/lint_serving.py

chiploss-smoke: lint-serving
	$(PYTEST_ENV) python -m pytest tests/test_chiploss.py \
		"tests/test_sharded_pipeline.py::TestDegradedMeshParity" \
		"tests/test_rss.py::TestDeviceRSSDegradedMesh" -q
	$(PYTEST_ENV) python bench.py --preset smoke --chiploss > /tmp/cilium_tpu_chiploss_gate.json
	$(PYTEST_ENV) python bench.py --preset smoke --chiploss --compare /tmp/cilium_tpu_chiploss_gate.json > /dev/null

chaos: chaos-pipeline ingest-smoke multichip-smoke audit-smoke kernel-smoke update-smoke ddos-smoke cluster-smoke pressure-smoke rss-smoke qos-smoke fqdn-smoke chiploss-smoke
	$(PYTEST_ENV) python -m cilium_tpu.cli.main faults chaos --failures 10
	$(PYTEST_ENV) python -m pytest tests/test_faults.py -q -m slow
	$(PYTEST_ENV) python -m pytest tests/test_pipeline_guard.py -q -m slow

# Zero-copy-ingestion gate (shim/feeder.py + the out= pack kernels): the
# tier-1 feeder/pack subset (poll-buffer reuse parity, FIFO verdict order
# through mock rings incl. an armed shim.rx_ring storm, fail-closed on
# pipeline rejection, the tracemalloc steady-state zero-alloc soak) plus
# the slow-marked 10k-frame feeder soak with faults armed the whole run.
ingest-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_feeder.py tests/test_kernels.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_feeder.py -q -m slow

# Ingestion-pipeline gate (pipeline/scheduler.py): the tier-1 pipeline
# subset (ordering, backpressure, deadline flush, fault retries, clean
# shutdown, serial-vs-pipelined verdict parity) plus the slow-marked
# FakeDatapath soak — 10k submissions with `pipeline.dispatch` faults
# armed, asserting no queued batch is lost or reordered.
pipeline-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_pipeline.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_pipeline.py -q -m slow

# Observability gate (cilium_tpu/observe/): the tier-1 observe + observer +
# pipeline subset (tracer sampling/ring, flow-metrics windows, autotuner
# hysteresis/convergence, tracing-on parity; ISSUE 11: FlowFilter mask
# composition, follow-mode gap accounting incl. a live writer race, relay
# merge/lag/gap re-emission, {rule=} hit counters + scrape race) plus the
# slow-marked soaks — the sampled-trace <2% contract, the observer
# filters-armed <2% attestation (PR 3 form), and the relay fan-in phase
# over a live 4-shard mesh + 3 peers — and a `bench.py --ingest --observer`
# D/A/D/A round gating the <2% fps attestation in the artifact.
observe-smoke:
	$(PYTEST_ENV) python -m pytest tests/test_observe.py tests/test_observer.py tests/test_pipeline.py -q -m "not slow"
	$(PYTEST_ENV) python -m pytest tests/test_observe.py tests/test_observer.py -q -m slow
	$(PYTEST_ENV) python bench.py --preset smoke --ingest --observer --frames 24000 > /tmp/ingest_observer.json
	python -c "import json; d=json.loads([l for l in open('/tmp/ingest_observer.json') if l.strip()][-1]); s=d['observer_soak']; print('observer soak:', s); assert s['ok'], 'observer overhead %s%% > %s%%' % (s['overhead_pct'], s['budget_pct'])"

shim:
	$(MAKE) -C cilium_tpu/shim

bench:
	python bench.py

clean:
	$(MAKE) -C cilium_tpu/shim clean 2>/dev/null || true
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
