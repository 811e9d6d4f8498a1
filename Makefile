# Development entry points.
#
# Tests run on the CPU: JAX_PLATFORMS=cpu with eight host devices
# (SURVEY.md §4), small worlds. What they establish is counts and
# correctness, never a rate. The chip is reached only through the chip
# tool, one process per chip: `python chip_smoke.py` first, then a cell of
# the benchmark, `python3 benchmarks/run.py --workload <cell> ...`
# (README "Quick start", PERF.md for every rate).

PYTEST_ENV = env JAX_PLATFORMS=cpu \
             XLA_FLAGS="--xla_force_host_platform_device_count=8"

.PHONY: test test-fast chaos lint-serving shim clean

test:
	$(PYTEST_ENV) python -m pytest tests/ -q

# tier-1: what the driver runs (it adds `-p xdist -n 6 --dist loadfile`)
test-fast:
	$(PYTEST_ENV) python -m pytest tests/ -q -x -m "not slow"

# Serving-path exception hygiene: a swallowed broad catch eats exactly the
# dispatch evidence device-loss detection runs on.
lint-serving:
	python tools/lint_serving.py

# Everything tier-1 leaves out. The scripted fault-injection scenario
# (runtime/faults.py) through the real jit datapath, via the CLI so that it
# prints its verdict-continuity report: regen failure storm → last-good
# serving + DEGRADED, clustermesh peer flap → ipcache convergence, pipeline
# dispatch storm + stall-storm (watchdog restart) + circuit breaker
# open/probe/close, corrupt checkpoint → cold-start fallback. Then every
# `slow` test: the 10k-submission soaks with faults armed (pipeline, feeder,
# sharded mesh, watchdog), the audited storms at sampling 1.0 (policy
# updates, CT flood, FQDN churn, mixed tenants, device RSS under skew), the
# chip-loss → degraded → heal cycles on the virtual mesh, the two-process
# clustermesh partition/heal soak, the observer and tracer overhead soaks.
chaos: lint-serving
	$(PYTEST_ENV) python -m cilium_tpu.cli.main faults chaos --failures 10
	$(PYTEST_ENV) python -m pytest tests/ -q -m slow

shim:
	$(MAKE) -C cilium_tpu/shim

clean:
	$(MAKE) -C cilium_tpu/shim clean 2>/dev/null || true
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
