"""Test config: force JAX onto CPU with 8 host devices BEFORE jax import,
and build the native shim the ring/feeder tests load.

This is the standard JAX idiom for testing pmap/shard_map sharding logic
without TPU hardware (SURVEY.md §4: the control-plane-fixture-replay analog).
Must run before anything imports jax, hence conftest at collection time.
"""

import os
import subprocess
import threading
import time

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

# libflowshim.so and goldengen are build products git does not carry; the
# modules that need them decide at import whether to skip, so they are built
# before collection. A checkout that cannot build them skips those tests.
subprocess.run(
    ["make", "-C", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "cilium_tpu", "shim")],
    check=False, capture_output=True)


# thread names of cilium_tpu's serving threads: pipeline worker and watchdog
# (`<name>-worker[-gN]`, `<name>-watchdog`), feeder (`<name>-harvest`),
# controllers (`ctrl-<name>`), the API server
_SERVING = ("-worker", "-watchdog", "-harvest", "ctrl-", "cilium-tpu-api")


@pytest.fixture(autouse=True, scope="module")
def _a_file_leaves_nothing_running():
    """Under `--dist loadfile` the files of one xdist worker share a
    process, so what a file leaves behind runs beside every later one.
    test_feeder.py's zero-allocation soak counts allocations of the whole
    process in the pack/stage files, and went red by what its neighbours
    left: an Engine built with ``trace_sample_rate > 0`` arms the
    process-wide TRACER and nothing disarms it (the ring then kept the
    soak's spans), and an engine nobody stopped keeps its threads serving.
    So a file ends with the tracer off and no serving thread alive; the
    wait allows a thread that is stopping to finish."""
    from cilium_tpu.observe.trace import TRACER
    yield
    TRACER.configure(sample_rate=0.0)

    def alive():
        return sorted(t.name for t in threading.enumerate()
                      if any(s in t.name for s in _SERVING))

    deadline = time.monotonic() + 10
    while alive() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not alive(), f"serving threads left running: {alive()}"
