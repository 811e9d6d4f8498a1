"""Test config: force JAX onto CPU with 8 host devices BEFORE jax import,
and build the native shim the ring/feeder tests load.

This is the standard JAX idiom for testing pmap/shard_map sharding logic
without TPU hardware (SURVEY.md §4: the control-plane-fixture-replay analog).
Must run before anything imports jax, hence conftest at collection time.
"""

import os
import subprocess

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

# libflowshim.so and goldengen are build products git does not carry; the
# modules that need them decide at import whether to skip, so they are built
# before collection. A checkout that cannot build them skips those tests.
subprocess.run(
    ["make", "-C", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "cilium_tpu", "shim")],
    check=False, capture_output=True)
