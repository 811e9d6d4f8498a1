"""The benchmark's own tests run on the CPU, in seconds:

    python -m pytest benchmarks/tests -q

They are not under ``tests/`` and tier-1 does not collect them. JAX is
held to the CPU with four virtual devices (for the 4-wide mesh cell)
before anything imports it.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DATA = os.path.join(HERE, "data")


def tiny_config(name):
    """A test-size configuration of ``data/configs``."""
    with open(os.path.join(DATA, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def tiny_manifest():
    with open(os.path.join(DATA, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session", autouse=True)
def native_libraries():
    from benchmarks.nic import nicgen
    nicgen.build_shim()
    nicgen.build()
