"""Tier-1's hold on the benchmark's direction tests (PERF.md §7(a), PR 34).

``benchmarks/tests/test_frames_direction.py`` holds five groups of tests:
the bytes ``frames.py`` gave at PR 32 for a flow set without the new
columns (frozen digests), egress frames through the shim's mock rings, the
egress world's plain reference against a per-address loop over its
documents, its table against the program's oracle, and a TCP payload
through the shim's request-line tokenizer. ``python -m pytest
benchmarks/tests`` runs them there; tier-1 collects ``tests/`` only, so
the driver's count did not guard them. This file brings every one of them
under tier-1 as it stands, case for case: pytest collects a test function
by the name it finds in a module, wherever it was defined, and a
``parametrize`` mark rides on the function.

The benchmark's conftest is not loaded here, only imported for its two
helpers (it holds JAX to the CPU, which ``tests/conftest.py`` has done
already, and its device count is overruled by ``jax_num_cpu_devices``);
the native shim its session fixture builds is built by ``tests/
conftest.py``.
"""

from benchmarks.tests.test_frames_direction import *  # noqa: F401,F403
