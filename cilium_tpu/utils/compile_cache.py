"""Where XLA's persistent compilation cache lives.

Every classify bucket shape is a separate XLA compile (seconds each on the
chip), and a process that starts cold pays all of them again. JAX keeps a
persistent cache when it is told a directory; the directory is part of the
cache key, so it must be the same path on every start.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it by itself. Nothing is set
  here, so the operator's placement is the only one.
- unset: one fixed path inside the checkout, ``<checkout>/.jax_cache``
  (git-ignored), derived from this package's own location.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — cilium_tpu/utils/compile_cache.py is three
#: levels below the checkout root
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX at the persistent compilation cache before the first
    compile; returns the directory in use. Idempotent."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
