"""L7-lite tokenized HTTP match (BASELINE config 4; the envoy-bypass path).

Per packet: gather its rule set's tensors and do one vectorized
method+prefix compare across all rules of the set. Set id 0 (no redirect)
vacuously matches.
"""

from __future__ import annotations

import jax.numpy as jnp

from cilium_tpu.utils import constants as C


def l7_match_batch(tensors, set_id, method, path):
    """set_id [N] int32 (0 = none), method [N] int32, path [N,64] uint8
    → matched [N] bool (True for set_id == 0)."""
    sid = jnp.clip(set_id, 0, tensors["l7_methods"].shape[0] - 1)
    m = tensors["l7_methods"][sid].astype(jnp.int32)        # [N,R]
    valid = tensors["l7_valid"][sid].astype(bool)           # [N,R]
    plen = tensors["l7_path_len"][sid]                      # [N,R]
    prefix = tensors["l7_path"][sid]                        # [N,R,64]
    m_ok = (m == C.HTTP_METHOD_ANY) | (m == method[:, None])
    pos = jnp.arange(prefix.shape[-1], dtype=jnp.int32)
    byte_ok = (prefix == path[:, None, :]) | (pos[None, None, :] >= plen[:, :, None])
    p_ok = byte_ok.all(axis=-1)
    any_rule = (valid & m_ok & p_ok).any(axis=-1)
    return jnp.where(set_id <= 0, True, any_rule)
