"""Plain float32 attention, the oracle the attention paths are tested
against (``tests/test_kernels.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def attention_reference(q, k, v, *, causal: bool = True, window=None):
    """q, k, v: (BH, S, D) — plain softmax attention, f32 math."""
    BH, S, D = q.shape
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)
