"""The attention kernel that training runs on a TPU.

``fused_attention`` is the flash kernel that ships with jax (forward
and backward Pallas kernels under ``jax.custom_vjp``), whose VJP keeps
the output and per-row softmax statistics and recomputes the scores
block by block in VMEM.  It compiles for the TPU; tests run it
elsewhere in Pallas's TPU interpret mode.
``repro.models.layers.apply_attention`` sends training self-attention
to it where ``fused_attention_blocks`` takes the shape and ``on_tpu``
holds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.experimental.pallas.ops.tpu import flash_attention as tpu_flash


def on_tpu() -> bool:
    """Whether the programs traced now are lowered for a TPU (the
    default backend): the one backend ``fused_attention`` compiles for."""
    return jax.default_backend() == "tpu"


def fused_attention_blocks(seq_len: int, head_dim: int):
    """The flash kernel's tiles for causal self-attention over
    ``seq_len`` tokens with heads of ``head_dim``, or None where the
    kernel cannot take the shape: its KV tiles are multiples of 128
    rows, and a head wider than 128 lanes must fill whole lanes."""
    if head_dim > 128 and head_dim % 128:
        return None
    block = next((b for b in (512, 256, 128) if seq_len % b == 0), None)
    if block is None:
        return None
    return tpu_flash.BlockSizes(
        block_q=block, block_k_major=block, block_k=block, block_b=1,
        block_q_major_dkv=block, block_k_major_dkv=block, block_k_dkv=block,
        block_q_dkv=block, block_k_major_dq=block, block_k_dq=block,
        block_q_dq=block)


def fused_attention(q, k, v):
    """Causal self-attention, differentiable, with O(S) saved state.

    q: (B, S, H, hd); k, v: (B, S, KH, hd), KV heads repeated here for
    GQA.  Returns (B, S, H, hd) in q's dtype.  Operands enter the MXU in
    their own dtype with float32 accumulation; the softmax statistics
    are float32.  The caller checks ``fused_attention_blocks``.
    """
    B, S, H, hd = q.shape
    rep = H // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    out = tpu_flash.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, sm_scale=hd ** -0.5,
        block_sizes=fused_attention_blocks(S, hd))
    return out.transpose(0, 2, 1, 3)
