"""Jit'd public wrappers for the Pallas kernels.

The kernels compile natively for the TPU.  ``interpret=True`` runs the
same kernel body through the Pallas interpreter instead, which is how
tests check them on the CPU; it is only ever the caller's choice.
Without it, a backend other than the TPU refuses the kernel.

``fused_attention`` is the one differentiable attention kernel: the
flash kernel that ships with jax (forward and backward Pallas kernels
under ``jax.custom_vjp``), whose VJP keeps the output and per-row
softmax statistics and recomputes the scores block by block in VMEM.
``repro.models.layers.apply_attention`` sends training self-attention
to it where ``fused_attention_blocks`` takes the shape and ``on_tpu``
holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental.pallas.ops.tpu import flash_attention as tpu_flash

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssd_scan import ssd_scan_bhsp


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, S, H, hd); k, v: (B, S, KH, hd) — GQA handled here.

    Returns (B, S, H, hd).
    """
    B, S, H, D = q.shape
    KH = k.shape[2]
    rep = H // KH
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    out = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, interpret: bool = False):
    """Model-layout SSD: x (B, S, H, P), dt (B, S, H), Bm/Cm (B, S, N).

    Returns (y (B, S, H, P), h_final (B, H, P, N)).
    """
    xk = x.transpose(0, 2, 1, 3)
    dtk = dt.transpose(0, 2, 1)
    y, hf = ssd_scan_bhsp(xk, dtk, A, Bm, Cm, chunk=chunk,
                          interpret=interpret)
    return y.transpose(0, 2, 1, 3), hf


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
