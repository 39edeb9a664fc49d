"""Flash attention Pallas TPU kernel (causal + sliding-window).

TPU-native adaptation: instead of a CUDA warp-level streaming softmax, the
kernel tiles Q into MXU-aligned (block_q x head_dim) VMEM blocks and
iterates KV blocks along an 'arbitrary' grid dimension, carrying the
online-softmax state (m, l, acc) in VMEM scratch between grid steps —
the canonical TPU flash pattern (HBM -> VMEM via BlockSpec, compute on the
MXU, no S x S materialisation).

Layout: inputs are (BH, S, D) with batch*heads flattened into the leading
grid dimension; GQA head-repeat happens in ops.py before the call.

Validated on CPU with interpret=True against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, seq_len: int, causal: bool,
                  window, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # skip fully-masked KV blocks (beyond the causal frontier / window)
    first_q = qi * block_q
    last_q = first_q + block_q - 1
    first_k = ki * block_k
    last_k = first_k + block_k - 1
    need = True
    if causal:
        need = jnp.asarray(first_k <= last_q)
    if window is not None:
        need = jnp.logical_and(need, jnp.asarray(last_k > first_q - window))

    @pl.when(need)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale        # (block_q, d)
        k = k_ref[...].astype(jnp.float32)                # (block_k, d)
        v = v_ref[...].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        mask = k_pos < seq_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                               # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # (block_q, block_k)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window=None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False):
    """q, k, v: (BH, S, D) — same head count (repeat GQA beforehand)."""
    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    grid = (BH, S // block_q, S // block_k)
    scale = D ** -0.5

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_len=S,
        causal=causal, window=window, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
