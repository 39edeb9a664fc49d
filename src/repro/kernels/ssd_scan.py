"""Chunked Mamba2/SSD scan as a Pallas TPU kernel.

TPU-native adaptation of the SSD algorithm (arXiv:2405.21060): the GPU
implementation leans on warp-level scans; on TPU we tile the sequence into
(chunk x P) VMEM blocks, compute the intra-chunk quadratic term on the MXU
(chunk-sized matmuls are MXU-aligned at chunk=128, P=64..128), and carry
the inter-chunk SSM state (P x N) in VMEM scratch across an 'arbitrary'
grid dimension — the recurrence becomes a grid-carried accumulator exactly
like flash attention's (m, l, acc).

Layouts: x (B, H, S, P); dt (B, H, S, 1); A (H, 1, 1); Bm/Cm (B, S, N)
shared across heads.  Outputs: y (B, H, S, P) and the final state
(B, H, P, N) written at the last chunk step.

Validated on CPU with interpret=True against kernels/ref.py
(ssd_reference — the sequential recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hf_ref, h_scr, *,
                chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[...].astype(jnp.float32)            # (chunk, P)
    dt = dt_ref[...].astype(jnp.float32)          # (chunk, 1)
    A = a_ref[0, 0]                               # scalar
    Bm = b_ref[...].astype(jnp.float32)           # (chunk, N)
    Cm = c_ref[...].astype(jnp.float32)           # (chunk, N)

    loga = dt * A                                 # (chunk, 1)
    idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = idx >= jdx
    # Mosaic has no cumsum: the inclusive prefix sum is a lower-triangular
    # ones matmul, at full f32 precision because exp() amplifies its error
    Lc = jnp.dot(causal.astype(jnp.float32), loga,
                 precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)      # (chunk, 1)
    L_total = jnp.sum(loga)                       # == Lc[-1]

    CB = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)
    delta = Lc - jnp.broadcast_to(Lc, (chunk, chunk)).T
    delta = jnp.where(causal, delta, 0.0)         # mask exponent pre-exp
    M = CB * jnp.exp(delta) * jnp.broadcast_to(dt, (chunk, chunk)).T
    M = jnp.where(causal, M, 0.0)
    y_intra = jnp.dot(M, x, preferred_element_type=jnp.float32)

    h = h_scr[...]                                # (P, N)
    y_state = jnp.dot(Cm, h.T,
                      preferred_element_type=jnp.float32) * jnp.exp(Lc)

    w = jnp.exp(L_total - Lc) * dt                # (chunk, 1)
    h_new = jnp.exp(L_total) * h + jnp.dot(
        (x * w).T, Bm, preferred_element_type=jnp.float32)
    h_scr[...] = h_new

    y_ref[...] = (y_intra + y_state).astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _final():
        hf_ref[...] = h_scr[...].astype(hf_ref.dtype)


def ssd_scan_bhsp(x, dt, A, Bm, Cm, *, chunk: int = 128,
                  interpret: bool = False):
    """x: (B, H, S, P); dt: (B, H, S); A: (H,); Bm/Cm: (B, S, N).

    Returns (y (B, H, S, P), h_final (B, H, P, N)) with zero initial state.
    """
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    grid = (B, H, S // chunk)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, hf = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, 1, 1), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt.reshape(B, H, S, 1), A.reshape(H, 1, 1), Bm, Cm)
    return y, hf
