"""Jit'd public wrappers for the Pallas kernels.

The kernels compile natively for the TPU.  ``interpret=True`` runs the
same kernel body through the Pallas interpreter instead, which is how
tests check them on the CPU; it is only ever the caller's choice.
Without it, a backend other than the TPU refuses the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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
