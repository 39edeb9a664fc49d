"""Mamba2 / SSD (state-space duality) block — arXiv:2405.21060.

Implements the chunked SSD algorithm for all chunks at once: intra-chunk
quadratic (attention-like) term + inter-chunk recurrence as masked decay
matmuls over blocks of chunks, with no sequential loop.  A
single-step decode path maintains (conv_state, ssm_state) caches for O(1)
per-token decoding — this is what makes ``long_500k`` tractable for the
ssm/hybrid archs.

``ssd_reference``, the sequential recurrence, is the oracle that
``ssd_chunked`` is tested against.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------

def ssd_reference(x, dt, A, B, C, h0=None):
    """Sequential SSD recurrence — the oracle.

    x: (b, S, H, P); dt: (b, S, H); A: (H,); B, C: (b, S, N).
    h_t = exp(dt_t A) h_{t-1} + dt_t * x_t (x) B_t ;  y_t = h_t . C_t
    Returns y: (b, S, H, P), h_final: (b, H, P, N).
    """
    b, S, H, P = x.shape
    N = B.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((b, H, P, N), jnp.float32)

    def step(h, inp):
        xt, dtt, Bt, Ct = inp           # (b,H,P), (b,H), (b,N), (b,N)
        a = jnp.exp(dtt * A)            # (b,H)
        h = a[..., None, None] * h + (dtt[..., None] * xt)[..., None] * Bt[:, None, None, :]
        y = jnp.einsum("bhpn,bn->bhp", h, Ct)
        return h, y

    xs = (x.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
          B.transpose(1, 0, 2), C.transpose(1, 0, 2))
    hf, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3), hf


def ssd_chunked(x, dt, A, B, C, h0=None, chunk: int = 64):
    """Chunked SSD, every chunk at once (arXiv:2405.21060, Sec. 6).

    Same signature/semantics as ``ssd_reference`` (float32 internal math);
    B and C may also come in G groups, (b, S, G, N), head h reading group
    h // (H / G): each group is then its own SSD over its heads, vmapped.
    Head-major layout ``(b, H, c, Q, .)`` keeps the chunk length Q next to
    P or N in the minor dimensions.  Every contraction has two operands,
    so no ``(b, H, c, Q, Q, P)`` intermediate can appear.  States pass
    between the c chunks through ``_decay_chain``: O(c) work, one
    (c x c) matmul up to 2048 chunks (128k tokens at chunk 64).

    Under differentiation the backward pass recomputes the chunks from the
    inputs (``jax.checkpoint``): stored, their ``(b, H, c, Q, Q)``,
    ``(b, H, c, Q, P)`` and per-chunk state intermediates would take as
    many residual bytes as the rest of the Mamba-2 block, for little compute.
    """
    b, S, H, P = x.shape
    assert S % chunk == 0, f"S={S} % chunk={chunk}"
    if B.ndim == 3:
        return _ssd_all_chunks(x, dt, A, B, C, h0, chunk)
    G, N = B.shape[2:]

    def split(a, axis):                      # heads -> (G, H // G)
        return a.reshape(a.shape[:axis] + (G, H // G) + a.shape[axis + 1:])

    y, hf = jax.vmap(lambda *a: _ssd_all_chunks(*a, chunk),
                     (2, 2, 0, 2, 2, 1), (2, 1))(
        split(x, 2), split(dt, 2), split(A, 0), B, C,
        None if h0 is None else split(h0, 1))
    return y.reshape(b, S, H, P), hf.reshape(b, H, P, N)


@functools.partial(jax.checkpoint, static_argnums=(6,))
def _ssd_all_chunks(x, dt, A, B, C, h0, chunk):
    b, S, H, P = x.shape
    N = B.shape[-1]
    nc = S // chunk
    f32 = jnp.float32
    dtf = dt.astype(f32).reshape(b, nc, chunk, H).transpose(0, 3, 1, 2)
    # dt folded into x: X[s] = dt_s x_s                    (b,H,c,Q,P)
    X = (x.astype(f32).reshape(b, nc, chunk, H, P).transpose(0, 3, 1, 2, 4)
         * dtf[..., None])
    Bf = B.astype(f32).reshape(b, nc, chunk, N)            # (b,c,Q,N)
    Cf = C.astype(f32).reshape(b, nc, chunk, N)
    L = jnp.cumsum(dtf * A[:, None, None], axis=-1)  # (b,H,c,Q) log decay

    # intra-chunk: y[t] = sum_{s<=t} exp(L[t]-L[s]) (C[t].B[s]) X[s].  Mask
    # the exponent *before* exp: the s>t half would overflow to +inf (L is
    # non-increasing), and exp's gradient there would poison the rest.
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, L[..., :, None] - L[..., None, :],
                              -jnp.inf))                   # (b,H,c,Q,Q)
    CB = jnp.einsum("bctn,bcsn->bcts", Cf, Bf)             # (b,c,Q,Q)
    y = jnp.einsum("bhcts,bhcsp->bhctp", decay * CB[:, None], X)

    # chunk-end states: sum_s exp(L[Q-1]-L[s]) X[s] (x) B[s]  (b,H,c,P,N)
    tail = jnp.exp(L[..., -1:] - L)
    states = jnp.einsum("bhcsp,bcsn->bhcpn", X * tail[..., None], Bf)

    # inter-chunk recurrence h_k = exp(T_k) h_{k-1} + states_k, T_k the
    # chunk's total log decay: from a zero state, then h0 decayed by every
    # chunk so far.  h_end[k] is the state leaving chunk k.
    T = L[..., -1]                                         # (b,H,c)
    h_end = _decay_chain(T, states)
    if h0 is None:
        h0 = jnp.zeros((b, H, P, N), f32)
    else:
        h0 = h0.astype(f32)
        h_end = h_end + (jnp.exp(jnp.cumsum(T, axis=-1))[..., None, None]
                         * h0[:, :, None])
    h_in = jnp.concatenate([h0[:, :, None], h_end[:, :, :-1]], axis=2)
    hf = h_end[:, :, -1]

    # incoming state's contribution: y[t] += exp(L[t]) C[t] . h_in
    y_off = jnp.einsum("bctn,bhcpn->bhctp", Cf, h_in) * jnp.exp(L)[..., None]
    y = (y + y_off).transpose(0, 2, 3, 1, 4).reshape(b, S, H, P)
    return y.astype(x.dtype), hf


def _decay_chain(log_a, s, block: int = 2048):
    """Every h_k of h_k = exp(log_a[k]) h_{k-1} + s[k], h_{-1} = 0, with no
    loop.  log_a: (b, H, n); s: (b, H, n, ...).

    Blocks of ``block`` steps each take one masked (block x block) decay
    matmul; the states entering the blocks are the same recurrence over
    the blocks' ends, solved by recursion.  So the work is O(n * block),
    and n <= block is one (n x n) matmul.  A second block level costs
    extra passes over every state (pad, the block-entry update, the
    slice); on a TPU v5e those took longer than the larger matmul costs,
    up to 2048 steps of Mamba-2's (64 x 128) states.
    """
    b, H, n = log_a.shape
    m = -(-n // block)
    g = n if m == 1 else block
    rest = s.shape[3:]
    tail = ((0, 0),) * len(rest)
    la = jnp.pad(log_a, ((0, 0), (0, 0), (0, m * g - n))).reshape(b, H, m, g)
    s = jnp.pad(s, ((0, 0), (0, 0), (0, m * g - n)) + tail)
    s = s.reshape((b, H, m, g) + rest)
    # decay[k, j] = exp(sum_{j<i<=k} la[i]), each sum taken from la itself:
    # as a difference of two cumulative sums it would lose its precision
    # after one large decay
    seg = jnp.cumsum(jnp.where(jnp.tril(jnp.ones((g, g), bool), -1),
                               la[..., :, None], 0.0), axis=-2)
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((g, g), bool)), seg,
                              -jnp.inf))                   # (b,H,m,g,g)
    h = jnp.einsum("bhmkj,bhmj...->bhmk...", decay, s)
    if m > 1:
        cum = jnp.cumsum(la, axis=-1)
        ends = _decay_chain(cum[..., -1], h[:, :, :, -1], block)
        enter = jnp.pad(ends[:, :, :-1], ((0, 0), (0, 0), (1, 0)) + tail)
        grow = jnp.exp(cum).reshape((b, H, m, g) + (1,) * len(rest))
        h = h + grow * enter[:, :, :, None]
    return h.reshape((b, H, m * g) + rest)[:, :, :n]


def ssd_decode_step(h, xt, dtt, A, Bt, Ct):
    """One-token SSD update. h: (b,H,P,N); xt: (b,H,P); dtt: (b,H)."""
    a = jnp.exp(dtt * A)
    h = a[..., None, None] * h + (dtt[..., None] * xt)[..., None] * Bt[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", h, Ct)
    return h, y


# ---------------------------------------------------------------------------
# Mamba2 block (in_proj -> conv -> SSD -> gated norm -> out_proj)
# ---------------------------------------------------------------------------

def init_mamba(key, cfg: ModelConfig, dtype):
    D = cfg.d_model
    di = cfg.d_inner
    N, H = cfg.ssm_state, cfg.ssm_heads
    GN = cfg.ssm_groups * N
    conv_dim = di + 2 * GN
    ks = jax.random.split(key, 4)
    return {
        "in_proj": dense_init(ks[0], (D, 2 * di + 2 * GN + H), dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, conv_dim), jnp.float32)
                   * (cfg.ssm_conv ** -0.5)).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm_scale": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[3], (di, D), dtype),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv. x: (B,S,C); w: (K,C). Returns (y, new_state)."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=1)              # (B, S+K-1, C)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(K)) + b
    new_state = xp[:, -(K - 1):, :] if K > 1 else None
    return y, new_state


def apply_mamba(p, x, cfg: ModelConfig, *, cache=None, chunk: int = 64):
    """x: (B, S, D). cache: dict(conv=(B,K-1,conv_dim), ssm=(B,H,P,N)) or None.
    Returns (out, new_cache)."""
    B_, S, D = x.shape
    di, N, H, G = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_groups
    P = di // H
    GN = G * N

    zxbcdt = x @ p["in_proj"]
    z, xs, Bc, Cc, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + GN, 2 * di + 2 * GN], axis=-1)

    conv_in = jnp.concatenate([xs, Bc, Cc], axis=-1)
    conv_state = cache["conv"] if cache is not None else None
    conv_out, new_conv = _causal_conv(conv_in, p["conv_w"], p["conv_b"], conv_state)
    conv_out = jax.nn.silu(conv_out)
    xs, Bc, Cc = jnp.split(conv_out, [di, di + GN], axis=-1)
    if G > 1:
        Bc, Cc = (a.reshape(B_, S, G, N) for a in (Bc, Cc))

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])     # (B,S,H)
    A = -jnp.exp(p["A_log"])                                        # (H,)
    xh = xs.reshape(B_, S, H, P)

    if cache is not None and S == 1:
        if G > 1:
            raise NotImplementedError("one-token decode reads one B/C group")
        h, y = ssd_decode_step(cache["ssm"], xh[:, 0].astype(jnp.float32),
                               dt[:, 0], A, Bc[:, 0].astype(jnp.float32),
                               Cc[:, 0].astype(jnp.float32))
        y = y[:, None].astype(x.dtype)                              # (B,1,H,P)
        new_cache = {"conv": new_conv, "ssm": h}
    else:
        ck = chunk if S % chunk == 0 else S
        h0 = cache["ssm"] if cache is not None else None
        y, h = ssd_chunked(xh, dt, A, Bc, Cc, h0=h0, chunk=ck)
        new_cache = {"conv": new_conv, "ssm": h} if cache is not None else None

    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B_, S, di)
    # gated RMSNorm (mamba2 style), over each of the G groups' channels
    g = y * jax.nn.silu(z.astype(jnp.float32))
    if G > 1:
        g = g.reshape(B_, S, G, di // G)
    ms = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = (g * jax.lax.rsqrt(ms + cfg.norm_eps)).reshape(B_, S, di) * p["norm_scale"]
    return g.astype(x.dtype) @ p["out_proj"], new_cache


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    P = di // H
    conv_dim = di + 2 * cfg.ssm_groups * N
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, H, P, N), jnp.float32),
    }
