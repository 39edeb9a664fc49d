"""Mamba-2 (SSD) decoder: weights from a seed, the plain float32
reference, and the work one token needs.

The reference computes the state-space dual layer in the chunked form of
arXiv:2405.21060 (Listing 1, ``ssd_minimal_discrete``), with one B and C
group shared by all heads.  The count fixes the chunk at ``SSD_CHUNK``
and counts the causal half of the intra-chunk products, so it does not
follow the chunk size the program picks.  Parameter trees follow the
staged trainer's layout; matrices and the convolution are kept in the
configuration's ``param_dtype``, the per-head and norm parameters in
float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.families.refmath import cross_entropy, normal, rmsnorm

NORM_EPS = 1e-6
SSD_CHUNK = 64


def _dims(m):
    di = m["ssm_expand"] * m["d_model"]
    H, N = m["ssm_heads"], m["ssm_state"]
    return di, H, di // H, N, m["ssm_conv"]


def init_layer(key, m):
    D = m["d_model"]
    di, H, P, N, K = _dims(m)
    dt = jnp.dtype(m["param_dtype"])
    f32 = jnp.float32
    k = jax.random.split(key, 9)
    dt0 = jnp.exp(jax.random.uniform(k[5], (H,), f32, jnp.log(1e-3),
                                     jnp.log(1e-1)))
    return {
        "ln1": {"scale": 1.0 + normal(k[0], (D,), 0.1, f32)},
        "mamba": {
            "in_proj": normal(k[1], (D, 2 * di + 2 * N + H), D ** -0.5, dt),
            "conv_w": normal(k[2], (K, di + 2 * N), K ** -0.5, dt),
            "conv_b": normal(k[3], (di + 2 * N,), 0.1, dt),
            "A_log": jnp.log(jax.random.uniform(k[4], (H,), f32, 1.0, 16.0)),
            "D": 1.0 + normal(k[6], (H,), 0.1, f32),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),   # softplus^-1
            "norm_scale": 1.0 + normal(k[7], (di,), 0.1, f32),
            "out_proj": normal(k[8], (di, D), di ** -0.5, dt),
        },
    }


def init_head(key, m):
    D, V = m["d_model"], m["vocab_size"]
    dt = jnp.dtype(m["param_dtype"])
    k = jax.random.split(key, 3)
    return {"embed": {"table": normal(k[0], (V, D), 0.02, dt),
                      "lm_head": normal(k[1], (D, V), D ** -0.5, dt)},
            "final_norm": {"scale": 1.0 + normal(k[2], (D,), 0.1,
                                                 jnp.float32)}}


def embed(head, tokens):
    return jnp.take(head["embed"]["table"], tokens, axis=0).astype(
        jnp.float32)


def _segsum(a):
    """(..., T) -> (..., T, T): sum of a over (j, i] where j <= i."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), d, -jnp.inf)


def ssd(x, dt, A, B, C, pr, chunk=SSD_CHUNK):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,  y_t = h_t C_t.

    x: (b, l, h, p); dt: (b, l, h); A: (h,); B, C: (b, l, n)."""
    b, l, h, p = x.shape
    chunk = chunk if l % chunk == 0 else l
    n, c = B.shape[-1], l // chunk
    X = (x * dt[..., None]).reshape(b, c, chunk, h, p)
    a = (dt * A).reshape(b, c, chunk, h).transpose(0, 3, 1, 2)   # b h c l
    Bc, Cc = B.reshape(b, c, chunk, n), C.reshape(b, c, chunk, n)
    a_cs = jnp.cumsum(a, axis=-1)
    y_diag = pr.ein("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc,
                    jnp.exp(_segsum(a)), X)
    decay = jnp.exp(a_cs[..., -1:] - a_cs)
    states = pr.ein("bcln,bhcl,bclhp->bchpn", Bc, decay, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cs[..., -1],
                                          ((0, 0), (0, 0), (1, 0)))))
    states = pr.ein("bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]
    y_off = pr.ein("bcln,bchpn,bhcl->bclhp", Cc, states, jnp.exp(a_cs))
    return (y_diag + y_off).reshape(b, l, h, p)


def layer(p, x, m, pr):
    Bsz, S, _ = x.shape
    di, H, P, N, K = _dims(m)
    mp = p["mamba"]
    h = rmsnorm(x, p["ln1"]["scale"], NORM_EPS)
    zxbcdt = pr.ein("bsd,de->bse", h, mp["in_proj"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * N],
                  zxbcdt[..., 2 * di + 2 * N:])
    w = mp["conv_w"].astype(jnp.float32)
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + S] * w[i] for i in range(K))
    xbc = jax.nn.silu(conv + mp["conv_b"].astype(jnp.float32))
    xs, Bm, Cm = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + mp["dt_bias"])
    xh = xs.reshape(Bsz, S, H, P)
    y = ssd(xh, dt, -jnp.exp(mp["A_log"]), Bm, Cm, pr)
    y = (y + mp["D"][:, None] * xh).reshape(Bsz, S, di)
    g = rmsnorm(y * jax.nn.silu(z), mp["norm_scale"], NORM_EPS)
    return x + pr.ein("bse,ed->bsd", g, mp["out_proj"])


def head_loss(head, x, labels, m, pr):
    h = rmsnorm(x, head["final_norm"]["scale"], NORM_EPS)
    return cross_entropy(pr.ein("bsd,dv->bsv", h, head["embed"]["lm_head"]),
                         labels)


def counts(m, seq_len):
    """Work the algorithm needs, from the shapes alone (see gpt.counts).

    The SSD part is the chunked form at ``SSD_CHUNK`` tokens: the causal
    half of C.B and of the intra-chunk mixing, plus the state read-out
    and the state update (2 N P per head each)."""
    D, V = m["d_model"], m["vocab_size"]
    di, H, P, N, K = _dims(m)
    item = jnp.dtype(m["param_dtype"]).itemsize
    Q = SSD_CHUNK
    proj = 2 * D * (2 * di + 2 * N + H) + 2 * di * D
    conv = 2 * K * (di + 2 * N)
    ssd_flops = Q * N + Q * P * H + 4 * N * P * H
    stored = D * (2 * di + 2 * N + H) + di * D + (K + 1) * (di + 2 * N)
    f32 = D + 3 * H + di
    return {"layer_flops": proj + conv + ssd_flops,
            "head_flops": 2 * D * V,
            "layer_param_bytes": stored * item + f32 * 4,
            "act_bytes": D * item}
