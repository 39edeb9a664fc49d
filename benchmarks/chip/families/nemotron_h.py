"""NVIDIA Nemotron-H (Nemotron 3 Nano): layers of one mixer each, Mamba-2,
MoE or attention, by the model's ``layer_pattern``; weights from a seed,
the plain float32 reference, and the work one token needs.

A kinded family (``reference.py``): ``layer_pattern`` holds one character
per layer, ``M`` Mamba-2, ``E`` MoE, ``*`` attention (the published
``hybrid_override_pattern``).  Every layer is ``x + mixer(RMSNorm(x))``;
a final RMSNorm comes before the untied head.  The equations follow the
published ``config.json`` and the ``nemotron_h`` modeling code it names:

- Mamba-2: ``in_proj`` to z, xBC and dt (no bias); a depthwise causal
  convolution with bias and SiLU over xBC; ``dt = softplus(dt +
  dt_bias)``, unclamped; ``A = -exp(A_log)``; the SSD with B and C in
  ``ssm_groups`` groups, head h reading group h // (H / G), plus D x;
  ``RMSNorm(y * silu(z))`` over each group's ``d_inner / G`` channels;
  ``out_proj``.  The inner width is ``ssm_heads * ssm_head_dim``.
- MoE: float32 router ``sigmoid(x W_r)`` over all ``num_experts``; the top
  ``num_experts_per_tok`` scores chosen (the score-correction bias is
  zero), renormalised to sum to one and scaled by ``routed_scaling``;
  experts ``down(relu(up x)^2)``.  The layer holds experts
  ``[first_expert, first_expert + experts_held)`` and adds only their
  part, plus the whole shared expert of width ``shared_d_ff``.
- Attention: GQA, causal, no bias and no rotary positions.

The SSD is the chunked form of arXiv:2405.21060 (Listing 1) at
``SSD_CHUNK``.  Matrices and the convolution are kept in ``param_dtype``,
the router, the per-head and the norm parameters in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.families.refmath import cross_entropy, normal, rmsnorm

SSD_CHUNK = 64
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def layer_kinds(m):
    return [KINDS[c] for c in m["layer_pattern"]]


def _ssm_dims(m):
    H, P = m["ssm_heads"], m["ssm_head_dim"]
    return H * P, H, P, m["ssm_groups"], m["ssm_state"], m["ssm_conv"]


def _held(m):
    return m["experts_held"] or m["num_experts"]


def _init_mamba(key, m):
    D = m["d_model"]
    di, H, P, G, N, K = _ssm_dims(m)
    dt = jnp.dtype(m["param_dtype"])
    f32 = jnp.float32
    k = jax.random.split(key, 9)
    conv = di + 2 * G * N
    dt0 = jnp.exp(jax.random.uniform(k[5], (H,), f32, jnp.log(1e-3),
                                     jnp.log(1e-1)))
    return {
        "in_proj": normal(k[1], (D, di + conv + H), D ** -0.5, dt),
        "conv_w": normal(k[2], (K, conv), K ** -0.5, dt),
        "conv_b": normal(k[3], (conv,), 0.1, dt),
        "A_log": jnp.log(jax.random.uniform(k[4], (H,), f32, 1.0, 16.0)),
        "D": 1.0 + normal(k[6], (H,), 0.1, f32),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),   # softplus^-1
        "norm_scale": 1.0 + normal(k[7], (di,), 0.1, f32),
        "out_proj": normal(k[8], (di, D), di ** -0.5, dt),
    }


def _init_moe(key, m):
    D, F, Fs = m["d_model"], m["d_ff"], m["shared_d_ff"]
    dt = jnp.dtype(m["param_dtype"])
    k = jax.random.split(key, 5)
    return {
        # logits of unit scale, so the top-k is decided by clear margins
        "router": normal(k[0], (D, m["num_experts"]), D ** -0.5,
                         jnp.float32),
        "w_up": normal(k[1], (_held(m), D, F), D ** -0.5, dt),
        "w_down": normal(k[2], (_held(m), F, D), F ** -0.5, dt),
        "shared": {"w_up": normal(k[3], (D, Fs), D ** -0.5, dt),
                   "w_down": normal(k[4], (Fs, D), Fs ** -0.5, dt)},
    }


def _init_attention(key, m):
    D = m["d_model"]
    q, kv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    dt = jnp.dtype(m["param_dtype"])
    k = jax.random.split(key, 4)
    return {"wq": normal(k[0], (D, q), D ** -0.5, dt),
            "wk": normal(k[1], (D, kv), D ** -0.5, dt),
            "wv": normal(k[2], (D, kv), D ** -0.5, dt),
            "wo": normal(k[3], (q, D), q ** -0.5, dt)}


_INIT = {"mamba": ("mamba", _init_mamba), "moe": ("moe", _init_moe),
         "attention": ("attn", _init_attention)}


def init_layer(key, m, kind):
    k0, k1 = jax.random.split(key)
    name, init = _INIT[kind]
    return {"ln1": {"scale": 1.0 + normal(k0, (m["d_model"],), 0.1,
                                          jnp.float32)},
            name: init(k1, m)}


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
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,  y_t = h_t C_t, head h
    reading B and C of group h // (H / G).

    x: (b, l, h, p); dt: (b, l, h); A: (h,); B, C: (b, l, g, n)."""
    b, l, h, p = x.shape
    chunk = chunk if l % chunk == 0 else l
    g, n = B.shape[2:]
    c, r = l // chunk, h // g
    X = (x * dt[..., None]).reshape(b, c, chunk, g, r, p)
    a = (dt * A).reshape(b, c, chunk, g, r).transpose(0, 3, 4, 1, 2)
    Bc, Cc = B.reshape(b, c, chunk, g, n), C.reshape(b, c, chunk, g, n)
    a_cs = jnp.cumsum(a, axis=-1)                          # b g r c l
    y_diag = pr.ein("bclgn,bcsgn,bgrcls,bcsgrp->bclgrp", Cc, Bc,
                    jnp.exp(_segsum(a)), X)
    decay = jnp.exp(a_cs[..., -1:] - a_cs)
    states = pr.ein("bclgn,bgrcl,bclgrp->bcgrpn", Bc, decay, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(
        a_cs[..., -1], ((0, 0), (0, 0), (0, 0), (1, 0)))))
    states = pr.ein("bgrzc,bcgrpn->bzgrpn", chunk_decay, states)[:, :-1]
    y_off = pr.ein("bclgn,bcgrpn,bgrcl->bclgrp", Cc, states, jnp.exp(a_cs))
    return (y_diag + y_off).reshape(b, l, h, p)


def _mamba(mp, h, m, pr):
    Bsz, S, _ = h.shape
    di, H, P, G, N, K = _ssm_dims(m)
    eps = m["norm_eps"]
    zxbcdt = pr.ein("bsd,de->bse", h, mp["in_proj"])
    conv = di + 2 * G * N
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + conv],
                  zxbcdt[..., di + conv:])
    w = mp["conv_w"].astype(jnp.float32)
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, i:i + S] * w[i] for i in range(K))
                      + mp["conv_b"].astype(jnp.float32))
    xs = xbc[..., :di].reshape(Bsz, S, H, P)
    Bm = xbc[..., di:di + G * N].reshape(Bsz, S, G, N)
    Cm = xbc[..., di + G * N:].reshape(Bsz, S, G, N)
    dt = jax.nn.softplus(dt + mp["dt_bias"])
    y = ssd(xs, dt, -jnp.exp(mp["A_log"]), Bm, Cm, pr)
    y = (y + mp["D"][:, None] * xs).reshape(Bsz, S, di)
    g = (y * jax.nn.silu(z)).reshape(Bsz, S, G, di // G)
    g = rmsnorm(g, 1.0, eps).reshape(Bsz, S, di) * mp["norm_scale"]
    return pr.ein("bse,ed->bsd", g, mp["out_proj"])


def _relu2(u):
    return jnp.square(jax.nn.relu(u))


def _moe(mp, h, m, pr):
    k, first = m["num_experts_per_tok"], m["first_expert"]
    scores = jax.nn.sigmoid(pr.ein("bsd,de->bse", h, mp["router"]))
    top, idx = jax.lax.top_k(scores, k)
    w = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) \
        * m["routed_scaling"]
    # each held expert's weight for every token (0 where not chosen)
    held = first + jnp.arange(_held(m))
    c = jnp.sum(jnp.where(idx[..., None] == held, w[..., None], 0.0),
                axis=-2)                                   # b s e
    u = _relu2(pr.ein("bsd,edf->bsef", h, mp["w_up"]))
    out = pr.ein("bsef,efd,bse->bsd", u, mp["w_down"], c)
    sh = mp["shared"]
    return out + pr.ein("bsf,fd->bsd",
                        _relu2(pr.ein("bsd,df->bsf", h, sh["w_up"])),
                        sh["w_down"])


def _attention(a, h, m, pr):
    B, S, _ = h.shape
    H, KH, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = pr.ein("bsd,dk->bsk", h, a["wq"]).reshape(B, S, H, hd)
    k = pr.ein("bsd,dk->bsk", h, a["wk"]).reshape(B, S, KH, hd)
    v = pr.ein("bsd,dk->bsk", h, a["wv"]).reshape(B, S, KH, hd)
    k, v = jnp.repeat(k, H // KH, axis=2), jnp.repeat(v, H // KH, axis=2)
    s = pr.ein("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = pr.ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return pr.ein("bsk,kd->bsd", o.reshape(B, S, H * hd), a["wo"])


def layer(p, x, m, pr, kind):
    h = rmsnorm(x, p["ln1"]["scale"], m["norm_eps"])
    if kind == "mamba":
        return x + _mamba(p["mamba"], h, m, pr)
    if kind == "moe":
        return x + _moe(p["moe"], h, m, pr)
    return x + _attention(p["attn"], h, m, pr)


def head_loss(head, x, labels, m, pr):
    h = rmsnorm(x, head["final_norm"]["scale"], m["norm_eps"])
    return cross_entropy(pr.ein("bsd,dv->bsv", h, head["embed"]["lm_head"]),
                         labels)


def counts(m, seq_len):
    """Work the algorithm needs, from the shapes alone, per layer kind (see
    ``gpt.counts`` and ``mamba2.counts`` for the conventions).

    Mamba-2: projections, convolution and the chunked SSD at
    ``SSD_CHUNK`` (the causal half of each group's C.B and of the
    intra-chunk mixing, the state read-out and update).  MoE: the router,
    the shared expert and the expected held-expert pairs,
    ``num_experts_per_tok * experts_held / num_experts`` a token.
    Attention: projections and the causal half of the score and value
    products."""
    D, V, F, Fs = m["d_model"], m["vocab_size"], m["d_ff"], m["shared_d_ff"]
    di, H, P, G, N, K = _ssm_dims(m)
    q, kv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    E, item = m["num_experts"], jnp.dtype(m["param_dtype"]).itemsize
    Q, conv = SSD_CHUNK, di + 2 * G * N
    mamba = (2 * D * (di + conv + H) + 2 * di * D + 2 * K * conv
             + G * Q * N + Q * P * H + 4 * N * P * H)
    mamba_bytes = ((D * (di + conv + H) + di * D + (K + 1) * conv) * item
                   + (D + 3 * H + di) * 4)
    pairs = m["num_experts_per_tok"] * _held(m) / E
    moe_flops = 2 * D * E + 4 * D * Fs + pairs * 4 * D * F
    moe_bytes = (2 * D * Fs + _held(m) * 2 * D * F) * item + (D * E + D) * 4
    attn = 2 * D * (q + 2 * kv) + 2 * q * D + 2 * seq_len * q
    attn_bytes = (2 * D * (q + kv)) * item + D * 4
    return {"layer_flops": {"mamba": mamba, "moe": moe_flops,
                            "attention": attn},
            "layer_param_bytes": {"mamba": mamba_bytes, "moe": moe_bytes,
                                  "attention": attn_bytes},
            "head_flops": 2 * D * V,
            "act_bytes": D * item}
