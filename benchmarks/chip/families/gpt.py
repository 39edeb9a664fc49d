"""GPT-like decoder (pre-LayerNorm, GELU MLP, rotary positions, tied
embedding): weights from a seed, the plain float32 reference, and the
work one token needs.

Parameter trees follow the staged trainer's layout (per-stage trees of
layer-stacked arrays, one head tree per data node), so the same arrays
can be handed to the program and to the reference.  Matrices are kept in
the configuration's ``param_dtype``, norm parameters in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.chip.families.refmath import (cross_entropy, layernorm,
                                              normal)

LN_EPS = 1e-6


def init_layer(key, m):
    D, F = m["d_model"], m["d_ff"]
    q, kv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    dt = jnp.dtype(m["param_dtype"])
    k = jax.random.split(key, 10)
    f32 = jnp.float32
    return {
        "ln1": {"scale": 1.0 + normal(k[0], (D,), 0.1, f32),
                "bias": normal(k[1], (D,), 0.1, f32)},
        "attn": {"wq": normal(k[2], (D, q), D ** -0.5, dt),
                 "wk": normal(k[3], (D, kv), D ** -0.5, dt),
                 "wv": normal(k[4], (D, kv), D ** -0.5, dt),
                 "wo": normal(k[5], (q, D), q ** -0.5, dt)},
        "ln2": {"scale": 1.0 + normal(k[6], (D,), 0.1, f32),
                "bias": normal(k[7], (D,), 0.1, f32)},
        "mlp": {"w_up": normal(k[8], (D, F), D ** -0.5, dt),
                "w_down": normal(k[9], (F, D), F ** -0.5, dt)},
    }


def init_head(key, m):
    D, V = m["d_model"], m["vocab_size"]
    k = jax.random.split(key, 3)
    return {"embed": {"table": normal(k[0], (V, D), 0.02,
                                      jnp.dtype(m["param_dtype"]))},
            "final_norm": {"scale": 1.0 + normal(k[1], (D,), 0.1,
                                                 jnp.float32),
                           "bias": normal(k[2], (D,), 0.1, jnp.float32)}}


def embed(head, tokens):
    return jnp.take(head["embed"]["table"], tokens, axis=0).astype(
        jnp.float32)


def _rope(x, theta):
    """x: (B, S, H, hd); rotate the two halves of each head."""
    hd, S = x.shape[-1], x.shape[1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def layer(p, x, m, pr):
    B, S, D = x.shape
    H, KH, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    h = layernorm(x, p["ln1"]["scale"], p["ln1"]["bias"], LN_EPS)
    a = p["attn"]
    q = pr.ein("bsd,dk->bsk", h, a["wq"]).reshape(B, S, H, hd)
    k = pr.ein("bsd,dk->bsk", h, a["wk"]).reshape(B, S, KH, hd)
    v = pr.ein("bsd,dk->bsk", h, a["wv"]).reshape(B, S, KH, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k, v = jnp.repeat(k, H // KH, axis=2), jnp.repeat(v, H // KH, axis=2)
    s = pr.ein("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = pr.ein("bhqk,bkhd->bqhd", w, v).reshape(B, S, H * hd)
    x = x + pr.ein("bsk,kd->bsd", o, a["wo"])
    h = layernorm(x, p["ln2"]["scale"], p["ln2"]["bias"], LN_EPS)
    u = _gelu(pr.ein("bsd,df->bsf", h, p["mlp"]["w_up"]))
    return x + pr.ein("bsf,fd->bsd", u, p["mlp"]["w_down"])


def head_loss(head, x, labels, m, pr):
    h = layernorm(x, head["final_norm"]["scale"], head["final_norm"]["bias"],
                  LN_EPS)
    logits = pr.ein("bsd,vd->bsv", h, head["embed"]["table"])
    return cross_entropy(logits, labels)


def counts(m, seq_len):
    """Work the algorithm needs, from the shapes alone.

    ``layer_flops``/``head_flops``: forward FLOPs per token (a multiply
    and an add are two).  Causal attention counts half of the S x S score
    and value products.  ``layer_param_bytes``: one layer's parameters as
    stored.  ``act_bytes``: one token's boundary activation.
    """
    D, F, V = m["d_model"], m["d_ff"], m["vocab_size"]
    q, kv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    item = jnp.dtype(m["param_dtype"]).itemsize
    proj = 2 * D * (q + 2 * kv) + 2 * q * D
    attn = 2 * seq_len * q          # QK^T and PV, each S*S/2 per head-dim
    mlp = 2 * 2 * D * F
    matrices = D * (q + 2 * kv) + q * D + 2 * D * F
    return {"layer_flops": proj + attn + mlp,
            "head_flops": 2 * D * V,
            "layer_param_bytes": matrices * item + 4 * D * 4,
            "act_bytes": D * item}
