"""A tiny model family with layers of three kinds, for the CPU tests of
the kinded family contract (``reference.py``).

``mlp`` layers have one tree, ``full`` and ``window`` layers another:
the same attention weights, causal over the whole sequence or over the
last ``window`` positions only, so a window/full mix is one tree shape
under two static behaviours.  The pattern is the model's string
``layer_pattern``, one letter a layer (``F``, ``W``, ``M``).  Pure
``jax.numpy`` in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.families.refmath import cross_entropy, normal, rmsnorm

EPS = 1e-6
KINDS = {"F": "full", "W": "window", "M": "mlp"}
F32 = jnp.float32

MODEL = {"num_layers": 7, "layer_pattern": "MFMWMFM", "d_model": 16,
         "num_heads": 2, "head_dim": 8, "d_ff": 32, "window": 3,
         "vocab_size": 64}


def layer_kinds(m):
    return tuple(KINDS[c] for c in m["layer_pattern"])


def init_layer(key, m, kind):
    D = m["d_model"]
    k = jax.random.split(key, 3)
    norm = 1.0 + normal(k[0], (D,), 0.1, F32)
    if kind == "mlp":
        F = m["d_ff"]
        return {"norm": norm, "w_up": normal(k[1], (D, F), D ** -0.5, F32),
                "w_down": normal(k[2], (F, D), F ** -0.5, F32)}
    q = m["num_heads"] * m["head_dim"]
    return {"norm": norm, "wqkv": normal(k[1], (D, 3 * q), D ** -0.5, F32),
            "wo": normal(k[2], (q, D), q ** -0.5, F32)}


def init_head(key, m):
    D, V = m["d_model"], m["vocab_size"]
    k = jax.random.split(key, 2)
    return {"embed": normal(k[0], (V, D), 0.5, F32),
            "norm": 1.0 + normal(k[1], (D,), 0.1, F32)}


def embed(head, tokens):
    return jnp.take(head["embed"], tokens, axis=0)


def layer(p, x, m, pr, kind):
    h = rmsnorm(x, p["norm"], EPS)
    if kind == "mlp":
        u = jax.nn.gelu(pr.ein("bsd,df->bsf", h, p["w_up"]))
        return x + pr.ein("bsf,fd->bsd", u, p["w_down"])
    B, S, _ = x.shape
    H, hd = m["num_heads"], m["head_dim"]
    qkv = pr.ein("bsd,dk->bsk", h, p["wqkv"]).reshape(B, S, 3, H, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = pr.ein("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    i = jnp.arange(S)
    seen = i[:, None] >= i[None, :]
    if kind == "window":
        seen &= i[:, None] - i[None, :] < m["window"]
    w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    o = pr.ein("bhqk,bkhd->bqhd", w, v).reshape(B, S, H * hd)
    return x + pr.ein("bsk,kd->bsd", o, p["wo"])


def head_loss(head, x, labels, m, pr):
    h = rmsnorm(x, head["norm"], EPS)
    return cross_entropy(pr.ein("bsd,vd->bsv", h, head["embed"]), labels)


def counts(m, seq_len):
    """Forward FLOPs per token and parameter bytes, by kind."""
    D, F, V = m["d_model"], m["d_ff"], m["vocab_size"]
    q = m["num_heads"] * m["head_dim"]
    attn = 2 * D * 3 * q + 2 * q * D
    return {"layer_flops": {"full": attn + 2 * seq_len * q,
                            "window": attn + 4 * m["window"] * q,
                            "mlp": 4 * D * F},
            "layer_param_bytes": {"full": (D + 4 * D * q) * 4,
                                  "window": (D + 4 * D * q) * 4,
                                  "mlp": (D + 2 * D * F) * 4},
            "head_flops": 2 * D * V, "act_bytes": D * 4}
