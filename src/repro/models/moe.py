"""Mixture-of-Experts layer (granite-moe, qwen2-moe style).

Two execution modes:

* ``dense``  — every expert computes every token; router combine-weights
  zero out the non-selected ones.  Simple, shards trivially (expert d_ff on
  the 'model' axis), but wastes E/topk of the FLOPs.  This is the paper-
  faithful baseline mode (GWTF does not optimise intra-stage compute).
* ``ragged`` — tokens are sorted by expert and computed with
  ``jax.lax.ragged_dot`` so only active (token, expert) pairs cost FLOPs.
  This is the beyond-paper optimisation used in the §Perf hillclimb.

Router load-balance auxiliary loss (Switch-style) is returned so training
can keep experts balanced — GWTF's bottleneck-stage argument applied to
experts.  The sigmoid router (nemotron_h) balances without one and
returns 0.

A layer may hold a share of the experts (``experts_held`` from
``first_expert``), as one chip of an expert-parallel deployment does: it
routes over all ``num_experts`` and, on the ``ragged`` path, computes
only its own experts' part of the result; the shared expert is whole.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init


def _gated(cfg: ModelConfig) -> bool:
    return cfg.mlp_type in ("swiglu", "geglu")


def _act(g, cfg: ModelConfig):
    if cfg.mlp_type == "swiglu":
        return jax.nn.silu(g)
    if cfg.mlp_type == "relu2":
        return jnp.square(jax.nn.relu(g))
    return jax.nn.gelu(g)


def init_moe(key, cfg: ModelConfig, dtype):
    """Router over all ``num_experts``; weights of the experts held."""
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts_held
    ks = jax.random.split(key, 5)
    p = {"router": dense_init(ks[0], (D, cfg.num_experts), jnp.float32,
                              scale=0.02)}
    if _gated(cfg):
        p["w_gate"] = dense_init(ks[1], (E, D, F), dtype)
    p["w_up"] = dense_init(ks[2], (E, D, F), dtype)
    p["w_down"] = dense_init(ks[3], (E, F, D), dtype)
    if cfg.num_shared_experts:
        Fs = cfg.shared_width
        sk = jax.random.split(ks[4], 3)
        p["shared"] = {}
        if _gated(cfg):
            p["shared"]["w_gate"] = dense_init(sk[0], (D, Fs), dtype)
        p["shared"]["w_up"] = dense_init(sk[1], (D, Fs), dtype)
        p["shared"]["w_down"] = dense_init(sk[2], (Fs, D), dtype)
    return p


def _route(p, x, cfg: ModelConfig):
    """Returns (weights (T,E) combine weights, aux_loss). x: (T, D)."""
    k = cfg.num_experts_per_tok
    if cfg.router == "sigmoid":
        # nemotron_h: float32 scores, the top k chosen (its score-correction
        # bias is zero here), their scores renormalised and scaled; no aux
        probs = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), p["router"],
                                       precision=jax.lax.Precision.HIGHEST))
        topv, topi = jax.lax.top_k(probs, k)
        topv = (topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
                * cfg.routed_scaling)
    else:
        logits = x.astype(jnp.float32) @ p["router"]      # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, k)              # (T, k)
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)   # renormalise
    combine = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], topi].set(topv)  # (T, E)
    if cfg.router == "sigmoid":
        return combine, topi, topv, jnp.float32(0.0)
    # Switch aux loss: E * sum_e (frac_tokens_e * mean_prob_e)
    frac = jnp.mean((combine > 0).astype(jnp.float32), axis=0)
    aux = cfg.num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
    return combine, topi, topv, aux


def _expert_mlp_dense(p, x, combine, cfg: ModelConfig):
    """All-experts path as a scan over experts. x: (T, D); combine: (T, E).

    A naive ``einsum('td,edf->tef')`` makes XLA broadcast x to every
    expert ((E, D, T) — tens of GB at 32k context) and materialise a
    (T, E, D) output.  Scanning experts keeps the live set to one
    (T, F) block; combine-weights fold in *before* the down-projection so
    the output accumulates directly into (T, D).  FLOPs are identical
    (this is the paper-faithful dense baseline the §Perf ragged
    optimisation is measured against).
    """
    from repro.parallel.sharding import shard

    def one_expert(acc, ewc):
        ew, c_e = ewc                          # {w_gate?, w_up, w_down}, (T,)
        if _gated(cfg):
            g = shard(x @ ew["w_gate"], "batch", "tp")
            h = _act(g, cfg) * (x @ ew["w_up"])    # (T, F)
        else:
            h = _act(shard(x @ ew["w_up"], "batch", "tp"), cfg)
        h = h * c_e[:, None].astype(h.dtype)
        return acc + h @ ew["w_down"], None

    acc0 = jnp.zeros_like(x)
    experts = {k: p[k] for k in ("w_gate", "w_up", "w_down") if k in p}
    out, _ = jax.lax.scan(one_expert, acc0,
                          (experts, combine.T.astype(x.dtype)))
    return out


def _expert_mlp_ragged(p, x, topi, topv, cfg: ModelConfig):
    """Active-only path: sort (token, expert) pairs by expert, ragged_dot.

    Only the pairs of the experts held, ``[first_expert, first_expert +
    experts_held)``, form groups; every other pair sorts after them and
    its rows are zero, so what the absent experts would add is left out.
    FLOPs ~ held pairs * D * F instead of T*E*D*F.
    """
    T, D = x.shape
    k = topi.shape[1]
    n = cfg.num_experts_held
    e = topi.reshape(-1) - cfg.first_expert                # (T*k,)
    e = jnp.where((e >= 0) & (e < n), e, n)
    order = jnp.argsort(e, stable=True)                    # held first, by expert
    se, st, sw = e[order], order // k, topv.reshape(-1)[order]
    # the token index by division and the group sizes by compare-and-sum:
    # on a TPU v5e a gathered index and bincount's scatter-add made the
    # Nemotron stage programs 6 % slower
    group_sizes = jnp.sum(se[None, :] == jnp.arange(n)[:, None], axis=1,
                          dtype=jnp.int32)
    held = (se < n)[:, None]

    def dot(a, w):
        # ragged_dot leaves the rows past the groups undefined (on a TPU,
        # whatever the buffer held): zero them here, so that no operand of
        # either pass, the backward's products with zero cotangents
        # included, reads them
        return jnp.where(held, jax.lax.ragged_dot(a, w, group_sizes), 0)

    xs = jnp.where(held, x[st], 0)                         # (T*k, D) gathered
    u = dot(xs, p["w_up"])
    h = _act(dot(xs, p["w_gate"]), cfg) * u if _gated(cfg) else _act(u, cfg)
    y = dot(h.astype(xs.dtype), p["w_down"]) * sw[:, None].astype(x.dtype)
    return jnp.zeros_like(x).at[st].add(y)


def _expert_mlp_capacity(p, x, topi, topv, cfg: ModelConfig,
                         capacity_factor: float = 2.0):
    """Active-only path via capacity-bounded dispatch (Switch-style).

    Tokens are sorted by expert; each expert processes at most
    C = capacity_factor * T * topk / E tokens (overflow dropped, weights
    renormalised by construction).  All shapes static, all ops standard
    (gather / batched dot / scatter) — lowers everywhere and keeps FLOPs
    at ~capacity_factor x the active compute instead of E/topk x.
    """
    from repro.parallel.sharding import shard
    T, D = x.shape
    k = cfg.num_experts_per_tok
    E = cfg.num_experts
    C = max(8, int(capacity_factor * T * k / E))
    flat_e = topi.reshape(-1)                       # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = topv.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(se, length=E)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * k) - starts[se]            # slot within expert
    keep = pos < C
    pos = jnp.where(keep, pos, 0)
    wk = jnp.where(keep, sw, 0.0)
    buf = jnp.zeros((E, C, D), x.dtype).at[se, pos].set(
        jnp.where(keep[:, None], x[st], 0))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    if _gated(cfg):
        g = shard(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]),
                  None, None, "tp")
        h = _act(g, cfg) * u
    else:
        h = _act(shard(u, None, None, "tp"), cfg)
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"])  # (E, C, D)
    out = jnp.zeros_like(x).at[st].add(
        y[se, pos] * wk[:, None].astype(y.dtype))
    return out


def apply_moe(p, x, cfg: ModelConfig, impl: str = "dense"):
    """x: (B, S, D) -> (out, aux_loss).

    The MoE block runs with the sequence dim *gathered* (no seq sharding):
    merging a batch-sharded dim with a seq-sharded dim would force GSPMD
    into pathological resharding of the (T, E, F) expert tensors.  The
    surrounding block re-applies the sequence-parallel constraint.
    """
    from repro.parallel.sharding import shard
    x = shard(x, "batch", None, None)
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    combine, topi, topv, aux = _route(p, xt, cfg)
    if impl == "ragged":
        out = _expert_mlp_ragged(p, xt, topi, topv, cfg)
    elif cfg.num_experts_held < cfg.num_experts:
        raise ValueError(f"a share of the experts runs only with impl="
                         f"'ragged', not {impl!r}")
    elif impl == "capacity":
        out = _expert_mlp_capacity(p, xt, topi, topv, cfg)
    else:
        out = _expert_mlp_dense(p, xt, combine, cfg)
    if cfg.num_shared_experts:
        sp = p["shared"]
        u = xt @ sp["w_up"]
        h = _act(xt @ sp["w_gate"], cfg) * u if _gated(cfg) else _act(u, cfg)
        out = out + h @ sp["w_down"]
    return out.reshape(B, S, D), aux
