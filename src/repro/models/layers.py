"""Core neural layers: norms, RoPE, MLP, chunked attention, embeddings.

Everything is functional: ``init_*`` builds a param dict, the apply
functions are pure.  Attention uses an online-softmax scan over KV blocks,
so 32k-context prefill never materialises an S x S score matrix; on a
TPU, training self-attention takes the fused flash kernel of
``repro.kernels.ops`` instead (``fused_attention_applies``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# Initialisers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: Optional[int] = None):
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p, x, cfg: ModelConfig):
    eps = cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (..., S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (dense)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, dtype):
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], (D, F), dtype),
            "w_up": dense_init(ks[1], (D, F), dtype),
            "w_down": dense_init(ks[2], (F, D), dtype),
        }
    return {
        "w_up": dense_init(ks[0], (D, F), dtype),
        "w_down": dense_init(ks[1], (F, D), dtype),
    }


def apply_mlp(p, x, cfg: ModelConfig):
    from repro.parallel.sharding import shard
    if cfg.mlp_type in ("swiglu", "geglu"):
        g = shard(x @ p["w_gate"], "batch", None, "tp")
        act = jax.nn.silu(g) if cfg.mlp_type == "swiglu" else jax.nn.gelu(g)
        h = act * (x @ p["w_up"])
    else:
        h = shard(jax.nn.gelu(x @ p["w_up"]), "batch", None, "tp")
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype, kv_in_dim: Optional[int] = None):
    """kv_in_dim overrides the K/V input width (cross-attention)."""
    D = cfg.d_model
    kv_in = kv_in_dim or D
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, cfg.q_dim), dtype),
        "wk": dense_init(ks[1], (kv_in, cfg.kv_dim), dtype),
        "wv": dense_init(ks[2], (kv_in, cfg.kv_dim), dtype),
        "wo": dense_init(ks[3], (cfg.q_dim, D), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dtype)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dtype)
    return p


def _online_attention(q, k, v, q_offset, causal: bool, window: Optional[int],
                      kv_len_valid=None, q_block: int = 512):
    """Flash-style attention: scan over query blocks, full K/V per block.

    q: (B, Sq, H, hd); k/v: (B, Sk, KH, hd).  GQA via head repeat.
    q_offset: absolute position of q[0] (int or traced scalar).
    kv_len_valid: optional scalar — number of valid KV entries (cache decode).
    Memory per block: B*H*q_block*Sk — bounded, never S^2.

    Differentiated, it keeps each block's scores, softmax and mask as
    residuals: S x S per head once Sq <= q_block.  So on a TPU, causal
    self-attention in training takes the fused kernel instead
    (``fused_attention_applies``, checked in Python at trace time, since
    a ``lax.platform_dependent`` branch would keep these residuals too);
    this path serves the CPU, caches, windows, cross-attention and
    shapes the kernel does not tile.
    """
    B, Sq, H, hd = q.shape
    _, Sk, KH, _ = k.shape
    rep = H // KH
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = hd ** -0.5
    kv_pos = jnp.arange(Sk)

    def block_attn(q_blk, q_pos):
        # q_blk: (B, qb, H, hd); q_pos: (qb,)
        # No explicit input convert: bf16 x bf16 -> f32 accumulation via
        # preferred_element_type (native on the MXU; an explicit astype
        # would get loop-hoisted by XLA into a full-cache f32 copy).
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((q_pos.shape[0], Sk), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        if kv_len_valid is not None:
            mask &= kv_pos[None, :] < kv_len_valid
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    if Sq <= q_block:
        return block_attn(q, q_offset + jnp.arange(Sq))

    n_blocks = Sq // q_block
    assert Sq % q_block == 0, f"Sq={Sq} not divisible by q_block={q_block}"
    qs = q.reshape(B, n_blocks, q_block, H, hd).transpose(1, 0, 2, 3, 4)

    def body(_, qb_i):
        qb, i = qb_i
        pos = q_offset + i * q_block + jnp.arange(q_block)
        return None, block_attn(qb, pos)

    _, outs = jax.lax.scan(body, None, (qs, jnp.arange(n_blocks)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


def _decode_attention(q, ck, cv, kv_valid, KH, hd, block: int = 2048):
    """Single-token attention against a long KV cache, scanned in chunks.

    q: (B, 1, H, hd); ck/cv: (B, C, KH*hd) flattened cache.  Online
    softmax over KV chunks keeps the working set to one (B, block, KH, hd)
    slice — and, critically, the per-chunk dynamic-slice depends on the
    loop index, so XLA cannot loop-hoist a bf16->f32 convert of the whole
    cache (a CPU-backend artifact that doubles analysed memory; on TPU the
    chunked form is simply the right VMEM-bounded pattern).
    """
    B, _, H, _ = q.shape
    C = ck.shape[1]
    block = min(block, C)
    n = C // block
    rem = C - n * block
    assert rem == 0, (C, block)
    rep = H // KH
    scale = hd ** -0.5
    qf = (q[:, 0] * scale).astype(q.dtype)                 # (B, H, hd)

    def chunk(carry, i):
        m_prev, l_prev, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(ck, i * block, block, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(cv, i * block, block, axis=1)
        kc = kc.reshape(B, block, KH, hd)
        vc = vc.reshape(B, block, KH, hd)
        if rep > 1:
            kc = jnp.repeat(kc, rep, axis=2)
            vc = jnp.repeat(vc, rep, axis=2)
        sc = jnp.einsum("bhd,bkhd->bhk", qf, kc,
                        preferred_element_type=jnp.float32)   # (B, H, block)
        pos = i * block + jnp.arange(block)
        mask = pos[None, None, :] < kv_valid
        sc = jnp.where(mask, sc, -1e30)
        m_cur = jnp.max(sc, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        pch = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(pch, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhk,bkhd->bhd", pch.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    init = (jnp.full((B, H), -1e30, jnp.float32),
            jnp.zeros((B, H), jnp.float32),
            jnp.zeros((B, H, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(chunk, init, jnp.arange(n))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out[:, None].astype(q.dtype)                    # (B, 1, H, hd)


def _constrain_attention_operands(q, k, v, H, KH):
    """Pick the TP layout for train/prefill attention.

    * H %% tp == 0: shard Q by heads evenly; K/V replicated when their
      head count does not also divide (GSPMD would otherwise shard K's
      head_dim and psum every score tensor).
    * H %% tp != 0 (e.g. 36, 25, 20 heads on a 16-way axis): shard Q heads
      *unevenly* (GSPMD pads) and replicate K/V — the padding wastes
      ceil/floor FLOPs but removes the partial-sum all-reduces entirely.
    """
    from repro.parallel.sharding import shard, shard_heads, tp_size
    tp = tp_size()
    if tp <= 1:
        return q, k, v
    if H % tp == 0:
        # even head counts: GSPMD already finds a psum-free layout
        # (measured: constraining K/V replicated here ADDS ~0.8e12 bytes
        # of k/v gathers on llama-90b — leave it alone).
        return q, k, v
    if KH > tp // 2:
        # uneven heads but near-MHA K/V (musicgen 24/24, qwen1.5 20/20):
        # replicating K/V would all-gather d_model-sized tensors per layer
        # (measured 5-10x collective regression) — GSPMD's default layout
        # is the better trade.
        return q, k, v
    # uneven Q heads + genuinely small GQA K/V (starcoder2 36/4, hymba
    # 25/5): pad-shard Q heads, replicate the small K/V — removes the
    # partial-sum score all-reduces (measured 9.2x on starcoder2 prefill).
    q = shard_heads(q, 2)
    k = shard(k, "batch", None, None, None)
    v = shard(v, "batch", None, None, None)
    return q, k, v


def fused_attention_applies(seq_len: int, head_dim: int) -> bool:
    """Whether causal self-attention without a cache or window over
    ``seq_len`` tokens takes the fused kernel (``kops.fused_attention``).

    Decided in Python while the program is traced, from what the trace
    can observe: the program is lowered for a TPU, no sharding mesh is
    active (GSPMD does not partition a Pallas kernel), and the kernel
    takes the shape.  A ``lax.platform_dependent`` or ``lax.cond`` would
    not do: under ``jax.vjp`` their partial evaluation keeps the
    residuals of every branch, the XLA branch's S x S arrays among them.
    """
    from repro.kernels import ops as kops
    from repro.parallel.sharding import active_mesh
    return (kops.on_tpu() and active_mesh() is None
            and kops.fused_attention_blocks(seq_len, head_dim) is not None)


def apply_attention(p, x, cfg: ModelConfig, *, positions, causal=True,
                    window=None, kv_x=None, cache=None, write_index=None,
                    kv_valid=None):
    """Self- or cross-attention with optional KV cache.

    x: (B, S, D).  kv_x: cross-attention memory (B, M, Dv) or None.
    cache: dict(k=(B, C, kv_dim), v=(B, C, kv_dim)) — kv dims kept
    *flattened* so the 'model'-axis sharding always divides (kvH*hd % 16
    == 0 for every assigned arch even when kvH itself is not).

    Decode semantics: K/V of this step are written at slot ``write_index``
    (``index % window`` for a ring buffer, else ``index``); ``kv_valid``
    is the number of live slots; attention attends to all live slots —
    every live slot is in the past, so no causal mask is needed for the
    single-token query.  RoPE uses absolute ``positions`` so ring slots
    are order-independent under softmax.

    Returns (out, new_cache).
    """
    from repro.parallel.sharding import shard

    B, S, D = x.shape
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    q = shard(x @ p["wq"], "batch", None, "tp")
    src = kv_x if kv_x is not None else x
    k = shard(src @ p["wk"], "batch", None, "tp")
    v = shard(src @ p["wv"], "batch", None, "tp")
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]

    q = q.reshape(B, S, H, hd)
    if kv_x is None and cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k.reshape(B, -1, KH, hd), positions, cfg.rope_theta)
        k = k.reshape(B, -1, cfg.kv_dim)

    new_cache = None
    if cache is not None:
        # head-padded cache layout (hillclimb D): zero-pad K/V (and Q by
        # whole head groups) so each device owns whole heads; the padded
        # head outputs are sliced away before wo.
        cache_kvd = cache["k"].shape[-1]
        pad_kv = cache_kvd - cfg.kv_dim
        KH_eff, H_eff = KH, H
        if pad_kv > 0:
            rep = H // KH
            KH_eff = cache_kvd // hd
            H_eff = KH_eff * rep
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv)))
            q = jnp.pad(q, ((0, 0), (0, 0), (0, H_eff - H), (0, 0)))
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, write_index, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, write_index, 0))
        # no shard() here: the cache layout is pinned by in_shardings and a
        # constraint would materialise an extra full-cache copy.
        new_cache = {"k": ck, "v": cv}
        C = ck.shape[1]
        if S == 1:
            # single new token: every live slot is in the past -> no mask
            out = _decode_attention(q, ck, cv, kv_valid, KH_eff, hd)
            if pad_kv > 0:
                out = out[:, :, :H, :]
        else:
            # multi-token prefill: the cache was empty, so attention only
            # covers this step's own K/V — use the pre-write tensors, NOT
            # the tp-sharded cache (reading the head-dim-sharded cache
            # back would psum every score tensor).
            k4 = k.reshape(B, S, KH_eff, hd)
            v4 = v.reshape(B, S, KH_eff, hd)
            qh, k4, v4 = _constrain_attention_operands(q, k4, v4, H_eff,
                                                       KH_eff)
            out = _online_attention(qh, k4, v4, q_offset=positions[0],
                                    causal=True, window=None)
            if pad_kv > 0:
                out = out[:, :, :H, :]
    else:
        k = k.reshape(B, -1, KH, hd)
        v = v.reshape(B, -1, KH, hd)
        q, k, v = _constrain_attention_operands(q, k, v, H, KH)
        if (kv_x is None and causal and window is None
                and fused_attention_applies(S, hd)):
            from repro.kernels import ops as kops
            out = kops.fused_attention(q, k, v)
        else:
            out = _online_attention(q, k, v, q_offset=0,
                                    causal=causal and kv_x is None,
                                    window=window)

    out = out.reshape(B, S, cfg.q_dim)
    return out @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# Embedding + LM head
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    p = {"table": dense_init(k1, (cfg.vocab_size, cfg.d_model), dtype, scale=0.02)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(k2, (cfg.d_model, cfg.vocab_size), dtype)
    return p


def embed_tokens(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def lm_logits(p, x, cfg: ModelConfig):
    w = p["table"].T if cfg.tie_embeddings else p["lm_head"]
    return x @ w


def chunked_xent_loss(embed_p, x, labels, cfg: ModelConfig, chunk: int = 512):
    """Cross-entropy without materialising (B, S, V) for 256k vocabs.

    Scans over sequence chunks; logits exist only per-chunk.
    x: (B, S, D), labels: (B, S) -> scalar mean loss.
    """
    B, S, D = x.shape
    w = embed_p["table"].T if cfg.tie_embeddings else embed_p["lm_head"]
    n = S // chunk if S % chunk == 0 else 1
    if n == 1:
        chunk = S
    xs = x.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def body(acc, xl):
        xc, lc = xl
        logits = (xc @ w).astype(jnp.float32)              # (B, chunk, V)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, ls))
    return total / (B * S)
