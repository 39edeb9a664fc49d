"""Composable decoder transformer covering all six assigned arch families.

* dense  — (GQA/MQA attention + gated MLP)           [qwen1.5, gemma, tinyllama, starcoder2]
* ssm    — attention-free Mamba2/SSD blocks          [mamba2-130m]
* moe    — attention + routed experts (+shared)      [granite-moe, qwen2-moe]
* hybrid — parallel attention + SSM heads per layer  [hymba]
* vlm    — self-attn blocks with interleaved gated
           cross-attention to stub patch embeddings  [llama-3.2-vision]
* audio  — decoder over stub codec-frame embeddings  [musicgen]

Layers are stacked and iterated with ``lax.scan`` so the lowered HLO is
O(1) in depth — 100-layer configs compile fast in the 512-device dry-run.
VLM interleaving is handled by scanning *superblocks* (1 cross-attn layer
+ (k-1) self-attn layers), keeping the scan body homogeneous.

Decode semantics (serve_step): ONE new token against a KV cache.
``decode_32k`` uses a full-length cache; ``long_500k`` uses a sliding-
window ring buffer (sub-quadratic variant) — slot = index % window, RoPE
at absolute positions, softmax is slot-order independent.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.config import ModelConfig
from repro.parallel.sharding import shard


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"ln1": L.init_norm(cfg)}
    if cfg.arch_type == "ssm":
        p["mamba"] = SSM.init_mamba(ks[0], cfg, dtype)
        return p
    p["attn"] = L.init_attention(ks[0], cfg, dtype)
    if cfg.arch_type == "hybrid":
        p["mamba"] = SSM.init_mamba(ks[1], cfg, dtype)
    p["ln2"] = L.init_norm(cfg)
    if cfg.is_moe:
        p["moe"] = MOE.init_moe(ks[2], cfg, dtype)
    else:
        p["mlp"] = L.init_mlp(ks[2], cfg, dtype)
    return p


# Layers of a ``layer_pattern`` (nemotron_h): one pre-norm and one mixer.
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def layer_kinds(cfg: ModelConfig):
    """The kind of each layer of ``cfg.layer_pattern``."""
    return [KINDS[c] for c in cfg.layer_pattern]


def _init_layer(key, cfg: ModelConfig, kind: str, dtype):
    p: Dict[str, Any] = {"ln1": L.init_norm(cfg)}
    if kind == "mamba":
        p["mamba"] = SSM.init_mamba(key, cfg, dtype)
    elif kind == "moe":
        p["moe"] = MOE.init_moe(key, cfg, dtype)
    else:
        p["attn"] = L.init_attention(key, cfg, dtype)
    return p


def _apply_layer(p, x, cfg: ModelConfig, kind: str, *, positions):
    """``x + mixer(norm(x))``; the MoE layer's routed part on the ragged
    path, over the experts the layer holds."""
    h = L.apply_norm(p["ln1"], x, cfg)
    if kind == "mamba":
        out, _ = SSM.apply_mamba(p["mamba"], h, cfg)
    elif kind == "moe":
        out, _ = MOE.apply_moe(p["moe"], h, cfg, impl="ragged")
    else:
        out, _ = L.apply_attention(p["attn"], h, cfg, positions=positions)
    return x + out


def _init_cross_block(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 2)
    return {
        "ln1": L.init_norm(cfg),
        "xattn": L.init_attention(ks[0], cfg, dtype),
        "gate_attn": jnp.zeros((), jnp.float32),
        "ln2": L.init_norm(cfg),
        "mlp": L.init_mlp(ks[1], cfg, dtype),
        "gate_mlp": jnp.zeros((), jnp.float32),
    }


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    if cfg.layer_pattern:
        raise NotImplementedError("a layer pattern runs on the staged "
                                  "runtime only (core/runtime/stages.py)")
    dtype = jnp.dtype(cfg.param_dtype)
    k_embed, k_blocks, k_cross, k_proj = jax.random.split(key, 4)
    params: Dict[str, Any] = {"embed": L.init_embed(k_embed, cfg, dtype),
                              "final_norm": L.init_norm(cfg)}
    Ln = cfg.num_layers
    if cfg.arch_type == "vlm" and cfg.cross_attn_every:
        k = cfg.cross_attn_every
        nb = Ln // k
        self_keys = jax.random.split(k_blocks, nb * (k - 1)).reshape(nb, k - 1, 2)
        cross_keys = jax.random.split(k_cross, nb)
        params["self_blocks"] = jax.vmap(jax.vmap(
            lambda kk: _init_block(kk, cfg, dtype)))(self_keys)
        params["cross_blocks"] = jax.vmap(
            lambda kk: _init_cross_block(kk, cfg, dtype))(cross_keys)
        params["vision_proj"] = {
            "w_proj": L.dense_init(k_proj, (cfg.vision_dim, cfg.d_model), dtype)}
    else:
        keys = jax.random.split(k_blocks, Ln)
        params["blocks"] = jax.vmap(lambda kk: _init_block(kk, cfg, dtype))(keys)
    return params


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16,
               kv_heads_override: Optional[int] = None) -> Dict[str, Any]:
    """Allocate the decode cache.  ``cache_len`` = min(seq_len, window).

    kv_heads_override > num_kv_heads pads the cache's head dim so it
    shards evenly over the model axis (launch/specs.pad_kv_heads)."""
    kvd = (kv_heads_override or cfg.num_kv_heads) * cfg.head_dim

    def attn_cache(lead):
        return {
            "k": jnp.zeros(lead + (batch, cache_len, kvd), dtype),
            "v": jnp.zeros(lead + (batch, cache_len, kvd), dtype),
        }

    def ssm_cache(lead):
        base = SSM.init_mamba_cache(cfg, batch, dtype)
        return jax.tree.map(lambda x: jnp.zeros(lead + x.shape, x.dtype), base)

    Ln = cfg.num_layers
    c: Dict[str, Any] = {}
    if cfg.arch_type == "ssm":
        c["ssm"] = ssm_cache((Ln,))
    elif cfg.arch_type == "hybrid":
        c["attn"] = attn_cache((Ln,))
        c["ssm"] = ssm_cache((Ln,))
    elif cfg.arch_type == "vlm" and cfg.cross_attn_every:
        k = cfg.cross_attn_every
        c["attn"] = attn_cache((Ln // k, k - 1))
    else:
        c["attn"] = attn_cache((Ln,))
    return c


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _apply_block(bp, x, cfg: ModelConfig, *, positions, window, cache,
                 write_index, kv_valid, moe_impl):
    """One decoder layer.  Returns (x, aux, new_cache)."""
    aux = jnp.float32(0.0)
    h = L.apply_norm(bp["ln1"], x, cfg)
    new_cache: Dict[str, Any] = {}
    if cfg.arch_type == "ssm":
        out, nc = SSM.apply_mamba(bp["mamba"], h, cfg,
                                  cache=cache.get("ssm") if cache else None)
        if cache is not None:
            new_cache["ssm"] = nc
        return x + out, aux, new_cache

    a_out, nc_a = L.apply_attention(
        bp["attn"], h, cfg, positions=positions, window=window,
        cache=cache.get("attn") if cache else None,
        write_index=write_index, kv_valid=kv_valid)

    if cfg.arch_type == "hybrid":
        s_out, nc_s = SSM.apply_mamba(bp["mamba"], h, cfg,
                                      cache=cache.get("ssm") if cache else None)
        if cache is not None:
            new_cache["attn"], new_cache["ssm"] = nc_a, nc_s
        x = x + 0.5 * (a_out + s_out)
    else:
        if cache is not None:
            new_cache["attn"] = nc_a
        x = x + a_out

    h2 = L.apply_norm(bp["ln2"], x, cfg)
    if cfg.is_moe:
        m_out, aux = MOE.apply_moe(bp["moe"], h2, cfg, impl=moe_impl)
    else:
        m_out = L.apply_mlp(bp["mlp"], h2, cfg)
    # Megatron-style sequence parallelism: the residual stream between
    # blocks is sharded along S over the 'model' axis (rules.seq); XLA
    # turns the row-parallel psum into reduce-scatter + all-gather pairs.
    return shard(x + m_out, "batch", "seq", None), aux, new_cache


def _apply_cross_block(bp, x, vision, cfg: ModelConfig):
    """Gated cross-attention layer (llama-3.2-vision style)."""
    h = L.apply_norm(bp["ln1"], x, cfg)
    out, _ = L.apply_attention(bp["xattn"], h, cfg, positions=None,
                               causal=False, kv_x=vision)
    x = x + jnp.tanh(bp["gate_attn"]).astype(x.dtype) * out
    h2 = L.apply_norm(bp["ln2"], x, cfg)
    x = x + jnp.tanh(bp["gate_mlp"]).astype(x.dtype) * L.apply_mlp(bp["mlp"], h2, cfg)
    return x


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward_hidden(params, cfg: ModelConfig, *, tokens=None, embeds=None,
                   vision=None, window=None, cache=None, abs_index=None,
                   write_index=None, moe_impl: str = "dense",
                   remat: Optional[bool] = None):
    """Run the decoder stack.  Returns (hidden, aux_loss, new_cache).

    abs_index:   absolute position of the first input token (decode).
    write_index: cache slot to write K/V at (ring slot for SWA decode).
    """
    if embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.param_dtype))
    else:
        x = L.embed_tokens(params["embed"], tokens)
    x = shard(x, "batch", "seq", None)
    B, S, _ = x.shape

    if abs_index is not None:
        positions = abs_index + jnp.arange(S)
        kv_valid = None
        if cache is not None and "attn" in cache:
            cache_len = cache["attn"]["k"].shape[-2]
            kv_valid = jnp.minimum(abs_index + S, cache_len)
        if write_index is None:
            write_index = abs_index
    else:
        positions = jnp.arange(S)
        kv_valid = None

    do_remat = cfg.remat if remat is None else remat
    block = functools.partial(_apply_block, cfg=cfg, positions=positions,
                              window=window, write_index=write_index,
                              kv_valid=kv_valid, moe_impl=moe_impl)

    aux0 = jnp.float32(0.0)
    if cfg.arch_type == "vlm" and cfg.cross_attn_every:
        vis = (vision.astype(x.dtype) @ params["vision_proj"]["w_proj"]
               if vision is not None else None)

        def inner(carry, layer_in):
            x2, aux2 = carry
            if cache is not None:
                sp, sc = layer_in
                x2, a, nc = block(sp, x2, cache={"attn": sc})
                nc = nc["attn"]
            else:
                sp = layer_in
                x2, a, nc = block(sp, x2, cache=None)
                nc = 0.0  # scan needs a pytree; dummy leaf
            return (x2, aux2 + a), nc

        def superblock(carry, layer_in):
            x1, aux1 = carry
            if cache is not None:
                cross_p, self_p, self_cache = layer_in
                inner_xs = (self_p, self_cache)
            else:
                cross_p, self_p = layer_in
                inner_xs = self_p
            if vis is not None:
                x1 = _apply_cross_block(cross_p, x1, vis, cfg)
            (x1, aux1), new_sc = jax.lax.scan(inner, (x1, aux1), inner_xs)
            return (x1, aux1), new_sc

        if do_remat:
            superblock = jax.checkpoint(superblock)
        if cache is not None:
            xs = (params["cross_blocks"], params["self_blocks"], cache["attn"])
            (x, aux), new_attn = jax.lax.scan(superblock, (x, aux0), xs)
            new_cache = {"attn": new_attn}
        else:
            xs = (params["cross_blocks"], params["self_blocks"])
            (x, aux), _ = jax.lax.scan(superblock, (x, aux0), xs)
            new_cache = None
    else:
        def layer(carry, layer_in):
            x2, aux2 = carry
            if cache is not None:
                bp, lc = layer_in
                x2, a, nc = block(bp, x2, cache=lc)
            else:
                bp = layer_in
                x2, a, nc = block(bp, x2, cache=None)
                nc = 0.0
            return (x2, aux2 + a), nc

        if do_remat:
            layer = jax.checkpoint(layer)
        xs = (params["blocks"], cache) if cache is not None else params["blocks"]
        (x, aux), new_cache = jax.lax.scan(layer, (x, aux0), xs)

    x = L.apply_norm(params["final_norm"], x, cfg)
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# Entry points: train loss / prefill / decode
# ---------------------------------------------------------------------------

def train_loss(params, batch, cfg: ModelConfig, *, moe_impl="dense"):
    """batch: dict(tokens (B,S) | embeds (B,S,D), labels (B,S), [vision])."""
    hidden, aux, _ = forward_hidden(
        params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
        vision=batch.get("vision"), moe_impl=moe_impl)
    loss = L.chunked_xent_loss(params["embed"], hidden, batch["labels"], cfg)
    return loss + cfg.router_aux_coef * aux


def prefill(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            vision=None, cache=None, moe_impl="dense"):
    """Fill the cache with a full prompt; returns (last_logits, cache).

    Assumes prompt length <= cache length (no ring wrap during prefill)."""
    hidden, _, new_cache = forward_hidden(
        params, cfg, tokens=tokens, embeds=embeds, vision=vision,
        cache=cache, abs_index=jnp.int32(0), write_index=jnp.int32(0),
        moe_impl=moe_impl, remat=False)
    logits = L.lm_logits(params["embed"], hidden[:, -1:], cfg)
    return logits[:, 0], new_cache


def decode_step(params, cfg: ModelConfig, *, tokens=None, embeds=None,
                vision=None, cache, index, window=None, moe_impl="dense"):
    """One decode step at absolute position ``index`` (scalar int32)."""
    if "attn" in cache:
        cache_len = cache["attn"]["k"].shape[-2]
        write_index = index % cache_len if window is not None else index
    else:
        write_index = index
    hidden, _, new_cache = forward_hidden(
        params, cfg, tokens=tokens, embeds=embeds, vision=vision,
        cache=cache, abs_index=index, write_index=write_index,
        moe_impl=moe_impl, remat=False)
    logits = L.lm_logits(params["embed"], hidden[:, -1:], cfg)
    return logits[:, 0], new_cache
