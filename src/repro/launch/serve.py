"""Serving driver: batched prefill + decode with a KV cache.

Complements launch/train.py — the decode_32k / long_500k dry-run shapes
lower exactly this step.  On CPU it serves a reduced config for real:

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --batch 4 --prompt-len 32 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m \
      --reduced --long    # sliding-window/SSM-state long-context mode
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--long", action="store_true",
                    help="sliding-window ring-buffer mode (long_500k path)")
    ap.add_argument("--window", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.models.transformer import decode_step, init_cache, prefill

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_layers=args.layers, d_model=args.d_model)
    window = args.window if args.long else None
    cache_len = window if args.long else args.prompt_len + args.gen

    # independent keys per purpose (params / prompt / aux / sampling) —
    # the shared split with the flow-routed serving runtime, so its
    # zero-churn decode is bit-comparable to this driver on one seed
    from repro.core.runtime.serving import serving_inputs

    B = args.batch
    params, prompt, vision, embeds, k_sample = serving_inputs(
        cfg, seed=args.seed, batch=B, prompt_len=args.prompt_len)

    cache = init_cache(cfg, B, cache_len, dtype=jnp.float32)
    t0 = time.time()
    if cfg.audio_frontend:
        logits, cache = prefill(params, cfg, embeds=embeds, cache=cache)
    else:
        logits, cache = prefill(params, cfg, tokens=prompt, vision=vision,
                                cache=cache)
    print(f"prefill: bs={B} len={args.prompt_len} "
          f"({time.time()-t0:.2f}s incl. compile)")

    step = jax.jit(lambda p, tok, c, i: decode_step(
        p, cfg, tokens=tok, vision=vision, cache=c, index=i, window=window))

    def sample(logits, k):
        if args.temperature <= 0:
            return jnp.argmax(logits, -1)[:, None]
        return jax.random.categorical(
            k, logits / args.temperature)[:, None]

    k_sample, k0 = jax.random.split(k_sample)
    tok = sample(logits, k0)
    out = [tok]
    t0 = time.time()
    for i in range(args.gen):
        k_sample, sk = jax.random.split(k_sample)
        logits, cache = step(params, tok, cache,
                             jnp.int32(args.prompt_len + i))
        tok = sample(logits, sk)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"decoded {args.gen} steps x {B} seqs in {dt:.2f}s "
          f"({B*args.gen/dt:.1f} tok/s{' , ring-buffer' if args.long else ''})")
    print("sample:", gen[0, :16].tolist())


if __name__ == "__main__":
    main()
