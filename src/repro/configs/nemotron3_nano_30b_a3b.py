"""NVIDIA-Nemotron-3-Nano-30B-A3B, one chip's share of its first period.

[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, config.json]  52 layers of
one mixer each, ``hybrid_override_pattern`` MEMEM*EMEMEM*E...: 23 Mamba-2
(64 heads of 64, state 128, 8 B/C groups, conv 4), 23 MoE (128 relu^2
experts of 1856, top-6 sigmoid router, routed scaling 2.5, one shared
expert of 3712) and 6 GQA attention layers (32 query / 2 KV heads of 128,
no rotary); d_model 2688, RMSNorm eps 1e-5, untied head.

Cut, widths untouched: the first 7 layers MEMEM*E (one whole period in
its published ratio; the rest would lie on further pipeline stages),
experts 0-7 of 128 held here (16 chips share each MoE layer; the router
still routes over all 128), vocabulary 16384 of 131072 (8 chips share
it).  Runs on the staged runtime (``core/runtime/stages.py``).
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="nemotron3-nano-30b-a3b",
    arch_type="hybrid",
    layer_pattern="MEMEM*E",
    num_layers=7,
    d_model=2688,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    d_ff=1856,
    vocab_size=16384,
    rope=False,
    mlp_type="relu2",
    norm_type="rmsnorm",
    norm_eps=1e-5,
    ssm_state=128,
    ssm_heads=64,
    ssm_head_dim=64,
    ssm_conv=4,
    ssm_expand=0,
    ssm_groups=8,
    num_experts=128,
    num_experts_per_tok=6,
    num_shared_experts=1,
    shared_d_ff=3712,
    router="sigmoid",
    routed_scaling=2.5,
    experts_held=8,
    tie_embeddings=False,
    param_dtype="bfloat16",
    source="hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16",
)
