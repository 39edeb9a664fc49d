"""Model-layer unit + property tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.moe import apply_moe, init_moe


def mini_cfg(**kw):
    base = dict(name="t", arch_type="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                vocab_size=97, param_dtype="float32", remat=False)
    base.update(kw)
    return ModelConfig(**base)


class TestRoPE:
    def test_norm_preserved(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 16))
        y = L.apply_rope(x, jnp.arange(8), 10000.0)
        np.testing.assert_allclose(np.linalg.norm(np.asarray(x)),
                                   np.linalg.norm(np.asarray(y)), rtol=1e-5)

    def test_relative_property(self):
        """<rope(q,m), rope(k,n)> depends only on m-n."""
        key = jax.random.PRNGKey(1)
        q = jax.random.normal(key, (1, 1, 1, 16))
        k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 16))

        def dot_at(m, n):
            qm = L.apply_rope(q, jnp.array([m]), 10000.0)
            kn = L.apply_rope(k, jnp.array([n]), 10000.0)
            return float(jnp.sum(qm * kn))

        assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4
        assert abs(dot_at(0, 0) - dot_at(7, 7)) < 1e-4


class TestChunkedLoss:
    def test_matches_naive(self):
        cfg = mini_cfg(vocab_size=64)
        key = jax.random.PRNGKey(0)
        p = L.init_embed(key, cfg, jnp.float32)
        x = jax.random.normal(key, (2, 16, cfg.d_model))
        labels = jax.random.randint(key, (2, 16), 0, 64)
        loss = L.chunked_xent_loss(p, x, labels, cfg, chunk=4)
        logits = L.lm_logits(p, x, cfg)
        logp = jax.nn.log_softmax(logits, -1)
        naive = -jnp.mean(jnp.take_along_axis(logp, labels[..., None],
                                              -1))
        np.testing.assert_allclose(float(loss), float(naive), rtol=1e-5)

    def test_chunk_sizes_agree(self):
        cfg = mini_cfg(vocab_size=50)
        key = jax.random.PRNGKey(3)
        p = L.init_embed(key, cfg, jnp.float32)
        x = jax.random.normal(key, (1, 24, cfg.d_model))
        labels = jax.random.randint(key, (1, 24), 0, 50)
        ref = L.chunked_xent_loss(p, x, labels, cfg, chunk=24)
        for c in (4, 6, 12):
            got = L.chunked_xent_loss(p, x, labels, cfg, chunk=c)
            np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)


class TestOnlineAttention:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 1000), window=st.sampled_from([None, 4, 8]))
    def test_property_matches_naive(self, seed, window):
        key = jax.random.PRNGKey(seed)
        B, S, H, hd = 1, 16, 2, 8
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, H, hd))
        v = jax.random.normal(ks[2], (B, S, H, hd))
        out = L._online_attention(q, k, v, q_offset=0, causal=True,
                                  window=window, q_block=4)
        # naive
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(S)[None, :]
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask[None, None], s, -1e30)
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


class TestMoEImpls:
    @pytest.mark.parametrize("arch", ["granite-moe-3b-a800m",
                                      "qwen2-moe-a2.7b"])
    def test_capacity_matches_dense(self, arch):
        cfg = get_config(arch).reduced()
        key = jax.random.PRNGKey(0)
        p = init_moe(key, cfg, jnp.float32)
        x = jax.random.normal(key, (2, 32, cfg.d_model))
        out_d, aux_d = apply_moe(p, x, cfg, impl="dense")
        out_c, aux_c = apply_moe(p, x, cfg, impl="capacity")
        np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_c),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(float(aux_d), float(aux_c), rtol=1e-5)

    def test_ragged_matches_dense(self):
        cfg = get_config("granite-moe-3b-a800m").reduced()
        key = jax.random.PRNGKey(1)
        p = init_moe(key, cfg, jnp.float32)
        x = jax.random.normal(key, (1, 16, cfg.d_model))
        out_d, _ = apply_moe(p, x, cfg, impl="dense")
        out_r, _ = apply_moe(p, x, cfg, impl="ragged")
        np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_r),
                                   rtol=1e-3, atol=1e-3)

    def test_router_aux_loss_balanced_is_low(self):
        """A perfectly uniform router gives aux ~ E * E*(1/E)*(1/E) = 1
        (x k for top-k overcounting of frac)."""
        cfg = get_config("granite-moe-3b-a800m").reduced()
        key = jax.random.PRNGKey(2)
        p = init_moe(key, cfg, jnp.float32)
        x = jax.random.normal(key, (4, 64, cfg.d_model)) * 1e-4  # ~uniform
        _, aux = apply_moe(p, x, cfg)
        assert float(aux) < cfg.num_experts_per_tok * 1.5


class TestConfigReduction:
    @pytest.mark.parametrize("arch", ["gemma-7b", "hymba-1.5b",
                                      "qwen2-moe-a2.7b",
                                      "llama-3.2-vision-90b"])
    def test_reduced_invariants(self, arch):
        cfg = get_config(arch)
        r = cfg.reduced()
        assert r.arch_type == cfg.arch_type
        assert r.num_layers <= 4 and r.d_model <= 512
        assert r.num_experts <= 4
        if r.num_heads:
            assert r.num_heads % max(r.num_kv_heads, 1) == 0
            assert r.num_heads * r.head_dim <= 8 * r.d_model
