"""Training attention through the fused flash kernel.

``apply_attention`` sends causal self-attention without a cache or
window to ``ops.fused_attention`` where the program is lowered for a TPU
and the kernel takes the shape; everything else keeps
``_online_attention``.  On the CPU the stage programs are the XLA ones,
bit for bit.  The kernel's numbers are checked here in Pallas's TPU
interpret mode at small shapes, and on a chip (skipped without one) at
the shapes of the GPT and Nemotron cells.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config
from repro.core.runtime import cache
from repro.core.runtime.stages import (StageCompute, stage_forward,
                                       stage_fused_attention)
from repro.kernels import ops
from repro.models import layers as L
from repro.parallel.sharding import ShardingRules, use_rules


def _cfg(**kw):
    cfg = get_config("gwtf-gpt-300m").reduced(num_layers=4, d_model=128)
    return dataclasses.replace(cfg, vocab_size=256, **kw)


def _calls(monkeypatch, on_tpu=True):
    """Record each call of the fused kernel (answered by the XLA path,
    so the rest of the program runs), with the backend choice patched."""
    calls = []

    def fused(q, k, v):
        calls.append(q.shape)
        return L._online_attention(q, k, v, 0, True, None)

    monkeypatch.setattr(ops, "on_tpu", lambda: on_tpu)
    monkeypatch.setattr(ops, "fused_attention", fused)
    return calls


def _attention_inputs(cfg, S, seed=0):
    p = L.init_attention(jax.random.PRNGKey(seed), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, S, cfg.d_model))
    return p, x


# ---------------------------------------------------------------------------
# The selection rule
# ---------------------------------------------------------------------------

def test_causal_self_attention_takes_the_kernel_on_a_tpu(monkeypatch):
    calls = _calls(monkeypatch)
    cfg = _cfg()
    p, x = _attention_inputs(cfg, 256)
    out, new_cache = L.apply_attention(p, x, cfg, positions=jnp.arange(256))
    assert calls == [(2, 256, cfg.num_heads, cfg.head_dim)]
    assert out.shape == x.shape and new_cache is None


@pytest.mark.parametrize("case", ["cpu", "cache", "cross", "window",
                                  "ragged_len", "mesh", "not_causal"])
def test_other_attention_keeps_the_xla_path(monkeypatch, case):
    """Each case that keeps ``_online_attention``: its output is the
    unpatched program's, bit for bit, and the kernel is never called."""
    cfg = _cfg()
    S = 200 if case == "ragged_len" else 256
    p, x = _attention_inputs(cfg, S)
    kw = dict(positions=jnp.arange(S))
    if case == "cache":
        kw.update(cache={"k": jnp.zeros((2, S, cfg.kv_dim)),
                         "v": jnp.zeros((2, S, cfg.kv_dim))},
                  write_index=0, kv_valid=S)
    elif case == "cross":
        kw.update(kv_x=jax.random.normal(jax.random.PRNGKey(5),
                                         (2, 64, cfg.d_model)))
    elif case == "window":
        kw.update(window=64)
    elif case == "not_causal":
        kw.update(causal=False)
    want, _ = L.apply_attention(p, x, cfg, **kw)

    calls = _calls(monkeypatch, on_tpu=case != "cpu")
    if case == "mesh":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                                 ("data", "model"))
        with use_rules(ShardingRules(), mesh):
            got, _ = L.apply_attention(p, x, cfg, **kw)
    else:
        got, _ = L.apply_attention(p, x, cfg, **kw)
    assert calls == []
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("seq_len,head_dim,takes", [
    (512, 64, True), (512, 128, True), (384, 64, True), (128, 32, True),
    (512, 256, True), (1024, 64, True), (256, 128, True), (200, 64, False),
    (64, 64, False), (512, 192, False), (320, 64, False)])
def test_kernel_tiles_follow_the_shape(seq_len, head_dim, takes):
    bs = ops.fused_attention_blocks(seq_len, head_dim)
    assert (bs is not None) == takes
    if takes:
        assert bs.has_backward_blocks
        for b in (bs.block_q, bs.block_k_major, bs.block_q_dkv,
                  bs.block_k_major_dkv, bs.block_q_dq, bs.block_k_major_dq):
            assert seq_len % b == 0 and b % 128 == 0


def test_stage_counts_fused_attention_layers(monkeypatch):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    gpt = get_config("gwtf-gpt-300m")
    nem = get_config("nemotron3-nano-30b-a3b")
    mamba = get_config("mamba2-130m")
    assert [stage_fused_attention(gpt, s, 4, 512) for s in range(4)] == [4] * 4
    assert [stage_fused_attention(nem, s, 4, 512)
            for s in range(4)] == [0, 0, 1, 0]
    assert [stage_fused_attention(mamba, s, 4, 512) for s in range(4)] == [0] * 4
    assert stage_fused_attention(gpt, 0, 4, 200) == 0
    monkeypatch.setattr(ops, "on_tpu", lambda: False)
    assert stage_fused_attention(gpt, 0, 4, 512) == 0


def test_snapshot_reads_no_fused_attention_on_the_cpu():
    cfg = _cfg()
    stage_p, _ = cache.initial_params(cfg, 2, 0)
    sc = StageCompute(cfg, 2)
    assert sc.snapshot()["fused_attention"] == [0, 0]
    x = jnp.zeros((2, 128, cfg.d_model), jnp.dtype(cfg.param_dtype))
    out, res = sc.forward_fused(0, stage_p[0], x)
    sc.backward_from_residuals(0, res, jnp.ones_like(out))
    sc.forward(1, stage_p[1], out)
    assert sc.snapshot()["fused_attention"] == [0, 0]


def test_snapshot_counts_the_stages_dispatched(monkeypatch):
    """With the backend choice patched to a TPU, each dispatched GPT
    stage reports its two attention layers, and the stage program calls
    the kernel once per traced layer body."""
    calls = _calls(monkeypatch)
    cfg = _cfg()
    stage_p, _ = cache.initial_params(cfg, 2, 0)
    sc = StageCompute(cfg, 2)
    x = jnp.zeros((2, 128, cfg.d_model), jnp.dtype(cfg.param_dtype))
    jax.eval_shape(lambda p, x: stage_forward(p, x, cfg), stage_p[0], x)
    assert len(calls) == 1           # the scan traces its body once
    sc._seq_len[0] = x.shape[1]      # as a dispatch of stage 0 records
    assert sc.snapshot()["fused_attention"] == [2, 0]


# ---------------------------------------------------------------------------
# The kernel's numbers
# ---------------------------------------------------------------------------

def _qkvg(B, S, H, KH, hd, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KH, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KH, hd), dtype)
    g = jax.random.normal(ks[3], (B, S, H, hd), dtype)
    return q, k, v, g


_ONLINE = functools.partial(L._online_attention, q_offset=0, causal=True,
                            window=None)


def _vjp(f, q, k, v, g):
    out, vjp = jax.vjp(f, q, k, v)
    return (out,) + vjp(g)


def _gaps(q, k, v, g, f, precision=None):
    """Largest gap of ``f``'s output, dq, dk and dv, traced at matmul
    ``precision``, from ``_online_attention``'s at full float32
    precision, each relative to the reference's largest entry."""
    with jax.default_matmul_precision(precision):
        got = _vjp(f, q, k, v, g)
    with jax.default_matmul_precision("highest"):
        want = _vjp(_ONLINE, q, k, v, g)
    return [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip(got, want)]


@pytest.mark.parametrize("S,H,KH,hd", [(256, 4, 4, 64), (384, 4, 2, 128),
                                      (256, 8, 2, 64)])
def test_fused_attention_matches_online_in_interpret_mode(S, H, KH, hd):
    """The wrapper's layout, GQA repeat and scale, through the shipped
    kernel's forward and backward run by Pallas's TPU interpreter: one
    tile of 256 and three of 128 (the online softmax across tiles), and
    four query heads to a KV head."""
    q, k, v, g = _qkvg(1, S, H, KH, hd)
    with pltpu.force_tpu_interpret_mode():
        gaps = _gaps(q, k, v, g, ops.fused_attention)
    assert max(gaps) < 1e-5, gaps


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="needs a TPU")
@pytest.mark.parametrize("H,KH,hd", [(16, 16, 64), (32, 2, 128)],
                         ids=["gpt", "nemotron"])
def test_fused_attention_matches_online_on_the_chip(H, KH, hd):
    """GPT (16 heads of 64) and Nemotron (32 query, 2 KV heads of 128)
    at S = 512, causal, float32: output, dq, dk, dv.  At full float32
    matmul precision the kernel reads what the XLA path reads; at the
    default precision (one bfloat16 pass on the MXU) it loses no more
    than the XLA path does at that precision."""
    q, k, v, g = _qkvg(4, 512, H, KH, hd)
    exact = _gaps(q, k, v, g, ops.fused_attention, "highest")
    assert max(exact) < 1e-4, exact
    fused = _gaps(q, k, v, g, ops.fused_attention)
    xla = _gaps(q, k, v, g, _ONLINE)
    assert all(f <= 2 * x for f, x in zip(fused, xla)), (fused, xla)
