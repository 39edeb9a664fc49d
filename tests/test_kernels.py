"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels run in interpret mode on CPU (the TPU lowering is the target;
interpret executes the same kernel body in Python).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ref import attention_reference, ssd_scan_reference
from repro.kernels.ssd_scan import ssd_scan_bhsp

TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S", [128, 256, 512])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(S, D, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(S + D), 3)
    BH = 2
    q = jax.random.normal(k1, (BH, S, D), dtype)
    k = jax.random.normal(k2, (BH, S, D), dtype)
    v = jax.random.normal(k3, (BH, S, D), dtype)
    out = flash_attention_bhsd(q, k, v, causal=True, block_q=64, block_k=64,
                               interpret=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_attention_window(window):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(window), 3)
    BH, S, D = 2, 256, 64
    q = jax.random.normal(k1, (BH, S, D))
    k = jax.random.normal(k2, (BH, S, D))
    v = jax.random.normal(k3, (BH, S, D))
    out = flash_attention_bhsd(q, k, v, causal=True, window=window,
                               block_q=64, block_k=64, interpret=True)
    ref = attention_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("blocks", [(64, 128), (128, 64), (256, 256)])
def test_flash_attention_block_shapes(blocks):
    bq, bk = blocks
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    BH, S, D = 1, 256, 64
    q = jax.random.normal(k1, (BH, S, D))
    k = jax.random.normal(k2, (BH, S, D))
    v = jax.random.normal(k3, (BH, S, D))
    out = flash_attention_bhsd(q, k, v, block_q=bq, block_k=bk,
                               interpret=True)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_gqa_wrapper():
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    B, S, H, KH, D = 2, 128, 8, 2, 64
    q = jax.random.normal(k1, (B, S, H, D))
    k = jax.random.normal(k2, (B, S, KH, D))
    v = jax.random.normal(k3, (B, S, KH, D))
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64,
                              interpret=True)
    kr = jnp.repeat(k, H // KH, axis=2)
    vr = jnp.repeat(v, H // KH, axis=2)
    ref = attention_reference(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        kr.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        vr.transpose(0, 2, 1, 3).reshape(B * H, S, D))
    ref = ref.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_kernels_never_infer_interpret_mode():
    """Interpret mode is never inferred: off the TPU, a kernel call
    that does not ask for it raises instead of silently interpreting."""
    x = jnp.ones((1, 128, 2, 64))
    if jax.default_backend() == "tpu":
        assert ops.flash_attention(x, x, x).shape == x.shape
        return
    with pytest.raises(ValueError, match="interpret"):
        ops.flash_attention(x, x, x, block_q=64, block_k=64)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,chunk", [(128, 32), (256, 64), (256, 128)])
@pytest.mark.parametrize("N", [16, 64])
def test_ssd_scan_shapes(S, chunk, N):
    key = jax.random.PRNGKey(S + N)
    ks = jax.random.split(key, 5)
    B, H, P = 2, 3, 32
    x = jax.random.normal(ks[0], (B, H, S, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, S)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y, hf = ssd_scan_bhsp(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, hfr = ssd_scan_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hfr),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_dtypes(dtype):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 5)
    B, H, S, P, N = 1, 2, 128, 16, 16
    x = jax.random.normal(ks[0], (B, H, S, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, S))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, N), dtype)
    y, hf = ssd_scan_bhsp(x, dt, A, Bm, Cm, chunk=64, interpret=True)
    yr, hfr = ssd_scan_reference(x, dt, A, Bm, Cm)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol["rtol"] * 10, atol=tol["atol"] * 10)


def test_ssd_model_chunked_matches_sequential():
    """The model's chunked SSD (jnp twin of the kernel) matches the
    sequential recurrence for several chunk sizes."""
    from repro.models.ssm import ssd_chunked, ssd_reference
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 5)
    b, S, H, P, N = 2, 192, 3, 16, 8
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (b, S, N))
    Cm = jax.random.normal(ks[4], (b, S, N))
    y_ref, h_ref = ssd_reference(x, dt, A, Bm, Cm)
    for chunk in (16, 48, 96, 192):
        y, h = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=3e-4, atol=3e-4)
