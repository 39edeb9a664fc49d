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


def _ssd_inputs(seed=3, b=2, S=192, H=3, P=16, N=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (b, S, N))
    Cm = jax.random.normal(ks[4], (b, S, N))
    h0 = jax.random.normal(ks[5], (b, H, P, N))
    return x, dt, A, Bm, Cm, h0


@pytest.mark.parametrize("h0", ["zeros", "random"])
@pytest.mark.parametrize("chunk", [2, 16, 48, 96, 192])
def test_ssd_model_chunked_matches_sequential(chunk, h0):
    """The model's chunked SSD (all chunks at once) matches the sequential
    recurrence, from a zero or a carried-in state; chunk 192 is the
    single-chunk case, chunk 2 passes states across 96 chunks."""
    from repro.models.ssm import ssd_chunked, ssd_reference
    x, dt, A, Bm, Cm, h_init = _ssd_inputs()
    h_init = None if h0 == "zeros" else h_init
    y_ref, h_ref = ssd_reference(x, dt, A, Bm, Cm, h0=h_init)
    y, h = ssd_chunked(x, dt, A, Bm, Cm, h0=h_init, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("chunk", [2, 16, 48, 192])
def test_ssd_model_chunked_gradients_match_sequential(chunk):
    """Gradients of the chunked SSD with respect to x, dt, A, B and C match
    those through the sequential recurrence, and stay finite for a head
    whose per-step decay exp(dt*A) underflows to zero."""
    from repro.models.ssm import ssd_chunked, ssd_reference
    x, dt, A, Bm, Cm, h0 = _ssd_inputs()
    A = A.at[0].set(-200.0)
    gy = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    gh = jax.random.normal(jax.random.PRNGKey(8), h0.shape)

    def loss(f, **kw):
        def g(*args):
            y, h = f(*args, h0=h0, **kw)
            return jnp.sum(y * gy) + jnp.sum(h * gh)
        return jax.grad(g, argnums=(0, 1, 2, 3, 4))

    grads = loss(ssd_chunked, chunk=chunk)(x, dt, A, Bm, Cm)
    refs = loss(ssd_reference)(x, dt, A, Bm, Cm)
    for name, g, r in zip(("x", "dt", "A", "B", "C"), grads, refs):
        g, r = np.asarray(g), np.asarray(r)
        assert np.all(np.isfinite(g)), name
        scale = np.max(np.abs(r))
        assert np.max(np.abs(g - r)) <= 5e-4 * scale, name


@pytest.mark.parametrize("block", [2, 4, 64])
def test_ssd_decay_chain_matches_loop(block):
    """The inter-chunk recurrence h_k = exp(a_k) h_{k-1} + s_k, solved in
    blocks with the block ends chained by recursion, matches a plain loop,
    also where a decay underflows to zero."""
    from repro.models.ssm import _decay_chain
    ka, ks = jax.random.split(jax.random.PRNGKey(5))
    log_a = -jax.nn.softplus(jax.random.normal(ka, (2, 3, 50)))
    log_a = log_a.at[0, 1, 20].set(-1e4)
    s = jax.random.normal(ks, (2, 3, 50, 6))
    h, want = jnp.zeros((2, 3, 6)), []
    for k in range(50):
        h = jnp.exp(log_a[..., k])[..., None] * h + s[:, :, k]
        want.append(h)
    got = _decay_chain(log_a, s, block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.stack(want, 2)),
                               rtol=1e-5, atol=1e-5)


def _count_primitive(jaxpr, name):
    """Equations named ``name`` in ``jaxpr`` and, recursively, in its
    sub-jaxprs."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)     # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    n += _count_primitive(sub, name)
    return n


def test_ssd_stage_program_has_no_chunk_loop():
    """A Mamba-2 stage's fwd_res holds one scan, the one over layers: the
    SSD computes its chunks without a sequential loop."""
    from repro.configs import get_config
    from repro.core.runtime.stages import init_stage_params, stage_kernels
    cfg = get_config("mamba2-130m").reduced(num_layers=2, d_model=64)
    p = init_stage_params(cfg, 0, 1, jax.random.PRNGKey(0))
    x = jnp.zeros((2, 256, cfg.d_model), jnp.dtype(cfg.param_dtype))
    jaxpr = jax.make_jaxpr(stage_kernels(cfg, False).fwd_res)(p, x)
    assert _count_primitive(jaxpr.jaxpr, "scan") == 1
