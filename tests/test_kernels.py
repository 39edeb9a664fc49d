"""The attention and SSD paths the models run, against their plain
float32 oracles over shapes and dtypes.

``_online_attention`` is the attention of every program off the TPU and
of every cache, window and cross-attention path on it; ``ssd_chunked``
is Mamba-2's SSD everywhere.  The fused kernel that training attention
takes on a TPU is checked in ``tests/test_fused_attention.py``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ref import attention_reference
from repro.models import layers as L
from repro.models.ssm import ssd_chunked, ssd_reference

TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# Online-softmax attention
# ---------------------------------------------------------------------------

def _reference_bshd(q, k, v, window=None):
    """``attention_reference`` in the model's (B, S, H, hd) layout."""
    B, S, H, D = q.shape
    to = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    out = attention_reference(to(q), to(k), to(v), causal=True,
                              window=window)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _attention_gaps(q, k, v, window=None):
    """Largest gap of ``_online_attention``'s output, dq, dk and dv, in
    query blocks of 128, from the float32 reference's on the same
    inputs, each relative to the reference's largest entry."""
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape, q.dtype)
    online = functools.partial(L._online_attention, q_offset=0,
                               causal=True, window=window, q_block=128)
    out, vjp = jax.vjp(online, q, k, v)
    got = (out,) + vjp(g)
    f32 = [a.astype(jnp.float32) for a in (q, k, v, g)]
    out, vjp = jax.vjp(functools.partial(_reference_bshd, window=window),
                       *f32[:3])
    want = (out,) + vjp(f32[3])
    return [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                  / jnp.max(jnp.abs(b))) for a, b in zip(got, want)]


def _qkv(shape, dtype, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in (k1, k2, k3))


@pytest.mark.parametrize("S", [128, 256, 512])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_online_attention_matches_reference(S, D, dtype):
    """Causal self-attention, output and VJP, in one query block
    (S = 128) and in several."""
    gaps = _attention_gaps(*_qkv((1, S, 2, D), dtype, S + D))
    assert max(gaps) < TOL[dtype]["rtol"], gaps


@pytest.mark.parametrize("window", [32, 64, 128])
def test_online_attention_window_matches_reference(window):
    gaps = _attention_gaps(*_qkv((2, 256, 1, 64), jnp.float32, window),
                           window=window)
    assert max(gaps) < TOL[jnp.float32]["rtol"], gaps


# ---------------------------------------------------------------------------
# Chunked SSD
# ---------------------------------------------------------------------------

def _ssd_args(B, S, H, P, N, dtype, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, N), dtype)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("S,chunk", [(128, 32), (256, 64), (256, 128)])
@pytest.mark.parametrize("N", [16, 64])
def test_ssd_chunked_shapes(S, chunk, N):
    args = _ssd_args(2, S, 3, 32, N, jnp.float32, S + N)
    y, hf = ssd_chunked(*args, chunk=chunk)
    yr, hfr = ssd_reference(*args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hfr),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunked_dtypes(dtype):
    """Inputs in the model's dtypes against the recurrence on the same
    values in float32."""
    args = _ssd_args(1, 128, 2, 16, 16, dtype, 1)
    y, hf = ssd_chunked(*args, chunk=64)
    yr, hfr = ssd_reference(*(a.astype(jnp.float32) for a in args))
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
