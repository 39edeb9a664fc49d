"""Nemotron-H on the staged runtime against its plain float32 reference
(``ref_nemotron_h.py``), at a small size on the CPU with seeded random
weights: stages of mixed layer kinds, grouped SSD, a share of the experts
and the sigmoid router."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ref_nemotron_h as ref
from repro.configs import get_config
from repro.models import ssm as SSM
from repro.models.config import ModelConfig

# every kind, 2 B/C groups, 4 of 16 experts held, float32 throughout
SMALL = dict(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32,
             shared_d_ff=48, vocab_size=128, ssm_state=8, ssm_heads=8,
             ssm_head_dim=16, ssm_groups=2, num_experts=16,
             num_experts_per_tok=3, experts_held=4, param_dtype="float32")
SEED = 2 ** 31 + 23


def _cfg(**kw):
    return dataclasses.replace(get_config("nemotron3-nano-30b-a3b"),
                               **dict(SMALL, **kw))


def _batch(cfg, B=2, S=64, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
            jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32))


@pytest.mark.parametrize("first", [0, 12])
def test_kinded_trainer_matches_the_reference(first):
    """The trainer's numeric pass (``_chunk_pass``: fused forward with
    residuals, head, backward from residuals, embedding pull-back) over 4
    stages [M,E] [M,E] [M,*] [E] gives the reference's loss and every
    gradient leaf."""
    from repro.core.runtime.activations import ActivationStore
    from repro.core.runtime.stages import StageCompute
    from repro.core.runtime.trainer import _chunk_pass

    cfg = _cfg(first_expert=first)
    stages, head = ref.weights(cfg, 4, SEED)
    toks, labels = _batch(cfg)
    grads = [None] * 4
    with jax.default_matmul_precision("highest"):
        loss_sum, g_head = _chunk_pass(
            StageCompute(cfg, 4, donate=False), ActivationStore(),
            list(stages), head, toks, labels[None], (0,), 2, remat=False,
            grad_stage=grads)
        want, (g_s, g_h) = jax.value_and_grad(ref.loss, argnums=(0, 1))(
            stages, head, toks, labels, cfg)
    np.testing.assert_allclose(loss_sum, float(want), rtol=2e-5)
    leaves = jax.tree.leaves((grads, g_head))
    assert len(leaves) == len(jax.tree.leaves((g_s, g_h))) > 30
    for a, b in zip(leaves, jax.tree.leaves((list(g_s), g_h))):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4 * scale)


@pytest.mark.parametrize("held", [4, 8])
def test_expert_shares_add_up_to_the_whole_layer(held):
    """The routed parts of every share of the experts, with the shared
    expert counted once, equal the uncut reference MoE layer."""
    from repro.models.moe import apply_moe, init_moe

    whole = _cfg(experts_held=0)
    p = init_moe(jax.random.PRNGKey(3), whole, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 16, whole.d_model))
    shared_only = _cfg(experts_held=held, num_experts_per_tok=1,
                       first_expert=whole.num_experts)   # routes to none held
    with jax.default_matmul_precision("highest"):
        total = 0.0
        for first in range(0, whole.num_experts, held):
            cfg = _cfg(experts_held=held, first_expert=first)
            part = dict(p, w_up=p["w_up"][first:first + held],
                        w_down=p["w_down"][first:first + held])
            total = total + apply_moe(part, h, cfg, impl="ragged")[0]
        shared, _ = apply_moe(dict(p, w_up=p["w_up"][:held],
                                   w_down=p["w_down"][:held]), h,
                              shared_only, impl="ragged")
        want = ref.moe_mixer(p, h, whole)
    n = whole.num_experts // held
    np.testing.assert_allclose(total - (n - 1) * shared, want, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("impl", ["ragged", "dense", "capacity"])
@pytest.mark.parametrize("mlp", ["relu2", "gelu", "swiglu"])
def test_every_expert_path_computes_one_layer(impl, mlp):
    """With every expert held, the ragged, dense and capacity paths give
    one function of the expert's activation: ungated for relu2 and gelu,
    gated for swiglu.  relu2 is also the reference's Nemotron layer."""
    from repro.models.moe import apply_moe, init_moe

    cfg = _cfg(experts_held=0, mlp_type=mlp)
    p = init_moe(jax.random.PRNGKey(7), cfg, jnp.float32)
    assert ("w_gate" in p) == (mlp == "swiglu")
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 8, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = apply_moe(p, h, cfg, impl=impl)[0]
        want = (ref.moe_mixer(p, h, cfg) if mlp == "relu2"
                else apply_moe(p, h, cfg, impl="ragged")[0])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def _undefined_past_the_groups(orig):
    """``ragged_dot`` whose rows past the groups, in its output and in its
    input cotangent, are NaN: what a TPU may leave there."""
    def fill(a, gs):
        past = jnp.arange(a.shape[0]) >= jnp.sum(gs)
        return jnp.where(past[:, None], jnp.nan, a)

    @jax.custom_vjp
    def rd(a, w, gs):
        return fill(orig(a, w, gs), gs)

    def fwd(a, w, gs):
        return rd(a, w, gs), (a, w, gs)

    def bwd(res, g):
        a, w, gs = res
        da, dw = jax.vjp(lambda a, w: orig(a, w, gs), a, w)[1](g)
        return fill(da, gs), dw, np.zeros(gs.shape, jax.dtypes.float0)

    rd.defvjp(fwd, bwd)
    return rd


def test_rows_past_the_expert_groups_are_never_read(monkeypatch):
    from repro.models.moe import apply_moe, init_moe

    cfg = _cfg(first_expert=4)
    p = init_moe(jax.random.PRNGKey(5), cfg, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 16, cfg.d_model))

    def f(p, h):
        return jnp.sum(jnp.square(apply_moe(p, h, cfg, impl="ragged")[0]))

    want = jax.value_and_grad(f, argnums=(0, 1))(p, h)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _undefined_past_the_groups(jax.lax.ragged_dot))
    got = jax.value_and_grad(f, argnums=(0, 1))(p, h)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("G", [2, 4])
@pytest.mark.parametrize("with_h0", [False, True])
def test_grouped_ssd_matches_the_recurrence(G, with_h0):
    """``ssd_chunked`` with B/C in G groups equals ``ssd_reference`` run on
    each group's heads with that group's B and C."""
    b, S, H, P, N = 2, 96, 8, 4, 6
    k = jax.random.split(jax.random.PRNGKey(G), 6)
    x = jax.random.normal(k[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, S, H)))
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=-1.0, maxval=1.5))
    B = jax.random.normal(k[3], (b, S, G, N))
    C = jax.random.normal(k[4], (b, S, G, N))
    h0 = jax.random.normal(k[5], (b, H, P, N)) if with_h0 else None
    y, hf = SSM.ssd_chunked(x, dt, A, B, C, h0=h0, chunk=16)
    r = H // G
    for g in range(G):
        hs = slice(g * r, (g + 1) * r)
        yr, hr = SSM.ssd_reference(x[:, :, hs], dt[:, :, hs], A[hs],
                                   B[:, :, g], C[:, :, g],
                                   None if h0 is None else h0[:, hs])
        np.testing.assert_allclose(y[:, :, hs], yr, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(hf[:, hs], hr, rtol=2e-4, atol=2e-4)


def _parent_apply_mamba(p, x, cfg, chunk=64):
    """``apply_mamba`` as it was before B/C groups (no cache), frozen."""
    B_, S, D = x.shape
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    P = di // H
    zxbcdt = x @ p["in_proj"]
    z, xs, Bc, Cc, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)
    conv_in = jnp.concatenate([xs, Bc, Cc], axis=-1)
    conv_out, _ = SSM._causal_conv(conv_in, p["conv_w"], p["conv_b"], None)
    conv_out = jax.nn.silu(conv_out)
    xs, Bc, Cc = jnp.split(conv_out, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(B_, S, H, P)
    y, _ = SSM._ssd_all_chunks(xh, dt, A, Bc, Cc, None,
                               chunk if S % chunk == 0 else S)
    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B_, S, di)
    g = y * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = g * jax.lax.rsqrt(ms + 1e-6) * p["norm_scale"]
    return g.astype(x.dtype) @ p["out_proj"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_group_mamba_is_the_parents_bit_for_bit(dtype):
    cfg = dataclasses.replace(get_config("mamba2-130m").reduced(
        num_layers=1, d_model=64), param_dtype=dtype)
    p = SSM.init_mamba(jax.random.PRNGKey(0), cfg, jnp.dtype(dtype))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64)).astype(dtype)

    def new(p, x):
        return SSM.apply_mamba(p, x, cfg)[0]

    old = jax.jit(lambda p, x: _parent_apply_mamba(p, x, cfg))
    np.testing.assert_array_equal(jax.jit(new)(p, x), old(p, x))
    gn = jax.jit(jax.grad(lambda p, x: jnp.sum(new(p, x).astype(
        jnp.float32) ** 2), argnums=(0, 1)))(p, x)
    go = jax.jit(jax.grad(lambda p, x: jnp.sum(old(p, x).astype(
        jnp.float32) ** 2), argnums=(0, 1)))(p, x)
    for a, b in zip(jax.tree.leaves(gn), jax.tree.leaves(go)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("size", ["small", "published"])
def test_stage_params_have_the_familys_layout(size):
    """``init_stage_params`` holds each stage as ``{kind: stacked}``
    with the trees, shapes and dtypes of the benchmark family's weights
    (``give_weights``' ``_same_layout``)."""
    from repro.core.runtime.stages import init_head_params, init_stage_params

    from benchmarks.chip.harness import _same_layout

    cfg = (_cfg(param_dtype="bfloat16") if size == "small"
           else get_config("nemotron3-nano-30b-a3b"))
    key = jax.random.PRNGKey(0)
    prog = jax.eval_shape(lambda k: [init_stage_params(cfg, s, 4, k)
                                     for s in range(4)], key)
    want, head = jax.eval_shape(lambda: ref.weights(cfg, 4, SEED))
    assert [sorted(p) for p in prog] == [
        ["mamba", "moe"], ["mamba", "moe"], ["attention", "mamba"], ["moe"]]
    assert _same_layout(list(want), prog)
    assert _same_layout(head, jax.eval_shape(
        lambda k: init_head_params(cfg, k), key))


def test_committed_configs_keep_their_widths():
    """The SSM inner width is ``ssm_expand * d_model`` wherever a config
    gives an expansion, and ``ssm_heads * ssm_head_dim`` (Nemotron's 4096,
    not 2 x 2688) where it gives 0."""
    from repro.configs import ARCH_IDS

    for arch in ARCH_IDS:
        cfg = get_config(arch)
        assert cfg.d_inner == cfg.ssm_expand * cfg.d_model, arch
        if cfg.ssm_heads:
            assert cfg.d_inner == cfg.ssm_heads * cfg.ssm_head_dim, arch
    assert ModelConfig("m", "ssm", 1, 64, 0, 0, 64, 0, 8,
                       ssm_heads=4).d_inner == 128
    assert get_config("nemotron3-nano-30b-a3b").d_inner == 4096


def test_nemotron_trains_through_make_gwtf():
    """The normal path: ``make_gwtf`` builds the staged trainer for the
    config, whose MoE stage programs route on the ragged path over the
    experts held and never run every expert."""
    from repro.core.runtime.stages import stage_kinds
    from repro.launch.train import build_parser, make_gwtf

    args = build_parser().parse_args(
        ["--arch", "nemotron3-nano-30b-a3b", "--reduced", "--layers", "7",
         "--d-model", "64", "--seq-len", "32", "--batch", "2",
         "--microbatches", "2", "--data-nodes", "1", "--relays-per-stage",
         "2"])
    cfg, trainer, shards = make_gwtf(args)
    assert cfg.layer_pattern == "MEMEM*E"
    losses = []
    for _ in range(2):
        r = trainer.iteration({dn: sh.microbatches()
                               for dn, sh in shards.items()})
        assert r.completed == r.launched == 2
        losses.append(r.loss)
    assert all(np.isfinite(losses))
    p = trainer.stage_params[3]
    x = jnp.zeros((2, 32, cfg.d_model), jnp.float32)
    jaxpr = str(jax.make_jaxpr(lambda p, x: trainer.stages._k.fwd(
        p, x, stage_kinds(cfg, 3, 4)))(p, x))
    assert "ragged_dot" in jaxpr
    assert p["moe"]["moe"]["w_up"].shape[1] == cfg.num_experts_held


@pytest.mark.parametrize("path", ["param_count", "decode_step"])
def test_paths_without_patterns_or_groups_refuse_them(path):
    """``param_count`` prices every layer alike and the one-token decode
    reads one B/C group: each refuses the model it would get wrong."""
    cfg = _cfg()
    if path == "param_count":
        with pytest.raises(NotImplementedError):
            cfg.param_count()
        return
    p = SSM.init_mamba(jax.random.PRNGKey(9), cfg, jnp.float32)
    cache = SSM.init_mamba_cache(cfg, 2)
    x = jnp.ones((2, 1, cfg.d_model))
    with pytest.raises(NotImplementedError):
        SSM.apply_mamba(p, x, cfg, cache=cache)
    one = dataclasses.replace(cfg, ssm_groups=1)
    p = SSM.init_mamba(jax.random.PRNGKey(9), one, jnp.float32)
    out, _ = SSM.apply_mamba(p, x, one, cache=SSM.init_mamba_cache(one, 2))
    assert out.shape == x.shape
