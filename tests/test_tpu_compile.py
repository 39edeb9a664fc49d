"""Compiles for a described TPU v5e chip, no chip attached.

The TPU compiler is installed with jaxlib, so it refuses here what the
chip would refuse: Mosaic lowerings interpret mode never checks, and
programs that do not fit the chip's memory.  The fused attention kernel
is compiled, forward and backward, at the head shapes of the models
that take it; the stage programs at the full width of
``gwtf-llama-300m`` on the chunk the staged trainer dispatches there
(one microbatch of 4 x 512 tokens).  The attention of the GPT and
Nemotron stages is compiled through the fused kernel that a TPU's
programs take: the tests steer the backend choice (``ops.on_tpu``),
which sees the CPU here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import
every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.runtime.stages import (init_head_params, init_stage_params,
                                       stage_kernels, stage_kinds)
from repro.kernels import ops

HBM_BYTES = 16e9          # one v5e chip
CHUNK = (4, 512)          # microbatch x sequence, paper Sec. VI


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without the chip: keep it out of the cache
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), tree)


def _device_bytes(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("H,KH,hd", [(16, 16, 64), (8, 2, 128)],
                         ids=["gpt", "gqa128"])
def test_fused_attention_vjp_compiles(one_chip, H, KH, hd):
    """``jax.vjp`` of the fused kernel on one 4 x 512 chunk: GPT's 16
    heads of 64, and 128-wide heads with four query heads to a KV head.
    Both the forward and the backward program are Mosaic kernels."""
    q = _spec(one_chip, CHUNK + (H, hd), jnp.bfloat16)
    kv = _spec(one_chip, CHUNK + (KH, hd), jnp.bfloat16)

    def fwd(q, k, v):
        return jax.vjp(ops.fused_attention, q, k, v)

    out, vjp = jax.eval_shape(fwd, q, kv, kv)
    fwd_text = jax.jit(fwd).lower(q, kv, kv).compile().as_text()
    bwd = jax.jit(lambda vjp, g: vjp(g)).lower(
        _on(one_chip, vjp), _on(one_chip, out)).compile()
    assert "tpu_custom_call" in fwd_text
    assert "tpu_custom_call" in bwd.as_text()


@pytest.fixture(scope="module")
def llama_stage(one_chip):
    """Abstract full-width stage 0 of 4, its donating kernels, and the
    chunk that enters it."""
    cfg = get_config("gwtf-llama-300m")
    assert (cfg.d_model, cfg.num_layers, cfg.param_dtype) == (
        1024, 16, "bfloat16")
    key = jax.random.PRNGKey(0)
    params = _on(one_chip, jax.eval_shape(
        lambda k: init_stage_params(cfg, 0, 4, k), key))
    x = _spec(one_chip, CHUNK + (cfg.d_model,), jnp.bfloat16)
    return cfg, stage_kernels(cfg, True), params, x


def test_stage_forward_with_residuals_fits_one_chip(llama_stage):
    cfg, k, params, x = llama_stage
    compiled = k.fwd_res.lower(params, x).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_stage_backward_from_residuals_fits_one_chip(llama_stage, one_chip):
    cfg, k, params, x = llama_stage
    _, vjp = jax.eval_shape(k.fwd_res, params, x)
    compiled = k.bwd_res.lower(_on(one_chip, vjp), x).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_head_loss_fits_one_chip(llama_stage, one_chip):
    cfg, k, _, _ = llama_stage
    head = _on(one_chip, jax.eval_shape(
        lambda key: init_head_params(cfg, key), jax.random.PRNGKey(0)))
    hidden = _spec(one_chip, (1,) + CHUNK + (cfg.d_model,), jnp.bfloat16)
    labels = _spec(one_chip, (1,) + CHUNK, jnp.int32)
    compiled = k.head.lower(head, hidden, labels).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def _fused_stage(one_chip, monkeypatch, name, stage):
    """Stage ``stage`` of 4 of ``name`` at full width, with its attention
    on the fused kernel: the compiled ``fwd_res`` and ``bwd_res`` and the
    residual tree.  Kernels built afresh, so no trace of the CPU's choice
    is reused."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = get_config(name)
    k = stage_kernels.__wrapped__(cfg, True)
    params = _on(one_chip, jax.eval_shape(
        lambda key: init_stage_params(cfg, stage, 4, key),
        jax.random.PRNGKey(0)))
    kinds = stage_kinds(cfg, stage, 4)
    x = _spec(one_chip, CHUNK + (cfg.d_model,), jnp.bfloat16)
    fwd = k.fwd_res.lower(params, x, kinds).compile()
    _, vjp = jax.eval_shape(k.fwd_res, params, x, kinds)
    bwd = k.bwd_res.lower(_on(one_chip, vjp), x).compile()
    return fwd, bwd, vjp


def test_gpt_stage_keeps_no_attention_matrix(one_chip, monkeypatch):
    """The GPT stage's residuals hold nothing of size S x S and total
    under 800 MiB a microbatch (1,234 MiB on the XLA path)."""
    fwd, bwd, vjp = _fused_stage(one_chip, monkeypatch, "gwtf-gpt-300m", 0)
    assert "tpu_custom_call" in fwd.as_text()
    assert "tpu_custom_call" in bwd.as_text()
    leaves = jax.tree.leaves(vjp)
    assert not [l.shape for l in leaves if l.shape[-2:] == (512, 512)]
    assert sum(l.size * l.dtype.itemsize for l in leaves) < 800 * 2 ** 20


def test_nemotron_attention_stage_fits_one_chip(one_chip, monkeypatch):
    fwd, bwd, _ = _fused_stage(one_chip, monkeypatch,
                               "nemotron3-nano-30b-a3b", 2)
    assert "tpu_custom_call" in fwd.as_text()
    assert _device_bytes(fwd) < HBM_BYTES
    assert _device_bytes(bwd) < HBM_BYTES
