"""Each plain reference against the program's own stage math, at reduced
width in float32: the same weights give the same loss and gradients, so
the reference and the program compute one model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import reference
from benchmarks.chip.families import gpt, mamba2
from benchmarks.chip.families.refmath import F32

from conftest import TINY_BATCH, TINY_MODELS

CASES = {
    "gpt": (gpt, "gwtf-gpt-300m"),
    "mamba2": (mamba2, "mamba2-130m"),
}


def _model(fam):
    from benchmarks.chip.harness import load_spec

    spec = load_spec({"gpt": "gpt300m-churn0",
                      "mamba2": "mamba2-churn0"}[fam])
    m = dict(spec.config["model"], **TINY_MODELS[fam])
    m["param_dtype"] = "float32"
    return m


def _program_loss(cfg):
    from repro.core.runtime.stages import embed_fn, loss_fn, stage_forward

    def f(stages, head, toks, labels):
        x = embed_fn(head, toks)
        for p in stages:
            x = stage_forward(p, x, cfg)
        return loss_fn(head, x, labels, cfg)

    return f


@pytest.mark.parametrize("fam", sorted(CASES))
def test_reference_matches_program_in_float32(fam):
    from repro.models.config import ModelConfig

    family, _ = CASES[fam]
    m = _model(fam)
    cfg = ModelConfig(**m)
    stages, head = reference.init_weights(family, m, 2, seed=3)
    B, S = TINY_BATCH[fam]["microbatch"], TINY_BATCH[fam]["seq_len"]
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, m["vocab_size"], (B, S)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, m["vocab_size"], (B, S)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(_program_loss(cfg), argnums=(0, 1))(
            stages, head, toks, labels)
        lr, gr = jax.value_and_grad(
            lambda s, h: reference._loss(family, m, F32, s, h, toks,
                                         labels), argnums=(0, 1))(
            stages, head)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-6)


@pytest.mark.parametrize("fam", sorted(CASES))
def test_weights_match_the_programs_layout(fam):
    """The benchmark's weights have exactly the trees, shapes and dtypes
    of the program's own initial parameters."""
    from repro.core.runtime.stages import init_head_params, init_stage_params
    from repro.models.config import ModelConfig

    from benchmarks.chip.harness import _same_layout

    family, _ = CASES[fam]
    m = dict(_model(fam), param_dtype="bfloat16")
    cfg = ModelConfig(**m)
    stages, head = reference.init_weights(family, m, 2, seed=5)
    key = jax.random.PRNGKey(0)
    prog = [init_stage_params(cfg, s, 2, key) for s in range(2)]
    assert _same_layout(list(stages), prog)
    assert _same_layout(head, init_head_params(cfg, key))


def test_weights_depend_only_on_the_seed():
    m = _model("gpt")
    a = reference.init_weights(gpt, m, 2, seed=2 ** 31 + 9)
    b = reference.init_weights(gpt, m, 2, seed=2 ** 31 + 9)
    c = reference.init_weights(gpt, m, 2, seed=2 ** 31 + 10)
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not all(np.array_equal(x, y) for x, y in zip(la, lc))


def test_stage_bounds_match_the_program():
    from repro.core.runtime.stages import stage_bounds

    cfg = dataclasses.make_dataclass("C", ["num_layers"])
    for L in (4, 6, 16, 24, 7):
        for S in (1, 2, 3, 4):
            assert reference.stage_bounds(L, S) == [
                stage_bounds(cfg(L), s, S) for s in range(S)]


def test_readings_of_a_run_against_itself_are_zero():
    ref = {"losses": [10.0, 9.0], "grad_norms": {"a": 1.0, "b": 2.0},
           "change_norms": {"a": 0.1, "b": 0.2}}
    r = reference.readings(ref, ref)
    assert (r["loss_gap"], r["grad_gap"], r["change_gap"]) == (0.0, 0.0, 0.0)


def test_a_state_left_unchanged_reads_one():
    ref = {"losses": [10.0], "grad_norms": {"a": 1.0, "b": 2.0},
           "change_norms": {"a": 0.1, "b": 0.2}}
    stuck = {"losses": [10.0], "grad_norms": {"a": 0.0, "b": 0.0},
             "change_norms": {"a": 0.0, "b": 0.0}}
    r = reference.readings(stuck, ref)
    assert r["grad_gap"] == pytest.approx(1.0)
    assert r["change_gap"] == pytest.approx(1.0)


def test_leaves_without_gradient_are_left_out_of_the_change():
    ref = {"losses": [1.0], "grad_norms": {"a": 1.0, "b": 1.0, "c": 1e-9},
           "change_norms": {"a": 0.1, "b": 0.1, "c": 1e-3}}
    prog = dict(ref, change_norms={"a": 0.1, "b": 0.1, "c": 0.5})
    assert reference.readings(prog, ref)["change_gap"] == 0.0
