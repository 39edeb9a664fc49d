"""The kinded family contract (``reference.py``) on a tiny family with
three layer kinds (``kinded_family.py``): the stage dict layout, weights
that do not depend on the number of stages, the reference loss and
gradients against a loop over the pattern written out by hand, a
reference run, and the work counts summed kind by kind."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import harness, reference, work
from benchmarks.chip.families.refmath import F32

import kinded_family as fam

M = fam.MODEL
SEED = 2 ** 31 + 41
# Pattern MFMWMFM: which layers each stage holds of each kind, in order.
LAYOUT = {
    2: [{"mlp": [0, 2], "full": [1], "window": [3]},
        {"mlp": [4, 6], "full": [5]}],
    4: [{"mlp": [0], "full": [1]}, {"mlp": [2], "window": [3]},
        {"mlp": [4], "full": [5]}, {"mlp": [6]}],
}


def _by_layer(stages, S):
    """Layer i's tree, read out of a stage layout through ``LAYOUT``."""
    out = [None] * M["num_layers"]
    for sp, want in zip(stages, LAYOUT[S]):
        for kind, idx in want.items():
            for j, i in enumerate(idx):
                out[i] = jax.tree.map(lambda a, j=j: a[j], sp[kind])
    return out


def _batch(seed, B=2, S=8):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, M["vocab_size"], (B, S)), jnp.int32),
            jnp.asarray(rng.integers(0, M["vocab_size"], (B, S)), jnp.int32))


@pytest.mark.parametrize("S", sorted(LAYOUT))
def test_stage_dicts_hold_each_kinds_layers_in_order(S):
    stages, _ = reference.init_weights(fam, M, S, SEED)
    assert [sorted(sp) for sp in stages] == [sorted(w) for w in LAYOUT[S]]
    assert reference.stage_kinds(fam, M, S) == [
        {k: len(v) for k, v in w.items()} for w in LAYOUT[S]]
    keys = jax.random.split(jax.random.split(jax.random.PRNGKey(SEED))[0],
                            M["num_layers"])
    kinds = fam.layer_kinds(M)
    for i, tree in enumerate(_by_layer(stages, S)):
        one = fam.init_layer(keys[i], M, kinds[i])
        assert jax.tree.structure(tree) == jax.tree.structure(one)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(one)):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_weights_do_not_depend_on_the_stage_count():
    two, head2 = reference.init_weights(fam, M, 2, SEED)
    four, head4 = reference.init_weights(fam, M, 4, SEED)
    for a, b in zip(_by_layer(two, 2), _by_layer(four, 4)):
        assert all(np.array_equal(x, y) for x, y in
                   zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(head2), jax.tree.leaves(head4)))


def test_window_and_full_share_a_tree_but_not_the_mixing():
    stages, head = reference.init_weights(fam, M, 4, SEED)
    p = jax.tree.map(lambda a: a[0], stages[0]["full"])
    x = fam.embed(head, _batch(1)[0])
    full, window = (fam.layer(p, x, M, F32, k) for k in ("full", "window"))
    # positions inside the window see the same keys either way
    np.testing.assert_allclose(full[:, :M["window"]],
                               window[:, :M["window"]], rtol=1e-6)
    assert not np.allclose(full[:, M["window"]:], window[:, M["window"]:])


def _hand_loss(layers, head, tokens, labels):
    x = fam.embed(head, tokens)
    for p, letter in zip(layers, M["layer_pattern"]):
        x = fam.layer(p, x, M, F32, fam.KINDS[letter])
    return fam.head_loss(head, x, labels, M, F32)


@pytest.mark.parametrize("S", sorted(LAYOUT))
def test_loss_and_gradients_match_a_hand_unrolled_loop(S):
    stages, head = reference.init_weights(fam, M, S, SEED)
    toks, labels = _batch(S)
    with jax.default_matmul_precision("highest"):
        lr, (gs, gh) = jax.value_and_grad(
            lambda s, h: reference._loss(fam, M, F32, s, h, toks, labels),
            argnums=(0, 1))(stages, head)
        lh, (gl, ghh) = jax.value_and_grad(_hand_loss, argnums=(0, 1))(
            _by_layer(stages, S), head, toks, labels)
    np.testing.assert_allclose(float(lr), float(lh), rtol=1e-6)
    for a, b in zip(jax.tree.leaves((_by_layer(gs, S), gh)),
                    jax.tree.leaves((gl, ghh))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


OPT = {"lr": 1e-2, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "grad_clip": 1.0}


def _steps():
    def mb(seed):
        t, lab = _batch(seed)
        return {"tokens": np.asarray(t), "labels": np.asarray(lab)}

    steps = [{0: [mb(10), mb(11)], 1: [mb(12)]},
             {0: [mb(13), mb(14)], 1: [mb(15)]}]
    return steps, [[(0, 0), (0, 1), (1, 0)], [(0, 1), (1, 0)]]


def test_reference_trains_on_dict_stages():
    steps, completed = _steps()
    run = reference.train(fam, M, 4, SEED, steps, completed, OPT)
    rd = reference.readings(run, run)
    assert all(rd[k] == 0.0 for k in ("loss1_gap", "loss_gap", "grad_gap",
                                      "change_gap", "grad_median_gap",
                                      "change_median_gap"))
    assert len(run["losses"]) == 2
    leaves = set(run["grad_norms"])
    assert {"stage3/mlp/w_up", "stage1/window/wqkv", "head0/embed",
            "head1/norm"} <= leaves
    assert not any(n.startswith(("stage3/full", "stage0/window"))
                   for n in leaves)
    assert set(run["change_norms"]) == leaves
    # the fp8 control runs on the same layout and reads apart
    control = reference.train(fam, M, 4, SEED, steps, completed, OPT,
                              precision="fp8")
    assert reference.readings(control, run)["loss_gap"] > 0.0


def test_give_weights_layout_check_takes_only_the_stage_dicts():
    stages, _ = reference.init_weights(fam, M, 4, SEED)
    assert harness._same_layout(list(stages), list(stages))
    # a stage missing a kind it holds, or holding one layer too many
    wrong = [dict(stages[0]), *stages[1:]]
    del wrong[0]["full"]
    assert not harness._same_layout(list(stages), wrong)
    wrong = [*stages[:3], {"mlp": jax.tree.map(
        lambda a: jnp.concatenate([a, a]), stages[3]["mlp"])}]
    assert not harness._same_layout(list(stages), wrong)


def test_work_sums_each_kind_by_hand(monkeypatch):
    monkeypatch.setattr(harness, "family_of", lambda spec: fam)
    spec = SimpleNamespace(
        config={"model": M}, traffic={"batch": {"microbatch": 2,
                                                "seq_len": 8},
                                      "topology": {"stages": 4}})
    rec = harness.record(spec, SimpleNamespace(completed=6, iterations=2,
                                               bwd_replays=3))
    assert rec.stage_layers == [2, 2, 2, 1]
    assert rec.stage_kinds == reference.stage_kinds(fam, M, 4)
    c = fam.counts(M, 8)
    lf, pb = c["layer_flops"], c["layer_param_bytes"]
    # the pattern holds four mlp layers, two full and one window
    fwd = 4 * lf["mlp"] + 2 * lf["full"] + lf["window"]
    params = 4 * pb["mlp"] + 2 * pb["full"] + pb["window"]
    assert work.train_flops_per_token(rec) == 3.0 * (fwd + c["head_flops"])
    act = 2 * 16 * c["act_bytes"]
    assert work.stage_pass(rec, "fwd") == (6 * fwd * 16,
                                           2 * params + 6 * 4 * act)
    flops, nbytes = work.stage_pass(rec, "bwd")
    # three replays, each the mean stage's backward work
    assert flops == pytest.approx(2 * (6 * fwd * 16 + 3 * fwd * 16 / 4))
    assert nbytes == 2 * 2 * params + (6 * 4 + 3) * act
