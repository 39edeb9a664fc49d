"""The plain reference for the staged trainer's first steps, and the
comparison that decides ``correct``.

What the reference does, in float32 at the highest matmul precision
(or, for the control, in fp8; see ``families/refmath.py``):

- builds the same weights from the seed (``init_weights``, the one
  jitted call that also makes the program's weights);
- for each step, runs every microbatch that the program reports as
  completed through embedding, all layers and the loss, and takes the
  gradient of each microbatch's mean token loss;
- averages the layers' gradients over all completed microbatches and
  each data node's head gradient over that node's microbatches;
- applies AdamW to each stage's tree and to each data node's head tree
  on its own (global-norm clipping per tree, decoupled weight decay on
  leaves of two or more dimensions), keeping every leaf in its stored
  type between steps.

``readings`` gives the numbers a cell's limits file may compare: the
relative gap of the first step's mean loss and of the worst step's, the
gap between the norms of the first gradient as the optimizer receives it
(worst leaf, and median leaf), and the gap between the norms of the
parameters' change over the compared steps (worst leaf, and median
leaf).  A leaf's gap is measured against the reference's norm of that
leaf or of the median leaf, whichever is larger.  Leaves whose first
reference gradient is under a thousandth of the median leaf's are left
out of the change.

The family contract.  A model family (``families/<family>.py``) gives
``init_head(key, model)``, ``embed(head, tokens)``, ``head_loss(head, x,
labels, model, pr)`` and ``counts(model, seq_len)``, and its layers in
one of two forms:

- Homogeneous (no ``layer_kinds``): every layer has one tree.
  ``init_layer(key, model)`` and ``layer(p, x, model, pr)``; ``counts``
  gives ``layer_flops`` and ``layer_param_bytes`` as numbers.  Stage
  *s* holds layers ``[lo, hi)`` of ``stage_bounds`` as one tree whose
  leaves are stacked over those layers, and runs them by one
  ``lax.scan``.
- Kinded: ``layer_kinds(model)`` gives one kind name per layer of
  ``model["num_layers"]``, read from a string key of the model (the
  harness never parses a pattern).  ``init_layer(key, model, kind)`` and
  ``layer(p, x, model, pr, kind)`` take the kind; ``counts`` gives
  ``layer_flops`` and ``layer_param_bytes`` as dicts keyed by kind
  (``head_flops`` and ``act_bytes`` as numbers).  Stage *s* is a dict
  ``{kind: tree}`` holding, for each kind with a layer in ``[lo, hi)``,
  that kind's layers stacked in layer order; a kind with no layer in
  the stage is absent.  Layer *i* of kind *k* is slice *j* of
  ``stage[k]``, *j* the number of earlier *k* layers in the stage, and
  layers run one by one in published order.  Leaf paths read
  ``stage0/<kind>/...``.

Either way layer *i*'s weights are drawn from ``jax.random.split(kl,
num_layers)[i]``, so they do not depend on the number of stages, and a
program that runs the family holds ``stage_params[s]`` in exactly this
layout (``harness.give_weights`` refuses any other).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.families.refmath import PRECISIONS

EXCLUDE_BELOW = 1e-3


def stage_bounds(num_layers: int, num_stages: int) -> List[Tuple[int, int]]:
    """Contiguous layer ranges, the first ``num_layers % num_stages``
    stages one layer longer."""
    per, extra = divmod(num_layers, num_stages)
    out, lo = [], 0
    for s in range(num_stages):
        hi = lo + per + (1 if s < extra else 0)
        out.append((lo, hi))
        lo = hi
    return out


def layer_kinds(family, model: dict):
    """The kind of each layer, or None for a homogeneous family."""
    if not hasattr(family, "layer_kinds"):
        return None
    kinds = tuple(family.layer_kinds(model))
    if len(kinds) != model["num_layers"]:
        raise ValueError(f"{len(kinds)} layer kinds for "
                         f"{model['num_layers']} layers")
    return kinds


def stage_kinds(family, model: dict, num_stages: int):
    """Per stage, ``{kind: its layers in the stage}`` in first-seen
    order; None for a homogeneous family."""
    kinds = layer_kinds(family, model)
    if kinds is None:
        return None
    return [{k: kinds[lo:hi].count(k) for k in dict.fromkeys(kinds[lo:hi])}
            for lo, hi in stage_bounds(len(kinds), num_stages)]


def _kinded_stages(family, model, kinds, keys, bounds):
    """Stage dicts of a kinded family: one vmap per kind over its
    layers' keys, each stage taking its contiguous run of them."""
    stages = [{} for _ in bounds]
    for kind in sorted(set(kinds)):
        idx = [i for i, k in enumerate(kinds) if k == kind]
        layers = jax.vmap(lambda k, kind=kind: family.init_layer(
            k, model, kind))(keys[jnp.asarray(idx)])
        for stage, (lo, hi) in zip(stages, bounds):
            pos = [j for j, i in enumerate(idx) if lo <= i < hi]
            if pos:
                stage[kind] = jax.tree.map(
                    lambda a, a0=pos[0], a1=pos[-1] + 1: a[a0:a1], layers)
    return tuple(stages)


@functools.lru_cache(maxsize=None)
def _init_fn(family, model_items: tuple, num_stages: int):
    model = dict(model_items)
    bounds = stage_bounds(model["num_layers"], num_stages)
    kinds = layer_kinds(family, model)

    @jax.jit
    def make(key):
        kl, kh = jax.random.split(key)
        keys = jax.random.split(kl, model["num_layers"])
        if kinds is not None:
            return (_kinded_stages(family, model, kinds, keys, bounds),
                    family.init_head(kh, model))
        layers = jax.vmap(lambda k: family.init_layer(k, model))(keys)
        stages = tuple(jax.tree.map(lambda a, lo=lo, hi=hi: a[lo:hi], layers)
                       for lo, hi in bounds)
        return stages, family.init_head(kh, model)

    return make


def init_weights(family, model: dict, num_stages: int, seed: int):
    """(stage trees, head tree) on the default device, in one jitted
    call from the seed."""
    return _init_fn(family, tuple(sorted(model.items())), num_stages)(
        jax.random.PRNGKey(seed))


def leaf_paths(tree) -> List[str]:
    return [jax.tree_util.keystr(p, simple=True, separator="/")
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def _diff_norms(new, old):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))])


def named_norms(trees: Dict[str, object], *, minus=None) -> Dict[str, float]:
    """{prefix/leaf: L2 norm} over several trees, e.g. {"stage0": ...}.
    With ``minus``, the norm of each leaf's difference to the same
    leaf there."""
    out = {}
    for name, tree in trees.items():
        vals = (_norms(tree) if minus is None
                else _diff_norms(tree, minus[name]))
        for path, v in zip(leaf_paths(tree), np.asarray(vals)):
            out[f"{name}/{path}"] = float(v)
    return out


class AdamWRef:
    """AdamW as stated in the traffic file, applied to one tree."""

    def __init__(self, lr, b1, b2, eps, weight_decay, grad_clip):
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps
        self.wd, self.clip = weight_decay, grad_clip
        self.step = jax.jit(self._step)

    def init(self, params):
        z = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return (z, z)

    def _step(self, params, grads, state, t):
        m, v = state
        g = jax.tree.map(lambda x: x.astype(jnp.float32), grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, self.clip / (gnorm + 1e-9)), g)
        m = jax.tree.map(lambda a, b: self.b1 * a + (1 - self.b1) * b, m, g)
        v = jax.tree.map(lambda a, b: self.b2 * a + (1 - self.b2) * b * b,
                         v, g)
        c1, c2 = 1 - self.b1 ** t, 1 - self.b2 ** t

        def upd(p, mm, vv):
            d = (mm / c1) / (jnp.sqrt(vv / c2) + self.eps)
            if p.ndim >= 2:
                d = d + self.wd * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - self.lr * d).astype(p.dtype)

        return jax.tree.map(upd, params, m, v), (m, v), g


def _loss(family, model, pr, stages, head, tokens, labels):
    x = family.embed(head, tokens)
    kinds = layer_kinds(family, model)
    if kinds is None:
        layer = jax.checkpoint(lambda h, p: (family.layer(p, h, model, pr),
                                             None))
        for sp in stages:
            x, _ = jax.lax.scan(layer, x, sp)
    else:
        for sp, (lo, hi) in zip(stages,
                                stage_bounds(len(kinds), len(stages))):
            seen = dict.fromkeys(sp, 0)
            for kind in kinds[lo:hi]:
                p = jax.tree.map(lambda a, j=seen[kind]: a[j], sp[kind])
                seen[kind] += 1
                x = jax.checkpoint(lambda p, h, kind=kind: family.layer(
                    p, h, model, pr, kind))(p, x)
    return family.head_loss(head, x, labels, model, pr)


@functools.lru_cache(maxsize=None)
def _grad_fn(family, model_items: tuple, precision: str):
    model = dict(model_items)
    pr = PRECISIONS[precision]
    return jax.jit(jax.value_and_grad(
        functools.partial(_loss, family, model, pr), argnums=(0, 1)))


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
_scale = jax.jit(lambda a, s: jax.tree.map(lambda x: x / s, a))


def train(family, model: dict, num_stages: int, seed: int,
          steps: Sequence[Dict[int, list]],
          completed: Sequence[Sequence[Tuple[int, int]]],
          optimizer: dict, *, precision: str = "f32",
          half_batch: bool = False) -> dict:
    """Run the reference over the given steps.

    ``steps[t][dn]`` are data node ``dn``'s microbatches fed to step
    ``t``; ``completed[t]`` lists the ``(dn, k)`` the program completed.
    ``half_batch`` plants the fault "half of the batch left out": only
    the first half of each node's completed microbatches are used and
    the mean is taken over them.
    Returns per-step losses, first-gradient norms and change norms.
    """
    grad = _grad_fn(family, tuple(sorted(model.items())), precision)
    stages, head0 = init_weights(family, model, num_stages, seed)
    stages0 = stages
    dns = sorted(steps[0])
    heads = {dn: head0 for dn in dns}
    opt = AdamWRef(**optimizer)
    s_state = [opt.init(p) for p in stages]
    h_state = {dn: opt.init(head0) for dn in dns}
    losses, first = [], None
    for t, (data, comp) in enumerate(zip(steps, completed), start=1):
        comp = list(comp)
        if half_batch:
            per = {dn: [c for c in comp if c[0] == dn] for dn in dns}
            comp = [c for dn in dns for c in per[dn][:max(1, len(per[dn]) // 2)]]
        acc_s, acc_h, n_dn, loss_sum = None, {}, {}, 0.0
        with jax.default_matmul_precision("highest"):
            for dn, k in comp:
                mb = data[dn][k]
                loss, (gs, gh) = grad(stages, heads[dn],
                                      jnp.asarray(mb["tokens"]),
                                      jnp.asarray(mb["labels"]))
                loss_sum += float(loss)
                acc_s = gs if acc_s is None else _add(acc_s, gs)
                acc_h[dn] = gh if dn not in acc_h else _add(acc_h[dn], gh)
                n_dn[dn] = n_dn.get(dn, 0) + 1
            losses.append(loss_sum / len(comp))
            g_stage = _scale(acc_s, float(len(comp)))
            new_stages, used = [], {}
            for s in range(num_stages):
                p, s_state[s], g = opt.step(stages[s], g_stage[s],
                                            s_state[s], float(t))
                new_stages.append(p)
                used[f"stage{s}"] = g
            stages = tuple(new_stages)
            for dn in acc_h:
                heads[dn], h_state[dn], g = opt.step(
                    heads[dn], _scale(acc_h[dn], float(n_dn[dn])),
                    h_state[dn], float(t))
                used[f"head{dn}"] = g
        if first is None:
            first = named_norms(used)
    trees = {f"stage{s}": p for s, p in enumerate(stages)}
    trees.update({f"head{dn}": h for dn, h in heads.items()})
    base = {f"stage{s}": p for s, p in enumerate(stages0)}
    base.update({f"head{dn}": head0 for dn in heads})
    return {"losses": losses, "grad_norms": first,
            "change_norms": named_norms(trees, minus=base)}


def _gaps(prog: Dict[str, float], ref: Dict[str, float], keep) -> dict:
    names = [n for n in ref if keep(n)]
    med = float(np.median([ref[n] for n in names]))
    gaps = {}
    for n in names:
        gap = abs(prog.get(n, 0.0) - ref[n]) / max(ref[n], med, 1e-30)
        gaps[n] = gap if np.isfinite(gap) else float("inf")
    return gaps


def readings(prog: dict, ref: dict) -> dict:
    """The compared numbers of ``prog`` against ``ref`` (both as
    ``train`` returns them), with the leaf that sets each worst-leaf
    number.  A missing step or a non-finite number reads as infinity.

    ``loss1_gap``: the first step's loss (the forward pass alone);
    ``loss_gap``: the worst step's loss; ``grad_gap`` / ``change_gap``:
    the worst leaf; ``grad_median_gap`` / ``change_median_gap``: the
    median leaf."""
    def rel(a, b):
        g = abs(a - b) / abs(b)
        return g if np.isfinite(g) else float("inf")

    inf = float("inf")
    same = len(prog["losses"]) == len(ref["losses"])
    steps = ([rel(a, b) for a, b in zip(prog["losses"], ref["losses"])]
             if same else [inf])
    g_ref = ref["grad_norms"]
    g_med = float(np.median(list(g_ref.values())))
    grad = _gaps(prog["grad_norms"], g_ref, lambda n: True)
    change = _gaps(prog["change_norms"], ref["change_norms"],
                   lambda n: g_ref[n] >= EXCLUDE_BELOW * g_med)
    g_leaf, c_leaf = max(grad, key=grad.get), max(change, key=change.get)
    return {"loss1_gap": steps[0], "loss_gap": max(steps),
            "grad_gap": grad[g_leaf], "change_gap": change[c_leaf],
            "grad_median_gap": float(np.median(list(grad.values()))),
            "change_median_gap": float(np.median(list(change.values()))),
            "grad_leaf": g_leaf, "change_leaf": c_leaf,
            "excluded": sorted(set(ref["change_norms"]) - set(change))}
