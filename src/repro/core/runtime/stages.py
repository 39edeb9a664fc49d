"""Per-stage compute: fused forward+residual dispatch, VJP backward.

The pre-refactor executor jitted the *entire* model end-to-end per
microbatch (``jax.value_and_grad`` over all stages at once), which has
no pipeline-stage structure: a crash anywhere forced rerunning the
whole graph, and B microbatches cost B full-model dispatches.

`StageCompute` lowers each pipeline stage to jitted primitives:

* ``forward_fused(s, params, x)`` — ONE dispatch that runs the stage's
  transformer blocks *and* captures the VJP residuals: ``jax.vjp``
  inside jit returns ``(out, vjp_fn)`` where ``vjp_fn`` is a
  ``jax.tree_util.Partial`` whose leaves are the residual arrays.  The
  primal output is bit-identical to the plain forward.
* ``backward_from_residuals(s, residuals, g)`` — pulls the cotangent
  ``g`` back through the stored residuals to ``(dparams, dx)``
  *without recomputing the forward*.  This is the default backward.
  (Inside Mamba layers the SSD runs under ``jax.checkpoint``: its
  residuals are its inputs, and this backward recomputes its chunk
  intermediates — see ``repro.models.ssm.ssd_chunked``.)
* ``forward(s, params, x)`` / ``backward(s, params, x, g)`` — the
  rematerialising pair kept as the in-engine equality oracle:
  ``backward`` re-runs the *same* compiled residual-capturing forward
  program and then the *same* compiled VJP program, so its result is
  bit-identical to the fused path by construction (program
  composition, not a separately compiled ``jax.vjp`` graph).  It is
  also the paper's Sec. V-D repair primitive: any replica holding the
  stage weights and the upstream activation can (re)produce the
  stage's backward.

A model with a ``layer_pattern`` (layers of different kinds, one mixer
each: Mamba-2, MoE, attention) holds each stage as ``{kind: that kind's
layers, stacked}``; its programs take the stage's kinds as a static
argument and run the layers one by one in published order, each under
``jax.named_scope("gwtf.<kind>")`` and ``jax.checkpoint``.  Models
without a pattern keep the one ``lax.scan`` over stacked blocks.

Attention: where the programs are lowered for a TPU, every causal
self-attention layer of both paths (no cache, no window, a sequence the
kernel tiles) runs through the fused flash kernel
(``repro.kernels.ops.fused_attention``), whose VJP saves the output and
the per-row softmax statistics and recomputes the scores block by block
in VMEM: ``fwd_res`` stores nothing of size S x S, and ``bwd_res``
writes none.  Elsewhere, the CPU included, the layers keep the XLA
attention (``_online_attention``) and the programs are unchanged.  The
choice is made in Python while the program is traced
(``layers.fused_attention_applies``), not by ``lax.platform_dependent``
or ``lax.cond``: under ``jax.vjp`` those keep the residuals of every
branch, the XLA branch's S x S arrays among them.  ``snapshot()``'s
``fused_attention`` counts, per stage, the layers that took the kernel.

Microbatches of the same stage are stacked along the batch axis, so B
microbatches cost one dispatch per stage instead of B full-model
dispatches.  Cotangents are donated to the backward dispatch on
backends that support buffer donation (stored activations and
residuals are *not* donated — recovery may replay them).

Dispatch counters (``fwd_calls``/``bwd_calls`` per stage) are the
ground truth for the recovery tests: a backward crash must add exactly
one stage-level dispatch, not a full-pipeline recompute.  A remat
backward additionally bumps ``remat_recomputes`` for the hidden
forward it re-runs; the fused path never does.

One set of jitted kernels serves every ``(ModelConfig, donate)`` pair
process-wide (``stage_kernels`` is ``lru_cache``d), so tests, the
scenario harness's runtime leg, and fuzz share compiled programs
instead of recompiling per trainer instance.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.transformer import (_apply_block, _apply_layer,
                                      _init_block, _init_layer, layer_kinds)


# ---------------------------------------------------------------------------
# Stage modules (moved verbatim from the pre-refactor executor)
# ---------------------------------------------------------------------------

def stage_bounds(cfg: ModelConfig, stage: int, num_stages: int):
    per = cfg.num_layers // num_stages
    extra = cfg.num_layers - per * num_stages
    lo = stage * per + min(stage, extra)
    hi = lo + per + (1 if stage < extra else 0)
    return lo, hi


def stage_kinds(cfg: ModelConfig, stage: int, num_stages: int):
    """The kinds of the stage's layers in published order, or None where
    the model has no ``layer_pattern`` (every layer one block)."""
    if not cfg.layer_pattern:
        return None
    lo, hi = stage_bounds(cfg, stage, num_stages)
    return tuple(layer_kinds(cfg)[lo:hi])


def stage_fused_attention(cfg: ModelConfig, stage: int, num_stages: int,
                          seq_len: int) -> int:
    """How many of the stage's attention layers its programs run through
    the fused kernel over ``seq_len`` tokens (``L.fused_attention_applies``):
    all of them or none."""
    kinds = stage_kinds(cfg, stage, num_stages)
    if kinds is None:
        lo, hi = stage_bounds(cfg, stage, num_stages)
        n = 0 if cfg.arch_type == "ssm" else hi - lo
    else:
        n = kinds.count("attention")
    return n if n and L.fused_attention_applies(seq_len, cfg.head_dim) else 0


def init_stage_params(cfg: ModelConfig, stage: int, num_stages: int, key):
    """Blocks [lo, hi) of the model as one stage (stacked for scan).  With
    a ``layer_pattern``: ``{kind: that kind's layers in the stage, stacked
    in layer order}``, a kind with no layer in the stage absent."""
    lo, hi = stage_bounds(cfg, stage, num_stages)
    keys = jax.random.split(jax.random.fold_in(key, stage), hi - lo)
    dtype = jnp.dtype(cfg.param_dtype)
    kinds = stage_kinds(cfg, stage, num_stages)
    if kinds is None:
        return jax.vmap(lambda kk: _init_block(kk, cfg, dtype))(keys)
    return {kind: jax.vmap(lambda kk, kind=kind: _init_layer(
        kk, cfg, kind, dtype))(keys[jnp.asarray(
            [i for i, k in enumerate(kinds) if k == kind])])
        for kind in dict.fromkeys(kinds)}


def stage_forward(stage_params, x, cfg: ModelConfig, kinds=None):
    """The stage's layers over ``x``.  ``kinds`` (``stage_kinds``) gives a
    patterned stage's layer order: layer j of a kind is slice j of that
    kind's stack, and each runs under the scope ``gwtf.<kind>`` and
    ``jax.checkpoint``, so that its residuals are its inputs and the
    backward recomputes the rest (a Mamba-2 and MoE stage would otherwise
    store about 1.5 GiB of residuals per 4 x 512 tokens)."""
    positions = jnp.arange(x.shape[1])
    if kinds is not None:
        seen = dict.fromkeys(stage_params, 0)
        for kind in kinds:
            p = jax.tree.map(lambda a, j=seen[kind]: a[j], stage_params[kind])
            seen[kind] += 1
            with jax.named_scope(f"gwtf.{kind}"):
                x = jax.checkpoint(lambda p, x, kind=kind: _apply_layer(
                    p, x, cfg, kind, positions=positions))(p, x)
        return x

    def body(carry, bp):
        h, _aux, _ = _apply_block(bp, carry, cfg, positions=positions,
                                  window=None, cache=None, write_index=None,
                                  kv_valid=None, moe_impl="dense")
        return h, None

    out, _ = jax.lax.scan(body, x, stage_params)
    return out


def init_head_params(cfg: ModelConfig, key):
    """Data-node module: embedding + final norm + LM head."""
    return {"embed": L.init_embed(key, cfg, jnp.dtype(cfg.param_dtype)),
            "final_norm": L.init_norm(cfg)}


def embed_fn(head_params, tokens):
    return L.embed_tokens(head_params["embed"], tokens)


def loss_fn(head_params, hidden, labels, cfg: ModelConfig):
    h = L.apply_norm(head_params["final_norm"], hidden, cfg)
    return L.chunked_xent_loss(head_params["embed"], h, labels, cfg)


def _donate_supported(backend: Optional[str] = None) -> bool:
    """Whether the (given or default) backend honours buffer donation.

    CPU silently ignores donation, so the flag is only *useful* on
    accelerators — but both code paths must stay correct everywhere;
    ``StageCompute(donate=...)`` can force either branch for tests.
    """
    b = backend if backend is not None else jax.default_backend()
    return b in ("gpu", "cuda", "rocm", "tpu")


class StageKernels(NamedTuple):
    """The jitted primitives for one ``(ModelConfig, donate)`` pair."""
    fwd: Any          # (p, x) -> out
    fwd_res: Any      # (p, x) -> (out, vjp Partial)   [residual capture]
    bwd_res: Any      # (vjp, g) -> (dp, dx)           [consumes residuals]
    embed: Any
    embed_bwd: Any
    head: Any


@lru_cache(maxsize=None)
def stage_kernels(cfg: ModelConfig, donate: bool) -> StageKernels:
    """Build (once per process) the jitted kernels for ``cfg``.

    jax retraces per parameter shape, so one kernel set serves every
    stage and every stage count; the cache key is the hashable frozen
    ``ModelConfig`` plus the donation flag.
    """
    def fwd_impl(p, x, kinds=None):
        return stage_forward(p, x, cfg, kinds)

    fwd = jax.jit(fwd_impl, static_argnums=2)

    def fwd_res_impl(p, x, kinds=None):
        # jax.vjp inside jit: the returned closure is a
        # jax.tree_util.Partial whose leaves are the residual arrays —
        # it round-trips the jit boundary as a pytree and can be fed
        # to bwd_res (possibly quantized in between).
        out, vjp = jax.vjp(lambda pp, xx: stage_forward(pp, xx, cfg, kinds),
                           p, x)
        return out, vjp

    fwd_res = jax.jit(fwd_res_impl, static_argnums=2)

    def bwd_res_impl(vjp, g):
        dp, dx = vjp(g)
        return dp, dx

    g_donate = (1,) if donate else ()
    bwd_res = jax.jit(bwd_res_impl, donate_argnums=g_donate)
    embed = jax.jit(embed_fn)

    def embed_bwd_impl(head_p, tokens, g):
        """Pull the stage-0 input cotangent back through the token
        embedding: the data node's share of the head gradient."""
        _, vjp = jax.vjp(lambda hp: embed_fn(hp, tokens), head_p)
        (dhp,) = vjp(g)
        return dhp

    # no donation: no output has the cotangent's shape, so XLA could
    # not reuse its buffer and would only warn
    embed_bwd = jax.jit(embed_bwd_impl)

    def head_impl(head_p, hidden, labels):
        """hidden: (B, mb, S, D); labels: (B, mb, S).

        Per-microbatch losses (each the mean over its own tokens,
        matching the centralized per-microbatch loss), with one VJP
        giving the head gradient summed over the B microbatches and
        the per-microbatch hidden cotangents.
        """
        def f(hp, h):
            losses = jax.vmap(
                lambda hh, ll: loss_fn(hp, hh, ll, cfg))(h, labels)
            return jnp.sum(losses), losses

        _, vjp, losses = jax.vjp(f, head_p, hidden, has_aux=True)
        g_head, g_hidden = vjp(jnp.float32(1.0))
        return losses, g_head, g_hidden

    head = jax.jit(head_impl)
    return StageKernels(fwd, fwd_res, bwd_res, embed, embed_bwd, head)


class StageCompute:
    """Per-stage primitives + dispatch accounting.

    Kernels are shared process-wide via :func:`stage_kernels`; counters
    are per instance and tracked at the call sites so recovery tests
    can pin exactly which stage recomputed and session-cached kernels
    cannot leak dispatch state across trainers or tests.
    """

    def __init__(self, cfg: ModelConfig, num_stages: int, *,
                 donate: Optional[bool] = None):
        self.cfg = cfg
        self.num_stages = num_stages
        self.donate = _donate_supported() if donate is None else donate
        self.fwd_calls: List[int] = [0] * num_stages
        self.bwd_calls: List[int] = [0] * num_stages
        self.remat_recomputes: List[int] = [0] * num_stages
        self.embed_calls = 0
        self.embed_bwd_calls = 0
        self.head_calls = 0
        # tokens per sequence of each stage's last dispatch (0: none yet)
        self._seq_len: List[int] = [0] * num_stages
        self._k = stage_kernels(cfg, self.donate)
        # each stage's kinds (None without a pattern), a static argument
        # of its programs
        self._kinds = [stage_kinds(cfg, s, num_stages)
                       for s in range(num_stages)]

    # ------------------------------------------------------------------
    def embed(self, head_params, tokens):
        self.embed_calls += 1
        return self._k.embed(head_params, tokens)

    def embed_backward(self, head_params, tokens, g):
        """Head-gradient contribution of the embedding lookup (the
        cotangent leaving stage 0's VJP)."""
        self.embed_bwd_calls += 1
        return self._k.embed_bwd(head_params, tokens, g)

    def forward(self, stage: int, params, x):
        """One plain dispatch of stage ``stage`` over a stacked batch
        (no residual capture — the remat path and forward repairs)."""
        self.fwd_calls[stage] += 1
        self._seq_len[stage] = x.shape[1]
        return self._k.fwd(params, x, self._kinds[stage])

    def forward_fused(self, stage: int, params, x) -> Tuple[Any, Any]:
        """One fused dispatch: ``(output, residuals)``.  The output is
        bit-identical to :meth:`forward`; the residuals (a
        ``jax.tree_util.Partial``) feed :meth:`backward_from_residuals`
        so the backward never re-runs the forward."""
        self.fwd_calls[stage] += 1
        self._seq_len[stage] = x.shape[1]
        return self._k.fwd_res(params, x, self._kinds[stage])

    def backward_from_residuals(self, stage: int, residuals, g
                                ) -> Tuple[Any, Any]:
        """Stage ``stage``'s VJP from stored residuals: zero forward
        recompute.  ``g`` is donated when ``self.donate``."""
        self.bwd_calls[stage] += 1
        return self._k.bwd_res(residuals, g)

    def backward(self, stage: int, params, x, g) -> Tuple[Any, Any]:
        """Rematerialising backward: replay stage ``stage``'s VJP from
        its stored input ``x``.

        Composed from the *same* compiled programs as the fused path
        (residual-capturing forward, then residual-consuming VJP), so
        fused and remat gradients are bit-identical — the in-engine
        equality oracle.  Counts one logical backward dispatch plus
        one ``remat_recomputes`` for the hidden forward.
        """
        self.bwd_calls[stage] += 1
        self.remat_recomputes[stage] += 1
        self._seq_len[stage] = x.shape[1]
        _, vjp = self._k.fwd_res(params, x, self._kinds[stage])
        return self._k.bwd_res(vjp, g)

    def head_loss(self, head_params, hidden, labels):
        self.head_calls += 1
        return self._k.head(head_params, hidden, labels)

    # ------------------------------------------------------------------
    @property
    def stage_dispatches(self) -> int:
        """Total logical stage-level dispatches (one per forward, one
        per backward — the unit the recovery tests count in; remat's
        hidden forward recompute is reported separately)."""
        return sum(self.fwd_calls) + sum(self.bwd_calls)

    @property
    def remat_recompute_count(self) -> int:
        """Forward recomputes hidden inside remat backwards — 0 on the
        fused path by construction."""
        return sum(self.remat_recomputes)

    def snapshot(self) -> Dict[str, Any]:
        """Dispatch counts, and per stage the attention layers its last
        dispatched programs ran through the fused kernel
        (``fused_attention``; 0 before any dispatch)."""
        fused = [stage_fused_attention(self.cfg, s, self.num_stages, n)
                 if n else 0 for s, n in enumerate(self._seq_len)]
        return dict(fwd=list(self.fwd_calls), bwd=list(self.bwd_calls),
                    remat=list(self.remat_recomputes),
                    embed=self.embed_calls, embed_bwd=self.embed_bwd_calls,
                    head=self.head_calls, fused_attention=fused)
