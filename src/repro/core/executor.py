"""Trainer facades over the staged real-compute runtime (Fig. 6).

This module is the stable entry point for real-JAX decentralized
training; the implementation lives in the layered
:mod:`repro.core.runtime` package (``stages`` / ``activations`` /
``recovery`` / ``trainer`` — the same layered shape as
:mod:`repro.core.sim`):

* :class:`DecentralizedTrainer` — GWTF training over a
  :class:`~repro.core.flow.graph.FlowNetwork` with *per-stage* fused
  jitted execution: each stage forward is one residual-carrying
  dispatch (``jax.vjp`` closure capture), the backward consumes the
  stored residuals so it never recomputes the forward
  (``remat=True`` restores the rematerialising oracle), microbatches
  are stacked in depth-first dispatch chunks, boundary activations
  and residuals are stored per (chunk, stage) — optionally int8
  quantised via ``activation_codec="int8"`` — and mid-iteration
  crashes are repaired stage-locally: a forward crash recomputes only
  the crashed stage from the stored input, a backward crash replays
  that stage's VJP from the stored residuals on a substitute replica
  (paper Sec. V-D) with zero forward recompute.  Churn is sampled by the
  simulator's :class:`~repro.core.sim.faults.ChurnModel` layer and
  repair decisions come from its
  :class:`~repro.core.sim.policies.RoutingPolicy` layer, so the flow
  engine, the event simulator, and real compute share one
  fault/recovery vocabulary.  Microbatches whose relay has no live
  substitute are requeued onto another complete-flow chain when one
  exists (``IterationResult.rerouted``) instead of silently dropped.
* :class:`CentralizedTrainer` — the no-decentralization baseline; at
  churn 0 the decentralized trajectory coincides with it (the paper's
  convergence claim).

The simulator answers *how long*, this runtime answers *what is
learned*.
"""
from __future__ import annotations

# Stage modules (re-exported for compatibility with the pre-refactor API)
from repro.core.runtime.stages import (embed_fn, init_head_params,
                                       init_stage_params, loss_fn,
                                       stage_bounds, stage_forward)
from repro.core.runtime.trainer import (CentralizedTrainer, IterationResult,
                                        RuntimeTrainer)


class DecentralizedTrainer(RuntimeTrainer):
    """GWTF training over a FlowNetwork with real JAX compute.

    Drop-in facade: the pre-refactor constructor signature
    ``(cfg, net, *, churn, lr, seed, rng)`` still works, and
    ``iteration()`` returns the same ``IterationResult`` head fields
    (``loss``/``completed``/``launched``/``dropped``) extended with the
    runtime's reroute/recompute counters.  Keyword arguments of
    :class:`~repro.core.runtime.trainer.RuntimeTrainer` (``policy=``,
    ``churn_model=``, ``checkpoint_dir=``, ``batch_microbatches=``,
    ...) pass straight through.
    """


__all__ = [
    "CentralizedTrainer",
    "DecentralizedTrainer",
    "IterationResult",
    "RuntimeTrainer",
    "embed_fn",
    "init_head_params",
    "init_stage_params",
    "loss_fn",
    "stage_bounds",
    "stage_forward",
]
