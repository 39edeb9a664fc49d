"""Layered real-compute runtime for GWTF training (paper Sec. V).

The runtime splits the old monolithic executor into the same layered
shape as :mod:`repro.core.sim`:

* :mod:`repro.core.runtime.stages` — per-stage fused
  forward+residual and residual-consuming backward dispatches (true
  pipeline-stage semantics, no backward-time forward recompute; the
  rematerialising pair is kept as the in-engine equality oracle),
  with same-stage microbatch stacking so B microbatches cost one
  dispatch per stage;
* :mod:`repro.core.runtime.activations` — the per-(chunk, stage)
  boundary-activation + VJP-residual store (optionally int8+scale
  quantised) that makes the paper's stage-local recovery real;
* :mod:`repro.core.runtime.cache` — process-wide memoised stage
  kernels and initial parameters, shared by trainers, tests, and the
  scenario harness;
* :mod:`repro.core.runtime.recovery` — crash injection and repair
  driven by the shared :class:`~repro.core.sim.faults.ChurnModel` and
  :class:`~repro.core.sim.policies.RoutingPolicy`/``FaultView``
  layers, including requeue-instead-of-drop;
* :mod:`repro.core.runtime.trainer` — gradient aggregation, AdamW
  updates, periodic per-stage checkpoints and joining-node bootstrap
  via :func:`repro.checkpoint.store.restore_stage`.

``repro.core.executor`` re-exports the drop-in trainer facades.
"""
from repro.core.runtime.activations import ActivationStore
from repro.core.runtime.recovery import RecoveryManager, Resolution
from repro.core.runtime.stages import StageCompute
from repro.core.runtime.trainer import (CentralizedTrainer, IterationResult,
                                        RuntimeTrainer)

__all__ = [
    "ActivationStore",
    "CentralizedTrainer",
    "IterationResult",
    "RecoveryManager",
    "Resolution",
    "RuntimeTrainer",
    "StageCompute",
]
