"""Plain float32 reference of a Nemotron-H model, a test oracle only.

The layer equations are those of the chip benchmark's family
(``benchmarks/chip/families/nemotron_h.py``: ``jax.numpy``, no kernel,
cache or batching, and nothing of the program imported), written from the
published ``config.json`` and the ``nemotron_h`` modeling code.  This
module runs them over a model given as a ``ModelConfig``, in float32 at
the highest matmul precision: embedding, every layer of the stages in
published order, the final norm and the loss.  Stages and head are in
the program's layout (``{kind: layers stacked}`` a stage).
"""
import dataclasses
import sys
from pathlib import Path

import jax

ROOT = str(Path(__file__).resolve().parents[1])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import reference  # noqa: E402
from benchmarks.chip.families import nemotron_h as family  # noqa: E402
from benchmarks.chip.families.refmath import F32  # noqa: E402


def model_of(cfg):
    """The family's model dict of a ``ModelConfig``."""
    return dataclasses.asdict(cfg)


def loss(stages, head, tokens, labels, cfg):
    """Mean token cross-entropy of the whole model."""
    with jax.default_matmul_precision("highest"):
        return reference._loss(family, model_of(cfg), F32, list(stages),
                               head, tokens, labels)


def moe_mixer(p, h, cfg):
    """One MoE layer's mixer (no norm, no residual) over ``h``."""
    with jax.default_matmul_precision("highest"):
        return family._moe(p, h, model_of(cfg), F32)


def weights(cfg, num_stages, seed):
    """(stages, head) of the family from ``seed``, in the program's
    layout."""
    return reference.init_weights(family, model_of(cfg), num_stages, seed)
