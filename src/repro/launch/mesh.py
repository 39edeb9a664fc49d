"""Production mesh construction (TPU v5e pods).

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first init).

Single pod : (16, 16)    axes (data, model)  = 256 chips
Multi-pod  : (2, 16, 16) axes (pod, data, model) = 512 chips
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    # Auto axes: the model code places tensors with with_sharding_constraint,
    # which refuses the Explicit axes jax.make_mesh defaults to
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(devices=None):
    """A (1, n) ("data", "model") mesh over ``devices`` (default: every
    local device) — tests, smoke runs, one host."""
    devices = list(jax.devices() if devices is None else devices)
    return _auto_mesh((1, len(devices)), ("data", "model"), devices)


# TPU v5e hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link
