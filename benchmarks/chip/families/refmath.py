"""Plain float32 building blocks shared by the reference model families.

Every contraction goes through a precision object, so one reference
serves two purposes: ``F32`` (float32 operands at the highest matmul
precision) is the reference that decides ``correct``, and ``FP8`` is the
control: the same mathematics with every contraction's operands rounded
to float8 e4m3 and every contraction's incoming cotangent rounded to
float8 e5m2, each with a per-tensor scale (the usual fp8 training
recipe).  Nothing here imports the program under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _round_scaled(x, dtype, fmax):
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, amax / fmax, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _q_operand(x):
    return _round_scaled(x, jnp.float8_e4m3fn, E4M3_MAX)


def _q_operand_fwd(x):
    return _q_operand(x), None


def _q_operand_bwd(_, g):
    return (g,)


_q_operand.defvjp(_q_operand_fwd, _q_operand_bwd)


@jax.custom_vjp
def _q_cotangent(y):
    return y


def _q_cotangent_fwd(y):
    return y, None


def _q_cotangent_bwd(_, g):
    return (_round_scaled(g, jnp.float8_e5m2, E5M2_MAX),)


_q_cotangent.defvjp(_q_cotangent_fwd, _q_cotangent_bwd)


class F32:
    name = "f32"

    @staticmethod
    def ein(spec, *ops):
        return jnp.einsum(spec, *(o.astype(jnp.float32) for o in ops),
                          precision=HIGHEST)


class FP8:
    name = "fp8"

    @staticmethod
    def ein(spec, *ops):
        ops = [_q_operand(o.astype(jnp.float32)) for o in ops]
        return _q_cotangent(jnp.einsum(spec, *ops, precision=HIGHEST))


PRECISIONS = {"f32": F32, "fp8": FP8}


def layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def cross_entropy(logits, labels):
    """Mean token cross-entropy; logits (..., V) float32."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
