"""Arithmetic shared by the references: matrix products in a chosen
precision, the seeded weight draw, RMSNorm, and the program's optimizer
(its learning-rate schedule and AdamW steps)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: the largest normal value of an 8-bit float with 4 exponent and 3
#: mantissa bits (IEEE-style e4m3)
F8_MAX = 240.0


def _round_fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, F8_MAX / amax, 1.0)
    return jax.lax.reduce_precision(x * scale, exponent_bits=4,
                                    mantissa_bits=3) / scale


@jax.custom_vjp
def _fp8(x):
    """Round x to an 8-bit float (e4m3) with a per-tensor absmax scale; the
    gradient that flows back through it is rounded the same way, with a
    scale of its own, as scaled fp8 training does."""
    return _round_fp8(x)


_fp8.defvjp(lambda x: (_round_fp8(x), None),
            lambda _, g: (_round_fp8(g),))


def cast(x, prec: str):
    """A matrix product's operand in ``prec``: ``f32``; ``bf16``, rounded
    to bfloat16 (the configuration's own precision, a witness and not the
    control); or ``fp8``, the control."""
    x = x.astype(jnp.float32)
    if prec == "fp8":
        return _fp8(x)
    return to_bf16(x) if prec == "bf16" else x


def einsum(spec: str, a, b, prec: str = "f32"):
    return jnp.einsum(spec, cast(a, prec), cast(b, prec), precision=HIGHEST)


def mm(a, b, prec: str = "f32"):
    return jnp.matmul(cast(a, prec), cast(b, prec), precision=HIGHEST)


def to_bf16(x):
    """Round float32 to the nearest bfloat16 value, kept in float32.  Not
    ``astype(bfloat16).astype(float32)``: XLA may drop such a pair of
    converts as excess precision, and the rounding with it."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def he(key, shape, fan_in=None):
    """A bfloat16 weight drawn as N(0, 1/fan_in), returned in float32."""
    fan_in = fan_in if fan_in is not None else shape[0]
    w = jax.random.normal(key, shape, jnp.float32) * (1.0 / np.sqrt(fan_in))
    return to_bf16(w)


def ones(d):
    return jnp.ones((d,), jnp.float32)


def rmsnorm(x, scale, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def model_key(seed: int):
    """The root key a configuration's weights are drawn from."""
    return jax.random.PRNGKey(seed)


# --------------------------------------------------------------- optimizer

def lr_at(opt, step: int) -> float:
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + np.cos(np.pi * prog))
    return opt["lr"] * warm * cos


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "clip"))
def adamw(params, grads, m, v, step, lr, b1, b2, eps, wd, clip):
    """AdamW with global-norm clipping; decoupled decay on every leaf of
    two or more dimensions; parameters stored in bfloat16 as the
    configuration states.  Returns (params, m, v, pre-clip norm, clipped
    gradient)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-9))
    g = jax.tree.map(lambda t: t * scale, grads)
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, mm_, vv):
        delta = (mm_ / bc1) / (jnp.sqrt(vv / bc2) + eps)
        if p.ndim >= 2:
            delta = delta + wd * p
        return to_bf16(p - lr * delta)

    return jax.tree.map(upd, params, m, v), m, v, gnorm, g
