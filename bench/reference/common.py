"""Pieces shared by the plain references: float32 matmuls at the highest
precision, RMS norm, and the fp8 stand-in that computes the control."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
FP8_MAX = 448.0                          # largest float8_e4m3fn value
# Random weights: the embedding is small (rms 0.1 at the first layer's
# input), so that the layers and not a copy of the input token decide the
# next token, and the final norm's weight brings the tied head's logits to
# a spread of about LOGIT_STD, as peaked as a trained model's.
EMBED_RMS = 0.1
LOGIT_STD = 2.0


def fp8(x, axis):
    """``x`` rounded to float8 e4m3 with one scale per slice along
    ``axis`` (the max of the slice maps to the format's max): what an fp8
    matmul's operands hold."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
    return q * scale


def mm(x, w, control: bool):
    """``x @ w`` in float32.  The control rounds both operands to fp8
    first: ``x`` per row (token), ``w`` per output column."""
    x = x.astype(F32)
    w = w.astype(F32)
    if control:
        x = fp8(x, -1)
        w = fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms(x, scale, eps):
    """RMS norm over the last axis with the stored offset scale
    (``weight = 1 + scale``)."""
    x = x.astype(F32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * (1.0 + scale.astype(F32))


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def small(key, shape, dtype, width=0.1):
    """Norm offsets: uniform in [-width, width], so that every norm weight
    takes part in the comparison."""
    return jax.random.uniform(key, shape, F32, -width, width).astype(dtype)


def final_scale(key, d, dtype):
    """The final norm's stored scale: weight ``LOGIT_STD / table std``."""
    weight = LOGIT_STD * d ** 0.5 / EMBED_RMS
    return (weight - 1.0 + small(key, (d,), F32) * weight).astype(dtype)
