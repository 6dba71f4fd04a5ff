"""Pallas TPU fused RMSNorm (+ gemma-style (1+scale) gain).

Fuses the mean-square reduction, rsqrt and gain multiply in one VMEM pass —
on TPU the unfused form costs three HBM round-trips of the activation; the
fused kernel reads x once and writes y once (2 x S x D bytes total).

Grid: (rows / block_rows,); each step streams a (block_rows, D) tile
HBM->VMEM, reduces along D on the VPU in f32, writes the normalized tile.
Validated with ``interpret=True`` against the jnp reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                 # (rows, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    gain = 1.0 + scale_ref[...].astype(jnp.float32)    # (1, D)
    o_ref[...] = (y * gain).astype(o_ref.dtype)


def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = False):
    """x: (..., D); scale: (D,) -> same shape as x."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    pad = -rows % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    n = (rows + pad) // block_rows

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x2, scale.reshape(1, d))
    return out[:rows].reshape(orig_shape)
