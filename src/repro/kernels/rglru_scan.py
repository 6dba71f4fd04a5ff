"""Pallas TPU kernel for the RG-LRU linear recurrence (Griffin).

h_t = a_t * h_{t-1} + beta_t * i_t * x_t   with a_t = exp(log_a_t), diagonal.

TPU mapping: grid = (batch, chunks, d_blocks); the chunk dimension is
``arbitrary`` (sequential) and a (1, block_d) VMEM scratch carries the
running hidden state.  Inside a chunk the recurrence is parallelized by
**doubling** (Blelloch-style): log2(Q) vectorized combine steps instead of Q
sequential steps — an elementwise scan is VPU work, so the doubling form
turns a latency-bound loop into ~log2(Q) full-width vector ops.

The wrapper pre-computes b_t = beta_t * i_t * x_t so the kernel is purely the
scan.  Validated with ``interpret=True`` against ``ref.rglru_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, y_ref, h_scr, *, chunk):
    ci = pl.program_id(2)   # chunk dim is innermost so h carries per d-block

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)       # (Q, Dblk) prefix-combinable decay
    b = b_ref[0].astype(jnp.float32)       # (Q, Dblk)

    # parallel prefix scan by doubling: after the loop,
    #   a[i] = prod_{j<=i} a_j ;  b[i] = scan(h0=0)[i]
    shift = 1
    while shift < chunk:
        a_prev = jnp.pad(a, ((shift, 0), (0, 0)), constant_values=1.0)[:chunk]
        b_prev = jnp.pad(b, ((shift, 0), (0, 0)), constant_values=0.0)[:chunk]
        b = b + a * b_prev
        a = a * a_prev
        shift *= 2

    h_prev = h_scr[...]                    # (1, Dblk)
    h_all = b + a * h_prev                 # (Q, Dblk): full states
    y_ref[0] = h_all.astype(y_ref.dtype)
    h_scr[...] = h_all[-1:][...]


def rglru(x, log_a, gate_x, *, chunk=256, block_d=None, interpret=False):
    """x, log_a, gate_x: (B, S, D) -> scanned hidden states (B, S, D)."""
    bsz, s, d = x.shape
    chunk = min(chunk, s)
    pad = -s % chunk
    f32 = jnp.float32

    a = jnp.exp(log_a.astype(f32))
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a.astype(f32)), 0.0))
    b = beta * gate_x.astype(f32) * x.astype(f32)
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk
    block_d = block_d or min(d, 512)
    dpad = -d % block_d
    if dpad:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, dpad)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, 0), (0, dpad)))
    nd = (d + dpad) // block_d

    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(bsz, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, d_, c_: (b_, c_, d_)),
            pl.BlockSpec((1, chunk, block_d), lambda b_, d_, c_: (b_, c_, d_)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d),
                               lambda b_, d_, c_: (b_, c_, d_)),
        out_shape=jax.ShapeDtypeStruct((bsz, s + pad, d + dpad), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
    return y[:, :s, :d]
