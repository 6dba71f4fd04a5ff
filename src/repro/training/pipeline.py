"""Pipeline parallelism: SPMD GPipe over a mesh axis.

The layer stack is already stored period-stacked (R periods of the repeating
block pattern), so pipelining falls out naturally: shard the period dim over
a ``stage`` mesh axis (R/S periods per stage) and rotate activations with
``ppermute`` on a GPipe schedule — M microbatches drain in M + S - 1 rotor
steps, bubble fraction (S-1)/(M+S-1).

This is the collective-permute pipelining formulation (every stage runs the
same program; stage identity comes from ``axis_index``), the TPU-idiomatic
way to express PP without per-stage programs.  On the production mesh the
``pod`` axis can serve as the stage axis (2 stages across pods — cross-pod
DCN carries only the (mb, S, D) activation cut, the cheapest possible
inter-pod traffic pattern).

Scope: embedding / tail layers / final norm / head run outside the pipeline
region (data-parallel); the pipelined region is the scanned period stack.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.config import ModelConfig
from repro.models import blocks as blk


def _stage_apply(slot_params_stack, x, cfg: ModelConfig, positions):
    """Run this stage's R/S periods over x. slot_params_stack: tuple of
    per-slot trees with leading dim R/S."""
    period_kinds = cfg.period_kinds()

    def period_body(carry, slot_params):
        x = carry
        for si, (kind, akind) in enumerate(period_kinds):
            x, _ = blk.apply_block(slot_params[si], x, cfg, kind, akind,
                                   positions=positions)
        return x, None

    x, _ = jax.lax.scan(period_body, x, slot_params_stack)
    return x


def gpipe_apply(mesh, stage_axis: str, periods_params, x_mb,
                cfg: ModelConfig):
    """Pipeline the period stack over ``stage_axis``.

    periods_params: tuple of per-slot stacked trees, leading dim R
                    (sharded over stage_axis -> R/S per stage).
    x_mb: (M, mb, S, D) microbatched embedded activations (replicated over
          the stage axis).
    Returns (M, mb, S, D) outputs of the full stack.
    """
    n_stages = mesh.shape[stage_axis]
    m = x_mb.shape[0]
    assert cfg.num_periods % n_stages == 0, (cfg.num_periods, n_stages)

    def body(params_local, mbs_local):
        s_idx = jax.lax.axis_index(stage_axis)
        seq = mbs_local.shape[2]
        positions = jnp.arange(seq, dtype=jnp.int32)
        total = m + n_stages - 1

        def step(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (clip keeps shapes static)
            inject = mbs_local[jnp.clip(t, 0, m - 1)]
            x_in = jnp.where(s_idx == 0, inject, state)
            y = _stage_apply(params_local, x_in, cfg, positions)
            # last stage emits microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            valid = jnp.logical_and(s_idx == n_stages - 1,
                                    t >= n_stages - 1)
            prev = outputs[out_idx]
            outputs = outputs.at[out_idx].set(jnp.where(valid, y, prev))
            # rotate activations to the next stage
            state = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (state, outputs), None

        state0 = jnp.zeros_like(mbs_local[0])
        out0 = jnp.zeros_like(mbs_local)
        (state, outputs), _ = jax.lax.scan(step, (state0, out0),
                                           jnp.arange(total))
        # only the last stage holds real outputs; broadcast them to all
        # stages (masked psum) so the post-pipeline region is replicated.
        outputs = jnp.where(s_idx == n_stages - 1, outputs, 0.0)
        return jax.lax.psum(outputs, stage_axis)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(stage_axis), periods_params),
                  P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(periods_params, x_mb)


def pipeline_forward(mesh, stage_axis: str, params, tokens,
                     cfg: ModelConfig, num_microbatches: int):
    """Full LM forward with the period stack pipelined.

    Embedding, tail layers, final norm and logits run outside the pipeline
    (replicated over the stage axis).  Returns logits (B, S, V).
    """
    from repro.models import layers as lyr

    b, s = tokens.shape[0], tokens.shape[1]
    assert b % num_microbatches == 0
    x = lyr.embed(params["embed"], tokens, cfg) if tokens.ndim == 2 \
        else tokens.astype(cfg.dtype)
    x_mb = x.reshape(num_microbatches, b // num_microbatches, s, -1)

    x_mb = gpipe_apply(mesh, stage_axis, params["periods"], x_mb, cfg)
    x = x_mb.reshape(b, s, -1)

    positions = jnp.arange(s, dtype=jnp.int32)
    for ti, (kind, akind) in enumerate(cfg.tail_kinds()):
        x, _ = blk.apply_block(params["tail"][ti], x, cfg, kind, akind,
                               positions=positions)
    x = lyr.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lyr.logits_head(params["embed"], x, cfg, params.get("head"))


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """GPipe bubble overhead: (S-1)/(M+S-1)."""
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
