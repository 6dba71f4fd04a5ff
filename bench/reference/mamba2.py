"""Plain float32 reference of the attention-free Mamba2 stack the serving
program runs as mamba2-780m (arXiv:2405.21060, hf:state-spaces/mamba2-780m):
pre-norm layers of the Mamba2 mixer — one in-projection to (z, xBC, dt), a
causal depthwise conv over xBC, SiLU, the selective state-space recurrence
with a scalar decay per head and one B/C group, a skip ``D x``, a gated
RMS norm ``norm(y * silu(z))`` and an out-projection; no MLP; tied
embeddings.

The recurrence runs token by token (``h = exp(dt A) h + dt B x^T``,
``y = C h + D x``), not in the chunked dual form the program's kernel uses.
The program's conventions apply as in the dense reference: input
embedding times ``sqrt(d_model)``, tied head times ``1/sqrt(d_model)``,
norm weight ``1 + scale``.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference.common import (EMBED_RMS, F32, final_scale, mm, normal,
                                    rms, small)

FAMILY = "ssm"
Q_BLOCK = 1                        # no attention: any length will do


def _sizes(dims: Dict):
    d_inner = dims["ssm_expand"] * dims["d_model"]
    n = dims["ssm_state"]
    heads = d_inner // dims["ssm_head_dim"]
    return d_inner, heads, n, d_inner + 2 * n


def init_params(key, dims: Dict, dtype):
    """Random weights in the program's layout: dt drawn log-uniform in
    [0.001, 0.1] and stored as its inverse softplus, A = -exp(a_log) with
    exp(a_log) uniform in [1, 16], as Mamba2 initialises them."""
    d, n_layers, v = dims["d_model"], dims["num_layers"], dims["vocab_size"]
    d_inner, heads, n, conv_dim = _sizes(dims)
    width = dims["conv_width"]
    ks = iter(jax.random.split(key, 16))
    dt = jnp.exp(jax.random.uniform(next(ks), (n_layers, heads), F32,
                                    math.log(1e-3), math.log(1e-1)))
    ssm = {
        "in_proj": normal(next(ks), (n_layers, d, 2 * d_inner + 2 * n + heads),
                          d ** -0.5, dtype),
        "conv_w": normal(next(ks), (n_layers, width, conv_dim), width ** -0.5,
                         dtype),
        "conv_b": small(next(ks), (n_layers, conv_dim), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "a_log": jnp.log(jax.random.uniform(next(ks), (n_layers, heads), F32,
                                            1.0, 16.0)).astype(dtype),
        "d_skip": (1.0 + small(next(ks), (n_layers, heads), F32)).astype(dtype),
        "norm": {"scale": small(next(ks), (n_layers, d_inner), dtype)},
        "out_proj": normal(next(ks), (n_layers, d_inner, d), d_inner ** -0.5,
                           dtype),
    }
    return {
        "embed": {"table": normal(next(ks), (v, d), EMBED_RMS * d ** -0.5,
                                  dtype)},
        "periods": ({"norm1": {"scale": small(next(ks), (n_layers, d), dtype)},
                     "ssm": ssm},),
        "tail": (),
        "final_norm": {"scale": final_scale(next(ks), d, dtype)},
    }


def _mixer(p, h, dims: Dict, control: bool):
    """One Mamba2 mixer over the sequence h: (L, d)."""
    d_inner, heads, n, conv_dim = _sizes(dims)
    hp, width = dims["ssm_head_dim"], dims["conv_width"]
    seq = h.shape[0]
    zxbcdt = mm(h, p["in_proj"], control)
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:d_inner + conv_dim]
    dt = zxbcdt[:, d_inner + conv_dim:]
    w = p["conv_w"].astype(F32)
    padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = sum(padded[i:i + seq] * w[i] for i in range(width))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(F32))
    xs = xbc[:, :d_inner].reshape(seq, heads, hp)
    b = xbc[:, d_inner:d_inner + n]
    c = xbc[:, d_inner + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))      # (L, H)
    a = -jnp.exp(p["a_log"].astype(F32))                     # (H,)
    d_skip = p["d_skip"].astype(F32)

    def step(state, inp):                                    # state (H, N, P)
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + b_t[None, :, None] * (dt_t[:, None] * x_t)[:, None, :])
        y = jnp.einsum("n,hnp->hp", c_t, state,
                       precision=jax.lax.Precision.HIGHEST)
        return state, y + d_skip[:, None] * x_t

    state0 = jnp.zeros((heads, n, hp), F32)
    _, y = jax.lax.scan(step, state0, (xs, dt, b, c))
    y = rms(y.reshape(seq, d_inner) * jax.nn.silu(z), p["norm"]["scale"],
            dims["norm_eps"])
    return mm(y, p["out_proj"], control)


def logits(params, dims: Dict, tokens, start, n: int, control: bool = False):
    """Logits (n, V) in float32 at positions ``start .. start + n - 1`` of
    ``tokens`` (L,).  ``control`` computes every linear layer from
    fp8-rounded operands."""
    d, eps = dims["d_model"], dims["norm_eps"]
    table = params["embed"]["table"]
    x = table[tokens].astype(F32) * math.sqrt(d)

    def layer(x, p):
        h = rms(x, p["norm1"]["scale"], eps)
        return x + _mixer(p["ssm"], h, dims, control), None

    x, _ = jax.lax.scan(layer, x, params["periods"][0])
    x = jax.lax.dynamic_slice_in_dim(x, start, n)
    x = rms(x, params["final_norm"]["scale"], eps) / math.sqrt(d)
    return mm(x, table.T, control)
