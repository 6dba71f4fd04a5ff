"""Core layers: initializers, norms, MLPs, embeddings.

Functional style: every layer is ``init_*(key, ...) -> params`` plus an
``apply``-like function taking the params dict.  Params are plain nested
dicts of jnp arrays so they stay trivially compatible with jax.tree utilities,
sharding-spec trees and our checkpointing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig


# ----------------------------------------------------------------- init utils
def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    """Truncated-normal fan-in init (maxtext-style 1/sqrt(fan_in))."""
    fan_in = shape[in_axis]
    std = 1.0 / np.sqrt(fan_in)
    # ``std`` is a float64 numpy scalar: without the cast it would promote
    # a bf16 draw to f32
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                              dtype=dtype)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=dtype)


# ---------------------------------------------------------------------- norms
def init_rmsnorm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.zeros((dim,), dtype=dtype)}  # gemma-style (1+scale)


def rmsnorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dtype)


def rmsnorm_headwise(params, x, eps: float = 1e-6):
    """qk-norm: normalize the trailing head_dim of (..., H, D) tensors."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dtype)


# ----------------------------------------------------------------------- mlps
def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(k1, (d, f), 0, cfg.param_dtype),
        "w_down": dense_init(k2, (f, d), 0, cfg.param_dtype),
    }
    if cfg.mlp_kind in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(k3, (d, f), 0, cfg.param_dtype)
    return p


def mlp(params, x, kind: str = "swiglu"):
    dtype = x.dtype
    up = x @ params["w_up"].astype(dtype)
    if kind == "swiglu":
        act = jax.nn.silu(x @ params["w_gate"].astype(dtype)) * up
    elif kind == "geglu":
        act = jax.nn.gelu(x @ params["w_gate"].astype(dtype)) * up
    elif kind == "gelu":
        act = jax.nn.gelu(up)
    else:
        raise ValueError(kind)
    return act @ params["w_down"].astype(dtype)


# ----------------------------------------------------------------- embeddings
def init_embedding(key, cfg: ModelConfig):
    return {"table": embed_init(key, (cfg.vocab_size, cfg.d_model), cfg.param_dtype)}


def embed(params, tokens, cfg: ModelConfig):
    out = jnp.take(params["table"].astype(cfg.dtype), tokens, axis=0)
    return out * jnp.asarray(np.sqrt(cfg.d_model), cfg.dtype)


def logits_head(params, x, cfg: ModelConfig, head_params=None):
    """Project to vocab. Tied: reuse embedding table; untied: own matrix.
    Tied logits are scaled 1/sqrt(d) (the transpose of the embed-side
    sqrt(d) scaling) so initial CE sits at ~ln(V)."""
    if head_params is not None:
        w = head_params["w"].astype(x.dtype)      # (d_model, vocab)
        return x @ w
    scale = jnp.asarray(1.0 / np.sqrt(cfg.d_model), x.dtype)
    return (x * scale) @ params["table"].astype(x.dtype).T


def init_logits_head(key, cfg: ModelConfig):
    return {"w": dense_init(key, (cfg.d_model, cfg.vocab_size), 0, cfg.param_dtype)}
