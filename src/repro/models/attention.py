"""Self-/cross-attention with GQA, qk-norm, sliding windows and KV caches.

The heavy math is delegated to ``repro.kernels.ops`` which dispatches to the
Pallas TPU kernels on TPU backends and to the pure-jnp reference elsewhere
(CPU tests, host dry-run) — same numerics, sharding-friendly einsums.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import GLOBAL, LOCAL, ModelConfig
from repro.models import rope as rope_lib
from repro.models.layers import dense_init, init_rmsnorm, rmsnorm_headwise


def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Dict[str, Any]:
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), 0, cfg.param_dtype),
        "wk": dense_init(ks[1], (d, hkv, hd), 0, cfg.param_dtype),
        "wv": dense_init(ks[2], (d, hkv, hd), 0, cfg.param_dtype),
        "wo": dense_init(ks[3], (h, hd, d), 0, cfg.param_dtype),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = init_rmsnorm(hd, cfg.param_dtype)
        p["k_norm"] = init_rmsnorm(hd, cfg.param_dtype)
    return p


def _window_for(cfg: ModelConfig, attn_kind: str) -> int:
    """Effective sliding window: 0 means full attention."""
    if attn_kind == LOCAL and cfg.sliding_window:
        return cfg.sliding_window
    if attn_kind == GLOBAL:
        return 0
    return cfg.sliding_window


def _rope_theta_for(cfg: ModelConfig, attn_kind: str) -> float:
    if attn_kind == LOCAL and cfg.local_rope_theta:
        return cfg.local_rope_theta
    return cfg.rope_theta


def _project_qkv(params, x, cfg: ModelConfig, positions, attn_kind: str, use_rope=True):
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if cfg.use_qk_norm:
        q = rmsnorm_headwise(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_headwise(params["k_norm"], k, cfg.norm_eps)
    if use_rope:
        cos, sin = rope_lib.rope_freqs(
            cfg.resolved_head_dim, _rope_theta_for(cfg, attn_kind), positions
        )
        q = rope_lib.apply_rope(q, cos, sin)
        k = rope_lib.apply_rope(k, cos, sin)
    return q, k, v


def _maybe_shard_heads(t, cfg: ModelConfig):
    """Constrain the head dim of an (B,S,H,D) activation onto the TP axis
    when H does not divide it: GSPMD pads uneven INTERMEDIATE shardings
    (36 heads -> 3/rank on 16 ranks, 48/36 = 1.33x pad waste) whereas the
    default layout replicated the whole S^2 attention 16x (§Perf iter 3)."""
    from repro.sharding import context as shctx

    ctx = shctx.get_activation_mesh()
    if ctx is None:
        return t
    mesh, axis = ctx
    tp = mesh.shape[axis]
    if t.shape[2] % tp == 0 or t.shape[2] == 1:
        return t           # evenly shardable (or MQA): GSPMD handles it
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    U = P.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(
        t, NamedSharding(mesh, P(U, U, axis, U)))


def self_attention(
    params,
    x,
    cfg: ModelConfig,
    attn_kind: str = GLOBAL,
    positions=None,
    return_kv: bool = False,
):
    """Full-sequence causal attention (training / prefill)."""
    from repro.kernels import ops

    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    q, k, v = _project_qkv(params, x, cfg, positions, attn_kind)
    q = _maybe_shard_heads(q, cfg)
    k = _maybe_shard_heads(k, cfg)
    v = _maybe_shard_heads(v, cfg)
    window = _window_for(cfg, attn_kind)
    out = ops.flash_attention(
        q, k, v,
        causal=True,
        window=window,
        scale=cfg.attn_scale or cfg.resolved_head_dim ** -0.5,
        softcap=cfg.logit_softcap,
    )
    out = _maybe_shard_heads(out, cfg)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    if return_kv:
        return y, (k, v)
    return y


# ------------------------------------------------------------------ cross-attn
def cross_attention(
    params,
    x,                       # (B, S, d)
    enc,                     # (B, T_img, d) stub patch/frame embeddings
    cfg: ModelConfig,
    kv_cached: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
):
    """Cross-attention to (stub) encoder states.  No positional rotation on
    image tokens (llama-3.2-vision style gated cross-attention, gate omitted
    in the reduced backbone spec; no causal mask over encoder tokens)."""
    from repro.kernels import ops

    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    if cfg.use_qk_norm:
        q = rmsnorm_headwise(params["q_norm"], q, cfg.norm_eps)
    if kv_cached is not None:
        k, v = kv_cached
        k = k.astype(dtype)
        v = v.astype(dtype)
    else:
        k = jnp.einsum("btd,dhk->bthk", enc, params["wk"].astype(dtype))
        v = jnp.einsum("btd,dhk->bthk", enc, params["wv"].astype(dtype))
        if cfg.use_qk_norm:
            k = rmsnorm_headwise(params["k_norm"], k, cfg.norm_eps)
    out = ops.flash_attention(
        q, k, v,
        causal=False,
        window=0,
        scale=cfg.attn_scale or cfg.resolved_head_dim ** -0.5,
        softcap=cfg.logit_softcap,
    )
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dtype))
    return y, (k, v)


def cross_kv(params, enc, cfg: ModelConfig):
    """Precompute encoder K/V once for the decode path."""
    dtype = enc.dtype
    k = jnp.einsum("btd,dhk->bthk", enc, params["wk"].astype(dtype))
    v = jnp.einsum("btd,dhk->bthk", enc, params["wv"].astype(dtype))
    if cfg.use_qk_norm:
        k = rmsnorm_headwise(params["k_norm"], k, cfg.norm_eps)
    return k, v
