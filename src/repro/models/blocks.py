"""Composable decoder blocks.

A block = temporal mixer (self-attn | cross-attn | SSD | RG-LRU) + optional
channel mixer (dense MLP or MoE), both pre-RMSNorm with residuals.  Every
block kind exposes three entry points used by the model:

  init_block(...)          -> params
  apply_block(...)         -> (y, aux)                (train / prefill)
  apply_block_decode(...)  -> (y, new_cache)          (single-token decode)
  init_block_cache(...)    -> cache pytree
  apply_block_prefill(...) -> (y, aux, cache)         (prefill filling cache)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ATTN, CROSS, GLOBAL, LOCAL, RGLRU, SSM, ModelConfig
from repro.kernels import ops, ref
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import init_mlp, init_rmsnorm, mlp, rmsnorm


def _has_mlp(cfg: ModelConfig, kind: str) -> bool:
    return cfg.d_ff > 0 or cfg.num_experts > 0


def _is_moe(cfg: ModelConfig, kind: str) -> bool:
    return cfg.num_experts > 0 and kind in (ATTN, CROSS)


# ------------------------------------------------------------------------ init
def init_block(key, cfg: ModelConfig, kind: str, attn_kind: str) -> Dict[str, Any]:
    ks = jax.random.split(key, 3)
    p: Dict[str, Any] = {"norm1": init_rmsnorm(cfg.d_model, cfg.param_dtype)}
    if kind in (ATTN, CROSS):
        p["attn"] = attn_lib.init_attention(ks[0], cfg, cross=kind == CROSS)
    elif kind == SSM:
        p["ssm"] = ssm_lib.init_ssm(ks[0], cfg)
    elif kind == RGLRU:
        p["rec"] = rglru_lib.init_rglru_block(ks[0], cfg)
    else:
        raise ValueError(kind)
    if _has_mlp(cfg, kind):
        p["norm2"] = init_rmsnorm(cfg.d_model, cfg.param_dtype)
        if _is_moe(cfg, kind):
            p["moe"] = moe_lib.init_moe(ks[1], cfg)
        else:
            p["mlp"] = init_mlp(ks[1], cfg)
    return p


def _channel_mix(p, x, cfg: ModelConfig, kind: str, num_groups: int):
    if not _has_mlp(cfg, kind):
        return x, jnp.zeros((), jnp.float32)
    h = rmsnorm(p["norm2"], x, cfg.norm_eps)
    if "moe" in p:
        y, aux = moe_lib.moe_ffn(p["moe"], h, cfg, num_groups=num_groups)
    else:
        y, aux = mlp(p["mlp"], h, cfg.mlp_kind), jnp.zeros((), jnp.float32)
    return x + y, aux


# -------------------------------------------------------------- train/prefill
def apply_block(p, x, cfg: ModelConfig, kind: str, attn_kind: str, *,
                positions=None, enc=None, num_groups: int = 1):
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if kind == ATTN:
        x = x + attn_lib.self_attention(p["attn"], h, cfg, attn_kind, positions)
    elif kind == CROSS:
        y, _ = attn_lib.cross_attention(p["attn"], h, enc, cfg)
        x = x + y
    elif kind == SSM:
        x = x + ssm_lib.ssm_mixer(p["ssm"], h, cfg)
    elif kind == RGLRU:
        x = x + rglru_lib.rglru_block(p["rec"], h, cfg)
    return _channel_mix(p, x, cfg, kind, num_groups)


# --------------------------------------------------------------------- caches
def _attn_cache_len(cfg: ModelConfig, attn_kind: str, capacity: int) -> int:
    if attn_kind == LOCAL and cfg.sliding_window:
        return min(capacity, cfg.sliding_window)
    return capacity


def init_block_cache(cfg: ModelConfig, kind: str, attn_kind: str,
                     batch: int, capacity: int) -> Dict[str, Any]:
    hd, hkv = cfg.resolved_head_dim, cfg.num_kv_heads
    if kind == ATTN:
        n = _attn_cache_len(cfg, attn_kind, capacity)
        return {
            "k": jnp.zeros((batch, n, hkv, hd), cfg.dtype),
            "v": jnp.zeros((batch, n, hkv, hd), cfg.dtype),
            # per-lane ring-slot absolute positions (-1 = empty): lanes of a
            # continuous batch sit at independent depths
            "pos": jnp.full((batch, n), -1, jnp.int32),
        }
    if kind == CROSS:
        t = cfg.num_image_tokens
        h = cfg.num_kv_heads
        return {
            "k": jnp.zeros((batch, t, h, hd), cfg.dtype),
            "v": jnp.zeros((batch, t, h, hd), cfg.dtype),
        }
    if kind == SSM:
        return ssm_lib.init_ssm_cache(cfg, batch)
    if kind == RGLRU:
        return rglru_lib.init_rglru_cache(cfg, batch)
    raise ValueError(kind)


# --------------------------------------------------------------------- decode
def apply_block_decode(p, x, cache, cfg: ModelConfig, kind: str, attn_kind: str,
                       *, cache_index, num_groups: int = 1, block_tables=None):
    """x: (B, 1, D).  Returns (y, new_cache, aux).

    ``cache_index`` is a scalar (all lanes at the same position) or a
    per-lane ``(B,)`` vector: lane b inserts its KV at ``cache_index[b]``
    and masks against its own length — the continuous-batching decode path.

    With ``block_tables`` ((B, max_pages) int32 page ids, -1 = absent) an
    attention block's cache is a **paged pool** ``{"k"/"v": (P, page,
    Hkv, D), "pos": (P, page)}`` shared by all lanes instead of per-lane
    rings: lane b's new KV is scattered into the pool row its table names
    for position ``cache_index[b]`` and attention gathers through the
    table (``ops.paged_decode_attention``).  A lane whose table slot is
    -1 (freed lane) writes to the pool's dump row (the last row, which no
    table ever references) so the batched step stays scatter-shaped
    without corrupting live pages.
    """
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    new_cache = cache
    if kind == ATTN:
        b = x.shape[0]
        cache_index = jnp.asarray(cache_index, jnp.int32)
        idx = jnp.broadcast_to(cache_index, (b,))
        # project + rope at each lane's absolute position
        positions = idx[:, None]                               # (B, 1)
        q, k, v = attn_lib._project_qkv(p["attn"], h, cfg, positions, attn_kind)
        window = attn_lib._window_for(cfg, attn_kind)
        scale = cfg.attn_scale or cfg.resolved_head_dim ** -0.5

        from repro.sharding import context as shctx
        serving = shctx.get_serving_mesh()
        if block_tables is not None:
            tables = jnp.asarray(block_tables, jnp.int32)      # (B, maxp)
            page = cache["k"].shape[1]
            dump = cache["k"].shape[0] - 1
            maxp = tables.shape[1]
            lanes = jnp.arange(b)
            entry = tables[lanes, jnp.minimum(idx // page, maxp - 1)]
            rows = jnp.where(entry >= 0, entry, dump)          # (B,)
            within = idx % page
            if serving is not None:
                from repro.serving.spmd_decode import spmd_paged_decode_attention
                mesh, b_ax, s_ax = serving
                out, k_cache, v_cache, pos = spmd_paged_decode_attention(
                    mesh, q, cache["k"], cache["v"], cache["pos"], tables,
                    k, v, rows, within, idx, window=window, scale=scale,
                    softcap=cfg.logit_softcap, batch_axis=b_ax, seq_axis=s_ax)
            else:
                k_cache = cache["k"].at[rows, within].set(
                    k[:, 0].astype(cache["k"].dtype))
                v_cache = cache["v"].at[rows, within].set(
                    v[:, 0].astype(cache["v"].dtype))
                pos = cache["pos"].at[rows, within].set(idx)
                out = ops.paged_decode_attention(
                    q, k_cache, v_cache, pos, tables, cache_len=idx + 1,
                    window=window, scale=scale, softcap=cfg.logit_softcap)
            y = jnp.einsum("bshk,hkd->bsd", out,
                           p["attn"]["wo"].astype(x.dtype))
            x = x + y
            x, aux = _channel_mix(p, x, cfg, kind, num_groups)
            return x, {"k": k_cache, "v": v_cache, "pos": pos}, aux
        n = cache["k"].shape[1]
        if serving is not None:
            # explicitly distributed split-S flash-decode (§Perf iter 2);
            # the per-lane (B,) index vector goes straight down — scalar
            # and vector callers share this one path
            from repro.serving.spmd_decode import spmd_decode_attention
            mesh, b_ax, s_ax = serving
            out, k_cache, v_cache, pos = spmd_decode_attention(
                mesh, q, cache["k"], cache["v"], k, v, cache["pos"],
                idx, window=window, scale=scale,
                softcap=cfg.logit_softcap, batch_axis=b_ax, seq_axis=s_ax)
        else:
            slots = jax.lax.rem(idx, n)                        # (B,)
            lanes = jnp.arange(b)
            k_cache = cache["k"].at[lanes, slots].set(
                k[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[lanes, slots].set(
                v[:, 0].astype(cache["v"].dtype))
            pos = cache["pos"].at[lanes, slots].set(idx)       # (B, n)
            out = ops.decode_attention(
                q, k_cache, v_cache, pos, cache_len=idx + 1, window=window,
                scale=scale, softcap=cfg.logit_softcap)
        y = jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"].astype(x.dtype))
        x = x + y
        new_cache = {"k": k_cache, "v": v_cache, "pos": pos}
    elif kind == CROSS:
        y, _ = attn_lib.cross_attention(
            p["attn"], h, None, cfg, kv_cached=(cache["k"], cache["v"]))
        x = x + y
    elif kind == SSM:
        y, new_cache = ssm_lib.ssm_decode(p["ssm"], h, cache, cfg)
        x = x + y
    elif kind == RGLRU:
        y, new_cache = rglru_lib.rglru_decode(p["rec"], h, cache, cfg)
        x = x + y
    x, aux = _channel_mix(p, x, cfg, kind, num_groups)
    return x, new_cache, aux


# -------------------------------------------------------------------- prefill
def apply_block_prefill(p, x, cfg: ModelConfig, kind: str, attn_kind: str, *,
                        positions=None, enc=None, num_groups: int = 1,
                        capacity: int = 0):
    """Like apply_block but also returns a filled decode cache."""
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    b, s, _ = x.shape
    aux0 = jnp.zeros((), jnp.float32)
    if kind == ATTN:
        y, (k, v) = attn_lib.self_attention(
            p["attn"], h, cfg, attn_kind, positions, return_kv=True)
        x = x + y
        n = _attn_cache_len(cfg, attn_kind, capacity)
        cache = init_block_cache(cfg, kind, attn_kind, b, capacity)
        take = min(s, n)
        # last `take` positions land in ring slots (pos % n)
        src_pos = jnp.arange(s - take, s)
        slots = src_pos % n
        kc = cache["k"].at[:, slots].set(k[:, s - take:].astype(cache["k"].dtype))
        vc = cache["v"].at[:, slots].set(v[:, s - take:].astype(cache["v"].dtype))
        pc = cache["pos"].at[:, slots].set(src_pos.astype(jnp.int32))
        new_cache = {"k": kc, "v": vc, "pos": pc}
    elif kind == CROSS:
        y, (k, v) = attn_lib.cross_attention(p["attn"], h, enc, cfg)
        x = x + y
        new_cache = {"k": k.astype(cfg.dtype), "v": v.astype(cfg.dtype)}
    elif kind == SSM:
        y, new_cache = ssm_lib.ssm_mixer(p["ssm"], h, cfg, return_state=True)
        x = x + y
    elif kind == RGLRU:
        y, new_cache = rglru_lib.rglru_block(p["rec"], h, cfg, return_state=True)
        x = x + y
    else:
        raise ValueError(kind)
    x, aux = _channel_mix(p, x, cfg, kind, num_groups)
    return x, new_cache, aux


# ------------------------------------------------------------ chunked prefill
def apply_block_prefill_chunk(p, x, cache, cfg: ModelConfig, kind: str,
                              attn_kind: str, *, start, num_groups: int = 1):
    """Extend an existing decode cache with a prompt chunk.

    x: (B, C, D) — the chunk's embeddings at absolute positions
    [start, start+C).  Every layer kind threads its cache chunk-to-chunk:

    * **attention** — queries attend over ``[ring cache || this chunk]``
      and the chunk's K/V is scattered into the ring *afterwards*.
      Reading before writing makes the path ring-wrap-safe: a chunk that
      spans the ring boundary would otherwise overwrite keys (absolute
      position ``pos + n``) that its own earlier queries still need
      inside their sliding window.
    * **SSD / RG-LRU** — the mixer consumes the incoming recurrent state
      (conv tail + hidden state) and returns the post-chunk state
      (``ssm_prefill_chunk`` / ``rglru_prefill_chunk``), so successive
      chunks compose to exactly the full-sequence scan.

    Cross-attention blocks are the one unsupported kind (their KV cache
    is the encoder's, filled by whole-prompt prefill with ``enc``) —
    ``model.chunked_prefill_caps`` reports capability per kind so callers
    can fall back per stack instead of gating on an all-or-nothing flag.
    """
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    new_cache = cache
    if kind == ATTN:
        b, c, _ = x.shape
        n = cache["k"].shape[1]
        start = jnp.asarray(start, jnp.int32)
        positions = start + jnp.arange(c, dtype=jnp.int32)     # (C,)
        q, k, v = attn_lib._project_qkv(p["attn"], h, cfg, positions,
                                        attn_kind)
        window = attn_lib._window_for(cfg, attn_kind)
        # attend over [old ring || chunk]: (B, C, n + C) mask — valid slot,
        # causal vs the query's absolute position, sliding window
        pos_cat = jnp.concatenate(
            [cache["pos"], jnp.broadcast_to(positions, (b, c))], axis=1)
        k_cat = jnp.concatenate([cache["k"], k.astype(cache["k"].dtype)], 1)
        v_cat = jnp.concatenate([cache["v"], v.astype(cache["v"].dtype)], 1)
        m = ((pos_cat[:, None, :] >= 0)
             & (pos_cat[:, None, :] <= positions[None, :, None]))
        if window > 0:
            m &= pos_cat[:, None, :] > positions[None, :, None] - window
        out = ref.mha_cache_masked(
            q, k_cat, v_cat, mask=m,
            scale=cfg.attn_scale or cfg.resolved_head_dim ** -0.5,
            softcap=cfg.logit_softcap)
        # now scatter the chunk's last min(C, n) keys into the ring (the
        # older ones are already beyond the ring and can never be read)
        take = min(c, n)
        src = positions[c - take:]
        slots = jax.lax.rem(src, n)
        kc = cache["k"].at[:, slots].set(k[:, c - take:].astype(cache["k"].dtype))
        vc = cache["v"].at[:, slots].set(v[:, c - take:].astype(cache["v"].dtype))
        pos = cache["pos"].at[:, slots].set(src)
        y = jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"].astype(x.dtype))
        x = x + y
        new_cache = {"k": kc, "v": vc, "pos": pos}
    elif kind == SSM:
        y, new_cache = ssm_lib.ssm_prefill_chunk(p["ssm"], h, cache, cfg)
        x = x + y
    elif kind == RGLRU:
        y, new_cache = rglru_lib.rglru_prefill_chunk(p["rec"], h, cache, cfg)
        x = x + y
    else:
        raise NotImplementedError(
            f"chunked prefill is not supported for {kind!r} blocks "
            "(see model.chunked_prefill_caps)")
    x, aux = _channel_mix(p, x, cfg, kind, num_groups)
    return x, new_cache, aux
