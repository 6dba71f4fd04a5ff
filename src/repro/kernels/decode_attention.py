"""Pallas TPU decode attention (flash-decode over a KV cache).

Decode is memory-bound: one query token must stream the whole KV cache
HBM->VMEM once.  Design:
  * grid = (batch, kv_blocks); kv_blocks is ``arbitrary`` (sequential) so
    the (m, l, acc) scratch accumulates while Mosaic pipelines the next KV
    tile's DMA behind the current tile's FLOPs — the streaming overlap IS
    the optimization at arithmetic intensity ~1.
  * A KV tile is ``(block_k, Hkv, D)``: every KV head of ``block_k``
    positions, read straight out of the cache's own ``(B, S, Hkv, D)``
    layout.  The last two block dims equal the array's, which is what the
    TPU lowering requires, and no relayout of the cache is ever needed.
    The kernel walks the heads of a tile in VMEM; all q heads of one kv
    group (GQA) are processed together as the MXU's M dimension.
  * Masking reads the ring's per-slot position plane: a slot attends when
    it holds a written position (``>= 0``) below the lane's length and
    inside the sliding window, so slot order is irrelevant.
    ``cache_len`` is a per-sequence ``(B,)`` vector in SMEM, so a
    continuous-batching decode step mixes lanes at arbitrary positions.

Validated on CPU with ``interpret=True`` against ``ref.decode_mha_reference``
and ``ref.paged_decode_mha_reference``, and compiled for v5e in
``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _init_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _accumulate_heads(q_ref, k_ref, v_ref, mask, m_scr, l_scr, acc_scr, *,
                      scale, softcap, hkv):
    """One online-softmax update per KV head over a ``(blk, Hkv, D)`` tile;
    ``mask`` is the tile's ``(1, blk)`` slot validity."""
    for h in range(hkv):
        q = q_ref[0, h, :, :].astype(jnp.float32)      # (q_per_kv, d)
        k = k_ref[0, :, h, :].astype(jnp.float32)      # (blk, d)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[h]                              # (q_per_kv, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[h] = m_new


def _finish(o_ref, l_scr, acc_scr, hkv):
    for h in range(hkv):
        out = acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)
        o_ref[0, h, :, :] = out.astype(o_ref.dtype)


def _scratch(hkv, rep, d):
    return [pltpu.VMEM((hkv, rep, 1), jnp.float32),    # running max
            pltpu.VMEM((hkv, rep, 1), jnp.float32),    # running sum
            pltpu.VMEM((hkv, rep, d), jnp.float32)]    # output accumulator


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                   m_scr, l_scr, acc_scr,
                   *, scale, window, softcap, block_k, num_kv_blocks, hkv):
    ki = pl.program_id(1)
    cache_len = len_ref[pl.program_id(0)]       # this lane's KV length

    @pl.when(ki == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    # Slot s of a ring holds a position congruent to s: before the ring
    # wraps, tiles at or past this lane's length are empty; after it wraps
    # every tile is live.  Either way a short lane does not pay for the
    # longest lane's cache.
    @pl.when(ki * block_k < cache_len)
    def _accumulate():
        pos = pos_ref[0]                               # (1, block_k)
        mask = (pos >= 0) & (pos < cache_len)
        if window > 0:
            mask &= pos > cache_len - 1 - window
        _accumulate_heads(q_ref, k_ref, v_ref, mask, m_scr, l_scr, acc_scr,
                          scale=scale, softcap=softcap, hkv=hkv)

    @pl.when(ki == num_kv_blocks - 1)
    def _done():
        _finish(o_ref, l_scr, acc_scr, hkv)


def _pick_block(smax: int, block_k: int) -> int:
    """A KV tile that divides the cache (no per-step pad copy of it) when
    ``block_k`` or 128 does, else ``block_k`` with padding."""
    if smax <= block_k:
        return smax
    for blk in (block_k, 128):
        if smax % blk == 0:
            return blk
    return block_k


def decode_attention(q, k_cache, v_cache, *, cache_len, pos, window=0,
                     scale=None, softcap=0.0, block_k=256, interpret=False):
    """q: (B, 1, Hq, D); caches: (B, Smax, Hkv, D) -> (B, 1, Hq, D).

    ``cache_len``: scalar, or a ``(B,)`` int vector of per-lane KV lengths
    (continuous batching: lanes decode at independent positions).
    ``pos``: ``(B, Smax)`` absolute position held by each slot (-1 =
    empty) — the ring cache's own plane."""
    b, _, hq, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
    pos = pos.astype(jnp.int32)

    block_k = _pick_block(smax, block_k)
    t_pad = -smax % block_k
    if t_pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        pos = jnp.pad(pos, ((0, 0), (0, t_pad)), constant_values=-1)
    nk = (smax + t_pad) // block_k

    # (B, 1, Hq, D) -> (B, Hkv, rep, D): group q heads by kv head
    qg = q[:, 0].reshape(b, hkv, rep, d)
    # (B, 1, S): a (1, block_k) tile of one lane is then a legal block
    pos3 = pos.reshape(b, 1, smax + t_pad)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=softcap,
        block_k=block_k, num_kv_blocks=nk, hkv=hkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, hkv, rep, d), lambda b_, ki, lr: (b_, 0, 0, 0)),
            pl.BlockSpec((1, block_k, hkv, d),
                         lambda b_, ki, lr: (b_, ki, 0, 0)),
            pl.BlockSpec((1, block_k, hkv, d),
                         lambda b_, ki, lr: (b_, ki, 0, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b_, ki, lr: (b_, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, hkv, rep, d),
                               lambda b_, ki, lr: (b_, 0, 0, 0)),
        scratch_shapes=_scratch(hkv, rep, d),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(lens, qg, k_cache, v_cache, pos3)
    return out.reshape(b, 1, hq, d)


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, pos_ref,
                         o_ref, m_scr, l_scr, acc_scr,
                         *, scale, window, softcap, page, max_pages, hkv):
    b_ = pl.program_id(0)
    pi = pl.program_id(1)
    cache_len = len_ref[b_]
    page_id = tbl_ref[b_ * max_pages + pi]

    @pl.when(pi == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    # A table slot with no page mapped, beyond this lane's length, or (with
    # a sliding window) wholly before it contributes nothing — and because
    # the BlockSpec index map routed an absent slot to a clamped row, the
    # gathered tile may be another lane's page: it must never reach the MXU.
    lane_live = (page_id >= 0) & (pi * page < cache_len)
    if window > 0:
        lane_live &= (pi + 1) * page > cache_len - 1 - window

    @pl.when(lane_live)
    def _accumulate():
        # A page slot is real only if the position actually written there is
        # the absolute position this table slot stands for — a page recycled
        # from a freed lane, or written only up to mid-page, fails this.
        kpos = pi * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        mask = (kpos < cache_len) & (pos_ref[0] == kpos)
        if window > 0:
            mask &= kpos > cache_len - 1 - window
        _accumulate_heads(q_ref, k_ref, v_ref, mask, m_scr, l_scr, acc_scr,
                          scale=scale, softcap=softcap, hkv=hkv)

    @pl.when(pi == max_pages - 1)
    def _done():
        _finish(o_ref, l_scr, acc_scr, hkv)


def paged_decode_attention(q, k_pages, v_pages, pos_pages, tables, *,
                           cache_len, window=0, scale=None, softcap=0.0,
                           interpret=False):
    """Flash-decode over a block-table paged KV pool.

    q:         (B, 1, Hq, D); pools: (P, page, Hkv, D); pos: (P, page)
    tables:    (B, max_pages) int32 page ids, -1 = absent
    cache_len: scalar or (B,) per-lane lengths.

    Same streaming grid as ``decode_attention`` with the KV-block axis
    replaced by the table-slot axis: the block table and per-lane lengths
    ride in scalar prefetch (``PrefetchScalarGridSpec``) so each KV tile's
    BlockSpec index map *dereferences the table* — the pipeline DMAs
    exactly the pages the lane owns, in position order, and non-contiguous
    pool rows cost nothing extra.  The page tile (all KV heads of one
    page) doubles as the flash block; masking re-checks the gathered
    ``pos`` so a recycled page never leaks a previous tenant's keys.
    """
    b, _, hq, d = q.shape
    num_rows, page, hkv = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    rep = hq // hkv
    max_pages = tables.shape[1]
    scale = scale if scale is not None else d ** -0.5

    qg = q[:, 0].reshape(b, hkv, rep, d)
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
    tbl = tables.reshape(-1).astype(jnp.int32)          # (B * max_pages,)
    pos3 = pos_pages.reshape(num_rows, 1, page)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=window, softcap=softcap,
        page=page, max_pages=max_pages, hkv=hkv)

    def _page_row(b_, pi, tbl_ref):
        # Clamp absent (-1) slots to row 0: the tile is skipped in-kernel.
        return jnp.maximum(tbl_ref[b_ * max_pages + pi], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=[
            pl.BlockSpec((1, hkv, rep, d),
                         lambda b_, pi, tbl_ref, len_ref: (b_, 0, 0, 0)),
            pl.BlockSpec((1, page, hkv, d),
                         lambda b_, pi, tbl_ref, len_ref:
                         (_page_row(b_, pi, tbl_ref), 0, 0, 0)),
            pl.BlockSpec((1, page, hkv, d),
                         lambda b_, pi, tbl_ref, len_ref:
                         (_page_row(b_, pi, tbl_ref), 0, 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda b_, pi, tbl_ref, len_ref:
                         (_page_row(b_, pi, tbl_ref), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, rep, d),
                               lambda b_, pi, tbl_ref, len_ref:
                               (b_, 0, 0, 0)),
        scratch_shapes=_scratch(hkv, rep, d),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(tbl, lens, qg, k_pages, v_pages, pos3)
    return out.reshape(b, 1, hq, d)
