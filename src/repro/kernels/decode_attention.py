"""Pallas TPU decode attention (flash-decode over a KV cache).

Decode is memory-bound: one query token must stream the whole KV cache
HBM->VMEM once.  The ring kernel (``decode_attention``):
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

The paged kernel (``paged_decode_attention``) reads a pool of pages shared
by all lanes through per-lane block tables, and streams only live pages:
  * The pools stay in HBM; the kernel issues its own DMAs.  The grid is one
    step per lane, and a ``fori_loop`` walks that lane's live blocks only:
    from the block where its sliding window starts to ``ceil(len / blk)``,
    with the block table and lengths in scalar prefetch.
  * A block is ``BLOCK_TOKENS // page`` table slots (capped by the table):
    one DMA per mapped page below the lane's length for each of K, V and
    the position row, into one half of a double-buffered VMEM block.  The
    next block's DMAs (after a lane's last block, the next lane's first)
    go out before the current block is computed.
  * So a slot past the length, with no page, or before the window costs
    neither a DMA nor a grid step, and a lane of length 0 costs neither.
  * A block's scores for all q heads are one matmul over its
    ``(blk * Hkv, D)`` rows, each head masked to its own KV head's rows,
    and ``p . v`` one more: Hkv times the FLOPs of per-head matmuls, but
    contiguous loads in place of one strided load per head.
    Masking re-checks each gathered position against the slot's absolute
    position, so a recycled or half-written page never leaks stale keys.

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


# Tokens of KV one DMA block of the paged kernel holds: the pages per block
# are this over the page size (at least one page, at most the table).
BLOCK_TOKENS = 256


def _spread_slots(ok, page, hkv):
    """``(pages, page)`` slot flags -> the ``(1, pages * page * hkv)`` row
    of a block's scores, whose lane ``t * hkv + h`` is token ``t`` of KV
    head ``h``.  Mosaic has no reshape that moves sublanes into lanes, so
    this is a 0/1 matmul (exact at any precision): ``ok @ T`` repeats page
    j's flags, each ``hkv`` times, across the whole row, and the selector
    keeps each repeat only in page j's own lanes."""
    n = ok.shape[0]
    width = n * page * hkv
    lane = jax.lax.broadcasted_iota(jnp.int32, (page, width), 1)
    tile = (lane // hkv) % page == jax.lax.broadcasted_iota(
        jnp.int32, (page, width), 0)
    spread = jax.lax.dot_general(
        ok.astype(jnp.float32), tile.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, width), 1)
    own = lane // (page * hkv) == jax.lax.broadcasted_iota(
        jnp.int32, (n, width), 0)
    return jnp.sum(jnp.where(own, spread, 0.0), axis=0, keepdims=True) > 0.5


def _accumulate_block(q_ref, k_blk, v_blk, mask, m_scr, l_scr, acc_scr, *,
                      scale, softcap, hkv):
    """One online-softmax update of every q head over a gathered
    ``(blk, Hkv, D)`` VMEM block, as two matmuls for all heads at once:
    the block read as ``(blk * Hkv, D)`` rows (a contiguous load, where
    one head's rows would be a strided one), and each q head's scores
    masked to its own KV head's rows.  ``q . k`` takes the cache's own
    operands (bf16 products are exact in f32) with f32 accumulation;
    softmax statistics, ``p`` and the accumulator stay f32."""
    blk, _, d = k_blk.shape
    hq = q_ref.shape[1]
    k = k_blk[...].reshape(blk * hkv, d)               # row t * hkv + h
    q = q_ref[0]                                       # (hq, d)
    if q.dtype != k.dtype:
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    q_head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // (hq // hkv)
    kv_head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) % hkv
    mask = mask & (q_head == kv_head)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]                                # (hq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    v = v_blk[...].reshape(blk * hkv, d).astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, pos_hbm,
                         o_ref, k_buf, v_buf, pos_buf, sems, slot_ref,
                         m_scr, l_scr, acc_scr,
                         *, scale, window, softcap, page, max_pages, bp, hkv):
    lane = pl.program_id(0)
    lanes = pl.num_programs(0)
    blk = bp * page

    def live_pages(b_):
        """``[lo, hi)``: the table slots of lane ``b_`` that hold a position
        below its length and inside the window."""
        n = len_ref[b_]
        hi = jnp.minimum((n + page - 1) // page, max_pages)
        lo = jnp.maximum(n - window, 0) // page if window > 0 else 0
        return lo, jnp.maximum(hi, lo)

    def live_blocks(b_):
        lo, hi = live_pages(b_)
        return lo // bp, (hi + bp - 1) // bp

    def block_pages(b_, ib, slot, fn):
        """``fn(j, row, copies)`` for each live table slot ``j`` of block
        ``ib``: its pool row (-1 if absent) and its K, V and position DMAs
        into slot ``j`` of buffer ``slot``."""
        lo, hi = live_pages(b_)

        def one(j, carry):
            row = tbl_ref[b_ * max_pages + ib * bp + j]
            src = jnp.maximum(row, 0)
            dst = pl.ds(j * page, page)
            fn(j, row, (
                pltpu.make_async_copy(k_hbm.at[src], k_buf.at[slot, dst],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[src], v_buf.at[slot, dst],
                                      sems.at[1, slot]),
                pltpu.make_async_copy(pos_hbm.at[src], pos_buf.at[slot, j],
                                      sems.at[2, slot])))
            return carry

        jax.lax.fori_loop(jnp.clip(lo - ib * bp, 0, bp),
                          jnp.clip(hi - ib * bp, 0, bp), one, 0)

    def start(b_, ib, slot):
        def go(j, row, copies):
            @pl.when(row >= 0)
            def _():
                for c in copies:
                    c.start()
        block_pages(b_, ib, slot, go)

    def wait(b_, ib, slot):
        def done(j, row, copies):
            @pl.when(row >= 0)
            def _():
                for c in copies:
                    c.wait()

            @pl.when(row < 0)
            def _():
                # An absent slot keeps stale positions that could match.
                pos_buf[slot, j] = jnp.full(pos_buf.shape[2:], -1, jnp.int32)
        block_pages(b_, ib, slot, done)

    first, end = live_blocks(lane)
    nxt = jnp.minimum(lane + 1, lanes - 1)
    nxt_first, nxt_end = live_blocks(nxt)
    nxt_live = (lane + 1 < lanes) & (nxt_first < nxt_end)

    @pl.when(lane == 0)
    def _first_lane():
        # Slots a block does not copy keep an earlier block's data (or, at
        # first, whatever VMEM held): their p is 0, and 0 x V must not be
        # NaN.  Every later lane finds its first block already in flight,
        # issued by the lane before.
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0

        @pl.when(first < end)
        def _():
            start(lane, first, 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    n = len_ref[lane]
    slot0 = slot_ref[0]
    kpos0 = (jax.lax.broadcasted_iota(jnp.int32, (bp, page), 0) * page
             + jax.lax.broadcasted_iota(jnp.int32, (bp, page), 1))

    def body(ib, carry):
        slot = jax.lax.rem(slot0 + ib - first, 2)
        # The next block's DMAs go out before this block's compute: this
        # lane's next block, or after its last the next lane's first.
        more = ib + 1 < end

        @pl.when(more | nxt_live)
        def _():
            start(jnp.where(more, lane, nxt), jnp.where(more, ib + 1, nxt_first),
                  1 - slot)

        wait(lane, ib, slot)
        # A slot counts only if the position written there is the absolute
        # position this table slot stands for: a page recycled from a freed
        # lane, or written only up to mid-page, fails this.
        kpos = ib * blk + kpos0
        ok = (pos_buf[slot, :, 0, :page] == kpos) & (kpos < n)
        if window > 0:
            ok &= kpos > n - 1 - window
        _accumulate_block(q_ref, k_buf.at[slot], v_buf.at[slot],
                          _spread_slots(ok, page, hkv), m_scr, l_scr, acc_scr,
                          scale=scale, softcap=softcap, hkv=hkv)
        return carry

    jax.lax.fori_loop(first, end, body, 0)

    @pl.when((first == end) & nxt_live)
    def _empty_lane():
        start(nxt, nxt_first, slot0)

    slot_ref[0] = jax.lax.rem(slot0 + end - first, 2)
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
        o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, pos_pages, tables, *,
                           cache_len, window=0, scale=None, softcap=0.0,
                           interpret=False):
    """Flash-decode over a block-table paged KV pool.

    q:         (B, 1, Hq, D); pools: (P, page, Hkv, D); pos: (P, page)
    tables:    (B, max_pages) int32 page ids, -1 = absent
    cache_len: scalar or (B,) per-lane lengths.

    The pools stay in HBM and the kernel issues its own DMAs.  The grid is
    one step per lane; the block table and lengths ride in scalar prefetch.
    A lane walks only its live blocks, ``[first, ceil(len / blk))`` with
    ``first`` the block where a sliding window starts, in a ``fori_loop``.
    A block is ``blk = bp * page`` tokens, ``bp = BLOCK_TOKENS // page``
    (capped by the table): per table slot below the lane's length with a
    page mapped, one DMA each of the page's K, V and position rows into
    slot ``j`` of a double-buffered VMEM block.  The next block's copies
    (after a lane's last block, the next lane's first) are issued before
    the current block is computed.  Absent slots, slots past the length or
    before the window, and lanes of length 0 issue no DMA and take no grid
    step: the step count is the lanes, the DMA count the live pages.
    Masking re-checks each gathered position against the slot's absolute
    position, so a recycled page never leaks a previous tenant's keys.
    """
    b, _, hq, d = q.shape
    num_rows, page, hkv = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    max_pages = tables.shape[1]
    bp = max(1, min(BLOCK_TOKENS // page, max_pages))     # pages a block
    scale = scale if scale is not None else d ** -0.5

    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
    tbl = tables.reshape(-1).astype(jnp.int32)          # (B * max_pages,)
    # One DMA moves whole rows of the (1, 128) tiles a position row lives in.
    width = -(-page // 128) * 128
    pos3 = jnp.pad(pos_pages.astype(jnp.int32), ((0, 0), (0, width - page)),
                   constant_values=-1).reshape(num_rows, 1, width)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=window, softcap=softcap,
        page=page, max_pages=max_pages, bp=bp, hkv=hkv)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    # q head i reads KV head i // (Hq / Hkv): (B, Hq, D) rows in that order
    lane_block = pl.BlockSpec((1, hq, d), lambda b_, *_: (b_, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[lane_block, hbm, hbm, hbm],
        out_specs=lane_block,
        scratch_shapes=[
            pltpu.VMEM((2, bp * page, hkv, d), k_pages.dtype),
            pltpu.VMEM((2, bp * page, hkv, d), v_pages.dtype),
            pltpu.VMEM((2, bp, 1, width), jnp.int32),
            pltpu.SemaphoreType.DMA((3, 2)),
            pltpu.SMEM((1,), jnp.int32),               # slot of the next block
            pltpu.VMEM((hq, 1), jnp.float32),          # running max
            pltpu.VMEM((hq, 1), jnp.float32),          # running sum
            pltpu.VMEM((hq, d), jnp.float32),          # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # lanes run in order: each prefetches the next one's first block
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(tbl, lens, q[:, 0], k_pages, v_pages, pos3)
    return out.reshape(b, 1, hq, d)
