"""Explicitly distributed decode attention: split-S flash-decode over the
mesh, written with shard_map.

Layout: KV cache (B, S, Hkv, D) with batch over ``data`` and SEQUENCE over
``model`` (kv-head counts rarely divide tp=16; sequence always does).  Each
model-rank:

  1. writes each lane's new K/V if that lane's ring slot lands in its
     S-shard (``cache_index`` may be a per-lane ``(B,)`` vector — lanes of
     a continuous batch sit at independent depths),
  2. computes a partial softmax (m, l, acc) over its local S chunk,
  3. joins via the log-sum-exp combine: two psums of (B, H) scalars and one
     of (B, H, D) — O(KB), vs the multi-GB cache all-gather GSPMD emits for
     the same computation (measured in EXPERIMENTS.md §Perf iter 2).

This is the distribution-layer twin of the Pallas ``decode_attention``
kernel (same math, split across chips instead of across VMEM tiles).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def _local_attend(q, k, v, valid, scale, softcap):
    """Partial flash-decode on the local S chunk.
    q: (B,1,H,D); k,v: (B,Sl,Hkv,D); valid: (Sl,) or (B,Sl) -> (m, l, acc)."""
    b, _, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    qg = q[:, 0].reshape(b, hkv, rep, d)
    vm = valid[None] if valid.ndim == 1 else valid          # (1|B, Sl)
    vm = vm[:, None, None, :]
    logits = jnp.einsum("bkrd,bskd->bkrs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(vm, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                            # (B,Hkv,rep)
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(vm, p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkrs,bskd->bkrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def spmd_decode_attention(mesh, q, k_cache, v_cache, new_k, new_v, pos,
                          cache_index, *, window: int = 0,
                          scale: float, softcap: float = 0.0,
                          batch_axis: Optional[str] = "data",
                          seq_axis: str = "model"):
    """Returns (out (B,1,H,D), k_cache', v_cache', pos').

    pos: (S,) — or per-lane (B, S) — int32 ring-slot absolute positions
    (-1 = empty).  ``cache_index`` is a scalar (all lanes at the same
    depth) or a per-lane ``(B,)`` vector — the continuous-batching case,
    where lane b writes its new token's K/V at slot ``cache_index[b] % S``
    and masks (validity + sliding window) against its OWN absolute
    position.  Per-lane indices require per-lane ``(B, S)`` pos.  Each
    S-shard performs the ring write only for the lanes whose slot lands
    in its local chunk, so lanes at wildly different depths still decode
    in one shard_map step.
    """
    b, _, hq, d = q.shape
    s = k_cache.shape[1]
    pos_batched = pos.ndim == 2
    idx_batched = jnp.ndim(cache_index) == 1
    if idx_batched and not pos_batched:
        raise ValueError("per-lane cache_index requires per-lane (B, S) pos")
    n_seq = mesh.shape[seq_axis]
    assert s % n_seq == 0, (s, n_seq)
    s_loc = s // n_seq

    if batch_axis:
        axes = batch_axis if isinstance(batch_axis, tuple) else (batch_axis,)
        ways = 1
        for a in axes:
            ways *= mesh.shape[a]
        bspec = batch_axis if b % ways == 0 else None
    else:
        bspec = None

    def body(q_l, k_l, v_l, nk_l, nv_l, pos_l, idx):
        rank = jax.lax.axis_index(seq_axis)
        start = rank * s_loc
        slot = jax.lax.rem(idx, s)                  # () or (Bl,)
        off = slot - start
        in_range = jnp.logical_and(off >= 0, off < s_loc)
        off_c = jnp.clip(off, 0, s_loc - 1)
        if idx_batched:
            def scatter_write(k_l, v_l, pos_l):
                # per-lane conditional ring write: lane b's slot may land
                # in a different S-shard than lane c's; each shard
                # scatters the new K/V for ALL lanes at their clipped
                # offsets, then keeps the write only for lanes it owns
                lanes = jnp.arange(k_l.shape[0])
                k_upd = k_l.at[lanes, off_c].set(nk_l[:, 0].astype(k_l.dtype))
                v_upd = v_l.at[lanes, off_c].set(nv_l[:, 0].astype(v_l.dtype))
                k_l = jnp.where(in_range[:, None, None, None], k_upd, k_l)
                v_l = jnp.where(in_range[:, None, None, None], v_upd, v_l)
                pos_upd = pos_l.at[lanes, off_c].set(idx)
                pos_l = jnp.where(in_range[:, None], pos_upd, pos_l)
                return k_l, v_l, pos_l

            def aligned_write(k_l, v_l, pos_l):
                # all lanes at the same depth (common right after a batch
                # of simultaneous joins): one aligned dynamic_update_slice
                # instead of the per-lane scatter
                inr = in_range[0]
                k_new = jax.lax.dynamic_update_slice(
                    k_l, nk_l.astype(k_l.dtype), (0, off_c[0], 0, 0))
                v_new = jax.lax.dynamic_update_slice(
                    v_l, nv_l.astype(v_l.dtype), (0, off_c[0], 0, 0))
                pos_new = jax.lax.dynamic_update_slice(
                    pos_l, idx[:, None], (0, off_c[0]))
                return (jnp.where(inr, k_new, k_l),
                        jnp.where(inr, v_new, v_l),
                        jnp.where(inr, pos_new, pos_l))

            k_l, v_l, pos_l = jax.lax.cond(
                jnp.all(idx == idx[0]), aligned_write, scatter_write,
                k_l, v_l, pos_l)
        else:
            # aligned lanes: one dynamic slice write, owning shard's sticks
            k_new = jax.lax.dynamic_update_slice(k_l, nk_l.astype(k_l.dtype),
                                                 (0, off_c, 0, 0))
            v_new = jax.lax.dynamic_update_slice(v_l, nv_l.astype(v_l.dtype),
                                                 (0, off_c, 0, 0))
            k_l = jnp.where(in_range, k_new, k_l)
            v_l = jnp.where(in_range, v_new, v_l)
            if pos_batched:
                pos_new = jax.lax.dynamic_update_slice(
                    pos_l, jnp.full((pos_l.shape[0], 1), idx, jnp.int32),
                    (0, off_c))
            else:
                pos_new = jax.lax.dynamic_update_slice(
                    pos_l, idx[None].astype(jnp.int32), (off_c,))
            pos_l = jnp.where(in_range, pos_new, pos_l)

        valid = pos_l >= 0
        if window > 0:
            # per-lane sliding window: each lane's window trails its own
            # absolute position
            hi = idx[:, None] if idx_batched else idx
            valid &= pos_l > hi - window
        m, l, acc = _local_attend(q_l, k_l, v_l, valid, scale, softcap)

        # log-sum-exp combine across S shards: O(B*H) + O(B*H*D) psums
        m_g = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axis)
        acc_g = jax.lax.psum(acc * corr[..., None], seq_axis)
        out = (acc_g / jnp.maximum(l_g, 1e-30)[..., None])
        out = out.reshape(q_l.shape[0], 1, hq, d).astype(q_l.dtype)
        return out, k_l, v_l, pos_l

    pos_spec = P(bspec, seq_axis) if pos_batched else P(seq_axis)
    idx_spec = P(bspec) if idx_batched else P()
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None, None),        # q (replicated on seq)
                  P(bspec, seq_axis, None, None),    # k cache
                  P(bspec, seq_axis, None, None),    # v cache
                  P(bspec, None, None, None),        # new k
                  P(bspec, None, None, None),        # new v
                  pos_spec,                          # pos
                  idx_spec),                         # cache_index
        out_specs=(P(bspec, None, None, None),
                   P(bspec, seq_axis, None, None),
                   P(bspec, seq_axis, None, None),
                   pos_spec),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, new_k, new_v, pos,
              jnp.asarray(cache_index, jnp.int32))


def spmd_paged_decode_attention(mesh, q, k_pool, v_pool, pos_pool, tables,
                                new_k, new_v, rows, within, cache_index, *,
                                window: int = 0, scale: float,
                                softcap: float = 0.0,
                                batch_axis: Optional[str] = "data",
                                seq_axis: str = "model"):
    """Block-table decode under the mesh: page pools sharded over rows.

    Pools ((P, page, Hkv, D) + (P, page) pos) shard their **row** axis over
    ``seq_axis`` — pages replace the contiguous S chunks of
    ``spmd_decode_attention``, so each rank owns a contiguous row range and
    the same lse combine joins the partial softmaxes.  ``rows`` / ``within``
    are each lane's pre-resolved write coordinates ((B,) int32, dump row
    for absent table slots); ``tables`` is the (B, max_pages) block table.
    Each rank keeps the scatter only for lanes whose row lands in its
    range, attends over the pages *it* owns (table entries outside the
    local range are masked), and psums (m, l, acc).

    The batch dim stays replicated: every rank must see every lane's table
    (pages are shared across lanes — sharding B would leave each batch
    shard with a divergent pool replica after the write).
    Requires ``P % mesh.shape[seq_axis] == 0`` (the engine rounds its page
    count up to suit).
    """
    del batch_axis                       # lanes replicated: pools are shared
    b, _, hq, d = q.shape
    prows, page = k_pool.shape[0], k_pool.shape[1]
    maxp = tables.shape[1]
    n_seq = mesh.shape[seq_axis]
    assert prows % n_seq == 0, (prows, n_seq)
    p_loc = prows // n_seq

    def body(q_l, k_l, v_l, pos_l, tbl, nk_l, nv_l, rows_g, within_g, idx):
        rank = jax.lax.axis_index(seq_axis)
        start = rank * p_loc
        off = rows_g - start
        in_range = jnp.logical_and(off >= 0, off < p_loc)    # (B,)
        # route lanes whose row lives on another rank to a scratch row
        # appended below the local slice (dropped after the scatter) — a
        # where() over the scattered array would race a clipped stray
        # write against a genuine one landing in the same cell
        off_c = jnp.where(in_range, off, p_loc)
        k_l = jnp.concatenate([k_l, jnp.zeros_like(k_l[:1])], 0).at[
            off_c, within_g].set(nk_l[:, 0].astype(k_l.dtype))[:p_loc]
        v_l = jnp.concatenate([v_l, jnp.zeros_like(v_l[:1])], 0).at[
            off_c, within_g].set(nv_l[:, 0].astype(v_l.dtype))[:p_loc]
        pos_l = jnp.concatenate([pos_l, jnp.zeros_like(pos_l[:1])], 0).at[
            off_c, within_g].set(idx)[:p_loc]

        # gather the locally-owned slice of every lane's table
        e_off = tbl - start                                  # (B, maxp)
        local = (tbl >= 0) & (e_off >= 0) & (e_off < p_loc)
        safe = jnp.clip(e_off, 0, p_loc - 1)
        k_g = k_l[safe].reshape(b, maxp * page, *k_l.shape[2:])
        v_g = v_l[safe].reshape(b, maxp * page, *v_l.shape[2:])
        pos_g = pos_l[safe].reshape(b, maxp * page)
        expected = jnp.arange(maxp * page, dtype=jnp.int32)[None]
        valid = (pos_g == expected) & (expected <= idx[:, None])
        valid &= jnp.repeat(local, page, axis=1)
        if window > 0:
            valid &= expected > idx[:, None] - window
        m, l, acc = _local_attend(q_l, k_g, v_g, valid, scale, softcap)

        m_g = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axis)
        acc_g = jax.lax.psum(acc * corr[..., None], seq_axis)
        out = (acc_g / jnp.maximum(l_g, 1e-30)[..., None])
        out = out.reshape(b, 1, hq, d).astype(q_l.dtype)
        return out, k_l, v_l, pos_l

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, None, None),          # q (replicated)
                  P(seq_axis, None, None, None),      # k pool (rows sharded)
                  P(seq_axis, None, None, None),      # v pool
                  P(seq_axis, None),                  # pos pool
                  P(None, None),                      # tables
                  P(None, None, None, None),          # new k
                  P(None, None, None, None),          # new v
                  P(None), P(None), P(None)),         # rows, within, idx
        out_specs=(P(None, None, None, None),
                   P(seq_axis, None, None, None),
                   P(seq_axis, None, None, None),
                   P(seq_axis, None)),
        check_vma=False,
    )
    idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32).reshape(-1),
                           (b,))
    return fn(q, k_pool, v_pool, pos_pool,
              jnp.asarray(tables, jnp.int32), new_k, new_v,
              jnp.asarray(rows, jnp.int32), jnp.asarray(within, jnp.int32),
              idx)
