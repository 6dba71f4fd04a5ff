"""Decoder-only LM over the composable block stack.

Layers execute as ``lax.scan`` over repeating *pattern periods* (gemma3's
5 local + 1 global, recurrentgemma's rglru/rglru/attn, llama-vision's
every-5th-cross) so 100-layer graphs lower as one period body — essential
for keeping 80 multi-pod dry-run compiles tractable.  Layers that do not
fill a whole period run unrolled as the ``tail``.

Params tree:
  {"embed": .., "periods": (slot0_stacked, slot1_stacked, ...),
   "tail": (layerA, layerB, ...), "final_norm": .., ["head": ..]}
Caches mirror the same periods/tail structure.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ATTN, CROSS, ModelConfig
from repro.models import blocks as blk
from repro.models import layers as lyr


# ------------------------------------------------------------------------ init
def init_model(key, cfg: ModelConfig) -> Dict[str, Any]:
    """Random parameters.  Layer ``i`` draws from ``split(key)[i]``; each
    period slot is initialised as one ``vmap`` over its layers' keys, so
    the stacked leaves are built directly (no per-layer copies to stack,
    and one traced layer per slot under ``jit``)."""
    cfg.validate()
    kinds = list(zip(cfg.layer_kinds(), cfg.attn_kinds()))
    keys = jax.random.split(key, cfg.num_layers + 2)
    p_len, reps = cfg.pattern_period, cfg.num_periods
    periods = tuple(
        jax.vmap(functools.partial(blk.init_block, cfg=cfg, kind=kinds[s][0],
                                   attn_kind=kinds[s][1]))(
            keys[s:reps * p_len:p_len])
        for s in range(p_len)
    ) if reps else tuple()
    tail = tuple(blk.init_block(keys[i], cfg, kinds[i][0], kinds[i][1])
                 for i in range(reps * p_len, cfg.num_layers))
    params = {
        "embed": lyr.init_embedding(keys[-2], cfg),
        "periods": periods,
        "tail": tail,
        "final_norm": lyr.init_rmsnorm(cfg.d_model, cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = lyr.init_logits_head(keys[-1], cfg)
    return params


def init_params(key, cfg: ModelConfig, sharding=None) -> Dict[str, Any]:
    """``init_model`` as one jitted program with every leaf placed by
    ``sharding`` (default: JAX's default device) — the serving path's
    initialiser: nothing is materialised twice on a device."""
    return jax.jit(functools.partial(init_model, cfg=cfg),
                   out_shardings=sharding)(key)


def count_params(cfg: ModelConfig) -> int:
    from repro.common.tree import tree_count
    shapes = jax.eval_shape(functools.partial(init_model, cfg=cfg),
                            jax.random.PRNGKey(0))
    return tree_count(shapes)


def count_active_params(cfg: ModelConfig) -> int:
    """Active params per token (MoE: only top-k experts count)."""
    total = count_params(cfg)
    if not cfg.num_experts:
        return total
    expert_p = 3 * cfg.d_model * cfg.d_ff          # gate/up/down per expert
    n_moe_layers = sum(1 for k in cfg.layer_kinds() if k in (ATTN, CROSS))
    inactive = n_moe_layers * (cfg.num_experts - cfg.num_experts_per_tok) * expert_p
    return total - inactive


# --------------------------------------------------------------------- forward
def forward(params, tokens, cfg: ModelConfig, *, enc=None, num_groups: int = 1,
            training: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (B, S) int32 (or (B, S, d_model) precomputed embeddings for
    stub-frontend archs).  Returns (logits (B,S,V), aux_loss scalar)."""
    if tokens.ndim == 2:
        x = lyr.embed(params["embed"], tokens, cfg)
    else:
        x = tokens.astype(cfg.dtype)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)
    period_kinds = cfg.period_kinds()

    def period_body(carry, slot_params):
        x, aux = carry
        for si, (kind, akind) in enumerate(period_kinds):
            x, a = blk.apply_block(slot_params[si], x, cfg, kind, akind,
                                   positions=positions, enc=enc,
                                   num_groups=num_groups)
            aux = aux + a
        return (x, aux), None

    body = period_body
    if training and cfg.remat:
        body = jax.checkpoint(period_body, prevent_cse=False)

    aux0 = jnp.zeros((), jnp.float32)
    if cfg.num_periods > 0 and cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body, (x, aux0), params["periods"])
    else:
        aux = aux0
        reps = cfg.num_periods
        for r in range(reps):
            slot_params = tuple(jax.tree.map(lambda a: a[r], sp)
                                for sp in params["periods"])
            (x, aux), _ = period_body((x, aux), slot_params)

    for ti, (kind, akind) in enumerate(cfg.tail_kinds()):
        x, a = blk.apply_block(params["tail"][ti], x, cfg, kind, akind,
                               positions=positions, enc=enc,
                               num_groups=num_groups)
        aux = aux + a

    x = lyr.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lyr.logits_head(params["embed"], x, cfg, params.get("head"))
    logits = _maybe_shard_vocab(logits, cfg)
    return logits, aux


def _maybe_shard_vocab(logits, cfg: ModelConfig):
    """Constrain the vocab dim onto the TP axis when V doesn't divide it
    (minicpm's 122753, mamba2's 50280): GSPMD pads uneven intermediates, so
    the logits matmul + CE logsumexp still split 16 ways (§Perf iter 3b)."""
    from repro.sharding import context as shctx

    ctx = shctx.get_activation_mesh()
    if ctx is None:
        return logits
    mesh, axis = ctx
    if cfg.vocab_size % mesh.shape[axis] == 0:
        return logits
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    U = P.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(
        logits, NamedSharding(mesh, P(U, U, axis)))


# ---------------------------------------------------------------------- caches
def init_cache(cfg: ModelConfig, batch: int, capacity: int) -> Dict[str, Any]:
    kinds = cfg.period_kinds()
    p_len, reps = cfg.pattern_period, cfg.num_periods

    def one(kind, akind):
        return blk.init_block_cache(cfg, kind, akind, batch, capacity)

    periods = tuple(
        jax.tree.map(lambda x: jnp.broadcast_to(x, (reps,) + x.shape),
                     one(k, a))
        for (k, a) in kinds
    )
    tail = tuple(one(k, a) for (k, a) in cfg.tail_kinds())
    return {"periods": periods, "tail": tail}


def init_paged_cache(cfg: ModelConfig, batch: int, capacity: int,
                     num_pages: int, page_size: int) -> Dict[str, Any]:
    """Like ``init_cache`` but attention KV lives in shared **page pools**
    instead of per-lane rings: every attention layer holds ``{"k"/"v":
    (num_pages + 1, page_size, Hkv, D), "pos": (num_pages + 1, page_size)}``
    indexed through per-lane block tables (see ``apply_block_decode``).
    The extra last row is the write dump for lanes with no page mapped.
    Recurrent / cross leaves keep their per-lane ``batch``-leading layout —
    only KV is paged."""
    kinds = cfg.period_kinds()
    p_len, reps = cfg.pattern_period, cfg.num_periods
    hd, hkv = cfg.resolved_head_dim, cfg.num_kv_heads

    def one(kind, akind):
        if kind == ATTN:
            return {
                "k": jnp.zeros((num_pages + 1, page_size, hkv, hd), cfg.dtype),
                "v": jnp.zeros((num_pages + 1, page_size, hkv, hd), cfg.dtype),
                "pos": jnp.full((num_pages + 1, page_size), -1, jnp.int32),
            }
        return blk.init_block_cache(cfg, kind, akind, batch, capacity)

    periods = tuple(
        jax.tree.map(lambda x: jnp.broadcast_to(x, (reps,) + x.shape),
                     one(k, a))
        for (k, a) in kinds
    )
    tail = tuple(one(k, a) for (k, a) in cfg.tail_kinds())
    return {"periods": periods, "tail": tail}


# ---------------------------------------------------------------------- decode
def decode_step(params, cache, tokens, cache_index, cfg: ModelConfig,
                *, num_groups: int = 1, block_tables=None
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """tokens: (B, 1) int32 (or (B, 1, d) embeddings).  One decode step:
    inserts KV at ``cache_index`` and predicts the next token's logits.

    ``cache_index`` is a scalar (all lanes aligned) or a per-lane ``(B,)``
    vector — the continuous-batching path, where every lane of the batch
    decodes at its own position in its own KV history.

    ``block_tables`` ((B, max_pages) int32, -1 = absent) switches attention
    layers to the paged-pool cache layout from ``init_paged_cache``; the
    same table indexes every attention layer's pool."""
    if tokens.ndim == 2:
        x = lyr.embed(params["embed"], tokens, cfg)
    else:
        x = tokens.astype(cfg.dtype)
    cache_index = jnp.asarray(cache_index, jnp.int32)
    period_kinds = cfg.period_kinds()

    def period_body(x, slot_params_and_cache):
        slot_params, slot_caches = slot_params_and_cache
        new_caches = []
        for si, (kind, akind) in enumerate(period_kinds):
            x, nc, _ = blk.apply_block_decode(
                slot_params[si], x, slot_caches[si], cfg, kind, akind,
                cache_index=cache_index, num_groups=num_groups,
                block_tables=block_tables)
            new_caches.append(nc)
        return x, tuple(new_caches)

    if cfg.num_periods > 0 and cfg.scan_layers:
        x, new_periods = jax.lax.scan(
            period_body, x, (params["periods"], cache["periods"]))
    else:
        new_list = []
        for r in range(cfg.num_periods):
            sp = tuple(jax.tree.map(lambda a: a[r], t) for t in params["periods"])
            sc = tuple(jax.tree.map(lambda a: a[r], t) for t in cache["periods"])
            x, ncs = period_body(x, (sp, sc))
            new_list.append(ncs)
        if new_list:
            new_periods = tuple(
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[nl[s] for nl in new_list])
                for s in range(len(period_kinds)))
        else:
            new_periods = cache["periods"]

    new_tail = []
    for ti, (kind, akind) in enumerate(cfg.tail_kinds()):
        x, nc, _ = blk.apply_block_decode(
            params["tail"][ti], x, cache["tail"][ti], cfg, kind, akind,
            cache_index=cache_index, num_groups=num_groups,
            block_tables=block_tables)
        new_tail.append(nc)

    x = lyr.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lyr.logits_head(params["embed"], x, cfg, params.get("head"))
    return logits, {"periods": new_periods, "tail": tuple(new_tail)}


# ----------------------------------------------------------- paged lane moves
def paged_commit(cache, lane_cache, lane, table_row, from_pos,
                 cfg: ModelConfig, page_size: int) -> Dict[str, Any]:
    """Install a finished lane prefill into the paged cache.

    ``lane_cache`` is the private B=1 *ring* cache chunked prefill filled
    (``init_cache(cfg, 1, capacity)``): attention rings are scattered into
    the page pools through ``table_row`` ((max_pages,) int32) — ring entry
    at absolute position ``p`` lands in pool row ``table_row[p // page]``
    slot ``p % page`` — while recurrent / cross leaves splice into batch
    row ``lane`` exactly like the ring engine's insert.  Entries with
    ``p < from_pos`` are routed to the dump row instead: those positions
    live in *shared* prefix pages another lane (or the prefix cache) may
    be reading, and a commit must never mutate a page it does not own.
    """
    maxp = table_row.shape[0]
    lane = jnp.asarray(lane, jnp.int32)
    from_pos = jnp.asarray(from_pos, jnp.int32)

    def commit_attn(pk, pv, pp, rk, rv, rp):
        dump = pk.shape[0] - 1
        p = rp[0]                                          # (n,) ring positions
        valid = (p >= 0) & (p >= from_pos)
        slot = jnp.minimum(jnp.maximum(p, 0) // page_size, maxp - 1)
        rows = jnp.where(valid, table_row[slot], dump)
        rows = jnp.where(rows >= 0, rows, dump)
        within = jnp.maximum(p, 0) % page_size
        pk = pk.at[rows, within].set(rk[0].astype(pk.dtype))
        pv = pv.at[rows, within].set(rv[0].astype(pv.dtype))
        pp = pp.at[rows, within].set(p)
        return pk, pv, pp

    def commit_block(kind, block, ring, stacked):
        if kind == ATTN:
            # period leaves carry a leading reps axis: vmap the per-layer
            # scatter over it (every rep shares the lane's one table row)
            if stacked:
                k, v, pos = jax.vmap(commit_attn)(
                    block["k"], block["v"], block["pos"],
                    ring["k"], ring["v"], ring["pos"])
            else:
                k, v, pos = commit_attn(block["k"], block["v"], block["pos"],
                                        ring["k"], ring["v"], ring["pos"])
            return {"k": k, "v": v, "pos": pos}
        axis = 1 if stacked else 0
        return jax.tree.map(
            lambda full, part: jax.lax.dynamic_update_slice_in_dim(
                full, part.astype(full.dtype), lane, axis=axis),
            block, ring)

    new_periods = tuple(
        commit_block(kind, cache["periods"][si], lane_cache["periods"][si],
                     True)
        for si, (kind, akind) in enumerate(cfg.period_kinds()))
    new_tail = tuple(
        commit_block(kind, cache["tail"][ti], lane_cache["tail"][ti], False)
        for ti, (kind, akind) in enumerate(cfg.tail_kinds()))
    return {"periods": new_periods, "tail": new_tail}


def paged_restore(cache, lane_cache, table_row, matched,
                  cfg: ModelConfig, page_size: int) -> Dict[str, Any]:
    """Fill a fresh B=1 prefill ring from cached prefix pages.

    The inverse of ``paged_commit``: ring slot ``s`` receives the pool
    entry for the absolute position the ring would hold after prefilling
    ``matched`` tokens — ``p = s + ((matched - 1 - s) // n) * n`` (the
    newest in-ring position congruent to ``s`` mod the ring length), valid
    while ``0 <= p < matched``.  Suffix chunk prefill then continues from
    ``start = matched`` as if those tokens had just been computed.
    Recurrent leaves are left untouched (a recurrent state cannot be
    restored from KV pages — the engine gates prefix reuse to
    attention-only stacks)."""
    maxp = table_row.shape[0]
    matched = jnp.asarray(matched, jnp.int32)

    def restore_attn(pk, pv, pp, rk, rv, rp):
        n = rk.shape[1]
        s = jnp.arange(n, dtype=jnp.int32)
        p = s + ((matched - 1 - s) // n) * n
        valid = (p >= 0) & (p < matched)
        sp = jnp.where(valid, p, 0)
        rows = table_row[jnp.minimum(sp // page_size, maxp - 1)]
        rows = jnp.where(valid & (rows >= 0), rows, pk.shape[0] - 1)
        gk = pk[rows, sp % page_size]                       # (n, hkv, hd)
        gv = pv[rows, sp % page_size]
        rk = jnp.where(valid[:, None, None], gk.astype(rk.dtype), rk[0])[None]
        rv = jnp.where(valid[:, None, None], gv.astype(rv.dtype), rv[0])[None]
        rp = jnp.where(valid, p, -1)[None]
        return rk, rv, rp

    new_periods = []
    for si, (kind, akind) in enumerate(cfg.period_kinds()):
        ring = lane_cache["periods"][si]
        if kind != ATTN:
            new_periods.append(ring)
            continue
        pool = cache["periods"][si]
        k, v, pos = jax.vmap(restore_attn)(
            pool["k"], pool["v"], pool["pos"],
            ring["k"], ring["v"], ring["pos"])
        new_periods.append({"k": k, "v": v, "pos": pos})
    new_tail = []
    for ti, (kind, akind) in enumerate(cfg.tail_kinds()):
        ring = lane_cache["tail"][ti]
        if kind != ATTN:
            new_tail.append(ring)
            continue
        pool = cache["tail"][ti]
        k, v, pos = restore_attn(pool["k"], pool["v"], pool["pos"],
                                 ring["k"], ring["v"], ring["pos"])
        new_tail.append({"k": k, "v": v, "pos": pos})
    return {"periods": tuple(new_periods), "tail": tuple(new_tail)}


def paged_copy_page(cache, src, dst, cfg: ModelConfig) -> Dict[str, Any]:
    """Copy pool row ``src`` -> ``dst`` in every attention layer's pools —
    the device half of copy-on-write (the allocator half decides *when*)."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def copy(leaf, stacked):
        if stacked:
            row = jax.lax.dynamic_index_in_dim(leaf, src, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(leaf, row, dst, axis=1)
        row = jax.lax.dynamic_index_in_dim(leaf, src, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(leaf, row, dst, axis=0)

    new_periods = []
    for si, (kind, akind) in enumerate(cfg.period_kinds()):
        block = cache["periods"][si]
        if kind == ATTN:
            block = jax.tree.map(lambda l: copy(l, True), block)
        new_periods.append(block)
    new_tail = []
    for ti, (kind, akind) in enumerate(cfg.tail_kinds()):
        block = cache["tail"][ti]
        if kind == ATTN:
            block = jax.tree.map(lambda l: copy(l, False), block)
        new_tail.append(block)
    return {"periods": tuple(new_periods), "tail": tuple(new_tail)}


# ------------------------------------------------------------ chunked prefill
def chunked_prefill_caps(cfg: ModelConfig, capacity: int) -> Dict[str, Any]:
    """Per-kind chunked-prefill capability report (replaces the old
    all-or-nothing ``supports_chunked_prefill`` gate).

    Chunked prefill extends a live decode cache one exact prompt piece at
    a time: attention layers read-then-scatter their ring cache
    (ring-wrap-safe), recurrent mixers (SSD/RG-LRU) thread their state
    chunk-to-chunk.  Cross-attention is the one unsupported kind (its KV
    cache belongs to the encoder and is filled with ``enc`` by
    whole-prompt prefill).

    Returns a dict:

    * ``kinds`` — ``{label: bool}`` per distinct layer kind in the stack
      (labels ``attn:global`` / ``attn:local`` / ``ssm`` / ``rglru`` /
      ``cross``);
    * ``supported`` — every layer kind can chunk-prefill;
    * ``max_chunk_tokens`` — the widest exact chunk: the smallest
      attention ring in the stack (a wider chunk would overwrite its own
      keys in one scatter); ``capacity`` for attention-free stacks;
    * ``max_prompt_tokens`` — longest prompt that chunk-prefills exactly,
      or ``None`` for unbounded: a global-attention layer (or a sliding
      window the ring cannot hold, ``capacity < window``) bounds it to
      its ring length, recurrent and full-window local layers do not.
    """
    from repro.common.config import GLOBAL
    from repro.models import attention as attn_lib
    from repro.models import blocks as blk

    kinds: Dict[str, bool] = {}
    max_chunk = capacity
    max_prompt: Optional[int] = None
    for kind, akind in zip(cfg.layer_kinds(), cfg.attn_kinds()):
        if kind == ATTN:
            label = f"attn:{akind}"
            kinds[label] = True
            n = blk._attn_cache_len(cfg, akind, capacity)
            max_chunk = min(max_chunk, n)
            window = attn_lib._window_for(cfg, akind)
            if window == 0 or n < window:
                max_prompt = n if max_prompt is None else min(max_prompt, n)
        elif kind == CROSS:
            kinds["cross"] = False
        else:
            kinds[kind] = True
    return {
        "kinds": kinds,
        "supported": all(kinds.values()) if kinds else False,
        "max_chunk_tokens": max(int(max_chunk), 1),
        "max_prompt_tokens": max_prompt,
    }


def prefill_chunk(params, cache, tokens, start, cfg: ModelConfig,
                  *, num_groups: int = 1, return_all_logits: bool = False
                  ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Extend ``cache`` with prompt chunk ``tokens`` ((B, C) int32) whose
    first token sits at absolute position ``start``.  Returns last-position
    logits (B, 1, V) — or all C positions' logits with
    ``return_all_logits`` — and the extended cache.  Works for every
    supported layer kind (``chunked_prefill_caps``): attention layers
    read-then-scatter their ring cache, SSD/RG-LRU mixers thread their
    recurrent state chunk-to-chunk.  Start from a fresh
    ``init_cache(cfg, B, capacity)`` with ``start=0``; successive calls
    advance ``start`` by the previous chunk length.  This is the serving
    engine's anti-stall: a long prompt prefills in bounded pieces
    interleaved between other lanes' decode steps."""
    if tokens.ndim == 2:
        x = lyr.embed(params["embed"], tokens, cfg)
    else:
        x = tokens.astype(cfg.dtype)
    start = jnp.asarray(start, jnp.int32)
    period_kinds = cfg.period_kinds()

    def period_body(x, slot_params_and_cache):
        slot_params, slot_caches = slot_params_and_cache
        new_caches = []
        for si, (kind, akind) in enumerate(period_kinds):
            x, nc, _ = blk.apply_block_prefill_chunk(
                slot_params[si], x, slot_caches[si], cfg, kind, akind,
                start=start, num_groups=num_groups)
            new_caches.append(nc)
        return x, tuple(new_caches)

    if cfg.num_periods > 0 and cfg.scan_layers:
        x, new_periods = jax.lax.scan(
            period_body, x, (params["periods"], cache["periods"]))
    else:
        new_list = []
        for r in range(cfg.num_periods):
            sp = tuple(jax.tree.map(lambda a: a[r], t) for t in params["periods"])
            sc = tuple(jax.tree.map(lambda a: a[r], t) for t in cache["periods"])
            x, ncs = period_body(x, (sp, sc))
            new_list.append(ncs)
        if new_list:
            new_periods = tuple(
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[nl[s] for nl in new_list])
                for s in range(len(period_kinds)))
        else:
            new_periods = cache["periods"]

    new_tail = []
    for ti, (kind, akind) in enumerate(cfg.tail_kinds()):
        x, nc, _ = blk.apply_block_prefill_chunk(
            params["tail"][ti], x, cache["tail"][ti], cfg, kind, akind,
            start=start, num_groups=num_groups)
        new_tail.append(nc)

    x = lyr.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    sel = x if return_all_logits else x[:, -1:]
    logits = lyr.logits_head(params["embed"], sel, cfg, params.get("head"))
    return logits, {"periods": new_periods, "tail": tuple(new_tail)}


# --------------------------------------------------------------------- prefill
def prefill(params, tokens, cfg: ModelConfig, capacity: int, *, enc=None,
            num_groups: int = 1) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Run the prompt through the stack, returning last-position logits and a
    cache filled up to ``tokens.shape[1]`` (ready for decode_step at index
    S, S+1, ...).  Uses the unrolled path (prefill is not the scan-critical
    compile)."""
    if tokens.ndim == 2:
        x = lyr.embed(params["embed"], tokens, cfg)
    else:
        x = tokens.astype(cfg.dtype)
    b, s = x.shape[0], x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)
    kinds = list(zip(cfg.layer_kinds(), cfg.attn_kinds()))
    p_len, reps = cfg.pattern_period, cfg.num_periods

    caches = []
    for i, (kind, akind) in enumerate(kinds):
        if i < reps * p_len:
            r, slot = divmod(i, p_len)
            lp = jax.tree.map(lambda a: a[r], params["periods"][slot])
        else:
            lp = params["tail"][i - reps * p_len]
        x, c, _ = blk.apply_block_prefill(lp, x, cfg, kind, akind,
                                          positions=positions, enc=enc,
                                          num_groups=num_groups,
                                          capacity=capacity)
        caches.append(c)

    period_caches = tuple(
        jax.tree.map(lambda *xs: jnp.stack(xs),
                     *[caches[r * p_len + sl] for r in range(reps)])
        for sl in range(p_len)
    )
    tail_caches = tuple(caches[reps * p_len:])
    x = lyr.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lyr.logits_head(params["embed"], x[:, -1:], cfg, params.get("head"))
    return logits, {"periods": period_caches, "tail": tail_caches}
