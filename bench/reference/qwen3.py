"""Plain float32 reference of the dense GQA decoder the serving program runs
as qwen3-4b: pre-norm layers of grouped-query attention with per-head
q/k RMS norm and rotary positions, then a SwiGLU MLP; tied embeddings.

It follows the Qwen3 description (hf:Qwen/Qwen3-4B) with the serving
program's conventions, which a reader comparing to Qwen3 should know:
the input embedding is scaled by ``sqrt(d_model)``, the tied output head
by ``1/sqrt(d_model)``, and each RMS norm stores ``scale`` with weight
``1 + scale``.  Rotary embedding rotates the two halves of each head
(``rotate_half``), as Qwen3 does.

``init_params`` makes the weights the benchmark serves, in the program's
parameter layout; the reference shares nothing else with the program.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference.common import (EMBED_RMS, F32, HIGHEST, final_scale, mm,
                                    normal, rms, small)

FAMILY = "dense"
Q_BLOCK = 512                      # queries per attention block


def init_params(key, dims: Dict, dtype):
    """Random weights for ``dims``.  The embedding is small (rms 0.1 at the
    first layer's input) so that the layers, not a copy of the input
    token, decide the next token."""
    d, f, n = dims["d_model"], dims["d_ff"], dims["num_layers"]
    hq, hkv = dims["num_heads"], dims["num_kv_heads"]
    hd, v = dims["head_dim"], dims["vocab_size"]
    ks = iter(jax.random.split(key, 16))
    block = {
        "norm1": {"scale": small(next(ks), (n, d), dtype)},
        "attn": {
            "wq": normal(next(ks), (n, d, hq, hd), d ** -0.5, dtype),
            "wk": normal(next(ks), (n, d, hkv, hd), d ** -0.5, dtype),
            "wv": normal(next(ks), (n, d, hkv, hd), d ** -0.5, dtype),
            "wo": normal(next(ks), (n, hq, hd, d), (hq * hd) ** -0.5, dtype),
            "q_norm": {"scale": small(next(ks), (n, hd), dtype)},
            "k_norm": {"scale": small(next(ks), (n, hd), dtype)},
        },
        "norm2": {"scale": small(next(ks), (n, d), dtype)},
        "mlp": {
            "w_gate": normal(next(ks), (n, d, f), d ** -0.5, dtype),
            "w_up": normal(next(ks), (n, d, f), d ** -0.5, dtype),
            "w_down": normal(next(ks), (n, f, d), f ** -0.5, dtype),
        },
    }
    return {
        "embed": {"table": normal(next(ks), (v, d), EMBED_RMS * d ** -0.5,
                                  dtype)},
        "periods": (block,),
        "tail": (),
        "final_norm": {"scale": final_scale(next(ks), d, dtype)},
    }


def _rope(x, pos, theta):
    """x: (L, H, D) at absolute positions ``pos`` (L,)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * inv                    # (L, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA over the whole sequence, one block of queries at a time.
    q: (L, Hq, D); k, v: (L, Hkv, D)."""
    seq, hq, hd = q.shape
    rep = hq // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)                          # head h -> h // rep
    v = jnp.repeat(v, rep, axis=1)
    blk = min(Q_BLOCK, seq)
    keys = jnp.arange(seq)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)  # (blk, H, D)
        qpos = i * blk + jnp.arange(blk)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / math.sqrt(hd)
        s = jnp.where(keys[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(seq // blk))
    return out.reshape(seq, hq, hd)


def logits(params, dims: Dict, tokens, start, n: int, control: bool = False):
    """Logits (n, V) in float32 at positions ``start .. start + n - 1`` of
    ``tokens`` (L,), L a multiple of ``min(Q_BLOCK, L)``.  ``control``
    computes every linear layer from fp8-rounded operands."""
    d, eps = dims["d_model"], dims["norm_eps"]
    hq, hkv, hd = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    table = params["embed"]["table"]
    seq = tokens.shape[0]
    pos = jnp.arange(seq)
    x = table[tokens].astype(F32) * math.sqrt(d)

    def layer(x, p):
        a = p["attn"]
        h = rms(x, p["norm1"]["scale"], eps)
        q = mm(h, a["wq"].reshape(d, hq * hd), control).reshape(seq, hq, hd)
        k = mm(h, a["wk"].reshape(d, hkv * hd), control).reshape(seq, hkv, hd)
        v = mm(h, a["wv"].reshape(d, hkv * hd), control).reshape(seq, hkv, hd)
        q = _rope(rms(q, a["q_norm"]["scale"], eps), pos, dims["rope_theta"])
        k = _rope(rms(k, a["k_norm"]["scale"], eps), pos, dims["rope_theta"])
        o = _attention(q, k, v).reshape(seq, hq * hd)
        x = x + mm(o, a["wo"].reshape(hq * hd, d), control)
        h = rms(x, p["norm2"]["scale"], eps)
        m = p["mlp"]
        g = jax.nn.silu(mm(h, m["w_gate"], control)) * mm(h, m["w_up"], control)
        return x + mm(g, m["w_down"], control), None

    x, _ = jax.lax.scan(layer, x, params["periods"][0])
    x = jax.lax.dynamic_slice_in_dim(x, start, n)
    x = rms(x, params["final_norm"]["scale"], eps) / math.sqrt(d)
    return mm(x, table.T, control)
