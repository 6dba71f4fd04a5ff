"""Pallas TPU flash attention (fused online-softmax attention).

TPU-native design:
  * grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is
    ``arbitrary`` (sequential) so the (m, l, acc) VMEM scratch accumulators
    carry across kv steps — the canonical TPU flash pattern.
  * q/k/v are viewed as ``(B, S, H*D)`` (a reshape of freshly computed
    activations, which XLA lays out for the kernel) and BlockSpecs stream
    ``(block, D)`` tiles with the head index in the last block position —
    the tiling the TPU lowering accepts (last two block dims divisible by
    8 and 128, or equal to the array's).  A head width that is not a
    multiple of 128 (64 in minicpm-2b and musicgen-medium) is zero-padded
    up to one first: the pad changes no score and no output column, at
    twice the kernel's FLOPs for D=64.  Block sizes are 128 at prefill
    lengths; a shorter sequence is one whole block.
  * GQA is free: the k/v index_map folds q-head -> kv-head, so kv tiles are
    fetched once per kv-head group.
  * causal / sliding-window / length masking via in-kernel iota compare.

Validated on CPU with ``interpret=True`` against ``ref.mha_reference`` and
compiled for v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, window, softcap, q_offset, kv_len,
                  block_q, block_k, num_kv_blocks):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (bq, d)
    k = k_ref[0].astype(jnp.float32)                   # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    qi = pl.program_id(2)
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_offset
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < kv_len
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)       # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                     # (bq, 1)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _done():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, scale=None, softcap=0.0,
                    q_offset=0, block_q=128, block_k=128, interpret=False):
    """q: (B, S, Hq, D); k, v: (B, T, Hkv, D) -> (B, S, Hq, D)."""
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    block_q = min(block_q, s)
    block_k = min(block_k, t)
    s_pad, t_pad = -s % block_q, -t % block_k
    if s_pad:
        q = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
    if t_pad:
        k = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    d_pad = -d % 128
    if d_pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
                   for x in (q, k, v))
    dp = d + d_pad
    nq, nk = (s + s_pad) // block_q, (t + t_pad) // block_k
    q = q.reshape(b, s + s_pad, hq * dp)
    k = k.reshape(b, t + t_pad, hkv * dp)
    v = v.reshape(b, t + t_pad, hkv * dp)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, kv_len=t,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b_, h, qi, ki: (b_, qi, h)),
            pl.BlockSpec((1, block_k, dp), lambda b_, h, qi, ki: (b_, ki, h // rep)),
            pl.BlockSpec((1, block_k, dp), lambda b_, h, qi, ki: (b_, ki, h // rep)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dp),
                               lambda b_, h, qi, ki: (b_, qi, h)),
        out_shape=jax.ShapeDtypeStruct((b, s + s_pad, hq * dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, dp), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out.reshape(b, s + s_pad, hq, dp)[:, :s, :, :d]
