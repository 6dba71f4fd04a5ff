"""Pallas TPU kernel for the Mamba2 SSD (state-space duality) chunked scan.

Algorithm per (batch, head), chunk Q=128, state N, head dim P:
  intra-chunk:  y_i += sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) xb_j
                -> two MXU matmuls through a (Q x Q) decay-masked score
  inter-chunk:  y_i += exp(cum_i) * C_i @ h_prev
  state update: h    = exp(total) * h_prev + (B * exp(total - cum)).T @ xb

TPU mapping: grid = (batch, heads, chunks) with the chunk dimension
``arbitrary`` (sequential); the (N x P) running state lives in VMEM scratch
and carries across chunk steps.  The (Q x Q) intra score and both state
matmuls are MXU-shaped (Q = 128, N = 128, P = 64).  The elementwise decay
math runs on the VPU in f32.

Inputs are pre-mixed by the wrapper: xb = x * dt in head-major
``(B, H, S, P)`` order, and the within-chunk cumulative log-decay
cum = cumsum(dt * (-exp(a_log))) as a column ``(B, H, S, 1)`` and a row
``(B, H, 1, S)`` — keeping the kernel purely about the scan, and every
block's last two dims either tile-aligned or whole (the TPU lowering's
rule; ``P = 64`` is only legal as a whole dim).  Validated with
``interpret=True`` against ``ref.ssd_reference`` and compiled for v5e in
``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xb_ref, cc_ref, cr_ref, b_ref, c_ref, y_ref, h_scr, *, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    xb = xb_ref[0, 0].astype(jnp.float32)           # (Q, P)
    cum_col = cc_ref[0, 0]                          # (Q, 1) cum_i
    cum_row = cr_ref[0, 0]                          # (1, Q) cum_j
    total = cum_row[:, chunk - 1:]                  # (1, 1)
    bm = b_ref[0].astype(jnp.float32)               # (Q, N)
    cm = c_ref[0].astype(jnp.float32)               # (Q, N)

    # ---- intra-chunk: (Q,Q) decay-masked score through the MXU
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iota_j <= iota_i
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, cum_col - cum_row,
                                                0.0)), 0.0)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    y = jax.lax.dot_general(cb * decay, xb, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,P)

    # ---- inter-chunk: contribution of the carried state
    h_prev = h_scr[...]                             # (N, P)
    y = y + jnp.exp(cum_col) * jax.lax.dot_general(
        cm, h_prev, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # ---- state update
    b_scaled = bm * jnp.exp(total - cum_col)        # (Q, N)
    h_scr[...] = jnp.exp(total) * h_prev + jax.lax.dot_general(
        b_scaled, xb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd(x, dt, a_log, b_mat, c_mat, d_skip=None, *, chunk=128, interpret=False):
    """x: (B,S,H,P); dt: (B,S,H); a_log: (H,); b_mat/c_mat: (B,S,N)."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    chunk = min(chunk, s)
    if chunk < s and chunk % 8:
        # a partial chunk must be sublane-aligned: pad to whole 128-chunks
        # instead (zero dt => no decay, no input: earlier outputs and the
        # carried state are unchanged)
        chunk = min(128, s)
    pad = -s % chunk
    nc = (s + pad) // chunk

    f32 = jnp.float32
    a = -jnp.exp(a_log.astype(f32))
    ld = dt.astype(f32) * a[None, None, :]                    # (B,S,H)
    xb = x.astype(f32) * dt.astype(f32)[..., None]            # (B,S,H,P)
    if pad:
        ld = jnp.pad(ld, ((0, 0), (0, pad), (0, 0)))
        xb = jnp.pad(xb, ((0, 0), (0, pad), (0, 0), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    cum = jnp.cumsum(ld.reshape(bsz, nc, chunk, h), axis=2)   # within chunk
    cum = cum.reshape(bsz, sp, h).transpose(0, 2, 1)          # (B,H,S)
    xb = xb.transpose(0, 2, 1, 3)                             # (B,H,S,P)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b_, h_, c_: (b_, h_, 0, c_)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda b_, h_, c_: (b_, h_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, sp, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_scan",
    )(xb, cum[..., None], cum[:, :, None, :], b_mat, c_mat)
    y = y.transpose(0, 2, 1, 3)[:, :s]                        # (B,S,H,P)

    if d_skip is not None:
        y = y + (d_skip.astype(f32)[None, None, :, None]
                 * x.astype(f32)).astype(y.dtype)
    return y
