"""Public jitted kernel entry points with backend dispatch.

On TPU backends the Pallas kernels are used; everywhere else (CPU tests,
host-platform multi-pod dry-run) the linear-memory jnp formulations from
``ref.py`` execute the same algorithm with shardable einsums.

``REPRO_KERNEL_IMPL`` picks the path off the chip: ``jnp`` (the default,
and the tests' reference) or ``pallas_interpret``.  On a TPU the path is
``pallas`` and nothing else: any other value is an error, so a run on the
chip can never silently measure the jnp or interpreter path.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref


def kernel_impl() -> str:
    """The kernel path in use: ``pallas`` | ``pallas_interpret`` | ``jnp``."""
    forced = os.environ.get("REPRO_KERNEL_IMPL", "")
    if jax.default_backend() == "tpu":
        if forced not in ("", "pallas"):
            raise RuntimeError(
                f"REPRO_KERNEL_IMPL={forced!r} on a TPU backend: the chip "
                "runs the Pallas kernels only (unset it)")
        return "pallas"
    return forced or "jnp"


def _per_device(kernel, q, k, v):
    """XLA cannot partition a Pallas call: under a serving mesh, run it on
    each device through ``shard_map`` — heads split over the mesh's
    sequence axis when q and kv head counts both divide it (each GQA group
    then stays on one device), replicated otherwise."""
    from repro.sharding import context as shctx
    serving = shctx.get_serving_mesh()
    if serving is None:
        return kernel(q, k, v)
    mesh, _, seq_axis = serving
    n = mesh.shape[seq_axis]
    spec = (P(None, None, seq_axis, None)
            if q.shape[2] % n == 0 and k.shape[2] % n == 0 else P())
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# ------------------------------------------------------------------ attention
def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    softcap=0.0, q_offset=0):
    """(B,S,H,D) x (B,T,Hkv,D) -> (B,S,H,D)."""
    impl = kernel_impl()
    if impl.startswith("pallas") and q.shape[1] > 1:
        from repro.kernels import flash_attention as fk
        return _per_device(functools.partial(
            fk.flash_attention, causal=causal, window=window, scale=scale,
            softcap=softcap, q_offset=q_offset,
            interpret=impl == "pallas_interpret"), q, k, v)
    if q.shape[1] * k.shape[1] <= 512 * 512:
        return ref.mha_reference(q, k, v, causal=causal, window=window,
                                 scale=scale, softcap=softcap, q_offset=q_offset)
    return ref.mha_chunked(q, k, v, causal=causal, window=window,
                           scale=scale, softcap=softcap, q_offset=q_offset)


def decode_attention(q, k_cache, v_cache, pos, *, cache_len, window=0,
                     scale=None, softcap=0.0):
    """(B,1,H,D) + (B,n,Hkv,D) ring caches + (B,n) slot positions
    -> (B,1,H,D).

    ``pos`` holds the absolute position written at each ring slot (-1 =
    empty), so slot order is irrelevant; ``cache_len`` is a scalar or a
    per-lane ``(B,)`` vector and each lane masks against its own length
    and sliding window."""
    impl = kernel_impl()
    if impl.startswith("pallas"):
        from repro.kernels import decode_attention as dk
        return dk.decode_attention(
            q, k_cache, v_cache, cache_len=cache_len, pos=pos, window=window,
            scale=scale, softcap=softcap,
            interpret=impl == "pallas_interpret")
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1, 1),
                            (q.shape[0], 1))
    valid = (pos >= 0) & (pos < lens)
    if window > 0:
        valid &= pos > lens - 1 - window
    return ref.decode_mha_masked(q, k_cache, v_cache, valid_mask=valid,
                                 scale=scale, softcap=softcap)


def paged_decode_attention(q, k_pages, v_pages, pos_pages, tables, *,
                           cache_len, window=0, scale=None, softcap=0.0):
    """(B,1,H,D) + (P,page,Hkv,D) pools + (B,max_pages) block tables
    -> (B,1,H,D).  The paged counterpart of ``decode_attention``: KV lives
    in a shared page pool and each lane reads the rows its table names."""
    impl = kernel_impl()
    if impl.startswith("pallas"):
        from repro.kernels import decode_attention as dk
        return dk.paged_decode_attention(
            q, k_pages, v_pages, pos_pages, tables, cache_len=cache_len,
            window=window, scale=scale, softcap=softcap,
            interpret=impl == "pallas_interpret")
    return ref.paged_decode_mha_reference(
        q, k_pages, v_pages, pos_pages, tables, cache_len=cache_len,
        window=window, scale=scale, softcap=softcap)


# ------------------------------------------------------------------------ SSD
def ssd(x, dt, a_log, b_mat, c_mat, d_skip=None, chunk=128):
    impl = kernel_impl()
    if impl.startswith("pallas"):
        from repro.kernels import ssd_scan as sk
        return sk.ssd(x, dt, a_log, b_mat, c_mat, d_skip, chunk=chunk,
                      interpret=impl == "pallas_interpret")
    return ref.ssd_chunked(x, dt, a_log, b_mat, c_mat, d_skip, chunk=chunk)


ssd_decode_step = ref.ssd_decode_step


# --------------------------------------------------------------------- RG-LRU
def rglru(x, log_a, gate_x):
    impl = kernel_impl()
    if impl.startswith("pallas"):
        from repro.kernels import rglru_scan as rk
        return rk.rglru(x, log_a, gate_x,
                        interpret=impl == "pallas_interpret")
    return ref.rglru_chunked(x, log_a, gate_x)


rglru_decode_step = ref.rglru_decode_step


# ------------------------------------------------------------------ conv bits
causal_conv1d = ref.causal_conv1d
causal_conv1d_step = ref.causal_conv1d_step
