"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes/dtypes, plus hypothesis property tests on the oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # pragma: no cover
    # deterministic local fallback; install requirements-dev.txt
    # for real property-based coverage
    from _hypothesis_fallback import given, settings, st

from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru
from repro.kernels.ssd_scan import ssd

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ------------------------------------------------------------ flash attention
FLASH_CASES = [
    # b, s, t, hq, hkv, d, causal, window, softcap, dtype
    (2, 128, 128, 4, 4, 64, True, 0, 0.0, jnp.float32),
    (1, 256, 256, 8, 2, 64, True, 0, 0.0, jnp.float32),
    (2, 200, 200, 4, 1, 32, True, 64, 0.0, jnp.float32),   # SWA + MQA + ragged
    (1, 128, 384, 4, 2, 64, False, 0, 0.0, jnp.float32),   # cross (kv longer)
    (2, 128, 128, 4, 2, 64, True, 0, 30.0, jnp.float32),   # softcap
    (2, 128, 128, 4, 2, 64, True, 32, 0.0, jnp.bfloat16),  # bf16
]


@pytest.mark.parametrize("b,s,t,hq,hkv,d,causal,window,softcap,dtype",
                         FLASH_CASES)
def test_flash_attention_vs_oracle(b, s, t, hq, hkv, d, causal, window,
                                   softcap, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, s, hq, d), dtype)
    k = rand(k2, (b, t, hkv, d), dtype)
    v = rand(k3, (b, t, hkv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, interpret=True)
    exp = ref.mha_reference(q, k, v, causal=causal, window=window,
                            softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


def test_chunked_attention_matches_reference():
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (2, 300, 4, 32))
    k = rand(k2, (2, 300, 2, 32))
    v = rand(k3, (2, 300, 2, 32))
    for window in (0, 64):
        out = ref.mha_chunked(q, k, v, causal=True, window=window,
                              q_block=64, kv_block=128)
        exp = ref.mha_reference(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------ decode attention
def contiguous_pos(b, smax, lens):
    """The position plane of a cache filled from slot 0: slot ``s`` holds
    position ``s`` below the lane's length, nothing (-1) past it."""
    lens = jnp.broadcast_to(jnp.asarray(lens, jnp.int32).reshape(-1, 1),
                            (b, 1))
    slot = jnp.arange(smax, dtype=jnp.int32)[None]
    return jnp.where(slot < lens, slot, -1)


DECODE_CASES = [
    (2, 512, 8, 2, 64, 300, 0, jnp.float32),
    (1, 300, 4, 4, 32, 123, 64, jnp.float32),
    (4, 256, 8, 1, 64, 255, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("b,smax,hq,hkv,d,clen,window,dtype", DECODE_CASES)
def test_decode_attention_vs_oracle(b, smax, hq, hkv, d, clen, window, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, 1, hq, d), dtype)
    kc = rand(k2, (b, smax, hkv, d), dtype)
    vc = rand(k3, (b, smax, hkv, d), dtype)
    out = decode_attention(q, kc, vc, cache_len=clen,
                           pos=contiguous_pos(b, smax, clen), window=window,
                           interpret=True)
    exp = ref.decode_mha_reference(q, kc, vc, cache_len=clen, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


PER_LANE_CASES = [
    (4, 512, 8, 2, 64, (300, 17, 511, 64), 0, jnp.float32),
    (3, 256, 4, 4, 32, (1, 123, 256), 64, jnp.float32),
    (4, 256, 8, 1, 64, (255, 8, 100, 31), 0, jnp.bfloat16),
]


@pytest.mark.parametrize("b,smax,hq,hkv,d,clens,window,dtype", PER_LANE_CASES)
def test_decode_attention_per_lane_cache_len(b, smax, hq, hkv, d, clens,
                                             window, dtype):
    """Continuous batching: each lane masks against its OWN cache_len.  The
    batched kernel with a (B,) length vector must match the scalar oracle
    applied lane-by-lane."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, 1, hq, d), dtype)
    kc = rand(k2, (b, smax, hkv, d), dtype)
    vc = rand(k3, (b, smax, hkv, d), dtype)
    clen_vec = jnp.asarray(clens, jnp.int32)
    out = decode_attention(q, kc, vc, cache_len=clen_vec,
                           pos=contiguous_pos(b, smax, clen_vec),
                           window=window, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for lane, clen in enumerate(clens):
        exp = ref.decode_mha_reference(q[lane:lane + 1], kc[lane:lane + 1],
                                       vc[lane:lane + 1], cache_len=clen,
                                       window=window)
        np.testing.assert_allclose(
            np.asarray(out[lane:lane + 1], np.float32),
            np.asarray(exp, np.float32), atol=tol, rtol=tol,
            err_msg=f"lane {lane} (cache_len={clen})")
    # vectorized jnp reference path agrees too
    exp_vec = ref.decode_mha_reference(q, kc, vc, cache_len=clen_vec,
                                       window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp_vec, np.float32),
                               atol=tol, rtol=tol)


RING_CASES = [
    # b, n, hq, hkv, d, lens, window, dtype
    (4, 64, 8, 2, 32, (1, 40, 64, 150), 0, jnp.float32),     # 150 wraps
    (3, 48, 4, 1, 16, (7, 100, 48), 16, jnp.float32),        # window + wrap
    (2, 64, 8, 2, 32, (33, 90), 0, jnp.bfloat16),
]


@pytest.mark.parametrize("b,n,hq,hkv,d,lens,window,dtype", RING_CASES)
def test_decode_attention_ring_pos_plane(b, n, hq, hkv, d, lens, window,
                                         dtype):
    """A ring cache passes its own slot-position plane: after the ring
    wraps, slot s holds the newest position congruent to s, and the kernel
    must mask by that plane (not by slot index) exactly like the jnp ring
    path (``ref.decode_mha_masked``)."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, 1, hq, d), dtype)
    kc = rand(k2, (b, n, hkv, d), dtype)
    vc = rand(k3, (b, n, hkv, d), dtype)
    lens_a = np.asarray(lens, np.int32)
    slot = np.arange(n)[None]
    pos = slot + ((lens_a[:, None] - 1 - slot) // n) * n
    pos = jnp.asarray(np.where(pos >= 0, pos, -1), jnp.int32)
    lens_j = jnp.asarray(lens_a)
    out = decode_attention(q, kc, vc, cache_len=lens_j, pos=pos,
                           window=window, interpret=True)
    valid = (pos >= 0) & (pos < lens_j[:, None])
    if window:
        valid &= pos > lens_j[:, None] - 1 - window
    exp = ref.decode_mha_masked(q, kc, vc, valid_mask=valid)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


PAGED_CASES = [
    # b, page, maxp, hq, hkv, d, lens, window, dtype
    (3, 8, 6, 8, 2, 32, (1, 20, 48), 0, jnp.float32),
    (2, 16, 4, 4, 1, 16, (17, 64), 24, jnp.float32),
    (2, 8, 8, 8, 2, 32, (30, 61), 0, jnp.bfloat16),
]


@pytest.mark.parametrize("b,page,maxp,hq,hkv,d,lens,window,dtype",
                         PAGED_CASES)
def test_paged_decode_attention_vs_oracle(b, page, maxp, hq, hkv, d, lens,
                                          window, dtype):
    """Block-table paged decode: shuffled pool rows, absent table slots and
    a recycled page whose written positions do not match must all agree
    with ``ref.paged_decode_mha_reference``."""
    rng = np.random.default_rng(0)
    rows = b * maxp + 1                          # + the dump row
    perm = rng.permutation(rows - 1).astype(np.int32).reshape(b, maxp)
    lens_a = np.asarray(lens, np.int32)
    tables = np.where(np.arange(maxp)[None] * page < lens_a[:, None], perm, -1)
    pos = np.full((rows, page), -1, np.int32)
    for lane in range(b):
        for j in range(maxp):
            if tables[lane, j] >= 0:
                pos[tables[lane, j]] = j * page + np.arange(page)
    # a previous tenant's page under the longest lane's first table slot
    pos[perm[int(np.argmax(lens_a)), 0]] += 3
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = rand(k1, (b, 1, hq, d), dtype)
    kp = rand(k2, (rows, page, hkv, d), dtype)
    vp = rand(k3, (rows, page, hkv, d), dtype)
    args = (q, kp, vp, jnp.asarray(pos), jnp.asarray(tables))
    out = paged_decode_attention(*args, cache_len=jnp.asarray(lens_a),
                                 window=window, interpret=True)
    exp = ref.paged_decode_mha_reference(*args, cache_len=jnp.asarray(lens_a),
                                         window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


# ------------------------------------------------------------------------ SSD
SSD_CASES = [
    (2, 256, 4, 64, 32, 64),
    (1, 128, 2, 32, 16, 128),     # single chunk
    (2, 192, 3, 16, 64, 64),      # odd heads / large state
]


@pytest.mark.parametrize("b,s,h,p,n,chunk", SSD_CASES)
def test_ssd_kernel_vs_oracle(b, s, h, p, n, chunk):
    ks = jax.random.split(KEY, 5)
    x = rand(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(rand(ks[1], (b, s, h)))
    a_log = rand(ks[2], (h,), scale=0.5)
    bm = rand(ks[3], (b, s, n), scale=0.3)
    cm = rand(ks[4], (b, s, n), scale=0.3)
    dsk = jnp.ones((h,))
    out = ssd(x, dt, a_log, bm, cm, dsk, chunk=chunk, interpret=True)
    exp = ref.ssd_reference(x, dt, a_log, bm, cm, dsk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-3, rtol=5e-3)


def test_ssd_chunked_jnp_matches_quadratic():
    ks = jax.random.split(KEY, 5)
    b, s, h, p, n = 2, 256, 4, 32, 16
    x = rand(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(rand(ks[1], (b, s, h)))
    a_log = rand(ks[2], (h,), scale=0.5)
    bm = rand(ks[3], (b, s, n), scale=0.3)
    cm = rand(ks[4], (b, s, n), scale=0.3)
    out = ref.ssd_chunked(x, dt, a_log, bm, cm, None, chunk=64)
    exp = ref.ssd_reference(x, dt, a_log, bm, cm, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-4, rtol=2e-4)


def test_ssd_decode_step_matches_full_scan():
    """Running the per-token recurrence over a sequence must equal the
    chunked scan — the prefill->decode handoff invariant."""
    ks = jax.random.split(KEY, 5)
    b, s, h, p, n = 1, 32, 2, 16, 8
    x = rand(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(rand(ks[1], (b, s, h)))
    a_log = rand(ks[2], (h,), scale=0.5)
    bm = rand(ks[3], (b, s, n), scale=0.3)
    cm = rand(ks[4], (b, s, n), scale=0.3)
    full = ref.ssd_reference(x, dt, a_log, bm, cm, None)
    hstate = jnp.zeros((b, h, n, p))
    for t in range(s):
        y, hstate = ref.ssd_decode_step(hstate, x[:, t], dt[:, t], a_log,
                                        bm[:, t], cm[:, t], None)
        np.testing.assert_allclose(np.asarray(y), np.asarray(full[:, t]),
                                   atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------- RG-LRU
RGLRU_CASES = [(2, 256, 128, 64), (1, 100, 48, 32), (2, 64, 256, 256)]


@pytest.mark.parametrize("b,s,d,chunk", RGLRU_CASES)
def test_rglru_kernel_vs_oracle(b, s, d, chunk):
    ks = jax.random.split(KEY, 3)
    x = rand(ks[0], (b, s, d))
    log_a = -jax.nn.softplus(rand(ks[1], (b, s, d)))
    gate = jax.nn.sigmoid(rand(ks[2], (b, s, d)))
    out = rglru(x, log_a, gate, chunk=chunk, interpret=True)
    exp = ref.rglru_reference(x, log_a, gate)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def test_rglru_chunked_matches_step_scan():
    ks = jax.random.split(KEY, 3)
    x = rand(ks[0], (2, 77, 32))
    log_a = -jax.nn.softplus(rand(ks[1], (2, 77, 32)))
    gate = jax.nn.sigmoid(rand(ks[2], (2, 77, 32)))
    np.testing.assert_allclose(
        np.asarray(ref.rglru_chunked(x, log_a, gate)),
        np.asarray(ref.rglru_reference(x, log_a, gate)),
        atol=2e-5, rtol=2e-5)


# ------------------------------------------------------- hypothesis properties
@settings(max_examples=20, deadline=None)
@given(s=st.integers(8, 96), h=st.sampled_from([1, 2, 4]),
       window=st.sampled_from([0, 8, 16]))
def test_property_causal_attention_prefix_invariance(s, h, window):
    """Attention output at position i must not change if the suffix after i
    changes — causality under any window."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(s * 7 + h), 4)
    q = rand(k1, (1, s, h, 16))
    k = rand(k2, (1, s, h, 16))
    v = rand(k3, (1, s, h, 16))
    out1 = ref.mha_reference(q, k, v, causal=True, window=window)
    i = s // 2
    k2_ = k.at[:, i + 1:].set(rand(k4, (1, s - i - 1, h, 16)))
    v2_ = v.at[:, i + 1:].set(rand(k4, (1, s - i - 1, h, 16)) + 1.0)
    out2 = ref.mha_reference(q, k2_, v2_, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out1[:, :i + 1]),
                               np.asarray(out2[:, :i + 1]),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(s=st.sampled_from([16, 32, 48, 64]),
       chunk=st.sampled_from([4, 8, 16]))
def test_property_ssd_chunk_size_invariance(s, chunk):
    """The chunked SSD result must be independent of chunk size."""
    ks = jax.random.split(jax.random.PRNGKey(s), 5)
    b, h, p, n = 1, 2, 8, 4
    x = rand(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(rand(ks[1], (b, s, h)))
    a_log = rand(ks[2], (h,), scale=0.5)
    bm = rand(ks[3], (b, s, n), scale=0.3)
    cm = rand(ks[4], (b, s, n), scale=0.3)
    base = ref.ssd_chunked(x, dt, a_log, bm, cm, None, chunk=s)
    alt = ref.ssd_chunked(x, dt, a_log, bm, cm, None, chunk=min(chunk, s))
    np.testing.assert_allclose(np.asarray(base), np.asarray(alt),
                               atol=2e-4, rtol=2e-4)


@settings(max_examples=15, deadline=None)
@given(b=st.integers(1, 3), s=st.integers(2, 40), d=st.sampled_from([8, 24]))
def test_property_rglru_zero_gate_zeros_output(b, s, d):
    """If the input gate is 0 everywhere, the recurrence emits zeros."""
    ks = jax.random.split(jax.random.PRNGKey(b * 100 + s), 2)
    x = rand(ks[0], (b, s, d))
    log_a = -jax.nn.softplus(rand(ks[1], (b, s, d)))
    out = ref.rglru_chunked(x, log_a, jnp.zeros_like(x))
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-7)
