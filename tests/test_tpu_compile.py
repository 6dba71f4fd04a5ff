"""AOT compiles of the Pallas kernels for a described TPU v5e, at the widths
the serving path launches them with.

Nothing runs: each test lowers a kernel for one chip of a ``v5e:2x2``
topology that is described, not attached, and asserts that the TPU
compiler accepts it and that the program holds the kernel
(``tpu_custom_call``).  This is the check interpret-mode tests cannot make:
block shapes must tile the way Mosaic requires, and the kernel must fit
the chip's fast memory.  The topology is described inside a fixture (never
at import), and every test skips where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import decode_attention as dk
from repro.kernels import flash_attention as fk
from repro.kernels import rglru_scan as rk
from repro.kernels import ssd_scan as sk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


QWEN = get_config("qwen3-4b")
H, HKV, D = QWEN.num_heads, QWEN.num_kv_heads, QWEN.resolved_head_dim
BF, I32 = jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("arch,seq", [
    pytest.param("qwen3-4b", 32, id="32"),      # a prefill chunk
    pytest.param("qwen3-4b", 512, id="512"),    # a prompt
    # heads 64 wide, not a lane multiple
    pytest.param("minicpm-2b", 512, id="minicpm-2b-512"),
])
def test_flash_attention_compiles_for_v5e(one_chip, arch, seq):
    cfg = get_config(arch)
    h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    _compile(lambda q, k, v: fk.flash_attention(q, k, v, causal=True),
             one_chip, ((1, seq, h, d), BF), ((1, seq, hkv, d), BF),
             ((1, seq, hkv, d), BF))


@pytest.mark.parametrize("capacity", [128, 4096])
def test_ring_decode_attention_compiles_for_v5e(one_chip, capacity):
    slots = 8
    _compile(lambda q, k, v, p, n: dk.decode_attention(
        q, k, v, cache_len=n, pos=p), one_chip,
        ((slots, 1, H, D), BF), ((slots, capacity, HKV, D), BF),
        ((slots, capacity, HKV, D), BF), ((slots, capacity), I32),
        ((slots,), I32))


def test_paged_decode_attention_compiles_for_v5e(one_chip):
    slots, page, capacity = 8, 16, 1024
    maxp = capacity // page
    rows = slots * maxp + 1
    _compile(lambda q, k, v, p, t, n: dk.paged_decode_attention(
        q, k, v, p, t, cache_len=n), one_chip,
        ((slots, 1, H, D), BF), ((rows, page, HKV, D), BF),
        ((rows, page, HKV, D), BF), ((rows, page), I32),
        ((slots, maxp), I32), ((slots,), I32))


def test_ssd_compiles_for_v5e(one_chip):
    cfg = get_config("mamba2-780m")
    h, p, n, seq = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, 512
    _compile(lambda x, dt, a, b, c, d: sk.ssd(x, dt, a, b, c, d,
                                              chunk=cfg.ssm_chunk),
             one_chip, ((1, seq, h, p), BF), ((1, seq, h), BF),
             ((h,), BF), ((1, seq, n), BF), ((1, seq, n), BF), ((h,), BF))


def test_rglru_compiles_for_v5e(one_chip):
    width, seq = get_config("recurrentgemma-9b").rglru_width, 512
    _compile(lambda x, a, g: rk.rglru(x, a, g), one_chip,
             ((1, seq, width), BF), ((1, seq, width), BF),
             ((1, seq, width), BF))
