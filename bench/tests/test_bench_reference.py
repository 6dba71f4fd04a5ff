"""The plain references against the program's jnp forward pass at small
widths, in float32 at the highest matmul precision."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests import sizes


@pytest.mark.parametrize("make", [sizes.small_qwen, sizes.small_mamba],
                         ids=["qwen3", "mamba2"])
def test_reference_matches_program_forward(make):
    from repro.configs import get_config
    from repro.models import model as model_lib

    config = make()
    dims = harness.dims_of(config)
    ref = harness.reference_of(config)
    f32 = jnp.float32
    cfg = get_config(config["program"]["arch"]).replace(
        dtype=f32, param_dtype=f32, **dims)
    params = ref.init_params(jax.random.PRNGKey(3), dims, f32)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        2, dims["vocab_size"], size=48), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model_lib.forward(params, toks[None], cfg)
        got = ref.logits(params, dims, toks, 0, 48)
        part = ref.logits(params, dims, toks, 40, 8)
    want = np.asarray(want[0])
    scale = np.abs(want).max()
    assert scale > 1.0                       # logits have a real spread
    assert np.abs(np.asarray(got) - want).max() <= 1e-4 * scale
    assert np.abs(np.asarray(part) - want[40:]).max() <= 1e-4 * scale


def test_weights_have_the_programs_layout():
    from repro.configs import get_config
    from repro.models import model as model_lib

    for make in (sizes.small_qwen, sizes.small_mamba):
        config = make()
        dims = harness.dims_of(config)
        cfg = get_config(config["program"]["arch"]).replace(**dims)
        ref = harness.reference_of(config)
        mine = jax.eval_shape(lambda k: ref.init_params(k, dims, jnp.bfloat16),
                              jax.random.PRNGKey(0))
        prog = jax.eval_shape(lambda k: model_lib.init_model(k, cfg),
                              jax.random.PRNGKey(0))
        shapes = lambda t: jax.tree.map(lambda a: a.shape, t)
        assert jax.tree.structure(mine) == jax.tree.structure(prog)
        assert shapes(mine) == shapes(prog)


def test_control_rounds_to_fp8():
    from bench.reference.common import fp8
    x = jnp.asarray([[1.0, 0.3, -0.0173, 448.0 / 3]], jnp.float32)
    q = np.asarray(fp8(x, -1))
    rel = np.abs(q - np.asarray(x)) / np.abs(np.asarray(x))
    assert (rel <= 2.0 ** -4 + 1e-6).all()   # 3 mantissa bits
    assert (rel > 0).any()
