"""Entry-point plumbing: which config a fleet serves, where the compile
cache lives, and that the kernel dispatch never hides the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.launch import compile_cache
from repro.launch.serve import serving_config
from repro.models import blocks as blk
from repro.models import model as M


def test_serving_config_full_width_holds_bf16_weights():
    cfg = serving_config("qwen3-4b", smoke=False)
    pub = get_config("qwen3-4b")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size) == \
        (36, 2560, 32, 8, 128, 9728, 151936)
    assert cfg.param_dtype == cfg.dtype == jnp.bfloat16
    assert cfg.replace(param_dtype=pub.param_dtype) == pub
    shapes = jax.eval_shape(lambda k: M.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    assert {x.dtype for x in jax.tree.leaves(shapes)} == {jnp.dtype(jnp.bfloat16)}
    # the smoke config is served exactly as before
    assert serving_config("qwen3-4b", smoke=True).param_dtype == jnp.float32


def test_serve_cli_no_smoke_flag():
    from repro.launch.serve import build_parser
    assert build_parser().parse_args([]).smoke
    assert not build_parser().parse_args(["--no-smoke"]).smoke


def test_loop_report_reads_stamps_and_counters():
    from types import SimpleNamespace

    from repro.core.telemetry import LoopCounters
    from repro.launch.serve import loop_report
    from repro.serving.engine import RequestResult

    def served(rid, wait, predicted, replica="replica0"):
        return RequestResult(rid, np.zeros(4, np.int32), 200.0, replica, 100.0,
                             enqueued_ms=101.0, admitted_ms=101.0 + wait,
                             first_ms=150.0, predicted_ms=predicted)

    results = [served(0, 2.0, 50.0), served(1, 4.0, 150.0),
               served(2, 9.0, 0.0, replica="replica1")]
    reps = [SimpleNamespace(name="replica0", slots=2, capacity=64,
                            counters=LoopCounters(
        turns=8, decode_steps=6, decode_tokens=9, context_tokens=192,
        prefill_chunks=2, loop_ms=400.0, prefill_ms=100.0)),
        SimpleNamespace(name="replica2", slots=2, capacity=64,
                        counters=LoopCounters())]
    first, idle = loop_report(results, reps)
    # waits 2 and 4 ms; |100 - 50| / 100 and |100 - 150| / 100;
    # 192 context tokens of 6 steps x 2 lanes x 64 slots
    assert first == ("replica0: queue wait p50=3.0ms p90=3.8ms prefill "
                     "share=25.0% predictor error p50=50.0% p90=50.0% "
                     "KV slots read=25.0% "
                     "(8 turns, 6 decode steps, 9 tokens, 2 prefill chunks)")
    assert "queue wait p50=n/a" in idle and "share=0.0%" in idle
    assert "KV slots read=n/a" in idle


def test_init_model_stacks_layers_as_per_layer_draws():
    """Each period slot is one vmap over its layers' keys: layer r must hold
    exactly what ``init_block`` draws from that layer's own key."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("recurrentgemma-9b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    keys = jax.random.split(jax.random.PRNGKey(0), cfg.num_layers + 2)
    kinds = list(zip(cfg.layer_kinds(), cfg.attn_kinds()))
    p_len = cfg.pattern_period
    for i in range(cfg.num_periods * p_len):
        r, s = divmod(i, p_len)
        one = blk.init_block(keys[i], cfg, *kinds[i])
        got = jax.tree.map(lambda a: a[r], params["periods"][s])
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(one)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_impl_refuses_non_pallas_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "jnp")
    with pytest.raises(RuntimeError, match="Pallas kernels only"):
        ops.kernel_impl()
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas_interpret")
    with pytest.raises(RuntimeError):
        ops.kernel_impl()
    monkeypatch.delenv("REPRO_KERNEL_IMPL")
    assert ops.kernel_impl() == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops.kernel_impl() == "jnp"


def test_compile_cache_dir_rule(monkeypatch):
    """The env var wins and nothing else is set; otherwise a fixed
    ``<repo>/.jax_cache``.  (``jax.config.update`` is recorded, not applied:
    tests never turn the cache on.)"""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
    assert (compile_cache.REPO_ROOT / "chip_smoke.py").exists()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert ".jax_cache" in (compile_cache.REPO_ROOT / ".gitignore").read_text()
