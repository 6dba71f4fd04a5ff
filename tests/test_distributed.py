"""Multi-device distribution tests (subprocess with fake host devices):
spmd flash-decode vs reference, int8 compressed all-reduce, sharded
train-step parity with single-device, elastic checkpoint restore across
mesh sizes."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env.setdefault("REPRO_KERNEL_IMPL", "jnp")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_spmd_decode_matches_reference():
    run_py("""
    from repro.launch.mesh import auto_mesh
    import jax, jax.numpy as jnp, numpy as np
    from repro.kernels import ref
    from repro.serving.spmd_decode import spmd_decode_attention
    mesh = auto_mesh((2, 4), ("data", "model"))
    b, s, hq, hkv, d = 4, 32, 8, 2, 16
    for trial, (idx, window) in enumerate([(5, 0), (20, 8), (31, 0)]):
        ks = jax.random.split(jax.random.PRNGKey(trial), 5)
        q = jax.random.normal(ks[0], (b,1,hq,d))
        kc = jax.random.normal(ks[1], (b,s,hkv,d))
        vc = jax.random.normal(ks[2], (b,s,hkv,d))
        nk = jax.random.normal(ks[3], (b,1,hkv,d))
        nv = jax.random.normal(ks[4], (b,1,hkv,d))
        pos = jnp.where(jnp.arange(s) < idx, jnp.arange(s), -1).astype(jnp.int32)
        out, kc2, vc2, pos2 = jax.jit(lambda *a: spmd_decode_attention(
            mesh, *a, window=window, scale=d**-0.5))(q, kc, vc, nk, nv, pos, idx)
        kref = kc.at[:, idx].set(nk[:,0]); vref = vc.at[:, idx].set(nv[:,0])
        pref = pos.at[idx].set(idx)
        valid = pref >= 0
        if window: valid &= pref > idx - window
        exp = ref.decode_mha_masked(q, kref, vref, valid_mask=valid, scale=d**-0.5)
        assert float(jnp.abs(out-exp).max()) < 1e-5
        assert float(jnp.abs(kc2-kref).max()) == 0
        assert int(jnp.abs(pos2-pref).max()) == 0
    print("OK")
    """)


def test_spmd_decode_per_lane_matches_reference():
    """Per-lane (B,) cache_index: lanes at different depths (different ring
    slots, landing in different S-shards) must match the per-lane
    single-device reference — including per-lane sliding windows."""
    run_py("""
    from repro.launch.mesh import auto_mesh
    import jax, jax.numpy as jnp, numpy as np
    from repro.kernels import ref
    from repro.serving.spmd_decode import spmd_decode_attention
    mesh = auto_mesh((2, 4), ("data", "model"))
    b, s, hq, hkv, d = 4, 32, 8, 2, 16
    for trial, window in enumerate([0, 8]):
        ks = jax.random.split(jax.random.PRNGKey(trial), 5)
        q = jax.random.normal(ks[0], (b,1,hq,d))
        kc = jax.random.normal(ks[1], (b,s,hkv,d))
        vc = jax.random.normal(ks[2], (b,s,hkv,d))
        nk = jax.random.normal(ks[3], (b,1,hkv,d))
        nv = jax.random.normal(ks[4], (b,1,hkv,d))
        idx = jnp.asarray([5, 20, 31, 0], jnp.int32)      # one per lane
        ar = jnp.arange(s)[None, :]
        pos = jnp.where(ar < idx[:, None], ar, -1).astype(jnp.int32)
        out, kc2, vc2, pos2 = jax.jit(lambda *a: spmd_decode_attention(
            mesh, *a, window=window, scale=d**-0.5))(q, kc, vc, nk, nv, pos, idx)
        lanes = jnp.arange(b); slots = idx % s
        kref = kc.at[lanes, slots].set(nk[:,0])
        vref = vc.at[lanes, slots].set(nv[:,0])
        pref = pos.at[lanes, slots].set(idx)
        valid = pref >= 0
        if window: valid &= pref > idx[:, None] - window
        exp = ref.decode_mha_masked(q, kref, vref, valid_mask=valid, scale=d**-0.5)
        assert float(jnp.abs(out-exp).max()) < 1e-5
        assert float(jnp.abs(kc2-kref).max()) == 0
        assert int(jnp.abs(pos2-pref).max()) == 0
    print("OK")
    """)


def test_decode_step_per_lane_on_mesh_matches_single_device():
    """model.decode_step with a per-lane (B,) cache_index under a serving
    mesh (spmd split-S decode) must equal the single-device path — the
    NotImplementedError this combination used to raise is gone."""
    run_py("""
    from repro.launch.mesh import auto_mesh
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.sharding import context as shctx
    cfg = get_smoke_config("granite-8b").replace(param_dtype=jnp.float32,
                                                 dtype=jnp.float32)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    B, cap = 4, 32
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0, cfg.vocab_size)
    idx = jnp.asarray([0, 3, 7, 12], jnp.int32)
    cache = M.init_cache(cfg, B, cap)
    lg_ref, cache_ref = jax.jit(
        lambda p,c,t,i: M.decode_step(p,c,t,i,cfg))(params, cache, tok, idx)
    mesh = auto_mesh((2, 4), ("data", "model"))
    with shctx.serving_mesh(mesh):
        lg_mesh, cache_mesh = jax.jit(
            lambda p,c,t,i: M.decode_step(p,c,t,i,cfg))(params, cache, tok, idx)
    assert float(jnp.abs(lg_ref - lg_mesh).max()) < 1e-4
    d = jax.tree.map(lambda a,b: float(jnp.abs(
        a.astype(jnp.float32)-b.astype(jnp.float32)).max()),
        cache_ref, cache_mesh)
    assert max(jax.tree.leaves(d)) < 1e-5
    print("OK")
    """)


def test_mesh_replica_tokens_match_single_device_reference():
    """A sharded Replica (serving_mesh set) running the full
    continuous-batching loop — chunked prefill, mid-stream lane join,
    per-lane indices through the spmd decode — must produce greedy tokens
    identical to the plain single-device decode loop, and fixed-seed
    sampled requests must reproduce across runs on the mesh."""
    run_py("""
    from repro.launch.mesh import auto_mesh
    import threading, time
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.engine import Replica, Request
    cfg = get_smoke_config("granite-8b").replace(param_dtype=jnp.float32,
                                                 dtype=jnp.float32)
    params = M.init_model(jax.random.PRNGKey(0), cfg)

    def reference(prompt, max_new, capacity=64):
        logits, cache = M.prefill(params, jnp.asarray(prompt)[None], cfg,
                                  capacity=capacity)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        out, pos = [], len(prompt)
        for _ in range(max_new):
            out.append(int(tok[0, 0]))
            lg, cache = M.decode_step(params, cache, tok, pos, cfg)
            tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
            pos += 1
        return out

    mesh = auto_mesh((1, 4), ("data", "model"))
    rep = Replica("mesh0", cfg, params, slots=2, capacity=64,
                  prefill_chunk_tokens=4, serving_mesh=mesh)
    rng = np.random.default_rng(11)
    long_prompt = rng.integers(2, cfg.vocab_size, size=(10,)).astype(np.int32)
    late_prompt = rng.integers(2, cfg.vocab_size, size=(17,)).astype(np.int32)
    out = {}
    def run_long():
        out["long"] = rep.generate(Request(0, long_prompt, 12, 1e9)).tolist()
    def run_late():
        time.sleep(0.05)
        out["late"] = rep.generate(Request(1, late_prompt, 5, 1e9)).tolist()
    t1 = threading.Thread(target=run_long); t2 = threading.Thread(target=run_late)
    t1.start(); t2.start(); t1.join(); t2.join()
    assert out["long"] == reference(long_prompt, 12), out
    assert out["late"] == reference(late_prompt, 5), out

    # sampled on the mesh: same key discipline as the engine, hand-rolled
    # single-device — the spmd decode must be distribution-transparent
    from repro.serving import sampling as S
    def sampled_reference(prompt, max_new, temp, seed, capacity=64):
        logits, cache = M.prefill(params, jnp.asarray(prompt)[None], cfg,
                                  capacity=capacity)
        keys = jnp.asarray(S.make_lane_key(seed))[None]
        t = jnp.full((1,), temp, jnp.float32)
        k0 = jnp.zeros((1,), jnp.int32); p1 = jnp.ones((1,), jnp.float32)
        keys, tok = S.sample_lane_tokens(
            keys, jnp.asarray(logits[0, -1], jnp.float32)[None], t, k0, p1)
        out, pos = [], len(prompt)
        for _ in range(max_new):
            out.append(int(tok[0]))
            lg, cache = M.decode_step(params, cache, tok[:, None], pos, cfg)
            keys, tok = S.sample_lane_tokens(keys, lg[:, -1], t, k0, p1)
            pos += 1
        return out

    ms1 = rep.generate(Request(2, long_prompt, 6, 1e9, temperature=0.8,
                               seed=5)).tolist()
    ms2 = rep.generate(Request(3, long_prompt, 6, 1e9, temperature=0.8,
                               seed=5)).tolist()
    rep.stop()
    assert ms1 == ms2, (ms1, ms2)
    assert ms1 == sampled_reference(long_prompt, 6, 0.8, 5), ms1
    print("OK")
    """, devices=4)


def test_replicas_run_on_the_device_their_params_live_on():
    """One process drives several devices: a Replica whose params live on
    device 1 keeps its caches there and decodes there, token-identical to
    a replica on device 0 (no constructor knob: placement follows the
    params)."""
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.launch.serve import replica_params
    from repro.serving.engine import Replica, Request
    cfg = get_smoke_config("qwen3-4b")
    params = replica_params(cfg, 2)
    devs = jax.devices()
    reps = [Replica(f"r{i}", cfg, params[i], slots=2, capacity=64)
            for i in range(2)]
    assert [r.device for r in reps] == devs[:2], [r.device for r in reps]
    for r, d in zip(reps, devs):
        assert {next(iter(x.devices())) for x in jax.tree.leaves(r._cache)} == {d}
    prompt = np.arange(2, 14, dtype=np.int32)
    outs = [r.generate(Request(0, prompt, 6, 1e9)).tolist() for r in reps]
    assert outs[0] == outs[1], outs
    for r, d in zip(reps, devs):
        assert {next(iter(x.devices())) for x in jax.tree.leaves(r._cache)} == {d}
        r.stop()
    print("OK")
    """, devices=2)


def test_pallas_prefill_under_serving_mesh_matches_single_device():
    """XLA cannot partition a Pallas call: under a serving mesh the flash
    kernel runs per device through shard_map — heads split when they
    divide the mesh axis, replicated when not — and whole-prompt prefill
    must equal the single-device result either way."""
    run_py("""
    import os
    os.environ["REPRO_KERNEL_IMPL"] = "pallas_interpret"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.launch.mesh import auto_mesh
    from repro.models import model as M
    from repro.sharding import context as shctx
    mesh = auto_mesh((1, 4), ("data", "model"))
    base = get_smoke_config("qwen3-4b").replace(param_dtype=jnp.float32,
                                                dtype=jnp.float32)
    for heads, kv in ((8, 4), (4, 2)):       # head-split, replicated
        cfg = base.replace(num_heads=heads, num_kv_heads=kv)
        params = M.init_model(jax.random.PRNGKey(0), cfg)
        toks = jnp.arange(2, 18, dtype=jnp.int32)[None]
        want, _ = jax.jit(lambda p, t: M.prefill(p, t, cfg, 32))(params, toks)
        with shctx.serving_mesh(mesh):
            got, _ = jax.jit(lambda p, t: M.prefill(p, t, cfg, 32))(params,
                                                                    toks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
    print("OK")
    """, devices=4)


def test_int8_compressed_allreduce():
    run_py("""
    from repro.launch.mesh import auto_mesh
    import jax, jax.numpy as jnp, numpy as np
    from repro.training.compression import make_compressed_allreduce
    mesh = auto_mesh((8,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 128)) * 0.02
    fn = make_compressed_allreduce(mesh, "data")
    out = np.asarray(fn({"g": x})["g"])[0]
    exact = np.mean(np.asarray(x), axis=0)
    # int8 quantization error is bounded by ~ (amax/127) per shard
    tol = float(np.abs(np.asarray(x)).max()) / 127.0 + 1e-6
    assert np.abs(out - exact).max() <= tol, np.abs(out - exact).max()
    print("OK")
    """)


def test_sharded_train_step_matches_single_device():
    """The same train step on a 4x2 mesh and on 1 device must produce the
    same loss and (numerically) the same updated params."""
    run_py("""
    from repro.launch.mesh import auto_mesh
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.common.config import TrainConfig
    from repro.configs import get_smoke_config
    from repro.launch.mesh import parallel_config_for
    from repro.sharding import specs as sp
    from repro.training import steps as steps_lib

    cfg = get_smoke_config("granite-8b").replace(param_dtype=jnp.float32,
                                                 dtype=jnp.float32)
    tc = TrainConfig(total_steps=10)
    key = jax.random.PRNGKey(0)
    state = steps_lib.init_train_state(key, cfg)
    tokens = jax.random.randint(key, (8, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens,
             "mask": jnp.ones((8, 32), jnp.float32)}
    step = steps_lib.make_train_step(cfg, tc)

    # single device
    s1, m1 = jax.jit(step)(state, batch)

    # sharded
    mesh = auto_mesh((4, 2), ("data", "model"))
    pc = parallel_config_for(mesh)
    specs = sp.state_specs(jax.eval_shape(lambda: state), mesh, pc)
    st_sh = sp.named(mesh, specs)
    bspec = sp.named(mesh, {k: P("data", None) for k in batch})
    fn = jax.jit(step, in_shardings=(st_sh, bspec), out_shardings=(st_sh, None))
    s2, m2 = fn(jax.device_put(state, st_sh),
                {k: jax.device_put(v, bspec[k]) for k, v in batch.items()})
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                     s1["params"], s2["params"])
    assert max(jax.tree.leaves(d)) < 1e-4
    print("OK")
    """)


def test_elastic_restore_across_mesh_sizes(tmp_path):
    """Save on an 8-device (4,2) mesh, restore onto (2,2) using 4 devices —
    the elastic rescale path (checkpoint is mesh-agnostic)."""
    run_py(f"""
    from repro.launch.mesh import auto_mesh
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.checkpoint.manager import CheckpointManager
    from repro.common.config import ParallelConfig
    from repro.configs import get_smoke_config
    from repro.ft.elastic import plan_rescale, reshard_state
    from repro.launch.mesh import parallel_config_for
    from repro.sharding import specs as sp
    from repro.training import steps as steps_lib

    cfg = get_smoke_config("qwen3-4b")
    state = steps_lib.init_train_state(jax.random.PRNGKey(0), cfg)
    mesh8 = auto_mesh((4, 2), ("data", "model"))
    specs8 = sp.state_specs(jax.eval_shape(lambda: state), mesh8,
                            parallel_config_for(mesh8))
    state8 = jax.device_put(state, sp.named(mesh8, specs8))
    mgr = CheckpointManager({str(tmp_path)!r})
    mgr.save(1, state8)

    plan = plan_rescale(ParallelConfig(dp=4, tp=2), available_devices=4)
    assert plan.new_tp == 2 and plan.new_dp == 2
    mesh4 = auto_mesh((plan.new_dp, plan.new_tp), ("data", "model"))
    pc4 = parallel_config_for(mesh4)
    template = jax.eval_shape(lambda: state)
    restored = mgr.restore(1, template)
    from repro.common.tree import tree_paths
    spec_map = dict(tree_paths(sp.state_specs(template, mesh4, pc4)))
    restored = reshard_state(restored, mesh4, lambda p: spec_map[p])
    from repro.common.tree import tree_allclose
    assert tree_allclose(jax.device_get(state8), jax.device_get(restored))
    print("OK")
    """)


def test_gpipe_pipeline_matches_forward():
    """SPMD GPipe over a 4-stage mesh must equal the plain forward."""
    run_py("""
    from repro.launch.mesh import auto_mesh
    import jax, jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.training.pipeline import pipeline_forward
    cfg = get_smoke_config("granite-8b").replace(
        num_layers=4, param_dtype=jnp.float32, dtype=jnp.float32)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                cfg.vocab_size)
    mesh = auto_mesh((4,), ("stage",))
    got = jax.jit(lambda p, t: pipeline_forward(
        mesh, "stage", p, t, cfg, num_microbatches=4))(params, tokens)
    want, _ = M.forward(params, tokens, cfg)
    assert float(jnp.abs(got - want).max()) < 1e-4
    print("OK")
    """, devices=4)


def test_gpipe_heterogeneous_periods():
    """Pipeline a gemma3-style (5 local + 1 global) period stack."""
    run_py("""
    from repro.launch.mesh import auto_mesh
    import jax, jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.training.pipeline import pipeline_forward
    cfg = get_smoke_config("gemma3-27b").replace(
        num_layers=12, param_dtype=jnp.float32, dtype=jnp.float32)  # 2 periods
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size)
    mesh = auto_mesh((2,), ("stage",))
    got = jax.jit(lambda p, t: pipeline_forward(
        mesh, "stage", p, t, cfg, num_microbatches=2))(params, tokens)
    want, _ = M.forward(params, tokens, cfg)
    assert float(jnp.abs(got - want).max()) < 1e-4
    print("OK")
    """, devices=2)
