#!/usr/bin/env python3
"""Bring-up smoke test of the DDS serving path on a TPU.

  python3 chip_smoke.py               # one chip
  python3 chip_smoke.py --four-chips  # the multi-chip paths, on four chips

One chip:
  1. every Pallas kernel against its ``kernels/ref.py`` oracle (float32,
     highest matmul precision) at qwen3-4b widths — flash prefill for a
     32-token chunk and a 512-token prompt, ring decode, paged decode —
     plus SSD at mamba2-780m widths and RG-LRU at recurrentgemma-9b widths;
  2. qwen3-4b at its published widths (36 layers, d_model 2560, 32/8 heads
     of 128, d_ff 9728, vocab 151936; random bf16 weights from a seed)
     served through ``ServingFleet.submit``: 8 requests with 128-512-token
     prompts on a ring-KV replica, and 4 requests sharing a 256-token system
     prompt on a paged replica with the prefix cache;
  3. whole-prompt prefill (flash kernel) against chunked prefill of the
     same prompt: last-position logits within a bf16 bound, which a last
     chunk prefilled without the earlier ones must exceed.

Four chips (``--four-chips``; only these paths and their baselines; qwen3-4b
at published widths, depth cut to 9 of 36 layers):
  * four one-chip qwen3-4b replicas, one per device, behind the DDS
    ``ServingFleet``: the coordinator spreads 16 requests over all four,
    their greedy tokens equal one replica's for the same prompts, and
    every device holds a copy of the weights;
  * ``Replica(serving_mesh=...)`` on a (1, 4) mesh: prefill and decode
    logits against the one-chip replica's within a bf16 bound.

The script needs a TPU and the Pallas kernel path: anywhere else it exits
non-zero without printing a result.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The compile cache follows ``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

BF16_BOUND = 2e-2        # kernel vs f32 oracle, relative to the oracle's max
MODEL_BOUND = 1e-2       # model logits, two bf16 paths, relative to max
DEADLINE_MS = 600_000.0  # requests here test correctness, not SLOs

SEQ = 512                # kernel checks: prompt length
PAGE = 16                # paged KV page
CHUNK = 32               # prefill chunk budget
SLOTS, CAPACITY = 8, 1024            # ring replica (and the decode kernels)
RING_PROMPTS = (128, 512)
PAGED_SLOTS, PAGED_CAPACITY, SYSTEM_LEN = 4, 512, 256
NEW_TOKENS = 32
# four chips: published widths with the depth cut to a quarter (2.6 GB of
# weights a copy: shorter init and decode steps).  As many requests as the
# four replicas have lanes, each long enough (>= 2.4 s at the 3.2 ms it
# takes to stream the weights) that none ends before the last arrives:
# the DDS coordinator then fills the other replicas' 12 lanes and keeps
# the last 4 itself
FOUR_CHIP_LAYERS = 9
FOUR_SLOTS, FOUR_CAPACITY = 4, 1024
FOUR_REQUESTS, FOUR_PROMPTS, FOUR_NEW_TOKENS = 16, (64, 128), 768


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))


def check(name: str, err: float, bound: float) -> None:
    print(f"{name}: rel_err={err:.3e} (bound {bound:g})", flush=True)
    if not err <= bound:
        fail(f"{name}: rel_err {err:.3e} > {bound:g}")


# ------------------------------------------------------------------ kernels
def kernel_checks(qwen, mamba, rglru_width: int) -> None:
    """Each Pallas kernel against its oracle at the given widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import decode_attention as dk
    from repro.kernels import flash_attention as fk
    from repro.kernels import ref
    from repro.kernels import rglru_scan as rk
    from repro.kernels import ssd_scan as sk

    bf, f32 = jnp.bfloat16, jnp.float32
    hq, hkv, hd = qwen.num_heads, qwen.num_kv_heads, qwen.resolved_head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 32))

    def rnd(shape, dtype=bf, scale=1.0):
        return (jax.random.normal(next(keys), shape, f32) * scale).astype(dtype)

    def oracle(fn, *args, **kw):
        with jax.default_matmul_precision("highest"):
            up = [a.astype(f32) if a.dtype == bf else a for a in args]
            return jax.jit(lambda *a: fn(*a, **kw))(*up)

    for s in (CHUNK, SEQ):
        q, k, v = rnd((1, s, hq, hd)), rnd((1, s, hkv, hd)), rnd((1, s, hkv, hd))
        got = jax.jit(lambda *a: fk.flash_attention(*a, causal=True))(q, k, v)
        check(f"kernel flash_attention S={s}", rel_err(
            got, oracle(ref.mha_reference, q, k, v, causal=True)), BF16_BOUND)

    # ring decode: per-lane lengths, some lanes past the ring (wrapped)
    rng = np.random.default_rng(2)
    lens = rng.integers(1, CAPACITY + CAPACITY // 2, size=SLOTS).astype(np.int32)
    slot = np.arange(CAPACITY)[None]
    pos = slot + ((lens[:, None] - 1 - slot) // CAPACITY) * CAPACITY
    pos = jnp.asarray(np.where(pos >= 0, pos, -1), jnp.int32)
    q = rnd((SLOTS, 1, hq, hd))
    kc, vc = rnd((SLOTS, CAPACITY, hkv, hd)), rnd((SLOTS, CAPACITY, hkv, hd))
    lens = jnp.asarray(lens)
    got = jax.jit(lambda *a: dk.decode_attention(
        *a[:3], cache_len=a[3], pos=a[4]))(q, kc, vc, lens, pos)
    valid = (pos >= 0) & (pos < lens[:, None])
    check(f"kernel decode_attention capacity={CAPACITY}", rel_err(
        got, oracle(ref.decode_mha_masked, q, kc, vc, valid_mask=valid)),
        BF16_BOUND)

    # paged decode: shuffled pool rows, absent slots, a stale page
    maxp = CAPACITY // PAGE
    rows = SLOTS * maxp + 1
    perm = rng.permutation(rows - 1).astype(np.int32).reshape(SLOTS, maxp)
    plens = rng.integers(1, CAPACITY, size=SLOTS).astype(np.int32)
    tables = np.where(np.arange(maxp)[None] * PAGE < plens[:, None], perm, -1)
    ppos = np.full((rows, PAGE), -1, np.int32)
    for b in range(SLOTS):
        for j in range(maxp):
            if tables[b, j] >= 0:
                ppos[tables[b, j]] = j * PAGE + np.arange(PAGE)
    # a previous tenant's page under the longest lane's first table slot
    ppos[perm[int(np.argmax(plens)), 0]] += 7
    kp, vp = rnd((rows, PAGE, hkv, hd)), rnd((rows, PAGE, hkv, hd))
    tables, ppos, plens = map(jnp.asarray, (tables, ppos, plens))
    got = jax.jit(lambda *a: dk.paged_decode_attention(
        *a[:5], cache_len=a[5]))(q, kp, vp, ppos, tables, plens)
    check(f"kernel paged_decode_attention page={PAGE}", rel_err(
        got, oracle(ref.paged_decode_mha_reference, q, kp, vp, ppos, tables,
                    cache_len=plens)), BF16_BOUND)

    h, p, n = mamba.ssm_heads, mamba.ssm_head_dim, mamba.ssm_state
    x = rnd((1, SEQ, h, p))
    dt = jax.nn.softplus(rnd((1, SEQ, h), f32)).astype(bf)
    a_log = rnd((h,), f32, 0.5)
    bm, cm = rnd((1, SEQ, n), scale=0.3), rnd((1, SEQ, n), scale=0.3)
    dsk = jnp.ones((h,), f32)
    got = jax.jit(lambda *a: sk.ssd(*a, chunk=mamba.ssm_chunk))(
        x, dt, a_log, bm, cm, dsk)
    check(f"kernel ssd S={SEQ} heads={h}x{p} state={n}", rel_err(
        got, oracle(ref.ssd_reference, x, dt, a_log, bm, cm, dsk)),
        BF16_BOUND)

    x = rnd((1, SEQ, rglru_width))
    log_a = -jax.nn.softplus(rnd((1, SEQ, rglru_width), f32)).astype(bf)
    gate = jax.nn.sigmoid(rnd((1, SEQ, rglru_width), f32)).astype(bf)
    got = jax.jit(rk.rglru)(x, log_a, gate)
    check(f"kernel rglru S={SEQ} width={rglru_width}", rel_err(
        got, oracle(ref.rglru_reference, x, log_a, gate)), BF16_BOUND)


# ------------------------------------------------------------------ serving
def _submit_all(fleet, reqs):
    with ThreadPoolExecutor(max_workers=max(len(reqs), 1)) as ex:
        return list(ex.map(fleet.submit, reqs))


def _submit_paced(fleet, reqs):
    """Submit one request at a time, each once every earlier one has been
    routed and has left its replica's queue, and a heartbeat has published
    that, so routing sees each placement however slowly a decode loop
    admits work."""
    reps = list(fleet.replicas.values())
    routed = sum(fleet.stats.values())
    with ThreadPoolExecutor(max_workers=len(reqs)) as ex:
        futs = []
        for i, req in enumerate(reqs):
            futs.append(ex.submit(fleet.submit, req))
            give_up = time.monotonic() + 60.0
            while (sum(fleet.stats.values()) < routed + i + 1
                   or any(r.state().queued for r in reps)):
                if time.monotonic() > give_up:
                    fail(f"request {req.request_id} was never admitted")
                time.sleep(0.005)
            time.sleep(2 * fleet.heartbeat_ms / 1e3)
        return [f.result() for f in futs]


def _check_results(name: str, results, reqs, vocab: int) -> None:
    import numpy as np
    outcomes = {}
    for r in results:
        outcomes[r.outcome] = outcomes.get(r.outcome, 0) + 1
    print(f"{name}: requests by outcome {outcomes}", flush=True)
    for r, q in zip(results, reqs):
        toks = np.asarray(r.tokens)
        if r.outcome != "ok" or r.error is not None:
            fail(f"{name}: request {q.request_id} {r.outcome}: {r.error}")
        if len(toks) != q.max_new_tokens:
            fail(f"{name}: request {q.request_id} returned {len(toks)} of "
                 f"{q.max_new_tokens} tokens")
        if toks.min() < 0 or toks.max() >= vocab:
            fail(f"{name}: request {q.request_id} token out of vocab range")


def _rate_line(name: str, results, wall_s: float) -> None:
    import numpy as np
    ttft = np.asarray([r.ttft_ms for r in results])
    toks = sum(len(r.tokens) for r in results)
    print(f"{name}: TTFT p50={np.median(ttft):.1f}ms max={ttft.max():.1f}ms; "
          f"{toks} tokens in {wall_s:.2f}s = {toks / wall_s:.1f} tokens/s "
          f"(host clock)", flush=True)


def _memory_line(devices) -> None:
    for d in devices:
        st = d.memory_stats() or {}
        print(f"memory {d}: bytes_in_use={st.get('bytes_in_use')} "
              f"peak_bytes_in_use={st.get('peak_bytes_in_use')}", flush=True)


def serving_checks(cfg) -> None:
    """Ring and paged+prefix replicas behind ``ServingFleet`` on one device,
    plus the whole-prompt vs chunked prefill logits check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import build_fleet, replica_params
    from repro.models import model as model_lib
    from repro.serving.engine import Request

    t0 = time.perf_counter()
    params = replica_params(cfg, 1)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params[0]))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params[0]))
    print(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads}x"
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; {n_params} params = {n_bytes} bytes "
          f"({jax.tree.leaves(params[0])[0].dtype}); init "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    rng = np.random.default_rng(0)

    # ---- ring replica: prompts of 128..512 tokens
    ring = build_fleet(cfg, "DDS", replicas=1, slots=SLOTS,
                       capacity=CAPACITY, prefill_chunk_tokens=CHUNK,
                       params=params)
    reqs = [Request(i, rng.integers(2, cfg.vocab_size,
                                    size=int(rng.integers(*RING_PROMPTS,
                                                          endpoint=True))
                                    ).astype(np.int32),
                    NEW_TOKENS, DEADLINE_MS) for i in range(SLOTS)]
    t0 = time.perf_counter()
    results = _submit_all(ring, reqs)
    wall = time.perf_counter() - t0
    _check_results("ring", results, reqs, cfg.vocab_size)
    _rate_line("ring", results, wall)

    # ---- whole-prompt prefill (flash kernel) vs chunked prefill, and a
    # planted fault the bound must catch: the last chunk prefilled without
    # the chunks before it
    rep = ring.replicas["replica0"]
    toks = jnp.asarray(reqs[0].prompt[:128])[None]
    last = toks.shape[1] - CHUNK
    with rep._scope():
        whole, _ = rep._prefill(rep.params, toks)
        lane = model_lib.init_cache(cfg, 1, rep.capacity)
        for start in range(0, toks.shape[1], CHUNK):
            part, lane = rep._prefill_chunk(
                rep.params, lane, toks[:, start:start + CHUNK], start)
        alone, _ = rep._prefill_chunk(
            rep.params, model_lib.init_cache(cfg, 1, rep.capacity),
            toks[:, last:], last)
    check(f"model prefill whole vs chunked ({toks.shape[1]} tokens, last "
          "logits)", rel_err(part, whole), MODEL_BOUND)
    err = rel_err(alone, whole)
    print(f"planted fault, last chunk without the earlier chunks: "
          f"rel_err={err:.3e} (must exceed {MODEL_BOUND:g})", flush=True)
    if not err > MODEL_BOUND:
        fail("the model bound cannot tell a chunk that ignores the "
             "earlier chunks")
    ring.stop()

    # ---- paged replica with the prefix cache: a shared system prompt
    paged = build_fleet(cfg, "DDS", replicas=1, slots=PAGED_SLOTS,
                        capacity=PAGED_CAPACITY, prefill_chunk_tokens=CHUNK,
                        paged=True, prefix_cache=True, params=params)
    system = rng.integers(2, cfg.vocab_size, size=SYSTEM_LEN).astype(np.int32)
    reqs = [Request(100 + i, np.concatenate(
        [system, rng.integers(2, cfg.vocab_size,
                              size=int(rng.integers(16, 48))
                              ).astype(np.int32)]), NEW_TOKENS // 2,
        DEADLINE_MS) for i in range(PAGED_SLOTS)]
    t0 = time.perf_counter()
    # the first request fills the cache; the rest share its pages
    results = [paged.submit(reqs[0])] + _submit_all(paged, reqs[1:])
    wall = time.perf_counter() - t0
    _check_results("paged+prefix", results, reqs, cfg.vocab_size)
    _rate_line("paged+prefix", results, wall)
    hit = paged.replicas["replica0"]._prefix.hit_rate()
    print(f"paged+prefix: prefix hit rate {hit:.3f}", flush=True)
    if not hit > 0.0:
        fail("prefix cache never hit")
    paged.stop()
    _memory_line(jax.devices()[:1])


# --------------------------------------------------------------- four chips
def four_chip_checks(cfg) -> None:
    """Four one-chip replicas behind the DDS fleet vs one replica, and a
    (1, 4)-mesh replica's logits vs the one-chip replica's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.policies import make_policy
    from repro.launch.mesh import auto_mesh
    from repro.launch.serve import replica_params
    from repro.models import model as model_lib
    from repro.serving.engine import (Replica, Request, ServingFleet,
                                      profile_replica)

    devices = jax.devices()[:4]
    if len(devices) < 4:
        fail(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    t0 = time.perf_counter()
    params = replica_params(cfg, 4)
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params[0]))
    print(f"{cfg.name}: {n_bytes} bytes of weights on each of "
          f"{[str(d) for d in devices]}; init+copies "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    for p, d in zip(params, devices):
        if {next(iter(x.devices())) for x in jax.tree.leaves(p)} != {d}:
            fail(f"a replica's weights are not on {d}")
        st = d.memory_stats() or {}
        print(f"memory {d}: bytes_in_use={st.get('bytes_in_use')}",
              flush=True)
        if st and st.get("bytes_in_use", 0) < n_bytes:
            fail(f"{d} holds fewer bytes than one copy of the weights")

    def make(i):
        t = time.perf_counter()
        rep = Replica(f"replica{i}", cfg, params[i], slots=FOUR_SLOTS,
                      capacity=FOUR_CAPACITY, prefill_chunk_tokens=CHUNK)
        return rep, profile_replica(rep), time.perf_counter() - t

    # each replica compiles for its own chip: build the four in parallel
    with ThreadPoolExecutor(max_workers=4) as ex:
        built = list(ex.map(make, range(4)))
    for rep, _, secs in built:
        print(f"{rep.name} on {rep.device}: warmup (compile) "
              f"{rep.warmup_s:.2f}s; built+profiled in {secs:.2f}s",
              flush=True)

    rng = np.random.default_rng(3)
    prompt_list = [rng.integers(2, cfg.vocab_size, size=int(
        rng.integers(*FOUR_PROMPTS, endpoint=True))).astype(np.int32)
        for _ in range(FOUR_REQUESTS)]

    def fresh():       # new Request objects: submit stamps created_ms
        return [Request(i, p, FOUR_NEW_TOKENS, DEADLINE_MS)
                for i, p in enumerate(prompt_list)]

    # Requests come from a client outside the fleet, so the DDS coordinator
    # (replica0) places each one: on an end device with a free lane that
    # meets the deadline (rule 2 keeps the coordinator light), else on
    # itself.  Alone in the fleet it serves every request: the baseline.
    fleet = ServingFleet(make_policy("DDS"), source="client",
                         coordinator="replica0", admission_margin=0.0)
    rep0, prof0, _ = built[0]
    fleet.add_replica(rep0, profile=prof0)
    reqs = fresh()
    base = _submit_all(fleet, reqs)
    _check_results("one replica", base, reqs, cfg.vocab_size)
    for rep, prof, _ in built[1:]:
        fleet.add_replica(rep, profile=prof)
    t0 = time.perf_counter()
    reqs = fresh()
    four = _submit_paced(fleet, reqs)
    wall = time.perf_counter() - t0
    _check_results("four replicas", four, reqs, cfg.vocab_size)
    _rate_line("four replicas", four, wall)
    served = {name: sum(r.replica == name for r in four)
              for name in sorted(fleet.replicas)}
    print(f"four replicas: requests served {served}; fleet placements "
          f"{fleet.stats}", flush=True)
    idle = [name for name, n in served.items() if n == 0]
    if idle:
        fail(f"the fleet placed no request on {idle}")
    for b, f, q in zip(base, four, reqs):
        if not np.array_equal(b.tokens, f.tokens):
            fail(f"request {q.request_id}: {f.replica} tokens differ from "
                 f"the one-replica run")
    print(f"four replicas: greedy tokens of all {FOUR_REQUESTS} requests "
          f"equal the one-replica tokens", flush=True)

    # one-chip reference logits, teacher-forced along its own greedy tokens
    toks = jnp.asarray(reqs[0].prompt[:128])[None]
    ref_logits = []
    with rep0._scope():
        lg, cache = rep0._prefill(rep0.params, toks)
        ref_logits.append(np.asarray(lg[0, -1], np.float32))
        feed = [int(jnp.argmax(lg[0, -1]))]
        for step in range(4):
            lg, cache = rep0._decode(rep0.params, cache,
                                     jnp.asarray([[feed[-1]]], jnp.int32),
                                     toks.shape[1] + step)
            ref_logits.append(np.asarray(lg[0, -1], np.float32))
            feed.append(int(jnp.argmax(lg[0, -1])))
    # the mesh replica re-initialises the same weights from the same seed
    probe = jax.jit(lambda p: sum(jnp.sum(x.astype(jnp.float32))
                                  for x in jax.tree.leaves(p)))
    checksum = float(probe(params[0]))
    fleet.stop()
    # free every one-chip copy now: the mesh holds a full copy per device
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    del fleet, built, rep0, cache, lg, params
    gc.collect()
    _memory_line(devices)

    mesh = auto_mesh((1, 4), ("data", "model"), devices=devices)
    t0 = time.perf_counter()
    mparams = model_lib.init_params(jax.random.PRNGKey(0), cfg,
                                    NamedSharding(mesh, PartitionSpec()))
    if float(probe(mparams)) != checksum:
        fail("the mesh replica's weights differ from the one-chip weights")
    mrep = Replica("mesh0", cfg, mparams, slots=FOUR_SLOTS,
                   capacity=FOUR_CAPACITY, prefill_chunk_tokens=CHUNK,
                   serving_mesh=mesh)
    print(f"mesh0 on {dict(mesh.shape)}: init+warmup (compile) "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    with mrep._scope():
        lg, cache = mrep._prefill(mparams, toks)
        got = [np.asarray(lg[0, -1], np.float32)]
        for step in range(4):
            lg, cache = mrep._decode(mparams, cache,
                                     jnp.asarray([[feed[step]]], jnp.int32),
                                     toks.shape[1] + step)
            got.append(np.asarray(lg[0, -1], np.float32))
    for i, (g, r) in enumerate(zip(got, ref_logits)):
        check(f"mesh (1,4) vs one chip, {'prefill' if i == 0 else 'decode'} "
              f"logits {i}", rel_err(g, r), MODEL_BOUND)
    out = mrep.generate(Request(999, reqs[0].prompt, NEW_TOKENS // 2,
                                DEADLINE_MS))
    if len(out) != NEW_TOKENS // 2:
        fail(f"mesh replica returned {len(out)} of {NEW_TOKENS // 2} tokens")
    print(f"mesh0: served one request through the batched loop "
          f"({len(out)} tokens)", flush=True)
    mrep.stop()
    _memory_line(devices)


# --------------------------------------------------------------------- main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths (needs 4 devices)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no src/repro next to {__file__}: run from a repo checkout")

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")

    from repro.kernels.ops import kernel_impl
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import serving_config

    try:
        impl = kernel_impl()
    except RuntimeError as e:
        fail(str(e))
    if impl != "pallas":
        fail(f"kernel path is {impl!r}, not 'pallas'")
    cache_dir = enable_compile_cache()
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:                           # noqa: BLE001
        libtpu = "unknown"
    print(f"jax {jax.__version__}; libtpu {libtpu}; device {dev.platform} "
          f"{dev.device_kind} x{len(jax.devices())}; kernel path {impl}; "
          f"compile cache {cache_dir}", flush=True)

    qwen = serving_config("qwen3-4b", smoke=False)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_checks(qwen.replace(num_layers=FOUR_CHIP_LAYERS))
    else:
        kernel_checks(qwen, serving_config("mamba2-780m", smoke=False),
                      serving_config("recurrentgemma-9b",
                                     smoke=False).rglru_width)
        serving_checks(qwen)
    print(f"all checks passed in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
