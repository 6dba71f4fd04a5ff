"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call = wall time of the
benchmark itself; derived = the headline metric checked against the paper).
Serving benches additionally write ``BENCH_serving.json`` (tokens/sec at
concurrency 1/4, routing deadline-hit rate, the measured per-occupancy step
curves — single-device and mesh-replica) so the serving perf trajectory is
tracked across PRs.

  PYTHONPATH=src python -m benchmarks.run                  # paper suite
  PYTHONPATH=src python -m benchmarks.run --live           # + live profiling
  PYTHONPATH=src python -m benchmarks.run --serving-smoke  # serving only (CI)
  PYTHONPATH=src python -m benchmarks.run --overload-smoke # overload row (CI)
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

from benchmarks import paper_tables as pt  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def _timed(fn):
    t0 = time.perf_counter()
    rows, derived = fn()
    us = (time.perf_counter() - t0) * 1e6
    return us, derived


BENCHES = [
    ("table2_size_runtime", pt.table2_size_runtime),
    ("tables3to6_container_profiles", pt.tables3to6_container_profiles),
    ("fig5_50images", pt.fig5_50images),
    ("fig6_1000images", pt.fig6_1000images),
    ("fig7_cpu_load", pt.fig7_cpu_load),
    ("fig8_scaleout", pt.fig8_scaleout),
    ("beyond_policies", pt.beyond_policies),
    ("staleness_sweep", pt.staleness_sweep),
]

# registered below (defined in this module, not paper_tables): the serving
# engine's continuous-batching throughput trajectory

# serving benches deposit their headline metrics here; main() writes the
# accumulated dict to BENCH_serving.json (the cross-PR perf trajectory)
SERVING_METRICS = {}


def bench_serving_throughput():
    """Tokens/sec of the multi-lane batched decode engine at concurrency
    1/2/4/8 (greedy, smoke config), against the sequential batch-1 engine
    it replaced.  Records the continuous-batching perf trajectory: lanes
    amortize per-step weight streaming + dispatch, so tokens/sec should
    scale with occupancy while the sequential baseline stays flat."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.engine import Replica, Request

    cfg = get_smoke_config("granite-8b").replace(param_dtype=jnp.float32,
                                                 dtype=jnp.float32)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    prompt_len, new_tokens = 16, 48
    rng = np.random.default_rng(0)

    def reqs(n):
        return [Request(i, rng.integers(2, cfg.vocab_size,
                                        size=(prompt_len,)).astype(np.int32),
                        new_tokens, 1e9) for i in range(n)]

    rep = Replica("bench", cfg, params, slots=8, capacity=128)
    # warm both paths' shapes out of the timed region
    rep.generate(reqs(1)[0])
    rep.generate_sequential(reqs(1)[0])

    rows = []
    batched_tps = {}
    for conc in (1, 2, 4, 8):
        rs = reqs(conc)
        t0 = time.perf_counter()
        threads = [threading.Thread(target=rep.generate, args=(r,))
                   for r in rs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        batched_tps[conc] = conc * new_tokens / dt
        rows.append({"conc": conc, "batched_tok_s": round(batched_tps[conc], 1)})

    # sequential baseline (the seed engine): requests decode one at a time,
    # batch-1, host sync per token — concurrency does not help it
    seq = reqs(4)
    t0 = time.perf_counter()
    for r in seq:
        rep.generate_sequential(r)
    seq_tps = len(seq) * new_tokens / (time.perf_counter() - t0)
    rows.append({"conc": 4, "sequential_tok_s": round(seq_tps, 1)})
    rep.stop()

    speedup = batched_tps[4] / seq_tps
    SERVING_METRICS["tokens_per_sec"] = {
        f"conc{c}": round(v, 1) for c, v in batched_tps.items()}
    SERVING_METRICS["sequential_tokens_per_sec"] = round(seq_tps, 1)
    SERVING_METRICS["speedup_conc4"] = round(speedup, 2)
    return rows, (f"conc4_speedup={speedup:.2f}x "
                  f"batched4={batched_tps[4]:.0f}tok/s seq={seq_tps:.0f}tok/s")


def bench_serving_recurrent_throughput():
    """Tokens/sec of the continuous-batching engine on a RECURRENT stack
    (mamba2-tiny, pure SSD — no attention layers at all): the chunked
    prefill that used to be attention-only now threads SSD state
    chunk-to-chunk, so the recurrent half of the config zoo runs the same
    multi-lane decode loop.  Tracks that the new workload's throughput
    scales with occupancy like the attention engine's does."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.engine import Replica, Request

    cfg = get_smoke_config("mamba2-780m").replace(param_dtype=jnp.float32,
                                                  dtype=jnp.float32)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    prompt_len, new_tokens = 16, 48
    rng = np.random.default_rng(0)

    def reqs(n):
        return [Request(i, rng.integers(2, cfg.vocab_size,
                                        size=(prompt_len,)).astype(np.int32),
                        new_tokens, 1e9) for i in range(n)]

    rep = Replica("bench-ssm", cfg, params, slots=4, capacity=128,
                  prefill_chunk_tokens=8)
    assert rep.prefill_caps["supported"], rep.prefill_caps
    rep.generate(reqs(1)[0])            # warm out of the timed region

    rows = []
    tps = {}
    for conc in (1, 4):
        rs = reqs(conc)
        t0 = time.perf_counter()
        threads = [threading.Thread(target=rep.generate, args=(r,))
                   for r in rs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        tps[conc] = conc * new_tokens / dt
        rows.append({"conc": conc, "batched_tok_s": round(tps[conc], 1)})
    rep.stop()

    SERVING_METRICS["recurrent"] = {
        "arch": "mamba2-780m (smoke)",
        "chunked_prefill": True,
        "tokens_per_sec": {f"conc{c}": round(v, 1) for c, v in tps.items()},
    }
    return rows, (f"ssm_conc4={tps[4]:.0f}tok/s conc1={tps[1]:.0f}tok/s "
                  f"chunked_prefill=on")


def bench_serving_paging():
    """The paged-KV memory unlock, measured at FIXED KV memory: the same
    token budget that buys the ring engine 8 worst-case lanes buys the
    paged engine 16+ usage-sized lanes — every one of which must serve a
    real request concurrently, token streams intact.  Also records
    tokens/sec at concurrency 8 with the prefix cache on (requests share
    a system prompt, so its KV is computed once) — the ``paging`` row of
    BENCH_serving.json tracks both across PRs (docs/PAGING.md)."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.engine import Replica, Request

    cfg = get_smoke_config("granite-8b").replace(param_dtype=jnp.float32,
                                                 dtype=jnp.float32)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    capacity, page_size = 128, 8
    ring_slots = 8
    budget_tokens = ring_slots * capacity       # the fixed KV budget
    num_pages = budget_tokens // page_size
    paged_slots = 16
    prompt_len, new_tokens = 16, 16             # realistic << capacity
    # a lane's reservation for this workload, in pages
    need = -(-(prompt_len + new_tokens - 1) // page_size)
    assert paged_slots * need <= num_pages      # all lanes fit the pool
    assert paged_slots >= 2 * ring_slots        # the >=2x memory unlock

    rep = Replica("bench-paged", cfg, params, slots=paged_slots,
                  capacity=capacity, prefill_chunk_tokens=16,
                  paged=True, page_size=page_size, num_pages=num_pages,
                  prefix_cache=True)
    rng = np.random.default_rng(0)
    sysp = rng.integers(2, cfg.vocab_size, size=(8,)).astype(np.int32)

    def reqs(n, base=0):
        # shared 1-block system prompt + private suffix
        return [Request(base + i, np.concatenate(
            [sysp, rng.integers(2, cfg.vocab_size,
                                size=(prompt_len - 8,))]).astype(np.int32),
            new_tokens, 1e9) for i in range(n)]

    rep.generate(reqs(1)[0])                    # warm + seed the prefix

    def run_conc(rs):
        out = {}
        t0 = time.perf_counter()
        def go(r):
            out[r.request_id] = rep.generate(r)
        threads = [threading.Thread(target=go, args=(r,)) for r in rs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out, time.perf_counter() - t0

    # 2x the ring's slot count, all genuinely concurrent at fixed memory
    out, _ = run_conc(reqs(paged_slots, base=100))
    assert len(out) == paged_slots
    assert all(len(v) == new_tokens for v in out.values())
    rep._alloc.check()

    rows, tps = [], {}
    for conc in (1, 8):
        out, dt = run_conc(reqs(conc, base=200 + 10 * conc))
        tps[conc] = conc * new_tokens / dt
        rows.append({"conc": conc, "paged_tok_s": round(tps[conc], 1)})
    hit_rate = rep._prefix.hit_rate()
    assert hit_rate > 0.0                       # shared prompt actually hit
    cow = rep.cow_copies
    rep.stop()

    SERVING_METRICS["paging"] = {
        "fixed_kv_budget_tokens": budget_tokens,
        "ring_slots_at_budget": ring_slots,
        "paged_slots_at_budget": paged_slots,
        "slot_multiplier": round(paged_slots / ring_slots, 2),
        "page_size": page_size,
        "num_pages": num_pages,
        "prefix_hit_rate": round(hit_rate, 3),
        "cow_copies": cow,
        "tokens_per_sec": {f"conc{c}": round(v, 1) for c, v in tps.items()},
    }
    return rows, (f"slots@fixed_mem={paged_slots}v{ring_slots} "
                  f"(x{paged_slots / ring_slots:.1f}) "
                  f"conc8={tps[8]:.0f}tok/s hit_rate={hit_rate:.2f}")


def bench_serving_routing():
    """DDS routing over a measured lane-mode profile: submit a burst of
    deadline-carrying requests through ServingFleet and record the
    deadline-hit rate plus the measured step/contention curves the router
    decided with.  Tracks whether the Update-Profile loop keeps routing
    decisions aligned with the hardware across PRs."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.core.policies import make_policy
    from repro.models import model as M
    from repro.serving.engine import (Replica, Request, ServingFleet,
                                      profile_replica)

    cfg = get_smoke_config("granite-8b").replace(param_dtype=jnp.float32,
                                                 dtype=jnp.float32)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rep = Replica("serve0", cfg, params, slots=4, capacity=128)
    prof = profile_replica(rep, prompt_lens=(8, 16), new_tokens=8)
    fleet = ServingFleet(make_policy("DDS"), source="serve0",
                         coordinator="serve0")
    fleet.add_replica(rep, profile=prof)

    prompt_len, new_tokens, n_requests = 16, 16, 12
    # SLO: a generous multiple of the occupancy-aware prediction for this
    # burst (full-occupancy step cadence, one wave per slots-worth of
    # requests) — the hit rate measures router+engine, not the SLO choice
    per_req = (prof.prefill_ms(float(prompt_len))
               + new_tokens * prof.step_curve(float(rep.slots)))
    waves = -(-n_requests // rep.slots)
    deadline_ms = 8.0 * waves * per_req
    # draw all prompts up front: np.random.Generator is not thread-safe,
    # and the fixed seed must mean a fixed workload across PRs
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, cfg.vocab_size,
                            size=(prompt_len,)).astype(np.int32)
               for _ in range(n_requests)]
    results = [None] * n_requests

    def run(i):
        req = Request(i, prompts[i], new_tokens, deadline_ms)
        results[i] = fleet.submit(req)

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(n_requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    hit = sum(1 for r in results if r.met(deadline_ms)) / n_requests
    fleet.stop()

    SERVING_METRICS["routing"] = {
        "requests": n_requests,
        "deadline_ms": round(deadline_ms, 1),
        "deadline_hit_rate": round(hit, 3),
        "placements": dict(fleet.stats),
    }
    # step_ms_by_occupancy IS the measured contention signal in lane mode
    # (the derived end-to-end contention curve is base + tokens x marginal
    # step cost — flat whenever the marginal cost is sub-timer-resolution,
    # which read as a fabricated constant in earlier BENCH_serving.json)
    SERVING_METRICS["profile"] = {
        "step_ms_by_occupancy": [round(y, 3) for y in prof.step_curve.ys],
        "prefill_chunk_ms": round(prof.prefill_chunk_ms, 3),
        "base_ms": round(prof.base_ms, 1),
    }
    rows = [{"deadline_hit_rate": hit, "requests": n_requests}]
    return rows, (f"hit_rate={hit:.2f} deadline={deadline_ms:.0f}ms "
                  f"step_ms={[round(y, 2) for y in prof.step_curve.ys]}")


def mesh_profile_record() -> dict:
    """Build a Replica on a (1, 4) serving mesh over the first four devices
    — its decode steps run the split-S distributed flash-decode with the
    per-lane index vector — time ``measure_step_curve`` and decode one
    request through the batched loop.  On a TPU the replica serves
    qwen3-4b at its published widths (the Pallas kernels need
    lane-aligned heads) with the depth cut to 9 of 36 layers, as
    ``chip_smoke.py --four-chips`` runs it; elsewhere a float32 granite-8b
    smoke config.  The record names the platform and the config it ran."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs import get_smoke_config
    from repro.launch.mesh import auto_mesh
    from repro.launch.serve import serving_config
    from repro.models import model as M
    from repro.serving.engine import Replica, Request, measure_step_curve

    dev = jax.devices()[0]
    if dev.platform == "tpu":
        cfg = serving_config("qwen3-4b", smoke=False).replace(num_layers=9)
        capacity = 1024
    else:
        cfg = get_smoke_config("granite-8b").replace(
            param_dtype=jnp.float32, dtype=jnp.float32)
        capacity = 128
    mesh = auto_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
    params = M.init_params(jax.random.PRNGKey(0), cfg,
                           NamedSharding(mesh, PartitionSpec()))
    rep = Replica("mesh0", cfg, params, slots=4, capacity=capacity,
                  serving_mesh=mesh)
    occs, step_ms, chunk_ms = measure_step_curve(rep, steps_per_point=4)
    toks = rep.generate(Request(0, np.arange(2, 10, dtype=np.int32), 4, 1e9))
    rep.stop()
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "config": f"{cfg.name} ({cfg.num_layers} layers, d_model "
                  f"{cfg.d_model})",
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "occupancy": occs,
        "step_ms_by_occupancy": [round(y, 3) for y in step_ms],
        "prefill_chunk_ms": round(chunk_ms, 3),
        "tokens_decoded": int(len(toks)),
    }


def bench_serving_mesh_step_curve():
    """Lane-occupancy step curve of a SHARDED replica
    (``mesh_profile_record``), so BENCH_serving.json tracks the
    distributed step cadence alongside the single-device one.  On a host
    with four devices it runs in this process, on them.  Otherwise it runs
    in a child with four fake CPU devices (the device count must be pinned
    via XLA_FLAGS before jax initializes); that child touches no
    accelerator, and its record says ``cpu``."""
    import subprocess

    import jax

    if len(jax.devices()) >= 4:
        rec = mesh_profile_record()
    else:
        root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=4"
                            ).strip()
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(root, "src"), root]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        env.setdefault("REPRO_KERNEL_IMPL", "jnp")
        env["JAX_PLATFORMS"] = "cpu"
        code = ("import json; from benchmarks.run import mesh_profile_record;"
                " print(json.dumps(mesh_profile_record()))")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, env=env,
                             timeout=900)
        assert out.returncode == 0, out.stderr[-3000:]
        rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["tokens_decoded"] == 4, rec
    SERVING_METRICS["mesh_profile"] = rec
    rows = [{"occupancy": o, "step_ms": m}
            for o, m in zip(rec["occupancy"], rec["step_ms_by_occupancy"])]
    return rows, (f"mesh={rec['mesh']} "
                  f"step_ms={rec['step_ms_by_occupancy']}")


def bench_serving_churn():
    """Deadline-hit rate under churn — the ROADMAP's tracked robustness
    metric.  Two parts land in the ``churn`` row of BENCH_serving.json:

    * **sim**: kill/rejoin and partition/heal scenarios through the
      discrete-event simulator (deterministic, covers every churn kind);
    * **live**: a two-replica ServingFleet under a burst of deadlined
      requests, with a ``FaultPlan`` crashing the replica DDS loaded up
      (the source) mid-burst — the monitor must evict it and the
      in-flight requests must fail over to the survivor.  Records hit
      rate, lost count, and the p99 latency of failed-over requests
      (the price of a death).

    Zero silent losses is asserted, not just reported: every submitted
    request returns ok (full token budget) or carries an explicit error.
    """
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.core.policies import make_policy
    from repro.core.simulator import ChurnEvent, SimConfig, run_sim
    from repro.ft import faults
    from repro.models import model as M
    from repro.serving.engine import (Replica, Request, ServingFleet,
                                      profile_replica)

    # ---- sim churn scenarios (every kind, deterministic) ----
    sim_metrics = {}
    scenarios = {
        "kill_rejoin": (ChurnEvent(500, "kill", "rasp2"),
                        ChurnEvent(2000, "rejoin", "rasp2")),
        "partition_heal": (ChurnEvent(500, "partition", "edge_server"),
                           ChurnEvent(1500, "heal", "edge_server")),
    }
    for name, churn in scenarios.items():
        cfg_s = SimConfig(num_tasks=200, interval_ms=30, constraint_ms=3000,
                          churn=churn)
        res = run_sim(make_policy("DDS"), cfg_s)
        for rec in res.records:     # every task accounted, none silent
            assert rec.finished_ms < float("inf") or rec.lost or rec.dropped
        sim_metrics[name] = {
            # hit_rate is over tasks the scheduler was accountable for:
            # admitted and not rendered infeasible by churn (a task whose
            # whole deadline budget went to a detection window no policy
            # controls); raw_hit_rate keeps the old all-tasks ratio
            "hit_rate": round(res.hit_rate, 3),
            "raw_hit_rate": round(res.num_met / cfg_s.num_tasks, 3),
            "lost": res.num_lost,
            "infeasible": res.num_infeasible,
            "failed_over": res.num_failed_over,
        }

    # ---- live: crash a replica under open-loop load ----
    cfg = get_smoke_config("granite-8b").replace(param_dtype=jnp.float32,
                                                 dtype=jnp.float32)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rep0 = Replica("serve0", cfg, params, slots=2, capacity=128)
    rep1 = Replica("serve1", cfg, params, slots=4, capacity=128)
    prof0 = profile_replica(rep0, prompt_lens=(8, 16), new_tokens=8)
    prof1 = profile_replica(rep1, prompt_lens=(8, 16), new_tokens=8)
    fleet = ServingFleet(make_policy("DDS"), source="serve0",
                         coordinator="serve0", heartbeat_ms=20.0,
                         staleness_factor=5.0,       # 100 ms alarm
                         progress_timeout_ms=2000.0, max_attempts=3)
    fleet.add_replica(rep0, profile=prof0)
    fleet.add_replica(rep1, profile=prof1)

    prompt_len, new_tokens, n_requests = 16, 16, 12
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, cfg.vocab_size,
                            size=(prompt_len,)).astype(np.int32)
               for _ in range(n_requests)]
    results = [None] * n_requests

    # one warm end-to-end request measures what a healthy fleet actually
    # delivers (profile math undershoots the Python-loop overhead badly);
    # the SLO then leaves a failed-over request room for one detection
    # window (staleness alarm) plus a full re-decode on the survivor
    warm = rng.integers(2, cfg.vocab_size,
                        size=(prompt_len,)).astype(np.int32)
    t0 = time.perf_counter()
    fleet.submit(Request(999, warm, new_tokens, 1e9))
    measured_ms = (time.perf_counter() - t0) * 1e3
    deadline_ms = max(8.0 * measured_ms, 6.0 * fleet.staleness_alarm_ms)

    # DDS loads up the source first, so THAT is the replica worth killing:
    # the burst's makespan is ~n/slots = 6x a single request, the crash
    # lands at ~2x, guaranteeing live lanes die and must fail over
    kill_at_ms = 2.0 * measured_ms
    inj = faults.inject(fleet, "serve0",
                        faults.FaultPlan([faults.crash(kill_at_ms)]))

    def run(i):
        results[i] = fleet.submit(
            Request(i, prompts[i], new_tokens, deadline_ms))

    inj.arm()
    threads = [threading.Thread(target=run, args=(i,))
               for i in range(n_requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    inj.stop()

    # zero silent losses: every request returned, each ok with its full
    # token budget or carrying an explicit error
    assert all(r is not None for r in results)
    for r in results:
        assert (r.ok and len(r.tokens) == new_tokens) or r.error, r
    # the loaded replica died mid-burst and the monitor caught it
    assert "serve0" in fleet.dead, fleet.dead
    hit = sum(1 for r in results if r.met(deadline_ms)) / n_requests
    fo_lat = sorted(r.latency_ms() for r in results if r.attempts > 1)
    fo_p99 = fo_lat[max(int(0.99 * len(fo_lat)) - 1, 0)] if fo_lat else 0.0
    live = {
        "requests": n_requests,
        "deadline_ms": round(deadline_ms, 1),
        "deadline_hit_rate": round(hit, 3),
        "lost": fleet.lost,
        "failovers": fleet.failovers,
        "failover_p99_ms": round(fo_p99, 1),
        "dead_replicas": list(fleet.dead),
        "placements": dict(fleet.stats),
    }
    fleet.stop()

    SERVING_METRICS["churn"] = {"sim": sim_metrics, "live": live}
    rows = [{"scenario": k, **v} for k, v in sim_metrics.items()]
    rows.append({"scenario": "live_crash", "hit_rate": hit,
                 "lost": fleet.lost, "failover_p99_ms": round(fo_p99, 1)})
    return rows, (f"live_hit={hit:.2f} lost={fleet.lost} "
                  f"failovers={fleet.failovers} fo_p99={fo_p99:.0f}ms "
                  f"dead={fleet.dead}")


def bench_serving_overload():
    """Goodput under saturation — the overload-control evidence row.

    Two parts land in the ``overload`` row of BENCH_serving.json:

    * **sim**: an open-loop offered-load sweep (1x/2x/3x of a near-capacity
      base rate) through the discrete-event simulator with the admission
      gate and bounded shedding queues enabled (deterministic);
    * **live**: one replica with the full overload stack on — feasibility
      admission, bounded EDF queues with deadline-aware shedding, brownout,
      circuit breakers — measured at 1x and 3x of its *measured* capacity
      with mixed interactive/batch priorities.

    The headline property is the plateau: goodput (deadline-hit tokens/sec)
    at 3x offered load must stay within 20% of its 1x value — overload
    control converts excess demand into explicit rejected/shed outcomes
    instead of letting queueing collapse take the whole fleet late.  Every
    request is accounted ok/rejected/shed/lost; zero silent losses, and
    both are asserted, not just reported."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.core.policies import make_policy
    from repro.core.simulator import SimConfig, run_sim
    from repro.models import model as M
    from repro.serving.engine import (Replica, Request, ServingFleet,
                                      profile_replica)
    from repro.serving.overload import BrownoutConfig

    # ---- sim sweep (deterministic; every task accounted) ----
    sim_rows = {}
    sim_goodput = {}
    base_interval_ms = 50.0         # just under fleet capacity at 1x
    for load in (1, 2, 3):
        cfg_s = SimConfig(num_tasks=80 * load,
                          interval_ms=base_interval_ms / load,
                          constraint_ms=1500.0,
                          admission_margin=1.1, max_queue=4)
        res = run_sim(make_policy("DDS_EDF"), cfg_s)
        for rec in res.records:     # accounting closes: nothing silent
            assert (rec.finished_ms < float("inf") or rec.lost
                    or rec.dropped or rec.rejected or rec.shed), rec
        makespan_s = cfg_s.num_tasks * cfg_s.interval_ms / 1e3
        sim_goodput[load] = res.num_met / makespan_s
        sim_rows[f"{load}x"] = {
            "offered_per_s": round(1e3 / cfg_s.interval_ms, 1),
            "goodput_per_s": round(sim_goodput[load], 1),
            "met": res.num_met, "rejected": res.num_rejected,
            "shed": res.num_shed, "dropped_late": res.num_dropped,
            "lost": res.num_lost,
        }
    assert sim_goodput[3] >= 0.8 * sim_goodput[1], sim_rows

    # ---- live: one replica, measured capacity, open-loop sweep ----
    cfg = get_smoke_config("granite-8b").replace(param_dtype=jnp.float32,
                                                 dtype=jnp.float32)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    prompt_len, new_tokens = 16, 16
    rep = Replica("over0", cfg, params, slots=4, capacity=64, max_queue=8,
                  brownout=BrownoutConfig(queue_high=6, queue_low=1,
                                          engage_after=2, restore_after=4,
                                          max_new_tokens_cap=new_tokens // 2))
    prof = profile_replica(rep, prompt_lens=(8, 16), new_tokens=8)
    fleet = ServingFleet(make_policy("DDS"), source="over0",
                         coordinator="over0", admission_margin=1.2)
    fleet.add_replica(rep, profile=prof)

    rng = np.random.default_rng(2)

    def prompts(n):
        return [rng.integers(2, cfg.vocab_size,
                             size=(prompt_len,)).astype(np.int32)
                for _ in range(n)]

    # measured capacity: two closed-loop waves at full occupancy (profile
    # math undershoots Python-loop overhead; capacity must be what the
    # engine actually delivers on this host)
    n_cap = 2 * rep.slots
    cap_reqs = [Request(900 + i, p, new_tokens, 1e9)
                for i, p in enumerate(prompts(n_cap))]
    fleet.submit(cap_reqs[0])       # warm compiles out of the timed region
    t0 = time.perf_counter()
    cap_threads = [threading.Thread(target=fleet.submit, args=(r,))
                   for r in cap_reqs]
    for t in cap_threads:
        t.start()
    for t in cap_threads:
        t.join()
    dt_cap = time.perf_counter() - t0
    capacity_rps = n_cap / dt_cap
    wave_ms = dt_cap / 2 * 1e3      # one slots-wide wave, measured
    deadline_ms = 6.0 * wave_ms
    # "1x" offers ~70% of measured capacity: at-capacity open-loop arrivals
    # are queueing-theory unstable, and the 1x leg must measure the healthy
    # fleet, not its knife edge
    interval_1x_s = 1.0 / (0.7 * capacity_rps)

    def sweep(load, n, id_base):
        ps = prompts(n)
        results = [None] * n
        threads = []
        t0 = time.perf_counter()
        for i in range(n):          # open loop: arrivals ignore completions
            lag = t0 + i * interval_1x_s / load - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            pr = "batch" if i % 3 == 2 else "interactive"
            req = Request(id_base + i, ps[i], new_tokens, deadline_ms,
                          priority=pr)
            th = threading.Thread(
                target=lambda i=i, req=req:
                    results.__setitem__(i, fleet.submit(req)))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        makespan_s = time.perf_counter() - t0
        assert all(r is not None for r in results)
        counts = {"ok": 0, "rejected": 0, "shed": 0, "lost": 0}
        for r in results:           # taxonomy is total: no other outcomes
            counts[r.outcome] += 1
            assert r.ok == (r.outcome == "ok") and (r.ok or r.error), r
        goodput = sum(len(r.tokens) for r in results
                      if r.ok and r.met(deadline_ms)) / makespan_s

        def p99(pr):
            ts = sorted(r.ttft_ms for r in results
                        if r.ok and r.priority == pr and r.ttft_ms > 0)
            return ts[max(int(0.99 * len(ts)) - 1, 0)] if ts else 0.0

        return {
            "offered_per_s": round(load * 0.7 * capacity_rps, 1),
            "goodput_tok_s": round(goodput, 1),
            "p99_ttft_ms": {"interactive": round(p99("interactive"), 1),
                            "batch": round(p99("batch"), 1)},
            "degraded": sum(1 for r in results if r.ok and r.degraded),
            **counts,
        }, goodput

    live_1x, good_1x = sweep(1, 10, 1000)
    live_3x, good_3x = sweep(3, 30, 3000)

    # deliberately infeasible probes: the admission gate must refuse them
    # outright (explicit "rejected", zero engine work, retry never tried)
    probes = [fleet.submit(Request(9000 + i, p, new_tokens, 0.5))
              for i, p in enumerate(prompts(3))]
    assert all(p.outcome == "rejected" and p.attempts == 0 for p in probes)

    # fleet counters close the books over everything submitted above
    assert fleet.rejected == (live_1x["rejected"] + live_3x["rejected"]
                              + len(probes))
    assert fleet.shed == live_1x["shed"] + live_3x["shed"]
    assert fleet.lost == live_1x["lost"] + live_3x["lost"]
    # the plateau: goodput at 3x within 20% of 1x — no congestion collapse
    assert good_3x >= 0.8 * good_1x, (live_1x, live_3x)
    brown = {"transitions": rep.brownout.transitions,
             "engaged_now": rep.browned_out}
    fleet.stop()

    SERVING_METRICS["overload"] = {
        "sim": sim_rows,
        "live": {"capacity_req_s": round(capacity_rps, 1),
                 "deadline_ms": round(deadline_ms, 1),
                 "1x": live_1x, "3x": live_3x,
                 "rejected_probes": len(probes),
                 "brownout": brown},
    }
    rows = [{"load": "1x", **{k: v for k, v in live_1x.items()
                              if not isinstance(v, dict)}},
            {"load": "3x", **{k: v for k, v in live_3x.items()
                              if not isinstance(v, dict)}}]
    return rows, (f"goodput_1x={good_1x:.0f}tok/s "
                  f"goodput_3x={good_3x:.0f}tok/s "
                  f"plateau={good_3x / max(good_1x, 1e-9):.2f}x "
                  f"shed3x={live_3x['shed']} rejected3x={live_3x['rejected']} "
                  f"lost3x={live_3x['lost']}")


def chaos_smoke():
    """Tiny churn scenario for CI (``--chaos-smoke``): asserts zero
    silently-lost requests end to end — simulator accounting closes, and a
    live replica crashed mid-decode yields only explicit outcomes (every
    blocked caller returns; no hangs, no truncated-but-\"ok\" streams)."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.core.policies import make_policy
    from repro.core.simulator import ChurnEvent, SimConfig, run_sim
    from repro.ft import faults
    from repro.models import model as M
    from repro.serving.engine import Replica, Request, ServingFleet

    # sim: a kill under load — every task must end met, late, lost, or
    # dropped (no task may simply vanish from the books)
    cfg_s = SimConfig(num_tasks=100, interval_ms=30, constraint_ms=2000,
                      churn=(ChurnEvent(400, "kill", "rasp2"),
                             ChurnEvent(1800, "rejoin", "rasp2")))
    res = run_sim(make_policy("DDS"), cfg_s)
    unaccounted = [r for r in res.records
                   if r.finished_ms == float("inf")
                   and not r.lost and not r.dropped]
    assert not unaccounted, f"{len(unaccounted)} tasks silently lost"

    # live: crash the only replica with requests in flight; every submit
    # must return an explicit outcome (ok with the full budget, or error)
    cfg = get_smoke_config("granite-8b").replace(param_dtype=jnp.float32,
                                                 dtype=jnp.float32)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rep = Replica("chaos0", cfg, params, slots=2, capacity=64)
    fleet = ServingFleet(make_policy("DDS"), source="chaos0",
                         coordinator="chaos0", heartbeat_ms=20.0,
                         staleness_factor=5.0, progress_timeout_ms=1000.0,
                         max_attempts=2, retry_backoff_ms=5.0)
    fleet.add_replica(rep)
    inj = faults.inject(fleet, "chaos0")

    n, new_tokens = 3, 64
    results = [None] * n

    def run(i):
        results[i] = fleet.submit(Request(
            i, np.arange(2, 10, dtype=np.int32), new_tokens, 1e9))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    time.sleep(0.3)                 # let decode get rolling, then kill it
    inj.apply("crash")
    for t in threads:
        t.join(timeout=120.0)
    assert not any(t.is_alive() for t in threads), \
        "a submit hung after the replica crashed — silent loss"
    n_ok = sum(1 for r in results if r is not None and r.ok)
    for r in results:
        assert r is not None
        assert (r.ok and len(r.tokens) == new_tokens) or r.error, r
    assert fleet.lost == n - n_ok    # every failure accounted, none silent
    inj.stop()
    fleet.stop()
    rows = [{"sim_lost": res.num_lost, "sim_failed_over": res.num_failed_over,
             "live_ok": n_ok, "live_lost": fleet.lost}]
    return rows, (f"sim_accounted=all live_ok={n_ok} "
                  f"live_lost={fleet.lost} no_silent_losses=True")


def live_profile_bench():
    """Measure a real jitted model step under thread contention on this host
    (the live analogue of Tables V/VI)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.core.profile import measure_profile
    from repro.models import model as M

    cfg = get_smoke_config("qwen3-4b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    fwd = jax.jit(lambda p, t: M.forward(p, t, cfg)[0])

    def step(size):
        t = jnp.ones((1, int(size)), jnp.int32)
        fwd(params, t).block_until_ready()

    prof = measure_profile("lm_step", step, sizes=(16, 32, 64),
                           concurrencies=(1, 2, 4), reps=2)
    rows = [{"size": s, "ms": round(m, 2)}
            for s, m in zip(prof.size_curve.xs, prof.size_curve.ys)]
    mono = all(a <= b * 1.5 for a, b in zip(prof.size_curve.ys,
                                            prof.size_curve.ys[1:]))
    return rows, (f"base={prof.base_ms:.1f}ms "
                  f"contention4={prof.contention(4)/max(prof.contention(1),1e-9):.1f}x "
                  f"size_monotoneish={mono}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", action="store_true",
                    help="also run live-host profiling benches (slow)")
    ap.add_argument("--serving-smoke", action="store_true",
                    help="run only the serving benches and write the JSON "
                         "(the CI perf-trajectory smoke)")
    ap.add_argument("--chaos-smoke", action="store_true",
                    help="run only the tiny churn/fault-injection scenario "
                         "and assert zero silently-lost requests (CI); does "
                         "not write the serving JSON")
    ap.add_argument("--overload-smoke", action="store_true",
                    help="run only the overload sweep (admission + shedding "
                         "+ brownout + breakers) and assert the 3x-load "
                         "goodput plateau; merges the overload row into the "
                         "serving JSON (CI)")
    ap.add_argument("--paging-smoke", action="store_true",
                    help="run only the paged-KV bench (>=2x concurrent "
                         "slots at fixed memory, prefix-cache hit rate, "
                         "tok/s); merges the paging row into the serving "
                         "JSON (CI)")
    ap.add_argument("--serving-json",
                    default=os.path.join(os.path.dirname(
                        os.path.abspath(__file__)), "..",
                        "BENCH_serving.json"),
                    help="where to write the serving metrics JSON")
    args, _ = ap.parse_known_args()
    enable_compile_cache()

    serving = [("bench_serving_throughput", bench_serving_throughput),
               ("bench_serving_recurrent_throughput",
                bench_serving_recurrent_throughput),
               ("bench_serving_paging", bench_serving_paging),
               ("bench_serving_routing", bench_serving_routing),
               ("bench_serving_mesh_step_curve", bench_serving_mesh_step_curve),
               ("bench_serving_churn", bench_serving_churn),
               ("bench_serving_overload", bench_serving_overload)]
    if args.chaos_smoke:
        benches = [("chaos_smoke", chaos_smoke)]
    elif args.overload_smoke:
        benches = [("bench_serving_overload", bench_serving_overload)]
    elif args.paging_smoke:
        benches = [("bench_serving_paging", bench_serving_paging)]
    elif args.serving_smoke:
        # the overload sweep and the paging bench have their own CI
        # smokes; keep the serving smoke at its current runtime
        benches = [b for b in serving
                   if b[0] not in ("bench_serving_overload",
                                   "bench_serving_paging")]
    else:
        benches = list(BENCHES) + serving
        if args.live:
            benches.append(("live_profile", live_profile_bench))

    print("name,us_per_call,derived")
    for name, fn in benches:
        us, derived = _timed(fn)
        print(f"{name},{us:.0f},{derived}", flush=True)

    # --chaos-smoke is an assertion run, not a metrics run: writing here
    # would clobber the full serving row set with a single row
    if SERVING_METRICS and not args.chaos_smoke:
        path = os.path.abspath(args.serving_json)
        # merge-on-write: partial runs (--overload-smoke, --serving-smoke)
        # each land their rows without clobbering the others'
        merged = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    merged = json.load(f)
            except (OSError, ValueError):
                merged = {}
        merged.update(SERVING_METRICS)
        with open(path, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# serving metrics -> {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
