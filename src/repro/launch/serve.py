"""Serving driver: DDS-routed continuous serving of a small model on this
host, demonstrating the full path: warm replica pools -> profile
pre-evaluation -> two-level DDS routing -> SLO accounting.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
      --requests 16 --policy DDS

``--smoke`` (the default) serves the architecture's reduced smoke config;
``--no-smoke`` serves its published config with weights held in the
compute dtype (bf16: qwen3-4b is 8.0 GB) — a chip's worth of model.
Replicas are spread over the host's local devices, one device each.

Per-request sampling rides on the request: ``--temperature/--top-k/--top-p``
set the knobs for every generated request (0 temperature = greedy), and
``--sample-seed`` fixes the PRNG root so a rerun reproduces the exact token
streams (each request i uses ``sample_seed + i``).  ``--eos-id`` attaches a
stop token to every request (lane freed the moment it is emitted).

Chunked prefill is universal (recurrent + sliding-window stacks included —
try ``--arch mamba2-780m`` or ``--arch recurrentgemma-9b``):
``--prefill-chunk-tokens`` sets the per-step prefill budget ceiling and
``--step-slo-ms`` makes the budget adaptive to the live decode-step cadence
(see docs/PREFILL.md).

Chaos (docs/FAULTS.md): ``--chaos crash|hang|slow|partition`` injects that
fault into the source replica partway through the run (``--chaos-at-ms``),
and the summary reports failovers / lost requests alongside the SLO
accounting — a live demonstration of detection, eviction, and
deadline-aware retry.

Paged KV (docs/PAGING.md): ``--paged`` swaps per-lane rings for block-table
lanes over a shared page pool (``--page-size``/``--num-pages``), and
``--prefix-cache`` adds cross-request prefix reuse — prompts opening with an
already-cached system prompt skip its prefill and copy-on-write diverge.

Overload control (docs/SERVING.md): ``--priority interactive|batch`` tags
every request's shedding class, ``--admission-margin`` scales the
feasibility floor the fleet refuses infeasible deadlines against (0
disables admission), and ``--brownout`` arms queue-pressure brownout on
each replica.  The summary then accounts every request by outcome:
ok / rejected / shed / lost.

At exit each replica reports its serving loop (docs/SERVING.md, "Spans and
counters"): queue wait p50/p90 (lane and KV pages), the share of loop
time spent in prefill, the router's ``T_que + T_process`` error against
the observed latency, and the loop's counts.
"""
from __future__ import annotations

import argparse
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.policies import make_policy
from repro.kernels.ops import kernel_impl
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.serving.engine import Replica, Request, ServingFleet
from repro.serving.overload import PRIORITIES, BrownoutConfig


def serving_config(arch: str, smoke: bool = True):
    """The config a fleet serves: the smoke config as is, or the published
    config with params held in the compute dtype (serving keeps no f32
    master copy)."""
    if smoke:
        return get_smoke_config(arch)
    cfg = get_config(arch)
    return cfg.replace(param_dtype=cfg.dtype)


def replica_params(cfg, replicas: int, seed: int = 0) -> List:
    """Random params for ``replicas`` replicas, replica ``i`` on local
    device ``i % n``: initialised once under ``jit`` on the first device
    and copied to each further device that needs them."""
    devices = jax.local_devices()
    first = model_lib.init_params(jax.random.PRNGKey(seed), cfg,
                                  jax.sharding.SingleDeviceSharding(devices[0]))
    placed = {0: first}
    for i in range(1, min(replicas, len(devices))):
        placed[i] = jax.device_put(first, devices[i])
    return [placed[i % len(devices)] for i in range(replicas)]


def build_fleet(cfg, policy_name: str, replicas: int = 2,
                slots: int = 2, capacity: int = 128,
                prefill_chunk_tokens: int = 32,
                step_slo_ms: float = 0.0,
                admission_margin: float = 0.0,
                brownout: bool = False,
                paged: bool = False, page_size: int = 16,
                num_pages: int = 0,
                prefix_cache: bool = False,
                params: Optional[List] = None) -> ServingFleet:
    """A DDS ``ServingFleet`` of ``replicas`` warm replicas; ``params``
    (one tree per replica) defaults to ``replica_params``."""
    params = params if params is not None else replica_params(cfg, replicas)
    fleet = ServingFleet(make_policy(policy_name), source="replica0",
                         coordinator="replica1" if replicas > 1 else "replica0",
                         admission_margin=admission_margin)
    for i in range(replicas):
        rep = Replica(f"replica{i}", cfg, params[i], slots=slots,
                      capacity=capacity,
                      prefill_chunk_tokens=prefill_chunk_tokens,
                      step_slo_ms=step_slo_ms,
                      brownout=BrownoutConfig() if brownout else None,
                      paged=paged, page_size=page_size,
                      num_pages=num_pages if num_pages > 0 else None,
                      prefix_cache=prefix_cache)
        fleet.add_replica(rep)
        paging = (f"paged KV ({rep.num_pages} pages x {rep.page_size} tok"
                  f"{', prefix cache' if prefix_cache else ''})"
                  if paged else "ring KV")
        print(f"replica{i} on {rep.device}: warmup (compile) "
              f"{rep.warmup_s:.2f}s — "
              f"cold-start paid up front; chunked prefill "
              f"{'on' if rep.prefill_caps['supported'] else 'off'} "
              f"(budget ceiling {rep.prefill_chunk_tokens} tokens); {paging}")
    return fleet


def _pct(values: List[float], q: float, unit: str) -> str:
    return f"{np.percentile(values, q):.1f}{unit}" if values else "n/a"


def loop_report(results, replicas) -> List[str]:
    """One line per replica: what its serving loop did for ``results``
    (the ``RequestResult``s it served) and over its life (``counters``)."""
    lines = []
    for rep in replicas:
        mine = [r for r in results if r.ok and r.replica == rep.name]
        waits = [r.admitted_ms - r.enqueued_ms for r in mine]
        errs = [100.0 * abs(r.latency_ms() - r.predicted_ms) / r.latency_ms()
                for r in mine if r.predicted_ms > 0.0 and r.latency_ms() > 0.0]
        c = rep.counters
        share = 100.0 * c.prefill_ms / c.loop_ms if c.loop_ms > 0.0 else 0.0
        # the decode kernel reads live context only: of every KV slot its
        # steps could address (lanes x capacity), the share it read
        slots = c.decode_steps * rep.slots * rep.capacity
        read = f"{100.0 * c.context_tokens / slots:.1f}%" if slots else "n/a"
        lines.append(
            f"{rep.name}: queue wait p50={_pct(waits, 50, 'ms')} "
            f"p90={_pct(waits, 90, 'ms')} prefill share={share:.1f}% "
            f"predictor error p50={_pct(errs, 50, '%')} "
            f"p90={_pct(errs, 90, '%')} KV slots read={read} "
            f"({c.turns} turns, {c.decode_steps} decode steps, "
            f"{c.decode_tokens} tokens, {c.prefill_chunks} prefill chunks)")
    return lines


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the reduced smoke config (--no-smoke: the "
                         "published config, bf16 weights)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--deadline-ms", type=float, default=10_000.0)
    ap.add_argument("--interval-ms", type=float, default=50.0)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--policy", default="DDS",
                    choices=["DDS", "DDS_EDF", "AOR", "AOE", "EODS", "JSQ"])
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k filter (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="per-request nucleus (top-p) filter (1 = disabled)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="PRNG root; request i samples with seed+i")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=32,
                    help="chunked-prefill budget CEILING per interleave "
                         "slot (clamped to the stack's capability report)")
    ap.add_argument("--step-slo-ms", type=float, default=0.0,
                    help="per-decode-step latency SLO: >0 shrinks the "
                         "prefill budget so chunk cost fits the slack over "
                         "the live step-time EWMA (0 = fixed ceiling)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop decoding when this token id is emitted "
                         "(trimmed from the output; -1 = disabled)")
    ap.add_argument("--chaos", default="",
                    choices=["", "crash", "hang", "slow", "partition"],
                    help="inject this fault into the source replica mid-run "
                         "(docs/FAULTS.md); empty = no chaos")
    ap.add_argument("--chaos-at-ms", type=float, default=500.0,
                    help="when the injected fault fires, relative to the "
                         "first request")
    ap.add_argument("--priority", default="interactive",
                    choices=list(PRIORITIES),
                    help="priority class for every request: under overload "
                         "the EDF queues shed lowest class first")
    ap.add_argument("--admission-margin", type=float, default=0.0,
                    help="feasibility-floor admission: reject a request "
                         "whose deadline is below margin x the best-case "
                         "completion floor (0 = admit everything)")
    ap.add_argument("--brownout", action="store_true",
                    help="arm queue-pressure brownout on each replica "
                         "(reversible degradation under sustained load; "
                         "docs/SERVING.md)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: block-table lanes over a shared "
                         "page pool instead of per-lane rings "
                         "(docs/PAGING.md)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool size (0 = slots x pages-per-lane, the "
                         "ring-equivalent footprint)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request prefix reuse: prompts sharing "
                         "full cached blocks skip their prefill "
                         "(global-attention stacks only; implies --paged)")
    return ap


def main():
    args = build_parser().parse_args()
    if args.prefix_cache:
        args.paged = True

    cache_dir = enable_compile_cache()
    cfg = serving_config(args.arch, args.smoke)
    dev = jax.devices()[0]
    print(f"{cfg.name}: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}; kernels={kernel_impl()}; "
          f"compile cache {cache_dir}")
    fleet = build_fleet(cfg, args.policy, replicas=args.replicas,
                        prefill_chunk_tokens=args.prefill_chunk_tokens,
                        step_slo_ms=args.step_slo_ms,
                        admission_margin=args.admission_margin,
                        brownout=args.brownout, paged=args.paged,
                        page_size=args.page_size, num_pages=args.num_pages,
                        prefix_cache=args.prefix_cache)

    inj = None
    if args.chaos:
        from repro.ft import faults
        # the source replica is the one DDS loads up first — killing it is
        # the demo worth watching (in-flight streams fail over to peers)
        victim = "replica0"
        ev = (faults.slow(args.chaos_at_ms, 4.0) if args.chaos == "slow"
              else faults.FaultEvent(args.chaos_at_ms, args.chaos))
        inj = faults.inject(fleet, victim, faults.FaultPlan([ev]))
        print(f"chaos: {args.chaos} on {victim} at t+{args.chaos_at_ms:.0f}ms")
        inj.arm()

    rng = np.random.default_rng(0)
    results: List = []
    with ThreadPoolExecutor(max_workers=8) as ex:
        futs = []
        for i in range(args.requests):
            prompt = rng.integers(2, cfg.vocab_size,
                                  size=(args.prompt_len,)).astype(np.int32)
            req = Request(i, prompt, args.new_tokens, args.deadline_ms,
                          temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p, seed=args.sample_seed + i,
                          eos_id=args.eos_id if args.eos_id >= 0 else None,
                          priority=args.priority)
            futs.append(ex.submit(fleet.submit, req))
            time.sleep(args.interval_ms / 1e3)
        results = [f.result() for f in futs]
    if inj is not None:
        inj.stop()

    met = sum(1 for r in results if r.met(args.deadline_ms))
    failed = sum(1 for r in results if not r.ok)
    failovers = sum(1 for r in results if r.failed_over)
    outcomes = {k: sum(1 for r in results if r.outcome == k)
                for k in ("ok", "rejected", "shed", "lost")}
    degraded = sum(1 for r in results if r.degraded)
    lats = sorted(r.latency_ms() for r in results)
    p50 = lats[len(lats) // 2]
    p99 = lats[min(int(len(lats) * 0.99), len(lats) - 1)]
    print(f"\npolicy={args.policy} requests={args.requests} met_SLO={met}"
          f" p50={p50:.0f}ms p99={p99:.0f}ms placements={fleet.stats}")
    print("outcomes: " + " ".join(f"{k}={v}" for k, v in outcomes.items())
          + f" degraded={degraded} browned_out={fleet.degraded()}")
    if args.chaos or failed or failovers:
        print(f"chaos summary: failed={failed} failed_over={failovers} "
              f"fleet_failovers={fleet.failovers} lost={fleet.lost} "
              f"dead={fleet.dead}")
    for line in loop_report(results, list(fleet.replicas.values())):
        print(line)
    fleet.stop()
    if outcomes["lost"]:
        # shed / rejected are overload control's accounted answers; a
        # lost request is a failure the run must not report as success
        raise SystemExit(f"{outcomes['lost']} request(s) lost")


if __name__ == "__main__":
    main()
