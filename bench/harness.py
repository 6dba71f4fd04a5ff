"""One run of one benchmark cell: set up the fleet a configuration file
describes, drive it with the cell's traffic for a measured window, check
what it served against the plain reference, and return the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the names in
``BENCHMARK.json``:

* ``bench/configs/<config>.json`` — published sizes, how they map onto the
  program's fields, replica and fleet settings, the reference module, the
  correctness limit;
* ``bench/traffic/<mix>.json`` — the mix's parameters, read by
  ``bench/generator.py``;
* ``bench/metrics/<metric>.py`` — ``read(name, run)`` for one per-layer
  metric (a dotted name resolves to the file of the part before the dot);
* ``bench/reference/<module>.py`` — the configuration's plain reference.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import logging
import os
import shutil
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from bench import correctness, generator, stats, trace_reduce
from bench.peaks import peaks

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
HEARTBEAT_S = 0.02
TRACE_S = 4.0
WARMUP_DEADLINE_MS = 1e12


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ files
def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: Dict, workload: str) -> Dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def config_of(bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def cell_metrics(bench: Dict, workload: str, trace: bool) -> List[Dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics,
    or with ``trace`` its per-layer ones."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if workload in m.get("workloads", [workload])]


def dims_of(config: Dict) -> Dict:
    """The configuration's sizes under the program's field names."""
    fields = config["program"]["fields"]
    return {fields[k]: v for k, v in config["model"].items() if k in fields}


def reference_of(config: Dict):
    return load_module(BENCH / "reference" / f"{config['reference']}.py",
                       f"bench_reference_{config['reference']}")


def seed_key(seed: int):
    """A PRNG key from a seed of any size, 16 bits at a time."""
    import jax
    key = jax.random.PRNGKey(0)
    seed = int(seed)
    while True:
        key = jax.random.fold_in(key, seed & 0xFFFF)
        seed >>= 16
        if not seed:
            return key


# ------------------------------------------------------------------ set-up
@dataclass
class Fleet:
    fleet: Any
    replicas: List[Any]
    params: List[Any]               # one weight tree per replica
    devices: List[Any]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program
    kept, so that only a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build_fleet(config: Dict, seed: int, devices: List,
                spec: Optional[Dict] = None) -> Fleet:
    """Weights from the seed on the first device (one jitted call, in the
    served dtype), a copy on each further device, one warm replica per
    device, profiled, behind one DDS ``ServingFleet``.  With ``spec`` (a
    traffic mix) every replica is warmed up on it before it joins the
    fleet: a cold checkout compiles during the warm-up, and a compile
    holds a replica's decode loop for longer than the fleet's hang
    detector allows a replica with work in hand."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.policies import make_policy
    from repro.serving.engine import Replica, ServingFleet, profile_replica

    dims = dims_of(config)
    dtype = jnp.dtype(config["dtype"])
    cfg = get_config(config["program"]["arch"]).replace(
        dtype=dtype, param_dtype=dtype, **dims)
    ref = reference_of(config)
    n = config["fleet"]["replicas"]
    t0 = time.monotonic()
    init = jax.jit(lambda k: ref.init_params(k, dims, dtype),
                   out_shardings=jax.sharding.SingleDeviceSharding(devices[0]))
    first = init(seed_key(seed))
    params = [first] + [jax.device_put(first, d) for d in devices[1:n]]
    jax.block_until_ready(params)
    t1 = time.monotonic()

    def make(i):
        rep = Replica(f"replica{i}", cfg, params[i], **config["replica"])
        return rep, profile_replica(rep)

    if n == 1:
        built = [make(0)]
    else:
        with ThreadPoolExecutor(max_workers=n) as ex:
            built = list(ex.map(make, range(n)))
    replicas = [r for r, _ in built]
    t2 = time.monotonic()
    if spec is not None:
        warm_up(replicas, spec, seed + 1, dims["vocab_size"])
    say(f"set-up: weights {t1 - t0:.3f} s, replicas built and profiled "
        f"{t2 - t1:.3f} s, warm-up {time.monotonic() - t2:.3f} s")
    f = config["fleet"]
    fleet = ServingFleet(make_policy(f["policy"]), source=f["source"],
                         coordinator=f["coordinator"],
                         admission_margin=f["admission_margin"])
    for rep, prof in built:
        fleet.add_replica(rep, profile=prof)
    return Fleet(fleet, replicas, params, devices[:n])


# ------------------------------------------------------------------ driving
@dataclass
class Run:
    """What a run observed; the per-layer readers take it as ``run``."""

    config: Dict
    dims: Dict
    family: str
    peaks: Dict
    window_ms: tuple                 # (start, end), host monotonic ms
    records: List[Dict] = field(default_factory=list)
    heartbeats: List[tuple] = field(default_factory=list)   # (t_ms, {name: (queue, running)})
    counters: Dict[str, Dict] = field(default_factory=dict)  # mark -> snapshot
    trace: Optional[trace_reduce.Reduced] = None
    trace_ms: Optional[tuple] = None  # traced window, host monotonic ms
    compiles_in_window: int = 0

    def counted(self) -> List[Dict]:
        lo, hi = self.window_ms
        return [r for r in self.records if lo <= r["due_ms"] < hi]


def _counters(fl: Fleet) -> Dict:
    return {"placed": dict(fl.fleet.stats),
            "prefilled": {r.name: r.prefilled_tokens for r in fl.replicas},
            "t_ms": time.monotonic() * 1e3}


def _record(p: generator.Planned, due_ms: float, res, sent_ms: float) -> Dict:
    toks = np.asarray(res.tokens, np.int32)
    ok = res.outcome == "ok" and res.error is None and \
        len(toks) == p.max_new_tokens
    return {"id": p.request_id, "due_ms": due_ms, "sent_ms": sent_ms,
            "finished_ms": res.finished_ms, "ttft_ms": res.ttft_ms,
            "n_out": len(toks), "max_new_tokens": p.max_new_tokens,
            "prompt": p.prompt, "tokens": toks, "ok": ok,
            "outcome": res.outcome, "replica": res.replica,
            "session": p.session, "turn": p.turn}


def _submit(fleet, p: generator.Planned, due_ms: float):
    from repro.serving.engine import Request
    sent = time.monotonic() * 1e3
    req = Request(p.request_id, p.prompt, p.max_new_tokens, p.deadline_ms,
                  created_ms=due_ms)
    return _record(p, due_ms, fleet.submit(req), sent)


def warm_up(replicas: List, spec: Dict, seed: int, vocab: int) -> None:
    """Serve a few requests of the cell's own mix, four tokens each, on
    every replica (straight to the replica, not through a router, which
    need not reach them all): first one alone, then the rest at once, so
    that every shape the window uses (prefix restore, commit, lane insert,
    first-token selection) has run on every device before the clock
    starts."""
    from repro.serving.engine import Request
    w = spec["warmup"]
    plan = generator.plan(spec, seed, vocab, w["horizon_s"], first_id=10**9)
    plan = plan.requests if isinstance(plan, generator.ClosedPlan) else plan
    plan = plan[:w["requests"]]
    # and one prompt of every chunk width: 2 * ceiling - 1 tokens prefill as
    # one chunk of each power of two up to the ceiling
    rng = np.random.default_rng(generator.seed_words(seed))
    widths = {rep.name: rng.integers(
        generator.FIRST_TOKEN, vocab, size=2 * rep.prefill_chunk_tokens - 1
    ).astype(np.int32) for rep in replicas}

    def serve(rep):
        # deadlines no predictor can shed: the warm-up must run whole
        reqs = [Request(p.request_id, p.prompt, min(p.max_new_tokens, 4),
                        WARMUP_DEADLINE_MS) for p in plan]
        rep.generate(Request(10**9 - 1, widths[rep.name], 2,
                             WARMUP_DEADLINE_MS))
        rep.generate(reqs[0])
        with ThreadPoolExecutor(max_workers=len(reqs)) as ex:
            outs = list(ex.map(rep.generate, reqs[1:]))
        if any(len(o) != r.max_new_tokens for o, r in zip(outs, reqs[1:])):
            raise RuntimeError(f"warm-up on {rep.name} came back short")

    with ThreadPoolExecutor(max_workers=len(replicas)) as ex:
        list(ex.map(serve, replicas))


class Marks(threading.Thread):
    """Samples the MP table's heartbeats through the run, snapshots the
    counters at the window's edges and, in a traced run, traces a few
    seconds in the middle of the window."""

    def __init__(self, fl: Fleet, run: Run, trace_path: Optional[Path]):
        super().__init__(name="bench-marks", daemon=True)
        self.fl, self.rec, self.trace_path = fl, run, trace_path
        self.done = threading.Event()
        self.error: Optional[BaseException] = None

    def _sleep_until(self, t_ms: float) -> None:
        while not self.done.is_set():
            left = t_ms - time.monotonic() * 1e3
            if left <= 0:
                return
            snap = self.fl.fleet.table.snapshot()
            self.rec.heartbeats.append((time.monotonic() * 1e3, {
                k: (v.state.queued + v.state.reserved, v.state.running)
                for k, v in snap.items()}))
            time.sleep(min(HEARTBEAT_S, left / 1e3))

    def run_trace(self, lo: float, hi: float) -> None:
        import jax
        span = min(TRACE_S, (hi - lo) / 2e3)
        start = lo + (hi - lo) / 2 - span * 500
        self._sleep_until(start)
        jax.profiler.start_trace(str(self.trace_path))
        self.rec.counters["trace_start"] = _counters(self.fl)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            t0 = time.monotonic() * 1e3
            self._sleep_until(t0 + span * 1e3)
            t1 = time.monotonic() * 1e3
        self.rec.counters["trace_end"] = _counters(self.fl)
        jax.profiler.stop_trace()
        self.rec.trace_ms = (t0, t1)

    def run(self) -> None:
        try:
            lo, hi = self.rec.window_ms
            self._sleep_until(lo)
            self.rec.counters["window_start"] = _counters(self.fl)
            if self.trace_path is not None:
                self.run_trace(lo, hi)
            self._sleep_until(hi)
            self.rec.counters["window_end"] = _counters(self.fl)
            self._sleep_until(float("inf"))
        except BaseException as e:          # noqa: BLE001 — reported by drive()
            self.error = e


def drive(fl: Fleet, spec: Dict, seed: int, vocab: int, seconds: float,
          run: Run, trace_path: Optional[Path], stop: bool = True) -> float:
    """Offer the cell's load: a lead-in, then the measured window.  Returns
    the host time (ms) at which the drain gave up on unfinished requests.
    With ``stop`` the fleet is stopped at the drain limit; without, the
    drain waits for every request (calibration and sweeps reuse one
    fleet)."""
    lead = float(spec["lead_in_s"])
    horizon = lead + seconds
    plan = generator.plan(spec, seed, vocab, horizon)
    t0 = time.monotonic() * 1e3 + 200.0
    run.window_ms = (t0 + lead * 1e3, t0 + horizon * 1e3)
    marks = Marks(fl, run, trace_path)
    marks.start()
    results: List = []
    pool = ThreadPoolExecutor(max_workers=spec["max_in_flight"],
                              thread_name_prefix="client")
    try:
        if isinstance(plan, generator.ClosedPlan):
            stream = iter(plan.requests)
            lock = threading.Lock()
            end_ms = run.window_ms[1]

            def client():
                out = []
                while time.monotonic() * 1e3 < end_ms:
                    with lock:
                        p = next(stream, None)
                    if p is None:
                        raise RuntimeError("the closed loop ran out of requests")
                    out.append(_submit(fl.fleet, p, time.monotonic() * 1e3))
                return out

            while time.monotonic() * 1e3 < t0:
                time.sleep(0.001)
            results = [pool.submit(client) for _ in range(plan.clients)]
        else:
            for p in plan:
                due = t0 + p.due_s * 1e3
                left = due - time.monotonic() * 1e3
                if left > 0:
                    time.sleep(left / 1e3)
                results.append(pool.submit(_submit, fl.fleet, p, due))
        drain_end = run.window_ms[1] + spec["drain_s"] * 1e3
        wait(results, timeout=max(drain_end - time.monotonic() * 1e3, 0.0) / 1e3)
    finally:
        marks.done.set()
        marks.join()
        # stop the fleet before collecting: requests still queued or
        # decoding at the drain limit return at once, and count as failed
        if stop:
            fl.fleet.stop()
        pool.shutdown(wait=True)
    if marks.error is not None:
        raise marks.error
    for fut in results:
        got = fut.result()
        for rec in (got if isinstance(got, list) else [got]):
            if rec["finished_ms"] > drain_end:
                rec["ok"] = False
            run.records.append(rec)
    return drain_end


# ------------------------------------------------------------------ metrics
def end_to_end(run: Run, horizon_ms: float, setup_s: float) -> Dict[str, float]:
    counted = run.counted()
    lo, hi = run.window_ms
    out = {"setup_s": setup_s}
    if counted:
        wait = horizon_ms - lo
        out["ttft_p90_ms"] = stats.tail(counted, stats.ttft_ms, 90, wait)
        out["tpot_p90_ms"] = stats.tail(counted, stats.tpot_ms, 90, wait)
    done = [r for r in run.records
            if r["ok"] and lo <= r["finished_ms"] < hi]
    out["output_tokens_per_s"] = sum(r["n_out"] for r in done) / ((hi - lo) / 1e3)
    return out


def per_layer(names: List[str], run: Run) -> Dict[str, float]:
    out = {}
    for name in names:
        mod = load_module(BENCH / "metrics" / f"{name.split('.')[0]}.py",
                          f"bench_metric_{name.split('.')[0]}")
        value = mod.read(name, run)
        if value is not None:
            out[name] = value
    return out


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


# ------------------------------------------------------------------ the run
def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             bench: Optional[Dict] = None, config: Optional[Dict] = None,
             spec: Optional[Dict] = None,
             keep_trace: Optional[str] = None) -> Dict:
    """One run of ``workload``; returns the result line's object.  Tests
    pass ``bench``/``config``/``spec`` and ``require_tpu=False`` to drive
    the same path at a small size on the CPU."""
    import jax

    bench = bench or load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, workload)
    config = config or config_of(bench, cell["config"])
    spec = spec or load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise SystemExit(f"JAX found no TPU (platform "
                             f"{devices[0].platform!r}); refusing to run")
        if len(devices) < cell["chips"]:
            raise SystemExit(f"{workload} needs {cell['chips']} chips; JAX "
                             f"found {len(devices)}")
        kind_peaks = peaks(devices[0].device_kind)
        from repro.kernels.ops import kernel_impl
        if kernel_impl() != "pallas":
            raise SystemExit(f"kernel path {kernel_impl()!r} is not 'pallas'")
    else:
        kind_peaks = peaks("TPU v5 lite")
    devices = devices[:cell["chips"]]
    if require_tpu:
        say(f"compile cache {enable_compile_cache()}")
    logging.getLogger("repro").setLevel(logging.WARNING)

    dims = dims_of(config)
    vocab = dims["vocab_size"]
    ref = reference_of(config)
    fl = build_fleet(config, seed, devices, spec)
    setup_s = time.monotonic() - t_start
    say(f"set-up {setup_s:.3f} s")

    run = Run(config, dims, ref.FAMILY, kind_peaks, (0.0, 0.0))
    compiles: List[float] = []
    from jax import monitoring

    def on_event(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.monotonic() * 1e3)

    monitoring.register_event_duration_secs_listener(on_event)
    # name on standard error whatever compiles from here on: nothing should
    jax.config.update("jax_log_compiles", True)
    trace_path = None
    if trace:
        trace_path = TRACE_DIR / f"{workload}.{seed}"
        shutil.rmtree(trace_path, ignore_errors=True)
    horizon_ms = drive(fl, spec, seed, vocab, seconds, run, trace_path)
    run.compiles_in_window = sum(
        1 for t in compiles if run.window_ms[0] <= t < run.window_ms[1])
    peak = memory_peak(fl.devices)
    if trace:
        xplane = _xplane(trace_path)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, os.path.join(keep_trace,
                                             f"{workload}.{seed}.xplane.pb"))
        run.trace = trace_reduce.reduce(*trace_reduce.read(xplane))
        shutil.rmtree(trace_path, ignore_errors=True)

    counted = run.counted()
    failed = sum(1 for r in counted if not r["ok"])
    late = [r["sent_ms"] - r["due_ms"] for r in counted] or [0.0]
    say(f"requests due in the window {len(counted)}, failed {failed}, "
        f"outcomes {_outcomes(counted)}; compiles in the window "
        f"{run.compiles_in_window}; generator late p99 "
        f"{stats.percentile(late, 99):.3f} ms, max {max(late):.3f} ms")

    # free the program's state before the reference runs on the chip
    params = fl.params[0]
    n_devices = len(fl.devices)
    del fl
    gc.collect()
    check = correctness.check(config, ref, dims, params, counted, seed)

    metrics_e2e = end_to_end(run, horizon_ms, setup_s)
    names = [m["name"] for m in cell_metrics(bench, workload, trace)]
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    values = per_layer(names, run) if trace else \
        {k: metrics_e2e[k] for k in names if k in metrics_e2e}
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": n_devices, "memory_peak_bytes": peak}
    out = {"correct": check["correct"], "attempted": len(counted),
           "failed": failed,
           "metrics": {k: {"value": v, "unit": units[k]}
                       for k, v in values.items()},
           "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = trace_reduce.breakdown(run.trace)
    out["compared"] = check["compared"]
    for line in check["lines"]:
        say(line)
    return out


def _xplane(path: Path) -> str:
    found = sorted(path.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return str(found[-1])


def _outcomes(records: List[Dict]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for r in records:
        k = "ok" if r["ok"] else (r["outcome"] if r["outcome"] != "ok"
                                  else "short")
        out[k] = out.get(k, 0) + 1
    return out
