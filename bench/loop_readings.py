#!/usr/bin/env python3
"""Run one cell once, as ``bench/run.py`` does, and read besides what the
serving program counts that the harness does not record yet: each
request's lifecycle stamps and the router's prediction, the replicas' loop
counters, and the KV pages in the MP table's heartbeats.

  python3 bench/loop_readings.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1> [--out <file>]

Standard output ends with the run's result line and then one JSON object
of readings (``--out`` also writes it to a file):

* ``ttft_p90_ms`` and ``tpot_p90_ms`` as the harness computes them, in
  traced runs too (whose result line carries only per-layer metrics);
* ``routing_ms`` — ``enqueued - created`` (routing and admission) at p50,
  p99 and max, and ``submit_ms``, ``enqueued - sent``: the same less the
  load generator's lateness; ``queue_wait_ms`` — ``admitted - enqueued`` (waiting for a
  lane and KV pages); ``prefill_ms`` — ``first - admitted`` (prefill,
  with the decode steps interleaved in it): p50 and p90 over the window's
  counted requests that were served, ``bench/stats.percentile``;
* ``predict_error_pct`` — ``100 |observed - predicted| / observed`` with
  ``observed = finished - created``, p50 and p90 over the same requests;
* ``prefill_share_pct`` — the replicas' prefill time over their loop time
  between the window's counter marks; ``loop`` — each replica's counters
  over the window;
* ``kv_page_use`` — pages written over pages reserved, summed over the
  window's heartbeats and the replicas;
* with ``--trace 1``: ``spans`` (``bench/spans.py``'s summary of the
  traced window) and ``decode_tokens_traced`` — the tokens the decode
  steps emitted in the traced window and the KV positions they attended,
  by the program's counters and as ``bench/window.decode_contexts`` places
  them from the client's stamps.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from dataclasses import asdict  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

STAMPS = ("enqueued_ms", "admitted_ms", "first_ms", "predicted_ms")


def stamp_gaps(records: List[Dict], start: str, end: str) -> List[float]:
    """``end - start`` for each served record that carries both stamps
    (a request is created at its ``due_ms``)."""
    return [r[end] - r[start] for r in records
            if r["ok"] and r.get(start, 0.0) > 0.0 and r.get(end, 0.0) > 0.0]


def predict_errors(records: List[Dict]) -> List[float]:
    out = []
    for r in records:
        observed = r["finished_ms"] - r["due_ms"]
        if r["ok"] and r.get("predicted_ms", 0.0) > 0.0 and observed > 0.0:
            out.append(100.0 * abs(observed - r["predicted_ms"]) / observed)
    return out


def loop_delta(run, start: str, end: str) -> Optional[Dict[str, Dict]]:
    """Each replica's loop counters between two counter marks."""
    a, b = run.counters.get(start), run.counters.get(end)
    if a is None or b is None or "loop" not in b:
        return None
    return {name: {k: v - a["loop"].get(name, {}).get(k, 0)
                   for k, v in c.items()}
            for name, c in b["loop"].items()}


def readings(run, pages: List[tuple]) -> Dict:
    """The readings of the module docstring, from a finished run whose
    records carry the stamps and whose counter marks carry ``loop``;
    ``pages`` holds ``(t_ms, {replica: (used, reserved)})`` samples."""
    from bench import stats

    def pct(values, qs):
        return {f"p{q}": stats.percentile(values, q) if values else None
                for q in qs}

    counted = run.counted()
    out = {"routing_ms": pct(stamp_gaps(counted, "due_ms", "enqueued_ms"),
                             (50, 99, 100)),
           "submit_ms": pct(stamp_gaps(counted, "sent_ms", "enqueued_ms"),
                            (50, 99, 100)),
           "queue_wait_ms": pct(stamp_gaps(counted, "enqueued_ms",
                                           "admitted_ms"), (50, 90)),
           "prefill_ms": pct(stamp_gaps(counted, "admitted_ms", "first_ms"),
                             (50, 90)),
           "predict_error_pct": pct(predict_errors(counted), (50, 90))}
    loop = out["loop"] = loop_delta(run, "window_start", "window_end")
    total = sum(c["loop_ms"] for c in loop.values()) if loop else 0.0
    out["prefill_share_pct"] = (
        100.0 * sum(c["prefill_ms"] for c in loop.values()) / total
        if total else None)
    lo, hi = run.window_ms
    used = reserved = 0
    for t, snap in pages:
        if lo <= t < hi:
            used += sum(u for u, _ in snap.values())
            reserved += sum(r for _, r in snap.values())
    out["kv_page_use"] = used / reserved if reserved else None
    return out


class PageSampler(threading.Thread):
    """Samples the KV page fields of the MP table's heartbeats, as the
    harness samples its queue fields."""

    def __init__(self, fleet, period_s: float):
        super().__init__(name="bench-pages", daemon=True)
        self.fleet, self.period_s = fleet, period_s
        self.samples: List[tuple] = []
        self.done = threading.Event()

    def run(self) -> None:
        while not self.done.wait(self.period_s):
            snap = self.fleet.table.snapshot()
            self.samples.append((time.monotonic() * 1e3, {
                k: (getattr(v.state, "pages_used", 0),
                    getattr(v.state, "pages_reserved", 0))
                for k, v in snap.items()}))


def run_with_readings(workload: str, seed: int, seconds: float,
                      trace: bool, keep_dir: str, **kw):
    """``harness.run_cell`` with its record, counter snapshot and drive
    widened to keep what the program offers; returns the result line's
    object and the readings."""
    from bench import harness, spans, window
    plain_record, plain_counters = harness._record, harness._counters
    plain_drive = harness.drive
    seen: Dict = {}

    def record(p, due_ms, res, sent_ms):
        rec = plain_record(p, due_ms, res, sent_ms)
        rec.update({k: getattr(res, k, 0.0) for k in STAMPS})
        return rec

    def counters(fl):
        snap = plain_counters(fl)
        snap["loop"] = {r.name: asdict(r.counters) for r in fl.replicas}
        return snap

    def drive(fl, spec, seed, vocab, seconds, run, trace_path, stop=True):
        sampler = PageSampler(fl.fleet, harness.HEARTBEAT_S)
        seen.update(run=run, sampler=sampler, replica_of={
            r.device.id: r.name for r in fl.replicas if r.device is not None})
        sampler.start()
        try:
            seen["horizon_ms"] = plain_drive(fl, spec, seed, vocab, seconds,
                                             run, trace_path, stop)
            return seen["horizon_ms"]
        finally:
            sampler.done.set()
            sampler.join()

    harness._record, harness._counters = record, counters
    harness.drive = drive
    try:
        out = harness.run_cell(workload, seed, seconds, trace,
                               keep_trace=keep_dir if trace else None, **kw)
    finally:
        harness._record, harness._counters = plain_record, plain_counters
        harness.drive = plain_drive
    run = seen["run"]
    got = harness.end_to_end(run, seen["horizon_ms"], 0.0)
    del got["setup_s"]
    got.update(readings(run, seen["sampler"].samples))
    if trace:
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(
            os.path.join(keep_dir, f"{workload}.{seed}.xplane.pb"))
        got["spans"] = spans.summarize(pd, seen["replica_of"])
        traced = loop_delta(run, "trace_start", "trace_end") or {}
        ctxs = window.decode_contexts(run.records, *run.trace_ms)
        got["decode_tokens_traced"] = {
            "counters": sum(c["decode_tokens"] for c in traced.values()),
            "counters_context": sum(c["context_tokens"]
                                    for c in traced.values()),
            "client_stamps": int(len(ctxs)),
            "client_stamps_context": int(ctxs.sum())}
    return out, got


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    import shutil
    from bench.harness import TRACE_DIR
    from bench.run import finite
    keep = str(TRACE_DIR / f"kept.{args.workload}.{args.seed}")
    try:
        out, got = run_with_readings(args.workload, args.seed, args.seconds,
                                     bool(args.trace), keep, t_start=T_START)
    except SystemExit as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    finally:
        shutil.rmtree(keep, ignore_errors=True)
    print(json.dumps(finite(out)), flush=True)
    print(json.dumps(finite(got)), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(finite({"result": out, "readings": got}), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
