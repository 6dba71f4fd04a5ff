#!/usr/bin/env python3
"""Find a session mix's knee once, on the chip: serve the cell's traffic at
each of several session rates in one process (one set-up) and report, per
rate, the turns served per second, the TTFT and TPOT tails, the share of
turns that met both limits, and whether the queue grew through the window.

  python3 bench/sweep.py --workload qwen3-4b.sessions --seed 11 \
      --rates 0.05,0.3,0.5 --seconds 30 [--ttft-ms 2000 --tpot-ms 100] \
      [--out sweep.json]

The limits default to the traffic file's.  The knee is the highest rate at
which 90% of the turns meet both limits with no growing backlog; the cell
then runs at about four fifths of it, a number fixed in its traffic file.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def summarize(run, seconds, ttft_lim, tpot_lim):
    import numpy as np
    from bench import stats
    counted = run.counted()
    lo, hi = run.window_ms
    third = (hi - lo) / 3
    q = [sum(v[0] for v in snap.values()) for t, snap in run.heartbeats
         if lo <= t < hi]
    qt = [t for t, snap in run.heartbeats if lo <= t < hi]
    first = [x for x, t in zip(q, qt) if t < lo + third]
    last = [x for x, t in zip(q, qt) if t >= hi - third]
    ttft = [stats.ttft_ms(r) for r in counted]
    tpot = [stats.tpot_ms(r) for r in counted]
    met = sum(1 for a, b in zip(ttft, tpot) if a <= ttft_lim and b <= tpot_lim)
    return {
        "turns": len(counted), "turns_per_s": len(counted) / seconds,
        "failed": sum(1 for r in counted if not r["ok"]),
        "ttft_p50_ms": stats.percentile(ttft, 50),
        "ttft_p90_ms": stats.percentile(ttft, 90),
        "tpot_p50_ms": stats.percentile(tpot, 50),
        "tpot_p90_ms": stats.percentile(tpot, 90),
        "met_share": met / max(len(counted), 1),
        "queue_first_third": float(np.mean(first)) if first else None,
        "queue_last_third": float(np.mean(last)) if last else None,
        "prompt_tokens_mean": float(np.mean([len(r["prompt"]) for r in counted]))
        if counted else None,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--ttft-ms", type=float, default=None)
    ap.add_argument("--tpot-ms", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    from bench import harness
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    config = harness.config_of(bench, cell["config"])
    spec = harness.load_json(harness.BENCH / "traffic" / f"{cell['traffic']}.json")
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        sys.exit("the sweep needs the cell's TPU chips")
    harness.enable_compile_cache()
    lim = dict(spec["limits"])
    if args.ttft_ms:
        lim["ttft_ms"] = args.ttft_ms
    if args.tpot_ms:
        lim["tpot_ms"] = args.tpot_ms
    spec["limits"] = lim
    dims = harness.dims_of(config)
    fl = harness.build_fleet(config, args.seed, devices[:cell["chips"]], spec)
    harness.say(f"set-up {time.monotonic() - T_START:.1f} s")
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        s = dict(spec, session_rate_per_s=rate, lead_in_s=min(spec["lead_in_s"], 10.0))
        run = harness.Run(config, dims, "dense", {}, (0.0, 0.0))
        harness.drive(fl, s, args.seed + 2 + i, dims["vocab_size"],
                      args.seconds, run, None, stop=False)
        row = {"session_rate_per_s": rate,
               **summarize(run, args.seconds, lim["ttft_ms"], lim["tpot_ms"])}
        rows.append(row)
        print(json.dumps(row), flush=True)
    fl.fleet.stop()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "limits": lim, "rows": rows},
                      f, indent=1)


if __name__ == "__main__":
    main()
