#!/usr/bin/env python3
"""Readings for a cell's correctness limit, on the chip, in one process:
for each seed, the fleet built from that seed serves the cell's traffic
for a short window, and the sample the benchmark would check is compared
with the reference twice — the program's served tokens (the lower
reading) and, for the control seeds, the token the fp8-rounded reference
puts first at each of the same positions (the upper reading).

  python3 bench/calibrate.py --workload qwen3-4b.sessions \
      --seeds 101,102,... --control-seeds 101,102,103 --seconds 20 \
      [--out calibrate.json]

The limit goes above the largest program reading and below the smallest
control reading, with more room above the former; ``PERF.md`` records the
readings and the limit.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    from bench import correctness, harness
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.find_cell(bench, args.workload)
    config = harness.config_of(bench, cell["config"])
    spec = harness.load_json(harness.BENCH / "traffic" / f"{cell['traffic']}.json")
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        sys.exit("calibration needs the cell's TPU chips")
    harness.enable_compile_cache()
    dims = harness.dims_of(config)
    ref = harness.reference_of(config)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    c = config["correctness"]
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        fl = harness.build_fleet(config, seed, devices[:cell["chips"]], spec)
        run = harness.Run(config, dims, ref.FAMILY, {}, (0.0, 0.0))
        harness.drive(fl, spec, seed, dims["vocab_size"], args.seconds, run,
                      None)
        params = fl.params[0]
        del fl
        gc.collect()
        counted = run.counted()
        picked = correctness.sample(counted, seed, c["sample_tokens"],
                                    c["max_requests"])
        score = correctness.Scorer(ref, dims, params)
        prog, ctrl = [], []
        for rec in picked:
            got = score(rec, control=seed in controls)
            prog.append(float(got[0].max()))
            if seed in controls:
                ctrl.append(float(got[1].max()))
        row = {"seed": seed, "requests": len(picked),
               "served_tokens": sum(r["n_out"] for r in picked),
               "counted": len(counted),
               "failed": sum(1 for r in counted if not r["ok"]),
               "program_max_gap": max(prog) if prog else None,
               "program_gaps": prog,
               "control_max_gap": max(ctrl) if ctrl else None,
               "control_gaps": ctrl, "seconds": time.monotonic() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del params, score
        gc.collect()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
