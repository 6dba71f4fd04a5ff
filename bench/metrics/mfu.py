"""Model step, whole: model FLOPs of the tokens decoded and prefilled in
the traced window, on every chip of the fleet, over the chips' busy time
in it (busy seconds per chip times chips) times one chip's bf16 peak.
Decode tokens count their attention over the live context; prompt tokens
count their weights only (their attention over earlier context is left
out, so the share is a lower bound)."""
from bench import counts
from bench.window import decode_contexts, prefilled


def read(name, run):
    if run.trace is None or run.trace_ms is None or run.trace.busy_s <= 0:
        return None
    ctxs = decode_contexts(run.records, *run.trace_ms)
    flops = sum(counts.token_flops(run.dims, run.family, int(c), True)
                for c in ctxs)
    pre = prefilled(run, "trace_start", "trace_end") or 0
    flops += pre * counts.token_flops(run.dims, run.family, 0, False)
    if not flops:
        return None
    chip_s = run.trace.busy_s * run.trace.devices
    return 100.0 * flops / (chip_s * run.peaks["bf16_flops_per_s"])
