"""Kernel ``ssd_scan``: the least time the chip needs for the SSD scan of
the prompt tokens prefilled in the traced window — the recurrence's
operations and its inputs and outputs, at the larger of the FLOP and HBM
bounds (bytes, at these shapes) — over the kernel's device time summed
over every chip that prefilled them (the trace gives it per chip,
averaged)."""
from bench import counts
from bench.window import prefilled

KERNEL = r"ssd_scan"


def read(name, run):
    if run.trace is None or run.family != "ssm":
        return None
    kernel_s = run.trace.seconds_of(KERNEL) * run.trace.devices
    tokens = prefilled(run, "trace_start", "trace_end")
    if kernel_s <= 0 or not tokens:
        return None
    c = counts.ssd_scan_counts(run.dims, tokens)
    p = run.peaks
    least = max(c["flops"] / p["bf16_flops_per_s"],
                c["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
