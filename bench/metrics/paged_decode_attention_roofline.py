"""Kernel ``paged_decode_attention``: the least time the chip needs for the
decode attention of the tokens decoded in the traced window — over their
live context only, at the larger of the FLOP and HBM bounds — over the
kernel's device time summed over every chip that decoded them (the trace
gives it per chip, averaged).  Bytes bound it at these shapes."""
from bench import counts
from bench.window import decode_contexts

KERNEL = r"paged_decode_attention"


def read(name, run):
    if run.trace is None or run.trace_ms is None:
        return None
    kernel_s = run.trace.seconds_of(KERNEL) * run.trace.devices
    if kernel_s <= 0:
        return None
    c = counts.paged_attention_counts(
        run.dims, decode_contexts(run.records, *run.trace_ms))
    if not c["bytes"]:
        return None
    p = run.peaks
    least = max(c["flops"] / p["bf16_flops_per_s"],
                c["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
