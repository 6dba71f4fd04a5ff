"""Scheduler: the share of the device's busy time in the traced window
that went to prefill, against the decode steps every output token waits
on: the programs that compute prompt chunks and move them into and out of
the KV cache (``jit_prefill_chunk``, ``jit_prefill``, ``jit_restore``,
``jit_copy_page``, ``jit_commit``, ``jit_insert``), from the trace's
program events.  A program that leaves its prefill work anonymous
(``jit__lambda``) cannot be read."""
import re

PREFILL = r"^jit_(prefill(_chunk)?|restore|copy_page|commit|insert)\b"
ANONYMOUS = re.compile(r"^jit__lambda")


def read(name, run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    if any(ANONYMOUS.match(n) for n in run.trace.module_s):
        return None
    secs, _ = run.trace.module_seconds_calls(PREFILL)
    return 100.0 * secs / run.trace.busy_s
