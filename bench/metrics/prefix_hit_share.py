"""KV manager: the share of prompt tokens the prefix cache spared the
prefill, ``1 - computed / submitted``: prompt tokens the replicas computed
over the window (their ``prefilled_tokens`` counters) against the prompt
tokens of the requests whose first token came in the window.  Only where
the replicas keep a prefix cache."""
from bench.window import prefilled


def read(name, run):
    if not run.config["replica"].get("prefix_cache"):
        return None
    computed = prefilled(run, "window_start", "window_end")
    lo, hi = run.window_ms
    submitted = sum(len(r["prompt"]) for r in run.records
                    if r["ok"] and lo <= r["due_ms"] + r["ttft_ms"] < hi)
    if computed is None or not submitted:
        return None
    return 1.0 - computed / submitted
