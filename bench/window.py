"""What the served requests did inside an interval of host time, for the
per-layer readers: the decode tokens emitted in it (with the context each
attended over) and the prompt tokens prefilled in it.

Emission times come from the client's own stamps: a request's first token
(from its prefill) at ``due + ttft``, and its later tokens, one decode step
each, spread evenly up to ``finished``.  The decode step that emits token
``i`` (1-based, ``i >= 2``) attends over ``len(prompt) + i - 1``
positions.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def decode_contexts(records: List[Dict], lo_ms: float, hi_ms: float) -> np.ndarray:
    """Context length of every decode token emitted in ``[lo_ms, hi_ms)``."""
    out = []
    for r in records:
        n = r["n_out"]
        if not r["ok"] or n < 2:
            continue
        first = r["due_ms"] + r["ttft_ms"]
        step = (r["finished_ms"] - first) / (n - 1)
        i = np.arange(2, n + 1)
        t = first + (i - 1) * step
        sel = (t >= lo_ms) & (t < hi_ms)
        if sel.any():
            out.append(len(r["prompt"]) + i[sel] - 1)
    return np.concatenate(out) if out else np.zeros((0,), np.int64)


def prefilled(run, start: str, end: str) -> Optional[int]:
    """Prompt tokens the replicas computed between two counter marks."""
    a, b = run.counters.get(start), run.counters.get(end)
    if a is None or b is None:
        return None
    return sum(b["prefilled"][k] - a["prefilled"].get(k, 0)
               for k in b["prefilled"])


def heartbeat_mean(run, index: int) -> Optional[float]:
    """Mean over the window's heartbeat samples, and over the replicas, of
    field ``index`` (0: queued + reserved, 1: running lanes)."""
    lo, hi = run.window_ms
    vals = [v[index] for t, snap in run.heartbeats if lo <= t < hi
            for v in snap.values()]
    return float(np.mean(vals)) if vals else None
