"""Per-request latency arithmetic.  A request that failed (shed, rejected,
lost, short of its tokens, or unfinished at the drain limit) counts as a
miss: its latency is +inf in every tail."""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

INF = float("inf")


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 100]): the smallest value with
    at least ``q`` percent of the sample at or below it.  Infinite values
    (misses) sort last, so a tail that reaches them is infinite."""
    if not values:
        return math.nan
    xs = sorted(values)
    k = max(math.ceil(q / 100.0 * len(xs)), 1)
    return xs[k - 1]


def ttft_ms(rec: Dict) -> float:
    """Due time to first token; +inf for a failed request."""
    return rec["ttft_ms"] if rec["ok"] else INF


def tpot_ms(rec: Dict) -> float:
    """Mean gap between output tokens after the first:
    ``(finished - due - ttft) / (n_out - 1)``; +inf for a failed request."""
    if not rec["ok"]:
        return INF
    n = rec["n_out"]
    if n < 2:
        return 0.0
    return (rec["finished_ms"] - rec["due_ms"] - rec["ttft_ms"]) / (n - 1)


def tail(records: List[Dict], fn, q: float, horizon_ms: float) -> float:
    """The ``q``-th percentile of ``fn`` over ``records``.  Where the tail
    lands on a miss, it reads ``horizon_ms``: the longest any counted
    request was waited for before the run gave up on it, which is the
    least its latency can be."""
    v = percentile([fn(r) for r in records], q)
    return horizon_ms if math.isinf(v) else v
