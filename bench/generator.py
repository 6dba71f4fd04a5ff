"""The one traffic generator: reads a mix's parameter file and makes the
requests of a run from ``--seed``.

Two kinds of mix, chosen by the file's ``kind``:

* ``sessions`` — an open loop of chat sessions.  Sessions start at fixed
  rate; each turn's prompt is the fleet-wide system prompt, the session's
  history (earlier user messages, and seeded assistant tokens as long as
  those turns' outputs) and a new user message, rounded up to whole pages
  so that each turn extends the previous turn's prompt page for page.
  Turn ``k + 1`` is due a think time after turn ``k`` was due, whether or
  not turn ``k`` has been answered (users of a chat UI type on).
* ``closed`` — a closed loop of ``clients`` callers; each sends its next
  request when the previous one returns.

Every size, arrival gap and their order is drawn from the file's
``pool_seed``: every run seed serves the same schedule, and draws only the
token ids (and, in the harness, the weights).  Runs of different seeds then
do the same work at the same times.  (Shuffling the sessions per seed made
the p90 TTFT of one-minute windows differ ten-fold between seeds while two
runs of one seed agreed within a few percent: at 4/5 of the knee the tail
is set by which long sessions happen to arrive together.)
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

FIRST_TOKEN = 2          # ids 0 and 1 are left out of prompts (pad / bos)


@dataclass
class Planned:
    """One request of a run: when it is due, what it asks for."""

    request_id: int
    due_s: float                 # seconds after the lead-in starts (open loop)
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int
    deadline_ms: float
    session: int = -1
    turn: int = 0


@dataclass
class ClosedPlan:
    """A closed loop: ``clients`` callers share one stream of requests."""

    clients: int
    requests: List[Planned] = field(default_factory=list)


def _lognormal_int(rng, spec: Dict, n: int) -> np.ndarray:
    """``n`` draws of a lognormal with the stated median and sigma, rounded
    and clipped to ``[min, max]``."""
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng, rate: float, n: int, span_s: float) -> np.ndarray:
    """``n`` exponential gaps of mean ``1/rate``, scaled so they sum to
    ``span_s``: the count of arrivals in the span is fixed, not Poisson."""
    g = rng.exponential(1.0 / rate, size=n)
    return g * (span_s / g.sum())


def _align_up(n: int, align: int) -> int:
    return -(-n // align) * align


# --------------------------------------------------------------- sessions
def session_shapes(spec: Dict, horizon_s: float) -> List[Dict]:
    """The sessions of a run, drawn from ``pool_seed`` alone: for each, its
    start gap and per-turn user length, output length and think time.
    Lengths are final (page-aligned, capped by the prompt limit)."""
    rng = np.random.default_rng(spec["pool_seed"])
    n = max(int(round(spec["session_rate_per_s"] * horizon_s)), 1)
    gaps = _gaps(rng, spec["session_rate_per_s"], n, horizon_s)
    t = spec["turns"]
    extra = rng.geometric(1.0 / (t["mean_extra"] + 1.0), size=n) - 1
    n_turns = np.minimum(1 + extra, t["max"])
    sys_len = spec["system_prompt_tokens"]
    align = spec["align_tokens"]
    limit = spec["max_prompt_tokens"]
    shapes = []
    for i in range(n):
        k = int(n_turns[i])
        users = _lognormal_int(rng, spec["user_tokens"], k)
        outs = _lognormal_int(rng, spec["output_tokens"], k)
        think = np.clip(rng.exponential(spec["think_s"]["mean"], size=k),
                        spec["think_s"]["min"], spec["think_s"]["max"])
        turns, length = [], sys_len
        for j in range(k):
            if j > 0:
                length += int(outs[j - 1])       # the previous answer
            end = _align_up(length + int(users[j]), align)
            if end > limit:
                break
            turns.append({"user": end - length, "out": int(outs[j]),
                          "think_s": float(think[j]) if j > 0 else 0.0})
            length = end
        shapes.append({"gap_s": float(gaps[i]), "turns": turns})
    return [s for s in shapes if s["turns"]]


def sessions(spec: Dict, seed: int, vocab: int, horizon_s: float,
             first_id: int = 0) -> List[Planned]:
    """Every turn due before ``horizon_s``, sorted by due time."""
    shapes = session_shapes(spec, horizon_s)
    rng = np.random.default_rng(seed_words(seed))
    system = _tokens(rng, spec["system_prompt_tokens"], vocab)
    lim = spec["limits"]
    out: List[Planned] = []
    rid = first_id
    start = 0.0
    for si, shape in enumerate(shapes):
        # the first session opens at 0; each later one a gap after the last
        start += shapes[si - 1]["gap_s"] if si else 0.0
        if start >= horizon_s:
            break
        history = system
        due = start
        for j, turn in enumerate(shape["turns"]):
            due += turn["think_s"]
            if due >= horizon_s:
                break
            user = _tokens(rng, turn["user"], vocab)
            prompt = np.concatenate([history, user])
            out.append(Planned(
                rid, due, prompt, turn["out"],
                lim["ttft_ms"] + turn["out"] * lim["tpot_ms"],
                session=si, turn=j))
            rid += 1
            history = np.concatenate([prompt, _tokens(rng, turn["out"], vocab)])
    out.sort(key=lambda p: (p.due_s, p.request_id))
    return out


# ----------------------------------------------------------------- closed
def closed(spec: Dict, seed: int, vocab: int, first_id: int = 0) -> ClosedPlan:
    """The shared request stream of a closed loop: the pool's sizes, token
    ids drawn from ``seed``."""
    pool = np.random.default_rng(spec["pool_seed"])
    n = spec["pool_requests"]
    plens = _lognormal_int(pool, spec["prompt_tokens"], n)
    outs = _lognormal_int(pool, spec["output_tokens"], n)
    rng = np.random.default_rng(seed_words(seed))
    reqs = [Planned(first_id + i, 0.0, _tokens(rng, int(plens[i]), vocab),
                    int(outs[i]), float(spec["deadline_ms"]))
            for i in range(n)]
    return ClosedPlan(int(spec["clients"]), reqs)


# ------------------------------------------------------------------ shared
def seed_words(seed: int):
    """A seed of any size (seeds may exceed 32 bits) as words numpy's
    generator takes whole."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(FIRST_TOKEN, vocab, size=int(n)).astype(np.int32)


def plan(spec: Dict, seed: int, vocab: int, horizon_s: float,
         first_id: int = 0):
    """The requests of one run of ``spec``: a sorted ``List[Planned]`` for
    an open loop, a ``ClosedPlan`` for a closed one."""
    if spec["kind"] == "sessions":
        return sessions(spec, seed, vocab, horizon_s, first_id)
    if spec["kind"] == "closed":
        return closed(spec, seed, vocab, first_id)
    raise ValueError(f"unknown traffic kind {spec['kind']!r}")
