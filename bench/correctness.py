"""``correct``: what the timed path served, against the plain reference.

Once the window has closed, a sample of the finished requests drawn from
the seed — the longest of them first, then one from each replica not yet
in it, then others until the sample holds the configured number of served
tokens — is run through the
configuration's float32 reference, prompt and served tokens together.  At
each served position the reference's best logit is compared with its logit
for the token the program served; the number compared is the widest such
gap over the sample, ``max_logit_gap``.  (Greedy decoding serves the
token with the best logit, so a sound program's gaps are rounding.)

The control reads the same gap for the token that the reference computed
from fp8-rounded operands puts first (``control_gaps``); calibration runs
it, the benchmark's own runs do not.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

BUCKET = 512                 # sequence lengths round up to this
SCORE_BUCKET = 128           # served positions round up to this


def _roundup(n: int, m: int) -> int:
    return -(-n // m) * m


def sample(records: List[Dict], seed: int, tokens: int,
           max_requests: int) -> List[Dict]:
    """Finished requests to compare: the longest (prompt + served tokens),
    then, in an order drawn from ``seed``, one from each replica that has
    none in the sample yet, then others until ``tokens`` served tokens or
    ``max_requests`` requests.  Every replica that finished a request is
    compared, whatever the limits."""
    ok = [r for r in records if r["ok"]]
    if not ok:
        return []
    ok.sort(key=lambda r: (-(len(r["prompt"]) + r["n_out"]), r["id"]))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    rest = [ok[i] for i in 1 + rng.permutation(len(ok) - 1)]
    out, served = [ok[0]], ok[0]["n_out"]
    seen = {ok[0].get("replica")}
    for r in rest:
        if r.get("replica") not in seen:
            seen.add(r.get("replica"))
            out.append(r)
            served += r["n_out"]
    for r in rest:
        if served >= tokens or len(out) >= max_requests:
            break
        if any(r is o for o in out):
            continue
        out.append(r)
        served += r["n_out"]
    return out


class Scorer:
    """The reference's logits at the served positions of one request, and
    the gaps they give; one compiled program per bucketed shape."""

    def __init__(self, ref, dims: Dict, params):
        import jax
        import jax.numpy as jnp
        self.ref, self.dims, self.params = ref, dims, params
        self._fns: Dict = {}
        self._jax, self._jnp = jax, jnp

    def _fn(self, length: int, n: int, control: bool):
        key = (length, n, control)
        if key not in self._fns:
            jax, jnp = self._jax, self._jnp
            ref, dims = self.ref, self.dims

            def gaps(params, toks, start, served):
                lg = ref.logits(params, dims, toks, start, n)
                best = lg.max(-1)
                got = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
                out = [best - got]
                if control:
                    top = ref.logits(params, dims, toks, start, n,
                                     control=True).argmax(-1)
                    out.append(best - jnp.take_along_axis(
                        lg, top[:, None], -1)[:, 0])
                return out

            self._fns[key] = jax.jit(gaps)
        return self._fns[key]

    def __call__(self, rec: Dict, control: bool = False) -> List[np.ndarray]:
        prompt, served = rec["prompt"], rec["tokens"]
        n = len(served)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        start = len(prompt) - 1
        n_b = _roundup(n, SCORE_BUCKET)
        blk = self.ref.Q_BLOCK
        length = _roundup(max(len(seq), start + n_b), max(BUCKET, blk))
        toks = np.zeros((length,), np.int32)
        toks[:len(seq)] = seq
        tgt = np.zeros((n_b,), np.int32)
        tgt[:n] = served
        out = self._fn(length, n_b, control)(
            self.params, self._jnp.asarray(toks), start, self._jnp.asarray(tgt))
        return [np.asarray(o, np.float64)[:n] for o in out]


def check(config: Dict, ref, dims: Dict, params, records: List[Dict],
          seed: int) -> Dict:
    """Compare a sample of ``records`` with the reference; returns
    ``correct``, the numbers compared with their limits, and lines for
    standard error."""
    c = config["correctness"]
    limit = float(c["max_logit_gap"])
    picked = sample(records, seed, c["sample_tokens"], c["max_requests"])
    vocab = dims["vocab_size"]
    worst = math.inf
    bad_range = False
    if picked:
        score = Scorer(ref, dims, params)
        worst = 0.0
        for rec in picked:
            toks = rec["tokens"]
            if toks.min() < 0 or toks.max() >= vocab:
                bad_range = True
                continue
            worst = max(worst, float(score(rec)[0].max()))
    served = sum(r["n_out"] for r in picked)
    correct = bool(picked) and not bad_range and worst <= limit
    lines = [f"compared {len(picked)} requests, {served} served tokens"
             f"{'; a token out of the vocabulary' if bad_range else ''}",
             f"max_logit_gap {worst!r} limit {limit!r}"]
    return {"correct": correct, "lines": lines,
            "compared": {"max_logit_gap": {"value": worst, "limit": limit}}}
