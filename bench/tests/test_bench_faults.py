"""A whole run on the CPU at a small size, past the harness's look for a
chip: ``correct`` holds for the program as it is, and comes out false with
the timed path broken underneath — a token altered where the decode step
produces it, and a decode step that returns its cache unchanged.  The
control (the reference from fp8-rounded operands) reads a wider gap than
the program on the same sample."""
import time

import pytest

from bench import correctness, harness
from bench.tests import sizes

CELL = "qwen3-4b.sessions"


def run(**kw):
    return harness.run_cell(CELL, 2**31 + 99, 3.0, False,
                            t_start=time.monotonic(), require_tpu=False,
                            bench=sizes.bench(), config=sizes.small_qwen(),
                            spec=sizes.small_sessions(), **kw)


def test_sound_run_is_correct_and_control_reads_wider(monkeypatch):
    kept = {}
    real = correctness.check

    def spy(config, ref, dims, params, records, seed):
        kept.update(ref=ref, dims=dims, params=params, records=records,
                    seed=seed, c=config["correctness"])
        return real(config, ref, dims, params, records, seed)

    monkeypatch.setattr(correctness, "check", spy)
    out = run()
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "compared"
    gap = out["compared"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    picked = correctness.sample(kept["records"], kept["seed"],
                                kept["c"]["sample_tokens"],
                                kept["c"]["max_requests"])
    score = correctness.Scorer(kept["ref"], kept["dims"], kept["params"])
    prog = ctrl = 0.0
    for rec in picked:
        p, c = score(rec, control=True)
        prog, ctrl = max(prog, p.max()), max(ctrl, c.max())
    assert ctrl > 0 and ctrl > 3 * prog


def _altered(orig):
    def step(self, params, cache, tok, idx, tables):
        nxt, cache = orig(self, params, cache, tok, idx, tables)
        return (nxt + 1) % self.cfg.vocab_size, cache
    return step


def _frozen(orig):
    def step(self, params, cache, tok, idx, tables):
        nxt, _ = orig(self, params, cache, tok, idx, tables)
        return nxt, cache
    return step


@pytest.mark.parametrize("fault", [_altered, _frozen],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.serving.engine import Replica
    monkeypatch.setattr(Replica, "_step_paged_impl",
                        fault(Replica._step_paged_impl))
    out = run()
    gap = out["compared"]["max_logit_gap"]
    assert out["correct"] is False
    assert gap["value"] > gap["limit"]
