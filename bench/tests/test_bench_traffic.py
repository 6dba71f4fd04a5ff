"""The traffic generator: deterministic for a seed, true to its stated
parameters, and session prompts that extend the previous turn page for
page."""
import collections

import numpy as np
import pytest

from bench import generator, harness

BIG_SEED = 2**31 + 12345          # seeds may pass 32 signed bits


def spec(name):
    return harness.load_json(harness.BENCH / "traffic" / f"{name}.json")


def _key(p):
    return (p.due_s, len(p.prompt), p.max_new_tokens, p.deadline_ms,
            p.session, p.turn)


@pytest.mark.parametrize("mix", ["sessions", "sessions-fleet4"])
def test_sessions_deterministic_for_a_seed(mix):
    s = spec(mix)
    a = generator.plan(s, BIG_SEED, 1000, 40.0)
    b = generator.plan(s, BIG_SEED, 1000, 40.0)
    assert [_key(p) for p in a] == [_key(p) for p in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = generator.plan(s, BIG_SEED + 1, 1000, 40.0)
    assert not all(np.array_equal(x.prompt[:50], y.prompt[:50])
                   for x, y in zip(a, c))


def test_sessions_same_work_for_every_seed():
    """Every seed serves the pool's schedule — the same sessions, turn
    lengths and due times — and draws only the token ids."""
    s = spec("sessions")
    horizon = 200.0
    shapes = generator.session_shapes(s, horizon)
    assert len(shapes) == round(s["session_rate_per_s"] * horizon)
    a = generator.sessions(s, 3, 1000, horizon)
    b = generator.sessions(s, BIG_SEED, 1000, horizon)
    assert a and [_key(p) for p in a] == [_key(p) for p in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    for p in a:
        turns = shapes[p.session]["turns"]
        want = s["system_prompt_tokens"] + sum(
            t["user"] + t["out"] for t in turns[:p.turn]) + turns[p.turn]["user"]
        assert len(p.prompt) == want
        assert p.max_new_tokens == turns[p.turn]["out"]
    # sessions open in the pool's order, a pool gap apart
    firsts = [p for p in a if p.turn == 0]
    assert [p.session for p in firsts] == sorted(p.session for p in firsts)
    assert firsts[1].due_s == pytest.approx(shapes[0]["gap_s"])


def test_sessions_match_stated_parameters():
    s = spec("sessions")
    horizon = 4000.0                       # many sessions, for the medians
    shapes = generator.session_shapes(s, horizon)
    turns = [len(x["turns"]) for x in shapes]
    assert max(turns) <= s["turns"]["max"]
    outs = [t["out"] for x in shapes for t in x["turns"]]
    o = s["output_tokens"]
    assert o["min"] <= min(outs) and max(outs) <= o["max"]
    assert abs(np.median(outs) / o["median"] - 1) < 0.1
    thinks = [t["think_s"] for x in shapes for t in x["turns"][1:]]
    assert s["think_s"]["min"] <= min(thinks)
    assert max(thinks) <= s["think_s"]["max"]
    users = [t["user"] for x in shapes for t in x["turns"]]
    u = s["user_tokens"]
    assert min(users) >= u["min"]
    assert max(users) <= u["max"] + s["align_tokens"]
    # 1 + geometric(mean 3), capped at 8, cut short by the prompt limit
    assert 2.5 < np.mean(turns) < 4.0
    gaps = [x["gap_s"] for x in shapes]
    assert abs(sum(gaps) - horizon) < 1e-6 * horizon
    assert abs(np.mean(gaps) * s["session_rate_per_s"] - 1) < 0.05


def test_session_prompts_extend_the_previous_turn_in_whole_pages():
    s = spec("sessions")
    plan = generator.plan(s, BIG_SEED, 5000, 300.0)
    by = collections.defaultdict(list)
    for p in plan:
        by[p.session].append(p)
    system = None
    multi = 0
    for turns in by.values():
        turns.sort(key=lambda p: p.turn)
        for p in turns:
            assert len(p.prompt) % s["align_tokens"] == 0
            assert len(p.prompt) <= s["max_prompt_tokens"]
            sp = p.prompt[:s["system_prompt_tokens"]]
            system = sp if system is None else system
            assert np.array_equal(sp, system)          # shared fleet-wide
        for prev, nxt in zip(turns, turns[1:]):
            multi += 1
            n = len(prev.prompt)
            assert np.array_equal(nxt.prompt[:n], prev.prompt)
            # then the previous answer's length of seeded tokens
            assert len(nxt.prompt) >= n + prev.max_new_tokens + 1
            assert nxt.due_s - prev.due_s >= s["think_s"]["min"] - 1e-9
    assert multi > 10


def test_sessions_deadline_and_horizon():
    s = spec("sessions")
    plan = generator.plan(s, 9, 1000, 30.0)
    lim = s["limits"]
    assert plan and all(p.due_s < 30.0 for p in plan)
    assert [p.due_s for p in plan] == sorted(p.due_s for p in plan)
    for p in plan:
        assert p.deadline_ms == lim["ttft_ms"] + p.max_new_tokens * lim["tpot_ms"]
        assert p.prompt.min() >= generator.FIRST_TOKEN and p.prompt.max() < 1000


def test_closed_loop_deterministic_and_within_stated_sizes():
    s = spec("batch")
    a = generator.plan(s, BIG_SEED, 777, 0.0)
    b = generator.plan(s, BIG_SEED, 777, 0.0)
    assert a.clients == s["clients"]
    assert [len(p.prompt) for p in a.requests] == [len(p.prompt) for p in b.requests]
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.requests[:50], b.requests[:50]))
    c = generator.plan(s, 5, 777, 0.0)
    assert [len(p.prompt) for p in a.requests] == \
        [len(p.prompt) for p in c.requests]
    assert not np.array_equal(a.requests[0].prompt, c.requests[0].prompt)
    pl = [len(p.prompt) for p in a.requests]
    ol = [p.max_new_tokens for p in a.requests]
    ps, os_ = s["prompt_tokens"], s["output_tokens"]
    assert ps["min"] <= min(pl) and max(pl) <= ps["max"]
    assert os_["min"] <= min(ol) and max(ol) <= os_["max"]
    assert abs(np.median(pl) / ps["median"] - 1) < 0.1
    assert abs(np.median(ol) / os_["median"] - 1) < 0.1
    assert all(p.deadline_ms == s["deadline_ms"] for p in a.requests)


def test_negative_seed_refused():
    with pytest.raises(ValueError):
        generator.plan(spec("batch"), -1, 100, 0.0)
