"""The serving program's lifecycle stamps, loop counters, KV page fields
and loop spans, on the benchmark's small paged replica serving concurrent
requests through its ``ServingFleet`` (CPU); and the readings
``bench/loop_readings.py`` takes from them, on hand-built runs."""
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from bench import generator, harness, loop_readings, spans
from bench.tests import sizes

PREFIX = 48                     # three pages of a shared system prompt


@pytest.fixture(scope="module")
def fleet():
    import jax
    fl = harness.build_fleet(sizes.small_qwen(), 2**31 + 5, jax.devices()[:1])
    yield fl
    fl.fleet.stop()


def _requests(first_id, n, vocab):
    from repro.serving.engine import Request
    rng = np.random.default_rng(first_id)
    system = rng.integers(generator.FIRST_TOKEN, vocab, PREFIX)
    out = []
    for i in range(n):
        tail = rng.integers(generator.FIRST_TOKEN, vocab, 8 + 9 * i)
        out.append(Request(first_id + i,
                           np.concatenate([system, tail]).astype(np.int32),
                           int(rng.integers(1, 12)), 1e9))
    return out


def _settled(rep):
    """The replica's counters once its loop has finished its last turn."""
    last = None
    for _ in range(100):
        now = dict(vars(rep.counters))
        if now == last:
            return now
        last = now
        time.sleep(0.05)
    raise AssertionError("the serving loop never settled")


def _serve(fl, reqs):
    with ThreadPoolExecutor(max_workers=len(reqs)) as ex:
        return list(ex.map(fl.fleet.submit, reqs))


@pytest.fixture(scope="module")
def served(fleet):
    """Eight requests at once, with the MP table's page fields sampled all
    the while; the replica's counters before and after."""
    rep = fleet.replicas[0]
    before = _settled(rep)
    samples, done = [], threading.Event()

    def sample():
        while not done.is_set():
            samples.extend(v.state for v in fleet.fleet.table.snapshot().values())
            samples.append(rep.state())
            time.sleep(0.002)

    t = threading.Thread(target=sample)
    t.start()
    reqs = _requests(100, 8, rep.cfg.vocab_size)
    try:
        results = _serve(fleet, reqs)
    finally:
        done.set()
        t.join(timeout=10)
    assert not t.is_alive()
    after = _settled(rep)
    return reqs, results, samples, {k: after[k] - before[k] for k in after}


def test_stamps_split_ttft_in_order(served):
    _, results, _, _ = served
    for r in results:
        assert r.ok
        assert (r.created_ms <= r.enqueued_ms <= r.admitted_ms
                <= r.first_ms <= r.finished_ms)
        assert r.first_ms - r.created_ms == r.ttft_ms
        assert r.predicted_ms > 0.0


def test_counters_count_every_decoded_token(served):
    reqs, results, _, delta = served
    assert delta["decode_tokens"] == sum(len(r.tokens) - 1 for r in results)
    # the decode step emitting token i (1-based, i >= 2) attends over
    # len(prompt) + i - 1 positions
    assert delta["context_tokens"] == sum(
        len(q.prompt) + i - 1 for q, r in zip(reqs, results)
        for i in range(2, len(r.tokens) + 1))
    assert delta["prefill_chunks"] >= len(reqs)
    assert delta["turns"] >= delta["decode_steps"] > 0
    assert delta["loop_ms"] >= delta["prefill_ms"] + delta["decode_ms"] > 0


def test_pages_used_within_reserved_and_freed_after_drain(served, fleet):
    _, _, samples, _ = served
    assert any(s.pages_used > 0 for s in samples)
    assert all(0 <= s.pages_used <= s.pages_reserved for s in samples)
    st = fleet.replicas[0].state()
    assert (st.pages_used, st.pages_reserved) == (0, 0)


def test_loop_spans_nest_under_a_real_profiler(fleet, tmp_path):
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(fleet, _requests(200, 3, fleet.replicas[0].cfg.vocab_size))
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(harness._xplane(tmp_path)))
    sp = spans.read_spans(pd)["replica0"]
    turns = [(s, e) for p, s, e in zip(sp.phase, sp.start, sp.end)
             if p == "turn"]
    decodes = [(s, e, d) for p, s, e, d in
               zip(sp.phase, sp.start, sp.end, sp.depth) if p == "decode"]
    assert turns and decodes
    for s, e, depth in decodes:
        assert depth == 1
        assert any(ts <= s and e <= te for ts, te in turns)
    names = {ev.name.split("#")[0] for plane in pd.planes
             for line in plane.lines for ev in line.events}
    assert {"dds.route", "dds.admission"} <= names


def _rec(due, ok=True, **stamps):
    rec = {"due_ms": due, "ok": ok, "finished_ms": due + 100.0}
    rec.update(stamps)
    return rec


def _run(records, counters=None):
    run = harness.Run({}, {}, "dense", {}, (1000.0, 2000.0), records=records)
    run.counters = counters or {}
    return run


def test_readings_on_a_hand_built_run():
    records = [
        _rec(1000.0, enqueued_ms=1001.0, admitted_ms=1011.0, first_ms=1041.0,
             predicted_ms=50.0),
        _rec(1100.0, enqueued_ms=1102.0, admitted_ms=1132.0, first_ms=1152.0,
             predicted_ms=150.0),
        _rec(1200.0, ok=False, enqueued_ms=1201.0),          # failed
        _rec(2500.0, enqueued_ms=2501.0, admitted_ms=2502.0,
             first_ms=2503.0, predicted_ms=100.0),            # after window
    ]
    loop = {"turns": 0, "loop_ms": 0.0, "prefill_ms": 0.0, "decode_ms": 0.0}
    counters = {"window_start": {"loop": {"replica0": dict(loop)}},
                "window_end": {"loop": {"replica0": dict(
                    loop, turns=10, loop_ms=400.0, prefill_ms=100.0)}}}
    pages = [(900.0, {"replica0": (50, 50)}),
             (1500.0, {"replica0": (3, 10)}),
             (1600.0, {"replica0": (5, 10)})]
    got = loop_readings.readings(_run(records, counters), pages)
    assert got["routing_ms"] == {"p50": 1.0, "p99": 2.0, "p100": 2.0}
    assert got["queue_wait_ms"] == {"p50": 10.0, "p90": 30.0}
    assert got["prefill_ms"] == {"p50": 20.0, "p90": 30.0}
    # observed 100 ms each: |100 - 50| / 100 and |100 - 150| / 100
    assert got["predict_error_pct"] == {"p50": 50.0, "p90": 50.0}
    assert got["prefill_share_pct"] == pytest.approx(25.0)
    assert got["loop"]["replica0"]["turns"] == 10
    assert got["kv_page_use"] == pytest.approx(8 / 20)


def test_readings_without_the_programs_stamps():
    """A program that sets no stamps, counters or page fields gives no
    reading, and none raises."""
    got = loop_readings.readings(_run([_rec(1000.0)]), [])
    assert all(v is None or all(x is None for x in v.values())
               for v in got.values())
