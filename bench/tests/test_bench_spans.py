"""The serving loop's host spans in a small synthetic ``.xplane.pb``: idle
gaps labelled with the deepest span that covers them, gaps no span covers
left unattributed, each device matched to its own replica; and the
``prefill_device_share`` reader on hand-built traces."""
import pytest

from bench import harness, spans, trace_reduce
from bench.tests.test_bench_trace import _line, _plane

META = {1: "fusion.1", 2: "jit__step_paged_impl(1)", 3: "jit_prefill_chunk(2)"}
HOST = {10: trace_reduce.WINDOW_SPAN, 11: "dds.replica0.turn",
        12: "dds.replica0.decode", 13: "dds.replica0.decode.sync",
        14: "dds.replica0.admit", 15: "dds.replica1.prefill",
        16: "dds.replica1.prefill.sync", 17: "dds.route#request_id=7#"}


def space():
    """Window 1000..2000 ns.  Device 0 runs [1000,1100), [1300,1400),
    [1800,1960): gaps [1100,1300), [1400,1800), [1960,2000).  Device 1 runs
    [1000,1500): gap [1500,2000).  Device 0's decode step program runs
    [1300,1400).  replica0's thread: turn [1050,1950) >
    decode [1080,1350) > decode.sync [1100,1290), and admit [1450,1500).
    replica1's thread: prefill [1480,1990) > prefill.sync [1500,1980).  A
    client thread: route [1400,1800)."""
    base = 500
    d0 = _plane(1, "/device:TPU:0", [
        _line(1, "XLA Ops", base, [(1, 1000, 100), (1, 1300, 100),
                                   (1, 1800, 160)]),
        _line(2, "XLA Modules", base, [(2, 1300, 100)])], META)
    d1 = _plane(2, "/device:TPU:1", [
        _line(1, "XLA Ops", base, [(1, 1000, 500)]),
        _line(2, "XLA Modules", base, [(3, 1000, 500)])], META)
    host = _plane(3, "/host:CPU", [
        _line(1, "python3", base, [(10, 1000, 1000)]),
        _line(2, "python3", base, [(11, 1050, 900), (12, 1080, 270),
                                   (13, 1100, 190), (14, 1450, 50)]),
        _line(3, "python3", base, [(15, 1480, 510), (16, 1500, 480)]),
        _line(4, "python3", base, [(17, 1400, 400)]),
    ], HOST)
    return "\n".join([d0, d1, host])


@pytest.fixture
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(space()))


def _labelled(pd, replica_of):
    devices, window = trace_reduce.from_profile(pd)
    gaps = spans.device_gaps(devices, window)
    return gaps, spans.gap_labels(gaps, spans.read_spans(pd), replica_of)


def test_spans_nest_per_replica_and_skip_the_router(profile):
    got = spans.read_spans(profile)
    assert set(got) == {"replica0", "replica1"}
    r0 = got["replica0"]
    assert list(zip(r0.phase, r0.depth)) == [
        ("turn", 0), ("decode", 1), ("decode.sync", 2), ("admit", 1)]
    assert got["replica1"].depth == [0, 1]


def test_gaps_match_the_reduction(profile):
    devices, window = trace_reduce.from_profile(profile)
    red = trace_reduce.reduce(devices, window)
    gaps = spans.device_gaps(devices, window)
    assert [e - s for _, s, e in gaps] == [
        pytest.approx(g[1] * 1e9) for g in red.gaps]
    assert [(p, s, e) for p, s, e in gaps] == [
        ("/device:TPU:1", 1500, 2000), ("/device:TPU:0", 1400, 1800),
        ("/device:TPU:0", 1100, 1300), ("/device:TPU:0", 1960, 2000)]


def test_gap_labels_take_the_deepest_covering_span(profile):
    gaps, labels = _labelled(profile, {0: "replica0", 1: "replica1"})
    # [1500,2000) on device 1: replica1's prefill.sync covers 480 of 500;
    # [1400,1800): only replica0's turn covers most of it (admit 50 ns);
    # [1100,1300): the leaf decode.sync under decode under turn;
    # [1960,2000): after replica0's turn ended
    assert labels == ["replica1:prefill.sync", "replica0:turn",
                      "replica0:decode.sync", "unattributed"]
    idle = spans.idle_by_label(gaps, labels, 2)
    red = trace_reduce.reduce(*trace_reduce.from_profile(profile))
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)
    assert idle["unattributed"] == pytest.approx(40e-9 / 2)


def test_each_device_reads_its_own_replica(profile):
    _, swapped = _labelled(profile, {0: "replica1", 1: "replica0"})
    # [1960,2000) on device 0 is now replica1's: prefill covers 30 of
    # its 40 ns, prefill.sync only half
    assert swapped == ["replica0:turn", "replica1:prefill.sync",
                       "unattributed", "replica1:prefill"]
    _, unknown = _labelled(profile, {})
    assert unknown == ["unattributed"] * 4


def test_phase_seconds_split_self_time(profile):
    got = spans.phase_seconds(spans.read_spans(profile)["replica0"],
                              1000, 2000)
    assert got["turn"]["s"] == pytest.approx(900e-9)
    assert got["turn"]["self_s"] == pytest.approx((900 - 270 - 50) * 1e-9)
    assert got["decode"]["self_s"] == pytest.approx(80e-9)
    assert got["decode.sync"] == {"s": pytest.approx(190e-9),
                                  "self_s": pytest.approx(190e-9), "count": 1}


def test_sync_edges_place_the_step_inside_its_sync(profile):
    devices, _ = trace_reduce.from_profile(profile)
    sp = spans.read_spans(profile)["replica0"]
    steps = [ev for ev in devices["/device:TPU:0"].modules
             if spans.STEP_PROGRAM.search(ev[0])]
    # decode.sync [1100,1290) overlaps the step [1300,1400) not at all;
    # shifted 200 ns later it holds the step from 1300 to 1490
    assert spans.sync_edges(sp, steps) == {}
    sp.start = [s + 200 for s in sp.start]
    sp.end = [e + 200 for e in sp.end]
    assert spans.sync_edges(sp, steps) == {
        "launch_ms": pytest.approx(0.0), "tail_ms": pytest.approx(90e-6),
        "tail_min_ms": pytest.approx(90e-6), "n": 1}


def _run(module_s, busy_s=0.5):
    red = trace_reduce.Reduced(1.0, busy_s, 1, {}, module_s,
                               {k: 1.0 for k in module_s}, [])
    run = harness.Run({}, {}, "dense", {}, (0.0, 1.0))
    run.trace = red
    return run


@pytest.mark.parametrize("module_s,want", [
    ({"jit__step_paged_impl(1)": 0.3, "jit_prefill_chunk(2)": 0.1,
      "jit_restore(3)": 0.03, "jit_commit(4)": 0.02,
      "jit_argmax(5)": 0.01}, 30.0),
    ({"jit__step_paged_impl(1)": 0.4}, 0.0),
    ({"jit__step_paged_impl(1)": 0.3, "jit__lambda(2)": 0.1}, None),
], ids=["named", "no_prefill", "anonymous"])
def test_prefill_device_share_reader(module_s, want):
    mod = harness.load_module(harness.BENCH / "metrics"
                              / "prefill_device_share.py", "bench_metric_pds")
    got = mod.read("prefill_device_share.tpot", _run(module_s))
    assert got == (pytest.approx(want) if want is not None else None)
    untraced = harness.Run({}, {}, "dense", {}, (0.0, 1.0))
    assert mod.read("prefill_device_share.tpot", untraced) is None


def test_summary_of_a_trace(profile):
    got = spans.summarize(profile, {0: "replica0", 1: "replica1"})
    assert got["idle_by_label"]["replica1:prefill.sync"] == pytest.approx(
        500e-9 / 2)
    assert got["idle_gaps"][0] == ["replica1:prefill.sync",
                                   pytest.approx(500e-9)]
    assert got["programs"]["jit_prefill_chunk(2)"]["calls"] == pytest.approx(0.5)
    assert set(got["phases"]) == {"replica0", "replica1"}
    assert got["sync_edges"] == {"replica0": {}, "replica1": {}}
