"""Operation and byte counts against hand counts at small shapes, and the
readers that turn them into shares."""
import numpy as np
import pytest

from bench import counts, harness, trace_reduce, window

DENSE = {"d_model": 8, "d_ff": 16, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 2, "num_layers": 3, "vocab_size": 10}
SSM = {"d_model": 4, "num_layers": 2, "vocab_size": 10, "ssm_state": 3,
       "ssm_expand": 2, "ssm_head_dim": 2, "conv_width": 4}


def test_dense_token_flops_hand_count():
    # per layer weights: q 8*4*2=64, k 8*2*2=32, v 32, o 4*2*8=64,
    # mlp 3*8*16=384 -> 576; attention over ctx=5: 4*5*4*2 = 160
    assert counts.dense_layer_weights(DENSE) == 576
    assert counts.dense_token_flops(DENSE, 5, logits=False) == 3 * (2 * 576 + 160)
    assert counts.dense_token_flops(DENSE, 5, logits=True) == \
        3 * (2 * 576 + 160) + 2 * 8 * 10


def test_paged_attention_counts_hand_count():
    c = counts.paged_attention_counts(DENSE, [5, 17])
    # flops: layers * 4 * ctx * hq * hd; bytes: layers * (k+v of live ctx
    # at hkv*hd bf16, plus q and out)
    assert c["flops"] == 3 * 4 * (5 + 17) * 4 * 2
    assert c["bytes"] == 3 * (2 * (5 + 17) * 2 * 2 * 2 + 2 * (2 * 4 * 2 * 2))


def test_reading_more_pages_than_are_live_does_not_raise_the_count():
    """A lane of 5 live tokens reads one 16-token page, a lane of 17 reads
    two; a kernel that walked the whole table would read 8 pages each.
    The count depends on the live context only."""
    live = counts.paged_attention_counts(DENSE, [5, 17])
    page, table_width = 16, 8
    pages_read = [-(-5 // page) * page, -(-17 // page) * page]
    whole_table = [page * table_width] * 2
    assert counts.paged_attention_counts(DENSE, pages_read)["bytes"] > live["bytes"]
    assert counts.paged_attention_counts(DENSE, whole_table)["bytes"] > live["bytes"]
    # what the reader feeds it: the contexts the served tokens attended over
    rec = {"ok": True, "n_out": 3, "due_ms": 0.0, "ttft_ms": 10.0,
           "finished_ms": 30.0, "prompt": np.zeros(4, np.int32)}
    ctx = window.decode_contexts([rec], 0.0, 100.0)
    assert list(ctx) == [5, 6]                  # tokens 2 and 3
    assert list(window.decode_contexts([rec], 15.0, 25.0)) == [5]


def test_ssd_scan_counts_hand_count():
    # d_inner 8, heads 4, state 3, head dim 2
    assert counts.ssd_scan_token_flops(SSM) == 4 * (5 * 3 * 2 + 3 * 2)
    c = counts.ssd_scan_counts(SSM, tokens=7)
    per_tok = 4 * (2 * 2 * 2 + 4) + 2 * 3 * 2
    assert c["bytes"] == 2 * 7 * per_tok
    assert c["flops"] == 2 * 7 * 4 * (5 * 3 * 2 + 3 * 2)


def test_ssm_token_flops_hand_count():
    # in_proj width 2*8 + 2*3 + 4 = 26; conv dim 8 + 6 = 14
    per_layer = (2 * 4 * 26 + 2 * 4 * 14 + 4 * (5 * 3 * 2 + 3 * 2)
                 + 6 * 8 + 2 * 8 * 4)
    assert counts.ssm_token_flops(SSM, logits=False) == 2 * per_layer
    assert counts.ssm_token_flops(SSM, logits=True) == 2 * per_layer + 2 * 4 * 10


def _run(trace, records, counters):
    run = harness.Run({"replica": {"prefix_cache": True}}, DENSE, "dense",
                      {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
                      (0.0, 1000.0), records=records, counters=counters)
    run.trace = trace
    run.trace_ms = (0.0, 1000.0)
    return run


def _records():
    return [{"ok": True, "n_out": 11, "due_ms": 0.0, "ttft_ms": 100.0,
             "finished_ms": 600.0, "prompt": np.zeros(20, np.int32)}]


def test_roofline_and_mfu_readers_stay_under_100():
    ops = {"paged_decode_attention": 0.01, "fusion": 0.2}
    red = trace_reduce.Reduced(1.0, 0.5, 1, ops, {"jit__step_paged_impl": 0.3},
                               {"jit__step_paged_impl": 10.0}, [])
    counters = {"trace_start": {"prefilled": {"r": 0}, "placed": {}},
                "trace_end": {"prefilled": {"r": 64}, "placed": {}}}
    run = _run(red, _records(), counters)
    roof = harness.per_layer(["paged_decode_attention_roofline.tpot"], run)
    ctxs = list(range(21, 31))                  # tokens 2..11 of a 20-token prompt
    c = counts.paged_attention_counts(DENSE, ctxs)
    want = 100 * max(c["flops"] / 1e12, c["bytes"] / 1e9) / 0.01
    assert roof["paged_decode_attention_roofline.tpot"] == pytest.approx(want)
    assert 0 < want < 100
    mfu = harness.per_layer(["mfu.tpot"], run)["mfu.tpot"]
    flops = sum(counts.dense_token_flops(DENSE, x, True) for x in ctxs) + \
        64 * counts.dense_token_flops(DENSE, 0, False)
    assert mfu == pytest.approx(100 * flops / (0.5 * 1e12))
    step = harness.per_layer(["decode_step_ms.tpot"], run)
    assert step["decode_step_ms.tpot"] == pytest.approx(30.0)
    assert harness.per_layer(["idle_share.tpot"], run)["idle_share.tpot"] == \
        pytest.approx(50.0)


@pytest.mark.parametrize("chips", [1, 4])
def test_device_time_readers_count_every_chip(chips):
    """The trace averages device time over the chips; the work of the
    tokens counted is the whole fleet's.  The readers that divide one by
    the other count every chip's seconds: on one chip they read what they
    always did, on four a quarter of it.  The rest are ratios of two
    averages or means per call, the same on any number of chips."""
    ops = {"paged_decode_attention": 0.01, "fusion": 0.2, "ssd_scan": 0.02}
    red = trace_reduce.Reduced(1.0, 0.5, chips, ops,
                               {"jit__step_paged_impl": 0.3,
                                "jit_prefill_chunk": 0.05},
                               {"jit__step_paged_impl": 10.0,
                                "jit_prefill_chunk": 2.0}, [])
    counters = {"trace_start": {"prefilled": {"r": 0}, "placed": {}},
                "trace_end": {"prefilled": {"r": 64}, "placed": {}}}
    run = _run(red, _records(), counters)
    out = harness.per_layer(["paged_decode_attention_roofline.tpot",
                             "mfu.tpot", "decode_step_ms.tpot",
                             "idle_share.tpot", "prefill_device_share.tpot"],
                            run)
    ctxs = list(range(21, 31))
    c = counts.paged_attention_counts(DENSE, ctxs)
    flops = sum(counts.dense_token_flops(DENSE, x, True) for x in ctxs) + \
        64 * counts.dense_token_flops(DENSE, 0, False)
    # one chip's readings, as these readers took them before they counted
    # chips
    roof = 100.0 * max(c["flops"] / 1e12, c["bytes"] / 1e9) / 0.01
    mfu = 100.0 * flops / (0.5 * 1e12)
    if chips == 1:
        assert out["paged_decode_attention_roofline.tpot"] == roof
        assert out["mfu.tpot"] == mfu
    else:
        assert out["paged_decode_attention_roofline.tpot"] == \
            pytest.approx(roof / chips)
        assert out["mfu.tpot"] == pytest.approx(mfu / chips)
    assert out["decode_step_ms.tpot"] == pytest.approx(30.0)
    assert out["idle_share.tpot"] == pytest.approx(50.0)
    assert out["prefill_device_share.tpot"] == pytest.approx(10.0)

    ssm = _run(red, [], counters)
    ssm.family, ssm.dims = "ssm", SSM
    sc = counts.ssd_scan_counts(SSM, 64)
    scan = 100.0 * max(sc["flops"] / 1e12, sc["bytes"] / 1e9) / 0.02
    got = harness.per_layer(["ssd_scan_roofline.tput"], ssm)
    assert got["ssd_scan_roofline.tput"] == pytest.approx(scan / chips)


def test_readers_with_nothing_to_read_return_nothing():
    red = trace_reduce.Reduced(1.0, 0.5, 1, {"fusion": 0.2}, {}, {}, [])
    run = _run(red, [], {})
    out = harness.per_layer(["paged_decode_attention_roofline.tpot",
                             "ssd_scan_roofline.tput", "decode_step_ms.tpot",
                             "route_max_share", "prefix_hit_share"], run)
    assert out == {}


def test_counter_readers():
    counters = {"window_start": {"prefilled": {"a": 10, "b": 0},
                                 "placed": {"a": 1, "b": 0}},
                "window_end": {"prefilled": {"a": 30, "b": 20},
                               "placed": {"a": 7, "b": 3}}}
    recs = [{"ok": True, "due_ms": 0.0, "ttft_ms": 10.0,
             "prompt": np.zeros(100, np.int32)}]
    run = _run(None, recs, counters)
    run.heartbeats = [(5.0, {"a": (2, 4), "b": (0, 2)}),
                      (15.0, {"a": (4, 6), "b": (2, 4)}),
                      (2000.0, {"a": (99, 99)})]
    out = harness.per_layer(["route_max_share", "prefix_hit_share",
                             "queue_depth_mean.ttft", "occupancy_mean.tput"], run)
    assert out["route_max_share"] == pytest.approx(6 * 2 / 9)
    assert out["prefix_hit_share"] == pytest.approx(1 - 40 / 100)
    assert out["queue_depth_mean.ttft"] == pytest.approx(2.0)
    assert out["occupancy_mean.tput"] == pytest.approx(4.0)
