"""Percentile and TPOT arithmetic (failures count as misses), and the
peaks table."""
import math

import pytest

from bench import stats
from bench.peaks import peaks


def rec(ok=True, due=0.0, ttft=100.0, finished=1100.0, n=11):
    return {"ok": ok, "due_ms": due, "ttft_ms": ttft, "finished_ms": finished,
            "n_out": n}


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([5.0], 90) == 5.0
    assert math.isnan(stats.percentile([], 90))


def test_tpot_is_mean_gap_after_the_first_token():
    assert stats.tpot_ms(rec()) == pytest.approx(100.0)     # (1100-0-100)/10
    assert stats.tpot_ms(rec(due=50.0, ttft=150.0, finished=1200.0)) == \
        pytest.approx(100.0)
    assert stats.ttft_ms(rec()) == 100.0


def test_failures_count_as_misses():
    ok = [rec(ttft=float(i)) for i in range(1, 10)]
    assert stats.tail(ok, stats.ttft_ms, 90, 9e9) == 9.0
    failed = ok + [rec(ok=False)]
    # 10 records, one failed: p90 is the 9th smallest, still finite
    assert stats.tail(failed, stats.ttft_ms, 90, 9e9) == 9.0
    two = ok + [rec(ok=False), rec(ok=False)]
    # 11 records, two misses: p90 is the 10th smallest, a miss, so the
    # tail reads the run's horizon
    assert stats.tail(two, stats.ttft_ms, 90, 12345.0) == 12345.0
    assert stats.ttft_ms(rec(ok=False)) == math.inf
    assert stats.tpot_ms(rec(ok=False)) == math.inf
    assert stats.tail(two, stats.tpot_ms, 90, 777.0) == 777.0


def test_peaks_known_and_unknown():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks("cpu")
