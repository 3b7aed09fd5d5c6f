"""The percentile rule and the spread, on known samples."""

import pytest

from harness import stats


def test_nearest_rank_percentiles():
    xs = list(range(1, 101))  # 1..100
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize(
    "n, tail",
    [(10, 50.0), (39, 50.0), (40, 75.0), (100, 90.0), (199, 90.0), (200, 95.0), (1000, 99.0), (10000, 99.9)],
)
def test_highest_tail_with_ten_samples_beyond_it(n, tail):
    assert stats.highest_supported_tail(n) == tail
    if tail != 50.0:
        assert stats.samples_beyond(n, tail) >= 10


def test_latency_summary_is_in_ms_and_counts_what_lies_beyond():
    s = stats.latency_summary([i / 1000.0 for i in range(1, 401)])
    assert s["n"] == 400 and s["p50_ms"] == pytest.approx(200.0) and s["p95_ms"] == pytest.approx(380.0)
    assert s["beyond_p95"] == 20 and s["highest_tail"] == 95.0


def test_spread_is_the_interquartile_distance_over_the_median():
    import statistics

    xs = [10.0, 10.2, 9.9, 10.4, 10.1, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / statistics.median(xs))


def test_union_counts_overlaps_once():
    assert stats.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == pytest.approx(4.0)
    assert stats.union_seconds([]) == 0.0
