"""Percentile, "ten samples beyond" and spread arithmetic."""

import statistics

import pytest

from benchmarks.lib import stats


@pytest.mark.parametrize("values,p,want", [
    ([10.0], 95, 10.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 50, 3.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 100, 5.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 0, 1.0),
    # rank 0.95 * 4 = 3.8 -> 4 + 0.8 * (5 - 4)
    ([5.0, 1.0, 4.0, 2.0, 3.0], 95, 4.8),
    # 200 samples 1..200: rank 0.95 * 199 = 189.05 -> 190.05
    ([float(i) for i in range(1, 201)], 95, 190.05),
])
def test_percentile_interpolates_between_order_statistics(values, p, want):
    assert stats.percentile(values, p) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n,want", [
    (5, None), (19, None), (20, 50.0), (99, 50.0), (100, 90.0),
    (199, 90.0), (200, 95.0), (999, 95.0), (1000, 99.0), (10000, 99.9),
])
def test_highest_percentile_with_ten_samples_beyond(n, want):
    assert stats.highest_supported_percentile(n) == want


def test_samples_beyond_p95_of_200_is_ten():
    assert stats.samples_beyond(200, 95) == pytest.approx(10.0)
    assert stats.samples_beyond(192, 95) == pytest.approx(9.6)


def test_spread_is_interquartile_distance_over_median():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / 102.5)
    assert stats.spread([7.0] * 6) == 0.0
