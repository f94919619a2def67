"""Percentiles, spread and window rates on cases worked by hand."""

import pytest

from benchmark import stats


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),                 # midway between 2 and 3
    (list(range(1, 101)), 95, 95.05),        # rank 0.95 * 99 = 94.05
    ([10.0], 95, 10.0),
    ([5, 1, 3], 100, 5.0),
])
def test_percentile_interpolates_between_order_statistics(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 95) is None and stats.median([]) is None


def test_rate_in_window_counts_deliveries_inside_it_only():
    stamps = [0.5, 1.0, 1.5, 2.0, 2.5]
    counts = [10, 20, 30, 40, 50]
    # [1.0, 2.5): 20 + 30 + 40 = 90 tokens over 1.5 s
    assert stats.rate_in_window(stamps, counts, 1.0, 2.5) == pytest.approx(60.0)


def test_spread_is_interquartile_distance_over_median():
    # quartiles of 1..5 are 2, 3, 4
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(2 / 3)
