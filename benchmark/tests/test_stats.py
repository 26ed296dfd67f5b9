import statistics

import pytest

from benchmark import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 0) == 10
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 90) == pytest.approx(46.0)   # rank 3.6
    assert stats.percentile(xs, 100) == 50
    assert stats.percentile([7], 95) == 7
    assert stats.median([3, 1, 2, 4]) == pytest.approx(2.5)


def test_percentile_of_nothing_is_an_error_not_zero():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_iqr_share_is_the_contracts_spread():
    xs = [100, 101, 102, 103, 104, 110]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.iqr_share(xs) == pytest.approx((q3 - q1) / 102.5)


def test_union_length_counts_overlaps_once():
    assert stats.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == 4
    assert stats.union_length([]) == 0
