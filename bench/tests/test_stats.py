import pytest

import stats


def test_nearest_rank_percentile():
    xs = list(range(1, 101))  # 1..100
    assert stats.percentile(xs, 50) == 51  # round(0.5 * 99) = 50 -> 51
    assert stats.percentile(xs, 95) == 95  # round(0.95 * 99) = 94 -> 95
    assert stats.percentile(xs, 0) == 1
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([3, 1, 2], 50) == 2  # sorts first


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_quartile_spread():
    # statistics.quantiles (exclusive): Q1 = 2.5, median 5, Q3 = 7.5 on 1..9
    assert stats.quartile_spread(range(1, 10)) == pytest.approx(5 / 5)
