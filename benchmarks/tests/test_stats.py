import numpy as np
import pytest

from benchmarks.lib.stats import (highest_supported_percentile, median,
                                  percentile, spread)


def test_percentile_matches_numpy_on_odd_and_even_samples():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100, 101):
        xs = rng.random(n).tolist()
        for q in (0, 25, 50, 90, 99, 100):
            assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert median([3, 1, 2]) == 2


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        percentile([], 50)


@pytest.mark.parametrize("n, want", [
    (5, None),        # not even the median has ten samples beyond it
    (20, 50),         # 10 beyond the median, 2 beyond the 90th
    (99, 50),         # 9.9 beyond the 90th
    (100, 90),        # 10 beyond the 90th, 5 beyond the 95th
    (200, 95),
    (1000, 99),
    (10000, 99.9),
])
def test_ten_samples_beyond_rule(n, want):
    assert highest_supported_percentile(n) == want


def test_spread_is_interquartile_over_median():
    assert spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(2.0 / 3.0)
