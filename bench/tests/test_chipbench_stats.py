"""Percentile and window arithmetic of the benchmark."""
import numpy as np
import pytest

from bench.lib import stats


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 150, 2001])
def test_percentile_matches_numpy_linear(q, n):
    xs = np.random.default_rng(n).exponential(size=n)
    assert stats.percentile(list(xs), q) == pytest.approx(
        np.percentile(xs, q), rel=1e-12)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_one_sample_per_gap_inside_the_window():
    times = {1: [0.0, 1.0, 3.0, 6.0], 2: [2.0, 2.5], 3: [7.0]}
    # request 1: gaps 1, 2 inside [0, 4]; the gap ending at 6 is out
    assert sorted(stats.token_gaps(times, (0.0, 4.0))) == [0.5, 1.0, 2.0]
    # a gap that starts before the window is left out
    assert sorted(stats.token_gaps(times, (0.5, 10.0))) == [0.5, 2.0, 3.0]
    assert stats.token_gaps({4: [1.0]}, (0.0, 9.0)) == []

