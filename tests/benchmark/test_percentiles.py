"""The benchmark's percentile arithmetic: a percentile is reported only
with ten samples beyond it, and the count is always printed."""

import pytest

from benchmark import stats


@pytest.mark.parametrize(
    "n, highest",
    [(9, None), (19, None), (20, "p50"), (99, "p50"), (100, "p90"),
     (999, "p95"), (1000, "p99"), (10_000, "p99.9")],
)
def test_highest_percentile_has_ten_samples_beyond(n, highest):
    # 99 samples leave 9 beyond the nearest-rank p90, so p90 needs 100.
    out = stats.summary(list(range(n)))
    assert out["count"] == n
    assert out["highest"] == highest
    if highest not in (None, "p50"):
        assert stats.samples_beyond(n, float(highest[1:])) >= stats.BEYOND
        assert highest in out


@pytest.mark.parametrize(
    "q, want", [(50.0, 50.0), (90.0, 90.0), (99.0, 99.0), (100.0, 100.0)]
)
def test_percentile_is_a_measured_sample(q, want):
    assert stats.percentile([float(v) for v in range(100, 0, -1)], q) == want


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50.0)


@pytest.mark.parametrize(
    "values, mean, iqm",
    [
        # ranks 25..75 of 100: the long run in 13 is in the mean alone
        ([float(v) for v in range(1, 100)] + [1000.0], 59.5, 50.0),
        ([3.0, 1.0, 2.0, 4.0], 2.5, 2.0),  # ranks 1..3
        ([7.0], 7.0, 7.0),
    ],
)
def test_summary_prints_mean_and_interquartile_mean_beside_the_median(
    values, mean, iqm
):
    out = stats.summary(values)
    assert out["mean"] == pytest.approx(mean)
    assert out["iqm"] == pytest.approx(iqm) == stats.interquartile_mean(values)
    assert out["p50"] == stats.percentile(values, 50.0)


def test_interquartile_mean_of_nothing_is_an_error():
    assert stats.summary([]) == {"count": 0}
    with pytest.raises(ValueError):
        stats.interquartile_mean([])
