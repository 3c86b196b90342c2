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
