"""Tail and rate arithmetic over a run's record."""
import pytest

from chipbench.lib import stats
from chipbench.lib.stats import Track


def tr(due, *times):
    return Track(due=due, stream=0, token_times=list(times))


def test_ttft_counts_unfinished_at_the_tail():
    tracks = [tr(0.0, 0.1, 0.2), tr(1.0, 1.5), tr(2.0), tr(5.0, 5.1)]
    # window [0, 3): the third request has no first token by stop=4
    v = stats.ttfts(tracks, 0.0, 3.0, stop=4.0)
    assert sorted(v) == pytest.approx([0.1, 0.5, 2.0])
    assert stats.percentile(v, 90) == pytest.approx(0.5 + 0.8 * 1.5)


def test_gaps_close_in_window():
    tracks = [tr(0.0, 0.5, 1.0, 2.5, 4.0), tr(0.0, 1.2, 1.4)]
    assert sorted(stats.gaps(tracks, 1.0, 3.0)) == pytest.approx([0.2, 0.5, 1.5])
    assert stats.tokens_in(tracks, 1.0, 3.0) == 4


def test_attainment():
    tracks = [tr(0.0, 0.1, 0.15), tr(0.0, 0.1, 0.3), tr(0.0, 0.5), tr(0.0)]
    assert stats.attainment(tracks, 0.0, 1.0, 2.0, ttft_s=0.2, gap_s=0.1) == 0.25


def test_percentile_and_spread():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.quartile_spread([10, 10, 10, 10, 10, 10]) == 0.0
    # statistics.quantiles' exclusive quartiles, not numpy's
    assert stats.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx((5.25 - 1.75) / 3.5)
