"""Metric arithmetic: whole-tick tokens/s."""

import dataclasses

import pytest

from benchmarks.chip import stats


@dataclasses.dataclass
class T:
    start: float
    positions: int


def test_tokens_per_s_counts_whole_ticks_started_in_the_window():
    ticks = [T(start=-1.0, positions=100),      # warm-up: before
             T(start=0.0, positions=4), T(start=4.0, positions=4),
             T(start=8.0, positions=4),         # ends at 12: counted whole
             T(start=12.0, positions=50)]       # after the end
    assert stats.tokens_per_s(ticks, 0.0, 12.0) == pytest.approx(1.0)


def test_tokens_per_s_is_not_a_median_of_chunks():
    # two slow ticks and one fast: all the work over all the time
    ticks = [T(start=0.0, positions=10), T(start=1.0, positions=10),
             T(start=2.0, positions=100)]
    assert stats.tokens_per_s(ticks, 0.0, 4.0) == pytest.approx(30.0)
