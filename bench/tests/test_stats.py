"""Percentiles and window arithmetic."""
import math

import pytest

from bench import stats


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_misses_count_as_infinite():
    # 20 samples, 2 misses: the 95th percentile is a miss
    samples = [0.1] * 18 + [math.inf] * 2
    assert math.isinf(stats.percentile(samples, 95))
    # 1 miss in 20: the 95th percentile is still a sample that was served
    samples = [0.1] * 19 + [math.inf]
    assert stats.percentile(samples, 95) == pytest.approx(0.1)


def test_straddling_requests_count_only_their_part_inside():
    times = {1: [-2.0, -1.0, 0.5, 1.5],        # started before the window
             2: [9.0, 9.5, 10.5, 11.0],        # ends after it
             3: [3.0]}
    assert stats.tokens_in_window(times, 0.0, 10.0) == 2 + 2 + 1
    tpot = stats.tpot_samples(times, 0.0, 10.0)
    assert sorted(tpot) == pytest.approx([0.5, 1.0])


def test_tpot_leaves_out_requests_that_span_too_little_inside():
    times = {1: [0.5, 1.5, 2.5],          # 2 s inside
             2: [9.8, 9.9, 10.5],         # 0.1 s inside: too short to time
             3: [9.0, 9.5]}               # 0.5 s inside
    tpot = stats.tpot_samples(times, 0.0, 10.0, min_span=0.25)
    assert sorted(tpot) == pytest.approx([0.5, 1.0])


def test_slots_are_timed_in_groups_that_span_enough():
    calls = [(0.0, 0.1), (0.1, 0.2), (0.25, 0.3),   # 0.3 s: 3 calls
             (0.3, 2.3),                            # a stall: one call
             (2.3, 2.4), (2.4, 2.5)]                # 0.2 s: left out
    assert stats.group_times(calls, 0.25) == pytest.approx([0.1, 2.0])


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert stats.union_length(iv) == pytest.approx(3.0)
    assert stats.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert stats.clip(iv, 1.0, 3.5) == [(1.0, 2.0), (3.0, 3.5)]
