"""The generator's arithmetic: the percentile rule, the mid-window rate
and lateness.  Run by hand: ``python -m pytest benchmark/tests -q``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import stats  # noqa: E402


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))          # 1..100
    assert stats.percentile(values, 50) == 50
    assert stats.percentile(values, 99) == 99
    assert stats.percentile(values, 100) == 100
    assert stats.percentile([7.0], 99) == 7.0
    assert stats.percentile([], 50) is None
    # order does not matter, and no interpolation happens
    assert stats.percentile([30, 10, 20], 50) == 20
    assert stats.percentile([10, 20], 50) == 10


def test_a_tail_needs_ten_samples_beyond_it():
    assert stats.samples_beyond(1000, 99) == 10   # just enough for p99
    assert stats.samples_beyond(999, 99) == 9     # not enough
    assert stats.samples_beyond(1200, 99) == 12
    assert stats.samples_beyond(100, 50) == 50
    assert stats.samples_beyond(0, 99) == 0


def test_median():
    assert stats.median([3, 1, 2]) == 2
    assert stats.median([4, 1, 3, 2]) == 2.5
    assert stats.median([]) is None


def test_mid_window_rate_counts_the_middle_only():
    # 10 s window: the middle is [1.5, 9.0), 7.5 s long
    done = [0.1, 1.4, 1.5, 2.0, 5.0, 8.99, 9.0, 9.9, 12.0]
    assert stats.mid_window_rate(done, 10.0) == 4 / 7.5
    # a steady 100/s for 10 s reads as 100/s
    steady = [i / 100.0 for i in range(1000)]
    assert abs(stats.mid_window_rate(steady, 10.0) - 100.0) < 0.2
    assert stats.mid_window_rate([], 0.0) is None


def test_lateness_is_from_the_moment_due():
    due = [0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0]
    sent = [0.0, 1.001, 2.0, 3.002, 4.1, 5.1, 6.3, 7.3]
    late = stats.lateness(due, sent)
    assert late["n"] == 8
    assert abs(late["mean_ms"] - (1 + 2 + 100 + 100 + 300 + 300) / 8) < 1e-6
    # third quarter (indices 4, 5) against the last (6, 7): 100 -> 300 ms
    assert abs(late["drift_ms"] - 200.0) < 1e-6
    # a request that left early is not negative lateness
    assert stats.lateness([1.0], [0.5])["mean_ms"] == 0.0
    assert stats.lateness([], [])["mean_ms"] is None
