"""Exact percentiles and the window rules."""

import pytest

import _tiny  # noqa: F401
from bench import stats


def test_percentiles_are_of_the_samples():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50.5
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    # a change inside one histogram bucket still moves the percentile
    assert stats.percentile([1.00, 1.01, 1.02], 50) == 1.01
    assert stats.percentile([1.00, 1.011, 1.02], 50) == 1.011
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tokens_count_inside_the_window_only():
    times = {1: [-0.5, 0.1, 0.2], 2: [9.9, 10.0, 10.5]}
    assert stats.window_tokens(times, 10.0) == 3


def test_gaps_count_by_their_later_token():
    times = {1: [-0.5, 0.1, 0.3], 2: [9.5, 9.9, 10.2], 3: [4.0]}
    gaps = sorted(stats.window_gaps(times, 10.0))
    assert gaps == pytest.approx([0.2, 0.4, 0.6])


def test_ttft_over_requests_due_in_the_window_with_grace():
    due = {1: 0.0, 2: 5.0, 3: 9.0, 4: 12.0}
    times = {1: [0.25], 2: [], 3: [10.5], 4: [12.1]}
    vals, missing = stats.ttfts(due, times, 10.0, grace_end=20.0)
    assert missing == 1
    # request 2 never answered: it counts with the wait until grace end
    assert sorted(vals) == pytest.approx([0.25, 1.5, 15.0])
