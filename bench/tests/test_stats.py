"""Window accounting and percentiles with censored requests."""
import math

import pytest

import stats


def test_percentile_is_linear_between_order_statistics():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert math.isclose(stats.percentile([0, 10], 95), 9.5)
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_ttft_counts_every_request_due_in_the_window_and_censors_at_close():
    due = {0: 0.5, 1: 1.0, 2: 2.0, 3: 3.0, 4: 3.5}
    tokens = {0: [0.7], 1: [1.25, 1.5], 2: [4.5], 4: []}
    # window [1, 4): request 0 was due before it; 2's first token came
    # after the close, 3 and 4 have none: all three count at 4 - due
    got = sorted(stats.ttft_samples(due, tokens, 1.0, 4.0))
    assert got == sorted([0.25, 2.0, 1.0, 0.5])


def test_itl_keeps_gaps_that_end_inside_the_window():
    tokens = {0: [0.5, 1.5, 2.0, 4.5], 1: [3.0, 3.25]}
    assert sorted(stats.itl_samples(tokens, 1.0, 4.0)) == [0.25, 0.5, 1.0]


def test_end_to_end_window_accounting():
    due = {0: 1.0, 1: 2.0}
    tokens = {0: [1.1, 1.2, 1.3], 1: [2.5, 3.5, 4.5]}
    e = stats.end_to_end(due, tokens, 1.0, 4.0)
    assert e["tokens_per_s"] == 5 / 3.0
    assert e["n_ttft"] == 2 and e["n_itl"] == 3
    assert math.isclose(e["ttft_p95_ms"], 1e3 * stats.percentile([0.1, 0.5], 95))
    assert math.isclose(e["itl_p95_ms"], 1e3 * stats.percentile([0.1, 0.1, 1.0], 95))
