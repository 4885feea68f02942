"""The end-to-end metrics' percentile and censoring rules."""
import pytest

import stats


def test_percentile_is_linear_between_order_statistics():
    assert stats.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    assert stats.percentile(list(range(101)), 95) == 95.0
    assert stats.percentile([10.0, 20.0], 95) == pytest.approx(19.5)
    assert stats.percentile([], 95) is None


def test_ttft_counts_from_due_and_censors_at_close():
    due = [0.0, 1.0, 2.0, 9.5, 11.0]
    first = [0.2, 1.5, None, None, 11.1]
    # the third never got a token: it counts 10 - 2; the fourth 10 - 9.5;
    # the fifth was due after the close and is not in the window
    assert stats.ttft_samples(due, first, close=10.0) == pytest.approx(
        [0.2, 0.5, 8.0, 0.5])


def test_ttft_first_token_after_close_is_censored():
    assert stats.ttft_samples([1.0], [12.0], close=10.0) == [9.0]


def test_token_gaps_keep_pairs_inside_the_window():
    times = {1: [1.0, 1.0, 1.3, 1.6], 2: [2.0, 2.5, 10.5], 3: [4.0]}
    assert stats.token_gaps(times, close=10.0) == pytest.approx(
        [0.0, 0.3, 0.3, 0.5])


def test_tokens_per_s_over_the_window():
    times = {1: [0.5, 1.0, 2.0], 2: [3.0, 11.0]}
    assert stats.tokens_per_s(times, 1.0, 3.0) == pytest.approx(3 / 2.0)
