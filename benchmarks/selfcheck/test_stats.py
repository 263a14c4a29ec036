"""Percentiles, gaps between tokens, first-token times: on hand-made
stamps whose answers can be worked out on paper."""

import pytest

from benchmarks.harness import stats


def test_percentile_interpolates_between_closest_ranks():
    values = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(values, 0) == 10.0
    assert stats.percentile(values, 50) == 30.0
    assert stats.percentile(values, 100) == 50.0
    assert stats.percentile(values, 95) == pytest.approx(48.0)
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 95) is None


def test_percentile_agrees_with_numpy():
    import numpy as np

    rng = np.random.default_rng(0)
    values = rng.exponential(1.0, 1000).tolist()
    for q in (5, 50, 95, 99):
        assert stats.percentile(values, q) == pytest.approx(
            float(np.percentile(values, q))
        )


def test_token_gaps_count_tokens_emitted_in_the_window_only():
    stamps = {
        1: [0.5, 1.5, 2.5, 3.5],   # first token before the window
        2: [1.2, 1.2, 2.0],        # prefill + first decode in one step
        3: [4.5, 5.5],             # after the window
    }
    gaps = stats.token_gaps(stamps, (1.0, 3.0))
    # request 1: tokens at 1.5 and 2.5 (gaps 1.0, 1.0; the gap at 1.5
    # reaches back before the window); request 2: 1.2 (gap 0), 2.0 (0.8)
    assert sorted(gaps) == pytest.approx([0.0, 0.8, 1.0, 1.0])
    assert stats.tokens_in_window(stamps, (1.0, 3.0)) == 5


def test_request_without_a_first_token_counts_as_the_window():
    due = {1: 1.0, 2: 2.0, 3: 2.5, 4: 0.5, 5: 2.9}
    stamps = {1: [1.4, 1.6], 2: [], 3: [3.4], 4: [0.9], 5: [2.95]}
    window = (1.0, 3.0)
    times = stats.first_token_times(due, stamps, window, lost=[5])
    # 1 -> 0.4; 2 has no token -> 2.0 (the window's length); 3's token
    # came after the window's end -> 2.0; 4 was due before the window
    # and is not counted; 5 was refused -> 2.0
    assert sorted(times) == pytest.approx([0.4, 2.0, 2.0, 2.0])


def test_iqr_share_is_the_contracts_spread():
    import statistics

    values = [100.0, 101.0, 99.0, 102.0, 98.0, 100.5]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.iqr_share(values) == pytest.approx(
        (q3 - q1) / statistics.median(values)
    )
