"""The benchmark's percentile and rate arithmetic: tails over all samples,
censored at the window's end, and rates over the whole window."""
from __future__ import annotations

import statistics

import numpy as np
import pytest

import _tiny  # noqa: F401
from chipbench import stats


def test_percentile_is_over_all_samples_not_a_median_of_chunks():
    # two chunks: one calm, one with a long tail
    calm, tail = [1.0] * 95, [1.0] * 80 + [100.0] * 15
    over_all = stats.percentile(calm + tail, 95)
    per_chunk = statistics.median([stats.percentile(calm, 95),
                                   stats.percentile(tail, 95)])
    assert over_all == pytest.approx(np.percentile(calm + tail, 95))
    assert over_all > per_chunk
    assert stats.percentile([], 95) is None


def test_ttft_censors_requests_without_a_first_token():
    t0, t1 = 10.0, 20.0
    reqs = [(11.0, [11.5, 12.0]),  # served: 0.5
            (12.0, []),  # never served: waits until the window closes
            (19.0, [25.0]),  # first token after the close: censored
            (9.0, [10.5]),  # due before the window: not counted
            (20.0, [20.1])]  # due at the close: not counted
    assert stats.ttft_samples(reqs, t0, t1) == pytest.approx([0.5, 8.0, 1.0])


def test_itl_samples_count_gaps_ending_in_the_window_and_censor_stalls():
    t0, t1 = 10.0, 20.0
    # gap ending at 10.5 started before the window: counted; 21 is after
    assert stats.itl_samples([9.0, 10.5, 11.0, 21.0], False, t0, t1) == \
        pytest.approx([1.5, 0.5, 9.0])
    # finished inside the window: no censored gap
    assert stats.itl_samples([11.0, 12.0], True, t0, t1) == \
        pytest.approx([1.0])
    # stalled since before the window: the whole wait enters
    assert stats.itl_samples([5.0], False, t0, t1) == pytest.approx([15.0])


def test_waits_and_tokens_in_window():
    assert stats.wait_samples([(11.0, 11.2), (12.0, 0.0), (13.0, 30.0)],
                              10.0, 20.0) == pytest.approx([0.2, 8.0, 7.0])
    assert stats.tokens_in([9.0, 10.0, 10.5, 20.0, 20.5], 10.0, 20.0) == 2


def test_output_rate_counts_unfinished_requests():
    # rate = every token in (t0, t1] over the window, finished or not
    tt = [[10.1, 10.2], [15.0, 16.0, 30.0]]
    n = sum(stats.tokens_in(t, 10.0, 20.0) for t in tt)
    assert n / 10.0 == pytest.approx(0.4)

