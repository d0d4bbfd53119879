"""The reduction from stamps to end-to-end numbers."""
import numpy as np

from bench.serve import ReqRecord
from bench.stats import itl_samples, percentile, tokens_in, ttft_samples


def rec(due, times):
    return ReqRecord(rid=str(due), due=due, token_times=list(times))


def test_percentile_is_numpys_linear():
    xs = list(range(1, 11))
    assert percentile(xs, 90) == np.percentile(xs, 90) == 9.1
    assert percentile([], 90) is None


def test_ttft_counts_every_due_request_and_censors_at_the_close():
    recs = [rec(0.5, [0.9, 1.0]),      # due before the window: left out
            rec(1.0, [1.25]),          # 0.25
            rec(2.0, [4.0]),           # first token after the close: censored
            rec(3.0, []),              # never served: censored
            rec(10.0, [10.1])]         # due after the window: left out
    got = ttft_samples(recs, 1.0, 5.0, t_close=3.5)
    assert got == [0.25, 1.5, 0.5]


def test_itl_takes_gaps_whose_later_token_is_in_the_window():
    recs = [rec(0.0, [0.5, 1.5, 2.0, 6.0]), rec(1.0, [1.1])]
    assert itl_samples(recs, 1.0, 5.0) == [1.0, 0.5]


def test_tokens_over_the_window():
    recs = [rec(0.0, [0.5, 1.5, 2.0, 6.0]), rec(1.0, [1.1, 4.99, 5.0])]
    assert tokens_in(recs, 1.0, 5.0) == 4
