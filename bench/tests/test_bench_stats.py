"""End-to-end metric arithmetic: percentiles over every request, censored
TTFT, TPOT from in-window tokens, tokens per second over the window."""
import math
import types

import numpy as np
import pytest

from bench import stats


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_is_numpys_linear(q):
    v = list(np.random.default_rng(0).exponential(size=37))
    assert math.isclose(stats.percentile(v, q), float(np.percentile(v, q)))
    assert math.isnan(stats.percentile([], q))


def req(i, due):
    return types.SimpleNamespace(req_id=i, arrival=due)


def test_ttft_counts_every_due_request_and_censors_the_unserved():
    reqs = [req(0, 0.5), req(1, 1.0), req(2, 9.0), req(3, 10.5), req(4, 12.0)]
    stamps = {0: [0.6], 1: [1.25, 1.5], 3: [10.9]}
    # window [1, 11): requests 1, 2 and 3 are due in it; 2 never started
    got = stats.ttft_ms(reqs, stamps, 1.0, 11.0)
    assert got == pytest.approx([250.0, 2000.0, 400.0])


def test_ttft_first_token_after_close_is_censored():
    got = stats.ttft_ms([req(0, 2.0)], {0: [5.0]}, 1.0, 3.0)
    assert got == pytest.approx([1000.0])


def test_tpot_uses_in_window_tokens_only():
    stamps = {0: [0.5, 1.0, 1.1, 1.3, 1.6],   # in window: 1.0 .. 1.6 -> 4
              1: [1.2],                      # one token: no gap
              2: [0.1, 0.2, 5.0]}            # none after 1.0 inside
    got = stats.tpot_ms(stamps, 1.0, 2.0)
    assert got == pytest.approx([200.0])


def test_tokens_in_window():
    stamps = {0: [0.5, 1.0, 1.5, 2.0, 2.5], 1: [1.9, 1.9]}
    assert stats.tokens_in_window(stamps, 1.0, 2.0) == 5
