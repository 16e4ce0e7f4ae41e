"""Percentiles and window accounting on synthetic timelines."""

import pytest

from chipbench import stats
from chipbench.stats import Record


def rec(due, first, n, gap, output_len=None, status=200, done=True):
    r = Record(0, "r", 10, output_len if output_len is not None else n, due)
    r.sent = due
    r.token_times = [first + i * gap for i in range(n)]
    r.status, r.done = status, done
    return r


def test_percentile_interpolates_like_numpy():
    xs = [1.0, 2.0, 3.0, 4.0, 10.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 90) == pytest.approx(7.6)
    assert stats.percentile([5.0], 90) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_window_accounting():
    seconds = 10.0
    records = [
        rec(-2.0, -1.5, 10, 0.5),   # ramp: not counted, 7 tokens land in [0, 10)
        rec(1.0, 1.2, 5, 0.1),      # counted, ttft 200 ms, tpot 100 ms
        rec(9.0, 9.4, 5, 0.5),      # counted, finishes after the window
        rec(10.0, 10.1, 3, 0.1),    # due at the close: not counted
        rec(5.0, 5.1, 2, 0.1, output_len=4),          # lost tokens: failed
        rec(6.0, 6.1, 0, 0.1, output_len=4, status=500),  # refused: failed
    ]
    e = stats.end_to_end(records, seconds, chips=2)
    assert e["attempted"] == 4 and e["failed"] == 2
    assert e["ttft_p50_ms"] == pytest.approx(300.0)   # of 200 and 400 ms
    assert e["tpot_p50_ms"] == pytest.approx(300.0)   # of 100 and 500 ms
    # tokens inside [0, 10): 7 (ramp) + 5 + 2 (9.4, 9.9) + 0 + 2 + 0
    assert stats.tokens_in_window(records, seconds) == 16
    assert e["output_tok_s_chip"] == pytest.approx(16 / 10.0 / 2)


def test_a_request_is_ok_only_with_every_token_and_a_closed_stream():
    assert rec(0, 0.1, 4, 0.1).ok
    assert not rec(0, 0.1, 4, 0.1, done=False).ok
    assert not rec(0, 0.1, 3, 0.1, output_len=4).ok
    assert not rec(0, 0.1, 4, 0.1, status=429).ok
    assert rec(0, 0.1, 1, 0.1).tpot is None
