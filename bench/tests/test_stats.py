"""Metric arithmetic: TTFT from the due time, gaps between tokens, the
window's rate, failed requests."""

import pytest

import stats
from stats import ReqLog


def _logs():
    a = ReqLog(0, sent=1.0, in_window=True, admitted=1.5, token_times=[2.0, 2.1, 2.3])
    b = ReqLog(1, sent=0.5, in_window=False, token_times=[0.9, 1.2, 1.4])
    c = ReqLog(2, sent=2.5, in_window=True)                      # never served
    d = ReqLog(3, sent=2.6, in_window=True, rejected=True)
    w = ReqLog(4, sent=0.0, in_window=False, warm=True, token_times=[0.1])
    e = ReqLog(5, sent=2.7, in_window=True, admitted=2.8, token_times=[2.9], dropped=True)
    return [a, b, c, d, w, e]


def test_ttft_counts_from_due_time():
    assert stats.ttft_ms(_logs()) == pytest.approx([1000.0, 200.0])


def test_itl_gaps_inside_the_window_only():
    gaps = stats.itl_gaps_ms(_logs(), 1.0, 2.2)
    # request 0: 2.0 -> 2.1; request 1: 1.2 -> 1.4 (0.9 lies before the window)
    assert sorted(gaps) == pytest.approx([100.0, 200.0])


def test_window_rate():
    # tokens in [1, 2.2): 1.2, 1.4, 2.0, 2.1
    assert stats.output_tok_s(_logs(), 1.0, 2.2) == pytest.approx(4 / 1.2)


def test_failed_counts_lost_dropped_and_rejected_not_warmup():
    assert stats.attempted_failed(_logs()) == (5, 3)


def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([0, 10], 95) == pytest.approx(9.5)
