"""Window arithmetic on a fake clock."""

import pytest

import benchpath  # noqa: F401
from benchkit import stats


def test_rate_is_all_work_over_all_time():
    assert stats.rate(300, 10.0) == 30.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_ledger_counts_window_tokens_and_gaps():
    led = stats.TokenLedger()
    # setup tick: request 1's first token, not in the window
    led.observe(1, 1, 0.5, in_window=False)
    # window: ticks at 1.0, 1.1, 1.3; request 2 starts at 1.1 and its
    # prefill and first decode land in one tick (two tokens at once)
    led.observe(1, 2, 1.0, True)
    led.observe(1, 3, 1.1, True)
    led.observe(2, 2, 1.1, True)
    led.observe(1, 4, 1.3, True)
    led.observe(2, 3, 1.3, True)
    led.observe(2, 3, 1.4, True)            # no new token: nothing counts
    assert led.tokens == 6
    # gaps: r1 1.0->1.1, 1.1->1.3 (0.5->1.0 straddles the window start);
    # r2: 0 between its two first tokens, then 1.1->1.3
    assert sorted(round(g, 9) for g in led.gaps) == [0.0, 0.1, 0.2, 0.2]
    assert led.first_t == {1: 0.5, 2: 1.1}


def test_tail_is_over_every_gap():
    gaps = [0.01] * 95 + [1.0] * 5
    assert stats.percentile(gaps, 95) == pytest.approx(0.0595)
    assert stats.percentile(gaps, 50) == pytest.approx(0.01)
    assert stats.percentile([], 95) is None
