import pytest

from benchmark import timing


def test_p95_counts_every_call_and_one_stall_shows():
    calls = [0.1] * 19 + [2.0]  # 20 calls, one stall
    assert timing.percentile(calls, 95) == 0.1  # the 19th of 20
    calls = [0.1] * 9 + [2.0]  # 10 calls: the stall is the tail
    assert timing.percentile(calls, 95) == 2.0
    assert timing.percentile([3.0, 1.0, 2.0], 50) == 2.0


def test_rate_takes_all_the_work_over_all_the_window():
    # 100 calls of 8 steps over 1,000 particles, one stall of 2 s among
    # 99 calls of 0.1 s: the window holds the stall
    window = 99 * 0.1 + 2.0
    r = timing.rate(1000 * 8, 100, window, chips=1)
    assert r == pytest.approx(8e5 / window)
    assert timing.rate(1000 * 8, 100, window, chips=4) == pytest.approx(r / 4)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        timing.percentile([], 95)
