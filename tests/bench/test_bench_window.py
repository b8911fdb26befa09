"""Window accounting on synthetic timestamps."""
import pytest

from bench import window as W


def steps():
    # two requests: rid 1 (prompt 2) and rid 2 (prompt 3), one step a second
    out = []
    for k in range(8):
        out.append(W.Step(t_call=k + 0.5, t_ready=k + 1.0, owners=[1, 2],
                          positions=[k, k]))
    return out


def test_tokens_and_gaps_in_the_window():
    plen = {1: 2, 2: 3}
    em = W.emissions(steps(), plen)
    assert em[1] == [2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0]   # pos >= 1
    assert em[2] == [3.0, 4.0, 5.0, 6.0, 7.0, 8.0]        # pos >= 2
    acc = W.account(steps(), plen, start=2.5, end=6.5)
    # tokens at t in [2.5, 6.5]: rid 1 at 3,4,5,6; rid 2 at 3,4,5,6
    assert acc["tokens"] == 8
    assert acc["tokens_per_s"] == pytest.approx(8 / 4.0)
    # gaps ending in the window: rid 1 (2->3 .. 5->6), rid 2 (3->4 .. 5->6)
    assert sorted(acc["itl_s"]) == [1.0] * 7


def test_nothing_after_the_end_counts():
    plen = {1: 2, 2: 3}
    acc = W.account(steps(), plen, start=0.0, end=3.5)
    assert acc["tokens"] == 3                      # 1 at 2 and 3; 2 at 3
    assert acc["itl_s"] == [1.0]


def test_spread_uses_statistics_quartiles():
    from statistics import quantiles
    v = [10.0, 11.0, 12.0, 13.0, 20.0, 9.0]
    q1, q2, q3 = quantiles(v, n=4)
    assert W.spread(v) == pytest.approx((q3 - q1) / q2)
