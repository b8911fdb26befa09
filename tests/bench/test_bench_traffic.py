"""The traffic generator: the same sizes for every seed, in another order."""
import pytest

from bench import traffic
from conftest import _load

CHAT = {"kind": "closed_loop", "clients": 8, "max_len": 160,
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 64},
        "output_len": {"dist": "lognormal", "median": 32, "sigma": 0.5,
                       "min": 8, "max": 96},
        "tokens": {"dist": "zipf", "s": 1.1}, "strata": 16,
        "ramp": {"prompt_len": 8, "output_len_max": 32}}


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 3])
def test_every_seed_sends_the_same_sizes_in_the_same_order(seed):
    a = traffic.Stream(CHAT, 1000, 1)
    b = traffic.Stream(CHAT, 1000, seed)
    ra = [a.next() for _ in range(40)]
    rb = [b.next() for _ in range(40)]
    assert [(len(r.prompt), r.max_new) for r in ra] == \
        [(len(r.prompt), r.max_new) for r in rb]
    assert [list(r.prompt) for r in ra] != [list(r.prompt) for r in rb]


def test_a_block_holds_every_quantile_once():
    s = traffic.Stream(CHAT, 1000, 1)
    q = traffic._quantiles(CHAT["prompt_len"], 16)
    o = traffic._quantiles(CHAT["output_len"], 16)
    reqs = [s.next() for _ in range(16)]
    assert sorted(len(r.prompt) for r in reqs) == sorted(q)
    assert sorted(r.max_new for r in reqs) == sorted(o)
    # the first half already spans the prompt lengths
    first = sorted(len(r.prompt) for r in reqs[:8])
    assert first[0] == min(q) and first[-1] >= sorted(q)[-2]


def test_tokens_stay_in_the_vocabulary():
    s = traffic.Stream(CHAT, 1000, 2)
    assert all(0 <= t < 1000 for _ in range(16) for t in s.next().prompt)


def test_lengths_are_clipped_and_fit_max_len():
    s = traffic.Stream(CHAT, 1000, 3)
    for _ in range(64):
        r = s.next()
        assert 8 <= len(r.prompt) <= 64 and 1 <= r.max_new <= 96
        assert len(r.prompt) + r.max_new <= 160


def test_ramp_is_staggered():
    s = traffic.Stream(CHAT, 1000, 3)
    ramp = s.ramp()
    assert [r.max_new for r in ramp] == [4, 8, 12, 16, 20, 24, 28, 32]
    assert all(len(r.prompt) == 8 for r in ramp)
    assert s.max_alloc() == max(p + n for p, n in s.block)


def test_test_traffic_parses():
    s = traffic.Stream(_load("tiny-chat.json"), 512, 1)
    assert len(s.ramp()) == 3



@pytest.mark.parametrize("change", [{"think_s": 2}, {"greedy": False},
                                    {"arrival": "poisson"}, {"ramp": None}])
def test_a_setting_the_generator_does_not_implement_is_refused(change):
    t = {k: v for k, v in dict(CHAT, **change).items() if v is not None}
    with pytest.raises(ValueError, match="not the generator's"):
        traffic.Stream(t, 1000, 1)
