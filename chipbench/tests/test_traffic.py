"""Traffic generation: deterministic per seed; the seed reorders the
work and never resamples its lengths."""

from collections import Counter

import pytest

from chipbench import traffic

MIXES = ["decode-heavy", "prefill-heavy", "tiny", "tiny-open"]


def pairs_of(mix, seed, seconds=51.0):
    if mix["loop"] == "open":
        return [(r.prompt_len, r.output_len)
                for r in traffic.open_loop_schedule(mix, seed, seconds)
                if r.due >= 0]
    return traffic.closed_loop_queue(mix, seed)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    mix = traffic.load_mix(name)
    assert pairs_of(mix, 5) == pairs_of(mix, 5)
    assert traffic.prompt_tokens(5, 3, 64, 1000) == \
        traffic.prompt_tokens(5, 3, 64, 1000)


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_same_multiset_other_order(name):
    mix = traffic.load_mix(name)
    a, b = pairs_of(mix, 1), pairs_of(mix, 2 ** 31 + 7)
    assert Counter(a) == Counter(b)
    assert a != b


def test_open_loop_count_and_times():
    mix = traffic.load_mix("tiny-open")
    sched = traffic.open_loop_schedule(mix, 9, 51.0)
    win = [r for r in sched if r.due >= 0]
    assert len(win) == round(mix["rate"] * 51.0)
    assert all(0.0 <= r.due < 51.0 for r in win)
    ramp = [r for r in sched if r.due < 0]
    assert len(ramp) == round(mix["rate"] * mix["ramp_s"])
    assert all(-mix["ramp_s"] <= r.due < 0.0 for r in ramp)
    dues = [r.due for r in sched]
    assert dues == sorted(dues)
    assert [r.index for r in sched] == list(range(len(sched)))
    # another seed: other arrival times
    other = traffic.open_loop_schedule(mix, 10, 51.0)
    assert [r.due for r in other] != dues


def test_lengths_within_the_clip_and_around_the_median():
    chat = {"kind": "lognormal", "median": 512, "sigma": 1.0,
            "min": 16, "max": 4096}
    ps = traffic.quantile_lengths(chat, 160)
    assert min(ps) >= 16 and max(ps) <= 4096
    assert abs(sorted(ps)[80] - 512) < 16
    assert traffic.quantile_lengths({"kind": "fixed", "value": 16}, 3) == [16] * 3
    us = traffic.quantile_lengths({"kind": "uniform", "min": 64, "max": 256}, 4)
    assert us == [88, 136, 184, 232]


def test_prompts_of_two_requests_differ():
    a = traffic.prompt_tokens(1, 0, 32, 151936)
    b = traffic.prompt_tokens(1, 1, 32, 151936)
    assert a[:16] != b[:16] and all(0 <= t < 151936 for t in a + b)
