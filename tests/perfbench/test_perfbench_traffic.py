"""traffic.py and stats.py: seeded schedules, stratified multisets, offered
rate, and the percentile / TTFT / TPOT arithmetic with failed requests
counted as misses."""

import math
import os
import sys
from collections import Counter

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

from perfbench import stats, traffic  # noqa: E402

CHAT = {
    "rate": 2.4,
    "prompt": {"choices": [64, 128, 256, 512, 1024],
               "weights": [0.30, 0.30, 0.20, 0.15, 0.05]},
    "output": {"lognormal": {"median": 96, "sigma": 0.8}, "clip": [16, 512],
               "snap": [16, 32, 48, 64, 96, 128, 192, 256, 384, 512]},
}
BIG_SEED = 2 ** 31 + 12345   # more than 32 signed bits hold


def _view(reqs):
    return [(r.rid, r.prompt_len, r.max_new, r.due) for r in reqs]


def test_same_seed_same_schedule_other_seed_other_order():
    a = traffic.open_loop(CHAT, 45, BIG_SEED)
    b = traffic.open_loop(CHAT, 45, BIG_SEED)
    c = traffic.open_loop(CHAT, 45, 7)
    assert _view(a[0]) == _view(b[0]) and _view(a[1]) == _view(b[1])
    assert _view(a[1]) != _view(c[1])
    assert (traffic.prompt_tokens(BIG_SEED, 3, 64, 1000)
            == traffic.prompt_tokens(BIG_SEED, 3, 64, 1000)).all()
    assert (traffic.prompt_tokens(BIG_SEED, 3, 64, 1000)
            != traffic.prompt_tokens(BIG_SEED, 4, 64, 1000)).any()


def test_every_seed_offers_the_same_multiset_and_the_asked_rate():
    runs = [traffic.open_loop(CHAT, 45, s) for s in (1, 2, BIG_SEED)]
    multisets = [Counter((r.prompt_len, r.max_new) for r in w) for _ramp, w in runs]
    assert multisets[0] == multisets[1] == multisets[2]
    for ramp, window in runs:
        assert len(window) == round(2.4 * 45)                 # offered rate as asked
        assert all(0 <= r.due < 45 for r in window)
        assert [r.due for r in window] == sorted(r.due for r in window)
        # the ramp holds every distinct shape of the window, once, same for all seeds
        assert (sorted((r.prompt_len, r.max_new) for r in ramp)
                == sorted(multisets[0]))
    assert _view(runs[0][0]) == _view(runs[1][0])
    prompts = [r.prompt_len for r in runs[0][1]]
    assert sum(prompts) / len(prompts) == pytest.approx(237, rel=0.03)
    outs = sorted(r.max_new for r in runs[0][1])
    assert outs[0] == 16 and outs[-1] == 512 and outs[len(outs) // 2] == 96


def test_quantiles_of_each_spec():
    assert traffic.stratified({"fixed": 128}, 3) == [128, 128, 128]
    assert traffic.stratified({"uniform": [16, 384], "step": 16}, 32)[0] == 16
    assert set(traffic.stratified({"uniform": [16, 384], "step": 16}, 32)) == set(
        range(16, 385, 16))                                     # every block count
    assert Counter(traffic.stratified({"choices": [1, 2], "weights": [3, 1]}, 8)
                   ) == {1: 6, 2: 2}
    with pytest.raises(ValueError):
        traffic.quantile({"zipf": 1}, 0.5)


def test_closed_loop_first_wave_covers_every_shape_it_can_send():
    spec = {"clients": 32, "prompt": {"fixed": 128},
            "output": {"uniform": [128, 384], "step": 16},
            "first_output": {"uniform": [16, 384], "step": 16}, "cycle": 68}
    a, b = traffic.ClosedLoop(spec), traffic.ClosedLoop(spec)
    first = a.first_wave()
    assert len(first) == 32
    assert {(r.prompt_len, r.max_new) for r in first} >= set(a.shapes()) - {
        s for s in a.shapes() if s[1] not in range(16, 385, 16)}
    assert set(a.shapes()) == set(b.shapes())
    # the order is fixed, not seeded: the seed makes weights and prompt tokens only
    assert [(r.prompt_len, r.max_new) for r in first] == [
        (r.prompt_len, r.max_new) for r in traffic.ClosedLoop(spec).first_wave()]
    later_a = Counter(a.next_request().max_new for _ in range(68))
    b.first_wave()
    later_b = Counter(b.next_request().max_new for _ in range(68))
    assert later_a == later_b and min(later_a) == 128 and max(later_a) == 384
    assert a.next_request().rid == "c100"


def test_percentile_counts_failed_requests_as_misses():
    vals = list(range(1, 11))                     # 10 finished requests
    assert stats.percentile(vals, 90) == 9
    assert stats.percentile(vals, 50) == 5
    # one failed among 10 attempted: the 90th percentile is the 9th of 10 -> still finite
    assert stats.percentile(vals[:9], 90, missed=1) == 9
    # two failed: rank 9 of 10 falls on a miss
    assert stats.percentile(vals[:8], 90, missed=2) == math.inf
    assert stats.samples_beyond(108, 90) == 10
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_ttft_and_tpot_arithmetic():
    r = traffic.Request("w0", 64, 17, due=1.0)
    assert stats.ttft_ms(r, t_open=100.0) is None               # no first token: a miss
    r.t_first = 101.25
    assert stats.ttft_ms(r, t_open=100.0) == pytest.approx(250.0)   # from DUE, not from the call
    # first token at 101.25, then two macro-steps of 8 tokens
    r.events = [(101.25, 1), (102.05, 8), (102.85, 8)]
    assert r.n_out == 17
    assert stats.tpot_ms(r) == pytest.approx((102.85 - 101.25) / 16 * 1e3)
    # admitted before the window opened at 102.0: count from its first emission inside
    assert stats.tpot_ms(r, not_before=102.0) == pytest.approx(100.0)
    assert stats.tpot_ms(traffic.Request("x", 1, 1, events=[(1.0, 1)])) is None
    assert stats.median([3, 1, 2]) == 2 and stats.median([4, 1, 2, 3]) == 2.5
