"""The schedule is the same multiset for every seed, and a permutation of
it per seed."""

import json
import os

import pytest
from bench_paths import BENCH

from kbench import schedule

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
               if f.endswith(".json"))
SEEDS = (0, 1, 7, 2**31 + 12345)  # the driver's seeds pass 2**31


def load_mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def make(mix, seed):
    if mix["loop"] == "open":
        return schedule.open_loop_schedule(mix, 4.0, 51, seed, vocab=1000)
    return [r for mine in schedule.closed_loop_schedule(mix, 48, seed, 1000)
            for r in mine]


@pytest.mark.parametrize("name", MIXES)
def test_same_multiset_for_every_seed(name):
    """What is judged — an open loop's window, a closed loop's lists — is
    the same multiset of (prompt length, output length, gap) for every
    seed.  (An open loop's ramp and cool-down are the stretches of the same
    periodic sequence before and after the window: their content follows
    the phase the seed picks.)"""
    mix = load_mix(name)
    judged = "window"  # closed-loop requests carry the default phase
    base = schedule.multiset(make(mix, SEEDS[0]))
    for seed in SEEDS[1:]:
        got = schedule.multiset(make(mix, seed))
        assert got[judged] == base[judged]
        assert set(got) == set(base)


def test_open_loop_seed_rotates_one_periodic_sequence():
    """The window holds exactly one period, cut at the phase the seed
    picks; the ramp is the end of the period before it and the cool-down
    the start of the one after."""
    mix = load_mix("chat")
    a = schedule.open_loop_schedule(mix, 4.0, 51, 0, vocab=1000)
    seed_b = next(s for s in range(1, 10**6) if schedule.phase_of(s, 204) == 100)
    b = schedule.open_loop_schedule(mix, 4.0, 51, seed_b, vocab=1000)
    wa = [(r.prompt_len, r.output_len, r.gap_s) for r in a if r.phase == "window"]
    wb = [(r.prompt_len, r.output_len, r.gap_s) for r in b if r.phase == "window"]
    assert wb == wa[100:] + wa[:100]
    head = 1 + mix["ramp_burst"]["n"]
    ramp_b = [r for r in b if r.phase == "ramp"][head:]
    assert [(r.prompt_len, r.output_len) for r in ramp_b] == \
        [x[:2] for x in wa[100 - len(ramp_b):100]]
    assert ramp_b[0].due_s >= -mix["ramp_s"] and ramp_b[-1].due_s < 0
    cool_b = [r for r in b if r.phase == "cooldown"]
    assert [(r.prompt_len, r.output_len) for r in cool_b] == \
        [x[:2] for x in wb[:len(cool_b)]]
    assert cool_b[0].due_s == pytest.approx(51.0)
    # a large seed (the driver's pass 2**31) is a phase like any other
    c = schedule.open_loop_schedule(mix, 4.0, 51, 2**31 + 12345, vocab=1000)
    k = schedule.phase_of(2**31 + 12345, len(wa))
    assert [(r.prompt_len, r.output_len) for r in c if r.phase == "window"] == \
        [x[:2] for x in wa[k:] + wa[:k]]


def test_neighbouring_seeds_cut_at_phases_spread_over_the_period():
    """Seeds 301..306 (a set's) are not one request apart: their phases
    cover the period, so a set's spread is the spread over phases."""
    n = 204
    phases = sorted(schedule.phase_of(s, n) for s in range(301, 307))
    steps = [b - a for a, b in zip(phases, phases[1:])]
    assert min(steps) >= 10 and phases[-1] - phases[0] >= n // 2
    # seeds as large as the driver's land inside the period, all over it
    big = {schedule.phase_of(2**31 + s, n) for s in range(2000)}
    assert min(big) == 0 and max(big) == n - 1 and len(big) == n


@pytest.mark.parametrize("name", MIXES)
def test_seed_permutes_order_and_token_ids(name):
    mix = load_mix(name)
    a, b, again = make(mix, 1), make(mix, 2), make(mix, 1)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [r.sampling_seed for r in a] != [r.sampling_seed for r in b]
    # the same seed gives the same inputs, to the token
    assert [(r.prompt, r.output_len, r.due_s, r.sampling_seed) for r in a] == \
        [(r.prompt, r.output_len, r.due_s, r.sampling_seed) for r in again]


def test_open_loop_window_holds_rate_times_seconds_requests():
    mix = load_mix("chat")
    for seed in SEEDS:
        reqs = schedule.open_loop_schedule(mix, 4.0, 51, seed, vocab=1000)
        window = [r for r in reqs if r.phase == "window"]
        assert len(window) == 204
        assert all(0.0 <= r.due_s < 51.0 for r in window)
        assert all(r.due_s < 0 for r in reqs if r.phase == "ramp")
        assert all(r.due_s >= 51.0 for r in reqs if r.phase == "cooldown")
        assert sum(r.gap_s for r in window) == pytest.approx(51.0)


def test_ramp_head_is_a_primer_then_a_burst_the_same_in_every_run():
    mix = load_mix("chat")
    heads = []
    for seed in SEEDS:
        reqs = schedule.open_loop_schedule(mix, 4.0, 51, seed, vocab=1000)
        head = [r for r in reqs if r.phase == "ramp"][:1 + mix["ramp_burst"]["n"]]
        assert head[0].due_s == -mix["ramp_s"]
        assert (head[0].prompt_len, head[0].output_len) == (
            mix["ramp_primer"]["prompt_len"], mix["ramp_primer"]["output_len"])
        assert all(r.due_s == pytest.approx(-mix["ramp_s"] + 0.05) for r in head[1:])
        assert all(64 <= r.output_len <= 128 for r in head[1:])
        heads.append((sorted(r.prompt_len for r in head),
                      sorted(r.output_len for r in head)))
    assert all(h == heads[0] for h in heads)


def test_lengths_respect_the_mix():
    mix = load_mix("chat")
    reqs = [r for r in schedule.open_loop_schedule(mix, 4.0, 51, 3, 1000)
            if r.phase == "window"]
    assert min(r.prompt_len for r in reqs) >= mix["prompt_len"]["min"]
    assert max(r.prompt_len for r in reqs) <= mix["prompt_len"]["max"]
    mean = sum(r.prompt_len for r in reqs) / len(reqs)
    assert 200 < mean < 270  # lognormal(median 160, sigma 0.9), clipped
    assert all(len(r.prompt) == r.prompt_len for r in reqs)


def test_any_stretch_of_a_run_samples_the_whole_distribution():
    """Low-discrepancy order: every stratum of 16 consecutive requests
    holds short and long prompts alike."""
    mix = load_mix("chat")
    reqs = [r for r in schedule.open_loop_schedule(mix, 4.0, 51, 9, 1000)
            if r.phase == "window"]
    lens = sorted(r.prompt_len for r in reqs)
    median = lens[len(lens) // 2]
    for i in range(0, len(reqs) - 16, 16):
        chunk = [r.prompt_len for r in reqs[i:i + 16]]
        below = sum(n <= median for n in chunk)
        assert 4 <= below <= 12, chunk


def test_closed_loop_first_lengths_are_staggered():
    mix = load_mix("decode-sat")
    per_client = schedule.closed_loop_schedule(mix, 48, 5, 1000)
    firsts = sorted(mine[0].output_len for mine in per_client)
    # residual lives spread from near 0 to near a whole request
    assert firsts[0] < 32 and firsts[-1] > 300
    assert all(mine[1].output_len >= mix["output_len"]["min"] for mine in per_client)


@pytest.mark.parametrize("dist,lo,hi", [
    ({"dist": "exponential"}, 0.6, 0.8),
    ({"dist": "uniform", "min": 0, "max": 2}, 0.99, 1.01),
    ({"dist": "lognormal", "median": 1.0, "sigma": 0.5}, 0.99, 1.01),
    ({"dist": "fixed", "value": 3}, 2.99, 3.01),
])
def test_quantile_medians(dist, lo, hi):
    assert lo < schedule.quantile(dist, 0.5) < hi


def test_an_unknown_distribution_is_refused():
    with pytest.raises(ValueError):
        schedule.quantile({"dist": "gamma", "cv": 2.5}, 0.5)
