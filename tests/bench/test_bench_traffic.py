"""The traffic generator: deterministic per seed, the stated length
distributions, prompt lengths in multiples of 128, and the same work
for every seed."""

import json
import os
from statistics import NormalDist

import numpy as np
import pytest

from bench import traffic

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "bench")
MIXES = ("fit",)
BIG_SEED = 2**33 + 12345


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_plan_is_deterministic_for_a_seed(name):
    a = traffic.plan(mix(name), BIG_SEED, 1000)
    b = traffic.plan(mix(name), BIG_SEED, 1000)
    c = traffic.plan(mix(name), BIG_SEED + 1, 1000)
    assert np.array_equal(a.prompt_lens, b.prompt_lens)
    assert np.array_equal(a.output_lens, b.output_lens)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert not all(np.array_equal(x, y) for x, y in zip(a.prompts, c.prompts))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes_in_the_same_order(name):
    a = traffic.plan(mix(name), 1, 1000)
    b = traffic.plan(mix(name), BIG_SEED, 1000)
    assert np.array_equal(a.prompt_lens, b.prompt_lens)
    assert np.array_equal(a.output_lens, b.output_lens)
    # the sizes are not paired in one fixed way within every block
    block = mix(name)["clients"]
    assert not np.array_equal(a.output_lens[:block],
                              a.output_lens[block:2 * block])


@pytest.mark.parametrize("name", MIXES)
def test_prompt_lengths_round_up_to_128_within_the_clip(name):
    m = mix(name)
    p = traffic.plan(m, 7, 1000)
    assert np.all(p.prompt_lens % 128 == 0)
    assert p.prompt_lens.min() >= m["prompt"]["min"]
    assert p.prompt_lens.max() <= m["prompt"]["max"]
    assert p.output_lens.min() >= m["output"]["min"]
    assert p.output_lens.max() <= m["output"]["max"]
    assert [len(x) for x in p.prompts] == list(p.prompt_lens)
    assert p.menu == sorted(set(int(x) for x in p.prompt_lens))


def test_lognormal_quantiles_follow_the_distribution():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 1,
         "max": 10**9}
    q = traffic.quantiles(d, 1001)
    assert q[500] == 512
    z = NormalDist().inv_cdf(900.5 / 1001)
    assert abs(q[900] - 512 * np.exp(0.8 * z)) <= 0.5
    assert np.all(np.diff(q) >= 0)


def test_uniform_quantiles_and_rounding():
    d = {"dist": "uniform", "min": 64, "max": 256}
    q = traffic.quantiles(d, 192)
    assert q.min() == 64 and q.max() == 256
    assert abs(q.mean() - 160) < 1
    r = traffic.quantiles({"dist": "uniform", "min": 100, "max": 300,
                           "round_up_to": 128}, 50)
    assert set(r) <= {128, 256, 384}


def test_closed_loop_blocks_hold_the_whole_stratified_set():
    m = mix("fit")
    p = traffic.plan(m, 3, 1000)
    block = m["clients"]
    want = sorted(traffic.quantiles(m["prompt"], block))
    for i in range(0, 4 * block, block):
        assert sorted(p.prompt_lens[i:i + block]) == want


def test_onboard_rules():
    fit = mix("fit")
    big = traffic.onboard_pages(fit, 32)
    assert big == 8 * -(-(512 + 64) // 32)
    half_of_mean = dict(fit, onboard_pages={"rule": "mean_working_set",
                                            "factor": 0.5})
    half = traffic.onboard_pages(half_of_mean, 32)
    ws = traffic.working_set_pages(traffic.quantiles(fit["prompt"], 512),
                                   traffic.quantiles(fit["output"], 512),
                                   32)
    assert half == round(0.5 * 8 * ws)
    assert 40 < half < 60
    with pytest.raises(ValueError):
        traffic.onboard_pages(dict(fit, onboard_pages={"rule": "none"}), 32)


def test_working_set_by_hand():
    # prompt 30, outputs 3: decode tokens 1, 2 hold ceil(31/8), ceil(32/8)
    assert traffic.working_set_pages([30], [3], 8) == (4 + 4) / 2
    # prompt 31, output 3: ceil(32/8)=4, ceil(33/8)=5
    assert traffic.working_set_pages([31], [3], 8) == (4 + 5) / 2


def test_seed_parts_take_large_seeds():
    assert traffic.seed_parts(5) == (5, 0)
    lo, hi = traffic.seed_parts(2**33 + 5)
    assert (lo, hi) == (5, 4)
    with pytest.raises(ValueError):
        traffic.seed_parts(-1)
