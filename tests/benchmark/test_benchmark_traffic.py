"""The traffic generator: deterministic in the seed, inside the clips, and
the same amount of work for every seed."""

import json
import os

import pytest

from benchmark.harness import plan, traffic

TRAFFIC_DIR = os.path.join(plan.BENCH_ROOT, "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC_DIR) if f.endswith(".json"))
BIG_SEED = 2**31 + 12345


def mix(name):
    return traffic.load_mix(os.path.join(TRAFFIC_DIR, name + ".json"))


@pytest.mark.parametrize("name", MIXES)
def test_sizes_respect_the_clips(name):
    m = mix(name)
    b = traffic.bounds(m, 2000)
    assert b["prompt_min"] == m["prompt"]["min"]
    assert b["prompt_max"] == m["prompt"]["max"]
    assert b["output_max"] == m["output"]["max"]
    for prompt, out in traffic.size_pool(m, 2000):
        assert m["prompt"]["min"] <= prompt <= m["prompt"]["max"]
        assert m["output"]["min"] <= out <= m["output"]["max"]
        assert prompt + out <= b["total_max"]
    small = traffic.bounds(m, 16)
    assert small["prompt_min"] > b["prompt_min"]
    assert small["prompt_max"] < b["prompt_max"]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    m = mix(name)
    a = traffic.sizes_for(m, BIG_SEED, "window", 300)
    assert a == traffic.sizes_for(m, BIG_SEED, "window", 300)
    assert traffic.prompt_ids(BIG_SEED, "window", 3, 50, 32000) == \
        traffic.prompt_ids(BIG_SEED, "window", 3, 50, 32000)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_differ_in_order_never_in_work(name):
    m = mix(name)
    a = traffic.sizes_for(m, 1, "window", 300)
    b = traffic.sizes_for(m, BIG_SEED, "window", 300)
    assert a != b and sorted(a) == sorted(b)
    assert traffic.sizes_for(m, 1, "preroll", 300) != a


def test_medians_are_what_the_file_says():
    m = mix(MIXES[0])
    pool = traffic.size_pool(m, 4000)
    prompts = sorted(p for p, _ in pool)
    outs = sorted(o for _, o in pool)
    assert abs(prompts[2000] - m["prompt"]["median"]) \
        < 0.1 * m["prompt"]["median"]
    assert abs(outs[2000] - m["output"]["median"]) \
        < 0.1 * m["output"]["median"]


def test_open_loop_arrivals_span_the_window_for_every_seed():
    m = {"loop": "open", "pool_seed": 3}
    n, seconds = 360, 30.0
    a = traffic.arrivals_for(m, 1, "window", n, seconds)
    b = traffic.arrivals_for(m, BIG_SEED, "window", n, seconds)
    assert len(a) == len(b) == n and a != b
    assert a == sorted(a) and a[0] == 0.0 and a[-1] < seconds
    gaps = lambda xs: sorted(round(y - x, 9) for x, y in zip(xs, xs[1:]))
    # the same multiset of gaps but for the one that closes the window
    ga, gb = set(gaps(a)), set(gaps(b))
    assert len(ga ^ gb) <= 2
    assert sum(traffic.gap_pool(m, n, seconds)) == pytest.approx(seconds)


def test_prompt_ids_stay_inside_the_vocabulary():
    ids = traffic.prompt_ids(7, "window", 0, 5000, 100)
    assert min(ids) >= 1 and max(ids) <= 98
    assert traffic.prompt_ids(7, "window", 1, 5000, 100) != ids


def test_pool_size_follows_the_loop():
    assert traffic.pool_size({"loop": "open"}, 12.0, 30.0) == 360
    assert traffic.pool_size({"loop": "closed", "pool": 256}, 0, 30.0) == 256


@pytest.mark.parametrize("bad", [
    {"loop": "ring"},
    {"loop": "open", "prompt": {"median": 5, "sigma": 1, "min": 8, "max": 9},
     "output": {"median": 5, "sigma": 1, "min": 1, "max": 9}},
])
def test_a_bad_mix_is_refused(tmp_path, bad):
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(bad))
    with pytest.raises((ValueError, KeyError)):
        traffic.load_mix(str(path))


@pytest.mark.parametrize("seed", [1, 77, BIG_SEED])
def test_a_seeds_order_is_balanced_over_prompt_length(seed):
    """Every run of 8 consecutive requests holds one prompt from each
    eighth of the pool, so no seed clumps the long prompts."""
    m = mix(MIXES[0])
    n = 64
    prompts = sorted(p for p, _ in traffic.size_pool(m, n))
    ranges = [(prompts[8 * k], prompts[8 * k + 7]) for k in range(8)]
    order = traffic.sizes_for(m, seed, "window", n)
    for start in range(0, n, 8):
        group = sorted(p for p, _ in order[start:start + 8])
        for p, (lo, hi) in zip(group, ranges):
            assert lo <= p <= hi


def test_quantiles_keep_the_tails_in_a_small_pool():
    m = mix(MIXES[0])
    prompts = [p for p, _ in traffic.size_pool(m, 64)]
    assert max(prompts) == m["prompt"]["max"]          # the clip is reached
    assert min(prompts) < m["prompt"]["median"] / 4
    assert 600 < sum(prompts) / 64 < 800               # mean ~ 650-700
