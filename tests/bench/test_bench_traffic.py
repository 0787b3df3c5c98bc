"""The traffic generator: the same seed gives the same requests, every
seed gives the same sizes, and the prefill shapes stay few."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import seeds, traffic  # noqa: E402

MIXES = {p.stem: json.loads(p.read_text())
         for p in (ROOT / "bench" / "traffic").glob("*.json")}
SERVE = {k: v for k, v in MIXES.items() if v["kind"] == "serve"}
SEEDS = [0, 1, 7, 2**31 + 11, 2**33 + 5, 987654321987]


@pytest.mark.parametrize("name", sorted(SERVE))
def test_same_seed_same_requests(name):
    mix = SERVE[name]
    a = traffic.requests(mix, 1000, 70, seeds.rng(SEEDS[3], 1))
    b = traffic.requests(mix, 1000, 70, seeds.rng(SEEDS[3], 1))
    c = traffic.requests(mix, 1000, 70, seeds.rng(SEEDS[4], 1))
    assert a == b
    assert a != c


@pytest.mark.parametrize("name", sorted(SERVE))
def test_every_seed_same_sizes_in_each_block(name):
    mix = SERVE[name]
    block = mix["block"]
    base = (Counter(traffic.block_lengths(mix["prompt"], block)),
            Counter(traffic.block_lengths(mix["output"], block)))
    first = None
    for seed in SEEDS:
        prompts, outs = traffic.requests(mix, 1000, 3 * block,
                                         seeds.rng(seed, 1))
        lens = ([len(p) for p in prompts], outs)
        for i in range(3):
            got = (Counter(lens[0][i * block:(i + 1) * block]),
                   Counter(lens[1][i * block:(i + 1) * block]))
            assert got == base
        # the same sizes at the same places: the seed sets token ids only
        first = first or lens
        assert lens == first


@pytest.mark.parametrize("name", sorted(SERVE))
def test_any_run_of_requests_samples_the_whole_distribution(name):
    """A window that admits a dozen consecutive requests, from anywhere in
    the queue, meets sizes whose mean is near the block's."""
    mix = SERVE[name]
    block = mix["block"]
    for spec, step in ((mix["prompt"], traffic.PROMPT_STEP),
                       (mix["output"], traffic.OUTPUT_STEP)):
        lens = traffic.draw(spec, block, 4 * block, step)
        mean = np.mean(traffic.block_lengths(spec, block))

        def worst(seq):
            return max(abs(np.mean(seq[i:i + 12]) / mean - 1)
                       for i in range(3 * block))

        assert worst(lens) <= 0.21, (name, spec)
        # every seeded shuffle of the same blocks strays further
        for seed in range(10):
            rng = np.random.default_rng(seed)
            shuffled = np.concatenate([rng.permutation(lens[:block])
                                       for _ in range(4)])
            assert worst(shuffled) > worst(lens)


@pytest.mark.parametrize("name", sorted(SERVE))
def test_prefill_shapes_bounded(name):
    mix = SERVE[name]
    shapes = traffic.shapes(mix["prompt"], mix["block"])
    assert len(shapes) <= 16
    spec = mix["prompt"]
    assert all(spec["min"] <= s <= spec["max"] for s in shapes)
    assert all(s % spec.get("round_to", 1) == 0 for s in shapes)
    for seed in SEEDS:
        prompts, _ = traffic.requests(mix, 1000, 500, seeds.rng(seed, 1))
        assert {len(p) for p in prompts} <= set(shapes)
    # every request fits the cache it is served from
    assert max(shapes) + mix["output"]["max"] <= mix["max_len"]


def test_lognormal_quantiles_center_on_the_median():
    spec = {"dist": "lognormal", "median": 512, "sigma": 0.8,
            "min": 1, "max": 10**6}
    lens = traffic.block_lengths(spec, 64)
    assert lens == sorted(lens)
    assert abs(np.median(lens) - 512) < 40


def test_seed_streams_independent_and_wide():
    a = seeds.rng(2**40 + 3, seeds.STREAM_TRAFFIC).integers(1 << 30)
    b = seeds.rng(2**40 + 3, seeds.STREAM_SAMPLE).integers(1 << 30)
    c = seeds.rng(3, seeds.STREAM_TRAFFIC).integers(1 << 30)
    assert len({a, b, c}) == 3
