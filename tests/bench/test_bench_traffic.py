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
    counts = None
    for seed in SEEDS:
        p = traffic.draw(mix["prompt"], block, 3 * block, seeds.rng(seed, 1))
        o = traffic.draw(mix["output"], block, 3 * block, seeds.rng(seed, 1))
        for i in range(3):
            got = (Counter(p[i * block:(i + 1) * block].tolist()),
                   Counter(o[i * block:(i + 1) * block].tolist()))
            counts = counts or got
            assert got == counts


@pytest.mark.parametrize("name", sorted(SERVE))
def test_prefill_shapes_bounded(name):
    mix = SERVE[name]
    shapes = traffic.shapes(mix["prompt"], mix["block"])
    assert len(shapes) <= 16
    spec = mix["prompt"]
    assert all(spec["min"] <= s <= spec["max"] for s in shapes)
    assert all(s % spec.get("round_to", 1) == 0 for s in shapes)
    for seed in SEEDS:
        drawn = traffic.draw(spec, mix["block"], 500, seeds.rng(seed, 1))
        assert set(drawn.tolist()) <= set(shapes)
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
