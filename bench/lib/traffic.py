"""The general generator that reads a traffic mix (bench/traffic/*.json).

Lengths are drawn so that every seed does the same work: within each block
of ``block`` requests the lengths are the block's fixed quantiles of the
named distribution, and the seed only sets their order (prompt and output
lengths shuffled apart) and the token ids. A window that spans whole blocks
then meets the same sizes on every seed, in another order.

A length spec is a dict:

    {"dist": "lognormal", "median": 512, "sigma": 0.8,
     "min": 128, "max": 2048, "round_to": 128}
    {"dist": "loguniform", "min": 2048, "max": 8192, "round_to": 512}

Values are clipped to [min, max] and rounded up to a multiple of
``round_to`` (default 1).
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List

import numpy as np


def quantile(spec: dict, q: float) -> float:
    dist = spec["dist"]
    if dist == "lognormal":
        z = NormalDist().inv_cdf(q)
        return float(spec["median"]) * math.exp(float(spec["sigma"]) * z)
    if dist == "loguniform":
        lo, hi = math.log(spec["min"]), math.log(spec["max"])
        return math.exp(lo + q * (hi - lo))
    raise ValueError(f"unknown length distribution {dist!r}")


def block_lengths(spec: dict, block: int) -> List[int]:
    """The ``block`` lengths every block of requests holds, ascending."""
    r = int(spec.get("round_to", 1))
    out = []
    for i in range(block):
        v = quantile(spec, (i + 0.5) / block)
        v = min(max(v, spec["min"]), spec["max"])
        out.append(int(-(-math.ceil(v - 1e-9) // r) * r))
    return sorted(out)


def shapes(spec: dict, block: int) -> List[int]:
    """The distinct lengths a mix can produce (its prefill shapes)."""
    return sorted(set(block_lengths(spec, block)))


def draw(spec: dict, block: int, count: int,
         rng: np.random.Generator) -> np.ndarray:
    """``count`` lengths: whole blocks of the fixed quantiles, each block
    in an order drawn from ``rng``."""
    base = np.asarray(block_lengths(spec, block))
    out = [rng.permutation(base) for _ in range(-(-count // block))]
    return np.concatenate(out)[:count]


def requests(mix: dict, vocab: int, count: int,
             rng: np.random.Generator):
    """(prompt token lists, output lengths) of ``count`` requests."""
    block = int(mix["block"])
    plens = draw(mix["prompt"], block, count, rng)
    olens = draw(mix["output"], block, count, rng)
    prompts = [rng.integers(0, vocab, int(n), dtype=np.int32).tolist()
               for n in plens]
    return prompts, [int(n) for n in olens]
