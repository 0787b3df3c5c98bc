"""The general generator that reads a traffic mix (bench/traffic/*.json).

Lengths are laid out so that every seed does the same work: within each
block of ``block`` requests the lengths are the block's fixed quantiles of
the named distribution, in a fixed low-discrepancy order (``order``), and
the seed sets only the token ids. A measured window of a closed loop admits
a dozen or two requests, a fraction of a block; in a seeded order which
sizes those are changes from seed to seed, and with them the window's work.
In this order any run of consecutive requests samples the whole
distribution evenly, and every seed meets the same sizes at the same
places. Prompt and output lengths follow orders of different steps, so
that they are paired across the distribution and not rank for rank.

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


# steps of the two orders: the golden ratio's fraction and sqrt(2) - 1,
# which are rationally independent, so the pairs fill the square evenly
PROMPT_STEP = 0.6180339887498949
OUTPUT_STEP = 0.41421356237309515


def order(block: int, step: float) -> np.ndarray:
    """Quantile indices of a block in the order requests take them:
    position ``p`` takes the rank of ``p * step`` modulo 1 (a Weyl
    sequence), so consecutive positions fall far apart in the
    distribution."""
    return np.argsort(np.argsort((np.arange(block) * step) % 1.0))


def draw(spec: dict, block: int, count: int, step: float) -> np.ndarray:
    """``count`` lengths: whole blocks of the fixed quantiles, each block
    in ``order(block, step)``."""
    base = np.asarray(block_lengths(spec, block))[order(block, step)]
    return np.resize(base, count)


def requests(mix: dict, vocab: int, count: int,
             rng: np.random.Generator):
    """(prompt token lists, output lengths) of ``count`` requests."""
    block = int(mix["block"])
    plens = draw(mix["prompt"], block, count, PROMPT_STEP)
    olens = draw(mix["output"], block, count, OUTPUT_STEP)
    prompts = [rng.integers(0, vocab, int(n), dtype=np.int32).tolist()
               for n in plens]
    return prompts, [int(n) for n in olens]
