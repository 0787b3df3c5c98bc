"""Everything random in a run comes from ``--seed`` through here.

Seeds are any whole number (the driver's exceed 32 bits); each is mixed
with a stream number, so data, traffic and weights draw independently.
"""
from __future__ import annotations

import numpy as np

STREAM_DATA, STREAM_TRAFFIC, STREAM_WEIGHTS, STREAM_SAMPLE, STREAM_CACHE = range(5)


def _seq(seed: int, stream: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed) % (1 << 64), stream])


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(_seq(seed, stream))


def key(seed: int, stream: int):
    """A JAX PRNG key for ``(seed, stream)``."""
    import jax

    a, b = (int(v) & 0x7FFFFFFF for v in _seq(seed, stream).generate_state(2))
    return jax.random.fold_in(jax.random.PRNGKey(a), b)
