"""Pallas TPU kernel: fused row softmax with the Taylor-series reciprocal.

max/exp/sum/scale in one VMEM-resident pass; the 1/sum is the paper's
division unit (recip_f32_bits) rather than an XLA divide. Rows are blocked;
the reduced dim stays whole inside the block (padded positions are masked to
-inf by the wrapper so they contribute exp(-inf)=0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.seeds import compute_segments
from . import common


def _softmax_kernel(x_ref, o_ref, *, n: int, precision_bits: int, schedule: str):
    x = x_ref[...].astype(jnp.float32)
    xmax = jnp.max(x, axis=-1, keepdims=True)
    # Fully-masked rows (all logits -inf: masked consumers and the wrapper's
    # pad rows) must come out as zeros, not exp(-inf - -inf) = nan; rows
    # with at least one finite logit have s >= exp(0) = 1, so s == 0 is an
    # exact tag for them after the guard below.
    mfin = jnp.where(jnp.isfinite(xmax), xmax, jnp.float32(0.0))
    ex = jnp.exp(x - mfin)
    s = common.tree_sum(ex)     # the jnp twin's summation order
    table = compute_segments(n, precision_bits)
    rs = common.recip_f32_bits(s, table, n, schedule)
    o_ref[...] = jnp.where(s == 0.0, jnp.float32(0.0),
                           ex * rs).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_iters", "precision_bits", "schedule",
                                             "block_rows", "interpret"))
def softmax_2d(x, *, n_iters: int = 2, precision_bits: int = 24,
               schedule: str = "factored", block_rows: int = 64,
               interpret: bool = True):
    """Softmax over the last dim of an (M, D) array."""
    m, d = x.shape
    bm = min(block_rows, m)
    grid = (pl.cdiv(m, bm),)
    spec = pl.BlockSpec((bm, d), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_softmax_kernel, n=n_iters, precision_bits=precision_bits,
                          schedule=schedule),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)
