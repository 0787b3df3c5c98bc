"""Pallas TPU kernel: Iterative Logarithmic Multiplier on uint32 lanes.

The bit-exact hardware model (paper §4-5) as a vector kernel: the priority
encoder is a bit-smear + population count, the LOD residue is a subtract, the
shifts are lane-local. Operands must be < 2^16 so every partial product fits
the uint32 lane. ``iters`` unrolls at trace time (it is the paper's accuracy
dial — each unrolled stage is one hardware pipeline stage).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = (256, 256)


def _floor_log2(v):
    for s in (1, 2, 4, 8, 16):
        v = v | (v >> s)
    return jax.lax.population_count(v) - jnp.uint32(1)


def _ilm_mul_kernel(a_ref, b_ref, o_ref, *, iters: int):
    a = a_ref[...]
    b = b_ref[...]
    acc = jnp.zeros_like(a)
    one = jnp.uint32(1)
    for _ in range(iters):
        valid = (a > 0) & (b > 0)
        k1 = _floor_log2(jnp.where(valid, a, one))
        k2 = _floor_log2(jnp.where(valid, b, one))
        ra = a - (one << k1)
        rb = b - (one << k2)
        p = (one << (k1 + k2)) + (ra << k2) + (rb << k1)
        acc = jnp.where(valid, acc + p, acc)
        a = jnp.where(valid, ra, a)
        b = jnp.where(valid, rb, b)
    o_ref[...] = acc


def _ilm_square_kernel(a_ref, o_ref, *, iters: int):
    a = a_ref[...]
    acc = jnp.zeros_like(a)
    one = jnp.uint32(1)
    for _ in range(iters):
        valid = a > 0
        k = _floor_log2(jnp.where(valid, a, one))
        r = a - (one << k)
        acc = jnp.where(valid, acc + (one << (k + k)) + (r << (k + one)), acc)
        a = jnp.where(valid, r, a)
    o_ref[...] = acc


def _grid_spec(shape, block):
    bm, bn = min(block[0], shape[0]), min(block[1], shape[1])
    grid = (pl.cdiv(shape[0], bm), pl.cdiv(shape[1], bn))
    return grid, pl.BlockSpec((bm, bn), lambda i, j: (i, j))


@functools.partial(jax.jit, static_argnames=("iters", "block", "interpret"))
def ilm_mul_2d(a, b, *, iters: int = 16, block=DEFAULT_BLOCK, interpret: bool = True):
    grid, spec = _grid_spec(a.shape, block)
    return pl.pallas_call(
        functools.partial(_ilm_mul_kernel, iters=iters),
        grid=grid, in_specs=[spec, spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.uint32),
        interpret=interpret,
    )(a.astype(jnp.uint32), b.astype(jnp.uint32))


@functools.partial(jax.jit, static_argnames=("iters", "block", "interpret"))
def ilm_square_2d(a, *, iters: int = 16, block=DEFAULT_BLOCK, interpret: bool = True):
    grid, spec = _grid_spec(a.shape, block)
    return pl.pallas_call(
        functools.partial(_ilm_square_kernel, iters=iters),
        grid=grid, in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.uint32),
        interpret=interpret,
    )(a.astype(jnp.uint32))
