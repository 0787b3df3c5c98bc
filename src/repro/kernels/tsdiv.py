"""Pallas TPU kernel: fused Taylor-series reciprocal / divide (the paper's unit).

Three refinement schedules share the datapath (see kernels/common.py):
"paper" (§6 powering), "factored" (log-depth squarings), and "goldschmidt"
(N += N*r residual-register recurrence — the rival algorithm of
arXiv:1909.10154 fused into the same VMEM-resident kernel).

Elementwise over 2D-tiled blocks resident in VMEM. The whole division unit —
unpack, PWL seed ladder, series refinement, repack — is one fused VPU kernel:
a single HBM read and write per element, vs. read/write per stage if composed
from jnp ops without fusion. Block shape defaults to (256, 256) f32 = 256 KiB
in + 256 KiB out, comfortably inside the ~16 MiB/core VMEM with double
buffering; the lane dim is a multiple of 128 (VREG lane width) and the
sublane dim a multiple of 8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.seeds import SeedTable, compute_segments
from . import common

DEFAULT_BLOCK = (256, 256)
# The uniform-divisor kernel's block: its per-lane work is half the
# two-plane kernel's, so the grid's per-step cost weighs more (a K-Means
# plane on a TPU v5e: 23.1 ms an iteration at (1024, 512), 27.7 at
# (256, 256), 23.6 at (512, 512)).
UNIFORM_BLOCK = (1024, 512)

# f32 hardware tile lattice: VREG lane width x sublane count. ops.py's
# shape-generic wrappers pad to the same lattice (imported from here).
SUBLANE = 8
LANE = 128


def _recip_kernel(x_ref, o_ref, *, table: SeedTable, n: int, schedule: str):
    o_ref[...] = common.recip_f32_bits(x_ref[...], table, n, schedule)


def _divide_kernel(a_ref, b_ref, o_ref, *, table: SeedTable, n: int, schedule: str):
    o_ref[...] = common.divide_f32_bits(a_ref[...], b_ref[...], table, n, schedule)


def _rsqrt_kernel(x_ref, o_ref, *, table: SeedTable, newton_iters: int):
    o_ref[...] = common.rsqrt_f32_bits(x_ref[...], table, newton_iters)


def _grid_spec(shape, block):
    bm, bn = min(block[0], shape[0]), min(block[1], shape[1])
    grid = (pl.cdiv(shape[0], bm), pl.cdiv(shape[1], bn))
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return grid, spec


def _tiled_grid_spec(shape, block):
    """2D grid over an arbitrary (M, N): blocks capped at the array but kept
    on the (8, 128) f32 tile lattice, ragged last tiles included.

    Unlike :func:`_grid_spec` (which assumes the wrappers pre-padded the
    operands to block multiples), this accepts any M, N >= 1: the grid is
    ``cdiv`` in both dims and the last row/column of blocks simply hangs off
    the array edge — Pallas pads the out-of-range reads and drops the
    out-of-range writes; the kernel masks the dead lanes (see
    ``_divide_tiled_kernel``) so no garbage operand ever enters the divide
    datapath.
    """
    bm = min(block[0], -(-shape[0] // SUBLANE) * SUBLANE)
    bn = min(block[1], -(-shape[1] // LANE) * LANE)
    grid = (pl.cdiv(shape[0], bm), pl.cdiv(shape[1], bn))
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return grid, spec, (bm, bn)


def _tile_valid_mask(shape, block):
    """(bm, bn) bool mask of lanes inside the (M, N) array for this tile."""
    i, j = pl.program_id(0), pl.program_id(1)
    bm, bn = block
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + i * bm
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + j * bn
    return (rows < shape[0]) & (cols < shape[1])


def _recip_tiled_kernel(x_ref, o_ref, *, table: SeedTable, n: int,
                        schedule: str, shape, block):
    """Fused reciprocal over one ragged (bm, bn) tile; dead lanes -> 1.0."""
    valid = _tile_valid_mask(shape, block)
    x = jnp.where(valid, x_ref[...], jnp.float32(1.0))
    o_ref[...] = common.recip_f32_bits(x, table, n, schedule)


def _rsqrt_tiled_kernel(x_ref, o_ref, *, table: SeedTable, newton_iters: int,
                        shape, block):
    """Fused full-edge rsqrt over one ragged (bm, bn) tile; dead lanes -> 1.0."""
    valid = _tile_valid_mask(shape, block)
    x = jnp.where(valid, x_ref[...], jnp.float32(1.0))
    o_ref[...] = common.rsqrt_f32_bits(x, table, newton_iters)


def _divide_tiled_kernel(a_ref, b_ref, o_ref, *, table: SeedTable, n: int,
                         schedule: str, shape, block):
    """Fused divide over one (bm, bn) tile of a ragged (M, N) operand pair.

    Lanes past the array edge (last-tile remainder rows/columns) are forced
    to the benign pair 1/1 before the datapath runs: the padded reads are
    implementation-defined, and while their quotients would be discarded on
    store anyway, masking keeps the kernel deterministic.
    """
    valid = _tile_valid_mask(shape, block)
    one = jnp.float32(1.0)
    a = jnp.where(valid, a_ref[...], one)
    b = jnp.where(valid, b_ref[...], one)
    o_ref[...] = common.divide_f32_bits(a, b, table, n, schedule)


def _divide_uniform_tiled_kernel(a_ref, b_ref, o_ref, *, table: SeedTable,
                                 n: int, schedule: str, block):
    """Fused divide of one (bm, bn) tile of a ragged (M, N) plane by the one
    divisor in the (1, 1) ``b_ref``.

    The divisor's half of the datapath (unpack, seed ladder, series or
    residual sequence, Veltkamp split, edge flags) runs once per tile on
    one vreg holding the divisor, tiled to one strip of SUBLANE rows
    (Mosaic broadcasts no (1, 1) integer array to a plane). The numerator's
    half then runs strip by strip, so that its intermediates stay in vector
    registers: whole-tile intermediates would spill. Lanes past the array
    edge are not masked: each lane's quotient depends on its own numerator
    alone, and theirs are dropped on store.
    """
    vreg = jnp.broadcast_to(b_ref[...], (SUBLANE, LANE))
    d = common.divisor_f32_bits(vreg, table, n, schedule)
    d = jax.tree.map(lambda v: jnp.tile(v, (1, block[1] // LANE)), d)

    def strip(i, carry):
        rows = pl.ds(pl.multiple_of(i * SUBLANE, SUBLANE), SUBLANE)
        o_ref[rows, :] = common.divide_by_f32_bits(a_ref[rows, :], d, schedule)
        return carry

    jax.lax.fori_loop(0, block[0] // SUBLANE, strip, 0)


@functools.partial(jax.jit, static_argnames=("n_iters", "precision_bits", "schedule",
                                             "block", "interpret"))
def tsdiv_recip_2d(x, *, n_iters: int = 2, precision_bits: int = 24,
                   schedule: str = "factored", block=DEFAULT_BLOCK,
                   interpret: bool = True):
    """Reciprocal of an f32 (M, N) array via the fused division-unit kernel."""
    table = compute_segments(n_iters, precision_bits)
    grid, spec = _grid_spec(x.shape, block)
    return pl.pallas_call(
        functools.partial(_recip_kernel, table=table, n=n_iters, schedule=schedule),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("newton_iters", "n_segments",
                                             "block", "interpret"))
def tsdiv_rsqrt_2d(x, *, newton_iters: int = 2, n_segments: int = 16,
                   block=DEFAULT_BLOCK, interpret: bool = True):
    """rsqrt of an f32 (M, N) array via the fused full-edge rsqrt kernel.

    The mode="taylor_pallas"/"goldschmidt_pallas" rsqrt datapath: PWL chord
    seed + Newton with the residual-compensated final step, FTZ edge
    contract in-kernel (``common.rsqrt_f32_bits``) — what
    ``kernels.ops.tsdiv_rsqrt`` launches for ``division_modes.rsqrt``.
    """
    from repro.core.seeds import rsqrt_seed_table

    table = rsqrt_seed_table(n_segments)
    grid, spec = _grid_spec(x.shape, block)
    return pl.pallas_call(
        functools.partial(_rsqrt_kernel, table=table, newton_iters=newton_iters),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("n_iters", "precision_bits", "schedule",
                                             "block", "interpret"))
def tsdiv_divide_2d(a, b, *, n_iters: int = 2, precision_bits: int = 24,
                    schedule: str = "factored", block=DEFAULT_BLOCK,
                    interpret: bool = True):
    """a / b elementwise: the fused exponent-separated divide datapath.

    schedule="goldschmidt" runs the joint N/D refinement in-kernel (the
    numerator rides the F-multiplies); the Taylor schedules run the mantissa
    series with the Markstein-corrected final multiply (Fig. 7's full-width
    multiplier). Either way the quotient is accurate wherever a/b is
    representable — no intermediate reciprocal to under/overflow.
    """
    table = compute_segments(n_iters, precision_bits)
    grid, spec = _grid_spec(a.shape, block)
    return pl.pallas_call(
        functools.partial(_divide_kernel, table=table, n=n_iters, schedule=schedule),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("n_iters", "precision_bits", "schedule",
                                             "block", "interpret"))
def tsdiv_divide_tiled_2d(a, b, *, n_iters: int = 2, precision_bits: int = 24,
                          schedule: str = "factored", block=None,
                          interpret: bool = True):
    """a / b over an arbitrary f32 (M, N) array — the streaming form.

    Same fused exponent-separated datapath as :func:`tsdiv_divide_2d`, but
    grid-scheduled directly over the native 2D layout: no flatten, no
    pre-padding copies. Large batched operands (distance matrices, centroid
    sums, whole activation planes) stream through VMEM one (bm, bn) tile at
    a time; non-multiple-of-block shapes are handled by ragged last tiles
    whose dead lanes are masked in-kernel. This is the path
    ``kernels.ops.tsdiv_divide`` takes for rank-2 operands.

    ``b`` is either a plane of ``a``'s shape or, for a plane divided by one
    number, a (1, 1) array (``kernels.ops.tsdiv_divide_uniform``): that
    divisor enters the kernel once, as a block whose index never moves, and
    its half of the datapath runs once per tile instead of once per lane.
    The quotients are the same bits as dividing by the broadcast plane.
    ``block`` defaults to ``DEFAULT_BLOCK`` for two planes and to
    ``UNIFORM_BLOCK`` for a (1, 1) divisor.
    """
    table = compute_segments(n_iters, precision_bits)
    uniform = b.shape == (1, 1) and a.shape != (1, 1)
    if block is None:
        block = UNIFORM_BLOCK if uniform else DEFAULT_BLOCK
    grid, spec, blk = _tiled_grid_spec(a.shape, block)
    if uniform:
        body = functools.partial(_divide_uniform_tiled_kernel, block=blk)
        b_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    else:
        body = functools.partial(_divide_tiled_kernel, shape=a.shape,
                                 block=blk)
        b_spec = spec
    return pl.pallas_call(
        functools.partial(body, table=table, n=n_iters, schedule=schedule),
        grid=grid,
        in_specs=[spec, b_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("n_iters", "precision_bits",
                                             "schedule", "block", "interpret"))
def tsdiv_recip_tiled_2d(x, *, n_iters: int = 2, precision_bits: int = 24,
                         schedule: str = "factored", block=DEFAULT_BLOCK,
                         interpret: bool = True):
    """Reciprocal over an arbitrary f32 (M, N) array — the streaming form.

    The unary twin of :func:`tsdiv_divide_tiled_2d`: grid-scheduled over the
    native layout with ragged last tiles masked in-kernel (dead lanes get the
    benign operand 1.0). This is what the mesh-aware dispatch launches per
    shard — the per-shard extents are whatever ``x.shape`` says, so ragged
    masking is automatically against *local* extents.
    """
    table = compute_segments(n_iters, precision_bits)
    grid, spec, blk = _tiled_grid_spec(x.shape, block)
    return pl.pallas_call(
        functools.partial(_recip_tiled_kernel, table=table, n=n_iters,
                          schedule=schedule, shape=x.shape, block=blk),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("newton_iters", "n_segments",
                                             "block", "interpret"))
def tsdiv_rsqrt_tiled_2d(x, *, newton_iters: int = 2, n_segments: int = 16,
                         block=DEFAULT_BLOCK, interpret: bool = True):
    """rsqrt over an arbitrary f32 (M, N) array — the streaming form.

    Same full-edge FTZ datapath as :func:`tsdiv_rsqrt_2d` but grid-scheduled
    directly over the native 2D layout with ragged last tiles masked
    in-kernel, so per-shard operands of any local extent launch without
    pre-padding copies. The mesh-aware rank >= 2 path of
    ``kernels.ops.tsdiv_rsqrt``.
    """
    from repro.core.seeds import rsqrt_seed_table

    table = rsqrt_seed_table(n_segments)
    grid, spec, blk = _tiled_grid_spec(x.shape, block)
    return pl.pallas_call(
        functools.partial(_rsqrt_tiled_kernel, table=table,
                          newton_iters=newton_iters, shape=x.shape, block=blk),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=interpret,
    )(x)
