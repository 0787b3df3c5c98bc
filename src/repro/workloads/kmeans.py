"""Batched K-Means (Lloyd) with every divide routed through the division unit.

K-Means is one of the two applications the source paper names as unlocked by
a cheap divider. Lloyd's algorithm has two division sites per iteration, and
both go through :mod:`repro.core.division_modes` here:

  1. **Assignment distances** — points are assigned by *mean* squared
     distance ``||x - c||^2 / D`` (the per-dimension normalization keeps the
     distance scale D-independent); the ``1/D`` is a batched divide over the
     whole (N, K) distance plane, which the Pallas modes stream through the
     tiled fused kernel.
  2. **Centroid update** — ``c_k = sum(x_i in k) / count_k``, a batched
     (K, D) / (K, 1) divide. Empty clusters keep their previous centroid
     (the divide's inf/nan lanes are masked out, as hardware FTZ would).

The inertia (mean within-cluster squared distance) is itself divided through
the unit, so the reported objective carries the mode's error signature too.

Everything is mode-agnostic: ``kmeans(x, k, cfg=EXACT)`` is the XLA-exact
twin of ``kmeans(x, k, cfg=DivisionConfig(mode="taylor"))`` on identical
inits, and :func:`repro.eval.workload_metrics.relative_delta` turns the two
inertias into the workload-level accuracy number recorded in
``BENCH_div.json``.

Supports leading batch dimensions: ``x`` of shape (..., N, D) clusters each
batch member independently (one shared init per call).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.core import division_modes as dm

__all__ = ["KMeansResult", "kmeans", "kmeans_sharded", "lloyd_step",
           "pairwise_mean_sqdist", "make_blobs"]


@dataclasses.dataclass(frozen=True)
class KMeansResult:
    """Outcome of a Lloyd run.

    centroids:     (..., K, D) final centroids.
    assignments:   (..., N) int32 cluster index per point (final centroids).
    inertia:       (...,) mean min squared distance under the final centroids.
    inertia_trace: (n_iters, ...) inertia before each update step — the
                   convergence curve, one entry per Lloyd iteration.
    """

    centroids: "object"
    assignments: "object"
    inertia: "object"
    inertia_trace: "object"


# f32 products at f32 precision on every backend: a TPU's default f32 dot
# rounds its operands to bf16, which would move distances and centroid sums
# by ~1e-3 relative, far more than the division unit under test. The CPU
# backend computes f32 dots in f32 either way.
_F32_DOT = "highest"


def pairwise_mean_sqdist(x, c, cfg: dm.DivisionConfig = dm.TAYLOR):
    """Mean squared distance plane ||x_n - c_k||^2 / D, shape (..., N, K).

    Expanded as x.x - 2 x.c + c.c (one einsum feeds the MXU on TPU); the
    1/D normalizer is the assignment-side division site and goes through
    ``division_modes.div`` — for the Pallas modes the whole (N, K) plane
    streams through the tiled fused divide kernel.
    """
    import jax.numpy as jnp

    x2 = jnp.sum(x * x, axis=-1)[..., :, None]
    c2 = jnp.sum(c * c, axis=-1)[..., None, :]
    xc = jnp.einsum("...nd,...kd->...nk", x, c, precision=_F32_DOT)
    d2 = jnp.maximum(x2 - 2.0 * xc + c2, 0.0)
    return dm.div(d2, jnp.asarray(x.shape[-1], x.dtype), cfg)


def _assign_and_inertia(x, c, cfg: dm.DivisionConfig):
    """Assignment + mean inertia under fixed centroids (no update)."""
    import jax.numpy as jnp

    d2 = pairwise_mean_sqdist(x, c, cfg)
    assign = jnp.argmin(d2, axis=-1)
    n_pts = jnp.asarray(x.shape[-2], x.dtype)
    inertia = dm.div(jnp.sum(jnp.min(d2, axis=-1), axis=-1), n_pts, cfg)
    return d2, assign, inertia


# Canonical accumulation blocking for the (N, K) x (N, D) centroid sums.
# Both the single-device and the sharded path reduce the same 8 row-major
# block partials in the same left-to-right order, so sharding cannot move
# the centroid sums by more than per-block matmul scheduling noise (the
# sums are f32; one global einsum vs a psum tree would differ by several
# ulps at N ~ 10^6 — see docs/numerics.md).
_SUM_BLOCKS = 8


def _block_cluster_sums(onehot, x, n_blocks: int):
    """(n_blocks, K, D) per-cluster sums over row-major row blocks."""
    import jax.numpy as jnp

    parts = [jnp.einsum("nk,nd->kd", o, b, precision=_F32_DOT)
             for o, b in zip(jnp.split(onehot, n_blocks, axis=0),
                             jnp.split(x, n_blocks, axis=0))]
    return jnp.stack(parts)


def _ordered_block_sum(stacked):
    """Left-to-right sum over the leading axis — one fixed reduction order."""
    out = stacked[0]
    for i in range(1, stacked.shape[0]):
        out = out + stacked[i]
    return out


def _cluster_sums(onehot, x):
    """Per-cluster coordinate sums, (..., K, D), canonical order when 2D."""
    import jax.numpy as jnp

    if x.ndim == 2 and x.shape[0] % _SUM_BLOCKS == 0:
        return _ordered_block_sum(_block_cluster_sums(onehot, x, _SUM_BLOCKS))
    return jnp.einsum("...nk,...nd->...kd", onehot, x, precision=_F32_DOT)


def lloyd_step(x, c, cfg: dm.DivisionConfig = dm.TAYLOR):
    """One Lloyd iteration: assign, update centroids, measure inertia.

    Returns ``(new_centroids, assignments, inertia)`` where inertia is
    measured *before* the update (the objective the assignment minimized).
    """
    import jax
    import jax.numpy as jnp

    k = c.shape[-2]
    d2, assign, inertia = _assign_and_inertia(x, c, cfg)
    onehot = jax.nn.one_hot(assign, k, dtype=x.dtype)        # (..., N, K)
    counts = jnp.sum(onehot, axis=-2)                        # (..., K)
    sums = _cluster_sums(onehot, x)                          # (..., K, D)
    # Empty clusters: divide by max(count, 1) — not by the raw count — so
    # the 0/0 lane never exists even in exact mode, whose d(a/b) = 1/b
    # cotangent would turn into 0 * inf = nan under the where mask below
    # (the approximate modes survive via attach_grad's finite-lane masking,
    # exact mode has no such guard). The masked lanes keep the previous
    # centroid — the workload-level analogue of the FTZ edge contract.
    occupied = counts[..., :, None] > 0
    new_c = dm.div(sums, jnp.maximum(counts, 1)[..., :, None], cfg)
    new_c = jnp.where(occupied, new_c, c)
    return new_c, assign, inertia


def kmeans(x, k: Optional[int] = None, *, cfg: dm.DivisionConfig = dm.TAYLOR,
           n_iters: int = 10, init=None, key=None) -> KMeansResult:
    """Run ``n_iters`` Lloyd iterations of K-Means on ``x`` (..., N, D).

    ``init`` (shape (..., K, D)) pins the starting centroids — pass the same
    init to two modes to measure the division unit's effect in isolation.
    Without it, ``k`` distinct points are drawn with ``key``
    (default PRNGKey(0)); the draw is shared across leading batch dims.
    """
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(x)
    if init is None:
        if k is None:
            raise ValueError("pass k or an explicit init")
        if key is None:
            key = jax.random.PRNGKey(0)
        idx = jax.random.choice(key, x.shape[-2], (k,), replace=False)
        init = jnp.take(x, idx, axis=-2)
    else:
        init = jnp.asarray(init, x.dtype)
        if k is not None and k != init.shape[-2]:
            raise ValueError(f"k={k} != init.shape[-2]={init.shape[-2]}")
    # One centroid set per batch member (a shared init broadcasts up front so
    # the scan carry keeps a fixed shape).
    init = jnp.broadcast_to(init, x.shape[:-2] + init.shape[-2:])

    def step(c, _):
        new_c, _, inertia = lloyd_step(x, c, cfg)
        return new_c, inertia

    centroids, trace = jax.lax.scan(step, init, None, length=n_iters)
    # Final assignment/inertia under the converged centroids — evaluation
    # only, no discarded centroid update.
    _, assign, inertia = _assign_and_inertia(x, centroids, cfg)
    return KMeansResult(centroids=centroids, assignments=assign,
                        inertia=inertia, inertia_trace=trace)


def kmeans_sharded(x, k: Optional[int] = None, *,
                   cfg: dm.DivisionConfig = dm.TAYLOR, n_iters: int = 10,
                   init=None, key=None) -> KMeansResult:
    """Data-parallel Lloyd over the active mesh: production-scale K-Means.

    ``x`` must be (N, D); points shard over the batch axes (the largest
    divisible prefix of ('pod','data'), see ``rules.batch_partition``) and
    centroids replicate. Each iteration runs the assignment on resident
    points only, then ``psum``s the per-cluster sums *and* counts across the
    mesh **before** the centroid divide — so the division unit consumes
    globally-reduced operands and empty-cluster masking sees global counts
    (a locally-empty cluster is not an empty cluster). The per-point
    assignment distances are elementwise in N, so assignments match the
    unsharded run bit-for-bit; the centroid sums are reduced tree-wise by
    ``psum`` rather than in one row-major einsum, which can move the last
    bit (see docs/numerics.md) — hence the <= 1 int ulp centroid gate in
    tests/test_sharded_kernels.py.

    Division sites inside the body run under ``rules.suspend_mesh()`` so the
    mesh-aware kernel dispatch never nests a second shard_map. Falls back to
    plain :func:`kmeans` when no mesh is active or no batch-axis prefix
    divides N.
    """
    import jax
    import jax.numpy as jnp
    from repro.sharding import rules as shr

    x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"kmeans_sharded wants (N, D) points, got {x.shape}")
    mesh = shr.active_mesh()
    axes = shr.batch_partition(mesh, x.shape[0]) if mesh is not None else ()
    n_shards = 1
    for ax in axes:
        n_shards *= mesh.shape[ax]
    if n_shards <= 1:
        return kmeans(x, k, cfg=cfg, n_iters=n_iters, init=init, key=key)

    if init is None:
        if k is None:
            raise ValueError("pass k or an explicit init")
        if key is None:
            key = jax.random.PRNGKey(0)
        idx = jax.random.choice(key, x.shape[0], (k,), replace=False)
        init = jnp.take(x, idx, axis=0)
    else:
        init = jnp.asarray(init, x.dtype)
        if k is not None and k != init.shape[-2]:
            raise ValueError(f"k={k} != init.shape[-2]={init.shape[-2]}")
    kk = init.shape[-2]
    n_total = jnp.asarray(x.shape[0], x.dtype)
    # When the canonical _SUM_BLOCKS blocking aligns with the shard layout,
    # each shard contributes whole blocks and the partials are combined in
    # the same left-to-right order as the single-device _cluster_sums —
    # that is what makes the <= 1 ulp centroid gate hold at 10^6 points.
    blocked = (x.shape[0] % _SUM_BLOCKS == 0
               and _SUM_BLOCKS % n_shards == 0)

    from jax.sharding import PartitionSpec as P

    def body(xl, c0):
        # xl: (N / n_shards, D) resident points; c0: replicated (K, D).
        with shr.suspend_mesh():
            def step(c, _):
                d2 = pairwise_mean_sqdist(xl, c, cfg)
                assign = jnp.argmin(d2, axis=-1)
                onehot = jax.nn.one_hot(assign, kk, dtype=xl.dtype)
                # Global reduction BEFORE the divide: the unit sees the
                # whole cluster's sum/count, not a shard's slice of it.
                # Counts are integer-valued f32 (exact up to 2^24), so the
                # psum order cannot move them; the sums are reduced in the
                # canonical block order when the layout allows (an
                # order-fixed psum: gather the block partials in shard
                # order, then one left-to-right sum on every device).
                counts = jax.lax.psum(jnp.sum(onehot, axis=-2), axes)
                if blocked:
                    parts = _block_cluster_sums(
                        onehot, xl, _SUM_BLOCKS // n_shards)
                    parts = jax.lax.all_gather(parts, axes, axis=0,
                                               tiled=True)
                    sums = _ordered_block_sum(parts)
                else:
                    sums = jax.lax.psum(
                        jnp.einsum("nk,nd->kd", onehot, xl,
                                   precision=_F32_DOT), axes)
                inertia = dm.div(
                    jax.lax.psum(jnp.sum(jnp.min(d2, axis=-1)), axes),
                    n_total, cfg)
                occupied = counts[:, None] > 0
                new_c = dm.div(sums, jnp.maximum(counts, 1)[:, None], cfg)
                new_c = jnp.where(occupied, new_c, c)
                return new_c, inertia

            centroids, trace = jax.lax.scan(step, c0, None, length=n_iters)
            d2 = pairwise_mean_sqdist(xl, centroids, cfg)
            assign = jnp.argmin(d2, axis=-1)
            inertia = dm.div(
                jax.lax.psum(jnp.sum(jnp.min(d2, axis=-1)), axes),
                n_total, cfg)
        return centroids, assign, inertia, trace

    pts = P(axes, None)
    run = jax.shard_map(
        body, mesh=mesh, in_specs=(pts, P()),
        # Everything but the assignments is psum-replicated across the mesh.
        out_specs=(P(), P(axes), P(), P()), check_vma=False)
    centroids, assign, inertia, trace = run(x, init)
    return KMeansResult(centroids=centroids, assignments=assign,
                        inertia=inertia, inertia_trace=trace)


def make_blobs(key, n: int, d: int, k: int, *, spread: float = 0.15,
               dtype=None):
    """Gaussian blob mixture for tests/benchmarks: (n, d) points, k centers.

    Centers are drawn uniform in [-1, 1]^d and points jittered around them
    with stddev ``spread`` — separated enough that all modes should agree on
    the clustering, close enough that near-boundary points exercise the
    divide's low bits.
    """
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    kc, kp, kj = jax.random.split(key, 3)
    centers = jax.random.uniform(kc, (k, d), dtype, -1.0, 1.0)
    which = jax.random.randint(kp, (n,), 0, k)
    noise = spread * jax.random.normal(kj, (n, d), dtype)
    return jnp.take(centers, which, axis=0) + noise
