"""QR decomposition via Givens rotations on the division unit.

QR is the second application the source paper names, and the Givens-rotation
unit of arXiv:2010.12376 (Hormigo & Muñoz — see PAPERS.md) is exactly a
hardware consumer of divide/rsqrt: zeroing entry (i, j) needs the rotation
coefficients

    r = sqrt(a^2 + b^2),   c = a / r,   s = b / r

with a = R[j, j], b = R[i, j]. Both evaluation strategies are offered, and
both route through :mod:`repro.core.division_modes`:

  * ``via="div"``   — r by square root, then the two quotients through
                      ``division_modes.div`` (two divides per rotation, the
                      source paper's unit on its headline op);
  * ``via="rsqrt"`` — one ``division_modes.rsqrt`` of a^2 + b^2, then two
                      multiplies (the Givens-unit formulation: division-free
                      at the cost of the rsqrt datapath).

The decomposition sweeps column by column, zeroing below-diagonal entries
with plane rotations applied to full rows (vectorized over N), accumulated
into an explicit Q. It is mode-agnostic: ``qr_givens(a, cfg=EXACT)`` is the
XLA-exact twin for accuracy deltas, and
:func:`repro.eval.workload_metrics.qr_residuals` turns (Q, R, A) into the
orthogonality / reconstruction / triangularity numbers recorded in
``BENCH_div.json``.
"""
from __future__ import annotations

import numpy as np

from repro.core import division_modes as dm

__all__ = ["givens_coeffs", "qr_givens", "qr_givens_batched",
           "qr_givens_sharded"]


def givens_coeffs(a, b, cfg: dm.DivisionConfig = dm.TAYLOR,
                  via: str = "div"):
    """Rotation coefficients (c, s) zeroing b against a; c^2 + s^2 = 1.

    The (a, b) = (0, 0) corner returns the identity rotation (c, s) = (1, 0)
    — the edge lanes of the division unit (0/0 -> nan, rsqrt(0) -> inf) are
    masked here, mirroring the special-value handling a hardware Givens unit
    wraps around its divider.

    The operands are pre-scaled by an exact power of two so a^2 + b^2 never
    under/overflows f32 while a and b are normal (the textbook safe-Givens
    scaling; (c, s) is 0-homogeneous in (a, b), so the scale cancels — a
    power of two keeps the scaling rounding-free, and the exponent shift is
    not a mantissa divide, so no division bypasses the unit).
    """
    import jax.numpy as jnp

    m = jnp.maximum(jnp.abs(a), jnp.abs(b))
    # floor's zero gradient makes inv a constant under autodiff — exactly
    # right, since (c, s) does not depend on the scale at all.
    e = jnp.clip(jnp.floor(jnp.log2(jnp.where(m > 0, m, 1.0))), -126.0, 126.0)
    inv = jnp.exp2(-e).astype(a.dtype)
    an, bn = a * inv, b * inv
    t = an * an + bn * bn                   # in [1, 8) whenever (a, b) != 0
    if via == "rsqrt":
        inv_r = dm.rsqrt(t, cfg)
        c, s = an * inv_r, bn * inv_r
    elif via == "div":
        r = jnp.sqrt(t)
        c, s = dm.div(an, r, cfg), dm.div(bn, r, cfg)
    else:
        raise ValueError(f"via must be 'div' or 'rsqrt', got {via!r}")
    safe = m > 0
    c = jnp.where(safe, c, jnp.ones_like(c))
    s = jnp.where(safe, s, jnp.zeros_like(s))
    return c, s


def _rotation_schedule(m: int, n: int):
    """Static (j, i) pairs: for each column j, zero rows j+1..m-1."""
    jj, ii = [], []
    for j in range(min(m - 1, n)):
        for i in range(j + 1, m):
            jj.append(j)
            ii.append(i)
    return np.asarray(jj, np.int32), np.asarray(ii, np.int32)


def qr_givens(a, cfg: dm.DivisionConfig = dm.TAYLOR, *, via: str = "div"):
    """Full QR of an (M, N) matrix, M >= 1: returns (Q, R) with A = Q @ R.

    Q is (M, M) orthogonal (a product of plane rotations), R is (M, N) with
    below-diagonal entries annihilated to the working precision — they are
    returned as computed (order-ulp residues, not hard zeros) so the
    delivered accuracy of the division mode is visible in the triangularity
    residual rather than masked by a ``triu``.

    The rotation sequence is data-independent (column-major, top-down), so
    the whole decomposition is one ``fori_loop`` over a static schedule:
    each step computes (c, s) through the configured division mode and
    applies the rotation to full rows of R and Q^T (vectorized over N).
    """
    import jax
    import jax.numpy as jnp

    a = jnp.asarray(a)
    if a.ndim != 2:
        raise ValueError(f"qr_givens expects a 2D matrix, got shape {a.shape}")
    m, n = a.shape
    r = a
    qt = jnp.eye(m, dtype=a.dtype)
    jj, ii = _rotation_schedule(m, n)
    if len(jj) == 0:
        return qt.T, r
    jj, ii = jnp.asarray(jj), jnp.asarray(ii)

    def body(k, carry):
        qt, r = carry
        j, i = jj[k], ii[k]
        rj, ri = r[j], r[i]
        c, s = givens_coeffs(rj[j], ri[j], cfg, via)
        r = r.at[j].set(c * rj + s * ri).at[i].set(c * ri - s * rj)
        qj, qi = qt[j], qt[i]
        qt = qt.at[j].set(c * qj + s * qi).at[i].set(c * qi - s * qj)
        return qt, r

    qt, r = jax.lax.fori_loop(0, len(jj), body, (qt, r))
    return qt.T, r


def qr_givens_batched(a, cfg: dm.DivisionConfig = dm.TAYLOR, *,
                      via: str = "div"):
    """QR of a batch of matrices: (..., M, N) -> (Q (..., M, M), R (..., M, N)).

    vmap over the flattened leading dims — the rotation schedule is static,
    so every batch member shares one trace and the per-rotation divides
    vectorize across the batch.
    """
    import jax
    import jax.numpy as jnp

    a = jnp.asarray(a)
    if a.ndim < 2:
        raise ValueError(f"qr_givens_batched expects (..., M, N), got {a.shape}")
    if a.ndim == 2:
        return qr_givens(a, cfg, via=via)
    lead = a.shape[:-2]
    a3 = a.reshape((-1,) + a.shape[-2:])
    q3, r3 = jax.vmap(lambda mat: qr_givens(mat, cfg, via=via))(a3)
    return (q3.reshape(lead + q3.shape[-2:]),
            r3.reshape(lead + r3.shape[-2:]))


def qr_givens_sharded(a, cfg: dm.DivisionConfig = dm.TAYLOR, *,
                      via: str = "div"):
    """Batched Givens QR with the batch dim sharded over the active mesh.

    ``a`` is (B, M, N); the batch shards over the largest divisible prefix of
    ('pod','data') (``rules.batch_partition``) and each device decomposes its
    resident matrices with :func:`qr_givens_batched`. The rotations are
    entirely intra-matrix, so there is nothing to reduce across the mesh —
    sharded QR is bit-identical to the batched single-device run. Division
    sites run under ``rules.suspend_mesh()`` (the body is already inside a
    shard_map). Falls back to :func:`qr_givens_batched` when no mesh is
    active or no batch-axis prefix divides B.
    """
    import jax.numpy as jnp
    from repro.sharding import rules as shr

    a = jnp.asarray(a)
    if a.ndim != 3:
        raise ValueError(f"qr_givens_sharded wants (B, M, N), got {a.shape}")
    mesh = shr.active_mesh()
    axes = shr.batch_partition(mesh, a.shape[0]) if mesh is not None else ()
    n_shards = 1
    for ax in axes:
        n_shards *= mesh.shape[ax]
    if n_shards <= 1:
        return qr_givens_batched(a, cfg, via=via)

    import jax
    from jax.sharding import PartitionSpec as P

    def body(al):
        with shr.suspend_mesh():
            return qr_givens_batched(al, cfg, via=via)

    spec = P(axes, None, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                         out_specs=(spec, spec), check_vma=False)(a)
