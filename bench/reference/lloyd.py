"""Plain float32 Lloyd's K-Means: the reference that decides ``correct``.

Imports nothing of the program. Semantics follow the K-Means the program
states: a point goes to the centroid of least mean squared distance
``||x - c||^2 / D`` (first index on ties), a centroid moves to the mean of
its points with IEEE division, an empty cluster keeps its centroid, and
after ``n_iters`` updates the assignment and the inertia (the mean over
points of the least mean squared distance) are taken under the final
centroids.

Departures from a textbook Lloyd, each the program's as stated:
- distances are expanded as ``x.x - 2 x.c + c.c`` and clipped at 0 (the
  direct form would need an (N, K, D) tensor);
- the divide by D before the argmin, which a textbook Lloyd leaves out (it
  moves no argmin exactly, but rounds).

Rows are processed in blocks so that the (rows, K) plane fits beside the
points. ``dot`` names how matrix products are computed: ``"highest"`` (the
reference, f32 products) or ``"bf16x3"`` (the control: three bf16 passes,
which is what precision ``high`` does on a TPU, written out so that it
means the same on every backend).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK_ROWS = 1 << 17


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def matmul(a, b, dot: str):
    """a @ b for f32 (M, K) x (K, N) at the named precision."""
    if dot == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    if dot == "bf16x3":
        ah, al = _split_bf16(a)
        bh, bl = _split_bf16(b)

        def f(u, v):
            return jnp.dot(u, v, preferred_element_type=jnp.float32)

        return f(ah, bh) + (f(ah, bl) + f(al, bh))
    raise ValueError(f"unknown dot {dot!r}")


@functools.partial(jax.jit, static_argnames=("dot",))
def _block(xb, n_valid, c, *, dot: str):
    """Assignment, summed least distance, per-cluster sums and counts of
    the first ``n_valid`` rows of the block ``xb``."""
    d = xb.shape[1]
    k = c.shape[0]
    x2 = jnp.sum(xb * xb, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)[None, :]
    d2 = jnp.maximum(x2 - 2.0 * matmul(xb, c.T, dot) + c2, 0.0)
    d2 = d2 / jnp.float32(d)
    assign = jnp.argmin(d2, axis=1).astype(jnp.int32)
    valid = jnp.arange(xb.shape[0]) < n_valid
    least = jnp.where(valid, jnp.min(d2, axis=1), 0.0)
    onehot = ((assign[:, None] == jnp.arange(k)[None, :])
              & valid[:, None]).astype(jnp.float32)
    sums = matmul(onehot.T, xb, dot)
    counts = jnp.sum(onehot, axis=0)
    return assign, jnp.sum(least), sums, counts


def _pass(x, c, dot: str, block_rows: int):
    n = x.shape[0]
    sums = counts = total = None
    assigns = []
    for start in range(0, n, block_rows):
        xb = x[start:start + block_rows]
        n_valid = xb.shape[0]
        if n_valid < block_rows and n > block_rows:
            xb = jnp.pad(xb, ((0, block_rows - n_valid), (0, 0)))
        a, t, s, cnt = _block(xb, n_valid, c, dot=dot)
        assigns.append(a[:n_valid])
        sums = s if sums is None else sums + s
        counts = cnt if counts is None else counts + cnt
        total = t if total is None else total + t
    return jnp.concatenate(assigns), total, sums, counts


def lloyd(x, c, n_iters: int, *, dot: str = "highest",
          block_rows: int = BLOCK_ROWS):
    """``n_iters`` Lloyd updates from centroids ``c`` over points ``x``
    (N, D); returns (centroids, assignments, inertia)."""
    x = jnp.asarray(x, jnp.float32)
    c = jnp.asarray(c, jnp.float32)
    n = x.shape[0]
    for _ in range(n_iters):
        _, _, sums, counts = _pass(x, c, dot, block_rows)
        means = sums / jnp.maximum(counts, 1.0)[:, None]
        c = jnp.where((counts > 0)[:, None], means, c)
    assign, total, _, _ = _pass(x, c, dot, block_rows)
    return c, assign, total / jnp.float32(n)
