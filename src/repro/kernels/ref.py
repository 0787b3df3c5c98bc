"""Pure-jnp oracles for every Pallas kernel (no pallas imports).

Two tiers per kernel:
  * ``*_ref``   — same algorithm, pure jnp (bit-comparable with the kernel);
  * ``*_exact`` — the mathematically exact op (what eq. 17 bounds against).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ilm as ilm_core
from repro.core.seeds import compute_segments, rsqrt_seed_table
from . import common


def tsdiv_recip_ref(x, *, n_iters: int = 2, precision_bits: int = 24,
                    schedule: str = "factored"):
    table = compute_segments(n_iters, precision_bits)
    return common.recip_f32_bits(x.astype(jnp.float32), table, n_iters, schedule)


def tsdiv_recip_exact(x):
    return 1.0 / x.astype(jnp.float32)


def tsdiv_divide_ref(a, b, *, n_iters: int = 2, precision_bits: int = 24,
                     schedule: str = "factored"):
    table = compute_segments(n_iters, precision_bits)
    return common.divide_f32_bits(a.astype(jnp.float32), b.astype(jnp.float32),
                                  table, n_iters, schedule)


def tsdiv_divide_exact(a, b):
    return a.astype(jnp.float32) / b.astype(jnp.float32)


def rmsnorm_ref(x, w, *, eps: float = 1e-6, newton_iters: int = 2,
                n_segments: int = 16, d_real: int | None = None):
    xf = x.astype(jnp.float32)
    d = xf.shape[-1] if d_real is None else d_real
    ss = jnp.sum(xf * xf, axis=-1, keepdims=True) / d
    se = ss + jnp.float32(eps)
    r = common.rsqrt_f32(se, rsqrt_seed_table(n_segments), newton_iters)
    # same row edge classes as the kernel: nan propagates, inf scales by 0
    r = jnp.where(jnp.isinf(se), jnp.float32(0.0), r)
    r = jnp.where(jnp.isnan(se), jnp.float32(jnp.nan), r)
    return (xf * r * w.astype(jnp.float32)).astype(x.dtype)


def rmsnorm_exact(x, w, *, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    ss = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ss + eps) * w.astype(jnp.float32)).astype(x.dtype)


def softmax_ref(x, *, n_iters: int = 2, precision_bits: int = 24,
                schedule: str = "factored"):
    xf = x.astype(jnp.float32)
    xmax = jnp.max(xf, axis=-1, keepdims=True)
    ex = jnp.exp(xf - xmax)
    s = common.tree_sum(ex)
    table = compute_segments(n_iters, precision_bits)
    return (ex * common.recip_f32_bits(s, table, n_iters, schedule)).astype(x.dtype)


def softmax_exact(x):
    return jax.nn.softmax(x.astype(jnp.float32), axis=-1).astype(x.dtype)


def flash_attention_exact(q, k, v, *, causal: bool = True):
    """Plain softmax attention oracle. q/k/v: (BH, S, hd)."""
    import math

    hd = q.shape[-1]
    s = jnp.einsum("bqh,bkh->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkh->bqh", p, v.astype(jnp.float32)).astype(q.dtype)


def ilm_mul_ref(a, b, *, iters: int = 16):
    return ilm_core.ilm_mul(a, b, iters)


def ilm_mul_exact(a, b):
    return (a.astype(jnp.uint32) * b.astype(jnp.uint32))


def ilm_square_ref(a, *, iters: int = 16):
    return ilm_core.ilm_square(a, iters)


def ilm_square_exact(a):
    a = a.astype(jnp.uint32)
    return a * a
