"""Shared kernel-body math: traceable inside Pallas kernels and in ref oracles.

Everything here is straight-line jnp on values already resident in VMEM —
no gathers (the PWL "ROM" is a compare/select ladder over compile-time
constants, which vectorizes perfectly on the VPU), no data-dependent shapes.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.seeds import SeedTable, compute_segments, rsqrt_seed_table

# One source of truth for the f32 field layout: the jnp twins' bit-level
# datapath (core/fpparts.py) and these kernel bodies must stay aligned
# field-for-field — the underflow="ftz" twins are pinned bit-identical to
# the fused kernels by tests/test_underflow_policy.py.
from repro.core.fpparts import (  # noqa: F401  (re-exported kernel-side)
    F32_SIGN, F32_MAG_MASK, F32_EXP_MASK, F32_MAN_MASK, F32_ONE_BITS,
    F32_IMPLICIT, tree_sum,
)


def seed_ladder(man: jax.Array, table: SeedTable) -> jax.Array:
    """PWL seed via compare/select ladder (the hardware LUT, vectorized).

    man must lie in [table.boundaries[0], table.boundaries[-1])."""
    slopes = table.slopes.astype(np.float32)
    intercepts = table.intercepts.astype(np.float32)
    y0 = slopes[0] * man + intercepts[0]
    for i, b in enumerate(table.inner_boundaries.astype(np.float32)):
        y0 = jnp.where(man >= b, slopes[i + 1] * man + intercepts[i + 1], y0)
    return y0


def series_refine(y0: jax.Array, man: jax.Array, n: int, schedule: str) -> jax.Array:
    """y0 * sum m^k with m = 1 - man*y0 (paper eq. 11), unrolled at trace time.

    The residual m is computed at full seed-product width (Dekker two-product,
    see taylor.exact_residual) and the series is accumulated without the
    leading 1 — together these keep the fused kernel within ~1 ulp of the
    exact reciprocal at the f32 operating point (n=2, 24-bit table).

    schedule="goldschmidt" runs the Goldschmidt residual-register recurrence
    (N += N*r; r *= r) instead of explicit powering — iters_for_terms(n)
    iterations cover the same series terms as the factored schedule.
    """
    from repro.core.taylor import exact_residual, series_sum

    if n <= 0:
        return y0
    if schedule == "goldschmidt":
        from repro.core.goldschmidt import _refine, iters_for_terms

        return _refine(y0, man, y0, iters_for_terms(n))
    return y0 + y0 * series_sum(jnp, exact_residual(man, y0), n, schedule)


def recip_f32_bits(x: jax.Array, table: SeedTable, n: int, schedule: str) -> jax.Array:
    """Full f32 reciprocal with explicit bit-level unpack/repack.

    This is the hardware datapath: sign/exponent/mantissa split, PWL seed on
    the mantissa in [1,2), series refinement, exponent negation by biased-
    exponent arithmetic. Denormal inputs flush to +-inf (treated as zero);
    reciprocals that would be denormal flush to +-0 — standard FTZ semantics
    of fast hardware dividers.
    """
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = bits & F32_SIGN
    exp = (bits >> 23) & jnp.uint32(0xFF)
    man_bits = bits & F32_MAN_MASK
    man = jax.lax.bitcast_convert_type(man_bits | F32_ONE_BITS, jnp.float32)
    rman = series_refine(seed_ladder(man, table), man, n, schedule)  # (0.5, 1]
    # 2^-(exp-127) has biased exponent 254-exp; clamp into the normal range.
    # The clamp runs in int32: Mosaic has no unsigned min/max. exp 0 and
    # exp 255 lanes (the only ones the clamp touches) are overwritten by the
    # edge selects below, so the unsigned and signed forms agree bit for bit.
    scale_exp = jnp.clip(254 - exp.astype(jnp.int32), 0, 254).astype(jnp.uint32)
    scale = jax.lax.bitcast_convert_type(scale_exp << 23, jnp.float32)
    r = rman * scale
    # Edges: zero/denormal -> inf; inf -> 0; nan -> nan.
    r = jnp.where(exp == 0, jnp.float32(np.inf), r)
    r = jnp.where((exp == 255) & (man_bits == 0), jnp.float32(0.0), r)
    rbits = jax.lax.bitcast_convert_type(r, jnp.uint32) | sign
    r = jax.lax.bitcast_convert_type(rbits, jnp.float32)
    return jnp.where((exp == 255) & (man_bits != 0), jnp.float32(np.nan), r)


def _pow2(k: jax.Array) -> jax.Array:
    """2^k for int32 k in [-126, 127], built by biased-exponent bitcast."""
    return jax.lax.bitcast_convert_type(
        (jnp.clip(k + 127, 1, 254).astype(jnp.uint32)) << 23, jnp.float32)


def divide_f32_bits(a: jax.Array, b: jax.Array, table: SeedTable, n: int,
                    schedule: str) -> jax.Array:
    """Fused exponent-separated a/b: the full divide datapath in one kernel.

    Sign xor, biased-exponent subtract, mantissa pair in [1, 2), then either
    the joint N/D Goldschmidt recurrence (schedule="goldschmidt": the
    numerator mantissa rides the F-multiplies, arXiv:1909.10154) or the
    Taylor series reciprocal with the Markstein-corrected final multiply
    (fpparts.refine_quotient — the full-width final multiplier of Fig. 7).
    The exponent difference is applied in two power-of-two multiplies so the
    intermediate scale never under/overflows while a/b is representable.
    FTZ semantics as elsewhere: denormal operands are treated as zeros and
    denormal quotients flush to +-0.
    """
    from repro.core import fpparts

    abits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bbits = jax.lax.bitcast_convert_type(b, jnp.uint32)
    sign = (abits ^ bbits) & F32_SIGN
    ea = ((abits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)
    eb = ((bbits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)
    amant = abits & F32_MAN_MASK
    bmant = bbits & F32_MAN_MASK
    man_a = jax.lax.bitcast_convert_type(amant | F32_ONE_BITS, jnp.float32)
    man_b = jax.lax.bitcast_convert_type(bmant | F32_ONE_BITS, jnp.float32)
    y0 = seed_ladder(man_b, table)
    if schedule == "goldschmidt":
        from repro.core.goldschmidt import _refine, iters_for_terms

        q_man = _refine(man_a * y0, man_b, y0, iters_for_terms(n))
    else:
        rman = series_refine(y0, man_b, n, schedule)
        q_man = fpparts.refine_quotient(man_a * rman, man_a, man_b, rman)
    # q = q_man * 2^(ea-eb), split so each factor is a normal power of two.
    de = ea - eb                                    # biased diff == unbiased diff
    h = de >> 1
    q = (q_man * _pow2(h)) * _pow2(de - h)
    # FTZ: quotients below the normal range flush to zero (sign added below).
    q = jnp.where(jnp.abs(q) < jnp.float32(2.0 ** -126), jnp.float32(0.0), q)
    # Edge classes on the FTZ'd operands: exp 0 => zero, exp 255 => inf/nan.
    a_zero, b_zero = ea == 0, eb == 0
    a_inf = (ea == 255) & (amant == 0)
    b_inf = (eb == 255) & (bmant == 0)
    q = jnp.where(b_zero, jnp.float32(np.inf), q)            # x/0 -> inf
    q = jnp.where(a_zero, jnp.float32(0.0), q)               # 0/x -> 0
    q = jnp.where(a_inf, jnp.float32(np.inf), q)             # inf/x -> inf
    q = jnp.where(b_inf, jnp.float32(0.0), q)                # x/inf -> 0
    q = jnp.where(a_zero & b_zero, jnp.float32(np.nan), q)   # 0/0
    q = jnp.where(a_inf & b_inf, jnp.float32(np.nan), q)     # inf/inf
    qbits = jax.lax.bitcast_convert_type(q, jnp.uint32) | sign
    q = jax.lax.bitcast_convert_type(qbits, jnp.float32)
    a_nan = (ea == 255) & (amant != 0)
    b_nan = (eb == 255) & (bmant != 0)
    return jnp.where(a_nan | b_nan, jnp.float32(np.nan), q)


def rsqrt_f32_bits(x: jax.Array, table: SeedTable, newton_iters: int) -> jax.Array:
    """Full f32 rsqrt with explicit bit-level unpack and the IEEE edge
    contract — the fused-kernel twin of ``core.taylor._rsqrt_bits``.

    Same datapath as :func:`rsqrt_f32` (even/odd exponent split onto one
    seed octave, PWL chord seed, Newton with the residual-compensated final
    step) but classification is bit tests and every edge class is handled:
    FTZ semantics as everywhere in the kernels — a zero exponent field
    (zero or subnormal) is the zero class -> signed inf; +inf -> +0;
    negative operands (including -inf) and nans -> nan. Bit-identical to
    the jnp twin under ``underflow="ftz"`` (the seed ladder selects the
    same segment the jnp ``take`` does, and the Newton arithmetic is
    shared).
    """
    from repro.core.taylor import _newton_rsqrt

    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = bits & F32_SIGN
    mag = bits & F32_MAG_MASK
    exp = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)
    man_bits = bits & F32_MAN_MASK
    x_zero = exp == 0                       # FTZ: zero/subnormal class
    x_inf = mag == F32_EXP_MASK
    x_nan = mag > F32_EXP_MASK
    man = jax.lax.bitcast_convert_type(man_bits | F32_ONE_BITS, jnp.float32)
    ef = exp - 127 + 1                      # frexp convention: |x| = (man/2)*2^ef
    s = ef >> 1                             # floor(ef / 2)
    odd = ef - 2 * s                        # 0 or 1
    u = jnp.where(odd == 1, man, man * jnp.float32(0.5))   # in [0.5, 2)
    y = _newton_rsqrt(u, seed_ladder(u, table), newton_iters)
    pw = jax.lax.bitcast_convert_type(
        jnp.clip(127 - s, 1, 254).astype(jnp.uint32) << 23, jnp.float32)
    r = y * pw                              # exact: rsqrt results are normal
    inf_s = jax.lax.bitcast_convert_type(F32_EXP_MASK | sign, jnp.float32)
    r = jnp.where(x_zero, inf_s, r)                      # +-0/sub -> +-inf
    r = jnp.where(x_inf, jnp.float32(0.0), r)            # +inf -> +0
    neg = (sign != 0) & ~x_zero                          # x < 0 -> nan
    return jnp.where(neg | x_nan, jnp.float32(np.nan), r)


def rsqrt_f32(x: jax.Array, table: SeedTable, newton_iters: int) -> jax.Array:
    """rsqrt for strictly-positive x (norm denominators): PWL seed + Newton.

    The final Newton step is residual-compensated (core.taylor._newton_rsqrt
    — two Dekker two-products) so the fused norms deliver the same ~0.5 ULP
    the jnp rsqrt twin does, instead of the ~2 ULP plain steps leave.
    """
    from repro.core.taylor import _newton_rsqrt

    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    exp = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32) - 127
    man = jax.lax.bitcast_convert_type(
        (bits & F32_MAN_MASK) | F32_ONE_BITS, jnp.float32)
    # x = man * 2^exp; with s = floor(exp/2): u = man * 2^(exp-2s) in [1, 4) —
    # shift the seed domain [0.5, 2) by scaling u by 1/2 and result by sqrt(2).
    s = exp >> 1  # floor division (arithmetic shift)
    odd = exp - 2 * s  # 0 or 1
    u = jnp.where(odd == 1, man * 2.0, man) * 0.5  # in [0.5, 2)
    y = _newton_rsqrt(u, seed_ladder(u, table), newton_iters)
    # rsqrt(x) = rsqrt(2u * 2^(2s + odd - 1)) ... assembled as y * 2^-(s)/sqrt(2)*...
    # We defined u = man' / 2 with man' in [1,4), x = man' * 2^(2s).
    # rsqrt(x) = rsqrt(2u) * 2^-s = y / sqrt(2) * 2^-s.
    inv_sqrt2 = jnp.float32(1.0 / np.sqrt(2.0))
    pow2 = jax.lax.bitcast_convert_type(
        ((jnp.clip(127 - s, 1, 254)).astype(jnp.uint32)) << 23, jnp.float32)
    return y * inv_sqrt2 * pow2
