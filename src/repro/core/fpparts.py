"""Shared sign/exponent/mantissa bookkeeping for the divide datapath.

Real hardware dividers (the source paper's unit, and the Goldschmidt units of
arXiv:1909.10154) never divide full floats: they xor the signs, subtract the
exponents, and refine a *mantissa pair in [1, 2)*, recombining at the very
end. Composing ``a * recip(b)`` instead materializes an intermediate
reciprocal that under/overflows even when ``a/b`` is representable (e.g.
a = 2^100, b = 2^127: 1/b is subnormal, but a/b = 2^-27 is a perfectly
normal float). This module is that hardware bookkeeping, factored once:

  * :func:`decompose_div`  — sign product, |a|/|b|, mantissas in [1, 2) via a
    single ``frexp`` per operand, and the unbiased exponents;
  * :func:`recombine_div`  — one round-trip back through ``ldexp``, split in
    two steps so the internal 2^k factor never overflows;
  * :func:`div_edges`      — the IEEE/hardware special-value contract
    (±0, ±inf, nan sign rules) applied after the mantissa math;
  * :func:`two_product`    — Dekker/Veltkamp error-free multiply, the
    building block for compensated residuals;
  * :func:`tree_sum`       — last-axis sum in one fixed pairwise order, the
    softmax denominator of the jnp twin and the fused kernel alike;
  * :func:`refine_quotient` — Markstein-style correcting final multiply:
    the hardware unit's final multiplier produces the full 2p-bit product
    and rounds once, which p-bit float emulation recovers by folding the
    exact remainder ``a - q0*b`` back through the reciprocal.

Everything is pure operator arithmetic parameterized by the array module
``xp``, so one body serves the numpy f64 oracles, the jnp f32 path, and the
Pallas kernel bodies alike.

Since PR 4 the jnp f32 twins no longer round-trip through ``frexp``/``ldexp``
at all: :func:`split_f32` / :func:`repack_f32` do the sign/exponent/mantissa
bookkeeping on the raw int32 bit patterns — the same field extraction as the
fused kernels' ``divide_f32_bits`` (kernels/common.py imports the field
masks from here) — with explicit subnormal normalization on the way in and a
round-to-nearest-even integer repack on the way out. Two reasons:

  * XLA's ``frexp`` mis-scales subnormal operands (``frexp(2^-127)`` ->
    ``(0.5, -149)``), so gradual underflow was a degraded, masked class;
  * this CPU backend runs FTZ/DAZ: float multiplies flush subnormal inputs
    *and* outputs, and even float comparisons report subnormals as zero —
    so both classification and the subnormal repack must be pure integer
    bit manipulation to be exact (and deterministic across backends).

The delivered subnormal behavior is a policy knob (``underflow=``):
``"gradual"`` (jnp-twin default) normalizes subnormal operands and rounds
underflowing results into the subnormal range exactly; ``"ftz"`` keeps the
hardware contract of the fused kernels — subnormal operands are zeros,
results that round subnormal flush to signed zero.
"""
from __future__ import annotations

import numpy as np

__all__ = [
    "two_product", "tree_sum", "sign_product", "decompose_div", "ldexp2",
    "recombine_div", "div_edges", "refine_quotient", "recombine_recip",
    "jnp_divide", "jnp_reciprocal", "jnp_rsqrt", "split_f32", "repack_f32",
    "bit_divide", "bit_reciprocal", "UNDERFLOW_POLICIES",
    "F32_SIGN", "F32_MAG_MASK", "F32_EXP_MASK", "F32_MAN_MASK",
    "F32_ONE_BITS", "F32_IMPLICIT",
]

# f32 field layout, shared with kernels/common.py (one source of truth for
# the "field-for-field" alignment between the jnp twins and the fused
# kernels' bit-level unpack).
F32_SIGN = np.uint32(0x8000_0000)
F32_MAG_MASK = np.uint32(0x7FFF_FFFF)
F32_EXP_MASK = np.uint32(0x7F80_0000)
F32_MAN_MASK = np.uint32(0x007F_FFFF)
F32_ONE_BITS = np.uint32(0x3F80_0000)
F32_IMPLICIT = np.uint32(0x0080_0000)   # hidden bit / smallest normal's bits

UNDERFLOW_POLICIES = ("gradual", "ftz")


def tree_sum(x):
    """Sum over the last axis (keepdims) in one fixed pairwise order.

    The lanes are zero-padded to a power of two and halved until one is
    left: ``x[..., :h] + x[..., h:]``. Every backend then rounds the same
    sums in the same order, where a plain ``jnp.sum`` leaves the order to
    the compiler (on the softmax conformance rows, XLA's CPU reduction in
    jax 0.9 came within 3.2 ULP of the f64 sum of the same 128 lanes; this
    tree, within 1.7). Adding the zero pad is
    exact. Traceable inside Pallas kernel bodies: the halvings are static
    slices.
    """
    import jax.numpy as jnp

    n = x.shape[-1]
    width = 1 << max(n - 1, 0).bit_length()
    if width != n:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (width - n,), x.dtype)], axis=-1)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x


def sign_product(xp, a, b):
    """±1 with the sign of a*b, signed zeros included (the quotient sign)."""
    return (xp.copysign(xp.asarray(1.0, a.dtype), a)
            * xp.copysign(xp.asarray(1.0, b.dtype), b))


def two_product(a, b):
    """Error-free transform of a product: returns (p, e) with a*b == p + e.

    Veltkamp-split both operands with the factor 2^ceil(prec/2) + 1
    (f32 -> 4097, f64 -> 2^27 + 1) and recover the rounding error of the
    p-bit product. Works under FMA contraction too — a contracted
    ``ah*bh - p`` is the exact error term.
    """
    p = a * b
    prec = np.finfo(np.dtype(a.dtype)).nmant + 1
    c = float(2 ** ((prec + 1) // 2) + 1)
    ta = c * a
    ah = ta - (ta - a)
    al = a - ah
    tb = c * b
    bh = tb - (tb - b)
    bl = b - bh
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, e


def decompose_div(xp, a, b):
    """Unpack a divide: sign product, magnitudes, [1,2) mantissas, exponents.

    Returns ``(s, aa, ab, man_a, man_b, ea, eb)`` with |a| = man_a * 2^(ea-1)
    and likewise for b (``frexp`` convention: frac in [0.5, 1), so the [1, 2)
    mantissa carries exponent e-1). Zeros keep a zero mantissa; infs/nans
    pass through frexp and are overridden by :func:`div_edges`.
    """
    s = sign_product(xp, a, b)
    aa, ab = xp.abs(a), xp.abs(b)
    fa, ea = xp.frexp(aa)
    fb, eb = xp.frexp(ab)
    man_a, man_b = fa * 2.0, fb * 2.0               # [1, 2); 0 stays 0
    return s, aa, ab, man_a, man_b, ea, eb


def ldexp2(xp, x, k):
    """ldexp for |k| up to ~2*emax: two steps so the internal 2^k factor
    never overflows even when x * 2^k is representable."""
    h = k // 2
    return xp.ldexp(xp.ldexp(x, h), k - h)


def recombine_div(xp, q_man, de, s):
    """q = q_man * 2^de * s. de = ea - eb spans ~[-2*emax, 2*emax]."""
    return ldexp2(xp, q_man, de) * s


def div_edges(xp, q, a, b, aa, ab, s):
    """IEEE special-value contract for a/b, applied after the mantissa math:

        x/±0 -> ±inf    ±inf/y -> ±inf    x/±inf -> ±0    (sign = s)
        0/0, inf/inf, nan operands -> nan
    """
    inf = xp.asarray(np.inf, q.dtype)
    zero = xp.asarray(0.0, q.dtype)
    nan = xp.asarray(np.nan, q.dtype)
    q = xp.where((ab == 0) & (aa != 0), xp.copysign(inf, s), q)
    q = xp.where(xp.isinf(aa) & ~xp.isinf(ab), xp.copysign(inf, s), q)
    q = xp.where(xp.isinf(ab) & ~xp.isinf(aa), xp.copysign(zero, s), q)
    q = xp.where((aa == 0) & (ab == 0), nan, q)
    q = xp.where(xp.isinf(aa) & xp.isinf(ab), nan, q)
    q = xp.where(xp.isnan(a) | xp.isnan(b), nan, q)
    return q


def recombine_recip(xp, rman, eb, b):
    """~1/b from the refined mantissa reciprocal (feeds the analytic VJP;
    under/overflow here only zeroes a gradient lane, never the primal)."""
    return xp.ldexp(rman, 1 - eb) * xp.sign(b)


def jnp_divide(a, b, impl):
    """Shared jnp wrapper for the exponent-separated divides.

    ``impl(jnp, af, bf) -> (q, rb)`` is the f32 divide body (Taylor or
    Goldschmidt). Handles dtype promotion (mixed bf16/f32 operands promote,
    as the composed ``a * recip(b)`` form did), the f32 compute dance, and
    supplies the analytic derivative dq = rb*da - q*rb*db through a
    ``custom_jvp`` (bitcasts carry zero cotangent, and the arithmetic
    straight-through of ``taylor.attach_grad`` would flush gradual-underflow
    primals on FTZ/DAZ backends — a custom derivative rule leaves the primal
    bits untouched; custom_jvp rather than custom_vjp so forward-mode
    autodiff keeps working, with reverse mode derived by transposing the
    linear tangent map). Edge lanes (q or 1/b non-finite) get zero
    derivative, not nan.
    """
    import jax
    import jax.numpy as jnp

    a, b = jnp.asarray(a), jnp.asarray(b)
    out_dtype = jnp.promote_types(a.dtype, b.dtype)
    # Broadcast OUTSIDE the custom_jvp boundary: autodiff of the broadcast
    # op itself sum-reduces cotangents back to each operand's shape.
    af, bf = jnp.broadcast_arrays(a.astype(jnp.float32),
                                  b.astype(jnp.float32))

    @jax.custom_jvp
    def _div(af, bf):
        return impl(jnp, af, bf)[0]

    @_div.defjvp
    def _div_jvp(primals, tangents):
        af, bf = primals
        da, db = tangents
        q, rb = impl(jnp, af, bf)
        rbm = jnp.where(jnp.isfinite(rb), rb, 0.0)
        qm = jnp.where(jnp.isfinite(q), q, 0.0)
        return q, rbm * da - qm * rbm * db

    return _div(af, bf).astype(out_dtype)


def jnp_reciprocal(x, impl):
    """Shared jnp wrapper for the bit-level reciprocals.

    ``impl(jnp, xf) -> r`` is the f32 body. Same custom_jvp rationale as
    :func:`jnp_divide`: d(1/x) = -r^2 dx with edge lanes masked to zero,
    and the primal bits pass through untouched (gradual-underflow results
    can be subnormal, which arithmetic straight-through would flush).
    """
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(x)
    out_dtype = x.dtype
    xf = x.astype(jnp.float32)

    @jax.custom_jvp
    def _recip(xf):
        return impl(jnp, xf)

    @_recip.defjvp
    def _recip_jvp(primals, tangents):
        (xf,), (dx,) = primals, tangents
        r = impl(jnp, xf)
        rf = jnp.where(jnp.isfinite(r), r, 0.0)
        return r, -(rf * rf) * dx

    return _recip(xf).astype(out_dtype)


def jnp_rsqrt(x, impl):
    """Shared jnp wrapper for the bit-level rsqrt datapaths.

    ``impl(jnp, xf) -> r`` is the f32 body. Same custom_jvp rationale as
    :func:`jnp_reciprocal` (the arithmetic straight-through of
    ``taylor.attach_grad`` would flush gradual-underflow *primals* on this
    FTZ/DAZ backend — a custom derivative rule leaves the primal bits
    untouched): d(x^-1/2) = -r^3/2 dx. The analytic coefficient itself can
    overflow f32 even where r is finite (r ~ 2^64 for subnormal operands
    gives r^3 ~ 2^192), so non-finite *gradient* lanes are masked to zero
    — the gradient lane degrades, the primal never does.
    """
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(x)
    out_dtype = x.dtype
    xf = x.astype(jnp.float32)

    @jax.custom_jvp
    def _rsqrt(xf):
        return impl(jnp, xf)

    @_rsqrt.defjvp
    def _rsqrt_jvp(primals, tangents):
        (xf,), (dx,) = primals, tangents
        r = impl(jnp, xf)
        rf = jnp.where(jnp.isfinite(r), r, 0.0)
        g = jnp.float32(-0.5) * rf * rf * rf
        g = jnp.where(jnp.isfinite(g), g, 0.0)
        return r, g * dx

    return _rsqrt(xf).astype(out_dtype)


# ----------------------------------------------------- bit-level f32 datapath

def split_f32(mag_bits):
    """Exponent/mantissa split of f32 *magnitude bits*, subnormal-exact.

    Returns ``(man, e)`` with ``man`` an f32 in [1, 2) and ``e`` int32 such
    that the magnitude equals ``man * 2^e`` for every finite nonzero input —
    subnormals are normalized (their leading-bit position found via an exact
    int->float convert of the mantissa field, never a float multiply, which
    FTZ/DAZ backends would flush). Zeros give (0.0, -127); infs/nans give
    (1.mantissa, 128) for the caller's edge overrides to discard.
    """
    import jax.numpy as jnp
    from jax import lax

    expf = (mag_bits >> 23).astype(jnp.int32)
    manf = mag_bits & F32_MAN_MASK
    # Subnormal magnitude = manf * 2^-149; int->float conversion of manf is
    # exact (manf < 2^24) and lands in the normal range, so its own biased
    # exponent reveals the leading-bit index L: manf = 1.xxx * 2^L.
    mf = manf.astype(jnp.float32)
    mfbits = lax.bitcast_convert_type(mf, jnp.uint32)
    lead = (mfbits >> 23).astype(jnp.int32) - 127
    is_sub = (expf == 0) & (manf != 0)
    man_bits = jnp.where(is_sub, (mfbits & F32_MAN_MASK) | F32_ONE_BITS,
                         manf | F32_ONE_BITS)
    e = jnp.where(is_sub, lead - 149, expf - 127)
    man = lax.bitcast_convert_type(man_bits, jnp.float32)
    man = jnp.where(mag_bits == 0, jnp.float32(0.0), man)
    e = jnp.where(mag_bits == 0, jnp.int32(-127), e)
    return man, e


def repack_f32(man, e, sign_bits, underflow: str = "gradual"):
    """RNE repack of ``sign * man * 2^e`` into f32 bits.

    ``man`` is a *normal* f32 in (0.5, 4) (a refined mantissa), ``e`` int32.
    Normal-range results are assembled exactly from the fields (bit-identical
    to the old exact ``ldexp`` round-trip); results below the normal range
    are rounded to nearest-even into the subnormal lattice by integer
    shift-and-round — a carry that rounds up to 2^-126 lands in the exponent
    field and correctly yields the smallest normal. ``underflow="ftz"``
    flushes results that are still subnormal *after* rounding to signed zero
    (the fused kernels' hardware contract); overflow saturates to infinity.
    Pure integer arithmetic after the field extraction: immune to runtime
    FTZ/DAZ, identical eager and jit.
    """
    import jax.numpy as jnp
    from jax import lax

    mbits = lax.bitcast_convert_type(man, jnp.uint32)
    me = (mbits >> 23).astype(jnp.int32) - 127          # -1, 0, or +1
    frac = (mbits & F32_MAN_MASK) | F32_IMPLICIT        # 24-bit significand
    et = e + me                                         # |q| = 1.frac * 2^et
    # Subnormal target: shift the 24-bit significand right by sh with RNE.
    # sh >= 25 rounds to zero (frac < 2^24 => frac/2^25 < 0.5); the clip to
    # 31 only keeps the shift well-defined for the lanes `where` discards.
    sh = jnp.clip(-126 - et, 0, 31).astype(jnp.uint32)
    keep = frac >> sh
    low = jnp.left_shift(jnp.uint32(1), sh) - jnp.uint32(1)
    rem = frac & low
    half = (low + jnp.uint32(1)) >> 1                   # 2^(sh-1); 0 at sh=0
    round_up = ((rem > half) | ((rem == half) & ((keep & 1) == 1))) & (sh > 0)
    sub_bits = keep + round_up.astype(jnp.uint32)
    norm_bits = ((et + 127).astype(jnp.uint32) << 23) | (frac & F32_MAN_MASK)
    bits = jnp.where(et >= -126, norm_bits, sub_bits)
    if underflow == "ftz":
        bits = jnp.where(bits < F32_IMPLICIT, jnp.uint32(0), bits)
    bits = jnp.where(et > 127, F32_EXP_MASK, bits)      # overflow -> inf
    return lax.bitcast_convert_type(bits | sign_bits, jnp.float32)


def bit_divide(a, b, mantissa_fn, underflow: str = "gradual"):
    """Bit-level exponent-separated a/b skeleton shared by the jnp twins.

    ``mantissa_fn(man_a, man_b) -> (q_man, rb_man)`` refines the [1, 2)
    mantissa pair (Taylor series + Markstein correction, or the joint N/D
    Goldschmidt recurrence). Classification is pure bit tests — on FTZ/DAZ
    backends float comparisons report subnormals as zero, which would
    misroute the gradual lanes into the x/0 contract. Edge overrides apply
    in the same order as ``kernels.common.divide_f32_bits`` so the
    ``underflow="ftz"`` twin is bit-identical to the fused kernel. Returns
    ``(q, rb)`` with rb ~ 1/b for the analytic VJP.
    """
    import jax.numpy as jnp
    from jax import lax

    abits = lax.bitcast_convert_type(a, jnp.uint32)
    bbits = lax.bitcast_convert_type(b, jnp.uint32)
    mag_a, mag_b = abits & F32_MAG_MASK, bbits & F32_MAG_MASK
    sign_bits = (abits ^ bbits) & F32_SIGN
    if underflow == "ftz":
        # Hardware contract: a zero exponent field (zero or subnormal) is
        # the zero class — same field test as the fused kernels.
        a_zero, b_zero = mag_a < F32_IMPLICIT, mag_b < F32_IMPLICIT
    else:
        a_zero, b_zero = mag_a == 0, mag_b == 0
    a_inf, b_inf = mag_a == F32_EXP_MASK, mag_b == F32_EXP_MASK
    a_nan, b_nan = mag_a > F32_EXP_MASK, mag_b > F32_EXP_MASK
    man_a, ea = split_f32(mag_a)
    man_b, eb = split_f32(mag_b)
    one = jnp.float32(1.0)
    man_a = jnp.where(man_a == 0, one, man_a)   # keep edge lanes finite; the
    man_b = jnp.where(man_b == 0, one, man_b)   # overrides below discard them
    q_man, rb_man = mantissa_fn(man_a, man_b)
    q = repack_f32(q_man, ea - eb, sign_bits, underflow)
    inf_s = lax.bitcast_convert_type(F32_EXP_MASK | sign_bits, jnp.float32)
    zero_s = lax.bitcast_convert_type(sign_bits, jnp.float32)
    nan = jnp.float32(np.nan)
    q = jnp.where(b_zero, inf_s, q)             # x/0   -> signed inf
    q = jnp.where(a_zero, zero_s, q)            # 0/y   -> signed 0
    q = jnp.where(a_inf, inf_s, q)              # inf/y -> signed inf
    q = jnp.where(b_inf, zero_s, q)             # x/inf -> signed 0
    q = jnp.where(a_zero & b_zero, nan, q)      # 0/0
    q = jnp.where(a_inf & b_inf, nan, q)        # inf/inf
    q = jnp.where(a_nan | b_nan, nan, q)
    rb = repack_f32(rb_man, -eb, bbits & F32_SIGN, underflow)
    return q, rb


def bit_reciprocal(x, mantissa_fn, underflow: str = "gradual"):
    """Bit-level 1/x skeleton shared by the jnp twins.

    ``mantissa_fn(man) -> rman`` refines the [1, 2) mantissa reciprocal.
    Same bit-test classification and edge order as
    ``kernels.common.recip_f32_bits``; ``underflow="gradual"`` additionally
    makes subnormal operands exact and rounds subnormal reciprocals (of
    near-maxfloat inputs) instead of flushing.
    """
    import jax.numpy as jnp
    from jax import lax

    bits = lax.bitcast_convert_type(x, jnp.uint32)
    mag = bits & F32_MAG_MASK
    sign_bits = bits & F32_SIGN
    if underflow == "ftz":
        x_zero = mag < F32_IMPLICIT
    else:
        x_zero = mag == 0
    x_inf, x_nan = mag == F32_EXP_MASK, mag > F32_EXP_MASK
    man, e = split_f32(mag)
    man = jnp.where(man == 0, jnp.float32(1.0), man)
    rman = mantissa_fn(man)                             # in (0.5, 1]
    r = repack_f32(rman, -e, sign_bits, underflow)
    inf_s = lax.bitcast_convert_type(F32_EXP_MASK | sign_bits, jnp.float32)
    zero_s = lax.bitcast_convert_type(sign_bits, jnp.float32)
    r = jnp.where(x_zero, inf_s, r)
    r = jnp.where(x_inf, zero_s, r)
    return jnp.where(x_nan, jnp.float32(np.nan), r)


def refine_quotient(q0, man_a, man_b, rman):
    """Markstein correcting step: q = q0 + rman * (man_a - q0*man_b).

    The remainder is computed error-free: two_product gives q0*man_b as
    p + e exactly, and man_a - p is exact by Sterbenz (p lies within a
    factor 2 of man_a since q0 ~ man_a/man_b). With rman accurate to even a
    few thousand ULPs the corrected quotient lands within ~1 ULP of
    man_a/man_b — this is the float emulation of the hardware unit's
    full-width final multiplier (Fig. 7), whose 2p-bit product is rounded
    exactly once.
    """
    p, e = two_product(q0, man_b)
    res = (man_a - p) - e
    return q0 + res * rman
