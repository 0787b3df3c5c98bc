"""Property suite for the bit-level f32 datapath (core/fpparts.py).

The tentpole invariants, hypothesis-style with pinned replays:

  (a) split_f32 -> repack_f32 is the *identity* on every finite f32 bit
      pattern — subnormals, signed zeros and extremes included;
  (b) the RNE repack agrees bit-for-bit with numpy's correctly-rounded
      f64 -> f32 cast on subnormal-range targets;
  (c) algebraic divide invariants in every non-ILM mode: exact sign
      antisymmetry div(-a, b) == -div(a, b), and exact power-of-two
      scaling div(ldexp(a, k), b) == ldexp(div(a, b), k) away from the
      under/overflow cliffs (both are exponent/sign bookkeeping only — the
      mantissa datapath must be oblivious to them).
"""
import numpy as np
import pytest

import jax.numpy as jnp
from jax import lax

from _hypothesis_compat import given, settings, st
from repro.core import division_modes as dm
from repro.core import fpparts

NON_ILM_MODES = ["exact", "taylor", "taylor_pallas",
                 "goldschmidt", "goldschmidt_pallas"]

# Pinned bit patterns: signed zeros, min/max subnormal, min/max normal,
# mid-range, halfway-rounding mantissas, and the subnormal boundary.
PINNED_BITS = [
    0x0000_0000, 0x8000_0000,             # +-0
    0x0000_0001, 0x8000_0001,             # +-min subnormal (2^-149)
    0x007F_FFFF, 0x807F_FFFF,             # +-max subnormal
    0x0080_0000, 0x8080_0000,             # +-min normal (2^-126)
    0x7F7F_FFFF, 0xFF7F_FFFF,             # +-max finite
    0x3F80_0000, 0x4000_0000,             # 1.0, 2.0
    0x0040_0000, 0x0000_0002,             # 2^-127, 2^-148
    0x3F80_0001, 0x3FFF_FFFF,             # 1.0+ulp, just under 2
]


def _roundtrip_bits(bits_u32: np.ndarray) -> np.ndarray:
    """split -> repack of the given f32 bit patterns, returning bits."""
    x = jnp.asarray(bits_u32).view(jnp.float32)
    b = lax.bitcast_convert_type(x, jnp.uint32)
    mag = b & fpparts.F32_MAG_MASK
    man, e = fpparts.split_f32(mag)
    back = fpparts.repack_f32(jnp.where(man == 0, jnp.float32(1.0), man), e,
                              b & fpparts.F32_SIGN)
    back = jnp.where(man == 0,
                     lax.bitcast_convert_type(b & fpparts.F32_SIGN,
                                              jnp.float32), back)
    return np.asarray(back).view(np.uint32)


def test_split_repack_identity_pinned():
    bits = np.asarray(PINNED_BITS, np.uint32)
    got = _roundtrip_bits(bits)
    mism = got != bits
    assert not mism.any(), [hex(b) for b in bits[mism]]


@settings(max_examples=64, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_split_repack_identity_random_bits(pattern):
    bits = np.asarray([pattern], np.uint32)
    if not np.isfinite(bits.view(np.float32))[0]:
        return                     # inf/nan: discarded by the edge overrides
    got = _roundtrip_bits(bits)
    assert got[0] == bits[0], hex(int(bits[0]))


def test_split_repack_identity_dense_subnormals():
    """Every 97th subnormal bit pattern plus both boundary neighborhoods."""
    bits = np.concatenate([
        np.arange(1, 0x0080_0000, 97, dtype=np.uint32),
        np.arange(0x007F_FFF0, 0x0080_0010, dtype=np.uint32),
    ])
    bits = np.concatenate([bits, bits | fpparts.F32_SIGN])
    got = _roundtrip_bits(bits)
    np.testing.assert_array_equal(got, bits)


@settings(max_examples=64, deadline=None)
@given(st.floats(1.0, 1.9999999), st.integers(-152, -120))
def test_repack_rne_matches_numpy_cast(man, e):
    """Subnormal-range repack == numpy's correctly-rounded f64 -> f32 cast."""
    manf = np.float32(man)
    got = np.asarray(fpparts.repack_f32(
        jnp.asarray([manf]), jnp.asarray([e], jnp.int32),
        jnp.zeros(1, jnp.uint32)))
    want = np.asarray([np.float64(manf) * 2.0 ** e]).astype(np.float32)
    assert got.view(np.uint32)[0] == want.view(np.uint32)[0], (man, e, got, want)


def test_repack_ftz_flushes_after_rounding():
    """FTZ flushes results still subnormal *after* RNE — a carry that rounds
    up to the smallest normal must survive (the hardware tininess rule)."""
    man = jnp.asarray([1.9999999, 1.5], jnp.float32)
    e = jnp.asarray([-127, -130], jnp.int32)
    got = np.asarray(fpparts.repack_f32(man, e, jnp.zeros(2, jnp.uint32),
                                        underflow="ftz"))
    assert got[0] == np.float32(2.0 ** -126), got   # rounded up to normal
    assert got[1] == 0.0, got                       # still subnormal: flushed


# ------------------------------------------------- algebraic divide invariants

PINNED_PAIRS = [
    (1.5, 3.0), (2.0 ** -100, 7.0), (1.0, 2.0 ** 100),
    (1.9999999, 1.0000001), (3.0, 2.0 ** -60),
]


@pytest.mark.parametrize("mode", NON_ILM_MODES)
def test_div_sign_antisymmetry_bitwise(mode):
    """div(-a, b) == -div(a, b) bit-for-bit: the sign never enters the
    mantissa datapath (it is a single xor in hardware)."""
    rng = np.random.default_rng(7)
    a = np.concatenate([[p[0] for p in PINNED_PAIRS],
                        np.ldexp(rng.uniform(1, 2, 59),
                                 rng.integers(-120, 121, 59))]).astype(np.float32)
    b = np.concatenate([[p[1] for p in PINNED_PAIRS],
                        np.ldexp(rng.uniform(1, 2, 59),
                                 rng.integers(-120, 121, 59))]).astype(np.float32)
    cfg = dm.DivisionConfig(mode=mode)
    q_pos = np.asarray(dm.div(jnp.asarray(a), jnp.asarray(b), cfg))
    q_neg = np.asarray(dm.div(jnp.asarray(-a), jnp.asarray(b), cfg))
    np.testing.assert_array_equal(q_neg.view(np.uint32),
                                  (-q_pos).view(np.uint32), err_msg=mode)


@settings(max_examples=20, deadline=None)
@given(st.floats(1.0, 1.9999999), st.floats(1.0, 1.9999999),
       st.integers(-30, 30), st.integers(-40, 40))
def test_div_pow2_scaling_invariance(ma, mb, eb, k):
    """div(ldexp(a, k), b) == ldexp(div(a, b), k) bitwise, away from cliffs.

    Power-of-two scalings only move the exponent field; both sides round
    the same mantissa quotient once, so they must agree exactly for every
    jnp mode (and exact XLA).
    """
    a = np.float32(ma)                    # quotient exponent in [-1, 1]
    b = np.float32(np.ldexp(mb, eb))
    ak = np.float32(np.ldexp(ma, k))      # scaled operand, still mid-range
    for mode in ("exact", "taylor", "goldschmidt"):
        cfg = dm.DivisionConfig(mode=mode)
        q = np.asarray(dm.div(jnp.asarray([a]), jnp.asarray([b]), cfg))
        qk = np.asarray(dm.div(jnp.asarray([ak]), jnp.asarray([b]), cfg))
        want = np.ldexp(q.astype(np.float64), k).astype(np.float32)
        assert qk.view(np.uint32)[0] == want.view(np.uint32)[0], (
            mode, ma, mb, eb, k, qk, want)


@pytest.mark.parametrize("mode", ["taylor_pallas", "goldschmidt_pallas"])
def test_div_pow2_scaling_invariance_pallas(mode):
    """Same invariance through the fused kernels, batched (one launch)."""
    rng = np.random.default_rng(11)
    n = 64
    ma = rng.uniform(1, 2, n)
    mb = rng.uniform(1, 2, n)
    eb = rng.integers(-30, 31, n)
    k = rng.integers(-40, 41, n)
    a = ma.astype(np.float32)
    b = np.ldexp(mb, eb).astype(np.float32)
    ak = np.ldexp(ma, k).astype(np.float32)
    cfg = dm.DivisionConfig(mode=mode)
    q = np.asarray(dm.div(jnp.asarray(a), jnp.asarray(b), cfg))
    qk = np.asarray(dm.div(jnp.asarray(ak), jnp.asarray(b), cfg))
    want = np.ldexp(q.astype(np.float64), k).astype(np.float32)
    np.testing.assert_array_equal(qk.view(np.uint32), want.view(np.uint32),
                                  err_msg=mode)


@pytest.mark.parametrize("d", [128, 100, 1])
def test_tree_sum_is_the_fixed_halving_order(d):
    """tree_sum rounds the same f32 sums in the same order as an explicit
    numpy halving tree over the zero-padded power-of-two width."""
    from repro.core.fpparts import tree_sum

    rng = np.random.default_rng(d)
    x = np.exp(rng.normal(0.0, 4.0, (16, d))).astype(np.float32)
    width = 1 << max(d - 1, 0).bit_length()
    ref = np.concatenate([x, np.zeros((16, width - d), np.float32)], axis=1)
    while ref.shape[1] > 1:
        h = ref.shape[1] // 2
        ref = (ref[:, :h] + ref[:, h:]).astype(np.float32)
    got = np.asarray(tree_sum(jnp.asarray(x)))
    assert got.shape == (16, 1)
    np.testing.assert_array_equal(got.view(np.uint32), ref.view(np.uint32))
