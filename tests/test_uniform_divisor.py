"""A plane divided by one number: the tiled divide kernel takes the divisor
as a (1, 1) array and runs its half of the datapath once per tile
(``kernels.ops.tsdiv_divide_uniform``, the path ``division_modes.div``
takes for a rank >= 2 numerator over a one-element divisor). Its quotients
and gradients must be the bits of the two-plane kernel fed the broadcast
divisor. Interpret mode on the CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import division_modes as dm
from repro.core.seeds import compute_segments
from repro.kernels import ops, tsdiv

F32 = np.float32


def _divisors():
    seg = compute_segments(2, 24).boundaries.astype(F32)
    below = np.nextafter(seg, F32(0))
    pow2 = [F32(2.0) ** k for k in (-126, -20, -1, 1, 20, 127)]
    edges = [0.0, -0.0, 1e-40, -1e-45, np.inf, -np.inf, np.nan]
    return np.concatenate([[42, 1, 3, -42, -3, -0.7, 7e-30, 3e30], pow2,
                           seg, below, edges]).astype(F32)


def _numerators(shape, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal(shape) * 2.0 ** rng.integers(-150, 150, shape)
    with np.errstate(over="ignore"):        # past f32's range: +-inf lanes
        a = a.astype(F32)
    specials = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1e-40, -1e-42,
                         np.finfo(F32).max, -np.finfo(F32).tiny, 1.0, 42.0],
                        F32)
    a.flat[:specials.size] = specials
    return jnp.asarray(a)


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("schedule", ["factored", "paper", "goldschmidt"])
@pytest.mark.parametrize("shape", [(1000, 500), (7, 130), (8, 128)])
def test_uniform_kernel_matches_two_plane_kernel(schedule, shape):
    a = _numerators(shape)
    for d in _divisors():
        b = jnp.full((1, 1), d, jnp.float32)
        one = tsdiv.tsdiv_divide_tiled_2d(a, b, schedule=schedule)
        plane = tsdiv.tsdiv_divide_tiled_2d(
            a, jnp.broadcast_to(b, shape), schedule=schedule)
        np.testing.assert_array_equal(_bits(one), _bits(plane),
                                      err_msg=f"divisor {d!r}")


@pytest.mark.parametrize("mode", ["taylor_pallas", "goldschmidt_pallas"])
def test_div_by_a_scalar_matches_the_broadcast_plane(mode):
    cfg = dm.DivisionConfig(mode=mode)
    a = _numerators((2, 13, 200), seed=1)
    for d in (42.0, -3.0, 0.0, np.inf):
        for b in (jnp.float32(d), jnp.full((1, 1, 1), d, jnp.float32)):
            got = dm.div(a, b, cfg)
            want = dm.div(a, jnp.full(a.shape, d, jnp.float32), cfg)
            assert got.shape == a.shape and got.dtype == want.dtype
            np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("d", [42.0, -0.75, 0.0])
def test_grad_matches_the_broadcast_path(d):
    cfg = dm.DivisionConfig(mode="taylor_pallas")
    a = jnp.asarray(np.random.default_rng(2).uniform(-5, 5, (37, 300)), F32)

    w = jnp.asarray(np.random.default_rng(3).uniform(-1, 1, a.shape), F32)

    def loss(q):            # finite cotangents on the x/0 = inf lanes too
        return jnp.sum(q * w)

    def uniform(a, b):
        return loss(dm.div(a, b, cfg))

    def broadcast(a, b):
        return loss(dm.div(a, jnp.broadcast_to(b, a.shape), cfg))

    b = jnp.float32(d)
    got = jax.grad(uniform, (0, 1))(a, b)
    want = jax.grad(broadcast, (0, 1))(a, b)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))
    assert np.isfinite(np.asarray(got[1]))


def test_who_takes_the_uniform_path(monkeypatch):
    """A plane over one number takes the uniform path; equal shapes, other
    broadcasts (the centroid divide (K, D) / (K, 1)) and rank 0/1
    numerators keep the two-plane kernel."""
    cfg = dm.DivisionConfig(mode="taylor_pallas")
    calls = []
    for name in ("tsdiv_divide", "tsdiv_divide_uniform"):
        real = getattr(ops, name)
        monkeypatch.setattr(
            ops, name, lambda *a, _n=name, _r=real, **k:
            (calls.append(_n), _r(*a, **k))[1])
    plane, col = jnp.ones((16, 200), F32), jnp.ones((16, 1), F32)
    cases = [((plane, F32(3)), "tsdiv_divide_uniform"),
             ((plane, jnp.ones((1, 1), F32)), "tsdiv_divide_uniform"),
             ((plane.astype(jnp.bfloat16), F32(3)), "tsdiv_divide_uniform"),
             ((plane, plane), "tsdiv_divide"),
             ((plane, col), "tsdiv_divide"),
             ((jnp.ones((200,), F32), F32(3)), "tsdiv_divide"),
             ((F32(6), F32(3)), "tsdiv_divide"),
             ((jnp.ones((16,), F32), jnp.ones((1, 1), F32)), "tsdiv_divide")]
    for (a, b), want in cases:
        calls.clear()
        q = dm.div(a, b, cfg)
        assert calls == [want], (np.shape(a), np.shape(b), calls)
        assert q.shape == jnp.broadcast_shapes(np.shape(a), np.shape(b))


def test_uniform_entry_refuses_what_it_cannot_divide():
    with pytest.raises(ValueError):
        ops.tsdiv_divide_uniform(jnp.ones((4, 4), F32), jnp.ones((2,), F32))
    with pytest.raises(ValueError):
        ops.tsdiv_divide_uniform(jnp.ones((4,), F32), F32(2))
    empty = ops.tsdiv_divide_uniform(jnp.ones((0, 4), F32), F32(2))
    assert empty.shape == (0, 4)
