"""Shape-generic jit'd wrappers around the Pallas kernels.

Arbitrary-rank inputs are reshaped/padded to the 2D tiled forms the kernels
expect (lane dim multiple of 128, sublane of 8), then cropped back. These are
the entry points ``core.division_modes`` uses for mode="taylor_pallas".

Mesh-aware dispatch: a ``pallas_call`` is not GSPMD-partitionable, so under
plain ``jax.jit`` any sharded operand reaching these wrappers is silently
all-gathered onto every device before the kernel runs. When a mesh is
registered (``repro.sharding.rules.use_mesh`` — the launcher does this), the
rank >= 2 paths instead wrap the tiled kernel launch in ``shard_map`` over
the batch axes (largest divisible prefix of ('pod','data'), see
``rules.batch_partition``): each device launches the kernel on its resident
rows, block specs derive from the *per-shard* shape, and ragged last tiles
are masked against local extents inside the kernel — no all-gather, no
resharding. Code already inside a shard_map body disables this with
``rules.suspend_mesh()``.

Interpret mode: ``INTERPRET`` is true only when the default backend is the
CPU, where the kernel bodies run in the Pallas interpreter (tests and
rehearsals). On every other backend the kernels are compiled by Mosaic;
nothing falls back to the interpreter there. A compile for a described,
unattached TPU from a CPU process sets ``INTERPRET = False`` itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ilm as ilm_k
from . import rmsnorm as rmsnorm_k
from . import softmax as softmax_k
from . import tsdiv as tsdiv_k

INTERPRET = jax.default_backend() == "cpu"

# One definition of the f32 tile lattice, shared with the tiled kernels.
_LANE = tsdiv_k.LANE
_SUBLANE = tsdiv_k.SUBLANE


def pallas_applicable(x) -> bool:
    """division_modes guard: kernels handle f32/bf16 with >= 1 total element.

    0-d and 1-element inputs are fine — _to_2d pads them out to one
    (8, 128) tile; only empty arrays fall back to the jnp path.
    """
    return x.dtype in (jnp.float32, jnp.bfloat16) and x.size >= 1


def _to_2d(x):
    """Flatten to (M, N) with N a multiple of 128 and M of 8, padding with ones."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = _LANE
    rows = -(-n // cols)
    rows_p = -(-rows // _SUBLANE) * _SUBLANE
    pad = rows_p * cols - n
    flat = jnp.concatenate([flat, jnp.ones((pad,), flat.dtype)])
    return flat.reshape(rows_p, cols), n


def _from_2d(y, n, shape):
    return y.reshape(-1)[:n].reshape(shape)


def _row_shard_axes(rows: int):
    """(mesh, batch_axes) when the active mesh can shard ``rows`` kernel rows.

    None when no mesh is registered (single-device tests/examples run the
    plain launch unchanged) or when no batch-axis prefix divides the row
    count (the kernel would need ragged *shard* extents, which shard_map
    does not express).
    """
    from repro.sharding import rules as shr

    mesh = shr.active_mesh()
    if mesh is None:
        return None
    axes = shr.batch_partition(mesh, rows)
    n = 1
    for ax in axes:
        n *= mesh.shape[ax]
    if n <= 1:
        return None
    return mesh, axes


def _shard_rows(fn, mesh, axes, n_args: int):
    """shard_map a row-tiled 2D kernel launch: dim 0 sharded over ``axes``.

    The body receives the per-shard (rows/n, N) block and launches the tiled
    kernel on it directly — grid and block specs are recomputed from the
    local shape, so sharded operands stay resident end to end (zero
    collectives; the conformance for this is pinned in
    tests/test_sharded_kernels.py). check_vma=False: the elementwise body
    has no varying-axis types for shard_map's checker to track through the
    pallas_call.
    """
    from jax.sharding import PartitionSpec as P

    spec = P(axes, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * n_args,
                         out_specs=spec, check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def tsdiv_recip(x, n_iters: int = 2, precision_bits: int = 24,
                schedule: str = "factored"):
    """Kernel reciprocal with analytic VJP (bitcasts bar autodiff):
    d(1/x) = -r^2 dx, reusing the kernel's own r."""
    orig_dtype, shape = x.dtype, x.shape
    if x.size == 0:      # no lanes to launch; keep the shape/dtype contract
        return (1.0 / x).astype(orig_dtype)
    if x.ndim >= 2:
        info = _row_shard_axes(int(np.prod(shape[:-1])))
        if info is not None:
            # Mesh-aware rank >= 2 path: per-shard tiled launches over the
            # native layout (the flatten-pad layout below would interleave
            # rows across shard boundaries). Engaged only when sharding
            # actually applies, so the single-device layout — and its
            # bit-pinned outputs — never changes.
            rows = int(np.prod(shape[:-1]))
            x2 = x.astype(jnp.float32).reshape(rows, shape[-1])
            y = _shard_rows(
                lambda xl: tsdiv_k.tsdiv_recip_tiled_2d(
                    xl, n_iters=n_iters, precision_bits=precision_bits,
                    schedule=schedule, interpret=INTERPRET),
                *info, n_args=1)(x2)
            return y.reshape(shape).astype(orig_dtype)
    x2, n = _to_2d(x.astype(jnp.float32))
    y = tsdiv_k.tsdiv_recip_2d(x2, n_iters=n_iters, precision_bits=precision_bits,
                               schedule=schedule, interpret=INTERPRET)
    return _from_2d(y, n, shape).astype(orig_dtype)


def _recip_fwd(x, n_iters, precision_bits, schedule):
    r = tsdiv_recip(x, n_iters, precision_bits, schedule)
    return r, r


def _recip_bwd(n_iters, precision_bits, schedule, r, g):
    # Edge lanes (r = ±inf at x = 0, which under the kernels' FTZ contract
    # includes subnormal operands flushed to the zero class) get zero
    # gradient, not 0*inf = nan — same contract as the jnp twins'
    # custom_jvp rule (fpparts.jnp_reciprocal).
    rf = jnp.where(jnp.isfinite(r), r, 0.0)
    return (-(g * rf * rf),)


tsdiv_recip.defvjp(_recip_fwd, _recip_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def tsdiv_divide(a, b, n_iters: int = 2, precision_bits: int = 24,
                 schedule: str = "factored"):
    """Fused exponent-separated divide kernel with analytic VJP.

    The primal is one kernel launch (no recip+multiply composition); the
    reciprocal kernel runs only on the backward pass to supply 1/b for
    d(a/b) = da/b - q*db/b. Operands must be pre-broadcast to equal shapes
    (division_modes.div does this): broadcasting inside a custom_vjp primal
    would desync the cotangent shapes, and silently flattening unequal
    shapes truncated to a's size.
    """
    if a.shape != b.shape:
        raise ValueError(
            f"tsdiv_divide requires equal shapes, got {a.shape} vs "
            f"{b.shape}; broadcast the operands first")
    orig_dtype, shape = a.dtype, a.shape
    if a.size == 0:      # no lanes to launch; keep the shape/dtype contract
        return jnp.divide(a, b).astype(orig_dtype)
    if a.ndim >= 2:
        # Rank >= 2 operands (distance planes, centroid sums, activation
        # planes — batched or not) stream through the tiled kernel: leading
        # dims collapse row-major into the sublane axis (a metadata-only
        # reshape, no copy), then a 2D grid with ragged last tiles masked
        # in-kernel — no pad copies on the way in or crop on the way out.
        # With an active mesh the launch goes through shard_map so sharded
        # operands stay resident (see module docstring).
        rows = int(np.prod(shape[:-1]))
        a2 = a.astype(jnp.float32).reshape(rows, shape[-1])
        b2 = b.astype(jnp.float32).reshape(rows, shape[-1])

        def launch(al, bl):
            return tsdiv_k.tsdiv_divide_tiled_2d(
                al, bl, n_iters=n_iters, precision_bits=precision_bits,
                schedule=schedule, interpret=INTERPRET)

        info = _row_shard_axes(rows)
        if info is not None:
            launch = _shard_rows(launch, *info, n_args=2)
        return launch(a2, b2).reshape(shape).astype(orig_dtype)
    # Rank 0/1 keeps the flatten-pad path deliberately: a vector laid out as
    # (1, N) in the tiled kernel would occupy one of eight sublanes per tile,
    # while _to_2d packs it (ceil(n/128), 128) at full utilization — the
    # conformance sweeps are exactly such rank-1 operands.
    a2, n = _to_2d(a.astype(jnp.float32))
    b2, _ = _to_2d(b.astype(jnp.float32))
    y = tsdiv_k.tsdiv_divide_2d(a2, b2, n_iters=n_iters,
                                precision_bits=precision_bits,
                                schedule=schedule, interpret=INTERPRET)
    return _from_2d(y, n, shape).astype(orig_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def tsdiv_rsqrt(x, newton_iters: int = 2, n_segments: int = 16):
    """Fused full-edge rsqrt kernel with analytic VJP (bitcasts bar autodiff):
    d(x^-1/2) = -r^3/2 dx, reusing the kernel's own r. The
    mode="taylor_pallas"/"goldschmidt_pallas" path of division_modes.rsqrt."""
    orig_dtype, shape = x.dtype, x.shape
    if x.size == 0:      # no lanes to launch; keep the shape/dtype contract
        return jax.lax.rsqrt(x.astype(jnp.float32)).astype(orig_dtype)
    if x.ndim >= 2:
        info = _row_shard_axes(int(np.prod(shape[:-1])))
        if info is not None:
            # Same rationale as tsdiv_recip: shard the native (rows, N)
            # layout, per-shard tiled launches; only engaged under a mesh.
            rows = int(np.prod(shape[:-1]))
            x2 = x.astype(jnp.float32).reshape(rows, shape[-1])
            y = _shard_rows(
                lambda xl: tsdiv_k.tsdiv_rsqrt_tiled_2d(
                    xl, newton_iters=newton_iters, n_segments=n_segments,
                    interpret=INTERPRET),
                *info, n_args=1)(x2)
            return y.reshape(shape).astype(orig_dtype)
    x2, n = _to_2d(x.astype(jnp.float32))
    y = tsdiv_k.tsdiv_rsqrt_2d(x2, newton_iters=newton_iters,
                               n_segments=n_segments, interpret=INTERPRET)
    return _from_2d(y, n, shape).astype(orig_dtype)


def _rsqrt_fwd(x, newton_iters, n_segments):
    r = tsdiv_rsqrt(x, newton_iters, n_segments)
    return r, r


def _rsqrt_bwd(newton_iters, n_segments, r, g):
    # Same contract as the jnp twin's custom_jvp rule (fpparts.jnp_rsqrt):
    # edge lanes (r = ±inf/nan) and lanes whose analytic -r^3/2 overflows
    # f32 get zero gradient, never nan poison.
    rf = jnp.where(jnp.isfinite(r), r, 0.0)
    coeff = jnp.float32(-0.5) * rf * rf * rf
    coeff = jnp.where(jnp.isfinite(coeff), coeff, 0.0)
    return (g * coeff,)


tsdiv_rsqrt.defvjp(_rsqrt_fwd, _rsqrt_bwd)


def _divide_fwd(a, b, n_iters, precision_bits, schedule):
    q = tsdiv_divide(a, b, n_iters, precision_bits, schedule)
    return q, (q, b)


def _divide_bwd(n_iters, precision_bits, schedule, res, g):
    q, b = res
    rb = tsdiv_recip(b, n_iters, precision_bits, schedule)
    # Mask edge lanes to zero gradient, as the jnp twins' custom_jvp
    # rule (fpparts.jnp_divide) does. Under the kernels' FTZ contract this
    # covers the subnormal lanes too: a subnormal b is the zero class, so
    # q and rb come back ±inf there and the whole lane is masked rather
    # than poisoned with 0*inf = nan.
    rb = jnp.where(jnp.isfinite(rb), rb, 0.0)
    qf = jnp.where(jnp.isfinite(q), q, 0.0)
    return (g * rb, -(g * qf * rb))


tsdiv_divide.defvjp(_divide_fwd, _divide_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def rmsnorm(x, w, eps: float = 1e-6, newton_iters: int = 2,
            n_segments: int = 16):
    """RMSNorm over the last dim of any (..., D) array.

    Analytic VJP (the pallas_call body bars autodiff): with
    r = rsqrt(mean(x^2) + eps), dx = r*w*g - (r^3/D) * x * sum(g*x*w) and
    dw = sum_batch(g * x * r) — the backward runs in plain jnp.
    """
    shape = x.shape
    d = shape[-1]
    d_pad = -(-d // _LANE) * _LANE
    x2 = x.reshape(-1, d)
    m = x2.shape[0]
    m_pad = -(-m // _SUBLANE) * _SUBLANE
    x2 = jnp.pad(x2, ((0, m_pad - m), (0, d_pad - d)))
    wp = jnp.pad(w, (0, d_pad - d))
    y = rmsnorm_k.rmsnorm_2d(x2, wp, eps=eps, newton_iters=newton_iters,
                             n_segments=n_segments, d_real=d,
                             interpret=INTERPRET)
    return y[:m, :d].reshape(shape)


def _rmsnorm_fwd(x, w, eps, newton_iters, n_segments):
    return rmsnorm(x, w, eps, newton_iters, n_segments), (x, w)


def _rmsnorm_bwd(eps, newton_iters, n_segments, res, g):
    x, w = res
    xf, wf, gf = (t.astype(jnp.float32) for t in (x, w, g))
    d = x.shape[-1]
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                      + jnp.float32(eps))
    inner = jnp.sum(gf * xf * wf, axis=-1, keepdims=True)
    gx = r * wf * gf - (r * r * r / d) * xf * inner
    gw = jnp.sum(gf * xf * r, axis=tuple(range(x.ndim - 1)))
    return gx.astype(x.dtype), gw.astype(w.dtype)


rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def softmax(x, n_iters: int = 2, precision_bits: int = 24,
            schedule: str = "factored"):
    """Softmax over the last dim of any (..., D) array (pad masked to -inf).

    Analytic VJP: dx = p * (g - sum(p*g)) reusing the kernel's own output
    (fully-masked rows carry p = 0, so their gradient is exactly zero).
    """
    shape = x.shape
    d = shape[-1]
    d_pad = -(-d // _LANE) * _LANE
    x2 = x.reshape(-1, d)
    m = x2.shape[0]
    m_pad = -(-m // _SUBLANE) * _SUBLANE
    x2 = jnp.pad(x2, ((0, m_pad - m), (0, d_pad - d)),
                 constant_values=-np.inf)
    y = softmax_k.softmax_2d(x2, n_iters=n_iters, precision_bits=precision_bits,
                             schedule=schedule, interpret=INTERPRET)
    return y[:m, :d].reshape(shape)


def _softmax_fwd(x, n_iters, precision_bits, schedule):
    p = softmax(x, n_iters, precision_bits, schedule)
    return p, p


def _softmax_bwd(n_iters, precision_bits, schedule, p, g):
    pf = p.astype(jnp.float32)
    pf = jnp.where(jnp.isfinite(pf), pf, 0.0)    # nan rows: masked gradient
    gf = g.astype(jnp.float32)
    dot = jnp.sum(pf * gf, axis=-1, keepdims=True)
    return ((pf * (gf - dot)).astype(p.dtype),)


softmax.defvjp(_softmax_fwd, _softmax_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, n_iters: int = 2,
                    precision_bits: int = 24, schedule: str = "factored"):
    """Flash attention with tsdiv softmax. q/k/v: (..., S, hd); leading dims
    flattened to the batch*heads grid axis.

    Ragged sequence lengths (any sq/sk, not just block multiples) are
    handled here: q is padded up to a block_q multiple (the padded rows are
    sliced off the output), k/v up to a block_k multiple with the padded key
    positions masked to NEG_INF in-kernel (``sk_real``) so they contribute
    exp(NEG_INF - m) = 0 to every real row's statistics.

    Analytic VJP: the forward is the fused kernel; the backward recomputes
    the score matrix in plain jnp (the standard attention gradient — O(S^2)
    memory, vs the O(S) forward; a fused backward kernel is future work).
    """
    from . import flash_attention as fa

    lead = q.shape[:-2]
    s, hd = q.shape[-2], q.shape[-1]
    q3 = q.reshape(-1, s, hd)
    k3 = k.reshape(-1, k.shape[-2], hd)
    v3 = v.reshape(-1, v.shape[-2], hd)
    sk = k3.shape[1]
    bq, bk = min(block_q, s), min(block_k, sk)
    sq_pad = -(-s // bq) * bq
    sk_pad = -(-sk // bk) * bk
    if sq_pad != s:
        q3 = jnp.pad(q3, ((0, 0), (0, sq_pad - s), (0, 0)))
    if sk_pad != sk:
        k3 = jnp.pad(k3, ((0, 0), (0, sk_pad - sk), (0, 0)))
        v3 = jnp.pad(v3, ((0, 0), (0, sk_pad - sk), (0, 0)))
    o = fa.flash_attention(q3, k3, v3, causal=causal, block_q=bq,
                           block_k=bk, n_iters=n_iters,
                           precision_bits=precision_bits, schedule=schedule,
                           sk_real=sk, interpret=INTERPRET)
    return o[:, :s, :].reshape(*lead, s, hd)


def _flash_fwd(q, k, v, causal, block_q, block_k, n_iters, precision_bits,
               schedule):
    o = flash_attention(q, k, v, causal, block_q, block_k, n_iters,
                        precision_bits, schedule)
    return o, (q, k, v)


def _flash_bwd(causal, block_q, block_k, n_iters, precision_bits, schedule,
               res, g):
    from . import flash_attention as fa

    q, k, v = res
    qf, kf, vf, gf = (t.astype(jnp.float32) for t in (q, k, v, g))
    scale = jnp.float32(1.0 / np.sqrt(q.shape[-1]))
    s = jnp.einsum("...qh,...kh->...qk", qf, kf) * scale
    if causal:
        mask = (jnp.arange(s.shape[-2])[:, None]
                >= jnp.arange(s.shape[-1])[None, :])
        s = jnp.where(mask, s, fa.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    dv = jnp.einsum("...qk,...qh->...kh", p, gf)
    dp = jnp.einsum("...qh,...kh->...qk", gf, vf)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jnp.einsum("...qk,...kh->...qh", ds, kf) * scale
    dk = jnp.einsum("...qk,...qh->...kh", ds, qf) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def ilm_mul(a, b, *, iters: int = 16):
    shape = a.shape
    a2, n = _to_2d(a.astype(jnp.uint32))
    b2, _ = _to_2d(b.astype(jnp.uint32))
    y = ilm_k.ilm_mul_2d(a2, b2, iters=iters, interpret=INTERPRET)
    return _from_2d(y, n, shape)


def ilm_square(a, *, iters: int = 16):
    shape = a.shape
    a2, n = _to_2d(a.astype(jnp.uint32))
    y = ilm_k.ilm_square_2d(a2, iters=iters, interpret=INTERPRET)
    return _from_2d(y, n, shape)
