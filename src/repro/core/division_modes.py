"""Framework-wide division dispatch — the paper's unit as a first-class feature.

Every division site in the framework calls through here — attention softmax,
RMSNorm rsqrt, MoE router normalization, Adam update, loss normalization, and
the application workloads (``repro.workloads``: K-Means assignment/update
divides, Givens-QR rotation coefficients) — so the divider implementation is
one config knob:

  * ``exact``         — native XLA divide/rsqrt (the baseline the paper compares
                        against: "a full-precision hardware divider").
  * ``taylor``        — paper's unit in pure jnp (PWL seed + series). This is
                        what the dry-run lowers: division becomes FMA chains.
  * ``taylor_pallas`` — fused Pallas TPU kernels (kernels/). CPU runs them in
                        interpret mode; TPU gets real VMEM-tiled kernels.
  * ``ilm``           — bit-faithful emulation with 16-bit ILM mantissa
                        arithmetic (tests/benchmarks only; slow by design).
  * ``goldschmidt``   — Goldschmidt N/D refinement (core/goldschmidt.py),
                        sharing the paper's seed ROM; the canonical rival
                        algorithm, kept on the same n_iters dial.
  * ``goldschmidt_pallas`` — the same refinement fused into the Pallas
                        division kernel (schedule="goldschmidt" in kernels/).

Besides the scalar ops (:func:`recip`, :func:`div`, :func:`rsqrt`), the
normalization *consumers* are first-class dispatch citizens: :func:`softmax`,
:func:`rmsnorm`, and :func:`attention` route every mode through one config
knob — the Pallas modes to the fused kernels (``kernels/ops.py``, with
schedule="goldschmidt" threaded for mode="goldschmidt_pallas"), the jnp
modes to twins whose divisions/rsqrts call back into this module. Their
delivered accuracy is gated by the consumer-conformance tier
(``repro.eval.consumers`` + the softmax/rmsnorm cells of the grid).

The delivered accuracy of every mode is measured in ULPs by
``repro.eval.conformance`` (``python -m repro.eval.conformance``).

Mesh awareness: the Pallas modes are safe to call on sharded operands. The
dispatch mechanics live in ``kernels/ops.py`` — when a mesh is registered via
``repro.sharding.rules.use_mesh``, the rank >= 2 kernel entry points wrap
their tiled launches in ``shard_map`` over the batch axes so sharded operands
stay device-resident (a bare ``pallas_call`` under jit would otherwise be
silently all-gathered, since it is not GSPMD-partitionable). Nothing in this
module changes per-mode numerics based on the mesh; callers already inside a
shard_map body use ``rules.suspend_mesh()`` around their division sites.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from . import goldschmidt, taylor
from .fpparts import UNDERFLOW_POLICIES, tree_sum
from .seeds import compute_segments, rsqrt_seed_table

__all__ = ["DivisionConfig", "recip", "div", "rsqrt", "softmax", "rmsnorm",
           "attention", "EXACT", "TAYLOR", "effective_underflow"]

MODES = ("exact", "taylor", "taylor_pallas", "goldschmidt",
         "goldschmidt_pallas", "ilm")


@dataclasses.dataclass(frozen=True)
class DivisionConfig:
    """Precision dial per paper eq. 17: (n_iters, precision_bits) -> segments."""

    mode: str = "taylor"
    precision_bits: int = 24      # f32 mantissa target; bf16 would need only 8
    n_iters: int = 2              # paper: n=5 @ 53 bits; n=2 suffices @ 24 bits
    schedule: str = "factored"    # 'paper' | 'factored'
    rsqrt_newton: int = 2
    rsqrt_segments: int = 16
    # Subnormal policy of the jnp twins: "gradual" (default) is exact IEEE
    # gradual underflow via the bit-level datapath (core/fpparts.py);
    # "ftz" keeps the fused kernels' hardware flush contract. The Pallas,
    # ILM, and exact modes always deliver FTZ on this backend — see
    # :func:`effective_underflow`.
    underflow: str = "gradual"

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"mode {self.mode!r} not in {MODES}")
        if self.underflow not in UNDERFLOW_POLICIES:
            raise ValueError(
                f"underflow {self.underflow!r} not in {UNDERFLOW_POLICIES}")

    @property
    def table(self):
        return compute_segments(self.n_iters, self.precision_bits)

    @property
    def rtable(self):
        return rsqrt_seed_table(self.rsqrt_segments)

    @property
    def gs_iters(self) -> int:
        """Goldschmidt iterations matching this n_iters' covered-term count."""
        return goldschmidt.iters_for_terms(self.n_iters)


EXACT = DivisionConfig(mode="exact")
TAYLOR = DivisionConfig(mode="taylor")


def effective_underflow(cfg: DivisionConfig) -> str:
    """The subnormal policy a config actually delivers.

    Only the pure-jnp twins honor ``cfg.underflow``: the fused Pallas
    kernels flush by design (the hardware contract), the ILM emulation
    keeps its bit-faithful legacy datapath, and mode="exact" inherits the
    backend's behavior — FTZ/DAZ on this CPU backend, so it is reported
    (and conformance-masked) conservatively as "ftz".
    """
    return cfg.underflow if cfg.mode in ("taylor", "goldschmidt") else "ftz"


def recip(x, cfg: DivisionConfig = TAYLOR):

    if cfg.mode == "exact":
        return 1.0 / x
    if cfg.mode in ("taylor", "taylor_pallas"):
        if cfg.mode == "taylor_pallas":
            from repro.kernels import ops as kops

            if kops.pallas_applicable(x):
                return kops.tsdiv_recip(x, n_iters=cfg.n_iters,
                                        precision_bits=cfg.precision_bits,
                                        schedule=cfg.schedule)
        return taylor.reciprocal(x, cfg.table, schedule=cfg.schedule,
                                 underflow=effective_underflow(cfg))
    if cfg.mode in ("goldschmidt", "goldschmidt_pallas"):
        if cfg.mode == "goldschmidt_pallas":
            from repro.kernels import ops as kops

            if kops.pallas_applicable(x):
                return kops.tsdiv_recip(x, n_iters=cfg.n_iters,
                                        precision_bits=cfg.precision_bits,
                                        schedule="goldschmidt")
        return goldschmidt.reciprocal(x, cfg.table, iters=cfg.gs_iters,
                                      underflow=effective_underflow(cfg))
    if cfg.mode == "ilm":
        return _recip_ilm_jnp(x, cfg)
    raise ValueError(cfg.mode)


def div(a, b, cfg: DivisionConfig = TAYLOR):
    """a/b through the exponent-separated datapath (never a * recip(b)).

    Every approximate mode refines the mantissa pair in [1, 2) and applies
    the exponent difference once at the end, so the quotient is accurate
    whenever a/b is representable — even where the intermediate reciprocal
    would under/overflow (a = 2^100, b = 2^127). The Pallas modes dispatch
    to the fused divide kernel (schedule="goldschmidt" runs the joint N/D
    refinement in-kernel); ilm keeps the bit-faithful a * recip(b)
    emulation, whose under/overflow is part of what it emulates.
    """
    if cfg.mode == "exact":
        return a / b
    if cfg.mode == "ilm":
        import jax.numpy as jnp

        from . import fpparts

        aj, bj = jnp.broadcast_arrays(jnp.asarray(a), jnp.asarray(b))
        q = aj * recip(bj, cfg)
        # The special-value logic sits outside the mantissa datapath even in
        # the ILM unit: the composed multiply turns inf * (recip-underflow-
        # to-0) into nan where IEEE wants inf.
        s = fpparts.sign_product(jnp, aj, bj)
        return fpparts.div_edges(jnp, q, aj, bj, jnp.abs(aj), jnp.abs(bj), s)
    if cfg.mode in ("taylor_pallas", "goldschmidt_pallas"):
        import jax.numpy as jnp

        from repro.kernels import ops as kops

        aj, bj = jnp.broadcast_arrays(jnp.asarray(a), jnp.asarray(b))
        # Promote mixed operands up front (as a * recip(b) would have): the
        # kernel wrapper returns its first argument's dtype.
        ct = jnp.promote_types(aj.dtype, bj.dtype)
        aj, bj = aj.astype(ct), bj.astype(ct)
        if kops.pallas_applicable(aj) and kops.pallas_applicable(bj):
            sched = (cfg.schedule if cfg.mode == "taylor_pallas"
                     else "goldschmidt")
            return kops.tsdiv_divide(aj, bj, n_iters=cfg.n_iters,
                                     precision_bits=cfg.precision_bits,
                                     schedule=sched)
    if cfg.mode in ("goldschmidt", "goldschmidt_pallas"):
        # Goldschmidt's hallmark: the numerator rides the F-multiplies.
        return goldschmidt.divide(a, b, cfg.table, iters=cfg.gs_iters,
                                  underflow=effective_underflow(cfg))
    return taylor.divide(a, b, cfg.table, schedule=cfg.schedule,
                         underflow=effective_underflow(cfg))


def rsqrt(x, cfg: DivisionConfig = TAYLOR):
    """1/sqrt(x) through the mode the config names — no silent fallthrough.

    exact -> XLA ``lax.rsqrt``; taylor/goldschmidt -> the shared jnp
    PWL-seed + Newton datapath (rsqrt's accuracy dial is ``rsqrt_newton``,
    not the series depth, so the two jnp algorithm families deliberately
    share one body — see ROADMAP); taylor_pallas/goldschmidt_pallas -> the
    fused full-edge rsqrt kernel (``kernels.ops.tsdiv_rsqrt``, FTZ) with
    the jnp twin as the documented fallback for non-launchable operands
    (empty arrays, unsupported dtypes); ilm -> Newton iterations with every
    multiply through the 16-bit ILM (tests/benchmarks only, ~12-bit).
    """
    import jax

    if cfg.mode == "exact":
        return jax.lax.rsqrt(x)
    if cfg.mode in ("taylor_pallas", "goldschmidt_pallas"):
        import jax.numpy as jnp

        from repro.kernels import ops as kops

        if kops.pallas_applicable(jnp.asarray(x)):
            return kops.tsdiv_rsqrt(jnp.asarray(x),
                                    newton_iters=cfg.rsqrt_newton,
                                    n_segments=cfg.rsqrt_segments)
    if cfg.mode == "ilm":
        return _rsqrt_ilm_jnp(x, cfg)
    return taylor.rsqrt(x, cfg.rtable, newton_iters=cfg.rsqrt_newton,
                        underflow=effective_underflow(cfg))


def softmax(x, axis: int = -1, cfg: DivisionConfig = TAYLOR, where=None):
    """Numerically-stable softmax whose 1/sum goes through the division unit.

    Mode-faithful dispatch: the Pallas modes route to the fused softmax
    kernel (``kernels.ops.softmax`` — max/exp/sum/scale in one VMEM pass,
    schedule="goldschmidt" for mode="goldschmidt_pallas") whenever the
    operand is kernel-launchable, with the jnp twin below as the documented
    fallback for non-launchable operands (empty arrays, dtypes the kernels
    don't take). The fallback twin still routes its 1/sum through
    :func:`recip` under the same config — its f32 intermediates are
    launchable, so a Pallas config reaches the fused *scalar* unit even
    when the fused *consumer* kernel cannot run; both paths deliver the
    Pallas modes' FTZ policy (see :func:`effective_underflow`).
    Fully-masked rows (``where`` all-False, or every logit -inf) return
    zeros in every mode — never 0 * recip(0) = nan (nor 0/0 in exact
    mode).
    """
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(x)
    if x.ndim == 0:
        # A single logit normalizes to 1 — jnp.max over axis=-1 of a scalar
        # would raise instead of degrading gracefully.
        return jnp.ones_like(x)
    if x.shape[axis] == 0:
        return x                     # no logits: empty in, empty out
    if cfg.mode in ("taylor_pallas", "goldschmidt_pallas"):
        from repro.kernels import ops as kops

        if kops.pallas_applicable(x):
            ax = axis % x.ndim
            xm = x if where is None else jnp.where(where, x, -jnp.inf)
            if ax != x.ndim - 1:
                xm = jnp.moveaxis(xm, ax, -1)
            sched = (cfg.schedule if cfg.mode == "taylor_pallas"
                     else "goldschmidt")
            out = kops.softmax(xm, n_iters=cfg.n_iters,
                               precision_bits=cfg.precision_bits,
                               schedule=sched)
            if ax != x.ndim - 1:
                out = jnp.moveaxis(out, -1, ax)
            return out
    # f32 compute with the input dtype back out, like every datapath in
    # core/ (and like the fused kernel): a bf16 exp would round the shifted
    # logit to 8 bits and amplify by |arg| — tens of output ULPs on
    # wide-dynamic-range rows.
    xf = x.astype(jnp.float32)
    xmax = jnp.max(xf, axis=axis, keepdims=True, where=where,
                   initial=-jnp.inf if where is not None else None)
    xmax = jnp.where(jnp.isfinite(xmax), xmax, 0.0)
    ex = jnp.exp(xf - jax.lax.stop_gradient(xmax))
    if where is not None:
        ex = jnp.where(where, ex, 0.0)
    # One fixed summation order, shared with the fused kernel: the row-sum
    # gate is about the unit's 1/s, so s must not move with the compiler.
    s = jnp.moveaxis(tree_sum(jnp.moveaxis(ex, axis, -1)), -1, axis)
    # Fully-masked rows have ex == 0 lane-wise, so a divisor of 1 yields the
    # zero row exactly; rows with any surviving logit have s >= 1.
    safe = jnp.where(s == 0, jnp.ones_like(s), s)
    out = ex / safe if cfg.mode == "exact" else ex * recip(safe, cfg)
    return out.astype(x.dtype)


def rmsnorm(x, w, cfg: DivisionConfig = TAYLOR, *, eps: float = 1e-6):
    """RMSNorm over the last dim; the 1/sqrt runs the configured mode.

    The Pallas modes dispatch to the fused kernel (``kernels.ops.rmsnorm``:
    mean-of-squares -> PWL-seeded Newton rsqrt -> scale in one VMEM pass);
    every other mode runs the jnp twin with the rsqrt routed through
    :func:`rsqrt` — so exact/taylor/goldschmidt/ilm all answer to the same
    config knob. When a Pallas config's operand is not kernel-launchable
    (empty, unsupported dtype), the twin's f32 mean-of-squares still
    reaches the fused rsqrt kernel through :func:`rsqrt` — the scalar unit
    stays fused even when the consumer kernel cannot run. f32 compute,
    input dtype back out.
    """
    import jax.numpy as jnp

    x = jnp.asarray(x)
    w = jnp.asarray(w)
    if x.ndim == 0 or x.shape[-1] == 0:
        return x
    if cfg.mode in ("taylor_pallas", "goldschmidt_pallas"):
        from repro.kernels import ops as kops

        if kops.pallas_applicable(x):
            return kops.rmsnorm(x, w, eps=eps,
                                newton_iters=cfg.rsqrt_newton,
                                n_segments=cfg.rsqrt_segments)
    xf = x.astype(jnp.float32)
    ss = jnp.mean(xf * xf, axis=-1, keepdims=True)
    if cfg.mode == "exact":
        import jax

        r = jax.lax.rsqrt(ss + jnp.float32(eps))
    else:
        r = rsqrt(ss + jnp.float32(eps), cfg)
    return (xf * r * w.astype(jnp.float32)).astype(x.dtype)


def attention(q, k, v, cfg: DivisionConfig = TAYLOR, *, causal: bool = True):
    """Scaled dot-product attention with the softmax 1/l through the unit.

    q/k/v: (..., S, hd). The Pallas modes dispatch to the fused
    flash-attention kernel (online softmax, Dao et al., with the final 1/l
    normalization in the paper's division unit; schedule="goldschmidt" for
    mode="goldschmidt_pallas"); every other mode runs the jnp twin whose
    row softmax is :func:`softmax` under the same config — one knob for
    every algorithm family (for a Pallas config whose q/k/v are not
    kernel-launchable, the twin's f32 score softmax re-dispatches and
    reaches the fused softmax kernel). Ragged sequence lengths are handled
    by the kernel wrapper (pad-and-mask).
    """
    import jax.numpy as jnp

    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    if cfg.mode in ("taylor_pallas", "goldschmidt_pallas"):
        from repro.kernels import ops as kops

        if (kops.pallas_applicable(q) and kops.pallas_applicable(k)
                and kops.pallas_applicable(v)):
            sched = (cfg.schedule if cfg.mode == "taylor_pallas"
                     else "goldschmidt")
            return kops.flash_attention(q, k, v, causal=causal,
                                        n_iters=cfg.n_iters,
                                        precision_bits=cfg.precision_bits,
                                        schedule=sched)
    # One causal-mask sentinel for the twin and the fused kernel: parity
    # between the two is a gated metric, so the constant must not fork.
    from repro.kernels.flash_attention import NEG_INF

    hd = q.shape[-1]
    s = jnp.einsum("...qh,...kh->...qk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * jnp.float32(1.0 / np.sqrt(hd))
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, jnp.float32(NEG_INF))
    p = softmax(s, -1, cfg)
    return jnp.einsum("...qk,...kh->...qh", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _ilm_fpmul(mant_bits: int = 12, iters: int = 12):
    """Float multiply with the mantissa product through the 16-bit jnp ILM.

    Mantissas are quantized to ``mant_bits`` so ILM products fit uint32
    lanes; the result carries ~12-bit precision — the "programmable
    accuracy" end of the paper's dial. Shared by the ILM reciprocal and
    rsqrt emulations (tests/benchmarks only).
    """
    import jax.numpy as jnp

    from . import ilm as ilm_mod

    def fpmul(a, b):
        fa, ea = jnp.frexp(jnp.abs(a))
        fb, eb = jnp.frexp(jnp.abs(b))
        scale = 1 << (mant_bits - 1)
        ma = jnp.round(fa * 2 * scale).astype(jnp.uint32)
        mb = jnp.round(fb * 2 * scale).astype(jnp.uint32)
        p = ilm_mod.ilm_mul(ma, mb, iters).astype(jnp.float32)
        r = jnp.ldexp(p / (4.0 * scale * scale), (ea - 1) + (eb - 1) + 2)
        return r * jnp.sign(a) * jnp.sign(b)

    return fpmul


def _recip_ilm_jnp(x, cfg: DivisionConfig):
    """Reciprocal with every multiply routed through the 16-bit jnp ILM."""
    import jax.numpy as jnp

    from . import powering

    table = compute_segments(min(cfg.n_iters, 5), min(cfg.precision_bits, 12))
    fpmul = _ilm_fpmul()

    xf = x.astype(jnp.float32)
    frac, e = jnp.frexp(jnp.abs(xf))
    man = frac * 2.0
    inner = jnp.asarray(table.inner_boundaries, jnp.float32)
    idx = jnp.sum((man[..., None] >= inner).astype(jnp.int32), axis=-1)
    y0 = (jnp.take(jnp.asarray(table.slopes, jnp.float32), idx) * man
          + jnp.take(jnp.asarray(table.intercepts, jnp.float32), idx))
    m = 1.0 - fpmul(man, y0)
    n = table.n_iters
    powers = powering.eval_powers(m, n, mul=fpmul, square=lambda a: fpmul(a, a))
    acc = jnp.ones_like(m) + m
    for k in range(2, n + 1):
        acc = acc + powers[k]
    rman = fpmul(y0, acc)
    r = jnp.ldexp(rman, 1 - e) * jnp.sign(xf)
    # Hardware edge semantics, same as every other mode: +-0 -> +-inf
    # (inf * sign(0) would be nan), +-inf -> +-0, nan -> nan.
    r = jnp.where(xf == 0, jnp.copysign(jnp.float32(np.inf), xf), r)
    r = jnp.where(jnp.isinf(xf), jnp.copysign(jnp.float32(0.0), xf), r)
    r = jnp.where(jnp.isnan(xf), jnp.float32(np.nan), r)
    r = taylor.attach_grad(r, [(xf, -r * r)])
    return r.astype(x.dtype)


def _rsqrt_ilm_jnp(x, cfg: DivisionConfig):
    """rsqrt with every Newton multiply through the 16-bit jnp ILM.

    PWL chord seed on the parity-folded mantissa (same ROM as the jnp
    twins, via ``cfg.rtable``), then ``cfg.rsqrt_newton`` Newton steps whose
    y*y, u*y^2 and correction products all run the ILM — the ~12-bit end of
    the dial, the explicit implementation the dispatch used to silently
    replace with the Taylor datapath. FTZ semantics (subnormal operands are
    the zero class, like every ILM/kernel path), IEEE edges as elsewhere:
    ±0 -> ±inf, +inf -> +0, x < 0 and nan -> nan. Gradients via the shared
    custom_jvp rule (fpparts.jnp_rsqrt). Tests/benchmarks only.
    """
    import jax.numpy as jnp

    from . import fpparts

    table = cfg.rtable
    fpmul = _ilm_fpmul()

    def impl(xp, xf):
        ax = xp.abs(xf)
        frac, e = xp.frexp(ax)          # ax = frac * 2^e, frac in [0.5, 1)
        s = e >> 1
        u = xp.ldexp(frac, e - 2 * s)   # in [0.5, 2)
        inner = xp.asarray(table.inner_boundaries, xp.float32)
        idx = xp.sum((u[..., None] >= inner).astype(jnp.int32), axis=-1)
        y = (xp.take(xp.asarray(table.slopes, xp.float32), idx) * u
             + xp.take(xp.asarray(table.intercepts, xp.float32), idx))
        for _ in range(cfg.rsqrt_newton):    # honor the dial exactly, like
            t = fpmul(u, fpmul(y, y))        # every other rsqrt datapath
            y = fpmul(y, 1.5 - 0.5 * t)
        r = xp.ldexp(y, -s)
        # FTZ zero class (zeros and subnormal magnitudes) -> signed inf.
        tiny = jnp.float32(2.0 ** -126)
        r = xp.where(ax < tiny, xp.copysign(jnp.float32(np.inf), xf), r)
        r = xp.where(xp.isinf(xf) & (xf > 0), jnp.float32(0.0), r)
        neg = (xf < 0) & ~(ax < tiny)
        return xp.where(neg | xp.isnan(xf), jnp.float32(np.nan), r)

    return fpparts.jnp_rsqrt(x, impl)
