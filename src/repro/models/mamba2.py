"""Mamba-2 (SSD, state-space duality) mixer: chunked train/prefill + recurrent decode.

Faithful minimal SSD (arXiv:2405.21060 listing 1 semantics):
  state:  S_t = exp(dt_t * A) S_{t-1} + dt_t * B_t x_t^T      (per head)
  output: y_t = C_t . S_t + D * x_t
Chunked form: intra-chunk attention-like term via the decay matrix
L[i,j] = exp(a_i - a_j) (i >= j, a = within-chunk cumsum of dt*A), plus the
inter-chunk carried state propagated by a lax.scan over chunks.

The projections are split (wz/wx/wB/wC/wdt + per-part depthwise convs) so the
'ssm_inner' dim shards cleanly over the model axis without slicing a fused
projection at unaligned offsets.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import division_modes as dm

# Named scopes (op metadata only): every Mamba layer runs under
# ``mamba.mixer`` (models/model.py), the chunked SSD of prefill under
# ``mamba.scan`` and the decode recurrence with its conv-state shift under
# ``mamba.step``.
SCOPE_MIXER = "mamba.mixer"
SCOPE_SCAN = "mamba.scan"
SCOPE_STEP = "mamba.step"


def _conv_bias(p, name: str):
    """The conv's bias (``mamba_conv_bias``), or None."""
    return p.get(f"conv_{name}_bias")


def _causal_conv(u, w, width: int, bias=None):
    """Depthwise causal conv via explicit shifts. u: (b, l, c); w: (width, c)."""
    out = u * w[-1]
    for k in range(1, width):
        shifted = jnp.pad(u, ((0, 0), (k, 0), (0, 0)))[:, : u.shape[1]]
        out = out + shifted * w[-1 - k]
    if bias is not None:
        out = out + bias
    return out


def _segsum_decay(a):
    """L[i,j] = exp(cumsum_i - cumsum_j) masked to i >= j. a: (..., q)."""
    q = a.shape[-1]
    ac = jnp.cumsum(a, axis=-1)
    diff = ac[..., :, None] - ac[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, jnp.exp(diff), 0.0)


def _ssd(p: Dict, xs_raw, B_raw, C_raw, dt_raw, cfg: ModelConfig, *,
         initial_state=None, lengths=None):
    """The chunked SSD of a whole sequence: convs, gates and the state
    scan. Returns (y (b, l, d_inner) f32, last state (b, h, p, n))."""
    b, l = xs_raw.shape[:2]
    h, pdim, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    q = min(cfg.ssm_chunk, l)
    assert l % q == 0, f"seq {l} not divisible by chunk {q}"
    nc = l // q

    xs = _causal_conv(xs_raw, p["conv_x"], cfg.conv_width,
                      _conv_bias(p, "x"))
    Bc = _causal_conv(B_raw, p["conv_B"], cfg.conv_width,
                      _conv_bias(p, "B")).astype(jnp.float32)
    Cc = _causal_conv(C_raw, p["conv_C"], cfg.conv_width,
                      _conv_bias(p, "C")).astype(jnp.float32)
    xs = jax.nn.silu(xs.astype(jnp.float32))
    Bc = jax.nn.silu(Bc)
    Cc = jax.nn.silu(Cc)

    dt = jax.nn.softplus(dt_raw + p["dt_bias"].astype(jnp.float32))  # (b,l,h)
    if lengths is not None:
        tmask = jnp.arange(l)[None, :] < lengths[:, None]            # (b,l)
        dt = dt * tmask[:, :, None]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                     # (h,)
    xh = xs.reshape(b, l, h, pdim)                                   # heads split

    # chunk views
    xc = xh.reshape(b, nc, q, h, pdim)
    dtc = dt.reshape(b, nc, q, h)
    Bq = Bc.reshape(b, nc, q, n)
    Cq = Cc.reshape(b, nc, q, n)
    adt = dtc * A  # (b,nc,q,h)

    # ---- intra-chunk (diagonal blocks)
    Ldec = _segsum_decay(jnp.moveaxis(adt, -1, -2))          # (b,nc,h,q,q)
    scores = jnp.einsum("bcin,bcjn->bcij", Cq, Bq)           # (b,nc,q,q)
    w = scores[:, :, None, :, :] * Ldec                      # (b,nc,h,i,j)
    xw = xc * dtc[..., None]                                 # dt_j x_j
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", w, xw)

    # ---- per-chunk end states: S_c = sum_j exp(a_end - a_j) dt_j B_j x_j^T
    acum = jnp.cumsum(adt, axis=2)                           # (b,nc,q,h)
    decay_to_end = jnp.exp(acum[:, :, -1:, :] - acum)        # (b,nc,q,h)
    Sc = jnp.einsum("bcjh,bcjn,bcjhp->bchpn", decay_to_end * dtc, Bq, xc)

    # ---- inter-chunk scan
    chunk_decay = jnp.exp(acum[:, :, -1, :])                 # (b,nc,h)
    S0 = (jnp.zeros((b, h, pdim, n), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def body(S_prev, xs_c):
        S_c, dec_c = xs_c                                    # (b,h,p,n), (b,h)
        S_new = S_c + dec_c[..., None, None] * S_prev
        return S_new, S_prev

    S_last, S_prevs = jax.lax.scan(
        body, S0, (jnp.moveaxis(Sc, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
        unroll=nc if cfg.scan_unroll else 1)
    S_prevs = jnp.moveaxis(S_prevs, 0, 1)                    # (b,nc,h,p,n)

    y_inter = jnp.einsum("bcin,bcih,bchpn->bcihp",
                         Cq, jnp.exp(acum), S_prevs)
    y = (y_intra + y_inter).reshape(b, l, h, pdim)
    y = y + p["D"].astype(jnp.float32)[None, None, :, None] * xh
    return y.reshape(b, l, h * pdim), S_last


def mamba_mixer(p: Dict, x, cfg: ModelConfig, *, initial_state=None,
                return_state: bool = False, lengths=None):
    """x: (b, l, d_model) -> (b, l, d_model). Chunked SSD over cfg.ssm_chunk.

    ``lengths`` (per-request real lengths of a right-padded batch) zeroes dt
    at pad positions, so decay = exp(0*A) = 1 and input contribution dt*B*x = 0
    there: the returned state equals the state after each row's real tokens.
    """
    b, l, _ = x.shape
    z = jnp.einsum("bld,di->bli", x, p["wz"])
    xs_raw = jnp.einsum("bld,di->bli", x, p["wx"])
    B_raw = jnp.einsum("bld,dn->bln", x, p["wB"])
    C_raw = jnp.einsum("bld,dn->bln", x, p["wC"])
    dt_raw = jnp.einsum("bld,dh->blh", x, p["wdt"]).astype(jnp.float32)

    with jax.named_scope(SCOPE_SCAN):
        y, S_last = _ssd(p, xs_raw, B_raw, C_raw, dt_raw, cfg,
                         initial_state=initial_state, lengths=lengths)

    # gated RMSNorm then output projection
    from .layers import rms_norm
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y.astype(x.dtype), p["norm"], cfg.division, cfg.norm_eps)
    out = jnp.einsum("bli,id->bld", y, p["wout"])
    if return_state:
        wm1 = cfg.conv_width - 1

        def tail(u):
            if lengths is None:
                return u[:, -wm1:]
            # last wm1 REAL positions per row; pos < 0 (prompt shorter than
            # the conv window) matches the zero-initialized decode conv state.
            tpos = lengths[:, None] - wm1 + jnp.arange(wm1)[None, :]
            ok = (tpos >= 0)[:, :, None]
            g = jnp.take_along_axis(u, jnp.maximum(tpos, 0)[:, :, None], axis=1)
            return jnp.where(ok, g, 0).astype(u.dtype)

        new_cache = {
            "state": S_last,
            "conv_x": tail(xs_raw).astype(jnp.float32).astype(xs_raw.dtype),
            "conv_B": tail(B_raw),
            "conv_C": tail(C_raw),
        }
        return out, new_cache
    return out


# --------------------------------------------------------------------- cache

def init_cache_mamba(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    h, pdim, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    wm1 = cfg.conv_width - 1
    return {
        "state": jnp.zeros((batch, h, pdim, n), jnp.float32),
        "conv_x": jnp.zeros((batch, wm1, cfg.d_inner), dtype),
        "conv_B": jnp.zeros((batch, wm1, n), dtype),
        "conv_C": jnp.zeros((batch, wm1, n), dtype),
    }


def abstract_cache_mamba(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        init_cache_mamba(cfg, batch, dtype))


def _conv_step(u_new, conv_state, w, bias=None):
    """One-token causal conv. u_new: (b,1,c); conv_state: (b, width-1, c)."""
    window = jnp.concatenate([conv_state, u_new], axis=1)  # (b, width, c)
    out = jnp.einsum("bwc,wc->bc", window, w)[:, None, :]
    if bias is not None:
        out = out + bias
    return out, window[:, 1:]


def decode_mamba(p: Dict, x, cache, cfg: ModelConfig):
    """One-token recurrent step. x: (b, 1, d_model)."""
    b = x.shape[0]
    h, pdim, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state

    z = jnp.einsum("bld,di->bli", x, p["wz"])
    xs = jnp.einsum("bld,di->bli", x, p["wx"])
    Bc = jnp.einsum("bld,dn->bln", x, p["wB"])
    Cc = jnp.einsum("bld,dn->bln", x, p["wC"])
    dt_raw = jnp.einsum("bld,dh->blh", x, p["wdt"]).astype(jnp.float32)

    with jax.named_scope(SCOPE_STEP):
        xs, cx = _conv_step(xs, cache["conv_x"], p["conv_x"],
                            _conv_bias(p, "x"))
        Bc, cB = _conv_step(Bc, cache["conv_B"], p["conv_B"],
                            _conv_bias(p, "B"))
        Cc, cC = _conv_step(Cc, cache["conv_C"], p["conv_C"],
                            _conv_bias(p, "C"))
        xs = jax.nn.silu(xs.astype(jnp.float32))
        Bc = jax.nn.silu(Bc.astype(jnp.float32))[:, 0]          # (b,n)
        Cc = jax.nn.silu(Cc.astype(jnp.float32))[:, 0]
        dt = jax.nn.softplus(dt_raw + p["dt_bias"].astype(jnp.float32))[:, 0]  # (b,h)
        A = -jnp.exp(p["A_log"].astype(jnp.float32))

        xh = xs.reshape(b, h, pdim)
        S = cache["state"]
        decay = jnp.exp(dt * A)                                  # (b,h)
        S_new = (decay[..., None, None] * S
                 + jnp.einsum("bh,bn,bhp->bhpn", dt, Bc, xh))
        y = jnp.einsum("bn,bhpn->bhp", Cc, S_new)
        y = y + p["D"].astype(jnp.float32)[None, :, None] * xh
        y = y.reshape(b, 1, h * pdim)

    from .layers import rms_norm
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y.astype(x.dtype), p["norm"], cfg.division, cfg.norm_eps)
    out = jnp.einsum("bli,id->bld", y, p["wout"])
    new_cache = {"state": S_new, "conv_x": cx, "conv_B": cB, "conv_C": cC}
    return out, new_cache
