"""Shared layer primitives. Every division/rsqrt goes through core.division_modes."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import division_modes as dm
from repro.configs.base import ModelConfig


def rms_norm(x, w, div: dm.DivisionConfig, eps: float = 1e-6):
    """RMSNorm through the division unit's consumer dispatch: the Pallas
    modes run the fused kernel, everything else the jnp twin — one knob."""
    return dm.rmsnorm(x, w, div, eps=eps)


def rope(x, positions, theta: float):
    """Rotary embeddings. x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


def gated_mlp(p, x):
    """SwiGLU MLP: wo(silu(wg x) * (wi x))."""
    h = jnp.einsum("...d,df->...f", x, p["wi"])
    g = jax.nn.silu(jnp.einsum("...d,df->...f", x, p["wg"]).astype(jnp.float32))
    return jnp.einsum("...f,fd->...d", (g.astype(h.dtype) * h), p["wo"])


def embed_tokens(embed, tokens, cfg: ModelConfig):
    x = jnp.take(embed, tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def residual_scale(y, cfg: ModelConfig):
    """A mixer's or FFN's output as it joins the residual stream."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return y


def lm_logits(params, x, cfg: ModelConfig):
    """f32 logits; divided by ``logits_scaling`` through the division unit
    (one divisor for the whole plane) where the config sets one."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("...d,dv->...v", x, head,
                        preferred_element_type=jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = dm.div(logits, jnp.full((1, 1), cfg.logits_scaling,
                                         jnp.float32), cfg.division)
    return logits
