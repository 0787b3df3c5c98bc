"""Plain float32 forward of the Granite (Llama-equation) decoder.

Imports nothing of the program. One sequence at a time, the whole sequence
at once (no cache, no batching, no kernels), every product at precision
``highest`` on the float32 values of the given weights:

    x = E[t]
    per layer:  h = rmsnorm(x) * g1
                q, k, v = h Wq, h Wk, h Wv;  q, k rotated (RoPE)
                a = softmax(q k^T / sqrt(d_head) + causal mask) v   (GQA:
                    query head j reads key/value head j // (H / KV))
                x = x + a Wo
                h = rmsnorm(x) * g2
                x = x + (silu(h W_gate) * (h W_up)) W_down
    logits = (rmsnorm(x) * g_final) W_head

RoPE rotates the two halves of each head (the Hugging Face Llama layout),
frequency ``theta ** (-i / (d_head / 2))``; rmsnorm is ``x / sqrt(mean(x^2)
+ eps)``. Departures from the published Granite Code model, which the
program shares: no attention or MLP biases, and the ``rope_theta`` and
``rms_norm_eps`` of the configuration file. Queries are processed in
blocks so that the (heads, block, T) score plane fits.

``dot="fp8"`` is the control: every operand of every product rounded to
float8 e4m3 with one scale per tensor (amax / 448), the step below the
bfloat16 the configuration serves in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 512
PAD_TO = 512
E4M3_MAX = 448.0


def _q8(a):
    a = a.astype(jnp.float32)
    s = jnp.max(jnp.abs(a)) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, dot):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if dot == "fp8":
        a, b = _q8(a), _q8(b)
    elif dot != "highest":
        raise ValueError(f"unknown dot {dot!r}")
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (T, heads, d_head)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, pos, *, theta, eps, dot):
    t = x.shape[0]
    n_kv, d_head = w["wk"].shape[1], w["wk"].shape[2]
    n_h = w["wq"].shape[1]
    rep = n_h // n_kv
    h = _rmsnorm(x, w["attn_norm"], eps)
    q = _rope(_mm("td,dhk->thk", h, w["wq"], dot), pos, theta)
    k = _rope(_mm("td,dhk->thk", h, w["wk"], dot), pos, theta)
    v = _mm("td,dhk->thk", h, w["wv"], dot)
    qg = q.reshape(t, n_kv, rep, d_head)
    scale = 1.0 / jnp.sqrt(jnp.float32(d_head))

    def block(args):
        qb, pb = args                                  # (B, KV, rep, hd)
        s = _mm("qgrd,tgd->grqt", qb, k, dot) * scale
        s = jnp.where(pb[None, None, :, None] >= pos[None, None, None, :],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("grqt,tgd->qgrd", p, v, dot)

    nb = t // Q_BLOCK
    out = jax.lax.map(block, (qg.reshape(nb, Q_BLOCK, n_kv, rep, d_head),
                              pos.reshape(nb, Q_BLOCK)))
    a = out.reshape(t, n_h, d_head)
    x = x + _mm("thk,hkd->td", a, w["wo"], dot)
    h = _rmsnorm(x, w["mlp_norm"], eps)
    gate = _mm("td,df->tf", h, w["w_gate"], dot)
    up = _mm("td,df->tf", h, w["w_up"], dot)
    return x + _mm("tf,fd->td", jax.nn.silu(gate) * up, w["w_down"], dot)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "dot"))
def _forward(weights, tokens, *, theta, eps, dot):
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = weights["embed"][tokens].astype(jnp.float32)
    layers = {k: v for k, v in weights.items()
              if k not in ("embed", "final_norm", "lm_head")}

    def body(x, w):
        return _layer(x, w, pos, theta=theta, eps=eps, dot=dot), None

    x, _ = jax.lax.scan(body, x, layers)
    x = _rmsnorm(x, weights["final_norm"], eps)
    return _mm("td,dv->tv", x, weights["lm_head"], dot)


def logits_at(weights, tokens, want, *, theta: float, eps: float,
              dot: str = "highest"):
    """(len(want), vocab) float32 logits at positions ``want`` of the
    sequence ``tokens``.

    ``weights``: embed (V, d); per layer, stacked on a leading axis,
    attn_norm (d,), wq (d, H, hd), wk/wv (d, KV, hd), wo (H, hd, d),
    mlp_norm (d,), w_gate/w_up (d, F), w_down (F, d); final_norm (d,);
    lm_head (d, V). The sequence is padded to a multiple of 512 (causal, so
    padding moves no earlier position), so that few shapes compile.
    """
    import numpy as np

    t = len(tokens)
    tp = -(-t // PAD_TO) * PAD_TO
    toks = np.zeros((tp,), np.int32)
    toks[:t] = tokens
    out = _forward(weights, jnp.asarray(toks), theta=float(theta),
                   eps=float(eps), dot=dot)
    return out[jnp.asarray(np.asarray(want, np.int32))]
