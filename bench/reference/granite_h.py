"""Plain float32 forward of Granite 4.0-H (``granitemoehybrid``): Mamba-2
and NoPE GQA attention layers, each followed by a sparse expert layer with
a shared expert.

Imports nothing of the program. One sequence at a time, the whole sequence
at once (no cache, no batching, no kernels), every product at precision
``highest`` on the float32 values of the given weights:

    x = E[t] * embedding_multiplier
    per layer:  h = rmsnorm(x) * g1
                Mamba-2:   z, u, B, C, dt_raw = h Wz, h Wx, h WB, h WC, h Wdt
                           u, B, C = silu(causal depthwise conv + bias)
                           dt = softplus(dt_raw + dt_bias), A = -exp(A_log)
                           S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T (per
                           head, token by token: the recurrence, not the
                           chunked SSD), y_t = S_t C_t + D x_t
                           m = (rmsnorm(y * silu(z)) * g_y) Wout
                or attention: q, k, v = h Wq, h Wk, h Wv (no rotation)
                           m = softmax(q k^T * attention_multiplier +
                           causal mask) v Wo  (query head j reads key/value
                           head j // (H / KV))
                x = x + m * residual_multiplier
                h = rmsnorm(x) * g2
                the router's logits over all experts, the top k of them,
                softmax over those k: each chosen expert's gate
                f = sum over the chosen experts held here of
                    gate * (silu(h Wg_e) * (h Wu_e)) Wd_e
                    + (silu(h Wg_s) * (h Wu_s)) Wd_s      (shared expert)
                x = x + f * residual_multiplier
    logits = ((rmsnorm(x) * g_final) E^T) / logits_scaling

rmsnorm is ``x / sqrt(mean(x^2) + eps)``. Departures from the published
model, which the program shares: only the experts held here (``held`` in
``hp``) contribute, the others' part lies on other chips of the deployment;
the published top-k softmax is taken over the chosen logits (the program
renormalizes a softmax over all of them, the same gates). Sequences are
padded to a multiple of 512 so that few shapes compile: pads come after
every real position, attention is causal, and the recurrence holds its
state over pads (dt = 0), so no real position or returned state sees them.

``dot="fp8"`` is the control: every operand of every product rounded to
float8 e4m3 with one scale per tensor (amax / 448), the step below the
bfloat16 the configuration serves in.

Weights (``weights``): ``embed`` (V, d), ``final_norm`` (d,) and
``layers``, a list of dicts, each with ``kind`` ("mamba" or "attn"),
``norm1``, ``norm2`` (d,); Mamba: ``wz``, ``wx`` (d, d_in), ``wB``, ``wC``
(d, n), ``wdt`` (d, H), ``conv_x`` (w, d_in), ``conv_B``, ``conv_C``
(w, n), ``conv_x_bias``, ``conv_B_bias``, ``conv_C_bias``, ``A_log``,
``dt_bias``, ``D`` (H,), ``gnorm`` (d_in,), ``wout`` (d_in, d); attention:
``wq`` (d, H, hd), ``wk``, ``wv`` (d, KV, hd), ``wo`` (H, hd, d); experts:
``router`` (d, E), ``w_up``, ``w_gate`` (E_held, d, f), ``w_down``
(E_held, f, d), ``s_up``, ``s_gate`` (d, f_s), ``s_down`` (f_s, d).
Hyperparameters (``hp``): ``eps``, ``k``, ``held`` (first expert held,
one past the last), ``ssm_head_dim``, ``embedding_multiplier``,
``residual_multiplier``, ``attention_multiplier``, ``logits_scaling``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# ------------------------------------------------------------------ mixers

def _conv(u, w, bias):
    """Causal depthwise conv of u (T, c) with w (width, c), plus bias."""
    width = w.shape[0]
    up = jnp.pad(u, ((width - 1, 0), (0, 0)))
    t = u.shape[0]
    return sum(up[i:i + t] * w[i] for i in range(width)) + bias


def _mamba(h, w, n_real, hp, dot):
    """(out (T, d), last state (H, P, N), conv state (width-1, d_in+2N))."""
    t = h.shape[0]
    z = _mm("td,di->ti", h, w["wz"], dot)
    u_raw = _mm("td,di->ti", h, w["wx"], dot)
    b_raw = _mm("td,dn->tn", h, w["wB"], dot)
    c_raw = _mm("td,dn->tn", h, w["wC"], dot)
    dt_raw = _mm("td,dh->th", h, w["wdt"], dot)
    u = jax.nn.silu(_conv(u_raw, w["conv_x"], w["conv_x_bias"]))
    bb = jax.nn.silu(_conv(b_raw, w["conv_B"], w["conv_B_bias"]))
    cc = jax.nn.silu(_conv(c_raw, w["conv_C"], w["conv_C_bias"]))
    dt = jax.nn.softplus(dt_raw + w["dt_bias"])
    dt = jnp.where(jnp.arange(t)[:, None] < n_real, dt, 0.0)   # pads: no-ops
    a = -jnp.exp(w["A_log"])
    heads = a.shape[0]
    xh = u.reshape(t, heads, hp["ssm_head_dim"])

    def step(s, inp):
        dt_t, x_t, b_t, c_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + dt_t[:, None, None] * x_t[:, :, None] * b_t[None, None, :]
        return s, _mm("hpn,n->hp", s, c_t, dot)

    s0 = jnp.zeros((heads, hp["ssm_head_dim"], bb.shape[-1]), jnp.float32)
    s_last, y = jax.lax.scan(step, s0, (dt, xh, bb, cc))
    y = y.reshape(t, heads, -1) + w["D"][None, :, None] * xh
    y = y.reshape(t, -1) * jax.nn.silu(z)
    y = _rmsnorm(y, w["gnorm"], hp["eps"])
    out = _mm("ti,id->td", y, w["wout"], dot)
    width = w["conv_x"].shape[0]
    raw = jnp.concatenate([u_raw, b_raw, c_raw], axis=-1)
    raw = jnp.pad(raw, ((width - 1, 0), (0, 0)))
    conv_state = jax.lax.dynamic_slice_in_dim(raw, n_real, width - 1)
    return out, s_last, conv_state


def _attention(h, w, hp, dot):
    """(out (T, d), (keys, values) (T, KV, hd))."""
    t = h.shape[0]
    n_kv, d_head = w["wk"].shape[1], w["wk"].shape[2]
    n_h = w["wq"].shape[1]
    rep = n_h // n_kv
    q = _mm("td,dhk->thk", h, w["wq"], dot)
    k = _mm("td,dhk->thk", h, w["wk"], dot)
    v = _mm("td,dhk->thk", h, w["wv"], dot)
    qg = q.reshape(t, n_kv, rep, d_head)
    pos = jnp.arange(t)

    def block(args):
        qb, pb = args
        s = _mm("qgrd,tgd->grqt", qb, k, dot) * hp["attention_multiplier"]
        s = jnp.where(pb[None, None, :, None] >= pos[None, None, None, :],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("grqt,tgd->qgrd", p, v, dot)

    nb = t // Q_BLOCK
    out = jax.lax.map(block, (qg.reshape(nb, Q_BLOCK, n_kv, rep, d_head),
                              pos.reshape(nb, Q_BLOCK)))
    a = out.reshape(t, n_h, d_head)
    return _mm("thk,hkd->td", a, w["wo"], dot), (k, v)


def _experts(h, w, hp, dot):
    """The held experts' part plus the shared expert, (T, d)."""
    logits = _mm("td,de->te", h, w["router"], dot)
    top, idx = jax.lax.top_k(logits, hp["k"])
    gate = jax.nn.softmax(top, axis=-1)                          # (T, k)
    lo, hi = hp["held"]
    mine = jnp.arange(lo, hi)
    g = jnp.sum(jnp.where(idx[:, None, :] == mine[None, :, None],
                          gate[:, None, :], 0.0), axis=-1)       # (T, E_held)
    up = _mm("td,edf->etf", h, w["w_up"], dot)
    act = jax.nn.silu(_mm("td,edf->etf", h, w["w_gate"], dot)) * up
    f = _mm("etf,efd->etd", act * g.T[:, :, None], w["w_down"], dot)
    shared = jax.nn.silu(_mm("td,df->tf", h, w["s_gate"], dot)) \
        * _mm("td,df->tf", h, w["s_up"], dot)
    return f.sum(axis=0) + _mm("tf,fd->td", shared, w["s_down"], dot)


@functools.partial(jax.jit, static_argnames=("kind", "hp", "dot"))
def _layer(x, w, n_real, *, kind, hp, dot):
    hp = dict(hp)
    w = _f32(w)
    h = _rmsnorm(x, w["norm1"], hp["eps"])
    if kind == "mamba":
        m, s, conv = _mamba(h, w, n_real, hp, dot)
        state = (s, conv)
    else:
        m, state = _attention(h, w, hp, dot)
    x = x + m * hp["residual_multiplier"]
    h = _rmsnorm(x, w["norm2"], hp["eps"])
    x = x + _experts(h, w, hp, dot) * hp["residual_multiplier"]
    return x, state


@functools.partial(jax.jit, static_argnames=("hp",))
def _embed(embed, tokens, *, hp):
    return embed[tokens].astype(jnp.float32) * dict(hp)["embedding_multiplier"]


@functools.partial(jax.jit, static_argnames=("hp", "dot"))
def _head(x, final_norm, embed, rows, *, hp, dot):
    hp = dict(hp)
    x = _rmsnorm(x[rows], final_norm.astype(jnp.float32), hp["eps"])
    return _mm("td,vd->tv", x, embed, dot) / hp["logits_scaling"]


def _padded(tokens, length=None):
    """``tokens`` padded to ``length`` positions (a multiple of 512), or to
    the next multiple of 512."""
    t = len(tokens)
    n = length if length is not None else -(-t // PAD_TO) * PAD_TO
    if n % PAD_TO or n < t:
        raise ValueError(f"cannot pad {t} tokens to {n}")
    toks = np.zeros((n,), np.int32)
    toks[:t] = tokens
    return jnp.asarray(toks)


def _hp(hp: dict):
    """A hashable copy of ``hp`` (jit's static argument)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in hp.items()))


def _run(weights, tokens, hp, dot, length):
    """The residual stream after the last layer, and each layer's state."""
    h = _hp(hp)
    n_real = jnp.int32(len(tokens))
    x = _embed(weights["embed"], _padded(tokens, length), hp=h)
    states = []
    for w in weights["layers"]:
        lw = {k: v for k, v in w.items() if k != "kind"}
        x, st = _layer(x, lw, n_real, kind=w["kind"], hp=h, dot=dot)
        states.append(st)
    return x, states


def logits_at(weights, tokens, want, hp: dict, dot: str = "highest",
              length=None):
    """(len(want), vocab) float32 logits at positions ``want`` of the
    sequence ``tokens``, computed over the sequence padded to ``length``
    positions (default: the next multiple of 512)."""
    x, _ = _run(weights, tokens, hp, dot, length)
    rows = np.zeros((-(-len(want) // 128) * 128,), np.int32)
    rows[:len(want)] = want
    out = _head(x, weights["final_norm"], weights["embed"], jnp.asarray(rows),
                hp=_hp(hp), dot=dot)
    return out[:len(want)]


def state_at(weights, tokens, hp: dict, dot: str = "highest", length=None):
    """What a decode cache holds after the sequence ``tokens``, per layer,
    as host float32 arrays: for a Mamba layer ``{"state": (H, P, N),
    "conv": (width-1, d_in + 2N)}`` (the last width-1 positions' x, B and
    C before the conv), for an attention layer ``{"k": (T, KV, hd),
    "v": (T, KV, hd)}``. ``length`` as ``logits_at``."""
    _, states = _run(weights, tokens, hp, dot, length)
    t = len(tokens)
    out = []
    for w, st in zip(weights["layers"], states):
        if w["kind"] == "mamba":
            out.append({"state": np.asarray(st[0]),
                        "conv": np.asarray(st[1])})
        else:
            out.append({"k": np.asarray(st[0])[:t],
                        "v": np.asarray(st[1])[:t]})
    return out
