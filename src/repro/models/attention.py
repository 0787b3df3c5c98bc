"""Attention: GQA full/causal, sliding-window (block-local), cross, and decode.

Softmax denominators route through the paper's division unit
(core.division_modes.softmax). The score scale (1/sqrt(head_dim), or the
config's ``attention_multiplier``) is a compile-time constant (no runtime
division); ``rope=False`` configs (NoPE) apply no position embedding.

Memory strategy: full attention is query-chunked (scan over query blocks,
keys whole) so 32k-token prefill never materializes an S x S score tensor;
sliding-window attention is block-local (each W-sized query block sees the
previous and current key blocks) making it O(S*W).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import division_modes as dm
from repro.configs.base import ModelConfig

NEG_INF = -1e30
# decode attention (decode_attention), op metadata for device traces
SCOPE_DECODE = "attn.decode"


def _proj_qkv(p, x, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    return q, k, v


def _repeat_kv(k, n_rep: int):
    from repro.sharding.rules import shard_dim

    if n_rep == 1:
        return shard_dim(k, 2)
    b, s, kv, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)
                         ).reshape(b, s, kv * n_rep, hd)
    # GQA head-repeat: pin the repeated heads to the model axis, else GSPMD
    # replicates the score tensors and inserts full-size all-reduces.
    return shard_dim(k, 2)


def _sdpa(q, k, v, mask, div: dm.DivisionConfig, scale: float):
    """q: (b,qs,h,hd), k/v: (b,ks,h,hd), mask: broadcastable to (b,h,qs,ks)."""
    from repro.sharding.rules import shard_dim

    q = shard_dim(q, 2)
    scores = jnp.einsum("bqhk,bthk->bhqt", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = shard_dim(scores, 1)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = dm.softmax(scores, axis=-1, cfg=div)
    out = jnp.einsum("bhqt,bthk->bqhk", probs.astype(v.dtype), v)
    return shard_dim(out, 2)


def full_attention(p, x, positions, cfg: ModelConfig, *, causal: bool = True,
                   kv_override: Optional[Tuple] = None, q_positions=None):
    """Training/prefill full attention, query-chunked above cfg.attn_chunk."""
    b, s, d = x.shape
    div = cfg.division
    scale = score_scale(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is not None:  # cross attention: k/v precomputed, no rope
        k, v = kv_override
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        q = rope_apply(q, positions, cfg)
        k = rope_apply(k, positions, cfg)
    k = _repeat_kv(k, cfg.q_per_kv)
    v = _repeat_kv(v, cfg.q_per_kv)
    t = k.shape[1]

    def attend_chunk(qc, qpos_c):
        if causal and kv_override is None:
            mask = qpos_c[:, None, :, None] >= positions[:, None, None, :]
        else:
            mask = jnp.ones((1, 1, 1, 1), bool)
        out = _sdpa(qc, k, v, mask, div, scale)
        return out

    chunk = cfg.attn_chunk
    if s <= chunk or s % chunk != 0:
        out = attend_chunk(q, positions)
    else:
        nb = s // chunk
        qs = q.reshape(b, nb, chunk, *q.shape[2:])
        ps = positions.reshape(b, nb, chunk)

        def body(_, xs):
            qc, pc = xs
            return None, attend_chunk(qc, pc)

        # scan over query chunks: (nb, b, chunk, ...)
        _, outs = jax.lax.scan(body, None,
                               (jnp.moveaxis(qs, 1, 0), jnp.moveaxis(ps, 1, 0)),
                               unroll=nb if cfg.scan_unroll else 1)
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s, *q.shape[2:])
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])


def sliding_attention(p, x, positions, cfg: ModelConfig):
    """Block-local sliding-window attention: O(S*W) compute and memory."""
    b, s, d = x.shape
    w = cfg.sliding_window
    div = cfg.division
    scale = score_scale(cfg)
    q, k, v = _proj_qkv(p, x, cfg)
    q = rope_apply(q, positions, cfg)
    k = rope_apply(k, positions, cfg)
    k = _repeat_kv(k, cfg.q_per_kv)
    v = _repeat_kv(v, cfg.q_per_kv)
    if s <= w:  # degenerate: plain causal attention
        mask = positions[:, None, :, None] >= positions[:, None, None, :]
        out = _sdpa(q, k, v, mask, div, scale)
        return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])
    assert s % w == 0, f"seq {s} must be a multiple of window {w}"
    nb = s // w
    h, hd = q.shape[2], q.shape[3]
    qb = q.reshape(b, nb, w, h, hd)
    kb = k.reshape(b, nb, w, h, hd)
    vb = v.reshape(b, nb, w, h, hd)
    zeros = jnp.zeros_like(kb[:, :1])
    kprev = jnp.concatenate([zeros, kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    k2 = jnp.concatenate([kprev, kb], axis=2)  # (b, nb, 2w, h, hd)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    qpos = jnp.arange(w)
    kpos = jnp.arange(2 * w) - w
    base = (qpos[:, None] >= kpos[None, :]) & (qpos[:, None] - kpos[None, :] < w)
    first = kpos[None, :] >= 0  # block 0 must not see the phantom prev block
    bidx = jnp.arange(nb)
    mask = base[None, :, :] & (first | (bidx[:, None, None] > 0))  # (nb, w, 2w)
    from repro.sharding.rules import shard_dim

    qb = shard_dim(qb, 3)
    k2 = shard_dim(k2, 3)
    v2 = shard_dim(v2, 3)
    scores = jnp.einsum("bnqhk,bnthk->bnhqt", qb, k2,
                        preferred_element_type=jnp.float32) * scale
    scores = shard_dim(scores, 2)
    scores = jnp.where(mask[None, :, None, :, :], scores, NEG_INF)
    probs = dm.softmax(scores, axis=-1, cfg=div)
    out = jnp.einsum("bnhqt,bnthk->bnqhk", probs.astype(v2.dtype), v2)
    out = shard_dim(out, 3)
    out = out.reshape(b, s, h, hd)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])


def score_scale(cfg: ModelConfig) -> float:
    """The attention score scale: ``attention_multiplier`` where the config
    sets one, else 1/sqrt(head_dim)."""
    if cfg.attention_multiplier is not None:
        return cfg.attention_multiplier
    return 1.0 / math.sqrt(cfg.head_dim)


def rope_apply(x, positions, cfg: ModelConfig):
    """RoPE at ``positions``; ``x`` unchanged where the config has no
    position embedding (``rope=False``, NoPE)."""
    if not cfg.rope:
        return x
    from .layers import rope

    return rope(x, positions, cfg.rope_theta)


# --------------------------------------------------------------------- cache

def init_cache_attn(cfg: ModelConfig, batch: int, max_len: int, window: int = 0,
                    dtype=jnp.bfloat16):
    length = window if window > 0 else max_len
    kv = cfg.n_kv_heads
    shape = (batch, length, kv, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def abstract_cache_attn(cfg: ModelConfig, batch: int, max_len: int, window: int = 0,
                        dtype=jnp.bfloat16):
    length = window if window > 0 else max_len
    shape = (batch, length, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def decode_positions(pos, batch: int):
    """Normalize decode ``pos`` to a (batch,) int32 vector.

    Accepts the legacy scalar (all requests at the same position) or a
    per-request (batch,) vector — the serving engine's padded-prompt fix:
    request i's tokens live at absolute positions 0..pos_i, so each slot
    writes, ropes, and masks at its own position.
    """
    pos_v = jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,))
    if pos_v.shape[0] == 1:
        pos_v = jnp.broadcast_to(pos_v, (batch,))
    return pos_v


def _decode_sdpa(q, k, v, valid, div: dm.DivisionConfig, scale: float):
    """One query token per slot against a cache in its stored layout.

    q: (b, 1, h, hd); k/v: (b, L, kv, hd); valid: (b, L) cache slots to
    attend, or None for all of them.

    Query head ``kv_i * g + j`` (``g = h // kv``, the order ``_repeat_kv``
    gives) attends kv head ``kv_i``: the products run over (kv head, query
    group), so the cache is read once and never repeated ``g``-fold. The
    softmax operand keeps its ``b*h`` rows of ``L``.
    """
    from repro.sharding.rules import shard_dim

    b, _, h, hd = q.shape
    kv = k.shape[2]
    qg = shard_dim(q.reshape(b, kv, h // kv, hd), 1)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = shard_dim(scores, 1)
    if valid is not None:
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = dm.softmax(scores, axis=-1, cfg=div)
    out = jnp.einsum("bkgt,btkd->bkgd", probs.astype(v.dtype), v)
    return shard_dim(out, 1).reshape(b, 1, h, hd)


@jax.named_scope(SCOPE_DECODE)
def decode_attention(p, x, cache, pos, cfg: ModelConfig, *, window: int = 0,
                     kv_override: Optional[Tuple] = None):
    """One-token decode. x: (b, 1, d); cache k/v: (b, L, kv, hd); pos: scalar
    or per-request (b,) vector of absolute positions.

    Full-attention layers index the cache at pos; sliding-window layers treat
    the cache as a ring buffer of size W (softmax is permutation-invariant, so
    ring order needs no unrotation). With a per-request pos vector each
    request writes its own slot, and the validity mask excludes every cache
    slot the request has not written/prefilled — in a padded batch the pad
    slots at positions >= len(prompt_i) are never attended (they sit above
    pos_i until the request's own generated tokens overwrite them).
    """
    b, one, d = x.shape
    div = cfg.division
    scale = score_scale(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is not None:
        out = _decode_sdpa(q, *kv_override, None, div, scale)
        return jnp.einsum("bqhk,hkd->bqd", out, p["wo"]), cache
    pos_v = decode_positions(pos, b)
    posv = pos_v[:, None]
    q = rope_apply(q, posv, cfg)
    k_new = rope_apply(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), posv, cfg)
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    L = cache["k"].shape[1]
    slot_v = jnp.mod(pos_v, L) if window > 0 else pos_v
    bidx = jnp.arange(b)
    k_c = cache["k"].at[bidx, slot_v].set(k_new[:, 0].astype(cache["k"].dtype))
    v_c = cache["v"].at[bidx, slot_v].set(v_new[:, 0].astype(cache["v"].dtype))
    idx = jnp.arange(L)
    if window == 0:
        valid = idx[None, :] <= pos_v[:, None]
    else:
        # Ring invariant: slot j holds the latest position p <= pos_i with
        # p % W == j (prefill builds rings the same way). held < 0 marks a
        # slot whose position would predate the sequence — never written.
        held = pos_v[:, None] - jnp.mod(pos_v[:, None] - idx[None, :], L)
        valid = held >= 0
    out = _decode_sdpa(q, k_c, v_c, valid, div, scale)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"]), {"k": k_c, "v": v_c}
