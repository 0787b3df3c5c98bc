"""Model assembly: pattern-driven layer stacks lowered as per-group lax.scans.

A config's layer pattern (mixer x ffn per layer) is grouped into periodic
blocks (configs.base.Group); each group lowers as ONE lax.scan over its
``repeat`` dim with parameters stacked on a leading 'layers' axis. HLO size is
O(period), not O(depth) — Jamba's 72 layers compile as a 9-iteration scan over
an 8-layer body. Caches stack the same way and ride the scan as xs/ys.

Modes: 'train' (no cache), 'prefill' (emit cache), 'decode' (carry cache).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import Group, LayerSpec, ModelConfig
from .attention import (abstract_cache_attn, decode_attention, full_attention,
                        init_cache_attn, sliding_attention)
from .layers import embed_tokens, gated_mlp, lm_logits, residual_scale, rms_norm
from .mamba2 import (SCOPE_MIXER, abstract_cache_mamba, decode_mamba,
                     init_cache_mamba, mamba_mixer)
from .moe import moe_ffn


# ----------------------------------------------------------------- kv capture

def _kv_for_cache(p, x, positions, cfg: ModelConfig):
    """Recompute post-rope K/V for prefill cache. XLA CSEs these einsums with
    the ones inside the attention call (identical operands)."""
    from .attention import rope_apply

    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    k = rope_apply(k, positions, cfg)
    return k, v


def _ring_from_prefill(k, window: int, lengths=None):
    """Arrange the last `window` entries into ring order slot = pos % window.

    With per-request ``lengths`` (padded-prompt serving), each row's ring
    holds its last ``window`` REAL positions (lengths[i]-window .. lengths[i]-1)
    so right-pad tokens never enter the sliding-window cache; positions < 0
    (prompt shorter than the window) leave zero slots that decode's validity
    mask excludes.
    """
    b, s = k.shape[0], k.shape[1]
    if lengths is None:
        if s <= window:
            pad = [(0, 0)] * k.ndim
            pad[1] = (0, window - s)
            return jnp.pad(k, pad)
        last = k[:, -window:]
        slots = jnp.mod(jnp.arange(s - window, s), window)
        ring = jnp.zeros((b, window, *k.shape[2:]), k.dtype)
        return ring.at[:, slots].set(last)
    pos = lengths[:, None] - window + jnp.arange(window)[None, :]     # (b, W)
    ok = (pos >= 0)[:, :, None, None]
    gathered = jnp.take_along_axis(k, jnp.maximum(pos, 0)[:, :, None, None],
                                   axis=1)
    gathered = jnp.where(ok, gathered, 0)
    # pos covers window consecutive ints per row, so mod is a bijection onto
    # slots — invalid (negative) entries land on slots no valid entry claims.
    slot = jnp.mod(pos, window)
    ring = jnp.zeros((b, window, *k.shape[2:]), k.dtype)
    return ring.at[jnp.arange(b)[:, None], slot].set(gathered)


# --------------------------------------------------------------- block fwd

def block_forward(bp: Dict, x, spec: LayerSpec, cfg: ModelConfig, positions,
                  *, mode: str, cache=None, pos=None, enc_out=None,
                  lengths=None):
    """Returns (x, new_cache, aux). ``lengths`` (prefill only): per-request
    real prompt lengths of a right-padded batch — pad positions become SSM
    no-ops and are excluded from sliding-window rings."""
    div = cfg.division
    aux = jnp.float32(0.0)
    new_cache: Dict[str, Any] = {}

    h = rms_norm(x, bp["mixer_norm"], div, cfg.norm_eps)
    if spec.mixer == "mamba":
        with jax.named_scope(SCOPE_MIXER):
            if mode == "decode":
                mh, new_cache["mamba"] = decode_mamba(bp["mamba"], h,
                                                      cache["mamba"], cfg)
            elif mode == "prefill":
                mh, new_cache["mamba"] = mamba_mixer(bp["mamba"], h, cfg,
                                                     return_state=True,
                                                     lengths=lengths)
            else:
                mh = mamba_mixer(bp["mamba"], h, cfg)
        x = x + residual_scale(mh, cfg)
    else:
        window = cfg.sliding_window if spec.mixer == "swa" else 0
        if mode == "decode":
            ah, new_cache["attn"] = decode_attention(
                bp["attn"], h, cache["attn"], pos, cfg, window=window)
        else:
            fn = sliding_attention if spec.mixer == "swa" else full_attention
            ah = fn(bp["attn"], h, positions, cfg)
            if mode == "prefill":
                k, v = _kv_for_cache(bp["attn"], h, positions, cfg)
                if window:
                    k = _ring_from_prefill(k, window, lengths)
                    v = _ring_from_prefill(v, window, lengths)
                new_cache["attn"] = {"k": k.astype(cfg.param_dtype),
                                     "v": v.astype(cfg.param_dtype)}
        x = x + residual_scale(ah, cfg)

    if "cross" in bp:  # encoder-decoder cross attention
        hc = rms_norm(x, bp["cross_norm"], div, cfg.norm_eps)
        if mode == "decode":
            ck, cv = cache["cross"]["ck"], cache["cross"]["cv"]
            ch, _ = decode_attention(bp["cross"], hc, None, pos, cfg,
                                     kv_override=(ck, cv))
            new_cache["cross"] = cache["cross"]
        else:
            ck = jnp.einsum("bsd,dhk->bshk", enc_out, bp["cross"]["wk"])
            cv = jnp.einsum("bsd,dhk->bshk", enc_out, bp["cross"]["wv"])
            ch = full_attention(bp["cross"], hc, positions, cfg, causal=False,
                                kv_override=(ck, cv))
            if mode == "prefill":
                new_cache["cross"] = {"ck": ck, "cv": cv}
        x = x + ch

    if spec.ffn != "none":
        h2 = rms_norm(x, bp["ffn_norm"], div, cfg.norm_eps)
        if spec.ffn == "moe":
            ff, a = moe_ffn(bp["ffn"], h2, cfg)
            aux = aux + a
        else:
            ff = gated_mlp(bp["ffn"], h2)
        x = x + residual_scale(ff, cfg)
    return x, new_cache, aux


# --------------------------------------------------------------- group scan

def _group_forward(gparams, group: Group, x, cfg: ModelConfig, positions, *,
                   mode: str, gcache=None, pos=None, enc_out=None,
                   specs_override=None, lengths=None):
    specs = specs_override or group.period

    def body_fn(carry, scanned):
        xc, auxc = carry
        if mode == "decode":
            lp, lc = scanned
        else:
            lp, lc = scanned, None
        new_caches = []
        seq_shard = cfg.sharding_rules.get("__seq_shard__")
        for i, spec in enumerate(specs):
            cache_i = lc["layers"][i] if lc is not None else None
            xc, nc, a = block_forward(lp["layers"][i], xc, spec, cfg, positions,
                                      mode=mode, cache=cache_i, pos=pos,
                                      enc_out=enc_out, lengths=lengths)
            if seq_shard is not None:
                # Megatron-SP: keep the residual stream sequence-sharded over
                # the model axis between blocks; GSPMD turns the TP all-reduce
                # pairs into reduce-scatter + all-gather (half the wire bytes).
                from repro.sharding.rules import shard_dim
                xc = shard_dim(xc, 1, seq_shard)
            new_caches.append(nc)
            auxc = auxc + a
        ys = {"layers": new_caches} if mode in ("prefill", "decode") else None
        return (xc, auxc), ys

    if cfg.remat and mode == "train":
        body_fn = jax.checkpoint(body_fn)

    carry0 = (x, jnp.float32(0.0))
    if group.repeat == 1:
        sc = (gparams, gcache) if mode == "decode" else gparams
        (x, aux), ys = body_fn(carry0, sc)
        return x, ys, aux
    xs = (gparams, gcache) if mode == "decode" else gparams
    unroll = group.repeat if cfg.scan_unroll else 1
    (x, aux), ys = jax.lax.scan(body_fn, carry0, xs, unroll=unroll)
    return x, ys, aux


# ----------------------------------------------------------------- encoder

def encode(cfg: ModelConfig, enc_params, enc_embeds):
    """Non-causal full-attention encoder over stub frontend embeddings."""
    b, s, _ = enc_embeds.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = enc_embeds.astype(cfg.param_dtype)
    spec = LayerSpec("attn", "dense")

    def body_fn(carry, lp):
        xc, _ = carry
        xc, _, _ = block_forward(lp["layers"][0], xc, spec, cfg, positions,
                                 mode="train")
        return (xc, jnp.float32(0.0)), None

    if cfg.n_encoder_layers == 1:
        (x, _), _ = body_fn((x, jnp.float32(0.0)), enc_params["groups"][0])
    else:
        (x, _), _ = jax.lax.scan(
            body_fn, (x, jnp.float32(0.0)), enc_params["groups"][0],
            unroll=cfg.n_encoder_layers if cfg.scan_unroll else 1)
    return rms_norm(x, enc_params["final_norm"], cfg.division, cfg.norm_eps)


# ------------------------------------------------------------------ forward

def forward(cfg: ModelConfig, params, *, tokens=None, embeds=None, cache=None,
            pos=None, mode: str = "train", enc_embeds=None, lengths=None):
    """Returns (logits, new_cache, aux). ``cache``/``pos`` for decode;
    ``enc_embeds`` for enc-dec / stub-frontend archs.

    ``pos`` (decode) may be a scalar or a per-request (b,) vector — the
    serving engine's padded-prompt fix decodes each request at its own
    absolute position. ``lengths`` (prefill) marks per-request real prompt
    lengths of a right-padded batch: pad positions become SSM no-ops and are
    excluded from sliding-window ring caches.
    """
    enc_out = None
    if cfg.is_encoder_decoder and mode != "decode":
        enc_out = encode(cfg, params["encoder"], enc_embeds)

    if embeds is not None and cfg.embed_inputs and not cfg.is_encoder_decoder:
        x = embeds.astype(cfg.param_dtype)
        b, s = x.shape[0], x.shape[1]
    else:
        x = embed_tokens(params["embed"], tokens, cfg)
        b, s = tokens.shape

    if mode == "decode":
        from .attention import decode_positions
        pos = decode_positions(pos, b)
        positions = pos[:, None]
        lengths = None
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if lengths is not None:
            lengths = jnp.asarray(lengths, jnp.int32)

    aux_total = jnp.float32(0.0)
    new_groups: List[Any] = []
    for gi, group in enumerate(cfg.groups()):
        gparams = params["groups"][gi]
        gcache = cache["groups"][gi] if cache is not None else None
        x, gc, aux = _group_forward(gparams, group, x, cfg, positions,
                                    mode=mode, gcache=gcache, pos=pos,
                                    enc_out=enc_out, lengths=lengths)
        new_groups.append(gc)
        aux_total = aux_total + aux

    x = rms_norm(x, params["final_norm"], cfg.division, cfg.norm_eps)
    logits = lm_logits(params, x, cfg)
    new_cache = {"groups": new_groups} if mode in ("prefill", "decode") else None
    return logits, new_cache, aux_total


# -------------------------------------------------------------------- caches

def _block_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int,
                 abstract: bool, cross: bool):
    dtype = jnp.dtype(cfg.param_dtype)
    mk_attn = abstract_cache_attn if abstract else init_cache_attn
    mk_mamba = abstract_cache_mamba if abstract else init_cache_mamba
    out: Dict[str, Any] = {}
    if spec.mixer == "mamba":
        out["mamba"] = mk_mamba(cfg, batch, dtype)
    else:
        window = cfg.sliding_window if spec.mixer == "swa" else 0
        out["attn"] = mk_attn(cfg, batch, max_len, window, dtype)
    if cross:
        shape = (batch, cfg.encoder_seq, cfg.n_kv_heads, cfg.head_dim)
        if abstract:
            out["cross"] = {"ck": jax.ShapeDtypeStruct(shape, dtype),
                            "cv": jax.ShapeDtypeStruct(shape, dtype)}
        else:
            out["cross"] = {"ck": jnp.zeros(shape, dtype),
                            "cv": jnp.zeros(shape, dtype)}
    return out


def make_cache(cfg: ModelConfig, batch: int, max_len: int, abstract: bool = False):
    """Decode cache matching the grouped/stacked parameter layout."""
    groups = []
    for g in cfg.groups():
        layer_caches = [
            _block_cache(cfg, s, batch, max_len, abstract,
                         cross=cfg.is_encoder_decoder)
            for s in g.period
        ]
        tree = {"layers": layer_caches}
        if g.repeat > 1:
            if abstract:
                tree = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct((g.repeat, *a.shape), a.dtype),
                    tree)
            else:
                tree = jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (g.repeat, *a.shape)).copy(), tree)
        groups.append(tree)
    return {"groups": groups}
