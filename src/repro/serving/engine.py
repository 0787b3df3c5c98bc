"""Serving engine: prefill + batched decode with per-layer-kind caches.

Cache layout mirrors the model's grouped scan structure; sizing is
layer-aware (full-length KV for global attention, W-sized ring buffers for
sliding-window layers, O(1) SSM/conv state for mamba).

Padded-prompt correctness: prompts of unequal length are right-padded to the
window/chunk alignment, but padding never leaks into the output — prefill
gathers each request's logit at ``len(prompt) - 1`` (not the padded end),
the model masks pad positions out of every cache kind (attention validity
mask, sliding-window ring gather, SSM dt-zeroing; see models/), and decode
runs at per-request positions so request i's token t lands at absolute
position ``len(prompt_i) + t``, progressively overwriting the pad slots.
``generate_batch`` is therefore token-identical to unpadded single-request
``generate``.

``ServingEngine.serve`` is the continuous-batching loop: admit a request
into a free batch slot (single-row prefill + cache row insert), decode all
active slots in lockstep (one jit'd step), release on EOS / ``max_new``,
refill from the queue. ``generate``/``generate_batch`` are the static-batch
special case. The division unit is a serving knob: pass ``division=`` to run
every softmax/rmsnorm in the decode path under that mode.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.division_modes import DivisionConfig
from repro.models import forward, make_cache


def prefill(cfg: ModelConfig, params, tokens, *, enc_embeds=None, embeds=None,
            lengths=None):
    """Returns (last_logits (B, V), cache). Seq must respect window/chunk
    alignment (the engine pads requests up to the alignment). With per-request
    ``lengths``, the returned logits are gathered at each request's last REAL
    position ``lengths[i] - 1`` and pad positions are masked out of the
    caches; without, the final position is used (unpadded batch)."""
    kw = {}
    if cfg.is_encoder_decoder:
        kw["enc_embeds"] = enc_embeds
    if cfg.embed_inputs and not cfg.is_encoder_decoder:
        logits, cache, _ = forward(cfg, params, embeds=embeds, mode="prefill",
                                   lengths=lengths, **kw)
    else:
        logits, cache, _ = forward(cfg, params, tokens=tokens, mode="prefill",
                                   lengths=lengths, **kw)
    if lengths is None:
        return logits[:, -1], cache
    lv = jnp.asarray(lengths, jnp.int32)
    last = jnp.take_along_axis(
        logits, (lv - 1)[:, None, None], axis=1)[:, 0]
    return last, cache


def decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    """One decode step. tokens: (B, 1); pos: scalar int32 or per-request (B,)
    vector of absolute positions. -> (logits, cache)."""
    logits, new_cache, _ = forward(cfg, params, tokens=tokens, cache=cache,
                                   pos=pos, mode="decode")
    return logits[:, 0], new_cache


def pad_cache_to(cache, from_len: int, to_len: int, cfg: ModelConfig = None):
    """Grow full-attention KV caches from ``from_len`` to ``to_len`` along the
    sequence axis (axis -3).

    With ``cfg`` the selection is structural: walk the grouped cache beside
    ``cfg.groups()`` and pad only the full-attention ('attn' mixer) K/V
    leaves. Sliding-window rings, SSM state/conv tails, and cross-attention
    K/V are never touched — the legacy shape heuristic (pad anything whose
    ``shape[-3] == from_len``) silently corrupts a ring cache whose window
    equals the prefill length. Without ``cfg`` the heuristic is kept for
    backward compatibility with unambiguous (dense full-attention) callers.
    """
    if to_len < from_len:
        raise ValueError(f"pad_cache_to: to_len {to_len} < from_len {from_len}")
    if to_len == from_len:
        return cache

    def pad(a):
        padw = [(0, 0)] * a.ndim
        padw[-3] = (0, to_len - from_len)
        return jnp.pad(a, padw)

    if cfg is None:
        def maybe(a):
            if a.ndim >= 3 and a.shape[-3] == from_len:
                return pad(a)
            return a
        return jax.tree_util.tree_map(maybe, cache)

    new_groups = []
    for g, gc in zip(cfg.groups(), cache["groups"]):
        layers = []
        for spec, lc in zip(g.period, gc["layers"]):
            lc = dict(lc)
            if spec.mixer == "attn" and "attn" in lc:
                lc["attn"] = {k: pad(v) for k, v in lc["attn"].items()}
            layers.append(lc)
        new_groups.append({"layers": layers})
    return {"groups": new_groups}


def _insert_cache_row(cache, row, slot: int, cfg: ModelConfig):
    """Write single-request cache ``row`` (batch 1) into batch slot ``slot``.

    Leaves of groups with ``repeat > 1`` carry a leading stacked-layers dim,
    so the batch axis is 1 there and 0 elsewhere."""
    new_groups = []
    for g, gc, rc in zip(cfg.groups(), cache["groups"], row["groups"]):
        ax = 1 if g.repeat > 1 else 0

        def ins(a, r, ax=ax):
            start = [0] * a.ndim
            start[ax] = slot
            return jax.lax.dynamic_update_slice(a, r.astype(a.dtype),
                                                tuple(start))

        new_groups.append(jax.tree_util.tree_map(ins, gc, rc))
    return {"groups": new_groups}


@dataclasses.dataclass
class Request:
    tokens: List[int]
    max_new: int = 32
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServingEngine:
    """Greedy-decoding engine: static batching (``generate``/``generate_batch``)
    and continuous batching (``serve``) over the smoke/full configs.

    ``division`` swaps the division unit the whole decode path runs on
    (``dataclasses.replace(cfg, division=...)``); ``eos_id`` enables early
    stop on that token."""

    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 256,
                 division: Optional[DivisionConfig] = None,
                 eos_id: Optional[int] = None):
        if division is not None:
            cfg = dataclasses.replace(cfg, division=division)
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.eos_id = eos_id
        # The weights are an argument of every program, never a closure:
        # a closed-over array is lowered as an HLO constant, which would
        # bake the whole checkpoint into each prefill and decode program.
        self._decode_fn = jax.jit(
            lambda w, c, t, p: decode_step(cfg, w, c, t, p))
        self._prefill_tok_fn = jax.jit(
            lambda w, t, l: prefill(cfg, w, t, lengths=l))
        self._prefill_emb_fn = jax.jit(
            lambda w, e, l: prefill(cfg, w, None, embeds=e, lengths=l))
        self._prefill_enc_fn = jax.jit(
            lambda w, t, enc, l: prefill(cfg, w, t, enc_embeds=enc,
                                         lengths=l))

    def _decode(self, cache, tokens, pos):
        return self._decode_fn(self.params, cache, tokens, pos)

    def _prefill_tok(self, tokens, lengths):
        return self._prefill_tok_fn(self.params, tokens, lengths)

    # ------------------------------------------------------------- alignment

    @property
    def _align(self) -> int:
        cfg = self.cfg
        a = cfg.sliding_window if cfg.sliding_window else 1
        if cfg.family in ("ssm", "hybrid"):
            a = a * cfg.ssm_chunk // math.gcd(a, cfg.ssm_chunk)
        return a

    def _pad_to(self, s_max: int) -> int:
        return -(-s_max // self._align) * self._align

    def _check_fits(self, s_max: int, max_new: int, pad_to: int):
        need = max(pad_to, s_max + max_new)
        if need > self.max_len:
            raise ValueError(
                f"prompt ({s_max}) + max_new ({max_new}) needs {need} cache "
                f"slots but max_len is {self.max_len}")

    # ----------------------------------------------------------- static batch

    def generate_batch(self, prompts, max_new: int = 32, *, enc_embeds=None,
                       embeds=None):
        """Batched requests of unequal length: right-pad to a common aligned
        length, prefill once (pad positions masked out of every cache kind),
        then decode all slots in lockstep at per-request positions. Output is
        token-identical to per-request unpadded ``generate``.

        VLM (``embed_inputs``) configs take ``embeds``: a list of per-request
        ``(len_i, d_model)`` arrays (decode consumes generated *tokens*).
        Encoder-decoder configs take ``enc_embeds``: ``(B, encoder_seq,
        d_model)``. Returns a list of generated-token lists."""
        cfg = self.cfg
        if cfg.embed_inputs and not cfg.is_encoder_decoder:
            if embeds is None:
                raise ValueError(
                    f"config '{cfg.name}' has embed_inputs=True: pass "
                    "embeds=[...(len_i, d_model) arrays] (prompt tokens have "
                    "no embedding path at prefill)")
            lens = [int(e.shape[0]) for e in embeds]
            B = len(embeds)
        else:
            if not prompts:
                raise ValueError("generate_batch: empty prompt list")
            if any(len(p) == 0 for p in prompts):
                raise ValueError("generate_batch: empty prompt")
            lens = [len(p) for p in prompts]
            B = len(prompts)
        if cfg.is_encoder_decoder and enc_embeds is None:
            raise ValueError(
                f"config '{cfg.name}' is encoder-decoder: pass "
                "enc_embeds=(B, encoder_seq, d_model)")
        s_max = max(lens)
        pad_to = self._pad_to(s_max)
        self._check_fits(s_max, max_new, pad_to)
        lengths = jnp.asarray(lens, jnp.int32)

        if cfg.embed_inputs and not cfg.is_encoder_decoder:
            emb = np.zeros((B, pad_to, cfg.d_model), np.float32)
            for i, e in enumerate(embeds):
                emb[i, :lens[i]] = np.asarray(e, np.float32)
            last_logits, cache = self._prefill_emb_fn(
                self.params, jnp.asarray(emb), lengths)
        else:
            toks = self._pad_prompts(prompts, pad_to)
            if cfg.is_encoder_decoder:
                last_logits, cache = self._prefill_enc_fn(
                    self.params, toks, jnp.asarray(enc_embeds), lengths)
            else:
                last_logits, cache = self._prefill_tok(toks, lengths)
        cache = pad_cache_to(cache, pad_to, self.max_len, cfg)

        pos_v = lengths  # request i's first generated token sits at len_i
        tok = jnp.argmax(last_logits, axis=-1)[:, None].astype(jnp.int32)
        outs = [[] for _ in range(B)]
        stopped = [False] * B
        for _ in range(max_new):
            for i in range(B):
                if not stopped[i]:
                    t = int(tok[i, 0])
                    outs[i].append(t)
                    if self.eos_id is not None and t == self.eos_id:
                        stopped[i] = True
            if all(stopped):
                break
            logits, cache = self._decode(cache, tok, pos_v)
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            pos_v = pos_v + 1
        return outs

    @staticmethod
    def _pad_prompts(prompts, pad_to: int):
        toks = np.zeros((len(prompts), pad_to), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p  # zero right-pad; pads are masked out
        return jnp.asarray(toks)

    def prefill_logits(self, prompts):
        """(B, vocab) logits at each token prompt's last real position: the
        prefill ``generate_batch`` runs, same padding and program, without
        the decode."""
        lens = [len(p) for p in prompts]
        toks = self._pad_prompts(prompts, self._pad_to(max(lens)))
        return self._prefill_tok(toks, jnp.asarray(lens, jnp.int32))[0]

    def generate(self, prompt_tokens=None, max_new: int = 32, *,
                 enc_embeds=None, embeds=None):
        """Single-request generate — the batch-of-one case of
        ``generate_batch`` (same padding/masking path, so batched and single
        generation are token-identical)."""
        if enc_embeds is not None and np.ndim(enc_embeds) == 2:
            enc_embeds = jnp.asarray(enc_embeds)[None]
        prompts = None if prompt_tokens is None else [list(prompt_tokens)]
        embs = None if embeds is None else [embeds]
        return self.generate_batch(prompts, max_new, enc_embeds=enc_embeds,
                                   embeds=embs)[0]

    # ------------------------------------------------------ continuous batch

    def serve(self, requests: Sequence[Request], *, slots: int = 2):
        """Continuous batching: admit requests into free batch slots
        (single-row prefill + cache-row insert), decode all active slots in
        lockstep, release each on EOS / its own ``max_new``, refill from the
        queue. Mutates and returns the ``Request`` objects (``out``/``done``).
        """
        cfg = self.cfg
        if cfg.embed_inputs and not cfg.is_encoder_decoder:
            raise ValueError(
                f"serve() prefills token prompts; embed-input config "
                f"'{cfg.name}' must use generate/generate_batch with embeds=")
        if cfg.is_encoder_decoder:
            raise ValueError(
                f"serve() does not carry per-slot encoder state; "
                f"encoder-decoder config '{cfg.name}' must use "
                "generate/generate_batch with enc_embeds=")
        for r in requests:
            if not r.tokens:
                raise ValueError("serve: empty prompt")
            pad_to = self._pad_to(len(r.tokens))
            self._check_fits(len(r.tokens), r.max_new, pad_to)

        B = slots
        cache = make_cache(cfg, B, self.max_len)
        pos_v = np.zeros((B,), np.int32)
        cur = np.zeros((B, 1), np.int32)
        active: List[Optional[Request]] = [None] * B
        queue = list(requests)

        def admit(slot: int, req: Request):
            nonlocal cache
            s = len(req.tokens)
            pad_to = self._pad_to(s)
            toks = np.zeros((1, pad_to), np.int32)
            toks[0, :s] = req.tokens
            last, row = self._prefill_tok(jnp.asarray(toks),
                                          jnp.asarray([s], jnp.int32))
            row = pad_cache_to(row, pad_to, self.max_len, cfg)
            cache = _insert_cache_row(cache, row, slot, cfg)
            cur[slot, 0] = int(jnp.argmax(last[0]))
            pos_v[slot] = s
            active[slot] = req

        while True:
            for i in range(B):
                if active[i] is None and queue:
                    admit(i, queue.pop(0))
            if not any(a is not None for a in active):
                break
            # record this step's token; release finished slots before decode
            for i in range(B):
                req = active[i]
                if req is None:
                    continue
                t = int(cur[i, 0])
                req.out.append(t)
                if len(req.out) >= req.max_new or (
                        self.eos_id is not None and t == self.eos_id):
                    req.done = True
                    active[i] = None
                    pos_v[i] = 0  # idle slot decodes garbage at pos 0;
                    # the row is fully overwritten on the next admit
            if not any(a is not None for a in active) and not queue:
                break
            logits, cache = self._decode(cache, jnp.asarray(cur),
                                         jnp.asarray(pos_v))
            cur = np.asarray(jnp.argmax(logits, axis=-1))[:, None].astype(np.int32)
            pos_v = pos_v + 1
        return list(requests)
