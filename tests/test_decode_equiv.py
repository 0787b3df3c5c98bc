"""Serving correctness: prefill + decode == full forward (f32, exact math).

Covers every cache type: full-attention KV, sliding-window ring, SSM state +
conv tails, hybrid stacks, cross-attention, and MoE (no-drop capacity)."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from repro.configs import get_smoke_config
from repro.core.division_modes import DivisionConfig
from repro.models import forward, init_params
from repro.serving import ServingEngine, pad_cache_to

ARCHS = ["llama3_8b", "gemma3_12b", "mamba2_780m", "jamba_1_5_large",
         "whisper_tiny", "deepseek_moe_16b", "llava_next_mistral_7b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_full(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), param_dtype="float32",
                              capacity_factor=8.0)
    if cfg.is_encoder_decoder:
        cfg = dataclasses.replace(cfg, encoder_seq=24)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    B, S, EXTRA = 2, 32, 8
    total = S + 16  # window/chunk aligned
    toks = jax.random.randint(key, (B, total), 0, cfg.vocab)
    kw = {}
    if cfg.is_encoder_decoder:
        kw["enc_embeds"] = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model), jnp.float32)

    if cfg.embed_inputs and not cfg.is_encoder_decoder:
        # VLM: prefill on embeddings (stub frontend), decode on tokens
        embeds_full = jax.random.normal(key, (B, total, cfg.d_model),
                                        jnp.float32)
        emb_tab = params["embed"].astype(jnp.float32)
        embeds_full = embeds_full.at[:, S:].set(
            jnp.take(emb_tab, toks[:, S:], axis=0))
        full_logits, _, _ = forward(cfg, params, embeds=embeds_full,
                                    mode="train", **kw)
        _, cache, _ = forward(cfg, params, embeds=embeds_full[:, :S],
                              mode="prefill", **kw)
    else:
        full_logits, _, _ = forward(cfg, params, tokens=toks, mode="train", **kw)
        _, cache, _ = forward(cfg, params, tokens=toks[:, :S],
                              mode="prefill", **kw)

    cache = pad_cache_to(cache, S, total)
    errs = []
    for t in range(EXTRA):
        dl, cache, _ = forward(cfg, params, tokens=toks[:, S + t:S + t + 1],
                               cache=cache, pos=S + t, mode="decode", **kw)
        errs.append(float(jnp.max(jnp.abs(dl[:, 0] - full_logits[:, S + t]))))
    scale = float(jnp.max(jnp.abs(full_logits)))
    assert max(errs) / scale < 3e-4, f"{arch}: rel err {max(errs)/scale}"


# ------------------------------------------------ mode-parameterized gates

@pytest.mark.parametrize("mode", ["taylor", "goldschmidt", "taylor_pallas"])
def test_prefill_decode_matches_full_under_mode(mode):
    """The prefill+decode==full gate holds under every division mode the
    serving knob exposes, not just the config default. gemma3 exercises both
    decode cache paths (swa ring + global KV) through the mode's softmax and
    rmsnorm."""
    cfg = dataclasses.replace(
        get_smoke_config("gemma3_12b"), param_dtype="float32",
        division=DivisionConfig(mode=mode, n_iters=2))
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    B, S, EXTRA = 2, 32, 4
    total = S + 16
    toks = jax.random.randint(key, (B, total), 0, cfg.vocab)
    full_logits, _, _ = forward(cfg, params, tokens=toks, mode="train")
    _, cache, _ = forward(cfg, params, tokens=toks[:, :S], mode="prefill")
    cache = pad_cache_to(cache, S, total, cfg)
    errs = []
    for t in range(EXTRA):
        dl, cache, _ = forward(cfg, params, tokens=toks[:, S + t:S + t + 1],
                               cache=cache, pos=S + t, mode="decode")
        errs.append(float(jnp.max(jnp.abs(dl[:, 0] - full_logits[:, S + t]))))
    scale = float(jnp.max(jnp.abs(full_logits)))
    assert max(errs) / scale < 3e-4, f"{mode}: rel err {max(errs)/scale}"


# --------------------------------------- serving mode-equivalence (vs EXACT)

def _replay(engine, prompts, steps, teacher=None):
    """Greedy decode through the engine's own jit'd steps. With ``teacher``
    (the EXACT run's chosen tokens), feed that stream instead of the
    engine's own argmax so the two runs see identical context at every step
    (no divergence feedback). Returns (argmaxes (steps, B), logits
    (steps, B, V))."""
    lens = [len(p) for p in prompts]
    B = len(prompts)
    pad_to = engine._pad_to(max(lens))
    toks = np.zeros((B, pad_to), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lengths = jnp.asarray(lens, jnp.int32)
    logits, cache = engine._prefill_tok(jnp.asarray(toks), lengths)
    cache = pad_cache_to(cache, pad_to, engine.max_len, engine.cfg)
    pos = lengths
    argmaxes, logit_seq = [], []
    for t in range(steps):
        logit_seq.append(np.asarray(logits))
        choice = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        argmaxes.append(np.asarray(choice[:, 0]))
        feed = choice if teacher is None else jnp.asarray(
            teacher[t])[:, None].astype(jnp.int32)
        logits, cache = engine._decode(cache, feed, pos)
        pos = pos + 1
    return np.stack(argmaxes), np.stack(logit_seq)


NON_ILM = ["taylor", "taylor_pallas", "goldschmidt", "goldschmidt_pallas"]


@pytest.mark.parametrize("arch,modes", [
    ("paper_fpdiv", NON_ILM),          # the paper's own config: every mode
    ("gemma3_12b", ["taylor"]),        # attention smoke (swa ring + global)
    ("jamba_1_5_large", ["goldschmidt"]),  # hybrid smoke (SSM + MoE + attn)
])
def test_serving_mode_equivalence_vs_exact(arch, modes):
    """Every non-ILM division mode, run as the serving knob, tracks the
    cfg=EXACT twin: >= 99% greedy-token agreement under teacher forcing and
    bounded logit drift."""
    cfg = dataclasses.replace(get_smoke_config(arch), param_dtype="float32",
                              capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    steps = 50 if arch == "paper_fpdiv" else 24
    prompts = [list(range(1, 14)), list(range(3, 20))]
    exact_eng = ServingEngine(cfg, params, max_len=96,
                              division=DivisionConfig(mode="exact"))
    teacher, exact_logits = _replay(exact_eng, prompts, steps)
    scale = float(np.max(np.abs(exact_logits)))
    for mode in modes:
        eng = ServingEngine(cfg, params, max_len=96,
                            division=DivisionConfig(mode=mode, n_iters=2))
        am, lg = _replay(eng, prompts, steps, teacher=teacher)
        agreement = float(np.mean(am == teacher))
        drift = float(np.max(np.abs(lg - exact_logits))) / scale
        assert agreement >= 0.99, f"{arch}/{mode}: agreement {agreement}"
        assert drift < 5e-3, f"{arch}/{mode}: logit drift {drift}"


def test_swa_ring_cache_wraps():
    """Decode past the window: ring slots recycle, result stays finite and
    matches a fresh prefill at every step."""
    cfg = dataclasses.replace(get_smoke_config("gemma3_12b"),
                              param_dtype="float32")
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    B, W = 1, cfg.sliding_window  # 16
    total = 4 * W
    toks = jax.random.randint(key, (B, total), 0, cfg.vocab)
    full_logits, _, _ = forward(cfg, params, tokens=toks, mode="train")
    S = 2 * W
    _, cache, _ = forward(cfg, params, tokens=toks[:, :S], mode="prefill")
    cache = pad_cache_to(cache, S, total)
    for t in range(S, total):  # decode through 2 more windows
        dl, cache, _ = forward(cfg, params, tokens=toks[:, t:t + 1],
                               cache=cache, pos=t, mode="decode")
    err = float(jnp.max(jnp.abs(dl[:, 0] - full_logits[:, -1])))
    assert err / float(jnp.max(jnp.abs(full_logits))) < 3e-4


# ------------------------------- grouped decode attention (no GQA broadcast)

def _gqa_cfg(q_per_kv, mode="taylor_pallas", window=0):
    kv = 2
    return dataclasses.replace(
        get_smoke_config("llama3_8b"), param_dtype="float32",
        n_heads=kv * q_per_kv, n_kv_heads=kv, head_dim=16, d_model=32,
        sliding_window=window, division=DivisionConfig(mode=mode, n_iters=2))


def _attn_params(cfg, key):
    ks = jax.random.split(key, 4)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {"wq": jax.random.normal(ks[0], (d, h, hd)) / d ** 0.5,
            "wk": jax.random.normal(ks[1], (d, kv, hd)) / d ** 0.5,
            "wv": jax.random.normal(ks[2], (d, kv, hd)) / d ** 0.5,
            "wo": jax.random.normal(ks[3], (h, hd, d)) / (h * hd) ** 0.5}


def _repeated_decode(p, x, cache, pos, cfg, window=0, kv_override=None):
    """Decode attention as it was written before the grouped products: the
    cache repeated q_per_kv-fold (``_repeat_kv``) and attended per head
    (``_sdpa``)."""
    from repro.models import attention as A

    b = x.shape[0]
    div, scale = cfg.division, A.score_scale(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is not None:
        k_all = A._repeat_kv(kv_override[0], cfg.q_per_kv)
        v_all = A._repeat_kv(kv_override[1], cfg.q_per_kv)
        out = A._sdpa(q, k_all, v_all, jnp.ones((1, 1, 1, 1), bool), div,
                      scale)
        return jnp.einsum("bqhk,hkd->bqd", out, p["wo"]), cache
    posv = pos[:, None]
    q = A.rope_apply(q, posv, cfg)
    k_new = A.rope_apply(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), posv, cfg)
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    L = cache["k"].shape[1]
    slot = jnp.mod(pos, L) if window > 0 else pos
    k_c = cache["k"].at[jnp.arange(b), slot].set(k_new[:, 0])
    v_c = cache["v"].at[jnp.arange(b), slot].set(v_new[:, 0])
    idx = jnp.arange(L)
    if window == 0:
        valid = idx[None, :] <= pos[:, None]
    else:
        valid = pos[:, None] - jnp.mod(pos[:, None] - idx[None, :], L) >= 0
    out = A._sdpa(q, A._repeat_kv(k_c, cfg.q_per_kv),
                  A._repeat_kv(v_c, cfg.q_per_kv), valid[:, None, None, :],
                  div, scale)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"]), {"k": k_c, "v": v_c}


# per-slot positions: the first and the last row of a full cache of 16 rows;
# a ring of 8 rows before, at and past its first wrap
_POSITIONS = {0: [0, 5, 9, 15], 8: [0, 7, 8, 21]}


@pytest.mark.parametrize("window", [0, 8], ids=["full", "ring"])
@pytest.mark.parametrize("q_per_kv", [1, 2, 4, 8])
def test_grouped_decode_attention_matches_repeated_heads(q_per_kv, window):
    """Each query group attends its own kv head's cache in place: the same
    outputs and cache as repeating the cache per query head, for MHA
    (q_per_kv 1) and GQA, full caches and rings, at per-slot positions."""
    from repro.models.attention import decode_attention

    cfg = _gqa_cfg(q_per_kv)
    key = jax.random.PRNGKey(q_per_kv + window)
    p = _attn_params(cfg, key)
    pos = jnp.asarray(_POSITIONS[window], jnp.int32)
    b, L = pos.shape[0], window or 16
    kc, kk, kx = jax.random.split(jax.random.fold_in(key, 1), 3)
    shape = (b, L, cfg.n_kv_heads, cfg.head_dim)
    cache = {"k": jax.random.normal(kc, shape),
             "v": jax.random.normal(kk, shape)}
    x = jax.random.normal(kx, (b, 1, cfg.d_model))
    out, new = jax.jit(lambda p, x, c, s: decode_attention(
        p, x, c, s, cfg, window=window))(p, x, cache, pos)
    ref, ref_cache = jax.jit(lambda p, x, c, s: _repeated_decode(
        p, x, c, s, cfg, window=window))(p, x, cache, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(new[name]),
                                      np.asarray(ref_cache[name]))


@pytest.mark.parametrize("mode", ["exact", "taylor_pallas"])
@pytest.mark.parametrize("q_per_kv", [1, 4])
def test_grouped_cross_attention_decode_matches_repeated_heads(q_per_kv,
                                                               mode):
    """Decode cross attention (``kv_override``, the encoder's K/V) takes the
    same grouped products, under XLA's division and the unit's kernel."""
    from repro.models.attention import decode_attention

    cfg = _gqa_cfg(q_per_kv, mode=mode)
    key = jax.random.PRNGKey(10 + q_per_kv)
    p = _attn_params(cfg, key)
    kk, kv_, kx = jax.random.split(jax.random.fold_in(key, 1), 3)
    shape = (3, 24, cfg.n_kv_heads, cfg.head_dim)
    kvo = (jax.random.normal(kk, shape), jax.random.normal(kv_, shape))
    x = jax.random.normal(kx, (3, 1, cfg.d_model))
    pos = jnp.zeros((3,), jnp.int32)
    out, _ = jax.jit(lambda p, x, k, v: decode_attention(
        p, x, None, pos, cfg, kv_override=(k, v)))(p, x, *kvo)
    ref, _ = jax.jit(lambda p, x, k, v: _repeated_decode(
        p, x, None, pos, cfg, kv_override=(k, v)))(p, x, *kvo)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_decode_step_repeats_no_cache():
    """The compiled decode step of a GQA model (q_per_kv 4) holds no
    instruction of the repeated cache's shape (b, L, h, hd), and its
    attention softmax still runs through the division unit under the
    decode scope."""
    from repro.models.attention import SCOPE_DECODE
    from repro.models.model import make_cache
    from repro.serving.engine import decode_step

    cfg = dataclasses.replace(_gqa_cfg(4), n_layers=1)
    b, L = 3, 40
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = make_cache(cfg, b, L)
    toks = jnp.zeros((b, 1), jnp.int32)
    pos = jnp.asarray([0, 17, L - 1], jnp.int32)
    text = jax.jit(lambda w, c, t, s: decode_step(cfg, w, c, t, s)).lower(
        params, cache, toks, pos).compile().as_text()
    repeated = f"[{b},{L},{cfg.n_heads},{cfg.head_dim}]"
    assert repeated not in text, f"a {repeated} instruction repeats the cache"
    assert f"[{b},{L},{cfg.n_kv_heads},{cfg.head_dim}]" in text
    assert f"{SCOPE_DECODE}/dm.softmax" in text
