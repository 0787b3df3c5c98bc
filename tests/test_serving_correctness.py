"""Serving-path correctness: the padded-prompt fix and the batching loop.

Pins the PR-6 bug fixes: (a) batched generation over unequal-length
(right-padded) prompts is token-identical to unpadded single-request
generation — the prefill logit is gathered at ``len(prompt) - 1`` and pad
positions are masked out of every cache kind; (b) ``pad_cache_to`` no longer
corrupts a sliding-window ring whose window equals the prefill length;
(c) ``serve()`` continuous batching (slot refill, per-request ``max_new``,
EOS release) reproduces ``generate()`` exactly; (d) embed-input and
encoder-decoder configs get a working hand-off or a clear ``ValueError``.
"""
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from repro.configs import get_smoke_config
from repro.models import forward, init_params
from repro.serving import Request, ServingEngine, pad_cache_to


def _setup(arch, *, max_len=96, **engine_kw):
    """f32 + no-drop MoE capacity: bit-stable across batch compositions."""
    cfg = dataclasses.replace(get_smoke_config(arch), param_dtype="float32",
                              capacity_factor=8.0)
    if cfg.is_encoder_decoder:
        cfg = dataclasses.replace(cfg, encoder_seq=24)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, ServingEngine(cfg, params, max_len=max_len, **engine_kw)


# ------------------------------------------------ weights are arguments

def test_engine_programs_take_weights_as_arguments():
    """A jit closed over the weights lowers them as HLO constants, which
    bakes the whole checkpoint into every prefill and decode program. The
    engine's programs must take them as arguments instead."""
    cfg, params, eng = _setup("paper_fpdiv")
    toks = jnp.zeros((2, 8), jnp.int32)
    lens = jnp.asarray([8, 5], jnp.int32)
    text = eng._prefill_tok_fn.lower(params, toks, lens).as_text()
    v, d = params["embed"].shape
    assert f"tensor<{v}x{d}xf32>" in text          # the embedding is an input
    assert not re.search(rf"stablehlo.constant dense<.*> : tensor<{v}x{d}x",
                         text)
    np.testing.assert_array_equal(
        np.asarray(eng.prefill_logits([[1] * 8, [2] * 5])),
        np.asarray(eng._prefill_tok(toks.at[0].set(1).at[1, :5].set(2),
                                    lens)[0]))


# --------------------------------------------------- padded-prompt identity

@pytest.mark.parametrize("arch", ["paper_fpdiv", "gemma3_12b",
                                  "jamba_1_5_large"])
def test_batched_padded_matches_single(arch):
    """Unequal-length prompts (one exactly the window/chunk size of 16):
    generate_batch must be token-identical to per-request generate."""
    _, _, eng = _setup(arch)
    prompts = [list(range(1, 12)), list(range(3, 25)), list(range(5, 21))]
    singles = [eng.generate(p, max_new=5) for p in prompts]
    batch = eng.generate_batch(prompts, max_new=5)
    assert batch == singles


def test_generate_batch_input_validation():
    _, _, eng = _setup("paper_fpdiv")
    with pytest.raises(ValueError, match="empty prompt"):
        eng.generate_batch([])
    with pytest.raises(ValueError, match="empty prompt"):
        eng.generate_batch([[1, 2], []])
    with pytest.raises(ValueError, match="max_len"):
        eng.generate_batch([list(range(1, 90))], max_new=32)


# -------------------------------------------------------------- pad_cache_to

def test_pad_cache_to_ring_window_equals_prompt():
    """Regression: with sliding_window == prompt_len, the legacy shape
    heuristic padded the W-sized ring to max_len (corrupting ring-modulo
    indexing); the cfg-structural walk leaves rings alone and still grows the
    full-attention caches."""
    cfg = dataclasses.replace(get_smoke_config("gemma3_12b"),
                              param_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    W = cfg.sliding_window
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, W), 0, cfg.vocab)
    _, cache, _ = forward(cfg, params, tokens=toks, mode="prefill")
    padded = pad_cache_to(cache, W, 64, cfg)
    shapes = {a.shape[-3] for a in jtu.tree_leaves(padded)}
    assert shapes == {W, 64}, f"rings must stay {W}, full KV grow to 64: {shapes}"
    # the legacy heuristic (no cfg) pads everything — the bug this pins
    legacy = {a.shape[-3] for a in jtu.tree_leaves(pad_cache_to(cache, W, 64))}
    assert legacy == {64}

    # end-to-end: decode past the window from a W-length prompt still matches
    eng = ServingEngine(cfg, params, max_len=64)
    single = eng.generate(list(range(1, W + 1)), max_new=W + 4)
    batch = eng.generate_batch([list(range(1, W + 1)), list(range(2, W - 3))],
                               max_new=W + 4)
    assert batch[0] == single


# ------------------------------------------------------- continuous batching

def test_serve_continuous_matches_generate():
    """4 requests through 2 slots: slot refill + per-request max_new, each
    output identical to a standalone generate()."""
    _, _, eng = _setup("paper_fpdiv")
    reqs = [Request(list(range(1, 10)), max_new=4),
            Request(list(range(2, 20)), max_new=6),
            Request(list(range(4, 11)), max_new=3),
            Request(list(range(7, 23)), max_new=5)]
    out = eng.serve(reqs, slots=2)
    assert out is not None and all(r.done for r in reqs)
    for r in reqs:
        assert r.out == eng.generate(r.tokens, max_new=r.max_new)


def test_serve_eos_release():
    """EOS stops a request early and frees its slot for the queue."""
    cfg, params, ref = _setup("paper_fpdiv")
    prompt = list(range(1, 10))
    full = ref.generate(prompt, max_new=6)
    eos = full[1]  # greedy-deterministic: the 2nd token becomes the EOS
    eng = ServingEngine(cfg, params, max_len=96, eos_id=eos)
    reqs = [Request(prompt, max_new=6), Request(list(range(2, 20)), max_new=4)]
    eng.serve(reqs, slots=1)  # one slot: EOS release must refill the queue
    assert reqs[0].done and reqs[0].out == full[:full.index(eos) + 1]
    assert reqs[1].done
    assert len(reqs[1].out) == 4 or reqs[1].out[-1] == eos


# ------------------------------------------------- embeds / enc-dec hand-off

def test_vlm_embeds_handoff_and_error():
    cfg, _, eng = _setup("llava_next_mistral_7b", max_len=64)
    e1 = jax.random.normal(jax.random.PRNGKey(2), (9, cfg.d_model))
    e2 = jax.random.normal(jax.random.PRNGKey(3), (14, cfg.d_model))
    singles = [eng.generate(embeds=e, max_new=4) for e in (e1, e2)]
    assert eng.generate_batch(None, max_new=4, embeds=[e1, e2]) == singles
    with pytest.raises(ValueError, match="embed_inputs"):
        eng.generate([1, 2, 3], max_new=2)
    with pytest.raises(ValueError, match="embed"):
        eng.serve([Request([1, 2, 3])])


def test_encdec_enc_embeds_handoff_and_error():
    cfg, _, eng = _setup("whisper_tiny", max_len=64)
    enc = jax.random.normal(jax.random.PRNGKey(5),
                            (2, cfg.encoder_seq, cfg.d_model))
    s0 = eng.generate([3, 4, 5, 6], max_new=4, enc_embeds=enc[0])
    s1 = eng.generate(list(range(7, 14)), max_new=4, enc_embeds=enc[1])
    batch = eng.generate_batch([[3, 4, 5, 6], list(range(7, 14))],
                               max_new=4, enc_embeds=enc)
    assert batch == [s0, s1]
    with pytest.raises(ValueError, match="enc_embeds"):
        eng.generate([1, 2], max_new=2)
    with pytest.raises(ValueError, match="encoder-decoder"):
        eng.serve([Request([1, 2])])
