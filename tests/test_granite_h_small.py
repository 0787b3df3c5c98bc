"""Granite 4.0-H Small on the serving path, at the smoke size on the CPU,
against the plain float32 reference of bench/reference/granite_h.py.

The model runs in float32 here, so the program and the reference differ
by rounding alone: logits agree to 1e-4 and states to 1e-4 of their norm.
Each Granite feature (no position embedding, the embedding, residual,
attention and logits multipliers, the conv bias) is pinned by a case in
which the program without it departs from the reference, and the expert
layer that holds a share of the experts is tied to the uncut layer
(eight shares, the shared expert counted once, add up to it) and shown to
drop no token.
"""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.kinds import serve_hybrid as sh  # noqa: E402
from bench.reference import granite_h  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.models.moe import moe_ffn  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402
from repro.serving.engine import _insert_cache_row, pad_cache_to  # noqa: E402
from repro.models import make_cache  # noqa: E402

MAX_LEN = 128
# one eighth of the smoke config's 16 routed experts, as the benchmark's
# configuration holds one eighth of the published 72
HELD = (4, 6)
LOGIT_TOL = 1e-4
STATE_TOL = 1e-4


def smoke(**kw):
    return dataclasses.replace(get_smoke_config("granite_4_0_h_small"),
                               **dict(dict(param_dtype="float32",
                                           experts_held=HELD), **kw))


@pytest.fixture(scope="module")
def model():
    cfg = smoke()
    params = sh.make_weights(cfg, 16)
    return cfg, params, sh.reference_weights(params, cfg)


_ENGINES = {}


def engine(cfg, params):
    """One engine (and so one compile of its programs) per model."""
    key = (repr(cfg), id(params))
    if key not in _ENGINES:
        _ENGINES[key] = ServingEngine(cfg, params, max_len=MAX_LEN)
    return _ENGINES[key]


def served_logits(cfg, params, prompt, n_decode):
    """The served tokens and the program's logits at each served position:
    prefill, then ``n_decode`` decode steps through the cache."""
    eng = engine(cfg, params)
    pad = eng._pad_to(len(prompt))
    last, cache = eng._prefill_tok(eng._pad_prompts([prompt], pad),
                                   jnp.asarray([len(prompt)]))
    cache = pad_cache_to(cache, pad, MAX_LEN, cfg)
    logits, toks = [np.asarray(last[0])], []
    pos = jnp.asarray([len(prompt)])
    for _ in range(n_decode):
        toks.append(int(np.argmax(logits[-1])))
        lg, cache = eng._decode(cache, jnp.asarray([[toks[-1]]]), pos)
        logits.append(np.asarray(lg[0]))
        pos = pos + 1
    return toks, np.stack(logits)


def reference_logits(ref, prompt, toks):
    weights, hp = ref
    seq = list(prompt) + list(toks)
    want = list(range(len(prompt) - 1, len(seq)))
    return np.asarray(granite_h.logits_at(weights, seq, want, hp))


def test_prefill_and_decode_match_the_reference(model):
    cfg, params, ref = model
    prompt = np.random.default_rng(0).integers(0, cfg.vocab, 37).tolist()
    toks, got = served_logits(cfg, params, prompt, 10)
    want = reference_logits(ref, prompt, toks)
    assert got.shape == want.shape == (11, cfg.vocab)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    assert len(set(toks)) > 3          # the served tokens are no copy loop


def engine_state(cache, cfg, slot, p):
    """What the cache holds for ``slot`` after ``p`` positions, in the form
    of ``granite_h.state_at``."""
    out = []
    for spec, lc in sh._layers(cache, cfg):
        if spec.mixer == "mamba":
            m = lc["mamba"]
            out.append({"state": np.asarray(m["state"][slot]),
                        "conv": np.concatenate(
                            [np.asarray(m[k][slot]) for k in
                             ("conv_x", "conv_B", "conv_C")], axis=-1)})
        else:
            out.append({k: np.asarray(lc["attn"][k][slot, :p])
                        for k in ("k", "v")})
    return out


def test_engine_state_matches_the_reference_state(model):
    """A request prefilled into slot 1 of three and decoded in the batch:
    its SSM state, conv state and K/V rows against ``state_at``."""
    cfg, params, ref = model
    weights, hp = ref
    eng = engine(cfg, params)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab, 29).tolist()
    pad = eng._pad_to(len(prompt))
    last, row = eng._prefill_tok(eng._pad_prompts([prompt], pad),
                                 jnp.asarray([len(prompt)]))
    cache = _insert_cache_row(make_cache(cfg, 3, MAX_LEN),
                              pad_cache_to(row, pad, MAX_LEN, cfg), 1, cfg)
    seq = list(prompt)
    tok = np.zeros((3, 1), np.int32)
    tok[1, 0] = int(np.argmax(last[0]))
    pos = np.asarray([0, len(prompt), 0], np.int32)
    for _ in range(9):
        seq.append(int(tok[1, 0]))
        lg, cache = eng._decode(cache, jnp.asarray(tok), jnp.asarray(pos))
        tok = np.asarray(jnp.argmax(lg, axis=-1))[:, None].astype(np.int32)
        pos = pos + 1
    got = engine_state(cache, cfg, 1, len(seq))
    want = granite_h.state_at(weights, seq, hp)
    assert sh.state_gap(got, want) < STATE_TOL
    # a state left at the prefill's would not pass
    stale = engine_state(_insert_cache_row(
        make_cache(cfg, 3, MAX_LEN), pad_cache_to(row, pad, MAX_LEN, cfg),
        1, cfg), cfg, 1, len(seq))
    assert sh.state_gap(stale, want) > 100 * STATE_TOL


# ---------------------------------------------------------- the expert layer

def share(p, lo, hi):
    """The expert layer's weights as a holder of experts [lo, hi) has
    them."""
    return dict(p, wi=p["wi"][lo:hi], wg=p["wg"][lo:hi], wo=p["wo"][lo:hi])


def test_expert_shares_add_up_to_the_uncut_layer():
    """The eight shares' outputs, the shared expert counted once, add up
    to the uncut reference layer (and to the program's uncut layer)."""
    cfg = smoke(experts_held=(0, 16))
    params = sh.make_weights(cfg, 3)
    p = params["groups"][0]["layers"][0]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, cfg.d_model))
    parts = [moe_ffn(share(p, 2 * i, 2 * i + 2), x,
                     dataclasses.replace(cfg, experts_held=(2 * i, 2 * i + 2)))[0]
             for i in range(8)]
    from repro.models.layers import gated_mlp
    shared = gated_mlp(p["shared"], x)
    total = sum(parts) - 7 * shared
    uncut = moe_ffn(p, x, cfg)[0]
    weights, hp = sh.reference_weights(params, cfg)
    w = {k: v for k, v in weights["layers"][0].items() if k != "kind"}
    ref = granite_h._experts(x.reshape(-1, cfg.d_model), granite_h._f32(w),
                             dict(hp, held=(0, 16)), "highest")
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(total).reshape(-1, cfg.d_model),
                               np.asarray(ref), atol=2e-5)


def test_held_experts_drop_no_token():
    """Every token routed to the same experts, all of them held here: the
    held layer equals the reference, where the capacity dispatch drops."""
    cfg = smoke(experts_held=(0, 4))
    params = sh.make_weights(cfg, 4)
    p = dict(params["groups"][0]["layers"][0]["ffn"])
    k = cfg.experts_per_tok
    # positive inputs and a router that favours experts 0..k-1 for all
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model)))
    router = -jnp.ones((cfg.d_model, cfg.n_experts), jnp.float32)
    p["router"] = router.at[:, :k].set(
        1.0 + 0.01 * jnp.arange(k, dtype=jnp.float32))
    held = moe_ffn(share(p, 0, 4), x, cfg)[0]
    weights, hp = sh.reference_weights(params, cfg)
    w = dict({kk: v for kk, v in weights["layers"][0].items()
              if kk != "kind"}, router=p["router"])
    ref = granite_h._experts(x.reshape(-1, cfg.d_model), granite_h._f32(w),
                             dict(hp, held=(0, 4)), "highest")
    np.testing.assert_allclose(np.asarray(held).reshape(-1, cfg.d_model),
                               np.asarray(ref), atol=2e-5)
    full = dict(p, wi=jnp.concatenate([p["wi"]] * 4)[:16],
                wg=jnp.concatenate([p["wg"]] * 4)[:16],
                wo=jnp.concatenate([p["wo"]] * 4)[:16])
    capped = moe_ffn(full, x, dataclasses.replace(
        cfg, experts_held=None, moe_dispatch="sort"))[0]
    assert float(jnp.max(jnp.abs(capped - held))) > 1e-2


def test_held_experts_need_the_sort_dispatch():
    cfg = smoke(moe_dispatch="cumsum")
    params = sh.make_weights(cfg, 5)
    x = jnp.ones((1, 4, cfg.d_model))
    with pytest.raises(ValueError, match="sort"):
        moe_ffn(params["groups"][0]["layers"][0]["ffn"], x, cfg)


# ------------------------------------------------------- Granite's features

def without_conv_bias(params):
    def strip(tree):
        if isinstance(tree, dict):
            return {k: strip(v) for k, v in tree.items()
                    if not k.endswith("_bias") or k == "dt_bias"}
        if isinstance(tree, list):
            return [strip(v) for v in tree]
        return tree
    return strip(params)


FEATURES = {
    "nope": (dict(rope=True), None),
    "embedding_multiplier": (dict(embedding_multiplier=1.0), None),
    "residual_multiplier": (dict(residual_multiplier=1.0), None),
    "attention_multiplier": (dict(attention_multiplier=None), None),
    "logits_scaling": (dict(logits_scaling=1.0), None),
    "conv_bias": (dict(), without_conv_bias),
}


@pytest.fixture(scope="module")
def served(model):
    """A prompt, the tokens the program serves for it, and the reference's
    logits at the served positions."""
    cfg, params, ref = model
    prompt = np.random.default_rng(2).integers(0, cfg.vocab, 40).tolist()
    toks, got = served_logits(cfg, params, prompt, 3)
    want = reference_logits(ref, prompt, toks)
    return prompt, got, want


@pytest.mark.parametrize("feature", sorted(FEATURES))
def test_each_granite_feature_is_needed(model, served, feature):
    """The program with one feature taken out departs from the reference
    by far more than the whole model does with it (about 1e-8 here)."""
    cfg, params, _ = model
    prompt, same, want = served
    change, strip = FEATURES[feature]
    _, got = served_logits(dataclasses.replace(cfg, **change),
                           strip(params) if strip else params, prompt, 3)
    assert np.max(np.abs(same - want)) < 1e-6
    assert np.max(np.abs(got - want)) > 1e-4


# ------------------------------------------------------ the dense model too

def test_granite_8b_smoke_decode_matches_its_reference():
    """The new fields at their defaults leave the dense Granite's program
    as it was: prefill and decode through the cache against
    bench/reference/granite.py."""
    from bench.reference import granite

    cfg = dataclasses.replace(get_smoke_config("granite_8b"),
                              param_dtype="float32")
    from repro.models import init_params
    params = init_params(cfg, jax.random.PRNGKey(6))
    prompt = np.random.default_rng(3).integers(0, cfg.vocab, 19).tolist()
    toks, got = served_logits(cfg, params, prompt, 8)
    (layer,) = params["groups"][0]["layers"]
    weights = {"embed": params["embed"], "final_norm": params["final_norm"],
               "lm_head": params["lm_head"],
               "attn_norm": layer["mixer_norm"], "wq": layer["attn"]["wq"],
               "wk": layer["attn"]["wk"], "wv": layer["attn"]["wv"],
               "wo": layer["attn"]["wo"], "mlp_norm": layer["ffn_norm"],
               "w_up": layer["ffn"]["wi"], "w_gate": layer["ffn"]["wg"],
               "w_down": layer["ffn"]["wo"]}
    seq = prompt + toks
    want = np.asarray(granite.logits_at(
        weights, seq, list(range(len(prompt) - 1, len(seq))),
        theta=cfg.rope_theta, eps=cfg.norm_eps))
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
