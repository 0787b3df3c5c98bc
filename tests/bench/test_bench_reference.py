"""The plain references agree with the program where both compute the same
thing exactly: Granite's forward against the serving engine's prefill and
decode (float32 weights, exact division), and Lloyd against ``kmeans`` in
exact mode."""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.kinds import kmeans as bk, serve as bs  # noqa: E402
from bench.lib import seeds  # noqa: E402
from bench.reference import granite, lloyd  # noqa: E402


@pytest.fixture(scope="module")
def smoke():
    from repro.configs import get_smoke_config
    from repro.core.division_modes import DivisionConfig
    cfg = dataclasses.replace(get_smoke_config("granite_8b"),
                              param_dtype="float32",
                              division=DivisionConfig(mode="exact"))
    params = bs.make_weights(cfg, 5)
    return cfg, params, bs.reference_weights(params, cfg)


def _ref(w, cfg, seq, want, dot="highest"):
    return np.asarray(granite.logits_at(w, seq, want, theta=cfg.rope_theta,
                                        eps=cfg.norm_eps, dot=dot))


def test_granite_reference_matches_prefill(smoke):
    from repro.serving import ServingEngine
    cfg, params, w = smoke
    eng = ServingEngine(cfg, params, max_len=64)
    rng = seeds.rng(1, 1)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (24, 9, 40)]
    got = np.asarray(eng.prefill_logits(prompts))
    for i, p in enumerate(prompts):
        ref = _ref(w, cfg, p, [len(p) - 1])[0]
        np.testing.assert_allclose(got[i], ref, rtol=0, atol=2e-4)


def test_granite_reference_matches_decode_through_the_cache(smoke):
    from repro.serving import ServingEngine
    from repro.serving.engine import pad_cache_to
    cfg, params, w = smoke
    eng = ServingEngine(cfg, params, max_len=64)
    prompt = seeds.rng(2, 1).integers(0, cfg.vocab, 17).tolist()
    last, cache = eng._prefill_tok(jnp.asarray([prompt], jnp.int32),
                                   jnp.asarray([17], jnp.int32))
    cache = pad_cache_to(cache, 17, 64, cfg)
    seq = list(prompt)
    tok = int(jnp.argmax(last[0]))
    for step in range(6):
        seq.append(tok)
        logits, cache = eng._decode(cache, jnp.asarray([[tok]], jnp.int32),
                                    jnp.asarray([len(seq) - 1], jnp.int32))
        ref = _ref(w, cfg, seq, [len(seq) - 1])[0]
        np.testing.assert_allclose(np.asarray(logits[0]), ref, rtol=0,
                                   atol=2e-4)
        tok = int(jnp.argmax(logits[0]))


def test_granite_reference_keys_and_values_match_the_cache(smoke):
    """``kv_at`` gives the rows the engine's cache holds after a prefill
    and decode steps: keys after RoPE, values, every layer."""
    from repro.serving import ServingEngine
    from repro.serving.engine import pad_cache_to
    cfg, params, w = smoke
    eng = ServingEngine(cfg, params, max_len=64)
    prompt = seeds.rng(6, 1).integers(0, cfg.vocab, 21).tolist()
    last, cache = eng._prefill_tok(jnp.asarray([prompt], jnp.int32),
                                   jnp.asarray([21], jnp.int32))
    cache = pad_cache_to(cache, 21, 64, cfg)
    seq = list(prompt)
    tok = int(jnp.argmax(last[0]))
    for _ in range(5):
        seq.append(tok)
        logits, cache = eng._decode(cache, jnp.asarray([[tok]], jnp.int32),
                                    jnp.asarray([len(seq) - 1], jnp.int32))
        tok = int(jnp.argmax(logits[0]))
    (layer,) = cache["groups"][0]["layers"]
    ref = granite.kv_at(w, seq, theta=cfg.rope_theta, eps=cfg.norm_eps)
    for name, r in zip(("k", "v"), ref):
        got = np.asarray(layer["attn"][name])
        got = got[:, 0] if got.ndim == 5 else got[None, 0]
        assert r.shape == (cfg.n_layers, len(seq), cfg.n_kv_heads,
                           cfg.head_dim)
        np.testing.assert_allclose(got[:, :len(seq)], r, rtol=0, atol=2e-4)
        assert not np.any(got[:, len(seq):])


def test_granite_control_departs_from_the_reference(smoke):
    cfg, params, w = smoke
    seq = seeds.rng(3, 1).integers(0, cfg.vocab, 40).tolist()
    ref = _ref(w, cfg, seq, list(range(40)))
    ctl = _ref(w, cfg, seq, list(range(40)), dot="fp8")
    assert np.max(np.abs(ctl - ref)) > 1e-2 * np.max(np.abs(ref))


def test_lloyd_reference_matches_exact_kmeans():
    from repro.core import division_modes as dm
    from repro.workloads import kmeans as km
    make = jax.jit(bk.make_data(4096, 6, 16, {"components": 16,
                                              "center_range": 1.0,
                                              "spread": 0.15}))
    x, c = make(seeds.key(4, 0))
    r = km.kmeans(x, cfg=dm.DivisionConfig(mode="exact"), n_iters=5, init=c)
    rc, ra, ri = lloyd.lloyd(x, c, 5, block_rows=1000)
    np.testing.assert_allclose(np.asarray(r.centroids), np.asarray(rc),
                               rtol=0, atol=1e-5)
    assert np.mean(np.asarray(r.assignments) == np.asarray(ra)) == 1.0
    assert abs(float(r.inertia) - float(ri)) <= 1e-5 * abs(float(ri))


def test_lloyd_control_is_three_bf16_passes():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    b = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    hi = np.asarray(lloyd.matmul(a, b, "highest"))
    x3 = np.asarray(lloyd.matmul(a, b, "bf16x3"))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    err3 = np.max(np.abs(x3 - exact))
    assert np.max(np.abs(hi - exact)) < err3 < 1e-3
