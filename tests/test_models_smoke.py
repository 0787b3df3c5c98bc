"""Per-arch smoke tests: reduced configs, one forward + one train step on CPU.

Asserts output shapes, finite logits, finite loss, finite & nonzero grads.
Full configs are exercised only via the dry-run (abstract, no allocation) —
here we also validate their *abstract* param counts against the published
sizes.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import forward, init_params, param_count
from repro.models.params import active_param_count
from repro.optim import adamw
from repro.train import step as ts

ARCHS = [a for a in ARCH_IDS]

EXPECTED_PARAMS_B = {
    "mamba2_780m": (0.78, 0.05),
    "granite_8b": (8.26, 0.3),
    "llama3_8b": (8.03, 0.3),
    "gemma3_12b": (11.8, 0.5),
    "tinyllama_1_1b": (1.10, 0.05),
    "llava_next_mistral_7b": (7.24, 0.3),
    "whisper_tiny": (0.041, 0.01),
    "jamba_1_5_large": (397.6, 5.0),
    "moonshot_v1_16b_a3b": (28.4, 1.0),   # 48L pinned by the assignment
    "deepseek_moe_16b": (16.4, 0.6),
    "granite_4_0_h_small": (32.2, 1.0),   # published 32B total
    "paper_fpdiv": (0.134, 0.02),
}

EXPECTED_ACTIVE_B = {
    "jamba_1_5_large": (93.2, 2.0),
    "deepseek_moe_16b": (2.83, 0.2),
    "moonshot_v1_16b_a3b": (4.8, 0.3),
    "granite_4_0_h_small": (8.8, 0.5),    # published 9B active
}


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_param_count(arch):
    cfg = get_config(arch)
    n = param_count(cfg) / 1e9
    want, tol = EXPECTED_PARAMS_B[arch]
    assert abs(n - want) < tol, f"{arch}: {n:.3f}B vs expected {want}B"
    if arch in EXPECTED_ACTIVE_B:
        na = active_param_count(cfg) / 1e9
        want_a, tol_a = EXPECTED_ACTIVE_B[arch]
        assert abs(na - want_a) < tol_a


def _batch_for(cfg, key, B=2, S=32):
    batch = {"labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.is_encoder_decoder:
        batch["enc_embeds"] = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab)
    elif cfg.embed_inputs:
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model),
                                            jnp.float32)
    else:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    B, S = 2, 32
    batch = _batch_for(cfg, key, B, S)
    kw = {k: v for k, v in batch.items() if k != "labels"}
    logits, cache, aux = forward(cfg, params, mode="train", **kw)
    assert logits.shape == (B, S, cfg.vocab)
    assert cache is None
    assert bool(jnp.all(jnp.isfinite(logits)))
    if cfg.n_experts:
        assert float(aux) > 0.0  # router aux loss is live


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    opt_cfg = adamw.AdamWConfig(division=cfg.division)
    state = ts.init_state(cfg, params, opt_cfg)
    batch = _batch_for(cfg, key)
    new_state, metrics = jax.jit(
        lambda s, b: ts.train_step(cfg, opt_cfg, s, b, n_micro=2))(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # params actually moved
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        state.params, new_state.params)
    assert max(jax.tree_util.tree_leaves(moved)) > 0
    assert int(new_state.step) == 1


def test_division_mode_exact_vs_taylor_close():
    """Same model, exact vs taylor division: logits agree to f32-kernel level."""
    cfg = get_smoke_config("paper_fpdiv")
    from repro.core.division_modes import DivisionConfig

    cfg_exact = dataclasses.replace(cfg, division=DivisionConfig(mode="exact"))
    key = jax.random.PRNGKey(2)
    params = init_params(cfg, key)
    toks = jax.random.randint(key, (2, 32), 0, cfg.vocab)
    lt, _, _ = forward(cfg, params, tokens=toks, mode="train")
    le, _, _ = forward(cfg_exact, params, tokens=toks, mode="train")
    assert float(jnp.max(jnp.abs(lt - le))) < 0.05


def test_groups_cover_all_layers():
    for arch in ARCHS:
        cfg = get_config(arch)
        total = sum(len(g.period) * g.repeat for g in cfg.groups())
        assert total == cfg.n_layers, arch
