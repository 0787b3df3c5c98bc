"""Parameter specs, abstract/concrete init, and logical sharding axes.

Models are defined against plain dict pytrees. Each leaf starts life as a
``ParamSpec`` carrying shape, logical axes and init; the spec tree is
materialized either concretely (``init_params``) or abstractly
(``abstract_params`` — ShapeDtypeStructs only, so 398B-parameter configs cost
nothing). Logical axes map to mesh axes through the per-arch rules
(``sharding.rules``).
"""
from __future__ import annotations

import dataclasses
import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import Group, LayerSpec, ModelConfig


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones
    scale: Optional[float] = None  # stddev for normal; default 1/sqrt(fan_in)
    dtype: Optional[str] = None    # overrides cfg.param_dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


# ------------------------------------------------------------- module specs

def _attn_specs(cfg: ModelConfig, cross: bool = False) -> Dict[str, ParamSpec]:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s_in = 1.0 / np.sqrt(d)
    s_out = 1.0 / np.sqrt(H * hd)
    return {
        "wq": ParamSpec((d, H, hd), ("embed", "heads", "head_dim"), scale=s_in),
        "wk": ParamSpec((d, KV, hd), ("embed", "kv_heads", "head_dim"), scale=s_in),
        "wv": ParamSpec((d, KV, hd), ("embed", "kv_heads", "head_dim"), scale=s_in),
        "wo": ParamSpec((H, hd, d), ("heads", "head_dim", "embed"), scale=s_out),
    }


def _mlp_specs(cfg: ModelConfig, d_ff: int) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    return {
        "wi": ParamSpec((d, d_ff), ("embed", "mlp"), scale=1.0 / np.sqrt(d)),
        "wg": ParamSpec((d, d_ff), ("embed", "mlp"), scale=1.0 / np.sqrt(d)),
        "wo": ParamSpec((d_ff, d), ("mlp", "embed"), scale=1.0 / np.sqrt(d_ff)),
    }


def _moe_specs(cfg: ModelConfig) -> Dict[str, Any]:
    # The router spans every routed expert; the expert weights only those
    # the layer holds (``experts_held``).
    d, E, f = cfg.d_model, cfg.n_experts_here, cfg.d_ff_expert
    out: Dict[str, Any] = {
        "router": ParamSpec((d, cfg.n_experts), ("embed", None),
                            scale=1.0 / np.sqrt(d), dtype="float32"),
        "wi": ParamSpec((E, d, f), ("experts", "embed", "expert_mlp"),
                        scale=1.0 / np.sqrt(d)),
        "wg": ParamSpec((E, d, f), ("experts", "embed", "expert_mlp"),
                        scale=1.0 / np.sqrt(d)),
        "wo": ParamSpec((E, f, d), ("experts", "expert_mlp", "embed"),
                        scale=1.0 / np.sqrt(f)),
    }
    if cfg.n_shared_experts:
        out["shared"] = _mlp_specs(cfg, cfg.n_shared_experts * cfg.d_ff_expert)
    return out


def _mamba_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, din, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    w = cfg.conv_width
    s = 1.0 / np.sqrt(d)
    out = {
        "wz": ParamSpec((d, din), ("embed", "ssm_inner"), scale=s),
        "wx": ParamSpec((d, din), ("embed", "ssm_inner"), scale=s),
        "wB": ParamSpec((d, n), ("embed", "ssm_state"), scale=s),
        "wC": ParamSpec((d, n), ("embed", "ssm_state"), scale=s),
        "wdt": ParamSpec((d, h), ("embed", "ssm_heads"), scale=s),
        "conv_x": ParamSpec((w, din), ("conv", "ssm_inner"), scale=1.0 / np.sqrt(w)),
        "conv_B": ParamSpec((w, n), ("conv", "ssm_state"), scale=1.0 / np.sqrt(w)),
        "conv_C": ParamSpec((w, n), ("conv", "ssm_state"), scale=1.0 / np.sqrt(w)),
        "A_log": ParamSpec((h,), ("ssm_heads",), init="zeros", dtype="float32"),
        "D": ParamSpec((h,), ("ssm_heads",), init="ones", dtype="float32"),
        "dt_bias": ParamSpec((h,), ("ssm_heads",), init="zeros", dtype="float32"),
        "norm": ParamSpec((din,), ("ssm_inner",), init="ones", dtype="float32"),
        "wout": ParamSpec((din, d), ("ssm_inner", "embed"), scale=1.0 / np.sqrt(din)),
    }
    if cfg.mamba_conv_bias:
        sb = 1.0 / np.sqrt(w)
        out["conv_x_bias"] = ParamSpec((din,), ("ssm_inner",), scale=sb)
        out["conv_B_bias"] = ParamSpec((n,), ("ssm_state",), scale=sb)
        out["conv_C_bias"] = ParamSpec((n,), ("ssm_state",), scale=sb)
    return out


def _block_specs(cfg: ModelConfig, spec: LayerSpec, cross: bool = False) -> Dict[str, Any]:
    d = cfg.d_model
    out: Dict[str, Any] = {
        "mixer_norm": ParamSpec((d,), ("embed",), init="ones", dtype="float32"),
    }
    if spec.mixer == "mamba":
        out["mamba"] = _mamba_specs(cfg)
    else:
        out["attn"] = _attn_specs(cfg)
    if cross:
        out["cross_norm"] = ParamSpec((d,), ("embed",), init="ones", dtype="float32")
        out["cross"] = _attn_specs(cfg, cross=True)
    if spec.ffn != "none":
        out["ffn_norm"] = ParamSpec((d,), ("embed",), init="ones", dtype="float32")
        out["ffn"] = _moe_specs(cfg) if spec.ffn == "moe" else _mlp_specs(cfg, cfg.dense_ff)
    return out


def _stack_specs(tree, repeat: int):
    """Prepend a 'layers' axis of size ``repeat`` to every leaf."""
    if repeat == 1:
        return tree
    return jax.tree_util.tree_map(
        lambda p: dataclasses.replace(p, shape=(repeat, *p.shape),
                                      axes=("layers", *p.axes)),
        tree, is_leaf=_is_spec)


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, V = cfg.d_model, cfg.vocab
    out: Dict[str, Any] = {}
    # VLM keeps its text-embedding table (decode consumes generated *tokens*);
    # only the modality frontend is stubbed (prefill takes embeddings).
    if not cfg.embed_inputs or cfg.is_encoder_decoder or cfg.family == "vlm":
        out["embed"] = ParamSpec((V, d), ("vocab", "embed"), scale=1.0)
    out["groups"] = [
        _stack_specs(
            {"layers": [_block_specs(cfg, s, cross=cfg.is_encoder_decoder)
                        for s in g.period]},
            g.repeat)
        for g in cfg.groups()
    ]
    out["final_norm"] = ParamSpec((d,), ("embed",), init="ones", dtype="float32")
    if not cfg.tie_embeddings:
        out["lm_head"] = ParamSpec((d, V), ("embed", "vocab"), scale=1.0 / np.sqrt(d))
    if cfg.is_encoder_decoder:
        enc_period = [LayerSpec("attn", "dense")] * 1
        enc = {"layers": [_block_specs(cfg, enc_period[0])]}
        out["encoder"] = {
            "groups": [_stack_specs(enc, cfg.n_encoder_layers)],
            "final_norm": ParamSpec((d,), ("embed",), init="ones", dtype="float32"),
        }
    return out


# ------------------------------------------------------------ materialization

def _leaf_dtype(p: ParamSpec, cfg: ModelConfig):
    return jnp.dtype(p.dtype or cfg.param_dtype)


def abstract_params(cfg: ModelConfig):
    return jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, _leaf_dtype(p, cfg)),
        model_specs(cfg), is_leaf=_is_spec)


def logical_axes(cfg: ModelConfig):
    return jax.tree_util.tree_map(lambda p: p.axes, model_specs(cfg), is_leaf=_is_spec)


def init_params(cfg: ModelConfig, key: jax.Array):
    """Concrete init. Per-leaf keys derive from the tree path (deterministic)."""
    specs = model_specs(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(specs, is_leaf=_is_spec)

    def init_leaf(path, p: ParamSpec):
        dt = _leaf_dtype(p, cfg)
        if p.init == "zeros":
            return jnp.zeros(p.shape, dt)
        if p.init == "ones":
            return jnp.ones(p.shape, dt)
        path_str = jax.tree_util.keystr(path)
        # crc32, not hash(): str hashes are salted per process, and the
        # weights must be the same in every process that passes this key.
        k = jax.random.fold_in(key, np.uint32(zlib.crc32(path_str.encode())
                                              % (2**31)))
        scale = p.scale if p.scale is not None else 1.0 / np.sqrt(p.shape[0])
        return (jax.random.normal(k, p.shape, jnp.float32) * scale).astype(dt)

    vals = [init_leaf(path, p) for path, p in leaves]
    return jax.tree_util.tree_unflatten(treedef, vals)


def param_count(cfg: ModelConfig) -> int:
    specs = model_specs(cfg)
    return sum(int(np.prod(p.shape)) for p in
               jax.tree_util.tree_leaves(specs, is_leaf=_is_spec))


def active_param_count(cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE: top-k + shared experts only)."""
    total = param_count(cfg)
    if not cfg.n_experts:
        return total
    specs = model_specs(cfg)
    expert_leaves = []

    def visit(path, p):
        if isinstance(p, ParamSpec) and "experts" in p.axes:
            expert_leaves.append(int(np.prod(p.shape)))

    jax.tree_util.tree_map_with_path(visit, specs, is_leaf=_is_spec)
    expert_total = sum(expert_leaves)
    frac = cfg.experts_per_tok / cfg.n_experts
    return int(total - expert_total * (1.0 - frac))
