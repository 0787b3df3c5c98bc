"""Config system: model configs, shape configs, sharding rules, registry.

Every assigned architecture is a ``ModelConfig`` in its own module under
``repro.configs``; ``get_config(arch_id)`` resolves it. Shapes are the four
assigned (seq_len, global_batch) cells; ``input_specs`` builds the
ShapeDtypeStruct stand-ins the dry-run lowers against.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.core.division_modes import DivisionConfig

# ----------------------------------------------------------------- layer spec

MIXERS = ("attn", "swa", "mamba")
FFNS = ("dense", "moe", "none")


@dataclass(frozen=True)
class LayerSpec:
    mixer: str
    ffn: str

    def __post_init__(self):
        assert self.mixer in MIXERS and self.ffn in FFNS


@dataclass(frozen=True)
class Group:
    """``repeat`` copies of the layer ``period`` — lowered as one lax.scan."""

    period: Tuple[LayerSpec, ...]
    repeat: int


# -------------------------------------------------------------- model config

@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    # --- layer pattern ---
    attn_period: int = 1           # attention every k-th layer (hybrid); 1 = all
    attn_offset: int = 0
    moe_period: int = 0            # 0 = no MoE; k = MoE ffn every k-th layer
    moe_offset: int = 0
    first_dense: int = 0           # leading layers forced dense-FFN (deepseek)
    # --- attention ---
    sliding_window: int = 0        # >0 enables SWA layers
    global_every: int = 0          # 1 global layer per this many (gemma 5:1 -> 6)
    rope_theta: float = 10_000.0
    rope: bool = True              # False: no position embedding (NoPE)
    attention_multiplier: Optional[float] = None  # score scale; None = 1/sqrt(head_dim)
    # --- moe ---
    n_experts: int = 0
    experts_per_tok: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    d_ff_dense: int = 0            # dense-FFN width when it differs (deepseek)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    moe_dispatch: str = "cumsum"   # cumsum (GShard-style positions) | sort
                                   # (megablocks-style; O(T log T), avoids the
                                   # global cumsum that blows up under SPMD)
    experts_held: Optional[Tuple[int, int]] = None  # [lo, hi): the routed
                                   # experts this layer holds (expert
                                   # parallelism's share); the router keeps
                                   # all n_experts, no token is dropped
    # --- ssm (mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    d_inner: int = 0
    ssm_chunk: int = 256
    conv_width: int = 4
    mamba_conv_bias: bool = False  # bias on the depthwise convs of x, B, C
    # --- enc-dec ---
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    encoder_seq: int = 0           # stub frontend: precomputed frames/patches
    # --- io ---
    embed_inputs: bool = False     # vlm/audio stub: inputs are embeddings
    tie_embeddings: bool = False
    # Granite-style scalars (1.0 adds no operation)
    embedding_multiplier: float = 1.0   # embeddings times this
    residual_multiplier: float = 1.0    # each mixer's and FFN's output times this
    logits_scaling: float = 1.0         # logits divided by this
    # --- numerics / distribution ---
    param_dtype: str = "bfloat16"
    opt_state_dtype: str = "float32"
    norm_eps: float = 1e-6
    division: DivisionConfig = field(default_factory=lambda: DivisionConfig(mode="taylor"))
    sharding_rules: Dict[str, Optional[str]] = field(default_factory=dict)
    remat: bool = True
    train_microbatch_size: int = 4  # sequences per data-shard per microbatch
    attn_chunk: int = 2048          # query-chunked attention threshold/size
    use_flash_kernel: bool = False  # fused flash-attention (kernels/
                                    # flash_attention.py) — zeroes the score
                                    # term of the HBM model; TPU fast path
    scan_unroll: bool = False       # dry-run cost probe: unroll scans so XLA
                                    # cost_analysis sees every trip (it counts
                                    # while-loop bodies exactly once)
    group_repeat_override: Optional[Tuple[int, ...]] = None  # cost-probe knob
    notes: str = ""

    # -------------------------------------------------- derived layer pattern
    def layer_specs(self) -> List[LayerSpec]:
        specs = []
        for i in range(self.n_layers):
            if self.family in ("ssm",):
                mixer = "mamba"
            elif self.attn_period > 1:
                mixer = "attn" if i % self.attn_period == self.attn_offset else "mamba"
            elif self.sliding_window > 0 and self.global_every > 0:
                mixer = "attn" if i % self.global_every == self.global_every - 1 else "swa"
            else:
                mixer = "attn"
            if self.family == "ssm":
                ffn = "none"
            elif self.moe_period > 0 and i >= self.first_dense \
                    and i % self.moe_period == self.moe_offset:
                ffn = "moe"
            else:
                ffn = "dense"
            specs.append(LayerSpec(mixer, ffn))
        return specs

    def groups(self) -> List[Group]:
        """Greedy periodic grouping: find the shortest period p such that the
        layer pattern is p-periodic, then scan over n_layers/p repeats.

        ``group_repeat_override`` swaps the repeat counts (dry-run cost probes
        lower tiny 1-2 repeat stacks and reconstruct full-depth cost affinely;
        XLA's cost_analysis counts loop bodies once, so depth must be probed,
        not trusted)."""
        base = self._groups_base()
        if self.group_repeat_override is not None:
            assert len(self.group_repeat_override) == len(base)
            return [Group(g.period, r)
                    for g, r in zip(base, self.group_repeat_override)]
        return base

    def _groups_base(self) -> List[Group]:
        specs = self.layer_specs()
        n = len(specs)
        lead = specs[: self.first_dense]
        rest = specs[self.first_dense:]
        out: List[Group] = []
        if lead:
            out.append(Group(tuple(lead), 1))
        m = len(rest)
        for p in range(1, m + 1):
            if m % p == 0 and all(rest[i] == rest[i % p] for i in range(m)):
                out.append(Group(tuple(rest[:p]), m // p))
                return out
        out.append(Group(tuple(rest), 1))
        return out

    @property
    def dense_ff(self) -> int:
        return self.d_ff_dense or self.d_ff

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def n_experts_here(self) -> int:
        """Routed experts whose weights this layer holds."""
        if self.experts_held is None:
            return self.n_experts
        lo, hi = self.experts_held
        return hi - lo


# -------------------------------------------------------------- shape config

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


LM_SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}

# Archs whose *every* attention layer is full attention skip long_500k
# (sub-quadratic requirement); SSM / hybrid / sliding-window archs run it.
SUBQUADRATIC_FAMILIES = ("ssm", "hybrid")


def long_context_ok(cfg: ModelConfig) -> bool:
    if cfg.family in SUBQUADRATIC_FAMILIES:
        return True
    if cfg.sliding_window > 0 and cfg.global_every > 0:
        return True  # gemma-style mostly-local attention
    return False


def shapes_for(cfg: ModelConfig) -> List[ShapeConfig]:
    out = [LM_SHAPES["train_4k"], LM_SHAPES["prefill_32k"], LM_SHAPES["decode_32k"]]
    if long_context_ok(cfg):
        out.append(LM_SHAPES["long_500k"])
    return out


# ---------------------------------------------------------------- registry

ARCH_IDS = [
    "mamba2_780m",
    "granite_8b",
    "llama3_8b",
    "gemma3_12b",
    "tinyllama_1_1b",
    "llava_next_mistral_7b",
    "whisper_tiny",
    "jamba_1_5_large",
    "moonshot_v1_16b_a3b",
    "deepseek_moe_16b",
    "granite_4_0_h_small",
    "paper_fpdiv",
]


def canon(arch: str) -> str:
    return arch.replace("-", "_").replace(".", "_")


def get_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canon(arch)}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canon(arch)}")
    return mod.SMOKE_CONFIG


# Default logical-axis -> mesh-axis rules. Arch configs override per-axis.
DEFAULT_RULES: Dict[str, Optional[str]] = {
    "embed": None,
    "heads": "model",
    "kv_heads": "model",      # dropped automatically when not divisible
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "expert_mlp": None,
    "ssm_inner": "model",
    "ssm_heads": "model",
    "ssm_state": None,
    "conv": None,
    "layers": None,
}


def rules_for(cfg: ModelConfig) -> Dict[str, Optional[str]]:
    rules = dict(DEFAULT_RULES)
    rules.update(cfg.sharding_rules)
    return rules
