"""granite-4.0-h-small [hybrid]: 40L d_model=4096, 36 Mamba-2 + 4 NoPE GQA
attention layers (32H, kv=8, head_dim 128), MoE in every layer (72 routed
experts of width 768, top-10, and a shared expert of width 1536),
vocab=100352, tied embeddings
(https://huggingface.co/ibm-granite/granite-4.0-h-small, config.json).

Layer pattern (period 10, scanned 4x): attention at position 5 of each
10-block (layers 5, 15, 25, 35), Mamba-2 elsewhere; every FFN is the expert
layer. Mamba-2: 128 heads x d_head 64, d_state 128, one group, conv width 4
with a bias on the convs of x, B and C, expand 2 (d_inner 8192), chunk 256,
no projection bias.

Granite's scalars: embeddings x 12, each mixer's and each FFN's output x
0.22 before it joins the residual stream, attention scores x 1/128 (not
1/sqrt(128)), no position embedding, logits / 16.

The shared expert of width 1536 is ``n_shared_experts=2`` at the routed
width 768: ``params._moe_specs`` builds one gated MLP of width
``n_shared_experts * d_ff_expert``, which is the published
``shared_intermediate_size``. The router is the unit's softmax over 72
logits, top-10, then renormalization through ``division_modes.recip``: the
same gates as Granite's softmax over the ten chosen logits.

Totals ~32.2B params, ~8.8B active: the published 32B / 9B. ``CONFIG``
holds every expert; a deployment that divides each layer's experts over
chips sets ``experts_held`` (the benchmark's configuration holds 9 of 72).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=768,
    vocab=100_352,
    attn_period=10, attn_offset=5,
    moe_period=1,
    n_experts=72, experts_per_tok=10,
    d_ff_expert=768, n_shared_experts=2,
    moe_dispatch="sort",
    ssm_state=128, ssm_heads=128, ssm_head_dim=64, d_inner=8192,
    ssm_chunk=256, conv_width=4, mamba_conv_bias=True,
    rope=False, attention_multiplier=0.0078125,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=16.0,
    tie_embeddings=True,
    norm_eps=1e-5,
)

SMOKE_CONFIG = ModelConfig(
    name="granite-4.0-h-smoke",
    family="hybrid",
    n_layers=10,
    d_model=64,
    n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=32,
    vocab=256,
    attn_period=10, attn_offset=5,
    moe_period=1,
    n_experts=16, experts_per_tok=4,
    d_ff_expert=32, n_shared_experts=2,
    moe_dispatch="sort",
    ssm_state=16, ssm_heads=8, ssm_head_dim=16, d_inner=128,
    ssm_chunk=16, conv_width=4, mamba_conv_bias=True,
    rope=False, attention_multiplier=0.0625,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=16.0,
    tie_embeddings=True,
    norm_eps=1e-5,
    remat=False,
)
