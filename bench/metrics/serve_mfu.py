"""serve_mfu (%): the served tokens' needed operations per second over the
chip's bf16 peak.

Needed, per layer, for every position the window processed (each prompt
token prefilled, each token fed to a decode step): 2 operations per
matrix weight (q, k, v, o and the three MLP matrices) and 4 d_head heads
ctx for attention, with ctx the keys a causal position needs (its own
position plus one); and the LM head, 2 d V, once for every token served
(prefill needs the last position's logits only).
"""


def layer_weights(cfg) -> int:
    d, h, kv, hd, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    return 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f


def needed_flops(cfg, work) -> float:
    layers = cfg["num_hidden_layers"]
    attn = 4.0 * cfg["head_dim"] * cfg["num_attention_heads"]
    per_pos = 2.0 * layer_weights(cfg) * layers
    prefill = sum(per_pos * s + attn * layers * s * (s + 1) / 2
                  for s in work["prompt_lens"])
    decode = sum(per_pos + attn * layers * ctx for ctx in work["decode_ctx"])
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * work["lm_rows"]
    return prefill + decode + head


def read(view):
    w = view.work
    if not w.get("window_s"):
        return None
    rate = needed_flops(view.config, w) / w["window_s"]
    return 100.0 * rate / (view.peaks["bf16_flops_per_s"] * view.chips)
