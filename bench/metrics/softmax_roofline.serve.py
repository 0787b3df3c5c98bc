"""softmax_roofline.serve (%): the fused softmax kernel's share of its
roofline in serving.

Needed: every attention score a causal position needs (s (s + 1) / 2 per
head and layer for a prompt of s tokens, ctx per head and layer for a
decode position), each an f32 read once and written once: 8 bytes. Counted
from the work, not from the padded planes the program materializes (a
decode step normalizes every cache slot up to max_len), so a program that
stops computing masked scores is credited. The share is those bytes over
the HBM bandwidth, over the kernel's device time in the trace.
"""
from bench.lib import kernels


def needed_bytes(cfg, work) -> float:
    hl = cfg["num_attention_heads"] * cfg["num_hidden_layers"]
    elems = sum(s * (s + 1) / 2 for s in work["prompt_lens"]) \
        + sum(work["decode_ctx"])
    return 8.0 * hl * elems


def read(view):
    t = view.trace
    secs = t.op_s(kernels.is_softmax) if t is not None else 0.0
    if not secs:
        return None
    return (100.0 * needed_bytes(view.config, view.work)
            / view.peaks["hbm_bytes_per_s"] / secs)
