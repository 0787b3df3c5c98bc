"""moe_roofline.hybrid (%): the expert layers against the HBM roofline.

Needed, per forward call (each decode step and each prefill the window
ran) and expert layer: the held experts' and the shared expert's weights
and the router read once, and the call's tokens read in and written out
once (a decode step's slots, a prefill's real prompt length). Counted from
the configuration and the window's work, not from the planes the program
materializes. The share is those bytes over the HBM bandwidth, over the
device time under ``moe.experts`` and ``moe.shared`` (each operation read
in the program that ran it, bench/lib/programs.py).
"""
from bench.lib import programs

EXPERTS = (r"moe\.experts", r"moe\.shared")
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_weight_bytes(cfg) -> float:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    b = DTYPE_BYTES[cfg["torch_dtype"]]
    held = cfg["num_local_experts"] * 3 * d * f * b
    shared = 3 * d * cfg["shared_intermediate_size"] * b
    router = d * cfg["published_num_local_experts"] * 4
    return held + shared + router


def needed_bytes(cfg, work) -> float:
    b = DTYPE_BYTES[cfg["torch_dtype"]]
    calls = work["decode_steps"] + len(work["prompt_lens"])
    tokens = work["decode_steps"] * work["slots"] + sum(work["prompt_lens"])
    per_layer = calls * layer_weight_bytes(cfg) \
        + tokens * cfg["hidden_size"] * b * 2
    return cfg["num_hidden_layers"] * per_layer


def read(view):
    got = programs.of_view(view)
    if got is None or not view.work.get("decode_steps"):
        return None
    secs = programs.scoped_s(view.trace, got[1], got[0], EXPERTS)
    if not secs:
        return None
    return (100.0 * needed_bytes(view.config, view.work)
            / view.peaks["hbm_bytes_per_s"] / secs)
