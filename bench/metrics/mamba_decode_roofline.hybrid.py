"""mamba_decode_roofline.hybrid (%): the Mamba-2 layers of the decode step
against the HBM roofline.

Needed, per decode step and Mamba layer: every slot's SSM state (heads x
d_head x d_state, f32) and conv state (d_conv - 1 positions of x, B and C,
in the served dtype) read once and written once, and the mixer's weights
read once (the in- and out-projections, the convs with their biases, A,
D, dt's bias and the gated norm's gain). Counted from the configuration
and the window's decode steps, not from the planes the program
materializes. The share is those bytes over the HBM bandwidth, over the
device time under ``mamba.mixer`` in the decode program (each operation
read in the program that ran it, bench/lib/programs.py).
"""
from bench.lib import programs

MIXER = r"mamba\.mixer"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def step_bytes(cfg, slots: int) -> float:
    d, h = cfg["hidden_size"], cfg["mamba_n_heads"]
    p, n, w = cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    din = cfg["mamba_expand"] * d
    b = DTYPE_BYTES[cfg["torch_dtype"]]
    layers = sum(1 for t in cfg["layer_types"] if t == "mamba")
    state = slots * h * p * n * 4 * 2
    conv = slots * (w - 1) * (din + 2 * n) * b * 2
    weights = ((2 * d * din + 2 * d * n + d * h + din * d) * b
               + (w + 1) * (din + 2 * n) * b + (3 * h + din) * 4)
    return layers * (state + conv + weights)


def read(view):
    got = programs.of_view(view)
    steps = view.work.get("decode_steps")
    if got is None or not steps:
        return None
    secs = programs.scoped_s(view.trace, got[1], got[0], (MIXER,),
                             program="decode")
    if not secs:
        return None
    need = step_bytes(view.config, view.work["slots"]) * steps
    return 100.0 * need / view.peaks["hbm_bytes_per_s"] / secs
