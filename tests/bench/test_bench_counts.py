"""Operation and byte counts of the roofline and mfu readers, against a
count made position by position."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import spec, trace as tr  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GRANITE = json.loads((ROOT / "bench/configs/granite-8b.json").read_text())


def view(work, trace=None, config=None, traffic=None, chips=1):
    return SimpleNamespace(work=work, trace=trace, config=config or {},
                           traffic=traffic or {}, peaks=PEAKS, chips=chips)


def one_kernel_trace(name, ns):
    return tr.Trace({"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [[name, 0, ns]]}]}]}, window=(0, 2 * ns))


def test_lloyd_mfu_counts():
    m = spec.load_metric("lloyd_mfu")
    w = {"points": 1224608, "clusters": 500, "dim": 42}
    assert m.needed_flops(w) == 4 * 1224608 * 500 * 42
    assert m.needed_bytes(w) == 4 * (2 * 1224608 * 42 + 500 * 42)
    # one iteration in exactly its least time reads 100%
    t_min = max(m.needed_flops(w) / 197e12, m.needed_bytes(w) / 819e9)
    v = view(dict(w, iters=10, window_s=10 * t_min, chips=1))
    assert m.read(v) == pytest.approx(100.0)
    # over four chips the same time is a quarter of the summed peak
    v = view(dict(w, iters=10, window_s=10 * t_min, chips=4))
    assert m.read(v) == pytest.approx(25.0)
    assert m.read(view({"iters": 0})) is None


def test_tsdiv_roofline_counts():
    m = spec.load_metric("tsdiv_roofline.kmeans")
    w = {"points": 16, "clusters": 4, "dim": 3}
    plane = 8 * 16 * 4 + 4            # quotient read+write, divisor once
    cents = 8 * 4 * 3 + 4 * 4         # (K, D) over the (K, 1) counts
    assert m.needed_bytes_per_call(w, 2) == 2 * (plane + cents + 12) \
        + plane + 12
    w = {"points": 1224608, "clusters": 500, "dim": 42}
    per_call = m.needed_bytes_per_call(w, 10)
    ns = per_call * 3 / 819e9 * 1e9   # three calls exactly at the roofline
    v = view(dict(w, calls=3, chips=1), one_kernel_trace(
        "%tsdiv_divide_tiled_2d.1", int(round(ns * 2))), traffic={"iters_per_call": 10})
    assert m.read(v) == pytest.approx(50.0, rel=1e-6)
    v = view(dict(w, calls=3, chips=1), one_kernel_trace("fusion", 100),
             traffic={"iters_per_call": 10})
    assert m.read(v) is None


def brute_flops(cfg, prompt_lens, decode_ctx, lm_rows):
    d, h, kv, hd, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    layers = cfg["num_hidden_layers"]
    weights = d * h * hd * 2 + d * kv * hd * 2 + d * f * 3
    total = 0
    for s in prompt_lens:
        for p in range(s):
            total += layers * (2 * weights + 4 * hd * h * (p + 1))
    for ctx in decode_ctx:
        total += layers * (2 * weights + 4 * hd * h * ctx)
    return total + lm_rows * 2 * d * cfg["vocab_size"]


def test_serve_mfu_counts():
    m = spec.load_metric("serve_mfu")
    work = {"prompt_lens": [128, 384], "decode_ctx": [129, 130, 385],
            "lm_rows": 5, "window_s": 1.0}
    want = brute_flops(GRANITE, [128, 384], [129, 130, 385], 5)
    assert m.needed_flops(GRANITE, work) == pytest.approx(want, rel=1e-12)
    v = view(work, config=GRANITE)
    assert m.read(v) == pytest.approx(100 * want / 197e12, rel=1e-12)
    # Granite-8B's 9 layers: 2 x 218.1M weights a position and layer
    assert m.layer_weights(GRANITE) == 218_103_808


def test_softmax_roofline_counts():
    m = spec.load_metric("softmax_roofline.serve")
    work = {"prompt_lens": [4, 2], "decode_ctx": [5, 7]}
    elems = (4 * 5 // 2 + 2 * 3 // 2 + 5 + 7) * 32 * 9
    assert m.needed_bytes(GRANITE, work) == 8 * elems
    ns = 8 * elems / 819e9 * 1e9
    v = view(work, one_kernel_trace("%softmax_2d.1", ns * 4), GRANITE)
    assert m.read(v) == pytest.approx(25.0, rel=1e-3)


def test_shares_read_nothing_without_a_trace():
    for name in ("unit_share.kmeans", "unit_share.serve",
                 "collective_share.kmeans", "device_idle_share.kmeans",
                 "device_idle_share.serve", "softmax_roofline.serve"):
        assert spec.load_metric(name).read(view({}, config=GRANITE)) is None


def test_collective_share_only_where_there_are_collectives():
    m = spec.load_metric("collective_share.kmeans")
    assert m.read(view({}, one_kernel_trace("%fusion.1", 100))) is None
    assert m.read(view({}, one_kernel_trace("%all-reduce.3", 100))) \
        == pytest.approx(100.0)
