"""The controls of bench/calibrate.py, at a size a test run holds: the
reference computed one precision step down departs from the reference
further than the program does, on the numbers the cells compare.

The limits themselves were set on the chip at each cell's own size; the
readings are in PERF.md."""
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from bench.kinds import kmeans as bk, serve as bs  # noqa: E402
from bench.lib import spec  # noqa: E402
from bench.lib.context import Ctx  # noqa: E402


def ctx_for(cell, seconds=1.0):
    return Ctx(cell=cell, seed=0, seconds=seconds, trace=False,
               t0=time.perf_counter(), devices=jax.devices()[:1],
               require_kernel=False)


def test_kmeans_control_three_bf16_passes():
    cell = spec.load_cell("kmeans-kdd99-k500")
    cell.config = dict(cell.config, points=16384, clusters=64)
    rows = bk.calibrate(ctx_for(cell), [21], {21}, n_calls=2)
    later = [r for r in rows if r["call"] == 1][0]
    assert later["control"]["centroid_err"] \
        > 3 * later["program"]["centroid_err"]


def test_serving_control_fp8(monkeypatch):
    from repro.configs import get_smoke_config
    from repro.core.division_modes import DivisionConfig
    small = get_smoke_config("granite_8b")
    monkeypatch.setattr(bs, "model_config", lambda c: dataclasses.replace(
        small, division=DivisionConfig(mode=c["division"])))
    cell = spec.Cell(
        name="granite-8b.chat40", chips=1, config_name="granite-8b",
        traffic_name="chat40",
        config=json.loads((ROOT / "bench/configs/granite-8b.json").read_text()),
        traffic=spec.load_traffic("chat40"), end_to_end=[], per_layer=[])
    cell.traffic = dict(
        cell.traffic, clients=4, max_len=96, block=8,
        prompt={"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
                "max": 64, "round_to": 8},
        output={"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2,
                "max": 32, "round_to": 1},
        ramp_decode_steps=8, queue_rate_bound=400, ramp_bound_s=5)
    rows = bs.calibrate(ctx_for(cell, 1.5), [5, 6], {5, 6})
    assert all(r["served"] > 0 and r["cache_rows"] > 0 for r in rows)
    for name in bs.CHECKS:
        prog = max(r["program"][name] for r in rows)
        ctl = min(r["control"][name] for r in rows)
        assert ctl > max(3 * prog, 1e-3), name
