"""A run with the timed path broken underneath comes out not correct.

Each test skips the look for a chip and drives the rest of a run at a
small size on the CPU, with the cell's own limits, once sound and once for
each fault the cell can have: a call that returns its state unchanged,
half of the points left out of the centroid means, an answer altered
where it is produced, the exchange between chips left out, and a served
token altered where it is produced.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from bench.kinds import kmeans as bk, serve as bs  # noqa: E402
from bench.lib import spec  # noqa: E402
from bench.lib.context import Ctx  # noqa: E402


def limits(cell):
    return {k: float(v["limit"]) for k, v in json.loads(
        (ROOT / "bench/limits" / f"{cell}.json").read_text())["checks"].items()}


def ctx_for(cell, seed=11, seconds=1.0, limits_of=None):
    return Ctx(cell=cell, seed=seed, seconds=seconds, trace=False,
               t0=time.perf_counter(), devices=jax.devices()[:cell.chips],
               require_kernel=False, limits=limits(limits_of or cell.name))


# ------------------------------------------------------------------ K-Means

def kmeans_cell():
    cell = spec.load_cell("kmeans-kdd99-k500")
    cell.config = dict(cell.config, points=8192, clusters=64)
    return cell


def unchanged(real):
    def call(x, k=None, **kw):
        return real(x, k, **dict(kw, n_iters=0))
    return call


def half_batch(real):
    def call(x, k=None, **kw):
        half = real(x[: x.shape[0] // 2], k, **kw)
        full = real(x, k, **dict(kw, n_iters=0, init=half.centroids))
        return dataclasses.replace(full, inertia_trace=half.inertia_trace)
    return call


def altered_answer(real):
    def call(x, k=None, **kw):
        r = real(x, k, **kw)
        n = x.shape[0] // 100
        a = r.assignments.at[:n].set((r.assignments[:n] + 1)
                                     % r.centroids.shape[0])
        return dataclasses.replace(r, assignments=a)
    return call


@pytest.mark.parametrize("fault", [None, unchanged, half_batch,
                                   altered_answer])
def test_kmeans_fault_is_not_correct(monkeypatch, fault):
    from repro.workloads import kmeans as km
    if fault is not None:
        monkeypatch.setattr(km, "kmeans", fault(km.kmeans))
    out = bk.run(ctx_for(kmeans_cell()))
    assert out.correct == (fault is None), [
        (c.name, c.value, c.limit) for c in out.checks]


SHARDED = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench.kinds import kmeans as bk
from bench.lib import device, spec
from bench.lib.context import Ctx
# The sharded configuration (bench/configs/kmeans-kdd99-x4.json) with the
# one-chip cell's traffic and limits: its cell waits for a four-chip run.
lim = {k: float(v["limit"]) for k, v in json.load(open(
    sys.argv[1] + "/bench/limits/kmeans-kdd99-k500.json"))["checks"].items()}
cfg = json.load(open(sys.argv[1] + "/bench/configs/kmeans-kdd99-x4.json"))
def cell(points):
    return spec.Cell(name="kmeans-kdd99-x4.lloyd10", chips=4,
                     config_name=cfg["name"], traffic_name="lloyd10",
                     config=dict(cfg, points=points, clusters=64),
                     traffic=spec.load_traffic("lloyd10"),
                     end_to_end=[], per_layer=[])
def run(points):
    ctx = Ctx(cell=cell(points), seed=5, seconds=1.0, trace=False,
              t0=time.perf_counter(), devices=jax.devices()[:4],
              require_kernel=False, limits=lim)
    return bk.run(ctx)
res = {"sound": run(8192).correct}
real_psum, real_gather = jax.lax.psum, jax.lax.all_gather
# the centroid sums' exchange left out (the counts' all-reduce stays)
jax.lax.all_gather = lambda x, axes, axis=0, tiled=False: x
res["no_sum_exchange"] = run(8192).correct
# every exchange left out: no collective is left, and the run refuses
jax.lax.psum = lambda x, axes: x
try:
    run(8192)
    res["no_exchange_refused"] = False
except device.Refused:
    res["no_exchange_refused"] = True
jax.lax.psum, jax.lax.all_gather = real_psum, real_gather
from repro.sharding import rules
real_part = rules.batch_partition
rules.batch_partition = lambda mesh, n: ()   # no axis shards the points
try:
    run(8192)
    res["fallback_refused"] = False
except device.Refused:
    res["fallback_refused"] = True
rules.batch_partition = real_part
print(json.dumps(res))
"""


def test_sharded_kmeans_faults_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", SHARDED, str(ROOT)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res == {"sound": True, "no_sum_exchange": False,
                   "no_exchange_refused": True, "fallback_refused": True}


# ------------------------------------------------------------------ serving

def serve_cell(monkeypatch):
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
    cell.config = dict(cell.config, hidden_size=small.d_model,
                       num_attention_heads=small.n_heads,
                       num_key_value_heads=small.n_kv_heads,
                       head_dim=small.head_dim, intermediate_size=small.d_ff,
                       vocab_size=small.vocab,
                       num_hidden_layers=small.n_layers)
    cell.traffic = dict(
        cell.traffic, clients=4, max_len=96, block=8,
        prompt={"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
                "max": 64, "round_to": 8},
        output={"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2,
                "max": 32, "round_to": 1},
        ramp_decode_steps=8, queue_rate_bound=400, ramp_bound_s=5)
    return cell


@pytest.mark.parametrize("fault", [False, True])
def test_serving_altered_token_is_not_correct(monkeypatch, fault):
    from repro.serving import engine
    if fault:
        real = engine.decode_step

        def altered(cfg, params, cache, tokens, pos):
            logits, cache = real(cfg, params, cache, tokens, pos)
            return logits.at[:, 0].set(logits.max(axis=-1) + 1.0), cache

        monkeypatch.setattr(engine, "decode_step", altered)
    out = bs.run(ctx_for(serve_cell(monkeypatch), seconds=1.5,
                         limits_of="granite-8b-chat"))
    assert out.correct == (not fault), [
        (c.name, c.value, c.limit) for c in out.checks]
