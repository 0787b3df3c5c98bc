"""Sharding scaling driver: 1-device against N-device meshes, one process.

Times the mesh-aware division-unit paths on a mesh over the first device and
on a mesh over every device this process sees, in the same process (one
process per chip: nothing here starts a child):

  * tiled fused divide through ``kernels.ops.tsdiv_divide`` on data-sharded
    (rows, cols) operands;
  * data-parallel K-Means (``workloads.kmeans_sharded``, mode=taylor) at
    --points scale.

On the 1-device mesh both fall back to their single-device paths, so the
pair is a sharded-vs-unsharded comparison (benchmarks/run.py
bench_sharding). On the CPU backend, force N virtual devices before the
process starts; the last stdout line is the JSON result:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.sharding.scaling --points 1000000
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, Optional, Sequence


def _time_us(fn, *args, reps: int, warmup: int = 1):
    out = None
    for _ in range(warmup):
        out = fn(*args)
    for o in out if isinstance(out, (tuple, list)) else (out,):
        if hasattr(o, "block_until_ready"):
            o.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    for o in out if isinstance(out, (tuple, list)) else (out,):
        if hasattr(o, "block_until_ready"):
            o.block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def measure(mesh, *, points: int = 1_000_000, dim: int = 8, k: int = 8,
            iters: int = 4, rows: int = 2048, cols: int = 384,
            reps: int = 3) -> Dict:
    """Tiled divide and sharded K-Means timings on ``mesh``."""
    import jax
    import jax.numpy as jnp

    from repro.core import division_modes as dm
    from repro.kernels import ops
    from repro.sharding import rules as shr
    from repro.workloads import kmeans as km

    a = jax.random.uniform(jax.random.PRNGKey(0), (rows, cols),
                           jnp.float32, 0.1, 10.0)
    b = jax.random.uniform(jax.random.PRNGKey(1), (rows, cols),
                           jnp.float32, 0.1, 10.0)
    sh2 = shr.data_sharding(mesh, 2, batch_size=rows)
    a_s, b_s = jax.device_put(a, sh2), jax.device_put(b, sh2)
    with shr.use_mesh(mesh):
        f_div = jax.jit(lambda u, v: ops.tsdiv_divide(u, v))
        us_div = _time_us(f_div, a_s, b_s, reps=reps)

    x = km.make_blobs(jax.random.PRNGKey(2), points, dim, k)
    init = jnp.take(x, jnp.arange(k) * (points // k), axis=0)
    x_s = jax.device_put(x, shr.data_sharding(mesh, 2, batch_size=points))
    cfg = dm.DivisionConfig(mode="taylor")
    with shr.use_mesh(mesh):
        def run_kmeans(xx, ii):
            res = km.kmeans_sharded(xx, cfg=cfg, n_iters=iters, init=ii)
            return res.centroids, res.assignments, res.inertia

        f_km = jax.jit(run_kmeans)
        us_km = _time_us(f_km, x_s, init, reps=reps)
        inertia = float(f_km(x_s, init)[2])
    return {
        "devices": mesh.size,
        "mesh": dict(mesh.shape),
        "tiled_divide_us": us_div,
        "tiled_divide_shape": [rows, cols],
        "kmeans_us": us_km,
        "kmeans": {"points": points, "dim": dim, "k": k, "iters": iters,
                   "inertia": inertia},
    }


def measure_pair(**kw) -> Dict:
    """:func:`measure` on a 1-device mesh and on an all-device mesh."""
    import jax

    from repro.launch.mesh import make_host_mesh

    n = jax.device_count()
    out = {f"devices{d}": measure(make_host_mesh(n_devices=d), **kw)
           for d in sorted({1, n})}
    one, full = out["devices1"], out[f"devices{n}"]
    out["speedup"] = {"devices": n,
                      "tiled_divide": one["tiled_divide_us"]
                      / full["tiled_divide_us"],
                      "kmeans": one["kmeans_us"] / full["kmeans_us"]}
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--points", type=int, default=1_000_000)
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--rows", type=int, default=2048)
    ap.add_argument("--cols", type=int, default=384)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)
    print(json.dumps(measure_pair(**vars(args))))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
