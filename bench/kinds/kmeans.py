"""K-Means cells: repeated Lloyd calls of the program over resident points.

Set-up makes the points and the first centroids on the device in one
jitted call from the seed, compiles the program's call once (checking for
its Mosaic kernel, for fallbacks to the jnp twin and, on a mesh, for the
sharded path), and runs it once. The window then calls it again and again,
each call starting from the centroids the previous one returned, until
``--seconds`` have passed; each call is waited for.

``correct``: calls drawn from the seed (the first of the window, and one
more) are run again by the plain float32 Lloyd of bench/reference/lloyd.py
from the same input centroids, and three numbers are compared with their
limits: the largest centroid difference (over the largest reference
coordinate), the share of points assigned differently, and the relative
difference of the inertia.
"""
from __future__ import annotations

import time

from bench.lib import device, seeds
from bench.lib.context import Check, Ctx, Outcome
from bench.lib.window import Window

CHECKS = ("centroid_err", "assign_mismatch", "inertia_err")


def make_data(n: int, d: int, k: int, data: dict):
    """Jitted ``key -> (points, first centroids)``.

    Points are a Gaussian mixture: ``components`` centers uniform in
    [-range, range]^d, each point one center plus N(0, spread^2) noise.
    The first centroids are k points, one drawn from each of k equal
    row strata, so they are distinct.
    """
    import jax
    import jax.numpy as jnp

    comps = int(data["components"])
    rng_, spread = float(data["center_range"]), float(data["spread"])
    stride = n // k

    def make(key):
        kc, kw, kn, ki = jax.random.split(key, 4)
        centers = jax.random.uniform(kc, (comps, d), jnp.float32,
                                     -rng_, rng_)
        which = jax.random.randint(kw, (n,), 0, comps)
        x = centers[which] + spread * jax.random.normal(kn, (n, d),
                                                        jnp.float32)
        idx = (jnp.arange(k) * stride
               + jax.random.randint(ki, (k,), 0, stride))
        return x, x[idx]

    return make


def program(cfg_json: dict, iters: int, mesh):
    """The timed call: ``(points, centroids) -> (centroids, assignments,
    inertia)`` through ``kmeans``, or ``kmeans_sharded`` on a mesh."""
    from repro.core import division_modes as dm
    from repro.sharding import rules as shr
    from repro.workloads import kmeans as km

    cfg = dm.DivisionConfig(mode=cfg_json["division"])

    def call(x, c):
        if mesh is None:
            r = km.kmeans(x, cfg=cfg, n_iters=iters, init=c)
        else:
            with shr.use_mesh(mesh):
                r = km.kmeans_sharded(x, cfg=cfg, n_iters=iters, init=c)
        return r.centroids, r.assignments, r.inertia

    return call


def compare(x, c_in, out, iters: int, dot: str = "highest"):
    """The three compared numbers for one call: the program's ``out``
    against the reference (``dot`` picks its precision) from ``c_in``."""
    import numpy as np

    from bench.reference import lloyd

    rc, ra, ri = lloyd.lloyd(x, c_in, iters, dot=dot)
    pc, pa, pi = (np.asarray(v) for v in out)
    rc, ra, ri = np.asarray(rc), np.asarray(ra), np.asarray(ri)
    cerr = float(np.max(np.abs(pc.astype(np.float64) - rc))
                 / max(float(np.max(np.abs(rc))), 1e-30))
    mism = float(np.mean(pa != ra))
    ierr = float(abs(float(pi) - float(ri)) / max(abs(float(ri)), 1e-30))
    return {"centroid_err": cerr, "assign_mismatch": mism,
            "inertia_err": ierr}


def sample_calls(n_calls: int, n_check: int, seed: int):
    """The first call, and ``n_check - 1`` more drawn from the seed."""
    rng = seeds.rng(seed, seeds.STREAM_SAMPLE)
    rest = list(range(1, n_calls))
    rng.shuffle(rest)
    return sorted([0] + rest[:max(0, n_check - 1)])


def build(ctx: Ctx):
    """(make, compiled, iters): the jitted data maker and the compiled
    call, checked for its kernel, for fallbacks and for the sharded path."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.workloads import kmeans as km

    cj, tj = ctx.cell.config, ctx.cell.traffic
    n, d, k = int(cj["points"]), int(cj["dim"]), int(cj["clusters"])
    iters = int(tj["iters_per_call"])
    mesh = None
    if ctx.cell.chips > 1:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(n_devices=ctx.cell.chips)
        shardings = (NamedSharding(mesh, P("data", None)),
                     NamedSharding(mesh, P()))
    else:
        one = jax.sharding.SingleDeviceSharding(ctx.devices[0])
        shardings = (one, one)
    make = jax.jit(make_data(n, d, k, cj["data"]), out_shardings=shardings)
    x, c0 = (jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
             for a, sh in zip(jax.eval_shape(make, seeds.key(0, 0)),
                              shardings))
    call = program(cj, iters, mesh)
    with device.FallbackSpy() as spy, device.CallSpy(km, "kmeans") as plain:
        compiled = jax.jit(call).lower(x, c0).compile()
    if spy.refused:
        raise device.Refused(f"jnp fallback at {spy.refused}")
    text = compiled.as_text()
    if ctx.require_kernel:
        device.check_kernel(compiled, "kmeans")
    if mesh is not None:
        if plain.calls:
            raise device.Refused("kmeans_sharded fell back to kmeans")
        if "all-reduce" not in text and "all-gather" not in text:
            raise device.Refused("the sharded call holds no collective")
    return make, compiled, iters


def setup(ctx: Ctx):
    """Points, first centroids and the compiled call, run once; with the
    seconds since process start at which each step of set-up ended."""
    import jax

    phases = {} if ctx.t_chips is None else {"chips": ctx.t_chips - ctx.t0}
    make, compiled, iters = build(ctx)
    phases["compile"] = time.perf_counter() - ctx.t0
    x, c0 = jax.block_until_ready(make(seeds.key(ctx.seed,
                                                 seeds.STREAM_DATA)))
    phases["data"] = time.perf_counter() - ctx.t0
    jax.block_until_ready(compiled(x, c0))
    phases["warm_call"] = time.perf_counter() - ctx.t0
    return x, c0, compiled, iters, phases


def run(ctx: Ctx) -> Outcome:
    import gc

    import jax

    x, c0, compiled, iters, phases = setup(ctx)
    counter = device.CompileCounter()
    win = Window(ctx.trace_dir if ctx.trace else None)
    calls = []
    c = c0
    t_open = win.open()
    setup_s = t_open - ctx.t0
    while True:
        with jax.profiler.TraceAnnotation("bench.call"):
            out = jax.block_until_ready(compiled(x, c))
        calls.append((c, out))
        c = out[0]
        if time.perf_counter() - t_open >= ctx.seconds:
            break
    t_close = win.close()
    trace = win.finish(ctx.save_trace, all_lines=ctx.save_trace is not None)
    compiles = counter.count(t_open, t_close)
    mem = device.memory_peak_bytes(ctx.devices, [compiled])
    mem_runtime = device.memory_peak_bytes(ctx.devices)

    n_calls = len(calls)
    picked = sample_calls(n_calls, int(ctx.cell.traffic["check_calls"]),
                          ctx.seed)
    kept = [calls[j] for j in picked]
    del calls, out, c, compiled
    gc.collect()
    worst = {name: 0.0 for name in CHECKS}
    for c_in, o in kept:
        got = compare(x, c_in, o, iters)
        worst = {name: max(worst[name], got[name]) for name in CHECKS}

    cj = ctx.cell.config
    n_iters = n_calls * iters
    window_s = t_close - t_open
    return Outcome(
        e2e={"setup_s": setup_s, "lloyd_iter_ms": window_s / n_iters * 1e3},
        work={"points": int(cj["points"]), "dim": int(cj["dim"]),
              "clusters": int(cj["clusters"]), "calls": n_calls,
              "iters": n_iters, "window_s": window_s, "chips": ctx.cell.chips},
        checks=[Check(name, worst[name], ctx.limits[name]) for name in CHECKS],
        attempted=n_calls, failed=0, memory_peak_bytes=mem, trace=trace,
        notes=[("setup_phases_s", {k: round(v, 3) for k, v in phases.items()}),
               ("memory_runtime_peak_bytes", mem_runtime),
               ("compiles_in_window", compiles), ("calls_checked", picked)])


def calibrate(ctx: Ctx, seed_list, control_seeds, n_calls: int = 4):
    """Readings for the limits: for each seed, ``n_calls`` calls of the
    program from its first centroids, each compared with the reference;
    for the seeds in ``control_seeds`` also the control (the reference at
    three bf16 passes, as precision ``high`` computes) from the same input
    centroids."""
    import jax

    from bench.reference import lloyd

    make, compiled, iters = build(ctx)
    rows = []
    for seed in seed_list:
        x, c = jax.block_until_ready(make(seeds.key(seed, seeds.STREAM_DATA)))
        for j in range(n_calls):
            out = jax.block_until_ready(compiled(x, c))
            row = {"seed": seed, "call": j,
                   "program": compare(x, c, out, iters)}
            if seed in control_seeds:
                ctl = lloyd.lloyd(x, c, iters, dot="bf16x3")
                row["control"] = compare(x, c, ctl, iters)
            rows.append(row)
            c = out[0]
    return rows
