"""Benchmark harness: one function per paper table/figure + kernel/e2e perf.

Prints ``name,us_per_call,derived`` CSV rows (derived = the quantity the
paper's table reports). Writes the full results to benchmarks/results.json.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run --only segments_table
  PYTHONPATH=src python -m benchmarks.run --only workloads [--quick]

The division-perf benches (``workloads``, ``tiled_divide``) additionally
merge their rows into ``BENCH_div.json`` at the repo root — the committed
perf-trajectory artifact (wall-clock + workload-level accuracy per division
mode, one snapshot per PR). On this container every Pallas cell runs
CPU-interpret, so those absolute numbers are proxies; the jnp-mode rows are
compiled XLA and are fair CPU comparisons (see docs/numerics.md).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

RESULTS = {}
QUICK = False

_BENCH_DIV = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          os.pardir, "BENCH_div.json")
_BENCH_DIV_KEYS = ("workloads", "tiled_divide", "consumers", "serving",
                   "sharding")


def _write_bench_div():
    """Merge the division-perf RESULTS sections into BENCH_div.json.

    Merging (rather than overwriting) lets ``--only workloads`` and
    ``--only tiled_divide`` each refresh their own section without erasing
    the other's trajectory point.
    """
    import jax

    from repro.kernels import ops

    path = os.path.abspath(_BENCH_DIV)
    doc = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            doc = {}
    doc["meta"] = {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "pallas_interpret": ops.INTERPRET,
        "note": ("With pallas_interpret the Pallas cells ran in the "
                 "interpreter: their wall-clock is a functional proxy, not "
                 "kernel perf. Timings are for the backend named here and "
                 "are not device numbers unless it is a TPU."),
    }
    for k in _BENCH_DIV_KEYS:
        if k in RESULTS:
            # quick is stamped per section, not on the global meta: sections
            # merge independently, so a CI-smoke refresh of one must not
            # relabel a retained full-run trajectory point in the other.
            doc[k] = {"quick": bool(QUICK), **RESULTS[k]}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"# wrote {path}")


def _block(out):
    """Wait for async (jax) results; no-op for plain values."""
    leaves = out if isinstance(out, (tuple, list)) else (out,)
    for o in leaves:
        if hasattr(o, "block_until_ready"):
            o.block_until_ready()


def _time_us(fn, *args, reps: int = 5, warmup: int = 2, ret_out: bool = False):
    out = None
    for _ in range(warmup):
        out = fn(*args)
    _block(out)          # async warmup work must not bleed into the window
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    _block(out)
    us = (time.perf_counter() - t0) / reps * 1e6
    return (us, out) if ret_out else us


def bench_segments_table():
    """Paper Table I: segment boundaries for n=5, 53-bit precision."""
    from repro.core import seeds

    t0 = time.perf_counter()
    table = seeds.compute_segments(5, 53)
    us = (time.perf_counter() - t0) * 1e6
    ours = np.round(table.boundaries[1:], 5).tolist()
    RESULTS["segments_table"] = {
        "ours": ours, "paper": seeds.PAPER_TABLE_I,
        "n_segments": table.n_segments,
        "max_rel_dev": float(np.max(np.abs(
            (np.array(ours) - np.array(seeds.PAPER_TABLE_I))
            / np.array(seeds.PAPER_TABLE_I)))),
    }
    print(f"segments_table,{us:.1f},n_segments={table.n_segments}"
          f";b0={ours[0]};paper_b0={seeds.PAPER_TABLE_I[0]}")


def bench_taylor_iters():
    """Paper §3 iteration-count claims + measured error vs n."""
    from repro.core import seeds, taylor
    import math

    rows = {}
    rows["single_segment_iters"] = seeds.iterations_required(1, 2, 53)   # paper: 17
    rows["two_segment_iters"] = max(
        seeds.iterations_required(1, math.sqrt(2), 53),
        seeds.iterations_required(math.sqrt(2), 2, 53))                  # paper: 15
    table = seeds.compute_segments(5, 53)
    rng = np.random.default_rng(0)
    x = rng.uniform(1, 2, 200_000)
    err_by_n = {}
    for n in range(0, 6):
        t0 = time.perf_counter()
        r = taylor.reciprocal_np(x, table, n_iters=n, schedule="paper")
        us = (time.perf_counter() - t0) * 1e6
        err = float(np.max(np.abs(r * x - 1)))
        err_by_n[n] = {"max_err": err, "bound": table.max_error_bound(n),
                       "bits": -np.log2(err) if err > 0 else 60}
        print(f"taylor_n{n},{us:.1f},max_err={err:.3e};bits={err_by_n[n]['bits']:.1f}")
    RESULTS["taylor_iters"] = {**rows, "err_by_n": err_by_n}
    print(f"taylor_iters,0,single_seg={rows['single_segment_iters']}(paper=17);"
          f"two_seg={rows['two_segment_iters']}(paper=15;eq17_gives_10)")


def bench_ilm_accuracy():
    """ILM error vs iterations (paper §4 accuracy/iterations trade)."""
    from repro.core import ilm

    rng = np.random.default_rng(1)
    a = rng.integers(1, 2**16, 100_000).astype(np.uint64)
    b = rng.integers(1, 2**16, 100_000).astype(np.uint64)
    exact = a * b
    rows = {}
    for iters in (1, 2, 3, 4, 6, 8, 16):
        t0 = time.perf_counter()
        p = ilm.ilm_mul_np(a, b, iters)
        us = (time.perf_counter() - t0) * 1e6
        rel = (exact - p).astype(np.float64) / exact.astype(np.float64)
        rows[iters] = {"max_rel": float(rel.max()),
                       "mean_rel": float(rel.mean()),
                       "exact_frac": float(np.mean(p == exact))}
        print(f"ilm_iter{iters},{us:.1f},max_rel={rel.max():.2e};"
              f"exact_frac={rows[iters]['exact_frac']:.3f}")
    RESULTS["ilm_accuracy"] = rows


def bench_powering_hw():
    """Paper §5 <50% hardware claim + §6 schedule op counts (both schedules)."""
    from repro.core import powering

    hw = powering.hw_cost()
    rows = {"area_ratio": hw["area_ratio"], "unit_ratio": hw["unit_ratio"],
            "op_counts": {}}
    for n in (3, 5, 7, 9, 17):
        rows["op_counts"][n] = {
            "paper": powering.op_counts(n, "paper"),
            "factored": powering.op_counts(n, "factored"),
        }
    RESULTS["powering_hw"] = rows
    print(f"powering_hw,0,area_ratio={hw['area_ratio']:.3f}(<0.5);"
          f"n5_paper={rows['op_counts'][5]['paper']};"
          f"n5_factored={rows['op_counts'][5]['factored']}")


def bench_kernel_throughput():
    """CPU-proxy kernel timings: tsdiv/softmax/rmsnorm vs XLA-native.

    Absolute numbers are CPU-interpret proxies; the TPU claim rides on the
    dry-run roofline (§Roofline), not these timings. jnp-mode (lowered FMA
    chains) runs compiled and IS a fair CPU comparison."""
    import jax
    import jax.numpy as jnp
    from repro.core import taylor
    from repro.core.seeds import compute_segments

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.uniform(0.01, 100, (1024, 1024)).astype(np.float32))
    t24 = compute_segments(2, 24)

    f_exact = jax.jit(lambda v: 1.0 / v)
    f_taylor = jax.jit(lambda v: taylor.reciprocal(v, t24))
    f_taylor_paper = jax.jit(lambda v: taylor.reciprocal(v, t24, schedule="paper"))
    us_e = _time_us(f_exact, x)
    us_t = _time_us(f_taylor, x)
    us_p = _time_us(f_taylor_paper, x)
    print(f"recip_xla,{us_e:.1f},1Melem")
    print(f"recip_taylor_factored,{us_t:.1f},ratio={us_t/us_e:.2f}x")
    print(f"recip_taylor_paper,{us_p:.1f},ratio={us_p/us_e:.2f}x")

    sm_exact = jax.jit(lambda v: jax.nn.softmax(v, -1))
    from repro.core.division_modes import DivisionConfig, softmax as dmsoft
    sm_t = jax.jit(lambda v: dmsoft(v, -1, DivisionConfig(mode="taylor")))
    us_se = _time_us(sm_exact, x)
    us_st = _time_us(sm_t, x)
    print(f"softmax_xla,{us_se:.1f},1Melem")
    print(f"softmax_taylor,{us_st:.1f},ratio={us_st/us_se:.2f}x")
    RESULTS["kernel_throughput"] = {
        "recip_xla_us": us_e, "recip_taylor_us": us_t,
        "recip_taylor_paper_us": us_p,
        "softmax_xla_us": us_se, "softmax_taylor_us": us_st,
    }


def bench_ulp_accuracy():
    """Conformance grid: delivered ULP accuracy per (mode x schedule x n x dtype).

    The machine-readable twin is `python -m repro.eval.conformance --json`;
    this row format keeps it greppable next to the perf numbers."""
    from repro.eval import conformance

    report = conformance.run_conformance(quick=True)
    for c in report["cells"]:
        o = c["overall"]
        name = f"ulp_{c['op']}_{c['mode']}_{c['schedule']}_n{c['n_iters']}_{c['dtype']}"
        print(f"{name},{c['seconds'] * 1e6:.0f},max_ulp={o['max_ulp']:.3f};"
              f"mean_ulp={o['mean_ulp']:.4f};edge_fail={c['edge_failures']}")
    RESULTS["ulp_accuracy"] = report


def bench_rsqrt():
    """op=rsqrt: wall-clock vs lax.rsqrt + delivered max ULP per policy.

    The compensated-final-Newton rsqrt is the divide-free Givens-QR
    formulation's datapath; this row records both its cost next to the
    native op and its accuracy on the paired odd/even-exponent sweep
    (machine-readable twin: the op=rsqrt cells of the conformance grid).
    """
    import jax
    import jax.numpy as jnp
    from repro.core.division_modes import DivisionConfig, rsqrt as dmrsqrt
    from repro.eval import ulp

    n = 1 << 17 if QUICK else 1 << 20
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)) + 0.01
    f_exact = jax.jit(jax.lax.rsqrt)
    f_taylor = jax.jit(lambda v: dmrsqrt(v, DivisionConfig(mode="taylor")))
    us_e = _time_us(f_exact, x)
    us_t = _time_us(f_taylor, x)
    print(f"rsqrt_xla,{us_e:.1f},{n}elem")
    print(f"rsqrt_taylor,{us_t:.1f},ratio={us_t/us_e:.2f}x")
    rows = {"rsqrt_xla_us": us_e, "rsqrt_taylor_us": us_t, "n": n}
    sweep = np.concatenate([np.abs(ulp.sweep_logspace(4096, "float32", 5)),
                            ulp.sweep_exponent_parity(2048, "float32", 6),
                            ulp.sweep_rsqrt_mantissa(4096, "float32", 7)])
    exact = 1.0 / np.sqrt(sweep.astype(np.float64))
    mask = ulp.oracle_mask(exact) & ulp.oracle_mask(sweep.astype(np.float64))
    for policy in ("gradual", "ftz"):
        cfgp = DivisionConfig(mode="taylor", underflow=policy)
        r = np.asarray(dmrsqrt(jnp.asarray(sweep), cfgp))
        mx = float(ulp.ulp_error(r, exact, where=mask).max())
        rows[f"max_ulp_{policy}"] = mx
        print(f"rsqrt_taylor_{policy},0,max_ulp={mx:.3f}")
    RESULTS["rsqrt"] = rows


def bench_e2e_softdiv():
    """End-to-end: smoke LM forward under exact vs taylor vs ilm division."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.core.division_modes import DivisionConfig
    from repro.models import forward, init_params
    from repro.train.step import loss_fn

    cfg = get_smoke_config("paper_fpdiv")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    rows = {}
    base_logits = None
    for mode in ("exact", "taylor", "ilm"):
        c = dataclasses.replace(cfg, division=DivisionConfig(mode=mode))
        f = jax.jit(lambda p, b: loss_fn(c, p, b)[0])
        us = _time_us(f, params, batch, reps=3, warmup=1)
        loss = float(f(params, batch))
        logits, _, _ = forward(c, params, tokens=toks, mode="train")
        if base_logits is None:
            base_logits = logits
            dev = 0.0
        else:
            dev = float(jnp.max(jnp.abs(logits - base_logits)))
        rows[mode] = {"loss": loss, "us": us, "logit_dev_vs_exact": dev}
        print(f"e2e_{mode},{us:.1f},loss={loss:.4f};logit_dev={dev:.2e}")
    RESULTS["e2e_softdiv"] = rows


def _workload_modes():
    """The BENCH_div mode set: taylor/factored n=2, goldschmidt, exact."""
    from repro.core.division_modes import DivisionConfig

    return [
        ("taylor_factored_n2", DivisionConfig(mode="taylor",
                                              schedule="factored", n_iters=2)),
        ("goldschmidt_n2", DivisionConfig(mode="goldschmidt", n_iters=2)),
        ("exact", DivisionConfig(mode="exact")),
    ]


def bench_workloads():
    """Division-powered workloads: K-Means + Givens QR, per mode x size.

    Wall-clock per call (jit-compiled, post-warmup) plus the workload-level
    accuracy deltas vs the XLA-exact twin on identical inits — the numbers
    that start the BENCH_div.json perf trajectory.
    """
    import jax
    import jax.numpy as jnp
    from repro.eval import workload_metrics as wm
    from repro.workloads import kmeans as km, qr as qrw

    kmeans_sizes = [(2048, 16, 8), (8192, 32, 16)]   # (N, D, K)
    qr_sizes = [(24, 16), (48, 32)]                  # (M, N)
    lloyd_iters = 8
    if QUICK:
        kmeans_sizes, qr_sizes, lloyd_iters = kmeans_sizes[:1], qr_sizes[:1], 4

    rows = {"kmeans": {}, "qr": {}}
    for n, d, k in kmeans_sizes:
        key = jax.random.PRNGKey(n)
        x = km.make_blobs(key, n, d, k)
        init = jnp.take(x, jnp.arange(k) * (n // k), axis=0)
        cell = {}
        exact_inertia = None
        for name, cfg in _workload_modes():
            f = jax.jit(lambda x, init, cfg=cfg: km.kmeans(
                x, cfg=cfg, init=init, n_iters=lloyd_iters).inertia)
            us, out = _time_us(f, x, init, ret_out=True)
            inertia = float(out)
            if name == "exact":
                exact_inertia = inertia
            cell[name] = {"us": us, "inertia": inertia}
            print(f"kmeans_{name}_n{n}d{d}k{k},{us:.1f},inertia={inertia:.6f}")
        for name in cell:
            cell[name]["inertia_delta_vs_exact"] = wm.relative_delta(
                cell[name]["inertia"], exact_inertia)
        rows["kmeans"][f"n{n}_d{d}_k{k}"] = cell
    for m, n in qr_sizes:
        a = jax.random.normal(jax.random.PRNGKey(m * n), (m, n), jnp.float32)
        cell = {}
        for name, cfg in _workload_modes():
            for via in ("div", "rsqrt"):
                f = jax.jit(lambda a, cfg=cfg, via=via: qrw.qr_givens(
                    a, cfg, via=via))
                us, (q, r) = _time_us(f, a, ret_out=True)
                res = wm.qr_residuals(q, r, a)
                cell[f"{name}_{via}"] = {"us": us, **res}
                print(f"qr_{name}_{via}_{m}x{n},{us:.1f},"
                      f"orth={res['orthogonality']:.2e};"
                      f"recon={res['reconstruction']:.2e}")
        rows["qr"][f"{m}x{n}"] = cell
    RESULTS["workloads"] = rows
    _write_bench_div()


def bench_tiled_divide():
    """Tiled fused divide kernel vs jnp.divide vs jnp-mode Taylor, per shape.

    Shapes include non-multiples of the (8, 128) tile so the ragged-last-tile
    path is what gets timed. Kernel wall-clock is interpret-mode off-TPU —
    a functional proxy (meta.pallas_interpret records this).
    """
    import jax
    import jax.numpy as jnp
    from repro.core import taylor
    from repro.core.seeds import compute_segments
    from repro.kernels import ops as kops

    shapes = [(512, 512), (513, 259), (1024, 1024)]
    if QUICK:
        shapes = [(513, 259)]
    t24 = compute_segments(2, 24)
    rng = np.random.default_rng(7)
    rows = {}
    for shape in shapes:
        a = jnp.asarray(np.ldexp(rng.uniform(1, 2, shape),
                                 rng.integers(-60, 60, shape)).astype(np.float32))
        b = jnp.asarray(np.ldexp(rng.uniform(1, 2, shape),
                                 rng.integers(-60, 60, shape)).astype(np.float32))
        f_xla = jax.jit(jnp.divide)
        f_jnp = jax.jit(lambda a, b: taylor.divide(a, b, t24))
        f_kern = jax.jit(lambda a, b: kops.tsdiv_divide(a, b))
        us_x = _time_us(f_xla, a, b)
        us_j = _time_us(f_jnp, a, b)
        us_k, out_k = _time_us(f_kern, a, b, ret_out=True)
        ref = np.asarray(a, np.float64) / np.asarray(b, np.float64)
        err = np.abs(np.asarray(out_k, np.float64) - ref)
        finite = np.isfinite(ref) & (np.abs(ref) >= 2.0 ** -126) \
            & (np.abs(ref) <= np.finfo(np.float32).max)
        max_rel = float(np.max(err[finite] / np.abs(ref[finite])))
        name = f"{shape[0]}x{shape[1]}"
        rows[name] = {"xla_us": us_x, "taylor_jnp_us": us_j,
                      "tiled_kernel_us": us_k, "kernel_max_rel_err": max_rel,
                      "ragged": shape[0] % 8 != 0 or shape[1] % 128 != 0}
        print(f"tiled_divide_{name},{us_k:.1f},xla={us_x:.1f}us;"
              f"jnp_taylor={us_j:.1f}us;max_rel={max_rel:.2e};"
              f"ragged={rows[name]['ragged']}")
    RESULTS["tiled_divide"] = rows
    _write_bench_div()


def bench_consumers():
    """Normalization consumers through the unit: softmax / rmsnorm /
    flash-attention x division modes x two shapes.

    Wall-clock per call (jit-compiled, post-warmup) plus the consumer-tier
    accuracy metrics (row-sum ULP-equivalents and vs-exact-twin integer ULP
    for the norms, max |dev| vs the exact twin for attention) — merged into
    BENCH_div.json as the ``consumers`` section. The Pallas rows run
    interpret-mode off-TPU (meta.pallas_interpret): functional proxies.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.division_modes import (DivisionConfig, EXACT, attention,
                                           rmsnorm, softmax)
    from repro.eval import consumers as cons

    norm_shapes = [(256, 512), (64, 2048)]
    attn_shapes = [(4, 128, 64), (2, 256, 64)]     # (batch*heads, S, hd)
    if QUICK:
        norm_shapes, attn_shapes = norm_shapes[:1], attn_shapes[:1]
    modes = _workload_modes() + [
        ("taylor_pallas_n2", DivisionConfig(mode="taylor_pallas", n_iters=2)),
        ("goldschmidt_pallas_n2",
         DivisionConfig(mode="goldschmidt_pallas", n_iters=2)),
    ]
    rows = {"softmax": {}, "rmsnorm": {}, "flash_attention": {}}
    for shape in norm_shapes:
        rng = np.random.default_rng(shape[0] * shape[1])
        x = jnp.asarray(rng.normal(0, 4, shape).astype(np.float32))
        w = jnp.asarray(cons.rmsnorm_weight(shape[1], seed=7))
        sm_exact = np.asarray(softmax(x, -1, EXACT))
        rn_exact = np.asarray(rmsnorm(x, w, EXACT))
        oracle_sm = cons.softmax_oracle(np.asarray(x, np.float64))
        oracle_rn = cons.rmsnorm_oracle(np.asarray(x, np.float64),
                                        np.asarray(w, np.float64))
        sm_cell, rn_cell = {}, {}
        for name, cfg in modes:
            f_sm = jax.jit(lambda v, cfg=cfg: softmax(v, -1, cfg))
            us, out = _time_us(f_sm, x, ret_out=True)
            out = np.asarray(out)
            sm_cell[name] = {
                "us": us,
                "row_sum_max_ulp1": float(cons.row_sum_ulp1(out).max()),
                "vs_exact_max_ulp": cons.vs_exact_int_ulp(out, sm_exact,
                                                          oracle_sm),
            }
            f_rn = jax.jit(lambda v, w, cfg=cfg: rmsnorm(v, w, cfg))
            us, out = _time_us(f_rn, x, w, ret_out=True)
            rn_cell[name] = {
                "us": us,
                "vs_exact_max_ulp": cons.vs_exact_int_ulp(
                    np.asarray(out), rn_exact, oracle_rn),
            }
            print(f"softmax_{name}_{shape[0]}x{shape[1]},"
                  f"{sm_cell[name]['us']:.1f},"
                  f"row_sum={sm_cell[name]['row_sum_max_ulp1']:.2f}ulp;"
                  f"vs_exact={sm_cell[name]['vs_exact_max_ulp']}ulp")
            print(f"rmsnorm_{name}_{shape[0]}x{shape[1]},"
                  f"{rn_cell[name]['us']:.1f},"
                  f"vs_exact={rn_cell[name]['vs_exact_max_ulp']}ulp")
        key = f"{shape[0]}x{shape[1]}"
        rows["softmax"][key] = sm_cell
        rows["rmsnorm"][key] = rn_cell
    for bh, s, hd in attn_shapes:
        rng = np.random.default_rng(bh * s)
        q = jnp.asarray(rng.normal(size=(bh, s, hd)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(bh, s, hd)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(bh, s, hd)).astype(np.float32))
        exact = np.asarray(attention(q, k, v, EXACT))
        cell = {}
        for name, cfg in modes:
            f = jax.jit(lambda q, k, v, cfg=cfg: attention(q, k, v, cfg))
            us, out = _time_us(f, q, k, v, reps=3, warmup=1, ret_out=True)
            dev = float(np.max(np.abs(np.asarray(out) - exact)))
            cell[name] = {"us": us, "max_dev_vs_exact": dev}
            print(f"attention_{name}_{bh}x{s}x{hd},{us:.1f},"
                  f"max_dev={dev:.2e}")
        rows["flash_attention"][f"{bh}x{s}x{hd}"] = cell
    RESULTS["consumers"] = rows
    _write_bench_div()


def bench_serving():
    """Serving trajectory: prefill ms + decode tokens/sec through the engine.

    paper_fpdiv smoke LM, batch x division mode (taylor factored n=2,
    goldschmidt, taylor_pallas, exact). Prefill and decode are the engine's
    own jit'd steps (compiled-exec timings, post-warmup) over unequal-length
    prompts, so the padded-prompt masking path is what gets timed — merged
    into BENCH_div.json as the ``serving`` section. The taylor_pallas rows
    run interpret-mode off-TPU (meta.pallas_interpret): functional proxies.
    """
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.core.division_modes import DivisionConfig
    from repro.models import init_params
    from repro.serving import ServingEngine, pad_cache_to

    cfg0 = get_smoke_config("paper_fpdiv")
    params = init_params(cfg0, jax.random.PRNGKey(0))
    prompt_len = 16 if QUICK else 32
    max_new = 8 if QUICK else 16
    batches = [1, 8]
    modes = _workload_modes() + [
        ("taylor_pallas_n2", DivisionConfig(mode="taylor_pallas", n_iters=2)),
    ]
    reps, warmup = (2, 1) if QUICK else (5, 2)
    rows = {}
    for B in batches:
        # unequal lengths exercise the padded-prompt masking path
        lens = [max(4, prompt_len - 3 * i) for i in range(B)]
        prompts = [list(range(1, L + 1)) for L in lens]
        cell = {}
        for name, div in modes:
            eng = ServingEngine(cfg0, params, division=div,
                                max_len=prompt_len + max_new + 16)
            pad_to = eng._pad_to(max(lens))
            toks = np.zeros((B, pad_to), np.int32)
            for i, p in enumerate(prompts):
                toks[i, :len(p)] = p
            toks = jnp.asarray(toks)
            lengths = jnp.asarray(lens, jnp.int32)
            us_pre = _time_us(lambda: eng._prefill_tok(toks, lengths)[0],
                              reps=reps, warmup=warmup)
            last, cache = eng._prefill_tok(toks, lengths)
            cache = pad_cache_to(cache, pad_to, eng.max_len, eng.cfg)
            tok = jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
            us_dec = _time_us(lambda: eng._decode(cache, tok, lengths)[0],
                              reps=reps * max_new, warmup=warmup)
            cell[name] = {
                "prefill_ms": us_pre / 1e3,
                "decode_us_per_step": us_dec,
                "decode_tok_s": B / (us_dec * 1e-6),
            }
            print(f"serving_{name}_b{B},{us_dec:.1f},"
                  f"prefill={us_pre / 1e3:.2f}ms;"
                  f"tok_s={cell[name]['decode_tok_s']:.1f}")
        rows[f"batch{B}"] = cell
    rows["config"] = {"arch": cfg0.name, "prompt_len": prompt_len,
                      "prompt_lens": "unequal (padded-prompt path)",
                      "max_new": max_new}
    RESULTS["serving"] = rows
    _write_bench_div()


def bench_sharding():
    """Mesh scaling: a 1-device mesh against an all-device mesh, tiled
    divide + K-Means, in this process (``repro.sharding.scaling``).

    On the 1-device mesh the mesh-aware dispatch falls back to the
    single-device paths, so the pair is a true sharded-vs-unsharded
    comparison on the devices this process has. On the CPU backend, force
    virtual devices before the run
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``); they share
    one host CPU, so the speedup column then measures dispatch overhead and
    XLA's intra-host parallelism, not a fleet.
    """
    from repro.sharding import scaling

    points = 200_000 if QUICK else 1_000_000
    rows_, cols = (1024, 256) if QUICK else (2048, 384)
    rows = scaling.measure_pair(points=points, rows=rows_, cols=cols,
                                reps=2 if QUICK else 3)
    for key, data in rows.items():
        if key == "speedup":
            continue
        n_dev = data["devices"]
        print(f"sharding_divide_d{n_dev},{data['tiled_divide_us']:.1f},"
              f"shape={rows_}x{cols}")
        print(f"sharding_kmeans_d{n_dev},{data['kmeans_us']:.1f},"
              f"points={points};inertia={data['kmeans']['inertia']:.6f}")
    sp = rows["speedup"]
    print(f"sharding_speedup,0,devices={sp['devices']};"
          f"divide={sp['tiled_divide']:.2f}x;kmeans={sp['kmeans']:.2f}x")
    RESULTS["sharding"] = rows
    _write_bench_div()


BENCHES = {
    "segments_table": bench_segments_table,
    "taylor_iters": bench_taylor_iters,
    "ilm_accuracy": bench_ilm_accuracy,
    "powering_hw": bench_powering_hw,
    "kernel_throughput": bench_kernel_throughput,
    "ulp_accuracy": bench_ulp_accuracy,
    "rsqrt": bench_rsqrt,
    "e2e_softdiv": bench_e2e_softdiv,
    "workloads": bench_workloads,
    "tiled_divide": bench_tiled_divide,
    "consumers": bench_consumers,
    "serving": bench_serving,
    "sharding": bench_sharding,
}


def main() -> None:
    global QUICK
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizing: one problem size per workload")
    args, _ = ap.parse_known_args()
    QUICK = args.quick
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    names = [args.only] if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()
    out = os.path.join(os.path.dirname(__file__), "results.json")
    with open(out, "w") as f:
        json.dump(RESULTS, f, indent=1, default=str)
    print(f"# wrote {out}")


if __name__ == "__main__":
    main()
