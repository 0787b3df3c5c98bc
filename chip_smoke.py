"""Smoke run of the division unit's main path on a TPU.

    python chip_smoke.py             # phases (a)-(c) on one chip
    python chip_smoke.py --chips 4   # phase (d) only: the sharded path, 4 chips

(a) unit: ``kernels.ops`` reciprocal, divide and rsqrt kernels on (4096, 4096)
    f32 sweeps, against the f64 oracle at the paper's eq. 17 bound (2 max
    ulp); whether each matches the committed CPU golden store bit for bit is
    printed, not gated.
(b) serving: ``ServingEngine`` on tinyllama_1_1b at full width with seeded
    random weights. ``generate_batch`` on 4 prompts of 128-512 tokens, 32 new
    tokens, in modes taylor_pallas and taylor; each mode's prefill logits
    against the exact engine on the same weights, within PREFILL_REL_BOUND.
(c) K-Means: 10^6 points (dim 8, k 8) in taylor_pallas against exact, gated
    on the inertia delta and the assignment agreement.
(d) ``kmeans_sharded`` and the shard_map-dispatched tiled divide on a mesh
    over every device, against the same calls on one device of this
    process: bit-identical or the phase fails.

Each phase prints one JSON line with its result, compile and run seconds and
the device's ``peak_bytes_in_use`` so far. The last line is
``{"ok": ..., "device": {"platform", "kind", "count"}}``. The script exits 1
when JAX finds no TPU (printing no result), when a Pallas phase would run in
interpret mode or compiles without its kernel (``tpu_custom_call``), when a
taylor_pallas call falls back to the jnp twin, and when any phase fails.
Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Phase (b): max |logits - exact logits| / max |exact logits| at the last
# prompt position. The CPU rehearsal (tinyllama's 22 layers at d_model 512,
# bf16 weights, prompts of 32-256 tokens) measured 1.5e-2 to 2.1e-2 for
# taylor and taylor_pallas; the bound leaves a factor of about 3.
PREFILL_REL_BOUND = 6e-2
# Phase (a): the paper's eq. 17 accuracy target for f32 at n=2, 24 bits.
UNIT_MAX_ULP = 2.0
# Phase (c): the K-Means gates of tests/test_workloads.py.
KMEANS_INERTIA_DELTA = 1e-4
KMEANS_AGREEMENT = 0.99


class PhaseFailed(Exception):
    pass


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def compile_checked(fn, *args, require_kernel: bool):
    """AOT-compile ``fn`` (jitted here unless it already is) for ``args``;
    returns (compiled, seconds).

    With ``require_kernel`` the compiled program must hold a Mosaic kernel.
    A later call of an already-jitted ``fn`` reuses this executable.
    """
    import jax

    t0 = time.perf_counter()
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    secs = time.perf_counter() - t0
    if require_kernel and "tpu_custom_call" not in compiled.as_text():
        raise PhaseFailed(f"{getattr(fn, '__name__', fn)}: no tpu_custom_call "
                          "in the compiled program")
    return compiled, secs


def timed(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


class FallbackSpy:
    """Records every operand the Pallas-mode guard turns away.

    ``division_modes`` sends an operand to the jnp twin when
    ``kernels.ops.pallas_applicable`` refuses it; inside this context each
    such refusal (at trace time) is kept in ``refused``.
    """

    def __init__(self):
        from repro.kernels import ops

        self.ops = ops
        self.real = ops.pallas_applicable
        self.refused = []

    def __enter__(self):
        def spy(x):
            ok = self.real(x)
            if not ok:
                self.refused.append((tuple(x.shape), str(x.dtype)))
            return ok

        self.ops.pallas_applicable = spy
        return self

    def __exit__(self, *exc):
        self.ops.pallas_applicable = self.real
        return False


# ------------------------------------------------------------------ phase a

def unit_operands(shape, seed: int):
    """Seeded f32 sweeps of ``shape``: half log-uniform over the normal
    range, half mantissa-dense. Returns (x, a, b, r): recip operands,
    divide numerators and denominators, positive rsqrt operands."""
    import numpy as np

    from repro.eval import ulp

    n = shape[0] * shape[1]
    x = np.concatenate([ulp.sweep_logspace(n // 2, "float32", seed),
                        ulp.sweep_mantissa(n // 4, "float32", seed + 1)])
    a = ulp.sweep_logspace(x.size, "float32", seed + 2)
    r = np.concatenate([
        np.abs(ulp.sweep_logspace(n // 2, "float32", seed + 3)),
        ulp.sweep_rsqrt_mantissa(n // 4, "float32", seed + 4)])
    return tuple(v.reshape(shape) for v in (x, a, x, r))


def golden_match(ops):
    """Kernel outputs on the golden stores' inputs, bit for bit."""
    import numpy as np
    import jax.numpy as jnp

    from repro.eval import golden, ulp

    out = {}
    with np.load(golden.GOLDEN_PATH) as z:
        x, want = z["inputs"], z["out:recip/taylor_pallas/factored/n2p24"]
    got = np.asarray(ops.tsdiv_recip(jnp.asarray(x)))
    out["recip"] = int((ulp.ulp_diff(got, want.view(np.float32)) > 0).sum())
    with np.load(golden.DIVIDE_PATH) as z:
        a, b, want = z["a"], z["b"], z["out:div/taylor_pallas/factored/n2p24"]
    got = np.asarray(ops.tsdiv_divide(jnp.asarray(a), jnp.asarray(b)))
    out["divide"] = int((ulp.ulp_diff(got, want.view(np.float32)) > 0).sum())
    # The fused rsqrt kernel is pinned to the underflow="ftz" jnp twin.
    with np.load(golden.RSQRT_PATH) as z:
        x, want = z["inputs"], z["out:rsqrt/taylor/newton2/ftz"]
    got = np.asarray(ops.tsdiv_rsqrt(jnp.asarray(x)))
    out["rsqrt"] = int((ulp.ulp_diff(got, want.view(np.float32)) > 0).sum())
    return {k: {"bit_identical": v == 0, "n_mismatch": v}
            for k, v in out.items()}


def phase_unit(shape=(4096, 4096), seed: int = 0, require_kernel=True):
    import jax
    import numpy as np

    from repro.eval import ulp
    from repro.kernels import ops

    x, a, b, r = unit_operands(shape, seed)
    x64, a64, b64, r64 = (v.astype(np.float64) for v in (x, a, b, r))
    with np.errstate(divide="ignore", invalid="ignore"):
        cases = {
            "recip": (ops.tsdiv_recip, (x,), 1.0 / x64,
                      ulp.oracle_mask(x64)),
            "divide": (ops.tsdiv_divide, (a, b), a64 / b64,
                       ulp.oracle_mask(a64) & ulp.oracle_mask(b64)),
            "rsqrt": (ops.tsdiv_rsqrt, (r,), 1.0 / np.sqrt(r64),
                      ulp.oracle_mask(r64)),
        }
    golden = golden_match(ops)
    failed = []
    for name, (fn, args, exact, operand_ok) in cases.items():
        dev = [jax.device_put(v) for v in args]
        compiled, c_s = compile_checked(fn, *dev,
                                        require_kernel=require_kernel)
        out, _ = timed(compiled, *dev)
        out, run_s = timed(compiled, *dev)
        mask = operand_ok & ulp.oracle_mask(exact) & ulp.cliff_guard(exact)
        errs = ulp.ulp_error(np.asarray(out), exact, where=mask)
        stats = ulp.summarize(errs, mask)
        ok = stats["max_ulp"] <= UNIT_MAX_ULP
        if not ok:
            failed.append(name)
        emit({"phase": "a_unit", "op": name, "ok": ok,
              "shape": list(shape), "max_ulp": stats["max_ulp"],
              "mean_ulp": stats["mean_ulp"], "n_measured": stats["n"],
              "bound_max_ulp": UNIT_MAX_ULP, "golden": golden[name],
              "compile_s": c_s, "run_s": run_s,
              "peak_bytes_in_use": peak_bytes()})
    if failed:
        raise PhaseFailed(f"unit ops over {UNIT_MAX_ULP} ulp: {failed}")


# ------------------------------------------------------------------ phase b

def serving_prompts(vocab: int, lens, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, int(n)).tolist() for n in lens]


def phase_serving(cfg=None, prompt_lens=(512, 389, 211, 128),
                  max_new: int = 32, seed: int = 0, require_kernel=True):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import init_params, make_cache
    from repro.serving import ServingEngine

    cfg = cfg or get_config("tinyllama_1_1b")
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0
    prompts = serving_prompts(cfg.vocab, prompt_lens, seed)
    max_len = max(prompt_lens) + max_new
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)

    def engine(mode):
        return ServingEngine(
            cfg, params, max_len=max_len,
            division=dataclasses.replace(cfg.division, mode=mode))

    exact = engine("exact")
    ref, ref_s = timed(exact.prefill_logits, prompts)
    ref = np.asarray(ref, np.float32)
    emit({"phase": "b_serving", "mode": "exact", "ok": True,
          "config": cfg.name, "layers": cfg.n_layers,
          "d_model": cfg.d_model, "param_init_s": init_s,
          "prompt_lens": list(prompt_lens),
          "first_prefill_s": ref_s, "peak_bytes_in_use": peak_bytes()})
    failed = []
    for mode in ("taylor_pallas", "taylor"):
        eng = engine(mode)
        toks = eng._pad_prompts(prompts, eng._pad_to(max(prompt_lens)))
        kernel = require_kernel and mode == "taylor_pallas"
        with FallbackSpy() as spy:
            _, c_pre = compile_checked(eng._prefill_tok_fn, params, toks,
                                       lens, require_kernel=kernel)
            _, c_dec = compile_checked(
                eng._decode_fn, params,
                make_cache(eng.cfg, len(prompts), max_len, abstract=True),
                jax.ShapeDtypeStruct((len(prompts), 1), jnp.int32),
                jax.ShapeDtypeStruct((len(prompts),), jnp.int32),
                require_kernel=kernel)
            t0 = time.perf_counter()
            eng.generate_batch(prompts, max_new=max_new)
            first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        outs = eng.generate_batch(prompts, max_new=max_new)
        run_s = time.perf_counter() - t0
        logits = np.asarray(eng.prefill_logits(prompts), np.float32)
        rel = float(np.max(np.abs(logits - ref)) / np.max(np.abs(ref)))
        argmax_agree = float(np.mean(logits.argmax(-1) == ref.argmax(-1)))
        tokens_ok = all(len(o) == max_new and all(0 <= t < cfg.vocab
                                                  for t in o) for o in outs)
        finite = bool(np.all(np.isfinite(logits)))
        ok = (rel <= PREFILL_REL_BOUND and tokens_ok and finite
              and not spy.refused)
        if not ok:
            failed.append(mode)
        emit({"phase": "b_serving", "mode": mode, "ok": ok,
              "prefill_rel_err_vs_exact": rel,
              "bound": PREFILL_REL_BOUND,
              "argmax_agreement_vs_exact": argmax_agree,
              "logits_finite": finite, "tokens_in_vocab": tokens_ok,
              "new_tokens": [len(o) for o in outs],
              "jnp_fallbacks": spy.refused,
              "prefill_compile_s": c_pre, "decode_compile_s": c_dec,
              "first_generate_s": first_s, "run_s": run_s,
              "peak_bytes_in_use": peak_bytes()})
    if failed:
        raise PhaseFailed(f"serving modes failed: {failed}")


# ------------------------------------------------------------------ phase c

def phase_kmeans(n_points: int = 1_000_000, dim: int = 8, k: int = 8,
                 n_iters: int = 10, seed: int = 0, require_kernel=True):
    import jax
    import jax.numpy as jnp

    from repro.core import division_modes as dm
    from repro.eval import workload_metrics as wm
    from repro.workloads import kmeans as km

    x = km.make_blobs(jax.random.PRNGKey(seed), n_points, dim, k)
    init = jnp.take(x, jnp.arange(k) * (n_points // k), axis=0)
    res = {}
    for mode in ("exact", "taylor_pallas"):
        cfg = dm.DivisionConfig(mode=mode)

        def run(xx, ii, cfg=cfg):
            r = km.kmeans(xx, cfg=cfg, n_iters=n_iters, init=ii)
            return r.centroids, r.assignments, r.inertia

        with FallbackSpy() as spy:
            compiled, c_s = compile_checked(
                run, x, init,
                require_kernel=require_kernel and mode != "exact")
        out, _ = timed(compiled, x, init)
        out, run_s = timed(compiled, x, init)
        res[mode] = out
        emit({"phase": "c_kmeans", "mode": mode, "ok": not spy.refused,
              "points": n_points, "dim": dim, "k": k, "iters": n_iters,
              "inertia": float(out[2]), "jnp_fallbacks": spy.refused,
              "compile_s": c_s, "run_s": run_s,
              "peak_bytes_in_use": peak_bytes()})
        if spy.refused:
            raise PhaseFailed(f"kmeans {mode} fell back: {spy.refused}")
    delta = wm.relative_delta(res["taylor_pallas"][2], res["exact"][2])
    agree = float(jnp.mean((res["taylor_pallas"][1]
                            == res["exact"][1]).astype(jnp.float32)))
    ok = delta <= KMEANS_INERTIA_DELTA and agree >= KMEANS_AGREEMENT
    emit({"phase": "c_kmeans", "mode": "taylor_pallas_vs_exact", "ok": ok,
          "inertia_rel_delta": delta, "bound_delta": KMEANS_INERTIA_DELTA,
          "assignment_agreement": agree, "bound_agreement": KMEANS_AGREEMENT})
    if not ok:
        raise PhaseFailed(f"kmeans delta {delta} / agreement {agree}")


# ------------------------------------------------------------------ phase d

def bits(v):
    """Host copy of ``v`` compared bit for bit (floats as their bits)."""
    import numpy as np

    v = np.asarray(v)
    return v.view(np.uint32) if v.dtype == np.float32 else v


def phase_mesh(n_points: int = 1_000_000, shape=(4096, 4096), dim: int = 8,
               k: int = 8, n_iters: int = 10, seed: int = 0,
               require_kernel=True):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import division_modes as dm
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import rules as shr
    from repro.workloads import kmeans as km

    mesh = make_host_mesh()
    cfg = dm.DivisionConfig(mode="taylor_pallas")
    _, a, b, _ = unit_operands(shape, seed)
    x = km.make_blobs(jax.random.PRNGKey(seed), n_points, dim, k)
    init = jnp.take(x, jnp.arange(k) * (n_points // k), axis=0)

    def kmeans_one(xx, ii):
        r = km.kmeans(xx, cfg=cfg, n_iters=n_iters, init=ii)
        return r.centroids, r.assignments, r.inertia

    def kmeans_mesh(xx, ii):
        with shr.use_mesh(mesh):
            r = km.kmeans_sharded(xx, cfg=cfg, n_iters=n_iters, init=ii)
        return r.centroids, r.assignments, r.inertia

    def divide_mesh(u, v):
        with shr.use_mesh(mesh):
            return ops.tsdiv_divide(u, v)

    rows = shr.data_sharding(mesh, 2, batch_size=shape[0])
    pts = shr.data_sharding(mesh, 2, batch_size=n_points)
    one = jax.devices()[0]
    cases = {
        "tiled_divide": (ops.tsdiv_divide,
                         [jax.device_put(v, one) for v in (a, b)],
                         divide_mesh,
                         [jax.device_put(v, rows) for v in (a, b)]),
        "kmeans": (kmeans_one, [x, init], kmeans_mesh,
                   [jax.device_put(x, pts), init]),
    }
    failed = []
    for name, (f1, args1, fn, argsn) in cases.items():
        c1, c1_s = compile_checked(f1, *args1, require_kernel=require_kernel)
        cn, cn_s = compile_checked(fn, *argsn, require_kernel=require_kernel)
        o1, _ = timed(c1, *args1)
        o1, run1_s = timed(c1, *args1)
        on, _ = timed(cn, *argsn)
        on, runn_s = timed(cn, *argsn)
        o1 = o1 if isinstance(o1, tuple) else (o1,)
        on = on if isinstance(on, tuple) else (on,)
        # Gated: the divide output, and K-Means centroids and assignments.
        # The K-Means inertia is a psum of per-device partials, a different
        # order than the one-device sum by design: reported, not gated.
        gated = o1 if name == "tiled_divide" else o1[:2]
        same = [bool(np.array_equal(bits(u), bits(v)))
                for u, v in zip(gated, on)]
        ok = all(same)
        if not ok:
            failed.append(name)
        line = {"phase": "d_mesh", "case": name, "ok": ok,
                "devices": mesh.size, "mesh": dict(mesh.shape),
                "bit_identical": same,
                "compile_s_one": c1_s, "compile_s_mesh": cn_s,
                "run_s_one": run1_s, "run_s_mesh": runn_s,
                "peak_bytes_in_use": peak_bytes()}
        if name == "kmeans":
            line["inertia_one"] = float(o1[2])
            line["inertia_mesh"] = float(on[2])
        emit(line)
    if failed:
        raise PhaseFailed(f"sharded differs from one device: {failed}")


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase (d) on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if args.chips != jax.device_count():
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{jax.device_count()} device(s)", file=sys.stderr)
        return 1

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    if ops.INTERPRET:
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    emit({"compile_cache": enable_compile_cache()})
    phases = ([phase_mesh] if args.chips == 4
              else [phase_unit, phase_serving, phase_kmeans])
    ok = True
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase(seed=args.seed)
        except Exception as e:  # noqa: BLE001  (reported; the run fails)
            ok = False
            traceback.print_exc()
            emit({"phase": phase.__name__, "ok": False,
                  "error": f"{type(e).__name__}: {e}"[:2000]})
        emit({"phase": phase.__name__, "wall_s": time.perf_counter() - t0})
    emit({"ok": ok, "device": {"platform": dev.platform,
                               "kind": dev.device_kind,
                               "count": jax.device_count()}})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
