"""Conformance runner: delivered ULP accuracy over (mode x schedule x n_iters x dtype).

Sweeps every cell of the division-mode grid against the f64 oracle on the
stratified operand corpus (eval/ulp.py) and emits a machine-readable report:

    PYTHONPATH=src python -m repro.eval.conformance            # full grid
    PYTHONPATH=src python -m repro.eval.conformance --quick    # CI-sized
    PYTHONPATH=src python -m repro.eval.conformance --json out.json

The five algorithm families on identical footing: exact (XLA), Taylor with
the paper's §6 schedule, Taylor factored, Goldschmidt (core/goldschmidt.py,
plus its fused-kernel twin), and the 16-bit ILM emulation; op in
{recip, div, rsqrt} plus the consumer tier {softmax, rmsnorm} (row
corpora and unit-isolating gates in eval/consumers.py). Masking is
underflow-policy-aware: gradual cells (the
bit-level jnp twins) measure subnormal operands and results, FTZ cells
exclude them as the flush edge class. The process exits non-zero if any
cell fails its gate (edge contract, or > 2 max ULP at the n >= 2 non-ILM
operating points), so CI can consume the run directly. Consumed by
tests/test_conformance.py (the paper's eq. 17 precision claim as a hard
gate) and benchmarks/run.py (bench_ulp_accuracy).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.division_modes import (DivisionConfig, div, recip, rsqrt,
                                       softmax, rmsnorm, effective_underflow)
from repro.core.seeds import compute_segments
from . import consumers, ulp

__all__ = ["Cell", "default_grid", "run_cell", "run_conformance",
           "format_table", "cell_gate", "main"]

# (n_iters, precision_bits) operating points: the paper's accuracy dial.
DIAL = ((1, 12), (2, 24), (3, 30))

# The eq. 17 operating point: every non-ILM cell at n >= 2 must deliver
# <= 2 max ULP (the paper's gate); n=1 @ 12-bit is the loose end of the
# dial by design and is not ULP-gated. ILM is ~12-bit by construction.
GATE_MAX_ULP = 2.0


@dataclasses.dataclass(frozen=True)
class Cell:
    """One conformance grid cell. schedule '-' = not applicable to the mode."""

    mode: str
    schedule: str = "-"
    n_iters: int = 2
    precision_bits: int = 24
    dtype: str = "float32"
    op: str = "recip"

    @property
    def key(self) -> str:
        return f"{self.op}/{self.mode}/{self.schedule}/n{self.n_iters}" \
               f"p{self.precision_bits}/{self.dtype}"

    def config(self) -> DivisionConfig:
        sched = self.schedule if self.schedule != "-" else "factored"
        return DivisionConfig(mode=self.mode, n_iters=self.n_iters,
                              precision_bits=self.precision_bits,
                              schedule=sched)


def default_grid(dtypes: Sequence[str] = ulp.DTYPES,
                 dial: Sequence = DIAL, quick: bool = False) -> List[Cell]:
    """Every (op x mode x schedule x n_iters x dtype) cell of the grid.

    op=rsqrt runs at the f32 operating point only (rsqrt's accuracy dial is
    ``rsqrt_newton``, not the series depth; taylor and goldschmidt share the
    jnp rsqrt datapath by design, and both Pallas modes share the fused
    full-edge rsqrt kernel — it has no schedule knob — so the
    goldschmidt_pallas rsqrt column is collapsed into the taylor_pallas
    cell rather than re-measuring an identical datapath). The consumer ops
    (softmax, rmsnorm) run at the (2, 24) operating point across every
    mode: their dial is gated by the vs-exact-twin and row-sum metrics, not
    the oracle ULP (see eval/consumers.py).
    """
    if quick:
        dial = [d for d in dial if d == (2, 24)] or [dial[0]]
    cells: List[Cell] = []
    for dt in dtypes:
        for op in ("recip", "div"):
            cells.append(Cell("exact", dtype=dt, op=op))
            for n, p in dial:
                for sched in ("paper", "factored"):
                    cells.append(Cell("taylor", sched, n, p, dt, op=op))
                cells.append(Cell("taylor_pallas", "factored", n, p, dt, op=op))
                cells.append(Cell("goldschmidt", "-", n, p, dt, op=op))
                cells.append(Cell("goldschmidt_pallas", "-", n, p, dt, op=op))
            # ILM carries ~12 mantissa bits by construction — one cell each.
            cells.append(Cell("ilm", "-", 2, 24, dt, op=op))
        cells.append(Cell("exact", dtype=dt, op="rsqrt"))
        for sched in ("paper", "factored"):
            cells.append(Cell("taylor", sched, 2, 24, dt, op="rsqrt"))
        cells.append(Cell("taylor_pallas", "factored", 2, 24, dt, op="rsqrt"))
        cells.append(Cell("goldschmidt", "-", 2, 24, dt, op="rsqrt"))
        cells.append(Cell("ilm", "-", 2, 24, dt, op="rsqrt"))
        for op in consumers.CONSUMER_OPS:
            cells.append(Cell("exact", dtype=dt, op=op))
            for sched in ("paper", "factored"):
                cells.append(Cell("taylor", sched, 2, 24, dt, op=op))
            cells.append(Cell("taylor_pallas", "factored", 2, 24, dt, op=op))
            cells.append(Cell("goldschmidt", "-", 2, 24, dt, op=op))
            cells.append(Cell("goldschmidt_pallas", "-", 2, 24, dt, op=op))
            cells.append(Cell("ilm", "-", 2, 24, dt, op=op))
    return cells


def _edge_failures(x64: np.ndarray, r64: np.ndarray) -> int:
    """IEEE contract on the edge corpus: +-0 -> +-inf, +-inf -> +-0, nan -> nan."""
    fails = 0
    zero = x64 == 0
    fails += int(np.sum(zero & ~(np.isinf(r64)
                                 & (np.signbit(r64) == np.signbit(x64)))))
    inf = np.isinf(x64)
    fails += int(np.sum(inf & ~((r64 == 0)
                                & (np.signbit(r64) == np.signbit(x64)))))
    nan = np.isnan(x64)
    fails += int(np.sum(nan & ~np.isnan(r64)))
    return fails


def _div_edge_failures(a64: np.ndarray, b64: np.ndarray,
                       q64: np.ndarray) -> int:
    """IEEE special-value contract for a/b on the operand-edge corpus.

    Checks only the lanes whose outcome is fixed by the operands' special
    values (zeros, infs, nans — including sign rules); finite/finite lanes
    that merely overflow or underflow are the FTZ class, judged elsewhere.
    """
    sign = np.signbit(a64) ^ np.signbit(b64)
    a_zero, b_zero = a64 == 0, b64 == 0
    a_inf, b_inf = np.isinf(a64), np.isinf(b64)
    a_nan, b_nan = np.isnan(a64), np.isnan(b64)
    finite_a = np.isfinite(a64)
    finite_b = np.isfinite(b64)
    # Subnormal operands are the FTZ class (kernels legitimately flush them
    # to zero before the special-value logic) — excluded from the sign-rule
    # lanes below; nan propagation holds regardless. f32 and bf16 share
    # emin = -126.
    tiny = np.ldexp(1.0, -126)
    subn = (((a64 != 0) & finite_a & (np.abs(a64) < tiny))
            | ((b64 != 0) & finite_b & (np.abs(b64) < tiny)))
    a_zero, b_zero = a_zero & ~subn, b_zero & ~subn
    a_inf, b_inf = a_inf & ~subn, b_inf & ~subn
    fails = 0
    # x/0 (x finite nonzero or inf) -> signed inf.
    lane = b_zero & ~a_zero & ~a_nan
    fails += int(np.sum(lane & ~(np.isinf(q64) & (np.signbit(q64) == sign))))
    # 0/y (y nonzero finite or inf) -> signed zero.
    lane = a_zero & ~b_zero & ~b_nan
    fails += int(np.sum(lane & ~((q64 == 0) & (np.signbit(q64) == sign))))
    # inf/y (y finite) -> signed inf;  x/inf (x finite) -> signed zero.
    lane = a_inf & finite_b & ~b_nan
    fails += int(np.sum(lane & ~(np.isinf(q64) & (np.signbit(q64) == sign))))
    lane = b_inf & finite_a & ~a_nan
    fails += int(np.sum(lane & ~((q64 == 0) & (np.signbit(q64) == sign))))
    # Invalid: 0/0, inf/inf, any nan operand -> nan.
    lane = (a_zero & b_zero) | (a_inf & b_inf) | a_nan | b_nan
    fails += int(np.sum(lane & ~np.isnan(q64)))
    return fails


def _rsqrt_edge_failures(x64: np.ndarray, r64: np.ndarray) -> int:
    """IEEE contract for rsqrt on the edge corpus.

    ±0 -> ±inf, +inf -> +0, x < 0 (incl. -inf) -> nan, nan -> nan.
    Subnormal-magnitude operands are policy-dependent (gradual: exact;
    FTZ: the zero class -> ±inf) and are judged by the ULP strata /
    policy tests instead.
    """
    subn = np.isfinite(x64) & (x64 != 0) & (np.abs(x64) < np.ldexp(1.0, -126))
    fails = 0
    zero = (x64 == 0) & ~subn
    fails += int(np.sum(zero & ~(np.isinf(r64)
                                 & (np.signbit(r64) == np.signbit(x64)))))
    fails += int(np.sum(np.isposinf(x64)
                        & ~((r64 == 0) & ~np.signbit(r64))))
    neg = (x64 < 0) & ~subn
    fails += int(np.sum(neg & ~np.isnan(r64)))
    fails += int(np.sum(np.isnan(x64) & ~np.isnan(r64)))
    return fails


def _softmax_edge_failures(cfg: DivisionConfig, dtype: str) -> int:
    """Masked-softmax contract on the edge rows (eval/consumers.py):

    fully-masked row -> exact zeros (never 0 * recip(0) = nan), single-
    survivor row -> probability 1 within 2 ULP-equivalents (ILM: its
    ~12-bit dial) with exact zeros elsewhere, nan row -> nan everywhere.
    """
    import jax.numpy as jnp

    p, _, _ = ulp._fmt(dtype)
    rows = consumers.softmax_edge_rows(dtype)
    out = np.asarray(softmax(jnp.asarray(rows), -1, cfg)).astype(np.float64)
    tol = 2.0 ** -10 if cfg.mode == "ilm" else 2.0 * 2.0 ** (1 - p)
    fails = int(np.sum(out[0] != 0.0))
    fails += int(not abs(out[1, 0] - 1.0) <= tol)
    fails += int(np.sum(out[1, 1:] != 0.0))
    fails += int(np.sum(~np.isnan(out[2])))
    return fails


def _rmsnorm_edge_failures(cfg: DivisionConfig, dtype: str) -> int:
    """RMSNorm edge contract: an all-zero row normalizes to exact zeros
    (0 * rsqrt(eps) * w) and a nan row propagates nan, in every mode."""
    import jax.numpy as jnp

    dt = ulp._resolve_dtype(dtype)
    d = 16
    rows = np.zeros((2, d)).astype(dt)
    rows[1, :] = 1.0
    rows[1, d // 2] = np.nan
    w = jnp.asarray(consumers.rmsnorm_weight(d))
    out = np.asarray(rmsnorm(jnp.asarray(rows), w, cfg)).astype(np.float64)
    fails = int(np.sum(out[0] != 0.0))
    fails += int(np.sum(~np.isnan(out[1])))
    return fails


def run_cell(cell: Cell, n_log: int = 4096, n_man: int = 4096,
             seed: int = 0) -> Dict:
    """Measure one cell over the stratified sweep; returns a report dict.

    Masks are policy-aware: cells whose delivered underflow policy is
    "gradual" (the bit-level jnp twins) keep subnormal operands and
    gradual-underflow results *inside* the ULP statistics — exactness there
    is the point of the datapath — while FTZ cells (fused kernels, ILM,
    XLA-native exact on this backend) exclude them as the flush edge class.
    """
    import jax.numpy as jnp

    cfg = cell.config()
    gradual = effective_underflow(cfg) == "gradual"
    table = compute_segments(cell.n_iters, cell.precision_bits)
    t0 = time.perf_counter()
    per_stratum: Dict[str, Dict] = {}
    edge_fail = 0
    agg: List[np.ndarray] = []
    extra: Dict = {}       # op-specific gated metrics (consumer cells)

    def measure(name: str, r_np: np.ndarray, exact: np.ndarray,
                mask: np.ndarray) -> None:
        """Shared per-stratum bookkeeping for all ops."""
        errs = ulp.ulp_error(r_np, exact, cell.dtype, where=mask)
        per_stratum[name] = ulp.summarize(errs, mask)
        agg.append(errs[mask])

    def operand_mask(x64: np.ndarray) -> np.ndarray:
        m = ulp.oracle_mask(x64, cell.dtype)
        if gradual:
            m = m | ulp.subnormal_mask(x64, cell.dtype)
        return m

    def result_mask(exact: np.ndarray, cliffs: bool) -> np.ndarray:
        m = ulp.oracle_mask(exact, cell.dtype)
        if cliffs:
            m = m & (ulp.cliff_guard(exact, cell.dtype) if not gradual
                     else ulp.overflow_guard(exact, cell.dtype))
        if gradual:
            # Gradual cells measure subnormal exact results too (the RNE
            # integer repack rounds into the subnormal lattice).
            m = m | ulp.subnormal_mask(exact, cell.dtype)
        return m

    if cell.op == "div":
        pairs = ulp.div_sweep(cell.dtype, n_log=n_log, n_man=n_man,
                              boundaries=table.boundaries, seed=seed)
        for name, (a_s, b_s) in pairs.items():
            a64 = np.asarray(a_s).astype(np.float64)
            b64 = np.asarray(b_s).astype(np.float64)
            q = div(jnp.asarray(a_s), jnp.asarray(b_s), cfg)
            q_np = np.asarray(q)
            with np.errstate(divide="ignore", invalid="ignore"):
                exact = a64 / b64
            # FTZ cells: ULP stats where the exact quotient AND both
            # operands are normal, quotients within 2 ULP of the cliffs
            # guard-banded. Gradual cells: subnormal operands/results are
            # measured; only the overflow cliff keeps its guard band.
            mask = (result_mask(exact, cliffs=True)
                    & operand_mask(a64) & operand_mask(b64))
            measure(name, q_np, exact, mask)
            if name == "subnormals":
                # FTZ signature on subnormal denominators: flushed-b lanes
                # divide as x/0 -> inf (or 0 for flushed numerators).
                q64 = q_np.astype(np.float64)
                per_stratum[name]["ftz_frac"] = float(
                    np.mean(np.isinf(q64) | (q64 == 0)))
            if name == "edges":
                edge_fail = _div_edge_failures(a64, b64,
                                               q_np.astype(np.float64))
    elif cell.op == "rsqrt":
        strata = ulp.rsqrt_sweep(cell.dtype, n_log=n_log, n_man=n_man,
                                 seed=seed)
        for name, xs in strata.items():
            x64 = np.asarray(xs).astype(np.float64)
            r_np = np.asarray(rsqrt(jnp.asarray(xs), cfg))
            with np.errstate(divide="ignore", invalid="ignore"):
                exact = 1.0 / np.sqrt(x64)     # x<0 -> nan, 0 -> inf
            # rsqrt never under/overflows on normal or subnormal operands,
            # so no cliff guards apply.
            mask = result_mask(exact, cliffs=False) & operand_mask(x64)
            measure(name, r_np, exact, mask)
            if name == "subnormals":
                r64 = r_np.astype(np.float64)
                per_stratum[name]["ftz_frac"] = float(
                    np.mean(np.isinf(r64) | (r64 == 0)))
            if name == "edges":
                edge_fail = _rsqrt_edge_failures(x64,
                                                 r_np.astype(np.float64))
    elif cell.op in consumers.CONSUMER_OPS:
        # Consumer cells: oracle ULP stats are informational (the shared
        # exp/reduction error dominates on hard strata, in every mode);
        # the gated numbers are the vs-exact-twin integer ULP and, for
        # softmax, the row-sum accuracy. See eval/consumers.py.
        exact_cfg = DivisionConfig(mode="exact")
        rows = max(8, min(n_log, 4096) // 64)
        d = 128
        row_sum_max = 0.0
        vs_exact_max = 0
        if cell.op == "softmax":
            strata_rows = consumers.softmax_rows(cell.dtype, rows, d, seed)
        else:
            strata_rows = consumers.rmsnorm_rows(cell.dtype, rows, d, seed)
            w = consumers.rmsnorm_weight(d, seed)
            wj = jnp.asarray(w)
        for name, xs in strata_rows.items():
            xj = jnp.asarray(xs)
            x64 = np.asarray(xs).astype(np.float64)
            if cell.op == "softmax":
                out = np.asarray(softmax(xj, -1, cfg))
                twin = np.asarray(softmax(xj, -1, exact_cfg))
                exact = consumers.softmax_oracle(x64)
                mask = ulp.oracle_mask(exact, cell.dtype)
            else:
                out = np.asarray(rmsnorm(xj, wj, cfg))
                twin = np.asarray(rmsnorm(xj, wj, exact_cfg))
                exact = consumers.rmsnorm_oracle(x64, w.astype(np.float64))
                mask = (ulp.oracle_mask(exact, cell.dtype)
                        & ~ulp.subnormal_mask(x64, cell.dtype))
            measure(name, out, exact, mask)
            ve = consumers.vs_exact_int_ulp(out, twin, exact, cell.dtype)
            per_stratum[name]["vs_exact_max_ulp"] = ve
            vs_exact_max = max(vs_exact_max, ve)
            if cell.op == "softmax":
                rs = float(consumers.row_sum_ulp1(out, cell.dtype).max())
                per_stratum[name]["row_sum_max_ulp1"] = rs
                row_sum_max = max(row_sum_max, rs)
        if cell.op == "softmax":
            edge_fail = _softmax_edge_failures(cfg, cell.dtype)
        else:
            edge_fail = _rmsnorm_edge_failures(cfg, cell.dtype)
        extra = {"vs_exact_max_ulp": vs_exact_max}
        if cell.op == "softmax":
            extra["row_sum_max_ulp1"] = row_sum_max
    else:
        strata = ulp.stratified_sweep(cell.dtype, n_log=n_log, n_man=n_man,
                                      boundaries=table.boundaries, seed=seed)
        for name, xs in strata.items():
            x64 = np.asarray(xs).astype(np.float64)
            r = recip(jnp.asarray(xs), cfg)
            r_np = np.asarray(r)
            with np.errstate(divide="ignore", invalid="ignore"):
                exact = 1.0 / x64          # IEEE: +-0 -> +-inf, +-inf -> +-0
            mask = result_mask(exact, cliffs=gradual) & operand_mask(x64)
            measure(name, r_np, exact, mask)
            if name == "subnormals":
                per_stratum[name]["ftz_frac"] = float(
                    np.mean(np.isinf(r_np.astype(np.float64))))
            if name == "edges":
                edge_fail = _edge_failures(x64, r_np.astype(np.float64))
    allv = np.concatenate(agg) if agg else np.zeros(0)
    out = dataclasses.asdict(cell)
    out.update({
        "key": cell.key,
        "underflow": effective_underflow(cfg),
        "overall": ulp.summarize(allv),
        "strata": per_stratum,
        "edge_failures": edge_fail,
        "seconds": round(time.perf_counter() - t0, 3),
    })
    out.update(extra)
    out["pass"] = cell_gate(out)
    return out


def run_conformance(cells: Optional[Sequence[Cell]] = None, *,
                    n_log: int = 4096, n_man: int = 4096,
                    quick: bool = False, seed: int = 0) -> Dict:
    """Run the grid; returns {meta, cells: [...]}, JSON-serializable."""
    import jax

    if cells is None:
        cells = default_grid(quick=quick)
    if quick:
        n_log, n_man = min(n_log, 1024), min(n_man, 1024)
    report = {
        "meta": {
            "jax": jax.__version__,
            "numpy": np.__version__,
            "backend": jax.default_backend(),
            "sweep": {"n_log": n_log, "n_man": n_man, "seed": seed},
        },
        "cells": [run_cell(c, n_log=n_log, n_man=n_man, seed=seed)
                  for c in cells],
    }
    return report


def cell_gate(cell_report: Dict) -> bool:
    """Pass/fail verdict for one measured cell.

    Every cell must honor the IEEE edge contract (edge_failures == 0) and
    produce finite ULP statistics; non-ILM cells at n_iters >= 2 must
    additionally deliver the paper's eq. 17 gate (<= 2 max ULP). The
    n=1 @ 12-bit dial point is the deliberately-loose end of the accuracy
    dial and is not ULP-gated.

    Consumer cells (op in {softmax, rmsnorm}) swap the oracle-ULP gate for
    the metrics that isolate the unit's contribution (eval/consumers.py):
    vs-exact-twin integer ULP and, for softmax, row-sum accuracy — the
    shared exp/reduction error dominates oracle ULPs on hard strata in
    every mode including exact, so gating on it would measure the
    consumer, not the divider.
    """
    o = cell_report["overall"]
    ok = cell_report["edge_failures"] == 0 and np.isfinite(o["max_ulp"])
    if cell_report.get("op") in consumers.CONSUMER_OPS:
        if cell_report["mode"] != "ilm" and cell_report["n_iters"] >= 2:
            ok = ok and (cell_report["vs_exact_max_ulp"]
                         <= consumers.VS_EXACT_GATE_ULP)
            if cell_report["op"] == "softmax":
                ok = ok and (cell_report["row_sum_max_ulp1"]
                             <= consumers.ROW_SUM_GATE_ULP)
        return bool(ok)
    if cell_report["mode"] != "ilm" and cell_report["n_iters"] >= 2:
        ok = ok and o["max_ulp"] <= GATE_MAX_ULP
    return bool(ok)


def cell_lookup(report: Dict, **kw) -> Dict:
    """First report cell matching all given field values (mode=, dtype=, ...)."""
    for c in report["cells"]:
        if all(c.get(k) == v for k, v in kw.items()):
            return c
    raise KeyError(f"no cell matching {kw}")


def format_table(report: Dict) -> str:
    """Human-readable mode x schedule x n_iters ULP table."""
    hdr = (f"{'op':5s} {'mode':18s} {'schedule':10s} {'n':>2s} {'bits':>4s} "
           f"{'dtype':9s} {'uflow':7s} {'max_ulp':>10s} {'mean_ulp':>10s} "
           f"{'p99':>8s} {'edges':>5s} {'gate':>5s}")
    lines = [hdr, "-" * len(hdr)]
    for c in report["cells"]:
        o = c["overall"]
        lines.append(
            f"{c['op']:5s} {c['mode']:18s} {c['schedule']:10s} "
            f"{c['n_iters']:2d} {c['precision_bits']:4d} {c['dtype']:9s} "
            f"{c.get('underflow', '-'):7s} "
            f"{o['max_ulp']:10.3f} {o['mean_ulp']:10.4f} {o['p99_ulp']:8.3f} "
            f"{'ok' if c['edge_failures'] == 0 else c['edge_failures']:>5} "
            f"{'pass' if c.get('pass', True) else 'FAIL':>5}")
    return "\n".join(lines)


def _emit(report: Dict, json_path: Optional[str]) -> int:
    """Shared tail of main/fanout: table, optional JSON, pass/fail exit."""
    print(format_table(report))
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# wrote {json_path}")
    failing = [c["key"] for c in report["cells"] if not c.get("pass", True)]
    if failing:
        print(f"# CONFORMANCE FAILURES ({len(failing)} cells):")
        for k in failing:
            print(f"#   {k}")
        return 1
    return 0


def _run_fanout(args, n: int) -> int:
    """Fan the grid out over ``n`` worker subprocesses, one ``--shard i/n``
    each — the grid is embarrassingly parallel by cell.

    Workers re-derive the same deterministic cell list and take the
    interleaved slice ``cells[i::n]``, so the merged report
    (``merged[i::n] = shard_i``) restores the exact single-process cell
    order. Each worker is its own jax process, so this runs on the CPU
    backend only: ``main`` refuses it on a TPU, where a chip belongs to one
    process. A worker that dies without writing its report fails the whole
    run.
    """
    import os
    import subprocess
    import tempfile

    cmd = [sys.executable, "-m", "repro.eval.conformance",
           "--seed", str(args.seed)]
    if args.quick:
        cmd.append("--quick")
    if args.modes:
        cmd += ["--modes", args.modes]
    src_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory() as td:
        paths = [os.path.join(td, f"shard{i}.json") for i in range(n)]
        procs = [subprocess.Popen(cmd + ["--shard", f"{i}/{n}",
                                         "--json", paths[i]],
                                  env=env, stdout=subprocess.DEVNULL)
                 for i in range(n)]
        rcs = [p.wait() for p in procs]
        shards = []
        for i, path in enumerate(paths):
            if not os.path.exists(path):
                print(f"# fanout shard {i}/{n} wrote no report "
                      f"(exit {rcs[i]})")
                return 1
            with open(path) as f:
                shards.append(json.load(f))
    merged: List = [None] * sum(len(s["cells"]) for s in shards)
    for i, s in enumerate(shards):
        merged[i::n] = s["cells"]
    report = {"meta": {**shards[0]["meta"], "fanout": n}, "cells": merged}
    return _emit(report, args.json)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized sweep (1024-point strata, n=2 dial only)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the machine-readable report here")
    ap.add_argument("--modes", default=None,
                    help="comma-separated mode filter (e.g. taylor,goldschmidt)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shard", default=None, metavar="K/N",
                    help="run only the interleaved grid slice cells[K::N]")
    ap.add_argument("--fanout", type=int, default=0, metavar="N",
                    help="fan the grid out over N --shard subprocesses and "
                         "merge their reports")
    args = ap.parse_args(argv)
    if args.fanout and args.shard:
        ap.error("--fanout and --shard are mutually exclusive")
    if args.fanout and args.fanout > 1:
        import jax

        if jax.default_backend() == "tpu":
            # This process now holds the chip; a worker would fail or hang
            # waiting for it. On a TPU the grid runs in one process.
            ap.error("--fanout starts one JAX process per shard and cannot "
                     "share a TPU; run the grid without --fanout")
        return _run_fanout(args, args.fanout)

    cells = default_grid(quick=args.quick)
    if args.modes:
        from repro.core.division_modes import MODES

        keep = set(args.modes.split(","))
        unknown = keep - set(MODES)
        if unknown:
            ap.error(f"unknown modes {sorted(unknown)}; valid: {MODES}")
        cells = [c for c in cells if c.mode in keep]
    if args.shard:
        try:
            k, n = (int(p) for p in args.shard.split("/"))
        except ValueError:
            ap.error("--shard wants K/N (e.g. 0/8)")
        if not 0 <= k < n:
            ap.error(f"--shard needs 0 <= K < N, got {args.shard}")
        cells = cells[k::n]
    report = run_conformance(cells, quick=args.quick, seed=args.seed)
    return _emit(report, args.json)


if __name__ == "__main__":
    sys.exit(main())
