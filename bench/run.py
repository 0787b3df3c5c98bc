"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (process start to the first timed call) makes the data or weights
on the device from ``--seed``, compiles or loads every program the cell's
traffic uses and warms it up. The window then runs the cell's traffic for
``--seconds``. With ``--trace 0`` the result holds the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
result holds its per-layer metrics, read by ``bench/metrics/<name>.py``.
After the window the outputs are compared with the plain reference; each
number compared is printed beside its limit, last on standard error and
last in the result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``busy_s`` and
``window_s`` on traced runs), ``breakdown`` on traced runs, then
``checks``. The run exits 1 and prints no result when JAX finds no TPU or
fewer chips than the cell asks for, when the Pallas kernels would be
interpreted, when the device is not in bench/peaks.json, when a program
lacks its Mosaic kernel, when a division site falls back to the jnp twin,
or, on a mesh, when the sharded path was not taken.

JAX's persistent compilation cache is kept in ``.jax_cache/`` at the root
of the checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

LIMITS = ROOT / "bench" / "limits"
TRACE_DIR = ROOT / "bench_out" / "trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", type=Path, default=None,
                    help="keep the window's recorded trace (every device "
                         "line) as JSON at this path")
    return ap.parse_args(argv)


def load_limits(cell: str) -> dict:
    path = LIMITS / f"{cell}.json"
    if not path.exists():
        raise FileNotFoundError(f"no limits file {path.relative_to(ROOT)}")
    return {k: float(v["limit"]) for k, v in
            json.loads(path.read_text())["checks"].items()}


def result_line(ctx, out, peaks) -> dict:
    """The run's result object, per-layer metrics read on traced runs."""
    from bench.lib import device, spec

    cell = ctx.cell
    metrics = {}
    if ctx.trace:
        view = View(ctx, out, peaks)
        for m in cell.per_layer:
            value = spec.load_metric(m["name"]).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out.e2e[m["name"]],
                                  "unit": m["unit"]}
    dev = device.describe(ctx.devices)
    dev["memory_peak_bytes"] = out.memory_peak_bytes
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": dev}
    if out.trace is not None:
        dev["busy_s"] = out.trace.busy_s
        dev["window_s"] = out.trace.window_s
        line["breakdown"] = {"device_ops": out.trace.top_ops(10),
                             "idle_gaps": out.trace.idle_by_host(10)}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


class View:
    """What a per-layer reader sees: the cell's configuration, the
    window's counters, its reduced trace and the chip's peaks."""

    def __init__(self, ctx, out, peaks):
        self.config = ctx.cell.config
        self.traffic = ctx.cell.traffic
        self.chips = ctx.cell.chips
        self.work = out.work
        self.trace = out.trace
        self.peaks = peaks


def main(argv=None) -> int:
    args = parse(argv)
    from bench.lib import device, spec
    from bench.lib.context import Ctx

    try:
        cell = spec.load_cell(args.workload)
        devices = device.chips(cell.chips)
        t_chips = time.perf_counter()
        peaks = device.load_peaks(devices[0].device_kind)
        device.require_compiled_kernels()
        limits = load_limits(cell.name)
    except (device.Refused, spec.SpecError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    dev = device.describe(devices)
    print(f"bench: {cell.name} on {dev['count']} x {dev['kind']} "
          f"({dev['platform']}), seed {args.seed}", file=sys.stderr)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ctx = Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), t0=T0, t_chips=t_chips,
              devices=devices, trace_dir=TRACE_DIR / cell.name, limits=limits,
              save_trace=args.save_trace)
    kind = spec.load_kind(cell.config["kind"])
    try:
        out = kind.run(ctx)
    except device.Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    line = result_line(ctx, out, peaks)
    for key, value in out.notes:
        print(f"bench: {key} = {value}", file=sys.stderr)
    for c in out.checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r})"
              f"{'' if c.ok else ' FAILED'}", file=sys.stderr)
    print(json.dumps(_finite(line), allow_nan=False), flush=True)
    return 0


def _finite(v):
    """``v`` with every non-finite float as null (JSON has no nan)."""
    if isinstance(v, dict):
        return {k: _finite(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_finite(x) for x in v]
    if isinstance(v, float) and not math.isfinite(v):
        return None
    return v


if __name__ == "__main__":
    sys.exit(main())
