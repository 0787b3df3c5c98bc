"""Readings from which a cell's limits are set: the program on many seeds
against the plain reference, and the control (the reference in the next
lower precision) on a few, all in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 [--seconds 8] [--out readings.jsonl]

Prints one JSON line per reading (and writes them to ``--out``). The
benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)

    from bench.lib import device, spec
    from bench.lib.context import Ctx

    cell = spec.load_cell(args.workload)
    devices = device.chips(cell.chips)
    device.load_peaks(devices[0].device_kind)
    device.require_compiled_kernels()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    ctx = Ctx(cell=cell, seed=seeds[0], seconds=args.seconds, trace=False,
              t0=T0, devices=devices)
    rows = spec.load_kind(cell.config["kind"]).calibrate(ctx, seeds, control)
    out = args.out.open("w") if args.out else None
    for row in rows:
        line = json.dumps({"workload": cell.name, **row})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
