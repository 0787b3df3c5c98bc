"""Measure a cell's spread: sets of runs with the same seeds, each run its
own process, as a check makes them.

    python3 bench/tools/sets.py --workload <cell> --seeds 11,12,13,14,15,16 \\
        [--sets 2] [--seconds 10] [--trace-seeds 21,22,23] [--out runs.jsonl]
    python3 bench/tools/sets.py --summarize runs.jsonl [--sets 2]

Prints, per end-to-end metric, each set's median and spread (interquartile
distance over the median, by ``statistics.quantiles(values, n=4)``) and
five times the widest spread, the bound that spread asks for;
``--summarize`` prints the same from the runs an earlier call wrote with
``--out``. This process never imports JAX, so each child run has the chip
to itself.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.perf_counter()
    r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    row = {"workload": workload, "seed": seed, "trace": trace,
           "rc": r.returncode, "wall_s": time.perf_counter() - t0,
           "stderr_tail": r.stderr[-1500:]}
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    if lines:
        row["result"] = json.loads(lines[-1])
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", type=Path, default=None)
    ap.add_argument("--summarize", type=Path, default=None)
    args = ap.parse_args(argv)
    if args.summarize:
        summarize([json.loads(line) for line in
                   args.summarize.read_text().splitlines() if line.strip()],
                  args.sets)
        return 0
    if not (args.workload and args.seeds):
        ap.error("--workload and --seeds are needed to run sets")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    rows = []
    out = args.out.open("a") if args.out else None

    def keep(row):
        rows.append(row)
        res = row.get("result", {})
        print(json.dumps({k: row[k] for k in ("seed", "trace", "rc",
                                              "wall_s")}
                         | {"correct": res.get("correct"),
                            "metrics": {k: v["value"] for k, v in
                                        res.get("metrics", {}).items()},
                            "checks": res.get("checks")}), flush=True)
        if row["rc"] != 0 or not res:
            print(row["stderr_tail"], file=sys.stderr, flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()

    for s in range(args.sets):
        for seed in seeds:
            row = one_run(args.workload, seed, args.seconds, 0)
            row["set"] = s
            keep(row)
    for seed in [int(s) for s in args.trace_seeds.split(",") if s]:
        keep(one_run(args.workload, seed, args.seconds, 1))
    if out:
        out.close()
    summarize(rows, args.sets)
    return 0


def summarize(rows, sets: int):
    widest = {}
    for s in range(sets):
        vals = {}
        for row in rows:
            if row.get("set") != s or "result" not in row:
                continue
            for k, v in row["result"]["metrics"].items():
                vals.setdefault(k, []).append(v["value"])
        for k, v in sorted(vals.items()):
            if len(v) < 2:
                continue
            sp = (statistics.quantiles(v, n=4)[2]
                  - statistics.quantiles(v, n=4)[0]) / statistics.median(v)
            widest[k] = max(widest.get(k, 0.0), sp)
            print(f"set {s} {k}: median {statistics.median(v)!r} "
                  f"spread {sp:.5f} n {len(v)}", flush=True)
    for k, sp in sorted(widest.items()):
        print(f"{k}: widest spread {sp:.5f}, five times {5 * sp:.5f}",
              flush=True)


if __name__ == "__main__":
    sys.exit(main())
