"""Set a cell's limits from calibration readings (bench/calibrate.py).

    python3 bench/tools/limits.py --workload <cell> readings.jsonl [...]

Each number a run compares is read as the run reads it: the worst over
what one run checks. For serving that is the widest gap over the checked
requests and cache rows. A K-Means run checks the first call and one more, so the
program's reading is the worst over all its calls (the most a run can
read) and the control's the first call's or the best other call's,
whichever is worse (the least a run can read). The lower reading is the
largest over the program's seeds, the upper the smallest over the
control's. A number whose upper
reading is under three times its lower has none and is reported, not
given a limit. Otherwise the limit sits two thirds of the way from the
lower to the upper reading on a log scale, so more room lies above the
lower reading than below the upper. The result goes to
bench/limits/<cell>.json with the readings it was set from.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def run_level(rows, side: str):
    """{seed: {number: what one run of that seed reads, at worst for the
    program and at best for the control}}."""
    calls = {}
    for r in rows:
        if side not in r:
            continue
        vals = r[side]
        calls.setdefault(r["seed"], []).append((r.get("call", 0), vals))
    out = {}
    for seed, got in calls.items():
        names = {k for _, v in got for k in v}
        first = [v for c, v in got if c == 0]
        rest = [v for c, v in got if c != 0]
        out[seed] = {}
        for k in names:
            if side == "program" or not rest:
                out[seed][k] = max(v[k] for _, v in got)
            else:
                out[seed][k] = max([v[k] for v in first]
                                   + [min(v[k] for v in rest)])
    return out


def limits(rows):
    prog, ctl = run_level(rows, "program"), run_level(rows, "control")
    out, unseparated = {}, {}
    for name in sorted({k for v in prog.values() for k in v}):
        lower = max(v[name] for v in prog.values())
        upper = min(v[name] for v in ctl.values())
        entry = {"lower": lower, "upper": upper,
                 "program_seeds": len(prog), "control_seeds": len(ctl)}
        if lower > 0 and upper >= 3 * lower:
            entry["limit"] = lower * (upper / lower) ** (2 / 3)
            out[name] = entry
        elif lower == 0 and upper > 0:
            entry["limit"] = upper / 10
            out[name] = entry
        else:
            unseparated[name] = entry
    return out, unseparated


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("readings", nargs="+", type=Path)
    ap.add_argument("--write", action="store_true",
                    help="write bench/limits/<cell>.json")
    args = ap.parse_args(argv)
    rows = [json.loads(line) for p in args.readings
            for line in p.read_text().splitlines() if line.strip()]
    rows = [r for r in rows if r.get("workload") == args.workload]
    out, unseparated = limits(rows)
    print(json.dumps({"checks": out, "unseparated": unseparated}, indent=1))
    if args.write:
        path = ROOT / "bench" / "limits" / f"{args.workload}.json"
        path.write_text(json.dumps({"checks": out}, indent=1) + "\n")
    return 0 if not unseparated else 2


if __name__ == "__main__":
    sys.exit(main())
