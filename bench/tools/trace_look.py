"""Print how a profiler trace names things: planes, lines, and the first
events of each device line with their statistics.

    python3 bench/tools/trace_look.py <dir holding an .xplane.pb> [--events 40]

Look at one trace this way before changing the reduction in
bench/lib/trace.py or a reader's kernel names in bench/metrics/.
"""
from __future__ import annotations

import argparse
import collections
import glob
import os


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dir")
    ap.add_argument("--events", type=int, default=40)
    args = ap.parse_args(argv)
    import jax

    files = sorted(glob.glob(os.path.join(args.dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    pd = jax.profiler.ProfileData.from_file(files[-1])
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{len(names)} names; top {names.most_common(8)}")
            if not plane.name.startswith("/device:"):
                continue
            for e in events[:args.events]:
                st = {k: (v if not isinstance(v, bytes) else "<bytes>")
                      for k, v in e.stats}
                print(f"    {e.name!r} start={e.start_ns} "
                      f"dur={e.duration_ns} {st}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
