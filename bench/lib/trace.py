"""Profiler traces: capture one window, record it, reduce it to numbers.

A trace is kept in a plain recorded form so that the reduction can be
checked on a small file in the repository:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

Device planes are those named ``/device:<KIND>:<n>``; on each, the line named
``XLA Ops`` holds the operations that ran on the device, each under its HLO
instruction's text; an operation is named by the instruction's name (the
text before `` = ``, such as ``%tsdiv_divide_tiled_2d.12``; a Pallas kernel
carries the name of the function that launched it). Control-flow
instructions (``while``, ``conditional``, ``call``) span the operations
they run and are left out, so that a gap inside a loop counts as idle and
no time is counted twice. Host spans are the
events whose names start with ``bench.``: the benchmark's own
``jax.profiler.TraceAnnotation`` spans around the calls into the program. The
measured window is the ``bench.window`` span; every number is clipped to it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# Collective operations as XLA names them, async halves included.
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum|allreduce|allgather)", re.IGNORECASE)

# Instructions that only hold other operations.
CONTAINER = re.compile(r"^%(while|conditional|call)(\.\d+)?$")

Interval = Tuple[int, int]


def short_name(name: str) -> str:
    """``%fusion.87 = f32[...] fusion(...)`` -> ``%fusion.87``."""
    return name.split(" = ", 1)[0] if name.startswith("%") else name


# ----------------------------------------------------------------- capture

def profile_options():
    """Host and device tracers on, the Python tracer off: a Python-level
    trace of a serving loop would be larger than the device trace."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def record(log_dir: str, keep_lines: Optional[Iterable[str]] = None) -> dict:
    """The newest ``*.xplane.pb`` under ``log_dir`` in recorded form.

    Host planes keep only ``bench.`` spans; device planes keep every line
    unless ``keep_lines`` names the ones to keep.
    """
    import jax

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    keep = set(keep_lines) if keep_lines is not None else None
    planes = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and keep is not None and line.name not in keep:
                continue
            events = []
            for ev in line.events:
                name = short_name(ev.name) if device else ev.name
                if not device and not name.startswith(SPAN_PREFIX):
                    continue
                events.append([name, int(ev.start_ns), int(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---------------------------------------------------------------- intervals

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged, non-overlapping cover of ``intervals``."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------- reduction

class Trace:
    """Reduction of one recorded trace to device and host numbers.

    Every figure is clipped to the ``bench.window`` span (or to ``window``,
    given in the trace's nanoseconds) and, for device figures, averaged over
    the device planes present.
    """

    def __init__(self, recorded: dict, window: Optional[Interval] = None):
        self.devices: Dict[str, List[Tuple[str, int, int]]] = {}
        self.spans: List[Tuple[str, int, int]] = []
        for plane in recorded["planes"]:
            if DEVICE_PLANE.match(plane["name"]):
                ops = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]
                self.devices[plane["name"]] = [
                    (short_name(n), s, s + d) for ln in ops
                    for n, s, d in ln["events"]
                    if not CONTAINER.match(short_name(n))]
            else:
                self.spans.extend(
                    (n, s, s + d) for ln in plane["lines"]
                    for n, s, d in ln["events"] if n.startswith(SPAN_PREFIX))
        if window is None:
            wins = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
            if not wins:
                raise ValueError(f"trace has no {WINDOW_SPAN} span")
            window = (min(s for s, _ in wins), max(e for _, e in wins))
        self.window = window
        self._inner = sorted((s, e, n) for n, s, e in self.spans
                             if n != WINDOW_SPAN)
        self._starts = [s for s, _, _ in self._inner]
        lo, hi = window
        self.devices = {
            name: [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                   if min(e, hi) > max(s, lo)]
            for name, evs in self.devices.items()}

    # ---------------------------------------------------------------- basic
    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _mean(self, per_device: Callable[[list], float]) -> float:
        if not self.devices:
            return 0.0
        return sum(per_device(evs) for evs in self.devices.values()) \
            / len(self.devices)

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, mean over devices."""
        return self._mean(
            lambda evs: length(union((s, e) for _, s, e in evs))) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_s(self, match: Callable[[str], bool]) -> float:
        """Device seconds of the operations whose name ``match`` accepts,
        mean over devices (overlapping events of one name counted once)."""
        return self._mean(lambda evs: length(union(
            (s, e) for n, s, e in evs if match(n)))) * 1e-9

    def op_count(self, match: Callable[[str], bool]) -> float:
        return self._mean(lambda evs: sum(1 for n, _, _ in evs if match(n)))

    def exposed_collective_s(self) -> float:
        """Seconds of collectives during which no other operation ran on
        that device, mean over devices."""
        def per_device(evs):
            coll = union((s, e) for n, s, e in evs if COLLECTIVE.search(n))
            other = union((s, e) for n, s, e in evs
                          if not COLLECTIVE.search(n))
            return length(subtract(coll, other))
        return self._mean(per_device) * 1e-9

    # ------------------------------------------------------------ breakdown
    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operation names with the most device time (seconds,
        mean over devices)."""
        tot: Dict[str, int] = {}
        for evs in self.devices.values():
            for name, s, e in evs:
                tot[name] = tot.get(name, 0) + (e - s)
        k = max(1, len(self.devices))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in top]

    def idle_gaps(self) -> List[Interval]:
        """Intervals of the window in which device 0 (by name) ran
        nothing."""
        if not self.devices:
            return [self.window]
        evs = self.devices[sorted(self.devices)[0]]
        busy = union((s, e) for _, s, e in evs)
        return subtract([self.window], busy)

    def host_span_at(self, t: int) -> str:
        """Innermost ``bench.`` span (other than the window) covering
        ``t``; the spans are sequential calls, nested at most a few deep."""
        best = None
        i = bisect.bisect_right(self._starts, t) - 1
        for s, e, name in self._inner[max(0, i - 8):i + 1]:
            if s <= t < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "between spans"

    def idle_by_host(self, n: int = 10) -> List[List]:
        """Idle device time summed by what the host was doing (the span
        covering each gap's midpoint), largest first."""
        tot: Dict[str, int] = {}
        for s, e in self.idle_gaps():
            label = self.host_span_at((s + e) // 2)
            tot[label] = tot.get(label, 0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[label, ns * 1e-9] for label, ns in top]

    def span_s(self, name: str) -> float:
        lo, hi = self.window
        return length(union(clip(
            [(s, e) for n, s, e in self.spans if n == name], lo, hi))) * 1e-9


def matcher(patterns: Sequence[str]) -> Callable[[str], bool]:
    """Name predicate: true where any regular expression in ``patterns``
    matches the operation's name."""
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    return lambda name: bool(rx.search(name))
