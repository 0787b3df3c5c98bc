"""Device time by named scope when a run executes several programs.

A serving engine runs a decode program and one prefill program per padded
prompt length. The trace names an operation by its instruction name only
(lib/trace.py), and those collide across programs: ``%fusion.3`` of the
decode step is not ``%fusion.3`` of a prefill. So each operation is read
in its own program: the device's ``XLA Modules`` line holds one span per
execution of a program, named by the executed HLO module (``jit_decode``,
with a run number in brackets), and the operations of that execution lie
inside it. The span's program is the compiled program of that module
name. Where several programs share the name (one prefill program per
padded length) it is the one among them that holds the most of the span's
instruction names (programs that hold them all alike are the same program
at another shape, and give the same paths). An operation outside every
module span (or a trace with no such line) is read in the first program,
in the order given, that holds its name.

A program's scopes are ``jax.named_scope`` paths from its compiled text
(``scopes.op_scopes``), as for a single-program cell (lib/scopes.py).
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import re
import shutil
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

from . import scopes, trace as tr

MODULES_LINE = "XLA Modules"
# any scope of a program (the unit's, a layer's), as opposed to jit, while
# and primitive names
SCOPE = r"[a-z]+\.[a-z_]+"

_NAME = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = ", re.MULTILINE)
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.MULTILINE)
# a module span's run number: ``jit_decode(123)``
_RUN = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Program:
    label: str
    module: str                     # HLO module name ('' where unnamed)
    names: FrozenSet[str]           # every instruction name
    paths: Dict[str, str]           # instruction name -> op_name path

    @classmethod
    def of(cls, label: str, hlo_text: str) -> "Program":
        m = _MODULE.search(hlo_text)
        return cls(label, m.group(1) if m else "",
                   frozenset(_NAME.findall(hlo_text)),
                   scopes.op_scopes(hlo_text))


Spans = Dict[str, List[Tuple[int, int, str]]]


def module_name(event: str) -> str:
    """The HLO module a ``XLA Modules`` event executed."""
    return _RUN.sub("", event.strip()).strip()


def module_spans(recorded: dict) -> Spans:
    """``{device plane: sorted [(start_ns, end_ns, module name)]}`` of the
    program executions on each device's ``XLA Modules`` line."""
    out: Spans = {}
    for plane in recorded["planes"]:
        if not tr.DEVICE_PLANE.match(plane["name"]):
            continue
        spans = [(s, s + d, module_name(n)) for ln in plane["lines"]
                 if ln["name"] == MODULES_LINE for n, s, d in ln["events"]]
        if spans:
            out[plane["name"]] = sorted(spans)
    return out


def finish(win, save=None):
    """``(trace.Trace, module spans)`` of a ``window.Window`` after its
    close, as ``Window.finish`` with the ``XLA Modules`` line kept too;
    ``(None, {})`` on an untraced window."""
    if win.trace_dir is None:
        return None, {}
    import jax

    jax.profiler.stop_trace()
    recorded = tr.record(str(win.trace_dir), keep_lines=None if save else
                         [tr.OPS_LINE, MODULES_LINE])
    shutil.rmtree(win.trace_dir, ignore_errors=True)
    if save is not None:
        save.parent.mkdir(parents=True, exist_ok=True)
        save.write_text(json.dumps(recorded))
    return tr.Trace(recorded), module_spans(recorded)


def _pick(module: str, names, progs: Sequence[Program]
          ) -> Optional[Program]:
    """The program of module ``module``; among several of that name, or
    where none has it, the one holding the most of ``names``."""
    named = [p for p in progs if p.module == module]
    if len(named) == 1:
        return named[0]
    best, score = None, 0
    for p in named or progs:
        n = len(names & p.names)
        if n > score:
            best, score = p, n
    return best


def attribute(trace, spans: Spans, progs: Sequence[Program]
              ) -> Dict[str, List[Tuple[str, str, int, int]]]:
    """``{device: [(program label, op_name path, start, end)]}`` for every
    operation of the trace's window ('' for an operation no program
    holds)."""
    out = {}
    for dev, evs in trace.devices.items():
        sp = spans.get(dev, [])
        starts = [s for s, _, _ in sp]
        groups: Dict[Optional[int], list] = {}
        for n, s, e in evs:
            i = bisect.bisect_right(starts, s) - 1
            key = i if i >= 0 and s < sp[i][1] else None
            groups.setdefault(key, []).append((n, s, e))
        rows = []
        for key, group in groups.items():
            if key is not None:
                prog = _pick(sp[key][2], {n for n, _, _ in group}, progs)
            for n, s, e in group:
                p = prog if key is not None else next(
                    (q for q in progs if n in q.names), None)
                rows.append(("" if p is None else p.label,
                             "" if p is None else p.paths.get(n, ""), s, e))
        out[dev] = rows
    return out


def scoped_s(trace, spans: Spans, progs: Sequence[Program],
             inside: Sequence[str], program: Optional[str] = None
             ) -> Optional[float]:
    """Device seconds (mean over devices) of the operations under a scope of
    ``inside``, in the program labelled ``program`` or in any. None where no
    operation carries a scope of the program."""
    if trace is None or not progs:
        return None
    rows = attribute(trace, spans, progs)
    if not any(re.search(rf"(^|/){SCOPE}(/|$)", path)
               for evs in rows.values() for _, path, _, _ in evs):
        return None
    per_device = []
    for evs in rows.values():
        per_device.append(tr.length(tr.union(
            (s, e) for label, path, s, e in evs
            if (program is None or label == program)
            and scopes.under(path, inside))))
    return sum(per_device) / len(per_device) * 1e-9


def top_scopes(trace, spans: Spans, progs: Sequence[Program],
               n: int = 12) -> List[List]:
    """The ``n`` labels ``<program>:<scopes>`` with the most device time
    (seconds, mean over devices), a label's scopes being the program scopes
    on an operation's path ('unscoped' without any); empty without
    programs."""
    if trace is None or not progs:
        return []
    tot: Dict[str, float] = {}
    rows = attribute(trace, spans, progs)
    for evs in rows.values():
        for label, path, s, e in evs:
            sc = "/".join(c for c in path.split("/")
                          if re.fullmatch(SCOPE, c)) or "unscoped"
            key = f"{label.rstrip('0123456789')}:{sc}"
            tot[key] = tot.get(key, 0.0) + (e - s) * 1e-9 / len(rows)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])][:n]


def of_view(view):
    """``(programs, module spans)`` a serving kind put in the view's work
    counters on a traced run, or None."""
    if view.trace is None:
        return None
    progs = view.work.get("programs")
    if not progs:
        return None
    return progs, view.work.get("module_spans", {})


def busy_share(view, inside: Sequence[str]) -> Optional[float]:
    """``scoped_s`` over all programs as a percentage of busy time."""
    got = of_view(view)
    if got is None or not view.trace.busy_s:
        return None
    secs = scoped_s(view.trace, got[1], got[0], inside)
    return None if secs is None else 100.0 * secs / view.trace.busy_s
