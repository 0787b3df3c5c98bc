"""How the program's named scopes are found in a device trace.

The program names its layers with ``jax.named_scope``: the division unit's
public entries as ``dm.<entry>`` (``dm.div``, ``dm.softmax``, ...,
core/division_modes.py) and the Lloyd phases as ``kmeans.distance``,
``kmeans.assign``, ``kmeans.update`` and ``kmeans.final``
(workloads/kmeans.py). A scope is compile-time metadata: the compiled
program's text gives each instruction an ``op_name`` path that holds every
scope in force where the operation was staged, fusions included, such as

    %broadcast_in_dim.45 = ... metadata={op_name="jit(call)/while/body/
        closed_call/kmeans.distance/dm.div/broadcast_in_dim" ...}

The device trace names an operation by the same instruction name
(lib/trace.py), so the path joins a traced operation to its scopes. A fusion
carries the path of the operation at its root. Instructions the compiler
adds itself (copies, asynchronous slices) carry no path.

The trace the harness hands a reader holds no paths, so ``of_view``
compiles the cell's program once more after the window (the persistent
compilation cache gives back the timed executable) and reads them from its
text. A recorded trace may carry the map as a top-level ``"scopes"`` object
(``attach``), which ``lib/trace.Trace`` does not read.

Each pattern below is a regular expression matched against one component
of the path.
"""
from __future__ import annotations

import re
import sys
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence

from . import kernels

# core/division_modes.py: any public entry of the division unit
UNIT = r"dm\.[a-z_]+"
# workloads/kmeans.py: the Lloyd phases
DISTANCE = r"kmeans\.distance"
ASSIGN = r"kmeans\.assign"
UPDATE = r"kmeans\.update"
# any scope of the program, as opposed to jit, while and primitive names
SCOPE = r"(?:dm|kmeans)\.[a-z_]+"

_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?(%[^\s=]+) = .*?\bmetadata=\{[^}]*?'
    r'op_name="((?:[^"\\]|\\.)*)"', re.MULTILINE)


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{"%instr.N": op_name}`` for every instruction of a compiled
    program's text (``compiled.as_text()``) that carries an ``op_name``."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def scopes_in(path: str) -> List[str]:
    """The program's scopes in an ``op_name`` path, outermost first."""
    return [c for c in path.split("/") if re.fullmatch(SCOPE, c)]


def under(path: str, patterns: Sequence[str]) -> bool:
    """True where some component of ``path`` is one of ``patterns``."""
    return any(re.fullmatch(p, c) for c in path.split("/") for p in patterns)


def attach(recorded: dict, paths: Dict[str, str]) -> dict:
    """``recorded`` (lib/trace.record's form) with a top-level ``scopes``
    map of the operations its device planes hold (an instruction that
    never ran is left out)."""
    from . import trace as tr

    names = {tr.short_name(n) for plane in recorded["planes"]
             if tr.DEVICE_PLANE.match(plane["name"])
             for ln in plane["lines"] for n, _, _ in ln["events"]}
    recorded["scopes"] = {n: p for n, p in paths.items() if n in names}
    return recorded


# --------------------------------------------------------------- reduction

def scoped(trace, paths: Optional[Dict[str, str]]) -> bool:
    """True where some traced operation's path holds a scope of the
    program: a program built before it named its scopes has none."""
    return trace is not None and bool(paths) and any(
        scopes_in(paths.get(name, "")) for name, _ in trace.top_ops(None))


def scoped_s(trace, paths: Optional[Dict[str, str]], inside: Sequence[str],
             outside: Sequence[str] = (),
             unit_kernels: bool = True) -> Optional[float]:
    """Device seconds (mean over devices, as ``Trace.op_s``) of the
    operations under a scope of ``inside`` and under none of ``outside``;
    the division unit's kernels (``kernels.is_unit``) left out unless
    ``unit_kernels``. None where no traced operation carries a scope."""
    if not scoped(trace, paths):
        return None
    seen: Dict[str, bool] = {}

    def match(name: str) -> bool:
        if name not in seen:
            path = paths.get(name, "")
            seen[name] = (under(path, inside) and not under(path, outside)
                          and (unit_kernels or not kernels.is_unit(name)))
        return seen[name]

    return trace.op_s(match)


def busy_share(trace, paths: Optional[Dict[str, str]],
               inside: Sequence[str], outside: Sequence[str] = (),
               unit_kernels: bool = True) -> Optional[float]:
    """``scoped_s`` as a percentage of device busy time."""
    if trace is None or not trace.busy_s:
        return None
    secs = scoped_s(trace, paths, inside, outside, unit_kernels)
    return None if secs is None else 100.0 * secs / trace.busy_s


def _label(paths: Dict[str, str], name: str) -> str:
    """An operation's scopes joined by ``/`` (``unscoped`` without any),
    with ``:kernel`` after a unit kernel."""
    text = "/".join(scopes_in(paths.get(name, ""))) or "unscoped"
    return text + ":kernel" if kernels.is_unit(name) else text


def top_scopes(trace, paths: Optional[Dict[str, str]],
               n: int = 8) -> List[List]:
    """The ``n`` labels with the most device time (seconds, mean over
    devices); empty where no traced operation carries a scope."""
    if not scoped(trace, paths):
        return []
    tot: Dict[str, float] = {}
    for name, secs in trace.top_ops(None):
        key = _label(paths, name)
        tot[key] = tot.get(key, 0.0) + secs
    return [[k, s] for k, s in sorted(tot.items(), key=lambda kv: -kv[1])][:n]


# ------------------------------------------------------ the cell's program

def program_paths(config: dict, traffic: dict, chips: int, devices=None,
                  require_kernel: bool = True) -> Dict[str, str]:
    """``op_scopes`` of the cell's compiled program, built as its kind
    builds it (``bench/kinds/<kind>.build``); empty for a kind with no
    such build. ``devices`` defaults to the cell's chips."""
    from . import device

    if config.get("kind") != "kmeans":
        return {}
    from bench.kinds import kmeans as kind

    ctx = SimpleNamespace(
        cell=SimpleNamespace(config=config, traffic=traffic, chips=chips),
        devices=devices if devices is not None else device.chips(chips),
        require_kernel=require_kernel)
    _, compiled, _ = kind.build(ctx)
    return op_scopes(compiled.as_text())


_LAST: list = [None, None]      # [view, its paths]: one build per run


def of_view(view) -> Optional[Dict[str, str]]:
    """The scope map for a reader's ``view``: its own ``scopes`` where it
    carries one (a recorded trace's), else the cell's program's, built
    once per view; None on an untraced view. Where it builds the map it
    prints the top scopes by device time on standard error
    (``bench: device_scopes = ...``)."""
    if view.trace is None:
        return None
    own = getattr(view, "scopes", None)
    if own is not None:
        return own
    if _LAST[0] is not view:
        paths = program_paths(view.config, view.traffic, view.chips)
        top = top_scopes(view.trace, paths)
        if top:
            print(f"bench: device_scopes = {top}", file=sys.stderr)
        _LAST[:] = [view, paths]
    return _LAST[1]
