"""Everything a run needs, found by name from ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Each lives in a file of its own that the harness finds by that name:

    bench/configs/<config>.json   sizes, the deployment, ``kind`` and mode
    bench/traffic/<traffic>.json  parameters that bench/lib/traffic.py reads
    bench/kinds/<kind>.py         the driver of that kind of cell
    bench/metrics/<metric>.py     one per-layer metric: ``read(view)``

Adding a cell, a configuration or a metric adds files and entries; no file
that is already there changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(Exception):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r}")


def end_to_end_for(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_for(bench: dict, cell: str) -> List[dict]:
    """Per-layer metrics the cell reports: those that list it, and those
    without a list whose ``moves`` metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def load_cell(name: str, root: Path = ROOT,
              bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "configuration")
    return Cell(
        name=name, chips=int(w["chips"]), config_name=c["name"],
        traffic_name=w["traffic"],
        config=json.loads((root / c["file"]).read_text()),
        traffic=load_traffic(w["traffic"], root),
        end_to_end=end_to_end_for(bench, name),
        per_layer=per_layer_for(bench, name))


def load_traffic(name: str, root: Path = ROOT) -> dict:
    path = root / "bench" / "traffic" / f"{name}.json"
    if not path.exists():
        raise SpecError(f"no traffic file {path.relative_to(root)}")
    return json.loads(path.read_text())


def load_kind(kind: str):
    """The driver module ``bench/kinds/<kind>.py``."""
    if not kind.isidentifier():
        raise SpecError(f"kind {kind!r} is not a module name")
    return importlib.import_module(f"bench.kinds.{kind}")


def load_metric(name: str, root: Path = ROOT):
    """The reader module ``bench/metrics/<name>.py`` (names may hold dots,
    so it is loaded from its path)."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no reader {path.relative_to(root)}")
    mod_name = "bench_metric_" + "".join(
        ch if ch.isalnum() else "_" for ch in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
