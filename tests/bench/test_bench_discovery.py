"""Cells, configurations, mixes and metrics are found by name; adding a
cell takes new files and a new entry, and no edit."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.load_cell(cell)
    assert c.config["kind"] == c.traffic["kind"]
    spec.load_kind(c.config["kind"])
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert hasattr(spec.load_metric(m["name"]), "read")
        assert m["moves"] in names
    limits = json.loads((ROOT / "bench" / "limits" / f"{cell}.json")
                        .read_text())["checks"]
    kind = spec.load_kind(c.config["kind"])
    assert set(limits) == set(kind.CHECKS)


def test_names_and_units_keep_to_the_contract():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).exists()
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))


def test_adding_a_cell_needs_only_new_files(tmp_path):
    """A copy of the benchmark gains a configuration, a mix and a metric
    as new files, and a cell as a new entry; the harness finds them."""
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((ROOT / "bench/configs/kmeans-kdd99.json").read_text())
    cfg.update(name="kmeans-small", points=8192, clusters=64)
    (tmp_path / "bench/configs/kmeans-small.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/lloyd3.json").write_text(json.dumps(
        {"kind": "kmeans", "iters_per_call": 3, "check_calls": 2}))
    (tmp_path / "bench/metrics/calls_per_s.kmeans.py").write_text(
        "def read(view):\n"
        "    return view.work['calls'] / view.work['window_s']\n")
    bench["configs"].append({"name": "kmeans-small", "source": "test",
                             "file": "bench/configs/kmeans-small.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "kmeans-small.lloyd3",
                               "config": "kmeans-small", "traffic": "lloyd3",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "lloyd_iter_ms":
            m["workloads"].append("kmeans-small.lloyd3")
    bench["per_layer"].append({"name": "calls_per_s.kmeans", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "K-Means workload (workloads/kmeans.py)",
                               "moves": "lloyd_iter_ms",
                               "workloads": ["kmeans-small.lloyd3"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("kmeans-small.lloyd3", root=tmp_path)
    assert cell.config["points"] == 8192
    assert cell.traffic["iters_per_call"] == 3
    assert [m["name"] for m in cell.per_layer] == ["calls_per_s.kmeans"]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                   "lloyd_iter_ms"}
    reader = spec.load_metric("calls_per_s.kmeans", root=tmp_path)
    from types import SimpleNamespace
    assert reader.read(SimpleNamespace(
        work={"calls": 6, "window_s": 2.0})) == 3.0
    after = {p: p.read_bytes() for p in before}
    assert after == before          # no file that was there changed


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.load_metric("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.load_kind("no such kind")
