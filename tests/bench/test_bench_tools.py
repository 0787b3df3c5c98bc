"""The tools that set a cell's bounds and limits: ``sets.py`` summarizes
saved runs as it summarizes the runs it makes, and ``limits.py`` sets a
limit for every serving check between the program's worst reading and the
control's best."""
import json
import statistics
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.tools import limits, sets  # noqa: E402


def test_sets_summarize_saved_runs(tmp_path, capsys):
    vals = {0: [100.0, 101.0, 102.0, 103.0, 104.0, 90.0],
            1: [100.0, 100.5, 101.0, 101.5, 102.0, 102.5]}
    rows = [{"set": s, "seed": i, "trace": 0, "rc": 0,
             "result": {"metrics": {"serve_tok_per_s": {"value": v}}}}
            for s, vs in vals.items() for i, v in enumerate(vs)]
    rows.append({"seed": 9, "trace": 1, "rc": 0,
                 "result": {"metrics": {"serve_mfu": {"value": 3.0}}}})
    path = tmp_path / "runs.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert sets.main(["--summarize", str(path)]) == 0
    out = capsys.readouterr().out
    want = []
    for v in vals.values():
        q = statistics.quantiles(v, n=4)
        want.append((q[2] - q[0]) / statistics.median(v))
    assert f"widest spread {max(want):.5f}, five times {5 * max(want):.5f}" \
        in out
    assert "serve_mfu" not in out          # traced runs belong to no set


def test_limits_for_every_serving_check():
    rows = [{"seed": s, "program": {"logit_gap": 0.01 * s, "cache_gap": 0.011},
             "control": {"logit_gap": 2.0 + s, "cache_gap": 0.5}}
            for s in (1, 2, 3)]
    rows += [{"seed": s, "program": {"logit_gap": 0.02, "cache_gap": 0.012}}
             for s in (4, 5)]
    out, unseparated = limits.limits(rows)
    assert not unseparated
    assert out["logit_gap"]["lower"] == pytest.approx(0.03)
    assert out["logit_gap"]["upper"] == pytest.approx(3.0)
    assert out["cache_gap"]["lower"] == pytest.approx(0.012)
    for e in out.values():
        assert e["lower"] < e["limit"] < e["upper"]
        assert e["limit"] == pytest.approx(
            e["lower"] * (e["upper"] / e["lower"]) ** (2 / 3))
    assert out["cache_gap"]["program_seeds"] == 5
    assert out["cache_gap"]["control_seeds"] == 3
