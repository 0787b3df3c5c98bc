"""CLI exit codes + degenerate-operand robustness for the public surface.

  (a) ``repro.eval.conformance`` exits non-zero when any grid cell fails
      its gate (edge-contract violation or a blown eq. 17 bound), so CI
      can consume the run directly;
  (b) ``repro.eval.golden --check`` exits non-zero on drift or a missing
      store, for every store including the new rsqrt one;
  (c) every public op (recip / div / rsqrt / softmax) accepts empty,
      rank-0, and bf16 scalar operands in every mode without crashing —
      extending the PR 3 empty-operand fix beyond divide.
  (d) ``repro.launch.serve`` routes ``--batch`` through the batched path,
      honours the division-mode flags, and rejects unknown modes.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import division_modes as dm
from repro.eval import conformance, golden

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(args, timeout=600):
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", *args],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=ROOT)


# ------------------------------------------------------------- exit codes

def _fake_cell(**over):
    cell = {
        "op": "recip", "mode": "taylor", "schedule": "factored",
        "n_iters": 2, "precision_bits": 24, "dtype": "float32",
        "key": "recip/taylor/factored/n2p24/float32", "underflow": "gradual",
        "overall": {"max_ulp": 0.5, "mean_ulp": 0.2, "p99_ulp": 0.4, "n": 10},
        "strata": {}, "edge_failures": 0, "seconds": 0.0,
    }
    cell.update(over)
    cell["pass"] = conformance.cell_gate(cell)
    return cell


def test_cell_gate_verdicts():
    assert _fake_cell()["pass"] is True
    assert _fake_cell(edge_failures=3)["pass"] is False
    assert _fake_cell(overall={"max_ulp": 3.0, "mean_ulp": 1.0,
                               "p99_ulp": 2.0, "n": 10})["pass"] is False
    # The loose end of the dial and ILM are not ULP-gated.
    assert _fake_cell(n_iters=1, overall={"max_ulp": 4000.0, "mean_ulp": 9.0,
                                          "p99_ulp": 100.0, "n": 10})["pass"]
    assert _fake_cell(mode="ilm", overall={"max_ulp": 1e4, "mean_ulp": 100.0,
                                           "p99_ulp": 1e3, "n": 10})["pass"]
    assert _fake_cell(overall={"max_ulp": float("inf"), "mean_ulp": 0.1,
                               "p99_ulp": 0.1, "n": 10})["pass"] is False


def test_conformance_main_exit_codes(monkeypatch, capsys):
    def fake_run(cells=None, quick=False, seed=0, **kw):
        return {"meta": {}, "cells": [_fake_cell()]}

    monkeypatch.setattr(conformance, "run_conformance", fake_run)
    assert conformance.main(["--quick"]) == 0

    def fake_run_bad(cells=None, quick=False, seed=0, **kw):
        return {"meta": {}, "cells": [_fake_cell(), _fake_cell(edge_failures=1)]}

    monkeypatch.setattr(conformance, "run_conformance", fake_run_bad)
    assert conformance.main(["--quick"]) == 1
    out = capsys.readouterr().out
    assert "CONFORMANCE FAILURES" in out


@pytest.mark.parametrize("backend,refused", [("tpu", True), ("cpu", False)])
def test_conformance_fanout_only_off_tpu(monkeypatch, capsys, backend,
                                         refused):
    """--fanout starts one JAX process per shard: refused on a TPU, whose
    chip belongs to one process; dispatched on the CPU backend."""
    import jax

    calls = []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(conformance, "_run_fanout",
                        lambda args, n: calls.append(n) or 0)
    if refused:
        with pytest.raises(SystemExit) as e:
            conformance.main(["--quick", "--fanout", "2"])
        assert e.value.code == 2 and calls == []
        assert "cannot share a TPU" in capsys.readouterr().err
    else:
        assert conformance.main(["--quick", "--fanout", "2"]) == 0
        assert calls == [2]


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache is the fixed .jax_cache/ at the root of the checkout."""
    import pathlib

    import jax

    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    got = compile_cache.enable_compile_cache()
    if env_dir is None:
        root = pathlib.Path(__file__).resolve().parent.parent
        assert got == str(root / ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", got)]
    else:
        assert got == env_dir and updates == []


def test_golden_main_nonzero_on_failure(monkeypatch, capsys):
    monkeypatch.setattr(golden, "check_rsqrt",
                        lambda **kw: [{"cell": "rsqrt/taylor/newton2",
                                       "n_mismatch": 1, "max_ulp_drift": 7}])
    assert golden.main(["--check", "--store", "rsqrt"]) == 1
    assert "GOLDEN-VECTOR REGRESSION" in capsys.readouterr().out


def test_golden_check_missing_store_fails(tmp_path):
    """Every store reports a missing file as a named failure (exit 1 via
    main), never an unhandled exception."""
    for fn in (golden.check, golden.check_divide, golden.check_rsqrt):
        failures = fn(path=tmp_path / "nope.npz")
        assert failures and "missing" in failures[0]["error"], fn.__name__


def test_golden_store_choices_include_rsqrt(capsys):
    with pytest.raises(SystemExit):
        golden.main(["--check", "--store", "bogus"])
    capsys.readouterr()


# ------------------------------------------------------------- serve CLI

def test_serve_cli_single_path():
    r = _run_cli(["--arch", "paper_fpdiv", "--smoke", "--batch", "1",
                  "--prompt-len", "12", "--max-new", "4"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "generated 4 tokens" in r.stdout
    assert "tok/s" in r.stdout
    assert "division=taylor" in r.stdout  # config default


def test_serve_cli_batched_with_division_flags():
    r = _run_cli(["--arch", "paper_fpdiv", "--smoke", "--batch", "3",
                  "--prompt-len", "14", "--max-new", "4",
                  "--division-mode", "goldschmidt", "--n-iters", "3",
                  "--schedule", "factored"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "division=goldschmidt" in r.stdout
    assert "n_iters=3" in r.stdout
    assert r.stdout.count("generated 4 tokens") == 3  # the batched path ran


def test_serve_cli_rejects_unknown_mode():
    r = _run_cli(["--arch", "paper_fpdiv", "--smoke",
                  "--division-mode", "bogus"], timeout=120)
    assert r.returncode != 0
    assert "invalid choice" in r.stderr


# ---------------------------------------------- degenerate-operand matrix

DEGENERATE = [
    ("empty", lambda: jnp.zeros((0,), jnp.float32)),
    ("empty2d", lambda: jnp.zeros((2, 0), jnp.float32)),
    ("rank0_f32", lambda: jnp.float32(2.5)),
    ("rank0_bf16", lambda: jnp.bfloat16(2.5)),
]


@pytest.mark.parametrize("mode", list(dm.MODES))
@pytest.mark.parametrize("case,make", DEGENERATE)
def test_public_ops_accept_degenerate_operands(mode, case, make):
    """recip/div/rsqrt/softmax: empty, rank-0 and bf16 scalars round-trip
    shape and dtype in every mode (no kernel launch on zero lanes, no
    reduction over an empty softmax axis, no rank assumptions)."""
    cfg = dm.DivisionConfig(mode=mode)
    x = make()
    r = dm.recip(x, cfg)
    assert r.shape == x.shape and r.dtype == x.dtype
    q = dm.div(x, x, cfg)
    assert q.shape == x.shape and q.dtype == x.dtype
    s = dm.rsqrt(x, cfg)
    assert s.shape == x.shape and s.dtype == x.dtype
    sm = dm.softmax(x, cfg=cfg)
    assert sm.shape == x.shape and sm.dtype == x.dtype


def test_degenerate_values_are_sane():
    """Beyond not crashing: rank-0 results carry the right values."""
    for mode in ("taylor", "taylor_pallas", "goldschmidt", "exact"):
        cfg = dm.DivisionConfig(mode=mode)
        assert abs(float(dm.recip(jnp.float32(4.0), cfg)) - 0.25) < 1e-6
        assert abs(float(dm.div(jnp.float32(6.0), jnp.float32(3.0), cfg))
                   - 2.0) < 1e-6
        assert abs(float(dm.rsqrt(jnp.float32(4.0), cfg)) - 0.5) < 1e-6
        assert float(dm.softmax(jnp.float32(3.0), cfg=cfg)) == 1.0
        bf = dm.div(jnp.bfloat16(1.0), jnp.bfloat16(3.0), cfg)
        assert bf.dtype == jnp.bfloat16
        assert abs(float(bf) - 1 / 3) < 0.01
