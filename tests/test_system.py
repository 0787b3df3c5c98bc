"""End-to-end behaviour tests: serving engine, examples, dry-run subprocess."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_serving_engine_generates():
    cfg = get_smoke_config("paper_fpdiv")
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_len=128)
    out = engine.generate(list(range(1, 17)), max_new=8)
    assert len(out) == 8
    assert all(0 <= t < cfg.vocab for t in out)


def test_serving_batched_matches_single():
    """Static batching: a batch of identical prompts decodes identically to
    the single-request path (greedy, deterministic)."""
    cfg = get_smoke_config("paper_fpdiv")
    params = init_params(cfg, jax.random.PRNGKey(3))
    engine = ServingEngine(cfg, params, max_len=96)
    single = engine.generate(list(range(1, 17)), max_new=6)
    batch = engine.generate_batch([list(range(1, 17))] * 3, max_new=6)
    assert all(b == single for b in batch)


def test_serving_greedy_deterministic():
    cfg = get_smoke_config("tinyllama_1_1b")
    params = init_params(cfg, jax.random.PRNGKey(1))
    engine = ServingEngine(cfg, params, max_len=64)
    a = engine.generate([5, 6, 7, 8], max_new=6)
    b = engine.generate([5, 6, 7, 8], max_new=6)
    assert a == b


def _run(cmd, timeout=900):
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=ROOT)


def test_quickstart_example():
    r = _run([sys.executable, "examples/quickstart.py"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "reciprocal" in r.stdout


@pytest.mark.slow
def test_dryrun_single_cell_subprocess(tmp_path):
    """The multi-pod deliverable, smoke-sized: one full 512-device cell."""
    r = _run([sys.executable, "-m", "repro.launch.dryrun",
              "--arch", "whisper_tiny", "--shape", "decode_32k",
              "--mesh", "multi", "--out", str(tmp_path)], timeout=1800)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[ok]" in r.stdout
