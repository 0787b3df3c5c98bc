"""chip_smoke.py off the chip: it refuses to report, and its phases run.

The script's platform check lives in ``main``; each phase takes its sizes
and ``require_kernel`` as arguments, so here the phases run at small sizes
on the CPU backend (Pallas in interpret mode, no ``tpu_custom_call`` to
find). Phase (d) needs several devices and runs in a subprocess that forces
four virtual ones.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core import division_modes as dm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load()


def _lines(out: str):
    return [json.loads(line) for line in out.splitlines()
            if line.startswith("{")]


def test_main_refuses_off_tpu(capsys):
    """No TPU: exit 1 and no result line, whatever the phases would say."""
    assert cs.main([]) == 1
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


@pytest.mark.parametrize("phase,kw", [
    ("phase_unit", {"shape": (64, 256)}),
    ("phase_kmeans", {"n_points": 4096, "n_iters": 3}),
    ("phase_serving", {"cfg": get_smoke_config("tinyllama_1_1b"),
                       "prompt_lens": (24, 17, 9, 5), "max_new": 4}),
])
def test_phase_passes_on_cpu(capsys, phase, kw):
    getattr(cs, phase)(require_kernel=False, **kw)
    lines = _lines(capsys.readouterr().out)
    assert lines and all(line["ok"] for line in lines), lines


def test_fallback_spy_records_refused_operands():
    """A taylor_pallas call the kernels cannot take is caught, not hidden."""
    cfg = dm.DivisionConfig(mode="taylor_pallas")
    with cs.FallbackSpy() as spy:
        dm.recip(jnp.ones((4, 8), jnp.float32), cfg)
        dm.recip(jnp.ones((4, 8), jnp.float16), cfg)
    assert spy.refused == [((4, 8), "float16")]


def test_phase_mesh_on_four_cpu_devices():
    """Phase (d): sharded K-Means and tiled divide bit-identical to one
    device, on a mesh of four virtual CPU devices."""
    snippet = (
        "import sys; sys.path.insert(0, '.')\n"
        "import chip_smoke as cs\n"
        "cs.phase_mesh(n_points=16384, shape=(512, 384), n_iters=3, "
        "require_kernel=False)\n")
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", snippet], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    lines = _lines(r.stdout)
    assert [line["case"] for line in lines] == ["tiled_divide", "kmeans"]
    assert all(line["ok"] and line["devices"] == 4 for line in lines), lines
