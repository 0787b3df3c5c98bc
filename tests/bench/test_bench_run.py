"""bench/run.py refuses to report anything that does not stand for the
chip: off a TPU, under the Pallas interpreter, on an unknown device kind,
without the Mosaic kernel, and from a checkout without the program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import device  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _load_run():
    import importlib.util
    s = importlib.util.spec_from_file_location("bench_run_entry",
                                               ROOT / "bench" / "run.py")
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m


def test_refuses_off_a_tpu(capsys):
    run = _load_run()
    rc = run.main(["--workload", BENCH["workloads"][0]["name"],
                   "--seed", "1", "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 1 and out == "" and "no TPU" in err


def test_refuses_unknown_device_kind_and_interpreter():
    with pytest.raises(device.Refused):
        device.load_peaks("TPU v99 imaginary")
    assert device.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(device.Refused):       # the CPU backend interprets
        device.require_compiled_kernels()


def test_refuses_a_program_without_its_kernel():
    import jax
    import jax.numpy as jnp
    compiled = jax.jit(lambda x: x / 3.0).lower(jnp.ones(8)).compile()
    with pytest.raises(device.Refused):
        device.check_kernel(compiled, "plain divide")


def test_refuses_a_jnp_fallback():
    import jax.numpy as jnp
    from repro.core import division_modes as dm
    with device.FallbackSpy() as spy:
        dm.div(jnp.zeros((0, 4), jnp.float32), jnp.float32(2.0),
               dm.DivisionConfig(mode="taylor_pallas"))
    assert spy.refused


def test_fails_with_only_the_benchmark_files(tmp_path):
    """A checkout holding BENCHMARK.json and the benchmark's paths alone:
    non-zero exit and no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "Error" in r.stderr or "bench:" in r.stderr


def test_memory_peak_counts_the_programs_temporaries():
    """The reported peak is at least what the timed program holds while it
    runs (arguments, outputs and temporaries), whatever the runtime's
    counter says."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((256, 256), jnp.float32)
    compiled = jax.jit(lambda a: (a @ a.T).sum(0)).lower(x).compile()
    need = device.program_bytes(compiled)
    assert need >= x.nbytes + 256 * 4
    got = device.memory_peak_bytes(jax.devices()[:1], [compiled])
    assert got is not None and got >= need
