"""The main-path kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) runs the kernel bodies through
XLA's CPU backend, so it cannot see what Mosaic refuses: unsigned min/max,
unaligned slices, more VMEM than a kernel may use. Here each wrapper is
compiled for one chip of a described, unattached ``v5e:2x2`` topology with
the interpreter off, and the compiled program must hold the kernel
(``tpu_custom_call``). Nothing runs: these tests say nothing about results
or times.

The topology is described inside a module-scoped fixture, never while a
module is imported, and every test skips where it cannot be described.
"""
import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001  (any failure means: no compiler)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_text(one_chip, monkeypatch):
    """Compile ``fn`` for one described chip; returns the compiled text.

    The persistent compilation cache is off around these compiles: an entry
    written for a described chip cannot be read back without one.
    """
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(ops, "INTERPRET", False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


F32, BF16, U32 = jnp.float32, jnp.bfloat16, jnp.uint32
PLANE = (4096, 1024)


@pytest.mark.parametrize("name,fn,shapes", [
    ("recip", ops.tsdiv_recip, [(PLANE, F32)]),
    ("divide", ops.tsdiv_divide, [(PLANE, F32), (PLANE, F32)]),
    ("rsqrt", ops.tsdiv_rsqrt, [(PLANE, F32)]),
    ("softmax", ops.softmax, [((8, 12, 512, 512), F32)]),
    # 1500 lanes pad to 1536, and the row sum's tree pads those to 2048.
    ("softmax_ragged", ops.softmax, [((64, 1500), F32)]),
    ("rmsnorm", ops.rmsnorm, [((8, 512, 2048), F32), ((2048,), F32)]),
    ("flash_attention_f32",
     functools.partial(ops.flash_attention, causal=True),
     [((8, 32, 512, 64), F32)] * 3),
    ("flash_attention_bf16",
     functools.partial(ops.flash_attention, causal=True),
     [((8, 32, 512, 64), BF16)] * 3),
    ("ilm_mul", ops.ilm_mul, [(PLANE, U32), (PLANE, U32)]),
])
def test_kernel_compiles_for_v5e(compiled_text, name, fn, shapes):
    assert "tpu_custom_call" in compiled_text(fn, *shapes), name
