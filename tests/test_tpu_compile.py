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

The K-Means call is also checked for its named scopes (the Lloyd phases
and the division unit, which the benchmark joins to a device trace by
``op_name``), for the chip and, where no TPU compiler is present, for the
CPU.
"""
import functools
import re
import sys
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.lib import kernels as bench_kernels, scopes  # noqa: E402


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
        """``shapes``: ``(shape, dtype)``, or ``(shape, dtype, sharding)``
        for an argument that is not on the one chip."""
        args = [jax.ShapeDtypeStruct(s, dt, sharding=sh[0] if sh else one_chip)
                for s, dt, *sh in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


F32, BF16, U32 = jnp.float32, jnp.bfloat16, jnp.uint32
PLANE = (4096, 1024)


@pytest.mark.parametrize("name,fn,shapes", [
    ("recip", ops.tsdiv_recip, [(PLANE, F32)]),
    ("divide", ops.tsdiv_divide, [(PLANE, F32), (PLANE, F32)]),
    ("divide_uniform", ops.tsdiv_divide_uniform, [(PLANE, F32), ((1, 1), F32)]),
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


# ------------------------------------------------------------ named scopes

# Instructions that hold or route values and do no work of their own.
NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
           "while", "conditional", "call"}
# What the compiler adds with no op metadata: copies between memory spaces,
# asynchronous slices and the custom call that joins their pieces.
INSERTED = {"copy", "copy-start", "copy-done", "slice-start", "slice-done",
            "custom-call"}
# The scan's own trip counter and its bound test, and copies into its
# carry made under the loop itself: loop control.
LOOP_CONTROL = re.compile(r"(^|/)while(/body/add|/cond/lt)?$")

_COMP = re.compile(r"^(ENTRY )?(%[^\s(]+) .*\{$")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def device_instructions(text):
    """``(name, opcode, line)`` of every instruction that runs as an
    operation of its own: those of the entry computation and of the
    computations a while, conditional or call runs (not fused bodies or
    reducers)."""
    comps, order, cur = {}, [], None
    for ln in text.split("\n"):
        m = _COMP.match(ln)
        if m:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                order.append(cur)
        elif ln == "}":
            cur = None
        elif cur is not None and " = " in ln:
            comps[cur].append(ln)
    run, out = set(), []
    while order:
        name = order.pop()
        if name in run:
            continue
        run.add(name)
        for ln in comps[name]:
            iname = ln.split(" = ", 1)[0].split()[-1]
            opcode = _OPCODE.search(ln.split(" = ", 1)[1]).group(1)
            out.append((iname, opcode, ln))
            if opcode in ("while", "conditional", "call"):
                order += re.findall(
                    r"(?:body|condition|to_apply|branch_computations|"
                    r"true_computation|false_computation)=\{?(%[^,}\s]+)",
                    ln)
    return out


def unscoped(text, inserted=INSERTED):
    """Instructions that do work and carry no ``kmeans.*`` scope, other
    than loop control and what the compiler adds with no metadata (of the
    opcodes in ``inserted``)."""
    paths = scopes.op_scopes(text)
    bad = []
    for name, opcode, line in device_instructions(text):
        if opcode in NO_WORK:
            continue
        path = paths.get(name)
        if path is None:
            if opcode not in inserted or "tpu_custom_call" in line:
                bad.append((name, opcode, None))
            continue
        if LOOP_CONTROL.search(path):
            continue
        if not scopes.under(path, ("kmeans\\.[a-z]+",)):
            bad.append((name, opcode, path))
    return bad


def _kmeans_call(n_iters):
    from repro.core import division_modes as dm
    from repro.workloads import kmeans as km

    cfg = dm.DivisionConfig(mode="taylor_pallas")

    def call(x, c):
        r = km.kmeans(x, cfg=cfg, n_iters=n_iters, init=c)
        return r.centroids, r.assignments, r.inertia

    return call


def test_kmeans_scopes_for_v5e(compiled_text):
    """Every operation of the Lloyd call on the chip sits in a phase's
    scope; every divide kernel in ``dm.div``, under the name the benchmark
    matches; the (N, K) plane's divides take the divisor unbroadcast, in
    the uniform-divisor scope, and no (N, K) plane is broadcast in
    ``dm.div``."""
    from repro.core import division_modes as dm

    n, k = 8192, 500
    text = compiled_text(_kmeans_call(2), ((n, 42), F32), ((k, 42), F32))
    assert unscoped(text) == []
    paths = scopes.op_scopes(text)
    ops_ = device_instructions(text)
    launches = [(nm, ln) for nm, op, ln in ops_
                if op == "custom-call" and "tpu_custom_call" in ln]
    assert len(launches) >= 3      # plane and centroid divides, final plane
    uniform = (re.escape(dm.SCOPE_UNIFORM),)
    planes = []
    for name, line in launches:
        assert name.startswith("%tsdiv_"), name
        assert bench_kernels.is_tsdiv(name), name
        assert scopes.under(paths[name], ("dm\\.div",)), paths[name]
        if f"= f32[{n},{k}]" in line:
            planes.append(name)
            assert scopes.under(paths[name], uniform), paths[name]
            assert scopes.under(paths[name], (scopes.DISTANCE,)), paths[name]
            assert f"f32[{n},{k}]" in line.split("custom-call(", 1)[1]
            assert "f32[1,1]" in line.split("custom-call(", 1)[1], line
        else:
            assert not scopes.under(paths[name], uniform), paths[name]
    assert len(planes) == 2        # the scan's plane and the final one
    broadcasts = [paths.get(nm, "") for nm, op, ln in ops_
                  if op == "broadcast" and f"= f32[{n},{k}]" in ln]
    assert not [p for p in broadcasts if scopes.under(p, ("dm\\.div",))]


def test_kmeans_sharded_scopes_for_v5e_mesh(topo, compiled_text):
    """The same on four described chips through ``kmeans_sharded``: the
    body's collectives sit in the phase that needs them."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import division_modes as dm
    from repro.sharding import rules as shr
    from repro.workloads import kmeans as km

    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    cfg = dm.DivisionConfig(mode="taylor_pallas")

    def call(x, c):
        with shr.use_mesh(mesh):
            r = km.kmeans_sharded(x, cfg=cfg, n_iters=2, init=c)
        return r.centroids, r.assignments, r.inertia

    text = compiled_text(
        call, ((4 * 8192, 42), F32, NamedSharding(mesh, P("data", None))),
        ((500, 42), F32, NamedSharding(mesh, P())))
    assert unscoped(text) == []
    paths = scopes.op_scopes(text)
    coll = [(op, paths[nm]) for nm, op, _ in device_instructions(text)
            if op in ("all-reduce", "all-gather")]
    assert {op for op, _ in coll} == {"all-reduce", "all-gather"}
    for op, path in coll:
        phases = ((scopes.UPDATE,) if op == "all-gather"
                  else (scopes.UPDATE, scopes.ASSIGN))
        assert scopes.under(path, phases), path


def test_kmeans_scopes_on_cpu():
    """The same scope check on the CPU backend (Pallas in interpret
    mode), which runs where no TPU compiler is installed."""
    x = jax.ShapeDtypeStruct((256, 42), F32)
    c = jax.ShapeDtypeStruct((16, 42), F32)
    text = jax.jit(_kmeans_call(2)).lower(x, c).compile().as_text()
    # The CPU compiler splits a reduction into a reduce-window fusion that
    # it gives no metadata.
    assert unscoped(text, INSERTED | {"fusion"}) == []
    paths = scopes.op_scopes(text)
    assert any(scopes.scopes_in(p)[-2:] == ["kmeans.update", "dm.div"]
               for p in paths.values())
    assert {s for p in paths.values() for s in scopes.scopes_in(p)} >= {
        "kmeans.distance", "kmeans.assign", "kmeans.update", "kmeans.final",
        "dm.div"}


def test_gqa_decode_attention_for_v5e(compiled_text):
    """Decode attention at Granite-8B's head widths (32 query heads over 8
    kv heads, head_dim 128) compiles for the chip without repeating the
    cache per query head, and its softmax is the unit's kernel under the
    decode scope."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.core import division_modes as dm
    from repro.models.attention import SCOPE_DECODE, decode_attention

    cfg = dataclasses.replace(
        get_smoke_config("llama3_8b"), n_heads=32, n_kv_heads=8,
        head_dim=128, d_model=4096,
        division=dm.DivisionConfig(mode="taylor_pallas"))
    b, L, bf16 = 8, 1024, jnp.bfloat16

    def step(wq, wk, wv, wo, k, v, x, pos):
        p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
        return decode_attention(p, x, {"k": k, "v": v}, pos, cfg)

    text = compiled_text(
        step, ((4096, 32, 128), bf16), ((4096, 8, 128), bf16),
        ((4096, 8, 128), bf16), ((32, 128, 4096), bf16),
        ((b, L, 8, 128), bf16), ((b, L, 8, 128), bf16),
        ((b, 1, 4096), bf16), ((b,), jnp.int32))
    assert f"[{b},{L},32,128]" not in text
    softmax = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln
               and f"{SCOPE_DECODE}/dm.softmax/" in ln]
    # one row of L per slot and query head, as before the grouping
    assert len(softmax) == 1 and f"f32[{b * 32},{L}]" in softmax[0]
