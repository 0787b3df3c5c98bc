"""The hybrid serving kind (bench/kinds/serve_hybrid.py), its readers and
its cells.

The configuration file maps onto the program's config or refuses; the
per-program scope attribution reads colliding instruction names in the
program that ran them; each new reader reads a recorded synthetic trace
and reads nothing (None, no error) from a program that has no scopes;
the new cell resolves; and a run with the timed path broken underneath
comes out not correct: a served token altered in the prefill and in the
decode step, a decode step that returns its state unchanged or not a
number, and half of the slots handed the other half's logits.
"""
import copy
import dataclasses
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from bench import run as bench_run  # noqa: E402
from bench.kinds import serve_hybrid as sh  # noqa: E402
from bench.lib import programs, spec  # noqa: E402
from bench.lib import trace as tr  # noqa: E402
from bench.lib.context import Ctx  # noqa: E402

CELL = "granite-4.0-h-small-chat"
FILE = json.loads((ROOT / "bench/configs/granite-4.0-h-small.json")
                  .read_text())


# ---------------------------------------------------------- configuration

def test_the_file_maps_onto_the_program():
    cfg = sh.model_config(FILE)
    assert cfg.experts_held == (0, 9) and cfg.n_experts == 72
    assert cfg.n_layers == 10 and cfg.d_model == 4096
    assert [s.mixer for s in cfg.layer_specs()].count("attn") == 1
    assert all(s.ffn == "moe" for s in cfg.layer_specs())


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 4095), ("intermediate_size", 512),
    ("mamba_d_state", 64), ("mamba_d_head", 32),
    ("published_num_local_experts", 64), ("num_experts_per_tok", 8),
    ("layer_types", ["mamba"] * 10), ("position_embedding_type", "rope"),
    ("logits_scaling", 8)])
def test_the_mapping_refuses_a_mismatch(key, value):
    with pytest.raises(ValueError, match=key):
        sh.model_config(dict(FILE, **{key: value}))


def test_the_new_cell_resolves():
    hyb = spec.load_cell(CELL)
    assert hyb.config["kind"] == hyb.traffic["kind"] == "serve_hybrid"
    assert {m["name"] for m in hyb.end_to_end} >= {"setup_s",
                                                   "serve_tok_per_s"}
    assert {m["name"] for m in hyb.per_layer} == {
        "mamba_share.hybrid", "moe_share.hybrid",
        "mamba_decode_roofline.hybrid", "moe_roofline.hybrid",
        "device_idle_share.serve", "unit_share.serve",
        "prefill_stall_share.serve"}
    assert set(bench_run.load_limits(CELL)) == set(sh.CHECKS)


# ------------------------------------------------------------- the readers

def hlo(*instrs, module="jit_decode"):
    body = "\n".join(
        f"  %{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, "
        f"calls=%fc, metadata={{op_name=\"jit(step)/{path}\"}}"
        for name, path in instrs)
    return (f"HloModule {module}, is_scheduled=true\n\n"
            f"ENTRY %main {{\n{body}\n}}\n")


# ``%fusion.1`` is a Mamba step in the decode program and the shared
# expert in the prefill program: read by name alone, the prefill's time
# would count as Mamba.
DECODE = hlo(("fusion.1", "mamba.mixer/mamba.step/mul"),
             ("fusion.2", "moe.experts/dot_general"))
PREFILL = hlo(("fusion.1", "moe.shared/dot_general"),
              ("fusion.9", "mamba.mixer/mamba.scan/exp"),
              module="jit_prefill_tokens")
RECORDED = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_decode(1)", 0, 1000],
                                           ["jit_prefill_tokens(2)", 2000,
                                            1000]]},
        {"name": "XLA Ops", "events": [["%fusion.1", 100, 400],
                                       ["%fusion.2", 500, 300],
                                       ["%fusion.1", 2100, 600],
                                       ["%fusion.9", 2700, 200]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["bench.window", 0, 4000]]}]}]}
SMALL = dict(FILE, hidden_size=64, intermediate_size=16,
             shared_intermediate_size=32, num_local_experts=2,
             published_num_local_experts=16, mamba_n_heads=4,
             mamba_d_head=8, mamba_d_state=8, num_hidden_layers=10)
WORK = {"slots": 3, "decode_steps": 2, "prompt_lens": [40],
        "window_s": 4e-6}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def view(work=None, recorded=RECORDED):
    progs = [programs.Program.of("decode", DECODE),
             programs.Program.of("prefill256", PREFILL)]
    w = dict(WORK, programs=progs,
             module_spans=programs.module_spans(recorded))
    return SimpleNamespace(config=SMALL, traffic={}, chips=1,
                           work=w if work is None else work,
                           trace=tr.Trace(recorded), peaks=PEAKS)


def reader(name):
    return spec.load_metric(name).read


def test_programs_read_colliding_names_in_their_own_program():
    v = view()
    rows = programs.attribute(v.trace, v.work["module_spans"],
                              v.work["programs"])
    got = [(label, path.split("/")[-2]) for label, path, _, _ in
           rows["/device:TPU:0"]]
    assert got == [("decode", "mamba.step"), ("decode", "moe.experts"),
                   ("prefill256", "moe.shared"), ("prefill256", "mamba.scan")]


def test_a_span_is_read_in_the_program_its_module_names():
    # the decode span holds only %fusion.1, which both programs hold, and
    # the prefill's %fusion.9: by overlap alone it would read as a prefill
    recorded = copy.deepcopy(RECORDED)
    ops = recorded["planes"][0]["lines"][1]["events"]
    ops[1] = ["%fusion.9", 500, 300]
    v = view(recorded=recorded)
    rows = programs.attribute(v.trace, v.work["module_spans"],
                              v.work["programs"])
    assert [label for label, _, _, _ in rows["/device:TPU:0"]] == [
        "decode", "decode", "prefill256", "prefill256"]


def test_prefill_shapes_that_share_a_module_name_go_by_overlap():
    longer = hlo(("fusion.1", "moe.shared/dot_general"),
                 ("fusion.7", "mamba.mixer/mamba.scan/exp"),
                 module="jit_prefill_tokens")
    progs = [programs.Program.of("decode", DECODE),
             programs.Program.of("prefill256", PREFILL),
             programs.Program.of("prefill512", longer)]
    assert [p.module for p in progs] == ["jit_decode", "jit_prefill_tokens",
                                         "jit_prefill_tokens"]
    v = view()
    rows = programs.attribute(v.trace, v.work["module_spans"], progs)
    assert [label for label, _, _, _ in rows["/device:TPU:0"]] == [
        "decode", "decode", "prefill256", "prefill256"]


def test_share_readers_on_a_synthetic_trace():
    v = view()
    assert v.trace.busy_s == pytest.approx(1500e-9)
    assert reader("mamba_share.hybrid")(v) == pytest.approx(40.0)
    assert reader("moe_share.hybrid")(v) == pytest.approx(60.0)
    assert reader("device_idle_share.serve")(v) == pytest.approx(62.5)


def test_roofline_readers_on_a_synthetic_trace():
    v = view()
    mod = spec.load_metric("mamba_decode_roofline.hybrid")
    need = mod.step_bytes(SMALL, 3) * 2
    assert mod.read(v) == pytest.approx(100 * need / 819e9 / 400e-9)
    moe = spec.load_metric("moe_roofline.hybrid")
    per_layer = 3 * 64 * 16 * 2 * 2 + 3 * 64 * 32 * 2 + 64 * 16 * 4
    calls, tokens = 2 + 1, 2 * 3 + 40
    need = 10 * (calls * per_layer + tokens * 64 * 2 * 2)
    assert moe.needed_bytes(SMALL, v.work) == need
    assert moe.read(v) == pytest.approx(100 * need / 819e9 / 900e-9)


def test_modules_line_left_out_reads_by_name_in_program_order():
    recorded = {"planes": [
        {"name": p["name"], "lines": [ln for ln in p["lines"]
                                      if ln["name"] != "XLA Modules"]}
        for p in RECORDED["planes"]]}
    v = view(recorded=recorded)
    # every %fusion.1 now read in the decode program, the first listed
    assert reader("mamba_share.hybrid")(v) == pytest.approx(
        100 * 1200 / 1500)


@pytest.mark.parametrize("name", ["mamba_share.hybrid", "moe_share.hybrid",
                                  "mamba_decode_roofline.hybrid",
                                  "moe_roofline.hybrid"])
def test_readers_read_nothing_from_a_program_without_scopes(name):
    plain = [programs.Program.of("decode", hlo(("fusion.1", "mul"),
                                               ("fusion.2", "dot")))]
    v = view()
    assert reader(name)(view(work=dict(WORK))) is None
    assert reader(name)(view(work=dict(v.work, programs=plain))) is None
    assert reader(name)(SimpleNamespace(**dict(vars(v), trace=None))) is None


# ------------------------------------------------------------------ faults

def small_cell(monkeypatch):
    """The cell's file and mix at the smoke size (float32, so that a sound
    run reads rounding alone), prompts of one padded shape."""
    from repro.configs import get_smoke_config
    from repro.core.division_modes import DivisionConfig

    small = dataclasses.replace(get_smoke_config("granite_4_0_h_small"),
                                experts_held=(0, 2), param_dtype="float32")
    monkeypatch.setattr(sh, "model_config", lambda c: dataclasses.replace(
        small, division=DivisionConfig(mode=c["division"])))
    cell = spec.load_cell(CELL)
    cell.traffic = dict(
        cell.traffic, clients=4, max_len=64, block=8,
        prompt={"dist": "lognormal", "median": 12, "sigma": 0.4, "min": 8,
                "max": 16, "round_to": 8},
        output={"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2,
                "max": 24, "round_to": 1},
        ramp_decode_steps=8, queue_rate_bound=400, ramp_bound_s=5)
    return cell


def altered_prefill(real):
    def call(cfg, params, tokens, **kw):
        last, cache = real(cfg, params, tokens, **kw)
        return last.at[:, 0].set(last.max(axis=-1) + 1.0), cache
    return call


def altered_decode(real):
    def call(cfg, params, cache, tokens, pos):
        logits, cache = real(cfg, params, cache, tokens, pos)
        return logits.at[:, 0].set(logits.max(axis=-1) + 1.0), cache
    return call


def unchanged_state(real):
    """A step that returns its state unchanged: the decode step's logits,
    but the cache it was given."""
    def call(cfg, params, cache, tokens, pos):
        logits, _ = real(cfg, params, cache, tokens, pos)
        return logits, cache
    return call


def half_of_the_slots(real):
    def call(cfg, params, cache, tokens, pos):
        logits, cache = real(cfg, params, cache, tokens, pos)
        half = logits.shape[0] // 2
        return logits.at[-half:].set(logits[:half]), cache
    return call


def nan_state(real):
    """A step whose SSM states come back not a number: the check must not
    pass over a nan."""
    def call(cfg, params, cache, tokens, pos):
        logits, cache = real(cfg, params, cache, tokens, pos)
        return logits, jax.tree_util.tree_map(
            lambda a: a * float("nan") if a.ndim == 4 else a, cache)
    return call


FAULTS = {"prefill": ("prefill", altered_prefill),
          "decode_step": ("decode_step", altered_decode),
          "unchanged_state": ("decode_step", unchanged_state),
          "half_batch": ("decode_step", half_of_the_slots),
          "nan_state": ("decode_step", nan_state)}


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_hybrid_fault_is_not_correct(monkeypatch, fault):
    from repro.serving import engine
    if fault is not None:
        name, make = FAULTS[fault]
        monkeypatch.setattr(engine, name, make(getattr(engine, name)))
    cell = small_cell(monkeypatch)
    ctx = Ctx(cell=cell, seed=2300000011, seconds=1.5, trace=False,
              t0=time.perf_counter(), devices=jax.devices()[:1],
              require_kernel=False, limits=bench_run.load_limits(CELL))
    out = sh.run(ctx)
    assert out.work["admitted"] > 0
    assert out.correct == (fault is None), [
        (c.name, c.value, c.limit) for c in out.checks]


# One wrong row in the state of a request in flight, at the cell's real
# shapes: ``state_gap`` is a root mean square over a layer's rows, so a row
# at distance d from the reference reads about d/sqrt(rows). The SSM state
# has 128 rows (heads), the conv state 3 (positions), the attention layer's
# keys and values one per position held (1,024 here). Requests in flight
# are checked through their state alone, so a zeroed row (d = 1) is caught
# in the conv state only, and a row in the SSM state or the keys only
# where d passes the limit times sqrt(rows).
ONE_ROW = [("state", (128, 64, 128), False), ("conv", (3, 10240), True),
           ("k", (1024, 8, 128), False)]


@pytest.mark.parametrize("kind,shape,zeroed_caught", ONE_ROW)
def test_one_wrong_row_of_a_state_in_flight(kind, shape, zeroed_caught):
    import numpy as np

    limit = bench_run.load_limits(CELL)["state_gap"]
    rng = np.random.default_rng(7)
    ref = rng.normal(size=shape).astype(np.float32)
    sound = ref + 1e-3 * rng.normal(size=shape).astype(np.float32)
    assert sh.state_gap([{kind: sound}], [{kind: ref}]) < limit

    zeroed = sound.copy()
    zeroed[1] = 0.0
    gap = sh.state_gap([{kind: zeroed}], [{kind: ref}])
    assert gap == pytest.approx(shape[0] ** -0.5, rel=0.05)
    assert (gap > limit) == zeroed_caught

    # a row at distance 1.1 x limit x sqrt(rows) is caught
    far = sound.copy()
    far[1] = ref[1] * (1.0 + 1.1 * limit * shape[0] ** 0.5)
    assert sh.state_gap([{kind: far}], [{kind: ref}]) > limit
