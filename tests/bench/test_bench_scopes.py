"""The join of a device trace to the program's named scopes, and the
readers that split the K-Means cell's device time by them."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import scopes, spec, trace as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("unit_operand_share.kmeans", "lloyd_plane_share.kmeans",
       "lloyd_update_share.kmeans")
OLD = ("lloyd_mfu", "tsdiv_roofline.kmeans", "unit_share.kmeans",
       "device_idle_share.kmeans")

# A trimmed compiled program in the form ``compiled.as_text()`` prints:
# a fused body whose inner instructions carry paths too, a fusion, the
# divide kernel, an instruction the compiler added without metadata, and
# a root tuple.
HLO = '''HloModule jit_call, is_scheduled=true

%fused_computation.45 (param_0.113: f32[64,42], param_1.98: f32[8,42]) -> f32[64,8] {
  %param_0.113 = f32[64,42]{1,0} parameter(0)
  %convolution.4 = f32[64,8]{1,0} convolution(%param_0.113, %param_1.98), dim_labels=bf_oi->bf, metadata={op_name="jit(call)/while/body/closed_call/kmeans.distance/dot_general" stack_frame_id=20}
  ROOT %maximum.3 = f32[64,8]{1,0} maximum(%convolution.4, %broadcast.9), metadata={op_name="jit(call)/while/body/closed_call/kmeans.distance/max" stack_frame_id=21}
}

ENTRY %main.18 (x.1: f32[64,42], c.1: f32[8,42]) -> (f32[64,8]) {
  %x.1 = f32[64,42]{1,0} parameter(0), metadata={op_name="x"}
  %fusion.87 = f32[64,8]{1,0:T(8,128)} fusion(%x.1, %c.1), kind=kOutput, calls=%fused_computation.45, metadata={op_name="jit(call)/while/body/closed_call/kmeans.distance/max" stack_frame_id=21}
  %broadcast_in_dim.45 = f32[64,8]{1,0:T(8,128)S(1)} broadcast(%constant.30), dimensions={}, metadata={op_name="jit(call)/while/body/closed_call/kmeans.distance/dm.div/broadcast_in_dim" stack_frame_id=26}
  %tsdiv_divide_tiled_2d.12 = f32[64,8]{1,0:T(8,128)S(1)} custom-call(%fusion.87, %broadcast_in_dim.45), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(call)/while/body/closed_call/kmeans.distance/dm.div/jit(tsdiv_divide_tiled_2d)/pallas_call" stack_frame_id=54}, backend_config={"custom_call_config":{"body":"TUzvUg=="}}
  %copy-start = (f32[8,128]{1,0:T(8,128)S(1)}, f32[8,128]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%constant.28)
  ROOT %tuple.16 = (f32[64,8]{1,0}) tuple(%tsdiv_divide_tiled_2d.12)
}
'''


def test_op_scopes_of_a_compiled_text():
    got = scopes.op_scopes(HLO)
    body = "jit(call)/while/body/closed_call/"
    assert got == {
        "%convolution.4": body + "kmeans.distance/dot_general",
        "%maximum.3": body + "kmeans.distance/max",
        "%x.1": "x",
        "%fusion.87": body + "kmeans.distance/max",
        "%broadcast_in_dim.45": body + "kmeans.distance/dm.div/broadcast_in_dim",
        "%tsdiv_divide_tiled_2d.12":
            body + "kmeans.distance/dm.div/jit(tsdiv_divide_tiled_2d)/pallas_call",
    }
    assert scopes.scopes_in(got["%tsdiv_divide_tiled_2d.12"]) == [
        "kmeans.distance", "dm.div"]
    assert scopes.scopes_in(got["%x.1"]) == []
    assert scopes.under(got["%broadcast_in_dim.45"], (scopes.UNIT,))
    assert not scopes.under(got["%fusion.87"], (scopes.UNIT,))
    assert not scopes.under("jit(call)/kmeans.distances/add",
                            (scopes.DISTANCE,))


# ------------------------------------------------------ hand-made trace

HANDMADE_SCOPES = {
    "%fusion.1": "jit(call)/kmeans.distance/max",
    "%tsdiv_divide_tiled_2d.3":
        "jit(call)/kmeans.distance/dm.div/jit(tsdiv_divide_tiled_2d)/pallas_call",
    "%fusion.2": "jit(call)/kmeans.distance/dm.div/broadcast_in_dim",
    "%all-reduce.1": "jit(call)/kmeans.update/psum",
    "%fusion.3": "jit(call)/kmeans.update/add",
    "%softmax_2d.7": "jit(call)/dm.softmax/jit(softmax_2d)/pallas_call",
    "%no-such-op.9": "jit(call)/kmeans.assign/argmin",
}


def handmade(scope_map=None):
    rec = json.loads((DATA / "trace_handmade.json").read_text())
    if scope_map is not None:
        rec = scopes.attach(rec, scope_map)
    return tr.Trace(rec)


def test_attach_scopes_keeps_what_ran():
    rec = scopes.attach(json.loads(
        (DATA / "trace_handmade.json").read_text()), HANDMADE_SCOPES)
    assert "%no-such-op.9" not in rec["scopes"]
    assert set(rec["scopes"]) == set(HANDMADE_SCOPES) - {"%no-such-op.9"}
    # the trace reduction reads a recording with a map as it reads one
    # without
    t, bare = tr.Trace(rec), handmade()
    assert (t.busy_s, t.window_s, t.top_ops(None)) \
        == (bare.busy_s, bare.window_s, bare.top_ops(None))


def test_scoped_seconds_by_hand():
    """Device 0: fusion.1 [1000,2000], tsdiv [2000,5000], fusion.2
    [4500,5500], all-reduce [7000,8000], fusion.3 [7500,8500], softmax
    [9500,10000]; device 1: fusion.1 [1000,3000], all-reduce
    [6000,8000], tsdiv [9000,10500] (clipped); means over the two."""
    t, m = handmade(), HANDMADE_SCOPES
    ns = 1e-9
    assert scopes.scoped_s(t, m, (scopes.DISTANCE,), (scopes.UNIT,)) \
        == pytest.approx((1000 + 2000) / 2 * ns)
    assert scopes.scoped_s(t, m, (scopes.UNIT,), unit_kernels=False) \
        == pytest.approx(1000 / 2 * ns)
    assert scopes.scoped_s(t, m, (scopes.UNIT,)) \
        == pytest.approx((3500 + 500 + 1500) / 2 * ns)
    assert scopes.scoped_s(t, m, (scopes.UPDATE,), (scopes.UNIT,)) \
        == pytest.approx((1500 + 2000) / 2 * ns)
    assert scopes.scoped_s(t, m, (scopes.ASSIGN,)) == 0.0
    # busy: device 0 6500 ns, device 1 5500 ns
    assert scopes.busy_share(t, m, (scopes.DISTANCE,), (scopes.UNIT,)) \
        == pytest.approx(100.0 * 1500 / 6000)
    top = scopes.top_scopes(t, m, 3)
    assert top[0] == ["kmeans.distance/dm.div:kernel",
                      pytest.approx(2250 * ns)]
    assert [k for k, _ in top[1:]] == ["kmeans.update", "kmeans.distance"]
    assert dict(scopes.top_scopes(t, m))["dm.softmax:kernel"] \
        == pytest.approx(250 * ns)


def test_nothing_to_read_without_program_scopes():
    """No map (a recorded trace from before scopes), or a map whose paths
    hold no scope of the program (a program built before it named them):
    every scoped number is None and no reader raises."""
    bare = {n: p.replace("kmeans.", "").replace("dm.", "")
            for n, p in HANDMADE_SCOPES.items()}
    for t, m in ((handmade(), None), (handmade(), {}), (handmade(), bare),
                 (None, HANDMADE_SCOPES)):
        assert scopes.scoped_s(t, m, (scopes.UNIT,)) is None
        assert scopes.busy_share(t, m, (scopes.UNIT,)) is None
        assert scopes.top_scopes(t, m) == []
        view = SimpleNamespace(trace=t, scopes=m if m is not None else {})
        for name in NEW:
            assert spec.load_metric(name).read(view) is None


def test_readers_build_the_map_once_per_view(monkeypatch, capsys):
    """A view without its own map (a live run) gets the map of the cell's
    program, built once for all three readers, and the run's standard
    error names the top scopes."""
    built = []

    def program_paths(config, traffic, chips):
        built.append((config["kind"], chips))
        return HANDMADE_SCOPES

    monkeypatch.setattr(scopes, "program_paths", program_paths)
    view = SimpleNamespace(config={"kind": "kmeans"}, traffic={}, chips=1,
                           trace=handmade())
    got = {name: spec.load_metric(name).read(view) for name in NEW}
    assert built == [("kmeans", 1)]
    assert got["lloyd_plane_share.kmeans"] \
        == pytest.approx(100.0 * 1500 / 6000)
    assert got["unit_operand_share.kmeans"] \
        == pytest.approx(100.0 * 500 / 6000)
    assert got["lloyd_update_share.kmeans"] \
        == pytest.approx(100.0 * 1750 / 6000)
    err = capsys.readouterr().err
    assert err.count("bench: device_scopes = ") == 1
    assert "kmeans.distance/dm.div:kernel" in err
    assert spec.load_metric(NEW[0]).read(
        SimpleNamespace(config={"kind": "kmeans"}, traffic={}, chips=1,
                        trace=None)) is None
    assert built == [("kmeans", 1)]


def test_program_paths_of_a_small_kmeans_cell_on_cpu():
    """The cell's program, built by its kind at a small size on the CPU,
    gives the scope map the readers join: the divide kernel under
    ``kmeans.distance/dm.div``, and every Lloyd phase present."""
    import jax

    cfg = json.loads((ROOT / "bench/configs/kmeans-kdd99.json").read_text())
    cfg = dict(cfg, points=2048, clusters=16)
    paths = scopes.program_paths(cfg, {"iters_per_call": 2}, 1,
                                 devices=jax.devices("cpu")[:1],
                                 require_kernel=False)
    found = {tuple(scopes.scopes_in(p)) for p in paths.values()}
    assert ("kmeans.distance",) in found
    assert ("kmeans.distance", "dm.div") in found
    assert ("kmeans.update",) in found
    assert any(f[:1] == ("kmeans.assign",) for f in found), found
    assert any(f[:1] == ("kmeans.final",) for f in found), found
    assert scopes.program_paths(dict(cfg, kind="serve"), {}, 1) == {}


# ----------------------------------------------- recorded TPU v5e traces

def kmeans_view(recorded):
    """A traced K-Means run's view of ``recorded``: 20 calls of 10
    iterations in a window of 10.16 s (work counters as the run gives
    them)."""
    cfg = json.loads((ROOT / "bench/configs/kmeans-kdd99.json").read_text())
    work = {"points": cfg["points"], "dim": cfg["dim"],
            "clusters": cfg["clusters"], "calls": 20, "iters": 200,
            "window_s": 10.16, "chips": 1}
    return SimpleNamespace(config=cfg, traffic={"iters_per_call": 10},
                           chips=1, work=work, trace=tr.Trace(recorded),
                           peaks=PEAKS, scopes=recorded.get("scopes", {}))


def load(name):
    return json.loads((DATA / name).read_text())


def test_old_readers_read_alike_with_and_without_a_map():
    """The four K-Means readers that match kernel names give identical
    values on the recording made before the program named its scopes,
    with and without the scoped recording's map attached."""
    plain = load("trace_kmeans_tpu_v5e.json")
    assert "scopes" not in plain
    mapped = scopes.attach(load("trace_kmeans_tpu_v5e.json"),
                           load("trace_kmeans_scoped_tpu_v5e.json")
                           ["scopes"])
    assert scopes.scoped(tr.Trace(mapped), mapped["scopes"])
    for name in OLD:
        reader = spec.load_metric(name)
        a = reader.read(kmeans_view(plain))
        b = reader.read(kmeans_view(mapped))
        assert a is not None and a == b, name


def test_scoped_readers_on_a_recorded_tpu_trace():
    """A trimmed window of the cell recorded on a TPU v5e with its scope
    map: the unit's kernels, its operand preparation, the plane passes
    and the centroid update account for the busy time between them, and
    the operations without a scope are the compiler's copies and slices."""
    rec = load("trace_kmeans_scoped_tpu_v5e.json")
    view = kmeans_view(rec)
    t = view.trace
    got = {name: spec.load_metric(name).read(view) for name in NEW + OLD}
    assert all(v is not None for v in got.values()), got
    assert 50 < got["unit_share.kmeans"] < 80
    assert 3 < got["unit_operand_share.kmeans"] < 20
    assert 10 < got["lloyd_plane_share.kmeans"] < 35
    assert 1 < got["lloyd_update_share.kmeans"] < 15
    covered = sum(got[name] for name in NEW) + got["unit_share.kmeans"]
    assert 99.0 < covered <= 100.0 + 1e-9
    unscoped = [n for n, _ in t.top_ops(None)
                if not scopes.scopes_in(rec["scopes"].get(n, ""))]
    assert all(n.startswith(("%copy", "%slice", "%custom-call"))
               for n in unscoped), unscoped
    labels = {k for k, _ in scopes.top_scopes(t, rec["scopes"], 100)}
    assert {"kmeans.distance/dm.div:kernel", "kmeans.distance/dm.div",
            "kmeans.distance", "kmeans.assign", "kmeans.update"} <= labels
    assert any(k.startswith("kmeans.final/") for k in labels), labels
