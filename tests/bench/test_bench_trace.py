"""The benchmark's reduction from a recorded trace to numbers."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import kernels, trace as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def t():
    return tr.Trace(json.loads((DATA / "trace_handmade.json").read_text()))


def test_interval_helpers():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)]) == [(1, 4), (5, 8)]
    assert tr.length([(1, 4), (5, 8)]) == 6
    assert tr.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]


def test_window_busy_idle(t):
    assert t.window == (500, 10500)
    assert t.n_devices == 2
    assert t.window_s == pytest.approx(1e-5)
    # device 0: [1000,5500] + [7000,8500] + [9500,10000]; device 1: 2000 +
    # 2000 + 1500 (its last kernel clipped at the window's end)
    assert t.busy_s == pytest.approx((6500 + 5500) / 2 * 1e-9)
    assert t.idle_share == pytest.approx(0.4)


def test_kernel_time_by_name(t):
    assert t.op_s(kernels.is_tsdiv) == pytest.approx((3000 + 1500) / 2 * 1e-9)
    assert t.op_s(kernels.is_softmax) == pytest.approx(500 / 2 * 1e-9)
    assert t.op_s(kernels.is_unit) == pytest.approx((3500 + 1500) / 2 * 1e-9)
    assert t.op_count(kernels.is_tsdiv) == 1


def test_exposed_collectives(t):
    # device 0: all-reduce [7000,8000] half under fusion.3; device 1: bare
    assert t.exposed_collective_s() == pytest.approx((500 + 2000) / 2 * 1e-9)


def test_gap_attribution(t):
    assert [list(g) for g in t.idle_gaps()] == [
        [500, 1000], [5500, 7000], [8500, 9500], [10000, 10500]]
    assert t.idle_by_host() == [["bench.decode", pytest.approx(2.5e-6)],
                                ["between spans", pytest.approx(1e-6)]]
    assert t.span_s("bench.call") == pytest.approx(5.2e-6)


def test_top_ops(t):
    top = t.top_ops(2)
    assert top[0] == ["%tsdiv_divide_tiled_2d.3", pytest.approx(2.25e-6)]
    assert top[1][1] == pytest.approx(1.5e-6)


def test_names_are_short_and_containers_dropped(t):
    names = {n for evs in t.devices.values() for n, _, _ in evs}
    assert "%fusion.1" in names and "%tsdiv_divide_tiled_2d.3" in names
    assert not any(n.startswith("%while") for n in names)
    assert tr.short_name("%a.1 = f32[2] add(%b, %c)") == "%a.1"
    assert kernels.is_tsdiv("%tsdiv_rsqrt_tiled_2d.4")
    assert not kernels.is_tsdiv("%fusion.12")
    assert kernels.is_rmsnorm("%rmsnorm_2d.16")


def test_no_window_span_is_an_error():
    rec = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["fusion", 0, 10]]}]}]}
    with pytest.raises(ValueError):
        tr.Trace(rec)
    assert tr.Trace(rec, window=(0, 20)).idle_share == pytest.approx(0.5)


def test_reduction_of_a_recorded_tpu_trace():
    """120 ms of a K-Means window recorded on a TPU v5e: the tiled divide
    kernel is the largest operation and the device is never idle long."""
    t = tr.Trace(json.loads((DATA / "trace_kmeans_tpu_v5e.json")
                            .read_text()))
    assert t.n_devices == 1
    assert 0 < t.busy_s <= t.window_s
    assert t.idle_share < 0.05
    top = t.top_ops(3)
    assert kernels.is_tsdiv(top[0][0])
    assert t.op_s(kernels.is_tsdiv) == pytest.approx(
        sum(s for n, s in t.top_ops(100) if kernels.is_tsdiv(n)))
    assert t.op_s(kernels.is_softmax) == 0.0
    assert t.idle_by_host()[0][0] == "bench.call"
