"""The reader of the share of the division unit's kernel time that divides
a plane by one number without broadcasting it (``dm.div.uniform_divisor``,
core/division_modes.py), on the recorded TPU v5e trace of the K-Means
cell."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.lib import kernels, scopes, spec, trace as tr  # noqa: E402
from repro.core import division_modes as dm  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
NAME = "unit_uniform_share.kmeans"
# The recording's plane divides: in the scan and in the final assignment.
PLANES = ("%tsdiv_divide_tiled_2d.12", "%tsdiv_divide_tiled_2d.11")


def view_of(scope_map):
    rec = json.loads((DATA / "trace_kmeans_scoped_tpu_v5e.json").read_text())
    return SimpleNamespace(trace=tr.Trace(rec), scopes=scope_map)


def recorded_map():
    return json.loads((DATA / "trace_kmeans_scoped_tpu_v5e.json")
                      .read_text())["scopes"]


def uniform_map(names):
    """The recording's map with the kernels ``names`` moved under the
    uniform-divisor scope, as the program now stages the plane divides."""
    m = recorded_map()
    for n in names:
        m[n] = m[n].replace("/dm.div/", f"/dm.div/{dm.SCOPE_UNIFORM}/")
    return m


@pytest.mark.parametrize("names", [(), PLANES[:1], PLANES])
def test_uniform_share_on_a_recorded_tpu_trace(names):
    """0 where no kernel sits under the scope (the recording's program
    broadcast its divisor), else those kernels' share of the unit's kernel
    time."""
    view = view_of(uniform_map(names))
    t = view.trace
    unit = t.op_s(kernels.is_unit)
    want = 100.0 * sum(t.op_s(lambda n, k=k: n == k) for k in names) / unit
    got = spec.load_metric(NAME).read(view)
    assert got == pytest.approx(want)
    if not names:
        assert got == 0.0
    if names == PLANES:
        # only the tiny centroid divide stays on two planes
        assert 99.9 < got < 100.0


def test_uniform_share_nothing_to_read():
    """No map, or a map without the program's scopes: None, no raise."""
    bare = {n: p.replace("kmeans.", "").replace("dm.", "")
            for n, p in recorded_map().items()}
    reader = spec.load_metric(NAME)
    for m in ({}, bare):
        assert reader.read(view_of(m)) is None
    assert reader.read(SimpleNamespace(trace=None, scopes={})) is None


def test_uniform_scope_is_a_path_of_dm_div():
    """The scope extends ``dm.div``'s name: the operations under it count
    as ``dm.div``'s for every reader that groups by the unit's entries,
    and the reader's pattern matches the program's name."""
    path = f"jit(call)/kmeans.distance/dm.div/{dm.SCOPE_UNIFORM}/pallas_call"
    assert scopes.scopes_in(path) == ["kmeans.distance", "dm.div"]
    assert scopes.under(path, (spec.load_metric(NAME).UNIFORM,))
    assert scopes.under(path, (scopes.UNIT,))
