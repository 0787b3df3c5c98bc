"""The serving cells' metrics are found by name in what the serving kind
computes.

``bench/run.py`` takes each end-to-end metric of a cell from the kind's
``e2e`` dict by its name in ``BENCHMARK.json``, and hands each per-layer
reader the kind's ``work`` counters. Here ``serve.window_stats`` runs on a
hand-made ``Driver`` and request list, so that a renamed key or counter
fails on the CPU and not in a run on the chip.
"""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.kinds import serve as bs  # noqa: E402
from bench.lib import spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SERVE_CELLS = [w["name"] for w in BENCH["workloads"]
               if spec.load_cell(w["name"]).config["kind"] == "serve"]


def timed(times):
    out = bs.TimedTokens()
    out.extend(range(len(times)))
    out.times = list(times)
    return out


def window():
    """A 10 s window (t 10 to 20) over two slots: request 0 finished with
    tokens at 9, 11, 12, 13; request 1 has tokens at 10.5, 12.5 and 21.
    One prefill of 8 tokens starts inside the window, one of 4 before it;
    decode steps run before the second prefill and then up to 13."""
    eng = SimpleNamespace(_prefill_tok=None, _decode=None)
    drv = bs.Driver(eng, ramp_steps=0, seconds=10.0, window=None)
    drv.t_open, drv.t_close = 10.0, 20.0
    drv.prefills = [(9.0, 9.2, 4), (10.2, 10.4, 8)]
    drv.decodes = [(9.5, 9.9), (10.6, 11.0), (11.5, 12.0), (12.1, 12.5),
                   (12.6, 13.0)]
    reqs = [SimpleNamespace(tokens=[1] * 4, out=timed([9.0, 11.0, 12.0, 13.0]),
                            done=True),
            SimpleNamespace(tokens=[1] * 8, out=timed([10.5, 12.5, 21.0]),
                            done=False)]
    return bs.window_stats(reqs, drv, slots=2)


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_every_end_to_end_metric_is_a_key_of_window_stats(cell):
    e2e, _ = window()
    names = {m["name"] for m in spec.load_cell(cell).end_to_end}
    # set-up is timed by serve.run(), not by the window
    assert names - {"setup_s"} <= set(e2e), names - set(e2e)
    assert "setup_s" in names


def test_window_stats_counts_only_what_falls_inside_the_window():
    e2e, work = window()
    # tokens at 11, 12, 13, 10.5, 12.5 and the prefill of 8 at 10.2
    assert work["generated"] == 5 and work["prompt_tokens"] == 8
    assert e2e["serve_tok_per_s"] == pytest.approx(13 / 10.0)
    # gaps with both tokens inside: 12-11, 13-12, 12.5-10.5
    assert work["itl_n"] == 3
    assert e2e["itl_p95_ms"] == pytest.approx(1900.0)
    assert e2e["itl_p90_ms"] == pytest.approx(1800.0)
    assert e2e["itl_p50_ms"] == pytest.approx(1000.0)
    assert work["window_s"] == 10.0 and work["admitted"] == 1
    # the admission at 10.2 stalls the loop until the decode call at 10.6
    assert work["stall_s"] == pytest.approx(0.4)


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_host_clock_readers_read_the_window_counters(cell):
    _, work = window()
    c = spec.load_cell(cell)
    view = SimpleNamespace(config=c.config, traffic=c.traffic, chips=c.chips,
                           work=work, trace=None,
                           peaks={"bf16_flops_per_s": 1.97e14,
                                  "hbm_bytes_per_s": 8.19e11})
    for m in c.per_layer:
        value = spec.load_metric(m["name"]).read(view)
        if m["source"] == "host_clock":
            assert value is not None and value > 0, m["name"]
        else:           # nothing traced: the reader finds nothing to read
            assert value is None, m["name"]


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_requests_stay_inside_the_context(cell):
    """Every prompt with its answer fits the cache, and the cache the
    model's context: no request is served at a position the model does
    not have."""
    from bench.lib import traffic
    c = spec.load_cell(cell)
    mix = c.traffic
    longest = max(traffic.shapes(mix["prompt"], mix["block"]))
    assert longest + mix["output"]["max"] <= mix["max_len"]
    assert mix["max_len"] <= c.config["max_position_embeddings"]


def test_closing_rows_take_each_request_from_its_own_slot():
    """At the close each request in flight is found in the slot whose
    position and fed token are its own; its rows are that slot's first
    len(prompt) + len(served) - 1, the longest request comes first, and
    finished requests are left out."""
    import jax.numpy as jnp
    import numpy as np
    layers, slots, rows = 2, 3, 16
    k = jnp.arange(layers * slots * rows * 2, dtype=jnp.float32).reshape(
        layers, slots, rows, 1, 2)
    cache = {"groups": [{"layers": [{"attn": {"k": k, "v": -k}}]}]}
    reqs = [SimpleNamespace(tokens=[5] * 4, out=[7, 8, 9], done=False),
            SimpleNamespace(tokens=[5] * 3, out=[4], done=False),
            SimpleNamespace(tokens=[5] * 6, out=[3, 2, 1, 6, 7], done=False),
            SimpleNamespace(tokens=[5] * 2, out=[1, 1], done=True)]
    # slot 0 holds request 2, slot 1 request 0, slot 2 request 1
    drv = SimpleNamespace(at_close=(cache, jnp.asarray([[7], [9], [4]]),
                                    jnp.asarray([10, 6, 3])))
    held = bs.closing_rows(reqs, drv, n=3, seed=1)
    assert held[0][0] is reqs[2]
    assert {id(r) for r, _, _ in held} == {id(reqs[0]), id(reqs[1]),
                                           id(reqs[2])}
    slot = {id(reqs[2]): 0, id(reqs[0]): 1, id(reqs[1]): 2}
    for r, kr, vr in held:
        p = len(r.tokens) + len(r.out) - 1
        want = np.asarray(k)[:, slot[id(r)], :p]
        np.testing.assert_array_equal(kr, want)
        np.testing.assert_array_equal(vr, -want)
