"""Serving cells: ``ServingEngine.serve`` under a closed loop of clients.

Set-up draws the weights on the device in one jitted call from the seed,
builds the engine (the division unit's mode from the configuration file),
compiles every prefill shape the mix can produce and the decode step by one
``serve`` pass with a request of each prompt length, and then starts the
measured ``serve`` call: ``clients`` slots fed from a queue drawn from the
seed. Once ``ramp_decode_steps`` decode steps have run, so that the slots'
finishing times have spread, the window opens; it closes at the first
decode step after ``--seconds``. The closed loop: a request is sent when a
slot frees, so its time to first token counts from that moment.

Host spans (``bench.prefill``, ``bench.decode``) wrap the engine's own
prefill and decode calls; ``serve`` waits for every step's tokens, so a
token's time is when ``serve`` appends it to its request.

``correct``: after the window, requests it finished are drawn from the
seed (the longest among them), and the plain float32 forward of
bench/reference/granite.py runs over each prompt with its served tokens.
The number compared is the widest gap by which a served token's reference
logit lies below the reference's best at that position.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import List, Optional

from bench.lib import device, seeds, stats, traffic
from bench.lib.context import Check, Ctx, Outcome
from bench.lib.window import Window

CHECKS = ("logit_gap",)


class StopWindow(Exception):
    pass


class TimedTokens(list):
    """A request's ``out`` list that notes when each token was appended."""

    def __init__(self):
        super().__init__()
        self.times: List[float] = []

    def append(self, tok):
        self.times.append(time.perf_counter())
        super().append(tok)


# ------------------------------------------------------------ configuration

# configuration-file key -> ModelConfig field
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "torch_dtype": "param_dtype"}


def model_config(cj: dict):
    """The program's ModelConfig for the file ``cj``: its registry entry
    cut to the file's depth, with the file's division mode; every size the
    file states must then agree with what the program runs."""
    from repro.configs import get_config
    from repro.core.division_modes import DivisionConfig

    cfg = dataclasses.replace(
        get_config(cj["program_config"]),
        n_layers=int(cj["num_hidden_layers"]),
        division=DivisionConfig(mode=cj["division"]))
    for key, field in FIELDS.items():
        want, got = cj[key], getattr(cfg, field)
        if want != got:
            raise ValueError(f"{cj['name']}: {key} = {want!r} but the "
                             f"program runs {field} = {got!r}")
    return cfg


def init_std(names: List[str], shape) -> float:
    """Standard deviation of a weight: 1 for the embedding, else
    1/sqrt(fan-in)."""
    leaf = names[-1]
    if leaf == "embed":
        return 1.0
    if "attn" in names and leaf in ("wq", "wk", "wv"):
        return 1.0 / math.sqrt(shape[-3])
    if "attn" in names and leaf == "wo":
        return 1.0 / math.sqrt(shape[-3] * shape[-2])
    return 1.0 / math.sqrt(shape[-2])


def make_weights(cfg, seed: int, sharding=None):
    """All weights on the device in one jitted call from the seed, in the
    dtype the program serves them in; norm gains are ones."""
    import jax
    import jax.numpy as jnp

    from repro.models import abstract_params

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params(cfg))

    def names(path):
        return [str(getattr(p, "key", getattr(p, "idx", ""))) for p in path]

    def make(key):
        vals = []
        for i, (path, a) in enumerate(flat):
            nm = names(path)
            if "norm" in nm[-1]:
                vals.append(jnp.ones(a.shape, a.dtype))
                continue
            std = init_std(nm, a.shape)
            w = jax.random.normal(jax.random.fold_in(key, i), a.shape, a.dtype)
            vals.append((w * jnp.asarray(std, a.dtype)).astype(a.dtype))
        return jax.tree_util.tree_unflatten(treedef, vals)

    return jax.jit(make, out_shardings=sharding)(
        seeds.key(seed, seeds.STREAM_WEIGHTS))


def reference_weights(params, cfg):
    """The weights as the reference takes them (same arrays, no copy)."""
    (group,) = params["groups"]
    (layer,) = group["layers"]
    if cfg.groups()[0].repeat == 1:
        import jax

        layer = jax.tree_util.tree_map(lambda a: a[None], layer)
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": params["lm_head"],
            "attn_norm": layer["mixer_norm"], "wq": layer["attn"]["wq"],
            "wk": layer["attn"]["wk"], "wv": layer["attn"]["wv"],
            "wo": layer["attn"]["wo"], "mlp_norm": layer["ffn_norm"],
            "w_up": layer["ffn"]["wi"], "w_gate": layer["ffn"]["wg"],
            "w_down": layer["ffn"]["wo"]}


# ------------------------------------------------------------------ driver

class Driver:
    """Wraps the engine instance's prefill and decode calls: host spans,
    the ramp, and the window's open and close."""

    def __init__(self, eng, ramp_steps: int, seconds: float,
                 window: Optional[Window]):
        import jax

        self.annotate = jax.profiler.TraceAnnotation
        self.real_prefill = eng._prefill_tok
        self.real_decode = eng._decode
        eng._prefill_tok = self.prefill
        eng._decode = self.decode
        self.ramp_steps, self.seconds, self.window = ramp_steps, seconds, window
        self.prefills: List[tuple] = []      # (start, end, padded length)
        self.decodes: List[tuple] = []       # (start, end)
        self.t_open = self.t_close = None

    def prefill(self, tokens, lengths):
        t = time.perf_counter()
        with self.annotate("bench.prefill"):
            out = self.real_prefill(tokens, lengths)
        self.prefills.append((t, time.perf_counter(), int(tokens.shape[1])))
        return out

    def decode(self, cache, tokens, pos):
        if self.window is not None:
            if self.t_open is None and len(self.decodes) >= self.ramp_steps:
                self.t_open = self.window.open()
            elif (self.t_open is not None
                  and time.perf_counter() - self.t_open >= self.seconds):
                self.t_close = self.window.close()
                raise StopWindow
        t = time.perf_counter()
        with self.annotate("bench.decode"):
            out = self.real_decode(cache, tokens, pos)
        self.decodes.append((t, time.perf_counter()))
        return out


def build_requests(mix: dict, vocab: int, count: int, seed: int):
    from repro.serving import Request

    prompts, outs = traffic.requests(
        mix, vocab, count, seeds.rng(seed, seeds.STREAM_TRAFFIC))
    return [Request(tokens=p, max_new=m, out=TimedTokens())
            for p, m in zip(prompts, outs)]


def queue_length(mix: dict, seconds: float) -> int:
    """Requests enough for the ramp and the window at any rate up to
    ``queue_rate_bound`` a second."""
    return int(mix["clients"] + mix["queue_rate_bound"]
               * (seconds + mix["ramp_bound_s"]))


def warm_up(eng, mix: dict, slots: int):
    """One ``serve`` pass with a request of every prompt length the mix can
    produce: compiles (or loads) every prefill shape and the decode step."""
    from repro.serving import Request

    reqs = [Request(tokens=[1] * n, max_new=2)
            for n in traffic.shapes(mix["prompt"], int(mix["block"]))]
    eng.serve(reqs, slots=slots)


def window_stats(reqs, drv: Driver, slots: int):
    """End-to-end numbers and the work counters of the window."""
    t0, t1 = drv.t_open, drv.t_close
    inside = (lambda t: t0 <= t <= t1)
    gen = prompt = 0
    itl, ttft = [], []
    ctx_decode, prompt_lens, lm_rows = [], [], 0
    # k-th release of a slot (in time order) sends queue entry slots + k
    released = sorted(r.out.times[-1] for r in reqs if r.done)
    for i, r in enumerate(reqs):
        ts = r.out.times
        for j, t in enumerate(ts):
            if not inside(t):
                continue
            gen += 1
            lm_rows += 1
            if j > 0:
                ctx_decode.append(len(r.tokens) + j)
                if inside(ts[j - 1]):
                    itl.append(ts[j] - ts[j - 1])
        if i >= slots and ts and i - slots < len(released):
            sent = released[i - slots]
            if inside(sent) and inside(ts[0]):
                ttft.append(ts[0] - sent)
    for start, _, n in drv.prefills:
        if inside(start):
            prompt += n
            prompt_lens.append(n)
    # Admission stalls: a loop turn's admissions run from its first
    # prefill to the turn's decode call (or to the window's close).
    stall = 0.0
    starts = sorted(p[0] for p in drv.prefills)
    k, prev_end = 0, -math.inf
    for d_start, d_end in drv.decodes + [(t1, t1)]:
        first = None
        while k < len(starts) and starts[k] < d_start:
            if first is None and starts[k] >= prev_end:
                first = starts[k]
            k += 1
        if first is not None and inside(first):
            stall += min(d_start, t1) - first
        prev_end = d_end
    window_s = t1 - t0
    e2e = {"serve_tok_per_s": (gen + prompt) / window_s}
    if itl:
        e2e["itl_p95_ms"] = stats.percentile(itl, 95) * 1e3
        e2e["itl_p50_ms"] = stats.percentile(itl, 50) * 1e3
    if ttft:    # printed, not a metric: too few admissions for a tail
        e2e["ttft_p95_ms"] = stats.percentile(ttft, 95) * 1e3
        e2e["ttft_p50_ms"] = stats.percentile(ttft, 50) * 1e3
    work = {"window_s": window_s, "generated": gen, "prompt_tokens": prompt,
            "prompt_lens": prompt_lens, "decode_ctx": ctx_decode,
            "lm_rows": lm_rows, "stall_s": stall, "itl_n": len(itl),
            "ttft_n": len(ttft),
            "admitted": sum(1 for s, _, _ in drv.prefills if inside(s))}
    return e2e, work


def pick(reqs, t0, t1, n: int, seed: int):
    """Requests finished in the window: the longest, and ``n - 1`` more
    drawn from the seed."""
    done = [r for r in reqs if r.done and t0 <= r.out.times[-1] <= t1]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r.tokens) + len(r.out)))
    rest = list(range(1, len(done)))
    seeds.rng(seed, seeds.STREAM_SAMPLE).shuffle(rest)
    return [done[0]] + [done[j] for j in rest[:n - 1]]


def gaps(weights, cfg, reqs, dot: str = "highest"):
    """Per request, the widest gap between the reference's best logit and
    its logit of the token served (``dot="highest"``) or of the token the
    control puts first (any other ``dot``), over the served positions."""
    import jax.numpy as jnp

    from bench.reference import granite

    out = []
    for r in reqs:
        seq = list(r.tokens) + list(r.out[:-1])
        want = list(range(len(r.tokens) - 1, len(seq)))
        ref = granite.logits_at(weights, seq, want, theta=cfg.rope_theta,
                                eps=cfg.norm_eps)
        if dot == "highest":
            chosen = jnp.asarray(list(r.out), jnp.int32)
        else:
            ctl = granite.logits_at(weights, seq, want, theta=cfg.rope_theta,
                                    eps=cfg.norm_eps, dot=dot)
            chosen = jnp.argmax(ctl, axis=-1)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
        out.append(float(jnp.max(best - got)))
    return out


def setup(ctx: Ctx):
    """(cfg, weights, engine, slots, programs, phases): the weights drawn,
    the engine built, its programs checked and warmed up; the checked
    programs, and the seconds since process start at which each step of
    set-up ended."""
    import jax

    from repro.serving import ServingEngine

    cj, mix = ctx.cell.config, ctx.cell.traffic
    phases = {} if ctx.t_chips is None else {"chips": ctx.t_chips - ctx.t0}
    cfg = model_config(cj)
    one = jax.sharding.SingleDeviceSharding(ctx.devices[0])
    params = jax.block_until_ready(make_weights(cfg, ctx.seed, one))
    phases["weights"] = time.perf_counter() - ctx.t0
    eng = ServingEngine(cfg, params, max_len=int(mix["max_len"]))
    slots = int(mix["clients"])
    programs = ()
    with device.FallbackSpy() as spy:
        if ctx.require_kernel:
            programs = check_programs(eng, cfg, mix, slots)
        phases["compile"] = time.perf_counter() - ctx.t0
        warm_up(eng, mix, slots)
        phases["warm_up"] = time.perf_counter() - ctx.t0
    if spy.refused:
        raise device.Refused(f"jnp fallback at {spy.refused}")
    return cfg, params, eng, slots, programs, phases


def check_programs(eng, cfg, mix, slots):
    """The largest prefill and the decode step, compiled and checked for
    their Mosaic kernels."""
    import jax
    import jax.numpy as jnp

    from repro.models import make_cache

    n = max(traffic.shapes(mix["prompt"], int(mix["block"])))
    pre = eng._prefill_tok_fn.lower(
        eng.params, jax.ShapeDtypeStruct((1, n), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32)).compile()
    device.check_kernel(pre, f"prefill ({n} tokens)")
    dec = eng._decode_fn.lower(
        eng.params, make_cache(cfg, slots, eng.max_len, abstract=True),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32)).compile()
    device.check_kernel(dec, f"decode ({slots} slots)")
    return pre, dec


def serve_window(ctx: Ctx, window: Window):
    """Set-up, then one ``serve`` call: the ramp, and the window of
    ``ctx.seconds`` that ``window`` times. Returns (cfg, params, requests,
    driver, programs, phases)."""
    mix = ctx.cell.traffic
    cfg, params, eng, slots, programs, phases = setup(ctx)
    reqs = build_requests(mix, cfg.vocab, queue_length(mix, ctx.seconds),
                          ctx.seed)
    drv = Driver(eng, int(mix["ramp_decode_steps"]), ctx.seconds, window)
    try:
        eng.serve(reqs, slots=slots)
    except StopWindow:
        return cfg, params, reqs, drv, programs, phases
    raise RuntimeError("the queue ran dry before the window closed; raise "
                       "queue_rate_bound")


def run(ctx: Ctx) -> Outcome:
    mix = ctx.cell.traffic
    counter = device.CompileCounter()
    win = Window(ctx.trace_dir if ctx.trace else None)
    cfg, params, reqs, drv, programs, phases = serve_window(ctx, win)
    trace = win.finish(ctx.save_trace, all_lines=ctx.save_trace is not None)
    t0, t1 = drv.t_open, drv.t_close
    compiles = counter.count(t0, t1)
    mem = device.memory_peak_bytes(ctx.devices, programs)
    mem_runtime = device.memory_peak_bytes(ctx.devices)
    del programs
    e2e, work = window_stats(reqs, drv, int(mix["clients"]))
    e2e["setup_s"] = t0 - ctx.t0
    del drv                     # the engine, its cache and programs
    gc.collect()

    picked = pick(reqs, t0, t1, int(mix["check_requests"]), ctx.seed)
    weights = reference_weights(params, cfg)
    worst = max(gaps(weights, cfg, picked)) if picked else math.nan
    return Outcome(
        e2e=e2e, work=work,
        checks=[Check("logit_gap", worst, ctx.limits["logit_gap"])],
        attempted=work["admitted"], failed=0, memory_peak_bytes=mem,
        trace=trace,
        notes=[("setup_phases_s", {k: round(v, 3) for k, v in phases.items()}),
               ("memory_runtime_peak_bytes", mem_runtime),
               ("compiles_in_window", compiles),
               ("requests_checked", len(picked)),
               ("served_tokens_checked", sum(len(r.out) for r in picked)),
               ("itl_samples", work["itl_n"]),
               ("ttft_samples", work["ttft_n"]),
               ("latencies_ms", {k: round(v, 4) for k, v in e2e.items()
                                 if k.endswith("_ms")})])


def calibrate(ctx: Ctx, seed_list, control_seeds):
    """Readings for the limits, all in this process: for each seed, fresh
    weights and traffic, a ramp and a window of ``ctx.seconds``, and the
    gaps of the checked requests against the reference; for the seeds in
    ``control_seeds`` also the control's gaps (the reference in fp8) at
    the same positions."""
    rows = []
    for seed in seed_list:
        c = dataclasses.replace(ctx, seed=seed)
        cfg, params, reqs, drv, _, _ = serve_window(c, Window(None))
        t0, t1 = drv.t_open, drv.t_close
        del drv
        gc.collect()
        picked = pick(reqs, t0, t1, int(c.cell.traffic["check_requests"]),
                      seed)
        weights = reference_weights(params, cfg)
        row = {"seed": seed, "requests": len(picked),
               "served": sum(len(r.out) for r in picked),
               "program": gaps(weights, cfg, picked)}
        if seed in control_seeds:
            row["control"] = gaps(weights, cfg, picked, dot="fp8")
        rows.append(row)
        del params, weights
        gc.collect()
    return rows
