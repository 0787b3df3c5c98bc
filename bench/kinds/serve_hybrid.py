"""Serving cells of a hybrid Mamba-2 / attention / expert model (Granite
4.0-H): ``ServingEngine.serve`` under a closed loop of clients.

The loop, the window and its numbers are the serving kind's
(bench/kinds/serve.py: ``Driver``, ``window_stats``, ``build_requests``,
``warm_up``, ``queue_length``, ``pick``); this file adds what a hybrid
model needs. Prompts are padded to the Mamba chunk by the engine, and a
prompt counts in ``serve_tok_per_s`` by its real length.

Set-up maps the configuration file onto the program's config (every size
the file states must equal what the program runs, ``model_config``), draws
the weights on the device from the seed (``make_weights``: Mamba-2's own
initialisation of ``A_log``, ``dt_bias`` and ``D``), compiles the largest
prefill and the decode step and checks them for their Mosaic kernels, and
warms up every prefill shape.

``correct``: ``logit_gap`` over requests the window finished, against the
plain float32 forward of bench/reference/granite_h.py (logits, not sampled
tokens), and ``state_gap`` for requests in flight at the window's close:
what the engine's cache holds for the request (each Mamba layer's SSM
state and conv state, the attention layer's key and value rows) against
what the reference computes from the same tokens. Each row's distance is
taken over the larger of the reference row's norm and its layer's median
row norm; ``state_gap`` is the widest, over layers and kinds of state, of
the root mean square of those distances (``state_gap``'s docstring says
why not the widest row). A row is a head of an SSM state, a position of a
conv state, or a position of the keys or the values. A nan fails either
check.

Traced runs attribute device time to the program's scopes per program:
the engine runs a decode program and one prefill program per padded
length, whose instruction names collide, so each operation is read in the
program whose ``XLA Modules`` span holds it (bench/lib/programs.py).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import List

from bench.kinds.serve import (Driver, build_requests, init_std, pick,
                               queue_length, warm_up, window_stats)
from bench.lib import device, programs, seeds, traffic
from bench.lib.context import Check, Ctx, Outcome
from bench.lib.window import Window

CHECKS = ("logit_gap", "state_gap")

# requests in flight at the close whose state is compared
STATE_REQUESTS = 3


# ------------------------------------------------------------ configuration

def _layer_types(cfg) -> List[str]:
    return ["attention" if s.mixer == "attn" else "mamba"
            for s in cfg.layer_specs()]


# configuration-file key -> what the program runs, from its ModelConfig
SIZES = {
    "num_hidden_layers": lambda c: c.n_layers,
    "hidden_size": lambda c: c.d_model,
    "num_attention_heads": lambda c: c.n_heads,
    "num_key_value_heads": lambda c: c.n_kv_heads,
    "head_dim": lambda c: c.head_dim,
    "intermediate_size": lambda c: c.d_ff_expert,
    "shared_intermediate_size":
        lambda c: c.n_shared_experts * c.d_ff_expert,
    "num_local_experts": lambda c: c.n_experts_here,
    "published_num_local_experts": lambda c: c.n_experts,
    "num_experts_per_tok": lambda c: c.experts_per_tok,
    "vocab_size": lambda c: c.vocab,
    "mamba_n_heads": lambda c: c.ssm_heads,
    "mamba_d_head": lambda c: c.ssm_head_dim,
    "mamba_d_state": lambda c: c.ssm_state,
    "mamba_d_conv": lambda c: c.conv_width,
    "mamba_chunk_size": lambda c: c.ssm_chunk,
    "mamba_expand": lambda c: c.d_inner // c.d_model,
    "mamba_n_groups": lambda c: 1,
    "mamba_conv_bias": lambda c: c.mamba_conv_bias,
    "mamba_proj_bias": lambda c: False,
    "attention_bias": lambda c: False,
    "attention_multiplier": lambda c: c.attention_multiplier,
    "embedding_multiplier": lambda c: c.embedding_multiplier,
    "residual_multiplier": lambda c: c.residual_multiplier,
    "logits_scaling": lambda c: c.logits_scaling,
    "position_embedding_type": lambda c: "rope" if c.rope else "nope",
    "rms_norm_eps": lambda c: c.norm_eps,
    "tie_word_embeddings": lambda c: c.tie_embeddings,
    "hidden_act": lambda c: "silu",
    "normalization_function": lambda c: "rmsnorm",
    "layer_types": _layer_types,
    "torch_dtype": lambda c: c.param_dtype,
}


def model_config(cj: dict):
    """The program's ModelConfig for the file ``cj``: its registry entry cut
    to the file's depth, holding the file's experts (``experts_held``), with
    the file's division mode; every size the file states must then equal
    what the program runs."""
    from repro.configs import get_config
    from repro.core.division_modes import DivisionConfig

    lo = int(cj.get("first_expert_held", 0))
    cfg = dataclasses.replace(
        get_config(cj["program_config"]),
        n_layers=int(cj["num_hidden_layers"]),
        experts_held=(lo, lo + int(cj["num_local_experts"])),
        division=DivisionConfig(mode=cj["division"]))
    for key, got in SIZES.items():
        want, got = cj[key], got(cfg)
        if want != got:
            raise ValueError(f"{cj['name']}: {key} = {want!r} but the "
                             f"program runs {got!r}")
    if cfg.d_inner != cj["mamba_expand"] * cj["hidden_size"]:
        raise ValueError(f"{cj['name']}: d_inner {cfg.d_inner} is not "
                         f"mamba_expand x hidden_size")
    return cfg


def make_weights(cfg, seed: int, sharding=None):
    """All weights on the device in one jitted call from the seed: norm
    gains and ``D`` ones, ``A_log`` = log U[1, 16], ``dt_bias`` the inverse
    softplus of dt log-uniform in [0.001, 0.1] (Mamba-2's initialisation),
    a conv's bias N(0, 1/conv width) (its fan-in), the embedding N(0,
    1/(embedding_multiplier^2 d_model)), every other weight normal with
    ``serve.init_std``.

    The embedding's scale: the tied head gives the last token a logit of
    about the embedding's share of the residual stream times sqrt(d_model)
    standard deviations above the rest, so a scaled embedding at RMS 1
    (N(0, 1) / 12 or more) makes every served token a copy of the last
    one. At RMS 1/sqrt(d_model) the first layer still reads the token
    whole (its norm rescales it) and the head is not dominated by it."""
    import jax
    import jax.numpy as jnp

    from repro.models import abstract_params

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_params(cfg))

    def names(path):
        return [str(getattr(p, "key", getattr(p, "idx", ""))) for p in path]

    def make(key):
        vals = []
        for i, (path, a) in enumerate(flat):
            nm, k = names(path), jax.random.fold_in(key, i)
            if "norm" in nm[-1] or nm[-1] == "D":
                v = jnp.ones(a.shape, jnp.float32)
            elif nm[-1] == "A_log":
                v = jnp.log(jax.random.uniform(k, a.shape, jnp.float32,
                                               1.0, 16.0))
            elif nm[-1] == "embed":
                v = jax.random.normal(k, a.shape, a.dtype) * jnp.asarray(
                    1.0 / (cfg.embedding_multiplier * math.sqrt(cfg.d_model)),
                    a.dtype)
            elif nm[-1] == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, a.shape, jnp.float32, math.log(1e-3), math.log(0.1)))
                v = dt + jnp.log(-jnp.expm1(-dt))
            else:
                std = (1.0 / math.sqrt(cfg.conv_width)
                       if nm[-1].endswith("_bias") else init_std(nm, a.shape))
                v = jax.random.normal(k, a.shape, a.dtype) \
                    * jnp.asarray(std, a.dtype)
            vals.append(v.astype(a.dtype))
        return jax.tree_util.tree_unflatten(treedef, vals)

    return jax.jit(make, out_shardings=sharding)(
        seeds.key(seed, seeds.STREAM_WEIGHTS))


def _layers(tree, cfg):
    """The per-layer subtrees of ``tree`` (parameters or cache) in layer
    order, each group's stacked leaves split by repeat."""
    import jax

    out = []
    for g, gt in zip(cfg.groups(), tree["groups"]):
        for r in range(g.repeat):
            for i, spec in enumerate(g.period):
                lt = gt["layers"][i]
                if g.repeat > 1:
                    lt = jax.tree_util.tree_map(lambda a, r=r: a[r], lt)
                out.append((spec, lt))
    return out


def reference_weights(params, cfg):
    """(weights, hp): the weights as bench/reference/granite_h.py takes
    them (the same arrays) and its hyperparameters."""
    from repro.models.attention import score_scale

    layers = []
    for spec, lp in _layers(params, cfg):
        ffn = lp["ffn"]
        w = {"norm1": lp["mixer_norm"], "norm2": lp["ffn_norm"],
             "router": ffn["router"], "w_up": ffn["wi"],
             "w_gate": ffn["wg"], "w_down": ffn["wo"],
             "s_up": ffn["shared"]["wi"], "s_gate": ffn["shared"]["wg"],
             "s_down": ffn["shared"]["wo"]}
        if spec.mixer == "mamba":
            m = lp["mamba"]
            w.update({k: m[k] for k in (
                "wz", "wx", "wB", "wC", "wdt", "conv_x", "conv_B", "conv_C",
                "conv_x_bias", "conv_B_bias", "conv_C_bias", "A_log",
                "dt_bias", "D", "wout")}, gnorm=m["norm"], kind="mamba")
        else:
            w.update(lp["attn"], kind="attn")
        layers.append(w)
    hp = {"eps": cfg.norm_eps, "k": cfg.experts_per_tok,
          "held": tuple(cfg.experts_held), "ssm_head_dim": cfg.ssm_head_dim,
          "embedding_multiplier": cfg.embedding_multiplier,
          "residual_multiplier": cfg.residual_multiplier,
          "attention_multiplier": score_scale(cfg),
          "logits_scaling": cfg.logits_scaling}
    return ({"embed": params["embed"], "final_norm": params["final_norm"],
             "layers": layers}, hp)


# ------------------------------------------------------------------ driver

class HybridDriver(Driver):
    """``serve.Driver`` that notes a prefill by its prompt's real length
    (the engine pads prompts to the Mamba chunk), and wraps the engine's
    cache insert of an admission, where the engine has one, in the host
    span ``bench.insert``."""

    def __init__(self, eng, *args):
        super().__init__(eng, *args)
        self.real_insert = getattr(eng, "_insert", None)
        if self.real_insert is not None:
            eng._insert = self.insert

    def insert(self, cache, row, slot, pad_to):
        with self.annotate("bench.insert"):
            return self.real_insert(cache, row, slot, pad_to)

    def prefill(self, tokens, lengths):
        out = super().prefill(tokens, lengths)
        t, e, _ = self.prefills[-1]
        self.prefills[-1] = (t, e, int(lengths[0]))
        return out


def padded_shapes(eng, mix) -> List[int]:
    """The prefill lengths the engine runs for the mix's prompts."""
    return sorted({eng._pad_to(n)
                   for n in traffic.shapes(mix["prompt"], int(mix["block"]))})


def compile_programs(eng, cfg, slots: int, lengths):
    """[(label, compiled)]: the decode step and a prefill of each length."""
    import jax
    import jax.numpy as jnp

    from repro.models import make_cache

    out = [("decode", eng._decode_fn.lower(
        eng.params, make_cache(cfg, slots, eng.max_len, abstract=True),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32)).compile())]
    for n in lengths:
        out.append((f"prefill{n}", eng._prefill_tok_fn.lower(
            eng.params, jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32)).compile()))
    return out


def setup(ctx: Ctx):
    """(cfg, weights, engine, slots, programs, phases), as serve.setup:
    the weights drawn, the engine built, the decode step and the largest
    prefill compiled and checked, every prefill shape warmed up."""
    import jax

    from repro.serving import ServingEngine

    cj, mix = ctx.cell.config, ctx.cell.traffic
    phases = {} if ctx.t_chips is None else {"chips": ctx.t_chips - ctx.t0}
    cfg = model_config(cj)
    if int(mix["max_len"]) > int(cj["max_position_embeddings"]):
        raise ValueError(f"max_len {mix['max_len']} is past the model's "
                         f"context of {cj['max_position_embeddings']}")
    one = jax.sharding.SingleDeviceSharding(ctx.devices[0])
    params = jax.block_until_ready(make_weights(cfg, ctx.seed, one))
    phases["weights"] = time.perf_counter() - ctx.t0
    eng = ServingEngine(cfg, params, max_len=int(mix["max_len"]))
    slots = int(mix["clients"])
    checked = ()
    with device.FallbackSpy() as spy:
        if ctx.require_kernel:
            checked = compile_programs(eng, cfg, slots,
                                       padded_shapes(eng, mix)[-1:])
            for label, c in checked:
                device.check_kernel(c, label)
            checked = tuple(c for _, c in checked)
        phases["compile"] = time.perf_counter() - ctx.t0
        warm_up(eng, mix, slots)
        phases["warm_up"] = time.perf_counter() - ctx.t0
    if spy.refused:
        raise device.Refused(f"jnp fallback at {spy.refused}")
    return cfg, params, eng, slots, checked, phases


def serve_window(ctx: Ctx, window: Window):
    """Set-up, then one ``serve`` call: the ramp and the window. Returns
    (cfg, params, engine, requests, driver, programs, phases)."""
    from bench.kinds import serve

    mix = ctx.cell.traffic
    cfg, params, eng, slots, checked, phases = setup(ctx)
    reqs = build_requests(mix, cfg.vocab, queue_length(mix, ctx.seconds),
                          ctx.seed)
    drv = HybridDriver(eng, int(mix["ramp_decode_steps"]), ctx.seconds,
                       window)
    try:
        eng.serve(reqs, slots=slots)
    except serve.StopWindow:
        return cfg, params, eng, reqs, drv, checked, phases
    raise RuntimeError("the queue ran dry before the window closed; raise "
                       "queue_rate_bound")


# ------------------------------------------------------------------ checks

def closing_state(reqs, drv: Driver, cfg, n: int, seed: int):
    """Requests in flight at the window's close (the one with the most
    served tokens and ``n - 1`` more drawn from the seed), each with what
    its slot's cache holds there, per layer as host float32 arrays in the
    form of ``granite_h.state_at``: the state after the prompt and every
    served token but the last, which the step the close cut off was to
    feed."""
    import jax
    import numpy as np

    cache, tokens, pos = drv.at_close
    tokens, pos = np.asarray(tokens)[:, 0], np.asarray(pos)
    slot_of = {}
    for r in reqs:
        if r.done or not r.out:
            continue
        p = len(r.tokens) + len(r.out) - 1
        match = np.flatnonzero((pos == p) & (tokens == r.out[-1]))
        if len(match) == 1:
            slot_of[id(r)] = (r, int(match[0]))
    live = sorted(slot_of.values(), key=lambda rs: -len(rs[0].out))
    if not live:
        return []
    rest = list(range(1, len(live)))
    seeds.rng(seed, seeds.STREAM_CACHE).shuffle(rest)
    chosen = [live[0]] + [live[j] for j in rest[:n - 1]]
    take = jax.jit(lambda a, i: jax.lax.dynamic_index_in_dim(
        a, i, axis=0, keepdims=False))

    def row(a, slot):
        return np.asarray(jax.device_get(take(a, slot))).astype(np.float32)

    layers = _layers(cache, cfg)
    out = []
    for r, slot in chosen:
        p = len(r.tokens) + len(r.out) - 1
        held = []
        for spec, lc in layers:
            if spec.mixer == "mamba":
                m = lc["mamba"]
                held.append({"state": row(m["state"], slot),
                             "conv": np.concatenate(
                                 [row(m[k], slot) for k in
                                  ("conv_x", "conv_B", "conv_C")], axis=-1)})
            else:
                held.append({k: row(lc["attn"][k], slot)[:p]
                             for k in ("k", "v")})
        out.append((r, held))
    return out


def _row_gap(got, ref) -> float:
    """Root mean square, over the rows (the leading axis), of each row's
    distance from the reference row over the larger of that row's norm and
    the median row norm."""
    import numpy as np

    shape = (ref.shape[0], -1)
    a, b = got.reshape(shape), ref.reshape(shape)
    norm = np.linalg.norm(b, axis=-1)
    floor = np.median(norm)
    dist = np.linalg.norm(a - b, axis=-1) \
        / np.maximum(np.maximum(norm, floor), 1e-30)
    return float(np.sqrt(np.mean(dist * dist)))


def worst(values) -> float:
    """The largest of ``values``; nan where any is nan (a state or a logit
    that is not a number fails its check), or where there are none."""
    import numpy as np

    return float(np.max(values)) if len(values) else math.nan


def state_gap(got, ref) -> float:
    """The widest ``_row_gap`` over every layer's state: SSM state rows are
    heads, conv state rows positions, key and value rows positions.

    A mean over rows, not their widest: a token whose top-10 experts sit
    at a near-tie is routed otherwise at any precision, which moves its own
    rows by tenths while the rest agree to rounding, so the widest row of a
    sound run read 0.007 to 0.41 on the chip and did not separate from the
    fp8 control's 1.19-1.43."""
    return worst([_row_gap(g[k], r[k]) for g, r in zip(got, ref)
                  for k in r])


def ref_length(mix) -> int:
    """Every reference sequence is padded to this length, so that one
    shape compiles."""
    return -(-int(mix["max_len"]) // 512) * 512


def gaps(weights, hp, reqs, length: int, dot: str = "highest"):
    """Per request, the widest gap between the reference's best logit and
    its logit of the token served (``dot="highest"``) or of the token the
    control puts first (any other ``dot``), over the served positions."""
    import jax.numpy as jnp

    from bench.reference import granite_h

    out = []
    for r in reqs:
        seq = list(r.tokens) + list(r.out[:-1])
        want = list(range(len(r.tokens) - 1, len(seq)))
        ref = granite_h.logits_at(weights, seq, want, hp, length=length)
        if dot == "highest":
            chosen = jnp.asarray(list(r.out), jnp.int32)
        else:
            ctl = granite_h.logits_at(weights, seq, want, hp, dot=dot,
                                      length=length)
            chosen = jnp.argmax(ctl, axis=-1)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
        out.append(float(jnp.max(best - got)))
    return out


def state_gaps(weights, hp, held, length: int, dot: str = "highest"):
    """Per request of ``closing_state``, ``state_gap`` of the engine's state
    (``dot="highest"``) or of the control's (any other ``dot``) against the
    reference's."""
    from bench.reference import granite_h

    out = []
    for r, got in held:
        seq = list(r.tokens) + list(r.out[:-1])
        ref = granite_h.state_at(weights, seq, hp, length=length)
        if dot != "highest":
            got = granite_h.state_at(weights, seq, hp, dot=dot,
                                     length=length)
        out.append(state_gap(got, ref))
    return out


# ------------------------------------------------------------------- runs

def run(ctx: Ctx) -> Outcome:
    mix = ctx.cell.traffic
    counter = device.CompileCounter()
    win = Window(ctx.trace_dir if ctx.trace else None)
    cfg, params, eng, reqs, drv, checked, phases = serve_window(ctx, win)
    trace, spans = programs.finish(win, ctx.save_trace)
    t0, t1 = drv.t_open, drv.t_close
    compiles = counter.count(t0, t1)
    mem = device.memory_peak_bytes(ctx.devices, checked)
    mem_runtime = device.memory_peak_bytes(ctx.devices)
    del checked
    e2e, work = window_stats(reqs, drv, int(mix["clients"]))
    e2e["setup_s"] = t0 - ctx.t0
    work["slots"] = int(mix["clients"])
    work["decode_steps"] = sum(1 for s, _ in drv.decodes if t0 <= s <= t1)
    if trace is not None:
        work["programs"] = [programs.Program.of(label, c.as_text()) for
                            label, c in compile_programs(
                                eng, cfg, int(mix["clients"]),
                                padded_shapes(eng, mix))]
        work["module_spans"] = spans
        notes = [("module_spans", {d: len(v) for d, v in spans.items()}),
                 ("device_scopes", programs.top_scopes(
                     trace, spans, work["programs"]))]
    else:
        notes = []
    held = closing_state(reqs, drv, cfg, STATE_REQUESTS, ctx.seed)
    del drv, eng                # the engine, its cache and programs
    gc.collect()

    picked = pick(reqs, t0, t1, int(mix["check_requests"]), ctx.seed)
    weights, hp = reference_weights(params, cfg)
    length = ref_length(mix)
    gap = {"logit_gap": gaps(weights, hp, picked, length),
           "state_gap": state_gaps(weights, hp, held, length)}
    return Outcome(
        e2e=e2e, work=work,
        checks=[Check(k, worst(v), ctx.limits[k]) for k, v in gap.items()],
        attempted=work["admitted"], failed=0, memory_peak_bytes=mem,
        trace=trace,
        notes=[("setup_phases_s", {k: round(v, 3) for k, v in phases.items()}),
               ("memory_runtime_peak_bytes", mem_runtime),
               ("compiles_in_window", compiles),
               ("decode_steps", work["decode_steps"]),
               ("requests_checked", len(picked)),
               ("served_tokens_checked", sum(len(r.out) for r in picked)),
               ("states_checked", len(held)),
               ("itl_samples", work["itl_n"]),
               ("ttft_samples", work["ttft_n"]),
               ("latencies_ms", {k: round(v, 4) for k, v in e2e.items()
                                 if k.endswith("_ms")})] + notes)


def calibrate(ctx: Ctx, seed_list, control_seeds):
    """Readings for the limits, all in this process: for each seed, fresh
    weights and traffic, a ramp and a window of ``ctx.seconds``, and the
    widest gaps of the checked requests and states against the reference;
    for the seeds in ``control_seeds`` also the control's (the reference in
    fp8) at the same positions and states."""
    rows = []
    mix = ctx.cell.traffic
    length = ref_length(mix)
    for seed in seed_list:
        c = dataclasses.replace(ctx, seed=seed)
        cfg, params, eng, reqs, drv, _, _ = serve_window(c, Window(None))
        t0, t1 = drv.t_open, drv.t_close
        held = closing_state(reqs, drv, cfg, STATE_REQUESTS, seed)
        del drv, eng
        gc.collect()
        picked = pick(reqs, t0, t1, int(mix["check_requests"]), seed)
        weights, hp = reference_weights(params, cfg)
        row = {"seed": seed, "requests": len(picked),
               "served": sum(len(r.out) for r in picked),
               "states": len(held),
               "program": {
                   "logit_gap": worst(gaps(weights, hp, picked, length)),
                   "state_gap": worst(state_gaps(weights, hp, held,
                                                 length))}}
        if seed in control_seeds:
            row["control"] = {
                "logit_gap": worst(gaps(weights, hp, picked, length,
                                        dot="fp8")),
                "state_gap": worst(state_gaps(weights, hp, held, length,
                                              dot="fp8"))}
        rows.append(row)
        del params, weights, held
        gc.collect()
    return rows
