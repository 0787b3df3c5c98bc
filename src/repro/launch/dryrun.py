"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
CPU devices host the production mesh; every train/prefill/decode program is
jit-lowered against ShapeDtypeStruct stand-ins (zero allocation — Jamba-398B
costs nothing), compiled through GSPMD, and its memory_analysis /
cost_analysis / collective schedule recorded for the roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_8b \
      --shape train_4k --mesh single --out experiments/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
# The VERY FIRST lines, before any jax import: the dry-run (and only the
# dry-run) needs 512 placeholder devices; jax locks device count at first init.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + \
    os.environ.get("XLA_FLAGS", "")

import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, LM_SHAPES, get_config, rules_for,
                           shapes_for)
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models import abstract_params, forward, make_cache
from repro.models.params import active_param_count
from repro.optim import adamw
from repro.sharding import rules as shr
from repro.train import step as train_step_lib


# ------------------------------------------------------------- input specs

def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    specs = {}
    if shape.kind == "train":
        if cfg.embed_inputs and not cfg.is_encoder_decoder:
            specs["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
        else:
            specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        if cfg.is_encoder_decoder:
            specs["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    elif shape.kind == "prefill":
        if cfg.embed_inputs and not cfg.is_encoder_decoder:
            specs["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
        else:
            specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        if cfg.is_encoder_decoder:
            specs["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    else:  # decode
        specs["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    return specs


def _batch_sharding_tree(cfg, shape, specs, mesh):
    out = {}
    for k, v in specs.items():
        out[k] = shr.data_sharding(mesh, v.ndim, batch_size=shape.global_batch)
    return out


def _cache_shardings(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Shard caches: batch over data axes when divisible; otherwise (long
    context, batch=1) shard the KV seq dim over 'data'. Heads/state shard
    over 'model' when divisible."""
    B = shape.global_batch
    # Largest divisible prefix of ('pod','data') — not all-or-nothing: a
    # batch divisible by 'pod' alone still shards over it (rules.py).
    ba = shr.batch_partition(mesh, B)
    batch_ok = bool(ba)
    model_n = mesh.shape["model"]

    cache = make_cache(cfg, B, shape.seq_len, abstract=True)

    def spec_for_leaf(path_names, a):
        nd = a.ndim
        parts = [None] * nd
        name = path_names[-1]
        # Trailing ranks (leading dims, if any, are 'layers' scan stacking):
        #   k/v/ck/cv: (B, L, kv, hd)   state: (B, h, p, n)   conv_*: (B, w-1, c)
        trail = 3 if name.startswith("conv") else 4
        bdim = nd - trail
        if batch_ok and a.shape[bdim] == B:
            parts[bdim] = ba if len(ba) > 1 else ba[0]
        kv_seq = cfg.sharding_rules.get("__kv_seq_shard__")
        if name in ("k", "v", "ck", "cv"):
            if kv_seq and a.shape[nd - 3] % mesh.shape.get(kv_seq, 1) == 0:
                # flash-decoding layout: cache sequence over the model axis
                parts[nd - 3] = kv_seq
            elif "data" not in ba and "data" in mesh.shape \
                    and a.shape[nd - 3] % mesh.shape["data"] == 0:
                parts[nd - 3] = "data"  # sequence-parallel cache (batch=1)
            if parts[nd - 3] != "model" and a.shape[nd - 2] % model_n == 0:
                parts[nd - 2] = "model"
        elif name == "state":
            if a.shape[nd - 3] % model_n == 0:
                parts[nd - 3] = "model"  # ssm heads
        elif name.startswith("conv"):
            if a.shape[nd - 1] % model_n == 0:
                parts[nd - 1] = "model"
        return NamedSharding(mesh, P(*parts))

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v, path + (str(i),)) for i, v in enumerate(tree)]
        return spec_for_leaf([p for p in path if not p.isdigit()] or ("?",), tree)

    return cache, walk(cache)


# --------------------------------------------------------------- cell runner

def _lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                n_micro: int, global_batch: int):
    """Build and lower the cell's program. Returns (lowered, kind)."""
    params_abs = abstract_params(cfg)
    pshard = shr.param_shardings(cfg, mesh)
    specs = input_specs(cfg, dataclasses_replace_batch(shape, global_batch))
    bshard = {k: shr.data_sharding(mesh, v.ndim, batch_size=global_batch)
              for k, v in specs.items()}
    opt_cfg = adamw.AdamWConfig(state_dtype=cfg.opt_state_dtype,
                                division=cfg.division)

    if shape.kind == "train":
        state_abs = train_step_lib.abstract_state(cfg, params_abs, opt_cfg)
        state_shard = train_step_lib.TrainState(
            params=pshard,
            opt=adamw.AdamWState(step=NamedSharding(mesh, P()),
                                 m=pshard, v=pshard),
            step=NamedSharding(mesh, P()))

        def fn(state, batch):
            new_state, metrics = train_step_lib.train_step(
                cfg, opt_cfg, state, batch, n_micro=n_micro)
            return new_state, metrics["loss"]

        lowered = jax.jit(
            fn, in_shardings=(state_shard, bshard),
            out_shardings=(state_shard, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        ).lower(state_abs, specs)
        return lowered, "train"

    shape_b = dataclasses_replace_batch(shape, global_batch)
    if shape.kind == "prefill":
        cache_abs, cache_shard = _cache_shardings(cfg, shape_b, mesh)

        def fn(params, batch):
            logits, cache, _ = forward(cfg, params, mode="prefill", **batch)
            return logits[:, -1], cache

        logits_shard = shr.data_sharding(mesh, 2, batch_size=global_batch)
        lowered = jax.jit(
            fn, in_shardings=(pshard, bshard),
            out_shardings=(logits_shard, cache_shard),
        ).lower(params_abs, specs)
        return lowered, "inference"

    cache_abs, cache_shard = _cache_shardings(cfg, shape_b, mesh)

    def fn(params, cache, tokens):
        logits, new_cache, _ = forward(
            cfg, params, tokens=tokens, cache=cache,
            pos=jnp.int32(shape.seq_len - 1), mode="decode")
        return logits[:, 0], new_cache

    logits_shard = shr.data_sharding(mesh, 2, batch_size=global_batch)
    lowered = jax.jit(
        fn, in_shardings=(pshard, cache_shard, bshard["tokens"]),
        out_shardings=(logits_shard, cache_shard),
        donate_argnums=(1,),
    ).lower(params_abs, cache_abs, specs["tokens"])
    return lowered, "inference"


def dataclasses_replace_batch(shape: ShapeConfig, global_batch: int):
    import dataclasses as dc

    return dc.replace(shape, global_batch=global_batch)


def _probe_measure(cfg, shape, mesh, global_batch, n_dev, pod_size):
    """Compile one small probe and extract {flops, bytes, ici, dcn, ops}."""
    lowered, _ = _lower_cell(cfg, shape, mesh, n_micro=1,
                             global_batch=global_batch)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    colls = rl.parse_collectives(compiled.as_text(), n_dev, pod_size)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "ici": colls["ici_bytes_tpu"],
        "dcn": colls["dcn_bytes_tpu"],
        "ici_raw": colls["ici_bytes"],
        "dcn_raw": colls["dcn_bytes"],
        "ops": colls["ops"],
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             variant: str = "base", skip_probe: bool = False):
    """Per cell:
      1. REAL program (scans rolled, full depth/microbatches): compile proof
         + memory_analysis. This is the runnability deliverable.
      2. COST PROBES: XLA's cost_analysis counts a while-loop body ONCE
         regardless of trip count (verified empirically), so per-step cost is
         reconstructed affinely: lower tiny stacks with group repeats
         (1,..,1) and (1,..,2,..,1), chunk-scans unrolled, one microbatch;
         cost = fixed + sum_g (R_g) * marginal_g, then x n_micro.
         Probes are small (1-2 periods) => fast compiles at full fidelity of
         per-layer HLO (remat, collectives, MoE dispatch all included).
    """
    import dataclasses as dc

    cfg = get_config(arch)
    cfg, model_axis = apply_variant(cfg, variant)
    shape = LM_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod, model=model_axis)
    n_dev = mesh.devices.size
    pod_size = (n_dev // mesh.shape["pod"]) if "pod" in mesh.shape else None

    n_batch = 1
    for ax in shr.batch_axes(mesh):
        n_batch *= mesh.shape[ax]
    if shape.kind == "train":
        per_dev_batch = max(1, shape.global_batch // n_batch)
        n_micro = max(1, per_dev_batch // cfg.train_microbatch_size)
    else:
        n_micro = 1

    base_groups = cfg.groups()
    n_groups = len(base_groups)

    with mesh, shr.use_mesh(mesh):
        # --- 1. real program: the runnability proof + memory analysis
        t0 = time.time()
        lowered, kind = _lower_cell(cfg, shape, mesh, n_micro=n_micro,
                                    global_batch=shape.global_batch)
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()

        # --- 2. affine cost probes
        t0 = time.time()
        KEYS = ("flops", "bytes", "ici", "dcn", "ici_raw", "dcn_raw")
        agg = {k: 0.0 for k in KEYS}
        ops_sample = []
        if not skip_probe:
            probe_batch = (shape.global_batch // n_micro
                           if shape.kind == "train" else shape.global_batch)
            ones = tuple(1 for _ in range(n_groups))
            pcfg = dc.replace(cfg, scan_unroll=True,
                              group_repeat_override=ones)
            p0 = _probe_measure(pcfg, shape, mesh, probe_batch, n_dev, pod_size)
            ops_sample = p0["ops"]
            marginals = []
            for gi in range(n_groups):
                if base_groups[gi].repeat == 1:
                    marginals.append(None)  # fixed part already covers it
                    continue
                rep = tuple(2 if i == gi else 1 for i in range(n_groups))
                pcfg_g = dc.replace(cfg, scan_unroll=True,
                                    group_repeat_override=rep)
                pg = _probe_measure(pcfg_g, shape, mesh, probe_batch, n_dev,
                                    pod_size)
                marginals.append({k: pg[k] - p0[k] for k in KEYS})
            for k in agg:
                total = p0[k]
                for gi, m in enumerate(marginals):
                    if m is not None:
                        total += (base_groups[gi].repeat - 1) * m[k]
                agg[k] = total * n_micro
        t_probe = time.time() - t0

    n_active = active_param_count(cfg)
    tokens_global = (shape.global_batch * shape.seq_len
                     if shape.kind != "decode" else shape.global_batch)
    model_flops = rl.model_flops_per_device(n_active, tokens_global, n_dev, kind)

    from repro.launch import memmodel
    mm = memmodel.hbm_traffic(cfg, shape, mesh, n_micro=n_micro,
                              fused_attention=cfg.use_flash_kernel)

    roof = rl.Roofline(
        flops=agg["flops"],
        bytes_accessed=mm["total_bytes"],
        ici_bytes=agg["ici"],
        dcn_bytes=agg["dcn"],
        ici_bytes_raw=agg["ici_raw"],
        dcn_bytes_raw=agg["dcn_raw"],
        model_flops=model_flops,
    )
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "variant": variant,
        "devices": n_dev,
        "n_micro": n_micro,
        # Every silent spec_for drop, named: a replicated 8B-param tensor
        # should be a report line, not a surprise OOM (report.py renders).
        "sharding_fallbacks": shr.param_fallbacks(cfg, mesh),
        "compile_s": t_compile,
        "probe_compile_s": t_probe,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_hbm_bytes": (mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes),
        },
        "hbm_traffic_model": mm,
        "hlo_bytes_accessed_upper_bound": agg["bytes"],
        "collectives": {
            "ici_bytes": agg["ici"],          # TPU-corrected (bf16 reductions)
            "dcn_bytes": agg["dcn"],
            "ici_bytes_raw": agg["ici_raw"],  # as compiled for CPU
            "dcn_bytes_raw": agg["dcn_raw"],
            "n_ops": len(ops_sample),
            "by_op": _summarize_ops(ops_sample),
        },
        "roofline": roof.to_dict(),
    }


def _summarize_ops(ops):
    agg = {}
    for o in ops:
        key = o["op"] + ("/dcn" if o["cross_pod"] else "")
        a = agg.setdefault(key, {"count": 0, "wire_bytes": 0.0})
        a["count"] += 1
        a["wire_bytes"] += o["wire_bytes"]
    return agg


# ------------------------------------------------------------ perf variants

def apply_variant(cfg: ModelConfig, variant: str):
    """Named perf-iteration variants (hillclimb experiments, §Perf).

    Compound variants combine with '+': e.g. ``tp4+seq_shard``.
    Returns (cfg, model_axis_size)."""
    import dataclasses as dc

    from repro.core.division_modes import DivisionConfig

    model_axis = 16
    for v in variant.split("+"):
        if v == "base":
            continue
        elif v == "exact_div":      # paper-baseline comparison: XLA divides
            cfg = dc.replace(cfg, division=DivisionConfig(mode="exact"))
        elif v == "div_paper_n5":   # paper-faithful: n=5, 53-bit, §6 schedule
            cfg = dc.replace(cfg, division=DivisionConfig(
                mode="taylor", n_iters=5, precision_bits=53, schedule="paper"))
        elif v == "no_remat":
            cfg = dc.replace(cfg, remat=False)
        elif v == "micro2x":
            cfg = dc.replace(cfg, train_microbatch_size=max(
                1, cfg.train_microbatch_size * 2))
        elif v == "micro_half":
            cfg = dc.replace(cfg, train_microbatch_size=max(
                1, cfg.train_microbatch_size // 2))
        elif v == "seq_shard":      # Megatron-style sequence parallelism
            cfg = dc.replace(cfg, sharding_rules={
                **cfg.sharding_rules, "__seq_shard__": "model"})
        elif v == "kvseq":          # flash-decoding: KV cache seq over model
            cfg = dc.replace(cfg, sharding_rules={
                **cfg.sharding_rules, "__kv_seq_shard__": "model"})
        elif v == "flash":          # fused flash-attention kernel (memmodel)
            cfg = dc.replace(cfg, use_flash_kernel=True)
        elif v == "ep_tp":          # MoE: experts local, expert-FF over model
            cfg = dc.replace(cfg, sharding_rules={
                **cfg.sharding_rules, "experts": None, "expert_mlp": "model"})
        elif v == "ep_model":       # MoE: experts over model axis
            cfg = dc.replace(cfg, sharding_rules={
                **cfg.sharding_rules, "experts": "model", "expert_mlp": None})
        elif v == "sort_dispatch":  # megablocks-style MoE position assignment
            cfg = dc.replace(cfg, moe_dispatch="sort")
        elif v == "local_dispatch":  # shard-local gather dispatch (collective-free)
            cfg = dc.replace(cfg, moe_dispatch="local")
        elif v == "optbf16":        # bf16 optimizer moments (fit at low TP)
            cfg = dc.replace(cfg, opt_state_dtype="bfloat16")
        elif v.startswith("tp"):    # tensor-parallel degree (data = 256/tp)
            model_axis = int(v[2:])
        elif v.startswith("chunk"):
            cfg = dc.replace(cfg, attn_chunk=int(v[5:]))
        elif v.startswith("mb"):    # absolute microbatch size
            cfg = dc.replace(cfg, train_microbatch_size=int(v[2:]))
        else:
            raise ValueError(f"unknown variant {v}")
    return cfg, model_axis


# --------------------------------------------------------------------- main

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    archs = [a for a in ARCH_IDS if a != "paper_fpdiv"] if args.all else [args.arch]
    for arch in archs:
        cfg = get_config(arch)
        shps = ([s.name for s in shapes_for(cfg)] if (args.all or not args.shape)
                else [args.shape])
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        for s in shps:
            for m in meshes:
                cells.append((arch, s, m))

    failures = 0
    for arch, s, m in cells:
        tag = f"{arch}_{s}_{m}" + (f"_{args.variant}" if args.variant != "base" else "")
        try:
            res = run_cell(arch, s, m == "multi", variant=args.variant)
            path = os.path.join(args.out, tag + ".json")
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            r = res["roofline"]
            print(f"[ok] {tag}: bound={r['bound']} "
                  f"t=(c {r['t_compute']:.4f}, m {r['t_memory']:.4f}, "
                  f"x {r['t_collective']:.4f})s mfu={r['mfu']:.3f} "
                  f"compile={res['compile_s']:.0f}s", flush=True)
        except Exception as e:
            failures += 1
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
