"""Serving launcher: prefill + greedy decode on the host, division unit as a knob.

  PYTHONPATH=src python -m repro.launch.serve --arch paper_fpdiv --smoke \
      --prompt-len 32 --max-new 16 --batch 4 --division-mode goldschmidt

``--batch 1`` runs the single-request path; ``--batch N`` runs the batched
path over N unequal-length prompts (exercising the padded-prompt masking).
``--division-mode``/``--n-iters``/``--schedule`` swap the division unit the
whole decode path runs on. Prints generated tokens plus prefill latency and
decode throughput.
"""
from __future__ import annotations

import argparse
import dataclasses
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_fpdiv")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--division-mode", default=None,
                    choices=["exact", "taylor", "taylor_pallas", "goldschmidt",
                             "goldschmidt_pallas", "ilm"],
                    help="division unit for every softmax/rmsnorm in the "
                         "decode path (default: the config's own mode)")
    ap.add_argument("--n-iters", type=int, default=None,
                    help="Taylor/Goldschmidt iteration count")
    ap.add_argument("--schedule", default=None, choices=["paper", "factored"],
                    help="Taylor evaluation schedule")
    args = ap.parse_args()

    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params
    from repro.serving import ServingEngine

    enable_compile_cache()
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    division = None
    if args.division_mode or args.n_iters or args.schedule:
        repl = {}
        if args.division_mode:
            repl["mode"] = args.division_mode
        if args.n_iters:
            repl["n_iters"] = args.n_iters
        if args.schedule:
            repl["schedule"] = args.schedule
        division = dataclasses.replace(cfg.division, **repl)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    engine = ServingEngine(cfg, params, division=division,
                           max_len=args.prompt_len + args.max_new + 64)
    print(f"[serve] arch={cfg.name} division={engine.cfg.division.mode} "
          f"n_iters={engine.cfg.division.n_iters} "
          f"schedule={engine.cfg.division.schedule} batch={args.batch}")

    if args.batch > 1:
        # unequal-length prompts exercise the padded-prompt masking path
        prompts = [list(range(1, max(2, args.prompt_len + 1 - 3 * i)))
                   for i in range(args.batch)]
        t0 = time.perf_counter()
        outs = engine.generate_batch(prompts, max_new=args.max_new)
        dt = time.perf_counter() - t0
        for p, o in zip(prompts, outs):
            print(f"prompt({len(p)} toks) -> generated {len(o)} tokens: {o}")
        n_tok = sum(len(o) for o in outs)
    else:
        prompt = list(range(1, args.prompt_len + 1))
        t0 = time.perf_counter()
        out = engine.generate(prompt, max_new=args.max_new)
        dt = time.perf_counter() - t0
        print(f"prompt({len(prompt)} toks) -> generated {len(out)} tokens: {out}")
        n_tok = len(out)
    print(f"[serve] {n_tok} tokens in {dt:.2f}s "
          f"(incl. compile) = {n_tok / dt:.1f} tok/s")


if __name__ == "__main__":
    main()
