"""Training launcher: single-host end-to-end driver.

On a real fleet each host runs this same script under
``jax.distributed.initialize`` (env-driven); the data pipeline shards by
host_index and the mesh comes from make_production_mesh. On this container it
drives the smoke/paper configs on the host mesh — the multi-pod path is
exercised by dryrun.py.

  PYTHONPATH=src python -m repro.launch.train --arch paper_fpdiv --steps 200 \
      --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_fpdiv")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--division", default=None,
                    choices=[None, "exact", "taylor", "taylor_pallas", "ilm"])
    args = ap.parse_args()

    import dataclasses

    from repro.configs import get_config, get_smoke_config
    from repro.core.division_modes import DivisionConfig
    from repro.data import DataConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train.loop import LoopConfig, run

    enable_compile_cache()
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    if args.division:
        cfg = dataclasses.replace(cfg, division=DivisionConfig(mode=args.division))

    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                          global_batch=args.global_batch, seed=args.seed)
    loop = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, n_micro=args.n_micro,
                      seed=args.seed)
    out = run(cfg, loop, data_cfg)
    print(f"final loss: {out['losses'][-1]:.4f} after {out['last_step']} steps")


if __name__ == "__main__":
    main()
