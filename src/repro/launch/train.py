"""Production training entrypoint.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /data/ck --variant fsdp

On a real fleet this binary runs once per host (jax.distributed
initializes from the cluster env); here it drives the same code on local
devices.  Auto-resumes from the newest valid checkpoint; crash-safe by
construction (see repro.train.loop).
"""
from __future__ import annotations

import argparse
import json

from repro.configs import ARCHS, get_arch, reduce_for_smoke
from repro.distributed.sharding import make_variant
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.train.loop import train
from repro.train.step import default_accum
from repro.configs.base import ShapeCfg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU demo)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_for_smoke(cfg)
    mesh = make_local_mesh(model=args.model_parallel)
    rules = make_variant(args.variant)
    shape = ShapeCfg("cli", "train", args.seq, args.batch)
    accum = args.accum if args.accum is not None else default_accum(cfg, shape)

    print(json.dumps({"arch": cfg.name, "params_m": cfg.n_params() / 1e6,
                      "mesh": dict(mesh.shape), "variant": rules.name,
                      "accum": accum, "steps": args.steps}))
    res = train(cfg, mesh, rules, n_steps=args.steps,
                global_batch=args.batch, seq_len=args.seq,
                base_lr=args.lr, warmup=args.warmup, accum_steps=accum,
                ckpt_root=args.ckpt_dir, ckpt_every=args.ckpt_every,
                keep=args.keep, seed=args.seed, log_every=10)
    print(json.dumps({"resumed_from": res.resumed_from,
                      "steps_run": res.steps_run,
                      "first_loss": res.losses[0] if res.losses else None,
                      "final_loss": res.losses[-1] if res.losses else None,
                      "wall_s": round(res.wall_s, 1),
                      "ckpt_stats": res.ckpt_stats}))


if __name__ == "__main__":
    main()
