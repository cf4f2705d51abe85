"""Production serving entrypoint: batched generate over the ServeEngine
with optional mid-run service checkpointing.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \
      --batch 4 --prompt-len 16 --new-tokens 32 --snapshot-dir /tmp/svc
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCHS, get_arch, reduce_for_smoke
from repro.distributed.sharding import make_variant
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.params import init_params
from repro.models.registry import get_api
from repro.serve.engine import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=1)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--snapshot-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_for_smoke(cfg)
    api = get_api(cfg)
    max_seq = args.prompt_len + args.new_tokens * args.rounds + 8
    params = init_params(api.param_defs(cfg, max_seq), jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, make_local_mesh(model=args.model_parallel),
                      make_variant(args.variant), max_seq=max_seq)

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    extras = {}
    if cfg.family == "audio":
        extras["frames"] = np.ones(
            (args.batch, cfg.encoder.n_frames, cfg.d_model), np.float32) * .1
    if cfg.family == "vlm":
        extras["vision_embeds"] = np.ones(
            (args.batch, cfg.n_vision_tokens, cfg.d_model), np.float32) * .1

    for r in range(args.rounds):
        res = eng.generate(prompts if r == 0 else res.tokens[:, -args.prompt_len:],
                           args.new_tokens, extras=extras)
        print(json.dumps({"round": r, "prefill_s": round(res.prefill_s, 3),
                          "decode_s": round(res.decode_s, 3),
                          "tok_per_s": round(res.tokens_per_s, 1)}))
        if args.snapshot_dir:
            eng.snapshot_service(CheckpointManager(args.snapshot_dir), step=r)
            print(json.dumps({"snapshot": args.snapshot_dir, "step": r}))


if __name__ == "__main__":
    main()
