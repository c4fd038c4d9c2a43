"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b \
        --steps 100 [--smoke] [--mesh single|multi|host]

On TPU hardware this builds the production mesh, shards the train state
per dist/shardings.py rules and runs the fault-tolerant TrainLoop. On this
CPU container use --smoke (reduced config, host mesh) — the full configs
are exercised via launch/dryrun.py instead.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on the host devices")
    ap.add_argument("--mesh", default="host", choices=["single", "multi", "host"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()

    from repro import env
    env.enable_compile_cache()

    from repro.configs.registry import get_config, get_smoke
    from repro.checkpoint.manager import CheckpointManager
    from repro.dist.shardings import ShardingRules
    from repro.training.optimizer import AdamWConfig
    from repro.training.train_loop import (TrainLoop, init_train_state,
                                           make_train_step)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    opt_cfg = AdamWConfig(lr=args.lr, schedule=args.schedule,
                          warmup_steps=max(5, args.steps // 20),
                          total_steps=args.steps)

    rules = None
    if args.mesh in ("single", "multi"):
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        rules = ShardingRules(mesh)

    params, opt_state = init_train_state(cfg, opt_cfg, jax.random.key(0),
                                         compress_grads=args.compress_grads)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params / 1e6:.1f}M "
          f"mesh={args.mesh} steps={args.steps}")

    rng = np.random.default_rng(0)

    def data(step: int):
        r = np.random.default_rng(step)
        shape = (args.global_batch, args.seq)
        if cfg.n_codebooks > 1:
            shape += (cfg.n_codebooks,)
        toks = r.integers(0, cfg.vocab_size, shape)
        batch = {"labels": jax.numpy.asarray(toks, jax.numpy.int32)}
        if cfg.input_mode == "embeddings":
            batch["embeddings"] = jax.numpy.asarray(
                rng.standard_normal((args.global_batch, args.seq,
                                     cfg.d_model)), cfg.cdtype)
        else:
            batch["tokens"] = batch["labels"]
        return batch

    step_fn = jax.jit(make_train_step(cfg, opt_cfg, rules,
                                      remat=not args.smoke,
                                      compress_grads=args.compress_grads),
                      donate_argnums=(0, 1))
    mgr = CheckpointManager(args.ckpt_dir, keep=3, async_save=True)
    loop = TrainLoop(cfg, opt_cfg, data, ckpt_manager=mgr, ckpt_every=50)
    loop.run(params, opt_state, args.steps, train_step=step_fn)


if __name__ == "__main__":
    main()
