"""Training launcher.

Local (CPU / single host) end-to-end run at smoke scale; on a pod the same
driver runs with ``--full`` after ``jax.distributed.initialize()`` (the
mesh/sharding machinery is the dry-run-proven path in launch/mesh.py).

    PYTHONPATH=src python -m repro.launch.train --arch deit-b --steps 100
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deit-b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64, help="LM sequence length")
    ap.add_argument("--full", action="store_true",
                    help="full published config (pod-scale)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    from repro.configs import get_config, get_smoke_config
    from repro.configs.shapes import ShapeSpec
    from repro.launch import steps as S
    from repro.training.train_loop import TrainLoopConfig, run

    use_compile_cache()
    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    if cfg.family == "lm":
        shape = ShapeSpec("cli", "train", seq_len=args.seq,
                          global_batch=args.batch)
    else:
        shape = ShapeSpec("cli", "train", img_res=getattr(cfg, "img_res", 64),
                          global_batch=args.batch)
    S.shapes_for(cfg)["cli"] = shape
    try:
        cell = S.build_cell(args.arch, "cli", cfg=cfg)
    finally:
        S.shapes_for(cfg).pop("cli", None)

    out = run(cell, TrainLoopConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, seed=args.seed))
    print(f"final loss {out['losses'][-1][1]:.4f} in {out['wall_s']:.1f}s")


if __name__ == "__main__":
    main()
