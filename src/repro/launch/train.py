"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-27b \
      --shape train_4k [--steps N] [--ckpt DIR] [--smoke]

On a real TPU fleet this process runs per host (jax.distributed.initialize
picks up the cluster env); --smoke runs the reduced config on CPU.  The mesh
is (data, model) per pod, with 'pod' prepended under --multi-pod.
"""
from __future__ import annotations

import argparse

from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config, tiny shape, local mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() (TPU fleet)")
    args = ap.parse_args()

    use_compile_cache()
    import jax
    if args.distributed:
        jax.distributed.initialize()

    from repro.configs import SHAPES, get_config, get_run_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_local_mesh, make_production_mesh
    from repro.models import build
    from repro.optim import AdamWConfig
    from repro.train import Trainer, TrainerConfig

    cfg = get_config(args.arch, reduced=args.smoke)
    run = get_run_config(args.arch)
    model = build(cfg, run)
    if args.smoke:
        mesh = make_local_mesh() if jax.device_count() == 1 else None
        shape = ShapeConfig("smoke", "train", 64, 8)
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        shape = SHAPES[args.shape]

    tc = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt,
                       ckpt_every=max(args.steps // 4, 1), log_every=10)
    trainer = Trainer(model, shape, AdamWConfig(dtype=run.adam_dtype),
                      tc, mesh=mesh)
    state, step = trainer.run()
    print(f"finished at step {step}; stragglers: {trainer.straggler_events}")


if __name__ == "__main__":
    main()
