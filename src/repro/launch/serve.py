"""Serving launcher: batched generation on the devices present.

  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
      [--smoke] [--tp 1] [--batch 8] [--prompt-len 32] [--new 32] \
      [--loop scan|python] [--policy crt3 --ber 1e-4]

The published configuration serves at full width; ``--smoke`` takes the
reduced one.  One device serves without a mesh; several form a
(data, model) mesh with ``--tp`` devices on 'model'.
"""
from __future__ import annotations

import argparse

from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="the reduced configuration")
    ap.add_argument("--tp", type=int, default=1,
                    help="devices on the 'model' axis when several serve")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--loop", choices=("scan", "python"), default="scan",
                    help="fused lax.scan decode loop (default) or the "
                         "per-token dispatch loop")
    ap.add_argument("--policy", default=None,
                    help="repro.ft registry policy name (e.g. crt3, cl)")
    ap.add_argument("--ber", type=float, default=1e-4)
    args = ap.parse_args()

    use_compile_cache()
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_run_config
    from repro.launch.mesh import make_mesh
    from repro.models import build
    from repro.serve.engine import Engine, ServeConfig

    cfg = get_config(args.arch, reduced=args.smoke)
    model = build(cfg, get_run_config(args.arch))
    n = len(jax.devices())
    mesh = (None if n == 1
            else make_mesh((n // args.tp, args.tp), ("data", "model")))
    params = jax.jit(lambda k: model.init(k))(jax.random.PRNGKey(0))
    policy = None
    if args.policy:
        from repro import ft
        policy = ft.get_policy(args.policy, ber=args.ber)
    engine = Engine(model, params, mesh=mesh,
                    cfg=ServeConfig(max_new_tokens=args.new,
                                    temperature=args.temperature,
                                    loop=args.loop),
                    policy=policy)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab)}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jnp.zeros(
            (args.batch, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.enc_dec:
        batch["frames"] = jnp.zeros(
            (args.batch, args.prompt_len, cfg.d_model), jnp.bfloat16)
    out = engine.generate(batch)
    print(f"generated {out.shape[1]} tokens for {out.shape[0]} requests "
          f"in {engine.stats.roundtrips} host roundtrips ({args.loop} loop)")
    print(out)


if __name__ == "__main__":
    main()
