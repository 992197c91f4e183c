#!/usr/bin/env python3
"""Chip smoke test: continuous-batching serving of h2o-danube-1.8b at its
full published width on a TPU, clean and under the crt3 protection policy.

  python chip_smoke.py [--seed 0]     # one chip
  python chip_smoke.py --chips 4      # four chips: sharded vs one device

One chip serves 16 requests (prompt lengths 32-480 drawn from ``--seed``,
32 new tokens each, random bf16 weights from ``--seed``) through
``serve.scheduler.Scheduler`` with a paged KV cache, three times:

  clean      no protection policy;
  reference  crt3 at BER 1e-3, no weight faults, reference backend;
  fused      the same crt3 on the fused Pallas kernel, compiled for the chip.

It fails unless every request ends by length with 32 tokens in [0, vocab),
the reference and fused tokens are bit-identical, and two requests served
alone get the tokens they got in the crowded batch (per-request fault
streams).  ``--chips 4`` serves the crt3 reference arm on a (data=2,
model=2) mesh of four chips and on one device, in one process, and fails
unless the tokens are bit-identical.

Earlier lines report each phase: compile and wall seconds, tokens and peak
device memory.  They are smoke timings, not a benchmark.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU the script exits 1 at once.
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()
# Sharded and single-device runs give bit-identical tokens only when XLA
# keeps explicit f32->bf16->f32 roundings (docs/serving.md "Sharded
# serving").  The flag is an XLA debug option: JAX reads it from XLA_FLAGS
# and hands it to the TPU compiler (libtpu rejects it in LIBTPU_INIT_ARGS).
os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""), "--xla_allow_excess_precision=false"]
).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "h2o-danube-1.8b"
N_REQUESTS, NEW_TOKENS = 16, 32
PROMPT_LENS = (32, 480)
BER = 1e-3                           # the benchmark's crt3 configuration
ALONE = (0, 7)                       # requests re-served alone under crt3


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds the XLA backend spends compiling, from JAX's own monitoring
    events (tracing is left out: nested jits would count twice)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def make_requests(seed, vocab, n=N_REQUESTS, lens=PROMPT_LENS):
    rng = np.random.default_rng(seed)
    return [(rid, rng.integers(0, vocab, int(rng.integers(lens[0], lens[1] + 1)))
             .tolist()) for rid in range(n)]


def serve(sched, reqs, new_tokens=NEW_TOKENS):
    from repro.serve.scheduler import Request
    out = sched.run([Request(rid, toks, max_new_tokens=new_tokens)
                     for rid, toks in reqs])
    return {rid: out[rid] for rid, _ in reqs}


def check_served(name, res, vocab, new_tokens=NEW_TOKENS):
    for rid, r in res.items():
        check(r.finish_reason == "length" and len(r.generated) == new_tokens,
              f"{name}: request {rid} ended {r.finish_reason!r} after "
              f"{len(r.generated)} tokens")
        check(all(0 <= t < vocab for t in r.generated),
              f"{name}: request {rid} emitted a token outside [0, {vocab})")
    return {rid: r.generated for rid, r in res.items()}


def memory(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_in_use")


def kernel_sites(sched, reqs):
    """Mosaic kernel calls in the decode chunk's lowered program (one per
    protected linear that runs the compiled kernel, per layer of the scan)
    and in the prefill program."""
    import jax.numpy as jnp
    B = sched.cfg.max_batch
    caches = jax.eval_shape(lambda: sched._init_caches(B))
    z = jnp.zeros((B,), jnp.int32)
    chunk = sched._chunk.lower(sched.params, caches, z, z, z, z,
                               jnp.ones((B,), bool), sched.cfg.decode_chunk)
    from repro.serve.scheduler import Request
    batch1, last_idx, _ = sched._make_batch1(Request(*reqs[0]))
    prefill = sched._prefill_one.lower(sched.params, batch1, last_idx,
                                       jnp.asarray(0, jnp.int32))
    count = lambda low: low.as_text().count("tpu_custom_call")  # noqa: E731
    return count(chunk), count(prefill)


def one_chip(model, params, reqs, sched_cfg, clock, device):
    """Clean, crt3-reference and crt3-fused serving, checked and reported."""
    from repro import ft
    from repro.kernels.fused_decode.kernel import VMEM_LIMIT
    from repro.serve.scheduler import Scheduler
    vocab = model.cfg.vocab
    crt3 = ft.get_policy("crt3", ber=BER, weight_faults=False)
    arms = (("clean", None, "reference"), ("reference", crt3, "reference"),
            ("fused", crt3, "fused"))
    tokens, alone = {}, {}
    for name, policy, backend in arms:
        sched = Scheduler(model, params, sched_cfg, policy=policy,
                          ft_backend=backend)
        c0, w0 = clock.seconds, time.perf_counter()
        # serving requests alone first compiles every executable, so the
        # crowded run below is timed warm
        if policy is not None:
            alone[name] = {rid: check_served(
                f"{name} alone", serve(sched, [reqs[rid]]), vocab)[rid]
                for rid in ALONE}
        else:
            serve(sched, [reqs[0]])
        warm_s, compile_s = time.perf_counter() - w0, clock.seconds - c0
        w0 = time.perf_counter()
        res = serve(sched, reqs)
        wall = time.perf_counter() - w0
        tokens[name] = check_served(name, res, vocab)
        peak, _ = memory(device)
        line = (f"smoke[{name}]: compile_s={compile_s:.3f} "
                f"warmup_wall_s={warm_s:.3f} wall_s={wall:.3f} "
                f"tokens={sched.stats.tokens} "
                f"tok_per_s={sched.stats.tokens / wall:.3f} "
                f"peak_bytes_in_use={peak} (smoke timing, not a benchmark)")
        if name == "fused":
            sites, pre_sites = kernel_sites(sched, reqs)
            check(sites > 0, "fused: the decode program holds no compiled "
                  "kernel call (interpreted or routed away)")
            steps = sched.stats.chunk_calls * sched_cfg.decode_chunk
            line += (f"\nsmoke[fused]: decode program holds {sites} kernel "
                     f"calls per layer; {sites * model.cfg.n_layers * steps} "
                     f"protected-linear calls ran the compiled kernel over "
                     f"{steps} decode steps; prefill program holds "
                     f"{pre_sites} (rule: the kernel serves a call whose VMEM "
                     f"plan fits {VMEM_LIMIT >> 20} MiB, the reference "
                     f"datapath every other)")
        print(line, flush=True)
        del sched
    check(tokens["reference"] == tokens["fused"],
          "crt3 tokens differ between the reference and fused backends")
    for name in ("reference", "fused"):
        for rid in ALONE:
            check(alone[name][rid] == tokens[name][rid],
                  f"{name}: request {rid} served alone differs from crowded")
    same = sum(tokens["clean"][r] == tokens["reference"][r] for r in tokens["clean"])
    print(f"smoke: reference == fused on all {len(reqs)} requests; alone == "
          f"crowded for requests {ALONE}; {same}/{len(reqs)} crt3 requests "
          "match clean", flush=True)


def four_chips(model, params, reqs, sched_cfg, clock, devices):
    """crt3 reference serving on a (data=2, model=2) mesh and on one device."""
    from repro import ft
    from repro.launch.mesh import make_mesh
    from repro.serve.scheduler import Scheduler
    vocab = model.cfg.vocab
    crt3 = ft.get_policy("crt3", ber=BER, weight_faults=False)
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    tokens = {}
    for name, m in (("mesh_2x2", mesh), ("one_device", None)):
        sched = Scheduler(model, params, sched_cfg, policy=crt3, mesh=m)
        c0, w0 = clock.seconds, time.perf_counter()
        res = serve(sched, reqs)
        wall = time.perf_counter() - w0
        tokens[name] = check_served(name, res, vocab)
        in_use = [memory(d)[1] for d in devices[:4]]
        print(f"smoke[{name}]: compile_s={clock.seconds - c0:.3f} "
              f"wall_s_incl_compile={wall:.3f} tokens={sched.stats.tokens} "
              f"bytes_in_use_per_device={in_use} "
              "(smoke timing, not a benchmark)", flush=True)
        if m is not None:
            check(all(b for b in in_use),
                  "mesh: a device of the mesh holds nothing")
        del sched
    check(tokens["mesh_2x2"] == tokens["one_device"],
          "crt3 tokens differ between the 2x2 mesh and one device")
    print(f"smoke: mesh_2x2 == one_device on all {len(reqs)} requests",
          flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.configs import get_config, get_run_config
    from repro.models import build
    from repro.serve.scheduler import SchedulerConfig

    clock = CompileClock()
    cfg = get_config(ARCH)
    model = build(cfg, get_run_config(ARCH))
    t0 = time.perf_counter()
    params = jax.jit(lambda k: model.init(k))(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"smoke: {ARCH} d_model={cfg.d_model} layers={cfg.n_layers} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} params={n_params} (random bf16, seed "
          f"{args.seed}) init_s={time.perf_counter() - t0:.3f} on "
          f"{len(devices)} x {devices[0].device_kind}", flush=True)
    reqs = make_requests(args.seed, cfg.vocab)
    sched_cfg = SchedulerConfig(max_batch=8, buckets=(512,),
                                max_new_tokens=NEW_TOKENS, seed=args.seed)
    if args.chips == 4:
        four_chips(model, params, reqs, sched_cfg, clock, devices)
    else:
        one_chip(model, params, reqs, sched_cfg, clock, devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
