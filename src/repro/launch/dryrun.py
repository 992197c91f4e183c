"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, lower + compile the appropriate
step (train_step / prefill / decode) for the production meshes and record:
  - memory_analysis (per-device bytes: proves it fits a 16 GB v5e)
  - cost_analysis (HLO flops/bytes; NB scan bodies are counted once — the
    roofline uses analytic FLOPs as primary, see benchmarks/roofline.py)
  - per-collective wire bytes parsed from the post-SPMD HLO, with while-loop
    bodies multiplied by their trip counts (nested scans handled).

Results land incrementally in dryrun_results/<mesh>/<arch>__<shape>.json.

Usage:
  python -m repro.launch.dryrun [--arch A] [--shape S] [--mesh single|multi|both]
"""
import os

from repro.launch.compile_cache import use_compile_cache

os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512")
use_compile_cache()

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCHS, SHAPES, get_config, get_run_config  # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh  # noqa: E402
from repro.models import build  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.parallel import sharding as S  # noqa: E402
from repro.train.train_step import (init_state, make_decode_step,  # noqa: E402
                                    make_prefill_step, make_train_step,
                                    state_shardings)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../dryrun_results")


# --------------------------------------------------------------- HLO parse -
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s32|s64|s16|s8|u32|u64|u16|u8|pred)\[([0-9,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "s64": 8,
          "s16": 2, "s8": 1, "u32": 4, "u64": 8, "u16": 2, "u8": 1, "pred": 1}
_COLL_RE = re.compile(
    r"=\s*(\([^=]*?\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _BYTES.get(dt.split("e")[0] if dt.startswith("f8") else dt, 2)
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return 1


def wire_bytes(line: str) -> float:
    """Per-device wire traffic of one collective (ring algorithms).
    XLA:CPU promotes bf16 reductions to f32 ('..._promoted' reducers); those
    move half the bytes on a TPU, where bf16 collectives are native."""
    m = _COLL_RE.search(line)
    out_bytes = _shape_bytes(m.group(1))
    if "_promoted" in line:
        out_bytes //= 2
    op = m.group(2)
    g = max(_group_size(line), 1)
    if op == "all-gather":
        return out_bytes * (g - 1) / g
    if op == "reduce-scatter":
        return out_bytes * (g - 1)
    if op == "all-reduce":
        return 2 * out_bytes * (g - 1) / g
    if op == "all-to-all":
        return out_bytes * (g - 1) / g
    return out_bytes  # collective-permute


def parse_collectives(hlo: str) -> dict:
    """Total per-device collective wire bytes, scan bodies x trip count."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        m = re.match(r"(ENTRY\s+)?%?([\w\.\-]+)\s*\([^)]*\)\s*->.*\{", stripped)
        if m and (stripped.endswith("{")):
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                comps["__entry__"] = comps[cur]
            continue
        if stripped == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(stripped)

    # map body computation -> trip count.  XLA stamps the while op with
    # backend_config known_trip_count; fall back to the condition's largest
    # compare constant.
    body_trip: dict[str, int] = {}
    for name, lines in comps.items():
        for ln in lines:
            if " while(" not in ln:
                continue
            bm = re.search(r"body=%?([\w\.\-]+)", ln)
            if not bm:
                continue
            tm = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', ln)
            if tm:
                body_trip[bm.group(1)] = int(tm.group(1))
                continue
            cm = re.search(r"condition=%?([\w\.\-]+)", ln)
            consts = [int(c) for c in re.findall(
                r"constant\((\d+)\)",
                "\n".join(comps.get(cm.group(1), [])))] if cm else []
            body_trip[bm.group(1)] = max(consts) if consts else 1

    per_op: dict[str, float] = {}
    memo: dict[str, tuple[float, dict]] = {}

    def total(comp: str, seen=()) -> tuple[float, dict]:
        if comp in memo:
            return memo[comp]
        if comp in seen or comp not in comps:
            return 0.0, {}
        t = 0.0
        ops: dict[str, float] = {}
        for ln in comps[comp]:
            cm = _COLL_RE.search(ln)
            if cm and "-done" not in ln.split("=")[1][:60]:
                b = wire_bytes(ln)
                t += b
                ops[cm.group(2)] = ops.get(cm.group(2), 0.0) + b
            if " while(" in ln:
                bm = re.search(r"body=%?([\w\.\-]+)", ln)
                if bm:
                    sub, sub_ops = total(bm.group(1), seen + (comp,))
                    trip = body_trip.get(bm.group(1), 1)
                    t += trip * sub
                    for k, v in sub_ops.items():
                        ops[k] = ops.get(k, 0.0) + trip * v
        memo[comp] = (t, ops)
        return t, ops

    entry = "__entry__" if "__entry__" in comps else next(iter(comps), None)
    t, ops = total(entry) if entry else (0.0, {})
    per_op.update(ops)
    return {"total_wire_bytes": t, "by_op": per_op,
            "trip_counts": body_trip}


# ----------------------------------------------------------------- lower ---
def _with_shardings(spec_tree, sharding_tree):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        spec_tree, sharding_tree)


def lower_cell(arch: str, shape_name: str, mesh, ft_emu: str = "",
               serve_replicated: bool = False):
    """Lower + compile one cell on `mesh`.  Returns result dict.

    Hillclimb knobs: ft_emu lowers the FlexHyCA-protected train step
    ("two_pass" naive port vs "fused" epilogue); serve_replicated uses the
    TP-only serving weight layout (no per-step FSDP collectives)."""
    import dataclasses
    cfg = get_config(arch)
    run = get_run_config(arch)
    if ft_emu:
        run = dataclasses.replace(run, ft_emu=ft_emu)
    shape = SHAPES[shape_name]
    if not cfg.supports(shape):
        return {"skipped": True,
                "reason": "long_500k requires sub-quadratic attention"}
    model = build(cfg, run)
    opt_cfg = AdamWConfig(dtype=run.adam_dtype)

    t0 = time.time()
    if shape.kind == "train":
        step, _ = make_train_step(model, opt_cfg, mesh=mesh)
        state_spec = jax.eval_shape(
            lambda k: init_state(model, k, opt_cfg), jax.random.PRNGKey(0))
        st = _with_shardings(state_spec, state_shardings(state_spec, mesh))
        batch = _with_shardings(model.batch_specs(shape),
                                S.batch_shardings(model.batch_specs(shape), mesh))
        lowered = jax.jit(step, donate_argnums=(0,)).lower(st, batch)
    elif shape.kind == "prefill":
        pf = make_prefill_step(model, mesh=mesh)
        param_spec = model.param_specs()
        ps = _with_shardings(param_spec, S.param_shardings(param_spec, mesh))
        batch = _with_shardings(model.batch_specs(shape),
                                S.batch_shardings(model.batch_specs(shape), mesh))
        lowered = jax.jit(pf).lower(ps, batch)
    else:  # decode
        dec = make_decode_step(model, mesh=mesh)
        param_spec = model.param_specs()
        ps = _with_shardings(param_spec,
                             S.param_shardings(param_spec, mesh,
                                               no_fsdp=serve_replicated))
        cache_spec = model.cache_specs(shape.global_batch, shape.seq_len)
        cs = _with_shardings(cache_spec,
                             S.cache_shardings(cache_spec, mesh,
                                               unrolled=cfg.unroll))
        tok = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = jax.jit(dec, donate_argnums=(1,)).lower(ps, cs, tok, pos)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)

    # exact per-device bytes of the step's persistent inputs (state/params/
    # caches), from the sharded specs — independent of CPU-backend quirks
    def _sharded_bytes(tree):
        tot = 0
        for leaf in jax.tree.leaves(tree):
            shard = leaf.sharding.shard_shape(leaf.shape)
            n = 1
            for d in shard:
                n *= d
            tot += n * leaf.dtype.itemsize
        return tot
    if shape.kind == "train":
        persistent = _sharded_bytes(st) + _sharded_bytes(batch)
    elif shape.kind == "prefill":
        persistent = _sharded_bytes(ps) + _sharded_bytes(batch)
    else:
        persistent = _sharded_bytes(ps) + _sharded_bytes(cs)

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(zip(mesh.axis_names, (int(s) for s in mesh.devices.shape))),
        "n_devices": int(mesh.size),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "cost": {k: v for k, v in cost.items()
                 if isinstance(v, (int, float)) and (
                     "flops" in k or "bytes" in k or k in ("transcendentals",))},
        "collectives": coll,
        "hlo_bytes": len(hlo),
    }
    # per-device fit check (v5e: 16 GiB).  XLA:CPU's FloatNormalization pass
    # upcasts every bf16 op to f32 (no native bf16 on this host backend), so
    # measured temp is ~2x the TPU value for bf16-activation models — we
    # report the raw CPU number and a bf16-adjusted TPU estimate (verified
    # against the buffer assignment: the dominant temps are f32 copies of
    # by-design-bf16 activations).  See EXPERIMENTS.md §Dry-run.
    arg = result["memory"]["argument_bytes"] or 0
    out = result["memory"]["output_bytes"] or 0
    tmp = result["memory"]["temp_bytes"] or 0
    alias = result["memory"]["alias_bytes"] or 0
    result["memory"]["per_device_total_cpu"] = arg + out + tmp - alias
    result["memory"]["persistent_bytes"] = persistent
    tpu_total = persistent + tmp // 2
    result["memory"]["per_device_total_tpu_est"] = tpu_total
    result["memory"]["fits_16g_cpu_raw"] = bool(arg + out + tmp - alias
                                                < 16 * 1024 ** 3)
    result["memory"]["fits_16g"] = bool(tpu_total < 16 * 1024 ** 3)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--tp", type=int, default=0,
                    help="override: logical mesh (256//tp, tp) on one pod")
    ap.add_argument("--ft", default="", choices=["", "two_pass", "fused"])
    ap.add_argument("--serve-replicated", action="store_true")
    ap.add_argument("--tag", default="",
                    help="results subdir tag for hillclimb variants")
    args = ap.parse_args()

    meshes = []
    if args.tp:
        meshes.append((f"single_tp{args.tp}",
                       make_mesh((256 // args.tp, args.tp),
                                 ("data", "model"))))
    else:
        if args.mesh in ("single", "both"):
            meshes.append(("single", make_production_mesh(multi_pod=False)))
        if args.mesh in ("multi", "both"):
            meshes.append(("multi", make_production_mesh(multi_pod=True)))

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)

    n_ok = n_skip = n_fail = 0
    for mesh_name, mesh in meshes:
        outdir = os.path.join(args.out, mesh_name + args.tag)
        os.makedirs(outdir, exist_ok=True)
        for arch in archs:
            for shape in shapes:
                path = os.path.join(outdir, f"{arch}__{shape}.json")
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {mesh_name} {arch} {shape}")
                    continue
                print(f"[lower ] {mesh_name} {arch} {shape} ...", flush=True)
                try:
                    res = lower_cell(arch, shape, mesh, ft_emu=args.ft,
                                     serve_replicated=args.serve_replicated)
                    if res.get("skipped"):
                        n_skip += 1
                        print(f"[skip  ] {arch} {shape}: {res['reason']}")
                    else:
                        n_ok += 1
                        mm = res["memory"]
                        print(f"[ok    ] {arch} {shape} "
                              f"compile={res['compile_s']}s "
                              f"mem/dev={mm['per_device_total_tpu_est']/2**30:.2f}GiB"
                              f"(cpu raw {mm['per_device_total_cpu']/2**30:.2f}) "
                              f"fits={mm['fits_16g']} "
                              f"coll={res['collectives']['total_wire_bytes']/2**30:.2f}GiB",
                              flush=True)
                except Exception:
                    n_fail += 1
                    res = {"arch": arch, "shape": shape, "failed": True,
                           "error": traceback.format_exc()}
                    print(f"[FAIL  ] {arch} {shape}\n{res['error']}",
                          flush=True)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
    print(f"done: ok={n_ok} skip={n_skip} fail={n_fail}")
    return n_fail


if __name__ == "__main__":
    raise SystemExit(main())
