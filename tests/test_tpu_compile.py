"""Compile the protected datapath for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached, so these tests catch what interpret mode
cannot: block shapes the chip refuses and kernels that outgrow VMEM.  They
compile (never run) at h2o-danube-1.8b widths (d_model 2560, d_ff 6912):

  * the fused decode kernel on every decode projection shape, M=8 rows with
    per-row truncation, as the scheduler's decode step sends it;
  * ``fused_protect_linear`` on calls its VMEM plan refuses (prefill rows,
    per-row weight faults): they take the reference datapath and compile
    with no kernel in them;
  * the largest row count the plan admits at each width, for every DPPU
    recompute source (one global ``t``; per-row with per-row weight
    faults) — the plan must never admit a shape the compiler refuses;
  * the scheduler's decode chunk (clean and under crt3) and its insert at
    the benchmark's size: the paged KV pool stays in place, with no slice,
    update-slice or copy of a layer's pool or of the layer stack.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import json
import math
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import ft
from repro.configs.base import ModelConfig, RunConfig
from repro.kernels.fused_decode.kernel import fused_decode
from repro.kernels.fused_decode.ops import fused_protect_linear, kernel_fits
from repro.models import build
from repro.serve.scheduler import Scheduler, SchedulerConfig

ROOT = Path(__file__).resolve().parents[1]
D, F, KV = 2560, 6912, 640     # h2o-danube-1.8b d_model, d_ff, 8 kv heads x 80
DECODE_SHAPES = {"wq_wo": (D, D), "wk_wv": (D, KV), "ffn_up_gate": (D, F),
                 "ffn_down": (F, D)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # any failure means no topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(sharding, m, k, n, per_row, dppu_src="none",
                    perrow_wf=False):
    args = [_sds((m, k), jnp.int8, sharding), _sds((k, n), jnp.int8, sharding),
            _sds((m, n), jnp.int32, sharding),
            _sds((1, 1), jnp.int32, sharding)]
    extra = {}
    if dppu_src == "wcl":
        extra["wq_clean"] = _sds((k, n), jnp.int8, sharding)
    if perrow_wf:
        extra["wflips"] = _sds((m, k, n), jnp.int32, sharding)
    if dppu_src != "none":
        extra["dflips"] = _sds((m, n), jnp.int32, sharding)
        extra["imp"] = _sds((1, n), jnp.int32, sharding)
    fn = jax.jit(lambda x, w, o, q, kw: fused_decode(
        x, w, o, q, per_row=per_row, dppu_src=dppu_src, perrow_wf=perrow_wf,
        interpret=False, **kw))
    return fn.lower(*args, extra).compile().as_text()


@pytest.mark.parametrize("site", sorted(DECODE_SHAPES))
def test_fused_decode_compiles_at_decode_shapes(one_chip, site):
    k, n = DECODE_SHAPES[site]
    assert kernel_fits(8, n)
    hlo = _compile_kernel(one_chip, 8, k, n, per_row=True)
    assert "tpu_custom_call" in hlo


def test_fused_decode_keeps_its_op_name(one_chip):
    # the benchmark reads the kernel's device time by the op name prefix
    # ``fused_decode`` (bench/metrics/kernel.fused_ms_per_step.py)
    hlo = _compile_kernel(one_chip, 8, D, D, per_row=True)
    calls = [ln.split(" = ", 1)[0].strip().lstrip("%")
             for ln in hlo.splitlines() if '"tpu_custom_call"' in ln]
    assert calls and all(c.startswith("fused_decode") for c in calls)


# DPPU recompute sources the ops wrapper picks (dppu_src, per-row weight
# faults); the plan must hold for each where it admits any row count
DPPU_VARIANTS = {"none": ("none", False), "reuse": ("reuse", False),
                 "wcl": ("wcl", False), "w_perrow_wf": ("w", True)}
ADMITTED = [
    pytest.param(site, var, id=site if var == "none" else f"{site}-{var}")
    for site, (_, n) in sorted(DECODE_SHAPES.items())
    for var, (src, wf) in DPPU_VARIANTS.items()
    if kernel_fits(8, n, dppu_src=src, perrow_wf=wf)]


@pytest.mark.parametrize("site,variant", ADMITTED)
def test_largest_admitted_rows_compile(one_chip, site, variant):
    k, n = DECODE_SHAPES[site]
    src, wf = DPPU_VARIANTS[variant]
    m = max(r for r in range(8, 4097, 8)
            if kernel_fits(r, n, dppu_src=src, perrow_wf=wf))
    assert not kernel_fits(m + 8, n, dppu_src=src, perrow_wf=wf)
    hlo = _compile_kernel(one_chip, m, k, n, per_row=wf, dppu_src=src,
                          perrow_wf=wf)
    assert "tpu_custom_call" in hlo


def _compile_protect(sharding, policy, m, k, n, key_rows):
    key_shape = (key_rows, 2) if key_rows else (2,)
    args = [_sds(key_shape, jnp.uint32, sharding),
            _sds((m, k), jnp.float32, sharding),
            _sds((k, n), jnp.bfloat16, sharding)]
    fn = jax.jit(lambda key, x, w: fused_protect_linear(
        key, x, w, policy, interpret=False))
    return fn.lower(*args).compile().as_text()


CASES = {
    # name: (weight_faults, rows, K, N, per-row keys, runs the kernel)
    "decode_perrow": (False, 8, D, F, True, True),
    "prefill_global_t": (False, 512, D, F, False, False),
    "decode_perrow_weight_faults": (True, 8, D, D, True, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_protect_linear_routes_and_compiles(one_chip, case):
    wf, m, k, n, per_row, kernel = CASES[case]
    policy = ft.get_policy("crt3", ber=1e-3, weight_faults=wf)
    assert kernel_fits(m, n, perrow_wf=wf and per_row) == kernel
    hlo = _compile_protect(one_chip, policy, m, k, n, m if per_row else 0)
    assert ("tpu_custom_call" in hlo) == kernel


# a pool-sized move: an op that slices, updates a slice of or copies an
# array of one or more layers' pools, in any shape (a copy-start's tuple too)
_MOVE = re.compile(r" = (.*?) (dynamic-slice|dynamic-update-slice|copy|"
                   r"copy-start|copy-done)\(")


def _pool_moves(hlo, layer_elems):
    moves = []
    for ln in hlo.splitlines():
        m = _MOVE.search(ln)
        if m and any(n and n % layer_elems == 0 for n in (
                math.prod(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"\[([\d,]*)\]", m.group(1)))):
            moves.append(ln.strip()[:160])
    return moves


def _bench_scheduler(config):
    """The scheduler of a benchmark configuration (``bench/configs/``) with
    abstract weights.  danube's: 24 layers, 8 slots, buckets up to 512, 256
    new tokens, blocks of 8, so the pool is 1 + 8 x (96 + 512) = 4865
    blocks a layer.  Returns it, its cache shapes and one layer's pool size
    in elements."""
    conf = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                      .read_text())
    mconf = dict(conf["model"], block_pattern=tuple(
        conf["model"]["block_pattern"]))
    model = build(ModelConfig(**mconf), RunConfig(**conf["run"]))
    prot, kw = conf["protection"], {}
    if prot:
        kw = {"policy": ft.get_policy(prot["policy"], ber=prot["ber"],
                                      weight_faults=prot["weight_faults"]),
              "ft_backend": prot["backend"]}
    scfg = dict(conf["scheduler"], buckets=tuple(
        conf["scheduler"]["buckets"]))
    sched = Scheduler(model, jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                      SchedulerConfig(**scfg), **kw)
    caches = jax.eval_shape(lambda: sched._init_caches(scfg["max_batch"]))
    pool = caches["seg0"]["s0"]["attn"]["k"]
    assert pool.shape == (24, 4865, 8, KV)
    return sched, caches, math.prod(pool.shape[1:])


def _on(sharding, tree):
    return jax.tree.map(lambda x: _sds(x.shape, x.dtype, sharding), tree)


@pytest.mark.parametrize("config", ["danube-1.8b", "danube-1.8b-crt3"])
def test_decode_chunk_keeps_pool_in_place(one_chip, config):
    """The layer scan writes and gathers the stacked pool in place: a
    per-layer slice of it was a transposing copy in and out of every layer,
    and moving 2 x 50 MB a layer that way cost about a quarter of each
    decode step."""
    sched, caches, layer_elems = _bench_scheduler(config)
    row = _sds((8,), jnp.int32, one_chip)
    compiled = sched._chunk.lower(
        _on(one_chip, sched.params), _on(one_chip, caches), row, row, row,
        row, _sds((8,), jnp.bool_, one_chip), 4).compile()
    assert _pool_moves(compiled.as_text(), layer_elems) == []
    # temporaries under the stacked K pool (1.2 GB), so no copy of the
    # stack hides in them: slicing it out per layer took 4.2 GB here
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 24 * layer_elems * 2


def test_insert_keeps_pool_in_place(one_chip):
    """Admission scatters a 512-token prefill's rows into the stacked pool
    in place, with no relayout copy of the stack in and out."""
    sched, caches, layer_elems = _bench_scheduler("danube-1.8b")
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    c1, _ = jax.eval_shape(sched._prefill_one, sched.params,
                           {"tokens": i32(1, 512)}, i32(1), i32())
    compiled = sched._insert.lower(
        _on(one_chip, caches), _on(one_chip, c1),
        *_on(one_chip, (i32(), i32(), i32(sched._wg), i32(sched._wl)))
    ).compile()
    assert _pool_moves(compiled.as_text(), layer_elems) == []
