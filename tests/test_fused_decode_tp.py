"""The fused protected linear under 4-way tensor parallelism.

Under a mesh whose ``model`` axis holds several devices, ``fused_protect_linear``
runs the kernel and its exchange in a ``shard_map`` (column-parallel: a
``pmax`` of the row maxima; row-parallel: a ``psum`` of the partial
accumulators), and the result must be the one-device result bit for bit.

The CPU cases run in one subprocess with four host devices
(``--xla_force_host_platform_device_count``; the main process keeps its one
device), where the kernel is interpreted:

  (a) ``fused_protect_linear`` on a (1, 4) mesh equals the one-device call
      for a column- and a row-parallel weight, per-row keys, under crt3 and
      under ``cl`` with an importance mask (DPPU ``reuse``), and its jaxpr
      holds the ``shard_map``;
  (b) a tiny Qwen2-shaped model (QKV bias, ``G`` layers, 8 heads and 4 KV
      heads, so that the paged pools head-shard) serves the same tokens
      through ``Scheduler`` with and without the mesh under crt3 ``fused``;
  (c) prefill and then decode on the mesh agree with the benchmark's plain
      reference, ``bench/references/dense_decoder.py``.

The compile cases describe a v5e:2x2 in a fixture (never at import) and
compile, without a chip, the crt3 ``fused`` decode chunk of a 2-layer model
at Qwen2-7B's published widths on a (1, 4) mesh, and a one-chip danube
decode chunk, with the kernel compiled as on the chip.
"""
import json
import math
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]

_CPU = """
    import json, sys
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import ft
    from repro.configs.base import ModelConfig, RunConfig
    from repro.launch.mesh import make_mesh
    from repro.models import build
    from repro.models.common import FTCtx
    from repro.parallel import sharding as S
    from repro.parallel.ctx import mesh_ctx
    from repro.serve.scheduler import Request, Scheduler, SchedulerConfig
    from bench import weights
    from bench.references import dense_decoder

    mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
    ctx = S.make_ctx(mesh)
    part = sys.argv[1]
    out = {}
    MODEL = %(model)s
    conf = {"model": MODEL, "run": {"param_dtype": "bfloat16",
                                    "compute_dtype": "bfloat16"}}
    m = dict(MODEL, block_pattern=tuple(MODEL["block_pattern"]))
    model = build(ModelConfig(**m), RunConfig(**conf["run"]))
    params = weights.make_params(model, %(seed)d)
    crt3 = ft.get_policy("crt3", ber=1e-3, weight_faults=False)
    rng = np.random.default_rng(0)

    # (a) the protected linear alone
    M, K, N = 8, 256, 384
    keys = jax.random.split(jax.random.PRNGKey(1), M)
    for pol_name in ("crt3", "cl") if part == "linear" else ():
        pol = ft.get_policy(pol_name, ber=1e-2, weight_faults=False)
        for split, (k, n) in (("column", (K, N)), ("row", (N, K))):
            x = jax.random.normal(jax.random.PRNGKey(0), (M, k))
            w = jax.random.normal(jax.random.PRNGKey(2), (k, n))
            imp = (None if pol_name == "crt3" else
                   jax.random.uniform(jax.random.PRNGKey(5), (n,)) < 0.3)
            spec = P(None, "model") if split == "column" else P("model", None)
            ws = jax.device_put(w, NamedSharding(mesh, spec))

            def sharded(key, x, w):
                with mesh_ctx(ctx):
                    return ft.protect_linear(key, x, w, pol, imp,
                                             backend="fused", split=split)

            one = jax.jit(lambda key, x, w: ft.protect_linear(
                key, x, w, pol, imp, backend="fused"))(keys, x, w)
            tp = jax.jit(sharded)(keys, x, ws)
            out[f"a/{pol_name}/{split}"] = {
                "equal": bool(np.array_equal(np.asarray(one), np.asarray(tp))),
                "shard_map": "shard_map" in str(jax.make_jaxpr(sharded)(
                    keys, x, ws))}

    # (b) the scheduler, with and without the mesh
    scfg = SchedulerConfig(max_batch=4, buckets=(16,), max_new_tokens=6,
                           decode_chunk=3, block_size=8)
    lens = [(5, 6), (16, 4), (9, 6), (12, 3), (3, 5)]
    prompts = [rng.integers(1, MODEL["vocab"], p).tolist() for p, _ in lens]
    for name, mesh_ in ((("one", None), ("tp4", mesh))
                        if part == "scheduler" else ()):
        sched = Scheduler(model, params, scfg, policy=crt3,
                          ft_backend="fused", mesh=mesh_)
        reqs = [Request(i, t, max_new_tokens=o)
                for i, (t, (_, o)) in enumerate(zip(prompts, lens))]
        sched.run(reqs)
        out[f"b/tokens_{name}"] = [r.generated for r in reqs]
    if part == "scheduler":
        print("RESULT " + json.dumps(out))
        sys.exit(0)

    # (c) prefill, then decode, on the mesh against the plain reference
    prompt = rng.integers(1, MODEL["vocab"], 12).tolist()
    steps = 3
    psh = S.param_shardings(params, mesh, no_fsdp=True)
    sp = jax.device_put(params, psh)

    @jax.jit
    def prefill(p, tok):
        with mesh_ctx(ctx):
            return model.prefill(p, {"tokens": tok}, max_len=len(prompt) + steps,
                                 ftc=FTCtx(crt3, jax.random.PRNGKey(7),
                                           backend="fused"))

    @jax.jit
    def decode(p, caches, tok, pos):
        with mesh_ctx(ctx):
            keys = jax.random.split(jax.random.fold_in(
                jax.random.PRNGKey(8), pos), 1)
            return model.decode_step(p, caches, tok, pos,
                                     ftc=FTCtx(crt3, keys, backend="fused"))

    caches, lg = prefill(sp, jnp.asarray([prompt], jnp.int32))
    got, seq = [np.asarray(lg[0], np.float32)], list(prompt)
    for i in range(steps):
        t = int(np.argmax(got[-1]))
        seq.append(t)
        caches, lg = decode(sp, caches, jnp.asarray([t], jnp.int32),
                            jnp.asarray(len(seq) - 1, jnp.int32))
        got.append(np.asarray(lg[0], np.float32))
    score = [range(len(prompt) - 1, len(seq))]
    want = dense_decoder.logits(conf, %(seed)d, [seq], score)[0]
    low = dense_decoder.logits(conf, %(seed)d, [seq], score, quant=4)[0]

    def err(a, b):   # worst position's max error over the logits' scale
        return float(max(np.abs(x - y).max() / np.abs(y).max()
                         for x, y in zip(a, b)))

    out["c/err"] = err(got, want)
    out["c/int4_err"] = err(low, want)
    out["c/shard_map"] = "shard_map" in str(jax.make_jaxpr(decode)(
        sp, caches, jnp.asarray([t], jnp.int32), jnp.asarray(1, jnp.int32)))
    print("RESULT " + json.dumps(out))
"""

TINY_QWEN2 = {"name": "tiny-qwen2", "family": "dense", "n_layers": 2,
              "d_model": 64, "n_heads": 8, "n_kv_heads": 4, "d_head": 16,
              "d_ff": 128, "vocab": 256, "block_pattern": ["G"],
              "qkv_bias": True, "act": "silu", "glu": True,
              "rope_theta": 1000000.0, "norm_eps": 1e-06,
              "tie_embeddings": False}


@pytest.fixture(scope="module")
def cpu_run():
    """The two CPU subprocesses, (a) with (c) and (b), started and left to
    run side by side and beside the compiles."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # bitwise parity of a partitioned and an unpartitioned graph needs the
    # explicit bf16 roundings kept (docs/serving.md "Sharded serving")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        "--xla_allow_excess_precision=false")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    code = textwrap.dedent(_CPU % {"model": repr(TINY_QWEN2),
                                   "seed": 2**31 + 5})
    procs = [subprocess.Popen([sys.executable, "-c", code, part],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True, env=env)
             for part in ("linear", "scheduler")]
    yield procs
    for p in procs:
        p.kill()
        p.wait()


@pytest.fixture(scope="module")
def tp4(cpu_run):
    got = {}
    for p in cpu_run:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-4000:]
        line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
        got.update(json.loads(line[-1][len("RESULT "):]))
    return got


# ---- compiled for a described v5e:2x2 -------------------------------------
@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # any failure means no topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernel compiled as on the chip (here the CPU backend would
    interpret it), with no trace of the interpreted kernel cached before or
    after."""
    from repro.kernels.fused_decode import kernel
    jax.clear_caches()
    monkeypatch.setattr(kernel, "resolve_interpret",
                        lambda i: False if i is None else i)
    yield
    monkeypatch.undo()
    jax.clear_caches()


def _chunk_hlo(config, n_layers, devices, monkeypatch):
    """Compiled HLO of the decode chunk of a benchmark configuration cut to
    ``n_layers``, its abstract weights and caches laid out as the scheduler
    serves them on ``devices`` (a (1, n) mesh when there are several)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import ft
    from repro.configs.base import ModelConfig, RunConfig
    from repro.launch.mesh import make_mesh
    from repro.models import build
    from repro.parallel import sharding as S
    from repro.serve.scheduler import Scheduler, SchedulerConfig

    conf = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                      .read_text())
    m = dict(conf["model"], n_layers=n_layers,
             block_pattern=tuple(conf["model"]["block_pattern"]))
    model = build(ModelConfig(**m), RunConfig(**conf["run"]))
    prot = conf["protection"]
    policy = ft.get_policy(prot["policy"], ber=prot["ber"],
                           weight_faults=prot["weight_faults"])
    scfg = dict(conf["scheduler"], buckets=tuple(conf["scheduler"]["buckets"]))
    spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mesh = None
    if len(devices) > 1:
        mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
        shard = lambda tree, sh: jax.tree.map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
            tree, sh)
        one = NamedSharding(mesh, P())
        # abstract weights, already in the layout: nothing to place
        monkeypatch.setattr(jax, "device_put", lambda x, s=None: x)
        params = shard(spec, S.param_shardings(spec, mesh, no_fsdp=True))
    else:
        one = jax.sharding.SingleDeviceSharding(devices[0])
        shard = lambda tree, sh: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
        params = shard(spec, None)
    sched = Scheduler(model, params, SchedulerConfig(**scfg), policy=policy,
                      ft_backend=prot["backend"], mesh=mesh)
    caches = jax.eval_shape(lambda: sched._init_caches(scfg["max_batch"]))
    caches = shard(caches, None if mesh is None else S.cache_shardings(
        caches, mesh, kv_heads=model.cfg.n_kv_heads))
    row = jax.ShapeDtypeStruct((scfg["max_batch"],), jnp.int32, sharding=one)
    act = jax.ShapeDtypeStruct((scfg["max_batch"],), jnp.bool_, sharding=one)
    return sched._chunk.lower(params, caches, row, row, row, row, act,
                              scfg["decode_chunk"]).compile().as_text()


def _custom_calls(hlo):
    """(name, result shape) of each ``tpu_custom_call``."""
    return [(ln.split(" = ", 1)[0].strip().lstrip("%"),
             ln.split(" = ", 1)[1].split(" custom-call(")[0])
            for ln in hlo.splitlines() if '"tpu_custom_call"' in ln]


_COLLECTIVE = re.compile(r" = (.*?) (all-reduce|all-gather|reduce-scatter|"
                         r"all-to-all|collective-permute)(-start)?\(")


def _collective_sizes(hlo):
    """Elements of each array a collective of the HLO returns."""
    sizes = []
    for ln in hlo.splitlines():
        m = _COLLECTIVE.search(ln)
        if m:
            for dims in re.findall(r"\[([\d,]*)\]", m.group(1)):
                sizes.append(math.prod(int(d) for d in dims.split(",") if d))
    return sizes


@pytest.mark.usefixtures("cpu_run", "compiled_kernels")
def test_qwen2_decode_chunk_compiles_sharded(topo, monkeypatch):
    """Every decode projection of a Qwen2-7B layer at published widths runs
    the kernel on each chip's shard, and only row maxima and accumulators
    cross the chips: no collective moves a weight shard."""
    hlo = _chunk_hlo("qwen2-7b-crt3", 2, topo.devices[:4], monkeypatch)
    calls = _custom_calls(hlo)
    # the benchmark reads the kernel's time by this prefix
    # (bench/metrics/kernel.fused_ms_per_step.py)
    assert all(name.startswith("fused_decode") for name, _ in calls)
    # one scanned layer body: wq (896 of 3584 columns), wk and wv (128 of
    # 512), wi and wg (4736 of 18944) as column-parallel accumulators with
    # their row maxima; attention and MLP wo as row-parallel partial sums
    col = "(s32[8,{}]{{1,0:T(8,128)S(1)}}, s32[8,1]{{1,0:T(8,128)S(1)}})"
    want = sorted([col.format(896), col.format(128), col.format(128),
                   col.format(4736), col.format(4736)]
                  + ["s32[8,3584]{1,0:T(8,128)S(1)}"] * 2)
    assert sorted(shape for _, shape in calls) == want
    # the largest array a collective may move: 8 rows of the widest output
    # (the smallest weight shard, wk's, holds 3584 x 128 = 458752)
    sizes = _collective_sizes(hlo)
    assert sizes and max(sizes) <= 8 * 18944


@pytest.mark.usefixtures("cpu_run", "compiled_kernels")
def test_danube_decode_chunk_compiles_on_one_chip(topo, monkeypatch):
    """On one chip the kernel keeps its single pass (accumulate and
    epilogue in one call) and no ``shard_map`` or collective enters."""
    hlo = _chunk_hlo("danube-1.8b-crt3", 2, topo.devices[:1], monkeypatch)
    calls = _custom_calls(hlo)
    assert len(calls) == 7
    assert all(name.startswith("fused_decode") and shape.startswith("(s8[8,")
               for name, shape in calls)
    assert not _collective_sizes(hlo)


# ---- on four CPU devices --------------------------------------------------
@pytest.mark.parametrize("case", ["crt3/column", "crt3/row", "cl/column",
                                  "cl/row"])
def test_sharded_linear_is_bit_identical(tp4, case):
    got = tp4[f"a/{case}"]
    assert got["shard_map"], "the sharded path was not taken"
    assert got["equal"]


def test_scheduler_serves_the_same_tokens_on_four_devices(tp4):
    assert tp4["b/tokens_tp4"] == tp4["b/tokens_one"]
    assert [len(t) for t in tp4["b/tokens_one"]] == [6, 4, 6, 3, 5]


def test_prefill_then_decode_agree_with_reference(tp4):
    """The error is the worst position's largest logit error over the
    reference's largest logit.  Protection is on (crt3 at BER 1e-3) and
    every projection is int8 on both operands over 2 layers of bf16
    weights and activations, so the logits move by several hundredths:
    0.097 at this seed.  0.25 leaves room for that and still fails the
    reference itself computed with int4 operands (0.62 here): a lower
    precision than the datapath's would not pass."""
    assert tp4["c/shard_map"]
    assert tp4["c/err"] < 0.25
    assert tp4["c/int4_err"] > 0.25
