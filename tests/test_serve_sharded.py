"""Sharded fault-tolerant serving: the per-shard determinism battery.

Each test runs in a subprocess with ``--xla_force_host_platform_device_count``
(the main process keeps its single-device view) and proves one clause of the
partition-exactness contract from docs/serving.md §Sharded serving:

  * temp-0 tokens from ``Engine`` and ``Scheduler`` are **bit-identical**
    between the no-mesh path and an 8-way (4 dp x 2 tp) mesh, for a dense
    (SWA) and a MoE config, under crt3 and under per-row weight faults —
    partitionable threefry (switched on by ``repro.core.faults``) makes every
    fault draw partition-invariant, and the integer FT datapath accumulates
    exactly under partitioned psum;
  * the scheduler's alone-vs-crowded per-request invariance survives TP
    sharding;
  * ``fold_axis_index`` gives shard_map regions per-shard streams that a
    host-side loop reproduces via ``fold_stream(key, s)``;
  * on a real mesh, paged pools are never DP-sharded on the pool dim.

The mesh is (4, 2) deliberately: tp=2 divides the reduced configs' kv heads
(2), heads (4) and experts (4), so caches head-shard (no split-K partial
softmax, which is *not* bitwise partition-invariant) and the MoE combine is
a two-term psum.  MoE capacity_factor is raised to 8.0 because capacity is
computed from per-shard token counts — with drop headroom the routed sets
match exactly (same convention as test_multidevice.py).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    # --xla_allow_excess_precision=false: XLA's default elides explicit
    # f32->bf16->f32 rounding when a fusion keeps the wider type, and the
    # elision decision differs between partitioned and unpartitioned graphs
    # — the one non-bitwise-invariant op in the whole serving path (see
    # docs/serving.md "Sharded serving").
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        "--xla_allow_excess_precision=false")
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


_SETUP = """
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro import ft
    from repro.configs import get_config
    from repro.models import build

    def load(name):
        cfg = get_config(name, reduced=True)
        if cfg.moe is not None:
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        m = build(cfg)
        return cfg, m, m.init(jax.random.PRNGKey(0))

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
"""


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "qwen3-moe-235b-a22b"])
def test_engine_sharded_bit_identical(arch):
    out = run_py(_SETUP + f"""
    from repro.serve.engine import Engine, ServeConfig
    cfg, m, params = load({arch!r})
    batch = {{'tokens': jax.random.randint(jax.random.PRNGKey(1), (4, 8),
                                           0, cfg.vocab)}}
    scfg = ServeConfig(max_new_tokens=6)
    for policy in ('crt3',
                   ft.get_policy('crt1', ber=3e-3, weight_faults=True)):
        ref = Engine(m, params, cfg=scfg, policy=policy).generate(
            batch, seed=3)
        shd = Engine(m, params, mesh=mesh, cfg=scfg, policy=policy).generate(
            batch, seed=3)
        assert (np.asarray(ref) == np.asarray(shd)).all(), (
            np.asarray(ref).tolist(), np.asarray(shd).tolist())
    print('OK')
    """)
    assert "OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "qwen3-moe-235b-a22b"])
def test_scheduler_sharded_bit_identical(arch):
    out = run_py(_SETUP + f"""
    from repro.serve.scheduler import Request, Scheduler, SchedulerConfig
    cfg, m, params = load({arch!r})
    def prompt(n, seed):
        return [int(t) for t in jax.random.randint(
            jax.random.PRNGKey(seed), (n,), 0, cfg.vocab)]
    mk = lambda: [Request(rid=i, tokens=prompt(4 + (i % 3), 20 + i),
                          max_new_tokens=5) for i in range(6)]
    scfg = SchedulerConfig(max_batch=4, buckets=(8,), max_new_tokens=6,
                           decode_chunk=3)
    for policy in ('crt3',
                   ft.get_policy('crt1', ber=3e-3, weight_faults=True)):
        ref = Scheduler(m, params, scfg, policy=policy).run(mk())
        shd = Scheduler(m, params, scfg, policy=policy, mesh=mesh).run(mk())
        for i in range(6):
            assert ref[i].generated == shd[i].generated, (
                i, ref[i].generated, shd[i].generated)
    print('OK')
    """)
    assert "OK" in out


@pytest.mark.slow
def test_scheduler_alone_vs_crowded_under_tp():
    """Per-request fault accounting survives sharding: a request's tokens
    under an 8-way mesh are a pure function of (rid, its own prompt)."""
    out = run_py(_SETUP + """
    from repro.serve.scheduler import Request, Scheduler, SchedulerConfig
    cfg, m, params = load('h2o-danube-1.8b')
    def prompt(n, seed):
        return [int(t) for t in jax.random.randint(
            jax.random.PRNGKey(seed), (n,), 0, cfg.vocab)]
    policy = ft.get_policy('crt1', ber=3e-3, weight_faults=True)
    scfg = SchedulerConfig(max_batch=4, buckets=(8,), max_new_tokens=6,
                           decode_chunk=3)
    alone = Scheduler(m, params, scfg, policy=policy, mesh=mesh).run(
        [Request(rid=7, tokens=prompt(5, 7), max_new_tokens=6)])
    crowd = [Request(rid=7, tokens=prompt(5, 7), max_new_tokens=6),
             Request(rid=8, tokens=prompt(3, 8), max_new_tokens=6),
             Request(rid=9, tokens=prompt(7, 9), max_new_tokens=6)]
    crowded = Scheduler(m, params, scfg, policy=policy, mesh=mesh).run(crowd)
    assert alone[7].generated == crowded[7].generated
    print('OK')
    """)
    assert "OK" in out


@pytest.mark.slow
def test_fold_axis_index_shard_map_contract():
    """Shard s's stream inside shard_map == fold_stream(key, s) on the host:
    the per-shard key-stream contract for explicitly-partitioned regions."""
    out = run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core.faults import fold_axis_index, fold_stream

    mesh = Mesh(np.array(jax.devices()), ('i',))
    base = jax.random.PRNGKey(42)

    def f(_):
        k = fold_axis_index(base, 'i')
        return jax.random.uniform(k, (1, 4))

    y = jax.shard_map(f, mesh=mesh, in_specs=(P('i'),), out_specs=P('i'),
                      check_vma=False)(jnp.zeros((8,)))
    ref = np.stack([np.asarray(jax.random.uniform(fold_stream(base, s), (4,)))
                    for s in range(8)])
    assert (np.asarray(y) == ref).all()
    print('OK')
    """)
    assert "OK" in out


@pytest.mark.slow
def test_paged_pool_replicated_on_real_mesh():
    """The satellite-1 regression on real devices: paged pool leaves
    ``(n_blocks, block_size, KH*Dh)`` are fully addressable from every DP
    shard (pool and block dims replicated) and split their heads-major rows
    over 'model', while dense per-slot rows shard over the batch."""
    out = run_py(_SETUP + """
    from repro.parallel import sharding as S
    cfg, m, params = load('h2o-danube-1.8b')
    caches = m.init_cache(4, 16, paged=(8, 17))
    sh = S.cache_shardings(caches, mesh, kv_heads=cfg.n_kv_heads)

    def leaves_with_paths(tree):
        return jax.tree_util.tree_flatten_with_path(tree)[0]

    def axes(entry):
        if entry is None:
            return set()
        return set(entry) if isinstance(entry, tuple) else {entry}

    pool_seen = bt_seen = 0
    for path, s in leaves_with_paths(sh):
        names = [str(getattr(k, 'key', '')) for k in path]
        off = 1 if names[0].startswith('seg') else 0   # scan-stack prefix
        spec = list(s.spec) + [None] * 8
        if names[-1] in ('k', 'v'):
            # pool + block dims replicated: addressable from every shard
            assert spec[off] is None and spec[off + 1] is None, (names,
                                                                 s.spec)
            # heads split on the last dim: each shard holds whole kv heads
            assert len(s.spec) == off + 3 and s.spec[-1] == 'model', (
                names, s.spec)
            pool_seen += 1
        if names[-1] == 'bt':
            assert 'data' in axes(spec[off]), (names, s.spec)
            bt_seen += 1
    assert pool_seen and bt_seen
    dense = S.cache_shardings(m.init_cache(4, 16), mesh)
    for path, s in leaves_with_paths(dense):
        off = 1 if str(getattr(path[0], 'key', '')).startswith('seg') else 0
        assert 'data' in axes((list(s.spec) + [None] * 8)[off]), (path,
                                                                  s.spec)
    print('OK')
    """)
    assert "OK" in out
