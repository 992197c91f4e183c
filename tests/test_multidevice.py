"""Multi-device behaviour (sharding rules, elastic re-mesh, distributed MoE)
run in subprocesses with forced host-device counts, so the main test process
keeps its single-device view."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import RunConfig
        from repro.launch.mesh import make_mesh
        from repro.models import build
        from repro.optim import AdamWConfig
        from repro.train import init_state, make_train_step
        import dataclasses
        cfg = dataclasses.replace(get_config('h2o-danube-1.8b', reduced=True),
                                  unroll=False)
        m = build(cfg, RunConfig(param_dtype='float32', compute_dtype='float32'))
        opt = AdamWConfig(lr=1e-3)
        batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (8, 64),
                                              0, cfg.vocab)}
        s0 = init_state(m, jax.random.PRNGKey(0), opt)
        _, st_local = make_train_step(m, opt, mesh=None)
        s1, met1 = st_local(jax.tree.map(jnp.copy, s0), batch)
        mesh = make_mesh((4, 2), ('data', 'model'))
        _, st_mesh = make_train_step(m, opt, mesh=mesh)
        s2, met2 = st_mesh(jax.tree.map(jnp.copy, s0), batch)
        d = max(float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree.leaves(s1['params']), jax.tree.leaves(s2['params'])))
        print('LOSSDIFF', abs(float(met1['loss']) - float(met2['loss'])))
        print('PARAMDIFF', d)
        assert abs(float(met1['loss']) - float(met2['loss'])) < 1e-3
        assert d < 1e-3
        print('OK')
    """)
    assert "OK" in out


@pytest.mark.slow
def test_moe_shard_map_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config
        from repro.configs.base import RunConfig
        from repro.launch.mesh import make_mesh
        from repro.models import build
        from repro.parallel import sharding as S
        from repro.parallel.ctx import mesh_ctx
        cfg = dataclasses.replace(get_config('qwen3-moe-235b-a22b', reduced=True),
                                  unroll=False)
        # capacity is per-shard, so drop sets differ between partitionings;
        # with headroom for every assignment the paths must agree exactly
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        m = build(cfg, RunConfig(param_dtype='float32', compute_dtype='float32'))
        params = m.init(jax.random.PRNGKey(0))
        batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (8, 16),
                                              0, cfg.vocab)}
        l0, _ = jax.jit(m.loss)(params, batch)     # single-device path
        mesh = make_mesh((2, 4), ('data', 'model'))
        ctx = S.make_ctx(mesh)
        def loss_mesh(p, b):
            with mesh_ctx(ctx):
                return m.loss(p, b)
        l1, _ = jax.jit(loss_mesh)(params, batch)  # shard_map EP path
        print('L0', float(l0), 'L1', float(l1))
        assert abs(float(l0) - float(l1)) < 2e-3
        print('OK')
    """)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_remesh_restore(tmp_path):
    out = run_py(f"""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.launch.mesh import make_mesh
        from repro.models import build
        from repro.optim import AdamWConfig
        from repro.train import init_state, make_train_step, state_shardings
        from repro.train import checkpoint as C
        from repro.train.elastic import plan_rescale, remesh_restore
        cfg = dataclasses.replace(get_config('h2o-danube-1.8b', reduced=True),
                                  unroll=False)
        m = build(cfg, RunConfig(param_dtype='float32', compute_dtype='float32'))
        opt = AdamWConfig(lr=1e-3)
        batch = {{'tokens': jax.random.randint(jax.random.PRNGKey(1), (8, 64),
                                               0, cfg.vocab)}}
        mesh8 = make_mesh((4, 2), ('data', 'model'))
        _, step8 = make_train_step(m, opt, mesh=mesh8)
        s = init_state(m, jax.random.PRNGKey(0), opt)
        s, _ = step8(s, batch)
        C.save('{tmp_path}/ck', s, 1)
        # "lose" half the data hosts: 8 -> 4 devices
        plan = plan_rescale(mesh8, surviving_devices=4, model_axis=2)
        assert plan.new_dp == 2 and plan.grad_accum_scale == 2
        mesh4 = make_mesh((2, 2), ('data', 'model'))
        like = jax.eval_shape(lambda k: init_state(m, k, opt),
                              jax.random.PRNGKey(0))
        s4, step, _, ctx = remesh_restore('{tmp_path}/ck', like, mesh4)
        assert step == 1
        _, step4 = make_train_step(m, opt, mesh=mesh4)
        s4b, met = step4(s4, batch)
        assert np.isfinite(float(met['loss']))
        print('OK')
    """)
    assert "OK" in out


@pytest.mark.slow
def test_grad_compression_psum():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.compression import compressed_psum_test
        err = compressed_psum_test(jax.random.PRNGKey(0), n_dev=8)
        print('ERR', err)
        assert err < 0.02
        print('OK')
    """)
    assert "OK" in out
