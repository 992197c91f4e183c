"""Elastic rescale: the plan is exact and global-batch-preserving on a
deterministic grid, and the full Trainer closed loop (lose a device ->
plan -> re-mesh -> restore -> continue) reproduces the uninterrupted run
up to gradient-accumulation reordering.

Property-based coverage of the same invariants: tests/test_elastic_props.py.
"""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 2, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_plan_rescale_grid():
    """Every (old_dp, survivors) cell: largest divisor that fits, batch
    preserved exactly."""
    from unittest import mock

    from repro.train.elastic import plan_rescale

    def mesh_like(dp):
        m = mock.Mock()
        m.shape = {"data": dp, "model": 1}
        return m

    # (old_dp, surviving_devices, model_axis) -> (new_dp, scale)
    expect = {
        (4, 4, 1): (4, 1),   # nothing lost: identity plan
        (4, 3, 1): (2, 2),   # 3 survive but 3 does not divide 4 -> dp=2
        (4, 2, 1): (2, 2),
        (4, 1, 1): (1, 4),
        (6, 5, 1): (3, 2),   # 5 doesn't divide 6
        (6, 4, 1): (3, 2),
        (6, 3, 1): (3, 2),
        (6, 2, 1): (2, 3),
        (8, 6, 2): (2, 4),   # model_axis=2: 6 devices fit dp<=3 -> divisor 2
        (8, 16, 2): (8, 1),  # extra capacity is never grown into
    }
    for (old_dp, surv, ax), (dp, scale) in expect.items():
        plan = plan_rescale(mesh_like(old_dp), surv, ax)
        assert (plan.new_dp, plan.grad_accum_scale) == (dp, scale), \
            (old_dp, surv, ax, plan)
        assert plan.new_dp * plan.grad_accum_scale == plan.old_dp
        assert plan.changed == (dp != old_dp)


def test_elastic_closed_loop_matches_uninterrupted(tmp_path):
    """Trainer.handle_device_loss end-to-end on a 2-device host: train to a
    checkpoint on a (2,1) mesh, lose one device, continue on (1,1) with
    grad_accum doubled -- the run must track the uninterrupted 2-device run
    (same global batch; only accumulation order differs)."""
    out = run_py(f"""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.launch.mesh import make_mesh
        from repro.models import build
        from repro.optim import AdamWConfig
        from repro.train import Trainer, TrainerConfig
        from repro.train.elastic import simulate_device_loss

        cfg = dataclasses.replace(get_config('h2o-danube-1.8b', reduced=True),
                                  unroll=False)
        def model():
            return build(cfg, RunConfig(param_dtype='float32',
                                        compute_dtype='float32'))
        shape = ShapeConfig('tiny', 'train', 64, 8)
        opt = AdamWConfig(lr=1e-3)
        mesh2 = make_mesh((2, 1), ('data', 'model'))

        # uninterrupted reference: 8 steps on the 2-device mesh
        tc_ref = TrainerConfig(total_steps=8, ckpt_every=100, log_every=1000,
                               ckpt_dir='{tmp_path}/ref', ckpt_async=False)
        ref = Trainer(model(), shape, opt, tc_ref, mesh=mesh2)
        s_ref, _ = ref.run()

        # elastic run: ckpt at 4, lose 1 device, continue 4 more on (1,1)
        tc = TrainerConfig(total_steps=4, ckpt_every=4, log_every=1000,
                           ckpt_dir='{tmp_path}/el', ckpt_async=False)
        tr = Trainer(model(), shape, opt, tc, mesh=mesh2)
        tr.run()
        survivors = simulate_device_loss(tr.mesh, 1)
        assert len(survivors) == 1
        state, step = tr.handle_device_loss(survivors)
        assert step == 4
        assert tr.mesh.shape['data'] == 1
        assert tr.model.run.grad_accum == 2   # global batch preserved
        tr.cfg.total_steps = 8
        s_el, end = tr.run(state, step)
        assert end == 8

        # pull both states off their (different) meshes before comparing
        d = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                for a, b in zip(
            jax.tree.leaves(s_ref['params']), jax.tree.leaves(s_el['params'])))
        l_ref = ref.metrics_log[-1]['loss']
        l_el = tr.metrics_log[-1]['loss']
        print('PARAMDIFF', d, 'LOSSDIFF', abs(l_ref - l_el))
        assert d < 5e-2, d
        assert abs(l_ref - l_el) < 5e-2, (l_ref, l_el)
        print('OK')
    """)
    assert "OK" in out
