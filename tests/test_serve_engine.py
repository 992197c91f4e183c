"""Scan-fused serving engine: parity, key hygiene, dispatch accounting.

The fused ``lax.scan`` decode loop must be a pure optimization: at
temperature 0 it emits bit-identical tokens to the legacy per-token python
loop under *every* registry protection policy and both ft backends — the
whole point of serving the paper's protected datapath fast is that the
protection semantics don't move.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ft
from repro.configs import get_config
from repro.models import build
from repro.serve.engine import Engine, ServeConfig


@pytest.fixture(scope="module")
def danube():
    cfg = get_config("h2o-danube-1.8b", reduced=True)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 9),
                                          0, cfg.vocab)}
    return m, params, batch


def _policy(name, **kw):
    # weight_faults=False keeps the parity sweep's compile cost sane (the
    # weight-SRAM fault planes double every site's injection graph and are
    # schedule-independent); test_per_call_keys_fresh_faults covers the
    # weight-fault stream with the default weight_faults=True
    return ft.get_policy(name, ber=1e-3, weight_faults=False, **kw)


def _pair(m, params, n_new=6, policy=None, **kw):
    scan = Engine(m, params, cfg=ServeConfig(max_new_tokens=n_new),
                  policy=policy, **kw)
    py = Engine(m, params, cfg=ServeConfig(max_new_tokens=n_new),
                policy=policy, loop="python", **kw)
    return scan, py


@pytest.mark.parametrize("name", [None, *ft.list_policies()])
def test_scan_matches_python_under_every_policy(danube, name):
    m, params, batch = danube
    policy = None if name is None else _policy(name)
    scan, py = _pair(m, params, n_new=4, policy=policy)
    a = np.asarray(scan.generate(batch, seed=3))
    b = np.asarray(py.generate(batch, seed=3))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 4)


def test_scan_matches_python_pallas_backend(danube):
    m, params, batch = danube
    policy = _policy("crt3")
    scan, py = _pair(m, params, n_new=4, policy=policy, ft_backend="pallas",
                     ft_t=6)
    a = np.asarray(scan.generate(batch, seed=3))
    b = np.asarray(py.generate(batch, seed=3))
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("arch", ["qwen3-moe-235b-a22b", "paligemma-3b",
                                  "mamba2-2.7b"])
def test_scan_matches_python_across_families(arch):
    cfg = get_config(arch, reduced=True)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 7),
                                          0, cfg.vocab)}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (2, cfg.n_frontend_tokens, cfg.d_model),
            jnp.bfloat16)
    policy = _policy("crt2")
    scan, py = _pair(m, params, n_new=4, policy=policy)
    np.testing.assert_array_equal(np.asarray(scan.generate(batch, seed=1)),
                                  np.asarray(py.generate(batch, seed=1)))


def test_roundtrip_accounting(danube):
    m, params, batch = danube
    scan, py = _pair(m, params, n_new=8)
    scan.generate(batch)
    py.generate(batch)
    assert scan.stats.roundtrips == 2          # prefill + fused loop
    assert py.stats.roundtrips == 1 + 8        # prefill + one per token
    assert py.stats.roundtrips / scan.stats.roundtrips >= 4.5


def test_per_call_keys_fresh_faults(danube):
    """Back-to-back generate() calls must not replay the same fault draws
    (the seed engine reused cfg.seed-derived keys on every call)."""
    m, params, batch = danube
    eng = Engine(m, params, cfg=ServeConfig(max_new_tokens=8),
                 policy=ft.get_policy("base", ber=3e-3))
    a = np.asarray(eng.generate(batch))
    b = np.asarray(eng.generate(batch))
    assert not (a == b).all()                  # fresh fault pattern
    # pinned streams replay exactly, for reliability accounting
    c = np.asarray(eng.generate(batch, seed=11))
    d = np.asarray(eng.generate(batch, seed=11))
    np.testing.assert_array_equal(c, d)
    k = jax.random.PRNGKey(4)
    np.testing.assert_array_equal(np.asarray(eng.generate(batch, key=k)),
                                  np.asarray(eng.generate(batch, key=k)))
    with pytest.raises(ValueError):
        eng.generate(batch, key=k, seed=1)


def test_temperature_sampling_parity_and_freshness(danube):
    """At temperature > 0 the scan path threads the sampling key through the
    carry with the same fold schedule as the python loop."""
    m, params, batch = danube
    scan_t = Engine(m, params, cfg=ServeConfig(max_new_tokens=8,
                                               temperature=1.0))
    py_t = Engine(m, params, cfg=ServeConfig(max_new_tokens=8,
                                             temperature=1.0),
                  loop="python")
    a = np.asarray(scan_t.generate(batch, seed=5))
    b = np.asarray(py_t.generate(batch, seed=5))
    np.testing.assert_array_equal(a, b)
    assert not (a == np.asarray(scan_t.generate(batch, seed=6))).all()


def test_engine_rejects_unknown_loop(danube):
    m, params, _ = danube
    with pytest.raises(ValueError):
        Engine(m, params, loop="unrolled")


def test_zero_new_tokens_is_prefill_only(danube):
    m, params, batch = danube
    eng = Engine(m, params, cfg=ServeConfig(max_new_tokens=8))
    out = eng.generate(batch, max_new_tokens=0)
    assert out.shape == (2, 0)
    assert eng.stats.roundtrips == 1
