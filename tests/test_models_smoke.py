"""Per-architecture reduced smoke tests: one forward/train step on CPU with
output shape + finiteness assertions, plus prefill->decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import build


def make_batch(cfg, B, S, key):
    ks = jax.random.split(key, 3)
    n_text = S - (cfg.n_frontend_tokens if cfg.frontend == "vision" else 0)
    batch = {"tokens": jax.random.randint(ks[0], (B, n_text), 0, cfg.vocab)}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jax.random.normal(
            ks[1], (B, cfg.n_frontend_tokens, cfg.d_model))
    if cfg.enc_dec:
        batch["frames"] = jax.random.normal(ks[2], (B, S, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_loss_and_grads(arch):
    cfg = get_config(arch, reduced=True)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 32, jax.random.PRNGKey(1))
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(m.loss, has_aux=True))(params, batch)
    assert np.isfinite(float(loss))
    assert abs(float(loss) - float(jnp.log(cfg.vocab))) < 1.5
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_decode_matches_full_forward(arch):
    cfg = get_config(arch, reduced=True)
    if cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 33
    batch_full = make_batch(cfg, B, S, jax.random.PRNGKey(1))
    batch_pre = dict(batch_full)
    batch_pre["tokens"] = batch_full["tokens"][:, :-1]
    _, logits_full = jax.jit(lambda p, b: m.prefill(p, b))(params, batch_full)
    caches, _ = jax.jit(lambda p, b: m.prefill(p, b, max_len=S + 4))(
        params, batch_pre)
    _, logits_dec = jax.jit(lambda p, c, t, i: m.decode_step(p, c, t, i))(
        params, caches, batch_full["tokens"][:, -1],
        jnp.asarray(S - 1, jnp.int32))
    scale = float(jnp.abs(logits_full).max()) + 1e-9
    err = float(jnp.abs(logits_full - logits_dec).max()) / scale
    assert err < 0.02, err


@pytest.mark.parametrize("arch", ARCHS)
def test_scan_path_matches_unrolled(arch):
    """Stacked-scan layers and python-loop layers are the same model."""
    cfg_u = get_config(arch, reduced=True)
    cfg_s = dataclasses.replace(cfg_u, unroll=False)
    mu, ms = build(cfg_u), build(cfg_s)
    pu = mu.init(jax.random.PRNGKey(0))
    ps = ms.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg_u, 2, 16, jax.random.PRNGKey(1))
    lu, _ = jax.jit(lambda p, b: mu.loss(p, b))(pu, batch)
    ls, _ = jax.jit(lambda p, b: ms.loss(p, b))(ps, batch)
    # different init trees (per-layer fold_in vs vmap split) — only check
    # both are healthy; exact equivalence is covered by decode tests
    assert np.isfinite(float(lu)) and np.isfinite(float(ls))


EXPECTED_PARAMS = {  # published sizes (paligemma/seamless = backbone only)
    "gemma2-27b": 27.2e9, "glm4-9b": 9.4e9, "qwen2-7b": 7.6e9,
    "h2o-danube-1.8b": 1.8e9, "dbrx-132b": 132e9,
    "qwen3-moe-235b-a22b": 235e9, "paligemma-3b": 2.5e9,
    "seamless-m4t-medium": 0.7e9, "mamba2-2.7b": 2.7e9,
    "recurrentgemma-9b": 8.6e9,
}


def test_full_configs_construct_specs_only():
    """FULL configs are exercised via ShapeDtypeStructs only (no alloc) and
    land within 35% of the published parameter counts."""
    from repro.configs import SHAPES
    for arch in ARCHS:
        cfg = get_config(arch)
        m = build(cfg)
        spec = m.param_specs()
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(spec))
        exp = EXPECTED_PARAMS[arch]
        assert 0.65 * exp < n_params < 1.35 * exp, (arch, n_params, exp)
        bs = m.batch_specs(SHAPES["train_4k"])
        assert bs["tokens"].shape[0] == 256


def _stack_unrolled(params, cfg):
    """The unrolled model's per-layer params, stacked into the scanned
    model's segment layout (layer b*len(pattern)+j -> seg0/s{j}[b])."""
    out = {k: v for k, v in params.items() if k != "layers"}
    (pattern, n_rep), = cfg.segments
    out["seg0"] = {
        f"s{j}": jax.tree.map(
            lambda *ls: jnp.stack(ls),
            *[params["layers"][f"l{b * len(pattern) + j}"]
              for b in range(n_rep)])
        for j in range(len(pattern))}
    return out


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "gemma2-27b"])
def test_scanned_decode_serves_like_unrolled(arch):
    """Scanned decode carries the stacked KV caches through the layer scan
    and updates them in place; the served tokens match the python-loop
    layers on the same weights."""
    from repro.serve.scheduler import Request, Scheduler, SchedulerConfig
    cfg_u = get_config(arch, reduced=True)
    cfg_s = dataclasses.replace(cfg_u, unroll=False)
    pu = build(cfg_u).init(jax.random.PRNGKey(0))
    scfg = SchedulerConfig(max_batch=2, buckets=(8, 16), max_new_tokens=6,
                           decode_chunk=2)
    rng = np.random.default_rng(0)
    reqs = [(i, rng.integers(0, cfg_u.vocab, n).tolist())
            for i, n in enumerate((5, 12, 8))]
    out = []
    for cfg, params in ((cfg_u, pu), (cfg_s, _stack_unrolled(pu, cfg_s))):
        sched = Scheduler(build(cfg), params, scfg)
        res = sched.run([Request(r, t, max_new_tokens=6) for r, t in reqs])
        out.append({r: res[r].generated for r, _ in reqs})
    assert out[0] == out[1]
