"""Default target manifest: the repo's real executables, traced.

Each target is the jaxpr (and, where donation matters, the lowered HLO) of
an executable the test batteries actually run: the serving engine's fused
decode loop, the continuous-batching scheduler's prefill and paged decode
chunk, the fused_decode protect triplet, the FAT train step, and the
batched DSE oracle.  Everything is traced abstractly (``jax.make_jaxpr`` /
``jax.eval_shape`` / ``jit(...).lower``) — nothing executes, so the whole
manifest runs in single-device CI; mesh targets trace under whatever mesh
the host devices allow (sharding_constraint eqns survive even a 1x1 mesh).

Trace shapes are deliberately tiny: every rule here is structural (dataflow,
dtypes, eqn params), so reduced configs exercise exactly the same contracts
as the full models.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from tools.ftverify.core import Target

_sds = jax.ShapeDtypeStruct


def _key_aval(batch=None):
    """Raw uint32 key aval(s) matching ``jax.random.PRNGKey``."""
    return _sds(((batch, 2) if batch else (2,)), jnp.uint32)


def _mesh():
    devs = jax.devices()
    tp = 2 if len(devs) % 2 == 0 and len(devs) >= 2 else 1
    dp = len(devs) // tp
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs).reshape(dp, tp), ("data", "model"))


@functools.lru_cache(maxsize=1)
def _danube():
    from repro.configs import get_config
    from repro.models import build
    cfg = get_config("h2o-danube-1.8b", reduced=True)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _policy():
    from repro.ft import get_policy
    # weight_faults=False bounds trace cost on the full-model targets (the
    # weight planes double every site's injection graph); the protect
    # triplet below keeps the default weight_faults=True
    return get_policy("crt3", ber=1e-3, weight_faults=False)


# ------------------------------------------------------- protect triplet --
def _protect_targets() -> list[Target]:
    from repro.ft import get_policy, protect_linear
    from repro.kernels.fused_decode.ops import fused_protect_linear

    pol = get_policy("crt3", ber=1e-3)
    x, w = _sds((4, 8), jnp.float32), _sds((8, 8), jnp.float32)
    tags = frozenset({"protect", "rng"})

    def ref():
        return jax.make_jaxpr(
            lambda k, xx, ww: protect_linear(k, xx, ww, pol))(
                _key_aval(), x, w)

    def fused():
        return jax.make_jaxpr(
            lambda k, xx, ww: fused_protect_linear(k, xx, ww, pol))(
                _key_aval(), x, w)

    def perrow():
        return jax.make_jaxpr(
            lambda k, xx, ww: protect_linear(k, xx, ww, pol))(
                _key_aval(batch=4), x, w)

    return [Target("protect.reference", tags, trace=ref),
            Target("protect.fused", tags, trace=fused),
            Target("protect.perrow", tags, trace=perrow)]


# ---------------------------------------------------------------- engine --
def _engine(mesh=None):
    from repro.serve.engine import Engine, ServeConfig
    _, m, params = _danube()
    return Engine(m, params, mesh=mesh, cfg=ServeConfig(max_new_tokens=4),
                  policy=_policy())


def _engine_avals(eng, n_new: int = 4):
    cfg, _, params = _danube()
    batch = {"tokens": _sds((2, 9), jnp.int32)}
    max_len = 9 + n_new
    caches, logits = jax.eval_shape(
        lambda p, b, k: eng._prefill(p, b, max_len, k),
        params, batch, _key_aval())
    tok = _sds(logits.shape[:-1], jnp.int32)
    pos0 = _sds((), jnp.int32)
    return params, caches, tok, pos0, batch, max_len


def _engine_targets() -> list[Target]:
    out = []
    for label, mesh in (("", None), (".mesh", _mesh())):
        eng = _engine(mesh)
        n_new = 4
        params, caches, tok, pos0, batch, max_len = _engine_avals(eng, n_new)
        tags = frozenset({"serve", "rng", "protect"}
                         | ({"mesh"} if mesh is not None else set()))
        loop_args = (params, caches, tok, pos0, _key_aval(), _key_aval())

        def trace(eng=eng, a=loop_args, n=n_new):
            return jax.make_jaxpr(
                lambda p, c, t, q, fk, sk: eng._loop(p, c, t, q, fk, sk, n)
            )(*a)

        def lower(eng=eng, a=loop_args, n=n_new):
            return eng._loop.lower(*a, n).as_text()

        out.append(Target(
            f"engine.decode_loop{label}", tags, trace=trace, lower=lower,
            donated_leaves=len(jax.tree_util.tree_leaves(caches)),
            mesh=mesh))
        if mesh is not None:
            def trace_pf(eng=eng, p=params, b=batch, ml=max_len):
                return jax.make_jaxpr(
                    lambda pp, bb, k: eng._prefill(pp, bb, ml, k)
                )(p, b, _key_aval())

            out.append(Target("engine.prefill.mesh", tags, trace=trace_pf,
                              mesh=mesh))
    return out


# ------------------------------------------------------------- scheduler --
def _sched_targets() -> list[Target]:
    from repro.serve.scheduler import Scheduler, SchedulerConfig
    _, m, params = _danube()
    sched = Scheduler(m, params, SchedulerConfig(
        max_batch=2, buckets=(8,), max_new_tokens=8, decode_chunk=2,
        kv="paged", block_size=8), policy=_policy())
    tags = frozenset({"serve", "rng", "protect"})

    def trace_prefill():
        return jax.make_jaxpr(sched._prefill_one)(
            params, {"tokens": _sds((1, 8), jnp.int32)},
            _sds((1,), jnp.int32), _sds((), jnp.int32))

    caches = jax.eval_shape(lambda: sched._init_caches(2))
    B = 2
    chunk_args = (params, caches, _sds((B,), jnp.int32),
                  _sds((B,), jnp.int32), _sds((B,), jnp.int32),
                  _sds((B,), jnp.int32), _sds((B,), jnp.bool_))

    def trace_chunk():
        return jax.make_jaxpr(
            lambda p, c, t, q, s, r, a: sched._chunk(p, c, t, q, s, r, a, 2)
        )(*chunk_args)

    def lower_chunk():
        return sched._chunk.lower(*chunk_args, 2).as_text()

    return [
        Target("scheduler.prefill", tags, trace=trace_prefill),
        Target("scheduler.chunk.paged", tags, trace=trace_chunk,
               lower=lower_chunk,
               donated_leaves=len(jax.tree_util.tree_leaves(caches))),
    ]


# ------------------------------------------------------------ train step --
def _train_target() -> list[Target]:
    from repro.optim import AdamWConfig
    from repro.train.train_step import init_state, make_train_step
    _, m, _ = _danube()
    opt = AdamWConfig(lr=1e-3)
    step, jit_step = make_train_step(m, opt, policy=_policy(), fat_ramp=10)
    state = jax.eval_shape(lambda k: init_state(m, k, opt),
                           jax.random.PRNGKey(0))
    batch = {"tokens": _sds((2, 16), jnp.int32)}
    tags = frozenset({"train", "rng", "protect"})

    def trace():
        return jax.make_jaxpr(step)(state, batch)

    def lower():
        return jit_step.lower(state, batch).as_text()

    return [Target("train.fat_step", tags, trace=trace, lower=lower,
                   donated_leaves=len(jax.tree_util.tree_leaves(state)))]


# ------------------------------------------------------------ DSE oracle --
def _dse_target() -> list[Target]:
    from repro.core.evaluate import _acc_under_fault
    from repro.ft import get_policy
    from repro.models.cnn import CNNConfig, init_cnn

    cfg = CNNConfig()
    params = jax.eval_shape(lambda k: init_cnn(k, cfg), _key_aval())
    pol = get_policy("crt3", ber=1e-3)
    _, treedef = jax.tree_util.tree_flatten(pol)
    R = 2
    args = (params, _sds((4, cfg.hw, cfg.hw, cfg.in_channels), jnp.float32),
            _sds((4,), jnp.int32), _sds((R,), jnp.float32), _key_aval(R))

    def trace():
        return jax.make_jaxpr(
            lambda p, i, l, b, k: _acc_under_fault(
                p, cfg, i, l, b, k, {}, treedef=treedef, protected=None)
        )(*args)

    return [Target("dse.batched_oracle",
                   frozenset({"protect", "rng", "dse"}), trace=trace)]


def default_manifest() -> list[Target]:
    return (_protect_targets() + _engine_targets() + _sched_targets()
            + _train_target() + _dse_target())
