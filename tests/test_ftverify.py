"""ftverify rule tests: per-rule seeded-bad fixtures (a jaxpr that violates
the contract must be flagged), clean fixtures (the sanctioned idiom stays
quiet), and the acceptance gates — the repo's own protect targets verify
clean, and test-local reverts of the PR 9 fixes (the threefry flag, the
post-rope constraint) are caught.

Fixtures are traced inline with ``jax.make_jaxpr``; nothing here executes
on device, so the whole battery runs in single-device CI.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from tools.ftverify import ALL_RULES, VerifyEnv, build_graph, verify_targets
from tools.ftverify.core import Target, TargetCtx
from tools.ftverify.rules import FTV102, FTV103, FTV105, FTV106
from tools.ftverify.rules.ftv101_int_datapath import (
    check_backward_slices, check_injected_roundtrips)
from tools.ftverify.rules.ftv102_partition import (
    PARTITIONABLE_MARKER, find_bf16_roundtrips, probe_threefry_lowering)
from tools.ftverify.rules.ftv103_key_streams import (check_reuse,
                                                     check_scan_invariance)
from tools.ftverify.rules.ftv104_one_executable import check_policy_leaves
from tools.ftverify.rules.ftv105_donation import count_aliased_inputs
from tools.ftverify.rules.ftv106_sharding import (check_rope_constraints,
                                                  find_rope_concats)

_sds = jax.ShapeDtypeStruct
ENV = VerifyEnv(excess_precision_pinned=True, threefry_partitionable=True,
                n_devices=1)


def graph_of(fn, *avals):
    return build_graph(jax.make_jaxpr(fn)(*avals))


def fnd(scope, msg):
    return (scope, msg)


def key_aval(batch=None):
    return _sds(((batch, 2) if batch else (2,)), jnp.uint32)


_DN = (((1,), (0,)), ((), ()))


# ------------------------------------------------------------------ FTV101 --
def test_ftv101_flags_float_excursion_into_truncation():
    def bad(x, w):
        acc = jax.lax.dot_general(x, w, _DN,
                                  preferred_element_type=jnp.int32)
        y = (acc.astype(jnp.float32) * 1.25).astype(jnp.int32)
        return jax.lax.shift_right_arithmetic(y, 3)

    g = graph_of(bad, _sds((4, 8), jnp.int32), _sds((8, 8), jnp.int32))
    out = check_backward_slices(g, fnd)
    assert len(out) == 1
    assert "float 'mul'" in out[0][1]


def test_ftv101_flags_narrow_integer_accumulation():
    def bad(x, w):
        acc = jax.lax.dot_general(x, w, _DN,
                                  preferred_element_type=jnp.int16)
        return jax.lax.shift_right_arithmetic(acc, jnp.int16(2))

    g = graph_of(bad, _sds((4, 8), jnp.int16), _sds((8, 8), jnp.int16))
    out = check_backward_slices(g, fnd)
    assert len(out) == 1
    assert "<32 bits" in out[0][1]


def test_ftv101_clean_integer_slice():
    def ok(x, w):
        acc = jax.lax.dot_general(x, w, _DN,
                                  preferred_element_type=jnp.int32)
        return jax.lax.shift_right_arithmetic(acc + 4, 3)

    g = graph_of(ok, _sds((4, 8), jnp.int8), _sds((8, 8), jnp.int8))
    assert check_backward_slices(g, fnd) == []


def test_ftv101_flags_injected_float_roundtrip():
    def bad(y, flips):
        z = (y ^ flips).astype(jnp.float32) * 2.0
        return z.astype(jnp.int32)

    g = graph_of(bad, _sds((8,), jnp.int32), _sds((8,), jnp.int32))
    out = check_injected_roundtrips(g, fnd)
    assert len(out) == 1
    assert "float round-trip" in out[0][1]


def test_ftv101_round_sanctions_the_requantize():
    def ok(y, flips):
        z = (y ^ flips).astype(jnp.float32) * 2.0
        return jnp.round(z).astype(jnp.int32)

    g = graph_of(ok, _sds((8,), jnp.int32), _sds((8,), jnp.int32))
    assert check_injected_roundtrips(g, fnd) == []


# ------------------------------------------------------------------ FTV102 --
def test_ftv102_finds_bf16_roundtrip_pairs():
    def f(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) * 2.0

    g = graph_of(f, _sds((8,), jnp.float32))
    assert len(find_bf16_roundtrips(g)) == 1


def test_ftv102_fires_only_when_excess_precision_unpinned():
    def f(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) * 2.0

    t = Target("fixture.bf16", frozenset(),
               trace=lambda: jax.make_jaxpr(f)(_sds((8,), jnp.float32)))
    assert FTV102.check_target(TargetCtx(t, ENV)) == []
    unpinned = VerifyEnv(excess_precision_pinned=False,
                         threefry_partitionable=True, n_devices=1)
    out = FTV102.check_target(TargetCtx(t, unpinned))
    assert [f.code for f in out] == ["FTV102"]
    assert "excess_precision" in out[0].message


def test_ftv102_catches_threefry_flag_revert():
    """Reverting the PR 9 partitionable-threefry pin must be caught."""
    import repro.core.faults  # noqa: F401 — pins the flag at import
    assert jax.config.jax_threefry_partitionable
    try:
        jax.config.update("jax_threefry_partitionable", False)
        out = FTV102.check_global(VerifyEnv.capture())
        assert [f.code for f in out] == ["FTV102"]
        assert "partition-variant" in out[0].message
        # the lowering really is the legacy (non-partitionable) form
        assert PARTITIONABLE_MARKER not in probe_threefry_lowering()
    finally:
        jax.config.update("jax_threefry_partitionable", True)
    assert FTV102.check_global(VerifyEnv.capture()) == []
    assert PARTITIONABLE_MARKER in probe_threefry_lowering()


# ------------------------------------------------------------------ FTV103 --
def test_ftv103_flags_laundered_key_reuse():
    def bad(k):
        a = jax.random.uniform(k, (4,))
        b = jax.random.uniform(jnp.reshape(k, (2,)), (4,))
        return a + b

    g = graph_of(bad, key_aval())
    out = check_reuse(g, fnd)
    assert len(out) == 1
    assert "same fault stream" in out[0][1]


def test_ftv103_distinct_fold_in_paths_clean():
    def ok(k):
        a = jax.random.uniform(jax.random.fold_in(k, 0), (4,))
        b = jax.random.uniform(jax.random.fold_in(k, 1), (4,))
        return a + b

    g = graph_of(ok, key_aval())
    assert check_reuse(g, fnd) == []


def test_ftv103_flags_scan_closed_over_key():
    def bad(k, xs):
        def body(c, x):
            return c + jax.random.uniform(k, ()), x
        return jax.lax.scan(body, 0.0, xs)

    g = graph_of(bad, key_aval(), _sds((4,), jnp.float32))
    out = check_scan_invariance(g, fnd)
    assert len(out) == 1
    assert "replayed every loop iteration" in out[0][1]


def test_ftv103_scan_key_folded_from_xs_clean():
    def ok(k, xs):
        def body(c, i):
            kk = jax.random.fold_in(k, i)
            return c + jax.random.uniform(kk, ()), i
        return jax.lax.scan(body, 0.0, xs)

    g = graph_of(ok, key_aval(), _sds((4,), jnp.int32))
    assert check_scan_invariance(g, fnd) == []


# ------------------------------------------------------------------ FTV104 --
def test_ftv104_flags_multi_leaf_policy(monkeypatch):
    @jax.tree_util.register_pytree_node_class
    class TwoLeafPolicy:
        def __init__(self, ber, s_th):
            self.ber, self.s_th = ber, s_th

        def tree_flatten(self):
            return (self.ber, self.s_th), None

        @classmethod
        def tree_unflatten(cls, aux, leaves):
            return cls(*leaves)

    import repro.ft as ft
    monkeypatch.setattr(ft, "list_policies", lambda: ["bad2"])
    monkeypatch.setattr(ft, "get_policy",
                        lambda name, **kw: TwoLeafPolicy(1e-3, 0.5))
    out = check_policy_leaves(fnd)
    assert len(out) == 1
    assert "2 leaves" in out[0][1]


# ------------------------------------------------------------------ FTV105 --
def test_ftv105_flags_dropped_donation():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax warns on the unusable donation
        hlo = jax.jit(lambda c, x: (c + x).sum(), donate_argnums=(0,)).lower(
            _sds((8,), jnp.float32), _sds((8,), jnp.float32)).as_text()
    assert count_aliased_inputs(hlo) == 0
    t = Target("fixture.dropped", frozenset(), lower=lambda: hlo,
               donated_leaves=1)
    out = FTV105.check_target(TargetCtx(t, ENV))
    assert [f.code for f in out] == ["FTV105"]
    assert "silently dropped" in out[0].message


def test_ftv105_landed_donation_clean():
    hlo = jax.jit(lambda c, x: c + x, donate_argnums=(0,)).lower(
        _sds((8,), jnp.float32), _sds((8,), jnp.float32)).as_text()
    assert count_aliased_inputs(hlo) >= 1
    t = Target("fixture.landed", frozenset(), lower=lambda: hlo,
               donated_leaves=1)
    assert FTV105.check_target(TargetCtx(t, ENV)) == []


# ------------------------------------------------------------------ FTV106 --
def _rope_like(x):
    c, s = jnp.cos(x), jnp.sin(x)
    lo, hi = x[:, :2], x[:, 2:]
    return jnp.concatenate([lo * c[:, :2] - hi * s[:, 2:],
                            hi * c[:, 2:] + lo * s[:, :2]], axis=-1)


def test_ftv106_finds_rope_concats():
    g = graph_of(_rope_like, _sds((4, 4), jnp.float32))
    assert len(find_rope_concats(g)) == 1


def test_ftv106_flags_unconstrained_rope_into_dot():
    def bad(x, w):
        return _rope_like(x) @ w

    g = graph_of(bad, _sds((4, 4), jnp.float32), _sds((4, 4), jnp.float32))
    out = check_rope_constraints(g, fnd)
    assert len(out) == 1
    assert "sharding_constraint" in out[0][1]


def test_ftv106_constrained_rope_clean():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    sh = NamedSharding(mesh, PartitionSpec(None, None))

    def ok(x, w):
        r = jax.lax.with_sharding_constraint(_rope_like(x), sh)
        return r @ w

    g = graph_of(ok, _sds((4, 4), jnp.float32), _sds((4, 4), jnp.float32))
    assert check_rope_constraints(g, fnd) == []


def test_ftv106_catches_post_rope_constraint_revert(monkeypatch):
    """Test-locally revert PR 9's post-rope re-constraint (neutralize the
    ``ac`` helper inside attention) and verify FTV106 fires on the traced
    mesh prefill; unpatched, the same target is clean."""
    import repro.models.attention as attn
    from tools.ftverify.targets import _engine_targets

    def mesh_prefill():
        for t in _engine_targets():
            if t.name == "engine.prefill.mesh":
                return t
        raise AssertionError("engine.prefill.mesh missing from manifest")

    t = mesh_prefill()
    assert FTV106.check_target(TargetCtx(t, ENV)) == []

    monkeypatch.setattr(attn, "ac", lambda x, *axes: x)
    out = FTV106.check_target(TargetCtx(mesh_prefill(), ENV))
    assert out and all(f.code == "FTV106" for f in out)
    assert any("post-rope" in f.scope for f in out)


# --------------------------------------------------------------- machinery --
def test_findings_use_stable_trace_paths():
    t = Target("some.target", frozenset())
    f = TargetCtx(t, ENV).finding("FTV101", "truncation", "msg")
    assert f.path == "trace://some.target" and f.line == 0
    assert f.baseline_key() == "FTV101 trace://some.target::truncation::msg"


def test_crashing_target_reports_ftv000_not_abort():
    def boom():
        raise RuntimeError("trace exploded")

    t = Target("fixture.boom", frozenset({"rng", "protect"}), trace=boom)
    findings = verify_targets([t], ENV, rules=[FTV103])
    assert [f.code for f in findings] == ["FTV000"]
    assert "trace exploded" in findings[0].message


def test_every_rule_has_code_name_invariant():
    seen = set()
    for rule in ALL_RULES:
        assert rule.code.startswith("FTV") and rule.name and rule.invariant
        assert rule.code not in seen
        seen.add(rule.code)
    assert len(ALL_RULES) == 6


def test_cli_list_rules_and_unknown_rule():
    from tools.ftverify.core import main
    assert main(["--list-rules"]) == 0
    assert main(["--rules", "FTV999", "--no-baseline"]) == 2


# ---------------------------------------------------------- acceptance gate --
def test_protect_targets_verify_clean():
    """The repo's own protect triplet (reference / fused / per-row) passes
    every trace rule, and every global check (threefry lowering, policy
    registry, cache_shardings) is clean — with the baseline empty."""
    from pathlib import Path

    from tools.ftlint.core import load_baseline
    from tools.ftverify.targets import _protect_targets

    findings = verify_targets(_protect_targets(), ENV)
    assert [f.render() for f in findings] == []
    repo = Path(__file__).resolve().parent.parent
    assert load_baseline(repo / "tools" / "ftverify" / "baseline.txt") == set()
