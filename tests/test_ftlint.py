"""ftlint rule-engine tests: one positive (fires), one negative (stays
quiet), and suppression coverage per rule, plus the acceptance gate —
the repo itself lints clean with an empty baseline.

Fixtures live in string literals so this file itself stays clean under
``python -m tools.ftlint tests``.
"""
import textwrap
from pathlib import Path

from tools.ftlint import ALL_RULES, lint_paths, lint_source
from tools.ftlint.core import load_baseline, split_baselined

REPO = Path(__file__).resolve().parent.parent


def codes(src, path="pkg/mod.py"):
    return [f.code for f in lint_source(textwrap.dedent(src), path)]


# ------------------------------------------------------------------ FTL001 --
def test_ftl001_positive_key_reused():
    src = """
    import jax

    def draw(key):
        a = jax.random.normal(key, (4,))
        b = jax.random.normal(key, (4,))
        return a + b
    """
    assert codes(src) == ["FTL001"]


def test_ftl001_negative_split_keys():
    src = """
    import jax

    def draw(key):
        k1, k2 = jax.random.split(key)
        a = jax.random.normal(k1, (4,))
        b = jax.random.normal(k2, (4,))
        return a + b
    """
    assert codes(src) == []


def test_ftl001_positive_loop_replay():
    src = """
    import jax

    def draws(key, n):
        out = []
        for i in range(n):
            out.append(jax.random.normal(key, (2,)))
        return out
    """
    assert codes(src) == ["FTL001"]


def test_ftl001_negative_loop_fold_in():
    src = """
    import jax

    def draws(key, n):
        out = []
        for i in range(n):
            k = jax.random.fold_in(key, i)
            out.append(jax.random.normal(k, (2,)))
        return out
    """
    assert codes(src) == []


def test_ftl001_suppressed_with_justification():
    src = """
    import jax

    def paired(key, x):
        a = jax.random.bernoulli(key, 0.5, x.shape)
        # ftlint: disable=FTL001 -- paired draw: same stream by design
        b = jax.random.bernoulli(key, 0.5, x.shape)
        return a, b
    """
    assert codes(src) == []


def test_ftl001_suppression_without_justification_is_ftl000():
    src = """
    import jax

    def paired(key, x):
        a = jax.random.bernoulli(key, 0.5, x.shape)
        b = jax.random.bernoulli(key, 0.5, x.shape)  # ftlint: disable=FTL001
        return a, b
    """
    assert codes(src) == ["FTL000"]


# ------------------------------------------------------------------ FTL002 --
def test_ftl002_positive_host_random_under_jit():
    src = """
    import random

    import jax

    @jax.jit
    def f(x):
        return x * random.random()
    """
    assert codes(src) == ["FTL002"]


def test_ftl002_positive_item_in_scan_body():
    src = """
    import jax

    def step(c, x):
        return c + x.item(), None

    def run(xs):
        return jax.lax.scan(step, 0.0, xs)
    """
    assert codes(src) == ["FTL002"]


def test_ftl002_negative_host_random_outside_trace():
    src = """
    import random

    def pick(xs):
        return random.choice(xs)
    """
    assert codes(src) == []


def test_ftl002_positive_set_iteration_in_traced_code():
    src = """
    import jax

    @jax.jit
    def f(x):
        for name in {"a", "b"}:
            x = x + len(name)
        return x
    """
    assert codes(src) == ["FTL002"]


# ------------------------------------------------------------------ FTL003 --
def test_ftl003_positive_structural_data_leaf():
    src = """
    import jax

    jax.tree_util.register_dataclass(MyPolicy,
                                     data_fields=["ber", "s_th"],
                                     meta_fields=["name"])
    """
    assert codes(src) == ["FTL003"]


def test_ftl003_negative_ber_only_leaf():
    src = """
    import jax

    jax.tree_util.register_dataclass(MyPolicy, data_fields=["ber"],
                                     meta_fields=["s_th", "name"])
    """
    assert codes(src) == []


def test_ftl003_positive_frozen_mutation_outside_ft():
    src = """
    def hack(policy):
        object.__setattr__(policy, "ber", 0.1)
    """
    assert codes(src, "src/repro/serve/engine.py") == ["FTL003"]


def test_ftl003_negative_frozen_mutation_inside_ft():
    src = """
    def __post_init__(self):
        object.__setattr__(self, "ber", float(self.ber))
    """
    assert codes(src, "src/repro/ft/policy.py") == []


def test_ftl003_positive_policy_built_in_traced_code():
    src = """
    import jax

    from repro.ft import get_policy

    @jax.jit
    def f(x):
        pol = get_policy("cl")
        return x * pol.ber
    """
    assert codes(src) == ["FTL003"]


# ------------------------------------------------------------------ FTL004 --
def test_ftl004_positive_float_cast_and_unpinned_matmul():
    src = """
    import jax.numpy as jnp

    def accumulate(xq, wq):
        y = jnp.matmul(xq, wq)
        return y.astype(jnp.float32)
    """
    got = codes(src, "src/repro/kernels/qmatmul/ref.py")
    assert got == ["FTL004", "FTL004"]


def test_ftl004_negative_pinned_matmul_and_scale_boundary():
    src = """
    import jax.numpy as jnp

    def accumulate(xq, wq, scale):
        y = jnp.matmul(xq, wq, preferred_element_type=jnp.int32)
        return y.astype(jnp.float32) * scale
    """
    assert codes(src, "src/repro/kernels/qmatmul/ref.py") == []


def test_ftl004_negative_outside_datapath_files():
    src = """
    import jax.numpy as jnp

    def accumulate(xq, wq):
        y = jnp.matmul(xq, wq)
        return y.astype(jnp.float32)
    """
    assert codes(src, "src/repro/models/attention.py") == []


# ------------------------------------------------------------------ FTL005 --
def test_ftl005_positive_bare_pallas_call():
    src = """
    from jax.experimental import pallas as pl

    def run(kernel, x):
        return pl.pallas_call(kernel, out_shape=x)(x)
    """
    got = codes(src, "src/repro/kernels/newkern/kernel.py")
    # missing interpret=, missing compiler_params, no divisibility guard
    assert got == ["FTL005", "FTL005", "FTL005"]


def test_ftl005_negative_full_kernel_contract():
    src = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def run(kernel, x, bm, interpret=False):
        assert x.shape[0] % bm == 0
        return pl.pallas_call(
            kernel,
            out_shape=x,
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
        )(x)
    """
    assert codes(src, "src/repro/kernels/newkern/kernel.py") == []


def test_ftl005_positive_hardcoded_interpret():
    src = """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def run(kernel, x, bm):
        assert x.shape[0] % bm == 0
        return pl.pallas_call(
            kernel, out_shape=x, interpret=True,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
        )(x)
    """
    assert codes(src, "src/repro/kernels/newkern/kernel.py") == ["FTL005"]


# ------------------------------------------------------------------ FTL006 --
def test_ftl006_positive_policy_marked_static():
    src = """
    from functools import partial

    import jax

    @partial(jax.jit, static_argnames=("policy",))
    def f(x, policy):
        return x
    """
    assert codes(src) == ["FTL006"]


def test_ftl006_positive_unhashable_static_default():
    src = """
    from functools import partial

    import jax

    @partial(jax.jit, static_argnums=(1,))
    def f(x, dims=[1, 2]):
        return x
    """
    assert codes(src) == ["FTL006"]


def test_ftl006_positive_jit_in_loop_and_bound_method():
    src = """
    import jax

    def run(model, xs):
        out = []
        for x in xs:
            out.append(jax.jit(model.forward)(x))
        return out
    """
    got = codes(src)
    assert got == ["FTL006", "FTL006"]  # bound method + jit-per-iteration


def test_ftl006_negative_hashable_static_args():
    src = """
    from functools import partial

    import jax

    @partial(jax.jit, static_argnames=("n", "treedef"))
    def f(x, n, treedef):
        return x * n
    """
    assert codes(src) == []


# ------------------------------------------------------------------ FTL007 --
def test_ftl007_positive_config_update_in_library_code():
    src = """
    import jax

    jax.config.update("jax_enable_x64", True)
    """
    assert codes(src, "src/repro/serve/engine.py") == ["FTL007"]


def test_ftl007_positive_through_import_alias():
    src = """
    from jax import config

    config.update("jax_default_matmul_precision", "float32")
    """
    assert codes(src, "src/repro/models/common.py") == ["FTL007"]


def test_ftl007_negative_sanctioned_site_and_tests():
    src = """
    import jax

    jax.config.update("jax_threefry_partitionable", True)
    """
    assert codes(src, "src/repro/core/faults.py") == []
    assert codes(src, "tests/test_faults.py") == []
    assert codes(src, "tests/conftest.py") == []


# --------------------------------------------------------------- machinery --
def test_syntax_error_is_ftl000_not_crash():
    assert codes("def broken(:\n    pass") == ["FTL000"]


def test_multi_code_suppression_covers_each_listed_code():
    src = """
    import jax

    def draw(key):
        a = jax.random.normal(key, (4,))
        b = jax.random.normal(key, (4,))  # ftlint: disable=FTL001,FTL004 -- paired by design
        return a + b
    """
    assert codes(src) == []


def test_empty_justification_marker_does_not_suppress():
    """A trailing ``--`` with no reason is not a valid waiver: the marker
    fails to parse and the original finding stays visible (fail-closed)."""
    src = """
    import jax

    def paired(key, x):
        a = jax.random.bernoulli(key, 0.5, x.shape)
        b = jax.random.bernoulli(key, 0.5, x.shape)  # ftlint: disable=FTL001 --
        return a, b
    """
    assert codes(src) == ["FTL001"]


def test_missing_file_warns_not_crashes(tmp_path, capsys):
    from tools.ftlint.core import iter_py_files, lint_paths
    assert list(iter_py_files(["no_such_file.py"], tmp_path)) == []
    assert lint_paths(["no_such_file.py"], root=tmp_path) == []
    assert "no such file" in capsys.readouterr().err


def test_deleted_file_mid_run_warns_not_crashes(tmp_path, capsys):
    from tools.ftlint.core import lint_file
    ghost = tmp_path / "ghost.py"
    assert lint_file(ghost, tmp_path) == []
    assert "cannot read" in capsys.readouterr().err


def test_baseline_entry_for_deleted_file_is_stale_not_fatal(tmp_path, capsys):
    """A baseline line pointing at a file that no longer exists must not
    fail the run — it surfaces as a stale-entry note."""
    from tools.ftlint.core import main
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    bl = tmp_path / "baseline.txt"
    bl.write_text("FTL001 src/gone/forever.py::draw::key reused\n")
    assert main([str(clean), "--baseline", str(bl)]) == 0
    assert "stale baseline" in capsys.readouterr().err


def test_report_key_matches_baseline_roundtrip(tmp_path):
    """The JSON report's ``key`` field is the exact baseline key: pasting a
    reported key into baseline.txt must suppress that finding on the next
    run (the report used to omit the key, and consumers reconstructing it
    drifted from the baseline format)."""
    import json

    from tools.ftlint.core import main
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        def draw(key):
            a = jax.random.normal(key, (4,))
            b = jax.random.normal(key, (4,))
            return a + b
    """))
    empty = tmp_path / "empty_baseline.txt"
    empty.write_text("")
    report = tmp_path / "report.json"
    assert main([str(bad), "--baseline", str(empty),
                 "--write-report", str(report)]) == 1
    rows = json.loads(report.read_text())["new"]
    assert rows and all("key" in r for r in rows)
    bl = tmp_path / "baseline.txt"
    bl.write_text("\n".join(r["key"] for r in rows) + "\n")
    assert main([str(bad), "--baseline", str(bl)]) == 0


def test_baseline_split_roundtrip():
    src = """
    import jax

    def draw(key):
        a = jax.random.normal(key, (4,))
        b = jax.random.normal(key, (4,))
        return a + b
    """
    findings = lint_source(textwrap.dedent(src), "pkg/mod.py")
    new, old = split_baselined(findings,
                               {f.baseline_key() for f in findings})
    assert new == [] and old == findings


def test_every_rule_has_code_name_invariant():
    seen = set()
    for rule in ALL_RULES:
        assert rule.code.startswith("FTL") and rule.name and rule.invariant
        assert rule.code not in seen
        seen.add(rule.code)
    assert len(ALL_RULES) >= 6


# ---------------------------------------------------------- acceptance gate --
def test_repo_lints_clean_with_empty_baseline():
    """The whole repo passes every rule; the baseline stays empty (any
    future entry needs a justification in the PR that adds it)."""
    findings = lint_paths(["src", "tests", "benchmarks", "examples",
                           "tools"],
                          root=REPO)
    assert [f.render() for f in findings] == []
    assert load_baseline(REPO / "tools" / "ftlint" / "baseline.txt") == set()
