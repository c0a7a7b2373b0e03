"""distlint SPMD rules: each rule fires on a deliberately broken step
function and stays quiet on the repaired twin, on a 2-device CPU mesh.

The known-good cases are shaped after the repo's real patterns (the
uniform-predicate cond of parallel/allreduce_ea.py, the fold_in-then-draw
dropout key of train/trainer.py), so a linter change that starts flagging
them is a regression against the codebase itself.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, random
from jax.sharding import Mesh, PartitionSpec as P

from distlearn_tpu.lint import Finding, lint_step
from distlearn_tpu.lint.core import filter_suppressed, format_findings


@pytest.fixture
def mesh(devices):
    return Mesh(np.array(devices[:2]), ("data",))


def _sm(mesh, f, in_specs, out_specs):
    # check_vma=False: several known-bad bodies are exactly the programs the
    # static replication checker refuses; the linter must catch them anyway.
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------- DL001

def test_dl001_unknown_axis_fires(mesh):
    def bad(x):
        return lax.psum(x, "batch")  # deployment mesh only has 'data'
    fs = lint_step(bad, [jnp.ones((4,))], mesh=mesh,
                   axis_env=[("batch", 2)], name="bad")
    assert _rules(fs) == ["DL001"]
    assert "batch" in fs[0].message


def test_dl001_quiet_on_mesh_axis(mesh):
    def good(x):
        return lax.psum(x, "data")
    assert lint_step(good, [jnp.ones((4,))], mesh=mesh,
                     axis_env=[("data", 2)], name="good") == []


# ---------------------------------------------------------------------- DL002

def test_dl002_collective_in_one_cond_branch_fires(mesh):
    def bad(x):
        def body(x):
            # Predicate computed from the LOCAL shard: devices disagree,
            # and only one branch issues a psum.
            return lax.cond(x.sum() > 0,
                            lambda v: lax.psum(v, "data"),
                            lambda v: v, x)
        return _sm(mesh, body, P("data"), P("data"))(x)
    fs = lint_step(bad, [jnp.ones((2, 4))], mesh=mesh, name="bad")
    assert _rules(fs) == ["DL002"]
    assert "cond" in fs[0].where


def test_dl002_quiet_on_uniform_predicate(mesh):
    """allreduce_ea.average_parameters pattern: branches diverge but the
    predicate is psum-derived, hence identical on every device — safe."""
    def good(x):
        def body(x):
            due = lax.psum((x.sum() > 0).astype(jnp.int32), "data") > 0
            return lax.cond(due,
                            lambda v: lax.psum(v, "data") / 2,
                            lambda v: v, x)
        return _sm(mesh, body, P("data"), P())(x)
    assert lint_step(good, [jnp.ones((2, 4))], mesh=mesh, name="good") == []


def test_dl002_quiet_when_branches_agree(mesh):
    def good(x):
        def body(x):
            return lax.cond(x.sum() > 0,
                            lambda v: lax.psum(v, "data"),
                            lambda v: lax.psum(2.0 * v, "data"), x)
        return _sm(mesh, body, P("data"), P())(x)
    assert lint_step(good, [jnp.ones((2, 4))], mesh=mesh, name="good") == []


def test_dl002_data_dependent_while_with_collective_fires(mesh):
    def bad(x):
        def body(x):
            def w_body(c):
                i, v = c
                return i + 1, lax.psum(v, "data")
            def w_cond(c):
                i, v = c
                return (v.sum() > 0) & (i < 3)  # local shard decides
            return lax.while_loop(w_cond, w_body, (0, x))[1]
        return _sm(mesh, body, P("data"), P("data"))(x)
    fs = lint_step(bad, [jnp.ones((2, 4))], mesh=mesh, name="bad")
    assert "DL002" in _rules(fs)
    assert "while" in fs[0].where


# ---------------------------------------------------------------------- DL003

def test_dl003_shared_key_fires(mesh):
    def bad(x, key):
        def body(x, key):
            return x + random.normal(key, x.shape)  # same draw on all nodes
        return _sm(mesh, body, (P("data"), P()), P("data"))(x, key)
    fs = lint_step(bad, [jnp.ones((2, 4)), random.PRNGKey(0)],
                   mesh=mesh, name="bad")
    assert _rules(fs) == ["DL003"]
    assert "fold_in" in fs[0].message


def test_dl003_quiet_after_axis_index_fold_in(mesh):
    """trainer._make_sgd_body's dropout-key pattern."""
    def good(x, key):
        def body(x, key):
            key = random.fold_in(key, lax.axis_index("data"))
            return x + random.normal(key, x.shape)
        return _sm(mesh, body, (P("data"), P()), P("data"))(x, key)
    assert lint_step(good, [jnp.ones((2, 4)), random.PRNGKey(0)],
                     mesh=mesh, name="good") == []


def test_dl003_quiet_outside_spmd_region(mesh):
    def good(key):
        return random.normal(key, (4,))  # single-program, no mesh axes
    assert lint_step(good, [random.PRNGKey(0)], mesh=mesh, name="good") == []


# ---------------------------------------------------------------------- DL004

def test_dl004_f16_psum_fires(mesh):
    def bad(x):
        def body(x):
            return lax.psum(x.astype(jnp.float16), "data")
        return _sm(mesh, body, P("data"), P())(x)
    fs = lint_step(bad, [jnp.ones((2, 4), jnp.float16)], mesh=mesh,
                   name="bad")
    assert _rules(fs) == ["DL004"]
    assert "float16" in fs[0].message


def test_dl004_quiet_on_f32_upcast(mesh):
    def good(x):
        def body(x):
            return lax.psum(x.astype(jnp.float32), "data").astype(jnp.float16)
        return _sm(mesh, body, P("data"), P())(x)
    assert lint_step(good, [jnp.ones((2, 4), jnp.float16)], mesh=mesh,
                     name="good") == []


def test_dl004_quiet_on_f16_pmax(mesh):
    """pmax/pmin are exact in any dtype — only accumulation loses bits."""
    def good(x):
        def body(x):
            return lax.pmax(x.astype(jnp.float16), "data")
        return _sm(mesh, body, P("data"), P())(x)
    assert lint_step(good, [jnp.ones((2, 4), jnp.float16)], mesh=mesh,
                     name="good") == []


# ---------------------------------------------------------------------- DL005

def test_dl005_unmatched_donation_fires():
    bad = jax.jit(lambda s, x: (x * 2.0).sum(), donate_argnums=(0,))
    args = [jnp.ones((8, 8)), jnp.ones((8, 8))]
    fs = lint_step(bad, args, name="bad")
    assert _rules(fs) == ["DL005"]


def test_dl005_quiet_on_aliasable_donation():
    good = jax.jit(lambda s, x: s + x, donate_argnums=(0,))
    args = [jnp.ones((8, 8)), jnp.ones((8, 8))]
    assert lint_step(good, args, name="good") == []


# ----------------------------------------------------------- shared machinery

def test_suppression_and_unknown_rule(mesh):
    def bad(x):
        def body(x):
            return lax.psum(x.astype(jnp.float16), "data")
        return _sm(mesh, body, P("data"), P())(x)
    args = [jnp.ones((2, 4), jnp.float16)]
    assert lint_step(bad, args, mesh=mesh, suppress={"DL004"}) == []
    with pytest.raises(ValueError, match="unknown rule"):
        filter_suppressed([], {"DL999"})
    with pytest.raises(ValueError, match="unknown rule"):
        Finding("DL999", "nope")


def test_walker_descends_scan_and_nested_jit(mesh):
    """Findings inside scan bodies and nested jits are not lost."""
    def bad(x):
        def body(x):
            inner = jax.jit(lambda v: lax.psum(v.astype(jnp.float16), "data"))
            def scanned(c, _):
                return c + inner(x).astype(x.dtype).sum(), None
            return lax.scan(scanned, 0.0, None, length=3)[0]
        return _sm(mesh, body, P("data"), P())(x)
    fs = lint_step(bad, [jnp.ones((2, 4))], mesh=mesh, name="bad")
    assert _rules(fs) == ["DL004"]
    assert "scan" in fs[0].where


def test_format_findings_renders_rule_and_location(mesh):
    def bad(x):
        return lax.psum(x, "batch")
    fs = lint_step(bad, [jnp.ones((4,))], mesh=mesh,
                   axis_env=[("batch", 2)], name="unit")
    text = format_findings(fs, header="unit:")
    assert "DL001" in text and "unit" in text
