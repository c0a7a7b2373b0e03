"""The hyper-connection's Pallas kernels (``ops/hyper_connection.py``) in
interpret mode against the ``jnp`` path of ``models/hybrid.py``: outputs and
every gradient (the streams, the sublayer's terms, ``phi``, ``alpha``,
``b_*``) in float32 and in bfloat16, one- and two-term branches, a grid of
several tiles and column steps, a tile count that pads; one Sinkhorn round
visibly short of twenty; the path table; the counter's labels; the ``mhc``
scope on every kernel call; and an accepted configuration's step program
untouched."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from program_util import program_text

from distlearn_tpu.models import hybrid
from distlearn_tpu.models.transformer import lm_loss
from distlearn_tpu.ops import hyper_connection


def _draw(L, n, C, dtype, seed=0):
    """Streams of bfloat16 values (exact in the matrix unit, whatever the
    dtype they are held in), gates off their drawn values so that every
    gradient is distinct, a cotangent for the output."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    M = 2 * n + n * n
    X = jax.random.normal(ks[0], (1, L, n, C), jnp.float32)
    X = X.astype(jnp.bfloat16)
    hc = {"phi": jax.random.normal(ks[1], (n * C, M), jnp.float32)
          / float(np.sqrt(n * C)),
          "alpha": jnp.array([0.25, 0.3, 0.35], jnp.float32),
          "b_pre": 0.1 * jax.random.normal(ks[2], (n,), jnp.float32),
          "b_post": 0.1 * jax.random.normal(ks[3], (n,), jnp.float32),
          "b_res": 2.0 * jnp.eye(n, dtype=jnp.float32)
          + 0.1 * jax.random.normal(ks[4], (n, n), jnp.float32)}
    t = jax.random.normal(ks[5], (2, 1, L, C), jnp.float32).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(ks[5], 1), (1, L, n * C),
                          jnp.float32)
    return X.reshape(1, L, n * C).astype(dtype), hc, t, g


def _branch(terms):
    """A sublayer of ``terms`` terms that reads x: ``[x / 2 + t_0, ...]``."""
    def branch(t):
        return lambda x: ([x * 0.5 + t[0]] + [t[1] * x][:terms - 1],)
    return branch


def _run(monkeypatch, impl, X, hc, t, g, terms, iters=20, grads=True):
    monkeypatch.setattr(hyper_connection, "select_hc", lambda *a: impl)

    def out(X, hc, t):
        return hybrid.hc_sublayer(hc, X, _branch(terms)(t), iters, 1e-6,
                                  1e-5)[0]

    def both(*a):
        y, pull = jax.vjp(out, *a)
        return y, pull(g.astype(y.dtype))
    with jax.enable_x64(False):
        return jax.jit(both if grads else out)(X, hc, t)


def _close(got, want, rtol, atol_share):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree_util.tree_leaves(got)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(
            b, a, rtol=rtol, atol=atol_share * float(np.abs(a).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("n, L, C, terms, rows, lanes", [
    (4, 32, 256, 1, 16, 128),     # two tiles, two column steps a stream
    (2, 24, 128, 2, 16, 128),     # a last tile that pads, two terms
])
def test_kernels_equal_the_jnp_path_in_float32(monkeypatch, n, L, C, terms,
                                               rows, lanes):
    """Every output and gradient to 1e-5 of the largest: the product at
    float32 precision from the stacked bfloat16 parts, the maps, Sinkhorn's
    rounds and their pull-back, the write-back's mix of dX' left to the
    read-in's pull-back."""
    monkeypatch.setattr(hyper_connection, "HC_ROWS", rows)
    monkeypatch.setattr(hyper_connection, "HC_LANES", lanes)
    X, hc, t, g = _draw(L, n, C, jnp.float32, seed=n)
    want = _run(monkeypatch, "xla", X, hc, t, g, terms)
    got = _run(monkeypatch, "kernel", X, hc, t, g, terms)
    _close(got, want, 1e-5, 1e-5)


def test_kernels_equal_the_jnp_path_in_bfloat16(monkeypatch):
    """bfloat16 streams and terms, two terms: within what rounding x, X',
    dX and dy to bfloat16 in another order leaves (the float32 test above
    holds the arithmetic to 1e-5)."""
    monkeypatch.setattr(hyper_connection, "HC_ROWS", 16)
    X, hc, t, g = _draw(32, 4, 256, jnp.bfloat16, seed=3)
    want = _run(monkeypatch, "xla", X, hc, t, g, 2)
    got = _run(monkeypatch, "kernel", X, hc, t, g, 2)
    _close(got, want, 2e-2, 2e-2)


def test_the_kernels_run_every_sinkhorn_round(monkeypatch):
    """At one round the kernels' output is the jnp path's at one round, and
    farther from the output at twenty than the float32 tolerance — a kernel
    that ran fewer rounds than asked would fail here (the chip's loss check
    cannot see it: PERF.md section 7c)."""
    X, hc, t, g = _draw(16, 4, 128, jnp.float32, seed=5)
    one = _run(monkeypatch, "kernel", X, hc, t, g, 1, iters=1, grads=False)
    twenty = _run(monkeypatch, "kernel", X, hc, t, g, 1, grads=False)
    _close(one, _run(monkeypatch, "xla", X, hc, t, g, 1, iters=1,
                     grads=False), 1e-5, 1e-5)
    gap = float(jnp.abs(one - twenty).max() / jnp.abs(twenty).max())
    assert gap > 1e-3, gap


@pytest.mark.parametrize("backend, dtype, n, C, want", [
    ("tpu", jnp.bfloat16, 4, 4096, "kernel"),
    ("tpu", jnp.bfloat16, 5, 256, "kernel"),
    ("tpu", jnp.bfloat16, 6, 4096, "xla"),      # phi's parts pass 128 rows
    ("tpu", jnp.bfloat16, 4, 4000, "xla"),      # lanes do not tile C
    ("tpu", jnp.float32, 4, 4096, "xla"),
    ("cpu", jnp.bfloat16, 4, 4096, "xla"),
    ("gpu", jnp.bfloat16, 4, 4096, "xla"),
])
def test_select_hc(backend, dtype, n, C, want):
    assert hyper_connection.select_hc(backend, dtype, n, C) == want


@pytest.mark.parametrize("impl", ["kernel", "xla"])
def test_the_counter_counts_each_traced_sublayer_by_its_path(monkeypatch,
                                                             impl):
    monkeypatch.setattr(hyper_connection, "select_hc", lambda *a: impl)
    X, hc, t, _ = _draw(16, 4, 128, jnp.bfloat16)
    before = hybrid.hyper_connections_traced()
    jax.eval_shape(lambda X: hybrid.hc_sublayer(
        hc, X, _branch(1)(t), 20, 1e-6, 1e-5)[0], X)
    after = hybrid.hyper_connections_traced()
    assert {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)} == {impl: 1}


def _pallas_name_stacks(jaxpr, outer=""):
    """``(kernel name, name stack)`` of every Pallas call, the stacks of the
    equations that enclose it prefixed, as the lowering writes them into
    the ``op_name``."""
    out = []
    for eqn in jaxpr.eqns:
        stack = outer + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"], stack))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _pallas_name_stacks(sub, stack)
    return out


def test_every_kernel_call_carries_the_mhc_scope(monkeypatch):
    """The read-in, the write-back and both pull-backs are Pallas calls in
    the scope ``mhc`` (``mhc_ms.train`` reads them), and the step holds no
    other Pallas call of this module."""
    monkeypatch.setattr(hyper_connection, "select_hc", lambda *a: "kernel")
    X, hc, t, g = _draw(16, 4, 128, jnp.bfloat16)

    def loss(X, hc):
        out = hybrid.hc_sublayer(hc, X, _branch(1)(t), 20, 1e-6, 1e-5)[0]
        return jnp.sum(out.astype(jnp.float32) * g)
    with jax.enable_x64(False):
        calls = _pallas_name_stacks(
            jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(X, hc).jaxpr)
    assert sorted(name for name, _ in calls) == [
        "hc_read_in", "hc_read_in_vjp", "hc_write_back", "hc_write_back_vjp"]
    assert all("mhc" in stack for _, stack in calls), calls


#: the lowered step of a toy without streams (``hc_mult`` 0, as every
#: configuration but GLM's), hashed on the commit before the kernels
PLAIN_TOY_SHA256 = ("ad01a41d5c25be01be3a0c3871843715"
                    "5ad7df1934ee44025236f2f53f5c9e27")


def test_a_configuration_without_streams_builds_the_program_it_built():
    model = hybrid.hybrid_lm(
        vocab=97, dim=32, layer_types=("gqa", "kda", "dsa", "window"), heads=4,
        kv_heads=2, head_dim=8, kda_heads=4, kda_head_dim=8,
        q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=0, v_head_dim=8, index_heads=2, index_head_dim=8,
        index_topk=16, index_pool=4, n_routed_experts=8,
        held_experts=(1, 5), experts_per_tok=2, expert_width=24,
        window=16, rope_theta=1e4, max_len=64, compute_dtype=jnp.bfloat16,
        remat="full")
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    with jax.enable_x64(False):
        lowered = jax.jit(jax.value_and_grad(
            lambda p, t: lm_loss(model, p, t))).lower(
                shapes, jax.ShapeDtypeStruct((1, 64), jnp.int32))
    assert hashlib.sha256(program_text(lowered).encode()).hexdigest() == \
        PLAIN_TOY_SHA256
