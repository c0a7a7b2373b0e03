"""Pallas fused-op tests (interpret mode on the CPU mesh — identical kernel
code path as TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import random

from distlearn_tpu.models import cifar_convnet, mnist_cnn
from distlearn_tpu.ops import (fused_elastic, fused_sgd, make_spec, pack,
                               unpack)


def test_pack_unpack_roundtrip():
    params, _ = mnist_cnn().init(random.PRNGKey(0))
    spec = make_spec(params)
    assert spec.padded % 1024 == 0
    rt = unpack(spec, pack(spec, params))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(rt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_fused_sgd_matches_tree_update():
    params, _ = cifar_convnet().init(random.PRNGKey(1))
    grads = jax.tree_util.tree_map(
        lambda p: jnp.full_like(p, 0.5), params)
    spec = make_spec(params)
    out = unpack(spec, fused_sgd(pack(spec, params), pack(spec, grads), 0.2))
    expected = jax.tree_util.tree_map(lambda p, g: p - 0.2 * g, params, grads)
    for a, b in zip(jax.tree_util.tree_leaves(expected),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_fused_elastic_matches_reference_math():
    """delta = (p - c) * alpha; p' = p - delta (lua/AllReduceEA.lua:35-39)."""
    rng = np.random.RandomState(0)
    p = {"a": rng.randn(100, 7).astype(np.float32),
         "b": rng.randn(33).astype(np.float32)}
    c = {"a": rng.randn(100, 7).astype(np.float32),
         "b": rng.randn(33).astype(np.float32)}
    spec = make_spec(p)
    new_flat, delta_flat = fused_elastic(pack(spec, p), pack(spec, c), 0.4)
    new_p, delta = unpack(spec, new_flat), unpack(spec, delta_flat)
    for k in p:
        d = (p[k] - c[k]) * 0.4
        np.testing.assert_allclose(np.asarray(delta[k]), d, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(new_p[k]), p[k] - d, rtol=1e-5, atol=1e-6)


def test_fused_ops_jit_under_vmap_free_shapes():
    # shorter than one block: the block is the whole array
    n = 1024 * 7
    x = jnp.arange(n, dtype=jnp.float32)
    out = fused_sgd(x, jnp.ones(n, jnp.float32), 1.0)
    np.testing.assert_allclose(np.asarray(out), np.arange(n) - 1.0, rtol=1e-6)
    # rows not a multiple of the block: the trailing partial block is
    # masked, and the grid stays short however awkward the row count
    from distlearn_tpu.ops.fused_update import _grid_for
    n = 1024 * 301                      # 2408 rows = 9 blocks of 256 + 104
    assert _grid_for(n) == (10, (256, 128))
    x = jnp.arange(n, dtype=jnp.float32)
    out = fused_sgd(x, jnp.ones(n, jnp.float32), 1.0)
    np.testing.assert_allclose(np.asarray(out), np.arange(n) - 1.0, rtol=1e-6)
