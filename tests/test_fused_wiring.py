"""The fused (Pallas + bucketed psum) trainer path must produce the SAME
training trajectory as the per-leaf tree_map path — VERDICT r1 #5: the
kernels are a component only if the production steps run through them.

Runs on the 8-device CPU mesh (Pallas interpret mode) so the identical code
path compiles on TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import random
from jax.sharding import NamedSharding, PartitionSpec as P

from distlearn_tpu.data import synthetic_cifar10
from distlearn_tpu.models import mnist_cnn
from distlearn_tpu.ops import flatten as flatten_lib
from distlearn_tpu.parallel.mesh import MeshTree
from distlearn_tpu.train import (build_ea_steps, build_sgd_step,
                                 build_sync_step, init_ea_state,
                                 init_train_state)


def _data(tree, batch=16):
    x = np.random.RandomState(0).randn(batch, 32, 32, 1).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 10, (batch,)).astype(np.int32)
    sh = NamedSharding(tree.mesh, P(tree.axis_name))
    return jax.device_put(x, sh), jax.device_put(y, sh)


def _model():
    return mnist_cnn()


def _leaves_equal(a, b, exact=True):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, z in zip(la, lb):
        x, z = np.asarray(x), np.asarray(z)
        if exact:
            np.testing.assert_array_equal(x, z)
        else:
            np.testing.assert_allclose(x, z, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("max_bucket_bytes", [None, 64 * 1024])
def test_fused_sgd_step_matches_treemap(max_bucket_bytes):
    tree = MeshTree(num_nodes=8)
    model = _model()
    bx, by = _data(tree)
    ts_a = init_train_state(model, tree, random.PRNGKey(0), 10)
    ts_b = init_train_state(model, tree, random.PRNGKey(0), 10)
    step_ref = build_sgd_step(model, tree, lr=0.1, fused=False)
    step_fused = build_sgd_step(model, tree, lr=0.1, fused=True,
                                max_bucket_bytes=max_bucket_bytes)
    for _ in range(3):
        ts_a, loss_a = step_ref(ts_a, bx, by)
        ts_b, loss_b = step_fused(ts_b, bx, by)
    _leaves_equal(ts_a.params, ts_b.params, exact=False)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)


def test_fused_sgd_step_with_contrib_matches():
    tree = MeshTree(num_nodes=8)
    model = _model()
    bx, by = _data(tree)
    contrib = jax.device_put(
        np.array([1, 1, 0, 1, 0, 1, 1, 1], np.float32),
        NamedSharding(tree.mesh, P(tree.axis_name)))
    ts_a = init_train_state(model, tree, random.PRNGKey(0), 10)
    ts_b = init_train_state(model, tree, random.PRNGKey(0), 10)
    step_ref = build_sgd_step(model, tree, lr=0.1, with_contrib=True,
                              fused=False)
    step_fused = build_sgd_step(model, tree, lr=0.1, with_contrib=True,
                                fused=True)
    ts_a, _ = step_ref(ts_a, bx, by, contrib)
    ts_b, _ = step_fused(ts_b, bx, by, contrib)
    _leaves_equal(ts_a.params, ts_b.params, exact=False)
    np.testing.assert_array_equal(np.asarray(ts_a.sync.my_steps),
                                  np.asarray(ts_b.sync.my_steps))
    # Winner-takes-all sync must leave params bitwise identical across the
    # device shards (params are replicated, spec P()).
    sync = build_sync_step(tree)
    ts_b = sync(ts_b)
    for leaf in jax.tree_util.tree_leaves(ts_b.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)


def test_fused_ea_round_matches_treemap():
    tree = MeshTree(num_nodes=8)
    model = _model()
    bx, by = _data(tree)
    ts_a = init_ea_state(model, tree, random.PRNGKey(0), 10)
    ts_b = init_ea_state(model, tree, random.PRNGKey(0), 10)
    local_a, round_a = build_ea_steps(model, tree, lr=0.05, alpha=0.25,
                                      fused=False)
    local_b, round_b = build_ea_steps(model, tree, lr=0.05, alpha=0.25,
                                      fused=True)
    for _ in range(2):
        ts_a, _ = local_a(ts_a, bx, by)
        ts_b, _ = local_b(ts_b, bx, by)
        ts_a = round_a(ts_a)
        ts_b = round_b(ts_b)
    _leaves_equal(ts_a.params, ts_b.params, exact=False)
    _leaves_equal(ts_a.center, ts_b.center, exact=False)


def test_bucket_spec_roundtrip_mixed_dtypes():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": jnp.ones((5,), jnp.float64),
            "c": jnp.full((3, 3), 2.0, jnp.float32),
            "d": jnp.asarray(7.0, jnp.float64)}
    spec = flatten_lib.make_bucket_spec(tree)
    assert len(spec.buckets) == 2  # one per dtype, no casting
    flats = flatten_lib.pack_buckets(spec, tree)
    for b, f in zip(spec.buckets, flats):
        assert f.dtype == b.dtype and f.shape == (b.padded,)
    back = flatten_lib.unpack_buckets(spec, flats)
    _leaves_equal(tree, back)


def test_bucket_spec_respects_max_bytes():
    tree = [jnp.zeros((1000,), jnp.float32) for _ in range(10)]
    spec = flatten_lib.make_bucket_spec(tree, max_bucket_bytes=3000 * 4)
    assert len(spec.buckets) >= 4          # <=3 leaves of 1000 f32 per bucket
    assert all(sum(b.sizes) <= 3000 for b in spec.buckets)
    flats = flatten_lib.pack_buckets(spec, tree)
    back = flatten_lib.unpack_buckets(spec, flats)
    _leaves_equal(tree, back)
