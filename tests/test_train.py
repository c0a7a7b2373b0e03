"""Fused train-step tests: replicated-params invariant, convergence, EA
divergence/contraction — the trainer-level analogue of the reference's
invariant suites (test/test_AllReduceSGD.lua, test/test_AllReduceEA.lua)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import random
from jax.sharding import NamedSharding, PartitionSpec as P

from distlearn_tpu.data import (PermutationSampler, batch_iterator,
                                make_dataset, synthetic_mnist)
from distlearn_tpu.models import mnist_cnn
from distlearn_tpu.parallel.mesh import MeshTree
from distlearn_tpu.train import (build_ea_cycle, build_ea_steps,
                                 build_eval_step, build_sgd_scan_step,
                                 build_sgd_step, build_sync_step,
                                 init_ea_state, init_train_state,
                                 reduce_confusion)
from distlearn_tpu.utils import metrics as M


def _data_stream(tree, n=512, batch=32, seed=0):
    x, y, nc = synthetic_mnist(n, seed=seed)
    ds = make_dataset(x, y, nc)
    samp = PermutationSampler(ds.size, seed=seed)
    sh = NamedSharding(tree.mesh, P("data"))
    for bx, by in batch_iterator(ds, samp, batch):
        yield jax.device_put(bx, sh), jax.device_put(by, sh)


def test_sgd_step_loss_decreases_and_counts_all_examples():
    tree = MeshTree(num_nodes=4)
    model = mnist_cnn()
    ts = init_train_state(model, tree, random.PRNGKey(0), 10)
    step = build_sgd_step(model, tree, lr=0.1)
    losses = []
    seen = 0
    for _ in range(3):
        for bx, by in _data_stream(tree):
            ts, loss = step(ts, bx, by)
            losses.append(float(loss))
            seen += bx.shape[0]
    assert losses[-1] < losses[0]
    cm = reduce_confusion(ts.cm)
    assert int(cm.sum()) == seen  # every example counted exactly once


def test_sgd_params_replicated_bitwise():
    """The reference's oracle: params identical on all nodes after sync
    (test/test_AllReduceSGD.lua:38).  With the fused step params are
    replicated *every* step — check the addressable shards agree bitwise."""
    tree = MeshTree(num_nodes=4)
    model = mnist_cnn()
    ts = init_train_state(model, tree, random.PRNGKey(0), 10)
    step = build_sgd_step(model, tree, lr=0.1, donate=False)
    for bx, by in _data_stream(tree, n=256, batch=64):
        ts, _ = step(ts, bx, by)
    for leaf in jax.tree_util.tree_leaves(ts.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)


def test_eval_step_confusion_and_loss():
    tree = MeshTree(num_nodes=4)
    model = mnist_cnn()
    params, mstate = model.init(random.PRNGKey(0))
    ev = build_eval_step(model, tree)
    cm = jax.device_put(jnp.zeros((4, 10, 10), jnp.int32),
                        NamedSharding(tree.mesh, P("data")))
    n = 0
    for bx, by in _data_stream(tree, n=256, batch=64):
        cm, loss = ev(params, mstate, cm, bx, by)
        n += bx.shape[0]
    g = reduce_confusion(cm)
    assert int(g.sum()) == n
    assert 0.0 <= M.total_valid(g) <= 1.0


def test_sgd_uneven_participation_and_winner_sync():
    """Uneven-data-partition path: contrib masks non-stepping nodes out of the
    gradient sum (lua/AllReduceSGD.lua:22-27); winner-takes-all sync keeps
    params bitwise-identical afterwards (lua :33-54 / test oracle :38)."""
    tree = MeshTree(num_nodes=4)
    model = mnist_cnn()
    ts = init_train_state(model, tree, random.PRNGKey(0), 10)
    step = build_sgd_step(model, tree, lr=0.1, donate=False, with_contrib=True)
    sync = build_sync_step(tree)
    sh = NamedSharding(tree.mesh, P("data"))
    contrib = jax.device_put(np.array([1, 1, 1, 0], np.int32), sh)
    total = 0
    for bx, by in _data_stream(tree, n=256, batch=64):
        ts, loss = step(ts, bx, by, contrib)
        total += 3 * (bx.shape[0] // 4)  # only 3 of 4 nodes count examples
    steps = np.asarray(jax.device_get(ts.sync.my_steps))
    np.testing.assert_array_equal(steps, [4, 4, 4, 0])
    assert int(reduce_confusion(ts.cm).sum()) == total
    ts = sync(ts)
    assert np.asarray(jax.device_get(ts.sync.my_steps)).sum() == 0
    for leaf in jax.tree_util.tree_leaves(ts.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)


def test_contrib_masks_batchnorm_stats():
    """Non-contributing nodes must not feed the sync-BN statistics (the
    BN analogue of lua/AllReduceSGD.lua:22-27 contributor masking)."""
    from distlearn_tpu.models import cifar_convnet
    tree = MeshTree(num_nodes=4)
    model = cifar_convnet(dropout_rate=0.0)
    step = build_sgd_step(model, tree, lr=0.0, donate=False, with_contrib=True)
    sh = NamedSharding(tree.mesh, P("data"))
    rng = np.random.RandomState(0)
    x = rng.randn(16, 32, 32, 3).astype(np.float32)
    y = (np.arange(16) % 10).astype(np.int32)
    # node 3's shard is wildly out-of-distribution; masked out -> stats should
    # match running the same step with only nodes 0-2's data
    x_bad = x.copy()
    x_bad[12:] *= 100.0
    contrib = jax.device_put(np.array([1, 1, 1, 0], np.int32), sh)
    ts1 = init_train_state(model, tree, random.PRNGKey(0), 10)
    ts1, _ = step(ts1, jax.device_put(x, sh), jax.device_put(y, sh), contrib)
    ts2 = init_train_state(model, tree, random.PRNGKey(0), 10)
    ts2, _ = step(ts2, jax.device_put(x_bad, sh), jax.device_put(y, sh), contrib)
    m1 = np.asarray(jax.device_get(ts1.model_state["bn1"]["mean"]))
    m2 = np.asarray(jax.device_get(ts2.model_state["bn1"]["mean"]))
    np.testing.assert_allclose(m1, m2, rtol=1e-6)


def _stacked_batches(tree, k, batch=32, seed=0):
    """k distinct batches stacked along a leading step axis, plus the same
    batches as a list (for the per-call reference path)."""
    pairs = []
    it = _data_stream(tree, n=k * batch, batch=batch, seed=seed)
    for bx, by in it:
        pairs.append((np.asarray(jax.device_get(bx)),
                      np.asarray(jax.device_get(by))))
    pairs = pairs[:k]
    xs = np.stack([p[0] for p in pairs])
    ys = np.stack([p[1] for p in pairs])
    sh = NamedSharding(tree.mesh, P(None, "data"))
    return jax.device_put(xs, sh), jax.device_put(ys, sh), pairs


def test_sgd_scan_step_matches_per_call_steps():
    """build_sgd_scan_step(K steps in one XLA program) must produce the same
    trajectory as K calls of build_sgd_step — same psum order, same update."""
    tree = MeshTree(num_nodes=4)
    model = mnist_cnn()
    k = 4
    xs, ys, pairs = _stacked_batches(tree, k)
    sh = NamedSharding(tree.mesh, P("data"))

    ts_ref = init_train_state(model, tree, random.PRNGKey(0), 10)
    step = build_sgd_step(model, tree, lr=0.1, donate=False)
    ref_losses = []
    for bx, by in pairs:
        ts_ref, loss = step(ts_ref, jax.device_put(bx, sh),
                            jax.device_put(by, sh))
        ref_losses.append(float(loss))

    ts = init_train_state(model, tree, random.PRNGKey(0), 10)
    scan_step = build_sgd_scan_step(model, tree, lr=0.1, donate=False)
    ts, losses = scan_step(ts, xs, ys)
    assert losses.shape == (k,)
    np.testing.assert_allclose(np.asarray(jax.device_get(losses)),
                               np.asarray(ref_losses), rtol=1e-5, atol=1e-6)
    ref_leaves = jax.tree_util.tree_leaves(jax.device_get(ts_ref.params))
    got_leaves = jax.tree_util.tree_leaves(jax.device_get(ts.params))
    for a, b in zip(ref_leaves, got_leaves):
        # atol 1e-5: scan fuses the k steps into one program, so XLA is free
        # to reassociate reductions differently than the per-call build —
        # identical math, different summation order, few-ulp f32 drift.
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    # step counters / confusion matrices advance identically
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(ts.sync.my_steps)),
        np.asarray(jax.device_get(ts_ref.sync.my_steps)))
    np.testing.assert_array_equal(reduce_confusion(ts.cm),
                                  reduce_confusion(ts_ref.cm))


def test_sgd_scan_step_uneven_participation_matches_per_call():
    """The scanned step with a [K, num_nodes] participation matrix must
    reproduce K per-call with_contrib steps — the uneven-data-partition
    semantics (lua/AllReduceSGD.lua:22-27) on the scanned path."""
    tree = MeshTree(num_nodes=4)
    model = mnist_cnn()
    k = 4
    xs, ys, pairs = _stacked_batches(tree, k, seed=2)
    sh = NamedSharding(tree.mesh, P("data"))
    # a different participation pattern each step, incl. one full row
    contribs = np.array([[1, 1, 1, 0],
                         [1, 0, 1, 1],
                         [1, 1, 1, 1],
                         [0, 1, 0, 1]], np.int32)

    ts_ref = init_train_state(model, tree, random.PRNGKey(0), 10)
    step = build_sgd_step(model, tree, lr=0.1, donate=False,
                          with_contrib=True)
    ref_losses = []
    for (bx, by), c in zip(pairs, contribs):
        ts_ref, loss = step(ts_ref, jax.device_put(bx, sh),
                            jax.device_put(by, sh), jax.device_put(c, sh))
        ref_losses.append(float(loss))

    ts = init_train_state(model, tree, random.PRNGKey(0), 10)
    scan_step = build_sgd_scan_step(model, tree, lr=0.1, donate=False,
                                    with_contrib=True)
    cs = jax.device_put(contribs, NamedSharding(tree.mesh, P(None, "data")))
    ts, losses = scan_step(ts, xs, ys, cs)
    np.testing.assert_allclose(np.asarray(jax.device_get(losses)),
                               np.asarray(ref_losses), rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(ts_ref.params)),
                    jax.tree_util.tree_leaves(jax.device_get(ts.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(ts.sync.my_steps)),
        np.asarray(jax.device_get(ts_ref.sync.my_steps)))
    # per-step column sums: only contributing steps advanced the counter
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(ts.sync.my_steps)), contribs.sum(axis=0))
    np.testing.assert_array_equal(reduce_confusion(ts.cm),
                                  reduce_confusion(ts_ref.cm))


def test_ea_cycle_matches_local_steps_plus_round():
    """build_ea_cycle(τ local steps + elastic round, one dispatch) must match
    τ local() calls followed by one rnd() call."""
    tree = MeshTree(num_nodes=4)
    model = mnist_cnn()
    tau = 3
    xs, ys, pairs = _stacked_batches(tree, tau, seed=1)
    sh = NamedSharding(tree.mesh, P("data"))

    ets_ref = init_ea_state(model, tree, random.PRNGKey(0), 10)
    local, rnd = build_ea_steps(model, tree, lr=0.1, alpha=0.25, donate=False)
    for bx, by in pairs:
        ets_ref, _ = local(ets_ref, jax.device_put(bx, sh),
                           jax.device_put(by, sh))
    ets_ref = rnd(ets_ref)

    ets = init_ea_state(model, tree, random.PRNGKey(0), 10)
    cycle = build_ea_cycle(model, tree, lr=0.1, alpha=0.25, donate=False)
    ets, losses = cycle(ets, xs, ys)
    assert losses.shape == (tau, 4)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(ets_ref.params)),
                    jax.tree_util.tree_leaves(jax.device_get(ets.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(ets_ref.center)),
                    jax.tree_util.tree_leaves(jax.device_get(ets.center))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_ea_local_steps_diverge_then_round_contracts():
    tree = MeshTree(num_nodes=4)
    model = mnist_cnn()
    ets = init_ea_state(model, tree, random.PRNGKey(0), 10)
    local, rnd = build_ea_steps(model, tree, lr=0.1, alpha=0.25, donate=False)

    def spread(ts):
        leaf = jax.tree_util.tree_leaves(ts.params)[0]
        arr = np.asarray(jax.device_get(leaf))
        return float(np.abs(arr - arr[0]).max())

    assert spread(ets) == 0.0
    for bx, by in _data_stream(tree, n=256, batch=64):
        ets, _ = local(ets, bx, by)
    d_before = spread(ets)
    assert d_before > 0  # nodes saw different shards -> divergence
    ets2 = rnd(ets)
    assert spread(ets2) < d_before  # elastic round contracts the gap

    # center replicas stay bitwise identical across nodes (deterministic psum)
    c = jax.tree_util.tree_leaves(ets2.center)[0]
    arr = np.asarray(jax.device_get(c))
    for i in range(1, arr.shape[0]):
        np.testing.assert_array_equal(arr[0], arr[i])


def test_eamsgd_momentum_local_steps():
    """EAMSGD (arXiv:1412.6651 §3): with momentum the velocity buffer moves
    and training converges; with momentum=0 velocity stays zero and the
    trajectory matches plain EASGD bitwise."""
    tree = MeshTree(num_nodes=4)
    model = mnist_cnn()
    xs, ys, pairs = _stacked_batches(tree, 3, seed=2)
    sh = NamedSharding(tree.mesh, P("data"))

    # momentum=0 path is bitwise the plain-EASGD path, vel untouched
    e0 = init_ea_state(model, tree, random.PRNGKey(0), 10)
    l0, _ = build_ea_steps(model, tree, lr=0.1, alpha=0.25, donate=False)
    em = init_ea_state(model, tree, random.PRNGKey(0), 10)
    lm, _ = build_ea_steps(model, tree, lr=0.1, alpha=0.25, donate=False,
                           momentum=0.0)
    for bx, by in pairs:
        bx, by = jax.device_put(bx, sh), jax.device_put(by, sh)
        e0, _ = l0(e0, bx, by)
        em, _ = lm(em, bx, by)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(e0.params)),
                    jax.tree_util.tree_leaves(jax.device_get(em.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(float(np.abs(np.asarray(jax.device_get(v))).max()) == 0.0
               for v in jax.tree_util.tree_leaves(em.vel))

    # momentum>0: velocity becomes non-zero, loss still decreases over epochs
    ets = init_ea_state(model, tree, random.PRNGKey(0), 10)
    local, rnd = build_ea_steps(model, tree, lr=0.05, alpha=0.2,
                                momentum=0.9)
    first = last = None
    k = 0
    for _ in range(3):
        for bx, by in _data_stream(tree, seed=3):
            ets, losses = local(ets, bx, by)
            k += 1
            if k % 10 == 0:
                ets = rnd(ets)
            m = float(np.mean(np.asarray(losses)))
            first = m if first is None else first
            last = m
    assert last < first
    assert any(float(np.abs(np.asarray(jax.device_get(v))).max()) > 0
               for v in jax.tree_util.tree_leaves(ets.vel))


def test_ea_training_converges():
    tree = MeshTree(num_nodes=4)
    model = mnist_cnn()
    ets = init_ea_state(model, tree, random.PRNGKey(0), 10)
    local, rnd = build_ea_steps(model, tree, lr=0.1, alpha=0.2)
    first = last = None
    k = 0
    for _ in range(3):
        for bx, by in _data_stream(tree):
            ets, losses = local(ets, bx, by)
            k += 1
            if k % 10 == 0:
                ets = rnd(ets)
            m = float(np.mean(np.asarray(losses)))
            first = m if first is None else first
            last = m
    assert last < first
