"""DeepSeek sparse attention over latent attention
(``ops/sparse_attention.py``) at toy size on the CPU: the result and its
gradients equal a dense softmax under the selection's mask; the selection
pins the configuration's reading of the pooled indexer — keys mean-pooled
over aligned groups, the top-k counted in positions, the query's own block
always kept and causal within it, ties to the earlier block; no gradient
reaches the indexer; the attended pairs the op counts are those its mask
holds; no array of the whole square exists."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distlearn_tpu.ops import sparse_attention as sa

B, L, H, R, J, DI, POOL, TOPK = 2, 64, 3, 16, 4, 8, 4, 16


def _inputs(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = lambda i, *s: jax.random.normal(ks[i], s, jnp.float32)  # noqa: E731
    return (n(0, B, L, H, R), n(1, B, L, R), n(2, B, L, J, DI),
            n(3, B, L, J), n(4, B, L, DI))


def _mask(qI, w, kI):
    """The selection's [B, L, L] mask from the op's own pieces."""
    kbar = kI.reshape(B, L // POOL, POOL, DI).mean(axis=2)
    blocks = sa.select_blocks(sa.index_scores(qI, w, kbar, 0, POOL), 0,
                              POOL, TOPK)
    s = jnp.arange(L)
    return jnp.repeat(blocks, POOL, axis=-1) & (s[None, :] <= s[:, None])


def _dense(qa, c, allowed, scale):
    s = jnp.einsum("bthr,bkr->bhtk", qa, c) * scale
    p = jax.nn.softmax(jnp.where(allowed[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhtk,bkr->bthr", p, c)


@pytest.mark.parametrize("block,groups", [(16, 4), (16, 2), (64, 4)])
def test_result_and_gradients_are_the_dense_softmax_under_the_mask(block,
                                                                   groups):
    qa, c, qI, w, kI = _inputs()
    scale = 1 / math.sqrt(R)
    allowed = _mask(qI, w, kI)
    op = jax.jit(lambda qa, c: sa.sparse_latent_attention(
        qa, c, qI, w, kI, pool=POOL, topk=TOPK, scale=scale, block=block,
        groups=groups))
    np.testing.assert_allclose(op(qa, c), _dense(qa, c, allowed, scale),
                               atol=2e-5)
    ct = jax.random.normal(jax.random.PRNGKey(9), qa.shape)
    got = jax.grad(lambda a, b: jnp.sum(op(a, b) * ct), (0, 1))(qa, c)
    want = jax.grad(lambda a, b: jnp.sum(_dense(a, b, allowed, scale) * ct),
                    (0, 1))(qa, c)
    for g, x in zip(got, want):
        np.testing.assert_allclose(g, x, atol=5e-5)
    # the last query: its whole block and three earlier ones of sixteen
    assert int(allowed[0, -1].sum()) == TOPK < L


def test_no_gradient_reaches_the_indexer():
    qa, c, qI, w, kI = _inputs(1)
    grads = jax.grad(lambda *a: jnp.sum(sa.sparse_latent_attention(
        qa, c, *a, pool=POOL, topk=TOPK, scale=0.25)), (0, 1, 2))(qI, w, kI)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)


def test_keys_are_mean_pooled_over_aligned_groups():
    """A block's score reads the MEAN of its four keys: the same keys in
    another order within their group score the same, one key moved to the
    next group does not."""
    _, _, qI, w, kI = _inputs(2)
    pool = lambda k: k.reshape(B, L // POOL, POOL, DI).mean(2)  # noqa: E731
    score = sa.index_scores(qI, w, pool(kI), 0, POOL)
    swapped = kI.at[:, 8].set(kI[:, 9]).at[:, 9].set(kI[:, 8])
    np.testing.assert_allclose(sa.index_scores(qI, w, pool(swapped), 0, POOL),
                               score, atol=1e-6)
    moved = kI.at[:, 7].set(kI[:, 8]).at[:, 8].set(kI[:, 7])
    assert not np.allclose(sa.index_scores(qI, w, pool(moved), 0, POOL)[
        :, -1], score[:, -1])
    # the score by its definition, one query and one block
    t, b = 40, 3
    want = sum(float(w[0, t, j]) * max(0.0, float(qI[0, t, j] @ pool(kI)[
        0, b])) for j in range(J)) / math.sqrt(DI)
    assert float(score[0, t, b]) == pytest.approx(want, rel=1e-5)
    assert float(score[0, t, t // POOL]) == -np.inf     # not an earlier one


def test_topk_counts_positions_and_the_tail_block_is_kept_causally():
    """Each query keeps its own block (positions up to itself) and
    ``TOPK / POOL - 1`` earlier blocks, all of them while it has fewer; the
    kept earlier blocks are those of highest score."""
    _, _, qI, w, kI = _inputs(3)
    allowed = np.asarray(_mask(qI, w, kI))
    kbar = kI.reshape(B, L // POOL, POOL, DI).mean(axis=2)
    score = np.asarray(sa.index_scores(qI, w, kbar, 0, POOL))
    keep = TOPK // POOL - 1
    for t in range(L):
        own = range(t - t % POOL, t + 1)
        assert allowed[:, t, list(own)].all()
        assert not allowed[:, t, t + 1:].any()
        earlier = allowed[:, t, :t - t % POOL].reshape(B, -1, POOL)
        assert (earlier.all(-1) == earlier.any(-1)).all()    # whole blocks
        n = earlier.all(-1).sum(-1)
        assert (n == min(keep, t // POOL)).all()
        for b in range(B):
            kept = np.flatnonzero(earlier[b].all(-1))
            dropped = np.setdiff1d(np.arange(t // POOL), kept)
            if len(dropped):
                assert score[b, t, kept].min() >= score[b, t, dropped].max()
    assert allowed[0].sum() == sa.selected_pairs(L, POOL, TOPK)


def test_equal_scores_go_to_the_earlier_block():
    score = jnp.where(jnp.arange(16) < 12, 1.0, -jnp.inf)[None, None]
    score = jnp.broadcast_to(score, (1, 1, 16))
    got = sa.select_blocks(score, 48, POOL, TOPK)        # query 48: block 12
    assert np.flatnonzero(np.asarray(got[0, 0])).tolist() == [0, 1, 2, 12]
    # two above the third-best score, four tied at it: the first of those
    score = jnp.array([3.0, 1.0, 1.0, 1.0, 1.0, 2.0] + [-jnp.inf] * 10)
    got = sa.select_blocks(score[None, None], 24, POOL, TOPK)
    assert np.flatnonzero(np.asarray(got[0, 0])).tolist() == [0, 1, 5, 6]


def test_the_counter_and_the_family_count_the_pairs_of_the_mask():
    assert sa.selected_pairs(8, 4, 8) == sum(
        t % 4 + 1 + 4 * min(1, t // 4) for t in range(8))
    assert sa.selected_pairs(2048, 4, 2048) == 2048 * 2049 // 2
    assert sa.selected_pairs(8192, 4, 2048) == 14671872
    before = sa.dsa_selected_traced().get(8192, 0)
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, 8192, 2, 8), (1, 8192, 8), (1, 8192, 2, 4), (1, 8192, 2),
        (1, 8192, 4))]
    jax.eval_shape(lambda *a: sa.sparse_latent_attention(
        *a, pool=4, topk=2048, scale=1.0), *args)
    assert sa.dsa_selected_traced()[8192] - before == 14671872


def test_no_square_array_exists_beyond_one_block():
    """The op's program holds no array as large as the whole square of one
    head: scores and indexer products exist one block of queries at a
    time."""
    qa, c, qI, w, kI = _inputs()
    jaxpr = jax.make_jaxpr(jax.grad(lambda a: jnp.sum(
        sa.sparse_latent_attention(a, c, qI, w, kI, pool=POOL, topk=TOPK,
                                   scale=1.0, block=16))))(qa)

    def sizes(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                yield int(np.prod(v.aval.shape)) if hasattr(
                    v.aval, "shape") else 0
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from sizes(inner)
    assert max(sizes(jaxpr.jaxpr)) < B * H * L * L


def test_lengths_the_pool_does_not_tile_are_refused():
    qa, c, qI, w, kI = _inputs()
    with pytest.raises(ValueError, match="multiples of the pool"):
        sa.sparse_latent_attention(qa, c, qI, w, kI, pool=POOL, topk=10,
                                   scale=1.0)
