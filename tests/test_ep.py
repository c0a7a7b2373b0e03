"""Expert parallelism: the all-to-all routed MoE must match a dense
reference (every expert computed for every token, top-1 selected), forward
and backward, when capacity is not binding; capacity drops must zero the
dropped tokens' outputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distlearn_tpu.parallel.ep import moe_ffn, route_top1, route_topk

E, N, D = 4, 12, 8      # 4 experts/devices, 12 tokens per device


def _params(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "experts": jnp.asarray(rng.randn(E, D, D).astype(np.float32) * 0.5),
        "router": jnp.asarray(rng.randn(D, E).astype(np.float32)),
    }


def _expert(p, h):
    return jnp.tanh(h @ p)


def _dense_reference(params, x_all):
    """x_all: [E, N, D] (per-device token blocks).  Dense top-1 MoE."""
    out = []
    for dev in range(E):
        x = x_all[dev]
        gates = jax.nn.softmax(x @ params["router"], axis=-1)     # [N, E]
        pick = jnp.argmax(gates, axis=-1)                         # [N]
        ys = jnp.stack([_expert(params["experts"][e], x)
                        for e in range(E)], axis=1)               # [N, E, D]
        y = jnp.take_along_axis(ys, pick[:, None, None], 1)[:, 0]
        out.append(y * jnp.max(gates, -1, keepdims=True))
    return jnp.stack(out)


def _moe(mesh, capacity_factor):
    def fn(params, x_all):
        ep = jnp.squeeze(params["experts"], 0)        # this device's expert
        x = jnp.squeeze(x_all, 0)
        y = moe_ffn(lambda p, h: _expert(p, h), ep, params["router"], x,
                    capacity_factor=capacity_factor, axis_name="expert")
        return y[None]
    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=({"experts": P("expert"), "router": P()}, P("expert")),
        out_specs=P("expert"), check_vma=False))


def test_moe_matches_dense_reference():
    mesh = Mesh(np.array(jax.devices()[:E]), ("expert",))
    params = _params()
    x_all = jnp.asarray(np.random.RandomState(1).randn(E, N, D)
                        .astype(np.float32))
    # capacity E*N covers any routing: no drops possible
    out = _moe(mesh, capacity_factor=float(E))(params, x_all)
    ref = _dense_reference(params, x_all)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_moe_gradients_match_dense():
    mesh = Mesh(np.array(jax.devices()[:E]), ("expert",))
    params = _params(2)
    x_all = jnp.asarray(np.random.RandomState(3).randn(E, N, D)
                        .astype(np.float32))
    moe = _moe(mesh, capacity_factor=float(E))
    g_moe = jax.grad(lambda p: jnp.sum(moe(p, x_all) ** 2))(params)
    g_ref = jax.grad(lambda p: jnp.sum(_dense_reference(p, x_all) ** 2))(params)
    for k in ("experts", "router"):
        np.testing.assert_allclose(np.asarray(g_moe[k]), np.asarray(g_ref[k]),
                                   rtol=2e-4, atol=2e-5)


def test_capacity_drops_zero_out_tokens():
    """With capacity 1 per expert, at most E tokens per device survive; all
    other rows must be exactly zero (Switch fallback-to-residual)."""
    logits = jnp.asarray(np.random.RandomState(0).randn(N, E), jnp.float32)
    dispatch, combine = route_top1(logits, capacity=1)
    assert dispatch.sum() <= E
    kept = np.asarray(dispatch.any(axis=(1, 2)))
    assert (np.asarray(combine).sum(axis=(1, 2))[~kept] == 0).all()
    # each (expert, slot) holds at most one token
    assert np.asarray(dispatch.sum(axis=0)).max() <= 1


def test_route_top1_positions_unique():
    logits = jnp.asarray(np.random.RandomState(4).randn(64, E), jnp.float32)
    dispatch, _ = route_top1(logits, capacity=16)
    per_slot = np.asarray(dispatch.sum(axis=0))       # [E, C]
    assert per_slot.max() <= 1                        # no slot collisions
    # every token whose expert had room is dispatched exactly once
    assert np.asarray(dispatch.sum(axis=(1, 2))).max() <= 1


def _dense_top2_reference(params, x_all):
    """Dense GShard top-2: both chosen experts run, gates renormalized
    over the two picks."""
    out = []
    for dev in range(E):
        x = x_all[dev]
        gates = jax.nn.softmax(x @ params["router"], axis=-1)     # [N, E]
        topv, topi = jax.lax.top_k(gates, 2)                      # [N, 2]
        w = topv / topv.sum(-1, keepdims=True)
        ys = jnp.stack([_expert(params["experts"][e], x)
                        for e in range(E)], axis=1)               # [N, E, D]
        y = sum(jnp.take_along_axis(ys, topi[:, j][:, None, None], 1)[:, 0]
                * w[:, j][:, None] for j in range(2))
        out.append(y)
    return jnp.stack(out)


def test_moe_top2_matches_dense_reference():
    """The distributed top-2 (GShard) path with non-binding capacity must
    equal the dense run-both-experts reference, forward and backward."""
    mesh = Mesh(np.array(jax.devices()[:E]), ("expert",))
    params = _params(5)
    x_all = jnp.asarray(np.random.RandomState(6).randn(E, N, D)
                        .astype(np.float32))

    def fn(p, xx):
        ep = jnp.squeeze(p["experts"], 0)
        y = moe_ffn(_expert, ep, p["router"], jnp.squeeze(xx, 0),
                    capacity_factor=float(E), axis_name="expert", top_k=2)
        return y[None]

    moe2 = jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=({"experts": P("expert"), "router": P()}, P("expert")),
        out_specs=P("expert"), check_vma=False))
    out = moe2(params, x_all)
    ref = _dense_top2_reference(params, x_all)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    g = jax.grad(lambda p: jnp.sum(moe2(p, x_all) ** 2))(params)
    g_ref = jax.grad(lambda p: jnp.sum(_dense_top2_reference(p, x_all) ** 2)
                     )(params)
    for k in ("experts", "router"):
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(g_ref[k]),
                                   rtol=2e-4, atol=2e-5)


def test_route_topk_aux_terms():
    """balance_loss is 1.0 for a perfectly uniform router and > 1 when
    skewed; dropped_frac counts capacity-dropped assignments exactly."""
    # uniform: every expert equally probable AND equally chosen
    N2 = 4 * E
    logits = jnp.zeros((N2, E), jnp.float32)
    # argmax ties break to expert 0 — build an exactly-cycling assignment
    # with small biases; P_e stays exactly 1/E by symmetry (each expert is
    # boosted in the same fraction of tokens)
    bias = 1e-3 * jax.nn.one_hot(jnp.arange(N2) % E, E)
    _, _, aux = route_topk(logits + bias, capacity=N2, k=1)
    np.testing.assert_allclose(float(aux["balance_loss"]), 1.0, rtol=1e-4)
    assert float(aux["dropped_frac"]) == 0.0
    # fully collapsed: all tokens pick expert 0 with prob ~1 -> loss ~ E
    big = jnp.zeros((N2, E), jnp.float32).at[:, 0].set(20.0)
    _, _, aux = route_topk(big, capacity=N2, k=1)
    np.testing.assert_allclose(float(aux["balance_loss"]), float(E),
                               rtol=1e-3)
    # capacity 1: E tokens kept of N2 assignments
    d3, _, aux = route_topk(big, capacity=1, k=1)
    assert float(aux["dropped_frac"]) == (N2 - 1) / N2


def test_route_top2_slots_unique_and_rank_priority():
    logits = jnp.asarray(np.random.RandomState(7).randn(64, E), jnp.float32)
    dispatch, combine, _ = route_topk(logits, capacity=16, k=2)
    per_slot = np.asarray(dispatch.sum(axis=0))       # [E, C]
    assert per_slot.max() <= 1                        # no slot collisions
    # each token dispatched at most twice (its two experts)
    assert np.asarray(dispatch.sum(axis=(1, 2))).max() <= 2
    # combine weights of kept assignments sum to at most 1 per token
    assert float(np.asarray(combine).sum(axis=(1, 2)).max()) <= 1.0 + 1e-5


def test_moe_rejects_wrong_router_shape():
    mesh = Mesh(np.array(jax.devices()[:E]), ("expert",))
    params = _params()
    bad = {"experts": params["experts"],
           "router": jnp.zeros((D, 2 * E), jnp.float32)}
    x_all = jnp.zeros((E, N, D), jnp.float32)
    with pytest.raises(ValueError, match="router_w must be"):
        _moe(mesh, capacity_factor=float(E))(bad, x_all)


# --- the held layer's router: softmax or sigmoid, chosen through a bias -----

def _routing(seed=0, n=40, d=8, e=12):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(d, e).astype(np.float32)),
            jnp.asarray(rng.randn(n, d).astype(np.float32)),
            jnp.asarray(0.3 * rng.randn(e).astype(np.float32)))


def _slots(plan, slot_w, held, n):
    """``{(token, expert): weight}`` of the plan's live slots."""
    rows, tile_expert, _ = (np.asarray(a) for a in plan)
    tile = rows.shape[0] // tile_expert.shape[0]
    w = np.asarray(slot_w)
    return {(int(t), held[tile_expert[p // tile]]): float(w[p])
            for p, t in enumerate(rows) if t < n}


@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_sigmoid_router_chooses_by_the_biased_score_and_weighs_by_the_bare(
        scale):
    """Written out in numpy: ``s = sigmoid(x W)``; the top-k of ``s + b``
    are chosen; each weighs ``scale * s_e / (sum over the chosen of s +
    1e-20)`` — the bias is in the choice and NOT in the weight.  The bias
    here changes some of the choices and not all."""
    from distlearn_tpu.parallel.ep import route_held
    router, x, bias = _routing()
    held, k = tuple(range(12)), 3
    plan, slot_w, aux = route_held(router, x, k, held, score="sigmoid",
                                   select_bias=bias, scale=scale)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(router, np.float64))))
    chosen = np.argsort(-(s + np.asarray(bias, np.float64)), axis=1)[:, :k]
    want = {}
    for t, es in enumerate(chosen):
        for e in es:
            want[(t, int(e))] = scale * s[t, e] / (s[t, es].sum() + 1e-20)
    got = _slots(plan, slot_w, held, x.shape[0])
    assert set(got) == set(want) and int(aux["dropped"]) == 0
    for key in want:
        assert got[key] == pytest.approx(want[key], rel=1e-5)
    unbiased = np.argsort(-s, axis=1)[:, :k]
    changed = sum(set(a) != set(b) for a, b in zip(chosen, unbiased))
    assert 0 < changed < x.shape[0]


def test_correction_bias_gets_no_gradient_and_the_scores_do():
    from distlearn_tpu.parallel.ep import route_held
    router, x, bias = _routing(1)

    def total(router, bias):
        _, slot_w, _ = route_held(router, x, 3, (0, 4, 7, 9),
                                  score="sigmoid", select_bias=bias,
                                  scale=2.5)
        return jnp.sum(slot_w * jnp.arange(slot_w.shape[0]))
    d_router, d_bias = jax.grad(total, argnums=(0, 1))(router, bias)
    assert float(jnp.abs(d_bias).max()) == 0.0
    assert float(jnp.abs(d_router).max()) > 0.0


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_router_defaults_are_what_they_were_and_each_scoring_is_counted(
        score):
    """No bias, scale 1: the top-k of the scores, renormalised over the
    chosen — for the softmax exactly the weights the function gave before it
    took a ``score``; a traced call counts in ``moe_router_total{score=}``."""
    from distlearn_tpu import obs
    from distlearn_tpu.parallel.ep import route_held
    router, x, _ = _routing(2)
    family = obs.counter("moe_router_total", labels=("score",))
    count = lambda: sum(s["value"] for s in family.sample()  # noqa: E731
                        if s["labels"] == {"score": score})
    before = family is not obs.NULL and count()
    plan, slot_w, _ = route_held(router, x, 2, tuple(range(12)), score=score)
    if family is not obs.NULL:
        assert count() == before + 1
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s = s / s.sum(-1, keepdims=True) if score == "softmax" \
        else 1.0 / (1.0 + np.exp(-logits))
    top = np.argsort(-s, axis=1)[:, :2]
    got = _slots(plan, slot_w, tuple(range(12)), x.shape[0])
    for t, es in enumerate(top):
        for e in es:
            assert got[(t, int(e))] == pytest.approx(
                s[t, e] / s[t, es].sum(), rel=1e-5)
    if score == "softmax":
        same, same_w, _ = route_held(router, x, 2, tuple(range(12)))
        np.testing.assert_array_equal(np.asarray(same_w), np.asarray(slot_w))


def test_sigmoid_layer_is_the_dense_sum_over_the_chosen_and_held():
    """``moe_held_ffn`` with the sigmoid router against every held expert
    applied to every token and weighted by hand."""
    from distlearn_tpu.parallel.ep import moe_held_ffn
    rng = np.random.RandomState(3)
    router, x, bias = _routing(3, n=24, d=8, e=12)
    held, k, f = (1, 4, 10), 3, 6
    wg, wu = (jnp.asarray(rng.randn(3, 8, f).astype(np.float32) * 0.4)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(3, f, 8).astype(np.float32) * 0.4)
    y, aux = moe_held_ffn(x, router, (wg, wu, wd), held, k, score="sigmoid",
                          select_bias=bias, scale=2.5)
    s = jax.nn.sigmoid(x @ router)
    _, chosen = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(s, chosen, -1)
    w = 2.5 * picked / picked.sum(-1, keepdims=True)
    want = jnp.zeros_like(x)
    for g, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1, keepdims=True)
        want = want + w_e * ((jax.nn.silu(x @ wg[g]) * (x @ wu[g])) @ wd[g])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5,
                               atol=2e-6)
    assert int(aux["assignments"].sum()) == int(
        jnp.isin(chosen, jnp.asarray(held)).sum())
    with pytest.raises(ValueError, match="score must be one of"):
        moe_held_ffn(x, router, (wg, wu, wd), held, k, score="tanh")
