"""The head's next-token cross-entropy (``ops/cross_entropy.py``, through
``models/transformer.py::_next_token_xent``): the ``"fused"`` custom_vjp
against the ``"xla"`` path it replaces on the TPU, what its backward keeps,
the counter of the paths and the rule that picks one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distlearn_tpu.models import transformer
from distlearn_tpu.models.hybrid import hybrid_lm
from distlearn_tpu.models.transformer import lm_loss, transformer_lm
from distlearn_tpu.ops import cross_entropy
from distlearn_tpu.parallel import sequence

F32, BF16 = jnp.float32, jnp.bfloat16


def _force(monkeypatch, impl):
    monkeypatch.setattr(cross_entropy, "select_xent", lambda backend: impl)


def _batch(B, L, V, dtype, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    logits = (3.0 * jax.random.normal(k1, (B, L, V), F32)).astype(dtype)
    tokens = jax.random.randint(k2, (B, L), 0, V, jnp.int32)
    return logits, tokens


def _loss_fn(tokens, ahead):
    """The helper as each call site of ``lm_loss`` calls it: ``ahead`` 1 the
    head (targets sliced by the caller), 2 the prediction module (its own
    slice), 0 the sequence-axis form (every position weighed, the last
    without a target)."""
    B, L = tokens.shape
    if ahead == 1:
        return lambda x: transformer._next_token_xent(
            x, tokens, 1, targets=tokens[:, 1:])
    if ahead == 2:
        return lambda x: transformer._next_token_xent(x, tokens, 2)
    w = (jnp.arange(L) < L - 1).astype(F32)
    targets = jnp.roll(tokens, -1, axis=1)
    return lambda x: transformer._next_token_xent(
        x, tokens, 0, targets=targets, w=w, count=lambda: jnp.sum(w) * B)


#: (B, L, V, logits' dtype, ahead, relative tolerance of loss and gradient)
CASES = {
    "head-f32": (2, 9, 97, F32, 1, 1e-6),
    "head-one-row-v256": (1, 16, 256, F32, 1, 1e-6),
    "head-bf16": (2, 9, 97, BF16, 1, 2 ** -7),
    "module-f32": (2, 9, 97, F32, 2, 1e-6),
    "module-bf16": (1, 12, 97, BF16, 2, 2 ** -7),
    "weighted-f32": (3, 9, 97, F32, 0, 1e-6),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_loss_and_gradient_match_the_xla_path(monkeypatch, case):
    """Loss and gradient with respect to the logits, both paths on the same
    logits: float32 within 1e-6, bfloat16 logits (the cotangent then
    rounded to bfloat16 by each path) within a bfloat16 ulp; the weights
    of the last one or two positions give what slicing them off gave."""
    B, L, V, dtype, ahead, tol = CASES[case]
    logits, tokens = _batch(B, L, V, dtype)
    f = _loss_fn(tokens, ahead)
    got = {}
    for impl in ("xla", "fused"):
        _force(monkeypatch, impl)
        loss, grad = jax.value_and_grad(f)(logits)
        assert grad.dtype == dtype
        got[impl] = (float(loss), np.asarray(grad.astype(F32)))
    (lx, gx), (lf, gf) = got["xla"], got["fused"]
    np.testing.assert_allclose(lf, lx, rtol=1e-6)
    scale = float(np.max(np.abs(gx)))
    np.testing.assert_allclose(gf, gx, rtol=tol, atol=tol * scale)
    # the positions without a target get no gradient on either path
    assert not np.any(gf[:, L - max(ahead, 1):])


def test_fused_loss_is_the_mean_over_the_positions_that_have_a_target(
        monkeypatch):
    """The weighted sum over all ``L`` rows with the last weighed 0 is the
    plain mean of the cross-entropy over the first ``L - 1``, computed here
    without either path."""
    logits, tokens = _batch(2, 9, 97, F32, seed=3)
    _force(monkeypatch, "fused")
    got = _loss_fn(tokens, 1)(logits)
    lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    want = -np.mean(np.take_along_axis(np.asarray(lp),
                                       np.asarray(tokens[:, 1:, None]), -1))
    np.testing.assert_allclose(float(got), want, rtol=1e-6)


def _toy_lm(module):
    if module:
        return hybrid_lm(
            vocab=97, dim=32, layer_types=("mla",) * 2, heads=4, kv_heads=4,
            head_dim=12, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, rope_theta=1e4,
            n_routed_experts=8, held_experts=(1, 5), experts_per_tok=2,
            expert_width=24, mtp_depth=1, mtp_weight=0.3, max_len=32)
    return transformer_lm(vocab=97, dim=32, depth=2, heads=4, max_len=32)


@pytest.mark.parametrize("module", [False, True])
def test_lm_loss_paths_agree_on_the_loss_and_every_parameters_gradient(
        monkeypatch, module):
    """``lm_loss`` of a whole toy model, the head alone and with a
    prediction module (its target two ahead), on both paths."""
    model = _toy_lm(module)
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 97,
                                jnp.int32)
    got = {}
    for impl in ("xla", "fused"):
        _force(monkeypatch, impl)
        got[impl] = jax.jit(jax.value_and_grad(
            lambda p: lm_loss(model, p, tokens)))(params)
    np.testing.assert_allclose(float(got["fused"][0]), float(got["xla"][0]),
                               rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got["fused"][1]),
                    jax.tree_util.tree_leaves(got["xla"][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


@pytest.mark.parametrize("reduce", [True, False])
def test_sequence_sharded_loss_agrees_on_both_paths(monkeypatch, reduce):
    """Over a sequence axis of two shards (the last position's target on
    the other shard), each shard's share (``reduce=False``) or the psum'd
    loss, and its gradient, on both paths."""
    model = _toy_lm(False)
    params, _ = model.init(jax.random.PRNGKey(2))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, 97,
                                jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    out = P("seq") if not reduce else P()

    def shard(p, t):
        loss, g = jax.value_and_grad(lambda q: lm_loss(
            model, q, t, seq_axis="seq", reduce=reduce))(p)
        return loss.reshape(1) if not reduce else loss, \
            jax.lax.psum(g, "seq")

    got = {}
    for impl in ("xla", "fused"):
        _force(monkeypatch, impl)
        got[impl] = jax.jit(jax.shard_map(
            shard, mesh=mesh, in_specs=(P(), P(None, "seq")),
            out_specs=(out, P()), check_vma=False))(params, tokens)
    np.testing.assert_allclose(np.asarray(got["fused"][0]),
                               np.asarray(got["xla"][0]), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got["fused"][1]),
                    jax.tree_util.tree_leaves(got["xla"][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("ahead", [0, 1, 2])
def test_fused_backward_keeps_the_logits_and_lse_and_pads_nothing(
        monkeypatch, ahead):
    """What the backward keeps is the logits as given and per-position
    vectors ([B, L]: the log-sum-exp, the targets, the weights): no
    log-probability array.  And the gradient's program neither pads nor
    updates a slice of a logits-sized array."""
    B, L, V = 2, 9, 97
    logits, tokens = _batch(B, L, V, F32)
    _force(monkeypatch, "fused")
    f = _loss_fn(tokens, ahead)
    _, pull = jax.vjp(f, logits)
    kept = jax.tree_util.tree_leaves(pull)
    assert [k.shape for k in kept if np.ndim(k) == 3] == [(B, L, V)]
    big, = [k for k in kept if np.ndim(k) == 3]
    assert big.dtype == logits.dtype
    np.testing.assert_array_equal(np.asarray(big), np.asarray(logits))
    assert all(np.shape(k) in ((B, L), ()) for k in kept if np.ndim(k) != 3)
    grad = jax.make_jaxpr(jax.grad(f))(logits).jaxpr
    written = [(e.primitive.name, v.aval.shape) for e in _eqns(grad)
               for v in e.outvars]
    assert not [w for w in written
                if w[0] in ("pad", "dynamic_update_slice", "concatenate")
                and w[1] == (B, L, V)], written
    assert not [w for w in written if w[0] == "gather"], written


@pytest.mark.parametrize("module,count", [(False, 1), (True, 2)])
def test_xent_counter_counts_a_head_and_a_module_each(monkeypatch, module,
                                                      count):
    """``xent_total{impl=}``: one a traced ``lm_loss``, two where the model
    has a prediction module; on the TPU every one is ``"fused"``."""
    monkeypatch.setattr(sequence, "_backend", lambda: "tpu")
    model = _toy_lm(module)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    before = transformer.xent_paths_traced()
    jax.make_jaxpr(lambda p, t: lm_loss(model, p, t))(
        shapes, jax.ShapeDtypeStruct((1, 32), jnp.int32))
    after = transformer.xent_paths_traced()
    assert {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)} == {"fused": count}


@pytest.mark.parametrize("backend,impl", [
    ("tpu", "fused"), ("cpu", "xla"), ("gpu", "xla"), ("cuda", "xla"),
    ("rocm", "xla")])
def test_select_xent_answers_fused_only_on_the_tpu(backend, impl):
    assert cross_entropy.select_xent(backend) == impl
