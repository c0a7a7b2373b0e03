"""The chunked gated delta rule (``ops/delta_rule.py``) against its
recurrence, position by position: forward, the hand-written backward passes
of the per-chunk operands (against autodiff of their plain forward) and of
the scan over the chunks, the operands' Pallas kernels in interpret mode
against the ``jnp`` path, a non-zero initial state, step sizes above 1
(negative eigenvalues), and a decay so strong that any split of an exponent
into an overflowing factor would show."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distlearn_tpu.ops import delta_rule
from distlearn_tpu.ops.delta_rule import (_block_inverse, _chunk_operands,
                                          _operands, _operands_vjp,
                                          _unit_lower_inverse,
                                          chunked_delta_rule)


def _sizes(monkeypatch, chunk, sub):
    """The op has one chunk length and sub-block (module constants, no
    argument); the tests set them to walk every branch at a small ``L``."""
    monkeypatch.setattr(delta_rule, "CHUNK", chunk)
    monkeypatch.setattr(delta_rule, "SUB", sub)


def _path(monkeypatch, path):
    """The op chooses its path from the call (``select_delta_rule``); the
    tests walk both on the CPU, the kernels in Pallas interpret mode."""
    monkeypatch.setattr(delta_rule, "select_delta_rule", lambda *a: path)


def recurrence(q, k, v, g, beta, S0):
    """S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T;  o_t = S_t^T q_t.
    Head-major like the system: q, k, v, g [B, H, L, .], beta [B, H, L]."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S
        err = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S)
        S = S + b_t[..., None, None] * k_t[..., None] * err[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)
    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 2), S


def _inputs(L=64, B=2, H=3, K=8, V=8, seed=0, brutal=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda i, *s: jax.random.normal(ks[i], s, jnp.float32)  # noqa: E731
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(n(0, B, H, L, K)) / np.sqrt(K)
    k = unit(n(1, B, H, L, K))
    v = n(2, B, H, L, V)
    g = -jnp.exp(1.5 * n(3, B, H, L, K) - 1.0)      # down to about -8 a step
    if brutal:
        g = g.at[:, :, 5].set(-60.0).at[:, :, 37, ::2].set(-200.0)
    beta = 2.0 * jax.nn.sigmoid(n(4, B, H, L))
    S0 = n(5, B, H, K, V)
    return q, k, v, g, beta, S0


@pytest.mark.parametrize("chunk,sub", [(16, 4), (16, 16), (32, 8), (8, 4),
                                       (64, 16)])
def test_chunked_forward_is_the_recurrence(monkeypatch, chunk, sub):
    _sizes(monkeypatch, chunk, sub)
    q, k, v, g, beta, S0 = _inputs()
    assert float(beta.max()) > 1.0 and float(beta.min()) < 1.0
    want_o, want_S = recurrence(q, k, v, g, beta, S0)
    got_o, got_S = chunked_delta_rule(q, k, v, g, beta, initial_state=S0)
    assert got_o.dtype == v.dtype and got_S.dtype == jnp.float32
    np.testing.assert_allclose(got_o, want_o, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(got_S, want_S, rtol=1e-4, atol=2e-5)
    zero_o, _ = chunked_delta_rule(q, k, v, g, beta)
    np.testing.assert_allclose(
        zero_o, recurrence(q, k, v, g, beta, jnp.zeros_like(S0))[0],
        rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_chunked_gradients_are_the_recurrences(monkeypatch, path):
    """Every input's gradient, the initial state's included, through the
    hand-written backward passes of the scan (one state a chunk kept, ``U``
    recomputed) and of the per-chunk operands, on both paths."""
    _sizes(monkeypatch, 16, 4)
    _path(monkeypatch, path)
    args = _inputs(brutal=False)

    def scalar(fn):
        def f(*a):
            o, S = fn(*a)
            return jnp.sum(o * jnp.cos(jnp.arange(o.size, dtype=jnp.float32)
                                       .reshape(o.shape))) \
                + jnp.sum(S * jnp.sin(jnp.arange(S.size, dtype=jnp.float32)
                                      .reshape(S.shape)))
        return f

    chunked = lambda *a: chunked_delta_rule(                # noqa: E731
        *a[:5], initial_state=a[5])
    got = jax.grad(scalar(chunked), argnums=tuple(range(6)))(*args)
    want = jax.grad(scalar(recurrence), argnums=tuple(range(6)))(*args)
    for name, a, b in zip("q k v g beta S0".split(), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()),
                                   err_msg=name)


def _chunks(a, chunk):
    """[B, H, L, X] -> [B, H, N, C, X], what ``_operands`` takes."""
    return a.reshape(a.shape[:2] + (-1, chunk, a.shape[-1]))


COTANGENTS = ("Wv", "Wk", "Qd", "Aqk", "Kd", "gam")


@pytest.mark.parametrize("only", COTANGENTS + ("all",))
@pytest.mark.parametrize("chunk,sub", [(16, 4), (32, 8), (64, 16)])
def test_operands_pull_back_is_autodiffs(chunk, sub, only):
    """The written-out pull-back of the per-chunk operands against JAX's
    own differentiation of their plain forward (``_operands``: no
    ``custom_vjp`` in it), float32, for each of the six cotangents alone
    and for all together; step sizes on both sides of 1, decays down to
    exp(-200)."""
    q, k, v, g, beta, _ = _inputs()
    assert float(beta.max()) > 1.0 and float(beta.min()) < 1.0
    args = [_chunks(a, chunk) for a in (q, k, v, g, beta[..., None])]
    plain = lambda *a: _operands(*a, sub, jnp.float32)[0]       # noqa: E731
    ops, pull = jax.vjp(plain, *args)
    keys = jax.random.split(jax.random.PRNGKey(7), len(ops))
    cot = [jax.random.normal(key, o.shape, o.dtype) if only in (name, "all")
           else jnp.zeros_like(o)
           for key, name, o in zip(keys, COTANGENTS, ops)]
    want = pull(tuple(cot))
    _, (X, Akk) = _operands(*args, sub, jnp.float32)
    got = _operands_vjp(*args, X, Akk, cot, sub, jnp.float32)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=2e-6 * max(1.0, float(jnp.abs(b).max())),
            err_msg=f"d{name} for the cotangent of {only}")


@pytest.mark.parametrize("L,chunk,sub", [(256, 32, 8), (192, 32, 8),
                                         (64, 16, 4)])
def test_kernels_are_the_jnp_path(L, chunk, sub):
    """The two Pallas calls (interpret mode here) against the ``jnp`` path
    of the same ``custom_vjp``: the six operands and all five gradients,
    where the kernels' block of 128 positions divides the sequence (256),
    where its last block is partly past the end (192: six chunks in blocks
    of four) and where the sequence is shorter than one block (64)."""
    q, k, v, g, beta, _ = _inputs(L=L, H=2, V=16)
    got, want = [], []
    for impl, out in (("kernel", got), ("xla", want)):
        ops, pull = jax.vjp(lambda *a: _chunk_operands(
            *a, chunk, sub, jnp.float32, impl), q, k, v, g, beta)
        keys = jax.random.split(jax.random.PRNGKey(11), len(ops))
        out += ops + pull(tuple(jax.random.normal(key, o.shape, o.dtype)
                                for key, o in zip(keys, ops)))
    names = COTANGENTS + tuple("dq dk dv dg dbeta".split())
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-5 * max(1.0, float(jnp.abs(b).max())),
            err_msg=name)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_no_exponent_ever_overflows(monkeypatch, path):
    """A channel that forgets everything in one step (exp(-200) underflows
    to 0, exp(+200) would be inf): results and gradients stay finite, at
    the shipped chunk length and sub-block, on both paths."""
    _path(monkeypatch, path)
    assert (delta_rule.CHUNK, delta_rule.SUB) == (32, 8)
    q, k, v, g, beta, S0 = _inputs()
    val, grads = jax.value_and_grad(
        lambda *a: jnp.sum(chunked_delta_rule(*a)[0] ** 2),
        argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert np.isfinite(float(val))
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in grads)


def test_bfloat16_products_stay_near_the_float32_result(monkeypatch):
    _sizes(monkeypatch, 16, 4)
    q, k, v, g, beta, _ = _inputs(brutal=False)
    want, _ = chunked_delta_rule(q, k, v, g, beta)
    got, S = chunked_delta_rule(q, k, v.astype(jnp.bfloat16), g, beta)
    assert got.dtype == jnp.bfloat16 and S.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=0.05, atol=0.03)


@pytest.mark.parametrize("C,sub", [(16, 4), (16, 16), (32, 4)])
def test_block_inverse_is_the_inverse(C, sub):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(3), (3, 2, C, C),
                                   jnp.float32), -1)
    want = np.linalg.inv(np.eye(C) + np.asarray(a, np.float64))
    np.testing.assert_allclose(_block_inverse(a, sub), want, rtol=2e-3,
                               atol=2e-3 * np.abs(want).max())
    # the substitution alone, and on the strict triangle its derivative
    # d(M^-1) = -M^-1 dM M^-1 (the identity the operands' pull-back uses)
    small = a[0, :, :8, :8] * 0.3
    np.testing.assert_allclose(
        _unit_lower_inverse(small),
        np.linalg.inv(np.eye(8) + np.asarray(small, np.float64)),
        rtol=1e-5, atol=1e-5)
    w = jnp.arange(small.size, dtype=small.dtype).reshape(small.shape)
    x = _unit_lower_inverse(small)
    want = -jnp.einsum("...ji,...jk,...lk->...il", x, w, x)
    got = jax.grad(lambda m: jnp.sum(_unit_lower_inverse(m) * w))(small)
    np.testing.assert_allclose(jnp.tril(got, -1), jnp.tril(want, -1),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("L,chunk,sub", [(60, 16, 4), (64, 24, 8),
                                         (64, 48, 16)])
def test_shapes_that_do_not_tile_are_refused(monkeypatch, L, chunk, sub):
    _sizes(monkeypatch, chunk, sub)
    q, k, v, g, beta, _ = _inputs(L=L)
    with pytest.raises(ValueError, match="multiple of"):
        chunked_delta_rule(q, k, v, g, beta)
