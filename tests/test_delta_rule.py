"""The chunked gated delta rule (``ops/delta_rule.py``) against its
recurrence, position by position: forward, the hand-written backward pass of
the scan over the chunks, a non-zero initial state, step sizes above 1
(negative eigenvalues), and a decay so strong that any split of an exponent
into an overflowing factor would show."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distlearn_tpu.ops import delta_rule
from distlearn_tpu.ops.delta_rule import (_block_inverse, _unit_lower_inverse,
                                          chunked_delta_rule)


def _sizes(monkeypatch, chunk, sub, head_group=delta_rule.HEAD_GROUP):
    """The op has one chunk length, sub-block and head group (module
    constants, no argument); the tests set them to walk every branch at a
    small ``L``."""
    monkeypatch.setattr(delta_rule, "CHUNK", chunk)
    monkeypatch.setattr(delta_rule, "SUB", sub)
    monkeypatch.setattr(delta_rule, "HEAD_GROUP", head_group)


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


@pytest.mark.parametrize("head_group", [1, 4])
def test_chunked_gradients_are_the_recurrences(monkeypatch, head_group):
    """Every input's gradient, the initial state's included, through the
    hand-written backward pass of the scan (one state a chunk kept, ``U``
    recomputed) and the rematerialised head groups."""
    _sizes(monkeypatch, 16, 4, head_group)
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


def test_no_exponent_ever_overflows():
    """A channel that forgets everything in one step (exp(-200) underflows
    to 0, exp(+200) would be inf): results and gradients stay finite, at
    the shipped chunk length and sub-block."""
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
    # the substitution's own backward pass: d(M^-1) = -M^-1 dM M^-1
    f = lambda m: jnp.sum(_unit_lower_inverse(m)                # noqa: E731
                          * jnp.arange(m.size, dtype=m.dtype).reshape(m.shape))
    small = a[0, :, :8, :8] * 0.3
    num = jax.grad(lambda m: jnp.sum(jnp.linalg.inv(jnp.eye(8) + m)
                                     * jnp.arange(m.size, dtype=m.dtype)
                                     .reshape(m.shape)))(small)
    np.testing.assert_allclose(jax.grad(f)(small), jnp.tril(num, -1),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("L,chunk,sub", [(60, 16, 4), (64, 24, 8),
                                         (64, 48, 16)])
def test_shapes_that_do_not_tile_are_refused(monkeypatch, L, chunk, sub):
    _sizes(monkeypatch, chunk, sub)
    q, k, v, g, beta, _ = _inputs(L=L)
    with pytest.raises(ValueError, match="multiple of"):
        chunked_delta_rule(q, k, v, g, beta)
