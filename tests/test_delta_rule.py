"""The chunked gated delta rule (``ops/delta_rule.py``) against its
recurrence, position by position: forward, the hand-written backward passes
of the per-chunk operands (against autodiff of their plain forward) and of
the scan over the chunks, the operands' and the scan's Pallas kernels in
interpret mode against the ``jnp`` / ``lax.scan`` paths, a non-zero initial
state, step sizes above 1 (negative eigenvalues), and a decay so strong
that any split of an exponent into an overflowing factor would show."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distlearn_tpu.ops import delta_rule
from distlearn_tpu.ops.delta_rule import (_block_inverse, _carry_state,
                                          _chunk_operands, _operands,
                                          _operands_vjp, _unit_lower_inverse,
                                          chunked_delta_rule)


def _sizes(monkeypatch, chunk, sub):
    """The op has one chunk length and sub-block (module constants, no
    argument); the tests set them to walk every branch at a small ``L``."""
    monkeypatch.setattr(delta_rule, "CHUNK", chunk)
    monkeypatch.setattr(delta_rule, "SUB", sub)


def _path(monkeypatch, path):
    """The op chooses its paths from the call (``select_delta_rule`` for
    the per-chunk operands, ``select_delta_scan`` for the scan over the
    chunks); the tests walk both on the CPU, the kernels in Pallas
    interpret mode."""
    monkeypatch.setattr(delta_rule, "select_delta_rule", lambda *a: path)
    monkeypatch.setattr(delta_rule, "select_delta_scan", lambda *a: path)


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



# ------------------------------------------------ the scan's two kernels --

def _scan_args(B, H, N, C, K, V, cd):
    """What ``_chunk_operands`` hands the scan, head-major: a non-zero
    initial state, the five operands (``Wv`` float32, the others in
    ``cd``) and decays in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 7)
    n = lambda i, *s: jax.random.normal(ks[i], s, jnp.float32)  # noqa: E731
    return (n(0, B, H, K, V), n(1, B, H, N, C, V),
            (0.2 * n(2, B, H, N, C, K)).astype(cd),
            (0.2 * n(3, B, H, N, C, K)).astype(cd),
            jnp.tril(0.3 * n(4, B, H, N, C, C)).astype(cd),
            (0.2 * n(5, B, H, N, C, K)).astype(cd),
            jax.nn.sigmoid(n(6, B, H, N, K) + 2.0))


def _blocks(monkeypatch, heads, chunks):
    """The scan's kernels carry SCAN_HEADS heads through SCAN_CHUNKS chunks
    a grid step (module constants, no argument); the tests set them small."""
    monkeypatch.setattr(delta_rule, "SCAN_HEADS", heads)
    monkeypatch.setattr(delta_rule, "SCAN_CHUNKS", chunks)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("B,H,N,heads,chunks", [
    (1, 2, 6, 2, 2),        # one head block, three chunk blocks
    (2, 4, 4, 2, 2),        # two head blocks, two chunk blocks, B = 2
    (1, 3, 3, 1, 8),        # fewer chunks than a block: one block of three
])
def test_scan_kernels_are_the_lax_scan(monkeypatch, dtype, B, H, N, heads,
                                       chunks):
    """The scan's two Pallas calls (interpret mode here) against the
    ``lax.scan`` path of the same ``custom_vjp``: O, the last state and all
    seven cotangents, from a non-zero initial state, with one head block
    and several, where the chunk block divides the chunks and where the
    chunks are fewer than a block."""
    _blocks(monkeypatch, heads, chunks)
    cd = jnp.dtype(dtype)
    args = _scan_args(B, H, N, 16, 16, 8, cd)
    got, want = [], []
    for impl, out in (("kernel", got), ("xla", want)):
        res, pull = jax.vjp(lambda *a: _carry_state(*a, cd, cd, impl), *args)
        keys = jax.random.split(jax.random.PRNGKey(9), 2)
        out += list(res) + list(pull(tuple(
            jax.random.normal(key, r.shape, jnp.float32).astype(r.dtype)
            for key, r in zip(keys, res))))
    # bfloat16: the kernel's backward sums dO^T Qd - dU^T Wk in one
    # accumulation where the scan rounds the two apart, so a carried dS
    # may round to another bfloat16 in the next chunk's products
    tol = 1e-5 if dtype == "float32" else 1e-2
    names = "O S_end dS0 dWv dWk dQd dAqk dKd dgam".split()
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=tol,
                                   atol=tol * float(np.abs(b).max()),
                                   err_msg=name)


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, those of the jaxprs it holds included,
    each before the ones it holds."""
    from jax.extend import core
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                if isinstance(sub, core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, core.Jaxpr):
                    yield from _eqns(sub)


def _pallas_calls(jaxpr):
    """``(name, outputs, equations in its kernel)`` of every Pallas call in
    ``jaxpr``, nested ones included."""
    return [(getattr(e.params["name"], "name", e.params["name"]),
             len(e.outvars), sum(1 for _ in _eqns(e.params["jaxpr"])))
            for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]


def test_the_primal_writes_no_kept_states(monkeypatch):
    """A ``pallas_call`` output is never dropped, so the scan's primal is a
    kernel with two outputs (O and the last state) and only the
    ``custom_vjp``'s forward rule writes the state a chunk starts from:
    the call alone, and the forward pass of a rematerialised gradient
    (``optimize_remat``), take the first; ``vjp`` and the recomputation the
    second, and the pull-back its own kernel."""
    _blocks(monkeypatch, 2, 2)
    args = _scan_args(1, 2, 4, 16, 16, 8, jnp.float32)
    scan = lambda *a: _carry_state(*a, jnp.float32, jnp.float32,  # noqa
                                   "kernel")
    loss = lambda *a: jnp.sum(scan(*a)[0] ** 2)              # noqa: E731
    calls = lambda f: [c[:2] for c in _pallas_calls(              # noqa
        jax.make_jaxpr(f)(*args).jaxpr)]
    primal = [("delta_rule_scan", 2)]
    keep = [("delta_rule_scan", 3)]
    pull = [("delta_rule_scan_vjp", 7)]
    assert calls(scan) == primal
    assert calls(lambda *a: jax.vjp(scan, *a)[0]) == keep
    assert calls(jax.grad(loss)) == keep + pull
    assert calls(jax.value_and_grad(jax.checkpoint(loss))) \
        == primal + keep + pull


def test_the_chunk_walk_is_a_loop(monkeypatch):
    """A grid step walks its chunks in a loop, its heads unrolled inside:
    the kernels' bodies, which every process traces and lowers anew (no
    compile cache saves that part of set-up), do not grow with
    SCAN_CHUNKS — unrolled, 8 x 8 bodies a call added seconds to the
    cell's ``setup_s`` on the chip (PERF.md section 6, PR 39)."""
    args = _scan_args(1, 2, 8, 16, 16, 8, jnp.float32)
    grad = jax.grad(lambda *a: jnp.sum(_carry_state(
        *a, jnp.float32, jnp.float32, "kernel")[0]))
    sizes = []
    for chunks in (2, 8):
        _blocks(monkeypatch, 2, chunks)
        sizes.append(_pallas_calls(jax.make_jaxpr(grad)(*args).jaxpr))
    assert [c[:2] for c in sizes[0]] == [("delta_rule_scan", 3),
                                         ("delta_rule_scan_vjp", 7)]
    assert sizes[0] == sizes[1]


def test_the_scan_counter_counts_each_path(monkeypatch):
    """``delta_rule_scan_total{impl=}`` moves once per traced
    ``chunked_delta_rule`` call, under the scan's resolved path."""
    _sizes(monkeypatch, 16, 4)
    _blocks(monkeypatch, 2, 2)
    q, k, v, g, beta, _ = _inputs(L=32, B=1, H=2)
    for path in ("xla", "kernel"):
        monkeypatch.setattr(delta_rule, "select_delta_scan",
                            lambda *a, path=path: path)
        before = delta_rule.delta_rule_scan_paths_traced()
        jax.jit(lambda *a: chunked_delta_rule(*a)).lower(q, k, v, g, beta)
        after = delta_rule.delta_rule_scan_paths_traced()
        assert {p: n - before.get(p, 0) for p, n in after.items()
                if n != before.get(p, 0)} == {path: 1}
    assert set(after) <= set(delta_rule.DELTA_RULE_IMPLS)


@pytest.mark.parametrize("backend,dtype,K,V,chunk,N,path", [
    ("tpu", "bfloat16", 128, 128, 32, 256, "kernel"),   # the cell's call
    ("tpu", "bfloat16", 256, 128, 16, 8, "kernel"),
    ("tpu", "bfloat16", 128, 128, 32, 3, "kernel"),     # fewer than a block
    ("tpu", "bfloat16", 128, 128, 32, 12, "xla"),       # the block does not
    ("tpu", "bfloat16", 128, 96, 32, 256, "xla"),       # divide; narrow V
    ("tpu", "bfloat16", 128, 128, 8, 256, "xla"),       # a chunk under a tile
    ("tpu", "float32", 128, 128, 32, 256, "xla"),
    ("cpu", "bfloat16", 128, 128, 32, 256, "xla"),
])
def test_select_delta_scan_table(backend, dtype, K, V, chunk, N, path):
    assert delta_rule.SCAN_CHUNKS == 8
    assert delta_rule.select_delta_scan(backend, jnp.dtype(dtype), K, V,
                                        chunk, N) == path
