"""Ring attention correctness: sharded-by-sequence blockwise result must
match single-device full attention, causal and non-causal, including a
gradient check (the backward pass also rides the ring)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlearn_tpu.parallel.sequence import local_attention, ring_attention
from tests.program_util import pallas_calls, program_text

B, L, H, D = 2, 32, 4, 16


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, L, H, D).astype(np.float32))
    return mk(), mk(), mk()


def _ring(mesh, causal):
    """Jitted sharded ring-attention wrapper (shared by the ring tests)."""
    return jax.jit(jax.shard_map(
        lambda qq, kk, vv: ring_attention(qq, kk, vv, "seq", causal=causal),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_local(causal):
    q, k, v = _qkv()
    devs = jax.devices()[:8]
    mesh = Mesh(np.array(devs), ("seq",))

    out = _ring(mesh, causal)(q, k, v)
    ref = local_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_gradients_match():
    q, k, v = _qkv(1)
    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("seq",))

    def ring_loss(qq, kk, vv):
        mapped = jax.shard_map(
            lambda a, b, c: ring_attention(a, b, c, "seq", causal=True),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False)
        return jnp.sum(mapped(qq, kk, vv) ** 2)

    def local_loss(qq, kk, vv):
        return jnp.sum(local_attention(qq, kk, vv, causal=True) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_local = jax.grad(local_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_local):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_single_device_degenerate():
    """axis size 1: ring attention == local attention exactly."""
    q, k, v = _qkv(2)
    mesh = Mesh(np.array(jax.devices()[:1]), ("seq",))
    np.testing.assert_allclose(
        np.asarray(_ring(mesh, causal=True)(q, k, v)),
        np.asarray(local_attention(q, k, v, causal=True)),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n_dev", [2, 4])
def test_alltoall_matches_local(causal, n_dev):
    """Ulysses head-scatter variant == full attention (H=4 divisible)."""
    from distlearn_tpu.parallel.sequence import alltoall_attention
    q, k, v = _qkv(3)
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("seq",))
    a2a = jax.jit(jax.shard_map(
        lambda qq, kk, vv: alltoall_attention(qq, kk, vv, "seq",
                                              causal=causal),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False))
    np.testing.assert_allclose(
        np.asarray(a2a(q, k, v)),
        np.asarray(local_attention(q, k, v, causal=causal)),
        rtol=2e-4, atol=2e-5)


def test_alltoall_gradients_match():
    from distlearn_tpu.parallel.sequence import alltoall_attention
    q, k, v = _qkv(4)
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))

    def a2a_loss(qq, kk, vv):
        mapped = jax.shard_map(
            lambda a, b, c: alltoall_attention(a, b, c, "seq", causal=True),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False)
        return jnp.sum(mapped(qq, kk, vv) ** 2)

    def local_loss(qq, kk, vv):
        return jnp.sum(local_attention(qq, kk, vv, causal=True) ** 2)

    g_a = jax.grad(a2a_loss, argnums=(0, 1, 2))(q, k, v)
    g_l = jax.grad(local_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_a, g_l):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_lm_alltoall_impl_matches_ring():
    """transformer_lm(seq_impl='alltoall') must produce the same logits as
    the ring implementation on the same shards."""
    from jax import random
    from distlearn_tpu.models.transformer import transformer_lm
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    L = 32
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, L)),
                       jnp.int32)
    outs = {}
    for impl in ("ring", "alltoall"):
        lm = transformer_lm(vocab=64, dim=32, depth=2, heads=4, max_len=L,
                            seq_impl=impl)
        params, _ = lm.init(random.PRNGKey(0))
        f = jax.jit(jax.shard_map(
            lambda p, t: lm.apply(p, {}, t, seq_axis="seq")[0],
            mesh=mesh, in_specs=(P(), P(None, "seq")),
            out_specs=P(None, "seq"), check_vma=False))
        outs[impl] = np.asarray(f(params, toks))
    np.testing.assert_allclose(outs["ring"], outs["alltoall"],
                               rtol=2e-4, atol=2e-5)


def test_alltoall_rejects_indivisible_heads():
    from distlearn_tpu.parallel.sequence import alltoall_attention
    q, k, v = _qkv(5)          # H=4 heads
    mesh = Mesh(np.array(jax.devices()[:3]), ("seq",))
    with pytest.raises(ValueError, match="divisible"):
        jax.shard_map(
            lambda qq, kk, vv: alltoall_attention(qq, kk, vv, "seq"),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False)(
            q[:, :30], k[:, :30], v[:, :30])


def test_bf16_attention_matches_f32_reference():
    """bf16 operands feed the matmuls natively with f32 accumulation
    (softmax stats stay f32): both the local and the ring path must stay
    within bf16 rounding of the f32 oracle, and ring must match local
    under the same dtype."""
    q, k, v = _qkv(7)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = np.asarray(local_attention(q, k, v, causal=True))

    out_local = local_attention(qb, kb, vb, causal=True)
    assert out_local.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out_local, np.float32), ref,
                               rtol=0.05, atol=0.02)

    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    out_ring = _ring(mesh, causal=True)(qb, kb, vb)
    assert out_ring.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out_ring, np.float32), ref,
                               rtol=0.05, atol=0.02)
    # ring vs local at the SAME dtype: much tighter (same rounding regime)
    np.testing.assert_allclose(np.asarray(out_ring, np.float32),
                               np.asarray(out_local, np.float32),
                               rtol=0.02, atol=0.01)


# --- the single-device path: chosen from the shape, or forced ---------------

_BF16, _F32 = "bfloat16", "float32"


@pytest.mark.parametrize("causal,L,D,dtype,backend,path", [
    # the benchmark's shape, chip_smoke's long one, a wider head, float32
    (True, 1024, 64, _BF16, "tpu", "splash"),
    (True, 4096, 64, _BF16, "tpu", "splash"),
    (True, 2048, 128, _BF16, "tpu", "splash"),
    (True, 1024, 64, _F32, "tpu", "splash"),
    (True, 1536, 64, _BF16, "tpu", "splash"),
    # the hybrid LM's softmax layer: 64 query heads over 8 K/V heads of 128
    # at 8192 positions (the head counts are not the selector's business:
    # grouped queries ride the same kernel, test_grouped_queries_* below)
    (True, 8192, 128, _BF16, "tpu", "splash"),
    (True, 8192, 128, _F32, "tpu", "splash"),
    (True, 8192, 128, _BF16, "cpu", "xla"),
    # not causal: nothing to skip
    (False, 1024, 64, _BF16, "tpu", "xla"),
    (False, 4096, 128, _F32, "tpu", "xla"),
    # under the shortest length measured to win (the serve engine's prefill
    # buckets, the CPU tests' toy lengths)
    (True, 512, 64, _BF16, "tpu", "xla"),
    (True, 128, 64, _BF16, "tpu", "xla"),
    (True, 32, 16, _F32, "tpu", "xla"),
    # ragged: not a multiple of 128, or only of the 128-wide block, which
    # loses to the full square
    (True, 1000, 64, _BF16, "tpu", "xla"),
    (True, 1100, 64, _BF16, "tpu", "xla"),
    (True, 1152, 64, _BF16, "tpu", "xla"),
    # ... while a 256-wide block still wins
    (True, 1280, 64, _BF16, "tpu", "splash"),
    # a head size the kernel's lanes do not take; a dtype never measured
    (True, 1024, 16, _BF16, "tpu", "xla"),
    (True, 1024, 80, _BF16, "tpu", "xla"),
    (True, 1024, 64, "float16", "tpu", "xla"),
    # the kernel is a TPU kernel
    (True, 1024, 64, _BF16, "cpu", "xla"),
    (True, 4096, 64, _BF16, "gpu", "xla"),
])
def test_select_attention_table(causal, L, D, dtype, backend, path):
    from distlearn_tpu.parallel.sequence import select_attention
    assert select_attention(causal, L, D, dtype, backend) == path
    assert select_attention(causal, L, D, jnp.dtype(dtype), backend) == path


def test_select_attention_reads_no_environment(monkeypatch):
    """The retired gates decide nothing: with every one of them set the
    choice and the numbers are what they are without."""
    from distlearn_tpu.parallel.sequence import select_attention
    q, k, v = _qkv(8)
    ref = local_attention(q, k, v, causal=True, impl="xla")
    for name, value in (("DISTLEARN_TPU_ATTN", "splash"),
                        ("DISTLEARN_TPU_FLASH", "1"),
                        ("DISTLEARN_TPU_CHUNK", "16")):
        monkeypatch.setenv(name, value)
    assert select_attention(True, 1024, 64, _BF16, "cpu") == "xla"
    assert select_attention(True, 1024, 64, _BF16, "tpu") == "splash"
    np.testing.assert_array_equal(
        np.asarray(local_attention(q, k, v, causal=True)), np.asarray(ref))


@pytest.mark.parametrize("causal", [False, True])
def test_default_path_here_is_the_full_square_bit_for_bit(causal):
    q, k, v = _qkv(9)
    np.testing.assert_array_equal(
        np.asarray(local_attention(q, k, v, causal=causal)),
        np.asarray(local_attention(q, k, v, causal=causal, impl="xla")))


def _qkv_heads64(L, seed, batch=1, heads=2, head=64):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(                             # noqa: E731
        rng.randn(batch, L, heads, head).astype(np.float32))
    return mk(), mk(), mk()


def _loss_and_grads(impl, q, k, v):
    def loss(a, b, c):
        out = local_attention(a, b, c, causal=True, impl=impl)
        return jnp.sum(out.astype(jnp.float32) ** 2)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# 1024 = the shortest length the default engages at (two 512 blocks a side:
# one block skipped, two on the diagonal, one unmasked); 384 = three 128
# blocks a side, the narrowest edge a forced call takes; a 64-wide head goes
# to the kernel sequence-minor, a 128-wide one head-minor
@pytest.mark.parametrize("L,D", [(384, 64), (1024, 64), (512, 128)])
def test_splash_matches_full_square_float32(L, D):
    """The blockwise kernel (Pallas interpret mode here) is the full-square
    path's math: forward and all three gradients, float32, tight."""
    q, k, v = _qkv_heads64(L, seed=10, head=D)
    got = local_attention(q, k, v, causal=True, impl="splash")
    ref = local_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    for a, b in zip(_loss_and_grads("splash", q, k, v),
                    _loss_and_grads("xla", q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=5e-5)


def test_splash_bf16_matches_f32_oracle():
    """bf16 operands, float32 scores and statistics: forward within today's
    bf16 tolerance of the float32 oracle, gradients as close to it as the
    full-square path's bf16 gradients are."""
    q, k, v = _qkv_heads64(1024, seed=11)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = np.asarray(local_attention(q, k, v, causal=True, impl="xla"))
    got = local_attention(qb, kb, vb, causal=True, impl="splash")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                               rtol=0.05, atol=0.02)
    g_ref = _loss_and_grads("xla", q, k, v)
    g_xla = _loss_and_grads("xla", qb, kb, vb)
    g_got = _loss_and_grads("splash", qb, kb, vb)
    for r, x, g in zip(g_ref, g_xla, g_got):
        assert g.dtype == jnp.bfloat16
        r = np.asarray(r)
        err_xla = np.abs(np.asarray(x, np.float32) - r).max()
        err_got = np.abs(np.asarray(g, np.float32) - r).max()
        assert err_got <= max(2 * err_xla, 0.02 * np.abs(r).max())


def _gqa_qkv(L, D, heads, kv_heads, seed):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(                           # noqa: E731
        rng.randn(1, L, h, D).astype(np.float32))
    return mk(heads), mk(kv_heads), mk(kv_heads)


@pytest.mark.parametrize("causal", [False, True])
def test_grouped_queries_full_square_is_the_repeated_heads(causal):
    """Query head i attends K/V head i // (H / Hkv): the full-square path
    with 2 K/V heads is bitwise the multi-head path on K/V repeated."""
    q, k, v = _gqa_qkv(48, 16, 6, 2, seed=12)
    got = local_attention(q, k, v, causal=causal)
    rep = lambda a: jnp.repeat(a, 3, axis=2)              # noqa: E731
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(local_attention(q, rep(k), rep(v), causal=causal)))
    with pytest.raises(ValueError, match="must divide"):
        local_attention(q, k[:, :, :1].repeat(4, axis=2), v, causal=causal)


def test_grouped_queries_splash_matches_full_square_float32():
    """The blockwise kernel's multi-query call a K/V head (Pallas interpret
    mode here) against the full square: forward and the three gradients,
    dK and dV summed over the group, at the hybrid LM's head size."""
    q, k, v = _gqa_qkv(256, 128, 4, 2, seed=13)
    got = local_attention(q, k, v, causal=True, impl="splash")
    ref = local_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    for a, b in zip(_loss_and_grads("splash", q, k, v),
                    _loss_and_grads("xla", q, k, v)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=5e-5)


# --- scores over one head size, values over another (latent attention) ------

def _latent_qkv(L, dqk, dv, heads, seed):
    rng = np.random.RandomState(seed)
    mk = lambda d: jnp.asarray(                           # noqa: E731
        rng.randn(1, L, heads, d).astype(np.float32))
    return mk(dqk), mk(dqk), mk(dv)


def test_unequal_head_sizes_full_square_is_the_definition():
    """q and k of one head size, v of another: the scale is 1 / sqrt(q's),
    the output has v's — against the definition in numpy float64."""
    q, k, v = _latent_qkv(48, 24, 16, 2, seed=31)
    got = local_attention(q, k, v, causal=True, impl="xla")
    assert got.shape == (1, 48, 2, 16)
    q64, k64, v64 = (np.asarray(a, np.float64) for a in (q, k, v))
    s = np.einsum("bqhd,bkhd->bhqk", q64, k64) / np.sqrt(24.0)
    s = np.where(np.tril(np.ones((48, 48), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v64)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


# 192 / 128 are the published sizes (q and k go to the kernel sequence-minor
# beside a head-minor v: each operand by its own minor size); 96 / 64 the
# same shape of problem at half the cost, both operands sequence-minor
@pytest.mark.parametrize("L,dqk,dv", [(256, 192, 128), (384, 96, 64)])
def test_unequal_head_sizes_splash_matches_full_square_float32(L, dqk, dv):
    """The blockwise kernel (Pallas interpret mode here) with the library's
    ``head_dim_v`` against the full square: forward and the three gradients,
    float32, at the tolerances of the equal-size comparison above."""
    q, k, v = _latent_qkv(L, dqk, dv, 2, seed=32)
    got = local_attention(q, k, v, causal=True, impl="splash")
    ref = local_attention(q, k, v, causal=True, impl="xla")
    assert got.shape == ref.shape == (1, L, 2, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    for a, b in zip(_loss_and_grads("splash", q, k, v),
                    _loss_and_grads("xla", q, k, v)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=5e-5)


def test_unequal_head_sizes_are_checked_and_counted():
    """k must have q's head size (v's is free); the error for mismatched
    K / V head COUNTS names the head sizes it was given; a call whose v is
    narrower than its q is counted in ``attn_latent_total``, an equal-size
    call is not."""
    from distlearn_tpu.parallel.sequence import attention_paths_traced
    q, k, v = _latent_qkv(32, 24, 16, 4, seed=33)
    with pytest.raises(ValueError, match="q and k must share the head size"):
        local_attention(q, k[..., :16], v, causal=True)
    with pytest.raises(ValueError, match=r"head sizes given: q 24, k 24, "
                                         r"v 16"):
        local_attention(q, k[:, :, :2], v[:, :, :1], causal=True)
    before = attention_paths_traced(latent=True).get("xla", 0)
    local_attention(q, k, v, causal=True)
    local_attention(q, k, k, causal=True)               # equal sizes
    assert attention_paths_traced(latent=True).get("xla", 0) == before + 1


@pytest.mark.parametrize("dqk,dv,want", [
    (64, 64, ("SEQ_MINOR",) * 3), (128, 128, ("HEAD_DIM_MINOR",) * 3),
    (192, 128, ("SEQ_MINOR", "SEQ_MINOR", "HEAD_DIM_MINOR"))])
def test_kernel_layout_is_each_operands_own(monkeypatch, dqk, dv, want):
    """The layout the kernel is handed an operand in follows from THAT
    operand's minor size (a multiple of the 128 lanes: head-minor; else
    sequence-minor) — for q, k, v of one size the one layout they always
    had, for 192-wide q and k beside a 128-wide v one each."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    seen, real = [], sk.make_splash_mha

    def spy(*a, block_sizes, **kw):
        seen.append(block_sizes)
        return real(*a, block_sizes=block_sizes, **kw)
    monkeypatch.setattr(sk, "make_splash_mha", spy)
    q, k, v = _latent_qkv(128, dqk, dv, 2, seed=34)
    jax.eval_shape(lambda a, b, c: local_attention(
        a, b, c, causal=True, impl="splash"), q, k, v)
    (sizes,) = seen
    assert tuple(l.name for l in (sizes.q_layout, sizes.k_layout,
                                  sizes.v_layout)) == want


# --- a causal window --------------------------------------------------------

def _band_oracle(q, k, v, window):
    """Causal attention over the band ``i - window < j <= i`` from the
    definition: a row's softmax over exactly its allowed keys (numpy,
    float64), K/V heads repeated for their group."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    B, L, H, D = q.shape
    k, v = (np.repeat(a, H // k.shape[2], axis=2) for a in (k, v))
    out = np.zeros_like(q)
    for i in range(L):
        lo = max(0, i - window + 1)
        s = np.einsum("bhd,bkhd->bhk", q[:, i], k[:, lo:i + 1]) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[:, i] = np.einsum("bhk,bkhd->bhd", p, v[:, lo:i + 1])
    return out


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 2)])
def test_window_full_square_is_the_band_from_the_definition(heads, kv_heads):
    q, k, v = _gqa_qkv(40, 16, heads, kv_heads, seed=21)
    got = local_attention(q, k, v, causal=True, window=7)
    np.testing.assert_allclose(np.asarray(got), _band_oracle(q, k, v, 7),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("impl", ["xla", "splash"])
def test_window_edge_attends_w_minus_1_back_and_not_w(impl):
    """``i - j = W - 1`` is attended, ``i - j = W`` is not: a value planted
    at key j reaches query j + W - 1 and does not reach query j + W.  On
    the blockwise kernel with a band edge INSIDE a block (W = 100 at block
    128) and across blocks."""
    L, W, j = 384, 100, 130
    q, k, v = _qkv_heads64(L, seed=22)
    bumped = v.at[:, j].add(1000.0)
    moved = np.abs(np.asarray(
        local_attention(q, k, bumped, causal=True, window=W, impl=impl)
        - local_attention(q, k, v, causal=True, window=W, impl=impl))
    ).max(axis=(0, 2, 3))                                   # per query
    assert (moved[:j] == 0).all()                           # causal
    assert (moved[j:j + W] > 1e-3).all()                    # the band
    assert moved[j + W - 1] > 1e-3 and (moved[j + W:] == 0).all()


@pytest.mark.parametrize("impl", ["xla", "splash"])
@pytest.mark.parametrize("window", [128, 129, 4096])
def test_window_that_covers_the_length_is_the_causal_call(impl, window):
    """``L <= W``: mask, result and program are the causal call's — bit for
    bit, and the window's counter does not move."""
    from distlearn_tpu.parallel.sequence import \
        attention_paths_traced as calls
    q, k, v = _qkv_heads64(128, seed=23)
    before = calls(windowed=True)
    got = local_attention(q, k, v, causal=True, window=window, impl=impl)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(local_attention(q, k, v, causal=True, impl=impl)))
    assert calls(windowed=True) == before
    same = lambda w: jax.jit(lambda a, b, c: local_attention(  # noqa: E731
        a, b, c, causal=True, window=w, impl=impl)).lower(q, k, v).as_text()
    assert same(window) == same(None)


@pytest.mark.parametrize("heads,kv_heads,L,D,window", [
    (2, 2, 384, 64, 100),      # MHA, sequence-minor, the edge inside a block
    (2, 2, 384, 64, 256),      # the edge on a block boundary
    (4, 2, 256, 128, 72)])     # grouped queries, head-minor
def test_window_splash_matches_full_square_float32(heads, kv_heads, L, D,
                                                   window):
    """The blockwise kernel with the band's ``LocalMask`` (Pallas interpret
    mode here; blocks below the band never visited) is the full-square
    path's masked math: forward and the three gradients, float32, tight, at
    the tolerances of the causal comparison above."""
    q, k, v = _gqa_qkv(L, D, heads, kv_heads, seed=24)

    def grads(impl):
        def loss(a, b, c):
            out = local_attention(a, b, c, causal=True, impl=impl,
                                  window=window)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = local_attention(q, k, v, causal=True, impl="splash", window=window)
    ref = local_attention(q, k, v, causal=True, impl="xla", window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(ref),
                               _band_oracle(q, k, v, window),
                               rtol=1e-5, atol=2e-6)
    for a, b in zip(grads("splash"), grads("xla")):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=5e-5)


def test_window_is_refused_without_causality_and_counted_with_it():
    from distlearn_tpu.parallel.sequence import \
        attention_paths_traced as calls
    q, k, v = _qkv_heads64(256, seed=25)
    with pytest.raises(ValueError, match="needs causal attention"):
        local_attention(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError, match="needs causal attention"):
        local_attention(q, k, v, causal=True, window=0)
    before, all_before = calls(windowed=True), calls()
    local_attention(q, k, v, causal=True, window=16)         # resolves: xla
    local_attention(q, k, v, causal=True, window=128, impl="splash")
    local_attention(q, k, v, causal=True)                    # no window
    after, all_after = calls(windowed=True), calls()
    assert after.get("xla", 0) - before.get("xla", 0) == 1
    assert after.get("splash", 0) - before.get("splash", 0) == 1
    assert all_after.get("xla", 0) - all_before.get("xla", 0) == 2
    # the path is chosen from the call's shape as it is without a window
    from distlearn_tpu.parallel.sequence import select_attention
    assert select_attention(True, 16384, 128, jnp.bfloat16, "tpu") == "splash"


@pytest.mark.parametrize("causal,L", [(False, 256), (True, 100), (True, 32)])
def test_forced_splash_raises_where_it_cannot_run(causal, L):
    """A forced path is never silently swapped for another one."""
    q = jnp.zeros((1, L, 1, 64))
    with pytest.raises(ValueError, match="splash attention cannot run"):
        local_attention(q, q, q, causal=causal, impl="splash")


@pytest.mark.parametrize("impl", ["bogus", "flash", "chunked"])
def test_local_attention_impl_validation(impl):
    q = jnp.zeros((1, 8, 1, 4))
    with pytest.raises(ValueError, match="impl"):
        local_attention(q, q, q, impl=impl)


def test_64_bit_types_keep_the_full_square_path_on_the_tpu(monkeypatch):
    """Mosaic takes no int64 loop counter: with ``jax_enable_x64`` on (as
    in these tests) a call that would pick the kernel on the TPU keeps the
    full-square path, and forcing the kernel there raises."""
    from distlearn_tpu.parallel import sequence
    assert jax.config.jax_enable_x64
    monkeypatch.setattr(sequence, "_backend", lambda: "tpu")
    q, k, v = _qkv_heads64(1024, seed=13)
    assert sequence.select_attention(True, 1024, 64, q.dtype,
                                     "tpu") == "splash"
    np.testing.assert_array_equal(
        np.asarray(local_attention(q, k, v, causal=True)),
        np.asarray(local_attention(q, k, v, causal=True, impl="xla")))
    with pytest.raises(ValueError, match="jax_enable_x64=True"):
        local_attention(q, k, v, causal=True, impl="splash")


def test_attn_kernel_counter_counts_the_resolved_path():
    """``attn_kernel_total{impl=}`` moves once per traced call, under the
    path the call resolved to — not once per run of a jitted program."""
    from distlearn_tpu.parallel.sequence import \
        attention_paths_traced as calls

    q, k, v = _qkv_heads64(256, seed=12)
    before = calls()
    local_attention(q, k, v, causal=True)                  # resolves: xla
    local_attention(q, k, v, causal=True, impl="splash")
    jitted = jax.jit(lambda a, b, c: local_attention(a, b, c, causal=False))
    jitted(q, k, v)
    jitted(q, k, v)                                        # traced once
    with pytest.raises(ValueError):
        local_attention(q, k, v, impl="bogus")             # counts nothing
    with pytest.raises(ValueError):
        local_attention(q, k, v, impl="splash")            # not causal: nor
    after = calls()
    assert after.get("xla", 0) - before.get("xla", 0) == 2
    assert after.get("splash", 0) - before.get("splash", 0) == 1
    assert set(after) <= {"xla", "splash"}


# --- what a rematerialised block keeps of the kernel ------------------------

def _attn_block(impl):
    """Projections, causal attention through ``impl``, output projection and
    the residual: what a checkpointed block has to compute again."""
    def block(w, x):
        q, k, v = (jnp.einsum("bld,dhk->blhk", x, w[n]) for n in "qkv")
        out = local_attention(q, k, v, causal=True, impl=impl)
        return x + jnp.einsum("blhk,hkd->bld", out, w["o"])
    return block


def _attn_block_inputs(heads, kv_heads, head=64, dim=32, L=128, seed=14):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = lambda i, *s: jax.random.normal(ks[i], s, jnp.float32) / 6  # noqa: E731
    w = {"q": n(0, dim, heads, head), "k": n(1, dim, kv_heads, head),
         "v": n(2, dim, kv_heads, head), "o": n(3, heads, head, dim)}
    return w, 6 * n(4, 1, L, dim)


def _block_grad(wrap, impl):
    block = wrap(_attn_block(impl))
    return jax.grad(lambda w, x: jnp.sum(block(w, x) ** 2), argnums=(0, 1))


def _wrap(name):
    from distlearn_tpu.models.core import checkpoint_block
    return {"named": checkpoint_block, "bare": jax.checkpoint,
            "none": lambda f: f}[name]


# the multi-head call, and the multi-query call a K/V head of grouped queries
@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 2)])
@pytest.mark.parametrize("wrap,calls", [("named", 2), ("bare", 3),
                                        ("none", 2)])
def test_checkpointed_block_runs_the_forward_kernel_once(heads, kv_heads,
                                                         wrap, calls):
    """The gradient of a block checkpointed through ``checkpoint_block``
    holds the forward and the backward kernel, as with no checkpoint at
    all; a bare ``jax.checkpoint`` holds the forward kernel a second time,
    run only to hand the backward kernel its output and log-sum-exp."""
    w, x = _attn_block_inputs(heads, kv_heads)
    jaxpr = jax.make_jaxpr(_block_grad(_wrap(wrap), "splash"))(w, x)
    assert pallas_calls(jaxpr) == calls


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 2)])
def test_kept_residuals_change_no_bit_of_the_gradient(heads, kv_heads):
    """Same kernels on the same operands, one call fewer: the gradients of
    the two checkpoints are bitwise equal (Pallas interpret mode here)."""
    w, x = _attn_block_inputs(heads, kv_heads)
    named = jax.jit(_block_grad(_wrap("named"), "splash"))(w, x)
    bare = jax.jit(_block_grad(_wrap("bare"), "splash"))(w, x)
    for a, b in zip(jax.tree_util.tree_leaves(named),
                    jax.tree_util.tree_leaves(bare)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 2)])
def test_full_square_block_keeps_its_input_alone(heads, kv_heads):
    """The full-square path names nothing (its residual would be the
    ``[B, H, L, L]`` probabilities): the policy finds no name, and the
    checkpointed block's gradient is the bare checkpoint's program, text
    for text — and no kernel is in it."""
    w, x = _attn_block_inputs(heads, kv_heads)
    named, bare = (jax.jit(_block_grad(_wrap(n), "xla")).lower(w, x)
                   for n in ("named", "bare"))
    assert program_text(named) == program_text(bare)
    assert pallas_calls(jax.make_jaxpr(
        _block_grad(_wrap("named"), "xla"))(w, x)) == 0


def test_the_name_alone_changes_no_program(monkeypatch):
    """A ``checkpoint_name`` is metadata: outside a checkpoint that asks for
    it, the kernel call with the name lowers to the text of the call
    without one (the call as it was before the name)."""
    from distlearn_tpu.parallel import sequence
    w, x = _attn_block_inputs(2, 2)
    with_name = jax.jit(_block_grad(_wrap("none"), "splash")).lower(w, x)
    monkeypatch.setattr(sequence, "ATTN_RESIDUALS", None)
    without = jax.jit(_block_grad(_wrap("none"), "splash")).lower(w, x)
    assert program_text(with_name) == program_text(without)


# --- zigzag causal ring attention (balanced layout, masked-block skip) ------


def _zigzag(mesh, n, unroll=False):
    from distlearn_tpu.parallel.sequence import ring_attention
    return jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "seq", causal=True,
                                       layout="zigzag", unroll=unroll),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False))


def test_zigzag_causal_matches_local():
    """Zigzag-laid-out causal ring == the full-attention oracle, after
    undoing the layout permutation (both 4 and 8 ranks: even/odd
    src-vs-my branches both exercised)."""
    from distlearn_tpu.parallel.sequence import zigzag_indices
    q, k, v = _qkv(7)
    for n in (4, 8):
        mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
        idx = zigzag_indices(n, L)
        inv = np.argsort(idx)
        out = _zigzag(mesh, n)(q[:, idx], k[:, idx], v[:, idx])[:, inv]
        ref = local_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


def test_zigzag_causal_gradients_match():
    from distlearn_tpu.parallel.sequence import zigzag_indices
    q, k, v = _qkv(8)
    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
    idx = zigzag_indices(n, L)
    inv = np.argsort(idx)
    zz = _zigzag(mesh, n)

    def loss_z(a, b, c):
        return jnp.sum(zz(a[:, idx], b[:, idx], c[:, idx])[:, inv] ** 2)

    def loss_l(a, b, c):
        return jnp.sum(local_attention(a, b, c, causal=True) ** 2)

    gz = jax.grad(loss_z, argnums=(0, 1, 2))(q, k, v)
    gl = jax.grad(loss_l, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gz, gl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_zigzag_halves_causal_flops():
    """The point of the layout: fully-masked blocks are never computed.
    Unrolled (so XLA's cost model counts every hop), the zigzag program's
    flops must be ~(2n+1)/(4n) of the contiguous causal ring's — about
    0.56 at n=4 — not merely 'a bit less'."""
    from distlearn_tpu.parallel.sequence import ring_attention
    # longer sequence than the shared fixture so the s^2 attention terms
    # dominate the per-hop softmax-stat overhead (at s=4 the overhead
    # hides the cut; the claim is about the quadratic terms)
    rng = np.random.RandomState(9)
    mk = lambda: jnp.asarray(rng.randn(1, 128, 2, 16).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))

    def build(layout):
        return jax.jit(jax.shard_map(
            lambda a, b, c: ring_attention(a, b, c, "seq", causal=True,
                                           layout=layout, unroll=True),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False))

    def flops(layout):
        return build(layout).lower(q, k, v).compile().cost_analysis()["flops"]

    fz = flops("zigzag")
    fc = flops("contig")
    assert fz / fc < 0.65, f"zigzag/contig flops = {fz/fc:.3f}"


def test_zigzag_indices_roundtrip_and_validation():
    from distlearn_tpu.parallel.sequence import zigzag_indices
    idx = zigzag_indices(4, 32)
    assert sorted(idx.tolist()) == list(range(32))
    # rank 0 holds stripes 0 and 7 (s=4): [0..3, 28..31]
    assert idx[:8].tolist() == [0, 1, 2, 3, 28, 29, 30, 31]
    with pytest.raises(ValueError, match="stripes"):
        zigzag_indices(4, 30)


def test_ring_layout_validation():
    from distlearn_tpu.parallel.sequence import ring_attention
    q, k, v = _qkv(10)
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    with pytest.raises(ValueError, match="layout"):
        jax.shard_map(
            lambda a, b, c: ring_attention(a, b, c, "seq", layout="spiral"),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False)(q, k, v)


def test_zigzag_noncausal_is_plain_ring():
    """Non-causal attention is permutation-equivariant: zigzag-ordered
    data through the standard ring already gives the right answer, so
    layout='zigzag' without causal must not change the math."""
    from distlearn_tpu.parallel.sequence import ring_attention, zigzag_indices
    q, k, v = _qkv(11)
    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
    idx = zigzag_indices(n, L)
    inv = np.argsort(idx)
    out = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "seq", causal=False,
                                       layout="zigzag"),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False))(
            q[:, idx], k[:, idx], v[:, idx])[:, inv]
    ref = local_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_zigzag_bf16_against_f32_oracle():
    """bf16 zigzag ring vs the f32 full-attention oracle: the f32
    softmax-stat accumulation must keep bf16 shards within bf16-level
    error of the exact result (mirrors the contiguous-ring bf16 test)."""
    from distlearn_tpu.parallel.sequence import ring_attention, zigzag_indices
    rng = np.random.RandomState(12)
    mk32 = lambda: jnp.asarray(rng.randn(1, 64, 2, 16).astype(np.float32))
    q, k, v = mk32(), mk32(), mk32()
    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
    idx = zigzag_indices(n, 64)
    inv = np.argsort(idx)
    out = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "seq", causal=True,
                                       layout="zigzag"),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False))(
            q[:, idx].astype(jnp.bfloat16), k[:, idx].astype(jnp.bfloat16),
            v[:, idx].astype(jnp.bfloat16))[:, inv]
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)
