"""Ring attention correctness: sharded-by-sequence blockwise result must
match single-device full attention, causal and non-causal, including a
gradient check (the backward pass also rides the ring)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlearn_tpu.parallel.sequence import local_attention, ring_attention

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


def _qkv_long(seed, L=256):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, L, H, D).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="Pallas flash attention is a TPU kernel")
def test_flash_local_attention_matches_reference():
    q, k, v = _qkv_long(6)                 # L=256: kernel-block compatible
    out_f = local_attention(q, k, v, causal=True, flash=True)
    out_r = local_attention(q, k, v, causal=True, flash=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               rtol=2e-2, atol=2e-2)


def test_flash_explicit_request_rejected_when_unsupported(monkeypatch):
    """flash=True must not be silently ignored: on a non-TPU backend (or
    incompatible L) it raises instead of materializing the O(L^2) buffer
    the caller asked to avoid."""
    monkeypatch.delenv("DISTLEARN_TPU_FLASH", raising=False)
    q, k, v = _qkv(7)                      # L=32 also violates blocking
    with pytest.raises(ValueError, match="flash attention needs"):
        local_attention(q, k, v, causal=True, flash=True)


def test_flash_env_fallback_on_unsupported(monkeypatch):
    """Env-enabled flash falls back to the portable path where the kernel
    can't run (CPU mesh / L % 128 != 0) — same numbers as flash off."""
    monkeypatch.setenv("DISTLEARN_TPU_FLASH", "1")
    q, k, v = _qkv(8)
    out = local_attention(q, k, v, causal=True)        # flash=None -> env
    ref = local_attention(q, k, v, causal=True, flash=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


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


# --- chunked causal attention (parallel/sequence.py chunked_causal_attention)


def test_chunked_causal_matches_local():
    """The chunk-skipped score computation is the same math as the full
    masked path — forward and gradients (the saved-softmax backward)."""
    from distlearn_tpu.parallel.sequence import chunked_causal_attention
    rng = np.random.RandomState(3)
    mk = lambda: jnp.asarray(rng.randn(2, 64, 4, 16).astype(np.float32))
    q, k, v = mk(), mk(), mk()

    ref = local_attention(q, k, v, causal=True, impl="xla")
    got = chunked_causal_attention(q, k, v, chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)

    g_ref = jax.grad(lambda a: jnp.sum(
        local_attention(a, k, v, causal=True, impl="xla") ** 2))(q)
    g_got = jax.grad(lambda a: jnp.sum(
        chunked_causal_attention(a, k, v, chunk=16) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-5)


def test_chunked_causal_ragged_falls_back():
    """L not divisible by the chunk (or too short) silently uses the xla
    path — same numbers either way."""
    from distlearn_tpu.parallel.sequence import chunked_causal_attention
    rng = np.random.RandomState(4)
    mk = lambda: jnp.asarray(rng.randn(1, 24, 2, 8).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    got = chunked_causal_attention(q, k, v, chunk=16)
    ref = local_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-7)


def test_local_attention_impl_validation():
    q = jnp.zeros((1, 8, 1, 4))
    with pytest.raises(ValueError, match="impl"):
        local_attention(q, q, q, impl="bogus")


def test_local_attention_chunked_impl_dispatch():
    """impl='chunked' on a causal call routes through the chunked path and
    still matches the oracle (CPU: flash unsupported, chunked is portable)."""
    rng = np.random.RandomState(5)
    mk = lambda: jnp.asarray(rng.randn(1, 2048, 2, 8).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    got = local_attention(q, k, v, causal=True, impl="chunked")
    ref = local_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


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
