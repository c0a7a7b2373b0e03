"""Sequence/context parallelism: ring attention over a mesh axis.

The reference is CNN-only (SURVEY.md §2c: SP/CP explicitly absent), but this
framework treats long-context as first-class: attention over sequences longer
than one chip's memory runs blockwise with K/V rotating around the ICI ring
(Ring Attention; blockwise online-softmax accumulation as in
FlashAttention), so sequence length scales linearly with the number of chips
while every hop rides a neighbor ICI link (``lax.ppermute``).

Usage: shard the sequence axis of q/k/v over a mesh axis inside
``shard_map`` and call :func:`ring_attention` with that axis name.  Each
device holds ``L_local = L / axis_size`` positions; communication is
``axis_size - 1`` neighbor exchanges of the local K/V block, fully
overlappable with the per-block attention compute by XLA's latency-hiding
scheduler.

All accumulation is f32 regardless of input dtype (bf16-safe).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from distlearn_tpu import obs


def _block_attn(q, k, v, scale, mask):
    """Scores + masked online-softmax partials for one K/V block.

    q: [B, Lq, H, D]; k/v: [B, Lk, H, D]; mask: [Lq, Lk] bool or None.
    Returns (m_blk [B,H,Lq], s_exp [B,H,Lq,Lk], o_blk [B,H,Lq,D]) partials.
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    m_blk = jnp.max(scores, axis=-1)                      # [B,H,Lq]
    # guard fully-masked rows: exp(-inf - -inf) -> exp(0) would be wrong,
    # so replace -inf row-max with 0 (the row's s_exp is all zeros anyway)
    m_safe = jnp.where(jnp.isneginf(m_blk), 0.0, m_blk)
    s_exp = jnp.exp(scores - m_safe[..., None])           # [B,H,Lq,Lk]
    s_exp = jnp.where(jnp.isneginf(scores), 0.0, s_exp)
    # AV in the value dtype with f32 accumulation (bf16 MXU path on bf16
    # configs; identical math for f32) — softmax stats stay f32 throughout
    o_blk = jnp.einsum("bhqk,bkhd->bhqd", s_exp.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    return m_safe, s_exp.sum(-1), o_blk


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool = False,
                   impl: str | None = None,
                   layout: str = "contig",
                   unroll: bool | int = False) -> jax.Array:
    """Blockwise ring attention.

    Args:
      q, k, v: local shards ``[B, L_local, H, D]`` — the global sequence is
        the concatenation over the mesh axis in rank order (``layout=
        "contig"``), or the :func:`zigzag_indices` permutation of it
        (``layout="zigzag"``).
      axis_name: mesh axis carrying the sequence shards.
      causal: apply a causal mask over GLOBAL positions.
      impl: forces the single-device path, honored ONLY in the degenerate
        n == 1 case (forwarded to :func:`local_attention`, which picks
        one from the shape when this is None).  For n > 1 the inner
        kernel is always the portable blockwise :func:`_block_attn` —
        its per-block partials carry the softmax statistics the ring
        merge needs; use the zigzag layout to halve the causal block
        work, and note its per-block score buffer is
        [B, H, L_loc/2, L_loc/2] (a quarter of the contiguous ring's
        per-block buffer).
      unroll: forwarded to the ring ``fori_loop`` — inlining the n-1
        hops lets XLA overlap each hop's ppermute with the next block's
        compute across iteration boundaries (the r3 GPipe lesson; use
        for small n).
      layout: ``"zigzag"`` + ``causal`` runs the balanced schedule that
        never computes fully-masked blocks (~2x FLOP cut at large n, and
        identical load on every rank — the contiguous causal ring makes
        every rank wait for rank n-1's n-blocks-of-work).  Non-causal
        attention is permutation-equivariant, so zigzag data needs no
        special handling there (the standard ring is already correct).

    Returns: local attention output ``[B, L_local, H, D]`` (q's dtype),
    in the same layout as the inputs.
    """
    if layout not in ("contig", "zigzag"):
        raise ValueError(f"layout must be 'contig' or 'zigzag', "
                         f"got {layout!r}")
    n = lax.axis_size(axis_name)
    if layout == "zigzag" and causal and n > 1:
        if q.shape[1] % 2:
            raise ValueError(
                f"zigzag layout needs an even local length (two stripes "
                f"per rank), got {q.shape[1]}")
        return _zigzag_ring_causal(q, k, v, axis_name, n,
                                   lax.axis_index(axis_name), unroll=unroll)
    if n == 1:
        # Degenerate ring: the whole sequence is local.  Delegate to the
        # single-device attention so its blockwise kernel (no O(L^2)
        # score buffer, causal block skip) engages — the ring body below
        # would materialize the full [B,H,L,L] s_exp for its one block.
        return local_attention(q, k, v, causal=causal, impl=impl)
    my = lax.axis_index(axis_name)
    B, Lq, H, D = q.shape
    scale = 1.0 / (D ** 0.5)

    q_pos = my * Lq + jnp.arange(Lq)                      # global q positions

    def body(i, carry):
        k_cur, v_cur, m, l, o = carry
        src = (my - i) % n                                # owner of this block
        if causal:
            k_pos = src * Lq + jnp.arange(Lq)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = None
        m_blk, l_blk, o_blk = _block_attn(q, k_cur, v_cur, scale, mask)
        m_new = jnp.maximum(m, m_blk)
        alpha = jnp.exp(m - m_new)                        # rescale old acc
        beta = jnp.exp(m_blk - m_new)
        l = l * alpha + l_blk * beta
        o = o * alpha[..., None] + o_blk * beta[..., None]
        # rotate K/V to the next neighbor (ring step over ICI)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m_new, l, o

    m0 = jnp.full((B, H, Lq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    o0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    _, _, m, l, o = lax.fori_loop(0, n, body, (k, v, m0, l0, o0),
                                  unroll=unroll)
    out = o / jnp.maximum(l, 1e-30)[..., None]            # [B,H,Lq,D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def zigzag_indices(n: int, L: int):
    """Global-position permutation for the zigzag sequence layout.

    With ``n`` ranks the global sequence splits into ``2n`` equal stripes;
    rank ``r`` holds stripes ``r`` and ``2n-1-r`` concatenated.  Returns an
    int array ``idx`` of length ``L`` such that ``x_zigzag = x[..., idx]``
    produces the layout whose rank-order contiguous shards are the zigzag
    shards (i.e. shard it with the same ``P(..., seq_axis)`` spec as the
    contiguous layout).  Invert with ``jnp.argsort(idx)``.

    Why: under a CAUSAL mask the contiguous layout is pathologically
    imbalanced — rank 0's queries see almost no keys while rank n-1's see
    all of them, and every rank pays the worst rank's wall clock.  Pairing
    an early stripe with its mirror-image late stripe gives every rank an
    identical two-full-blocks-per-hop schedule (see
    :func:`ring_attention` ``layout="zigzag"``).
    """
    import numpy as np
    if L % (2 * n):
        raise ValueError(f"sequence length {L} must divide into 2*n={2*n} "
                         "equal zigzag stripes")
    s = L // (2 * n)
    idx = []
    for r in range(n):
        idx.extend(range(r * s, (r + 1) * s))
        idx.extend(range((2 * n - 1 - r) * s, (2 * n - r) * s))
    return np.asarray(idx, np.int32)


def _merge_blocks(acc, blk):
    """Online-softmax merge of two blockwise partial results
    ``(m [B,H,Lq], l [B,H,Lq], o [B,H,Lq,D])``."""
    m, l, o = acc
    mb, lb, ob = blk
    m_new = jnp.maximum(m, mb)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(mb - m_new)
    return (m_new, l * alpha + lb * beta,
            o * alpha[..., None] + ob * beta[..., None])


def _zigzag_ring_causal(q, k, v, axis_name, n, my, unroll=False):
    """Causal ring attention on the zigzag layout (local shard = early
    stripe ``a=my`` ++ late stripe ``b=2n-1-my``).

    Per ring hop the work is exactly two UNMASKED stripe blocks on every
    rank: ``qb×k_early(src)`` always (the late stripe sees every early
    stripe), plus ``qa×k_early(src)`` when ``src < my`` or
    ``qb×k_late(src)`` when ``src > my`` — one of the two, never both, so
    the load is identical on all ranks and the fully-masked blocks the
    contiguous layout wastes ~half its FLOPs computing are never
    launched.  Hop 0 handles the two in-stripe causal diagonals plus the
    local ``qb×ka`` block."""
    B, L2, H, D = q.shape
    s = L2 // 2
    scale = 1.0 / (D ** 0.5)
    tri = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    qa, qb = q[:, :s], q[:, s:]
    ka, kb = k[:, :s], k[:, s:]
    va, vb = v[:, :s], v[:, s:]

    # hop 0: local blocks
    acc_a = _block_attn(qa, ka, va, scale, tri)              # diagonal of a
    acc_b = _merge_blocks(_block_attn(qb, ka, va, scale, None),   # full
                          _block_attn(qb, kb, vb, scale, tri))    # diagonal

    def body(i, carry):
        kc, vc, kd, vd, acc_a, acc_b = carry
        src = (my - i) % n
        # unconditional: late queries attend src's early stripe
        acc_b = _merge_blocks(acc_b, _block_attn(qb, kc, vc, scale, None))
        # one conditional full block — same shape either way, so select
        # the operands and then select which accumulator takes the result
        pred = src < my
        q_sel = jnp.where(pred, qa, qb)
        k_sel = jnp.where(pred, kc, kd)
        v_sel = jnp.where(pred, vc, vd)
        blk = _block_attn(q_sel, k_sel, v_sel, scale, None)
        new_a = _merge_blocks(acc_a, blk)
        new_b = _merge_blocks(acc_b, blk)
        acc_a = jax.tree_util.tree_map(
            lambda nw, old: jnp.where(pred, nw, old), new_a, acc_a)
        acc_b = jax.tree_util.tree_map(
            lambda old, nw: jnp.where(pred, old, nw), acc_b, new_b)
        perm = [(j, (j + 1) % n) for j in range(n)]
        rot = lambda t: lax.ppermute(t, axis_name, perm)   # noqa: E731
        return rot(kc), rot(vc), rot(kd), rot(vd), acc_a, acc_b

    init = (*(lax.ppermute(t, axis_name, [(j, (j + 1) % n) for j in range(n)])
              for t in (ka, va, kb, vb)), acc_a, acc_b)
    *_, acc_a, acc_b = lax.fori_loop(1, n, body, init, unroll=unroll)

    def finish(acc):
        m, l, o = acc
        return o / jnp.maximum(l, 1e-30)[..., None]        # [B,H,s,D]

    out = jnp.concatenate([finish(acc_a), finish(acc_b)], axis=2)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def alltoall_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       axis_name: str, causal: bool = False,
                       impl: str | None = None) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.

    Two ``all_to_all`` collectives swap the SEQUENCE sharding for a HEAD
    sharding: each device then holds the FULL sequence for ``H/n`` of the
    heads, runs ordinary full-attention locally, and swaps back.  Compared
    to :func:`ring_attention` (n-1 neighbor hops, never materializes the
    full sequence): total bytes moved are lower (two all-to-alls of the
    activations vs rotating K/V n-1 times), but the full ``L x L`` score
    block must fit in memory and the head count must be divisible by the
    axis size — the standard trade; both variants are first-class.

    q/k/v: local shards ``[B, L_local, H, D]`` (global sequence = rank-order
    concatenation over the axis).  Returns ``[B, L_local, H, D]``.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return local_attention(q, k, v, causal=causal, impl=impl)
    H = q.shape[2]
    if H % n:
        raise ValueError(
            f"alltoall_attention needs head count divisible by the "
            f"sequence-axis size, got {H} heads over {n} devices; use "
            "ring_attention for this configuration")

    def seq_to_heads(x):
        # [B, L_loc, H, D] -> [B, L, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    out = local_attention(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v),
                          causal=causal, impl=impl)  # full-seq, local heads
    # [B, L, H/n, D] -> [B, L_loc, H, D]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


#: the implementations ``impl=`` may name (``None`` = :func:`select_attention`)
ATTN_IMPLS = ("xla", "splash")

#: block edges the blockwise kernel is run at, widest first.  Measured on the
#: v5e at ``[8, 1024, 20, 64]`` bf16 inside the scanned, rematerialised LM
#: step (PERF.md section 6, PR 27): 512 is the fastest, 256 still beats the
#: full-square path, 128 loses to it — so the default engages only where one
#: of the first two tiles the length, and 128 serves a forced
#: ``impl="splash"`` alone.
_SPLASH_BLOCKS = (512, 256, 128)
_SPLASH_MIN_BLOCK = 256
#: the shortest local length the blockwise kernel was measured to win at
_SPLASH_MIN_LEN = 1024


#: the ``checkpoint_name`` the blockwise kernel gives the two results its
#: backward call reads besides q, k, v: its output and its log-sum-exp.  A
#: name is metadata; only a checkpoint whose policy asks for it
#: (:func:`distlearn_tpu.models.core.checkpoint_block`) keeps them, and then
#: the backward pass does not run the forward kernel a second time.  The
#: full-square path names nothing: its residual would be the
#: ``[B, H, L, L]`` probabilities.
ATTN_RESIDUALS = "attn_residuals"


def _splash_block(L: int) -> int | None:
    """The widest block edge that tiles a length-``L`` sequence, if any."""
    return next((b for b in _SPLASH_BLOCKS if L % b == 0), None)


#: bytes the kernel's fused backward call may write as its UNREDUCED dq: one
#: copy of q for every K/V block of that call (``L / block_kv_dkv`` of them,
#: summed by XLA afterwards).  A quarter of a v5e's memory: at [1, 16384, 32,
#: 192] the 32 copies of 512-wide blocks are 6.4 GB, which no step has room
#: for beside its parameters (compiled for the chip: 16.10 of 15.75 GB,
#: PERF.md section 6, PR 36); every call the package made before fits it at
#: the block it had (the largest, [1, 16384, 28, 128]: 3.76 GB).
_DQ_UNREDUCED_MAX = 4 * 2 ** 30
#: the K/V blocks of that call to widen to, widest first: 4096 is refused by
#: Mosaic's scoped VMEM at head size 192, and of the rest the wider was the
#: faster at every width measured on the v5e at [1, 16384, 32, 192 / 128]
#: (forward + backward 110.1 / 99.5 / 94.7 ms at 512 / 1024 / 2048: fewer
#: copies to write and to sum; PERF.md section 6, PR 36)
_DKV_BLOCKS = (2048, 1024)


def _dkv_block(block: int, q_bytes: int, L: int) -> int:
    """The K/V block of the fused backward call: ``block`` where the
    unreduced dq it makes (``L / block`` copies of q, ``q_bytes`` each) is
    within :data:`_DQ_UNREDUCED_MAX` — every call but the widest — and else
    the widest of :data:`_DKV_BLOCKS` that tiles ``L``."""
    if L // block * q_bytes <= _DQ_UNREDUCED_MAX:
        return block
    return next((b for b in _DKV_BLOCKS if b > block and L % b == 0), block)


def select_attention(causal: bool, L: int, D: int, dtype,
                     backend: str) -> str:
    """The single-device attention path of a call, decided from what the
    call itself shows — causality, local length, head size (``D``: that of
    q and k, the one the scores are taken over; v's may differ and decides
    nothing), dtype, backend — and from nothing else (no environment
    variable, no model name).

    ``"splash"`` (causal attention blockwise, masked blocks skipped, no
    ``[B, H, L, L]`` array in HBM) where it was measured to win: on the TPU,
    causal, ``L`` at least 1024 and a multiple of a block at least 256 wide,
    a head size the kernel's lanes take (a multiple of 64), bf16 or float32.
    Everything else — non-causal, short or ragged lengths, other backends —
    is ``"xla"``, the full-square path, bit for bit what it was."""
    if (backend == "tpu" and causal and L >= _SPLASH_MIN_LEN
            and (_splash_block(L) or 0) >= _SPLASH_MIN_BLOCK and D % 64 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))):
        return "splash"
    return "xla"


def _attn_counter():
    return obs.counter(
        "attn_kernel_total",
        "local_attention calls traced, by resolved implementation",
        labels=("impl",))


def _window_counter():
    return obs.counter(
        "attn_window_total",
        "local_attention calls traced whose causal mask is cut to a band "
        "(window < L), by resolved implementation",
        labels=("impl",))


def _latent_counter():
    return obs.counter(
        "attn_latent_total",
        "local_attention calls traced whose v is narrower than its q (the "
        "scores over one head size, the values of another), by resolved "
        "implementation", labels=("impl",))


def attention_paths_traced(windowed: bool = False,
                           latent: bool = False) -> dict[str, int]:
    """``{impl: local_attention calls traced so far}`` in this process (the
    ``attn_kernel_total`` counter; empty with ``DISTLEARN_OBS=0``).
    ``windowed``: of those, the calls whose mask was a band
    (``attn_window_total``); ``latent``: those whose v was narrower than
    their q (``attn_latent_total``)."""
    family = (_latent_counter() if latent
              else _window_counter() if windowed else _attn_counter())
    if family is obs.NULL:
        return {}
    return {s["labels"]["impl"]: s["value"] for s in family.sample()}


def _backend() -> str:
    """The platform the program being traced will run on.  Its own function
    because a test that compiles for a described (not attached) TPU has to
    answer for the chip here, and JAX's own lowering must not hear it."""
    return jax.default_backend()


def _splash_causal_attention(q, k, v, block: int, interpret: bool,
                             window: int | None = None):
    """Causal attention through JAX's Pallas ``splash_attention`` kernel
    with a ``CausalMask``: blocks above the diagonal are never visited,
    scores and softmax statistics are float32 and live in VMEM only, the
    backward pass is the kernel's fused dK/dV/dQ call.  With ``window`` the
    mask is the causal BAND (``LocalMask``: position i sees ``i - window <
    j <= i``) and the blocks below the band are never visited either.  Its
    output and log-sum-exp carry the name :data:`ATTN_RESIDUALS`.  q/k/v:
    ``[B, L, H, D]``; the kernel wants ``[H, L, D]`` per batch row and an
    already scaled q.  v's head size may differ from q's and k's (the
    kernel's ``head_dim_v``): the output has v's."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    B, L, H, D = q.shape
    Hkv = k.shape[2]
    mask = sm.CausalMask((L, L)) if window is None else sm.LocalMask(
        (L, L), window_size=(window - 1, 0), offset=0)
    # A head narrower than the 128 lanes is padded to them wherever it is
    # the minor dimension (``[.., L, 64]`` is stored as ``[.., L, 128]``);
    # sequence-minor q/k/v are not.  Measured at D = 64: 22 ms of a 433 ms
    # step, in the projections that write them (PERF.md section 6, PR 27).
    # Each operand by its OWN minor size: q and k of 192 beside a v of 128
    # are sequence-minor beside head-minor (PERF.md section 6, PR 36).
    layout = lambda a: (sk.QKVLayout.HEAD_DIM_MINOR         # noqa: E731
                        if a.shape[-1] % 128 == 0 else sk.QKVLayout.SEQ_MINOR)
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=_dkv_block(block, q.nbytes, L),
        block_kv_dkv_compute=block, use_fused_bwd_kernel=True,
        q_layout=layout(q), k_layout=layout(k), v_layout=layout(v))
    heads_first = lambda a: a.transpose(0, 2, 1, 3)   # noqa: E731
    # scaled in float32, rounded once (exact at D = 64: the scale is 1/8)
    qs = (q.astype(jnp.float32) * (1.0 / (D ** 0.5))).astype(q.dtype)
    if Hkv == H:
        kernel = sk.make_splash_mha(
            sm.MultiHeadMask([mask] * H), block_sizes=sizes,
            head_shards=1, q_seq_shards=1,
            residual_checkpoint_name=ATTN_RESIDUALS, interpret=interpret)
        out = jax.vmap(kernel)(heads_first(qs), heads_first(k),
                               heads_first(v))
        return heads_first(out).astype(q.dtype)
    # grouped queries: the H / Hkv query heads that share a K/V head ride
    # one multi-query call, which reads that head's K and V once for all of
    # them and sums their dK / dV inside the kernel; the K/V heads are a
    # second vmapped axis
    group = H // Hkv
    kernel = sk.make_splash_mqa(
        sm.MultiHeadMask([mask] * group), block_sizes=sizes,
        head_shards=1, q_seq_shards=1,
        residual_checkpoint_name=ATTN_RESIDUALS, interpret=interpret)
    qg = heads_first(qs).reshape(B, Hkv, group, L, D)
    out = jax.vmap(jax.vmap(kernel))(qg, heads_first(k), heads_first(v))
    return heads_first(out.reshape(B, H, L, v.shape[-1])).astype(q.dtype)


def local_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    impl: str | None = None,
                    window: int | None = None) -> jax.Array:
    """Single-device attention (same layout as the sharded variants), for
    non-sharded runs and as the per-shard kernel of
    :func:`alltoall_attention`.  q: [B, L, H, D]; k: [B, L, Hkv, D]; v:
    [B, L, Hkv, Dv] with ``Hkv`` dividing ``H`` (grouped queries: query head
    ``i`` attends K/V head ``i // (H / Hkv)``; ``Hkv == H`` is ordinary
    multi-head attention and runs exactly the code it always ran).  q and k
    share the head size the scores are taken over — the scale is ``1 /
    sqrt(D)`` — and v may have ANOTHER (latent attention: 192-wide scores
    over 128-wide values); the result is [B, L, H, Dv].  Equal sizes run
    the code and the program they always ran.

    ``window`` (causal attention only) cuts the mask to a band: position
    ``i`` attends ``j`` with ``j <= i`` and ``i - j < window``.  A window
    that covers the whole length is no window: the call is the causal one,
    mask, counter and program.  Both paths take it — the blockwise kernel
    skips the blocks outside the band, the full-square path masks them —
    and :func:`select_attention` chooses between them from the call's shape
    as it does without one.

    ``impl=None`` — what every caller in the package passes — resolves
    through :func:`select_attention`.  Naming an implementation
    (:data:`ATTN_IMPLS`) forces it, for tests and ``examples/lm.py
    --attnImpl``: ``"xla"`` is the fused full-square path (float32
    ``[B, H, L, L]`` scores and probabilities), ``"splash"`` the blockwise
    Pallas kernel (:func:`_splash_causal_attention`).  A forced path that
    cannot run at this shape RAISES on every backend — a row labelled
    "splash" must have run that kernel; off the TPU a forced ``"splash"``
    runs the kernel in Pallas interpret mode (slow; the CPU tests).

    The resolved path is counted in ``attn_kernel_total{impl=}`` (``obs``):
    once per traced call, not per step — a jitted program is traced once;
    a call whose mask is a band also in ``attn_window_total{impl=}``, one
    whose v is narrower than its q also in ``attn_latent_total{impl=}``."""
    B, L, H, D = q.shape
    Hkv = k.shape[2]
    if k.shape[-1] != D:
        raise ValueError(f"q and k must share the head size the scores are "
                         f"taken over, got {D} and {k.shape[-1]} (v's, "
                         f"{v.shape[-1]}, may differ)")
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window={window} needs causal attention and "
                             "at least one position to attend")
        if window >= L:
            window = None
    if H % Hkv or v.shape[2] != Hkv:
        raise ValueError(f"{H} query heads cannot share {Hkv} K / "
                         f"{v.shape[2]} V heads: the K/V head count must "
                         "divide the query head count (head sizes given: q "
                         f"{D}, k {k.shape[-1]}, v {v.shape[-1]})")
    backend = _backend()
    # with 64-bit types on, the kernel's loop counters trace as int64, which
    # Mosaic refuses (the interpreter, off the TPU, takes them)
    mosaic_refuses = backend == "tpu" and jax.config.jax_enable_x64
    if impl is None:
        impl = select_attention(causal, L, D, q.dtype, backend)
        if mosaic_refuses:
            impl = "xla"
    elif impl not in ATTN_IMPLS:
        raise ValueError(f"attention impl must be one of {ATTN_IMPLS}, "
                         f"got {impl!r}")
    block = _splash_block(L)
    if impl == "splash" and (not causal or block is None or mosaic_refuses):
        raise ValueError(
            f"splash attention cannot run here (causal={causal}, L={L}, "
            f"jax_enable_x64={jax.config.jax_enable_x64}): it needs causal "
            "attention, a local length that is a multiple of "
            f"{_SPLASH_BLOCKS[-1]} and, on the TPU, 64-bit types off")
    _attn_counter().labels(impl=impl).inc()
    if window is not None:
        _window_counter().labels(impl=impl).inc()
    if v.shape[-1] < D:
        _latent_counter().labels(impl=impl).inc()
    if impl == "splash":
        return _splash_causal_attention(q, k, v, block,
                                        interpret=backend != "tpu",
                                        window=window)
    scale = 1.0 / (D ** 0.5)
    if Hkv != H:
        # full-square path of grouped queries: each K/V head repeated for
        # its group (short or ragged lengths and the CPU; the blockwise
        # kernel never copies them)
        k, v = (jnp.repeat(a, H // Hkv, axis=2) for a in (k, v))
    # native-dtype inputs + f32 ACCUMULATION: on bf16 configs the MXU runs
    # bf16 matmuls accumulating in f32 (upcasting the operands instead
    # would force f32 matmuls — 8x slower on the systolic array — and f32
    # score traffic; for f32 models this is identical math)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        pos = jnp.arange(L)
        allowed = pos[:, None] >= pos[None, :]
        if window is not None:
            allowed &= pos[:, None] - pos[None, :] < window
        scores = jnp.where(allowed, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)   # stays f32 (stable softmax)
    out = jnp.einsum("bhqk,bkhd->bhqd", w.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
