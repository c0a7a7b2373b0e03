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
      impl: single-device kernel choice, honored ONLY in the degenerate
        n == 1 case (forwarded to :func:`local_attention`).  For n > 1
        the inner kernel is always the portable blockwise
        :func:`_block_attn` — the Pallas flash kernel in this jax
        version returns no softmax residuals, so its per-block outputs
        cannot be merged across ring hops; use the zigzag layout to
        halve the causal block work, and note its per-block score
        buffer is [B, H, L_loc/2, L_loc/2] (a quarter of the contiguous
        ring's per-block buffer).
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
        # single-device kernel so the flash/chunked paths (no O(L^2)
        # score buffer / causal FLOP skip) stay available — the blockwise
        # fallback below would materialize the full [B,H,L,L] s_exp for
        # its one block.
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


def resolve_chunk(L: int) -> int:
    """Effective chunked-attention chunk for local length ``L``:
    ``DISTLEARN_TPU_CHUNK`` when set (must be a positive int — a
    malformed override raises rather than silently benchmarking a config
    the user did not ask for), else the measured default
    ``max(128, L // 32)`` (see :func:`chunked_causal_attention`).
    The ONE place the resolution rule lives — the example's advisory note
    and the attention dispatch both call it, so they cannot drift."""
    import os
    env = os.environ.get("DISTLEARN_TPU_CHUNK")
    if env:
        try:
            c = int(env)
        except ValueError:
            raise ValueError(
                f"DISTLEARN_TPU_CHUNK={env!r} is not an integer") from None
        if c <= 0:
            raise ValueError(
                f"DISTLEARN_TPU_CHUNK={env!r} must be positive")
        return c
    return max(128, L // 32)


def chunked_engages(L: int, chunk: int | None = None) -> bool:
    """Whether the chunked causal path actually runs at local length
    ``L`` (it needs ``L > chunk`` and ``L % chunk == 0``; otherwise the
    dispatch falls back to plain XLA attention)."""
    c = chunk if chunk else resolve_chunk(L)
    return L > c and L % c == 0


def chunked_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             chunk: int | None = None) -> jax.Array:
    """Causal attention with the masked half of the score matrix never
    computed — a portable (pure-XLA) counterpart to flash attention tuned
    for the opposite end of the memory/compute trade.

    The query axis is split into static chunks; chunk ``i`` attends only
    to keys ``[0, (i+1)*chunk)``, so the matmul and exp work is the causal
    ~L^2/2 rather than the full L^2 the naive path computes-then-masks.
    Unlike flash, the per-chunk softmax weights are left for XLA to save
    as backward residuals: the backward pass re-runs NO exp.  On v5e the
    lm_long config is exp/VPU-bound, where flash pays ~3x the exp count
    (forward + two backward recomputes) of this path's 1x — measured
    (docs/PERF.md): chunked beats both flash and the naive path at
    seq 4096 while using O(L^2/2) f32 residual memory, which fits at the
    batch sizes a 16 GB chip trains at this length anyway.  For long
    sequences at larger batch, flash remains the memory-bound choice.

    Only the diagonal sub-block gets a mask; the strict-past prefix is
    computed unmasked — no [L, L] predicate materialization.

    ``chunk=None`` resolves via :func:`resolve_chunk` (``DISTLEARN_TPU_
    CHUNK`` override, else ``max(128, L // 32)``): the measured v5e sweep
    at L=4096 improves monotonically down to 128 (5.6 -> 11.3 steps/s
    on the full train step across 2048/1024/512/256/128), while capping
    the chunk count at 32 keeps the unrolled per-block program bounded
    for very long sequences (the compile-size failure mode the scanned
    depth layout exists for).  Chunks must stay multiples of the
    128-lane tile — 384 measured catastrophically (6.1 steps/s).
    """
    B, L, H, D = q.shape
    if chunk is None:
        chunk = resolve_chunk(L)
    if not chunked_engages(L, chunk):
        return local_attention(q, k, v, causal=True, impl="xla")
    scale = 1.0 / (D ** 0.5)
    pos = jnp.arange(chunk)
    diag_mask = pos[:, None] >= pos[None, :]          # [chunk, chunk]
    outs = []
    for i in range(L // chunk):
        qs = q[:, i * chunk:(i + 1) * chunk]
        parts = []
        if i:  # strictly-past keys: fully visible, no mask at all
            s_pre = jnp.einsum("bqhd,bkhd->bhqk", qs, k[:, :i * chunk],
                               preferred_element_type=jnp.float32) * scale
            parts.append(s_pre)
        s_diag = jnp.einsum("bqhd,bkhd->bhqk", qs,
                            k[:, i * chunk:(i + 1) * chunk],
                            preferred_element_type=jnp.float32) * scale
        parts.append(jnp.where(diag_mask[None, None], s_diag, -jnp.inf))
        s = jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]
        w = jax.nn.softmax(s, axis=-1)                # f32, saved for bwd
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", w.astype(q.dtype),
                               v[:, :(i + 1) * chunk],
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def _flash_enabled(override: bool | None) -> bool:
    """Opt-in Pallas flash-attention (TPU only).  Priority: explicit arg >
    ``DISTLEARN_TPU_FLASH`` env > off.  Off by default because at moderate
    lengths XLA's own fused attention is on par (measured on v5e: flash
    wins ~10-12% at L >= 4096 and removes the O(L^2) score buffer — turn
    it on for long-context configs)."""
    if override is not None:
        return bool(override)
    from distlearn_tpu.utils.flags import env_truthy
    return bool(env_truthy("DISTLEARN_TPU_FLASH"))


def local_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    flash: bool | None = None,
                    impl: str | None = None) -> jax.Array:
    """Single-device attention (same layout as the sharded variants), for
    non-sharded runs and as the per-shard kernel of
    :func:`alltoall_attention`.  q/k/v: [B, L, H, D].

    ``impl`` picks the kernel: ``"xla"`` (naive fused, full [B,H,L,L]
    scores), ``"flash"`` (Pallas blockwise online softmax, no score
    materialization), or ``"chunked"`` (:func:`chunked_causal_attention`
    — causal FLOP skip with saved softmax weights).  Default resolution:
    the ``flash`` arg (back-compat), then the ``DISTLEARN_TPU_ATTN`` env
    var, then ``DISTLEARN_TPU_FLASH``, then xla.

    On the TPU backend a requested kernel that cannot run at this shape
    RAISES, however it was requested: a row labelled "flash" or
    "chunked" must have run that kernel.  Off-TPU (tests, CPU examples)
    an env-requested flash and a non-engaging chunked fall back to xla
    so one setting can cover mixed configs; an explicit flash argument
    raises everywhere."""
    B, L, H, D = q.shape
    explicit_flash = flash is True or impl == "flash"
    if impl is None:
        if flash is not None:
            impl = "flash" if flash else "xla"
        else:
            import os
            impl = os.environ.get("DISTLEARN_TPU_ATTN") \
                or ("flash" if _flash_enabled(None) else "xla")
    if impl not in ("xla", "flash", "chunked"):
        raise ValueError(f"attention impl must be 'xla', 'flash', or "
                         f"'chunked', got {impl!r}")
    on_tpu = jax.default_backend() == "tpu"
    if impl == "chunked":
        chunk = resolve_chunk(L)
        if causal and chunked_engages(L, chunk):
            return chunked_causal_attention(q, k, v, chunk=chunk)
        if on_tpu:
            raise ValueError(
                f"chunked attention cannot run here (causal={causal}, "
                f"L={L}, chunk={chunk}): it needs causal attention with "
                "L > chunk and L % chunk == 0")
        impl = "xla"     # chunking only pays off via the causal FLOP skip
    if impl == "flash":
        # the Pallas kernel's default blocking needs L to be a multiple of
        # its 128-wide blocks
        if on_tpu and L >= 128 and L % 128 == 0:
            from jax.experimental.pallas.ops.tpu.flash_attention import \
                flash_attention
            out = flash_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=causal,
                sm_scale=1.0 / (D ** 0.5))
            return out.transpose(0, 2, 1, 3).astype(q.dtype)
        if explicit_flash or on_tpu:
            # refusing loudly beats silently materializing the O(L^2)
            # buffer the caller asked to avoid
            raise ValueError(
                "flash attention needs the TPU backend and seq len a "
                f"multiple of 128; got backend={jax.default_backend()}, "
                f"L={L}. Drop the explicit flash request to use the "
                "portable path.")
        # env-enabled off-TPU: portable fallback
    scale = 1.0 / (D ** 0.5)
    # native-dtype inputs + f32 ACCUMULATION: on bf16 configs the MXU runs
    # bf16 matmuls accumulating in f32 (upcasting the operands instead
    # would force f32 matmuls — 8x slower on the systolic array — and f32
    # score traffic; for f32 models this is identical math)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        pos = jnp.arange(L)
        scores = jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)   # stays f32 (stable softmax)
    out = jnp.einsum("bhqk,bkhd->bhqd", w.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
