"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

Absent from the reference (single-process forward/backward per node —
SURVEY.md §2c), provided here as a first-class mesh dimension alongside
data/sequence/tensor parallelism: stage parameters are sharded over a
``pipe`` axis (one stage per device), microbatches stream through the
stages, and the inter-stage hop is a neighbor ``ppermute`` riding one ICI
link.  The whole pipeline — all ticks, all stages — is ONE ``lax.scan``
inside one jitted shard_map program, so XLA overlaps each tick's compute
with the neighbor transfer, and ``jax.grad`` through the scan yields the
standard GPipe backward schedule for free (functional autodiff replaces the
hand-written backward pipelines of imperative frameworks).

Schedule: ``M`` microbatches over ``S`` stages take ``M + S - 1`` ticks;
bubble fraction ``(S-1)/(M+S-1)`` — choose ``M >> S`` to amortize.

SPMD shape: every device runs the same program; at tick ``t`` stage 0
ingests microbatch ``t`` (or zeros once input is exhausted) while stages
``1..S-1`` consume the activation ppermuted from their predecessor.  The
last stage's valid outputs are broadcast back to all stages (psum-masked,
like :func:`distlearn_tpu.parallel.mesh.broadcast_from`), keeping the
caller's output replicated over the pipe axis.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


PyTree = Any


def pipeline_apply(stage_fn: Callable, stage_params: PyTree, x: jax.Array,
                   num_microbatches: int, axis_name: str = "pipe",
                   consume_fn: Callable | None = None,
                   unroll: bool | int = False) -> jax.Array:
    """Run ``x`` through ``S`` pipelined stages (``S`` = size of
    ``axis_name``).

    Args:
      stage_fn: ``(params, h) -> h`` — ONE stage's transform.  Must map a
        microbatch ``[mb, ...]`` to the same shape (inter-stage activations
        are homogeneous, the usual pipeline restriction).
      stage_params: THIS device's stage parameters (caller shards a stacked
        ``[S, ...]`` pytree over the pipe axis and squeezes, exactly like
        the per-node state in distlearn_tpu.train).
      x: the full local batch ``[B, ...]`` (replicated over the pipe axis);
        ``B`` must divide into ``num_microbatches`` equal microbatches.
      num_microbatches: GPipe ``M``; bubble = (S-1)/(M+S-1).
      consume_fn: optional ``(out_mb, mb_index) -> scalar`` folding each
        microbatch's LAST-stage output (e.g. its loss share) as it emerges
        from the pipeline.  SPMD caveat: it executes every tick on every
        rank (same program everywhere); only the last rank's valid ticks
        are accumulated — the rest are masked to zero, so no gradient
        flows from them.
      unroll: forwarded to the tick ``lax.scan``.  ``True`` inlines all
        ``T = M+S-1`` ticks so XLA can fuse and overlap across tick
        boundaries, at the cost of a ~T-times-larger program and a
        longer compile; off by default (no pipeline step has been
        measured on the chip: ROADMAP R5).

    Returns:
      Without ``consume_fn``: ``[B, ...]`` outputs of the LAST stage,
      replicated over the pipe axis (differentiable end to end).
      With ``consume_fn``: the LOCAL share of ``Σ_mb consume_fn(out_mb,
      mb)`` — nonzero only on the last rank; ``lax.psum`` it over
      ``axis_name`` *outside* the differentiated region (psum transposes
      to psum under shard_map).  This path never materializes the
      ``[T, mb, ...]`` output stack and skips the output broadcast — the
      scalar psum replaces a full [B, ...] collective.
    """
    S = lax.psum(1, axis_name)          # static under shard_map
    idx = lax.axis_index(axis_name)
    B = x.shape[0]
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible into {M} microbatches")
    mb = B // M
    mbs = x.reshape((M, mb) + x.shape[1:])
    T = M + S - 1

    # Probe the stage output type (abstract — no FLOPs run): the scan carry
    # must be well-typed, and pipelining requires homogeneous activations.
    out_aval = jax.eval_shape(stage_fn, stage_params, mbs[0])
    if out_aval.shape != mbs[0].shape:
        raise ValueError(
            f"stage_fn must preserve activation shape (got {mbs[0].shape} "
            f"-> {out_aval.shape}); wrap in/out projections around the "
            "pipeline, not inside it")
    zeros_state = jnp.zeros(out_aval.shape, out_aval.dtype)

    fwd_perm = [(j, j + 1) for j in range(S - 1)]   # no wraparound

    def ingest(state, t):
        # stage 0 ingests microbatch t (zeros once exhausted); others take
        # the activation their predecessor ppermuted last tick
        feed = lax.dynamic_index_in_dim(mbs, jnp.minimum(t, M - 1), 0,
                                        keepdims=False)
        feed = jnp.where(t < M, feed, jnp.zeros_like(feed))
        return jnp.where(idx == 0, feed.astype(zeros_state.dtype), state)

    if consume_fn is not None:
        def tick(carry, t):
            state, acc = carry
            out = stage_fn(stage_params, ingest(state, t))
            m = t - (S - 1)          # microbatch index emerging this tick
            val = consume_fn(out, jnp.maximum(m, 0))
            acc = acc + jnp.where((idx == S - 1) & (m >= 0), val,
                                  jnp.zeros_like(val))
            return (lax.ppermute(out, axis_name, fwd_perm), acc), None

        (_, acc), _ = lax.scan(tick, (zeros_state,
                                      jnp.zeros((), jnp.float32)),
                               jnp.arange(T), unroll=unroll)
        return acc

    def tick(state, t):
        out = stage_fn(stage_params, ingest(state, t))
        nxt = lax.ppermute(out, axis_name, fwd_perm)
        return nxt, out

    _, outs = lax.scan(tick, zeros_state, jnp.arange(T),
                       unroll=unroll)                      # [T, mb, ...]

    # The last stage's outputs at ticks S-1 .. T-1 are microbatches 0..M-1.
    valid = lax.dynamic_slice_in_dim(outs, S - 1, M, axis=0)
    y = valid.reshape((B,) + valid.shape[2:])
    # broadcast from the last stage so every device returns the result
    from distlearn_tpu.parallel.mesh import broadcast_from
    return broadcast_from(y, S - 1, axis_name)


def pipeline_1f1b(stage_fn: Callable, stage_params: PyTree,
                  consume_fn: Callable, consume_params: PyTree,
                  x: jax.Array, num_microbatches: int,
                  axis_name: str = "pipe"):
    """One-forward-one-backward pipeline schedule, gradients included.

    :func:`pipeline_apply` + ``jax.grad`` IS GPipe: all M forwards run
    before any backward, so the autodiff residuals of every in-flight
    microbatch stay live — activation memory O(M).  This function runs
    the 1F1B schedule instead: each microbatch's backward starts as soon
    as it leaves the last stage, so at most ``2(S-1)+1`` microbatch
    INPUTS are ever held per stage — activation memory O(S), the reason
    1F1B is the production schedule when M >> S.  The price: gradients
    are computed manually (``jax.vjp`` per tick) rather than by
    differentiating through the forward scan, so this function RETURNS
    gradients and cannot itself sit under ``jax.grad``.

    Schedule (SPMD — every rank runs the same T-tick scan, masked by its
    ``axis_name`` index): tick ``t`` runs the GPipe forward for
    microbatch ``t - idx`` AND the backward for microbatch
    ``t - 2(S-1) + idx``; the last stage seeds its own cotangent from
    ``consume_fn``'s vjp in the same tick its forward emerges, and
    cotangents ride a backward neighbor ppermute.  Total ticks
    ``T = M + 2S - 2`` (vs GPipe's ``M + S - 1`` forward ticks plus the
    reversed backward scan — same compute, same bubble fraction).  The
    per-tick backward re-runs the stage forward inside ``jax.vjp``
    (recompute-from-stage-input), matching the memory/FLOP trade of
    ``remat=True`` GPipe.

    Args:
      stage_fn: ``(stage_params, h) -> h`` — shape-preserving, as in
        :func:`pipeline_apply`.
      consume_fn: ``(consume_params, out_mb, mb_index) -> scalar`` — the
        last-stage loss share (e.g. this microbatch's share of the
        global-mean NLL).  Unlike :func:`pipeline_apply`'s ``consume_fn``
        it takes its parameters EXPLICITLY, because their gradient must
        be returned (a closure would silently drop it).
      consume_params: pytree of parameters consumed by ``consume_fn``.
      x: ``[B, ...]`` input ACTIVATIONS (already embedded), replicated
        over the pipe axis.
      num_microbatches: M; ``B`` must divide evenly.

    Returns ``(local_share, g_stage_params, g_consume_params, g_x)``:
    the loss share (nonzero only on the last rank — psum it), this
    stage's parameter gradients, ``consume_fn``'s parameter gradients
    (nonzero only on the last rank — psum over pipe reassembles), and
    the gradient w.r.t. ``x`` (nonzero only on rank 0; backprop it
    through the embedding outside).
    """
    S = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B = x.shape[0]
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible into {M} microbatches")
    mb = B // M
    mbs = x.reshape((M, mb) + x.shape[1:])
    T = M + 2 * S - 2
    BUF = 2 * S - 1            # max in-flight saved inputs per stage

    out_aval = jax.eval_shape(stage_fn, stage_params, mbs[0])
    if out_aval.shape != mbs[0].shape:
        raise ValueError(
            f"stage_fn must preserve activation shape (got {mbs[0].shape} "
            f"-> {out_aval.shape})")
    act_dtype = out_aval.dtype
    zeros_act = jnp.zeros(out_aval.shape, act_dtype)

    fwd_perm = [(j, j + 1) for j in range(S - 1)]
    bwd_perm = [(j, j - 1) for j in range(1, S)]
    zf32 = jnp.zeros((), jnp.float32)

    def tick(carry, t):
        fwd_in, buf, cot_in, g_stage, g_cons, gx, share = carry

        # ---- forward half: GPipe ingest + stage forward -------------------
        m_f = t - idx                      # this stage's fwd microbatch
        fwd_valid = (m_f >= 0) & (m_f < M)
        feed = lax.dynamic_index_in_dim(mbs, jnp.clip(m_f, 0, M - 1), 0,
                                        keepdims=False)
        a_in = jnp.where(idx == 0, feed.astype(act_dtype), fwd_in)
        out = stage_fn(stage_params, a_in)
        buf = lax.dynamic_update_index_in_dim(buf, a_in, t % BUF, 0)

        # last stage: fold the loss share and seed the cotangent for this
        # SAME microbatch's backward, which runs this very tick.  The head
        # vjp (vocab-sized logits matmul + log-softmax + backward) is S
        # times the necessary compute if every stage runs it only to mask
        # the result — consume_fn contains no collectives, so lax.cond
        # genuinely skips it on all ranks but the live last stage.
        def cons(cp, o):
            return consume_fn(cp, o, jnp.clip(m_f, 0, M - 1))

        last_live = (idx == S - 1) & fwd_valid

        def head_live(cp, o):
            val, cvjp = jax.vjp(cons, cp, o)
            g_cp_t, seed = cvjp(jnp.ones((), val.dtype))
            return val.astype(jnp.float32), g_cp_t, seed.astype(act_dtype)

        def head_skip(cp, o):
            return (zf32, jax.tree_util.tree_map(jnp.zeros_like, cp),
                    jnp.zeros(o.shape, act_dtype))

        val, g_cp_t, seed = lax.cond(last_live, head_live, head_skip,
                                     consume_params, out)
        share = share + val
        g_cons = jax.tree_util.tree_map(lambda a, g: a + g, g_cons, g_cp_t)

        # ---- backward half: 1F1B interleave -------------------------------
        m_b = t - (2 * S - 2) + idx        # this stage's bwd microbatch
        bwd_valid = (m_b >= 0) & (m_b < M)
        cot = jnp.where(idx == S - 1, seed.astype(act_dtype),
                        cot_in.astype(act_dtype))
        # its input was saved at tick m_b + idx
        slot = jnp.clip(m_b + idx, 0, T - 1) % BUF
        a_saved = lax.dynamic_index_in_dim(buf, slot, 0, keepdims=False)
        _, svjp = jax.vjp(stage_fn, stage_params, a_saved)
        g_p_t, g_in = svjp(cot)
        g_stage = jax.tree_util.tree_map(
            lambda a, g: a + jnp.where(bwd_valid, g, jnp.zeros_like(g)),
            g_stage, g_p_t)
        # rank 0's input-gradient is the embedding cotangent for mb m_b
        gx_upd = lax.dynamic_update_index_in_dim(
            gx, g_in.astype(gx.dtype), jnp.clip(m_b, 0, M - 1), 0)
        gx = jnp.where((idx == 0) & bwd_valid, gx_upd, gx)

        # ---- neighbor exchanges for the next tick -------------------------
        fwd_nxt = lax.ppermute(out, axis_name, fwd_perm)
        cot_nxt = lax.ppermute(g_in, axis_name, bwd_perm)
        return (fwd_nxt, buf, cot_nxt, g_stage, g_cons, gx, share), None

    init = (zeros_act,
            jnp.zeros((BUF,) + out_aval.shape, act_dtype),
            zeros_act,
            jax.tree_util.tree_map(jnp.zeros_like, stage_params),
            jax.tree_util.tree_map(jnp.zeros_like, consume_params),
            jnp.zeros(mbs.shape, x.dtype),
            zf32)
    (_, _, _, g_stage, g_cons, gx, share), _ = lax.scan(
        tick, init, jnp.arange(T))
    return share, g_stage, g_cons, gx.reshape((B,) + x.shape[1:])
