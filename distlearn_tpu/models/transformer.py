"""Decoder-only transformer LM — the long-context model family.

Not in the reference (CNN classifiers only — SURVEY.md §2c), but first-class
here: the attention runs as ring attention over a sequence mesh axis
(distlearn_tpu.parallel.sequence) and the MLP/attention projections support
tensor parallelism over a model mesh axis, so one model spans
(data, seq, model) meshes.

Sharding convention (inside ``shard_map``): ``apply`` receives LOCAL param
shards.  With ``tp_axis`` set, the caller shards

* ``wq/wk/wv``:   [E, H, D]  → heads split over tp   (spec P(None, tp))
* ``wo``:         [H, D, E]  → heads split over tp   (spec P(tp))
* ``mlp/w1,b1``:  [E, F], [F] → F split over tp      (spec P(None, tp) / P(tp))
* ``mlp/w2``:     [F, E]    → F split over tp        (spec P(tp))

and ``apply`` inserts the one ``psum`` per block that TP requires (after
``wo`` and ``w2`` — the Megatron pattern: column-parallel then row-parallel).
:func:`param_specs` produces exactly these PartitionSpecs for a param pytree.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, random

from jax.sharding import PartitionSpec as P

from distlearn_tpu.models.core import (Model, checkpoint_block,
                                       scan_reducing)
from distlearn_tpu.parallel.sequence import (alltoall_attention,
                                             local_attention, ring_attention)
from distlearn_tpu.parallel.tp import tp_enter, tp_reduce

PyTree = Any


def _norm_init(shape, dtype):
    return {"scale": jnp.ones(shape, dtype)}


def _rmsnorm(params, x, eps=1e-6):
    with jax.named_scope("norm"):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        y = x * lax.rsqrt(var + eps).astype(x.dtype)
        return y * params["scale"].astype(x.dtype)


def attn_qkv(blk: PyTree, x: jax.Array, cd, tp_axis: str | None = None):
    """Pre-norm + q/k/v projections of one block — the ONE home of the
    projection math, shared by :func:`attn_apply` (training forward) and
    :func:`greedy_generate` (prefill + per-tick decode), so a future
    change (bias terms, RoPE, QK-norm) cannot silently diverge between
    training and generation."""
    h = _rmsnorm(blk["ln1"], x)
    with jax.named_scope("attn_proj"):
        if tp_axis is not None:   # enter column-parallel region ("f")
            h = tp_enter(h, tp_axis)
        q = jnp.einsum("ble,ehd->blhd", h, blk["wq"].astype(cd))
        k = jnp.einsum("ble,ehd->blhd", h, blk["wk"].astype(cd))
        v = jnp.einsum("ble,ehd->blhd", h, blk["wv"].astype(cd))
    return q, k, v


#: how :func:`rotary` pairs the dimensions of a head
ROPE_PAIRINGS = ("half", "interleaved")


def rotary(x: jax.Array, positions: jax.Array, theta: float,
           pairing: str = "half") -> jax.Array:
    """Rotary position embedding of ``x`` [B, L, H, D] at ``positions``
    [L] over the whole of ``x``'s last axis (a caller that rotates a slice
    of a head hands that slice): pair ``d`` of the ``D/2`` is turned by
    ``positions * theta ** (-2 d / D)``.  ``pairing`` (:data:`ROPE_PAIRINGS`)
    says which two dimensions pair ``d`` is: ``"half"`` — ``(d, d + D/2)``
    ("rotate half", the default) — or ``"interleaved"`` — ``(2d, 2d + 1)``,
    neighbours, the result in the same places (a head as complex numbers
    ``x[2d] + i x[2d+1]``, each times ``exp(i angle_d)``).  The angles,
    their sines and the rotation are float32 whatever ``x`` is (at position
    16k a bfloat16 angle is off by whole turns); the result is rounded to
    ``x``'s dtype once.  Applied to q and k before the attention kernel, the
    scores depend on ``i - j`` alone.  Callers put it inside their
    ``attn_proj`` scope; the inner name ``rope`` is what a profile finds it
    by."""
    half = x.shape[-1] // 2
    if x.shape[-1] != 2 * half:
        raise ValueError(f"rotary needs an even head size, got {x.shape[-1]}")
    if pairing not in ROPE_PAIRINGS:
        raise ValueError(f"pairing must be one of {ROPE_PAIRINGS}, got "
                         f"{pairing!r}")
    with jax.named_scope("rope"):
        freq = jnp.float32(theta) ** (
            jnp.arange(half, dtype=jnp.float32) * (-1.0 / half))
        angle = positions.astype(jnp.float32)[:, None, None] * freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)           # [L, 1, D/2]
        x32 = x.astype(jnp.float32)
        if pairing == "interleaved":
            a, b = x32[..., 0::2], x32[..., 1::2]
            return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                             axis=-1).reshape(x.shape).astype(x.dtype)
        a, b = x32[..., :half], x32[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1).astype(x.dtype)


def attn_out(blk: PyTree, x: jax.Array, att: jax.Array, cd,
             tp_axis: str | None = None) -> jax.Array:
    """Output projection + residual (the other half shared with the
    decoder)."""
    with jax.named_scope("attn_proj"):
        proj = jnp.einsum("blhd,hde->ble", att, blk["wo"].astype(cd))
        if tp_axis is not None:   # heads were sharded: reduce ("g")
            proj = tp_reduce(proj, tp_axis)
        return x + proj


def attn_apply(blk: PyTree, x: jax.Array, cd, *, seq_attn=None,
               seq_axis: str | None = None, tp_axis: str | None = None,
               attn_impl: str | None = None):
    """Attention half of a transformer block (pre-norm attention residual)
    on a LOCAL param shard — split out of :func:`block_apply` so the
    selective-remat mode can checkpoint the FFN half alone (saving the
    attention output and the blockwise kernel's softmax residuals instead of
    re-running the attention forward in the backward pass)."""
    q, k, v = attn_qkv(blk, x, cd, tp_axis)
    with jax.named_scope("attn_core"):
        if seq_axis is not None:
            att = seq_attn(q, k, v, seq_axis, causal=True, impl=attn_impl)
        else:
            att = local_attention(q, k, v, causal=True, impl=attn_impl)
    return attn_out(blk, x, att, cd, tp_axis)


def ffn_apply(blk: PyTree, x: jax.Array, cd, *, tp_axis: str | None = None,
              ep_axis: str | None = None,
              moe_capacity_factor: float = 1.25, moe_top_k: int = 1,
              return_moe_aux: bool = False):
    """FFN/MoE half of a transformer block (see :func:`attn_apply`)."""
    h = _rmsnorm(blk["ln2"], x)
    with jax.named_scope("mlp"):
        if "router" in blk:       # routed MoE FFN (parallel/ep.py)
            from distlearn_tpu.parallel.ep import moe_ffn, moe_ffn_local

            Bq, Lq, Dq = h.shape
            flat = h.reshape(Bq * Lq, Dq)

            def expert(p, t):
                u = jax.nn.gelu(t @ p["we1"].astype(cd)
                                + p["wb1"].astype(cd))
                return u @ p["we2"].astype(cd)

            eparams = {k2: blk[k2] for k2 in ("we1", "wb1", "we2")}
            if ep_axis is None:
                y = moe_ffn_local(expert, eparams, blk["router"], flat,
                                  moe_capacity_factor, top_k=moe_top_k,
                                  return_aux=return_moe_aux)
            else:                 # one expert per device on ep_axis
                n_local = blk["we1"].shape[0]
                if n_local != 1:
                    raise ValueError(
                        f"stacked expert leaves hold {n_local} shards on "
                        "this device; expected exactly one per device on "
                        "ep_axis")
                local = jax.tree_util.tree_map(
                    lambda a: jnp.squeeze(a, 0), eparams)
                y = moe_ffn(expert, local, blk["router"], flat,
                            moe_capacity_factor, axis_name=ep_axis,
                            top_k=moe_top_k, return_aux=return_moe_aux)
            if return_moe_aux:
                y, aux = y
                return x + y.reshape(Bq, Lq, Dq).astype(x.dtype), aux
            return x + y.reshape(Bq, Lq, Dq).astype(x.dtype)
        if return_moe_aux:
            raise ValueError(
                "return_moe_aux=True on a dense block (no router)")
        if tp_axis is not None:
            h = tp_enter(h, tp_axis)
        h = h @ blk["w1"].astype(cd) + blk["b1"].astype(cd)
        h = jax.nn.gelu(h)
        h = h @ blk["w2"].astype(cd)
        if tp_axis is not None:   # hidden was sharded: reduce ("g")
            h = tp_reduce(h, tp_axis)
        return x + h + blk["b2"].astype(cd)


def block_apply(blk: PyTree, x: jax.Array, cd, *, seq_attn=None,
                seq_axis: str | None = None, tp_axis: str | None = None,
                ep_axis: str | None = None,
                moe_capacity_factor: float = 1.25, moe_top_k: int = 1,
                return_moe_aux: bool = False,
                attn_impl: str | None = None):
    """One transformer block (pre-norm attention + FFN/MoE residuals) on a
    LOCAL param shard — the single source of truth for the block math,
    shared by :func:`transformer_lm`'s apply and the pipeline-parallel
    stage fn (distlearn_tpu.train.lm.build_lm_pp_step).  ``cd`` is the
    compute dtype; axes as in :func:`transformer_lm`.

    ``return_moe_aux=True`` (MoE blocks only) returns ``(x, aux)`` with
    the routing-health dict from :func:`distlearn_tpu.parallel.ep
    .route_topk` (balance loss + dropped fraction) — an explicit output,
    not a side channel, so it survives ``jax.checkpoint``."""
    x = attn_apply(blk, x, cd, seq_attn=seq_attn, seq_axis=seq_axis,
                   tp_axis=tp_axis, attn_impl=attn_impl)
    return ffn_apply(blk, x, cd, tp_axis=tp_axis, ep_axis=ep_axis,
                     moe_capacity_factor=moe_capacity_factor,
                     moe_top_k=moe_top_k, return_moe_aux=return_moe_aux)


def _pos_rows(pos, L: int, seq_axis: str | None, seq_layout: str):
    """The ``L`` rows of the position table THIS sequence shard holds."""
    if seq_axis is None:
        return lax.dynamic_slice_in_dim(pos, 0, L)
    my = lax.axis_index(seq_axis)
    if seq_layout != "zigzag":
        return lax.dynamic_slice_in_dim(pos, my * L, L)
    # local shard = early stripe my ++ late stripe 2n-1-my
    n_sh = lax.axis_size(seq_axis)
    s_len = L // 2
    pa = lax.dynamic_slice_in_dim(pos, my * s_len, s_len)
    pb = lax.dynamic_slice_in_dim(pos, (2 * n_sh - 1 - my) * s_len, s_len)
    return jnp.concatenate([pa, pb], axis=0)


def transformer_lm(vocab: int = 256, dim: int = 128, depth: int = 2,
                   heads: int = 4, mlp_ratio: int = 4, max_len: int = 2048,
                   dtype=jnp.float32, compute_dtype=None,
                   seq_impl: str = "ring", remat: bool = False,
                   attn_impl: str | None = None, scan_blocks: bool = False,
                   moe_experts: int = 0, moe_every: int = 2,
                   moe_capacity_factor: float = 1.25,
                   moe_top_k: int = 1) -> Model:
    """Returns a :class:`Model` whose ``apply(params, state, tokens, ...)``
    maps int tokens [B, L_local] -> next-token logits [B, L_local, vocab].

    ``axis_name`` (data axis) is unused here; sequence and tensor axes are
    passed per-call via ``seq_axis`` / ``tp_axis`` keywords.  ``seq_impl``
    picks the sequence-parallel attention: ``"ring"`` (neighbor-hop K/V
    rotation, unbounded L) or ``"alltoall"`` (Ulysses head-scatter — needs
    heads divisible by the seq axis and the full score block in memory).
    ``attn_impl`` forces the single-device attention path
    (``"xla"``/``"splash"`` — see
    :func:`distlearn_tpu.parallel.sequence.local_attention`; None = chosen
    from the call's shape, dtype and backend).  It applies whenever the
    attention runs locally: no ``seq_axis``, or a size-1 sequence axis.
    With a real (>1) sequence axis the ring/all-to-all blockwise math takes
    over and the argument is inert — see
    :func:`distlearn_tpu.parallel.sequence.ring_attention` for why (and for
    the zigzag layout that does the causal FLOP cut there).

    ``remat=True`` (= ``"full"``) makes each block one checkpoint
    (:func:`distlearn_tpu.models.core.checkpoint_block`): its activations
    are recomputed in the backward pass instead of saved — HBM drops from
    O(depth * L * dim) to O(L * dim) at ~1/3 extra FLOPs, the standard
    trade for long-context/deep configs.  A block's checkpoint holds the
    block's input and, where the blockwise attention kernel runs, that
    kernel's output and log-sum-exp — the first the size of the input
    (``[B, L, dim]`` in the compute dtype; twice that on the chip where a
    64-wide head is padded to the 128 lanes), the second ``[B, H, L]``
    float32 — because they are all the kernel's backward call lacks:
    without them the backward pass runs the whole forward kernel again.  At
    the memory limit that is one more ``[tokens, dim]`` array a layer
    beside the one already held, the price of every recipe that
    checkpoints around a flash kernel and not a setting; on the
    full-square path nothing more than the input is held.  ``remat="mlp"``
    is the selective middle ground (Megatron-style selective activation
    recomputation): only the FFN half of each block is checkpointed, so
    the attention output AND the blockwise kernel's softmax residuals stay
    saved — the backward pass never re-runs the attention forward, at the
    cost of keeping O(L * dim) attention activations per block live.

    ``scan_blocks=True`` stores the per-block parameters STACKED on a
    leading ``[depth]`` axis (``params["blocks"]``) and runs the depth
    loop as one ``lax.scan`` — the program (and its compile time) no
    longer grows with depth.
    Identical math to the unrolled layout (tested); convert between
    layouts with :func:`stack_block_params` / :func:`unstack_block_params`.
    Requires a homogeneous dense stack (no MoE blocks — their routed
    leaves are a different pytree shape).  ``apply(...,
    grad_reduce_axis=name)`` on a scanned stack makes the loop
    :func:`distlearn_tpu.models.core.scan_reducing`: the gradient of
    ``params["blocks"]`` then comes out of the backward pass summed over
    that mesh axis, layer by layer behind the loop's own work, and its
    caller must not sum it again (``train/lm.py::build_lm_step`` decides;
    nothing else passes it).

    ``moe_experts=E`` makes every ``moe_every``-th block's FFN a routed
    top-``moe_top_k`` mixture of ``E`` experts (parallel/ep.py; k=1 is
    Switch, k=2 GShard).  Pass ``ep_axis`` to ``apply`` to shard the
    experts one-per-device over that mesh axis (requires ``E == axis
    size``; the data axis is the usual choice — EP group == DP group);
    with ``ep_axis=None`` all experts run locally.  MoE blocks bypass
    tensor parallelism (their parallelism IS the expert axis); the router
    stays replicated so routing is identical everywhere.

    MoE models return routing-health metrics through the state output:
    ``apply`` yields ``(logits, {"moe_balance_loss", "moe_dropped_frac"})``
    — the mean Switch balance loss and dropped-assignment fraction over
    the MoE blocks.  :func:`lm_loss` folds the balance term into the
    training loss with ``moe_balance_weight`` (the Switch §2.2 auxiliary:
    without it, top-1 routing collapses onto a few experts).
    """
    if seq_impl not in ("ring", "alltoall"):
        raise ValueError(f"seq_impl must be 'ring' or 'alltoall', "
                         f"got {seq_impl!r}")
    if isinstance(remat, str):
        if remat not in ("full", "mlp"):
            raise ValueError(f"remat must be False, True/'full', or 'mlp', "
                             f"got {remat!r}")
    else:
        # any truthy non-string (True, 1, ...) means full remat — int-ish
        # config flags must not silently disable checkpointing
        remat = "full" if remat else False
    if moe_experts < 0 or (moe_experts > 0 and moe_every < 1):
        raise ValueError(f"moe_experts must be >= 0 and moe_every >= 1, "
                         f"got {moe_experts}/{moe_every}")
    if moe_experts > 0 and moe_every > depth:
        raise ValueError(
            f"moe_every={moe_every} > depth={depth}: no block would be MoE "
            f"— the requested {moe_experts}-expert model would silently "
            "train dense")
    if moe_experts > 0 and not 1 <= moe_top_k <= moe_experts:
        raise ValueError(f"moe_top_k={moe_top_k} must be in "
                         f"[1, moe_experts={moe_experts}]")
    if scan_blocks and moe_experts:
        raise ValueError(
            "scan_blocks needs a homogeneous dense stack: MoE blocks hold "
            "routed expert leaves the dense blocks lack, so they cannot "
            "ride one lax.scan — drop scan_blocks or moe_experts")
    seq_attn = ring_attention if seq_impl == "ring" else alltoall_attention

    def _is_moe(i: int) -> bool:
        return moe_experts > 0 and (i % moe_every) == moe_every - 1
    head_dim = dim // heads
    hidden = dim * mlp_ratio
    cd = compute_dtype or dtype

    def init(key):
        keys = iter(random.split(key, 4 + depth * 8))
        scale = 1.0 / math.sqrt(dim)
        params = {
            "embed": random.normal(next(keys), (vocab, dim), dtype) * scale,
            "pos": random.normal(next(keys), (max_len, dim), dtype) * scale,
            "out_norm": _norm_init((dim,), dtype),
        }
        for i in range(depth):
            blk = {
                "ln1": _norm_init((dim,), dtype),
                "wq": random.normal(next(keys), (dim, heads, head_dim), dtype) * scale,
                "wk": random.normal(next(keys), (dim, heads, head_dim), dtype) * scale,
                "wv": random.normal(next(keys), (dim, heads, head_dim), dtype) * scale,
                "wo": random.normal(next(keys), (heads, head_dim, dim), dtype) * scale,
                "ln2": _norm_init((dim,), dtype),
            }
            if _is_moe(i):
                E = moe_experts
                blk["router"] = random.normal(next(keys), (dim, E),
                                              dtype) * scale
                blk["we1"] = random.normal(next(keys), (E, dim, hidden),
                                           dtype) * scale
                blk["wb1"] = jnp.zeros((E, hidden), dtype)
                blk["we2"] = random.normal(next(keys), (E, hidden, dim),
                                           dtype) * (1.0 / math.sqrt(hidden))
            else:
                blk["w1"] = random.normal(next(keys), (dim, hidden),
                                          dtype) * scale
                blk["b1"] = jnp.zeros((hidden,), dtype)
                blk["w2"] = random.normal(next(keys), (hidden, dim), dtype) \
                    * (1.0 / math.sqrt(hidden))
                blk["b2"] = jnp.zeros((dim,), dtype)
            params[f"block{i}"] = blk
        if scan_blocks:
            return stack_block_params(params, depth), {}
        return params, {}

    def apply(params, state, tokens, train=True, rng=None, axis_name=None,
              bn_weight=None, seq_axis=None, tp_axis=None, ep_axis=None,
              seq_layout="contig", grad_reduce_axis=None):
        B, L = tokens.shape
        sa = seq_attn
        if seq_layout not in ("contig", "zigzag"):
            raise ValueError(f"seq_layout must be 'contig' or 'zigzag', "
                             f"got {seq_layout!r}")
        if seq_layout == "zigzag":
            if seq_axis is None:
                raise ValueError(
                    "seq_layout='zigzag' without a sequence axis: the "
                    "layout permutes data across shards — drop it for "
                    "single-shard runs")
            if seq_impl != "ring":
                raise ValueError(
                    "seq_layout='zigzag' needs seq_impl='ring' (the "
                    "all-to-all path applies its causal mask in natural "
                    "order)")
            import functools
            sa = functools.partial(seq_attn, layout="zigzag")
        with jax.named_scope("embed"):
            pos_emb = _pos_rows(params["pos"], L, seq_axis, seq_layout)
            x = params["embed"][tokens].astype(cd)
            x = x + pos_emb.astype(cd)[None]

        def make_block(is_moe):
            if remat == "mlp":
                # selective: attention residuals saved, FFN recomputed
                def ffn(blk, x):
                    return ffn_apply(blk, x, cd, tp_axis=tp_axis,
                                     ep_axis=ep_axis,
                                     moe_capacity_factor=moe_capacity_factor,
                                     moe_top_k=moe_top_k,
                                     return_moe_aux=is_moe)
                ffn_ckpt = jax.checkpoint(ffn)

                def block(blk, x):
                    x = attn_apply(blk, x, cd, seq_attn=sa,
                                   seq_axis=seq_axis, tp_axis=tp_axis,
                                   attn_impl=attn_impl)
                    return ffn_ckpt(blk, x)
                return block

            def block(blk, x):
                return block_apply(blk, x, cd, seq_attn=sa,
                                   seq_axis=seq_axis, tp_axis=tp_axis,
                                   ep_axis=ep_axis,
                                   moe_capacity_factor=moe_capacity_factor,
                                   moe_top_k=moe_top_k,
                                   return_moe_aux=is_moe,
                                   attn_impl=attn_impl)
            return checkpoint_block(block) if remat == "full" else block

        # ONE wrapper per block kind, reused across the depth loop: a fresh
        # checkpoint closure per block stops XLA deduplicating the remat
        # computation; sharing restores it
        blk_dense = make_block(False)
        blk_moe = make_block(True) if moe_experts > 0 else None

        balance = dropped = n_moe = 0
        if scan_blocks and grad_reduce_axis is not None:
            x = scan_reducing(blk_dense, x, params["blocks"],
                              grad_reduce_axis)
        elif scan_blocks:
            x, _ = lax.scan(lambda h, blk: (blk_dense(blk, h), None),
                            x, params["blocks"])
        else:
            for i in range(depth):
                if _is_moe(i):
                    x, aux = blk_moe(params[f"block{i}"], x)
                    balance = balance + aux["balance_loss"]
                    dropped = dropped + aux["dropped_frac"]
                    n_moe += 1
                else:
                    x = blk_dense(params[f"block{i}"], x)
        if n_moe:
            state = dict(state, moe_balance_loss=balance / n_moe,
                         moe_dropped_frac=dropped / n_moe)

        x = _rmsnorm(params["out_norm"], x)
        with jax.named_scope("head_loss"):
            logits = (x @ params["embed"].T.astype(cd)).astype(dtype)
        return logits, state

    return Model(init=init, apply=apply, name="transformer_lm",
                 input_shape=(max_len,), num_classes=vocab)


def decode_attend(q: jax.Array, ck: jax.Array, cv: jax.Array,
                  live: jax.Array, cd) -> jax.Array:
    """One decode tick's cached attention: ``[B,1,H,D]`` query against the
    ``[B,T,H,D]`` K/V cache under the boolean ``live`` mask (broadcastable
    to ``[B,H,1,T]``; dead cache positions score ``-inf``).  The ONE home
    of the cached-attention math, shared by :func:`greedy_generate` and
    the slot-addressed serving engine (``distlearn_tpu.serve.engine``) —
    token parity between the two is a tested invariant, so the math must
    not fork."""
    D = q.shape[-1]
    with jax.named_scope("attn_core"):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, ck,
                       preferred_element_type=jnp.float32)
        s = s * (1.0 / (D ** 0.5))
        s = jnp.where(live, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w.astype(cd), cv)


def generate_params(params: PyTree) -> tuple[PyTree, int]:
    """Normalize a :func:`transformer_lm` tree for decoding: unstack the
    scanned layout, reject MoE blocks (per-tick routing would compute
    expert capacity over one token — a different model than the one
    trained), and return ``(per_block_params, depth)``.  Shared by
    :func:`greedy_generate` and the serving engine."""
    # numpy trees (checkpoint loads, device_get'd sharded params) are
    # legal input; the decode scan closes over the leaves, and a numpy
    # leaf indexed by a tracer inside the scan body fails to trace.
    params = jax.tree_util.tree_map(jnp.asarray, params)
    if "blocks" in params:
        d = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
        params = unstack_block_params(params, d)
    depth = sum(1 for k in params if k.startswith("block"))
    for i in range(depth):
        if "router" in params[f"block{i}"]:
            raise ValueError(
                "greedy decoding supports dense blocks only: per-tick "
                "MoE routing computes capacity over ONE token, not the "
                "batch the router trained with (block"
                f"{i} has a router)")
    return params, depth


def greedy_generate(params: PyTree, tokens: jax.Array, steps: int,
                    compute_dtype=None,
                    attn_impl: str | None = None,
                    prompt_lens: jax.Array | None = None) -> jax.Array:
    """KV-cached greedy decoding for a :func:`transformer_lm` parameter
    tree (per-block layout): ``[B, P]`` prompt -> ``[B, steps]``
    generated ids.

    The training stack is forward/backward only (the reference is a
    training framework); this is the inference half of the LM family —
    one prefill pass caches every block's K/V (same math as
    :func:`attn_apply`, with the projections exposed so the cache can be
    captured), then a ``lax.scan`` emits one token per tick: each tick
    computes ONE position's q/k/v, appends to the cache with a
    ``dynamic_update_slice``, and attends over the cache under a static
    position mask — static shapes throughout, so the whole decode is one
    compiled program (no per-token retrace, no O(T^2) recompute of the
    naive re-run-the-prefix rollout).  DENSE blocks only: per-tick MoE
    routing would compute expert capacity over one token instead of the
    full batch×length the model trained with — a different model, so it
    is rejected rather than silently approximated.  Scanned-layout trees
    (``"blocks"``) are unstacked automatically.  ``attn_impl`` should
    match the model's kernel (float-level kernel differences can flip
    argmax at near-tie logits).  Greedy (argmax) sampling.

    ``prompt_lens`` (``[B]`` ints) lifts the equal-length restriction:
    row ``b`` holds ``prompt_lens[b]`` real tokens LEFT-padded to ``P``
    (pad ids are arbitrary — they are masked out of the attention and
    get position 0's embedding).  Left padding keeps the decode loop
    uniform: every row's last prompt token sits at column ``P-1``, so
    the first generated position is column ``P`` for all rows and each
    row's logical positions are ``column - (P - prompt_lens[b])``.
    ``prompt_lens=None`` is the original equal-length path, bit-for-bit
    unchanged (tested).

    Equivalence to the no-cache rollout is tested
    (tests/test_transformer.py).
    """
    params, depth = generate_params(params)
    cd = compute_dtype or params["embed"].dtype
    B, P = tokens.shape
    T = P + steps
    if T > params["pos"].shape[0]:
        raise ValueError(f"prompt + steps = {T} exceeds max_len "
                         f"{params['pos'].shape[0]}")
    if prompt_lens is not None:
        plens = jnp.asarray(prompt_lens, jnp.int32).reshape(B)
        pad = (P - plens)[:, None]                 # [B,1] left-pad widths

    # ---- prefill: full causal pass, caches seeded with the prompt K/V
    if prompt_lens is None:
        x = params["embed"][tokens].astype(cd)
        x = x + params["pos"][:P].astype(cd)[None]
    else:
        # logical position of column j in row b: j - pad_b (pads clamp to
        # 0 — they never contribute: masked out of every attention below)
        pos_idx = jnp.maximum(jnp.arange(P)[None, :] - pad, 0)   # [B,P]
        x = params["embed"][tokens].astype(cd)
        x = x + params["pos"][pos_idx].astype(cd)
    caches = []
    for i in range(depth):
        blk = params[f"block{i}"]
        q, k, v = attn_qkv(blk, x, cd)
        ck = jnp.zeros((B, T) + k.shape[2:], k.dtype)
        cv = jnp.zeros((B, T) + v.shape[2:], v.dtype)
        caches.append((lax.dynamic_update_slice_in_dim(ck, k, 0, 1),
                       lax.dynamic_update_slice_in_dim(cv, v, 0, 1)))
        if prompt_lens is None:
            att = local_attention(q, k, v, causal=True, impl=attn_impl)
        else:
            # causal AND key-not-pad: same einsum shape as the decode
            # tick, applied over all P query positions at once
            D = q.shape[-1]
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                           preferred_element_type=jnp.float32)
            s = s * (1.0 / (D ** 0.5))
            cols = jnp.arange(P)
            # [B,1,q,k]: key k visible to query q iff k <= q (causal) and
            # k is past row b's left padding.  Pad queries additionally
            # see themselves: an all-masked softmax is NaN, and 0*NaN
            # poisons the value einsum for the REAL queries too — self
            # attention keeps pad lanes finite (their K/V stay masked
            # out of every real lane, here and in the decode ticks).
            mask = ((cols[None, None, None, :] <= cols[None, None, :, None])
                    & (cols[None, :] >= pad)[:, None, None, :]) \
                | jnp.eye(P, dtype=bool)[None, None]
            s = jnp.where(mask, s, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            att = jnp.einsum("bhqk,bkhd->bqhd", w.astype(cd), v)
        x = attn_out(blk, x, att, cd)
        x = ffn_apply(blk, x, cd)
    x = _rmsnorm(params["out_norm"], x)
    logits = (x[:, -1] @ params["embed"].T.astype(cd)).astype(jnp.float32)
    first = jnp.argmax(logits, axis=-1)            # [B]

    def decode(carry, _):
        tok, pos, caches = carry                   # tok [B], pos scalar
        x = params["embed"][tok].astype(cd)[:, None]
        if prompt_lens is None:
            x = x + lax.dynamic_slice_in_dim(params["pos"], pos, 1,
                                             0).astype(cd)[None]
        else:
            # row b decodes logical position plens_b + (pos - P)
            x = x + params["pos"][plens + (pos - P)].astype(cd)[:, None]
        new_caches = []
        for i in range(depth):
            blk = params[f"block{i}"]
            ck, cv = caches[i]
            q, k1, v1 = attn_qkv(blk, x, cd)       # [B,1,H,D]
            ck = lax.dynamic_update_slice_in_dim(ck, k1, pos, 1)
            cv = lax.dynamic_update_slice_in_dim(cv, v1, pos, 1)
            new_caches.append((ck, cv))
            live = jnp.arange(T)[None, None, None, :] <= pos
            if prompt_lens is not None:
                live = live & (jnp.arange(T)[None, :]
                               >= pad)[:, None, None, :]
            x = attn_out(blk, x, decode_attend(q, ck, cv, live, cd), cd)
            x = ffn_apply(blk, x, cd)
        x = _rmsnorm(params["out_norm"], x)
        lg = (x[:, 0] @ params["embed"].T.astype(cd)).astype(jnp.float32)
        nxt = jnp.argmax(lg, axis=-1)
        return (nxt, pos + 1, new_caches), tok

    (_, _, _), out = lax.scan(decode, (first, jnp.int32(P), caches),
                              None, length=steps)
    return jnp.swapaxes(out, 0, 1)                 # [B, steps]


def stack_block_params(params: PyTree, depth: int) -> PyTree:
    """Per-block layout (``block0..block{depth-1}``) -> scanned layout
    (the per-block leaves stacked on a leading ``[depth]`` axis under
    ``"blocks"``).  The ``scan_blocks=True`` parameter layout."""
    blocks = [params[f"block{i}"] for i in range(depth)]
    out = {k: v for k, v in params.items() if not k.startswith("block")}
    out["blocks"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                           *blocks)
    return out


def unstack_block_params(params: PyTree, depth: int) -> PyTree:
    """Inverse of :func:`stack_block_params`."""
    out = {k: v for k, v in params.items() if k != "blocks"}
    for i in range(depth):
        out[f"block{i}"] = jax.tree_util.tree_map(lambda a, i=i: a[i],
                                                  params["blocks"])
    return out


def param_specs(params: PyTree, tp_axis: str | None,
                ep_axis: str | None = None) -> PyTree:
    """PartitionSpecs for shard_map in_specs: TP shards heads / MLP hidden
    over ``tp_axis``; EP shards the expert-stacked MoE leaves over
    ``ep_axis`` (router replicated); everything else replicated.  Leaves
    under the scanned ``"blocks"`` layout get the same spec shifted one
    axis right (their leading axis is depth)."""
    def spec_for(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        leafname = names[-1]
        if leafname in ("we1", "wb1", "we2"):
            spec = P(ep_axis) if ep_axis else P()   # leading expert axis
        elif tp_axis is None:
            spec = P()
        elif leafname in ("wq", "wk", "wv"):
            spec = P(None, tp_axis)          # [E, H, D]: split heads
        elif leafname == "wo":
            spec = P(tp_axis)                # [H, D, E]: split heads
        elif leafname in ("w1",):
            spec = P(None, tp_axis)          # [E, F]: split hidden
        elif leafname in ("b1",):
            spec = P(tp_axis)                # [F]
        elif leafname == "w2":
            spec = P(tp_axis)                # [F, E]: split hidden
        else:
            spec = P()
        if "blocks" in names[:-1]:           # scanned layout: depth axis
            spec = P(None, *spec)
        return spec

    return jax.tree_util.tree_map_with_path(spec_for, params)


def _mtp_loss(state, tokens, seq_axis):
    """The weighted loss of a multi-token-prediction module whose logits the
    model's ``state`` carries (see :func:`lm_loss`), or None."""
    if not isinstance(state, dict) or "mtp_logits" not in state:
        return None
    if seq_axis is not None and lax.axis_size(seq_axis) != 1:
        raise NotImplementedError(
            "lm_loss: the prediction module's target two ahead would have "
            f"to cross the shards of axis {seq_axis!r}; keep the sequence "
            "on one shard")
    with jax.named_scope("head_loss"):
        lp = jax.nn.log_softmax(
            state["mtp_logits"][:, :-2].astype(jnp.float32))
        nll = -jnp.take_along_axis(lp, tokens[:, 2:, None], -1)[..., 0]
        return state["mtp_weight"] * nll.mean()


def lm_loss(model: Model, params, tokens, seq_axis=None, tp_axis=None,
            ep_axis=None, reduce: bool = True,
            moe_balance_weight: float = 0.0, seq_layout: str = "contig",
            grad_reduce_axis: str | None = None):
    """Next-token cross-entropy.  With a sequence axis, the final position's
    target lives on the next shard — the shift rides a ppermute so the loss
    is exact across shard boundaries.

    ``reduce=False`` returns the LOCAL shard's share of the global-mean loss
    (local masked sum / global token count) WITHOUT the cross-shard psum —
    the form to differentiate inside shard_map: ``psum`` transposes to
    ``psum`` there, so differentiating the psum'd global loss would scale
    gradients by the seq-axis size; differentiate the local share and psum
    the resulting partial gradients instead (distlearn_tpu.train.lm).

    ``moe_balance_weight`` adds that multiple of the model's Switch
    load-balancing loss (state output ``moe_balance_loss``) — required for
    stable MoE training; ignored for dense models.

    ``grad_reduce_axis`` goes to a scanned :func:`transformer_lm`'s
    ``apply`` (see there) and to no other model.

    Where the model's state carries the logits of a multi-token-prediction
    module (``mtp_logits`` [B, L, V] with ``mtp_weight``:
    :func:`distlearn_tpu.models.hybrid.hybrid_lm` ``mtp_depth=1``, in
    training), the loss gains ``mtp_weight`` times the mean cross-entropy of
    ``mtp_logits[:, :-2]`` against ``tokens[:, 2:]`` — position ``i``'s
    module logits predict the token TWO ahead — under the same scope
    ``head_loss``.  Such a model keeps the sequence on one shard."""
    reducing = ({} if grad_reduce_axis is None
                else {"grad_reduce_axis": grad_reduce_axis})
    logits, st = model.apply(params, {}, tokens, train=True,
                             seq_axis=seq_axis, tp_axis=tp_axis,
                             ep_axis=ep_axis, seq_layout=seq_layout,
                             **reducing)
    bal = (moe_balance_weight * st["moe_balance_loss"]
           if moe_balance_weight and isinstance(st, dict)
           and "moe_balance_loss" in st else None)
    mtp = _mtp_loss(st, tokens, seq_axis)
    if seq_axis is None:
        targets = tokens[:, 1:]
        with jax.named_scope("head_loss"):
            lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            nll = -jnp.take_along_axis(lp, targets[..., None], -1)[..., 0]
            loss = nll.mean()
        if mtp is not None:
            loss = loss + mtp
        return loss + bal if bal is not None else loss
    n = lax.axis_size(seq_axis)
    my = lax.axis_index(seq_axis)
    L = tokens.shape[1]
    if seq_layout == "zigzag":
        # local shard = early stripe a=my ++ late stripe b=2n-1-my.  Each
        # stripe's boundary target is the HEAD of the globally-next
        # stripe: stripe a+1 is rank my+1's early stripe (except a+1 == n,
        # which is rank n-1's own LATE stripe), and stripe b+1 = 2n-my is
        # rank my-1's late stripe (except b == 2n-1 on rank 0 — the
        # global end, masked below).  Two neighbor ppermutes deliver both.
        s_len = L // 2
        ta, tb = tokens[:, :s_len], tokens[:, s_len:]
        early_head, late_head = tokens[:, :1], tokens[:, s_len:s_len + 1]
        from_next = lax.ppermute(early_head, seq_axis,
                                 [(j, (j - 1) % n) for j in range(n)])
        from_prev = lax.ppermute(late_head, seq_axis,
                                 [(j, (j + 1) % n) for j in range(n)])
        bound_a = jnp.where(my == n - 1, late_head, from_next)
        targets = jnp.concatenate([ta[:, 1:], bound_a, tb[:, 1:],
                                   from_prev], axis=1)
        # only the global last position (rank 0's late-stripe tail) has
        # no target
        w = jnp.ones((L,), jnp.float32).at[-1].set(
            jnp.where(my == 0, 0.0, 1.0))
    else:
        # first token of the NEXT shard (ring shift by -1)
        perm = [(j, (j - 1) % n) for j in range(n)]
        nxt_first = lax.ppermute(tokens[:, :1], seq_axis, perm)  # [B,1]
        targets = jnp.concatenate([tokens[:, 1:], nxt_first], axis=1)
        pos = my * L + jnp.arange(L)
        w = (pos < n * L - 1).astype(jnp.float32)
    with jax.named_scope("head_loss"):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.take_along_axis(lp, targets[..., None], -1)[..., 0]
        # mask the target-less global last position; normalize by the
        # GLOBAL token count (a constant — no gradient flows through it)
        count = lax.psum(jnp.sum(w) * tokens.shape[0], seq_axis)
        local = jnp.sum(nll * w[None, :]) / jnp.maximum(count, 1.0)
    if mtp is not None:
        local = local + mtp
    if bal is not None:
        # each shard routes its own tokens: 1/n of the balance term per
        # shard makes the psum'd total the cross-shard mean
        local = local + bal / n
    return lax.psum(local, seq_axis) if reduce else local
