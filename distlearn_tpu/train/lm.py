"""Fused LM train step over a (data, seq, model) mesh — the 3D-parallel
composition: data parallelism (gradient psum), sequence parallelism (ring
attention + shifted targets), and tensor parallelism (Megatron-style sharded
projections) in ONE jitted shard_map program.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from distlearn_tpu.models.core import Model, checkpoint_block
from distlearn_tpu.models.transformer import (_rmsnorm, block_apply, lm_loss,
                                              param_specs,
                                              stack_block_params,
                                              unstack_block_params)
from distlearn_tpu import obs
from distlearn_tpu.parallel.mesh import cut_axis
from distlearn_tpu.parallel.pp import pipeline_apply
from distlearn_tpu.train.trainer import (_timed, apply_elastic_round,
                                         local_update)


def lm_local_grads(model: Model, params, tokens, *, seq_axis, tp_axis,
                   ep_axis=None, accum_steps: int = 1,
                   moe_balance_weight: float = 0.0,
                   seq_layout: str = "contig",
                   grad_reduce_axis: str | None = None):
    """``(local_loss_share, grads)`` of the LM objective on THIS device's
    shard — the gradient machinery shared by every LM step builder
    (:func:`build_lm_step`, ``optim.build_lm_optax_step``).

    Differentiates the LOCAL loss share (``lm_loss(reduce=False)``): psum
    transposes to psum under shard_map, so the global psum'd loss must
    not sit inside the differentiated function.  ``accum_steps=k`` scans
    k microbatches and averages — memory lever, same effective batch.
    ``grad_reduce_axis``: see :func:`lm_loss` (the gradient of a scanned
    stack then comes back summed over that axis; only
    :func:`build_lm_step` passes it).
    """
    def local_grad(toks):
        return jax.value_and_grad(
            lambda p: lm_loss(model, p, toks, seq_axis=seq_axis,
                              tp_axis=tp_axis, ep_axis=ep_axis,
                              reduce=False,
                              moe_balance_weight=moe_balance_weight,
                              seq_layout=seq_layout,
                              grad_reduce_axis=grad_reduce_axis)
            )(params)

    if accum_steps == 1:
        return local_grad(tokens)
    if tokens.shape[0] % accum_steps:
        raise ValueError(
            f"per-device batch {tokens.shape[0]} not divisible by "
            f"accum_steps={accum_steps}")
    micro = tokens.reshape((accum_steps, -1) + tokens.shape[1:])

    def body(carry, toks):
        acc_l, acc_g = carry
        li, gi = local_grad(toks)
        return (acc_l + li,
                jax.tree_util.tree_map(jnp.add, acc_g, gi)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (acc_l, acc_g), _ = lax.scan(
        body, (jnp.zeros((), jnp.float32), zero), micro)
    return (acc_l / jnp.float32(accum_steps),
            jax.tree_util.tree_map(
                lambda g: g / jnp.asarray(accum_steps, g.dtype), acc_g))


#: A scanned layer whose gradient takes fewer bytes on one chip than this
#: keeps the ``psum`` after the loop.  Set from a sweep on the four chips of
#: a v5e host (12 layers, 8 x 1024 tokens a chip, the step's time with the
#: sum inside the loop against the psum after it; my chip run, PR 32): at
#: 3.2, 7.1 and 12.6 MB a layer the two read the same to 0.9 % either way
#: (an exchange under 1 MB costs the 100 us it takes to start, whatever its
#: size), at 28.3 MB the loop wins 1.9 %, at 50.4 MB 2.1 %, at GPT-2-large's
#: 78.7 MB 3.0 %.
PIPELINED_LAYER_BYTES = 16 << 20


def _local_shape(leaf, spec, mesh: Mesh) -> tuple[int, ...]:
    """Shape of ``leaf``'s shard on one device of ``mesh`` under ``spec``."""
    names = tuple(spec) + (None,) * (leaf.ndim - len(spec))
    return tuple(
        d // math.prod(mesh.shape[a] for a in jax.tree_util.tree_leaves(name))
        for d, name in zip(leaf.shape, names))


def _local_bytes(leaf, spec, mesh: Mesh) -> int:
    """Bytes of ``leaf``'s shard on one device of ``mesh`` under ``spec``."""
    return (math.prod(_local_shape(leaf, spec, mesh))
            * jnp.dtype(leaf.dtype).itemsize)


def _pipelined(mesh: Mesh, template, pspecs, data_axis, seq_axis, ep_axis,
               accum_steps) -> bool:
    """Whether the block gradients are summed over ``data_axis`` INSIDE the
    backward pass (:func:`~distlearn_tpu.models.core.scan_reducing`): the
    stack is scanned, the data axis (a power of two above 1) is the only
    axis those gradients are summed over, one backward pass makes them,
    every leaf of a layer can be cut in as many chunks as the axis has
    devices, and a layer is large enough for the exchange to be bound by
    its bytes."""
    if "blocks" not in template or accum_steps > 1 or ep_axis is not None:
        return False
    if seq_axis is not None and mesh.shape[seq_axis] > 1:
        return False
    dp = mesh.shape[data_axis]
    if dp < 2 or dp & (dp - 1):
        return False
    leaves = jax.tree_util.tree_leaves(template["blocks"])
    specs = jax.tree_util.tree_leaves(
        pspecs["blocks"], is_leaf=lambda s: isinstance(s, P))
    if len({leaf.dtype for leaf in leaves}) > 1:
        return False
    if any(cut_axis(_local_shape(leaf, spec, mesh)[1:], dp) is None
           for leaf, spec in zip(leaves, specs)):
        return False
    layer = sum(_local_bytes(leaf, spec, mesh)
                for leaf, spec in zip(leaves, specs)) // leaves[0].shape[0]
    return layer >= PIPELINED_LAYER_BYTES


def build_lm_step(model: Model, mesh: Mesh, params_template, lr: float,
                  data_axis: str = "data", seq_axis: str | None = "seq",
                  tp_axis: str | None = "model",
                  ep_axis: str | None = None, accum_steps: int = 1,
                  moe_balance_weight: float = 0.0,
                  donate: bool = True,
                  seq_layout: str = "contig") -> Callable:
    """``step(params, tokens) -> (params, loss)``.

    ``tokens``: [global_B, global_L] int32, sharded (data, seq).
    ``params``: sharded per :func:`param_specs` over ``tp_axis`` (replicated
    across data/seq).  Gradients are psum'd over data+seq axes (params are
    replicated there); TP-sharded leaves need no gradient collective — each
    device owns its slice.

    Where a SCANNED stack trains data-parallel (see :func:`_pipelined`: the
    mesh and the leaf sizes decide, no argument does), the gradients of
    ``params["blocks"]`` are summed over ``data_axis`` inside the backward
    loop, each layer's behind the layers after it
    (:func:`~distlearn_tpu.models.core.scan_reducing`): float32, the same
    bytes over the links, every replica the same bits.  The psum after the
    loop then keeps the leaves made outside it (embedding, positions, last
    norm).  The gauges ``train.grad_reduce.pipelined_bytes{step=lm}`` and
    ``train.grad_reduce.tail_bytes{step=lm}`` say, from build time, how many
    bytes a step one chip sums inside the loop and after it; the gauge
    ``train.mtp.loss_weight{step=lm}`` the weight of a multi-token-prediction
    module's loss in what the step differentiates (``models/hybrid.py``; 0
    for a model without one).

    ``ep_axis`` (MoE models): the mesh axis the expert-stacked leaves are
    sharded over — normally ``data_axis`` itself (EP group == DP group,
    one expert per data-parallel device).  Expert leaves are EXCLUDED from
    the data-axis gradient psum: each device owns a distinct expert slice,
    and the transposed all-to-all already accumulated every replica's
    contribution to it; summing across the axis would mix different
    experts' gradients.  They still reduce over ``seq_axis`` (each
    sequence shard routes its own tokens) and share the 1/dp objective
    scaling.

    ``accum_steps=k`` splits each device's batch rows into ``k``
    microbatches scanned sequentially (live activation memory drops ~k-
    fold — composes with the model's ``remat``); the averaged gradient
    feeds the same single reduction + update, so the effective batch is
    unchanged and dense models match the single-shot step exactly (the
    transformer has no dropout state).  MoE models are the exception:
    expert capacity is computed per ROUTING CALL, so microbatching rounds
    bucket sizes and decides overflow drops per microbatch — training is
    still correct, but not bit-identical to the single-shot step.

    The update is one ``tree_map`` over the leaves and never the packed
    Pallas kernel of ``ops/fused_update.py``: on a tree of hundreds of
    millions of parameters, packing gradients and parameters into buckets
    and unpacking the result costs more passes over memory than the
    per-leaf update makes (PERF.md section 7a.2).
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    axes = tuple(a for a in (data_axis, seq_axis) if a is not None)
    # expert leaves reduce over every replicated axis EXCEPT the one that
    # shards them — summing across ep_axis would mix different experts
    ep_grad_axes = tuple(a for a in axes if a != ep_axis)
    pspecs = param_specs(params_template, tp_axis, ep_axis)
    pipelined = _pipelined(mesh, params_template, pspecs, data_axis,
                           seq_axis, ep_axis, accum_steps)
    # the axes each leaf's gradient is still to be summed over after the
    # backward pass
    grad_axes = jax.tree_util.tree_map(
        lambda s: ep_grad_axes if ep_axis is not None and ep_axis in s
        else axes, pspecs)
    if pipelined:
        grad_axes["blocks"] = jax.tree_util.tree_map(
            lambda _: (), params_template["blocks"])

    held = jax.tree_util.tree_map(
        lambda leaf, s: _local_bytes(leaf, s, mesh), params_template, pspecs)
    tail = jax.tree_util.tree_map(
        lambda n, over: n * (math.prod(mesh.shape[a] for a in over) > 1),
        held, grad_axes)
    obs.gauge(
        "train.grad_reduce.pipelined_bytes", "gradient bytes one chip sums "
        "over the data axis inside the backward loop, a step",
        labels=("step",)).labels(step="lm").set(
            sum(jax.tree_util.tree_leaves(held["blocks"])) if pipelined else 0)
    obs.gauge(
        "train.grad_reduce.tail_bytes", "gradient bytes one chip hands to "
        "the psum after the backward pass, a step",
        labels=("step",)).labels(step="lm").set(
            sum(jax.tree_util.tree_leaves(tail)))
    obs.gauge(
        "train.mtp.loss_weight", "weight of the multi-token-prediction "
        "module's loss in the loss the step differentiates (0: the model "
        "has no module)", labels=("step",)).labels(step="lm").set(
            getattr(model.apply, "mtp_weight", 0.0))

    def step(params, tokens):
        local_loss, grads = lm_local_grads(
            model, params, tokens, seq_axis=seq_axis, tp_axis=tp_axis,
            ep_axis=ep_axis, accum_steps=accum_steps,
            moe_balance_weight=moe_balance_weight, seq_layout=seq_layout,
            grad_reduce_axis=data_axis if pipelined else None)
        loss = lax.psum(local_loss, seq_axis) if seq_axis else local_loss
        # Sum partial grads over seq (params replicated there, each shard
        # holds part of the chain) and AVERAGE over data (the global
        # objective is the mean of per-replica losses — matching
        # allreduce_sgd's 1/n convention).  TP leaves need no collective:
        # the f/g pattern leaves each slice's gradient exact.
        dp = lax.psum(1, data_axis)

        def reduce_grad(g, over):
            if over:
                g = lax.psum(g, over)
            return g / jnp.asarray(dp, g.dtype)

        with jax.named_scope("grad_reduce"):
            grads = jax.tree_util.tree_map(reduce_grad, grads, grad_axes)
        with jax.named_scope("update"):
            new_params = jax.tree_util.tree_map(
                lambda p, g: p - jnp.asarray(lr, p.dtype)
                * g.astype(p.dtype), params, grads)
        return new_params, lax.pmean(loss, data_axis)

    tok_spec = P(data_axis, seq_axis) if seq_axis else P(data_axis)
    mapped = shard_map(step, mesh=mesh,
                           in_specs=(pspecs, tok_spec),
                           out_specs=(pspecs, P()),
                           check_vma=False)
    return _timed(jax.jit(mapped, donate_argnums=(0,) if donate else ()),
                  "lm")


def build_lm_moe_metrics(model: Model, mesh: Mesh, params_template,
                         data_axis: str = "data",
                         seq_axis: str | None = "seq",
                         tp_axis: str | None = "model",
                         ep_axis: str | None = None) -> Callable:
    """``metrics(params, tokens) -> {"moe_balance_loss", "moe_dropped_frac"}``
    — routing-health monitor for MoE LMs (forward only, no grads): the mean
    Switch balance loss (1.0 = perfectly balanced router) and the fraction
    of routing assignments dropped by expert capacity.  Same mesh/sharding
    contract as :func:`build_lm_step`; values are averaged over the
    data/seq axes.  Run at report cadence, not every step."""
    pspecs = param_specs(params_template, tp_axis, ep_axis)
    axes = tuple(a for a in (data_axis, seq_axis) if a is not None)

    def metrics(params, tokens):
        _, st = model.apply(params, {}, tokens, train=True,
                            seq_axis=seq_axis, tp_axis=tp_axis,
                            ep_axis=ep_axis)
        if "moe_balance_loss" not in st:
            raise ValueError("model returned no MoE routing metrics — "
                             "build it with moe_experts > 0")
        out = {"moe_balance_loss": st["moe_balance_loss"],
               "moe_dropped_frac": st["moe_dropped_frac"]}
        return {k: lax.pmean(v, axes) if axes else v
                for k, v in out.items()}

    tok_spec = P(data_axis, seq_axis) if seq_axis else P(data_axis)
    return jax.jit(shard_map(
        metrics, mesh=mesh, in_specs=(pspecs, tok_spec),
        out_specs={"moe_balance_loss": P(), "moe_dropped_frac": P()},
        check_vma=False))


def build_lm_routing_metrics(model: Model, mesh: Mesh, params_template,
                             data_axis: str = "data",
                             seq_axis: str | None = "seq",
                             tp_axis: str | None = "model",
                             ep_axis: str | None = None) -> Callable:
    """``metrics(params, tokens) -> dict`` — :func:`build_lm_moe_metrics`'s
    sibling for the dropless layer of ``models/hybrid.py`` (forward only,
    same mesh/sharding contract; run at report cadence).  Per layer, summed
    over the data/seq axes: ``assignments`` [layers, held] (what each held
    expert received from this batch), ``unheld_frac`` [layers] (the share of
    tokens none of whose experts is held here) and ``dropped`` [layers]
    (held assignments that found no slot — 0, the layer has no capacity).
    Each call also adds them to the ``obs`` counters
    ``moe_assignments_total{layer,expert}``, ``moe_tokens_total{layer,held}``
    and ``moe_dropped_total{layer}``."""
    from distlearn_tpu import obs
    pspecs = param_specs(params_template, tp_axis, ep_axis)
    axes = tuple(a for a in (data_axis, seq_axis) if a is not None)

    def metrics(params, tokens):
        _, st = model.apply(params, {}, tokens, train=True,
                            seq_axis=seq_axis, tp_axis=tp_axis,
                            ep_axis=ep_axis)
        if "moe_assignments" not in st:
            raise ValueError("model returned no routing counters — build "
                             "it with models.hybrid.hybrid_lm")
        out = {"assignments": st["moe_assignments"],
               "dropped": st["moe_dropped"]}
        if axes:
            out = {k: lax.psum(v, axes) for k, v in out.items()}
        frac = st["moe_unheld_frac"]
        out["unheld_frac"] = lax.pmean(frac, axes) if axes else frac
        return out

    tok_spec = P(data_axis, seq_axis) if seq_axis else P(data_axis)
    device_fn = jax.jit(shard_map(
        metrics, mesh=mesh, in_specs=(pspecs, tok_spec),
        out_specs={"assignments": P(), "dropped": P(), "unheld_frac": P()},
        check_vma=False))

    assigned = obs.counter(
        "moe_assignments_total", "assignments a held expert received",
        labels=("layer", "expert"))
    seen = obs.counter(
        "moe_tokens_total", "tokens routed, by whether any of their experts "
        "is held here", labels=("layer", "held"))
    dropped = obs.counter(
        "moe_dropped_total", "held assignments that found no slot",
        labels=("layer",))

    def counted(params, tokens):
        out = jax.device_get(device_fn(params, tokens))
        n_tokens = int(tokens.shape[0] * tokens.shape[1])
        for layer, row in enumerate(out["assignments"]):
            for expert, n in enumerate(row):
                assigned.labels(layer=str(layer), expert=str(expert)).inc(
                    int(n))
            unheld = round(float(out["unheld_frac"][layer]) * n_tokens)
            seen.labels(layer=str(layer), held="no").inc(unheld)
            seen.labels(layer=str(layer), held="yes").inc(n_tokens - unheld)
            dropped.labels(layer=str(layer)).inc(int(out["dropped"][layer]))
        return out

    return counted


def stack_blocks(params, depth: int):
    """Split a :func:`transformer_lm` param pytree into
    ``(shared, stacked_blocks)``: the embed/pos/out_norm leaves, and the
    per-block leaves stacked along a new leading ``[depth]`` axis (the
    pipeline-stage axis — shard it ``P(pipe_axis)``).  Thin split over
    :func:`distlearn_tpu.models.transformer.stack_block_params` (the
    ``scan_blocks`` layout) so the two layouts share one stacking
    implementation."""
    both = stack_block_params(params, depth)
    stacked = both.pop("blocks")
    return both, stacked


def unstack_blocks(shared, stacked, depth: int):
    """Inverse of :func:`stack_blocks` (back to the apply() layout)."""
    return unstack_block_params(dict(shared, blocks=stacked), depth)


def build_lm_pp_step(mesh: Mesh, shared_template, stacked_template,
                     lr: float, num_microbatches: int,
                     compute_dtype=None, data_axis: str = "data",
                     pipe_axis: str = "pipe", remat: bool = False,
                     unroll: bool | int = False,
                     donate: bool = True) -> Callable:
    """Pipeline-parallel LM train step over a ``(data, pipe)`` mesh:
    ``step(shared, stacked, tokens) -> (shared, stacked, loss)``.

    ``k = depth / n_stages`` transformer blocks per pipeline stage (depth
    must divide evenly; sharding the stacked ``[depth, ...]`` block axis
    over ``pipe`` hands each stage its k contiguous blocks, scanned in
    order inside the stage fn — ``remat=True`` checkpoints each block
    (:func:`~distlearn_tpu.models.core.checkpoint_block`, as the model
    constructors' ``remat="full"`` does) so only one block's activations
    per in-flight microbatch stay live).
    Microbatches stream through the stages via
    :func:`distlearn_tpu.parallel.pp.pipeline_apply`, so the whole GPipe
    schedule — all ticks, forward and backward — is one XLA program, and
    the microbatch count doubles as the gradient-accumulation lever.
    ``unroll=True`` inlines the tick scan (see pipeline_apply; program
    size grows ~T-fold, so keep it for small microbatch counts).

    Each microbatch's loss share is folded ON the last rank as it emerges
    from the pipeline (``consume_fn``) — only a scalar psum crosses the
    pipe axis, not the [B, L, D] activation broadcast, and head gradients
    seed solely on the last rank (masked elsewhere), so no 1/S rescaling
    is needed.  Embedding/positional/head leaves (``shared``) are
    replicated over both axes; their partial grads (rank 0 ingests, last
    rank computes the head) are SUMMED over pipe to reassemble and
    averaged over data.  Block leaves are sharded k-per-device over
    ``pipe`` (grads reduce over data only).  Composes with data
    parallelism; TP/SP/MoE stay with :func:`build_lm_step` — the two
    factorizations cover different model regimes (PP for deep dense
    stacks whose params exceed one chip).
    """
    n_stages = mesh.shape[pipe_axis]
    depth = jax.tree_util.tree_leaves(stacked_template)[0].shape[0]
    if depth % n_stages:
        raise ValueError(
            f"stacked blocks hold {depth} layers but the {pipe_axis!r} "
            f"axis has {n_stages} devices — depth must divide into an "
            "equal number of blocks per stage")
    for need in ("embed", "pos", "out_norm"):
        if need not in shared_template:
            raise ValueError(f"shared params missing {need!r} — pass the "
                             "(shared, stacked) pair from stack_blocks()")

    def step(shared, stacked, tokens):
        # local stacked leaves: [k, ...] — this stage's k contiguous blocks
        B, L = tokens.shape
        M = num_microbatches
        if B % M:
            raise ValueError(f"per-replica batch {B} not divisible into "
                             f"{M} microbatches")
        toks_mb = tokens.reshape(M, B // M, L)

        def local_loss(shared, blk_local):
            cd = compute_dtype or shared["embed"].dtype
            x = shared["embed"][tokens].astype(cd)
            x = x + shared["pos"][:L].astype(cd)[None]

            one = lambda bp, h: block_apply(bp, h, cd)   # noqa: E731
            if remat:
                one = checkpoint_block(one)

            def stage(bp_stack, h):
                h, _ = lax.scan(lambda hh, bp: (one(bp, hh), None),
                                h, bp_stack)
                return h

            def consume(out_mb, m):
                hh = _rmsnorm(shared["out_norm"], out_mb)
                logits = (hh @ shared["embed"].T.astype(cd)
                          ).astype(jnp.float32)
                lp = jax.nn.log_softmax(logits[:, :-1])
                tgt = lax.dynamic_index_in_dim(toks_mb, m, 0,
                                               keepdims=False)[:, 1:]
                nll = -jnp.take_along_axis(lp, tgt[..., None], -1)[..., 0]
                # this microbatch's share of the global batch-mean loss
                return nll.sum() / jnp.float32(B * (L - 1))

            return pipeline_apply(stage, blk_local, x, M,
                                  axis_name=pipe_axis, consume_fn=consume,
                                  unroll=unroll)

        local_share, (g_shared, g_blk) = jax.value_and_grad(
            local_loss, argnums=(0, 1))(shared, stacked)
        # the share is nonzero only on the last rank: psum restores the loss
        loss = lax.psum(local_share, pipe_axis)
        dp = lax.psum(1, data_axis)
        # shared leaves: partial grads live on the pipe ranks that touched
        # them — SUM over pipe reassembles; average over data (1/n as in
        # allreduce_sgd)
        g_shared = jax.tree_util.tree_map(
            lambda g: lax.psum(g, (data_axis, pipe_axis))
            / jnp.asarray(dp, g.dtype), g_shared)
        g_blk = jax.tree_util.tree_map(
            lambda g: lax.psum(g, data_axis) / jnp.asarray(dp, g.dtype),
            g_blk)
        shared = jax.tree_util.tree_map(
            lambda p, g: p - jnp.asarray(lr, p.dtype) * g.astype(p.dtype),
            shared, g_shared)
        stacked_new = jax.tree_util.tree_map(
            lambda p, g: p - jnp.asarray(lr, p.dtype) * g.astype(p.dtype),
            stacked, g_blk)
        return shared, stacked_new, lax.pmean(loss, data_axis)

    mapped = shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(pipe_axis), P(data_axis)),
        out_specs=(P(), P(pipe_axis), P()),
        check_vma=False)
    return _timed(jax.jit(mapped, donate_argnums=(0, 1) if donate else ()),
                  "lm_pp")


def build_lm_pp_1f1b_step(mesh: Mesh, shared_template, stacked_template,
                          lr: float, num_microbatches: int,
                          compute_dtype=None, data_axis: str = "data",
                          pipe_axis: str = "pipe", remat: bool = False,
                          donate: bool = True) -> Callable:
    """1F1B-scheduled pipeline-parallel LM train step — same contract,
    sharding, and gradient semantics as :func:`build_lm_pp_step`
    (``step(shared, stacked, tokens) -> (shared, stacked, loss)``), but
    each microbatch's backward starts the moment it leaves the last
    stage (:func:`distlearn_tpu.parallel.pp.pipeline_1f1b`), so live
    activation memory is O(S) stage-inputs instead of GPipe's O(M)
    autodiff residuals — the schedule to use when the microbatch count
    is cranked up for bubble amortization.  ``remat`` checkpoints each
    block inside the stage fn (the per-tick backward already recomputes
    the stage forward from its input; block-level remat additionally
    bounds the recompute graph's own liveness for k-block stages).

    Embedding/positional gradients flow through the returned ``g_x``
    (rank 0), head/out-norm gradients through the explicit consume
    params (last rank); both reassemble with the same pipe-axis psum as
    the GPipe builder, so the two schedules are drop-in interchangeable
    (equivalence is tested).
    """
    from distlearn_tpu.parallel.pp import pipeline_1f1b
    n_stages = mesh.shape[pipe_axis]
    depth = jax.tree_util.tree_leaves(stacked_template)[0].shape[0]
    if depth % n_stages:
        raise ValueError(
            f"stacked blocks hold {depth} layers but the {pipe_axis!r} "
            f"axis has {n_stages} devices — depth must divide into an "
            "equal number of blocks per stage")
    for need in ("embed", "pos", "out_norm"):
        if need not in shared_template:
            raise ValueError(f"shared params missing {need!r} — pass the "
                             "(shared, stacked) pair from stack_blocks()")

    def step(shared, stacked, tokens):
        B, L = tokens.shape
        M = num_microbatches
        if B % M:
            raise ValueError(f"per-replica batch {B} not divisible into "
                             f"{M} microbatches")
        toks_mb = tokens.reshape(M, B // M, L)
        cd = compute_dtype or shared["embed"].dtype

        def embed_fn(sh):
            x = sh["embed"][tokens].astype(cd)
            return x + sh["pos"][:L].astype(cd)[None]

        x, embed_vjp = jax.vjp(embed_fn,
                               {"embed": shared["embed"],
                                "pos": shared["pos"]})

        one = lambda bp, h: block_apply(bp, h, cd)   # noqa: E731
        if remat:
            one = checkpoint_block(one)

        def stage(bp_stack, h):
            h, _ = lax.scan(lambda hh, bp: (one(bp, hh), None), h, bp_stack)
            return h

        def consume(cp, out_mb, m):
            hh = _rmsnorm(cp["out_norm"], out_mb)
            logits = (hh @ cp["embed"].T.astype(cd)).astype(jnp.float32)
            lp = jax.nn.log_softmax(logits[:, :-1])
            tgt = lax.dynamic_index_in_dim(toks_mb, m, 0,
                                           keepdims=False)[:, 1:]
            nll = -jnp.take_along_axis(lp, tgt[..., None], -1)[..., 0]
            return nll.sum() / jnp.float32(B * (L - 1))

        cp = {"out_norm": shared["out_norm"], "embed": shared["embed"]}
        local_share, g_blk, g_cp, g_x = pipeline_1f1b(
            stage, stacked, consume, cp, x, M, axis_name=pipe_axis)
        (g_embed,) = embed_vjp(g_x.astype(x.dtype))

        loss = lax.psum(local_share, pipe_axis)
        dp = lax.psum(1, data_axis)
        # reassemble shared grads: embedding side (rank 0) + head side
        # (last rank); embed appears in both
        g_shared = {"embed": g_embed["embed"] + g_cp["embed"],
                    "pos": g_embed["pos"],
                    "out_norm": g_cp["out_norm"]}
        g_shared = jax.tree_util.tree_map(
            lambda g: lax.psum(g, (data_axis, pipe_axis))
            / jnp.asarray(dp, g.dtype), g_shared)
        g_blk = jax.tree_util.tree_map(
            lambda g: lax.psum(g, data_axis) / jnp.asarray(dp, g.dtype),
            g_blk)
        shared = jax.tree_util.tree_map(
            lambda p, g: p - jnp.asarray(lr, p.dtype) * g.astype(p.dtype),
            shared, g_shared)
        stacked_new = jax.tree_util.tree_map(
            lambda p, g: p - jnp.asarray(lr, p.dtype) * g.astype(p.dtype),
            stacked, g_blk)
        return shared, stacked_new, lax.pmean(loss, data_axis)

    mapped = shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(pipe_axis), P(data_axis)),
        out_specs=(P(), P(pipe_axis), P()),
        check_vma=False)
    return _timed(jax.jit(mapped, donate_argnums=(0, 1) if donate else ()),
                  "lm_pp_1f1b")


class LMMixedState(NamedTuple):
    """Mixed-precision LM train state: ``params`` is the bf16 WORKING copy
    every matmul reads (2 bytes/param — halves the weight-read traffic of
    the f32-param step across forward, dgrad, and wgrad), ``master`` the
    f32 copy the update applies to (bf16's 8-bit mantissa underflows
    ``p - lr*g`` when ``lr*g`` is ~256x smaller than ``p``; the master
    keeps SGD exact).  Invariant: ``params == master.astype(bf16)``."""
    params: Any
    master: Any


def init_lm_mixed_state(params, param_dtype=jnp.bfloat16) -> LMMixedState:
    """Master := the f32 init; working copy := its ``param_dtype`` cast."""
    cast = jax.tree_util.tree_map(
        lambda p: p.astype(param_dtype), params)
    return LMMixedState(params=cast, master=params)


def build_lm_mixed_step(model: Model, mesh: Mesh, params_template, lr: float,
                        data_axis: str = "data",
                        seq_axis: str | None = "seq",
                        tp_axis: str | None = "model",
                        ep_axis: str | None = None, accum_steps: int = 1,
                        moe_balance_weight: float = 0.0,
                        grad_dtype=jnp.float32,
                        donate: bool = True,
                        seq_layout: str = "contig") -> Callable:
    """:func:`build_lm_step` with bf16 working params + f32 masters:
    ``step(st, tokens) -> (st, loss)`` on :class:`LMMixedState`.

    Motivation: the f32-param step reads 4-byte weights in every matmul
    even though the MXU computes in bf16 (the convert fuses into the
    matmul but the HBM read does not shrink).  Storing the working copy in bf16 halves the weight bytes
    the three matmul passes pull per step; the f32 master confines f32
    elementwise traffic to the update itself.  Same mesh/sharding
    contract as :func:`build_lm_step` (``params_template`` may be either
    precision — only shapes matter for the specs).

    ``grad_dtype`` is the dtype gradients are REDUCED and applied in
    (default f32: bf16 grads from the bf16-param backward are upcast
    before the data/seq psum, so the cross-replica sum accumulates full
    precision; pass ``jnp.bfloat16`` to halve gradient ICI bytes when
    the replica count is small enough for bf16 accumulation).
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    axes = tuple(a for a in (data_axis, seq_axis) if a is not None)
    ep_grad_axes = tuple(a for a in axes if a != ep_axis)
    pspecs = param_specs(params_template, tp_axis, ep_axis)
    is_ep_leaf = jax.tree_util.tree_map(
        lambda s: ep_axis is not None and ep_axis in s, pspecs)

    def step(st: LMMixedState, tokens):
        local_loss, grads = lm_local_grads(
            model, st.params, tokens, seq_axis=seq_axis, tp_axis=tp_axis,
            ep_axis=ep_axis, accum_steps=accum_steps,
            moe_balance_weight=moe_balance_weight, seq_layout=seq_layout)
        loss = lax.psum(local_loss, seq_axis) if seq_axis else local_loss
        dp = lax.psum(1, data_axis)

        def reduce_grad(g, is_ep):
            g = g.astype(grad_dtype)
            gaxes = ep_grad_axes if is_ep else axes
            if gaxes:
                g = lax.psum(g, gaxes)
            return g / jnp.asarray(dp, g.dtype)

        with jax.named_scope("grad_reduce"):
            grads = jax.tree_util.tree_map(reduce_grad, grads, is_ep_leaf)
        with jax.named_scope("update"):
            master = jax.tree_util.tree_map(
                lambda m, g: m - jnp.asarray(lr, m.dtype)
                * g.astype(m.dtype), st.master, grads)
            params = jax.tree_util.tree_map(
                lambda p, m: m.astype(p.dtype), st.params, master)
        return (LMMixedState(params, master),
                lax.pmean(loss, data_axis))

    tok_spec = P(data_axis, seq_axis) if seq_axis else P(data_axis)
    spec = LMMixedState(params=pspecs, master=pspecs)
    mapped = shard_map(step, mesh=mesh, in_specs=(spec, tok_spec),
                           out_specs=(spec, P()), check_vma=False)
    return _timed(jax.jit(mapped, donate_argnums=(0,) if donate else ()),
                  "lm_mixed")


class LMEAState(NamedTuple):
    """Per-node elastic-averaging state for LM training: every leaf has a
    leading ``[num_nodes]`` axis sharded over the data mesh axis (replicas
    deliberately diverge between rounds — lua/AllReduceEA.lua semantics on
    the transformer family the reference never had)."""
    params: Any
    center: Any
    vel: Any


def init_lm_ea_state(model: Model, tree, key) -> LMEAState:
    """Identical init on every node, center := params, zero momentum
    (mirrors distlearn_tpu.train.trainer.init_ea_state for classifiers)."""
    params, _ = model.init(key)
    n = tree.num_nodes
    stack = lambda t: tree.put_per_node(jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t))
    return LMEAState(params=stack(params), center=stack(params),
                     vel=stack(jax.tree_util.tree_map(jnp.zeros_like,
                                                      params)))


def build_lm_ea_steps(model: Model, tree, lr: float, alpha: float,
                      momentum: float = 0.0, donate: bool = True,
                      fused: bool | None = None,
                      max_bucket_bytes: int | None = None):
    """EASGD for the transformer LM over a data mesh axis: returns
    ``(local_step, ea_round)`` with the same contract as
    :func:`distlearn_tpu.train.trainer.build_ea_steps` — τ−1 of every τ
    steps run with ZERO collectives (the host owns the τ cadence), then
    one fused elastic round couples the replicas through the center
    (lua/AllReduceEA.lua:25-47 recast; ``momentum`` adds the paper's
    EAMSGD local rule).

    ``local_step(state, tokens) -> (state, losses[num_nodes])`` — tokens
    ``[global_B, L]`` sharded over the data axis; each node trains its own
    replica on its shard.  ``ea_round(state) -> state``.
    """
    from distlearn_tpu.parallel.mesh import expand_node, squeeze_node
    axis = tree.axis_name

    def local_step(st: LMEAState, tokens):
        p = squeeze_node(st.params)
        loss, grads = jax.value_and_grad(
            lambda q: lm_loss(model, q, tokens, seq_axis=None,
                              tp_axis=None))(p)
        p, v = local_update(p, grads, squeeze_node(st.vel), lr, momentum)
        vel = expand_node(v) if momentum else st.vel
        return (LMEAState(expand_node(p), st.center, vel),
                loss[None] if loss.ndim == 0 else loss)

    def ea_round(st: LMEAState):
        p, c = apply_elastic_round(squeeze_node(st.params),
                                   squeeze_node(st.center), alpha, axis,
                                   fused, max_bucket_bytes)
        return LMEAState(expand_node(p), expand_node(c), st.vel)

    spec = LMEAState(params=P(axis), center=P(axis), vel=P(axis))
    local = jax.jit(
        shard_map(local_step, mesh=tree.mesh,
                      in_specs=(spec, P(axis)),
                      out_specs=(spec, P(axis)), check_vma=False),
        donate_argnums=(0,) if donate else ())
    rnd = jax.jit(
        shard_map(ea_round, mesh=tree.mesh, in_specs=(spec,),
                      out_specs=spec, check_vma=False),
        donate_argnums=(0,) if donate else ())
    return _timed(local, "lm_ea_local"), _timed(rnd, "lm_ea_round")
