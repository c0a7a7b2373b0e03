"""Optax-backed fused train step — the reference's ``optim`` library slot.

The reference's examples hand-roll SGD (examples/mnist.lua:112-116) but its
ecosystem slot for optimizers is the external ``optim`` package (sgd with
momentum, adagrad, ... — SURVEY.md §2b "optim/xlua/lapp" row).  The
TPU-native equivalent is optax: any ``GradientTransformation`` drops into
the same fused AllReduceSGD step — forward, backward, gradient psum with
contributor normalization, optimizer update, metrics — still ONE XLA
program per step.  :func:`build_sgd_step` stays the bare-SGD hot path
(reference parity + the Pallas fused-update route); this builder is the
general-optimizer variant.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax, random
from jax.sharding import PartitionSpec as P

from jax import shard_map

from distlearn_tpu.models.core import Model, loss_fn
from distlearn_tpu.ops import flatten as flatten_lib
from distlearn_tpu.parallel import allreduce_sgd
from distlearn_tpu.parallel import mesh as mesh_lib
from distlearn_tpu.parallel.mesh import MeshTree
from distlearn_tpu.utils import metrics as metrics_lib

PyTree = Any


class OptaxTrainState(NamedTuple):
    """Like trainer.TrainState plus the optimizer state (replicated — it is
    a deterministic function of the replicated params/grads)."""
    params: PyTree
    model_state: PyTree
    opt_state: PyTree
    sync: Any
    cm: jax.Array
    rng: jax.Array


def init_optax_state(model: Model, tree: MeshTree, tx, key: jax.Array,
                     num_classes: int) -> OptaxTrainState:
    from distlearn_tpu.train.trainer import init_common
    params, mstate, sync, cm, rng = init_common(model, tree, key,
                                                num_classes)
    return OptaxTrainState(params=params, model_state=mstate,
                           opt_state=tx.init(params), sync=sync, cm=cm,
                           rng=rng)


def build_optax_step(model: Model, tree: MeshTree, tx,
                     accum_steps: int = 1, donate: bool = True) -> Callable:
    """One fused data-parallel step with an optax optimizer:
    ``step(ts, x, y) -> (ts, loss)``.

    Same collective structure as :func:`~distlearn_tpu.train.build_sgd_step`
    (params replicated, batch sharded, grads psum'd + contributor-
    normalized before the update), with ``tx.update`` in place of the bare
    SGD rule — e.g. ``optax.sgd(lr, momentum=0.9)``, ``optax.adamw(lr)``.
    The optimizer state stays bitwise-replicated because every replica
    applies the identical psum'd gradient.

    ``accum_steps=k`` runs gradient accumulation: each device's shard is
    split into ``k`` microbatches processed by a ``lax.scan`` (live
    activation memory drops by ~k) whose averaged gradient feeds ONE
    psum + optimizer update — the effective batch is unchanged.  For
    batchnorm models the running stats are those of the LAST microbatch
    (the standard approximation); the loss/gradient math is exact for
    per-example losses.
    """
    axis = tree.axis_name
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    def step(ts: OptaxTrainState, x, y):
        rng, dropout_rng = random.split(ts.rng)
        dropout_rng = random.fold_in(dropout_rng, lax.axis_index(axis))

        if accum_steps == 1:
            def _loss(p):
                return loss_fn(model, p, ts.model_state, x, y, train=True,
                               rng=dropout_rng, axis_name=axis)

            (loss, (log_probs, mstate)), grads = \
                jax.value_and_grad(_loss, has_aux=True)(ts.params)
        else:
            if x.shape[0] % accum_steps:
                raise ValueError(
                    f"per-device batch {x.shape[0]} not divisible by "
                    f"accum_steps={accum_steps}")
            xm = x.reshape((accum_steps, -1) + x.shape[1:])
            ym = y.reshape((accum_steps, -1) + y.shape[1:])

            def micro(carry, inp):
                acc_g, acc_l, mstate, i = carry
                xi, yi = inp
                mb_rng = random.fold_in(dropout_rng, i)

                def _loss(p):
                    return loss_fn(model, p, mstate, xi, yi, train=True,
                                   rng=mb_rng, axis_name=axis)

                (li, (lp, mstate)), gi = \
                    jax.value_and_grad(_loss, has_aux=True)(ts.params)
                acc_g = jax.tree_util.tree_map(jnp.add, acc_g, gi)
                return (acc_g, acc_l + li, mstate, i + 1), lp

            zero_g = jax.tree_util.tree_map(jnp.zeros_like, ts.params)
            (acc_g, acc_l, mstate, _), lps = lax.scan(
                micro, (zero_g, jnp.zeros((), jnp.float32), ts.model_state,
                        jnp.zeros((), jnp.int32)), (xm, ym))
            # per-leaf dtype division: a strongly-typed f32 scalar would
            # silently promote bf16 grads (and then the optimizer state)
            grads = jax.tree_util.tree_map(
                lambda g: g / jnp.asarray(accum_steps, g.dtype), acc_g)
            loss = acc_l / jnp.float32(accum_steps)
            log_probs = lps.reshape((x.shape[0],) + lps.shape[2:])
        sync_local = mesh_lib.squeeze_node(ts.sync)
        grads, sync_local, _ = allreduce_sgd.sum_and_normalize_gradients(
            grads, sync_local, axis_name=axis)
        updates, opt_state = tx.update(grads, ts.opt_state, ts.params)
        params = jax.tree_util.tree_map(
            lambda p, u: p + u.astype(p.dtype), ts.params, updates)
        cm_new = metrics_lib.update_confusion(jnp.squeeze(ts.cm, 0),
                                              log_probs, y)
        new_ts = OptaxTrainState(params, mstate, opt_state,
                                 mesh_lib.expand_node(sync_local),
                                 cm_new[None], rng)
        return new_ts, lax.pmean(loss, axis)

    specs = OptaxTrainState(params=P(), model_state=P(), opt_state=P(),
                            sync=P(axis), cm=P(axis), rng=P())
    mapped = shard_map(step, mesh=tree.mesh, in_specs=(specs, P(axis),
                                                           P(axis)),
                           out_specs=(specs, P()), check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# ZeRO-1: optimizer state sharded over the data axis
# ---------------------------------------------------------------------------

class ZeroTrainState(NamedTuple):
    """Params replicated; OPTIMIZER STATE SHARDED — each device holds the
    state for only its 1/N slice of the flattened parameters (ZeRO stage 1:
    with Adam that cuts the 2x-params state memory by the data-axis size).
    ``opt_state`` leaves are stacked node arrays ``[N, ...]`` over the
    axis, like the EA per-node state."""
    params: PyTree
    model_state: PyTree
    opt_state: PyTree
    sync: Any
    cm: jax.Array
    rng: jax.Array


def _zero_layout(params: PyTree, n: int):
    """(FlatSpec, shard-divisible flat length, per-device chunk)."""
    for leaf in jax.tree_util.tree_leaves(params):
        if jnp.asarray(leaf).dtype != jnp.float32:
            raise ValueError(
                "ZeRO sharding packs params into one f32 buffer; got a "
                f"{jnp.asarray(leaf).dtype} leaf (use build_optax_step for "
                "mixed-dtype trees)")
    spec = flatten_lib.make_spec(params)
    total = ((spec.padded + n - 1) // n) * n
    return spec, total, total // n


def _pack_padded(spec, tree, total: int) -> jax.Array:
    flat = flatten_lib.pack(spec, tree)
    if total > spec.padded:
        flat = jnp.concatenate([flat, jnp.zeros(total - spec.padded,
                                                flat.dtype)])
    return flat


def _check_elementwise(tx, n: int):
    """Probe that ``tx`` commutes with sharding: updating a vector in one
    piece must equal updating its N chunks independently.  Catches
    slice-coupling transforms (e.g. ``clip_by_global_norm``) that would
    otherwise make ZeRO training silently diverge from the replicated-state
    step — each shard would see only its own norm."""
    # Multiple steps with DIRECTION-varying gradients: a one-step probe
    # cannot catch e.g. clip_by_global_norm->adam (adam cancels any
    # per-step uniform scale); across steps the shard-vs-full clip ratios
    # vary and the divergence shows.
    m = 8 * n
    p = jnp.linspace(-1.0, 1.0, m, dtype=jnp.float32)
    gs = [jnp.sin(jnp.arange(m, dtype=jnp.float32) * (0.3 + t))
          * (2.0 + 3.0 * t) for t in range(3)]
    state, pf = tx.init(p), p
    for g in gs:
        u, state = tx.update(g, state, pf)
        pf = pf + u
    shards = []
    for i in range(n):
        sl = slice(i * 8, (i + 1) * 8)
        s, pi = tx.init(p[sl]), p[sl]
        for g in gs:
            u, s = tx.update(g[sl], s, pi)
            pi = pi + u
        shards.append(pi)
    if not jnp.allclose(pf, jnp.concatenate(shards), rtol=1e-6, atol=1e-6):
        raise ValueError(
            "optimizer is not elementwise (its update couples parameter "
            "slices, e.g. a global-norm clip), so ZeRO sharding would "
            "silently change the training math — use build_optax_step")


def init_zero_state(model: Model, tree: MeshTree, tx, key: jax.Array,
                    num_classes: int) -> ZeroTrainState:
    from distlearn_tpu.train.trainer import init_common
    params, mstate, sync, cm, rng = init_common(model, tree, key,
                                                num_classes)
    n = tree.num_nodes
    _check_elementwise(tx, n)
    spec, total, chunk = _zero_layout(params, n)
    slices = _pack_padded(spec, params, total).reshape(n, chunk)
    per_dev = [tx.init(slices[i]) for i in range(n)]
    opt = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_dev)
    return ZeroTrainState(params=params, model_state=mstate,
                          opt_state=tree.put_per_node(opt), sync=sync,
                          cm=cm, rng=rng)


class LMZeroState(NamedTuple):
    """ZeRO-1 state for the LM family.  ``params`` replicated in the model
    dtype (f32 or bf16 — mixed trees allowed); ``master`` is the sharded
    FP32 MASTER COPY of the packed parameters (``[N, chunk]`` over the data
    axis) the optimizer actually updates — the mixed-precision recipe: bf16
    forward/backward, f32 update, params re-materialized from the master
    each step.  ``opt_state`` is the optimizer state over the f32 chunks,
    sharded the same way (ZeRO-1: Adam's 2x-params memory / N, plus the
    1x f32 master / N)."""
    params: PyTree
    master: jax.Array
    opt_state: PyTree


def _lm_zero_layout(params: PyTree, n: int):
    for leaf in jax.tree_util.tree_leaves(params):
        dt = getattr(leaf, "dtype", None) or jnp.asarray(leaf).dtype
        if not jnp.issubdtype(dt, jnp.floating):
            raise ValueError(
                f"ZeRO master copy requires floating leaves, got {dt}")
    spec = flatten_lib.make_spec(params)
    total = ((spec.padded + n - 1) // n) * n
    return spec, total, total // n


def init_lm_zero_state(params: PyTree, tree: MeshTree, tx) -> LMZeroState:
    """Shard the f32 master + optimizer state over the data axis.  ``tx``
    must be elementwise (same probe as :func:`init_zero_state`)."""
    n = tree.num_nodes
    _check_elementwise(tx, n)
    spec, total, chunk = _lm_zero_layout(params, n)
    slices = _pack_padded(spec, params, total).reshape(n, chunk)
    per_dev = [tx.init(slices[i]) for i in range(n)]
    opt = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_dev)
    return LMZeroState(params=params,
                       master=tree.put_per_node(slices),
                       opt_state=tree.put_per_node(opt))


def build_lm_zero_step(model: Model, tree: MeshTree, tx,
                       moe_balance_weight: float = 0.0,
                       donate: bool = True) -> Callable:
    """ZeRO-1 train step for the transformer-LM family:
    ``step(st, tokens) -> (st, loss)`` over the data mesh axis.

    Same comm recipe as :func:`build_zero_optax_step` — pack local grads
    flat (cast f32), **reduce-scatter** so each device receives only the
    summed 1/N chunk its optimizer state covers, sliced elementwise
    ``tx.update`` against the sharded F32 MASTER slice, one tiled
    ``all_gather`` re-materializes the replicated params — applied to the
    model family where optimizer-state memory actually matters, with
    mixed-precision support the classifier variant rejects: bf16 (or
    mixed) param trees train against f32 master copies, cut N-ways across
    the axis.  Data parallelism only on this builder; the TP/SP-composed
    variant over a (data, seq, model) mesh is
    :func:`build_lm_zero_mesh_step`.  From the reference's
    viewpoint this is the ``optim``-slot upgrade of lua/AllReduceSGD.lua's
    hot loop: allreduce-equivalent bandwidth, state memory / N.
    """
    from distlearn_tpu.models.transformer import lm_loss
    axis = tree.axis_name
    n = tree.num_nodes

    def step(st: LMZeroState, tokens):
        spec, total, chunk = _lm_zero_layout(st.params, n)
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(model, p, tokens, seq_axis=None, tp_axis=None,
                              moe_balance_weight=moe_balance_weight)
            )(st.params)
        gslice = lax.psum_scatter(
            _pack_padded(spec, grads, total), axis,
            scatter_dimension=0, tiled=True) / jnp.float32(n)
        master_local = jnp.squeeze(st.master, 0)          # [chunk] f32
        opt_local = mesh_lib.squeeze_node(st.opt_state)
        updates, opt_local = tx.update(gslice, opt_local, master_local)
        master_local = master_local + updates
        flat_new = lax.all_gather(master_local, axis, tiled=True)  # [total]
        params = flatten_lib.unpack(spec, flat_new)   # casts to leaf dtypes
        return (LMZeroState(params, master_local[None],
                            mesh_lib.expand_node(opt_local)),
                lax.pmean(loss, axis))

    specs = LMZeroState(params=P(), master=P(axis), opt_state=P(axis))
    mapped = shard_map(step, mesh=tree.mesh, in_specs=(specs, P(axis)),
                           out_specs=(specs, P()), check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


class LMOptaxState(NamedTuple):
    """Replicated-state optax training for the LM family."""
    params: PyTree
    opt_state: PyTree


def build_lm_optax_step(model: Model, mesh, tx,
                        data_axis: str = "data",
                        seq_axis: str | None = "seq",
                        accum_steps: int = 1,
                        moe_balance_weight: float = 0.0,
                        donate: bool = True,
                        seq_layout: str = "contig") -> Callable:
    """Any optax optimizer on the transformer-LM family over a
    ``(data, seq)`` mesh: ``step(st, tokens) -> (st, loss)`` with
    ``st = LMOptaxState(params, opt_state)``, both replicated (every
    replica applies the identical psum'd gradient, so the state stays
    bitwise-replicated — the ``build_optax_step`` recipe on the model
    family the reference never had).  Initialize with
    ``LMOptaxState(params, tx.init(params))``.

    Tensor-parallel or expert-sharded leaves would need sharded optimizer
    state; pass ``tp_axis`` work to :func:`build_lm_zero_mesh_step`
    (sharded f32 masters) instead — this builder rejects nothing because
    it simply never shards params.  MoE models run with all experts
    resident (``ep_axis=None``); ``moe_balance_weight`` folds the Switch
    auxiliary loss in.  ``accum_steps`` microbatches the per-device rows
    exactly as :func:`distlearn_tpu.train.lm.build_lm_step` does.
    """
    from distlearn_tpu.train.lm import lm_local_grads
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    axes = tuple(a for a in (data_axis, seq_axis) if a is not None)

    def step(st: LMOptaxState, tokens):
        local_loss, grads = lm_local_grads(
            model, st.params, tokens, seq_axis=seq_axis, tp_axis=None,
            accum_steps=accum_steps,
            moe_balance_weight=moe_balance_weight, seq_layout=seq_layout)
        loss = lax.psum(local_loss, seq_axis) if seq_axis else local_loss
        dp = lax.psum(1, data_axis)
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g, axes) / jnp.asarray(dp, g.dtype), grads)
        updates, opt_state = tx.update(grads, st.opt_state, st.params)
        params = jax.tree_util.tree_map(
            lambda p, u: p + u.astype(p.dtype), st.params, updates)
        return (LMOptaxState(params, opt_state),
                lax.pmean(loss, data_axis))

    tok_spec = P(data_axis, seq_axis) if seq_axis else P(data_axis)
    spec = LMOptaxState(params=P(), opt_state=P())
    mapped = shard_map(step, mesh=mesh, in_specs=(spec, tok_spec),
                           out_specs=(spec, P()), check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


class LMMixedOptaxState(NamedTuple):
    """Mixed-precision optax LM training: bf16 working ``params`` (what
    the matmuls read), f32 ``master`` (what the optimizer walks), and the
    optimizer state over the master (see
    :class:`distlearn_tpu.train.lm.LMMixedState` for the traffic
    analysis)."""
    params: PyTree
    master: PyTree
    opt_state: PyTree


def init_lm_mixed_optax_state(params, tx,
                              param_dtype=jnp.bfloat16
                              ) -> LMMixedOptaxState:
    """Master := the f32 init, working copy := its cast, optimizer state
    over the MASTER (moments accumulate in f32)."""
    cast = jax.tree_util.tree_map(lambda p: p.astype(param_dtype), params)
    return LMMixedOptaxState(params=cast, master=params,
                             opt_state=tx.init(params))


def build_lm_mixed_optax_step(model: Model, mesh, tx,
                              data_axis: str = "data",
                              seq_axis: str | None = "seq",
                              accum_steps: int = 1,
                              moe_balance_weight: float = 0.0,
                              grad_dtype=jnp.float32,
                              donate: bool = True,
                              seq_layout: str = "contig") -> Callable:
    """:func:`build_lm_optax_step` with bf16 working params + f32 masters
    (``step(st, tokens) -> (st, loss)`` on :class:`LMMixedOptaxState`):
    gradients come off the bf16-param backward, are upcast to
    ``grad_dtype`` for the cross-replica psum, feed ``tx.update`` against
    the f32 master, and the new master re-casts into the working copy —
    the f32 elementwise traffic is confined to the optimizer itself while
    every matmul pass reads 2-byte weights.  Initialize with
    :func:`init_lm_mixed_optax_state`."""
    from distlearn_tpu.train.lm import lm_local_grads
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    axes = tuple(a for a in (data_axis, seq_axis) if a is not None)

    def step(st: LMMixedOptaxState, tokens):
        local_loss, grads = lm_local_grads(
            model, st.params, tokens, seq_axis=seq_axis, tp_axis=None,
            accum_steps=accum_steps,
            moe_balance_weight=moe_balance_weight, seq_layout=seq_layout)
        loss = lax.psum(local_loss, seq_axis) if seq_axis else local_loss
        dp = lax.psum(1, data_axis)
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g.astype(grad_dtype), axes)
            / jnp.asarray(dp, grad_dtype), grads)
        updates, opt_state = tx.update(grads, st.opt_state, st.master)
        master = jax.tree_util.tree_map(
            lambda m, u: m + u.astype(m.dtype), st.master, updates)
        params = jax.tree_util.tree_map(
            lambda p, m: m.astype(p.dtype), st.params, master)
        return (LMMixedOptaxState(params, master, opt_state),
                lax.pmean(loss, data_axis))

    tok_spec = P(data_axis, seq_axis) if seq_axis else P(data_axis)
    spec = LMMixedOptaxState(params=P(), master=P(), opt_state=P())
    mapped = shard_map(step, mesh=mesh, in_specs=(spec, tok_spec),
                           out_specs=(spec, P()), check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def fsdp_param_specs(params: PyTree, mesh,
                     data_axis: str = "data") -> PyTree:
    """ZeRO-3 / FSDP shardings: every leaf sharded over ``data_axis``
    along its LARGEST evenly-divisible dimension (balanced slices);
    leaves with no divisible dimension stay replicated.  Unlike
    :func:`distlearn_tpu.models.transformer.param_specs` (which encodes
    the TP/EP math), these specs carry no algebra — they are pure
    storage partitioning for the compiler-driven composition below."""
    n = mesh.shape[data_axis]

    def spec_for(leaf):
        shape = tuple(jnp.shape(leaf))
        for i, _ in sorted(enumerate(shape), key=lambda t: -t[1]):
            if shape[i] >= n and shape[i] % n == 0:
                return P(*([None] * i + [data_axis]))
        return P()

    return jax.tree_util.tree_map(spec_for, params)


def init_lm_fsdp_params(params: PyTree, mesh,
                        data_axis: str = "data") -> PyTree:
    """Place params fully sharded (1/N of the model resident per device
    for every divisible leaf) for :func:`build_lm_fsdp_step`."""
    from jax.sharding import NamedSharding
    return jax.device_put(params, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        fsdp_param_specs(params, mesh, data_axis)))


def build_lm_fsdp_step(model: Model, mesh, params_template, lr: float,
                       data_axis: str = "data", accum_steps: int = 1,
                       donate: bool = True) -> Callable:
    """Fully-sharded data parallelism (ZeRO-3) for the LM family —
    ``step(params, tokens) -> (params, loss)`` with parameters LIVING
    sharded over the data axis, completing the ZeRO ladder next to the
    ZeRO-1 builders (sharded optimizer state, replicated params).

    This is deliberately the OTHER TPU idiom from the shard_map
    builders: a plain ``jit`` over the GLOBAL computation with sharding
    annotations on inputs/outputs and ``with_sharding_constraint`` on
    gradients/updates — XLA's SPMD partitioner inserts the weight
    all-gathers before each use (forward and backward), reduce-scatters
    each gradient back to its owner shard, and runs the update on the
    local 1/N slice.  Annotate, let the compiler place collectives —
    the composition recipe the explicit-collective builders complement.
    Batch semantics match ``build_lm_step`` at ``sp=tp=1``: the global
    batch shards over ``data_axis`` and the loss is the global mean, so
    the two steps are numerically interchangeable (tested).

    ``accum_steps=k`` scans k equal microbatches of the global batch
    and averages — the same memory lever (and exact-equivalence
    semantics) as ``build_lm_step``'s.  Dense models; place params with
    :func:`init_lm_fsdp_params`."""
    from jax.sharding import NamedSharding
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    specs = fsdp_param_specs(params_template, mesh, data_axis)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs)
    tok_sharding = NamedSharding(mesh, P(data_axis))
    from distlearn_tpu.models.transformer import lm_loss as _lm_loss

    def loss_and_grads(params, tokens):
        if accum_steps == 1:
            return jax.value_and_grad(
                lambda p: _lm_loss(model, p, tokens))(params)
        if tokens.shape[0] % accum_steps:
            raise ValueError(
                f"global batch {tokens.shape[0]} not divisible by "
                f"accum_steps={accum_steps}")
        micro = tokens.reshape((accum_steps, -1) + tokens.shape[1:])

        def body(carry, toks):
            acc_l, acc_g = carry
            li, gi = jax.value_and_grad(
                lambda p: _lm_loss(model, p, toks))(params)
            return (acc_l + li,
                    jax.tree_util.tree_map(jnp.add, acc_g, gi)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        (l, g), _ = lax.scan(body, (jnp.zeros((), jnp.float32), zero),
                             micro)
        # equal microbatches: the mean of per-micro means IS the global
        # mean, and likewise for the gradients
        return (l / jnp.float32(accum_steps),
                jax.tree_util.tree_map(
                    lambda x: x / jnp.asarray(accum_steps, x.dtype), g))

    def step(params, tokens):
        loss, grads = loss_and_grads(params, tokens)
        # the ONE load-bearing constraint: gradients owned shard-wise
        # forces GSPMD's reduce-scatter here and a sharded update below
        # (out_shardings pins the returned params' layout)
        grads = jax.lax.with_sharding_constraint(grads, shardings)
        new = jax.tree_util.tree_map(
            lambda p, g: p - jnp.asarray(lr, p.dtype) * g.astype(p.dtype),
            params, grads)
        return new, loss

    return jax.jit(step, in_shardings=(shardings, tok_sharding),
                   out_shardings=(shardings, NamedSharding(mesh, P())),
                   donate_argnums=(0,) if donate else ())


def _local_template(params: PyTree, pspecs: PyTree, mesh) -> PyTree:
    """ShapeDtypeStructs of each leaf's LOCAL shard under ``pspecs``."""
    def shrink(leaf, spec):
        shape = list(jnp.shape(leaf))
        for i, ax in enumerate(tuple(spec)):
            if ax is not None:
                axes = (ax,) if isinstance(ax, str) else tuple(ax)
                for a in axes:
                    shape[i] //= mesh.shape[a]
        return jax.ShapeDtypeStruct(tuple(shape),
                                    jnp.asarray(leaf).dtype)
    return jax.tree_util.tree_map(shrink, params, pspecs)


def init_lm_zero_mesh_state(params, mesh, tx, data_axis: str = "data",
                            tp_axis: str | None = "model") -> LMZeroState:
    """ZeRO-1 state over a multi-axis mesh: the f32 master + optimizer
    state cover each device's LOCAL (TP-sharded) parameters, cut
    ``data``-ways across the data axis — ZeRO composed with tensor (and
    sequence) parallelism.  ``params`` must already be placed with
    :func:`distlearn_tpu.models.transformer.param_specs` shardings.
    Master layout: ``[n_data, n_tp, chunk]`` sharded ``P(data, tp)`` —
    unspecified mesh axes (e.g. seq) are replicated, so no seq argument
    is needed here; every seq rank holds and updates the same slice.
    """
    from distlearn_tpu.models.transformer import param_specs
    n = mesh.shape[data_axis]
    _check_elementwise(tx, n)
    pspecs = param_specs(params, tp_axis)
    local_t = _local_template(params, pspecs, mesh)
    spec, total, chunk = _lm_zero_layout(local_t, n)

    def init(params_local):
        flat = _pack_padded(spec, params_local, total)
        my = lax.axis_index(data_axis)
        mine = lax.dynamic_slice_in_dim(flat, my * chunk, chunk)
        opt = tx.init(mine)
        exp = lambda a: jnp.asarray(a)[None, None]      # noqa: E731
        return (exp(mine),
                jax.tree_util.tree_map(exp, opt))

    out_spec = P(data_axis, tp_axis) if tp_axis else P(data_axis, None)
    master, opt = jax.jit(shard_map(
        init, mesh=mesh, in_specs=(pspecs,),
        out_specs=(out_spec,
                   jax.tree_util.tree_map(lambda _: out_spec,
                                          tx.init(jnp.zeros((chunk,),
                                                            jnp.float32)))),
        check_vma=False))(params)
    return LMZeroState(params=params, master=master, opt_state=opt)


def build_lm_zero_mesh_step(model: Model, mesh, params_template, tx,
                            data_axis: str = "data",
                            seq_axis: str | None = "seq",
                            tp_axis: str | None = "model",
                            moe_balance_weight: float = 0.0,
                            donate: bool = True) -> Callable:
    """ZeRO-1 LM step composed with tensor + sequence parallelism over a
    ``(data, seq, model)`` mesh: ``step(st, tokens) -> (st, loss)``.

    Per device: grads of the local loss share (ring attention over
    ``seq_axis``, Megatron TP over ``tp_axis`` — the
    :func:`build_lm_step` math), packed flat in f32; the seq-axis psum
    runs on the packed buffer (every leaf — TP shards included — reduces
    over seq exactly as in ``build_lm_step``), the data-axis reduction is
    the ZeRO **reduce-scatter**, the sliced elementwise update runs
    against the sharded f32 master, and one data-axis ``all_gather``
    re-materializes the local params.  Optimizer-state memory: local
    params (already /TP for the sharded leaves) further cut /data.
    MoE/EP is not supported here (expert leaves must not reduce over
    their own axis); use :func:`build_lm_step` for MoE models.
    """
    from distlearn_tpu.models.transformer import lm_loss, param_specs
    n = mesh.shape[data_axis]
    pspecs = param_specs(params_template, tp_axis)
    local_t = _local_template(params_template, pspecs, mesh)
    spec, total, chunk = _lm_zero_layout(local_t, n)

    def step(st: LMZeroState, tokens):
        params = st.params
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(model, p, tokens, seq_axis=seq_axis,
                              tp_axis=tp_axis, reduce=False,
                              moe_balance_weight=moe_balance_weight)
            )(params)
        loss = lax.psum(loss, seq_axis) if seq_axis else loss
        flat = _pack_padded(spec, grads, total)
        if seq_axis:
            flat = lax.psum(flat, seq_axis)
        gslice = lax.psum_scatter(flat, data_axis, scatter_dimension=0,
                                  tiled=True) / jnp.float32(n)
        master_local = jnp.squeeze(st.master, (0, 1))     # [chunk] f32
        opt_local = jax.tree_util.tree_map(
            lambda a: jnp.squeeze(a, (0, 1)), st.opt_state)
        updates, opt_local = tx.update(gslice, opt_local, master_local)
        master_local = master_local + updates
        flat_new = lax.all_gather(master_local, data_axis, tiled=True)
        new_params = flatten_lib.unpack(spec, flat_new)
        exp = lambda a: jnp.asarray(a)[None, None]        # noqa: E731
        return (LMZeroState(new_params, exp(master_local),
                            jax.tree_util.tree_map(exp, opt_local)),
                lax.pmean(loss, data_axis))

    zspec = P(data_axis, tp_axis) if tp_axis else P(data_axis, None)
    st_spec = LMZeroState(
        params=pspecs, master=zspec,
        opt_state=jax.tree_util.tree_map(
            lambda _: zspec, tx.init(jnp.zeros((chunk,), jnp.float32))))
    tok_spec = P(data_axis, seq_axis) if seq_axis else P(data_axis)
    mapped = shard_map(step, mesh=mesh, in_specs=(st_spec, tok_spec),
                           out_specs=(st_spec, P()), check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def build_zero_optax_step(model: Model, tree: MeshTree, tx,
                          donate: bool = True) -> Callable:
    """ZeRO-1 fused step: ``step(ts, x, y) -> (ts, loss)``.

    Comm structure (the ZeRO-1 recipe): local gradients are packed flat
    and **reduce-scattered** — each device receives only the summed 1/N
    chunk its optimizer state covers (~P bytes over the ring vs ~2P for
    the non-sharded path's full allreduce) — the sliced elementwise
    ``tx.update`` runs against the sharded state, and ONE tiled
    ``all_gather`` reassembles the updated parameters (replicated again
    for the next step).  Net: allreduce-equivalent bandwidth
    (reduce-scatter + all-gather) with the optimizer-state memory cut by
    N.  Restricted to ELEMENTWISE optimizers (adam, momentum, rmsprop...):
    a transform that couples slices, e.g. ``clip_by_global_norm``, would
    see only its shard's norm.  Full participation each step (uneven-step
    accounting keeps the reference cadence via the sync counter).
    """
    axis = tree.axis_name
    n = tree.num_nodes

    def step(ts: ZeroTrainState, x, y):
        spec, total, chunk = _zero_layout(ts.params, n)
        rng, dropout_rng = random.split(ts.rng)
        dropout_rng = random.fold_in(dropout_rng, lax.axis_index(axis))

        def _loss(p):
            return loss_fn(model, p, ts.model_state, x, y, train=True,
                           rng=dropout_rng, axis_name=axis)

        (loss, (log_probs, mstate)), grads = \
            jax.value_and_grad(_loss, has_aux=True)(ts.params)
        sync_local = mesh_lib.squeeze_node(ts.sync)
        sync_local = allreduce_sgd.SGDSyncState(
            my_steps=sync_local.my_steps + 1)

        # reduce-scatter the packed LOCAL grads: arrives pre-sliced +
        # summed; normalize by the (full-participation) node count
        my = lax.axis_index(axis)
        gslice = lax.psum_scatter(
            _pack_padded(spec, grads, total), axis,
            scatter_dimension=0, tiled=True) / jnp.float32(n)
        pslice = lax.dynamic_slice_in_dim(
            _pack_padded(spec, ts.params, total), my * chunk, chunk)
        opt_local = mesh_lib.squeeze_node(ts.opt_state)
        updates, opt_local = tx.update(gslice, opt_local, pslice)
        new_slice = pslice + updates
        flat_new = lax.all_gather(new_slice, axis, tiled=True)   # [total]
        params = flatten_lib.unpack(spec, flat_new)

        cm_new = metrics_lib.update_confusion(jnp.squeeze(ts.cm, 0),
                                              log_probs, y)
        new_ts = ZeroTrainState(params, mstate,
                                mesh_lib.expand_node(opt_local),
                                mesh_lib.expand_node(sync_local),
                                cm_new[None], rng)
        return new_ts, lax.pmean(loss, axis)

    specs = ZeroTrainState(params=P(), model_state=P(), opt_state=P(axis),
                           sync=P(axis), cm=P(axis), rng=P())
    mapped = shard_map(step, mesh=tree.mesh, in_specs=(specs, P(axis),
                                                           P(axis)),
                           out_specs=(specs, P()), check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())
