"""Model container: the functional equivalent of the reference's
``{params, f, df}`` export (examples/Model.lua:81-85).

A :class:`Model` bundles ``init`` (params + mutable state from a PRNG key) and
``apply`` (pure forward).  ``loss_fn`` mirrors the reference's ``f`` returning
``(loss, prediction)`` (examples/Model.lua:57-61); gradients come from
``jax.value_and_grad`` — the ``df = grad(f, ...)`` equivalent, with
``stableGradients`` buffer pinning unnecessary under XLA's functional model.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from distlearn_tpu.models import nn
from distlearn_tpu.parallel.mesh import StagedSum
from distlearn_tpu.parallel.sequence import ATTN_RESIDUALS

PyTree = Any

#: The ``jax.named_scope`` names that model and train-step code give the
#: device work (docs/OBSERVABILITY.md "device time by model part").  Flat
#: siblings, never nested: an instruction's ``op_name`` carries at most
#: one of them.  Forward / backward / recomputation are NOT scopes — JAX
#: marks those itself (``jvp(``, ``transpose(``, ``rematted_computation``).
#: The one list: trace readers import it, nothing else spells the names.
#: ``linattn_core`` (a linear-attention layer's convolution, normalisation,
#: decay and chunked delta rule) and ``moe`` (router, grouping, the held
#: experts' grouped products, combine) are ``models/hybrid.py``'s; the
#: dense :func:`~distlearn_tpu.models.transformer.transformer_lm` uses the
#: list less those two.
SCOPES = ("embed", "norm", "attn_proj", "attn_core", "mlp", "head_loss",
          "grad_reduce", "update", "linattn_core", "moe")


def checkpoint_block(fn: Callable) -> Callable:
    """``fn`` as one rematerialised block — what ``remat="full"`` and the
    pipeline builders' ``remat=True`` mean, in the one place that says it.

    The checkpoint keeps the block's input and, where the blockwise
    attention kernel ran inside it, the two results that kernel names
    (:data:`~distlearn_tpu.parallel.sequence.ATTN_RESIDUALS`: its output,
    ``[tokens, dim]`` in the compute dtype like the input, and its float32
    log-sum-exp ``[B, H, L]``); everything else is computed again in the
    backward pass.  Without them the backward pass would run the whole
    forward kernel a second time only to hand its own backward call those
    two arrays (27.5 ms of a 412.6 ms step at GPT-2-large's sizes: PERF.md
    section 6, PR 30).  A block on the full-square path names nothing, so
    its checkpoint holds the input alone, as a bare ``jax.checkpoint``
    does: what is kept follows from the path the attention call took."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(
            ATTN_RESIDUALS))


def scan_reducing(block: Callable, x: jax.Array, blocks: PyTree,
                  axis_name: str) -> jax.Array:
    """``lax.scan(lambda h, b: (block(b, h), None), x, blocks)[0]`` — the
    depth loop over stacked ``blocks`` — whose backward pass hands back the
    cotangent of ``blocks`` already SUMMED over ``axis_name``, the sum made
    behind the layers that follow instead of after the loop.

    The backward loop is ours.  Each iteration runs one layer's pullback
    (``block``'s own: a :func:`checkpoint_block` keeps and recomputes what it
    does anywhere else) and carries the gradient that layer made, as it
    was made, into the next iteration, which packs it first of all and
    moves it and the layers before it one stage on through a
    :class:`~distlearn_tpu.parallel.mesh.StagedSum`: every transfer of an
    iteration reads the carry alone, so it starts at the top of the
    iteration and is awaited at its bottom, with the layer's whole backward
    between.  A layer's gradient lands in the stack ``log2(n) + 1``
    iterations after the one that made it (2.75 gradients are held in
    flight at ``n = 4``); the last ones are finished after the loop, where
    nothing is left to hide behind.  The axis size must be a power of two,
    ``blocks`` of one dtype and every leaf cut by the axis size — the
    caller's to check (``train/lm.py::build_lm_step``)."""
    depth = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    with jax.named_scope("grad_reduce"):
        reduce = StagedSum(jax.tree_util.tree_map(lambda a: a[0], blocks),
                           axis_name)
    pulls = []        # the pullback's tree, from the forward trace

    @jax.custom_vjp
    def run(blocks, x):
        return lax.scan(lambda h, b: (block(b, h), None), x, blocks)[0]

    def forward(blocks, x):
        def body(h, b):
            y, pull = jax.vjp(block, b, h)
            kept, tree = jax.tree_util.tree_flatten(pull)
            given = jax.tree_util.tree_leaves(b)
            # a residual that IS a leaf of this layer's parameters is read
            # from the stack again, not stacked a second time
            source = [next((j for j, g in enumerate(given) if g is k), None)
                      for k in kept]
            pulls[:] = [(tree, source)]
            return y, [k for k, j in zip(kept, source) if j is None]
        y, kept = lax.scan(body, x, blocks)
        return y, (blocks, kept)

    def backward(residuals, dy):
        blocks, kept = residuals
        tree, source = pulls[0]

        def body(carry, layer):
            dh, made, slots, stack = carry
            i, b, kept_i = layer
            with jax.named_scope("grad_reduce"):
                # the gradient the iteration before made is packed before
                # anything of this layer runs: its buffer is free again by
                # the time this layer's gradient is written
                dh, slots[0] = lax.optimization_barrier(
                    (dh, reduce.enter(made)))
                slots, stack = reduce.advance(slots, stack,
                                              i + reduce.stages)
            given, kept_i = jax.tree_util.tree_leaves(b), iter(kept_i)
            pull = jax.tree_util.tree_unflatten(
                tree, [next(kept_i) if j is None else given[j]
                       for j in source])
            db, dh = pull(dh)
            return (dh, db, slots, stack), None

        with jax.named_scope("grad_reduce"):
            slots = reduce.empty()
            start = (dy, lax.optimization_barrier(jax.tree_util.tree_map(
                lambda a: jnp.zeros_like(a[0]), blocks)), slots,
                     jax.tree_util.tree_map(jnp.zeros_like, blocks))
        (dx, made, slots, stack), _ = lax.scan(
            body, start, (jnp.arange(depth), blocks, kept), reverse=True)
        # what is still in flight: round r finishes layer ``stages - 1 - r``
        with jax.named_scope("grad_reduce"):
            slots[0] = reduce.enter(made)
            for r in range(reduce.stages):
                row = reduce.stages - 1 - r
                slots, stack = reduce.advance(
                    slots, stack, row if row < depth else None, first=r)
        return stack, dx

    run.defvjp(forward, backward)
    return run(blocks, x)


class Model(NamedTuple):
    """``init(key) -> (params, state)``;
    ``apply(params, state, x, train, rng, axis_name) -> (logits, new_state)``.

    ``state`` carries batch-norm running stats (empty dict when none);
    ``axis_name`` enables cross-replica (sync) batchnorm statistics.
    """
    init: Callable[..., tuple[PyTree, PyTree]]
    apply: Callable[..., tuple[jax.Array, PyTree]]
    name: str
    input_shape: tuple[int, ...]   # per-example, e.g. (32, 32, 1)
    num_classes: int


def loss_fn(model: Model, params: PyTree, state: PyTree, x, y,
            train: bool = True, rng=None, axis_name: str | None = None,
            bn_weight=None):
    """NLL loss over log-softmax outputs (ref examples/Model.lua:50-61).

    Returns ``(loss, (log_probs, new_state))`` — shaped for
    ``jax.value_and_grad(..., has_aux=True)``.
    """
    log_probs, new_state = model.apply(params, state, x, train=train, rng=rng,
                                       axis_name=axis_name, bn_weight=bn_weight)
    loss = nn.nll_loss(log_probs, y)
    return loss, (log_probs, new_state)


def param_count(params: PyTree) -> int:
    return sum(int(jnp.size(p)) for p in jax.tree_util.tree_leaves(params))
