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

from distlearn_tpu.models import nn
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
