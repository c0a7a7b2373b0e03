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
