"""The next-token cross-entropy of a language model's head as one
``jax.custom_vjp`` over the logits, with its backward written out.

Per position ``i`` of the logits ``x`` [B, L, V], its target ``t_i`` and a
weight ``w_i`` (0 where a position has no target):

    lse_i  = log sum_v exp(x_iv)                (float32, from the row's max)
    loss   = sum_i w_i (lse_i - x_i,t_i)
    dx_iv  = g w_i (exp(x_iv - lse_i) - [v == t_i])

The forward reads the logits and converts them to float32 as it goes: no
log-probability array is written, and what the backward keeps is the logits
as given and ``lse`` [B, L].  The backward is one elementwise pass that
returns the cotangent in the logits' dtype, so the compiler fuses it with
the model's cast and the head's products read one array written once.  The
weights replace slicing the logits: a position without a target counts
nothing, and no padded cotangent is made.

Two paths (:func:`select_xent`, from the backend alone): ``"fused"``, this
module, on the TPU; ``"xla"`` elsewhere, ``jax.nn.log_softmax`` and a gather
in ``models/transformer.py``, whose programs stay as they were."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32


def select_xent(backend: str) -> str:
    """The path of the head's cross-entropy: ``"fused"`` (this module's
    :func:`weighted_xent`) on the TPU, ``"xla"`` on every other backend."""
    return "fused" if backend == "tpu" else "xla"


def _hit(x, targets):
    """``[v == t_i]`` over the logits ``x`` [B, L, V]: the targets' one-hot
    mask, as a comparison against the vocabulary's iota that XLA fuses."""
    return lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) \
        == targets[..., None]


def _target_logit(x, targets):
    """``x[..., targets]`` [B, L] in float32, as a masked sum over the
    vocabulary: a reduction XLA fuses beside the row's max, where a gather
    would read the rows one element at a time."""
    return jnp.sum(jnp.where(_hit(x, targets), x.astype(_F32), 0.0), axis=-1)


def _lse(x):
    """``log sum exp`` over the last axis of ``x``, in float32."""
    x = x.astype(_F32)
    m = jnp.max(x, axis=-1)
    return m + jnp.log(jnp.sum(jnp.exp(x - m[..., None]), axis=-1))


@jax.custom_vjp
def weighted_xent(logits, targets, w):
    """``sum(w * (lse - logits[target]))`` over every position of
    ``logits`` [B, L, V] (any float dtype), ``targets`` [B, L] int and the
    float32 weights ``w`` [B, L]; float32.  Gradient for ``logits`` only."""
    return _fwd(logits, targets, w)[0]


def _fwd(logits, targets, w):
    lse = _lse(logits)
    loss = jnp.sum(w * (lse - _target_logit(logits, targets)))
    return loss, (logits, targets, w, lse)


def _bwd(res, g):
    logits, targets, w, lse = res
    p = jnp.exp(logits.astype(_F32) - lse[..., None])
    d = (g * w)[..., None] * (p - _hit(logits, targets).astype(_F32))
    return d.astype(logits.dtype), None, None


weighted_xent.defvjp(_fwd, _bwd)
