"""Pallas TPU kernels for the hot elementwise updates.

Two fused updates (the framework's per-step HBM-bound tail after the
matmul-heavy backward pass):

* :func:`fused_sgd` — ``p' = p - lr * g`` over the packed flat buffer:
  one kernel launch for the whole model instead of one XLA op per leaf.

* :func:`fused_elastic` — the EASGD local move (lua/AllReduceEA.lua:35-39,
  lua/AllReduceEA.md:12-24): ``delta = (p - c) * alpha; p' = p - delta``
  producing both outputs in a single pass over HBM (p and c are each read
  once; p' and delta written once — the minimum possible traffic for the
  round's local math; the psum of delta and the center add ride on XLA
  around the kernel).

On non-TPU backends the kernels run in Pallas interpret mode, so tests and
the CPU mesh exercise the identical code path.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distlearn_tpu.ops import flatten as flatten_lib
from distlearn_tpu.ops.flatten import LANE

PyTree = Any


def fused_enabled(override: bool | None = None) -> bool:
    """Resolve whether trainers take the fused-kernel path.

    The explicit ``override`` when given, else on a TPU and off elsewhere
    (interpret-mode Pallas on CPU is correct but slower than XLA's own
    fusion, so it is opt-in there)."""
    if override is not None:
        return bool(override)
    return jax.default_backend() == "tpu"


_BLOCK_ROWS = 256  # rows of 128 lanes per grid step (128 KiB f32 per ref)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _grid_for(n: int) -> tuple[int, tuple[int, int]]:
    """``(grid, block)`` over the ``[n // LANE, LANE]`` view.  The block is
    the whole array when it is short, else ``_BLOCK_ROWS`` rows — a
    multiple of every dtype's sublane tile (8 f32 / 16 bf16) — with the
    trailing partial block masked by Pallas.  (Shrinking the block until
    it divided the row count made the grid explode on awkward sizes: the
    dim-4096 LM's 7,373,728 rows ran as 230,429 32-row steps.)"""
    rows = n // LANE
    block_rows = min(_BLOCK_ROWS, rows)
    return pl.cdiv(rows, block_rows), (block_rows, LANE)


def _sgd_kernel(lr: float, p_ref, g_ref, o_ref):
    p = p_ref[:]
    o_ref[:] = p - jnp.asarray(lr, p.dtype) * g_ref[:].astype(p.dtype)


@functools.partial(jax.jit, static_argnames=("lr",))
def fused_sgd(p_flat: jax.Array, g_flat: jax.Array, lr: float) -> jax.Array:
    """One-launch SGD over packed params (shape [padded], padded % 1024 == 0)."""
    n = p_flat.shape[0]
    grid, block = _grid_for(n)
    shape2d = (n // LANE, LANE)
    spec = pl.BlockSpec(block, lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_sgd_kernel, lr),
        out_shape=jax.ShapeDtypeStruct(shape2d, p_flat.dtype),
        grid=(grid,),
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=_interpret(),
    )(p_flat.reshape(shape2d), g_flat.reshape(shape2d))
    return out.reshape(n)


def _elastic_kernel(alpha: float, p_ref, c_ref, o_ref, d_ref):
    p = p_ref[:]
    d = (p - c_ref[:].astype(p.dtype)) * jnp.asarray(alpha, p.dtype)
    d_ref[:] = d
    o_ref[:] = p - d


@functools.partial(jax.jit, static_argnames=("alpha",))
def fused_elastic(p_flat: jax.Array, c_flat: jax.Array, alpha: float
                  ) -> tuple[jax.Array, jax.Array]:
    """One-launch elastic move: returns ``(new_p, delta)`` (both [padded])."""
    n = p_flat.shape[0]
    grid, block = _grid_for(n)
    shape2d = (n // LANE, LANE)
    spec = pl.BlockSpec(block, lambda i: (i, 0))
    new_p, delta = pl.pallas_call(
        functools.partial(_elastic_kernel, alpha),
        out_shape=(jax.ShapeDtypeStruct(shape2d, p_flat.dtype),
                   jax.ShapeDtypeStruct(shape2d, p_flat.dtype)),
        grid=(grid,),
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        interpret=_interpret(),
    )(p_flat.reshape(shape2d), c_flat.reshape(shape2d))
    return new_p.reshape(n), delta.reshape(n)


# ---------------------------------------------------------------------------
# Pytree-level wrappers over bucketed flat buffers (trainer hot path)
# ---------------------------------------------------------------------------

def sgd_update_buckets(spec: flatten_lib.BucketSpec,
                       params: PyTree, grad_flats: list[jax.Array],
                       lr: float) -> PyTree:
    """Apply ``p' = p - lr*g`` where gradients are already packed (post-psum)
    flat buckets; params are packed, updated by one kernel launch per bucket,
    and unpacked.  Replaces the reference's per-tensor walkTable update loop
    (examples/mnist.lua:112-116) with a few large streaming passes."""
    p_flats = flatten_lib.pack_buckets(spec, params)
    new = [fused_sgd(p, g, lr) for p, g in zip(p_flats, grad_flats)]
    return flatten_lib.unpack_buckets(spec, new)


def elastic_round_buckets(params: PyTree, center: PyTree, alpha: float,
                          axis_name: str,
                          max_bucket_bytes: int | None = None
                          ) -> tuple[PyTree, PyTree]:
    """The full EASGD round (lua/AllReduceEA.lua:35-45) on flat buckets:
    one fused kernel produces (p', delta) per bucket, ONE psum per bucket
    reduces the deltas (vs one per leaf), center moves on the flat buffer.
    Returns ``(new_params, new_center)``."""
    from jax import lax
    spec = flatten_lib.make_bucket_spec(params, max_bucket_bytes)
    p_flats = flatten_lib.pack_buckets(spec, params)
    c_flats = flatten_lib.pack_buckets(spec, center)
    new_p, new_c = [], []
    for p, c in zip(p_flats, c_flats):
        np_, d = fused_elastic(p, c, alpha)
        new_p.append(np_)
        new_c.append(c + lax.psum(d, axis_name))
    return (flatten_lib.unpack_buckets(spec, new_p),
            flatten_lib.unpack_buckets(spec, new_c))
