"""Expert parallelism: a routed mixture-of-experts layer over a mesh axis.

Absent from the reference (SURVEY.md §2c lists EP as explicitly out of its
scope), provided as the last of the framework's first-class mesh
dimensions (data / sequence / tensor / pipeline / expert).  The design is
the GShard/Switch pattern expressed TPU-natively:

* **Routing** (per device, local tokens): a linear router picks each
  token's top-1 expert; tokens beyond an expert's capacity are dropped
  (their combine weight is zero — output falls back to the residual
  stream, the standard Switch behavior).
* **Dispatch/combine as einsums**: boolean dispatch mask ``[N, E, C]`` and
  float combine weights ``[N, E, C]`` turn gather/scatter into two MXU
  einsums — no dynamic shapes, no sorting, XLA-friendly.
* **All-to-all over the expert axis**: each device owns ONE expert; the
  dispatched buckets ``[E, C, D]`` are exchanged so device ``e`` receives
  every peer's bucket for expert ``e``, applies its expert FFN to
  ``E*C`` tokens in one batched matmul, and the reverse all-to-all routes
  results home.  Both hops ride ICI.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from distlearn_tpu import obs
from distlearn_tpu.parallel import sequence

PyTree = Any


def route_topk(router_logits: jax.Array, capacity: int, k: int = 1
               ) -> tuple[jax.Array, jax.Array, dict]:
    """Top-k routing with capacity (k=1: Switch; k=2: GShard).

    Args:
      router_logits: ``[N, E]`` raw router scores for local tokens.
      capacity: per-expert bucket size ``C``.
      k: experts per token.  Combine weights are the chosen gates
        renormalized over the k picks (GShard); with k=1 this is the raw
        top-1 gate (Switch).  Bucket slots are claimed in rank order —
        every token's 1st choice before any token's 2nd — so congestion
        drops low-rank assignments first.

    Returns ``(dispatch, combine, aux)``: dispatch ``[N, E, C]`` bool —
    token n occupies slot c of expert e; combine ``[N, E, C]`` float32 —
    gate weight at the same coordinates (zero for dropped assignments);
    aux — routing health terms:

    * ``balance_loss``: the Switch load-balancing loss ``E · Σ_e f_e·P_e``
      (arXiv:2101.03961 eq. 4-6): ``f_e`` = fraction of tokens whose TOP
      choice is expert e, ``P_e`` = mean router probability on e.  Equals
      1.0 at perfect balance; grows as the router collapses.  Both factors
      see the pre-capacity assignment, so the gradient pushes the router
      itself toward balance (differentiable through ``P_e``).
    * ``dropped_frac``: fraction of the ``N*k`` assignments dropped by
      capacity (combine weight zero — tokens fall back to the residual).
    """
    N, E = router_logits.shape
    if not 1 <= k <= E:
        raise ValueError(f"top-k routing needs 1 <= k <= num_experts, "
                         f"got k={k} with {E} experts")
    gates = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    topv, topi = lax.top_k(gates, k)                        # [N, k]
    if k == 1:
        weights = topv          # Switch: the RAW top-1 gate scales the
        # output, so router gradients flow through the kept path
    else:
        # GShard: renormalize the chosen gates over the k picks
        weights = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    dispatch3 = jnp.zeros((N, E, capacity), jnp.bool_)
    combine = jnp.zeros((N, E, capacity), jnp.float32)
    counts = jnp.zeros((E,), jnp.int32)     # slots claimed by higher ranks
    for j in range(k):
        onehot = jax.nn.one_hot(topi[:, j], E, dtype=jnp.int32)  # [N, E]
        # position within the expert bucket, after rank<j claims.  If a
        # higher rank overflowed the bucket, counts pushes pos past
        # capacity — full buckets drop lower ranks either way.
        pos = (jnp.cumsum(onehot, axis=0) + counts[None, :]) * onehot - 1
        disp = (onehot > 0) & (pos < capacity)              # [N, E] kept?
        slot = jax.nn.one_hot(jnp.where(disp, pos, -1), capacity,
                              dtype=jnp.bool_)              # [N, E, C]
        d3 = slot & disp[..., None]
        dispatch3 = dispatch3 | d3
        combine = combine + d3.astype(jnp.float32) \
            * weights[:, j][:, None, None]
        counts = counts + jnp.sum(onehot, axis=0)
        if j == 0:
            frac_tokens = jnp.mean(onehot.astype(jnp.float32), axis=0)
    frac_probs = jnp.mean(gates, axis=0)                    # P_e
    aux = {
        "balance_loss": E * jnp.sum(frac_tokens * frac_probs),
        "dropped_frac": 1.0 - jnp.sum(dispatch3.astype(jnp.float32))
        / (N * k),
    }
    return dispatch3, combine, aux


def route_top1(router_logits: jax.Array, capacity: int
               ) -> tuple[jax.Array, jax.Array]:
    """Top-1 routing with capacity (``route_topk`` with k=1, aux dropped).

    Returns ``(dispatch, combine)``: dispatch ``[N, E, C]`` bool — token n
    goes to slot c of expert e; combine ``[N, E, C]`` float32 — softmax
    gate weight at the same coordinates (zero for dropped tokens).
    """
    dispatch, combine, _ = route_topk(router_logits, capacity, k=1)
    return dispatch, combine


def _route_and_bucket(router_w: jax.Array, x: jax.Array,
                      capacity_factor: float, E: int, top_k: int = 1):
    """Shared routing prologue: capacity, top-k dispatch/combine masks, the
    per-expert token buckets, and the routing-health aux terms.  ONE
    implementation so the local oracle and the distributed path cannot
    silently diverge."""
    N, _ = x.shape
    capacity = max(1, int(-(-N * capacity_factor * top_k // E)))
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)   # [N, E]
    dispatch, combine, aux = route_topk(logits, capacity, top_k)
    buckets = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), x)
    return combine, buckets, capacity, aux


def _combine(combine_w: jax.Array, expert_out: jax.Array) -> jax.Array:
    return jnp.einsum("nec,ecd->nd", combine_w.astype(expert_out.dtype),
                      expert_out)


def moe_ffn_local(expert_fn: Callable, stacked_params: PyTree,
                  router_w: jax.Array, x: jax.Array,
                  capacity_factor: float = 1.25, top_k: int = 1,
                  return_aux: bool = False):
    """Single-device mixture-of-experts (all experts resident): the same
    routing/dispatch/combine math as :func:`moe_ffn` with the all-to-all
    hops removed and the experts applied under ``vmap``.  This is both the
    no-expert-axis fallback for MoE models and the reference oracle the
    distributed path is tested against.

    ``stacked_params``: pytree whose leaves carry a leading expert axis
    ``[E, ...]``; ``expert_fn(params_e, tokens)`` applies ONE expert.
    ``return_aux=True`` additionally returns the :func:`route_topk` aux
    dict (balance loss + dropped fraction).
    """
    E = router_w.shape[1]
    combine, buckets, _, aux = _route_and_bucket(router_w, x,
                                                 capacity_factor, E, top_k)
    out = jax.vmap(expert_fn)(stacked_params, buckets)      # [E, C, D]
    y = _combine(combine, out)
    return (y, aux) if return_aux else y


def moe_ffn(expert_fn: Callable, expert_params: PyTree, router_w: jax.Array,
            x: jax.Array, capacity_factor: float = 1.25,
            axis_name: str = "expert", top_k: int = 1,
            return_aux: bool = False):
    """Expert-parallel mixture-of-experts FFN (one expert per device).

    Args:
      expert_fn: ``(params, tokens) -> tokens`` — THIS device's expert,
        applied to a ``[E*C, D]`` batch of dispatched tokens.
      expert_params: this device's expert parameters (caller shards a
        stacked ``[E, ...]`` pytree over ``axis_name`` and squeezes).
      router_w: ``[D, E]`` router weights (replicated — every device must
        route identically).
      x: local tokens ``[N, D]`` (flatten batch/sequence first).
      capacity_factor: bucket size ``C = ceil(N * top_k / E * factor)``.
      top_k: experts per token (1 = Switch, 2 = GShard).
      return_aux: also return the :func:`route_topk` aux dict (balance
        loss + dropped fraction) for this device's local tokens.

    Returns ``[N, D]``: gate-weighted expert outputs; capacity-dropped
    tokens contribute zeros (add the residual stream outside).
    """
    E = lax.psum(1, axis_name)
    N, D = x.shape
    if router_w.shape != (D, E):
        raise ValueError(
            f"router_w must be [{D}, {E}] (token dim x expert-axis size, "
            f"one expert per device), got {router_w.shape}")
    combine, buckets, capacity, aux = _route_and_bucket(
        router_w, x, capacity_factor, E, top_k)
    # all-to-all: device e receives every peer's bucket for expert e,
    # stacked along a peer axis -> [E_peers, C, D] -> one batched FFN call
    recv = lax.all_to_all(buckets, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                       # [E*C, ...] rows
    out = expert_fn(expert_params, recv.reshape(E * capacity, D))
    out = out.reshape(E, capacity, D)
    # reverse hop: peers get their tokens back at the same coordinates
    home = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                       # [E, C, D]
    y = _combine(combine, home)
    return (y, aux) if return_aux else y


# --------------------------------------------------------------------------
# The share of an expert layer one device holds: dropless, grouped
# --------------------------------------------------------------------------
#
# The layer above gives every device ONE expert and every expert a bucket of
# fixed capacity; what overflows is dropped.  The layer below is TOLD which
# experts it holds (``held``: any subset of the router's ``E``), routes over
# all ``E``, drops nothing, and computes the part of the result its own
# experts give:
#
#     s = softmax(x W_r)  in R^E;   I = top-k(s);   w_i = s_i / sum_{j in I} s_j
#     y = sum_{i in I and held}  w_i  GLU_i(x)        (SwiGLU or ReGLU)
#
# The router may read another array than the experts do (``route_from``),
# and may score by sigmoid, choose through a per-expert correction bias and
# scale its weights (``route_held``'s ``score``, ``select_bias``, ``scale``):
#
#     s = sigmoid(x W_r);   I = top-k(s + b);   w_i = scale s_i / sum_{j in I} s_j
#
# What the experts held elsewhere would add is left out; summed over the
# shares of every holder it is the whole layer (tests/test_hybrid_lm.py).
# On one device it runs without an exchange.
#
# ``route_held`` lays the assignments out expert by expert (the plan);
# ``grouped_glu`` computes on the plan by one of two paths, which
# ``select_grouped`` chooses from the call's shapes (``moe_grouped_total``
# counts which): ``"gmm"``, the rows of the tiles in use packed into an
# expert-sorted buffer and multiplied by a grouped-matmul kernel, which
# fetches an expert's weights once for all its rows — where an expert expects
# a tile of rows or more; ``"xla"``, a loop over the tiles that slices and
# casts a tile's weights every iteration — other backends, other dtypes, odd
# widths, experts with less than a tile — and the oracle of the tests.

#: rows an expert's assignments are padded to a multiple of: the row tile of
#: the kernel path's grouped products (every group starts on a tile, so no
#: tile is visited for two experts), and the rows of one product of the
#: ``"xla"`` loop, which costs sum_e ceil(count_e / GROUP_TILE) iterations.
#: Swept on the v5e on the loop alone: of 256 and 512, 256 gave the faster
#: step at the load ``solar-open2-250b.train-8k`` sends a held expert (about
#: 200 assignments: fewer padded rows), by 0.5 % on every seed (PERF.md
#: section 6, PR 29); the kernel's tiles were swept at 256 and 512 rows at
#: ``smallthinker-21b-a3b.train-16k``'s load (1,536 an expert), where they
#: differ by under 4 % a product either way (PERF.md section 6, PR 35)
GROUP_TILE = 256


#: how a router turns its logits into the scores of :func:`route_held`
ROUTER_SCORES = ("softmax", "sigmoid")


def _router_counter():
    return obs.counter(
        "moe_router_total",
        "route_held calls traced, by how the router scores its experts",
        labels=("score",))


def route_held(router_w: jax.Array, x: jax.Array, top_k: int, held,
               score: str = "softmax", select_bias: jax.Array | None = None,
               scale: float = 1.0):
    """Route ``x`` [N, D] (whatever array the router reads: the experts'
    input or another of as many rows) over all ``E`` outputs of ``router_w``
    [D, E] and group the assignments that fall on the ``held`` experts by
    expert.

    ``score`` (:data:`ROUTER_SCORES`): the scores ``s`` are the softmax of
    the logits over the ``E`` experts, or each logit's sigmoid.  The top-k
    are CHOSEN by ``s + select_bias`` (``select_bias`` [E]: a per-expert
    correction that steers the choice and nothing else; None: by ``s``) and
    WEIGHED by the un-biased ``s`` renormalised over the chosen, times
    ``scale``: ``w_e = scale * s_e / sum over chosen of s`` (the sum of
    sigmoids with ``1e-20`` added, as that router's published code does).
    The weights are differentiable through ``s``; the bias enters the discrete
    choice alone, so its gradient is exactly zero.  The defaults are what
    the function always did.

    Scores and top-k are float32 at full matmul precision whatever the
    compute dtype: the choice of experts is discrete, so a rounded score
    does not give a slightly different output but a different expert.

    Returns ``(plan, slot_w, aux)``.  ``plan = (rows, tile_expert,
    n_tiles)``: ``rows`` [P] the token of every slot (``N`` marks padding),
    the slots of one expert contiguous and padded to a multiple of
    :data:`GROUP_TILE`; ``tile_expert`` [P / GROUP_TILE] the index INTO
    ``held`` each tile belongs to;
    ``n_tiles`` how many tiles hold anything.  ``P`` is sized for the worst
    case (every token on ``min(top_k, len(held))`` held experts), so nothing
    is ever dropped; the work is that of the tiles in use.  ``slot_w`` [P]
    float32 are the combine weights (differentiable; 0 on padding).
    ``aux``: ``assignments`` [len(held)] per held expert, ``unheld_frac``
    the share of tokens none of whose experts is held, and ``dropped`` (held
    assignments that found no slot: 0 by construction, counted, not
    assumed)."""
    N = x.shape[0]
    E = router_w.shape[1]
    tile = GROUP_TILE
    held = tuple(int(h) for h in held)
    G = len(held)
    if not 1 <= top_k <= E or not held or len(set(held)) != G \
            or min(held) < 0 or max(held) >= E:
        raise ValueError(f"top_k={top_k} and held={held} do not fit a "
                         f"router of {E} experts")
    if score not in ROUTER_SCORES:
        raise ValueError(f"score must be one of {ROUTER_SCORES}, got "
                         f"{score!r}")
    _router_counter().labels(score=score).inc()
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
              else jax.nn.sigmoid(logits))
    if select_bias is None:
        topv, topi = lax.top_k(scores, top_k)               # [N, k]
    else:
        _, topi = lax.top_k(scores + lax.stop_gradient(
            select_bias.astype(jnp.float32)), top_k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    total = jnp.sum(topv, axis=-1, keepdims=True)
    if score == "sigmoid":      # every chosen sigmoid may underflow; the
        total = total + 1e-20   # top of a softmax never does
    weights = topv / total
    if scale != 1.0:
        weights = scale * weights
    lut = jnp.full((E,), -1, jnp.int32).at[jnp.asarray(held)].set(
        jnp.arange(G, dtype=jnp.int32))
    local = lut[topi].reshape(N * top_k)                    # index into held
    onehot = (local[:, None] == jnp.arange(G)[None, :]).astype(jnp.int32)
    counts = jnp.sum(onehot, axis=0)                        # [G]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    padded = -(-counts // tile) * tile
    start = jnp.cumsum(padded) - padded
    P = -(-(N * min(top_k, G) + G * (tile - 1)) // tile) * tile
    pos = jnp.where(local >= 0, start[jnp.maximum(local, 0)] + rank, P)
    token = jnp.arange(N * top_k, dtype=jnp.int32) // top_k
    rows = jnp.full((P,), N, jnp.int32).at[pos].set(token, mode="drop")
    slot_w = jnp.zeros((P,), jnp.float32).at[pos].set(
        weights.reshape(-1), mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(jnp.cumsum(padded), jnp.arange(P // tile) * tile,
                         side="right"), G - 1).astype(jnp.int32)
    n_tiles = (jnp.sum(padded) // tile).astype(jnp.int32)
    aux = {"assignments": counts,
           "unheld_frac": jnp.mean(jnp.all(
               local.reshape(N, top_k) < 0, axis=1).astype(jnp.float32)),
           "dropped": jnp.sum(counts) - jnp.sum(rows < N)}
    return (rows, tile_expert, n_tiles), slot_w, aux


def _tile(plan, slot_w, x, i):
    rows, tile_expert, _ = plan
    tile = rows.shape[0] // tile_expert.shape[0]
    idx = lax.dynamic_slice_in_dim(rows, i * tile, tile)
    w = lax.dynamic_slice_in_dim(slot_w, i * tile, tile)
    idx = jnp.minimum(idx, x.shape[0] - 1)      # padding: any row, weight 0
    return idx, w, x[idx], tile_expert[i]


def _expert(weights, e, cd):
    return [lax.dynamic_index_in_dim(a, e, 0, keepdims=False).astype(cd)
            for a in weights]


def _dot(a, b, eq="ij,jk->ik"):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


#: the gate activations of a held expert, by name (a static argument of the
#: grouped product: its backward pass is written out for each)
GATE_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def glu_gate(a, act: str = "silu", limit: float | None = None):
    """The gate half of a gated linear unit, ``act(a)``; with ``limit`` (a
    clamped SwiGLU, ``act(min(a, limit)) * clip(b, -limit, limit)``) the
    pre-activation is clamped from above first."""
    return GATE_ACTS[act](a if limit is None else jnp.minimum(a, limit))


def glu_up(b, limit: float | None = None):
    """The linear half of a gated linear unit: ``b``, clipped to ``[-limit,
    limit]`` where a limit is given (:func:`glu_gate`)."""
    return b if limit is None else jnp.clip(b, -limit, limit)

#: the implementations of the grouped product (:func:`select_grouped`)
GROUPED_IMPLS = ("xla", "gmm")

#: the VMEM the kernel path's double-buffered blocks may take by this
#: module's count (the chip's scoped default is 16 MiB;
#: tests/test_transformer.py compiles the kernels at the published widths
#: for the chip).  Their row tile is :data:`GROUP_TILE`.
_GMM_VMEM = 15.5 * 2 ** 20
#: the fewest assignments an expert is EXPECTED to get (``N top_k / E``) for
#: the kernel path: 7/8 of a tile.  NOT where the two paths cross: timed
#: alone on a v5e (forward and backward, 8 experts, D 4096, F 1280 and
#: 2048), the kernels beat the loop at every load from 128 rows an expert
#: to 256, by 2-25 % (PERF.md section 6).  Near a tile the seed's routing
#: decides how many experts spill into a second one, which the loop pays
#: for (at 228 rows an expert the step's length spread 0.58 % over six
#: seeds on the loop, 0.31 % on the kernels); below 7/8 the layers of 205
#: rows an expert (320 experts, 8,192 tokens) stay on the loop, their
#: move to the kernels not yet measured end to end.
_GMM_MIN_ROWS = GROUP_TILE * 7 // 8


def select_grouped(backend: str, dtype, D: int, F: int,
                   rows_per_expert: int) -> str:
    """The path of a held-expert layer's grouped product, decided from what
    the call itself shows — backend, compute dtype, the experts' widths,
    the assignments an expert is expected to get (``N top_k / E``, static)
    — and from nothing else (no environment variable, no model's argument).

    ``"gmm"`` (packed rows through the grouped-matmul kernels, an expert's
    weights fetched once) on the TPU in bfloat16, at widths the kernel's
    lanes tile (multiples of 128), where an expert expects at least
    :data:`_GMM_MIN_ROWS` rows; everything else is ``"xla"``, the loop over
    tiles."""
    if (backend == "tpu" and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and D % 128 == 0 and F % 128 == 0
            and rows_per_expert >= _GMM_MIN_ROWS):
        return "gmm"
    return "xla"


def _grouped_counter():
    return obs.counter(
        "moe_grouped_total",
        "moe_held_ffn calls traced, by the grouped product's resolved "
        "implementation", labels=("impl",))


def grouped_paths_traced() -> dict[str, int]:
    """``{impl: moe_held_ffn calls traced so far}`` in this process (the
    ``moe_grouped_total`` counter; empty with ``DISTLEARN_OBS=0``)."""
    family = _grouped_counter()
    if family is obs.NULL:
        return {}
    return {s["labels"]["impl"]: s["value"] for s in family.sample()}


def _hidden(a, b, act, cd, limit=None):
    """The hidden layer ``act(a) * b`` of pre-activations ``a``, ``b``
    (float32) rounded to ``cd``, and the pull-back ``dh -> (db, da)`` of a
    float32 cotangent of it, each rounded to ``cd``; with ``limit`` the
    clamped unit of :func:`glu_gate`, whose clamps pass no cotangent where
    they bite."""
    if limit is not None:
        h, pull = _hidden(jnp.minimum(a, limit), jnp.clip(b, -limit, limit),
                          act, cd)

        def clamped(dh):
            db, da = pull(dh)
            return (jnp.where(jnp.abs(b) < limit, db, 0).astype(cd),
                    jnp.where(a < limit, da, 0).astype(cd))
        return h, clamped
    if act == "silu":
        s = jax.nn.sigmoid(a)
        return (a * s * b).astype(cd), lambda dh: (
            (dh * a * s).astype(cd),
            (dh * b * s * (1.0 + a * (1.0 - s))).astype(cd))
    r = jnp.maximum(a, 0.0)         # relu: the gate is a where it is positive
    return (r * b).astype(cd), lambda dh: (
        (dh * r).astype(cd), jnp.where(a > 0.0, dh * b, 0.0).astype(cd))


def _xla_fwd(x, wg, wu, wd, slot_w, plan, cd, act, first=0, y=None,
             limit=None):
    """The ``"xla"`` path: one loop over the tiles IN USE (a dynamic trip
    count) from tile ``first`` on, adding into ``y`` (None: zeros): a tile's
    rows gathered, its expert's weights sliced out of the float32 stacks and
    cast, its result scatter-added, every iteration."""
    def body(i, y):
        idx, w, xe, e = _tile(plan, slot_w, x, i)
        g, u, d = _expert((wg, wu, wd), e, cd)
        h = glu_gate(_dot(xe, g), act, limit) * glu_up(_dot(xe, u), limit)
        return y.at[idx].add(_dot(h.astype(cd), d) * w[:, None])
    return lax.fori_loop(first, plan[2], body,
                         jnp.zeros(x.shape, jnp.float32) if y is None else y)


def _xla_bwd(x, wg, wu, wd, slot_w, plan, cd, act, dy, first=0, carry=None,
             limit=None):
    """The ``"xla"`` path's backward: a second such loop that recomputes
    each tile's hidden layer, adding into ``carry = (dx, dwg, dwu, dwd,
    dslot_w)`` (None: zeros)."""
    tile = plan[0].shape[0] // plan[1].shape[0]

    def body(i, carry):
        dx, dg, du, dd, dw = carry
        idx, w, xe, e = _tile(plan, slot_w, x, i)
        g, u, d = _expert((wg, wu, wd), e, cd)
        h, pull = _hidden(_dot(xe, g), _dot(xe, u), act, cd, limit)
        dyt = dy[idx]
        dw = lax.dynamic_update_slice_in_dim(
            dw, jnp.sum(dyt.astype(jnp.float32) * _dot(h, d), axis=-1),
            i * tile, 0)
        dye = (dyt * w[:, None].astype(cd)).astype(cd)
        db, da = pull(_dot(dye, d, "ij,kj->ik"))
        add = lambda acc, v: acc.at[e].add(v)               # noqa: E731
        return (dx.at[idx].add(_dot(da, g, "ij,kj->ik")
                               + _dot(db, u, "ij,kj->ik")),
                add(dg, _dot(xe, da, "ij,ik->jk")),
                add(du, _dot(xe, db, "ij,ik->jk")),
                add(dd, _dot(h, dye, "ij,ik->jk")), dw)

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)       # noqa: E731
    return lax.fori_loop(
        first, plan[2], body, carry or (zeros(x), zeros(wg), zeros(wu),
                                        zeros(wd), zeros(slot_w)))


# ---- the "gmm" path: packed rows through grouped-matmul kernels -----------
#
# ``route_held``'s plan lays the slots out expert by expert, so the rows of
# the tiles in use, gathered in that order, are a packed expert-sorted buffer
# whose groups (an expert's padded slots) are contiguous and start on a row
# tile.  A grouped matrix product (:func:`_gmm`) walks the buffer's row tiles
# and keeps a group's weight block in VMEM while consecutive tiles belong to
# the group; its transpose (:func:`_tgmm`) sums a group's weight-gradient
# block in VMEM over the group's tiles.  So an expert's weights, cast to the
# compute dtype once a layer and pass, and its gradient block move once for
# ALL its rows, where the loop above moves them once a tile.  The buffer
# holds the plan's first CHUNK of rows (``P`` is the worst case, several
# times the load), sized so that the expected load fits it; the kernels'
# grids are dynamic, so their work is that of the tiles in use.  Tiles beyond
# the chunk — a load above the expected — are the loop's: it starts where the
# chunk ends and adds into the kernels' results.


def _kernel_tiles(plan, chunk: int | None) -> int:
    """The tiles of the plan in its first ``chunk`` rows (rounded up; None
    or more than the plan holds: all of them)."""
    tiles = plan[1].shape[0]
    tile = plan[0].shape[0] // tiles
    return tiles if chunk is None else min(tiles, -(-chunk // tile))


def _gmm_chunk(expected: int, G: int) -> int:
    """Rows of the packed buffer, the part of the plan the kernels take: the
    load the router is EXPECTED to send the held experts (``expected``
    assignments) with a thirty-second of room and the padding's worst case —
    what the buffer costs beside its products (the gathers, the elementwise
    passes, the sort) it costs for all its rows, used or not."""
    return expected + expected // 32 + G * (GROUP_TILE - 1)


def _widths(n: int) -> list[int]:
    """The tile widths a dimension of ``n`` may be cut to, widest first:
    ``n`` itself and its divisors that are multiples of 128."""
    return [n] + [t for t in range(n - n % 128, 0, -128)
                  if n % t == 0 and t != n]


def _gmm_tiles(tm: int, k: int, n: int) -> tuple[int, int, int]:
    """The grouped product's (rows, contraction, columns): the WHOLE
    contraction, so that the weight block's index changes only with the
    group and the block stays in VMEM over the group's consecutive row
    tiles, and the widest columns whose blocks (lhs, rhs and the float32 out,
    twice buffered each, and the float32 product before it is stored) fit
    :data:`_GMM_VMEM` (the lhs is read once a column tile)."""
    fits = lambda tn: 2 * (2 * tm * k + 2 * k * tn + 4 * tm * tn) \
        + 4 * tm * tn <= _GMM_VMEM                          # noqa: E731
    return tm, k, next(filter(fits, _widths(n)), _widths(n)[-1])


def _tgmm_tiles(tm: int, k: int, n: int) -> tuple[int, int, int]:
    """The transposed grouped product's (rows, out rows, out columns),
    ``lhs [m, k]^T rhs [m, n]``: of the out blocks that fit
    :data:`_GMM_VMEM` — held three times in float32 (twice buffered, once as
    the accumulator) beside the row tiles of both operands, twice in
    bfloat16 and once transposed — the one that re-reads the fewest bytes:
    the lhs is read once a column tile, the rhs once a row tile of the out
    block."""
    fit = [(k * (n // tn) + n * (k // tk), -tk * tn, tk, tn)
           for tk in _widths(k) for tn in _widths(n)
           if 12 * tk * tn + 8 * tm * (tk + tn) <= _GMM_VMEM]
    _, _, tk, tn = min(fit, default=(0, 0, _widths(k)[-1], _widths(n)[-1]))
    return tm, tk, tn


# The three kernels of the path.  JAX's own grouped matrix product
# (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm``, ``tgmm``) in the
# special case the plan gives — every group starts on a row tile — which
# needs none of its group metadata (offsets, partial tiles, masks: 0.85 MB
# of program and a quarter of a second of tracing for every call, in every
# process, PERF.md section 6, PR 35): the grid walks the row tiles IN USE in
# order (a dynamic grid: the work is theirs; rows past them come out
# unwritten), a tile's weight block is its group's, so the block's index
# changes — and the block is fetched — once a group.  Jitted, so that a step
# of several layers lowers each once.


def _pallas(kernel, grid, in_specs, out_spec, out_shape, *, prefetch,
            scratch=(), interpret, **kw):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=prefetch, grid=grid, in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch]),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel",) + ("arbitrary",) * (len(grid) - 1)),
        interpret=interpret, **kw)


@functools.partial(jax.jit,
                   static_argnames=("transpose_rhs", "out", "interpret"))
def _gmm(tile_expert, n_used, lhs, rhs, transpose_rhs=False,
         out=jnp.float32, interpret=False):
    """``lhs_tile rhs[group of the tile]`` for the first ``n_used`` row tiles
    of ``lhs`` [m, k] (tile ``i`` belongs to group ``tile_expert[i]``); rhs
    [G, k, n], or [G, n, k] ``transpose_rhs``; [m, n] in ``out``, summed in
    float32."""
    from jax.experimental import pallas as pl
    (m, k), n = lhs.shape, rhs.shape[1 if transpose_rhs else 2]
    tm, _, tn = _gmm_tiles(m // tile_expert.shape[0], k, n)
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def kernel(te, lhs, rhs, out):
        out[...] = lax.dot_general(
            lhs[...], rhs[...], dims,
            preferred_element_type=jnp.float32).astype(out.dtype)
    rhs_spec = pl.BlockSpec((None, tn, k), lambda j, i, te: (te[i], j, 0)) \
        if transpose_rhs else \
        pl.BlockSpec((None, k, tn), lambda j, i, te: (te[i], 0, j))
    return _pallas(
        kernel, (n // tn, n_used),
        [pl.BlockSpec((tm, k), lambda j, i, te: (i, 0)), rhs_spec],
        pl.BlockSpec((tm, tn), lambda j, i, te: (i, j)),
        jax.ShapeDtypeStruct((m, n), out), prefetch=1, interpret=interpret,
        name="gmm")(tile_expert, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("G", "interpret"))
def _tgmm(tile_expert, n_used, lhs, rhs, G, interpret=False):
    """The sum over a group's row tiles of ``lhs_tile^T rhs_tile``, [G, k, n]
    float32 (zero for a group with no tile), lhs [m, k], rhs [m, n]: a
    group's block stays in VMEM while consecutive tiles belong to the group
    and is stored when the group changes."""
    from jax.experimental import pallas as pl
    (m, k), n = lhs.shape, rhs.shape[1]
    tm, tk, tn = _tgmm_tiles(m // tile_expert.shape[0], k, n)

    def kernel(te, lhs, rhs, zeros, out, acc):
        del zeros       # left in HBM: it IS the out buffer, which so reads
        #                 zero where no tile of a group is visited
        i, last = pl.program_id(2), pl.num_programs(2) - 1
        group = te[i]

        @pl.when((i == 0) | (te[jnp.maximum(i - 1, 0)] != group))
        def _():
            acc[...] = jnp.zeros_like(acc)
        acc[...] += lax.dot(lhs[...].swapaxes(0, 1), rhs[...],
                            preferred_element_type=jnp.float32)

        @pl.when((i == last) | (te[jnp.minimum(i + 1, last)] != group))
        def _():
            out[...] = acc[...]
    block = pl.BlockSpec((None, tk, tn), lambda j, kk, i, te: (te[i], kk, j))
    return _pallas(
        kernel, (n // tn, k // tk, n_used),
        [pl.BlockSpec((tm, tk), lambda j, kk, i, te: (i, kk)),
         pl.BlockSpec((tm, tn), lambda j, kk, i, te: (i, j)),
         pl.BlockSpec(memory_space=pl.ANY)],
        block, jax.ShapeDtypeStruct((G, k, n), jnp.float32), prefetch=1,
        scratch=[(tk, tn)], interpret=interpret,
        input_output_aliases={3: 0}, name="tgmm")(
            tile_expert, lhs, rhs, jnp.zeros((G, k, n), jnp.float32))


#: tokens a block of the unpack's output (:func:`_sum_by_token`)
_UNPACK_TILE = 256


@functools.partial(jax.jit, static_argnames=("N", "tm", "interpret"))
def _sum_by_token(vals, tokens, N, tm, interpret=False):
    """``out[n] = sum of vals[p] over the rows p with tokens[p] == n`` (rows
    of tokens ``>= N`` count nowhere, whatever they hold), [N, D] float32;
    ``tm`` rows of ``vals`` a tile.

    Not a scatter-add, which the TPU does a row at a time (9.2 ms for the
    27 k rows of [.., 2560] this takes 2.2 for, PERF.md section 6, PR 35):
    ONE gather brings the rows into token order, where a tile of
    :data:`_UNPACK_TILE` tokens owns a contiguous run of them, and a tile's
    sums are the product of the run's one-hot matrix (made in VMEM from the
    run's tokens) with the run.  The grid walks the (row tile, token tile)
    pairs that meet, in order — sorted tokens make a token tile's pairs
    consecutive, so its block is summed in VMEM and stored once.  A product
    like the layer's others: ``vals`` come in the compute dtype, the sum is
    float32."""
    from jax.experimental import pallas as pl
    T = _UNPACK_TILE
    C, D = vals.shape
    tiles, row_tiles = -(-N // T), C // tm
    key = jnp.where(tokens < N, tokens, tiles * T).astype(jnp.int32)
    order = jnp.argsort(key)
    key = key[order]
    # the pairs: row tile r meets the token tiles first[r] .. last[r] (none
    # if it holds no row of a token); pair i is the (i - before[r])-th of r
    first = jnp.minimum(key[::tm] // T, tiles)
    last = jnp.minimum(key[tm - 1::tm] // T, tiles - 1)
    meets = jnp.maximum(last - first + 1, 0)
    before = jnp.cumsum(meets) - meets
    i = jnp.arange(row_tiles + tiles, dtype=jnp.int32)  # the most pairs
    pair_row = jnp.minimum(
        jnp.sum(before[None, :] + meets[None, :] <= i[:, None], axis=1,
                dtype=jnp.int32), row_tiles - 1)
    pair_tok = jnp.minimum(first[pair_row] + i - before[pair_row], tiles - 1)
    _, _, tn = _gmm_tiles(tm, tm, D)

    def kernel(rows, toks, key, vals, zeros, out, acc):
        del rows, zeros     # zeros: left in HBM, the out buffer itself
        i, last = pl.program_id(1), pl.num_programs(1) - 1
        tok = toks[i]

        @pl.when((i == 0) | (toks[jnp.maximum(i - 1, 0)] != tok))
        def _():
            acc[...] = jnp.zeros_like(acc)
        onehot = key[...] == tok * T + lax.broadcasted_iota(
            jnp.int32, (T, tm), 0)
        acc[...] += lax.dot(onehot.astype(vals.dtype), vals[...],
                            preferred_element_type=jnp.float32)

        @pl.when((i == last) | (toks[jnp.minimum(i + 1, last)] != tok))
        def _():
            out[...] = acc[...]
    return _pallas(
        kernel, (D // tn, jnp.sum(meets)),
        [pl.BlockSpec((1, tm), lambda j, i, r, t: (0, r[i])),
         pl.BlockSpec((tm, tn), lambda j, i, r, t: (r[i], j)),
         pl.BlockSpec(memory_space=pl.ANY)],
        pl.BlockSpec((T, tn), lambda j, i, r, t: (t[i], j)),
        jax.ShapeDtypeStruct((tiles * T, D), jnp.float32), prefetch=2,
        scratch=[(T, tn)], interpret=interpret, input_output_aliases={4: 0},
        name="sum_by_token")(
            pair_row, pair_tok, key[None, :], vals[order],
            jnp.zeros((tiles * T, D), jnp.float32))[:N]


def _packed(plan, slot_w, per):
    """The rows of the plan's first ``per`` tiles: ``(tok, w, tile_expert,
    n_used, live)`` — the rows' tokens (``N`` on padding), their combine
    weights, each tile's group, how many of the tiles are in use, and which
    rows lie in one."""
    rows, tile_expert, n_tiles = plan
    tile = rows.shape[0] // tile_expert.shape[0]
    n_used = jnp.minimum(n_tiles, per)
    return (rows[:per * tile], slot_w[:per * tile], tile_expert[:per], n_used,
            jnp.arange(per * tile, dtype=jnp.int32) < n_used * tile)


def _gmm_fwd(x, wg, wu, wd, slot_w, plan, cd, act, per, limit=None):
    interpret = sequence._backend() != "tpu"
    N = x.shape[0]
    tok, w, tile_expert, n_used, _ = _packed(plan, slot_w, per)
    gmm = functools.partial(_gmm, tile_expert, n_used, interpret=interpret)
    xs = x[jnp.minimum(tok, N - 1)]
    h = (glu_gate(gmm(xs, wg.astype(cd)), act, limit)
         * glu_up(gmm(xs, wu.astype(cd)), limit)).astype(cd)
    o = (gmm(h, wd.astype(cd)) * w[:, None]).astype(cd)
    return _xla_fwd(x, wg, wu, wd, slot_w, plan, cd, act, per,
                    _sum_by_token(o, tok, N, o.shape[0] // per, interpret),
                    limit)


def _gmm_bwd(x, wg, wu, wd, slot_w, plan, cd, act, dy, per, limit=None):
    """As :func:`_xla_bwd` for the packed rows, with two of its sums taken
    the cheaper way round.  ``d slot_w[p] = sum_f h[p, f] * (dy[rows[p]]
    wd^T)[f]`` — the same number as ``sum_d dy[rows[p], d] * (h wd)[p, d]``
    without the down product a second time.  ``d wd = (slot_w h)^T dy`` —
    the combine weight rides on the hidden rows, a third as wide as ``dy``'s.
    ``dx``'s two products are one, over the concatenated gate and up
    weights, so their sum is never an array.  The weight gradients are the
    kernel's output as it is; the loop adds what lies beyond the chunk."""
    interpret = sequence._backend() != "tpu"
    N, F = x.shape[0], wg.shape[2]
    tok, w, tile_expert, n_used, live = _packed(plan, slot_w, per)
    gmm = functools.partial(_gmm, tile_expert, n_used, interpret=interpret)
    tgmm = functools.partial(_tgmm, tile_expert, n_used, G=wg.shape[0],
                             interpret=interpret)
    idx = jnp.minimum(tok, N - 1)
    xs, dyt = x[idx], dy[idx]
    wgu = jnp.concatenate([wg.astype(cd), wu.astype(cd)], axis=2)
    ab = gmm(xs, wgu)
    h, pull = _hidden(ab[:, :F], ab[:, F:], act, cd, limit)
    dhu = gmm(dyt, wd.astype(cd), transpose_rhs=True)
    # rows past the tiles in use come out of the kernels unwritten (and no
    # kernel reads them): a select, where a weight of 0 would not do
    dw = jnp.where(live, jnp.sum(h.astype(jnp.float32) * dhu, axis=-1), 0.0)
    db, da = pull(dhu * w[:, None])
    dxs = gmm(jnp.concatenate([da, db], axis=1), wgu, transpose_rhs=True,
              out=cd)
    return _xla_bwd(x, wg, wu, wd, slot_w, plan, cd, act, dy, per, (
        _sum_by_token(dxs, tok, N, dxs.shape[0] // per, interpret),
        tgmm(xs, da), tgmm(xs, db),
        tgmm((h * w[:, None]).astype(cd), dyt),
        jnp.pad(dw, (0, slot_w.shape[0] - dw.shape[0]))), limit)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def grouped_glu(x, wg, wu, wd, slot_w, plan, cd, act="silu", impl="xla",
                chunk=None, limit=None):
    """``y[n] = sum over the slots p of token n of slot_w[p] *
    GLU_{e(p)}(x[n])``, ``GLU(x) = (act(x wg) * (x wu)) wd`` — the grouped
    product of :func:`route_held`'s plan.  x [N, D]; wg, wu [G, D, F]; wd
    [G, F, D] (float32); ``act`` one of :data:`GATE_ACTS` (``"silu"``:
    SwiGLU, ``"relu"``: ReGLU), clamped where ``limit`` is given
    (:func:`glu_gate`); returns [N, D] float32.  Operands in ``cd``,
    sums in float32, the hidden layer rounded to ``cd``, whichever path.

    ``impl`` (:data:`GROUPED_IMPLS`; :func:`moe_held_ffn` asks
    :func:`select_grouped`): ``"xla"`` a loop over the tiles in use that
    slices and casts a tile's weights every iteration; ``"gmm"`` the plan's
    first ``chunk`` rows (None: all of them) packed and multiplied by the
    grouped-matmul kernels (in Pallas interpret mode off the TPU), the tiles
    beyond them by the loop.  Either way the work is that of the tiles in
    use, a dynamic count, so the backward pass is written by hand, and
    recomputes the hidden layer."""
    if impl == "gmm":
        return _gmm_fwd(x, wg, wu, wd, slot_w, plan, cd, act,
                        _kernel_tiles(plan, chunk), limit)
    return _xla_fwd(x, wg, wu, wd, slot_w, plan, cd, act, limit=limit)


def _gg_fwd(x, wg, wu, wd, slot_w, plan, cd, act, impl, chunk, limit):
    return grouped_glu(x, wg, wu, wd, slot_w, plan, cd, act, impl, chunk,
                       limit), (x, wg, wu, wd, slot_w, plan)


def _gg_bwd(cd, act, impl, chunk, limit, res, dy):
    x, wg, wu, wd, slot_w, plan = res
    dy = dy.astype(cd)
    if impl == "gmm":
        dx, dg, du, dd, dw = _gmm_bwd(x, wg, wu, wd, slot_w, plan, cd, act,
                                      dy, _kernel_tiles(plan, chunk), limit)
    else:
        dx, dg, du, dd, dw = _xla_bwd(x, wg, wu, wd, slot_w, plan, cd, act,
                                      dy, limit=limit)
    return (dx.astype(x.dtype), dg.astype(wg.dtype), du.astype(wu.dtype),
            dd.astype(wd.dtype), dw, None)


grouped_glu.defvjp(_gg_fwd, _gg_bwd)


def moe_held_ffn(x: jax.Array, router_w: jax.Array, experts, held,
                 top_k: int, *, compute_dtype=None,
                 ep_axis: str | None = None, route_from: jax.Array | None = None,
                 act: str = "silu", score: str = "softmax",
                 select_bias: jax.Array | None = None, scale: float = 1.0,
                 limit: float | None = None):
    """The held experts' part of a routed gated-linear-unit layer (see the
    section comment above): ``x`` [N, D], ``router_w`` [D, E], ``experts =
    (wg, wu, wd)`` stacked over ``len(held)``, their gate activation ``act``
    (:data:`GATE_ACTS`).  Returns ``(y [N, D] in the compute dtype, aux)``
    with :func:`route_held`'s counters.

    ``route_from`` [N, D]: the array the ROUTER reads where that is not the
    one the experts read (a layer that scores its experts on its input,
    before attention, and feeds them the post-attention norm); None routes
    from ``x``.  ``score``, ``select_bias``, ``scale``: :func:`route_held`'s
    (how the router scores, the correction bias of its choice, the factor
    on its weights).  ``limit``: the clamp of a clamped SwiGLU
    (:func:`glu_gate`; None: none).

    ``ep_axis``: the mesh axis over which other devices hold the other
    experts.  With it the same layer is the expert-parallel one — every
    device routes its own tokens, the assignments go to the holders and the
    results come home — but that exchange is NOT written yet for a layer
    that holds several experts a device, and nothing stands in for it: the
    call raises.  Without it (one device, or experts replicated) the layer
    computes its share with no exchange at all."""
    if ep_axis is not None:
        raise NotImplementedError(
            "moe_held_ffn over ep_axis: the dropless exchange of "
            "assignments between devices that each hold several experts is "
            "not written; without ep_axis the layer computes the share of "
            "the experts it is told it holds")
    if act not in GATE_ACTS:
        raise ValueError(f"act must be one of {tuple(GATE_ACTS)}, got {act!r}")
    cd = compute_dtype or x.dtype
    N, D = x.shape
    G, _, F = experts[0].shape
    expected = N * top_k * G // router_w.shape[1]
    impl = select_grouped(sequence._backend(), cd, D, F, expected // G)
    if impl == "gmm" and jax.config.jax_enable_x64:
        impl = "xla"        # Mosaic takes no 64-bit counter (local_attention)
    _grouped_counter().labels(impl=impl).inc()
    plan, slot_w, aux = route_held(
        router_w, x if route_from is None else route_from, top_k, held,
        score=score, select_bias=select_bias, scale=scale)
    chunk = _gmm_chunk(expected, G) if impl == "gmm" else None
    y = grouped_glu(x.astype(cd), *experts, slot_w, plan, cd, act, impl,
                    chunk, limit)
    return y.astype(cd), aux
