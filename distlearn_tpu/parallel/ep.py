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
# The router may read another array than the experts do (``route_from``).
#
# What the experts held elsewhere would add is left out; summed over the
# shares of every holder it is the whole layer (tests/test_hybrid_lm.py).
# On one device it runs without an exchange.

#: rows of one grouped product: an expert's assignments are padded to a
#: multiple of it, so a step costs sum_e ceil(count_e / GROUP_TILE) products.
#: Of 256 and 512, 256 gave the faster step on the v5e at the load the hybrid
#: LM's cell sends a held expert (about 200 assignments a step: fewer padded
#: rows), by 0.5 % on every seed (PERF.md section 6, PR 29)
GROUP_TILE = 256


def route_held(router_w: jax.Array, x: jax.Array, top_k: int, held):
    """Route ``x`` [N, D] (whatever array the router reads: the experts'
    input or another of as many rows) over all ``E`` outputs of ``router_w``
    [D, E] and group the assignments that fall on the ``held`` experts by
    expert.

    Scores, softmax and top-k are float32 at full matmul precision whatever
    the compute dtype: the choice of experts is discrete, so a rounded score
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
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(scores, top_k)                   # [N, k]
    weights = topv / jnp.sum(topv, axis=-1, keepdims=True)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def grouped_glu(x, wg, wu, wd, slot_w, plan, cd, act="silu"):
    """``y[n] = sum over the slots p of token n of slot_w[p] *
    GLU_{e(p)}(x[n])``, ``GLU(x) = (act(x wg) * (x wu)) wd`` — the grouped
    product of :func:`route_held`'s plan.  x [N, D]; wg, wu [G, D, F]; wd
    [G, F, D] (float32, cast to ``cd`` a tile at a time); ``act`` one of
    :data:`GATE_ACTS` (``"silu"``: SwiGLU, ``"relu"``: ReGLU); returns
    [N, D] float32.  One loop over the tiles IN USE (a dynamic trip count,
    so the backward pass is written by hand, as a second such loop that
    recomputes each tile's hidden layer)."""
    gate = GATE_ACTS[act]

    def body(i, y):
        idx, w, xe, e = _tile(plan, slot_w, x, i)
        g, u, d = _expert((wg, wu, wd), e, cd)
        h = gate(_dot(xe, g)) * _dot(xe, u)
        return y.at[idx].add(_dot(h.astype(cd), d) * w[:, None])
    return lax.fori_loop(0, plan[2], body,
                         jnp.zeros(x.shape, jnp.float32))


def _gg_fwd(x, wg, wu, wd, slot_w, plan, cd, act):
    return grouped_glu(x, wg, wu, wd, slot_w, plan, cd, act), \
        (x, wg, wu, wd, slot_w, plan)


def _gg_bwd(cd, act, res, dy):
    x, wg, wu, wd, slot_w, plan = res
    tile = plan[0].shape[0] // plan[1].shape[0]
    dy = dy.astype(cd)

    def body(i, carry):
        dx, dg, du, dd, dw = carry
        idx, w, xe, e = _tile(plan, slot_w, x, i)
        g, u, d = _expert((wg, wu, wd), e, cd)
        a, b = _dot(xe, g), _dot(xe, u)
        # the hidden layer again, and (d b, d a) of a cotangent dh of it
        if act == "silu":
            s = jax.nn.sigmoid(a)
            h = (a * s * b).astype(cd)
            pull = lambda dh: (                             # noqa: E731
                (dh * a * s).astype(cd),
                (dh * b * s * (1.0 + a * (1.0 - s))).astype(cd))
        else:                       # relu: the gate is a where it is positive
            r = jnp.maximum(a, 0.0)
            h = (r * b).astype(cd)
            pull = lambda dh: (                             # noqa: E731
                (dh * r).astype(cd),
                jnp.where(a > 0.0, dh * b, 0.0).astype(cd))
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
    dx, dg, du, dd, dw = lax.fori_loop(
        0, plan[2], body, (zeros(x), zeros(wg), zeros(wu), zeros(wd),
                           zeros(slot_w)))
    return (dx.astype(x.dtype), dg.astype(wg.dtype), du.astype(wu.dtype),
            dd.astype(wd.dtype), dw, None)


grouped_glu.defvjp(_gg_fwd, _gg_bwd)


def moe_held_ffn(x: jax.Array, router_w: jax.Array, experts, held,
                 top_k: int, *, compute_dtype=None,
                 ep_axis: str | None = None, route_from: jax.Array | None = None,
                 act: str = "silu"):
    """The held experts' part of a routed gated-linear-unit layer (see the
    section comment above): ``x`` [N, D], ``router_w`` [D, E], ``experts =
    (wg, wu, wd)`` stacked over ``len(held)``, their gate activation ``act``
    (:data:`GATE_ACTS`).  Returns ``(y [N, D] in the compute dtype, aux)``
    with :func:`route_held`'s counters.

    ``route_from`` [N, D]: the array the ROUTER reads where that is not the
    one the experts read (a layer that scores its experts on its input,
    before attention, and feeds them the post-attention norm); None routes
    from ``x``.

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
    plan, slot_w, aux = route_held(
        router_w, x if route_from is None else route_from, top_k, held)
    y = grouped_glu(x.astype(cd), *experts, slot_w, plan, cd, act)
    return y.astype(cd), aux
