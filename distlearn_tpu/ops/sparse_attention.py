"""DeepSeek sparse attention (DSA) over latent attention with no rotary
part: a lightning indexer scores POOLED keys, each query keeps the best
blocks of them, and the attention runs over those positions alone.

Per query position ``t`` (``J`` indexer heads of width ``d``, pool ``P``,
``topk`` positions kept; the indexer's inputs are the layer's):

    kbar_b = mean(kI_{P b .. P b + P - 1})              aligned groups of P
    I[t,b] = sum_j w[t,j] relu(qI[t,j] . kbar_b) / sqrt(d)      b < t // P
    S_t    = {the block t // P}  U  {the top min(topk / P - 1, t // P)
             earlier blocks by I[t, .]}                  (<= topk positions)
             of equal scores, the earlier block first
    a_h,t  = softmax_{s in S_t, s <= t}(qa_h,t . c_s * scale) c_s

``qa`` is a head's query already multiplied into the latent (the ABSORBED
form: ``q_h Wk_h^T``), ``c`` the one latent row a position that every head
reads as its key and its value; the caller multiplies ``a_h`` out through
the head's value map.  The selection is a discrete choice: no gradient
reaches the indexer (its inputs are ``stop_gradient``-ed here), as none
reaches a router's correction bias.

The result is EXACT: a dense softmax under the selection's mask.  It is
computed a block of :data:`QUERY_BLOCK` queries at a time against the keys
up to the end of the block's group (:data:`QUERY_GROUPS` groups, a
``lax.scan`` over the blocks of each: static shapes, a program of four
bodies), so neither ``[H, L, L]`` scores nor ``[J, L, L / P]`` indexer
products exist beyond one block.  Masked-dense: every key up to the group's
end is multiplied and the unselected ones masked, so a step does 5/8 of the
square's work, not the selection's (PERF.md says what that leaves).  Each
block's attention is its own ``jax.checkpoint``; its output and its
selection mask carry the name
:data:`~distlearn_tpu.parallel.sequence.ATTN_RESIDUALS`, which a
rematerialised layer (``models.core.checkpoint_block``) keeps, so that its
recomputed forward pass need not run the indexer and the attention again.

Inner names, inside the caller's ``attn_core``: ``dsa_index`` (the pooling,
the scores, the top-k, the mask) and ``attn_dsa`` (the attention over the
selection).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from distlearn_tpu import obs
from distlearn_tpu.parallel.sequence import ATTN_RESIDUALS

#: queries a block of the masked-dense evaluation, and the groups of
#: consecutive blocks that share a key length (a scan each): the work is
#: (G + 1) / 2G of the square's, 5/8 at four groups, against a half for a
#: block each and all of it for one group
QUERY_BLOCK = 256
QUERY_GROUPS = 4

_HIGHEST = lax.Precision.HIGHEST


def selected_pairs(L: int, pool: int, topk: int) -> int:
    """``sum_t |S_t|`` of one sequence of ``L`` positions: the tail block's
    positions up to ``t`` and ``min(topk / pool - 1, t // pool)`` whole
    earlier blocks."""
    keep = topk // pool - 1
    return sum(t % pool + 1 + pool * min(keep, t // pool) for t in range(L))


def _selected_counter():
    return obs.counter(
        "dsa_selected_total", "(query, key) pairs a traced sparse-attention "
        "call attends, one sequence, by its length", labels=("seq",))


def dsa_selected_traced() -> dict[int, int]:
    """``{sequence length: pairs attended by the sparse-attention calls
    traced so far}`` in this process (the ``dsa_selected_total`` counter,
    one sequence a call; empty with ``DISTLEARN_OBS=0``)."""
    family = _selected_counter()
    if family is obs.NULL:
        return {}
    return {int(s["labels"]["seq"]): s["value"] for s in family.sample()}


def index_scores(qI, w, kbar, start: int, pool: int):
    """``I[b, t, n]`` for the queries ``start .. start + T - 1`` against the
    pooled keys ``kbar`` [B, N, d]: float32, ``-inf`` where the block is not
    an EARLIER one (``n >= t // pool``).  qI [B, T, J, d], w [B, T, J]."""
    s = jnp.einsum("btjd,bnd->btjn", qI, kbar, precision=_HIGHEST)
    score = jnp.einsum("btj,btjn->btn", w, jax.nn.relu(s),
                       precision=_HIGHEST) / math.sqrt(qI.shape[-1])
    t = start + jnp.arange(qI.shape[1])
    earlier = jnp.arange(kbar.shape[1])[None, :] < (t // pool)[:, None]
    return jnp.where(earlier, score, -jnp.inf)


def select_blocks(score, start: int, pool: int, topk: int):
    """bool [B, T, N]: each query's tail block and its best ``topk / pool -
    1`` earlier blocks by ``score`` (all of them where it has fewer; of
    equal scores the earlier block first, ``lax.top_k``'s order)."""
    keep = topk // pool - 1
    T, N = score.shape[1:]
    chosen = score > -jnp.inf
    if keep < N:
        # above the keep-th score, and of the scores equal to it the
        # earliest as many as are left: no scatter of the top-k's indices
        # (a scatter a row, 7 ms a group on the v5e, PERF.md section 6)
        kth = lax.top_k(score, keep)[0][..., -1:]
        above = score > kth
        tie = chosen & (score == kth)
        left = keep - jnp.sum(above, axis=-1, keepdims=True)
        chosen = above | (tie & (jnp.cumsum(tie, axis=-1) <= left))
    t = start + jnp.arange(T)
    return chosen | (jnp.arange(N)[None, :] == (t // pool)[:, None])


@jax.checkpoint
def _attend(qa, c, allowed, scale):
    """One block: qa [B, T, H, R], c [B, K, R], allowed [B, T, K]."""
    s = jnp.einsum("bthr,bkr->bhtk", qa, c,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(allowed[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhtk,bkr->bthr", p.astype(c.dtype), c,
                      preferred_element_type=jnp.float32).astype(qa.dtype)


def sparse_latent_attention(qa, c, qI, w, kI, *, pool: int, topk: int,
                            scale: float, block: int = QUERY_BLOCK,
                            groups: int = QUERY_GROUPS):
    """``a`` [B, L, H, R] (the equations at the top of the module): qa [B, L,
    H, R] absorbed queries and c [B, L, R] latents in the compute dtype; the
    indexer's qI [B, L, J, d], w [B, L, J] (already divided by sqrt(J)) and
    kI [B, L, d], read in float32.  ``L`` must be a multiple of ``pool``,
    ``topk`` of ``pool``.  The blocks of queries fall into ``groups``
    consecutive groups, each a ``lax.scan`` over its blocks against the keys
    up to the group's end."""
    B, L, H, R = qa.shape
    if L % pool or topk % pool or topk < pool:
        raise ValueError(f"sparse attention needs the length ({L}) and topk "
                         f"({topk}) to be multiples of the pool ({pool})")
    T = next(b for b in range(min(block, L), 0, -1)
             if L % b == 0 and b % pool == 0)
    n = L // T
    G = next(g for g in range(min(groups, n), 0, -1) if n % g == 0)
    _selected_counter().labels(seq=str(L)).inc(selected_pairs(L, pool, topk))
    qI, w, kI = (lax.stop_gradient(a.astype(jnp.float32))
                 for a in (qI, w, kI))
    with jax.named_scope("dsa_index"):
        kbar = kI.reshape(B, L // pool, pool, -1).mean(axis=2)

    def blocks_of(a, lo, hi):           # [n_g, B, T, ...] of rows lo..hi
        a = a[:, lo:hi]
        return jnp.moveaxis(a.reshape((B, (hi - lo) // T, T) + a.shape[2:]),
                            1, 0)

    out = []
    for g in range(G):
        lo, end = g * L // G, (g + 1) * L // G
        kbar_g, c_g = kbar[:, :end // pool], c[:, :end]

        def one_block(_, xs, kbar_g=kbar_g, c_g=c_g, end=end):
            start, qa_b, qI_b, w_b = xs
            with jax.named_scope("dsa_index"):
                score = index_scores(qI_b, w_b, kbar_g, start, pool)
                chosen = select_blocks(score, start, pool, topk)
                s = jnp.arange(end)
                allowed = jnp.repeat(chosen, pool, axis=-1) \
                    & (s[None, :] <= (start + jnp.arange(T))[:, None])
                allowed = checkpoint_name(allowed, ATTN_RESIDUALS)
            with jax.named_scope("attn_dsa"):
                return None, checkpoint_name(
                    _attend(qa_b, c_g, allowed, jnp.float32(scale)),
                    ATTN_RESIDUALS)
        _, o = lax.scan(one_block, None, (
            jnp.arange(lo, end, T), blocks_of(qa, lo, end),
            blocks_of(qI, lo, end), blocks_of(w, lo, end)))
        out.append(jnp.moveaxis(o, 0, 1).reshape(B, end - lo, H, R))
    return jnp.concatenate(out, axis=1)
