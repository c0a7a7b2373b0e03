"""The gated delta rule with a per-channel decay (KDA, the Kimi-Linear
layer's recurrence), computed chunk by chunk.

Per head, with state ``S`` [K, V] (float32), key ``k_t`` and query ``q_t``
[K], value ``v_t`` [V], log-decay ``g_t`` [K] (``<= 0``, so the decay
``exp(g_t)`` lies in (0, 1]) and step size ``beta_t`` (any real; (0, 2) lets
the transition have negative eigenvalues):

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                          S_0 given

The recurrence is sequential over positions; :func:`chunked_delta_rule`
computes the same numbers ``chunk`` positions at a time (the WY / UT
transform of the delta rule, arXiv:2406.06484, with the decay of
arXiv:2510.26692 folded in).  Within a chunk, with ``G_t = sum_{u<=t} g_u``
counted from the chunk's start and ``S_0`` the state the chunk starts from:

    A[t,s]   = beta_t  sum_d k_td k_sd exp(G_td - G_sd)      s <  t
    Aqk[t,s] =         sum_d q_td k_sd exp(G_td - G_sd)      s <= t
    X        = (I + A)^-1                     (unit lower triangular)
    U        = X (beta v) - X (beta k exp G) S_0             ("new values")
    O        = (q exp G) S_0 + Aqk U
    S_C      = Diag(exp G_C) S_0 + (k exp(G_C - G))^T U

Everything but ``S_0`` is independent between chunks, so it is computed for
all chunks at once (large batched products, differentiated by JAX); only
the three lines that hold ``S_0`` run as a scan over the chunks
(:func:`_carry_state`), whose backward pass is written by hand: it keeps
ONE state a chunk — never one a position — and recomputes ``U`` per chunk.

Every exponent is ``<= 0``: a difference ``G_t - G_s`` is never split into
two factors one of which could overflow.  Pairs inside one sub-block of
``sub`` positions are summed channel by channel with their own exponent;
pairs of different sub-blocks are split at the first position ``r`` of the
later one (``s < r <= t``, so both ``G_t - G_r`` and ``G_r - G_s`` are
``<= 0``) and become matrix products.

Arithmetic: cumulative log-decays, exponentials, the triangular inverse and
the carried state are float32; the matrix products take their operands in
``compute_dtype`` (bfloat16 in training, float32 in the tests) and
accumulate in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

#: positions a chunk and a sub-block (pairs inside a sub-block are summed
#: channel by channel, pairs of different sub-blocks as matrix products);
#: swept on the v5e inside the hybrid LM's step at [1, 64, 8192, 128]: of
#: (64, 16) (64, 8) (32, 8) (32, 4) (16, 8) (16, 4) (64, 4) this pair is the
#: fastest (PERF.md section 6, PR 29).  One value each, no argument: a
#: sequence is a multiple of CHUNK positions long.
CHUNK = 32
SUB = 8
#: heads whose per-chunk operands are made together (memory, not results)
HEAD_GROUP = 4

_HI = lax.Precision.HIGHEST


def _mm(a, b, eq, cd):
    """``einsum`` with operands in the compute dtype, float32 out."""
    return jnp.einsum(eq, a.astype(cd), b.astype(cd),
                      preferred_element_type=jnp.float32)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` [..., c, c], by
    forward substitution row by row (``c`` small and static)."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    x = jnp.broadcast_to(eye, a.shape)
    for i in range(1, c):
        # row i of the inverse: e_i - a[i, :i] X[:i, :]; rows >= i of x
        # are still unit rows and a[i, j >= i] = 0, so the full row does
        row = jnp.einsum("...j,...jk->...k", a[..., i, :], x, precision=_HI)
        x = x - eye[:, i][:, None] * row[..., None, :]
    return x


def _uli_fwd(a):
    x = _unit_lower_inverse(a)
    return x, x


def _uli_bwd(x, dx):
    # d(M^-1) = -M^-1 dM M^-1  =>  dA = -X^T dX X^T, on the strict triangle
    da = -jnp.einsum("...ji,...jk,...lk->...il", x, dx, x, precision=_HI)
    return (jnp.tril(da, -1),)


_unit_lower_inverse.defvjp(_uli_fwd, _uli_bwd)


def _block_inverse(a, sub):
    """``(I + a)^-1`` for strictly lower triangular ``a`` [..., C, C]:
    ``sub``-wide diagonal blocks by substitution, then merged two at a time
    (``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``)."""
    C = a.shape[-1]

    def tiles(size, row, col):
        """The [size, size] tiles (2j + row, 2j + col) of ``a`` in units of
        ``size``, stacked on a new axis -3."""
        return jnp.stack(
            [a[..., (j + row) * size:(j + row + 1) * size,
               (j + col) * size:(j + col + 1) * size]
             for j in range(0, C // size, 2 if row or col else 1)], axis=-3)

    inv = _unit_lower_inverse(tiles(sub, 0, 0))              # [..., m, c, c]
    size = sub
    while size < C:
        p, q = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        r = -jnp.einsum("...ij,...jk,...kl->...il", q, tiles(size, 1, 0), p,
                        precision=_HI)
        top = jnp.concatenate([p, jnp.zeros_like(p)], axis=-1)
        inv = jnp.concatenate([top, jnp.concatenate([r, q], axis=-1)],
                              axis=-2)                       # [..., n, 2s, 2s]
        size *= 2
    return inv[..., 0, :, :]


def _pairs_within(rows, k, G, sub):
    """Decayed pair sums inside each sub-block, channel by channel:
    ``out[r, t, s] = sum_d rows[r,t,d] k[s,d] exp(G[t,d] - G[s,d])`` for
    ``s <= t``, zero above.  rows: [R, ..., c, K]; k, G: [..., c, K].  The
    forward pass is one fused reduction; its backward pass materialises the
    [c, c, K] exponentials, which is why the caller runs a few heads at a
    time (:data:`HEAD_GROUP`)."""
    t = jnp.arange(sub)
    keep = (t[:, None] >= t[None, :])[..., None]
    diff = G[..., :, None, :] - G[..., None, :, :]           # [..., t, s, K]
    e = jnp.exp(jnp.where(keep, diff, -jnp.inf))
    return jnp.sum(rows[..., :, None, :] * (k[..., None, :, :] * e), axis=-1)


def _decayed_pairs(q, k, G, sub, cd):
    """``(Akk, Aqk)`` [..., C, C] of the module docstring, without beta:
    ``Akk`` strictly lower, ``Aqk`` lower with its diagonal.  q, k, G:
    [..., C, K] float32, G the cumulative log-decay of the chunk."""
    C, K = q.shape[-2:]
    lead = q.shape[:-2]
    m = C // sub
    split = lambda a: a.reshape(lead + (m, sub, K))          # noqa: E731
    kb, Gb = split(k), split(G)
    rows = jnp.stack([split(q), kb])                         # [2, ..., m,c,K]
    within = _pairs_within(rows, kb, Gb, sub)                # [2, ..., m,c,c]
    # rows of sub-block i against every earlier position, split at the
    # sub-block's first position: both factors decay, none grows
    ref = Gb[..., :1, :]                                     # [..., m, 1, K]
    left = rows * jnp.exp(Gb - ref)
    out = []
    for i in range(m):
        parts = []
        if i:
            right = k[..., :i * sub, :] * jnp.exp(
                ref[..., i, :, :] - G[..., :i * sub, :])     # [..., i*c, K]
            parts.append(_mm(left[..., i, :, :], right,
                             "r...td,...sd->r...ts", cd))
        parts.append(within[..., i, :, :])
        if (i + 1) * sub < C:
            parts.append(jnp.zeros((2,) + lead + (sub, C - (i + 1) * sub),
                                   jnp.float32))
        out.append(jnp.concatenate(parts, axis=-1))
    full = jnp.concatenate(out, axis=-2)                     # [2, ..., C, C]
    return jnp.tril(full[1], -1), full[0]


# ------------------------------------------------- the scan over the chunks --

def _new_values(S, Wv, Wk, cd):
    """``U`` of a chunk that starts from state ``S``."""
    return Wv - _mm(Wk, S, "hbck,hbkv->hbcv", cd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _carry_state(S0, Wv, Wk, Qd, Aqk, Kd, gam, cd):
    """The scan: per-chunk operands stacked on axis 0 ([N, H, B, ...]),
    ``S0`` [H, B, K, V] float32.  Returns ``(O [N, H, B, C, V], S_N)``."""
    return _carry_fwd(S0, Wv, Wk, Qd, Aqk, Kd, gam, cd)[0]


def _carry_fwd(S0, Wv, Wk, Qd, Aqk, Kd, gam, cd):
    def body(S, xs):
        wv, wk, qd, aqk, kd, g = xs
        U = _new_values(S, wv, wk, cd)
        O = _mm(qd, S, "hbck,hbkv->hbcv", cd) \
            + _mm(aqk, U, "hbcs,hbsv->hbcv", cd)
        S_next = g[..., None] * S + _mm(kd, U, "hbck,hbcv->hbkv", cd)
        return S_next, (O, S)
    S_end, (O, S_starts) = lax.scan(body, S0, (Wv, Wk, Qd, Aqk, Kd, gam))
    return (O, S_end), (S_starts, Wv, Wk, Qd, Aqk, Kd, gam)


def _carry_bwd(cd, res, cot):
    S_starts, Wv, Wk, Qd, Aqk, Kd, gam = res
    dO, dS_end = cot

    def body(dS_next, xs):
        S, wv, wk, qd, aqk, kd, g, do = xs
        U = _new_values(S, wv, wk, cd)                       # recomputed
        dU = _mm(aqk, do, "hbcs,hbcv->hbsv", cd) \
            + _mm(kd, dS_next, "hbck,hbkv->hbcv", cd)
        dS = _mm(qd, do, "hbck,hbcv->hbkv", cd) + g[..., None] * dS_next \
            - _mm(wk, dU, "hbck,hbcv->hbkv", cd)
        grads = (dU.astype(wv.dtype),
                 (-_mm(dU, S, "hbcv,hbkv->hbck", cd)).astype(wk.dtype),
                 _mm(do, S, "hbcv,hbkv->hbck", cd).astype(qd.dtype),
                 _mm(do, U, "hbcv,hbsv->hbcs", cd).astype(aqk.dtype),
                 _mm(U, dS_next, "hbcv,hbkv->hbck", cd).astype(kd.dtype),
                 jnp.sum(S * dS_next, axis=-1).astype(g.dtype))
        return dS, grads

    dS0, grads = lax.scan(body, dS_end,
                          (S_starts, Wv, Wk, Qd, Aqk, Kd, gam, dO),
                          reverse=True)
    return (dS0,) + grads


_carry_state.defvjp(_carry_fwd, _carry_bwd)


def _chunk_operands(q, k, v, g, beta, sub, cd):
    """Everything of a chunk that does not hold the state, for all chunks
    at once.  q, k, v, g: [..., N, C, X] float32; beta [..., N, C, 1].
    Returns ``(Wv, Wk, Qd, Aqk, Kd, gam)`` of :func:`_carry_state`."""
    # the cumulative sum as a product with a triangle of ones, at full
    # precision: the matrix unit does in one pass over the chunk what a
    # windowed reduction does in C
    C = g.shape[-2]
    G = jnp.einsum("ts,...sk->...tk", jnp.tril(jnp.ones((C, C), g.dtype)), g,
                   precision=_HI)
    Akk, Aqk = _decayed_pairs(q, k, G, sub, cd)
    X = _block_inverse(beta * Akk, sub)
    decay = jnp.exp(G)
    Wv = jnp.einsum("...ts,...sv->...tv", X, beta * v, precision=_HI)
    Wk = jnp.einsum("...ts,...sk->...tk", X, beta * k * decay, precision=_HI)
    G_end = G[..., -1:, :]
    return (Wv, Wk.astype(cd), (q * decay).astype(cd), Aqk.astype(cd),
            (k * jnp.exp(G_end - G)).astype(cd), jnp.exp(G_end[..., 0, :]))


def chunked_delta_rule(q, k, v, g, beta, *, initial_state=None,
                       compute_dtype=None):
    """The recurrence of the module docstring over HEAD-MAJOR inputs.

    q, k: [B, H, L, K] (normalised and scaled by the caller); v: [B, H, L,
    V]; g: [B, H, L, K] log-decay, ``<= 0``; beta: [B, H, L];
    ``initial_state`` [B, H, K, V] float32 or None for zeros.  ``L`` must be
    a multiple of :data:`CHUNK` (itself a power-of-two multiple of
    :data:`SUB`).  Returns ``(o [B, H, L, V] in v's dtype, S_L [B, H, K, V]
    float32)``.  Head-major, because a chunk is then a contiguous block of
    each head's positions: a caller whose projections write [B, H, L, .]
    directly (a product's output layout is free) pays no transpose at all.

    The per-chunk operands are made :data:`HEAD_GROUP` heads at a time, each
    group rematerialised: their many [L, K]-sized float32 intermediates
    then exist for one group, not for all heads, while the scan over the
    chunks — a short chain of small products — runs once for all heads."""
    B, H, L, K = q.shape
    V = v.shape[-1]
    chunk, sub = CHUNK, SUB
    if L % chunk or chunk % sub or (chunk // sub) & (chunk // sub - 1):
        raise ValueError(
            f"chunked_delta_rule needs L={L} a multiple of chunk={chunk} and "
            f"chunk a power-of-two multiple of sub={sub}")
    cd = compute_dtype or v.dtype
    N = L // chunk
    f32 = jnp.float32
    hg = math.gcd(H, HEAD_GROUP)

    def chunks(a):          # [B, H, L, X] -> [H / hg, hg, B, N, C, X]
        a = a.reshape(B, H // hg, hg, N, chunk, -1)
        return a.transpose(1, 2, 0, 3, 4, 5)        # no copy at B = 1

    @jax.checkpoint
    def group(args):
        return _chunk_operands(*(a.astype(f32) for a in args), sub, cd)

    ops = lax.map(group, (chunks(q), chunks(k), chunks(v), chunks(g),
                          chunks(beta[..., None])))
    # [H / hg, hg, B, N, ...] -> [N, H, B, ...]: the scan's leading axis
    ops = [jnp.moveaxis(a.reshape((H,) + a.shape[2:]), 2, 0) for a in ops]
    S0 = jnp.zeros((H, B, K, V), f32) if initial_state is None \
        else jnp.swapaxes(initial_state.astype(f32), 0, 1)
    O, S_end = _carry_state(S0, *ops, cd)                    # [N, H, B, C, V]
    o = O.transpose(2, 1, 0, 3, 4).reshape(B, H, L, V)
    return o.astype(v.dtype), jnp.swapaxes(S_end, 0, 1)
