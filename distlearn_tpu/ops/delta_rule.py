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
all chunks at once (:func:`_chunk_operands`: the six operands ``Wv = X
(beta v)``, ``Wk = X (beta k exp G)``, ``Qd = q exp G``, ``Aqk``, ``Kd = k
exp(G_C - G)``, ``gam = exp G_C``); only the three lines that hold ``S_0``
run as a scan over the chunks (:func:`_carry_state`).  Both halves are
differentiated by hand.  The scan's backward pass keeps ONE state a chunk —
never one a position — and recomputes ``U`` per chunk.

The pull-back of the operands (:func:`_operands_vjp`), per head and chunk,
for cotangents ``dWv, dWk, dQd, dAqk, dKd, dgam``; ``P_a[t,s] = sum_d a_td
k_sd exp(G_td - G_sd)`` is the pair matrix of ``a`` (``A = beta tril(P_k,
-1)``, ``Aqk = tril(P_q)``), ``Bv = beta v``, ``Bk = beta k exp G``:

    dBv = X^T dWv       dBk = X^T dWk
    dM  = -tril(dBv Wv^T + dBk Wk^T, -1)          (= -X^T (dWv Bv^T + dWk
                                                   Bk^T) X^T: d(M^-1) =
                                                   -M^-1 dM M^-1)
    dbeta = sum_s dM Akk + sum_d dBv v + sum_d dBk k exp G
    dv  = beta dBv
    a pair matrix with cotangent dP (masked like P) gives
        da[t,d] = sum_s dP[t,s] k[s,d] exp(G_td - G_sd)
        dk[s,d] = sum_t dP[t,s] a[t,d] exp(G_td - G_sd)
        dG     += a da - k dk                     (every pair holds exp(G_t -
                                                   G_s): nothing more to do)
      once with a = k, dP = beta dM and once with a = q, dP = tril(dAqk),
      evaluated in the forward's two regimes (below), so no [c, c, K] array
    dq += dQd exp G                     dG += dQd Qd
    dk += dKd exp(G_C - G) + beta exp(G) dBk
                                        dG += dBk Bk - dKd Kd
    dG_C += sum_t dKd Kd + dgam gam
    dg  = the suffix sum of dG inside the chunk

Kept for it from the forward: the inputs (alive anyway) and the two [C, C]
matrices ``X`` and ``Akk`` a chunk; recomputed: ``G``, the decays, ``Wv``
and ``Wk`` in float32 and the pair sums' exponentials.  The pull-back was
JAX's own until PR 37: autodiff of the pair sums wrote their [c, c, K]
exponentials (19.2 GB for all heads), which is why the operands were made
four heads at a time, each group under its own ``jax.checkpoint`` — one more
forward in every backward pass.

Two evaluations of that one ``custom_vjp`` (:func:`select_delta_rule`, from
backend, dtype and shapes): on the TPU in bfloat16 two Pallas kernels, a
grid step a head's 128 positions, every intermediate in VMEM, the operands
written straight into the scan's layout; elsewhere ``jnp``, a group of
:data:`HEAD_GROUP` heads at a time (for all heads at once the TPU compiler
writes 2 GB of exponentials or a dozen lane-padded 512 MB slices: the step
no longer fits the chip), with no checkpoint of its own.

The scan has two evaluations too (:func:`select_delta_scan`, from the same
things and the chunk count).  On the TPU in bfloat16 two Pallas kernels
(:func:`_scan_kernel`, :func:`_scan_kernel_vjp`): a grid step carries
:data:`SCAN_HEADS` heads through :data:`SCAN_CHUNKS` chunks, and each
head's float32 state stays in VMEM across all of its chunks, so HBM sees
the operands, O and — only in the forward rule, for the pull-back — the
state each chunk starts from; the primal, which a rematerialised step's
forward pass runs, writes O and the last state alone (``optimize_remat``:
a Pallas call's unused output is never dropped, as a ``lax.scan``'s is).
Inside a grid step a loop walks the chunks, the heads unrolled under each.
The sweep of (heads, chunks) a step is at :data:`SCAN_HEADS`: eight heads
interleaved fill the matrix unit, and a layer's three calls take 10.7 ms
on the v5e where the ``lax.scan`` and its glue took about 25.  Elsewhere
the ``lax.scan`` over all heads, a loop whose carry XLA keeps in HBM: the
CPU's path and the kernels' reference in the tests.

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

from distlearn_tpu import obs
from distlearn_tpu.parallel import sequence

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
    ``s <= t``, zero above.  rows: [R, ..., c, K]; k, G: [..., c, K].  One
    fused reduction: the [c, c, K] exponentials are never written."""
    t = jnp.arange(sub)
    keep = (t[:, None] >= t[None, :])[..., None]
    diff = G[..., :, None, :] - G[..., None, :, :]           # [..., t, s, K]
    e = jnp.exp(jnp.where(keep, diff, -jnp.inf))
    return jnp.sum(rows[..., :, None, :] * (k[..., None, :, :] * e), axis=-1)


def _pairs_within_vjp(rows, k, G, dP, sub):
    """The pull-back of :func:`_pairs_within` for ``dP`` [R, ..., c, c]
    (zero above the diagonal): ``(drows [R, ..., c, K], dk [..., c, K])``.
    One earlier position ``s`` at a time, so that no [c, c, K] array is
    ever an operand the compiler could choose to write (for all heads it
    is 2 GB): the exponentials of a step are [c, K] a sub-block."""
    t = jnp.arange(sub)[:, None]
    drows, dk = 0.0, []
    for s in range(sub):
        e = jnp.exp(jnp.where(t >= s, G - G[..., s:s + 1, :], -jnp.inf))
        w = dP[..., s:s + 1] * e                             # [R, ..., c, K]
        drows = drows + w * k[..., s:s + 1, :]
        dk.append(jnp.sum(w * rows, axis=(0, -2)))
    return drows, jnp.stack(dk, axis=-2)


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


def _decayed_pairs_vjp(q, k, G, dAkk, dAqk, sub, cd):
    """The pull-back of :func:`_decayed_pairs`: ``(dq, dk_t, dk_s)``
    [..., C, K] for cotangents ``dAkk`` (strictly lower) and ``dAqk``
    (lower) [..., C, C] — ``dk_t`` what ``k`` gets as the LATER position of
    a pair (the rows of ``Akk``), ``dk_s`` as the earlier one (both
    matrices).  ``G``'s gradient needs nothing more: every pair holds
    ``exp(G_t - G_s)``, so it is ``q dq + k dk_t - k dk_s``.  Evaluated as
    the forward is: inside a sub-block channel by channel, across
    sub-blocks as products split at the later one's first position."""
    C, K = q.shape[-2:]
    lead = q.shape[:-2]
    m = C // sub
    split = lambda a: a.reshape(lead + (m, sub, K))          # noqa: E731
    kb, Gb = split(k), split(G)
    rows = jnp.stack([split(q), kb])                         # [2, ..., m,c,K]
    dP = jnp.stack([dAqk, dAkk])                             # [2, ..., C, C]
    diag = jnp.stack([dP[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
                      for i in range(m)], axis=-3)           # [2, ..., m,c,c]
    drows, dk_s = _pairs_within_vjp(rows, kb, Gb, diag, sub)
    ref = Gb[..., :1, :]
    lift = jnp.exp(Gb - ref)                                 # [..., m, c, K]
    left = rows * lift
    drows = [drows[..., i, :, :] for i in range(m)]
    dk_s = dk_s.reshape(lead + (C, K))
    for i in range(1, m):
        fall = jnp.exp(ref[..., i, :, :] - G[..., :i * sub, :])
        dPi = dP[..., i * sub:(i + 1) * sub, :i * sub]       # [2, ..., c, i*c]
        dleft = _mm(dPi, k[..., :i * sub, :] * fall,
                    "r...ts,...sd->r...td", cd)
        drows[i] = drows[i] + dleft * lift[..., i, :, :]
        dright = _mm(dPi, left[..., i, :, :], "r...ts,r...td->...sd", cd)
        dk_s = dk_s.at[..., :i * sub, :].add(dright * fall)
    drows = jnp.concatenate(drows, axis=-2)                  # [2, ..., C, K]
    return drows[0], drows[1], dk_s


# ------------------------------------------------- the scan over the chunks --

def _new_values(S, Wv, Wk, cd):
    """``U`` of a chunk that starts from state ``S``."""
    return Wv - _mm(Wk, S, "hbck,hbkv->hbcv", cd)


def _scan_major(a):
    """[B, H, N, ...] -> [N, H, B, ...]: the ``lax.scan``'s leading axis."""
    return jnp.moveaxis(a, 2, 0).swapaxes(1, 2)


def _head_major(a):
    """[N, H, B, ...] -> [B, H, N, ...]."""
    return jnp.moveaxis(a.swapaxes(1, 2), 0, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _carry_state(S0, Wv, Wk, Qd, Aqk, Kd, gam, cd, od, impl):
    """The scan over the chunks, head-major: ``S0`` [B, H, K, V] float32,
    the per-chunk operands [B, H, N, C, .] and ``gam`` [B, H, N, K].
    Returns ``(O [B, H, L, V] in od, S_N [B, H, K, V] float32)``.  ``impl``
    (:func:`select_delta_scan`): ``"kernel"`` the Pallas calls of
    :func:`_scan_kernel` — this primal, which the forward pass of a
    rematerialised step runs, the one that writes no kept states
    (``optimize_remat``); ``"xla"`` a ``lax.scan`` over all heads."""
    if impl == "kernel":
        return _scan_kernel(S0, Wv, Wk, Qd, Aqk, Kd, gam, cd, od,
                            keep=False)
    return _carry_fwd(S0, Wv, Wk, Qd, Aqk, Kd, gam, cd, od, impl)[0]


def _carry_fwd(S0, Wv, Wk, Qd, Aqk, Kd, gam, cd, od, impl):
    res = (Wv, Wk, Qd, Aqk, Kd, gam)
    if impl == "kernel":
        O, S_end, S_starts = _scan_kernel(S0, *res, cd, od, keep=True)
        return (O, S_end), (S_starts,) + res

    def body(S, xs):
        wv, wk, qd, aqk, kd, g = xs
        U = _new_values(S, wv, wk, cd)
        O = _mm(qd, S, "hbck,hbkv->hbcv", cd) \
            + _mm(aqk, U, "hbcs,hbsv->hbcv", cd)
        S_next = g[..., None] * S + _mm(kd, U, "hbck,hbcv->hbkv", cd)
        return S_next, (O, S)
    S_end, (O, S_starts) = lax.scan(body, jnp.swapaxes(S0, 0, 1),
                                    tuple(_scan_major(a) for a in res))
    O = _head_major(O).reshape(S0.shape[:2] + (-1, O.shape[-1]))
    return (O.astype(od), jnp.swapaxes(S_end, 0, 1)), (S_starts,) + res


def _carry_bwd(cd, od, impl, res, cot):
    if impl == "kernel":
        return _scan_kernel_vjp(*res, *cot, cd)
    S_starts, *ops = res
    dO, dS_end = cot
    dO = dO.reshape(ops[0].shape).astype(jnp.float32)
    Wv, Wk, Qd, Aqk, Kd, gam, dO = (_scan_major(a) for a in (*ops, dO))

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

    dS0, grads = lax.scan(body, jnp.swapaxes(dS_end, 0, 1),
                          (S_starts, Wv, Wk, Qd, Aqk, Kd, gam, dO),
                          reverse=True)
    return (jnp.swapaxes(dS0, 0, 1),) + tuple(_head_major(d) for d in grads)


_carry_state.defvjp(_carry_fwd, _carry_bwd, optimize_remat=True)


# ------------------------------------------------ the per-chunk operands --

def _tri(C, dtype):
    return jnp.tril(jnp.ones((C, C), dtype))


def _operands(q, k, v, g, beta, sub, cd):
    """Everything of a chunk that does not hold the state, for all chunks
    at once, in float32 and before any rounding.  q, k, v, g: [..., C, X]
    float32; beta [..., C, 1].  Returns ``(Wv, Wk, Qd, Aqk, Kd, gam)`` of
    :func:`_carry_state` and ``(X, Akk)`` [..., C, C]."""
    # the cumulative sum as a product with a triangle of ones, at full
    # precision: the matrix unit does in one pass over the chunk what a
    # windowed reduction does in C
    G = jnp.einsum("ts,...sk->...tk", _tri(g.shape[-2], g.dtype), g,
                   precision=_HI)
    Akk, Aqk = _decayed_pairs(q, k, G, sub, cd)
    X = _block_inverse(beta * Akk, sub)
    decay = jnp.exp(G)
    Wv = jnp.einsum("...ts,...sv->...tv", X, beta * v, precision=_HI)
    Wk = jnp.einsum("...ts,...sk->...tk", X, beta * k * decay, precision=_HI)
    G_end = G[..., -1:, :]
    return (Wv, Wk, q * decay, Aqk, k * jnp.exp(G_end - G),
            jnp.exp(G_end[..., 0, :])), (X, Akk)


def _operands_vjp(q, k, v, g, beta, X, Akk, cot, sub, cd):
    """The pull-back of :func:`_operands` (module docstring, "The
    pull-back"), every array float32 and laid out as there; ``cot`` the six
    cotangents.  Returns ``(dq, dk, dv, dg, dbeta)``."""
    dWv, dWk, dQd, dAqk, dKd, dgam = cot
    tri = _tri(g.shape[-2], g.dtype)
    G = jnp.einsum("ts,...sk->...tk", tri, g, precision=_HI)
    decay = jnp.exp(G)
    G_end = G[..., -1:, :]
    tail = jnp.exp(G_end - G)
    Kd, Bv, Bk = k * tail, beta * v, beta * k * decay
    hi = functools.partial(jnp.einsum, precision=_HI)
    # 1. through the products with the inverse, and the inverse
    Wv = hi("...ts,...sv->...tv", X, Bv)
    Wk = hi("...ts,...sk->...tk", X, Bk)
    dBv = hi("...ts,...tv->...sv", X, dWv)
    dBk = hi("...ts,...tk->...sk", X, dWk)
    dM = -jnp.tril(hi("...tv,...sv->...ts", dBv, Wv)
                   + hi("...tk,...sk->...ts", dBk, Wk), -1)
    dbeta = jnp.sum(dM * Akk, axis=-1, keepdims=True) \
        + jnp.sum(dBv * v, axis=-1, keepdims=True) \
        + jnp.sum(dBk * k * decay, axis=-1, keepdims=True)
    # 2. through the two pair matrices
    dq, dk_t, dk_s = _decayed_pairs_vjp(q, k, G, beta * dM, jnp.tril(dAqk),
                                        sub, cd)
    dG = q * dq + k * (dk_t - dk_s)
    # 3. the elementwise operands
    dG = dG + dQd * q * decay + dBk * Bk - dKd * Kd
    dG = dG.at[..., -1, :].add(jnp.sum(dKd * Kd, axis=-2)
                               + dgam * jnp.exp(G_end[..., 0, :]))
    dq = dq + dQd * decay
    dk = dk_t + dk_s + dKd * tail + beta * decay * dBk
    # 4. the cumulative sum's transpose: a suffix sum inside the chunk
    dg = jnp.einsum("st,...sk->...tk", tri, dG, precision=_HI)
    return dq, dk, beta * dBv, dg, dbeta


# ------------------------------------------- the operands as Pallas kernels --
#
# One grid step makes the operands of ``_ROWS`` = 128 consecutive positions
# of one head (``_ROWS / C`` chunks) with every intermediate in VMEM.  The
# [C, C] matrices of those chunks are held as ONE [128, 128] matrix that is
# block-diagonal by chunk, so a product with them is one pass of the 128 x
# 128 matrix unit (which a [32, 32] product would occupy just as long), and
# "the same chunk" is a mask, never a slice.  Positions lie on sublanes,
# channels on lanes; a sub-block of 8 positions is one vector register.

#: positions a grid step of the kernels covers
_ROWS = 128
#: rows of the block that carries a grid step's ``gam`` (a chunk a row)
_GAM_ROWS = 8


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _dot(a, b, dims=((1,), (0,))):
    """A float32 product at "highest" (six passes), contracting ``dims``."""
    return lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def _dot_cd(a, b, dims, cd):
    """A product with operands in the compute dtype, float32 out."""
    return lax.dot_general(a.astype(cd), b.astype(cd), (dims, ((), ())),
                           preferred_element_type=jnp.float32)


def _group_row(a, size, s):
    """Row ``s`` of every group of ``size`` rows of ``a`` [R, X], broadcast
    over the group's rows."""
    R, X = a.shape
    a = a.reshape(R // size, size, X)
    return jnp.broadcast_to(a[:, s:s + 1, :], a.shape).reshape(R, X)


def _group_sum(a, size):
    """The sum over every group of ``size`` rows of ``a`` [R, X], broadcast
    over the group's rows."""
    R, X = a.shape
    a = a.reshape(R // size, size, X)
    return jnp.broadcast_to(jnp.sum(a, axis=1, keepdims=True),
                            a.shape).reshape(R, X)


class _Block:
    """The index masks of a block of ``R`` positions cut into chunks of
    ``C`` and sub-blocks of ``sub``."""

    def __init__(self, R, C, sub):
        self.row, self.col = _iota((R, R), 0), _iota((R, R), 1)
        self.same = self.row // C == self.col // C         # the same chunk
        #: the column counted from the start of the ROW's sub-block
        self.colsub = self.col - self.row // sub * sub
        t = _iota((R, 1), 0)
        self.tsub, self.tch = t % sub, t % C


def _k_pairs(q, k, G, m, C, sub, cd):
    """The decayed pair sums of :func:`_decayed_pairs` on a block: ``(Pq,
    Pk)`` [R, R], both lower triangular WITH the diagonal, zero outside
    the row's chunk."""
    R = q.shape[0]
    Pq = Pk = jnp.zeros((R, R), jnp.float32)
    for s in range(sub):             # pairs (t, s) inside a sub-block
        e = jnp.exp(jnp.where(m.tsub >= s, G - _group_row(G, sub, s),
                              -jnp.inf))
        ke = _group_row(k, sub, s) * e
        hit = m.colsub == s
        Pq = jnp.where(hit, jnp.sum(q * ke, axis=1, keepdims=True), Pq)
        Pk = jnp.where(hit, jnp.sum(k * ke, axis=1, keepdims=True), Pk)
    lift = jnp.exp(G - _group_row(G, sub, 0))
    left_q, left_k = (q * lift).astype(cd), (k * lift).astype(cd)
    for i in range(1, C // sub):     # sub-block i against the earlier ones
        fall = jnp.exp(jnp.where(m.tch < i * sub,
                                 _group_row(G, C, i * sub) - G, -jnp.inf))
        here = m.same & (m.tch // sub == i)
        right = (k * fall).astype(cd)
        Pq = Pq + jnp.where(here, _dot_cd(left_q, right, ((1,), (1,)), cd),
                            0.0)
        Pk = Pk + jnp.where(here, _dot_cd(left_k, right, ((1,), (1,)), cd),
                            0.0)
    return Pq, Pk


def _k_pairs_vjp(q, k, G, dPq, dPk, m, C, sub, cd):
    """:func:`_decayed_pairs_vjp` on a block: ``(dq, dk_t, dk_s)`` [R, K]
    for ``dPq`` (lower), ``dPk`` (strictly lower) [R, R]."""
    dq = dkt = dks = jnp.zeros(q.shape, jnp.float32)
    for s in range(sub):
        e = jnp.exp(jnp.where(m.tsub >= s, G - _group_row(G, sub, s),
                              -jnp.inf))
        hit = m.colsub == s
        wq = jnp.sum(jnp.where(hit, dPq, 0.0), axis=1, keepdims=True)
        wk = jnp.sum(jnp.where(hit, dPk, 0.0), axis=1, keepdims=True)
        ke = _group_row(k, sub, s) * e
        dq, dkt = dq + wq * ke, dkt + wk * ke
        dks = jnp.where(m.tsub == s,
                        _group_sum((wq * q + wk * k) * e, sub), dks)
    lift = jnp.exp(G - _group_row(G, sub, 0))
    left_q, left_k = (q * lift).astype(cd), (k * lift).astype(cd)
    for i in range(1, C // sub):
        fall = jnp.exp(jnp.where(m.tch < i * sub,
                                 _group_row(G, C, i * sub) - G, -jnp.inf))
        cut = m.same & (m.tch // sub == i) & (m.col % C < i * sub)
        dPqi = jnp.where(cut, dPq, 0.0).astype(cd)
        dPki = jnp.where(cut, dPk, 0.0).astype(cd)
        right = (k * fall).astype(cd)
        dq = dq + _dot_cd(dPqi, right, ((1,), (0,)), cd) * lift
        dkt = dkt + _dot_cd(dPki, right, ((1,), (0,)), cd) * lift
        dks = dks + fall * (_dot_cd(dPqi, left_q, ((0,), (0,)), cd)
                            + _dot_cd(dPki, left_k, ((0,), (0,)), cd))
    return dq, dkt, dks


def _k_inverse(A, m, C, sub):
    """``(I + A)^-1`` for ``A`` [R, R] strictly lower triangular and zero
    outside the row's chunk.  The ``sub``-wide diagonal blocks by
    elimination, a column at a time — ``(I + D)^-1 = (I - d_{c-2} e^T) ...
    (I - d_0 e^T)``, the arithmetic of the substitution row by row — then
    merged two at a time as :func:`_block_inverse` does, each level two
    products of whole blocks: ``X - X A_off X``."""
    X = (m.row == m.col).astype(jnp.float32)
    for j in range(sub - 1):
        d = jnp.sum(jnp.where(m.colsub == j, A, 0.0), axis=1, keepdims=True)
        X = X - d * _group_row(X, sub, j)
    size = sub
    while size < C:
        off = (m.row // (2 * size) == m.col // (2 * size)) \
            & (m.row // size % 2 == 1) & (m.col // size % 2 == 0)
        X = X - _dot(_dot(X, jnp.where(off, A, 0.0)), X)
        size *= 2
    return X


def _last_rows(C):
    """[_GAM_ROWS, _ROWS] one-hot: row ``n`` picks the last position of the
    block's chunk ``n`` (a product with it at "highest" is exact)."""
    shape = (_GAM_ROWS, _ROWS)
    return (_iota(shape, 1) == _iota(shape, 0) * C + C - 1).astype(
        jnp.float32)


def _beta_column(brow, m):
    """beta [1, R] (lane-dense in HBM) as the column [R, 1] that scales
    rows."""
    return jnp.sum(jnp.where(m.row == m.col, brow, 0.0), axis=1,
                   keepdims=True)


def _k_operands(q, k, v, g, brow, C, sub, cd):
    """:func:`_operands` on a block: q, k, v, g [R, X] float32, beta [1, R].
    Returns ``Wv, Wk, Qd, Aqk, Kd, exp(G), X, Akk`` — the [C, C] ones as
    [R, R] block-diagonal by chunk, ``gam`` the last row of a chunk of
    ``exp(G)``."""
    m = _Block(q.shape[0], C, sub)
    beta = _beta_column(brow, m)
    G = _dot((m.same & (m.col <= m.row)).astype(jnp.float32), g)
    Aqk, Pk = _k_pairs(q, k, G, m, C, sub, cd)
    Akk = jnp.where(m.col < m.row, Pk, 0.0)
    X = _k_inverse(beta * Akk, m, C, sub)
    decay = jnp.exp(G)
    return (_dot(X, beta * v), _dot(X, beta * k * decay), q * decay, Aqk,
            k * jnp.exp(_group_row(G, C, C - 1) - G), decay, X, Akk)


def _k_operands_vjp(q, k, v, g, brow, X, Akk, dWv, dWk, dQd, dAqk, dKd,
                    dgam, C, sub, cd):
    """:func:`_operands_vjp` on a block.  ``X``, ``Akk`` [R, R] as
    :func:`_k_operands` left them; ``dAqk`` [R, C] (a chunk's rows under
    one another), ``dgam`` [_GAM_ROWS, K] (a chunk a row).  Returns ``dq,
    dk, dv, dg`` [R, X] and ``dbeta`` [1, R]."""
    R = q.shape[0]
    m = _Block(R, C, sub)
    beta = _beta_column(brow, m)
    lower = m.same & (m.col <= m.row)
    G = _dot(lower.astype(jnp.float32), g)
    decay = jnp.exp(G)
    tail = jnp.exp(_group_row(G, C, C - 1) - G)
    kd, Kd = k * decay, k * tail
    Bv, Bk = beta * v, beta * kd
    # 1. through the products with the inverse, and the inverse
    dBv, dBk = _dot(X, dWv, ((0,), (0,))), _dot(X, dWk, ((0,), (0,)))
    dM = -jnp.where(m.same & (m.col < m.row),
                    _dot(dBv, _dot(X, Bv), ((1,), (1,)))
                    + _dot(dBk, _dot(X, Bk), ((1,), (1,))), 0.0)
    dbeta = jnp.sum(dM * Akk, axis=1, keepdims=True) \
        + jnp.sum(dBv * v, axis=1, keepdims=True) \
        + jnp.sum(dBk * kd, axis=1, keepdims=True)
    # 2. through the two pair matrices (dAqk spread over its chunk's columns)
    spread = (_iota((C, R), 1) % C == _iota((C, R), 0)).astype(jnp.float32)
    dPq = jnp.where(lower, _dot_cd(dAqk, spread, ((1,), (0,)), cd), 0.0)
    dq, dkt, dks = _k_pairs_vjp(q, k, G, dPq, beta * dM, m, C, sub, cd)
    # 3. the elementwise operands; a chunk's last row also gets G_C's
    dG = q * dq + k * (dkt - dks) + dQd * q * decay + dBk * Bk - dKd * Kd \
        + jnp.where(m.tch == C - 1, _group_sum(dKd * Kd, C), 0.0) \
        + _dot(_last_rows(C), dgam, ((0,), (0,))) * decay
    dq = dq + dQd * decay
    dk = dkt + dks + dKd * tail + beta * decay * dBk
    # 4. the suffix sum inside the chunk
    dg = _dot((m.same & (m.col >= m.row)).astype(jnp.float32), dG)
    return dq, dk, beta * dBv, dg, jnp.sum(
        jnp.where(m.row == m.col, dbeta, 0.0), axis=0, keepdims=True)


def _loaded(refs, i, L):
    """The blocks of ``refs`` as float32 [R, X], the rows of a last block
    that lie past the sequence's end zeroed (what is read there is not
    data, and a masked product would still multiply it)."""
    xs = [r[...].astype(jnp.float32) for r in refs]
    xs = [x.reshape(_ROWS, x.shape[-1]) for x in xs]
    if L % _ROWS == 0:
        return xs
    inside = _iota((_ROWS, 1), 0) < L - i * _ROWS
    return [jnp.where(inside, x, 0.0) for x in xs]


def _specs(chunk):
    """The BlockSpecs of a grid ``(B, H, L / _ROWS)``: ``seq`` for
    [B, H, L, X], ``ops`` for the scan's [B, H, N, C, X], ``blk`` for
    [B, H, L / _ROWS, ...]."""
    from jax.experimental import pallas as pl
    nb = _ROWS // chunk
    seq = lambda X: pl.BlockSpec(                             # noqa: E731
        (None, None, _ROWS, X), lambda b, h, i: (b, h, i, 0))
    ops = lambda X: pl.BlockSpec(                             # noqa: E731
        (None, None, nb, chunk, X), lambda b, h, i: (b, h, i, 0, 0))
    blk = lambda r, c: pl.BlockSpec(                          # noqa: E731
        (None, None, None, r, c), lambda b, h, i: (b, h, i, 0, 0))
    return seq, ops, blk


def _pallas(kernel, grid, in_specs, out_specs, out_shape, interpret, name):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid)),
        interpret=interpret, name=name)


def _beta_rows(beta, nblk):
    """beta [B, H, L] -> [B, H, nblk, 1, _ROWS]: a block's step sizes on
    the lanes."""
    B, H, L = beta.shape
    beta = jnp.pad(beta.astype(jnp.float32),
                   ((0, 0), (0, 0), (0, nblk * _ROWS - L)))
    return beta.reshape(B, H, nblk, 1, _ROWS)


def _kernel_fwd(q, k, v, g, beta, chunk, sub, cd, interpret):
    """:func:`_chunk_operands` by one kernel call: the six operands laid
    out for the scan, and ``(X, Akk)`` [B, H, L / _ROWS, _ROWS, _ROWS]."""
    from jax.experimental import pallas as pl
    B, H, L, K = q.shape
    V = v.shape[-1]
    N, nb, nblk = L // chunk, _ROWS // chunk, -(-L // _ROWS)

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, wv_ref, wk_ref, qd_ref,
               aqk_ref, kd_ref, gam_ref, x_ref, akk_ref):
        Wv, Wk, Qd, Aqk, Kd, decay, X, Akk = _k_operands(
            *_loaded((q_ref, k_ref, v_ref, g_ref), pl.program_id(2), L),
            b_ref[...], chunk, sub, cd)
        for ref, a in ((wv_ref, Wv), (wk_ref, Wk), (qd_ref, Qd),
                       (kd_ref, Kd)):
            ref[...] = a.reshape(ref.shape).astype(ref.dtype)
        for n in range(nb):                    # a chunk's own [C, C] block
            rows = slice(n * chunk, (n + 1) * chunk)
            aqk_ref[n] = Aqk[rows, rows].astype(aqk_ref.dtype)
        gam_ref[...] = _dot(_last_rows(chunk), decay)
        x_ref[...], akk_ref[...] = X, Akk

    seq, ops, blk = _specs(chunk)
    scan = lambda X, dt: jax.ShapeDtypeStruct((B, H, N, chunk, X), dt)  # noqa
    square = jax.ShapeDtypeStruct((B, H, nblk, _ROWS, _ROWS), jnp.float32)
    Wv, Wk, Qd, Aqk, Kd, gam, X, Akk = _pallas(
        kernel, (B, H, nblk),
        [seq(K), seq(K), seq(V), seq(K), blk(1, _ROWS)],
        [ops(V), ops(K), ops(K), ops(chunk), ops(K), blk(_GAM_ROWS, K),
         blk(_ROWS, _ROWS), blk(_ROWS, _ROWS)],
        [scan(V, jnp.float32), scan(K, cd), scan(K, cd), scan(chunk, cd),
         scan(K, cd),
         jax.ShapeDtypeStruct((B, H, nblk, _GAM_ROWS, K), jnp.float32),
         square, square], interpret, "delta_rule_operands")(
             q, k, v, g, _beta_rows(beta, nblk))
    gam = gam[:, :, :, :nb].reshape(B, H, nblk * nb, K)[:, :, :N]
    return (Wv, Wk, Qd, Aqk, Kd, gam), (X, Akk)


def _kernel_bwd(q, k, v, g, beta, X, Akk, cot, chunk, sub, cd, interpret):
    """The pull-back of :func:`_kernel_fwd` by one kernel call: ``(dq, dk,
    dv, dg, dbeta)`` in the inputs' shapes and dtypes."""
    from jax.experimental import pallas as pl
    B, H, L, K = q.shape
    V = v.shape[-1]
    N, nb, nblk = L // chunk, _ROWS // chunk, -(-L // _ROWS)
    *cot, dgam = cot
    # [B, H, N, K] -> [B, H, nblk, _GAM_ROWS, K]: a chunk a row
    dgam = jnp.pad(dgam.astype(jnp.float32),
                   ((0, 0), (0, 0), (0, nblk * nb - N), (0, 0)))
    dgam = jnp.pad(dgam.reshape(B, H, nblk, nb, K),
                   ((0, 0),) * 3 + ((0, _GAM_ROWS - nb), (0, 0)))

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, x_ref, akk_ref, dwv_ref,
               dwk_ref, dqd_ref, daqk_ref, dkd_ref, dgam_ref, *out_refs):
        qv, kv, vv, gv, *cots = _loaded(
            (q_ref, k_ref, v_ref, g_ref, dwv_ref, dwk_ref, dqd_ref,
             daqk_ref, dkd_ref), pl.program_id(2), L)
        grads = _k_operands_vjp(qv, kv, vv, gv, b_ref[...], x_ref[...],
                                akk_ref[...], *cots, dgam_ref[...], chunk,
                                sub, cd)
        for ref, d in zip(out_refs, grads):
            ref[...] = d.astype(ref.dtype)

    seq, ops, blk = _specs(chunk)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)   # noqa: E731
    *grads, dbeta = _pallas(
        kernel, (B, H, nblk),
        [seq(K), seq(K), seq(V), seq(K), blk(1, _ROWS), blk(_ROWS, _ROWS),
         blk(_ROWS, _ROWS), ops(V), ops(K), ops(K), ops(chunk), ops(K),
         blk(_GAM_ROWS, K)],
        [seq(K), seq(K), seq(V), seq(K), blk(1, _ROWS)],
        [like(q), like(k), like(v), like(g),
         jax.ShapeDtypeStruct((B, H, nblk, 1, _ROWS), jnp.float32)],
        interpret, "delta_rule_operands_vjp")(
            q, k, v, g, _beta_rows(beta, nblk), X, Akk, *cot, dgam)
    dbeta = dbeta.reshape(B, H, nblk * _ROWS)[:, :, :L]
    return (*grads, dbeta.astype(beta.dtype))


# ------------------------------------------- the scan as Pallas kernels --
#
# A grid ``(B, H / hb, N / nc)``: a step carries ``hb`` heads through ``nc``
# chunks, the chunk axis sequential ("arbitrary"; the pull-back walks it
# backwards through its index maps).  Each head's state lives in a VMEM
# scratch for the whole walk, TRANSPOSED (``T = S^T`` [V, K]): ``gam``, a
# row over K, then scales it along the lanes, and no product needs more
# than the transposes the matrix unit's operand load makes.  Inside a step
# a loop walks the chunks and the heads are unrolled under each, so that
# their independent chains of small products interleave on the matrix unit
# (a head alone is a chain of two dependent 32-row products a chunk).  The
# chunks stay a loop: every process traces and lowers a kernel's body anew,
# and ``hb x nc`` unrolled bodies a call cost the cell seconds of set-up.

#: heads and chunks a grid step of the scan's kernels carries; swept on the
#: v5e at [1, 64, 8192, 128] bf16, a layer's three calls (primal, keeping,
#: pull-back; ms), the chunks a loop: 4 x 16 11.30, 8 x 8 10.66,
#: 8 x 16 10.62, 8 x 32 10.65, 16 x 8 10.64, 16 x 16 10.66 (the lax.scan
#: 27.6 without its primal; the chunks unrolled, 1 x 8 22.57, 2 x 8 13.57,
#: 4 x 8 10.91, 8 x 8 10.55, 16 x 8 10.42): eight heads fill the matrix
#: unit and the chunks a step do not matter (PERF.md section 6, PR 39).
#: One value each, no argument.
SCAN_HEADS = 8
SCAN_CHUNKS = 8


def _scan_block(H, N):
    """``(hb, nc)`` of a call: heads and chunks a grid step."""
    return math.gcd(H, SCAN_HEADS), SCAN_CHUNKS if N % SCAN_CHUNKS == 0 else N


def _scan_specs(B, H, N, C, V, K, reverse):
    """The BlockSpecs of the grid ``(B, H / hb, N / nc)`` and ``(hb, nc)``:
    ``per`` for the operands, O and dO [B, H, N, C, X], ``gams`` for
    [B, H, N, K], ``kept`` for the kept states [B, H, N, V, K] and
    ``state`` for a state [B, H, V, K] (the same block over the walk)."""
    from jax.experimental import pallas as pl
    hb, nc = _scan_block(H, N)
    last = N // nc - 1
    at = (lambda i: last - i) if reverse else (lambda i: i)   # noqa: E731
    per = lambda X: pl.BlockSpec(                             # noqa: E731
        (None, hb, nc, C, X), lambda b, h, i: (b, h, at(i), 0, 0))
    gams = pl.BlockSpec((None, hb, nc, K), lambda b, h, i: (b, h, at(i), 0))
    kept = pl.BlockSpec((None, hb, nc, V, K),
                        lambda b, h, i: (b, h, at(i), 0, 0))
    state = pl.BlockSpec((None, hb, V, K), lambda b, h, i: (b, h, 0, 0))
    return per, gams, kept, state, (hb, nc)


def _vmem_bytes(specs, dtypes):
    """What a grid step's blocks take in VMEM, double-buffered, with their
    two minor dimensions padded to whole tiles."""
    total = 0
    for spec, dt in zip(specs, dtypes):
        *lead, r, c = (1 if d is None else d for d in spec.block_shape)
        total += math.prod(lead) * -(-r // 16) * 16 * -(-c // 128) * 128 \
            * jnp.dtype(dt).itemsize
    return 2 * total


def _scan_call(kernel, grid, in_specs, args, out_specs, out_shape, name):
    """One Pallas call of the scan on ``args`` (interpret mode off the
    TPU), ``in_specs[0]`` a state's: a VMEM scratch for the ``hb`` states
    [V, K] of a step, and the VMEM its blocks take."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    hb, V, K = in_specs[0].block_shape[1:]
    vmem = _vmem_bytes(in_specs + out_specs,
                       [a.dtype for a in args] + [o.dtype for o in out_shape])
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, V, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + hb * V * K * 4 + (8 << 20)),
        interpret=sequence._backend() != "tpu", name=name)(*args)


def _scan_kernel(S0, Wv, Wk, Qd, Aqk, Kd, gam, cd, od, keep):
    """:func:`_carry_state`'s forward by one Pallas call (interpret mode off
    the TPU): ``(O [B, H, L, V] in od, S_N)``, and with ``keep`` the state
    each chunk starts from, [B, H, N, V, K] (transposed, as it lives)."""
    from jax.experimental import pallas as pl
    B, H, N, C, V = Wv.shape
    K = Wk.shape[-1]
    per, gams, kept, state, (hb, nc) = _scan_specs(B, H, N, C, V, K, False)

    def kernel(s0_ref, wv_ref, wk_ref, qd_ref, aqk_ref, kd_ref, gam_ref,
               o_ref, end_ref, *rest):
        *starts, t_ref = rest
        i = pl.program_id(2)

        @pl.when(i == 0)
        def _():
            t_ref[...] = s0_ref[...]

        def chunk(n, carry):
            for h in range(hb):
                T = t_ref[h]
                if starts:
                    starts[0][h, n] = T
                Tc = T.astype(cd)
                # Wk S and Qd S: one pass over the state
                P = _dot_cd(jnp.concatenate([wk_ref[h, n], qd_ref[h, n]]),
                            Tc, ((1,), (1,)), cd)
                U = (wv_ref[h, n] - P[:C]).astype(cd)
                O = P[C:] + _dot_cd(aqk_ref[h, n], U, ((1,), (0,)), cd)
                o_ref[h, n] = O.astype(o_ref.dtype)
                t_ref[h] = T * gam_ref[h, pl.ds(n, 1), :] \
                    + _dot_cd(U, kd_ref[h, n], ((0,), (0,)), cd)
            return carry

        lax.fori_loop(0, nc, chunk, 0)

        @pl.when(i == pl.num_programs(2) - 1)
        def _():
            end_ref[...] = t_ref[...]

    f32 = jnp.float32
    out_specs = [per(V), state] + ([kept] if keep else [])
    out_shape = [jax.ShapeDtypeStruct((B, H, N, C, V), od),
                 jax.ShapeDtypeStruct((B, H, V, K), f32)]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((B, H, N, V, K), f32))
    out = _scan_call(
        kernel, (B, H // hb, N // nc),
        [state, per(V), per(K), per(K), per(C), per(K), gams],
        (jnp.swapaxes(S0, 2, 3), Wv, Wk, Qd, Aqk, Kd, gam), out_specs,
        out_shape, "delta_rule_scan")
    return (out[0].reshape(B, H, N * C, V), jnp.swapaxes(out[1], 2, 3)) \
        + tuple(out[2:])


def _scan_kernel_vjp(S_starts, Wv, Wk, Qd, Aqk, Kd, gam, dO, dS_end, cd):
    """:func:`_carry_state`'s pull-back by one Pallas call, the chunks walked
    backwards, ``U`` recomputed from the kept state: ``(dS0, dWv, dWk,
    dQd, dAqk, dKd, dgam)`` in the shapes and dtypes of the primal's
    arguments."""
    from jax.experimental import pallas as pl
    B, H, N, C, V = Wv.shape
    K = Wk.shape[-1]
    per, gams, kept, state, (hb, nc) = _scan_specs(B, H, N, C, V, K, True)

    def kernel(end_ref, kept_ref, wv_ref, wk_ref, qd_ref, aqk_ref, kd_ref,
               gam_ref, do_ref, ds0_ref, dwv_ref, dwk_ref, dqd_ref, daqk_ref,
               dkd_ref, dgam_ref, e_ref):
        i = pl.program_id(2)

        @pl.when(i == 0)
        def _():
            e_ref[...] = end_ref[...]

        def chunk(j, carry):
            n = nc - 1 - j
            for h in range(hb):
                T, E = kept_ref[h, n], e_ref[h]    # S^T and dS_next^T
                Tc, Ec = T.astype(cd), E.astype(cd)
                wk, kd = wk_ref[h, n], kd_ref[h, n]
                do = do_ref[h, n].astype(cd)
                U = (wv_ref[h, n] - _dot_cd(wk, Tc, ((1,), (1,)), cd)
                     ).astype(cd)
                dU = _dot_cd(aqk_ref[h, n], do, ((0,), (0,)), cd) \
                    + _dot_cd(kd, Ec, ((1,), (1,)), cd)
                both = jnp.concatenate([do, dU.astype(cd)])
                # dS^T = dO^T Qd - dU^T Wk + gam dS_next^T
                e_ref[h] = E * gam_ref[h, pl.ds(n, 1), :] + _dot_cd(
                    both, jnp.concatenate([qd_ref[h, n], -wk]),
                    ((0,), (0,)), cd)
                dqk = _dot_cd(both, Tc, ((1,), (0,)), cd)  # dQd; -dWk
                dwv_ref[h, n] = dU.astype(dwv_ref.dtype)
                dqd_ref[h, n] = dqk[:C].astype(dqd_ref.dtype)
                dwk_ref[h, n] = (-dqk[C:]).astype(dwk_ref.dtype)
                daqk_ref[h, n] = _dot_cd(do, U, ((1,), (1,)), cd).astype(
                    daqk_ref.dtype)
                dkd_ref[h, n] = _dot_cd(U, Ec, ((1,), (0,)), cd).astype(
                    dkd_ref.dtype)
                dgam_ref[h, pl.ds(n, 1), :] = jnp.sum(T * E, axis=0,
                                                      keepdims=True)
            return carry

        lax.fori_loop(0, nc, chunk, 0)

        @pl.when(i == pl.num_programs(2) - 1)
        def _():
            ds0_ref[...] = e_ref[...]

    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)   # noqa: E731
    out = _scan_call(
        kernel, (B, H // hb, N // nc),
        [state, kept, per(V), per(K), per(K), per(C), per(K), gams, per(V)],
        (jnp.swapaxes(dS_end.astype(jnp.float32), 2, 3), S_starts, Wv, Wk,
         Qd, Aqk, Kd, gam, dO.reshape(B, H, N, C, V)),
        [state, per(V), per(K), per(K), per(C), per(K), gams],
        [jax.ShapeDtypeStruct((B, H, V, K), jnp.float32), like(Wv), like(Wk),
         like(Qd), like(Aqk), like(Kd), like(gam)], "delta_rule_scan_vjp")
    return (jnp.swapaxes(out[0], 2, 3),) + tuple(out[1:])


# ------------------------------------------------------ the path, and both --

#: the implementations of the per-chunk operands (:func:`select_delta_rule`)
DELTA_RULE_IMPLS = ("xla", "kernel")


def select_delta_rule(backend: str, dtype, K: int, V: int, chunk: int,
                      sub: int) -> str:
    """The path of a :func:`chunked_delta_rule` call's per-chunk operands,
    decided from what the call itself shows — backend, compute dtype, the
    head's widths, the chunk and sub-block — and from nothing else.

    ``"kernel"`` (the two Pallas calls: every intermediate in VMEM) on the
    TPU in bfloat16, at widths the lanes tile (multiples of 128), a
    sub-block that is whole vector registers (a multiple of 8 positions)
    and a chunk that tiles a block of :data:`_ROWS` positions in at most
    :data:`_GAM_ROWS` pieces; everything else is ``"xla"``, the same
    ``custom_vjp`` in ``jnp`` a head group at a time."""
    if (backend == "tpu" and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and K % 128 == 0 and V % 128 == 0 and sub % 8 == 0
            and _ROWS % chunk == 0 and _ROWS // chunk <= _GAM_ROWS):
        return "kernel"
    return "xla"


def select_delta_scan(backend: str, dtype, K: int, V: int, chunk: int,
                      N: int) -> str:
    """The path of a :func:`chunked_delta_rule` call's scan over its ``N``
    chunks (:func:`_carry_state`), from what the call shows and nothing
    else: ``"kernel"`` (the two Pallas calls, each head's state in VMEM
    across the chunks) on the TPU in bfloat16, at widths the lanes tile
    (multiples of 128), a chunk of whole bfloat16 tiles (a multiple of 16
    positions) and a chunk count that :data:`SCAN_CHUNKS` divides or that
    is smaller; everything else ``"xla"``, the ``lax.scan``."""
    if (backend == "tpu" and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and K % 128 == 0 and V % 128 == 0 and chunk % 16 == 0
            and (N % SCAN_CHUNKS == 0 or N < SCAN_CHUNKS)):
        return "kernel"
    return "xla"


def _path_counter():
    return obs.counter(
        "delta_rule_total",
        "chunked_delta_rule calls traced, by the per-chunk operands' "
        "resolved implementation", labels=("impl",))


def _by_impl(family) -> dict[str, int]:
    if family is obs.NULL:
        return {}
    return {s["labels"]["impl"]: s["value"] for s in family.sample()}


def delta_rule_paths_traced() -> dict[str, int]:
    """``{impl: chunked_delta_rule calls traced so far}`` in this process
    (the ``delta_rule_total`` counter; empty with ``DISTLEARN_OBS=0``)."""
    return _by_impl(_path_counter())


def _scan_counter():
    return obs.counter(
        "delta_rule_scan_total",
        "chunked_delta_rule calls traced, by the scan over the chunks' "
        "resolved implementation", labels=("impl",))


def delta_rule_scan_paths_traced() -> dict[str, int]:
    """``{impl: chunked_delta_rule calls traced so far}`` by the scan's path
    (the ``delta_rule_scan_total`` counter; empty with ``DISTLEARN_OBS=0``)."""
    return _by_impl(_scan_counter())


def _grouped(a, chunk):
    """[B, H, L, X] -> [H / hg, hg, B, N, C, X] (no copy at B = 1)."""
    B, H, L, X = a.shape
    hg = math.gcd(H, HEAD_GROUP)
    return a.reshape(B, H // hg, hg, L // chunk, chunk, X).transpose(
        1, 2, 0, 3, 4, 5)


def _ungrouped(a):
    """[H / hg, hg, B, N, ...] -> [B, H, N, ...]."""
    return jnp.swapaxes(a.reshape((-1,) + a.shape[2:]), 0, 1)


def _group_major(a):
    """[B, H, N, ...] -> [H / hg, hg, B, N, ...], float32."""
    hg = math.gcd(a.shape[1], HEAD_GROUP)
    a = jnp.swapaxes(a.astype(jnp.float32), 0, 1)
    return a.reshape((-1, hg) + a.shape[1:])


def _f32(args):
    return (a.astype(jnp.float32) for a in args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _chunk_operands(q, k, v, g, beta, chunk, sub, cd, impl):
    """The six operands of :func:`_carry_state` ([B, H, N, ...]; ``Wv`` and
    ``gam`` float32, the others in ``cd``) from head-major q, k, v, g
    [B, H, L, X] and beta [B, H, L], differentiated by hand: the backward
    pass keeps the inputs and the two [C, C] matrices ``X`` and ``Akk`` a
    chunk.  ``impl`` (:func:`select_delta_rule`): ``"kernel"`` one Pallas
    call each way (in interpret mode off the TPU); ``"xla"``
    :func:`_operands` and :func:`_operands_vjp`, :data:`HEAD_GROUP` heads
    at a time — their [L, K]-sized float32 intermediates and lane-padded
    [c, c] ones then exist for one group."""
    return _chunk_operands_fwd(q, k, v, g, beta, chunk, sub, cd, impl)[0]


def _chunk_operands_fwd(q, k, v, g, beta, chunk, sub, cd, impl):
    args = (q, k, v, g, beta)
    if impl == "kernel":
        ops, kept = _kernel_fwd(*args, chunk, sub, cd,
                                sequence._backend() != "tpu")
        return ops, args + kept
    ops, kept = lax.map(
        lambda a: _operands(*_f32(a), sub, cd),
        tuple(_grouped(a, chunk) for a in (q, k, v, g, beta[..., None])))
    Wv, *mid, gam = (_ungrouped(a) for a in ops)
    return (Wv, *(a.astype(cd) for a in mid), gam), args + kept


def _chunk_operands_bwd(chunk, sub, cd, impl, res, cot):
    args, kept = res[:5], res[5:]
    if impl == "kernel":
        return _kernel_bwd(*args, *kept, cot, chunk, sub, cd,
                           sequence._backend() != "tpu")
    q, k, v, g, beta = args
    grads = lax.map(
        lambda a: _operands_vjp(*_f32(a[0]), *a[1], a[2], sub, cd),
        (tuple(_grouped(a, chunk) for a in (q, k, v, g, beta[..., None])),
         kept, tuple(_group_major(a) for a in cot)))
    # [H / hg, hg, B, N, C, X] -> [B, H, L, X]
    return tuple(d.transpose(2, 0, 1, 3, 4, 5).reshape(a.shape)
                 .astype(a.dtype) for d, a in zip(grads, args))


_chunk_operands.defvjp(_chunk_operands_fwd, _chunk_operands_bwd)


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

    The per-chunk operands (:func:`_chunk_operands`) and the scan over the
    chunks (:func:`_carry_state`) each take the path that
    :func:`select_delta_rule` / :func:`select_delta_scan` give the call,
    counted under ``delta_rule_total`` / ``delta_rule_scan_total``."""
    B, H, L, K = q.shape
    V = v.shape[-1]
    chunk, sub = CHUNK, SUB
    if L % chunk or chunk % sub or (chunk // sub) & (chunk // sub - 1):
        raise ValueError(
            f"chunked_delta_rule needs L={L} a multiple of chunk={chunk} and "
            f"chunk a power-of-two multiple of sub={sub}")
    cd = compute_dtype or v.dtype
    impl = select_delta_rule(sequence._backend(), cd, K, V, chunk, sub)
    scan = select_delta_scan(sequence._backend(), cd, K, V, chunk, L // chunk)
    _path_counter().labels(impl=impl).inc()
    _scan_counter().labels(impl=scan).inc()
    ops = _chunk_operands(q, k, v, g, beta, chunk, sub, cd, impl)
    S0 = jnp.zeros((B, H, K, V), jnp.float32) if initial_state is None \
        else initial_state.astype(jnp.float32)
    return _carry_state(S0, *ops, cd, v.dtype, scan)
