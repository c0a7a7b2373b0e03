"""A manifold-constrained hyper-connection (``models/hybrid.py``'s
``hc_sublayer``) as two Pallas kernels, each with its pull-back as a Pallas
kernel: the READ-IN and the WRITE-BACK around one sublayer ``F``.

Per position, with the ``n`` streams ``X`` [n, C] (``v`` = vec X, ``N`` =
n C) and the gates ``phi`` [N, M] (``M`` = 2n + n^2), ``alpha`` [3],
``b_pre``, ``b_post`` [n], ``b_res`` [n, n]:

    r      = rsqrt(mean(v^2) + norm_eps);     z = (v phi) r           [M]
    h_pre  = sigmoid(alpha_0 z_pre + b_pre);  h_post = 2 sigmoid(alpha_1
             z_post + b_post);  H_res = Sinkhorn(exp(alpha_2 z_res + b_res))
    x      = sum_i h_pre_i X_i                              (the read-in)
    X'_i   = sum_j H_res_ij X_j + h_post_i y,  y = sum of F(x)'s terms
                                                            (the write-back)

The read-in (:func:`read_in`) reads a tile of :data:`HC_ROWS` positions of
the streams [T, N] ONCE: the RMS statistic, the product with ``phi``, the
gates, ``exp`` and every Sinkhorn round happen in VMEM, and it writes ``x``
and the maps, ``[T, 128]`` float32 a tile (``h_pre``, ``h_post``, ``H_res``
in its first ``M`` columns).  Sinkhorn runs with the positions on the lanes:
each of the ``n^2`` entries is a ``[1, T]`` row, so that a row or column sum
is ``n - 1`` adds of whole vectors.  The write-back (:func:`write_back`)
reads the streams and each of ``y``'s terms once and writes ``X'``.

Pull-backs.  The write-back's reads ``dX'``, the streams and the terms once
and writes ``dy`` and the maps' cotangents (``dH_res_ij = sum_c dX'_i X_j``,
``dh_post_i = sum_c dX'_i y``).  The mix ``sum_i H_res_ij dX'_i`` — the
write-back's share of ``dX`` — is left to the read-in's pull-back: the
write-back hands ``dX'`` on unmixed as the cotangent of the streams it was
given, which are the read-in's third output (the read-in's input itself, no
copy).  So ``dX`` is written once, by the read-in's pull-back, which reads
``dx``, ``dX'`` and the streams once, recomputes the maps and Sinkhorn's
rounds (keeping each round's input in a VMEM scratch [iters, n^2, T] and
walking them in reverse), and sums ``dphi`` (float32, resident in VMEM over
the whole grid), ``dalpha`` and the ``db_*`` (a row a position, summed
after the call).  The pair is one unit: a write-back takes only a link that
:func:`read_in` made.

Precision.  ``v phi`` is taken at float32 precision in ONE pass of the
matrix unit: the streams are bfloat16 (exact in the matrix unit), and
``phi`` in float32 is the sum of three bfloat16 parts, stacked side by side
— what XLA's ``Precision.HIGHEST`` makes of the product in six passes.  The
pull-back's ``du phi^T`` splits ``du`` the same way and stacks the six
cross products that ``HIGHEST`` keeps along the contraction; ``v^T du``
stacks the three parts of ``du``.  Every other operation is float32; ``x``,
``X'`` and ``dX`` are written in the streams' dtype.

Two paths (:func:`select_hc`, from what the call shows): these kernels on
the TPU for bfloat16 streams whose width the lanes tile; elsewhere the
``jnp`` form in ``models/hybrid.py`` (``hc_maps``, ``sinkhorn``).  Off the
TPU the kernels run in Pallas interpret mode (the tests)."""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from distlearn_tpu.parallel import sequence

#: positions a grid step (one tile of the streams, [HC_ROWS, n C]) and the
#: columns a step of the loops over a tile's columns; one value each, no
#: argument.  Swept on the v5e at [1, 8192, 4, 4096] bf16, a sublayer's four
#: calls (ms): 128 / 512 4.14, 256 / 512 4.18, 128 / 1024 4.05, 128 / 256
#: 4.57, 256 / 1024 4.14 (PERF.md section 6)
HC_ROWS = 128
HC_LANES = 1024
#: the width of the maps a position: h_pre, h_post, H_res in the first
#: 2n + n^2 columns; in the read-in's product, the statistic r in the last
_MAPS = 128

_F32, _BF16 = jnp.float32, jnp.bfloat16
_NN = (((1,), (0,)), ((), ()))                      # a b


def _stride(n: int) -> int:
    """Rows a part of the stacked ``phi`` takes: 2n + n^2, to whole f32
    sublane tiles."""
    return -(-(2 * n + n * n) // 8) * 8


def select_hc(backend: str, dtype, n: int, C: int) -> str:
    """The path of a hyper-connection, from what the call shows and nothing
    else: ``"kernel"`` (the Pallas calls of this module) on the TPU for
    bfloat16 streams of a width the lanes tile (C a multiple of 128) and few
    enough streams that the three parts of ``phi`` fit one 128-row block
    (n <= 5); everything else ``"xla"``, the ``jnp`` form of
    ``models/hybrid.py``."""
    if (backend == "tpu" and jnp.dtype(dtype) == jnp.dtype(_BF16)
            and C % 128 == 0 and 3 * _stride(n) < _MAPS):
        return "kernel"
    return "xla"


# ------------------------------------------------------------ in VMEM --

def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _dot(a, b, dims, interpret):
    """A product of bfloat16 operands, float32 out.  In interpret mode (off
    the TPU) in float32, which holds each product of two bfloat16 values
    exactly: the CPU has no bfloat16 x bfloat16 = float32 product."""
    if interpret:
        a, b = a.astype(_F32), b.astype(_F32)
        return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST)
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _ds(start, size):
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(start, size), size)


def _rows_to_block(rows: dict, shape):
    """``{row index: [1, T]}`` -> a [R, T] block, zeros elsewhere."""
    at = _iota(shape, 0)
    out = jnp.zeros(shape, _F32)
    for k, row in rows.items():
        out = jnp.where(at == k, row, out)
    return out


def _cols_to_block(cols: dict, shape):
    """``{column index: [T, 1]}`` -> a [T, L] block, zeros elsewhere."""
    at = _iota(shape, 1)
    out = jnp.zeros(shape, _F32)
    for k, col in cols.items():
        out = jnp.where(at == k, col, out)
    return out


def _fold(a):
    """[T, W] -> [T, 128]: the lane blocks summed (a lane sum's first
    part, whole vectors)."""
    W = a.shape[1]
    if W <= 128:
        return a
    return sum(a[:, q * 128:(q + 1) * 128] for q in range(W // 128))


class _Plan(NamedTuple):
    """A call's static facts: ``n`` streams of ``C``, ``T`` positions a grid
    step, ``W`` columns a loop step, Pallas interpret mode or not.  Hashable:
    the calls are jitted on it, so that a step that wraps ten sublayers
    traces and lowers each kernel once."""
    n: int
    C: int
    T: int
    W: int
    interpret: bool

    @property
    def N(self):
        return self.n * self.C

    @property
    def M(self):
        return 2 * self.n + self.n * self.n

    @property
    def S(self):
        return _stride(self.n)

    @property
    def steps(self):
        return self.C // self.W

    def cols(self, i, k):
        """Stream ``i``'s columns of loop step ``k``."""
        return _ds(i * self.C + k * self.W, self.W)


def _plan(n: int, C: int, R: int) -> _Plan:
    return _Plan(n, C, HC_ROWS if R >= HC_ROWS else R,
                 math.gcd(C, HC_LANES), sequence._backend() != "tpu")


def _z_sweep(g, x_ref, phiz_ref, dx_ref=None, acc_ref=None):
    """One read of a tile of the streams: ``(zc [T, 128], ss [T, 1])``, the
    three parts of ``v phi`` side by side and the sum of squares; with
    ``dx_ref``, also ``dh_pre_i``'s lane partial sums ``dx X_i`` into
    ``acc_ref[i]``."""
    T = x_ref.shape[0]

    def step(k, carry):
        zc, ss = carry
        dx = None if dx_ref is None else dx_ref[:, _ds(k * g.W, g.W)] \
            .astype(_F32)
        for i in range(g.n):
            v = x_ref[:, g.cols(i, k)]
            vf = v.astype(_F32)
            ss = ss + _fold(vf * vf)
            zc = zc + _dot(v.astype(_BF16), phiz_ref[g.cols(i, k), :], _NN,
                           g.interpret)
            if dx is not None:
                acc_ref[i] += _fold(dx * vf)
        return zc, ss

    zc, ss = lax.fori_loop(0, g.steps, step, (
        jnp.zeros((T, _MAPS), _F32), jnp.zeros((T, min(g.W, 128)), _F32)))
    return zc, jnp.sum(ss, axis=1, keepdims=True)


def _maps_rows(g, zc, ss, norm_eps):
    """The gates' pre-activations a row each: ``(u [S, T] (v phi), z [S, T],
    r [1, T])`` — the product's three parts summed, the statistic moved from
    a column to a row with them (one transpose)."""
    r = lax.rsqrt(ss / g.N + norm_eps)
    rows = jnp.where(_iota(zc.shape, 1) == _MAPS - 1, r, zc).T    # [128, T]
    S = g.S
    u = rows[:S] + rows[S:2 * S] + rows[2 * S:3 * S]
    r_row = rows[_MAPS - 1:_MAPS]
    return u, u * r_row, r_row


def _gates(g, s_ref, z):
    """``(h_pre, h_post, exp(...))`` as lists of [1, T] rows (``s_ref``: the
    scalars alpha, b_pre, b_post, b_res)."""
    n = g.n
    a0, a1, a2 = s_ref[0], s_ref[1], s_ref[2]
    row = lambda k: z[k:k + 1]                                # noqa: E731
    sig = lambda w: 1.0 / (1.0 + jnp.exp(-w))                 # noqa: E731
    pre = [sig(a0 * row(i) + s_ref[3 + i]) for i in range(n)]
    post = [2.0 * sig(a1 * row(n + i) + s_ref[3 + n + i]) for i in range(n)]
    m0 = [jnp.exp(a2 * row(2 * n + k) + s_ref[3 + 2 * n + k])
          for k in range(n * n)]
    return pre, post, m0


def _normalise(m, n, eps):
    """One Sinkhorn round on the rows ``m[i n + j]``: ``(R, M', s, c)``,
    rows divided by their sums + eps, then columns."""
    s = [sum(m[i * n + j] for j in range(n)) + eps for i in range(n)]
    R = [m[i * n + j] * (1.0 / s[i]) for i in range(n) for j in range(n)]
    c = [sum(R[i * n + j] for i in range(n)) + eps for j in range(n)]
    Mp = [R[i * n + j] * (1.0 / c[j]) for i in range(n) for j in range(n)]
    return R, Mp, s, c


def _sinkhorn(m0, n, iters, eps, keep_ref=None):
    """``iters`` rounds on the rows; with ``keep_ref`` [iters, n^2, T] each
    round's input kept."""
    def round_(k, m):
        if keep_ref is not None:
            for idx, row in enumerate(m):
                keep_ref[k, idx:idx + 1, :] = row
        return tuple(_normalise(m, n, eps)[1])
    return list(lax.fori_loop(0, iters, round_, tuple(m0)))


def _sinkhorn_vjp(dres, n, iters, eps, keep_ref):
    """The rounds walked back from the kept inputs: ``dres`` -> the
    cotangent of Sinkhorn's input (lists of [1, T] rows)."""
    def round_(j, dM):
        k = iters - 1 - j
        m = [keep_ref[k, idx:idx + 1, :] for idx in range(n * n)]
        R, Mp, s, c = _normalise(m, n, eps)
        t = [sum(dM[i * n + jj] * Mp[i * n + jj] for i in range(n))
             for jj in range(n)]
        dR = [(dM[i * n + jj] - t[jj]) * (1.0 / c[jj])
              for i in range(n) for jj in range(n)]
        q = [sum(dR[i * n + jj] * R[i * n + jj] for jj in range(n))
             for i in range(n)]
        return tuple((dR[i * n + jj] - q[i]) * (1.0 / s[i])
                     for i in range(n) for jj in range(n))
    return list(lax.fori_loop(0, iters, round_, tuple(dres)))


def _parts(a):
    """float32 ``a`` as three bfloat16 parts (float32 values), their sum
    ``a`` to float32 precision."""
    p1 = a.astype(_BF16).astype(_F32)
    p2 = (a - p1).astype(_BF16).astype(_F32)
    return p1, p2, (a - p1 - p2).astype(_BF16).astype(_F32)


# ----------------------------------------------------------- the calls --

def _vmem_limit(blocks):
    """Double-buffered blocks ``(shape, dtype)`` plus room for the kernel's
    own values."""
    total = sum(math.prod(s) * jnp.dtype(d).itemsize for s, d in blocks)
    return int(min(2 * total + (24 << 20), 120 << 20))


def _call(kernel, g, rows, in_specs, out_specs, out_shape, name, *,
          scratch=(), arbitrary=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    blocks = [(s.block_shape, a) for s, a in in_specs if s.block_shape] + \
        [(s.block_shape, o.dtype) for s, o in zip(out_specs, out_shape)]
    return pl.pallas_call(
        kernel, grid=(rows // g.T,), in_specs=[s for s, _ in in_specs],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if arbitrary else "parallel",),
            vmem_limit_bytes=_vmem_limit(blocks)),
        interpret=g.interpret, name=name)


def _specs(T):
    """BlockSpecs over a grid of row tiles: ``tile(cols)`` a tile of an
    [R, cols] array, ``whole(shape)`` the same block at every step,
    ``smem`` the scalars."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tile = lambda c: pl.BlockSpec((T, c), lambda i: (i, 0))   # noqa: E731
    whole = lambda s: pl.BlockSpec(s, lambda i: (0,) * len(s))  # noqa: E731
    return tile, whole, pl.BlockSpec(memory_space=pltpu.SMEM)


def _specs_cols(rows, T):
    """A [rows, T] block of a [rows, R] array: a tile's positions on the
    lanes."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((rows, T), lambda i: (0, i))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _read_in_call(X2, phiz, scal, g, iters, eps, norm_eps):
    """The read-in: ``(x [R, C] in X2's dtype, maps [R, 128] float32)``."""
    R, N = X2.shape
    n, T = g.n, g.T
    tile, whole, smem = _specs(T)

    def kernel(s_ref, x_ref, phiz_ref, xo_ref, maps_ref):
        zc, ss = _z_sweep(g, x_ref, phiz_ref)
        _, z, _ = _maps_rows(g, zc, ss, norm_eps)
        pre, post, m0 = _gates(g, s_ref, z)
        res = _sinkhorn(m0, n, iters, eps)
        maps = _rows_to_block(dict(enumerate(pre + post + res)),
                              (_MAPS, T)).T
        maps_ref[...] = maps

        def mix(k, carry):
            acc = 0.0
            for i in range(n):
                acc = acc + maps[:, i:i + 1] * x_ref[:, g.cols(i, k)] \
                    .astype(_F32)
            xo_ref[:, _ds(k * g.W, g.W)] = acc.astype(xo_ref.dtype)
            return carry
        lax.fori_loop(0, g.steps, mix, 0)

    return _call(
        kernel, g, R,
        [(smem, scal.dtype), (tile(N), X2.dtype),
         (whole(phiz.shape), phiz.dtype)],
        [tile(g.C), tile(_MAPS)],
        [jax.ShapeDtypeStruct((R, g.C), X2.dtype),
         jax.ShapeDtypeStruct((R, _MAPS), _F32)], "hc_read_in")(
             scal, X2, phiz)


@functools.partial(jax.jit, static_argnums=(3,))
def _write_back_call(X2, maps, terms, g):
    """The write-back: X' [R, N] in X2's dtype."""
    R, N = X2.shape
    n = g.n
    tile, _, _ = _specs(g.T)

    def kernel(maps_ref, x_ref, *refs):
        *t_refs, o_ref = refs
        maps = maps_ref[...]

        def step(k, carry):
            y = sum(t[:, _ds(k * g.W, g.W)].astype(_F32) for t in t_refs)
            X = [x_ref[:, g.cols(j, k)].astype(_F32) for j in range(n)]
            for i in range(n):
                out = sum(maps[:, 2 * n + i * n + j:2 * n + i * n + j + 1]
                          * X[j] for j in range(n))
                out = out + maps[:, n + i:n + i + 1] * y
                o_ref[:, g.cols(i, k)] = out.astype(o_ref.dtype)
            return carry
        lax.fori_loop(0, g.steps, step, 0)

    return _call(
        kernel, g, R,
        [(tile(_MAPS), _F32), (tile(N), X2.dtype)]
        + [(tile(g.C), t.dtype) for t in terms],
        [tile(N)], [jax.ShapeDtypeStruct((R, N), X2.dtype)],
        "hc_write_back")(maps, X2, *terms)[0]


@functools.partial(jax.jit, static_argnums=(4, 5))
def _write_back_vjp_call(X2, maps, terms, dXo, g, dy_dtype):
    """The write-back's pull-back, less the mix of ``dX'``: ``(dy [R, C] in
    ``dy_dtype``, dmaps [R, 128] float32)``."""
    from jax.experimental.pallas import tpu as pltpu
    R, N = X2.shape
    n, T = g.n, g.T
    tile, _, _ = _specs(T)
    n_acc = n * n + n

    def kernel(maps_ref, x_ref, dxo_ref, *refs):
        *t_refs, dy_ref, dmaps_ref, acc_ref = refs
        maps = maps_ref[...]
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

        def step(k, carry):
            y = sum(t[:, _ds(k * g.W, g.W)].astype(_F32) for t in t_refs)
            X = [x_ref[:, g.cols(j, k)].astype(_F32) for j in range(n)]
            dy = 0.0
            for i in range(n):
                d = dxo_ref[:, g.cols(i, k)].astype(_F32)
                dy = dy + maps[:, n + i:n + i + 1] * d
                for j in range(n):
                    acc_ref[i * n + j] += _fold(d * X[j])
                acc_ref[n * n + i] += _fold(d * y)
            dy_ref[:, _ds(k * g.W, g.W)] = dy.astype(dy_ref.dtype)
            return carry
        lax.fori_loop(0, g.steps, step, 0)
        sums = [jnp.sum(acc_ref[a], axis=1, keepdims=True)
                for a in range(n_acc)]
        cols = {2 * n + k: sums[k] for k in range(n * n)}
        cols.update({n + i: sums[n * n + i] for i in range(n)})
        dmaps_ref[...] = _cols_to_block(cols, maps.shape)

    lanes = min(g.W, 128)
    return _call(
        kernel, g, R,
        [(tile(_MAPS), _F32), (tile(N), X2.dtype), (tile(N), dXo.dtype)]
        + [(tile(g.C), t.dtype) for t in terms],
        [tile(g.C), tile(_MAPS)],
        [jax.ShapeDtypeStruct((R, g.C), dy_dtype),
         jax.ShapeDtypeStruct((R, _MAPS), _F32)], "hc_write_back_vjp",
        scratch=[pltpu.VMEM((n_acc, T, lanes), _F32)])(
            maps, X2, dXo, *terms)


def _small_rows(n):
    """Rows of the pull-back's per-position gradients of the scalars:
    alpha (3), b_pre, b_post (n each), b_res (n^2), to whole tiles."""
    return -(-(3 + 2 * n + n * n) // 8) * 8


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _read_in_vjp_call(X2, phiz, phig, scal, dx, dXo, dmaps, g, iters, eps,
                      norm_eps):
    """The read-in's pull-back, with the write-back's mix of ``dX'``:
    ``(dX [R, N] in X2's dtype, dphi's parts [P, N] float32 (rows 0, S, 2S
    hold the three parts of dphi^T), the scalars' gradients a position
    [_small_rows(n), R])``."""
    from jax.experimental.pallas import tpu as pltpu
    R, N = X2.shape
    n, T = g.n, g.T
    tile, whole, smem = _specs(T)
    S, P = g.S, -(-3 * g.S // 16) * 16
    SR = _small_rows(n)
    coef_col = _MAPS - 1

    def kernel(s_ref, x_ref, phiz_ref, phig_ref, dx_ref, dxo_ref, dmaps_ref,
               dX_ref, dphi_ref, small_ref, keep_ref, acc_ref):
        from jax.experimental import pallas as pl

        @pl.when(pl.program_id(0) == 0)
        def _():
            dphi_ref[...] = jnp.zeros(dphi_ref.shape, _F32)

        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)
        zc, ss = _z_sweep(g, x_ref, phiz_ref, dx_ref, acc_ref)
        u, z, r = _maps_rows(g, zc, ss, norm_eps)
        pre, post, m0 = _gates(g, s_ref, z)
        res = _sinkhorn(m0, n, iters, eps, keep_ref)
        # cotangents of the maps, a row each
        dm = dmaps_ref[...].T
        dpost = [dm[n + i:n + i + 1] for i in range(n)]
        dres = [dm[2 * n + k:2 * n + k + 1] for k in range(n * n)]
        dpre_cols = {i: jnp.sum(acc_ref[i], axis=1, keepdims=True)
                     for i in range(n)}
        dpre_rows = _cols_to_block(dpre_cols, (T, _MAPS)).T
        dm0 = _sinkhorn_vjp(dres, n, iters, eps, keep_ref)
        a0, a1, a2 = s_ref[0], s_ref[1], s_ref[2]
        dw_pre = [dpre_rows[i:i + 1] * pre[i] * (1.0 - pre[i])
                  for i in range(n)]
        dw_post = [dpost[i] * post[i] * (1.0 - 0.5 * post[i])
                   for i in range(n)]
        dw_res = [dm0[k] * m0[k] for k in range(n * n)]
        zrow = lambda k: z[k:k + 1]                           # noqa: E731
        small = [sum(dw_pre[i] * zrow(i) for i in range(n)),
                 sum(dw_post[i] * zrow(n + i) for i in range(n)),
                 sum(dw_res[k] * zrow(2 * n + k) for k in range(n * n))]
        small_ref[...] = _rows_to_block(
            dict(enumerate(small + dw_pre + dw_post + dw_res)), (SR, T))
        dz = _rows_to_block(dict(enumerate(
            [a0 * w for w in dw_pre] + [a1 * w for w in dw_post]
            + [a2 * w for w in dw_res])), (S, T))
        du = dz * r
        dr = jnp.sum(dz * u, axis=0, keepdims=True)
        coef = dr * (-r * r * r / g.N)
        d1, d2, d3 = _parts(du)
        gap = jnp.zeros((_MAPS - 3 * S, T), _F32)
        # the six cross products of du phi^T along the contraction, and
        # du's three parts for v^T du
        lhs = jnp.concatenate([d1, d1, d1, gap, d2, d2, d3, gap]).T \
            .astype(_BF16)                                   # [T, 256]
        du3 = jnp.concatenate(
            [d1, d2, d3, jnp.zeros((P - 3 * S, T), _F32)]).astype(_BF16)
        cols = dict(enumerate(pre + res))
        cols[coef_col] = coef
        cols = _rows_to_block(cols, (_MAPS, T)).T            # [T, 128]

        def step(k, carry):
            dx = dx_ref[:, _ds(k * g.W, g.W)].astype(_F32)
            dxo = [dxo_ref[:, g.cols(i, k)].astype(_F32) for i in range(n)]
            for j in range(n):
                v = x_ref[:, g.cols(j, k)]
                vf = v.astype(_F32)
                d = _dot(lhs, phig_ref[:, g.cols(j, k)], _NN, g.interpret) \
                    + cols[:, coef_col:coef_col + 1] * vf \
                    + cols[:, j:j + 1] * dx
                for i in range(n):
                    c = n + i * n + j
                    d = d + cols[:, c:c + 1] * dxo[i]
                dX_ref[:, g.cols(j, k)] = d.astype(dX_ref.dtype)
                dphi_ref[:, g.cols(j, k)] += _dot(du3, v.astype(_BF16), _NN,
                                                  g.interpret)
            return carry
        lax.fori_loop(0, g.steps, step, 0)

    lanes = min(g.W, 128)
    return _call(
        kernel, g, R,
        [(smem, scal.dtype), (tile(N), X2.dtype),
         (whole(phiz.shape), phiz.dtype), (whole(phig.shape), phig.dtype),
         (tile(g.C), dx.dtype), (tile(N), dXo.dtype), (tile(_MAPS), _F32)],
        [tile(N), whole((P, N)), _specs_cols(SR, T)],
        [jax.ShapeDtypeStruct((R, N), X2.dtype),
         jax.ShapeDtypeStruct((P, N), _F32),
         jax.ShapeDtypeStruct((SR, R), _F32)], "hc_read_in_vjp",
        scratch=[pltpu.VMEM((max(iters, 1), n * n, T), _F32),
                 pltpu.VMEM((n, T, lanes), _F32)], arbitrary=True)(
            scal, X2, phiz, phig, dx, dXo, dmaps)


# ---------------------------------------------- the two differentiable --

def _stacked(phi, n):
    """``phi`` [N, M] as bfloat16 parts, ``(phiz [N, 128], phig [256, N])``:
    ``phiz``'s columns 0 / S / 2S the three parts (the read-in's product);
    ``phig`` transposed, rows 0 / S / 2S the three parts and rows 128 + (0 /
    S / 2S) the parts that ``du``'s second and third parts meet (p1, p2,
    p1): with ``du``'s parts stacked as (d1, d1, d1 | d2, d2, d3) one
    product sums the six cross terms of ``HIGHEST``."""
    S = _stride(n)
    p1, p2, p3 = (jnp.pad(p.T, ((0, S - p.shape[1]), (0, 0)))
                  for p in _parts(phi.astype(_F32)))
    gap = jnp.zeros((_MAPS - 3 * S, phi.shape[0]), _F32)
    phig = jnp.concatenate([p1, p2, p3, gap, p1, p2, p1, gap]).astype(_BF16)
    return phig[:_MAPS].T, phig


def _scalars(alpha, b_pre, b_post, b_res):
    return jnp.concatenate([a.astype(_F32).reshape(-1)
                            for a in (alpha, b_pre, b_post, b_res)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _read_in(X2, phi, alpha, b_pre, b_post, b_res, g, iters, eps, norm_eps):
    return _read_in_fwd(X2, phi, alpha, b_pre, b_post, b_res, g, iters, eps,
                        norm_eps)[0]


def _read_in_fwd(X2, phi, alpha, b_pre, b_post, b_res, g, iters, eps,
                 norm_eps):
    phiz, phig = _stacked(phi, g.n)
    scal = _scalars(alpha, b_pre, b_post, b_res)
    x, maps = _read_in_call(X2, phiz, scal, g, iters, eps, norm_eps)
    params = (phi, alpha, b_pre, b_post, b_res)
    return (x, maps, X2), (X2, phiz, phig, scal, params)


def _read_in_bwd(g, iters, eps, norm_eps, res, cot):
    X2, phiz, phig, scal, params = res
    dx, dmaps, dXo = cot
    dX, dphi, small = _read_in_vjp_call(X2, phiz, phig, scal, dx, dXo, dmaps,
                                        g, iters, eps, norm_eps)
    n, S, M = g.n, g.S, g.M
    dphi = (dphi[:M] + dphi[S:S + M] + dphi[2 * S:2 * S + M]).T
    small = jnp.sum(small, axis=1)
    grads = (dphi, small[:3], small[3:3 + n], small[3 + n:3 + 2 * n],
             small[3 + 2 * n:3 + M].reshape(n, n))
    return (dX,) + tuple(d.astype(p.dtype).reshape(p.shape)
                         for d, p in zip(grads, params))


_read_in.defvjp(_read_in_fwd, _read_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _write_back(X2, maps, terms, g):
    return _write_back_call(X2, maps, terms, g)


def _write_back_fwd(X2, maps, terms, g):
    return _write_back_call(X2, maps, terms, g), (X2, maps, terms)


def _write_back_bwd(g, res, dXo):
    X2, maps, terms = res
    dtypes = {jnp.dtype(t.dtype) for t in terms}
    dy_dtype = dtypes.pop() if len(dtypes) == 1 else jnp.dtype(_F32)
    dy, dmaps = _write_back_vjp_call(X2, maps, terms, dXo, g, dy_dtype)
    # dX' goes on UNMIXED: the read-in's pull-back mixes it into dX
    return dXo, dmaps, tuple(dy.astype(t.dtype) for t in terms)


_write_back.defvjp(_write_back_fwd, _write_back_bwd)


def read_in(hc, X, n: int, iters: int, eps: float, norm_eps: float):
    """The read-in of the ``n`` streams side by side, ``X`` [B, L, n C]
    (the hyper-connection's gates ``hc``): ``(x [B, L, C] in X's dtype,
    link)``, the link what :func:`write_back` needs."""
    B, L, N = X.shape
    R = B * L
    g = _plan(n, N // n, R)
    X2 = jnp.pad(X.reshape(R, N), ((0, -R % g.T), (0, 0)))
    x, maps, X2 = _read_in(X2, hc["phi"], hc["alpha"], hc["b_pre"],
                           hc["b_post"], hc["b_res"], g, iters, eps,
                           norm_eps)
    return x[:R].reshape(B, L, g.C), (X2, maps, g, X.shape)


def write_back(link, terms):
    """``X'`` [B, L, n C] from :func:`read_in`'s link and the sublayer's
    terms (each [B, L, C] or [B L, C]), summed in float32."""
    X2, maps, g, shape = link
    R = shape[0] * shape[1]
    terms = tuple(jnp.pad(t.reshape(R, g.C), ((0, X2.shape[0] - R), (0, 0)))
                  for t in terms)
    return _write_back(X2, maps, terms, g)[:R].reshape(shape)
