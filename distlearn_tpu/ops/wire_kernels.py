"""Fused wire-codec kernels — the device↔wire hot path (ROADMAP item 5).

The packed wire (comm/wire.py) quantizes on the host with numpy: int8
encode walks the delta ~6 times (abs, max, div, rint, clip, astype) and
the client's error-feedback residual then *decodes the frame it just
encoded* (another alloc + 2 walks), so a sync's codec cost is ~13
full-buffer memory passes.  On emulated 25 MB/s links the link hides
that; on real DCN the pack/unpack becomes the bound — the QSGD/1-bit-SGD
lesson that quantizer *cost*, not just quantizer ratio, decides
end-to-end throughput (Alistarh et al. 2017; Seide et al. 2014).

Two fused codec ops, each in two backend flavors behind one dispatch
(mirroring ops/fused_update.py):

* ``quantize_ef_into`` — int8 quantize + error-feedback residual in ONE
  pass: ``q = clip(rint(d/scale)); r = d - q*scale`` with ``scale =
  max|d|/127``.  d is read twice (amax + codec), q and r written once —
  the minimum traffic for the round's codec math.
* ``dequant_add`` — dequantize + elastic apply fused: ``c' = c + q*scale``
  without ever materializing the decoded f32 copy the receive path used
  to allocate per sync.

Backends:

* **TPU** — Pallas kernels (:func:`quantize_ef_jax`,
  :func:`dequant_add_jax`), so a device-resident delta quantizes on the
  VPU and only int8 crosses D2H (4x fewer staging bytes).  On non-TPU
  backends the same kernels run in Pallas interpret mode — that is how
  the CPU test mesh proves them against the numpy reference.  Compiled
  by Mosaic on a v5e (chip_smoke.py): ``scale`` and the apply are
  bitwise the reference's, but the chip's f32 divide is not correctly
  rounded, so ``q`` differs by one step where ``d/scale`` lands within
  rounding of a .5 tie (measured: 1 element in 2**20).  Error feedback
  absorbs it — ``q*scale + r == d`` holds to one ulp either way — so
  the device route is self-consistent but NOT bitwise-interchangeable
  with the host routes.
* **host native (CPU)** — a tiny single-pass SIMD C kernel
  (:mod:`wire_native`), compiled by the system compiler at first use and
  silently absent when there is no compiler.  This is the CPU production
  route: one pass over a leaf where numpy makes five.
* **host blocked (CPU fallback)** — a cache-blocked numpy implementation
  working in L2-resident chunks through one reusable thread-local
  scratch buffer (numpy cannot fuse the 5 ufunc passes any further).
  XLA-CPU is the wrong tool for this op: every ``jit`` call pays a
  device_put input copy (~2 passes) before its first reduction, which is
  why the host routes stay in numpy / C and not in the jitted kernels.

Bitwise parity with comm/wire.py's reference codec is load-bearing (the
tier-1 EASGD trajectory tests assert it at 50 rounds, S=1 and S=4):

* the chunked amax uses ``max(max(c), -min(c))`` per chunk — max is an
  exact, order-insensitive reduction, so the result equals the
  reference's ``np.max(np.abs(d))`` bit for bit;
* ``scale`` uses the reference's own formula (python-float ``amax/127.0``
  then a cast to the leaf dtype) — double rounding and all;
* the blocked path skips the reference's ``np.clip``: after the
  non-finite amax check every ``|d| <= amax``, so ``|d/scale| <=
  amax/scale <= 127/(1 - 2**-24) < 127.5`` and ``rint`` lands in
  [-127, 127] already — dropping the clip cannot change a single output
  (np.clip is the single most expensive op in the reference walk);
* ``r = d - q*scale`` is evaluated as separate mul + sub (no FMA
  contraction in numpy), matching ``decoded()`` + ``np.subtract``.
"""

from __future__ import annotations

import functools
import math
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distlearn_tpu.ops import wire_native
from distlearn_tpu.ops.flatten import LANE
from distlearn_tpu.ops.fused_update import _grid_for, _interpret
from distlearn_tpu.utils import flags

__all__ = [
    "wirek_enabled", "quantize_ef_into", "fp16_ef_into", "dequant_add",
    "fp16_add", "quantize_ef_jax", "dequant_add_jax", "encode_ef_into",
]


def wirek_enabled(override: bool | None = None) -> bool:
    """Resolve whether the wire path takes the fused codec kernels.

    Priority: explicit ``override`` > ``DISTLEARN_TPU_WIREK`` env (0/1) >
    on by default (the host-blocked path wins on every host measured;
    the env switch exists so the parity tests — and a paranoid operator —
    can pin the original numpy reference path)."""
    if override is not None:
        return bool(override)
    env = flags.env_truthy("DISTLEARN_TPU_WIREK")
    if env is not None:
        return env
    return True


# ---------------------------------------------------------------------------
# Host path: cache-blocked numpy (the CPU production route)
# ---------------------------------------------------------------------------

#: Elements per chunk — 128k f32 = 512 KB keeps chunk + scratch L2-resident
#: (below 32k the per-call numpy overhead dominates).
_CHUNK = 1 << 17

_scratch = threading.local()


def _chunk_scratch(dtype: np.dtype) -> np.ndarray:
    """One reusable per-thread chunk buffer per dtype — stripe appliers on
    different server threads must not share it."""
    bufs = getattr(_scratch, "bufs", None)
    if bufs is None:
        bufs = _scratch.bufs = {}
    buf = bufs.get(dtype)       # dtype-keyed: no per-call .name string
    if buf is None:
        buf = bufs[dtype] = np.empty(_CHUNK, dtype)
    return buf


def _amax_blocked(flat: np.ndarray) -> float:
    """``float(np.max(np.abs(flat)))`` without the |x| temporary: chunked
    ``max(max, -min)`` — exact for every float ordering, NaN-propagating
    (a NaN chunk max poisons the python-level max comparisons into
    keeping NaN via the ``!=`` trick below)."""
    amax = -math.inf
    nan = False
    for lo in range(0, flat.size, _CHUNK):
        c = flat[lo:lo + _CHUNK]
        hi = float(c.max())
        neg = -float(c.min())
        if hi != hi or neg != neg:
            nan = True
            break
        if hi > amax:
            amax = hi
        if neg > amax:
            amax = neg
    return math.nan if nan else amax


def quantize_ef_into(d: np.ndarray, q: np.ndarray, r: np.ndarray) -> float:
    """Fused int8 quantize + error-feedback residual, blocked.

    Writes ``q`` (int8, same shape) and ``r = d - dequant(q)`` (same
    dtype/shape — the caller's residual carry), returns the python-float
    ``scale`` for the manifest.  Bitwise-identical to
    ``wire._encode_leaf(d, "int8")`` + ``decoded()`` + ``np.subtract``.
    Raises ``ValueError`` on non-finite input, exactly like the
    reference (the center must never take a poisoned delta)."""
    flat = d.reshape(-1)
    qf = q.reshape(-1)
    rf = r.reshape(-1)
    native = wire_native.usable_quant(d, q, r) and flat.size
    if native:
        amax = wire_native.amax_checked(flat)
    else:
        amax = _amax_blocked(flat) if flat.size else 0.0
    if not math.isfinite(amax):
        raise ValueError(
            "int8 wire codec cannot encode non-finite values (inf/nan leaf)")
    scale = amax / 127.0
    if scale == 0.0:
        qf[...] = 0
        rf[...] = flat          # q decodes to 0 => the whole delta carries
        return scale
    st = d.dtype.type(scale)
    if native:
        wire_native.quant_ef_f32(flat, st, qf, rf)
        return scale
    for lo in range(0, flat.size, _CHUNK):
        c = flat[lo:lo + _CHUNK]
        s = _chunk_scratch(d.dtype)[:c.size]
        np.divide(c, st, out=s)
        np.rint(s, out=s)       # |c/st| <= 127.0000076 -> clip-free (doc top)
        qc = qf[lo:lo + _CHUNK]
        np.copyto(qc, s, casting="unsafe")    # integral values: exact
        # dequant from s, not qc: s holds the same integral values the
        # int8 cast preserved, so s*st == f32(qc)*st bitwise — and reads
        # the hot f32 scratch instead of re-widening int8 (~2.5x faster)
        np.multiply(s, st, out=s)
        np.subtract(c, s, out=rf[lo:lo + _CHUNK])
    return scale


def fp16_ef_into(d: np.ndarray, h: np.ndarray, r: np.ndarray) -> None:
    """Fused fp16 downcast + residual: ``h = f16(d); r = d - widen(h)``,
    blocked through the chunk scratch (the reference decodes the f16
    frame into a fresh full-size f32 array first)."""
    flat = d.reshape(-1)
    hf = h.reshape(-1)
    rf = r.reshape(-1)
    for lo in range(0, flat.size, _CHUNK):
        c = flat[lo:lo + _CHUNK]
        hc = hf[lo:lo + _CHUNK]
        np.copyto(hc, c, casting="unsafe")    # round-to-nearest-even cast
        s = _chunk_scratch(d.dtype)[:c.size]
        np.copyto(s, hc, casting="unsafe")    # widen back (exact)
        np.subtract(c, s, out=rf[lo:lo + _CHUNK])


def dequant_add(t: np.ndarray, wirebuf: np.ndarray, scale: float | None,
                out: np.ndarray | None = None) -> np.ndarray:
    """Fused dequantize + elastic apply: ``out = t + dequant(wirebuf)``
    without materializing the decoded copy.  ``scale`` selects int8
    (float) vs fp16 (None).  ``out`` may alias ``t`` (the serial server's
    in-place apply) or be a fresh buffer (the concurrent server's
    immutable publish); default allocates."""
    if out is None:
        out = np.empty_like(t)
    tf = t.reshape(-1)
    wf = wirebuf.reshape(-1)
    of = out.reshape(-1)
    st = t.dtype.type(scale) if scale is not None else None
    if (st is not None and tf.size
            and wire_native.usable_apply(t, wirebuf, out)
            and wire_native.dequant_add_f32(tf, wf, st, of)):
        return out
    for lo in range(0, tf.size, _CHUNK):
        wc = wf[lo:lo + _CHUNK]
        s = _chunk_scratch(t.dtype)[:wc.size]
        if st is None:
            np.copyto(s, wc, casting="unsafe")      # fp16 widen
        else:
            np.multiply(wc, st, out=s)              # int8 dequant
        np.add(tf[lo:lo + _CHUNK], s, out=of[lo:lo + _CHUNK])
    return out


def fp16_add(t: np.ndarray, wirebuf: np.ndarray,
             out: np.ndarray | None = None) -> np.ndarray:
    return dequant_add(t, wirebuf, None, out=out)


# ---------------------------------------------------------------------------
# Device path: Pallas kernels (TPU production route; interpret on CPU)
# ---------------------------------------------------------------------------

#: int8 min tile is (32, 128) — pad flats to 32*128 elements so one grid
#: covers f32 and int8 refs alike (fused_update pads to the f32 tile only;
#: its 256-row block is a multiple of 32 too, so its grid serves both).
_TILE_Q = 32 * LANE


def _quant_ef_kernel(x_ref, s_ref, q_ref, r_ref):
    x = x_ref[:]
    st = s_ref[0, 0].astype(x.dtype)
    q = jnp.rint(x / st).astype(jnp.int8)
    q_ref[:] = q
    r_ref[:] = x - q.astype(x.dtype) * st


@jax.jit
def _quant_ef_call(x2d: jax.Array, st: jax.Array):
    n = x2d.shape[0] * LANE
    grid, block = _grid_for(n)
    spec = pl.BlockSpec(block, lambda i: (i, 0))
    return pl.pallas_call(
        _quant_ef_kernel,
        out_shape=(jax.ShapeDtypeStruct(x2d.shape, jnp.int8),
                   jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)),
        grid=(grid,),
        in_specs=[spec, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=(spec, spec),
        interpret=_interpret(),
    )(x2d, st)


@jax.jit
def _amax_call(x2d: jax.Array) -> jax.Array:
    return jnp.max(jnp.abs(x2d))


def _pad2d(flat: np.ndarray) -> tuple[jax.Array, int]:
    n = flat.size
    padded = -(-max(n, 1) // _TILE_Q) * _TILE_Q
    x = jnp.asarray(flat)
    if padded != n:
        x = jnp.pad(x, (0, padded - n))
    return x.reshape(padded // LANE, LANE), n


def quantize_ef_jax(d: np.ndarray | jax.Array
                    ) -> tuple[np.ndarray, float, np.ndarray]:
    """The Pallas route of :func:`quantize_ef_into` — one fused kernel
    producing ``(q, scale, r)``.  The scale division happens on the HOST
    in python floats (the reference's exact formula), so the kernel is
    purely elementwise and the manifest scale matches numpy bit for bit.
    Inside the kernel ``r`` may be contracted to an FMA by the backend —
    q and scale (the wire-visible outputs) are bitwise-stable; r can
    differ from the reference by <= 1 ulp (tests pin exactly that)."""
    arr = np.asarray(d) if not isinstance(d, jax.Array) else d
    shape = arr.shape
    flat = arr.reshape(-1)
    if flat.size == 0:
        return (np.zeros(shape, np.int8), 0.0,
                np.zeros(shape, np.asarray(arr).dtype))
    x2d, n = _pad2d(flat)
    amax = float(_amax_call(x2d))
    if not math.isfinite(amax):
        raise ValueError(
            "int8 wire codec cannot encode non-finite values (inf/nan leaf)")
    scale = amax / 127.0
    dt = x2d.dtype
    if scale == 0.0:
        return (np.zeros(shape, np.int8), 0.0,
                np.asarray(flat, dtype=dt).reshape(shape).copy())
    st = jnp.asarray(np.array([[dt.type(scale)]], dtype=dt))
    q2d, r2d = _quant_ef_call(x2d, st)
    q = np.asarray(q2d).reshape(-1)[:n].reshape(shape)
    r = np.asarray(r2d).reshape(-1)[:n].reshape(shape)
    return q, scale, r


def _dequant_add_kernel(c_ref, q_ref, s_ref, o_ref):
    c = c_ref[:]
    st = s_ref[0, 0].astype(c.dtype)
    o_ref[:] = c + q_ref[:].astype(c.dtype) * st


@jax.jit
def _dequant_add_call(c2d: jax.Array, q2d: jax.Array, st: jax.Array):
    n = c2d.shape[0] * LANE
    grid, block = _grid_for(n)
    spec = pl.BlockSpec(block, lambda i: (i, 0))
    return pl.pallas_call(
        _dequant_add_kernel,
        out_shape=jax.ShapeDtypeStruct(c2d.shape, c2d.dtype),
        grid=(grid,),
        in_specs=[spec, spec, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=spec,
        interpret=_interpret(),
    )(c2d, q2d, st)


def dequant_add_jax(t: np.ndarray | jax.Array, q: np.ndarray,
                    scale: float) -> np.ndarray:
    """The Pallas route of :func:`dequant_add` (int8): the center slice
    and int8 wire bytes meet on the VPU; only the applied result comes
    back.  Used by the device-pinned concurrent server, where it also
    quarters the H2D staging bytes (int8 up instead of decoded f32)."""
    arr = np.asarray(t) if not isinstance(t, jax.Array) else t
    shape = arr.shape
    flat = arr.reshape(-1)
    if flat.size == 0:
        return np.zeros(shape, np.asarray(arr).dtype)
    c2d, n = _pad2d(flat)
    q2d, _ = _pad2d(np.asarray(q).reshape(-1))
    st = jnp.asarray(np.array([[c2d.dtype.type(scale)]], dtype=c2d.dtype))
    o2d = _dequant_add_call(c2d, q2d, st)
    return np.asarray(o2d).reshape(-1)[:n].reshape(shape)


# ---------------------------------------------------------------------------
# Payload assembly: fused encode into a (reusable) frame buffer
# ---------------------------------------------------------------------------

def _use_device_route(x) -> bool:
    """Device-resident leaves on a TPU backend quantize on-device; every
    other combination takes the blocked host route (measured faster on
    CPU than interpret-mode Pallas by an order of magnitude)."""
    return isinstance(x, jax.Array) and jax.default_backend() == "tpu"


def encode_ef_into(leaves, residuals, codec: str, out=None):
    """Fused-codec replacement for the client's encode-then-decode walk:
    one pass per leaf produces the wire bytes AND the error-feedback
    residual (``residuals[i]`` is overwritten with the new carry; raw
    leaves carry a zero residual, matching ``d - decoded() == 0``).

    ``out`` is an optional :class:`wire.FrameBuffer`: wire bytes land in
    one preallocated contiguous region (reused across syncs), so
    ``Conn.send_packed`` ships a single iovec instead of a per-leaf
    gather and steady-state syncs allocate nothing.  Returns a
    ``wire.PackedPayload`` whose manifest is byte-identical to
    ``wire.encode_leaves``'s for the same inputs."""
    from distlearn_tpu.comm import wire

    if codec not in ("fp16", "int8"):
        raise ValueError(
            f"encode_ef_into is for lossy codecs, got {codec!r}")
    arrs = []
    for x in leaves:
        if _use_device_route(x):
            arrs.append(x)
            continue
        a = np.asarray(x)
        if not a.flags.c_contiguous:
            a = np.ascontiguousarray(a)
        arrs.append(a)
    if out is not None:
        total = sum(wire.encoded_nbytes(np.dtype(a.dtype), int(a.size),
                                        codec)
                    for a in arrs)
        out.reserve(total)
    entries, bufs = [], []
    offset = logical = 0
    for a, r in zip(arrs, residuals):
        dtype = np.dtype(a.dtype)
        shape = tuple(a.shape)
        size = int(a.size)
        extra: dict = {}
        if codec == "int8" and dtype.kind == "f":
            enc = "int8"
            if out is not None:
                buf = out.view(offset, size, np.dtype(np.int8), shape)
            else:
                buf = np.empty(shape, np.int8)
            if _use_device_route(a):
                q, scale, rr = quantize_ef_jax(a)
                np.copyto(buf, q)
                np.copyto(r, rr)
            else:
                scale = quantize_ef_into(a, buf, r)
            extra = {"scale": scale}
        elif (codec == "fp16" and dtype.kind == "f"
              and dtype.itemsize > 2):
            enc = "fp16"
            if out is not None:
                buf = out.view(offset, 2 * size, np.dtype(np.float16),
                               shape)
            else:
                buf = np.empty(shape, np.float16)
            if _use_device_route(a):
                a = np.asarray(jax.device_get(a))
            fp16_ef_into(a, buf, r)
        else:
            enc = "raw"
            if _use_device_route(a):
                a = np.asarray(jax.device_get(a))
            if out is not None:
                buf = out.view(offset, a.nbytes, dtype, shape)
                np.copyto(buf, a)
            else:
                buf = a
            if r is not None:
                r[...] = 0          # raw decodes to itself: zero carry
        entry = {"dtype": dtype.name, "shape": list(shape),
                 "enc": enc, "offset": offset, "nbytes": buf.nbytes}
        entry.update(extra)
        entries.append(entry)
        bufs.append(buf)
        offset += buf.nbytes
        logical += size * dtype.itemsize
    manifest = {"v": wire.WIRE_V, "codec": codec, "leaves": entries}
    payload = wire.PackedPayload(manifest, bufs, codec, offset, logical)
    if out is not None:
        payload.frame = out.frame(offset)
    return payload
