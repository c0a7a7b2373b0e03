"""Jaxpr-level SPMD/collective linter (rules DL001-DL005).

The linter abstractly traces a step function to a closed jaxpr
(:func:`jax.make_jaxpr`) and walks it, descending through every
higher-order primitive the repo emits (``pjit``, ``shard_map``, ``cond``,
``while``, ``scan``, ``remat``, custom-derivative calls).  Two pieces of
state thread through the walk:

* ``bound`` — the set of mesh axis names the current code is executing
  under, one entry per device along that axis.  Extended by ``shard_map``
  equations (their ``mesh`` param) and seeded at the top level from the
  trace ``axis_env`` intersected with the deployment mesh, so an axis
  bound at trace time but absent from the real mesh is *not* considered
  bound — that is exactly rule DL001.

* per-value **taint** — the set of bound axes across which a value may
  differ between devices.  Sources: ``axis_index`` output and
  ``shard_map`` inputs sharded along an axis (``in_specs``).  A reducing
  collective over axes ``A`` makes its result identical along ``A`` and
  subtracts ``A`` from the taint; everything else unions its operands.
  Taint is what lets DL002 stay quiet on the repo's
  ``lax.cond(any_due, ...)`` pattern (predicate derived from a ``psum``
  is device-uniform, so divergent branches are safe) while still firing
  when the predicate genuinely varies per device, and what lets DL003
  recognise ``fold_in(key, axis_index(...))`` as per-device randomness.

Entry points: :func:`lint_step` (trace a callable and lint it, including
the DL005 donation audit when the callable is jitted) and
:func:`lint_jaxpr` (lint an already-closed jaxpr).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
from jax.extend import core

from distlearn_tpu.lint.core import Finding, filter_suppressed

__all__ = ["lint_step", "lint_jaxpr", "lint_donation"]

# Cross-device communication primitives: a mismatched sequence of these
# across devices is a hang.  ``axis_index`` is checked for DL001 but is
# not a synchronization point, so it stays out of this set.
_COLLECTIVES = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "pgather",
    "all_gather", "all_to_all", "reduce_scatter",
})
# Collectives that *accumulate* across devices: low-precision operands
# lose mantissa once the reduction fan-in grows (DL004).  pmax/pmin are
# exact in any dtype and exempt.
_ACCUMULATING = frozenset({"psum", "reduce_scatter"})
# Collectives whose result is identical along the reduced/gathered axes.
_UNIFORMIZING = frozenset({"psum", "pmax", "pmin", "all_gather"})
# PRNG consumption points (typed-key and raw-uint32 paths).
_RNG_CONSUMERS = frozenset({"random_bits", "threefry2x32"})


def _collective_axes(eqn) -> tuple[str, ...]:
    """Mesh axis names a collective equation communicates over."""
    if eqn.primitive.name in ("psum", "pmax", "pmin"):
        axes = eqn.params.get("axes", ())
    else:
        axes = eqn.params.get("axis_name", ())
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _sub_jaxpr(params):
    """Best-effort: the single sub-jaxpr of a call-like equation."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        v = params.get(key)
        if isinstance(v, (core.Jaxpr, core.ClosedJaxpr)):
            return v
    return None


class _WalkResult(NamedTuple):
    out_taints: list          # frozenset per outvar
    seq: tuple                # ordered collective signature ((prim, axes), ...)
    findings: list            # list[Finding]


def _walk_closed(cj, in_taints, bound, path):
    if isinstance(cj, core.ClosedJaxpr):
        return _walk(cj.jaxpr, in_taints, bound, path)
    return _walk(cj, in_taints, bound, path)


def _walk(jaxpr: core.Jaxpr, in_taints, bound: frozenset, path: str) -> _WalkResult:
    env: dict = {}
    findings: list[Finding] = []
    seq: list = []

    def taint_of(atom):
        if isinstance(atom, core.Literal):
            return frozenset()
        return env.get(atom, frozenset())

    for v, t in zip(jaxpr.invars, in_taints):
        env[v] = t
    for v in jaxpr.constvars:
        env[v] = frozenset()

    for i, eqn in enumerate(jaxpr.eqns):
        prim = eqn.primitive.name
        here = f"{path}/{prim}#{i}"
        in_ts = [taint_of(a) for a in eqn.invars]
        default_out = frozenset().union(*in_ts) if in_ts else frozenset()

        if prim == "shard_map":
            mesh_axes = frozenset(str(a) for a in eqn.params["mesh"].axis_names)
            inner_bound = bound | mesh_axes
            body_in = []
            for t, spec in zip(in_ts, eqn.params["in_specs"]):
                # a PartitionSpec entry is None, an axis name, or a tuple
                sharded = frozenset(
                    str(a) for part in spec if part is not None
                    for a in (part if isinstance(part, tuple) else (part,)))
                body_in.append(t | sharded)
            sub = _walk_closed(eqn.params["jaxpr"], body_in, inner_bound,
                               f"{here}")
            findings += sub.findings
            seq += sub.seq
            # Leaving the region the per-device shards are reassembled into
            # global arrays: variance along this shard_map's axes is spent.
            for v, t in zip(eqn.outvars, sub.out_taints):
                env[v] = t - mesh_axes
            continue

        if prim == "cond":
            pred_t = in_ts[0]
            branches = eqn.params["branches"]
            subs = [_walk_closed(br, in_ts[1:], bound,
                                 f"{here}[branch {k}]")
                    for k, br in enumerate(branches)]
            for s in subs:
                findings += s.findings
            sigs = {s.seq for s in subs}
            if len(sigs) > 1 and pred_t:
                findings.append(Finding(
                    "DL002",
                    "collective sequences differ across cond branches "
                    f"({' vs '.join(_fmt_seq(s.seq) for s in subs)}) and the "
                    f"predicate varies across mesh axes {sorted(pred_t)}; "
                    "devices taking different branches will issue mismatched "
                    "collectives and hang",
                    where=here))
            seq += subs[0].seq
            for k, v in enumerate(eqn.outvars):
                t = frozenset().union(*(s.out_taints[k] for s in subs))
                env[v] = t | pred_t
            continue

        if prim == "while":
            cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
            cond_consts, body_consts = in_ts[:cn], in_ts[cn:cn + bn]
            carry = list(in_ts[cn + bn:])
            body_j = eqn.params["body_jaxpr"]
            cond_j = eqn.params["cond_jaxpr"]
            for _ in range(8):  # taint fixpoint over the carry
                out = _walk_closed(body_j, body_consts + carry, bound, here)
                new = [c | o for c, o in zip(carry, out.out_taints)]
                if new == carry:
                    break
                carry = new
            body = _walk_closed(body_j, body_consts + carry, bound,
                                f"{here}[body]")
            cond = _walk_closed(cond_j, cond_consts + carry, bound,
                                f"{here}[cond]")
            findings += body.findings + cond.findings
            pred_t = cond.out_taints[0] if cond.out_taints else frozenset()
            if pred_t and (body.seq or cond.seq):
                findings.append(Finding(
                    "DL002",
                    "while loop contains collectives "
                    f"({_fmt_seq(body.seq + cond.seq)}) but its predicate "
                    f"varies across mesh axes {sorted(pred_t)}; devices may "
                    "run different trip counts and hang",
                    where=here))
            seq += cond.seq + body.seq
            for v, t in zip(eqn.outvars, carry):
                env[v] = t | pred_t
            continue

        if prim == "scan":
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            consts, carry, xs = in_ts[:nc], list(in_ts[nc:nc + nk]), in_ts[nc + nk:]
            body_j = eqn.params["jaxpr"]
            for _ in range(8):
                out = _walk_closed(body_j, consts + carry + xs, bound, here)
                new = [c | o for c, o in zip(carry, out.out_taints[:nk])]
                if new == carry:
                    break
                carry = new
            body = _walk_closed(body_j, consts + carry + xs, bound,
                                f"{here}[body]")
            findings += body.findings
            seq += body.seq
            outs = carry + list(body.out_taints[nk:])
            for v, t in zip(eqn.outvars, outs):
                env[v] = t
            continue

        if prim in _COLLECTIVES or prim == "axis_index":
            axes = _collective_axes(eqn)
            unknown = [a for a in axes if a not in bound]
            if unknown:
                findings.append(Finding(
                    "DL001",
                    f"{prim} over axis {unknown!r} but only "
                    f"{sorted(bound) or 'no axes'} are bound by the "
                    "enclosing mesh/shard_map",
                    where=here))
            if prim == "axis_index":
                for v in eqn.outvars:
                    env[v] = frozenset(axes)
                continue
            if prim in _ACCUMULATING:
                for a in eqn.invars:
                    dt = getattr(a.aval, "dtype", None)
                    if (dt is not None and jax.numpy.issubdtype(dt, jax.numpy.floating)
                            and dt.itemsize < 4):
                        findings.append(Finding(
                            "DL004",
                            f"{prim} over {axes!r} accumulates in {dt.name}; "
                            "upcast the operand to >=float32 before the "
                            "reduction and cast back after",
                            where=here))
            seq.append((prim, tuple(sorted(axes))))
            out_t = default_out
            if prim in _UNIFORMIZING:
                out_t = out_t - frozenset(axes)
            for v in eqn.outvars:
                env[v] = out_t
            continue

        if prim in _RNG_CONSUMERS:
            if bound and not default_out:
                findings.append(Finding(
                    "DL003",
                    f"PRNG key consumed ({prim}) inside an SPMD region over "
                    f"axes {sorted(bound)} but the key is identical on every "
                    "device; fold in a per-device value first, e.g. "
                    "random.fold_in(key, lax.axis_index(axis))",
                    where=here))
            for v in eqn.outvars:
                env[v] = default_out
            continue

        sub = _sub_jaxpr(eqn.params)
        if sub is not None:
            body = sub.jaxpr if isinstance(sub, core.ClosedJaxpr) else sub
            if len(body.invars) == len(eqn.invars):
                name = eqn.params.get("name")
                sub_path = f"{here}" + (f"({name})" if name else "")
                s = _walk_closed(sub, in_ts, bound, sub_path)
                findings += s.findings
                seq += s.seq
                if len(s.out_taints) == len(eqn.outvars):
                    for v, t in zip(eqn.outvars, s.out_taints):
                        env[v] = t
                    continue
        # Default transfer: outputs inherit the union of operand taints.
        for v in eqn.outvars:
            env[v] = default_out

    return _WalkResult([taint_of(v) for v in jaxpr.outvars],
                       tuple(seq), findings)


def _fmt_seq(seq) -> str:
    if not seq:
        return "[]"
    return "[" + ", ".join(f"{p}@{','.join(a)}" for p, a in seq) + "]"


def lint_jaxpr(closed_jaxpr: core.ClosedJaxpr, *, mesh=None, axis_env=None,
               name: str = "step") -> list[Finding]:
    """Lint a closed jaxpr.

    ``mesh`` (a :class:`jax.sharding.Mesh` or iterable of axis names) is the
    deployment mesh; ``axis_env`` the ``(name, size)`` bindings the jaxpr
    was traced under, if any.  Axes bound at trace time but missing from
    the deployment mesh are treated as unbound, so collectives over them
    raise DL001.
    """
    env_axes = frozenset(a for a, _ in (axis_env or ()))
    mesh_axes = _mesh_axis_names(mesh)
    bound = env_axes if mesh_axes is None else env_axes & mesh_axes
    in_taints = [frozenset() for _ in closed_jaxpr.jaxpr.invars]
    return _walk(closed_jaxpr.jaxpr, in_taints, bound, name).findings


def _mesh_axis_names(mesh):
    if mesh is None:
        return None
    names = getattr(mesh, "axis_names", mesh)
    return frozenset(str(a) for a in names)


def lint_donation(fn, args, *, name: str = "step") -> list[Finding]:
    """DL005: every donated input leaf must have a shape/dtype-matching
    output leaf to alias; otherwise the donation deletes a buffer XLA can
    never reuse and any later read of it fails."""
    try:
        lowered = fn.lower(*args)
        args_info = jax.tree_util.tree_leaves(lowered.args_info)
        out_info = jax.tree_util.tree_leaves(lowered.out_info)
    except Exception:  # not a jit wrapper, or lowering unsupported here
        return []
    findings = []
    outs = [(tuple(o.shape), jax.numpy.dtype(o.dtype)) for o in out_info]
    for a in args_info:
        if not a.donated:
            continue
        key = (tuple(a.shape), jax.numpy.dtype(a.dtype))
        if key in outs:
            outs.remove(key)  # each output aliases at most one input
        else:
            findings.append(Finding(
                "DL005",
                f"donated input {key[1].name}{list(key[0])} has no matching "
                "output to alias; the buffer is invalidated without being "
                "reused",
                where=name))
    return findings


def lint_step(fn, args: Sequence, *, mesh=None, axis_env=None,
              suppress=(), name: str = "step",
              check_donation: bool = True) -> list[Finding]:
    """Trace ``fn(*args)`` abstractly and lint the resulting jaxpr.

    ``args`` may be concrete arrays or :class:`jax.ShapeDtypeStruct`s.
    When ``fn`` is a jit wrapper the DL005 donation audit runs as well.
    """
    make = jax.make_jaxpr(fn, axis_env=list(axis_env) if axis_env else None)
    closed = make(*args)
    findings = lint_jaxpr(closed, mesh=mesh, axis_env=axis_env, name=name)
    if check_donation:
        findings += lint_donation(fn, args, name=name)
    return filter_suppressed(findings, suppress)
