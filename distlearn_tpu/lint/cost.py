"""Static collective-traffic & memory cost model (rules DL201, DL202).

Where :mod:`distlearn_tpu.lint.spmd` analyzes the program the *author*
wrote (the jaxpr), this module analyzes the program the *compiler* built:
each step function is lowered and compiled on the deployment mesh and the
post-fusion HLO module is walked to attribute

* **bytes per collective kind per mesh axis** — every ``all-reduce``,
  ``all-gather``, ``reduce-scatter``, ``collective-permute`` and
  ``all-to-all`` op is parsed out of the module text with its payload
  shape and replica groups, and the groups are mapped back to the mesh
  axes they span (explicit ``{{0,4},{1,5}}`` lists, iota-form
  ``[2,4]<=[8]`` lists, and permute ``source_target_pairs`` all
  supported);
* **post-fusion collective op counts** — what fusion actually left in the
  module, which is what the wire sees (``ops/fused_update.py`` degrading
  to per-tensor reduces shows up here long before a profile would);
* **compiled peak/temp memory** from ``compiled.memory_analysis()``
  (:func:`compiled_memory_stats`).

The numbers are *per device per step*: the module XLA emits under SPMD
partitioning is the one program every device runs, with local (sharded)
shapes, so a payload byte count is what one device moves through one
step.  Two rules fire directly from the model:

* **DL201** — the compiled module contains more *large* all-gathers
  (payload >= :data:`GATHER_BYTES_THRESHOLD`) than the jaxpr requested
  explicitly: GSPMD sharding propagation lost a sharding on a hot path
  and is rematerializing a full buffer every step.
* **DL202** — the caller declared a sharded in-spec for a large argument
  but the compiled executable materializes that parameter fully
  replicated (>= :data:`REPLICATED_BYTES_THRESHOLD`).

Budget regression rules DL203-DL205 compare a :class:`CostReport` against
the committed per-family lockfiles — see :mod:`distlearn_tpu.lint.budget`.

Serve-path performance rules (DL206-DL209)
------------------------------------------
The serving hot path has failure modes training steps don't, so four
more rules ride the same compile:

* **DL206** — donation audit.  With ``donation=True`` the analyzer
  diffs the *declared* donations (``lowered.args_info``) against the
  ``input_output_alias`` table XLA actually committed to: a donated
  buffer the compiled program does NOT alias silently doubles its
  footprint (the K/V pools are the motivating case), and a large
  (>= :data:`DONATION_BYTES_THRESHOLD`) undonated input whose
  shape/dtype matches an unconsumed output is a donation the author
  forgot.  This is the compiled-program counterpart of the jaxpr-level
  DL005.
* **DL207** — recompile audit.  Every report carries the input
  ``signature`` (dtype + weak-type flag + shape per leaf) and the
  measured ``compile_s``; :func:`audit_compiles` counts distinct
  lowerings per family (the prefill bucket set), estimates the warmup
  tail, and flags two units in one bracketed group (``prefill[8]`` /
  ``prefill[16]``) that lower the *same shapes* under different
  dtype/weak-type signatures — the accidental-retrace class.  The
  distinct-compile *count* is budget-gated in the family lockfile
  (:mod:`distlearn_tpu.lint.budget`), so a new bucket fails tier-1
  until consciously re-baselined.
* **DL208** — entry relayout.  :func:`count_entry_relayouts` counts
  ``copy``/``transpose`` instructions in the ENTRY computation whose
  operand is an entry parameter — the compiler disagreeing with the
  caller about layout and paying a materialized relayout on every
  dispatch.  The count is budget-gated per unit (exact, like DL205).
* **DL209** — non-jitted tick-loop work.  :func:`lint_tick_loop` is a
  pure AST pass over ``serve/engine.py`` and ``serve/scheduler.py``
  flagging numpy/jnp *tensor math* (not bookkeeping) in the per-tick
  host methods (:data:`TICK_HOT_METHODS`) — math there runs once per
  tick on the host and belongs inside the jitted tick program.
"""

from __future__ import annotations

import ast
import math
import re
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

from distlearn_tpu.lint.core import Finding

__all__ = ["CollectiveOp", "CostReport", "analyze_step", "audit_compiles",
           "count_entry_relayouts", "lint_tick_loop", "parse_collectives",
           "GATHER_BYTES_THRESHOLD", "REPLICATED_BYTES_THRESHOLD",
           "DONATION_BYTES_THRESHOLD", "COLLECTIVE_KINDS",
           "TICK_HOT_METHODS"]

#: HLO opcodes the model attributes traffic to.
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

#: DL201 fires only for implicit all-gathers at least this large: tiny
#: gathers (scalars, loop counters, eval metrics) are GSPMD doing its job.
GATHER_BYTES_THRESHOLD = 1 << 20

#: DL202 fires only for replicated parameters at least this large.
REPLICATED_BYTES_THRESHOLD = 1 << 20

#: DL206's *missing*-donation arm only flags undonated inputs at least
#: this large (64 KiB): the K/V pools it exists for are hundreds of KiB
#: even on the lint mesh, while scalars/lens/token vectors that happen
#: to shape-match an output are not worth a donation.  The *wasted* arm
#: (declared donated, not aliased) fires at any size — a wasted donation
#: is a correctness smell, not just a memory one.
DONATION_BYTES_THRESHOLD = 1 << 16

#: Per-tick host methods on the serve hot path that DL209 audits: the
#: decode/admit/step loop bodies in ``serve/engine.py`` and
#: ``serve/scheduler.py``, the per-round prefill/verify/draft paths
#: (chunked prefill + speculative decode), and the per-admission radix
#: walks in ``serve/prefix_cache.py``.  Nested ``def``s inside them are
#: the staged (jitted) program bodies and are exempt.
TICK_HOT_METHODS = frozenset({"tick", "admit", "step", "_tick", "_admit",
                              "_expire", "_dispatch", "verify", "begin",
                              "prefill_step", "_advance_prefills",
                              "_pump_prefill", "propose", "match",
                              "insert", "evict_nodes", "evict_for_free"})

#: numpy/jnp calls DL209 treats as tensor *math* when issued per tick on
#: the host.  Bookkeeping (``asarray``, ``flatnonzero``, ``zeros``,
#: ``arange``, boolean masks) is deliberately absent: marshalling
#: arguments for the jitted program is the host loop's job.
_TENSOR_MATH_FNS = frozenset({
    "exp", "exp2", "expm1", "log", "log2", "log10", "log1p", "sqrt",
    "power", "tanh", "sin", "cos", "sinh", "cosh",
    "matmul", "dot", "vdot", "inner", "outer", "tensordot", "einsum",
    "argmax", "argmin", "softmax", "logsumexp",
    "cumsum", "cumprod", "mean", "std", "var", "median",
    "sort", "argsort", "take_along_axis", "top_k",
})

# f8 variants intentionally coarse; HLO spells dtypes like f32, bf16, s64.
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_DTYPE_BYTES.update({f"f8{suffix}": 1 for suffix in
                     ("e4m3fn", "e5m2", "e4m3b11fnuz", "e4m3fnuz", "e5m2fnuz")})

_SHAPE_RE = re.compile(r"([a-z]+[0-9]+(?:[a-z0-9]*)?|pred)\[([0-9,]*)\]")
# `%name = <shape> <kind>(`: shape is a bare token or a (tuple).  Operand
# references (`%all-gather.3`) never match — they are not preceded by
# `= <shape>` and not followed by `(`.
_OP_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|\S+)\s+"
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[0-9,{} ]*\}\}|\{\}|"
                        r"\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)")
_PAIRS_RE = re.compile(r"source_target_pairs=\{([0-9,{} ]*)\}")


def _shape_bytes(shape_token: str) -> int:
    """Byte size of one HLO shape token (``f32[4,8]{1,0}`` or a tuple)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_token):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue  # token dtype (opaque, s32[]-like already matched)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


def _parse_groups(attr: str) -> list[tuple[int, ...]]:
    """Parse a ``replica_groups=`` payload into device-id groups."""
    if attr.startswith("{"):
        return [tuple(int(x) for x in grp.split(",") if x.strip())
                for grp in re.findall(r"\{([0-9, ]+)\}", attr)]
    # iota form: [G,S]<=[dims](T(perm))? — arange over the flattened device
    # space, reshaped to `dims`, transposed by `perm`, regrouped as G rows.
    m = re.match(r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", attr)
    if not m:
        return []
    out_dims = [int(x) for x in m.group(1).split(",")]
    iota_dims = [int(x) for x in m.group(2).split(",")]
    ids = np.arange(math.prod(iota_dims)).reshape(iota_dims)
    if m.group(3):
        ids = ids.transpose([int(x) for x in m.group(3).split(",")])
    return [tuple(int(x) for x in row)
            for row in ids.reshape(out_dims[0], -1)]


def _mesh_device_ids(mesh) -> tuple[np.ndarray, tuple[str, ...]] | None:
    devices = getattr(mesh, "devices", None)
    names = getattr(mesh, "axis_names", None)
    if devices is None or names is None:
        return None
    ids = np.vectorize(lambda d: getattr(d, "id", -1))(np.asarray(devices))
    return ids, tuple(str(a) for a in names)


def _axes_for_groups(mesh, groups: Sequence[tuple[int, ...]]
                     ) -> tuple[str, ...]:
    """Mesh axes a replica-group list spans (``("?",)`` when unknown).

    A collective grouped along axis subset ``S`` partitions the devices
    into one group per coordinate of the *other* axes; we test every
    non-empty subset (meshes here have <= 4 axes) against the parsed
    groups.  Size-1 groups are the degenerate no-communication case and
    return ``()``.
    """
    if not groups:
        return ("?",)
    if all(len(g) <= 1 for g in groups):
        return ()
    info = _mesh_device_ids(mesh)
    if info is None:
        return ("?",)
    ids, names = info
    want = {frozenset(g) for g in groups}
    for mask in range(1, 1 << len(names)):
        subset = [i for i in range(len(names)) if mask & (1 << i)]
        rest = [i for i in range(len(names)) if i not in subset]
        grouped = ids.transpose(rest + subset).reshape(
            -1, math.prod(ids.shape[i] for i in subset))
        if {frozenset(int(x) for x in row) for row in grouped} == want:
            return tuple(names[i] for i in subset)
    return ("?",)


def _axes_for_pairs(mesh, pairs: Sequence[tuple[int, int]]
                    ) -> tuple[str, ...]:
    """Mesh axes a permute's source->target pairs move along."""
    info = _mesh_device_ids(mesh)
    if info is None or not pairs:
        return ("?",)
    ids, names = info
    where = {int(v): np.unravel_index(i, ids.shape)
             for i, v in enumerate(ids.ravel())}
    axes: set[str] = set()
    for src, dst in pairs:
        if src not in where or dst not in where:
            return ("?",)
        for dim, (a, b) in enumerate(zip(where[src], where[dst])):
            if a != b:
                axes.add(names[dim])
    return tuple(a for a in names if a in axes)


@dataclass(frozen=True)
class CollectiveOp:
    """One post-fusion collective in the compiled module."""

    kind: str            # one of COLLECTIVE_KINDS
    bytes: int           # payload bytes (local/per-device shape)
    axes: tuple          # mesh axes the op communicates over
    shape: str           # the HLO result shape token, for messages

    @property
    def axis_key(self) -> str:
        return f"{self.kind}@{','.join(self.axes) or '-'}"


@dataclass
class CostReport:
    """Static cost of one compiled step function.

    ``bytes_by_kind`` / ``ops_by_kind`` aggregate over mesh axes;
    ``bytes_by_axis`` keeps the per-axis split (keys like
    ``"all-reduce@data"``).  ``memory`` is the
    :func:`compiled_memory_stats` dict (or None where the backend
    reports nothing); ``flops`` comes from the compiler's own cost
    analysis when available.
    """

    name: str
    collectives: list[CollectiveOp] = field(default_factory=list)
    memory: dict | None = None
    flops: float | None = None
    #: hashable input signature: one (dtype, weak_type, shape) triple per
    #: flat argument leaf — two units with equal signatures share one
    #: compile-cache entry, distinct signatures are distinct lowerings
    #: (the DL207 accounting unit)
    signature: tuple | None = None
    #: measured lowering+compile wall time; feeds the warmup-tail
    #: estimate but stays OUT of the lockfile (nondeterministic)
    compile_s: float | None = None
    #: entry-parameter copy/transpose count in the compiled module
    #: (DL208); None when no HLO was inspected
    relayout_ops: int | None = None

    @property
    def bytes_by_kind(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for op in self.collectives:
            out[op.kind] = out.get(op.kind, 0) + op.bytes
        return out

    @property
    def ops_by_kind(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for op in self.collectives:
            out[op.kind] = out.get(op.kind, 0) + 1
        return out

    @property
    def bytes_by_axis(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for op in self.collectives:
            out[op.axis_key] = out.get(op.axis_key, 0) + op.bytes
        return out

    @property
    def ops_by_axis(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for op in self.collectives:
            out[op.axis_key] = out.get(op.axis_key, 0) + 1
        return out

    @property
    def peak_bytes(self) -> int | None:
        return self.memory.get("peak") if self.memory else None

    def to_json(self) -> dict:
        return {
            "collective_bytes": self.bytes_by_kind,
            "collective_ops": self.ops_by_kind,
            "bytes_by_axis": self.bytes_by_axis,
            "peak_bytes": self.peak_bytes,
            "temp_bytes": self.memory.get("temp") if self.memory else None,
            "flops": self.flops,
            "relayout_ops": self.relayout_ops,
        }


def parse_collectives(hlo_text: str, mesh=None) -> list[CollectiveOp]:
    """Extract every collective op from compiled HLO module text.

    Async pairs are counted once (the ``-start`` op carries the shape and
    groups; ``-done`` never matches).  ``mesh`` enables axis attribution;
    without it every op reports axes ``("?",)``.
    """
    ops = []
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group("kind")
        nbytes = _shape_bytes(m.group("shape"))
        if kind == "collective-permute":
            pm = _PAIRS_RE.search(line)
            pairs = [tuple(int(x) for x in p.split(","))
                     for p in re.findall(r"\{([0-9, ]+)\}",
                                         pm.group(1))] if pm else []
            axes = _axes_for_pairs(mesh, pairs) if mesh is not None else ("?",)
        else:
            gm = _GROUPS_RE.search(line)
            groups = _parse_groups(gm.group(1)) if gm else []
            axes = (_axes_for_groups(mesh, groups)
                    if mesh is not None else ("?",))
        ops.append(CollectiveOp(kind=kind, bytes=nbytes, axes=axes,
                                shape=m.group("shape")))
    return ops


def _count_explicit_gathers(fn, args) -> int:
    """Author-requested all-gathers: ``all_gather``/``pgather`` equations
    anywhere in the traced jaxpr (the baseline DL201 subtracts)."""
    import jax
    from jax.extend import core as jcore
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception:
        return 0

    def jaxprs_in(v):
        if isinstance(v, (jcore.Jaxpr, jcore.ClosedJaxpr)):
            yield v.jaxpr if isinstance(v, jcore.ClosedJaxpr) else v
        elif isinstance(v, (list, tuple)):
            for item in v:
                yield from jaxprs_in(item)

    count = 0
    stack = [closed.jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            if eqn.primitive.name in ("all_gather", "pgather"):
                count += 1
            for v in eqn.params.values():
                stack.extend(jaxprs_in(v))
    return count


def _spec_is_sharded(spec) -> bool:
    """True when a PartitionSpec/NamedSharding names at least one axis."""
    inner = getattr(spec, "spec", spec)       # NamedSharding -> its spec
    try:
        parts = tuple(inner)
    except TypeError:
        return False
    for p in parts:
        if p is None:
            continue
        if isinstance(p, (tuple, list)):
            if any(p):
                return True
        else:
            return True
    return False


def _audit_replicated_params(lowered, compiled, args, in_specs,
                             name: str) -> list[Finding]:
    """DL202: declared-sharded large arguments compiled fully replicated."""
    import jax
    try:
        actual = compiled.input_shardings[0]
    except Exception:
        return []
    arg_leaves = jax.tree_util.tree_leaves(args)
    spec_leaves = jax.tree_util.tree_leaves(
        in_specs, is_leaf=lambda x: x is None or _is_spec(x))
    if len(arg_leaves) != len(spec_leaves) or \
            len(arg_leaves) != len(actual):
        return []
    findings = []
    for leaf, spec, sharding in zip(arg_leaves, spec_leaves, actual):
        if spec is None or not _spec_is_sharded(spec):
            continue
        size = getattr(leaf, "size", 0) * getattr(
            np.dtype(getattr(leaf, "dtype", "f4")), "itemsize", 4)
        if size < REPLICATED_BYTES_THRESHOLD:
            continue
        if getattr(sharding, "is_fully_replicated", False):
            findings.append(Finding(
                "DL202",
                f"argument declared sharded as {spec} "
                f"({size} bytes) compiles to a fully replicated "
                "parameter; the sharding was dropped between the in-spec "
                "and the executable (check with_sharding_constraint / "
                "jit in_shardings wiring)",
                where=name))
    return findings


def _is_spec(x) -> bool:
    from jax.sharding import NamedSharding, PartitionSpec
    return isinstance(x, (NamedSharding, PartitionSpec))


# --------------------------------------------------------------- DL206 --

def _alias_param_ids(hlo_text: str) -> set[int]:
    """Flat parameter numbers the compiled module's ``input_output_alias``
    table aliases to an output.  The attribute nests braces
    (``{ {0}: (23, {}, may-alias), ... }``), so the payload is isolated
    with a brace scan and the targets read as ``(N, ...)`` tuples."""
    marker = "input_output_alias={"
    i = hlo_text.find(marker)
    if i < 0:
        return set()
    j, depth = i + len(marker), 1
    while j < len(hlo_text) and depth:
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
        j += 1
    sub = hlo_text[i + len(marker):j - 1]
    return {int(n) for n in re.findall(r"\((\d+)\s*,", sub)}


def _leaf_bytes(leaf) -> int:
    size = getattr(leaf, "size", None)
    if size is None:
        size = math.prod(getattr(leaf, "shape", ()) or (1,))
    return int(size) * getattr(
        np.dtype(getattr(leaf, "dtype", "f4")), "itemsize", 4)


def _check_donation(lowered, hlo_text: str, name: str) -> list[Finding]:
    """DL206: declared donations vs. the aliases XLA committed to, plus
    large undonated inputs a matching output could have consumed."""
    import jax
    try:
        in_leaves = jax.tree_util.tree_leaves(lowered.args_info)
        out_leaves = jax.tree_util.tree_leaves(lowered.out_info)
    except Exception:
        return []            # pre-args_info jax: nothing to audit
    aliased = _alias_param_ids(hlo_text)
    findings = []
    for i, leaf in enumerate(in_leaves):
        if getattr(leaf, "donated", False) and i not in aliased:
            findings.append(Finding(
                "DL206",
                f"input #{i} ({tuple(leaf.shape)}/{leaf.dtype}, "
                f"{_leaf_bytes(leaf)} bytes) is declared donated but the "
                "compiled program aliases it to NO output — the caller's "
                "buffer is invalidated and no memory is saved; drop the "
                "donation or give the program a shape/dtype-matching "
                "output to reuse it",
                where=name))
    # outputs still available for aliasing: each committed alias consumes
    # one output of the donated input's (shape, dtype) — count-aware so
    # two same-shaped pools can't both claim the same output
    out_count = Counter((tuple(leaf.shape), str(leaf.dtype))
                        for leaf in out_leaves)
    for i in sorted(aliased):
        if i < len(in_leaves):
            leaf = in_leaves[i]
            key = (tuple(leaf.shape), str(leaf.dtype))
            if out_count.get(key):
                out_count[key] -= 1
    for i, leaf in enumerate(in_leaves):
        if getattr(leaf, "donated", False):
            continue
        key = (tuple(leaf.shape), str(leaf.dtype))
        nbytes = _leaf_bytes(leaf)
        if nbytes >= DONATION_BYTES_THRESHOLD and out_count.get(key):
            out_count[key] -= 1
            findings.append(Finding(
                "DL206",
                f"input #{i} ({tuple(leaf.shape)}/{leaf.dtype}, {nbytes} "
                "bytes) is not donated but a shape/dtype-matching output "
                "leaf goes unaliased — the program holds both buffers "
                "live every dispatch; donate the input (engine pools: "
                "DecodeEngine(donate=True)) to halve its footprint",
                where=name))
    return findings


# --------------------------------------------------------------- DL207 --

def _arg_signature(args) -> tuple:
    """Per-leaf (dtype, weak_type, shape) triples — the compile-cache
    key distinct lowerings are counted by (DL207)."""
    import jax
    return tuple(
        (str(getattr(leaf, "dtype", "?")),
         bool(getattr(leaf, "weak_type", False)),
         str(tuple(getattr(leaf, "shape", ()))))
        for leaf in jax.tree_util.tree_leaves(args))


def audit_compiles(family: str, reports) -> tuple[list[Finding], dict]:
    """DL207 drift audit + the family's compile summary.

    Returns ``(findings, summary)``: findings flag two units of one
    bracketed group (``decode_prefill[8]``/``[16]``) whose signatures
    share every shape but differ in dtype or weak-type — the same
    logical program paying two warmup compiles because a host-side cast
    or Python-scalar leak drifted the signature.  ``summary`` is
    ``{"count": distinct lowerings, "warmup_s_estimate": measured
    compile seconds}`` — the count is what the budget lockfile gates.
    """
    findings: list[Finding] = []
    sigs = {name: rep.signature for name, rep in sorted(reports.items())
            if rep.signature is not None}
    groups: dict[str, list] = {}
    for name, sig in sigs.items():
        groups.setdefault(name.split("[", 1)[0], []).append((name, sig))
    for base, members in sorted(groups.items()):
        by_shapes: dict[tuple, tuple] = {}
        for name, sig in members:
            shapes = tuple(s for _dt, _wk, s in sig)
            prev = by_shapes.setdefault(shapes, (name, sig))
            if prev[1] != sig:
                findings.append(Finding(
                    "DL207",
                    f"units {prev[0]!r} and {name!r} lower identical "
                    "shapes under different dtype/weak-type signatures — "
                    "one logical program costs two warmup compiles "
                    "(a dtype cast or weak-typed Python scalar drifted "
                    "the compile-cache key)",
                    where=f"{family}:{base}"))
    count = len(set(sigs.values()))
    warmup = sum(rep.compile_s or 0.0 for rep in reports.values())
    return findings, {"count": count,
                      "warmup_s_estimate": round(warmup, 3)}


# --------------------------------------------------------------- DL208 --

_PARAM_DEF_RE = re.compile(r"%([\w.\-]+)\s*=\s*\S+\s+parameter\(")
_RELAYOUT_RE = re.compile(
    r"=\s*\S+\s+(?:copy|transpose)\("
    r"(?:[a-z0-9_]+\[[0-9,]*\](?:\{[^}]*\})?\s+)?%([\w.\-]+)")


def count_entry_relayouts(hlo_text: str) -> int:
    """``copy``/``transpose`` ops in the ENTRY computation whose operand
    is an entry parameter — the compiler re-materializing an argument in
    a different layout on every dispatch (DL208).  Only the ENTRY block
    is scanned: fusion-region ``parameter()`` lines are computation-local
    and say nothing about the program's entry layout contract."""
    m = re.search(r"^ENTRY\b", hlo_text, re.M)
    if not m:
        return 0
    depth, started, lines = 0, False, []
    for line in hlo_text[m.start():].splitlines():
        lines.append(line)
        depth += line.count("{") - line.count("}")
        if "{" in line:
            started = True
        if started and depth <= 0:
            break
    block = "\n".join(lines)
    params = set(_PARAM_DEF_RE.findall(block))
    return sum(1 for operand in _RELAYOUT_RE.findall(block)
               if operand in params)


# --------------------------------------------------------------- DL209 --

def _scan_hot_method(node, modname: str, clsname: str) -> list[Finding]:
    findings = []

    def walk(n):
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue     # staged closure: runs inside the XLA program
            where = (f"{modname}.{clsname}.{node.name}:"
                     f"{getattr(child, 'lineno', node.lineno)}")
            if isinstance(child, ast.BinOp) and isinstance(child.op,
                                                           ast.MatMult):
                findings.append(Finding(
                    "DL209",
                    f"host-side matrix multiply (@) in per-tick method "
                    f"{clsname}.{node.name}() runs on every tick — it "
                    "belongs inside the jitted tick program",
                    where=where))
            elif (isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Attribute)
                    and isinstance(child.func.value, ast.Name)
                    and child.func.value.id in ("np", "jnp", "numpy")
                    and child.func.attr in _TENSOR_MATH_FNS):
                findings.append(Finding(
                    "DL209",
                    f"per-tick host tensor math "
                    f"{child.func.value.id}.{child.func.attr}(...) in "
                    f"{clsname}.{node.name}() — every call is a Python-"
                    "level pass over tensor data in the serve hot loop; "
                    "move it inside the jitted tick program",
                    where=where))
            walk(child)

    walk(node)
    return findings


def lint_tick_loop(sources=None) -> list[Finding]:
    """DL209: numpy/jnp tensor math in the per-tick host methods.

    ``sources`` is a list of ``(source, modname)`` pairs (or raw source
    strings); defaults to ``serve/engine.py`` + ``serve/scheduler.py`` +
    ``serve/prefix_cache.py`` + ``serve/speculate.py`` (every module
    with per-round host work).  Only methods named in
    :data:`TICK_HOT_METHODS` directly on a class body are scanned —
    nested ``def``s are the staged program bodies the math is SUPPOSED
    to live in, and are skipped both as scan roots and inside a hot
    method."""
    if sources is None:
        import inspect
        from distlearn_tpu.serve import (engine, prefix_cache, scheduler,
                                         speculate)
        sources = [(inspect.getsource(m), m.__name__)
                   for m in (engine, scheduler, prefix_cache, speculate)]
    findings: list[Finding] = []
    for item in sources:
        src, modname = item if isinstance(item, tuple) else (item,
                                                             "<string>")
        for cls in ast.walk(ast.parse(src)):
            if not isinstance(cls, ast.ClassDef):
                continue
            for stmt in cls.body:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                        and stmt.name in TICK_HOT_METHODS:
                    findings += _scan_hot_method(stmt, modname, cls.name)
    return findings


def compiled_memory_stats(compiled) -> dict | None:
    """Byte-level memory stats of a compiled executable, or None where
    the backend reports nothing.

    ``compiled.memory_analysis()`` as a plain dict with ``argument``,
    ``output``, ``temp``, ``alias``, ``generated_code`` byte counts plus
    a derived ``peak`` (arguments + outputs + temporaries, minus donated
    aliases — the live-at-once footprint the budget lockfiles gate)."""
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    stats = {k: int(getattr(ma, k + "_size_in_bytes", 0) or 0)
             for k in ("argument", "output", "temp", "alias",
                       "generated_code")}
    if not any(stats.values()):
        return None
    stats["peak"] = max(0, stats["argument"] + stats["output"]
                        + stats["temp"] - stats["alias"])
    return stats


def analyze_step(fn, args: Sequence, *, mesh=None, name: str = "step",
                 in_specs=None,
                 gather_threshold: int = GATHER_BYTES_THRESHOLD,
                 donation: bool = False
                 ) -> tuple[CostReport, list[Finding]]:
    """Compile ``fn(*args)`` and build its :class:`CostReport`.

    Returns ``(report, findings)`` where findings are the compile-level
    rules (DL201 implicit all-gather, DL202 replicated parameter, and —
    with ``donation=True`` — DL206 wasted/missing donation); the
    lockfile rules DL203-DL205/DL207/DL208 are applied by
    :func:`distlearn_tpu.lint.budget.check_family` over a whole family's
    reports.  ``in_specs`` (optional pytree of
    PartitionSpec/NamedSharding leaves matching ``args``) enables DL202.
    The report also carries the unit's compile-cache ``signature``,
    measured ``compile_s``, and entry ``relayout_ops`` for the DL207/
    DL208 budget gates.
    """
    import jax
    t0 = time.perf_counter()
    # ``args`` may be abstract (ShapeDtypeStruct) — nothing is executed
    lowered = (fn if hasattr(fn, "lower") else jax.jit(fn)).lower(*args)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    report = CostReport(
        name=name,
        collectives=parse_collectives(hlo, mesh),
        memory=compiled_memory_stats(compiled),
        flops=(compiled.cost_analysis() or {}).get("flops"),
        signature=_arg_signature(args),
        compile_s=compile_s,
        relayout_ops=count_entry_relayouts(hlo),
    )
    findings = []
    large = [op for op in report.collectives
             if op.kind == "all-gather" and op.bytes >= gather_threshold]
    explicit = _count_explicit_gathers(fn, args) if large else 0
    if len(large) > explicit:
        worst = max(large, key=lambda op: op.bytes)
        findings.append(Finding(
            "DL201",
            f"compiled module contains {len(large)} all-gather op(s) of "
            f">= {gather_threshold} bytes but the jaxpr requests only "
            f"{explicit}; GSPMD inserted a replication gather (largest: "
            f"{worst.shape} over axes {list(worst.axes)}, {worst.bytes} "
            "bytes/step) — re-shard the producer or add a "
            "with_sharding_constraint",
            where=name))
    if in_specs is not None:
        findings += _audit_replicated_params(lowered, compiled, args,
                                             in_specs, name)
    if donation:
        findings += _check_donation(lowered, hlo, name)
    return report, findings
