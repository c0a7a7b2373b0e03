"""Device time by pass and by model part, under names that outlive a
refactor: the trace reducer's per-operation self times
(``run.trace.reduction["ops"]``: ``{"<instruction> <shape>": [self s, count,
whole s]}``) joined with what the PROGRAM says each instruction of its step
belongs to (``distlearn_tpu.utils.profiling.scope_table`` of the step's own
optimized HLO: JAX's ``op_name``, which carries the ``jax.named_scope`` of
``distlearn_tpu.models.core.SCOPES`` and JAX's own marks of the pass).

Pure functions on text and dicts first (tested on the CPU with hand counts),
then the two that read a run.  A program that has no scopes, no catalog of
step programs or no ``train.dispatch`` span (the parent of the PR that added
them) gives ``None`` everywhere: a reader then leaves its metric out.
"""

from __future__ import annotations

import json
import re
import statistics

from harness import log

#: the passes of a train step, in the order of the table's rows; ``other``
#: is what belongs to no pass: gradient reduction, update, loss bookkeeping
PHASES = ("fwd", "recompute", "bwd", "other")
UNSCOPED = "unscoped"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=(\s.*)$")
_SHAPE = re.compile(r"^(\w+)\[([\d,]*)\]")
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}


def phase_of(op_name: str) -> str:
    """The pass an ``op_name`` belongs to, from JAX's own marks."""
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "bwd"
    if "jvp(" in op_name:
        return "fwd"
    return "other"


def scope_of(op_name: str, scopes) -> str:
    """The declared scope among the ``/`` components of ``op_name`` (the
    innermost, should two nest), seen through JAX's transform wrappers
    (``transpose(jvp(attn_core))``) but not through ``jit(...)``, which
    names a function and not a scope; ``UNSCOPED`` if there is none."""
    found = UNSCOPED
    for part in op_name.split("/"):
        while (m := _WRAPPED.match(part)):
            if m.group(1) in ("jit", "pjit"):
                part = ""
                break
            part = m.group(2)
        if part in scopes:
            found = part
    return found


def instructions(hlo_text: str) -> dict:
    """``{instruction name: (opcode, bytes of its array result or None,
    operand names)}`` of every instruction of an HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _DEF.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(rest)
        if not op:
            continue
        shape = _SHAPE.match(rest.lstrip())
        size = None
        if shape and shape.group(1) in _DTYPE_BYTES:
            size = _DTYPE_BYTES[shape.group(1)]
            for d in filter(None, shape.group(2).split(",")):
                size *= int(d)
        args = rest[op.end() - 1:]
        depth, end = 0, len(args)
        for i, c in enumerate(args):
            depth += (c == "(") - (c == ")")
            if depth == 0 and i:
                end = i
                break
        out[name] = (op.group(1), size,
                     re.findall(r"%([\w.\-]+)", args[:end]))
    return out


def collective_kind(opcode: str) -> str | None:
    """``all-reduce`` for ``all-reduce``, ``all-reduce-start`` and
    ``all-reduce-done``; None for anything that is not a collective."""
    base = re.sub(r"-(start|done)$", "", opcode)
    return base if base in COLLECTIVES else None


def collective_bytes(hlo_text: str) -> dict:
    """``{collective kind: bytes}`` one chip hands to the collective
    instructions of the module, each instruction of the text counted once
    (one inside a ``while`` body too: no cell has one there): the bytes of
    its operands, sync or ``-start`` form; a ``-done`` hands over nothing
    new."""
    ins = instructions(hlo_text)
    total: dict[str, int] = {}
    for opcode, _, operands in ins.values():
        kind = collective_kind(opcode)
        if kind and not opcode.endswith("-done"):
            total[kind] = total.get(kind, 0) + sum(
                ins[o][1] or 0 for o in operands if o in ins)
    return total


def reduce_ops(ops: dict, table: dict, opcodes: dict, scopes, calls: int):
    """Seconds of SELF time per call, by pass and scope.

    ``ops``: the trace reducer's (key's first word = instruction name);
    ``table``: instruction -> ``op_name``; ``opcodes``: instruction ->
    opcode (to set collectives apart); ``scopes``: the declared names.
    Returns ``{"phases": {phase: {scope | "unscoped": s}}, "collective_s",
    "busy_s", "unnamed_s", "largest_unscoped": [[key, s, op_name], ...]}``
    — every operation lands in exactly one cell of ``phases`` or in
    ``collective_s``, so they add up to ``busy_s`` (self times add up to
    the busy time).  ``unnamed_s``: time of operations the program's text
    does not name at all (they count as ``other`` / unscoped)."""
    phases = {p: {} for p in PHASES}
    collective = unnamed = busy = 0.0
    loose = []
    for key, (self_s, _count, _whole) in ops.items():
        name = key.split(" ", 1)[0]
        s = self_s / calls
        busy += s
        if collective_kind(opcodes.get(name, name.rsplit(".", 1)[0])):
            collective += s
            continue
        op_name = table.get(name)
        if op_name is None:
            unnamed += s
            op_name = ""
        scope = scope_of(op_name, scopes)
        cell = phases[phase_of(op_name)]
        cell[scope] = cell.get(scope, 0.0) + s
        if scope == UNSCOPED:
            loose.append([key, s, op_name])
    loose.sort(key=lambda r: -r[1])
    return {"phases": phases, "collective_s": collective, "busy_s": busy,
            "unnamed_s": unnamed, "largest_unscoped": loose[:8]}


def _lm_program():
    """The LM step's shim from the program's catalog, its scope names, and
    the program's ``scope_table``; None where the program has none."""
    try:
        from distlearn_tpu.models.core import SCOPES
        from distlearn_tpu.train.trainer import step_programs
        from distlearn_tpu.utils.profiling import scope_table
    except ImportError:
        return None
    shim = step_programs().get("lm")
    return None if shim is None else (shim, SCOPES, scope_table)


def step_hlo(run, result):
    """The optimized HLO text of the LM step this run built, once per run
    (kept in ``result.window``); None where the program cannot say.

    In a traced run the instruction NAMES matter, and the executable the
    window ran may have come out of a persistent cache that another
    checkout filled from the same mathematics under other scope names (JAX
    keys the cache without them).  So the in-memory programs are dropped
    first — the window is over, nothing runs after it — and
    ``hlo_text()`` compiles the step anew with its names in the key: same
    compiler, same instructions, this source's names."""
    if "step_hlo" not in result.window:
        prog = _lm_program()
        if prog and run.trace.reduction is not None:
            import jax
            jax.clear_caches()
        result.window["step_hlo"] = prog[0].hlo_text() if prog else None
    return result.window["step_hlo"]


def by_phase_and_scope(run, result):
    """:func:`reduce_ops` of this run's traced window, in milliseconds per
    step, once per run; logs the whole table.  None without a device trace
    or without a program that names its instructions."""
    if "scope_reduction" in result.window:
        return result.window["scope_reduction"]
    result.window["scope_reduction"] = None
    red, prog = run.trace.reduction, _lm_program()
    calls = result.window.get("calls")
    if red is None or prog is None or not calls:
        return None
    _, scopes, scope_table = prog
    text = step_hlo(run, result)
    table = scope_table(text)
    out = reduce_ops(red["ops"], table,
                     {n: v[0] for n, v in instructions(text).items()},
                     scopes, calls)
    ms = {p: {k: round(v * 1e3, 4) for k, v in sorted(cell.items())}
          for p, cell in out["phases"].items()}
    log("device ms per step by pass and scope (self time, first chip): "
        + json.dumps(ms))
    log(f"device ms per step: busy {out['busy_s'] * 1e3:.3f}, in collectives "
        f"{out['collective_s'] * 1e3:.3f}, in operations the step's text "
        f"does not name {out['unnamed_s'] * 1e3:.3f}; "
        f"{len(table)} instructions named; largest unscoped [key, ms, "
        "op_name]: " + json.dumps([[k, round(s * 1e3, 4), o]
                                   for k, s, o in out["largest_unscoped"]]))
    result.window["scope_reduction"] = out
    return out


def phase_ms(run, result, phase: str):
    out = by_phase_and_scope(run, result)
    return None if out is None else 1e3 * sum(out["phases"][phase].values())


def scope_ms(run, result, *scopes: str):
    out = by_phase_and_scope(run, result)
    return None if out is None else 1e3 * sum(
        cell.get(s, 0.0) for cell in out["phases"].values() for s in scopes)


def dispatch_spans_ms(run, name: str, **labels):
    """Median ``dur`` in ms of the program's ``name`` spans (with these
    labels) that STARTED inside the measured window: ``t0`` is on the
    clock of ``run.t_process`` and ``run.setup_s``.  None if there is
    none (a program whose spans have no ``t0``, or obs switched off)."""
    from distlearn_tpu import obs
    if run.setup_s is None:
        return None
    start = run.t_process + run.setup_s
    durs = [s["dur"] for s in obs.spans()
            if s["name"] == name and s.get("t0", -1.0) >= start
            and all(s.get("labels", {}).get(k) == v
                    for k, v in labels.items())]
    return statistics.median(durs) * 1e3 if durs else None
