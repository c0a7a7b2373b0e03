"""Device time by pass and by model part, under names that outlive a
refactor: the trace reducer's per-operation self times
(``run.trace.reduction["ops"]``: ``{"<instruction> <shape>": [self s, count,
whole s]}``) joined with what the PROGRAM says each instruction of its step
belongs to (``distlearn_tpu.utils.profiling.scope_table`` of the step's own
optimized HLO: JAX's ``op_name``, which carries the ``jax.named_scope`` of
``distlearn_tpu.models.core.SCOPES`` and JAX's own marks of the pass).

Pure functions on text and dicts first (tested on the CPU with hand counts),
then the ones that read a run.  A program that has no scopes, no catalog of
step programs or no ``train.dispatch`` span (the parent of the PR that added
them) gives ``None`` everywhere: a reader then leaves its metric out.
"""

from __future__ import annotations

import functools
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


def components(op_name: str) -> list[str]:
    """The ``/`` components of ``op_name`` seen through JAX's transform
    wrappers (``transpose(jvp(attn_core))`` is ``attn_core``) but not
    through ``jit(...)``, which names a function and not a scope (it reads
    as the empty string)."""
    out = []
    for part in op_name.split("/"):
        while (m := _WRAPPED.match(part)):
            if m.group(1) in ("jit", "pjit"):
                part = ""
                break
            part = m.group(2)
        out.append(part)
    return out


def scope_of(op_name: str, scopes) -> str:
    """The declared scope among the components of ``op_name`` (the
    innermost, should two nest); ``UNSCOPED`` if there is none."""
    found = UNSCOPED
    for part in components(op_name):
        if part in scopes:
            found = part
    return found


@functools.lru_cache(maxsize=2)      # a run's readers all ask about one text
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


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_CALLEE = re.compile(
    r"\b(body|condition|calls|to_apply|true_computation|false_computation|"
    r"branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_SCALAR = re.compile(r"^\s*[su]\d+\[\]\S*\s+constant\((-?\d+)\)")
_LESS = re.compile(
    r"\scompare\(%?([\w.\-]+),\s*%?([\w.\-]+)\),\s*direction=LT")
_INDEX = re.compile(r"\sget-tuple-element\(.*\),\s*index=(\d+)")


def _loop_trips(rests: dict, roots: dict, ins: dict, rest: str):
    """How often a ``while`` (its text after ``=``) runs its body: the
    compiler's own ``known_trip_count`` where the text has it (the CPU's),
    else read from the loop itself (the TPU compiler writes none): a
    condition ``counter < N`` on a counter that starts at a constant ``c``
    gives N - c — a counter that steps by one, as every ``lax.scan`` and
    ``fori_loop`` has it.  None where neither can be read."""
    known = _TRIPS.search(rest)
    if known:
        return int(known.group(1))
    cond = re.search(r"\bcondition=%?([\w.\-]+)", rest)
    init = re.search(r"\swhile\(%?([\w.\-]+)\)", rest)
    less = cond and _LESS.search(rests.get(roots.get(cond.group(1)), ""))
    if not (less and init):
        return None
    counter, bound = (rests.get(n, "") for n in less.groups())
    index, n = _INDEX.search(counter), _SCALAR.match(bound)
    start = ins.get(init.group(1), ("", None, []))[2]
    if not (index and n) or int(index.group(1)) >= len(start):
        return None
    start = start[int(index.group(1))]
    for _ in range(4):              # through the copies a compiler puts in
        if ins.get(start, ("",))[0] not in ("copy", "bitcast", "convert"):
            break
        start = ins[start][2][0]
    c = _SCALAR.match(rests.get(start, ""))
    return max(0, int(n.group(1)) - int(c.group(1))) if c else None


@functools.lru_cache(maxsize=2)
def structure(hlo_text: str):
    """How the module's computations hang together: ``(computation of each
    instruction, {instruction: [(callee computation, times)]}, entry)``.
    ``times`` is how often one execution of the instruction runs the
    callee: a ``while``'s trip count for its body (:func:`_loop_trips`;
    None where it cannot be read), 1 for everything else."""
    where, rests, roots, entry, comp = {}, {}, {}, None, None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            if line.startswith("ENTRY"):
                entry = comp
            continue
        m = _DEF.match(line)
        if not m or comp is None:
            continue
        name, rest = m.groups()
        where[name], rests[name] = comp, rest
        if line.lstrip().startswith("ROOT"):
            roots[comp] = name
    ins = instructions(hlo_text)
    calls = {}
    for name, rest in rests.items():
        for attr, names in _CALLEE.findall(rest):
            for callee in re.findall(r"[\w.\-]+", names):
                times = _loop_trips(rests, roots, ins, rest) \
                    if attr == "body" else 1
                calls.setdefault(name, []).append((callee, times))
    return where, calls, entry


def executions(hlo_text: str) -> dict:
    """``{computation: how often one run of the module executes it}``: the
    entry once, a ``while`` body its loop's trip count times its loop's own
    executions, anything else as often as its callers.  A loop whose trip
    count cannot be read counts ONCE, and is logged."""
    where, calls, entry = structure(hlo_text)
    callers: dict[str, list] = {}
    unknown = []
    for name, callees in calls.items():
        for callee, times in callees:
            if times is None:
                unknown.append(name)
                times = 1
            callers.setdefault(callee, []).append((where[name], times))
    if unknown:
        log(f"{len(unknown)} loops with no known_trip_count and no "
            "condition it can be read from in the step's text, their bodies "
            f"count once: {unknown[:8]}")
    done: dict[str, int] = {}

    def runs(comp):
        if comp not in done:
            done[comp] = 0          # a cycle cannot be: HLO calls form a DAG
            done[comp] = 1 if comp == entry else sum(
                runs(c) * t for c, t in callers.get(comp, ()))
        return done[comp]
    return {comp: runs(comp) for comp in set(where.values())}


def collective_bytes(hlo_text: str) -> dict:
    """``{collective kind: bytes}`` one chip hands to the collective
    instructions of the module in ONE run of it: the bytes of an
    instruction's operands (sync or ``-start`` form; a ``-done`` hands over
    nothing new) times the trip count of every ``while`` it sits in
    (:func:`executions`)."""
    ins = instructions(hlo_text)
    where, _, _ = structure(hlo_text)
    runs = executions(hlo_text)
    total: dict[str, int] = {}
    for name, (opcode, _, operands) in ins.items():
        kind = collective_kind(opcode)
        if kind and not opcode.endswith("-done"):
            total[kind] = total.get(kind, 0) + runs.get(where.get(name), 1) \
                * sum(ins[o][1] or 0 for o in operands if o in ins)
    return total


def enclosed_by(hlo_text: str, names) -> set:
    """The instructions that run INSIDE one of ``names``: those of the
    computations an instruction of ``names`` calls (a ``while``'s body and
    condition, a call's callee), and of whatever those call."""
    where, calls, _ = structure(hlo_text)
    inside_of: dict[str, list] = {}
    for name, comp in where.items():
        inside_of.setdefault(comp, []).append(name)
    out, todo = set(), [c for n in names for c, _ in calls.get(n, ())]
    seen = set()
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name in inside_of.get(comp, ()):
            out.add(name)
            todo.extend(c for c, _ in calls.get(name, ()))
    return out


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
    """Milliseconds a step in ``phase``; None where the step has no such
    pass (a step that recomputes nothing) or nothing can be read."""
    out = by_phase_and_scope(run, result)
    return out and 1e3 * sum(out["phases"][phase].values()) or None


def scope_ms(run, result, *scopes: str):
    """Milliseconds a step under ``scopes``, all passes together; None where
    the step has none of them (a dense model has no ``moe``)."""
    out = by_phase_and_scope(run, result)
    return out and 1e3 * sum(
        cell.get(s, 0.0) for cell in out["phases"].values()
        for s in scopes) or None


def collective_ms(run, result):
    """Device milliseconds a step in collective instructions (self time,
    first chip); None where the traced step ran none."""
    out = by_phase_and_scope(run, result)
    return out["collective_s"] * 1e3 if out and out["collective_s"] else None


def inner_whole_s(run, result, inner: str):
    """Seconds a step the device spent in the instructions whose
    ``op_name`` holds the component ``inner`` (a ``jax.named_scope`` inside
    a declared scope: a kernel's own name), by their WHOLE duration
    (``ops[key][2]``): an instruction that encloses others so named (a
    ``while`` over chunks) is taken once and what runs inside it not again.
    None without a device trace, a program that names its instructions, or
    any instruction so named."""
    red, prog = run.trace.reduction, _lm_program()
    calls = result.window.get("calls")
    if red is None or prog is None or not calls:
        return None
    text = step_hlo(run, result)
    named = {name for name, op_name in prog[2](text).items()
             if inner in components(op_name)}
    named -= enclosed_by(text, named)
    whole = [v[2] for key, v in red["ops"].items()
             if key.split(" ", 1)[0] in named]
    if not whole:
        return None
    log(f"{inner}: {len(whole)} traced instructions, whole duration "
        f"{sum(whole) / calls * 1e3:.3f} ms a step")
    return sum(whole) / calls


def roofline_share(run, result, inner: str, cost):
    """Per cent of its roofline at which the kernel named ``inner`` ran:
    the least time the chip could take for one sample's ``cost`` =
    ``(operations, bytes)`` — the larger of operations over the matrix peak
    and bytes over the HBM peak — times the samples a chip does a step,
    over :func:`inner_whole_s`.  None where that is."""
    whole = inner_whole_s(run, result, inner)
    if not whole:
        return None
    ops, nbytes = cost
    by = {"operations": ops / run.peaks["bf16_flops_per_s"],
          "bytes": nbytes / run.peaks["hbm_bytes_per_s"]}
    bound = max(by, key=by.get)
    samples = result.window["samples"] / result.window["calls"] \
        / result.window["chips"]
    log(f"{inner} roofline: bound by {bound}, least "
        f"{by[bound] * samples * 1e3:.3f} ms a step of {whole * 1e3:.3f}")
    return 100.0 * by[bound] * samples / whole


def dispatch_spans_ms(run, name: str, **labels):
    """Median ``dur`` in ms of the program's ``name`` spans (with these
    labels) that STARTED inside the measured window (a call the kind makes
    after it is none of the window's): ``t0`` is on the clock of
    ``run.t_process``, ``run.setup_s`` and ``run.closed_s``.  None if there
    is none (a program whose spans have no ``t0``, or obs switched off)."""
    from distlearn_tpu import obs
    if run.setup_s is None:
        return None
    start = run.t_process + run.setup_s
    end = run.t_process + run.closed_s
    durs = [s["dur"] for s in obs.spans()
            if s["name"] == name and start <= s.get("t0", -1.0) <= end
            and all(s.get("labels", {}).get(k) == v
                    for k, v in labels.items())]
    return statistics.median(durs) * 1e3 if durs else None
