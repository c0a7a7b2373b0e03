"""Set-up split by what the PROGRAM says it was doing: the ``obs`` spans that
``distlearn_tpu.utils.compile_cache.watch_compiles`` records for every JAX
trace, lowering and backend compile (``jit.trace`` / ``jit.lower`` /
``jit.compile{cache=}``, each under its program's ``fun``), the mark
``process.ready`` its ``enable_compile_cache`` leaves, and the step shim's
``train.first_call{step=}`` — all on the ``perf_counter`` clock of
``run.t_process`` and ``run.setup_s``.

S = the spans that lie inside this run's set-up.  Five numbers come out of
it, and ``reach_s + jit_s + run_s == setup_s`` by construction:

* ``reach_s``  — process start to ``process.ready``: interpreter, imports,
  JAX reaching the chip;
* ``trace_s``  — UNION of the ``jit.trace`` and ``jit.lower`` intervals:
  host Python that no persistent cache saves (a union: JAX reports a trace
  for every jitted function traced inside another's trace, so a sum counts
  nested tracing twice; the program keeps a span of those that took a
  millisecond or more);
* ``fetch_s``  — union of the ``jit.compile{cache=hit}`` intervals:
  executables read, deserialised and loaded;
* ``step_s``   — the first ``train.first_call{step=lm}``, whole;
* ``run_s``    — ``setup_s - reach_s - jit_s`` with ``jit_s`` the union of
  ALL ``jit.*`` intervals: set-up seconds in which nothing was being
  traced, lowered, fetched or compiled.

Pure functions on a span list first (tested with hand counts), then the ones
that read a run.  A program without these spans (the parent of the PR that
added them, ``DISTLEARN_OBS=0``, a run no entry point marked) gives ``None``
everywhere: a reader then leaves its metric out — never 0, never below it.
"""

from __future__ import annotations

import json

from harness import log

JIT = ("jit.trace", "jit.lower", "jit.compile")


def union_s(intervals) -> float:
    """Seconds covered by ``[(start, end), ...]``, overlaps counted once."""
    total, covered = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > covered:
            total += end - max(start, covered)
            covered = end
    return total


def within(spans, t_process: float, setup_s: float) -> list[dict]:
    """S: the spans whose whole interval lies inside the set-up."""
    end = t_process + setup_s
    return [s for s in spans
            if t_process <= s.get("t0", float("-inf"))
            and s["t0"] + s["dur"] <= end]


def _interval(s):
    return s["t0"], s["t0"] + s["dur"]


def _label(s, key):
    return s.get("labels", {}).get(key)


def _positive(x):
    return x if x > 0 else None


def nested_by_name(spans) -> dict:
    """``{fun: seconds}`` of the ``jit.*`` spans that lie inside an earlier
    one's interval: what a SUM of the spans counts a second time."""
    out, covered = {}, float("-inf")
    for s in sorted((s for s in spans if s["name"] in JIT),
                    key=lambda s: (s["t0"], -s["dur"])):
        start, end = _interval(s)
        if end <= covered:
            fun = _label(s, "fun") or ""
            out[fun] = out.get(fun, 0.0) + s["dur"]
        covered = max(covered, end)
    return out


def split(spans, t_process: float, setup_s: float):
    """The five parts of ``setup_s`` (module docstring) from a span list,
    with ``jit_s``, ``compiled_s`` (union of the ``jit.compile`` intervals
    that were no hit), ``misses`` (their programs by name) and ``spans``
    (S itself); a part that has nothing to read, or comes out at or under
    0, is None.  None altogether without a ``process.ready`` in S."""
    inside = within(spans, t_process, setup_s)
    ready = [s["t0"] for s in inside if s["name"] == "process.ready"]
    if not ready:
        return None
    jit = [s for s in inside if s["name"] in JIT]
    compiles = [s for s in jit if s["name"] == "jit.compile"]
    hits = [s for s in compiles if _label(s, "cache") == "hit"]
    misses = [s for s in compiles if _label(s, "cache") != "hit"]
    first = sorted((s for s in inside if s["name"] == "train.first_call"
                    and _label(s, "step") == "lm"), key=lambda s: s["t0"])
    reach = min(ready) - t_process
    jit_s = union_s(map(_interval, jit))
    return {
        "reach_s": _positive(reach),
        "trace_s": _positive(union_s(
            _interval(s) for s in jit if s["name"] != "jit.compile")),
        "fetch_s": _positive(union_s(map(_interval, hits))),
        "step_s": _positive(first[0]["dur"]) if first else None,
        "run_s": _positive(setup_s - reach - jit_s) if reach > 0 else None,
        "jit_s": jit_s,
        "compiled_s": union_s(map(_interval, misses)),
        "misses": [[_label(s, "fun"), _label(s, "cache"), s["dur"]]
                   for s in misses],
        "spans": inside}


def _evicted_since(ring, t_process: float) -> bool:
    """Whether the ring has lost a span of this run: it has evicted records
    (``obs_spans_dropped_total``) and no longer reaches back to before the
    run began, so the evicted ones may be the run's."""
    from distlearn_tpu import obs
    dropped = sum(s["value"] for fam in obs.REGISTRY.snapshot()
                  if fam["name"] == "obs_spans_dropped_total"
                  for s in fam["samples"])
    return bool(dropped) and not (
        ring and ring[0].get("t0", t_process) + ring[0]["dur"] < t_process)


def of_run(run, result):
    """:func:`split` of this run's set-up from the program's span ring, once
    per run (kept in ``result.window``); None where the run has no set-up
    yet, the program records no spans, or the ring lost some of the run's."""
    if "setup_split" not in result.window:
        from distlearn_tpu import obs
        ring, parts = obs.spans(), None
        if run.setup_s is not None and _evicted_since(ring, run.t_process):
            log("the span ring has evicted records of this run "
                "(obs_spans_dropped_total): the set-up readers give nothing")
        elif run.setup_s is not None:
            parts = split(ring, run.t_process, run.setup_s)
        result.window["setup_split"] = parts
    return result.window["setup_split"]


def part(run, result, key: str):
    """One part of the split, or None."""
    parts = of_run(run, result)
    return parts and parts[key]


def log_programs(run, result, top: int = 12):
    """Earlier lines of the run: the set-up's programs by seconds
    (``compile_cache.programs`` of S), its compile misses by name, the
    identity ``reach + jit + run = setup_s``, what compiled AFTER set-up
    (a traced run compiles its step once more for its names), and
    ``compile_s`` — the SUM of JAX's trace, lower and compile events — beside
    the unions, with what the sum counts twice by name."""
    parts = of_run(run, result)
    if parts is None:
        return
    from distlearn_tpu import obs
    from distlearn_tpu.utils import compile_cache
    rows = compile_cache.programs(parts["spans"])
    log(f"set-up programs, {len(rows)} names in {len(parts['spans'])} spans "
        f"of the ring's {len(obs.spans())}, dearest {top} [fun, trace s, "
        "lower s, compile s, cache, compiles]: " + json.dumps(
            [[r["fun"], round(r["trace_s"], 3), round(r["lower_s"], 3),
              round(r["compile_s"], 3), r["cache"], r["count"]]
             for r in rows[:top]]))
    log("set-up compile misses [fun, cache, s]: " + json.dumps(
        [[f, c, round(d, 3)] for f, c, d in parts["misses"]]))
    zero = lambda x: x or 0.0
    log(f"set-up {run.setup_s:.3f}s = reach {zero(parts['reach_s']):.3f} + "
        f"jit {parts['jit_s']:.3f} (trace+lower {zero(parts['trace_s']):.3f},"
        f" fetch {zero(parts['fetch_s']):.3f}, compiled "
        f"{parts['compiled_s']:.3f}) + run {zero(parts['run_s']):.3f}; the "
        f"step's first call {zero(parts['step_s']):.3f}")
    opened = run.t_process + run.setup_s
    log("compiled after set-up, outside every set-up metric [fun, cache, s, "
        "s after the window opened]: " + json.dumps(
            [[_label(s, "fun"), _label(s, "cache"), round(s["dur"], 3),
              round(s["t0"] - opened, 1)] for s in obs.spans()
             if s["name"] == "jit.compile" and s["t0"] >= opened]))
    if run.setup_meter:
        unions = zero(parts["trace_s"]) + zero(parts["fetch_s"]) \
            + parts["compiled_s"]
        nested = sorted(nested_by_name(parts["spans"]).items(),
                        key=lambda kv: -kv[1])
        log(f"compile_s {run.setup_meter['compile_s']:.3f} (a sum) against "
            f"the unions {unions:.3f}: {run.setup_meter['compile_s'] - unions:.3f}"
            f" counted twice; of it {sum(v for _, v in nested):.3f}s in spans "
            "inside another's interval (the rest in nested traces too short "
            "to leave a span), by name: " + json.dumps(
                [[f, round(v, 3)] for f, v in nested[:top]]))
