"""Persistent XLA compilation cache, placed from outside.

A fresh process pays the full XLA compile of every train / tick / prefill
program on its first dispatch — tens of seconds on the chip.  The
persistent cache turns a warm start into a deserialize.  One rule, for
every entry point:

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment — JAX reads it
  itself; this module sets NO directory in code, so whoever launches the
  program (a runner that keeps a cache between calls, a CI job) decides
  where it lives.
* unset — the fixed ``<checkout>/.jax_cache`` (git-ignored).  Never a
  temp name, pid or timestamp: the path is part of the cache key, so a
  directory that moves never hits.

Only ENTRY POINTS call :func:`enable_compile_cache` (the examples'
``setup_platform``, ``chip_smoke.py``, ``benchmarks/run.py``).  Library
code never does: a constructor must not reconfigure process-global JAX
state, and tests that count compiles must not depend on a cache on disk.

Set-up seen from inside.  JAX reports every trace, lowering and backend
compile it makes through ``jax.monitoring``; :func:`watch_compiles` (which
:func:`enable_compile_cache` calls) turns each into a span of the ``obs``
ring, under the program's name, on the ring's ``perf_counter`` clock:

* ``jit.trace{fun=}`` — Python tracing of ``fun`` to a jaxpr.  JAX reports
  one for every jitted function traced INSIDE another's trace too (the
  ``jnp`` helpers a step calls), so these nest: take their union, not
  their sum.  A trace shorter than :data:`TRACE_FLOOR_S` leaves no span.
* ``jit.lower{fun=}`` — that jaxpr made an MLIR module.
* ``jit.compile{fun=, cache=hit|miss|off}`` — the backend's turn: with
  ``hit`` the executable was read from the persistent cache, deserialised
  and loaded onto the device; with ``miss`` it was compiled and written;
  ``off``: no persistent cache took part.

``enable_compile_cache`` also records the mark ``process.ready`` (a span of
length 0).  WHERE that is, is the entry point's decision: each calls it once
it holds its devices and before its first compile, so what lies before the
mark is the interpreter, the imports and JAX reaching the chip.
The listeners stay for the life of the process: a program that compiles in
the middle of training is a ``jit.compile`` span with its name.
:func:`programs` reads the ring back as one row a program,
:func:`jit_seconds` and :func:`compiles` as totals for a phase.
"""

from __future__ import annotations

import os
import re
import threading

from distlearn_tpu import obs

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compile cache; returns the directory in
    effect.  Call before the first compile (the cache latches on/off
    there) and AFTER the entry point holds its devices: the call leaves the
    mark ``process.ready``, and what the process did before it is read as
    reaching them."""
    import jax
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # persist every program, however fast the compile or small the entry
    # (1, not 0: the cache reads 0 as "unset" and substitutes its default)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 1)
    watch_compiles()
    obs.record_span("process.ready", 0.0)
    return jax.config.jax_compilation_cache_dir


# ------------------------------------------------ compiles as obs spans --

_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "jit.trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
           "/jax/core/compile/backend_compile_duration": "jit.compile"}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_WRAPPED = re.compile(r"^\w+\((.*)\)$")

#: a ``jit.trace`` shorter than this leaves no span.  JAX reports a trace for
#: every ``add`` and ``multiply`` a traced function calls, at every call site
#: — thousands a set-up, tens of microseconds each — and they would push the
#: set-up's own spans out of the ring (4096).  What is dropped lies inside an
#: enclosing trace's span, or is under a millisecond of an eager call.
TRACE_FLOOR_S = 1e-3

#: span name -> its column of :func:`programs`
_COLUMNS = {"jit.trace": "trace_s", "jit.lower": "lower_s",
            "jit.compile": "compile_s"}

_watching = False
_tls = threading.local()


def _compile_counter():
    return obs.counter(
        "jit_compile_total", "programs the backend compiled or loaded, by "
        "what the persistent compile cache did (hit: read and loaded; miss: "
        "compiled and written; off: no persistent cache took part)",
        labels=("cache",))


def _on_event(name, **_):
    # fires inside the backend-compile interval, on the compiling thread
    cache = _CACHE_EVENTS.get(name)
    if cache is not None:
        _tls.cache = cache


def _on_duration(name, secs, fun_name="", **_):
    span = _EVENTS.get(name)
    if span is None or (span == "jit.trace" and secs < TRACE_FLOOR_S):
        return
    # the module's name is ``jit(my_step)``, the traced function's
    # ``my_step``: one name for the three spans of a program
    wrapped = _WRAPPED.match(str(fun_name))
    labels = {"fun": wrapped.group(1) if wrapped else str(fun_name)}
    if span == "jit.compile":
        labels["cache"], _tls.cache = getattr(_tls, "cache", "off"), "off"
        _compile_counter().labels(cache=labels["cache"]).inc()
    obs.record_span(span, secs, **labels)


def watch_compiles() -> bool:
    """Record every JAX trace, lowering and backend compile of this
    process as an ``obs`` span (module docstring) and count the compiles
    in ``jit_compile_total{cache=}``.  Registers one duration listener and
    one event listener with ``jax.monitoring``, once a process however
    often it is called; with the ``obs`` kill switch off nothing is
    registered.  Returns whether the listeners are in place."""
    global _watching
    if obs.enabled() and not _watching:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        for cache in ("hit", "miss", "off"):
            _compile_counter().labels(cache=cache)
        _watching = True
    return _watching


def programs(spans=None) -> list[dict]:
    """One row a program from the ``jit.*`` spans of ``spans`` (default:
    the ring): ``{"fun", "trace_s", "lower_s", "compile_s", "cache",
    "count"}``, dearest first.  ``count`` is the number of backend compiles
    under that name (0: the function was only traced, inside another's
    trace) and ``cache`` what the persistent cache did for them (``hit``,
    ``miss``, ``off``; ``hit/miss`` where they differ).  A row's
    ``trace_s`` holds what was traced inside it, so the rows' sum counts
    nested tracing twice.  What to print after the first step.

    JAX reports a function's bare ``__name__`` and nothing else, so
    functions that share one share a row: every ``lambda`` handed to
    ``jax.jit`` is ``<lambda>``, every Pallas kernel body ``wrapped``.  A
    recompile in the middle of a run is found by its span's ``t0``; give a
    program you want to find by name a ``def`` of its own."""
    rows: dict[str, dict] = {}
    for s in obs.spans() if spans is None else spans:
        if s["name"] not in _COLUMNS:
            continue
        labels = s.get("labels", {})
        row = rows.setdefault(labels.get("fun", ""), {
            "fun": labels.get("fun", ""), "trace_s": 0.0, "lower_s": 0.0,
            "compile_s": 0.0, "cache": set(), "count": 0})
        row[_COLUMNS[s["name"]]] += s["dur"]
        if s["name"] == "jit.compile":
            row["cache"].add(labels.get("cache", "off"))
            row["count"] += 1
    for row in rows.values():
        row["cache"] = "/".join(sorted(row["cache"]))
    return sorted(rows.values(), key=lambda r: -(
        r["trace_s"] + r["lower_s"] + r["compile_s"]))


def jit_seconds(since: float) -> float:
    """Seconds under a ``jit.*`` span that began at or after ``since`` (a
    ``perf_counter`` reading), overlaps counted once: what a phase of the
    process spent tracing, lowering, fetching and compiling, on whatever
    thread.  From the ring, so a phase that leaves more than the ring
    holds (4096 spans) reads low."""
    total, covered = 0.0, since
    for t0, dur in sorted((s["t0"], s["dur"]) for s in obs.spans()
                          if s["name"] in _COLUMNS and s["t0"] >= since):
        if t0 + dur > covered:
            total += t0 + dur - max(t0, covered)
            covered = t0 + dur
    return total


def compiles() -> dict:
    """``jit_compile_total`` read back, ``{"hit": n, "miss": n, "off": n}``;
    empty while nothing watches or the ``obs`` switch is off."""
    if not (_watching and obs.enabled()):
        return {}
    return {cache: _compile_counter().labels(cache=cache).value
            for cache in ("hit", "miss", "off")}
