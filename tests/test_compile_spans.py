"""Set-up seen from inside the program: ``utils.compile_cache.watch_compiles``
turns JAX's own trace / lower / backend-compile events into ``obs`` spans
under the program's name, ``enable_compile_cache`` leaves the mark
``process.ready``, the step shim puts the call that compiles under
``train.first_call``; ``jit_seconds`` and ``compiles`` read a phase back, as
``chip_smoke.measure`` does.  Counts and names only: no time of these runs
is recorded anywhere."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlearn_tpu import obs
from distlearn_tpu.obs import core, trace
from distlearn_tpu.utils import compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JIT = ("jit.trace", "jit.lower", "jit.compile")
#: JAX times its events on the wall clock and the span is laid on
#: ``perf_counter`` when the event arrives: a child may stick out of its
#: parent by the listeners' own latency
SLACK = 2e-3


def _listeners():
    from jax._src import monitoring
    return (monitoring.get_event_duration_listeners().count(
                compile_cache._on_duration),
            monitoring.get_event_listeners().count(compile_cache._on_event))


@pytest.fixture(autouse=True)
def _leave_the_ring_empty():
    """Other tests of the suite expect to find it so."""
    yield
    trace.clear()


@pytest.fixture
def watching(monkeypatch):
    """The listeners in place for one test, and gone after it: the suite's
    other tests compile too, and read the ring."""
    core.configure(True)
    monkeypatch.setattr(compile_cache, "_watching", False)
    assert compile_cache.watch_compiles() is True
    trace.clear()
    yield
    jax.monitoring.unregister_event_duration_listener(
        compile_cache._on_duration)
    jax.monitoring.unregister_event_listener(compile_cache._on_event)
    core.configure(None)


def _jit_spans(since=0):
    return [s for s in obs.spans()[since:] if s["name"] in JIT]


def _inside(child, parent):
    return (parent["t0"] - SLACK <= child["t0"]
            and child["t0"] + child["dur"]
            <= parent["t0"] + parent["dur"] + SLACK)


def test_a_first_call_leaves_three_spans_under_one_name(watching):
    @jax.jit
    def long_helper(x):
        for _ in range(200):                    # many ms of Python to trace
            x = x * 1.0001 + 1.0
        return x

    @jax.jit
    def my_step(x, w):
        return jnp.sum(jnp.matmul(long_helper(x), w))

    x = jnp.ones((4, 4), jnp.float32)
    trace.clear()
    my_step(x, x)
    mine = [s for s in _jit_spans() if s["labels"]["fun"] == "my_step"]
    assert [s["name"] for s in mine] == list(JIT)
    assert mine[2]["labels"] == {"fun": "my_step", "cache": "off"}
    assert all(s["dur"] > 0 and s["t0"] > 0 for s in mine)
    # a jitted function it calls is traced INSIDE its own trace, and named
    nested, = [s for s in _jit_spans() if s["labels"]["fun"] == "long_helper"]
    assert nested["name"] == "jit.trace" and _inside(nested, mine[0])
    assert nested["dur"] >= compile_cache.TRACE_FLOOR_S
    # ... but not the 400 jnp helpers under a millisecond each that JAX
    # reports beside it (every ``multiply``, every ``add``)
    traced = [s for s in _jit_spans() if s["name"] == "jit.trace"]
    assert all(s["dur"] >= compile_cache.TRACE_FLOOR_S for s in traced)
    assert len(traced) < 20
    # lower follows trace, compile follows lower
    assert mine[0]["t0"] < mine[1]["t0"] < mine[2]["t0"]

    seen = len(obs.spans())
    my_step(x, x)                               # from memory: nothing
    assert _jit_spans(seen) == []

    y = jnp.ones((8, 8), jnp.float32)
    jax.block_until_ready(y)
    seen = len(obs.spans())
    my_step(y, y)                               # a new shape: named again
    again = [s["name"] for s in _jit_spans(seen)
             if s["labels"]["fun"] == "my_step"]
    assert again == list(JIT)


def test_programs_is_one_row_a_name(watching):
    @jax.jit
    def doubled(x):
        return jnp.sum(x * 2.0)

    doubled(jnp.ones((3,), jnp.float32))
    doubled(jnp.ones((5,), jnp.float32))
    rows = {r["fun"]: r for r in compile_cache.programs()}
    row = rows["doubled"]
    assert row["count"] == 2 and row["cache"] == "off"
    assert row["lower_s"] > 0 and row["compile_s"] > 0
    # dearest first
    totals = [r["trace_s"] + r["lower_s"] + r["compile_s"]
              for r in compile_cache.programs()]
    assert totals == sorted(totals, reverse=True)
    # a hand-made list: sums by name, mixed cache labels, a traced-only name
    spans = [
        {"name": "jit.trace", "t0": 1.0, "dur": 0.5, "labels": {"fun": "f"}},
        {"name": "jit.trace", "t0": 1.1, "dur": 0.2, "labels": {"fun": "g"}},
        {"name": "jit.lower", "t0": 1.5, "dur": 0.25, "labels": {"fun": "f"}},
        {"name": "jit.compile", "t0": 2.0, "dur": 1.0,
         "labels": {"fun": "f", "cache": "hit"}},
        {"name": "jit.compile", "t0": 4.0, "dur": 2.0,
         "labels": {"fun": "f", "cache": "miss"}},
        {"name": "train.dispatch", "t0": 5.0, "dur": 9.0,
         "labels": {"step": "lm"}}]
    assert compile_cache.programs(spans) == [
        {"fun": "f", "trace_s": 0.5, "lower_s": 0.25, "compile_s": 3.0,
         "cache": "hit/miss", "count": 2},
        {"fun": "g", "trace_s": 0.2, "lower_s": 0.0, "compile_s": 0.0,
         "cache": "", "count": 0}]


def test_the_counter_has_three_children(watching):
    @jax.jit
    def counted(x):
        return x + 1

    fam = core.REGISTRY.counter("jit_compile_total", labels=("cache",))
    before = fam.labels(cache="off").value
    counted(jnp.ones((2,), jnp.float32))
    assert fam.labels(cache="off").value >= before + 1
    text = core.REGISTRY.render_prometheus()
    for cache in ("hit", "miss", "off"):
        assert f'jit_compile_total{{cache="{cache}"}}' in text


def test_watching_twice_registers_once(watching):
    assert _listeners() == (1, 1)
    assert compile_cache.watch_compiles() is True
    assert _listeners() == (1, 1)


def test_nothing_is_registered_with_obs_off(monkeypatch):
    monkeypatch.setattr(compile_cache, "_watching", False)
    before = _listeners()
    core.configure(False)
    try:
        assert compile_cache.watch_compiles() is False
        assert _listeners() == before
    finally:
        core.configure(None)


# ------------------------------------------------ the persistent cache --

_PROBE = """
import json, sys
import jax, jax.numpy as jnp
from distlearn_tpu import obs
from distlearn_tpu.utils import compile_cache as cc
cc.enable_compile_cache()

@jax.jit
def probe_program(x):
    return jnp.tanh(x) @ x

probe_program(jnp.ones((16, 16), jnp.float32))
print(json.dumps({
    "spans": [[s["name"], s.get("labels", {})] for s in obs.spans()
              if s["name"] in ("jit.compile", "process.ready")],
    "ready": [[s["t0"], s["dur"], s["ts"]] for s in obs.spans()
              if s["name"] == "process.ready"],
    "programs": [r for r in cc.programs() if r["fun"] == "probe_program"],
    "counter": {s["labels"]["cache"]: s["value"]
                for f in obs.REGISTRY.snapshot()
                if f["name"] == "jit_compile_total" for s in f["samples"]}}))
"""


def _probe(cache_dir, **env):
    """``enable_compile_cache()`` and one program in a fresh interpreter (it
    reconfigures process-global jax state — never in the suite's own
    process), against ``cache_dir``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT,
               JAX_COMPILATION_CACHE_DIR=str(cache_dir), **env)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd="/",
                         capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_hit_and_miss_are_labelled_against_a_cache_on_disk(tmp_path):
    cold = _probe(tmp_path / "xla")
    # the mark comes first: a span of length 0 on both clocks
    assert cold["spans"][0] == ["process.ready", {}]
    (t0, dur, ts), = cold["ready"]
    assert dur == 0.0 and t0 > 0 and ts > 1e9
    mine = [lab for name, lab in cold["spans"]
            if lab.get("fun") == "probe_program"]
    assert mine == [{"fun": "probe_program", "cache": "miss"}]
    assert {lab["cache"] for name, lab in cold["spans"][1:]} == {"miss"}
    assert cold["counter"]["miss"] == len(cold["spans"]) - 1
    assert cold["counter"]["hit"] == cold["counter"]["off"] == 0

    warm = _probe(tmp_path / "xla")
    assert {lab["cache"] for name, lab in warm["spans"][1:]} == {"hit"}
    row, = warm["programs"]
    assert row["cache"] == "hit" and row["count"] == 1
    assert warm["counter"]["hit"] == len(warm["spans"]) - 1
    assert warm["counter"]["miss"] == 0


def test_the_kill_switch_leaves_no_span_and_no_listener(tmp_path):
    rec = _probe(tmp_path / "xla", DISTLEARN_OBS="0")
    assert rec == {"spans": [], "ready": [], "programs": [], "counter": {}}


# ------------------------------------------------------ the step's shim --

def _toy_lm_step():
    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.train.lm import build_lm_step
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                ("data", "seq", "model"))
    model = transformer_lm(vocab=61, dim=16, depth=2, heads=2, max_len=8)
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 61, jnp.int32),
        NamedSharding(mesh, P("data", "seq")))
    step = build_lm_step(model, mesh, params, lr=0.1, donate=False)
    return step, params, tokens


def test_first_call_is_call_zero_only_and_holds_the_steps_jit_spans(watching):
    trace.clear()
    step, params, tokens = _toy_lm_step()
    for _ in range(3):
        params, loss = step(params, tokens)
    jax.block_until_ready(loss)
    by_name = {}
    for s in obs.spans():
        by_name.setdefault(s["name"], []).append(s)
    first, = by_name["train.first_call"]
    assert first["labels"] == {"step": "lm"}
    dispatches = by_name["train.dispatch"]
    assert len(dispatches) == 3
    # call 0's dispatch lies inside it, the later ones after it
    assert _inside(dispatches[0], first)
    assert all(d["t0"] >= first["t0"] + first["dur"] for d in dispatches[1:])
    # the step program's own trace, lower and compile lie inside it (call
    # 1 meets parameters placed by call 0 and compiles once more, outside)
    mine = [s for s in _jit_spans() if s["labels"]["fun"] == "step"
            and s["t0"] < first["t0"] + first["dur"]]
    assert [s["name"] for s in mine] == list(JIT)
    assert all(_inside(s, first) for s in mine)
    assert sum(s["dur"] for s in mine) <= first["dur"] + 3 * SLACK


def test_the_histogram_observes_the_spans_own_duration(watching):
    step, params, tokens = _toy_lm_step()
    trace.clear()
    count, total = step._h.count, step._h.sum
    for _ in range(4):
        params, loss = step(params, tokens)
    jax.block_until_ready(loss)
    durs = [s["dur"] for s in obs.spans() if s["name"] == "train.dispatch"]
    assert step._h.count == count + 4 == count + len(durs)
    assert step._h.sum - total == pytest.approx(sum(durs), rel=1e-12)


def test_the_histogram_is_left_alone_once_the_switch_is_off(watching):
    """A step built with the switch on and called with it off times
    nothing: its histogram gains no observation, and no zero."""
    step, params, tokens = _toy_lm_step()
    params, loss = step(params, tokens)
    count, total = step._h.count, step._h.sum
    core.configure(False)
    params, loss = step(params, tokens)
    jax.block_until_ready(loss)
    assert (step._h.count, step._h.sum) == (count, total)


# ------------------------------------------------- a phase, read back --

_RING = [{"name": "jit.trace", "t0": 10.0, "dur": 4.0},
         {"name": "jit.trace", "t0": 11.0, "dur": 1.0},      # nested
         {"name": "jit.lower", "t0": 14.0, "dur": 1.0},
         {"name": "train.dispatch", "t0": 15.0, "dur": 9.0},  # no jit span
         {"name": "jit.compile", "t0": 13.5, "dur": 3.0},     # overlaps both
         {"name": "jit.compile", "t0": 20.0, "dur": 0.5}]


@pytest.mark.parametrize("since, want", [
    (0.0, 7.0),          # [10, 16.5] once, and [20, 20.5]
    (10.5, 4.5),         # the outer trace began before: 1 + 3 + 0.5
    (13.75, 1.5),        # only the lower [14, 15] and the last compile
    (21.0, 0.0)])
def test_jit_seconds_is_a_union_of_the_spans_begun_since(monkeypatch, since,
                                                         want):
    monkeypatch.setattr(compile_cache.obs, "spans", lambda: list(_RING))
    assert compile_cache.jit_seconds(since) == pytest.approx(want)


def test_compiles_reads_the_counter_back(watching, monkeypatch):
    before = compile_cache.compiles()
    assert set(before) == {"hit", "miss", "off"}
    jax.jit(lambda x: x * 3.0)(jnp.ones((7,), jnp.float32))
    after = compile_cache.compiles()
    assert after["off"] - before["off"] >= 1
    assert (after["hit"], after["miss"]) == (before["hit"], before["miss"])
    monkeypatch.setattr(compile_cache, "_watching", False)
    assert compile_cache.compiles() == {}


def test_chip_smoke_reads_its_phases_from_the_programs_spans(watching,
                                                             monkeypatch):
    """``chip_smoke.measure`` keeps no listeners of its own: a phase's
    set-up is what ``watch_compiles`` recorded while it ran."""
    sys.path.insert(0, _ROOT)
    import chip_smoke

    def phase():
        @jax.jit
        def phase_program(x):
            return jnp.sum(x * x)
        return {"loss": float(phase_program(jnp.ones((9,), jnp.float32)))}

    listeners = _listeners()
    res = chip_smoke.measure(phase)
    assert _listeners() == listeners == (1, 1)
    assert res["loss"] == 9.0 and res["programs"] >= 1
    assert (res["cache_hits"], res["cache_misses"]) == (0, 0)
    assert 0 < res["setup_s"] + 0.01 and res["setup_s"] <= res["wall_s"] + 0.01
    assert res["run_s"] == pytest.approx(res["wall_s"] - res["setup_s"],
                                         abs=0.011)
    monkeypatch.setattr(compile_cache, "_watching", False)
    assert set(chip_smoke.measure(phase)) == {"loss", "wall_s"}


@pytest.mark.parametrize("tpu", [False, True])
def test_an_example_marks_ready_once_it_has_its_platform(monkeypatch, tpu):
    """``examples/common.py::setup_platform`` enables the cache — and so
    leaves ``process.ready`` — LAST: after the CPU is pinned, or after
    ``jax.devices()`` gave it the chip."""
    sys.path.insert(0, os.path.join(_ROOT, "examples"))
    import common
    from distlearn_tpu.utils import platform
    order = []
    monkeypatch.setattr(platform, "force_cpu",
                        lambda n: order.append(("pin", n)))
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: order.append("ready"))

    class _Chip:
        platform = "tpu"
    monkeypatch.setattr(jax, "devices",
                        lambda: order.append("devices") or [_Chip()])
    common.setup_platform(3, tpu)
    assert order == (["devices", "ready"] if tpu else [("pin", 3), "ready"])
