"""The five readers of set-up (``setup_spans.py`` and the ``layer_metrics``
that call it), on the CPU: hand counts on hand-made span lists, every reader
silent on a run no entry point marked, and the toy twin of every cell's kind
marked the way ``run.py`` marks a run.  Nothing here is a measurement."""

import copy
import time

import pytest

import jax

import manifest_rules as rules
from manifest_rules import bench_run, harness

import setup_spans  # noqa: E402  (benchmarks/ is on the path by now)

from distlearn_tpu import obs  # noqa: E402
from distlearn_tpu.obs import core, trace  # noqa: E402
from distlearn_tpu.utils import compile_cache  # noqa: E402

MAN = bench_run.manifest()
CELLS = {w["name"]: w for w in MAN["workloads"]}
FIVE = ("setup_reach_s", "setup_trace_s", "setup_fetch_s", "setup_step_s",
        "setup_run_s")


def _span(name, t0, dur, **labels):
    rec = {"type": "span", "name": name, "ts": 0.0, "t0": t0, "dur": dur}
    if labels:
        rec["labels"] = labels
    return rec


# a set-up of 20 s that began at t = 100: the mark at 104; a step traced for
# 3 s with a helper traced inside it, lowered for 1 s; a hit of 2 s; a miss
# of 4 s with an eager helper's hit inside it; the step's first call; and a
# compile that straddles the window's opening, which is none of set-up's
SPANS = [
    _span("jit.compile", 90.0, 1.0, fun="earlier_run", cache="hit"),
    _span("process.ready", 104.0, 0.0),
    _span("jit.trace", 105.5, 0.5, fun="helper"),           # inside step's
    _span("jit.trace", 105.0, 3.0, fun="step"),             # 105 .. 108
    _span("jit.lower", 108.0, 1.0, fun="step"),             # 108 .. 109
    _span("jit.compile", 109.0, 2.0, fun="step", cache="hit"),   # .. 111
    _span("train.dispatch", 104.9, 6.15, step="lm"),
    _span("train.first_call", 104.9, 6.2, step="lm"),
    _span("jit.compile", 112.5, 0.5, fun="eager", cache="hit"),  # inside ref
    _span("jit.compile", 112.0, 4.0, fun="ref", cache="miss"),   # 112 .. 116
    _span("train.first_call", 117.0, 0.1, step="lm"),       # a later build's
    _span("train.first_call", 104.0, 9.0, step="sgd"),      # another step's
    _span("jit.compile", 119.5, 1.0, fun="late", cache="miss"),  # straddles
    _span("train.dispatch", 121.0, 0.001, step="lm"),
]


def test_union_counts_an_overlap_once():
    assert setup_spans.union_s([]) == 0.0
    assert setup_spans.union_s([(1.0, 2.0), (4.0, 5.0)]) == 2.0
    assert setup_spans.union_s([(1.0, 4.0), (2.0, 3.0)]) == 3.0
    assert setup_spans.union_s([(2.0, 5.0), (1.0, 3.0), (5.0, 6.0)]) == 5.0


def test_the_split_against_hand_counts():
    parts = setup_spans.split(SPANS, 100.0, 20.0)
    assert parts["reach_s"] == 4.0
    # trace 105..108 (the helper's lies inside) and lower 108..109
    assert parts["trace_s"] == 4.0
    # hits: step 109..111 and eager 112.5..113
    assert parts["fetch_s"] == 2.5
    assert parts["compiled_s"] == 4.0
    # all jit spans: 105..111 and 112..116
    assert parts["jit_s"] == 10.0
    assert parts["step_s"] == 6.2               # the first of step=lm
    assert parts["run_s"] == 20.0 - 4.0 - 10.0
    assert parts["reach_s"] + parts["jit_s"] + parts["run_s"] == 20.0
    assert parts["misses"] == [["ref", "miss", 4.0]]
    names = [s["labels"]["fun"] for s in parts["spans"]
             if s["name"] in setup_spans.JIT]
    assert "earlier_run" not in names and "late" not in names
    # what a sum of the spans counts twice: the helper's trace, eager's hit
    assert setup_spans.nested_by_name(parts["spans"]) == {"helper": 0.5,
                                                          "eager": 0.5}
    # the sum JAX's events give (compile_s) stands over the three unions by
    # the nested trace (the hit inside the miss is in two of the unions)
    total = sum(s["dur"] for s in parts["spans"]
                if s["name"] in setup_spans.JIT)
    assert total - (parts["trace_s"] + parts["fetch_s"]
                    + parts["compiled_s"]) == 0.5


@pytest.mark.parametrize("case,t_process,want", [
    # the mark is older than the process's start: another run's
    ("ready_older_than_t_process", 104.5,
     None),
    # no hit inside set-up: nothing for the fetch, the rest stands
    ("no_hit", 100.0,
     {"fetch_s": None, "reach_s": 4.0, "trace_s": 4.0, "run_s": 6.0}),
    # no first call of the lm step: nothing for the step
    ("no_first_call", 100.0, {"step_s": None, "reach_s": 4.0}),
    # nothing traced at all: no trace, no fetch, and the run is the rest
    ("no_jit", 100.0, {"trace_s": None, "fetch_s": None, "reach_s": 4.0,
                       "run_s": 16.0}),
])
def test_a_reader_gives_nothing_where_its_spans_are_absent(case, t_process,
                                                          want):
    spans = {
        "ready_older_than_t_process": SPANS,
        "no_hit": [dict(s, labels=dict(s.get("labels", {}), cache="miss"))
                   if s["name"] == "jit.compile" else s for s in SPANS],
        "no_first_call": [s for s in SPANS if s["name"] != "train.first_call"],
        "no_jit": [s for s in SPANS if s["name"] not in setup_spans.JIT],
    }[case]
    parts = setup_spans.split(spans, t_process, 120.0 - t_process)
    if want is None:
        assert parts is None
        return
    for key, value in want.items():
        assert parts[key] == value, key
    for key in ("reach_s", "trace_s", "fetch_s", "step_s", "run_s"):
        assert parts[key] is None or parts[key] > 0


def test_a_part_at_or_under_zero_is_nothing():
    # the mark AT the process's start, and jit spans that fill the set-up
    spans = [_span("process.ready", 100.0, 0.0),
             _span("jit.compile", 100.0, 20.0, fun="all", cache="miss")]
    parts = setup_spans.split(spans, 100.0, 20.0)
    assert parts["reach_s"] is None and parts["run_s"] is None
    assert parts["fetch_s"] is None and parts["trace_s"] is None


# ------------------------------------------------ the readers on a run --

@pytest.fixture(scope="module")
def meter():
    return harness.CompileMeter()


def _read_five(run, result):
    return {n: harness.load_module("layer_metrics", n).read(run, result)
            for n in FIVE}


@pytest.fixture(autouse=True)
def _leave_the_ring_empty():
    """Other tests of the suite expect to find it so."""
    yield
    trace.clear()


@pytest.fixture
def marked(monkeypatch):
    """What ``run.py``'s entry does before a run, undone after the test:
    the listeners in place and the mark left, obs on."""
    core.configure(True)
    monkeypatch.setattr(compile_cache, "_watching", False)
    compile_cache.watch_compiles()
    yield lambda: obs.record_span("process.ready", 0.0)
    jax.monitoring.unregister_event_duration_listener(
        compile_cache._on_duration)
    jax.monitoring.unregister_event_listener(compile_cache._on_event)
    core.configure(None)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_five_are_listed_for_the_cell(cell):
    listed = {m["name"]: m for m in
              bench_run.cell_metrics(MAN, cell, "per_layer")}
    for name in FIVE:
        m = listed[name]
        assert (m["moves"], m["unit"], m["better"], m["source"]) == (
            "setup_s", "s", "lower", "program_span")
        assert cell in m["workloads"]       # a later cell joins by listing


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_unmarked_toy_run_gives_nothing_and_keeps_the_source_rule(cell, meter):
    """No ``watch_compiles()``, no mark: every new reader is silent, though
    the step shim's ``train.first_call`` is in the ring."""
    trace.clear()
    run, result = rules.toy_run(CELLS[cell], meter)
    assert any(s["name"] == "train.first_call" for s in obs.spans())
    assert _read_five(run, result) == dict.fromkeys(FIVE)
    found = rules.readers_keep_the_source_rule(MAN, run, result)
    assert set(FIVE) <= set(found)
    assert all(found[n] is None for n in FIVE)


def _marked_toy_run(cell, meter, mark):
    """The toy twin of ``cell``'s kind, begun the way ``run.py`` begins a
    run: the clock read first, then the mark, then the cell."""
    toy = copy.deepcopy(rules.TOYS[rules.kind_of(cell["name"])])
    # the ring reaches back to before the run, whatever earlier tests of
    # this process evicted from it
    trace.clear()
    obs.record_span("before.the.run", 0.0)
    t_process = time.perf_counter()
    time.sleep(0.01)                    # the interpreter, the imports
    mark()
    return bench_run.measure_cell(
        cell, toy["config"], toy["workload"], seed=2**31 + 9, seconds=0.3,
        trace=0, devices=jax.devices(), peaks=rules.PEAKS, meter=meter,
        t_process=t_process)


def test_marked_toy_run_adds_up_to_its_setup(meter, marked, capsys):
    """One kind, one toy: the cells differ in name only."""
    run, result = _marked_toy_run(CELLS[min(CELLS)], meter, marked)
    got = _read_five(run, result)
    # no persistent cache in the suite's process: no hit, so no fetch
    assert got.pop("setup_fetch_s") is None
    assert all(v is not None and v > 0 for v in got.values()), got
    parts = result.window["setup_split"]
    assert got["setup_reach_s"] + parts["jit_s"] + got["setup_run_s"] \
        == pytest.approx(run.setup_s, abs=1e-9)
    assert got["setup_step_s"] < run.setup_s
    assert got["setup_trace_s"] <= parts["jit_s"]
    # the unions stand under JAX's own sum, which the meter took
    unions = got["setup_trace_s"] + parts["compiled_s"]
    assert unions <= run.setup_meter["compile_s"] + 1e-3
    # the step's first call holds its program's spans
    first, = [s for s in parts["spans"] if s["name"] == "train.first_call"]
    mine = [s for s in parts["spans"] if s["name"] in setup_spans.JIT
            and s["labels"]["fun"] == "step"
            and first["t0"] - 2e-3 <= s["t0"] <= first["t0"] + first["dur"]]
    assert {s["name"] for s in mine} == set(setup_spans.JIT)
    # the log holds the table, the misses and both sides of the sum
    out = capsys.readouterr().out
    assert "set-up programs" in out and '"step"' in out
    assert "set-up compile misses" in out and "counted twice" in out
    found = rules.readers_keep_the_source_rule(MAN, run, result)
    assert all(found[n] is None or found[n] > 0 for n in FIVE)


def test_a_program_compiled_after_setup_is_outside_every_metric(meter,
                                                                marked):
    run, result = _marked_toy_run(CELLS[min(CELLS)], meter, marked)
    before = dict(_read_five(run, result))

    @jax.jit
    def recompiled_in_the_middle(x):
        return x * 3

    recompiled_in_the_middle(jax.numpy.ones((3,)))
    late = [s for s in obs.spans() if s["name"] == "jit.compile"
            and s["labels"]["fun"] == "recompiled_in_the_middle"]
    assert len(late) == 1 and late[0]["t0"] > run.t_process + run.setup_s
    result.window.pop("setup_split")
    assert _read_five(run, result) == before


def test_a_ring_that_lost_spans_of_the_run_gives_nothing(meter, marked,
                                                         monkeypatch):
    run, result = _marked_toy_run(CELLS[min(CELLS)], meter, marked)
    assert _read_five(run, result)["setup_reach_s"] is not None
    # the ring evicts: its oldest record is now younger than the run
    monkeypatch.setattr(trace, "_ring", trace._ring)
    trace.set_ring_size(len(obs.spans()))
    obs.record_span("one.more", 0.0)
    result.window.pop("setup_split")
    assert _read_five(run, result) == dict.fromkeys(FIVE)
    # evictions that took only records older than the run do no harm
    trace.set_ring_size(4096)
    run, result = _marked_toy_run(CELLS[min(CELLS)], meter, marked)
    assert _read_five(run, result)["setup_reach_s"] is not None
