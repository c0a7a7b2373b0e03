"""The rules a ``BENCHMARK.json`` keeps, as functions of a manifest, so that
the tests apply them to the committed one AND to a copy with a cell appended:
names, units and limits; every cell's files found by name; and what a reader
gives on an untraced CPU run, which follows from its ``source`` and from
nothing about the order of ``workloads``.  Beside them the toy twin of each
kind (``toys/<kind>.json``), run through ``run.py``'s own functions."""

import copy
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

import harness  # noqa: E402
import run as bench_run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TOYS = {f[:-5]: json.load(open(os.path.join(HERE, "toys", f)))
        for f in sorted(os.listdir(os.path.join(HERE, "toys")))
        if f.endswith(".json")}


def kind_of(cell: str) -> str:
    return harness.load_json("workloads", cell + ".json")["kind"]


def toy_run(cell: dict, meter, *, trace=0, seconds=0.6, seed=2**31 + 7,
            **workload):
    """The toy twin of ``cell``'s kind under the cell's name; ``workload``
    overrides parameters of the toy's workload file."""
    toy = copy.deepcopy(TOYS[kind_of(cell["name"])])
    return bench_run.measure_cell(
        cell, toy["config"], dict(toy["workload"], **workload), seed=seed,
        seconds=seconds, trace=trace, devices=jax.devices(),
        peaks=PEAKS, meter=meter, t_process=time.perf_counter())


def names_units_and_limits(man: dict):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51
    names = [e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in man[g]]
    for n in names + [w["traffic"] for w in man["workloads"]]:
        assert NAME.match(n), n
    for g in ("configs", "workloads"):
        assert len({e["name"] for e in man[g]}) == len(man[g])
    metrics = man["end_to_end"] + man["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    cells = {w["name"] for w in man["workloads"]}
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", ())) <= cells, m["name"]
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(man["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert len(json.dumps(man)) < 64 * 1024


def cells_resolve_and_report(man: dict):
    e2e = {m["name"] for m in man["end_to_end"]}
    used = set()
    for w in man["workloads"]:
        cell, config, workload = bench_run.resolve(man, w["name"])
        used.add(cell["config"])
        assert workload["kind"] in TOYS, "a kind brings its toy"
        for folder, name in (("kinds", workload["kind"]),
                             ("families", config["family"]),
                             ("reference", config["family"])):
            assert os.path.isfile(os.path.join(BENCH, folder, name + ".py"))
        mine = {m["name"] for m in
                bench_run.cell_metrics(man, w["name"], "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layer = bench_run.cell_metrics(man, w["name"], "per_layer")
        assert layer
        for m in layer:
            assert set(m) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
            assert m["moves"] in mine, (w["name"], m["name"])
            assert hasattr(harness.load_module("layer_metrics", m["name"]),
                           "read")
    assert used == {c["name"] for c in man["configs"]}
    assert e2e == {m["name"] for w in man["workloads"] for m in
                   bench_run.cell_metrics(man, w["name"], "end_to_end")}
    for c in man["configs"]:
        assert c["file"].startswith("benchmarks/configs/")
        assert c["reduced"] == json.load(
            open(os.path.join(ROOT, c["file"])))["reduced"]


def readers_keep_the_source_rule(man: dict, run, result) -> dict:
    """What every reader listed for ``run``'s cell gives on an UNTRACED toy
    run, by its ``source``: a reader of the device trace nothing; one of the
    host's clock a number above 0; one of the program's spans or counters a
    number above 0, or nothing where this toy's program has no such thing
    (that it finds its number is proved by the test of the family or mesh
    that feeds it).  Returns what was found."""
    assert run.trace.reduction is None
    found = {}
    for m in bench_run.cell_metrics(man, run.cell["name"], "per_layer"):
        value = harness.load_module("layer_metrics", m["name"]).read(
            run, result)
        found[m["name"]] = value
        if m["source"] == "device_trace":
            assert value is None, m["name"]
        elif m["source"] == "host_clock":
            assert value is not None and value > 0, m["name"]
        else:
            assert value is None or value > 0, m["name"]
    return found


def with_appended_cell(man: dict, like: str, name: str) -> dict:
    """A copy of ``man`` with a one-chip cell ``name`` APPENDED: ``like``'s
    configuration under a traffic of its own, listed by every end-to-end
    metric ``like`` reports and by EVERY per-layer reader there is."""
    man = copy.deepcopy(man)
    src = {w["name"]: w for w in man["workloads"]}[like]
    man["workloads"].append(dict(src, name=name, chips=1,
                                 traffic=name.split(".", 1)[-1]))
    for m in man["end_to_end"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(name)
    for m in man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    return man
