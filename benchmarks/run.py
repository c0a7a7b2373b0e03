#!/usr/bin/env python
"""The benchmark's command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that sets no platform and refuses anything but a TPU.  It reads
``BENCHMARK.json`` for the cell, finds the cell's files BY NAME —
``configs/<config>.json`` (its ``family`` names ``families/<family>.py`` and
``reference/<family>.py``), ``workloads/<cell>.json`` (its ``kind`` names
``kinds/<kind>.py``), ``layer_metrics/<metric>.py`` — builds the cell through
the repo's constructors, warms the cell's own shapes (set-up), measures for
``--seconds``, checks the outputs against the plain reference, and prints one
JSON line last.  No cell, model or metric is named in this file.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` measures a
short traced window and prints its per-layer metrics and the breakdown.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cell_metrics(man: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` (end_to_end | per_layer) this cell reports:
    those with no ``workloads`` key, and those that list it."""
    return [m for m in man[group]
            if "workloads" not in m or cell in m["workloads"]]


def resolve(man: dict, name: str):
    """(cell entry, configuration, workload parameters) of cell ``name``."""
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as fh:
        config = json.load(fh)
    workload = harness.load_json("workloads", name + ".json")
    return cell, config, workload


def measure_cell(cell, config, workload, *, seed, seconds, trace, devices,
                 peaks, meter, t_process):
    """One run of one cell on ``devices``: the harness's ``Run`` and the
    kind's ``Result``.  The platform is the caller's business: ``main``
    refuses anything but a TPU, the CPU tests call this at toy size."""
    run = harness.Run(cell=cell, config=config, workload=workload, seed=seed,
                      seconds=seconds, trace=harness.TraceWindow(bool(trace)),
                      devices=list(devices), peaks=peaks, meter=meter,
                      t_process=t_process)
    return run, harness.load_module("kinds", workload["kind"]).run(run)


def result_line(man, run, result) -> dict:
    """The result line of a measured cell: its end-to-end metrics, or in a
    traced run whatever its per-layer readers find."""
    cell = run.cell["name"]
    metrics = {}
    if run.trace.enabled:
        for m in cell_metrics(man, cell, "per_layer"):
            value = harness.load_module("layer_metrics", m["name"]).read(
                run, result)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = dict(result.end_to_end, **{harness.SETUP: run.setup_s})
        for m in cell_metrics(man, cell, "end_to_end"):
            if m["name"] not in values:
                raise RuntimeError(f"kind {run.workload['kind']!r} did not "
                                   f"report {m['name']!r} for cell {cell!r}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    inside = result.window.get("compiled_inside", {})
    line = {"correct": bool(result.correct and not inside.get("programs")),
            "attempted": int(result.attempted), "failed": int(result.failed),
            "metrics": metrics,
            "device": harness.device_record(run.devices, run.trace)}
    if run.trace.reduction is not None:
        line["breakdown"] = {
            "device_ops": run.trace.reduction["device_ops"],
            "idle_gaps": run.trace.reduction["idle_gaps"]}
    # every number the check compared beside its limit: last in the line
    line["compared"] = dict(
        result.compared,
        programs_compiled_in_window=[inside.get("programs", 0), 0])
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    man = manifest()
    cell, config, workload = resolve(man, args.workload)

    import jax
    devs = jax.devices()            # whatever JAX picked: no platform set here
    d0 = devs[0]
    if d0.platform != "tpu" or len(devs) < cell["chips"]:
        print(f"benchmarks/run.py: needs {cell['chips']} TPU chip(s), JAX found "
              f"platform={d0.platform!r} with {len(devs)} device(s); refusing "
              "to run", file=sys.stderr)
        return 1
    peaks = harness.device_peaks(d0.device_kind)      # unknown kind: error
    from distlearn_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    meter = harness.CompileMeter()
    harness.log(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
                f"trace {args.trace}; {d0.device_kind} x {len(devs)}, "
                f"compile cache {cache_dir}")
    run, result = measure_cell(cell, config, workload, seed=args.seed,
                               seconds=args.seconds, trace=args.trace,
                               devices=devs[:cell["chips"]], peaks=peaks,
                               meter=meter, t_process=T_PROCESS)
    harness.log(f"compile meter at exit: {json.dumps(meter.snapshot())}")
    line = result_line(man, run, result)
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value!r} limit {limit!r}", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
