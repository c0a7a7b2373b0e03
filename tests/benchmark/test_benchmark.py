"""The benchmark's own checks, at toy sizes on the CPU: every kind of cell
runs end to end through ``run.py``'s own functions, the result line has the
contract's keys, the measured loop counts all the work over all the time,
``BENCHMARK.json`` keeps the contract's character rules and every cell's
files resolve by name, the trace reducer gives known numbers on
a synthetic event list, the layer-by-layer reference equals the whole-model
one, the analytic FLOP counts equal hand counts, and the command itself
refuses to run without a TPU.  Nothing here is a measurement: no number of
these runs is recorded anywhere."""

import copy
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402
import run as bench_run  # noqa: E402
import trace_reduce  # noqa: E402

MAN = bench_run.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

TOY_LM = {"family": "transformer_lm", "vocab_size": 97, "n_positions": 64,
          "n_embd": 32, "n_layer": 2, "n_head": 4, "n_inner": None}
TOY = {
    "train_lm": (TOY_LM, {
        "kind": "train_lm", "mesh": [2, 1, 1], "global_batch": 4, "seq": 16,
        "lr": 0.05, "compute_dtype": None, "scan_blocks": True,
        "remat": "full", "ring_batches": 2, "in_flight": 3, "check_steps": 2,
        "check_micro": 2, "loss_tolerance": 1e-4, "trace_seconds": 0.3}),
}
CELL_OF_KIND = {harness.load_json("workloads", w["name"] + ".json")["kind"]: w
                for w in MAN["workloads"]}


@pytest.fixture(scope="module")
def meter():
    return harness.CompileMeter()


def _toy_run(kind, meter, trace=0, seconds=0.6):
    config, workload = copy.deepcopy(TOY[kind])
    return bench_run.measure_cell(
        CELL_OF_KIND[kind], config, workload, seed=2**31 + 7,
        seconds=seconds, trace=trace, devices=jax.devices(), peaks=PEAKS,
        meter=meter, t_process=time.perf_counter())


def test_every_kind_in_the_manifest_has_a_toy():
    assert set(TOY) == set(CELL_OF_KIND) == {
        f[:-3] for f in os.listdir(os.path.join(BENCH, "kinds"))
        if not f.startswith("_") and f.endswith(".py")}


@pytest.mark.parametrize("kind", sorted(TOY))
def test_kind_runs_end_to_end_and_line_keeps_the_contract(kind, meter):
    run, result = _toy_run(kind, meter)
    line = json.loads(json.dumps(bench_run.result_line(MAN, run, result)))
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"] for m in
            bench_run.cell_metrics(MAN, run.cell["name"], "end_to_end")}
    assert set(line["metrics"]) == want and "setup_s" in want
    for m in line["metrics"].values():
        assert m["value"] > 0 and UNIT.match(m["unit"])
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}


@pytest.mark.parametrize("kind", sorted(TOY))
def test_readers_find_their_numbers_or_nothing(kind, meter):
    """The readers that need no device trace find their numbers; the ones
    that need it return nothing rather than a number from elsewhere."""
    run, result = _toy_run(kind, meter)
    found = {m["name"]: harness.load_module("layer_metrics", m["name"]).read(
        run, result) for m in
        bench_run.cell_metrics(MAN, run.cell["name"], "per_layer")}
    assert found.pop("device_idle_share.train") is None
    assert found and all(v is not None and v > 0 for v in found.values()), found


# ------------------------------------------------------ the measured loop --

def _fake_steps(step_s):
    """``step_once`` of a fake device that runs one call at a time, each for
    ``step_s``; what it returns is ready when its call is done."""
    free_at = [time.perf_counter()]

    class Out:
        def __init__(self, ready_at):
            self.ready_at = ready_at

        def block_until_ready(self):
            time.sleep(max(0.0, self.ready_at - time.perf_counter()))
            return self

    def step_once(i):
        free_at[0] = max(free_at[0], time.perf_counter()) + step_s
        return Out(free_at[0])
    return step_once


@pytest.mark.parametrize("in_flight", [1, 2, 4])
def test_measured_loop_counts_all_the_work_over_all_the_time(in_flight):
    loop = harness.load_module("kinds", "_train_loop")
    rate, stats = loop.measure(_fake_steps(0.02), seconds=0.3,
                               samples_per_call=8, in_flight=in_flight)
    # every dispatched call completed and counts, the ones in flight at the
    # window's end included; the time runs to the last completion
    assert stats["calls"] == len(stats["dispatch_s"]) \
        == len(stats["completion_gaps_s"]) >= 0.3 / 0.02 - 1
    assert stats["samples"] == 8 * stats["calls"]
    assert sum(stats["completion_gaps_s"]) == pytest.approx(stats["elapsed_s"])
    assert stats["elapsed_s"] >= 0.3
    assert rate == pytest.approx(stats["samples"] / stats["elapsed_s"])
    assert rate <= 8 / 0.02 * 1.001
    assert stats["host"]["sleeper_late_max_s"] >= 0
    assert stats["host"]["gc_collections"] >= 0 and stats["host"]["loadavg"]


def test_host_probe_sees_a_garbage_collection():
    import gc
    with harness.HostProbe(period=0.001) as probe:
        gc.collect()
        time.sleep(0.01)
    assert probe.report["gc_collections"] >= 1 and probe.report["gc_s"] > 0
    assert probe.report["sleeper_late_max_s"] < 1.0
    assert gc.callbacks.count(probe._on_gc) == 0


# ---------------------------------------------------------- the manifest --

def test_manifest_names_units_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    names = [e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in MAN[g]]
    for n in names + [w["traffic"] for w in MAN["workloads"]]:
        assert NAME.match(n), n
    for g in ("configs", "workloads"):
        assert len({e["name"] for e in MAN[g]}) == len(MAN[g])
    metrics = MAN["end_to_end"] + MAN["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert len(json.dumps(MAN)) < 64 * 1024


def test_every_cell_resolves_by_name_and_reports_what_it_must():
    e2e = {m["name"] for m in MAN["end_to_end"]}
    used = set()
    for w in MAN["workloads"]:
        cell, config, workload = bench_run.resolve(MAN, w["name"])
        used.add(cell["config"])
        for folder, name in (("kinds", workload["kind"]),
                             ("families", config["family"]),
                             ("reference", config["family"])):
            assert os.path.isfile(os.path.join(BENCH, folder, name + ".py"))
        mine = {m["name"] for m in
                bench_run.cell_metrics(MAN, w["name"], "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layer = bench_run.cell_metrics(MAN, w["name"], "per_layer")
        assert layer
        for m in layer:
            assert set(m) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
            assert m["moves"] in mine, (w["name"], m["name"])
            assert hasattr(harness.load_module("layer_metrics", m["name"]),
                           "read")
    assert used == {c["name"] for c in MAN["configs"]}
    assert e2e == {m["name"] for w in MAN["workloads"] for m in
                   bench_run.cell_metrics(MAN, w["name"], "end_to_end")}
    for c in MAN["configs"]:
        assert c["file"].startswith("benchmarks/configs/")
        assert c["reduced"] == json.load(
            open(os.path.join(ROOT, c["file"])))["reduced"]


def test_gpt2_large_is_at_its_published_sizes():
    cfg = harness.load_json("configs", "gpt2-large.json")
    assert (cfg["n_layer"], cfg["n_embd"], cfg["n_head"], cfg["vocab_size"],
            cfg["n_positions"]) == (36, 1280, 20, 50257, 1024)
    assert cfg["reduced"] == [] and cfg["departures"]
    fam = harness.load_module("families", "transformer_lm")
    assert fam.param_count(cfg) == cfg["parameters"] == 773752320
    wl = harness.load_json("workloads", "gpt2-large.train.json")
    assert wl["global_batch"] * wl["seq"] == 8192 and wl["mesh"] == [1, 1, 1]


def test_command_refuses_to_run_without_a_tpu():
    cell = MAN["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert res.returncode != 0
    assert "refusing to run" in res.stderr
    assert not any(l.startswith("{") for l in res.stdout.splitlines())


def test_unknown_device_kind_is_an_error():
    assert harness.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.device_peaks("cpu")
    for chip in harness.load_json("peaks.json")["chips"].values():
        assert chip["source"]


# ------------------------------------------------------ the trace reducer --

def _ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def test_trace_reducer_on_a_synthetic_event_list():
    d0, d1, ops = "/device:TPU:0", "/device:TPU:1", "XLA Ops"
    long_name = ("%fusion.7 = f32[8,1024]{1,0:T(8,128)} fusion(f32[8,1024]{1,0}"
                 " %param.1, f32[1024]{0} %param.2), kind=kLoop")
    events = [
        # device 0: a while [0, 400) enclosing two children, nothing for
        # 300, then an all-reduce of 100 and the same fusion again
        _ev(d0, ops, "%while.1 = (s32[], f32[8]) while(...)", 0, 400),
        _ev(d0, ops, long_name, 0, 150),
        _ev(d0, ops, "%custom-call.3 = f32[64,128] custom-call(...)", 200, 100),
        _ev(d0, ops, "all-reduce.5", 700, 100),
        _ev(d0, ops, long_name, 800, 50),
        _ev(d0, "XLA Modules", "jit_step", 0, 850),       # not an op: ignored
        _ev(d0, "Steps", "1", 0, 850),
        # device 1: busy 425 of the window
        _ev(d1, ops, "fusion.1", 0, 425),
        # host: most of the gap [300, 700) lies inside bench.fetch; an
        # unrelated annotation and a shorter overlapping one lose to it
        _ev("/host:CPU", "python", "bench.fetch", 380, 400),
        _ev("/host:CPU", "python", "bench.dispatch", 690, 30),
        _ev("/host:CPU", "python", "PjitFunction(step)", 300, 500),
        # a span of the program's own (dotted lower-case, as obs.span names
        # them) names the short gap [150, 200) as bench.* names the long one
        _ev("/host:CPU", "python", "async_ea.sync", 140, 70),
    ]
    red = trace_reduce.reduce_events(events, window_s=1e-6, device_ids=[0, 1])
    # busy = the union of the LEAF operations: the while's own 150 ns
    # ([150, 200) and [300, 400)) are not an operation running
    assert red["per_device"] == {0: pytest.approx(400e-9),
                                 1: pytest.approx(425e-9)}
    assert red["busy_s"] == pytest.approx(412.5e-9)
    assert red["window_s"] == 1e-6
    ops_s = {k: v for k, v in red["ops"].items()}
    assert ops_s["fusion.7 f32[8,1024]"] == [pytest.approx(200e-9), 2,
                                             pytest.approx(200e-9)]
    assert ops_s["custom-call.3 f32[64,128]"][:2] == [pytest.approx(100e-9), 1]
    # self time 150 of a whole duration of 400: its children cover the rest
    assert ops_s["while.1 s32[]"] == [pytest.approx(150e-9), 1,
                                      pytest.approx(400e-9)]
    assert ops_s["all-reduce.5"][:2] == [pytest.approx(100e-9), 1]
    assert sum(v[0] for v in ops_s.values()) == pytest.approx(550e-9)
    assert red["device_ops"][0] == ["fusion.7 f32[8,1024]",
                                    pytest.approx(200e-9)]
    assert all(len(n) < 100 for n, _ in red["device_ops"])
    assert red["idle_gaps"][0] == ["bench.fetch", pytest.approx(400e-9)]
    assert red["idle_gaps"][1] == ["async_ea.sync", pytest.approx(50e-9)]
    one = trace_reduce.reduce_events(events, window_s=1e-6, device_ids=[0])
    assert one["busy_s"] == pytest.approx(400e-9)
    with pytest.raises(ValueError):
        trace_reduce.reduce_events(events, window_s=1.0, device_ids=[7])


def test_traced_run_reports_only_what_its_readers_find(meter):
    """On the CPU the trace holds no TPU plane, so a traced run must fail
    loudly rather than report a device number from somewhere else."""
    with pytest.raises(ValueError, match="no device operation"):
        _toy_run("train_lm", meter, trace=1)


# ---------------------------------------------- references and FLOP counts --

def _toy_lm_params(depth=3):
    fam = harness.load_module("families", "transformer_lm")
    cfg = dict(TOY_LM, n_layer=depth)
    model = fam.build(cfg, max_len=16)
    params = fam.init_params(model, harness.seed_key(5))
    return cfg, model, params, fam.to_reference(params)


def test_layerwise_reference_equals_whole_model_forward_and_grad():
    ref = harness.load_module("reference", "transformer_lm")
    _, _, _, rp = _toy_lm_params()
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 97, jnp.int32)
    want_l, want_g = jax.value_and_grad(ref.loss)(rp, toks)
    got_l, got_g = ref.layerwise_loss_and_grads(rp, toks, micro=2)
    assert got_l == pytest.approx(float(want_l), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)
    assert ref.layerwise_loss(rp, toks, micro=2) == pytest.approx(
        float(want_l), rel=1e-6)


def test_reference_is_the_systems_model_at_float32():
    """Independent code, same mathematics: the repo's model in float32
    agrees with the reference on logits, in both parameter layouts."""
    ref = harness.load_module("reference", "transformer_lm")
    fam = harness.load_module("families", "transformer_lm")
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 97, jnp.int32)
    for scan in (False, True):
        model = fam.build(dict(TOY_LM, n_layer=3), max_len=16,
                          scan_blocks=scan)
        params = fam.init_params(model, harness.seed_key(9))
        got, _ = model.apply(params, {}, toks, train=False)
        np.testing.assert_allclose(got, ref.logits(fam.to_reference(params),
                                                   toks), atol=2e-5)


def test_analytic_flops_against_hand_counts():
    lm = harness.load_module("families", "transformer_lm")
    # 1 layer, E=4, F=16, V=10, L=2: per token 8*16 + 4*4*16 = 384 in the
    # block and 2*4*10 = 80 in the head; attention 2*L*L*E = 32; x3
    cfg = {"n_embd": 4, "n_layer": 1, "n_inner": None, "vocab_size": 10,
           "n_positions": 8, "n_head": 2}
    assert lm.train_flops_per_sample(cfg, 2) == 3 * (2 * (384 + 80) + 32)
    # wte 40 + wpe 32 + ln_f 4; layer: 4*16 + 2*64 + 16 + 3*4 = 220
    assert lm.param_count(cfg) == 40 + 32 + 4 + 220
