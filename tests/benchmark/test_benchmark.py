"""The benchmark's own checks, at toy sizes on the CPU: every kind of cell
runs end to end through ``run.py``'s own functions, the result line has the
contract's keys, the measured loop counts all the work over all the time,
``BENCHMARK.json`` keeps the contract's character rules and every cell's
files resolve by name, the trace reducer gives known numbers on
a synthetic event list, the layer-by-layer reference equals the whole-model
one, the analytic FLOP counts equal hand counts, and the command itself
refuses to run without a TPU.  Nothing here is a measurement: no number of
these runs is recorded anywhere."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import manifest_rules as rules
from manifest_rules import BENCH, ROOT, UNIT, bench_run, harness

import trace_reduce  # noqa: E402  (benchmarks/ is on the path by now)

MAN = bench_run.manifest()
CELLS = {w["name"]: w for w in MAN["workloads"]}
TOY_LM = rules.TOYS["train_lm"]["config"]


@pytest.fixture(scope="module")
def meter():
    return harness.CompileMeter()


def test_every_kind_has_a_toy_and_every_toy_a_kind():
    kinds = {f[:-3] for f in os.listdir(os.path.join(BENCH, "kinds"))
             if not f.startswith("_") and f.endswith(".py")}
    assert set(rules.TOYS) == kinds >= {rules.kind_of(c) for c in CELLS}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_toy_runs_end_to_end_under_every_cells_name(cell, meter):
    """The line keeps the contract, and every reader listed for the cell
    gives what its ``source`` says it must on an untraced CPU run."""
    run, result = rules.toy_run(CELLS[cell], meter)
    line = json.loads(json.dumps(bench_run.result_line(MAN, run, result)))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"] for m in bench_run.cell_metrics(MAN, cell, "end_to_end")}
    assert set(line["metrics"]) == want and "setup_s" in want
    for m in line["metrics"].values():
        assert m["value"] > 0 and UNIT.match(m["unit"])
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # each number the check compared stands beside its limit, inside it
    assert line["compared"]["programs_compiled_in_window"] == [0, 0]
    gaps = [v for k, v in line["compared"].items() if k.startswith("loss_gap")]
    assert len(gaps) == 3 and all(0 <= g <= tol for g, tol in gaps)
    after, limit = line["compared"]["loss_after_minus_first"]
    assert after < limit == 0.0
    found = rules.readers_keep_the_source_rule(MAN, run, result)
    assert any(v is not None for v in found.values())


def test_an_appended_cell_that_lists_every_reader_keeps_every_rule(
        meter, monkeypatch):
    """What a later PR does without an edit: a cell of kind ``train_lm``
    appended to a copy of the manifest, listed by EVERY per-layer reader
    there is — those of the device trace and of what a dense model does not
    have among them."""
    like = min(c for c in CELLS if rules.kind_of(c) == "train_lm"
               and CELLS[c]["chips"] == 1)
    name = CELLS[like]["config"] + ".appended-by-a-later-pr"
    man = rules.with_appended_cell(MAN, like, name)
    assert [w["name"] for w in man["workloads"]].count(name) == 1
    listed = {m["name"] for m in bench_run.cell_metrics(man, name, "per_layer")}
    assert listed == {m["name"] for m in MAN["per_layer"]}
    load = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda *parts: load(
        *(parts if parts != ("workloads", name + ".json")
          else ("workloads", like + ".json"))))
    rules.names_units_and_limits(man)
    rules.cells_resolve_and_report(man)
    run, result = rules.toy_run(man["workloads"][-1], meter)
    found = rules.readers_keep_the_source_rule(man, run, result)
    assert set(found) == listed
    by_source = {}
    for m in man["per_layer"]:
        by_source.setdefault(m["source"], []).append(found[m["name"]])
    assert all(v is None for v in by_source["device_trace"])
    assert all(v is not None for v in by_source["host_clock"])
    line = bench_run.result_line(man, run, result)
    assert line["correct"] is True


# ------------------------------------------- the check after the window --

def test_window_that_ends_on_an_unseen_batch_is_correct(meter):
    """A ring longer than the window hands over steps: the window ends on a
    batch no step has seen, whose loss says nothing about training.  The
    kind compares the check's batch with itself, after the window."""
    cell = CELLS[min(CELLS)]
    run, result = rules.toy_run(cell, meter, seconds=0.05, ring_batches=64,
                                in_flight=1, lr=0.02)
    assert 1 < result.window["calls"] < 64       # ends on a batch not seen
    assert result.correct is True
    assert result.compared["loss_after_minus_first"][0] < 0


def test_a_step_that_does_not_train_is_not_correct(meter):
    cell = CELLS[min(CELLS)]
    run, result = rules.toy_run(cell, meter, seconds=0.05, ring_batches=64,
                                in_flight=1, lr=0.0)
    assert result.compared["loss_after_minus_first"] == [0.0, 0.0]
    assert result.correct is False
    assert bench_run.result_line(MAN, run, result)["correct"] is False


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
    rules.names_units_and_limits(MAN)


def test_every_cell_resolves_by_name_and_reports_what_it_must():
    rules.cells_resolve_and_report(MAN)


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
    cell = min(CELLS)
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
        rules.toy_run(CELLS[min(CELLS)], meter, trace=1)


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
