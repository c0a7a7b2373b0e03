"""The scope / pass readers of the benchmark (``scope_reduce.py`` and the
``layer_metrics`` that call it), at toy size on the CPU: hand counts on two
hand-written HLO texts and synthetic ``ops`` dicts, every device reader
silent without a device trace, the two program-side readers finding their
numbers on an untraced toy run, and the four-chip cell's data.  Nothing here
is a measurement."""

import json
import time
import types

import pytest

import jax

import manifest_rules as rules
from manifest_rules import bench_run, harness

import scope_reduce  # noqa: E402  (benchmarks/ is on the path by now)

from distlearn_tpu.models.core import SCOPES  # noqa: E402
from distlearn_tpu.utils.profiling import scope_table  # noqa: E402

MAN = bench_run.manifest()
ONE, DP4 = "gpt2-large.train", "gpt2-large.train-dp4"

# a step program in miniature, in the TPU compiler's own spelling: a fused
# computation (its inner instruction never runs by itself), a while body, an
# entry with a sync all-reduce named by JAX (psum.7), an async all-gather,
# and one instruction without metadata
HLO = """\
HloModule jit_step, entry_computation_layout={(f32[4,8]{1,0})->f32[4,8]{1,0}}

%fused_computation.1 (p0: bf16[8,16]) -> bf16[8,16] {
  %p0 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %mul.9 = bf16[8,16]{1,0:T(8,128)(2,1)} multiply(%p0, %p0), metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/mul" stack_frame_id=2}
}

%body.2 (arg: (s32[], bf16[8,16])) -> (s32[], bf16[8,16]) {
  %arg = (s32[]{:T(128)}, bf16[8,16]{1,0:T(8,128)(2,1)}) parameter(0)
  %gte.1 = bf16[8,16]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %fusion.1 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn_core/dot_general" stack_frame_id=3}
  %fusion.2 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attn_core/exp"}
  %fusion.3 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%fusion.2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/dot_general"}
  %copy.4 = bf16[8,16]{1,0:T(8,128)(2,1)} copy(%fusion.3)
  %fusion.5 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%copy.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp())/while/body/squeeze"}
  ROOT %tuple.1 = (s32[]{:T(128)}, bf16[8,16]{1,0:T(8,128)(2,1)}) tuple(%gte.0, %fusion.5)
}

ENTRY %main.3 (param.0: f32[4,8]) -> f32[4,8] {
  %param.0 = f32[4,8]{1,0:T(4,128)} parameter(0), metadata={op_name="params['embed']"}
  %while.1 = (s32[]{:T(128)}, bf16[8,16]{1,0:T(8,128)(2,1)}) while(%tuple.0), condition=%cond.1, body=%body.2, metadata={op_name="jit(step)/jvp()/while"}
  %fusion.6 = f32[4,8]{1,0:T(4,128)} fusion(%param.0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(head_loss)/jit(log_softmax)/reduce_max"}
  %psum.7 = f32[4,8]{1,0:T(4,128)} all-reduce(%fusion.6), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_1.2, metadata={op_name="jit(step)/shard_map/grad_reduce/psum"}
  %slice.1 = f32[2,8]{1,0:T(2,128)} slice(%param.0), slice={[0:2], [0:8]}
  %all-gather-start.1 = (f32[2,8]{1,0:T(2,128)}, f32[8,8]{1,0:T(8,128)}) all-gather-start(%slice.1), channel_id=2, dimensions={0}
  %all-gather-done.1 = f32[8,8]{1,0:T(8,128)} all-gather-done(%all-gather-start.1)
  %fusion.8 = f32[4,8]{1,0:T(4,128)} fusion(%psum.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/shard_map/grad_reduce/div"}
  ROOT %fusion.9 = f32[4,8]{1,0:T(4,128)} fusion(%param.0, %fusion.8), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/shard_map/update/sub"}
}
"""

# what the trace reducer hands over for TWO calls of that program:
# {"<instruction> <shape>": [self s, count, whole s]}
OPS = {
    "fusion.1 bf16[8,16]": [0.40, 4, 0.40],     # fwd attn_core     0.200
    "fusion.2 bf16[8,16]": [0.20, 4, 0.20],     # recompute attn    0.100
    "fusion.3 bf16[8,16]": [0.60, 4, 0.60],     # bwd mlp           0.300
    "copy.4 bf16[8,16]": [0.02, 4, 0.02],       # not named         0.010
    "fusion.5 bf16[8,16]": [0.04, 4, 0.04],     # bwd, no scope     0.020
    "while.1 s32[]": [0.06, 2, 1.32],           # fwd, no scope     0.030
    "fusion.6 f32[4,8]": [0.10, 2, 0.10],       # fwd head_loss     0.050
    "psum.7 f32[4,8]": [0.30, 2, 0.30],         # collective        0.150
    "all-gather-start.1 f32[2,8]": [0.01, 2, 0.01],   # collective  0.005
    "all-gather-done.1 f32[8,8]": [0.03, 2, 0.03],    # collective  0.015
    "fusion.8 f32[4,8]": [0.08, 2, 0.08],       # other grad_reduce 0.040
    "fusion.9 f32[4,8]": [0.16, 2, 0.16],       # other update      0.080
}


# a second step in miniature, with what the first has not: a chunked kernel
# under an inner name (``delta_rule``, inside the scope ``linattn_core``)
# whose loop over chunks encloses one so-named instruction and a
# ``collective-permute`` that the loop runs three times, the scope ``moe``,
# and an all-reduce after the loop
HLO2 = """\
HloModule jit_step2, entry_computation_layout={(f32[4,8]{1,0})->f32[4,8]{1,0}}

%fused_computation.1 (p0: f32[4,8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0:T(4,128)} parameter(0)
  ROOT %mul.9 = f32[4,8]{1,0:T(4,128)} multiply(%p0, %p0)
}

%body.3 (arg.3: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %arg.3 = (s32[]{:T(128)}, f32[4,8]{1,0:T(4,128)}) parameter(0)
  %gte.3 = f32[4,8]{1,0:T(4,128)} get-tuple-element(%arg.3), index=1
  %fusion.11 = f32[4,8]{1,0:T(4,128)} fusion(%gte.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(linattn_core)/delta_rule/while/body/mul"}
  %collective-permute-start.1 = (f32[4,8]{1,0:T(4,128)}, f32[4,8]{1,0:T(4,128)}) collective-permute-start(%fusion.11), channel_id=3, source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(step)/transpose(jvp())/while/body/grad_reduce/ppermute"}
  %collective-permute-done.1 = f32[4,8]{1,0:T(4,128)} collective-permute-done(%collective-permute-start.1)
  ROOT %tuple.3 = (s32[]{:T(128)}, f32[4,8]{1,0:T(4,128)}) tuple(%gte.0, %collective-permute-done.1)
}

%cond.3 (arg.4: (s32[], f32[4,8])) -> pred[] {
  %arg.4 = (s32[]{:T(128)}, f32[4,8]{1,0:T(4,128)}) parameter(0)
  %constant.3 = s32[]{:T(128)} constant(3)
  %gte.4 = s32[]{:T(128)} get-tuple-element(%arg.4), index=0
  ROOT %lt.1 = pred[]{:T(512)} compare(%gte.4, %constant.3), direction=LT, metadata={op_name="jit(step)/jvp(linattn_core)/delta_rule/while/cond/lt"}
}

ENTRY %main.9 (param.0: f32[4,8]) -> f32[4,8] {
  %param.0 = f32[4,8]{1,0:T(4,128)} parameter(0)
  %constant.0 = s32[]{:T(128)} constant(0)
  %copy.1 = s32[]{:T(128)} copy(%constant.0)
  %tuple.9 = (s32[]{:T(128)}, f32[4,8]{1,0:T(4,128)}) tuple(%copy.1, %param.0)
  %fusion.10 = f32[4,8]{1,0:T(4,128)} fusion(%param.0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(linattn_core)/conv_general_dilated"}
  %while.2 = (s32[]{:T(128)}, f32[4,8]{1,0:T(4,128)}) while(%tuple.9), condition=%cond.3, body=%body.3, metadata={op_name="jit(step)/jvp(linattn_core)/delta_rule/while"}, backend_config={"known_trip_count":{"n":"3"},"known_init_step":{"init":"0","step":"1"}}
  %fusion.12 = f32[4,8]{1,0:T(4,128)} fusion(%param.0), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(linattn_core))/delta_rule/dot_general"}
  %fusion.13 = f32[4,8]{1,0:T(4,128)} fusion(%fusion.12), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(moe))/dot_general"}
  ROOT %all-reduce.2 = f32[4,8]{1,0:T(4,128)} all-reduce(%fusion.13), channel_id=4, replica_groups={{0,1}}, to_apply=%region_1.2
}
"""

# two calls of it: the loop's whole duration holds its body's
OPS2 = {
    "fusion.10 f32[4,8]": [0.10, 2, 0.10],      # fwd linattn_core  0.050
    "while.2 s32[]": [0.04, 2, 0.70],           # fwd linattn_core  0.020
    "fusion.11 f32[4,8]": [0.60, 6, 0.60],      # fwd linattn_core  0.300
    "collective-permute-start.1 f32[4,8]": [0.02, 6, 0.02],   # coll. 0.010
    "collective-permute-done.1 f32[4,8]": [0.04, 6, 0.04],    # coll. 0.020
    "fusion.12 f32[4,8]": [0.20, 2, 0.20],      # bwd linattn_core  0.100
    "fusion.13 f32[4,8]": [0.30, 2, 0.30],      # bwd moe           0.150
    "all-reduce.2 f32[4,8]": [0.10, 2, 0.10],   # collective        0.050
}

# the delta rule's yardstick of the fake family: one sample needs 2e9
# operations (2 ms at the fake matrix peak) and 1e9 bytes (10 ms at the fake
# HBM peak: memory-bound); four samples a step against the 0.45 s a step of
# while.2 (0.70 whole, its body NOT again) and fusion.12 (0.20)
COST = (2e9, 1e9)


@pytest.mark.parametrize("op_name,phase,scope", [
    ("jit(step)/jvp()/while/body/closed_call/attn_core/dot_general",
     "fwd", "attn_core"),
    ("jit(step)/jvp(head_loss)/jit(log_softmax)/reduce_max",
     "fwd", "head_loss"),
    ("jit(step)/transpose(jvp(embed))/scatter-add", "bwd", "embed"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/norm/rsqrt", "recompute", "norm"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/"
     "dot_general", "bwd", "mlp"),
    ("jit(step)/shard_map/update/sub", "other", "update"),
    ("jit(step)/jvp()/while/body/closed_call/remat2", "fwd", "unscoped"),
    # jit(...) names a function, not a scope; a longer name is another name
    ("jit(update)/jit(norm)/mul", "other", "unscoped"),
    ("jit(step)/jvp()/mlp_extra/mul", "fwd", "unscoped"),
    ("", "other", "unscoped"),
])
def test_phase_and_scope_of_an_op_name(op_name, phase, scope):
    assert scope_reduce.phase_of(op_name) == phase
    assert scope_reduce.scope_of(op_name, SCOPES) == scope


def test_reduce_ops_against_hand_counts():
    table = scope_table(HLO)
    assert table["fusion.1"].endswith("attn_core/dot_general")
    assert "copy.4" not in table and "mul.9" in table
    opcodes = {n: v[0] for n, v in scope_reduce.instructions(HLO).items()}
    assert opcodes["psum.7"] == "all-reduce"
    assert opcodes["all-gather-done.1"] == "all-gather-done"
    out = scope_reduce.reduce_ops(OPS, table, opcodes, SCOPES, calls=2)
    assert out["phases"] == {
        "fwd": {"attn_core": pytest.approx(0.2),
                "head_loss": pytest.approx(0.05),
                "unscoped": pytest.approx(0.03)},
        "recompute": {"attn_core": pytest.approx(0.1)},
        "bwd": {"mlp": pytest.approx(0.3), "unscoped": pytest.approx(0.02)},
        "other": {"unscoped": pytest.approx(0.01),
                  "grad_reduce": pytest.approx(0.04),
                  "update": pytest.approx(0.08)}}
    assert out["collective_s"] == pytest.approx(0.17)
    assert out["unnamed_s"] == pytest.approx(0.01)
    assert out["busy_s"] == pytest.approx(1.0)
    # every operation lands in one cell: the parts add up to the busy time
    parts = sum(v for cell in out["phases"].values() for v in cell.values())
    assert parts + out["collective_s"] == pytest.approx(out["busy_s"])
    assert [r[0] for r in out["largest_unscoped"]] == [
        "while.1 s32[]", "fusion.5 bf16[8,16]", "copy.4 bf16[8,16]"]


def test_collective_bytes_count_each_instruction_once():
    # the all-reduce hands over its f32[4,8] operand, the async all-gather
    # its f32[2,8] operand at the -start; the -done hands over nothing new
    assert scope_reduce.collective_bytes(HLO) == {"all-reduce": 128,
                                                  "all-gather": 64}
    ins = scope_reduce.instructions(HLO)
    assert ins["fusion.9"] == ("fusion", 128, ["param.0", "fusion.8"])
    assert ins["while.1"][1] is None            # a tuple has no array size
    assert scope_reduce.collective_kind("collective-permute-done") \
        == "collective-permute"
    assert scope_reduce.collective_kind("fusion") is None


@pytest.mark.parametrize("how", ["known_trip_count", "condition", "neither"])
def test_collective_bytes_count_a_loops_instruction_by_its_trip_count(
        how, capsys):
    # the loop hands its f32[4,8] to the collective-permute three times a
    # run, the all-reduce after it once.  The trip count is the compiler's
    # own where the text has it (the CPU's), else read from the loop's
    # condition ``counter < 3`` and its start 0 (the TPU's text has no
    # known_trip_count); a loop that gives neither counts once, and says so
    text = HLO2
    if how != "known_trip_count":
        text = text.replace('"known_trip_count":{"n":"3"},', "")
    if how == "neither":
        text = text.replace("direction=LT", "direction=NE")
    trips = 1 if how == "neither" else 3
    runs = scope_reduce.executions(text)
    assert (runs["main.9"], runs["cond.3"], runs["body.3"]) == (1, 1, trips)
    assert runs["fused_computation.1"] == 3 + trips
    assert scope_reduce.collective_bytes(text) == {
        "collective-permute": trips * 128, "all-reduce": 128}
    assert ("bodies count once" in capsys.readouterr().out) \
        == (how == "neither")
    where, calls, entry = scope_reduce.structure(HLO2)
    assert entry == "main.9" and where["fusion.11"] == "body.3"
    assert calls["while.2"] == [("cond.3", 1), ("body.3", 3)]
    assert scope_reduce.enclosed_by(HLO2, {"while.2"}) >= {
        "fusion.11", "collective-permute-start.1", "mul.9", "lt.1"}
    assert "fusion.12" not in scope_reduce.enclosed_by(HLO2, {"while.2"})
    # a counter that starts at 1 runs the body twice
    late = text.replace("constant(0)", "constant(1)")
    if how == "condition":
        assert scope_reduce.executions(late)["body.3"] == 2


# ------------------------------------------------ the readers on a run --

class _Program:
    def __init__(self, text=HLO):
        self.text = text

    def hlo_text(self):
        return self.text


def _fake_run(reduction, monkeypatch, program=_Program()):
    """A run and a result as the readers see them, with the catalog of step
    programs answering with the hand-written text."""
    cleared = []
    monkeypatch.setattr(jax, "clear_caches", lambda: cleared.append(1))
    monkeypatch.setattr(
        scope_reduce, "_lm_program",
        lambda: program and (program, SCOPES, scope_table))
    run = types.SimpleNamespace(
        trace=types.SimpleNamespace(reduction=reduction), t_process=0.0,
        setup_s=0.0, peaks=rules.PEAKS, config={}, workload={"seq": 16},
        family=types.SimpleNamespace(delta_rule_cost=lambda cfg, seq: COST))
    result = types.SimpleNamespace(
        window={"calls": 2, "samples": 8, "chips": 1}, end_to_end={})
    return run, result, cleared


def _read(metric, run, result):
    return harness.load_module("layer_metrics", metric).read(run, result)


#: what each reader of the device trace reads on the first module's two
#: calls, and — where that module has nothing for it — on the second's
WANT = {"fwd_ms.train": 280.0, "recompute_ms.train": 100.0,
        "bwd_ms.train": 320.0, "attn_core_ms.train": 300.0,
        "update_ms.train": 120.0, "unscoped_share.train": 6.0,
        "collective_ms.train": 170.0}
WANT2 = {"linattn_core_ms.train": 470.0, "moe_ms.train": 150.0,
         "collective_ms.train": 80.0,
         "linattn_roofline.train": 100 * 0.010 * 4 / 0.45,
         "fwd_ms.train": 370.0, "bwd_ms.train": 250.0,
         "unscoped_share.train": 0.0}
#: the readers this file has hand counts for; a later PR's reader brings its
#: hand count in a test file of its own
DEVICE_READERS = sorted(set(WANT) | set(WANT2))


def test_the_hand_counts_are_of_listed_readers_of_the_device_trace():
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    assert all(by_name[m]["source"] == "device_trace" for m in DEVICE_READERS)


@pytest.mark.parametrize("metric", DEVICE_READERS)
def test_device_reader_is_silent_without_a_trace_and_right_with_one(
        metric, monkeypatch):
    for text, ops, want in ((HLO, OPS, WANT), (HLO2, OPS2, WANT2)):
        prog = _Program(text)
        run, result, cleared = _fake_run(None, monkeypatch, prog)
        assert _read(metric, run, result) is None and not cleared
        run, result, cleared = _fake_run({"ops": ops}, monkeypatch, prog)
        # a module with nothing for the reader: nothing, never a 0
        assert _read(metric, run, result) == (
            pytest.approx(want[metric]) if metric in want else None)
        # the step is compiled anew for its names once per run, whatever
        # the number of readers (see scope_reduce.step_hlo)
        for other in DEVICE_READERS:
            _read(other, run, result)
        assert cleared == [1]
        # a program without the catalog: nothing, no error
        run, result, _ = _fake_run({"ops": ops}, monkeypatch, program=None)
        assert _read(metric, run, result) is None


def test_a_family_without_the_kernel_gives_its_roofline_reader_nothing(
        monkeypatch):
    run, result, _ = _fake_run({"ops": OPS2}, monkeypatch, _Program(HLO2))
    run.family = types.SimpleNamespace()
    assert _read("linattn_roofline.train", run, result) is None
    # and a kernel's share is taken over the loop ONCE: counting the body
    # again (0.60 more over two calls) would read 5.33 %, not 8.89 %
    run, result, _ = _fake_run({"ops": OPS2}, monkeypatch, _Program(HLO2))
    assert scope_reduce.inner_whole_s(run, result, "delta_rule") \
        == pytest.approx(0.45)


def test_the_parts_add_up_to_the_busy_time(monkeypatch):
    run, result, _ = _fake_run({"ops": OPS}, monkeypatch)
    out = scope_reduce.by_phase_and_scope(run, result)
    other = out["phases"]["other"]
    rest = sum(v for k, v in other.items()
               if k not in ("update", "grad_reduce"))
    total = sum(_read(m, run, result) for m in
                ("fwd_ms.train", "recompute_ms.train", "bwd_ms.train",
                 "update_ms.train")) + 1e3 * (rest + out["collective_s"])
    assert total == pytest.approx(1e3 * out["busy_s"])


@pytest.fixture(scope="module")
def toy_run():
    """The new cell's toy twin, untraced, on two virtual CPU devices: mesh
    [2, 1, 1] has a gradient all-reduce."""
    cell = {w["name"]: w for w in MAN["workloads"]}[DP4]
    return rules.toy_run(cell, harness.CompileMeter(), seconds=0.5,
                         seed=2**31 + 11)


def test_program_dispatch_reads_the_programs_own_spans(toy_run):
    from distlearn_tpu import obs
    run, result = toy_run
    got = _read("program_dispatch_ms.train", run, result)
    assert got is not None and got > 0
    # the program's span sits inside the benchmark's own clock around the
    # same call, so it cannot read longer
    assert got <= _read("dispatch_ms.train", run, result)
    inside = [s for s in obs.spans() if s["name"] == "train.dispatch"
              and s["t0"] >= run.t_process + run.setup_s]
    # the window's calls, and the one the kind makes after it on the
    # check's batch, which the reader leaves out
    assert len(inside) == result.window["calls"] + 1
    assert sum(s["t0"] <= run.t_process + run.closed_s for s in inside) \
        == result.window["calls"]
    # a window that starts after every span: nothing to read
    late = types.SimpleNamespace(t_process=time.perf_counter(), setup_s=0.0,
                                 closed_s=float("inf"))
    assert scope_reduce.dispatch_spans_ms(late, "train.dispatch",
                                          step="lm") is None


def test_collective_mb_counts_the_gradient_all_reduce(toy_run):
    run, result = toy_run
    got = _read("collective_mb.train", run, result)
    text = result.window["step_hlo"]
    assert "all-reduce" in text
    by_kind = scope_reduce.collective_bytes(text)
    assert got == pytest.approx(sum(by_kind.values()) / 1e6) and got > 0
    # every parameter's gradient crosses the data axis once, in float32
    # (the CPU compiler also keeps the activation psums over the seq and
    # model axes of size one, which the TPU compiler drops: hence a bound)
    n_params = result.window["params"]
    assert 4 * n_params <= by_kind["all-reduce"] < 8 * n_params
    # and the untraced run gave the device readers nothing to read
    for metric in DEVICE_READERS:
        assert _read(metric, run, result) is None


def test_result_line_of_the_new_cell_in_a_traced_run(toy_run, monkeypatch):
    """What ``run.py`` prints for the four-chip cell with ``--trace 1``,
    as far as the CPU can show it: every reader listed for the cell is
    found by name and the two program-side ones report."""
    run, result = toy_run
    monkeypatch.setattr(run.trace, "enabled", True)
    line = json.loads(json.dumps(bench_run.result_line(MAN, run, result)))
    assert {"program_dispatch_ms.train", "collective_mb.train",
            "dispatch_ms.train", "step_ms.train", "mfu.train",
            "compile_s"} <= set(line["metrics"])
    assert not set(DEVICE_READERS) & set(line["metrics"])
    assert line["metrics"]["collective_mb.train"]["unit"] == "MB"


# ------------------------------------------------------ the cell's data --

def test_dp4_cell_is_the_one_chip_cell_on_four_chips():
    cell, config, workload = bench_run.resolve(MAN, DP4)
    one_cell, one_config, one = bench_run.resolve(MAN, ONE)
    assert cell["chips"] == 4 and one_cell["chips"] == 1
    assert config == one_config and cell["config"] == one_cell["config"]
    differs = {k for k in set(one) | set(workload)
               if one.get(k) != workload.get(k)}
    assert differs == {"mesh", "global_batch"}
    assert workload["mesh"] == [4, 1, 1]
    # the one-chip cell's work per chip, so the rates compare as scaling
    assert workload["global_batch"] == 4 * one["global_batch"]


def test_sources_of_the_program_side_readers_and_who_lists_what():
    """Which reader a cell lists follows from what it RUNS, not from where
    it stands in ``workloads``: every training cell lists the pass / scope
    readers, every cell that spans chips the readers of collectives."""
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    assert by_name["collective_mb.train"]["source"] == "program_counter"
    assert by_name["program_dispatch_ms.train"]["source"] == "program_span"
    cells = {w["name"]: w for w in MAN["workloads"]}
    train = {c for c in cells if rules.kind_of(c) == "train_lm"}
    for metric in WANT:
        if not metric.startswith("collective"):
            assert set(by_name[metric]["workloads"]) >= train, metric
    for metric in ("collective_mb.train", "collective_ms.train"):
        assert by_name[metric]["moves"] == "train_samples_per_s"
        assert {c for c in train if cells[c]["chips"] == 4} \
            <= set(by_name[metric]["workloads"])
