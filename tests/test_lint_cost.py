"""DL2xx fixtures: every cost/budget rule has a known-bad step that fires
and a known-good step that stays quiet, on the 8-device CPU mesh.

The firing fixtures are the real failure modes the rules exist for: a
mis-sharded matmul whose operand GSPMD must rematerialize with a
replication all-gather (DL201), a sharded in-spec that compiles to a
replicated parameter (DL202), stale budget lockfiles (DL203-DL205), and
the serve-path rules — a donation the compiled program can't use / a
pool left undonated (DL206), an unbudgeted extra lowering or a
dtype-drift retrace (DL207), an entry-parameter relayout over budget
(DL208), and host-side tensor math in the per-tick loop (DL209).
"""

import copy
import warnings

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlearn_tpu.lint import budget as budget_mod
from distlearn_tpu.lint import cost as cost_mod
from jax import shard_map

pytestmark = pytest.mark.lint

BIG = (1024, 1024)            # f32: 4 MiB, comfortably over the 1 MiB bar


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def _big_args():
    return (jax.ShapeDtypeStruct(BIG, "float32"),
            jax.ShapeDtypeStruct((8, BIG[0]), "float32"))


# ---------------------------------------------------------------- DL201 --

def test_dl201_fires_on_replication_gather(devices):
    """A replication constraint on a sharded 4 MiB operand forces GSPMD to
    insert an all-gather the jaxpr never asked for."""
    mesh = _mesh()
    repl = NamedSharding(mesh, P())

    def f(w, x):
        return x @ jax.lax.with_sharding_constraint(w, repl)

    fn = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)), repl))
    report, findings = cost_mod.analyze_step(fn, _big_args(), mesh=mesh,
                                             name="bad_gather")
    assert any(f.rule == "DL201" for f in findings), findings
    assert report.bytes_by_kind.get("all-gather", 0) >= 1 << 22
    assert report.bytes_by_axis.get("all-gather@data", 0) >= 1 << 22


def test_dl201_quiet_below_threshold(devices):
    """The same replication pattern on a small operand is GSPMD doing its
    job, not a hot-path regression."""
    mesh = _mesh()
    repl = NamedSharding(mesh, P())

    def f(w, x):
        return x @ jax.lax.with_sharding_constraint(w, repl)

    fn = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)), repl))
    args = (jax.ShapeDtypeStruct((64, 64), "float32"),
            jax.ShapeDtypeStruct((8, 64), "float32"))
    _, findings = cost_mod.analyze_step(fn, args, mesh=mesh,
                                        name="small_gather")
    assert not [f for f in findings if f.rule == "DL201"]


def test_dl201_quiet_for_explicit_gather(devices):
    """An all-gather the author wrote (jaxpr-level ``all_gather``) is
    budgeted traffic, not an inserted one — even far over the threshold."""
    mesh = _mesh()

    def f(w):
        return jax.lax.all_gather(w, "data", axis=0, tiled=True)

    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("data", None),),
                           out_specs=P(), check_vma=False))
    w = jax.ShapeDtypeStruct(BIG, "float32")
    report, findings = cost_mod.analyze_step(fn, (w,), mesh=mesh,
                                             name="explicit_gather")
    assert report.bytes_by_kind.get("all-gather", 0) >= 1 << 22
    assert not [f for f in findings if f.rule == "DL201"]


# ---------------------------------------------------------------- DL202 --

def test_dl202_fires_when_sharding_lost(devices):
    """jit without in_shardings + a replicated output constraint: sharding
    propagation replicates the 4 MiB parameter the in-spec declared
    sharded."""
    mesh = _mesh()
    repl = NamedSharding(mesh, P())

    def g(w, x):
        return jax.lax.with_sharding_constraint(x @ w, repl)

    _, findings = cost_mod.analyze_step(
        jax.jit(g), _big_args(), mesh=mesh, name="lost_sharding",
        in_specs=(P("data", None), P()))
    assert any(f.rule == "DL202" for f in findings), findings


def test_dl202_quiet_when_sharding_honored(devices):
    """Pinning the same spec through jit in_shardings keeps the parameter
    sharded (contraction-dim partial matmul + all-reduce) — quiet."""
    mesh = _mesh()
    repl = NamedSharding(mesh, P())

    def g(w, x):
        return jax.lax.with_sharding_constraint(x @ w, repl)

    fn = jax.jit(g, in_shardings=(NamedSharding(mesh, P("data", None)), repl))
    report, findings = cost_mod.analyze_step(
        fn, _big_args(), mesh=mesh, name="kept_sharding",
        in_specs=(P("data", None), P()))
    assert not [f for f in findings if f.rule == "DL202"]
    # the sharded matmul reduces partial products instead of gathering
    assert report.bytes_by_kind.get("all-reduce", 0) > 0


# ----------------------------------------------------- DL203/DL204/DL205 --

@pytest.fixture(scope="module")
def step_report():
    """One real psum step compiled once, reused by every budget fixture."""
    mesh = _mesh()

    def f(p, g):
        return p - 0.1 * jax.lax.psum(g, "data")

    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), P("data")),
                           out_specs=P(), check_vma=False))
    args = (jax.ShapeDtypeStruct((1, 256), "float32"),
            jax.ShapeDtypeStruct((8, 256), "float32"))
    report, findings = cost_mod.analyze_step(fn, args, mesh=mesh,
                                             name="psum_step")
    assert not findings
    assert report.bytes_by_kind.get("all-reduce", 0) > 0
    return report


def test_budget_roundtrip_quiet(step_report, tmp_path):
    """Fresh lockfile -> reload -> compare: in budget, no findings."""
    reports = {"psum_step": step_report}
    budget_mod.save_budget("fx", reports, budget_dir=str(tmp_path))
    assert budget_mod.check_family("fx", reports,
                                   budget_dir=str(tmp_path)) == []


def test_dl203_fires_without_lockfile(step_report, tmp_path):
    findings = budget_mod.check_family("fx", {"psum_step": step_report},
                                       budget_dir=str(tmp_path))
    assert [f.rule for f in findings] == ["DL203"]
    assert "no committed budget lockfile" in findings[0].message


def test_dl203_fires_on_stale_bytes(step_report):
    stale = {"tolerance": dict(budget_mod.DEFAULT_TOLERANCE),
             "units": {"psum_step": {
                 "collective_bytes": {"all-reduce": 1},
                 "collective_ops": dict(step_report.ops_by_kind),
                 "peak_bytes": step_report.peak_bytes}}}
    findings = budget_mod.check_family("fx", {"psum_step": step_report},
                                       budget=stale)
    assert [f.rule for f in findings] == ["DL203"]
    assert "exceeds the committed" in findings[0].message


def test_dl203_fires_on_new_collective_kind(step_report):
    stale = {"units": {"psum_step": {
        "collective_bytes": {},       # lockfile predates any traffic
        "collective_ops": dict(step_report.ops_by_kind),
        "peak_bytes": step_report.peak_bytes}}}
    findings = budget_mod.check_family("fx", {"psum_step": step_report},
                                       budget=stale)
    assert [f.rule for f in findings] == ["DL203"]
    assert "appeared" in findings[0].message


def test_dl203_fires_on_unknown_unit(step_report):
    findings = budget_mod.check_family("fx", {"renamed": step_report},
                                       budget={"units": {}})
    assert [f.rule for f in findings] == ["DL203"]
    assert "not in the committed budget lockfile" in findings[0].message


def test_dl204_fires_on_peak_regression(step_report):
    assert step_report.peak_bytes, "CPU backend stopped reporting memory"
    stale = {"units": {"psum_step": {
        "collective_bytes": dict(step_report.bytes_by_kind),
        "collective_ops": dict(step_report.ops_by_kind),
        "peak_bytes": 1}}}
    findings = budget_mod.check_family("fx", {"psum_step": step_report},
                                       budget=stale)
    assert [f.rule for f in findings] == ["DL204"]


def test_dl205_fires_on_op_count_regression(step_report):
    stale = {"units": {"psum_step": {
        "collective_bytes": dict(step_report.bytes_by_kind),
        "collective_ops": {},          # fusion used to leave zero ops
        "peak_bytes": step_report.peak_bytes}}}
    findings = budget_mod.check_family("fx", {"psum_step": step_report},
                                       budget=stale)
    assert [f.rule for f in findings] == ["DL205"]


def test_budgets_quiet_on_growth_within_tolerance(step_report):
    """Numbers inside the committed tolerance band do not fire."""
    entry = {"collective_bytes": {
        k: int(v / 1.1) for k, v in step_report.bytes_by_kind.items()},
        "collective_ops": dict(step_report.ops_by_kind),
        "peak_bytes": int(step_report.peak_bytes / 1.1)}
    budget = {"tolerance": dict(budget_mod.DEFAULT_TOLERANCE),
              "units": {"psum_step": copy.deepcopy(entry)}}
    assert budget_mod.check_family("fx", {"psum_step": step_report},
                                   budget=budget) == []


# ------------------------------------------------------------ HLO parser --

def test_parse_collectives_tuple_iota_and_pairs():
    """Tuple shapes, iota-form replica groups, and permute pairs all parse
    and attribute to the right mesh axes."""
    hlo = """
  %ar = (f32[16]{0}, f32[8]{0}) all-reduce(%a, %b), replica_groups=[2,4]<=[8], to_apply=%add
  %ag = bf16[32,4]{1,0} all-gather(bf16[4,4]{1,0} %p), channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %cp = f32[4]{0} collective-permute(f32[4]{0} %x), source_target_pairs={{0,1},{1,2},{2,3}}
  %done = f32[4]{0} all-reduce-done(f32[4]{0} %h)
"""
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("a", "b"))
    ops = cost_mod.parse_collectives(hlo, mesh)
    assert [op.kind for op in ops] == ["all-reduce", "all-gather",
                                      "collective-permute"]
    ar, ag, cp = ops
    assert ar.bytes == (16 + 8) * 4
    assert ar.axes == ("b",)          # [2,4]<=[8]: rows of 4 along axis b
    assert ag.bytes == 32 * 4 * 2
    assert ag.axes == ("b",)
    assert cp.bytes == 16
    assert cp.axes == ("b",)


def test_parse_collectives_async_start_counts_once():
    hlo = """
  %s = f32[64]{0} all-gather-start(f32[8]{0} %p), replica_groups=[1,8]<=[8], dimensions={0}
  %d = f32[64]{0} all-gather-done(f32[64]{0} %s)
"""
    mesh = _mesh()
    ops = cost_mod.parse_collectives(hlo, mesh)
    assert len(ops) == 1
    assert ops[0].kind == "all-gather"
    assert ops[0].axes == ("data",)


# ---------------------------------------------------------------- DL206 --

BIG_POOL = (256, 256)         # f32: 256 KiB, over DONATION_BYTES_THRESHOLD


def test_dl206_fires_on_wasted_donation(devices):
    """Donating a buffer the program's outputs can't absorb (no
    shape/dtype match) invalidates the caller's copy for nothing."""
    fn = jax.jit(lambda y: jax.numpy.zeros((64,), "float32"),
                 donate_argnums=(0,))
    args = (jax.ShapeDtypeStruct(BIG_POOL, "float32"),)
    with warnings.catch_warnings():
        # jax itself warns 'Some donated buffers were not usable' — that
        # warning is exactly the condition DL206 turns into a gate
        warnings.simplefilter("ignore")
        _, findings = cost_mod.analyze_step(fn, args, name="wasted",
                                            donation=True)
    dl = [f for f in findings if f.rule == "DL206"]
    assert len(dl) == 1, findings
    assert "declared donated" in dl[0].message


def test_dl206_fires_on_missing_donation(devices):
    """A 256 KiB in-place update without donation holds input AND output
    buffers live — the KV-pool footprint doubler."""
    fn = jax.jit(lambda s: s + 1.0)
    args = (jax.ShapeDtypeStruct(BIG_POOL, "float32"),)
    _, findings = cost_mod.analyze_step(fn, args, name="undonated",
                                        donation=True)
    dl = [f for f in findings if f.rule == "DL206"]
    assert len(dl) == 1, findings
    assert "not donated" in dl[0].message


def test_dl206_quiet_when_donation_aliases(devices):
    fn = jax.jit(lambda s: s + 1.0, donate_argnums=(0,))
    args = (jax.ShapeDtypeStruct(BIG_POOL, "float32"),)
    _, findings = cost_mod.analyze_step(fn, args, name="donated",
                                        donation=True)
    assert not [f for f in findings if f.rule == "DL206"], findings


def test_dl206_quiet_below_threshold(devices):
    """Small bookkeeping buffers (lens, cursors) shape-matching an output
    are not worth a donation — the missing arm has a size floor."""
    fn = jax.jit(lambda s: s + 1)
    args = (jax.ShapeDtypeStruct((4,), "int32"),)
    _, findings = cost_mod.analyze_step(fn, args, name="lens",
                                        donation=True)
    assert not [f for f in findings if f.rule == "DL206"], findings


def test_dl206_needs_opt_in(devices):
    """Training-family callers never asked for the donation audit —
    the default analyze_step stays DL206-silent."""
    fn = jax.jit(lambda s: s + 1.0)
    args = (jax.ShapeDtypeStruct(BIG_POOL, "float32"),)
    _, findings = cost_mod.analyze_step(fn, args, name="train_step")
    assert not [f for f in findings if f.rule == "DL206"], findings


# ---------------------------------------------------------------- DL207 --

def _rep(name, sig):
    return cost_mod.CostReport(name=name, signature=sig, compile_s=0.25)


def test_audit_compiles_counts_distinct_lowerings():
    reports = {
        "prefill[8]": _rep("prefill[8]", (("float32", False, "(8,)"),)),
        "prefill[16]": _rep("prefill[16]", (("float32", False, "(16,)"),)),
        "tick": _rep("tick", (("float32", False, "(4,)"),)),
    }
    findings, summary = cost_mod.audit_compiles("decode", reports)
    assert findings == []
    assert summary["count"] == 3
    assert summary["warmup_s_estimate"] == pytest.approx(0.75)


def test_dl207_fires_on_signature_drift():
    """Two buckets lowering the same shapes under different dtypes is one
    logical program paying two compiles."""
    reports = {
        "prefill[8]": _rep("prefill[8]", (("float32", False, "(8,)"),)),
        "prefill[8]x": _rep("prefill[8]x", (("bfloat16", False, "(8,)"),)),
    }
    findings, summary = cost_mod.audit_compiles("decode", reports)
    assert [f.rule for f in findings] == ["DL207"]
    assert "dtype/weak-type" in findings[0].message
    assert summary["count"] == 2


def test_dl207_fires_on_unbudgeted_compile_count(step_report):
    """An extra lowering beyond the committed compile count fails the
    gate — the new-prefill-bucket acceptance case."""
    budget = {"units": {"psum_step": step_report.to_json()},
              "compiles": {"count": 0}}
    findings = budget_mod.check_family("fx", {"psum_step": step_report},
                                       budget=budget)
    assert [f.rule for f in findings] == ["DL207"]
    assert "distinct programs" in findings[0].message


def test_dl207_quiet_at_committed_count_and_without_key(step_report):
    budget = {"units": {"psum_step": step_report.to_json()},
              "compiles": {"count": 1}}
    assert budget_mod.check_family("fx", {"psum_step": step_report},
                                   budget=budget) == []
    # pre-DL207 lockfiles have no 'compiles' key: the gate must skip,
    # not fire, so old trees keep linting while they re-baseline
    legacy = {"units": {"psum_step": step_report.to_json()}}
    assert budget_mod.check_family("fx", {"psum_step": step_report},
                                   budget=legacy) == []


def test_save_budget_commits_compile_count(step_report, tmp_path):
    budget_mod.save_budget("fx", {"psum_step": step_report},
                           budget_dir=str(tmp_path))
    committed = budget_mod.load_budget("fx", budget_dir=str(tmp_path))
    assert committed["compiles"] == {"count": 1}


# ---------------------------------------------------------------- DL208 --

_RELAYOUT_HLO = """
%fused_computation {
  %param_0 = f32[8,4]{1,0} parameter(0)
  %t.1 = f32[4,8]{1,0} transpose(f32[8,4]{1,0} %param_0), dimensions={1,0}
  ROOT %r = f32[4,8]{1,0} negate(f32[4,8]{1,0} %t.1)
}

ENTRY %main.1 (p0: f32[8,4], p1: f32[16]) -> f32[4,8] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = f32[16]{0} parameter(1)
  %copy.2 = f32[8,4]{0,1} copy(f32[8,4]{1,0} %p0)
  %other = f32[16]{0} negate(f32[16]{0} %p1)
  %t.9 = f32[4,8]{1,0} transpose(f32[8,4]{0,1} %copy.2), dimensions={1,0}
  ROOT %out = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %t.9), kind=kLoop, calls=%fused_computation
}
"""


def test_count_entry_relayouts_scans_entry_only():
    """The entry param's copy counts; the fusion region's transpose of
    its OWN parameter(0) does not — region params say nothing about the
    entry layout contract."""
    assert cost_mod.count_entry_relayouts(_RELAYOUT_HLO) == 1
    assert cost_mod.count_entry_relayouts("no entry here") == 0


def test_dl208_fires_over_committed_relayouts(step_report):
    entry = step_report.to_json()
    assert entry["relayout_ops"] == step_report.relayout_ops
    drifted = copy.deepcopy(step_report)
    drifted.relayout_ops = (step_report.relayout_ops or 0) + 2
    findings = budget_mod.check_family(
        "fx", {"psum_step": drifted},
        budget={"units": {"psum_step": entry}})
    assert [f.rule for f in findings] == ["DL208"]
    assert "relayout" in findings[0].message


def test_dl208_quiet_at_committed_count(step_report):
    budget = {"units": {"psum_step": step_report.to_json()}}
    assert budget_mod.check_family("fx", {"psum_step": step_report},
                                   budget=budget) == []


# ---------------------------------------------------------------- DL209 --

_HOT_LOOP_SRC = '''
class Scheduler:
    def tick(self):
        probs = np.exp(self.logits)          # host softmax: flagged
        score = self.a @ self.b              # host matmul: flagged
        idx = np.flatnonzero(self.free)      # bookkeeping: exempt
        fn = lambda v: np.exp(v)             # not executed per tick
        def prefill(p, x):                   # staged program body: exempt
            return jnp.softmax(x @ p)
        return idx

    def helper(self):
        return np.exp(self.x)                # not a hot method: exempt
'''


def test_dl209_fires_on_host_tensor_math():
    findings = cost_mod.lint_tick_loop([(_HOT_LOOP_SRC, "fx.sched")])
    assert [f.rule for f in findings] == ["DL209", "DL209"]
    assert "np.exp" in findings[0].message
    assert "matrix multiply" in findings[1].message
    assert findings[0].where.startswith("fx.sched.Scheduler.tick:")


def test_dl209_quiet_on_real_serve_loop():
    """The shipped engine/scheduler tick paths are bookkeeping-only —
    the default-target pass returns nothing."""
    assert cost_mod.lint_tick_loop() == []
