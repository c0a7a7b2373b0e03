"""Named step-function families for distlint.

Each :class:`Entry` knows how to build one family's step functions on a
small mesh over the *available* devices and lint every one of them.  The
registry is what ``tools/distlint.py --family sgd`` and the tier-1 gate
test iterate over, so adding a builder here is how a new train step opts
into CI linting.

Builders return :class:`Unit` objects.  A unit that carries its jitted
callable (``fn``/``args``/``mesh``) additionally goes through the static
cost model (:mod:`distlearn_tpu.lint.cost`): the step is compiled on the
mesh, its post-fusion collective traffic and peak memory are extracted,
and the result is checked against the family's committed budget lockfile
(:mod:`distlearn_tpu.lint.budget`, rules DL201-DL205).  Host-protocol
units (no compilable step) carry ``fn=None`` and skip the cost pass.

Callers must provide >= :data:`MIN_DEVICES` devices (the test conftest and
the CLI both force 8 virtual CPU devices before jax initialises).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

from distlearn_tpu.lint.core import Finding, LintResult, filter_suppressed

__all__ = ["Entry", "Unit", "MIN_DEVICES", "families", "run_family",
           "run_family_costed", "run_all"]

MIN_DEVICES = 8


@dataclass
class Unit:
    """One lintable unit: findings plus (optionally) the compilable step."""

    name: str
    findings: list[Finding] = field(default_factory=list)
    fn: Callable | None = None
    args: tuple = ()
    mesh: Any = None
    in_specs: Any = None     # pytree of PartitionSpecs matching args (DL202)
    donation: bool = False   # run the DL206 donation audit on this unit
    info: dict = field(default_factory=dict)  # analysis metadata
    # (state counts, ...) surfaced on the LintResult / in --format json


@dataclass(frozen=True)
class Entry:
    name: str
    description: str
    run: Callable[[], list[Unit]]


def _mnist_setup(num_nodes=2):
    import jax
    from jax import random
    from distlearn_tpu.models import mnist_cnn
    from distlearn_tpu.parallel.mesh import MeshTree
    tree = MeshTree(num_nodes=num_nodes)
    model = mnist_cnn()
    return jax, random, model, tree


def _lint_units(units, mesh) -> list[Unit]:
    """Lint ``(name, fn, args)`` triples into step-carrying Units."""
    from distlearn_tpu.lint.spmd import lint_step
    return [Unit(n, lint_step(f, a, mesh=mesh, name=n),
                 fn=f, args=tuple(a), mesh=mesh)
            for n, f, a in units]


def _sgd_family():
    jax, random, model, tree = _mnist_setup()
    from distlearn_tpu.train import (build_eval_step, build_sgd_scan_step,
                                     build_sgd_step, build_sync_step,
                                     init_train_state)
    ts = init_train_state(model, tree, random.PRNGKey(0), 10)
    x = jax.ShapeDtypeStruct((8, 32, 32, 1), "float32")
    y = jax.ShapeDtypeStruct((8,), "int32")
    xs = jax.ShapeDtypeStruct((3, 8, 32, 32, 1), "float32")
    ys = jax.ShapeDtypeStruct((3, 8), "int32")
    units = [
        ("sgd_step", build_sgd_step(model, tree, lr=0.1), (ts, x, y)),
        ("sgd_scan_step", build_sgd_scan_step(model, tree, lr=0.1),
         (ts, xs, ys)),
        ("sync_step", build_sync_step(tree), (ts,)),
        ("eval_step", build_eval_step(model, tree),
         (ts.params, ts.model_state, ts.cm, x, y)),
    ]
    return _lint_units(units, tree.mesh)


def _ea_family():
    jax, random, model, tree = _mnist_setup()
    from distlearn_tpu.train import (build_ea_cycle, build_ea_steps,
                                     init_ea_state)
    ts = init_ea_state(model, tree, random.PRNGKey(0), 10)
    x = jax.ShapeDtypeStruct((8, 32, 32, 1), "float32")
    y = jax.ShapeDtypeStruct((8,), "int32")
    xs = jax.ShapeDtypeStruct((4, 8, 32, 32, 1), "float32")
    ys = jax.ShapeDtypeStruct((4, 8), "int32")
    local_step, ea_round = build_ea_steps(model, tree, lr=0.1, alpha=0.5)
    cycle = build_ea_cycle(model, tree, lr=0.1, alpha=0.5)
    units = [
        ("ea_local_step", local_step, (ts, x, y)),
        ("ea_round", ea_round, (ts,)),
        ("ea_cycle", cycle, (ts, xs, ys)),
    ]
    return _lint_units(units, tree.mesh)


def _lm_family():
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.train import build_lm_step
    dp, sp, tp = 2, 2, 2
    mesh = Mesh(np.array(jax.devices()[:dp * sp * tp]).reshape(dp, sp, tp),
                ("data", "seq", "model"))
    L = 16 * sp
    model = transformer_lm(vocab=32, dim=32, depth=2, heads=4, max_len=L)
    params, _ = model.init(jax.random.PRNGKey(0))
    step = build_lm_step(model, mesh, params, lr=0.1)
    tokens = jax.ShapeDtypeStruct((2 * dp, L), "int32")
    return _lint_units([("lm_step", step, (params, tokens))], mesh)


def _lm_mixed_family():
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.train import build_lm_mixed_step, init_lm_mixed_state
    dp, sp, tp = 2, 2, 2
    mesh = Mesh(np.array(jax.devices()[:dp * sp * tp]).reshape(dp, sp, tp),
                ("data", "seq", "model"))
    L = 16 * sp
    model = transformer_lm(vocab=32, dim=32, depth=2, heads=4, max_len=L)
    params, _ = model.init(jax.random.PRNGKey(0))
    st = init_lm_mixed_state(params)
    # Default grad_dtype=f32 upcasts bf16 grads BEFORE the psum — the
    # DL004-clean scheme.
    step = build_lm_mixed_step(model, mesh, params, lr=0.1)
    tokens = jax.ShapeDtypeStruct((2 * dp, L), "int32")
    return _lint_units([("lm_mixed_step", step, (st, tokens))], mesh)


def _pp_family():
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.train import (build_lm_pp_1f1b_step, build_lm_pp_step,
                                     stack_blocks)
    depth = 2
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "pipe"))
    model = transformer_lm(vocab=64, dim=32, depth=depth, heads=2, max_len=16)
    params, _ = model.init(jax.random.PRNGKey(0))
    shared, stacked = stack_blocks(params, depth)
    tokens = jax.ShapeDtypeStruct((8, 16), "int32")
    units = [
        ("lm_pp_step", build_lm_pp_step(mesh, shared, stacked, lr=0.1,
                                        num_microbatches=2),
         (shared, stacked, tokens)),
        ("lm_pp_1f1b_step", build_lm_pp_1f1b_step(mesh, shared, stacked,
                                                  lr=0.1,
                                                  num_microbatches=2),
         (shared, stacked, tokens)),
    ]
    return _lint_units(units, mesh)


def _optax_family():
    jax, random, model, tree = _mnist_setup()
    import optax
    from distlearn_tpu.train import (build_optax_step,
                                     build_zero_optax_step,
                                     init_optax_state, init_zero_state)
    tx = optax.sgd(0.1, momentum=0.9)
    ts = init_optax_state(model, tree, tx, random.PRNGKey(0), 10)
    step = build_optax_step(model, tree, tx)
    adam = optax.adam(1e-3)
    zts = init_zero_state(model, tree, adam, random.PRNGKey(0), 10)
    zstep = build_zero_optax_step(model, tree, adam)
    x = jax.ShapeDtypeStruct((8, 32, 32, 1), "float32")
    y = jax.ShapeDtypeStruct((8,), "int32")
    units = [
        ("optax_step", step, (ts, x, y)),
        ("zero_optax_step", zstep, (zts, x, y)),
    ]
    return _lint_units(units, tree.mesh)


def _ep_family():
    """MoE expert-parallel step: all-to-all dispatch/return over the
    ``expert`` axis plus a psum'd replicated-router update — the
    registry's only all-to-all traffic, so the cost lockfile pins it."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    from distlearn_tpu.parallel.ep import moe_ffn
    from jax import shard_map
    E, N, D = MIN_DEVICES, 16, 32
    mesh = Mesh(np.array(jax.devices()[:E]), ("expert",))

    def expert(p, h):
        return jnp.tanh(h @ p)

    def fwd(params, x_all):
        ep_w = jnp.squeeze(params["experts"], 0)   # this device's expert
        x = jnp.squeeze(x_all, 0)
        y = moe_ffn(expert, ep_w, params["router"], x, axis_name="expert")
        return y[None]

    def loss(params, x_all):
        return jnp.mean(fwd(params, x_all) ** 2)

    def train(params, x_all):
        l, g = jax.value_and_grad(loss)(params, x_all)
        # expert weights are per-device (owned), the router is replicated:
        # its grad must be reduced across the expert axis before the update
        g_router = lax.psum(g["router"], "expert")
        new = {"experts": params["experts"] - 0.1 * g["experts"],
               "router": params["router"] - 0.1 * g_router}
        return new, lax.pmean(l, "expert")

    specs = ({"experts": P("expert"), "router": P()}, P("expert"))
    mk = lambda f, out: jax.jit(shard_map(
        f, mesh=mesh, in_specs=specs, out_specs=out, check_vma=False))
    params = {"experts": jax.ShapeDtypeStruct((E, D, D), "float32"),
              "router": jax.ShapeDtypeStruct((D, E), "float32")}
    x_all = jax.ShapeDtypeStruct((E, N, D), "float32")
    units = [
        ("moe_fwd", mk(fwd, P("expert")), (params, x_all)),
        ("moe_train_step",
         mk(train, ({"experts": P("expert"), "router": P()}, P())),
         (params, x_all)),
    ]
    return _lint_units(units, mesh)


def _seq_family():
    """Sequence-parallel attention steps: ring (collective-permute per
    hop), the zigzag causal schedule, and the Ulysses all-to-all head
    swap — three distinct traffic shapes over one ``seq`` axis."""
    import numpy as np
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from distlearn_tpu.parallel.sequence import (alltoall_attention,
                                                 ring_attention)
    from jax import shard_map
    n = MIN_DEVICES
    mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
    B, L, H, D = 2, 16 * n, n, 16     # H divisible by n (ulysses), L/n even
    qkv = tuple(jax.ShapeDtypeStruct((B, L, H, D), "float32")
                for _ in range(3))

    def mk(f):
        return jax.jit(shard_map(f, mesh=mesh, in_specs=(P(None, "seq"),) * 3,
                                 out_specs=P(None, "seq"), check_vma=False))
    units = [
        ("ring_attention",
         mk(lambda q, k, v: ring_attention(q, k, v, "seq", causal=True)),
         qkv),
        ("zigzag_ring_attention",
         mk(lambda q, k, v: ring_attention(q, k, v, "seq", causal=True,
                                           layout="zigzag")), qkv),
        ("ulysses_attention",
         mk(lambda q, k, v: alltoall_attention(q, k, v, "seq")), qkv),
    ]
    return _lint_units(units, mesh)


def _decode_family():
    """Serving decode programs (distlearn_tpu.serve): the tp-sharded
    continuous-batching tick, EVERY bucketed prefill AND prefill chunk
    (resumable chunked prefill), and the speculative verify.  The cost
    lockfile pins the two psums per block — a serving regression that
    adds collectives to the per-token path shows up here, not at p99 —
    plus the serve-path DL206-DL209 surface: the engine runs with
    donation on (its production configuration), every unit goes through
    the donation audit, the full bucket set pins the family's
    distinct-compile count (DL207), each unit's entry relayout count is
    budgeted (DL208), and the tick-loop AST pass (DL209) rides along as
    a findings-only unit."""
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from distlearn_tpu.lint.cost import lint_tick_loop
    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.serve.engine import DecodeEngine
    tp = 2
    mesh = Mesh(np.array(jax.devices()[:tp]), ("model",))
    model = transformer_lm(vocab=64, dim=32, depth=2, heads=4, max_len=64)
    params, _ = model.init(jax.random.PRNGKey(0))
    eng = DecodeEngine(params, num_slots=4, page=8, mesh=mesh,
                       tp_axis="model", donate=True)
    units = [("decode_tick", eng.tick_program, eng.tick_args())]
    units += [(f"decode_prefill[{b}]", eng.prefill_program,
               eng.prefill_args(b)) for b in eng.buckets]
    units += [(f"decode_chunk[{b}]", eng.chunk_program,
               eng.chunk_args(b)) for b in eng.buckets]
    units += [("decode_verify", eng.verify_program, eng.verify_args())]
    out = _lint_units(units, mesh)
    for u in out:
        u.donation = True
    out.append(Unit("tick_loop", lint_tick_loop()))
    return out


def _wirek_family():
    """Fused wire-codec kernels (ops/wire_kernels): the Pallas int8
    quantize+error-feedback and dequantize+apply calls plus the amax
    reduction, on a wire-stripe-shaped block.  Single-device elementwise
    programs (mesh=None, no collectives) — the lockfile pins their flops
    and peak memory, so a regression back to a multi-pass or
    extra-copy lowering of the codec fails tier-1, mirroring how the
    collective budgets pin the SPMD families."""
    import jax
    from distlearn_tpu.ops import wire_kernels as wk
    from distlearn_tpu.ops.flatten import LANE
    from distlearn_tpu.ops.fused_update import _BLOCK_ROWS
    rows = 4 * _BLOCK_ROWS              # 4 grid steps of the block spec
    x = jax.ShapeDtypeStruct((rows, LANE), "float32")
    q = jax.ShapeDtypeStruct((rows, LANE), "int8")
    st = jax.ShapeDtypeStruct((1, 1), "float32")
    units = [
        ("quant_ef", wk._quant_ef_call, (x, st)),
        ("dequant_add", wk._dequant_add_call, (x, q, st)),
        ("wire_amax", wk._amax_call, (x,)),
    ]
    return _lint_units(units, None)


def _sync_family():
    """The sync collectives themselves: the MeshBackend allreduce
    programs (plain + contrib-masked) and the two device-side phases of
    the HybridBackend hierarchical allreduce (in-mesh reduce-scatter,
    post-host-leg all-gather) — so the DL2xx cost budgets cover
    cross-node sync, not just the train steps that call it
    (comm/backend.py, lint/budgets/sync.json)."""
    import jax
    from distlearn_tpu.comm.backend import HybridBackend, MeshBackend
    mb = MeshBackend(num_nodes=8)
    # representative mixed payload: a matrix + a bias per node row
    val = {"b": jax.ShapeDtypeStruct((8, 64), "float32"),
           "w": jax.ShapeDtypeStruct((8, 128, 64), "float32")}
    cvec = jax.ShapeDtypeStruct((8,), "int32")
    hb = HybridBackend(0, 1, num_devices=8)
    plan = hb._plan(val)
    rs, ag = hb._programs(*plan)
    chunks = tuple(jax.ShapeDtypeStruct((padded,), dt.name)
                   for dt, _idxs, _total, padded, _chunks in plan[5])
    units = [
        ("sync_mesh_allreduce",
         mb.mesh_tree.all_reduce_program(False), (val,)),
        ("sync_mesh_allreduce_masked",
         mb.mesh_tree.all_reduce_program(True), (val, cvec)),
        ("sync_hybrid_reduce_scatter", rs, (val, cvec)),
        ("sync_hybrid_all_gather", ag, chunks),
    ]
    return _lint_units(units, mb.mesh)


def _protocol_family():
    from distlearn_tpu.lint.protocol import (async_ea_sync_schedule,
                                             check_schedules,
                                             lint_comm_protocols,
                                             ring_allreduce_schedule,
                                             tree_allreduce_schedule)
    units = [Unit("comm_protocols", lint_comm_protocols(num_nodes=7))]
    # Cover the schedule space beyond the default size as well.
    for n in (2, 3, 5, 8):
        units.append(Unit(f"tree[{n}]",
                          check_schedules(tree_allreduce_schedule(n),
                                          name=f"tree[{n}]")))
        units.append(Unit(f"ring[{n}]",
                          check_schedules(ring_allreduce_schedule(n),
                                          name=f"ring[{n}]")))
    units.append(Unit("async_ea[L=5]",
                      check_schedules(async_ea_sync_schedule(num_leaves=5),
                                      name="async_ea[L=5]")))
    return units


def _model_family():
    """Explicit-state model checking (DL301-DL304) + schedule↔code
    conformance (DL310): every process model in ``lint/model.py`` is
    exhaustively explored, with its state/transition counts carried as
    unit info, and every ``async_ea_*`` schedule is diffed against the
    wire constants/call sites in ``async_ea.py``."""
    from distlearn_tpu.lint.conformance import (lint_conformance,
                                                lint_serve_frames)
    from distlearn_tpu.lint.model import lint_models
    units = [Unit(spec.name, rep.findings, info=rep.info)
             for rep, spec in lint_models()]
    units.append(Unit("conformance", lint_conformance()))
    units.append(Unit("serve_frames", lint_serve_frames()))
    return units


def _races_family():
    """Static lockset race detection (DL111/DL112), split into the core
    scope (async_ea, ha, serve server/scheduler, obs core) and the
    fleet-era ``router`` scope (serve router, obs Collector, fault
    plan, autoscaler)."""
    from distlearn_tpu.lint.races import (core_targets, fleet_targets,
                                          lint_races)
    return [Unit("lockset", lint_races(core_targets())),
            Unit("router", lint_races(fleet_targets()))]


_FAMILIES = {
    "sgd": Entry("sgd", "fused AllReduceSGD steps (sgd/scan/sync/eval)",
                 _sgd_family),
    "ea": Entry("ea", "elastic-averaging steps (local/round/cycle)",
                _ea_family),
    "lm": Entry("lm", "3D-parallel LM train step", _lm_family),
    "lm_mixed": Entry("lm_mixed", "bf16-working/f32-master LM step",
                      _lm_mixed_family),
    "pp": Entry("pp", "pipeline-parallel LM steps (GPipe + 1F1B)",
                _pp_family),
    "optax": Entry("optax", "optax-backed data-parallel + ZeRO-sharded steps",
                   _optax_family),
    "ep": Entry("ep", "MoE expert-parallel steps (all-to-all dispatch)",
                _ep_family),
    "seq": Entry("seq", "sequence-parallel attention (ring/zigzag/ulysses)",
                 _seq_family),
    "decode": Entry("decode",
                    "serving decode programs (continuous-batch tick + "
                    "paged prefill)", _decode_family),
    "wirek": Entry("wirek",
                   "fused wire-codec kernels (int8 quantize+EF / "
                   "dequantize+apply / amax)", _wirek_family),
    "sync": Entry("sync",
                  "collective-backend sync programs (mesh allreduce + "
                  "hybrid reduce-scatter/all-gather)", _sync_family),
    "protocol": Entry("protocol",
                      "host comm schedules (tree/ring/AsyncEA) + lock audit",
                      _protocol_family),
    "model": Entry("model",
                   "explicit-state protocol models (sync/sharded/replay/"
                   "failover/serve) + schedule↔code conformance",
                   _model_family),
    "races": Entry("races",
                   "static lockset race detection over the threaded modules",
                   _races_family),
}


def families() -> dict[str, Entry]:
    return dict(_FAMILIES)


def _require_devices():
    import jax
    n = len(jax.devices())
    if n < MIN_DEVICES:
        raise RuntimeError(
            f"distlint needs >= {MIN_DEVICES} devices to build the step "
            f"families (got {n}); set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
            "importing jax (tools/distlint.py does this)")


# Build+lower+compile output per (family, cost) pair.  Everything a
# family analyses — module sources, step builders, budget inputs — is
# fixed once the process has imported the package, so rebuilding the
# mesh and re-lowering every program on a second run in the same
# process (the tier-1 gate test and the in-process CLI tests both walk
# the decode family) only burns warmup time.  Only the per-unit
# findings/info and the cost reports are retained; the jitted callables
# are dropped so the compiled executables can be collected.
_BUILD_CACHE: dict[tuple[str, bool], tuple[list, dict]] = {}


def _build_family_costed(name: str, cost: bool):
    """Build one family and run its cost pass; memoised per process."""
    key = (name, cost)
    hit = _BUILD_CACHE.get(key)
    if hit is not None:
        return hit
    units = _FAMILIES[name].run()
    reports = {}
    per_unit = []
    for u in units:
        findings = list(u.findings)
        if cost and u.fn is not None:
            from distlearn_tpu.lint import cost as cost_mod
            report, cost_findings = cost_mod.analyze_step(
                u.fn, u.args, mesh=u.mesh, name=f"{name}:{u.name}",
                in_specs=u.in_specs, donation=u.donation)
            reports[u.name] = report
            findings += cost_findings
        per_unit.append((u.name, findings, dict(u.info)))
    _BUILD_CACHE[key] = (per_unit, reports)
    return per_unit, reports


def run_family_costed(name: str, *, suppress: Sequence[str] = (),
                      cost: bool = True, budget_dir: str | None = None):
    """Lint one family AND run its steps through the static cost model.

    Returns ``(results, reports)``: one :class:`LintResult` per unit (plus
    a synthetic ``<family>:budget`` result when lockfile comparison finds
    anything), and a ``{unit_name: CostReport}`` dict for the CLI's cost
    tables / ``--update-budgets``.
    """
    _require_devices()
    per_unit, reports = _build_family_costed(name, cost)
    results = []
    for uname, findings, info in per_unit:
        results.append(LintResult(f"{name}:{uname}",
                                  filter_suppressed(list(findings), suppress),
                                  info=dict(info)))
    if cost:
        from distlearn_tpu.lint import budget as budget_mod
        bfindings = filter_suppressed(
            budget_mod.check_family(name, reports, budget_dir=budget_dir),
            suppress)
        if bfindings:
            results.append(LintResult(f"{name}:budget", bfindings))
        if reports:
            from distlearn_tpu.lint import cost as cost_mod
            cfindings, summary = cost_mod.audit_compiles(name, reports)
            results.append(LintResult(
                f"{name}:compiles",
                filter_suppressed(cfindings, suppress), info=summary))
    return results, reports


def run_family(name: str, *, suppress: Sequence[str] = (),
               cost: bool = True) -> list[LintResult]:
    """Lint one family; returns one :class:`LintResult` per step function."""
    return run_family_costed(name, suppress=suppress, cost=cost)[0]


def run_all(*, suppress: Sequence[str] = (),
            cost: bool = True) -> list[LintResult]:
    out = []
    for name in _FAMILIES:
        out += run_family(name, suppress=suppress, cost=cost)
    return out
