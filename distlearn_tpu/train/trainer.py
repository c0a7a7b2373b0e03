"""Fused train-step builders — the TPU hot path.

The reference's hot loop is: dataset batch → autograd fwd+bwd →
``tree.allReduce`` over TCP → manual SGD update (call stack SURVEY.md §3.1,
examples/mnist.lua:99-116).  Every stage is a separate host-driven operation
crossing the process boundary.  The TPU-native design collapses the entire
step — forward, backward, gradient psum, normalization, SGD update, metric
update — into ONE jitted ``shard_map`` program per mesh, so XLA overlaps the
ICI collective with backprop compute and fuses the elementwise update into the
gradient producers.  This is the BASELINE.json north-star structure.

Two families:

* :func:`build_sgd_step` — AllReduceSGD training.  Params REPLICATED across
  the mesh (spec ``P()``), batch sharded along the data axis.  Gradients are
  psum'd and contributor-normalized (lua/AllReduceSGD.lua:18-30 semantics)
  inside the step.

* :func:`build_ea_steps` — AllReduceEA training.  Params are PER-NODE (stacked
  leading node axis, spec ``P(axis)``) because EASGD nodes intentionally
  diverge between averaging rounds.  Returns a collective-free local step and
  a fused elastic-round step; the host calls the round every ``tau`` steps
  (τ−1 of τ steps run with zero communication — the point of EASGD,
  lua/AllReduceEA.lua:31).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax, random
from jax.sharding import PartitionSpec as P

from distlearn_tpu import obs
from jax import shard_map

from distlearn_tpu.models.core import Model, loss_fn
from distlearn_tpu.ops import flatten as flatten_lib
from distlearn_tpu.ops import fused_update
from distlearn_tpu.parallel import allreduce_ea, allreduce_sgd
from distlearn_tpu.parallel import mesh as mesh_lib
from distlearn_tpu.parallel.mesh import MeshTree
from distlearn_tpu.utils import metrics as metrics_lib

PyTree = Any


#: name -> the newest shim a builder returned under that name.  Strong
#: references, bounded by the number of builder names: a caller reads a
#: program's text after the code that built and ran the step has
#: returned and dropped it (the benchmark's readers do).
_programs: dict = {}


def step_programs() -> dict:
    """The step programs built in this process, by the name their builder
    gave them (``sgd``, ``lm``, ...; the newest build per name).  What a
    trace reader asks for a program's :meth:`_TimedStep.hlo_text`.
    Empty with ``DISTLEARN_OBS=0``."""
    return dict(_programs)


def _signature(args):
    """The abstract signature of a call's arguments — shape, dtype and
    (where the array is committed to one) sharding of every array,
    anything else (a Python scalar) as it is; no buffer is kept.  None for
    a call made under a trace (``make_jaxpr``, an outer ``jit``): that is
    no dispatch, and a tracer has no placement."""
    if any(isinstance(x, jax.core.Tracer)
           for x in jax.tree_util.tree_leaves(args)):
        return None

    def abstract(x):
        if not (hasattr(x, "shape") and hasattr(x, "dtype")):
            return x
        placed = getattr(x, "committed", False)
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if placed else None)
    return jax.tree_util.tree_map(abstract, args)


class _TimedStep:
    """Telemetry shim around a jitted step: one ``train.dispatch`` span
    per call, which times the host dispatch (async — the wall time to
    ENQUEUE the program, which is what the scan/cycle builders exist to
    amortize, not device compute), the same time into a histogram, and a
    call count.  It keeps the abstract signature of its first call (no
    buffer), so :meth:`hlo_text` can name the program the device ran, and
    puts that one call under ``train.first_call{step=}``: what the step
    costs a fresh process (with ``utils.compile_cache.watch_compiles`` the
    program's own ``jit.*`` spans lie inside it).
    ``__getattr__`` forwards everything else to the jitted callable so
    ``.lower()`` consumers — the benchmark, the distcost budget gate — see
    the unwrapped object and compiled HLO stays identical."""

    def __init__(self, fn, name: str):
        self._fn = fn
        self._name = name
        self._sig = None
        self._hlo = None
        lat = obs.histogram(
            "train_step_dispatch_seconds",
            "host-side dispatch wall time per jitted step call",
            labels=("step",))
        cnt = obs.counter("train_steps_total", "jitted step dispatches",
                          labels=("step",))
        self._h = lat.labels(step=name)
        self._c = cnt.labels(step=name)
        _programs[name] = self

    def __call__(self, *a, **kw):
        if self._sig is None:
            self._sig = _signature((a, kw))
            if self._sig is not None:
                # the call that traces, lowers and fetches or compiles the
                # program: its jit.* spans lie inside this one
                with obs.span("train.first_call", step=self._name):
                    return self(*a, **kw)
        with obs.span("train.dispatch", step=self._name) as span:
            out = self._fn(*a, **kw)
        self._c.inc()
        if span.dur is not None:        # the switch went off after the build
            self._h.observe(span.dur)
        return out

    def hlo_text(self) -> str:
        """The optimized HLO of the program the first call ran, as text:
        every instruction with the ``op_name`` that says which
        ``jax.named_scope`` and which pass (forward, backward,
        recomputation) it came from — see
        :func:`distlearn_tpu.utils.profiling.scope_table`.  Lowered and
        compiled again from the remembered signature on first use, so call
        it outside any timed window.

        JAX's persistent cache keys a program WITHOUT its ``op_name``s,
        so the executable a call loads may be one that another checkout
        compiled from the same mathematics under other scope names (or
        none): same instructions, its names — and within a process JAX
        answers this compile from memory with that very executable.  A
        caller that needs THIS source's names whatever the cache held
        drops JAX's in-memory programs first (``jax.clear_caches()``, as
        ``benchmarks/scope_reduce.py`` does once its window is over); the
        compile that then happens here keeps the metadata in its key, so
        it can only hit an entry that an ``hlo_text()`` of this very
        source wrote."""
        if self._sig is None:
            raise RuntimeError(
                f"step {self._name!r} has not been called yet: there is no "
                "signature to lower it with")
        if self._hlo is None:
            a, kw = self._sig
            flag = "jax_compilation_cache_include_metadata_in_key"
            before = getattr(jax.config, flag)
            jax.config.update(flag, True)
            try:
                self._hlo = self._fn.lower(*a, **kw).compile().as_text()
            finally:
                jax.config.update(flag, before)
        return self._hlo

    def __getattr__(self, name):
        return getattr(self._fn, name)


def _timed(fn, name: str):
    """Wrap a builder's result for telemetry; the raw jitted fn comes back
    untouched when the kill switch is off (zero indirection disabled)."""
    if not obs.enabled():
        return fn
    return _TimedStep(fn, name)


class TrainState(NamedTuple):
    """Carried through the jitted SGD step (all donated).

    ``cm`` is a stacked per-node confusion matrix ``[num_nodes, C, C]``
    sharded over the data axis (each node counts its own shard's
    predictions; sum at report time — ref examples/mnist.lua:120-125).
    """
    params: PyTree
    model_state: PyTree      # batchnorm running stats (sync-BN: replicated)
    sync: allreduce_sgd.SGDSyncState   # my_steps stacked [num_nodes], sharded
    cm: jax.Array            # [num_nodes, C, C] device-side confusion matrix
    rng: jax.Array


def _sgd_update(params: PyTree, grads: PyTree, lr) -> PyTree:
    """Manual SGD — the reference's update loop (examples/mnist.lua:112-116)."""
    return jax.tree_util.tree_map(
        lambda p, g: p - jnp.asarray(lr, p.dtype) * g.astype(p.dtype),
        params, grads)


def local_update(params: PyTree, grads: PyTree, vel: PyTree, lr: float,
                 momentum: float) -> tuple[PyTree, PyTree]:
    """The EA-family local optimizer, shared by the classifier and LM
    paths: plain SGD (``momentum=0``, velocity untouched) or heavy-ball
    EAMSGD (arXiv:1412.6651 §3: ``v = μ·v + g; p -= lr·v``)."""
    if not momentum:
        return _sgd_update(params, grads, lr), vel
    vel = jax.tree_util.tree_map(
        lambda v, g: jnp.asarray(momentum, v.dtype) * v + g.astype(v.dtype),
        vel, grads)
    params = jax.tree_util.tree_map(
        lambda p, v: p - jnp.asarray(lr, p.dtype) * v.astype(p.dtype),
        params, vel)
    return params, vel


def apply_elastic_round(params: PyTree, center: PyTree, alpha: float,
                        axis: str, fused: bool | None = None,
                        max_bucket_bytes: int | None = None
                        ) -> tuple[PyTree, PyTree]:
    """One fused elastic round on LOCAL (per-node) pytrees, shared by the
    classifier and LM paths: Pallas packed buckets when enabled (one psum
    per bucket), per-leaf XLA round otherwise."""
    if fused_update.fused_enabled(fused):
        return fused_update.elastic_round_buckets(params, center, alpha,
                                                  axis, max_bucket_bytes)
    st = allreduce_ea.EAState(center=center, step=jnp.zeros((), jnp.int32))
    params, st = allreduce_ea.elastic_round(params, st, alpha,
                                            axis_name=axis)
    return params, st.center


def init_common(model: Model, tree: MeshTree, key: jax.Array,
                num_classes: int):
    """Shared data-parallel state init: identical params on every node, a
    per-node step counter (ref ``stepsPerNode``), a per-node confusion
    matrix, and the training rng.  Returns
    ``(params, model_state, sync, cm, rng)`` — the common fields of every
    replicated-params TrainState flavor (SGD / optax / ZeRO)."""
    init_key, train_key = random.split(key)
    params, mstate = model.init(init_key)
    n = tree.num_nodes
    sync = allreduce_sgd.SGDSyncState(
        my_steps=tree.put_per_node(jnp.zeros((n,), jnp.int32)))
    cm = tree.put_per_node(jnp.zeros((n, num_classes, num_classes),
                                     jnp.int32))
    return params, mstate, sync, cm, train_key


def init_train_state(model: Model, tree: MeshTree, key: jax.Array,
                     num_classes: int) -> TrainState:
    params, mstate, sync, cm, rng = init_common(model, tree, key,
                                                num_classes)
    return TrainState(params=params, model_state=mstate, sync=sync, cm=cm,
                      rng=rng)


def build_sgd_step(model: Model, tree: MeshTree, lr: float,
                   donate: bool = True, with_contrib: bool = False,
                   fused: bool | None = None,
                   max_bucket_bytes: int | None = None) -> Callable:
    """One fused AllReduceSGD step: ``step(ts, x, y) -> (ts, loss)``.

    ``x``/``y`` are GLOBAL batches (leading axis = global batch) sharded over
    the data axis; params/state replicated.  Inside: local fwd+bwd on the
    node's shard, psum+normalize grads (contributor semantics of
    lua/AllReduceSGD.lua:18-30), SGD update, confusion-matrix update, loss
    pmean.  Sync batchnorm: stats pmean'd across nodes, so the
    replicated-params invariant holds bitwise.

    ``with_contrib=True`` adds a 4th argument: a per-node 0/1 vector
    ``[num_nodes]`` (sharded over the axis) marking which nodes contribute
    this step — the uneven-data-partition case (lua/AllReduceSGD.lua:22-27).
    Non-contributors' grads are masked out, their params still receive the
    identical psum'd update (keeping params replicated), their step counter
    and confusion matrix do not advance; pair with :func:`build_sync_step`
    for the end-of-epoch winner-takes-all sync.

    ``fused`` (default: on when running on TPU, see
    :func:`distlearn_tpu.ops.fused_update.fused_enabled`) routes the gradient
    psum and the SGD update through packed flat buckets: one collective and
    one Pallas kernel launch per bucket instead of one XLA op per parameter
    leaf — the per-tensor walkTable loop of the reference
    (lua/AllReduceSGD.lua:24) collapsed into a few HBM streaming passes.
    ``max_bucket_bytes`` splits huge models into several buckets.
    """
    axis = tree.axis_name
    _body = _make_sgd_body(model, tree, lr, fused, max_bucket_bytes)

    specs_ts = TrainState(params=P(), model_state=P(), sync=P(axis),
                          cm=P(axis), rng=P())
    if with_contrib:
        def step(ts, x, y, contrib):
            return _body(ts, x, y, jnp.squeeze(contrib, 0))
        in_specs = (specs_ts, P(axis), P(axis), P(axis))
    else:
        def step(ts, x, y):
            return _body(ts, x, y, None)
        in_specs = (specs_ts, P(axis), P(axis))
    mapped = shard_map(step, mesh=tree.mesh,
                           in_specs=in_specs,
                           out_specs=(specs_ts, P()),
                           check_vma=False)
    return _timed(jax.jit(mapped, donate_argnums=(0,) if donate else ()),
                  "sgd")


def _make_sgd_body(model: Model, tree: MeshTree, lr: float,
                   fused: bool | None, max_bucket_bytes: int | None):
    """The per-node body of one fused AllReduceSGD step (shared by the
    per-call and the scanned builders)."""
    axis = tree.axis_name
    use_fused = fused_update.fused_enabled(fused)

    def _body(ts: TrainState, x, y, contrib):
        rng, dropout_rng = random.split(ts.rng)
        dropout_rng = random.fold_in(dropout_rng, lax.axis_index(axis))

        def _loss(p):
            return loss_fn(model, p, ts.model_state, x, y, train=True,
                           rng=dropout_rng, axis_name=axis, bn_weight=contrib)

        (loss, (log_probs, mstate)), grads = \
            jax.value_and_grad(_loss, has_aux=True)(ts.params)
        sync_local = mesh_lib.squeeze_node(ts.sync)
        if use_fused:
            spec = flatten_lib.make_bucket_spec(grads, max_bucket_bytes)
            g_flats, sync_local, n = allreduce_sgd.sum_and_normalize_gradients(
                flatten_lib.pack_buckets(spec, grads), sync_local,
                contrib=contrib, axis_name=axis)
            params = fused_update.sgd_update_buckets(spec, ts.params,
                                                     g_flats, lr)
        else:
            grads, sync_local, n = allreduce_sgd.sum_and_normalize_gradients(
                grads, sync_local, contrib=contrib, axis_name=axis)
            params = _sgd_update(ts.params, grads, lr)
        sync = mesh_lib.expand_node(sync_local)
        cm_new = metrics_lib.update_confusion(jnp.squeeze(ts.cm, 0),
                                              log_probs, y)
        if contrib is not None:
            keep = contrib.astype(jnp.bool_)
            cm_new = jnp.where(keep, cm_new, jnp.squeeze(ts.cm, 0))
            denom = jnp.maximum(n, 1).astype(loss.dtype)
            mean_loss = lax.psum(loss * contrib.astype(loss.dtype), axis) / denom
        else:
            mean_loss = lax.pmean(loss, axis)
        return TrainState(params, mstate, sync, cm_new[None], rng), mean_loss

    return _body


def build_sgd_scan_step(model: Model, tree: MeshTree, lr: float,
                        donate: bool = True, fused: bool | None = None,
                        max_bucket_bytes: int | None = None,
                        with_contrib: bool = False) -> Callable:
    """K chained AllReduceSGD steps as ONE XLA program:
    ``steps(ts, xs, ys) -> (ts, losses)`` with ``xs``/``ys`` carrying a
    leading ``[K]`` step axis (replicated) over the normal data-sharded batch
    axes, ``losses`` shaped ``[K]``.

    Semantically identical to calling :func:`build_sgd_step`'s step K times
    (same psum/normalize/update per step, state threads through a
    ``lax.scan``), but the host dispatches ONCE per K steps.  For a small
    model the per-call dispatch can cost as much as the step's compute —
    the reference has the same structure cost in every ``tree.allReduce``
    socket round trip (SURVEY.md §3.1), which this design removes
    entirely.  K is read from the input shape at trace time.

    ``with_contrib=True`` adds a 4th argument ``[K, num_nodes]`` of 0/1
    participation flags (sharded over the axis), one row per chained step —
    the per-call step's uneven-data-partition masking
    (lua/AllReduceSGD.lua:22-27) on the scanned hot path: each step's row
    masks grads/steps/metrics exactly as :func:`build_sgd_step`'s
    ``with_contrib`` does per call.
    """
    axis = tree.axis_name
    _body = _make_sgd_body(model, tree, lr, fused, max_bucket_bytes)

    specs_ts = TrainState(params=P(), model_state=P(), sync=P(axis),
                          cm=P(axis), rng=P())
    if with_contrib:
        def steps(ts, xs, ys, contribs):
            def scan_body(carry, xyc):
                x, y, c = xyc
                new_ts, loss = _body(carry, x, y, jnp.squeeze(c, 0))
                return new_ts, loss
            ts, losses = lax.scan(scan_body, ts, (xs, ys, contribs))
            return ts, losses
        in_specs = (specs_ts, P(None, axis), P(None, axis), P(None, axis))
    else:
        def steps(ts, xs, ys):
            def scan_body(carry, xy):
                x, y = xy
                new_ts, loss = _body(carry, x, y, None)
                return new_ts, loss
            ts, losses = lax.scan(scan_body, ts, (xs, ys))
            return ts, losses
        in_specs = (specs_ts, P(None, axis), P(None, axis))
    mapped = shard_map(steps, mesh=tree.mesh,
                           in_specs=in_specs,
                           out_specs=(specs_ts, P()),
                           check_vma=False)
    return _timed(jax.jit(mapped, donate_argnums=(0,) if donate else ()),
                  "sgd_scan")


def build_sync_step(tree: MeshTree, donate: bool = False) -> Callable:
    """End-of-epoch winner-takes-all parameter sync over a :class:`TrainState`
    (ref ``synchronizeParameters``, lua/AllReduceSGD.lua:33-54): the node with
    the most contributing steps this epoch wins; its params broadcast to all;
    step counters reset.  Only meaningful after uneven-participation steps —
    under full participation params are already replicated."""
    axis = tree.axis_name

    def step(ts: TrainState):
        params, sync_local = allreduce_sgd.synchronize_parameters(
            ts.params, mesh_lib.squeeze_node(ts.sync), axis_name=axis)
        return ts._replace(params=params,
                           sync=mesh_lib.expand_node(sync_local))

    specs_ts = TrainState(params=P(), model_state=P(), sync=P(axis),
                          cm=P(axis), rng=P())
    mapped = shard_map(step, mesh=tree.mesh, in_specs=(specs_ts,),
                           out_specs=specs_ts, check_vma=False)
    return _timed(jax.jit(mapped, donate_argnums=(0,) if donate else ()),
                  "sync")


def build_eval_step(model: Model, tree: MeshTree) -> Callable:
    """Fused eval step: ``eval_step(params, mstate, cm, x, y) -> (cm, loss)``.
    Confusion matrix stays per-node (spec ``P(axis)``); reduce with
    :func:`reduce_confusion` at report time (ref allreduces the matrix —
    examples/mnist.lua:122, cifar10.lua:234)."""
    axis = tree.axis_name

    def step(params, mstate, cm, x, y):
        loss, (log_probs, _) = loss_fn(model, params, mstate, x, y,
                                       train=False, axis_name=axis)
        cm = metrics_lib.update_confusion(jnp.squeeze(cm, 0), log_probs, y)
        return cm[None], lax.pmean(loss, axis)

    mapped = shard_map(step, mesh=tree.mesh,
                           in_specs=(P(), P(), P(axis), P(axis), P(axis)),
                           out_specs=(P(axis), P()),
                           check_vma=False)
    return _timed(jax.jit(mapped, donate_argnums=(2,)), "eval")


def reduce_confusion(cm: jax.Array):
    """Sum stacked per-node confusion matrices ``[N, C, C]`` into one global
    ``[C, C]`` (host-level; ref examples/mnist.lua:120-125)."""
    import numpy as np
    return np.asarray(jax.device_get(cm)).sum(axis=0)


# ---------------------------------------------------------------------------
# Elastic averaging (EASGD) steps
# ---------------------------------------------------------------------------

class EATrainState(NamedTuple):
    """Per-node training state for EASGD — every leaf has a leading
    ``num_nodes`` axis sharded over the data mesh axis (nodes diverge).
    ``vel`` is the per-node momentum buffer (EAMSGD, arXiv:1412.6651 §3);
    zeros and untouched when the local optimizer is plain SGD."""
    params: PyTree
    model_state: PyTree
    center: PyTree
    vel: PyTree
    cm: jax.Array
    rng: jax.Array


def init_ea_state(model: Model, tree: MeshTree, key: jax.Array,
                  num_classes: int) -> EATrainState:
    """Identical init on every node (ref seed-0 + initial scatter —
    examples/mnist-ea.lua:63), center := params (lua/AllReduceEA.lua:11-22),
    zero momentum."""
    init_key, train_key = random.split(key)
    params, mstate = model.init(init_key)
    n = tree.num_nodes
    stack = lambda t: tree.put_per_node(jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), t))
    params_n = stack(params)
    rngs = random.split(train_key, n)
    return EATrainState(
        params=params_n, model_state=stack(mstate),
        center=stack(params),
        vel=stack(jax.tree_util.tree_map(jnp.zeros_like, params)),
        cm=tree.put_per_node(jnp.zeros((n, num_classes, num_classes), jnp.int32)),
        rng=tree.put_per_node(rngs))


def build_ea_steps(model: Model, tree: MeshTree, lr: float, alpha: float,
                   donate: bool = True, fused: bool | None = None,
                   max_bucket_bytes: int | None = None,
                   momentum: float = 0.0) -> tuple[Callable, Callable]:
    """Returns ``(local_step, ea_round)``.

    ``local_step(ts, x, y) -> (ts, losses)`` — grad + local SGD, ZERO
    collectives (the τ−1 quiet steps; ref examples/mnist-ea.lua:100-107).
    BN stats stay per-node (nodes diverge anyway — matches reference, where
    running stats are process-local buffers).

    ``ea_round(ts) -> ts`` — the fused elastic round (delta, psum, center
    move) — lua/AllReduceEA.lua:35-45 as ONE XLA program.  With ``fused``
    (default on TPU) the round runs on packed flat buckets: one Pallas
    kernel produces (p', delta) and ONE psum per bucket carries the deltas,
    instead of a collective per parameter leaf.

    ``momentum > 0`` switches the local optimizer to heavy-ball SGD —
    **EAMSGD** from the EASGD paper (arXiv:1412.6651 §3, the variant the
    reference never implemented): ``v = μ·v + g; p -= lr·v`` per quiet
    step, elastic round unchanged.  (torch-optim parameterization; the
    paper's ``v = δv − ηg; x += v`` is the same update with ``v`` rescaled
    by ``−η``.)
    """
    local_step, ea_round = _make_ea_bodies(model, tree, lr, alpha, fused,
                                           max_bucket_bytes, momentum)
    axis = tree.axis_name
    spec_ts = EATrainState(params=P(axis), model_state=P(axis), center=P(axis),
                           vel=P(axis), cm=P(axis), rng=P(axis))
    local = jax.jit(
        shard_map(local_step, mesh=tree.mesh,
                      in_specs=(spec_ts, P(axis), P(axis)),
                      out_specs=(spec_ts, P(axis)), check_vma=False),
        donate_argnums=(0,) if donate else ())
    rnd = jax.jit(
        shard_map(ea_round, mesh=tree.mesh, in_specs=(spec_ts,),
                      out_specs=spec_ts, check_vma=False),
        donate_argnums=(0,) if donate else ())
    return _timed(local, "ea_local"), _timed(rnd, "ea_round")


def _make_ea_bodies(model: Model, tree: MeshTree, lr: float, alpha: float,
                    fused: bool | None, max_bucket_bytes: int | None,
                    momentum: float = 0.0):
    """Per-node (local_step, ea_round) bodies shared by the per-call and the
    scanned EASGD builders."""
    axis = tree.axis_name
    use_fused = fused_update.fused_enabled(fused)
    _sq, _ex = mesh_lib.squeeze_node, mesh_lib.expand_node

    def local_step(ts: EATrainState, x, y):
        params, mstate, rng = _sq(ts.params), _sq(ts.model_state), _sq(ts.rng)
        cm = _sq(ts.cm)
        rng, dropout_rng = random.split(rng)

        def _loss(p):
            return loss_fn(model, p, mstate, x, y, train=True,
                           rng=dropout_rng, axis_name=None)

        (loss, (log_probs, mstate)), grads = \
            jax.value_and_grad(_loss, has_aux=True)(params)
        params, v = local_update(params, grads, _sq(ts.vel), lr, momentum)
        vel = _ex(v) if momentum else ts.vel
        cm = metrics_lib.update_confusion(cm, log_probs, y)
        new_ts = EATrainState(_ex(params), _ex(mstate), ts.center, vel,
                              _ex(cm), _ex(rng))
        return new_ts, loss[None] if loss.ndim == 0 else loss

    def ea_round(ts: EATrainState):
        params, center = apply_elastic_round(
            _sq(ts.params), _sq(ts.center), alpha, axis, use_fused,
            max_bucket_bytes)
        return EATrainState(_ex(params), ts.model_state, _ex(center),
                            ts.vel, ts.cm, ts.rng)

    return local_step, ea_round


def build_ea_cycle(model: Model, tree: MeshTree, lr: float, alpha: float,
                   donate: bool = True, fused: bool | None = None,
                   max_bucket_bytes: int | None = None,
                   momentum: float = 0.0) -> Callable:
    """One full EASGD cycle — τ collective-free local steps then the fused
    elastic round — as ONE XLA program: ``cycle(ts, xs, ys) -> (ts, losses)``
    with ``xs``/``ys`` carrying a leading ``[tau]`` step axis and ``losses``
    shaped ``[tau, num_nodes]``.

    This is the EASGD communication structure itself (τ−1 quiet steps per
    round, lua/AllReduceEA.lua:31 / examples/mnist-ea.lua:110) compiled into
    a single dispatch: the host talks to the device once per *round*, not
    once per step, and XLA schedules the round's psum right after the last
    local update.  τ is read from the input shape at trace time.
    """
    local_step, ea_round = _make_ea_bodies(model, tree, lr, alpha, fused,
                                           max_bucket_bytes, momentum)
    axis = tree.axis_name

    def cycle(ts, xs, ys):
        def scan_body(carry, xy):
            x, y = xy
            new_ts, loss = local_step(carry, x, y)
            return new_ts, loss
        ts, losses = lax.scan(scan_body, ts, (xs, ys))
        return ea_round(ts), losses

    spec_ts = EATrainState(params=P(axis), model_state=P(axis), center=P(axis),
                           vel=P(axis), cm=P(axis), rng=P(axis))
    mapped = shard_map(cycle, mesh=tree.mesh,
                           in_specs=(spec_ts, P(None, axis), P(None, axis)),
                           out_specs=(spec_ts, P(None, axis)),
                           check_vma=False)
    return _timed(jax.jit(mapped, donate_argnums=(0,) if donate else ()),
                  "ea_cycle")
