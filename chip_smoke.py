#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the three main paths once, in ONE process (a chip belongs to one
process), through the builders the examples call, at the full width of
models the repo supports, with seeded random weights and synthetic data:

* ``trainer`` — examples/cifar10.py's path: MeshTree -> init_train_state ->
  build_sgd_scan_step (K=20) / build_sgd_step -> build_sync_step on the CIFAR
  convnet, per-chip batch 256, bf16 compute, fused Pallas update; then one
  EASGD tau-cycle (build_ea_cycle) and tau local steps + one elastic round
  (build_ea_steps), tau=10.
* ``lm`` — examples/lm.py's path: transformer_lm(vocab 32768, dim 1024,
  depth 8, heads 16, bf16) + build_lm_step at batch 8 x seq 1024, then one
  step at seq 4096; at both lengths the attention ``local_attention`` picks
  by default must be the blockwise kernel (the ``obs`` counter
  ``attn_kernel_total`` says which path each traced call resolved to).
* ``hybrid_lm`` — the pattern LM (models/hybrid.py) at ITS published widths
  (4096 wide, 64 query heads over 8 K/V heads of 128, 64 KDA heads of 128,
  experts 1280 wide, a router of 320, 8 a token; one period of 4 layers, 2
  experts held, an eighth of an eighth of the vocabulary) through the same
  build_lm_step at 1 x 2048 tokens, bf16, full remat: the grouped-query
  layer on the blockwise kernel (two Mosaic calls: the recomputation runs
  none), no assignment dropped, losses that fall.
* ``serve`` — examples/lm.py --serve's path at the same width: DecodeEngine
  (8 slots, max_len 1024) behind ServeServer, ServeClients on threads over
  the framed-TCP port: prompts in several prefill buckets, a prefix-cache
  hit, a chunked prefill under a decoding stream, a speculative verify.
* ``wire_kernels`` — the device route of the int8 wire codec
  (ops/wire_kernels.py) against its numpy reference.

What "right" means, per phase: finite losses that fall; parameters bitwise
equal across nodes after sync; every mesh device holding its shard; the
fused update and the blockwise attention present in the lowered program as
Mosaic custom calls (not interpreted loops); every stream complete.  The serve
check is made ON LOGITS, not on token equality: the engine runs as the
example runs it (float32 params, the TPU's default matmul precision — bf16
MXU passes), the reference is the training forward ``model.apply`` in
float32 at precision "highest", teacher-forced on each served stream, and
every served token's reference logit must be within ``SERVE_LOGIT_TOL`` of
the reference's best logit at that position.  (Token-for-token equality
with ``greedy_generate`` is a float32 fact, pinned on the CPU by
tests/test_serve.py; at bf16-pass precision near-tied argmaxes flip.)

``python chip_smoke.py`` sets no platform and refuses anything but a TPU
(exit 1, no result line).  It adapts to the device count it finds: on a
four-chip host the trainer mesh spans all four and the LM runs on a
(data=2, seq=1, model=2) mesh.  The phases are plain functions taking
sizes, so tests/test_chip_smoke.py runs them at toy size on the CPU mesh.

Last stdout line on success:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import json
import re
import sys
import threading
import time

#: serve check: max allowed (best reference logit - served token's reference
#: logit), in logit units.  Logits of this init are ~N(0,1) over the vocab,
#: so a token drawn from a broken cache sits ~4 below the best.  Measured on
#: the v5e at full width: worst gap 0.0028 with the engine as the example
#: runs it (float32 params, bf16 MXU passes; 2 of 176 tokens were near-ties
#: that flipped), 0.032 with the engine forced to bfloat16 compute.  The
#: bound sits between the two: ~5x room for another chip or compiler, and an
#: engine computing in a lower precision than it states fails.
SERVE_LOGIT_TOL = 0.015


def measure(fn) -> dict:
    """Run one phase; its result dict plus wall / set-up / run seconds.
    Set-up is read from the program's own spans and counter
    (``utils.compile_cache.watch_compiles``, on since ``main`` enabled the
    cache): the phase's seconds under a ``jit.*`` span, overlaps counted
    once and compiles on the serve thread too, and its backend compiles by
    what the persistent cache did.  With ``DISTLEARN_OBS=0``: the wall."""
    from distlearn_tpu.utils import compile_cache
    before = compile_cache.compiles()
    t0 = time.perf_counter()
    res = fn()
    wall = time.perf_counter() - t0
    res["wall_s"] = round(wall, 2)
    if before:
        done = {k: v - before[k] for k, v in compile_cache.compiles().items()}
        setup = compile_cache.jit_seconds(since=t0)
        res.update(programs=sum(done.values()), cache_hits=done["hit"],
                   cache_misses=done["miss"], setup_s=round(setup, 2),
                   run_s=round(wall - setup, 2))
    return res


def _require_mosaic(lowered, what: str) -> int:
    """Mosaic custom calls in a lowered program.  On the TPU there must be
    at least one — none means the Pallas kernel runs as an interpreted
    loop (as it does, by design, on the CPU test mesh)."""
    import jax
    calls = lowered.as_text().count("tpu_custom_call")
    _require(calls >= 1 or jax.default_backend() != "tpu",
             f"{what} is not a Mosaic custom call")
    return calls


def _require(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _distinct_devices(arr) -> int:
    return len({s.device for s in arr.addressable_shards})


def _shards_bitwise_equal(tree) -> bool:
    """Every device's shard of every leaf is bit-identical: the full copy of
    a replicated leaf, the node's row of a node-stacked one."""
    import jax
    import numpy as np
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        if any(not np.array_equal(shards[0], s) for s in shards[1:]):
            return False
    return True


# --------------------------------------------------------------- trainer --

def phase_trainer(*, num_nodes: int, per_node_batch: int = 256,
                  scan_k: int = 20, tau: int = 10, lr: float = 0.1,
                  bf16: bool = True, fused: bool | None = None,
                  dispatches: int = 3, require_falling: bool = True) -> dict:
    """``require_falling=False`` is for toy sizes only: a handful of steps
    on two images per node (batch norm, dropout) has no trend to assert."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import random
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distlearn_tpu.data import synthetic_cifar10
    from distlearn_tpu.models import cifar_convnet
    from distlearn_tpu.parallel.mesh import MeshTree
    from distlearn_tpu.train import (build_ea_cycle, build_ea_steps,
                                     build_sgd_scan_step, build_sgd_step,
                                     build_sync_step, init_ea_state,
                                     init_train_state)

    tree = MeshTree(num_nodes=num_nodes)
    axis = tree.axis_name
    model = cifar_convnet(compute_dtype=jnp.bfloat16 if bf16 else None)
    batch = per_node_batch * num_nodes
    k = max(scan_k, tau)
    xs, ys = zip(*(synthetic_cifar10(batch, seed=i)[:2] for i in range(k)))
    xs, ys = np.stack(xs), np.stack(ys)
    stacked = NamedSharding(tree.mesh, P(None, axis))
    flat = NamedSharding(tree.mesh, P(axis))
    bxs, bys = (jax.device_put(a[:scan_k], stacked) for a in (xs, ys))
    bx, by = (jax.device_put(a[0], flat) for a in (xs, ys))
    out: dict = {"nodes": num_nodes, "global_batch": batch,
                 "compute_dtype": "bfloat16" if bf16 else "float32"}
    _require(_distinct_devices(bx) == num_nodes,
             f"batch shards sit on {_distinct_devices(bx)} device(s), "
             f"expected {num_nodes}")

    # -- AllReduceSGD: scanned step, per-call step, sync ---------------------
    ts = init_train_state(model, tree, random.PRNGKey(0), 10)
    scan = build_sgd_scan_step(model, tree, lr=lr, fused=fused)
    step = build_sgd_step(model, tree, lr=lr, fused=fused)
    sync = build_sync_step(tree)
    lowered = step.lower(ts, bx, by)
    out["mosaic_calls_sgd_step"] = _require_mosaic(lowered,
                                                   "fused SGD update")
    _require(num_nodes == 1 or "all-reduce" in lowered.compile().as_text(),
             f"compiled SGD step holds no all-reduce on {num_nodes} nodes")
    for i in range(dispatches):
        ts, ls = scan(ts, bxs, bys)
        if i == 0:
            first = float(np.mean(ls))
    last = float(np.mean(ls))
    for _ in range(dispatches):
        ts, l1 = step(ts, bx, by)
    ts = sync(ts)
    _require(np.isfinite([first, last, float(l1)]).all(),
             f"non-finite SGD loss: {first}, {last}, {float(l1)}")
    _require(not require_falling or last < first,
             f"SGD loss did not fall: {first:.4f} -> {last:.4f}")
    _require(_distinct_devices(ts.cm) == num_nodes,
             "per-node state does not span the mesh")
    _require(_shards_bitwise_equal(ts.params),
             "params differ across nodes after synchronize_parameters")
    out.update(sgd_loss_first=first, sgd_loss_last=last,
               sgd_steps=dispatches * (scan_k + 1))

    # -- EASGD: one scanned tau-cycle, then tau local steps + one round ------
    ea = init_ea_state(model, tree, random.PRNGKey(0), 10)
    cycle = build_ea_cycle(model, tree, lr=lr, alpha=0.2, fused=fused)
    local, rnd = build_ea_steps(model, tree, lr=lr, alpha=0.2, fused=fused)
    exs, eys = (jax.device_put(a[:tau], stacked) for a in (xs, ys))
    out["mosaic_calls_ea_round"] = _require_mosaic(rnd.lower(ea),
                                                   "fused elastic round")
    ea, ea_first = cycle(ea, exs, eys)
    ea, ea_last = cycle(ea, exs, eys)
    for _ in range(tau):
        ea, ll = local(ea, bx, by)
    ea = rnd(ea)
    ea_first, ea_last, ll = (np.asarray(a, np.float32)
                             for a in (ea_first, ea_last, ll))
    _require(np.isfinite(ea_first).all() and np.isfinite(ea_last).all()
             and np.isfinite(ll).all(), "non-finite EASGD loss")
    _require(not require_falling or ea_last.mean() < ea_first.mean(),
             f"EASGD loss did not fall: {ea_first.mean():.4f} -> "
             f"{ea_last.mean():.4f}")
    _require(_distinct_devices(jax.tree_util.tree_leaves(ea.params)[0])
             == num_nodes, "EASGD per-node params do not span the mesh")
    _require(_shards_bitwise_equal(ea.center),
             "EASGD center differs across nodes after the elastic round")
    out.update(ea_loss_first=float(ea_first.mean()),
               ea_loss_last=float(ea_last.mean()))
    return out


def _moved(before: dict, after: dict) -> dict:
    """The counters of ``after`` that moved since ``before``, by how much."""
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}


def _lower_default(step, params, tokens, what):
    """Lower a step built with no ``attn_impl`` and say which attention
    ``local_attention`` resolved while it was traced (the ``obs``
    counter).  On the TPU it must be the blockwise kernel alone, as a
    Mosaic call."""
    import jax
    from distlearn_tpu.parallel.sequence import attention_paths_traced
    before = attention_paths_traced()
    lowered = step.lower(params, tokens)
    traced = _moved(before, attention_paths_traced())
    if jax.default_backend() == "tpu":
        _require(set(traced) == {"splash"},
                 f"{what}: the blockwise attention did not engage by "
                 f"default (attn_kernel_total moved by {traced})")
        _require_mosaic(lowered, f"{what}: blockwise attention")
    return lowered, traced


# -------------------------------------------------------------------- lm --

def phase_lm(*, mesh_shape: tuple[int, int, int] = (1, 1, 1),
             vocab: int = 32768, dim: int = 1024, depth: int = 8,
             heads: int = 16, batch: int = 8, seq: int = 1024,
             long_seq: int = 4096, steps: int = 4,
             bf16: bool = True) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import random
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distlearn_tpu.models.transformer import param_specs, transformer_lm
    from distlearn_tpu.train.lm import build_lm_step

    dp, sp, tp = mesh_shape
    n_dev = dp * sp * tp
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(dp, sp, tp),
                ("data", "seq", "model"))
    cd = jnp.bfloat16 if bf16 else None
    out: dict = {"mesh": {"data": dp, "seq": sp, "model": tp},
                 "dim": dim, "depth": depth, "vocab": vocab}

    def build(max_len, b, **kw):
        lm = transformer_lm(vocab=vocab, dim=dim, depth=depth, heads=heads,
                            max_len=max_len, compute_dtype=cd, **kw)
        params, _ = lm.init(random.PRNGKey(0))
        placed = jax.device_put(params, jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            param_specs(params, tp_axis="model")))
        step = build_lm_step(lm, mesh, params, lr=0.1)
        tokens = jax.device_put(
            np.random.RandomState(0).randint(0, vocab, (b, max_len))
            .astype(np.int32), NamedSharding(mesh, P("data", "seq")))
        return step, placed, tokens

    step, params, tokens = build(seq, batch)
    _require(_distinct_devices(tokens) == n_dev,
             "tokens do not live on every mesh device")
    _require(_distinct_devices(params["block0"]["wq"]) == n_dev,
             "block0/wq does not live on every mesh device")
    lowered, traced = _lower_default(step, params, tokens, f"seq {seq}")
    out["attn_kernels"] = {str(seq): traced}
    _require(n_dev == 1 or "all-reduce" in lowered.compile().as_text(),
             f"compiled LM step holds no all-reduce on mesh {mesh_shape}")
    # same params, same tokens, the full-square path forced: the two
    # attentions agree to bf16 on the loss before any update
    xstep, xparams, _ = build(seq, batch, attn_impl="xla")
    _, xloss = xstep(xparams, tokens)
    del xparams
    losses = []
    for _ in range(steps):
        params, loss = step(params, tokens)
        losses.append(float(loss))
    _require(np.isfinite(losses).all(), f"non-finite LM loss: {losses}")
    _require(losses[-1] < losses[0], f"LM loss did not fall: {losses}")
    _require(abs(float(xloss) - losses[0]) < 0.01 * abs(losses[0]),
             f"default attention and impl='xla' disagree on the first loss: "
             f"{losses[0]} against {float(xloss)}")
    out.update(batch=batch, seq=seq, losses=[round(l, 4) for l in losses],
               loss_xla=round(float(xloss), 4))
    del params

    # long context, one step, default attention again (selective remat,
    # which no cell of the benchmark runs)
    lstep, lparams, ltokens = build(long_seq, dp, remat="mlp")
    _, traced = _lower_default(lstep, lparams, ltokens,
                               f"seq {long_seq}")
    out["attn_kernels"][str(long_seq)] = traced
    lparams, loss = lstep(lparams, ltokens)
    _require(np.isfinite(float(loss)), "non-finite long-context loss")
    out.update(long_seq=long_seq, long_loss=round(float(loss), 4))
    del lparams
    return out


# ------------------------------------------------------------- hybrid lm --

def phase_hybrid_lm(*, vocab: int = 3072, dim: int = 4096, heads: int = 64,
                    kv_heads: int = 8, head_dim: int = 128,
                    kda_heads: int = 64, kda_head_dim: int = 128,
                    experts: int = 320, held: tuple = (0, 1), top_k: int = 8,
                    expert_width: int = 1280, seq: int = 2048,
                    steps: int = 3, lr: float = 0.003,
                    bf16: bool = True) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import random
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distlearn_tpu.models import hybrid_lm
    from distlearn_tpu.train.lm import (build_lm_routing_metrics,
                                        build_lm_step)

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("data", "seq", "model"))
    model = hybrid_lm(
        vocab=vocab, dim=dim, layer_types=("gqa", "kda", "kda", "kda"),
        heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        kda_heads=kda_heads, kda_head_dim=kda_head_dim,
        n_routed_experts=experts, held_experts=held, experts_per_tok=top_k,
        expert_width=expert_width, max_len=seq,
        compute_dtype=jnp.bfloat16 if bf16 else None, remat="full")
    params = jax.jit(lambda k: model.init(k)[0])(random.PRNGKey(0))
    tokens = jax.device_put(
        np.random.RandomState(0).randint(0, vocab, (1, seq)).astype(np.int32),
        NamedSharding(mesh, P("data", "seq")))
    routing = build_lm_routing_metrics(model, mesh, params)(params, tokens)
    _require(int(routing["dropped"].sum()) == 0,
             f"the dropless expert layer dropped: {routing['dropped']}")
    _require(int(routing["assignments"].sum()) > 0,
             "no token was routed to a held expert")
    from distlearn_tpu.ops.delta_rule import (delta_rule_paths_traced,
                                              delta_rule_scan_paths_traced)
    from distlearn_tpu.parallel.ep import grouped_paths_traced
    step = build_lm_step(model, mesh, params, lr=lr)
    moe_before, rule_before = grouped_paths_traced(), delta_rule_paths_traced()
    scan_before = delta_rule_scan_paths_traced()
    lowered, traced = _lower_default(step, params, tokens, "hybrid LM")
    moe_traced = _moved(moe_before, grouped_paths_traced())
    rule_traced = _moved(rule_before, delta_rule_paths_traced())
    scan_traced = _moved(scan_before, delta_rule_scan_paths_traced())
    kernels = re.findall(r'kernel_name = "([^"]*)"', lowered.as_text())
    mosaic = sum(name.startswith("splash_mqa") for name in kernels)
    rule = sorted(name for name in kernels if name.startswith("delta_rule"))
    # one softmax layer, rematerialised: its checkpoint keeps the kernel's
    # output and log-sum-exp, so the step holds the forward and the
    # backward kernel and no third call in the recomputation; at this load
    # (2,048 x 8 / 320: 51 rows an expert) the held experts' grouped product
    # stays the loop, with no kernel of its own (moe_grouped_total); each of
    # the three delta-rule layers makes its per-chunk operands by a kernel
    # in the forward and the recomputed forward and pulls back by another
    # (delta_rule_total), and scans its chunks the same way
    # (delta_rule_scan_total), and nothing else in the step is a Mosaic call
    _require((mosaic == 2 and set(moe_traced) == {"xla"}
              and rule == ["delta_rule_operands"] * 6
              + ["delta_rule_operands_vjp"] * 3 + ["delta_rule_scan"] * 6
              + ["delta_rule_scan_vjp"] * 3
              and set(rule_traced) == set(scan_traced) == {"kernel"}
              and len(kernels) == mosaic + len(rule))
             or jax.default_backend() != "tpu",
             f"hybrid LM: Mosaic calls {kernels} in the rematerialised step, "
             "expected 2 of its one softmax layer (forward and backward "
             "kernel) and 18 of its three delta-rule layers (two forwards "
             "and a pull-back each of the operands and of the scan); grouped "
             f"products traced as {moe_traced}, the delta rule as "
             f"{rule_traced}, its scan as {scan_traced}")
    losses = []
    for _ in range(steps):
        params, loss = step(params, tokens)
        losses.append(float(loss))
    _require(np.isfinite(losses).all(), f"non-finite hybrid LM loss: {losses}")
    _require(losses[-1] < losses[0], f"hybrid LM loss did not fall: {losses}")
    return {"dim": dim, "seq": seq, "held": list(held), "attn_kernels": traced,
            "moe_grouped": moe_traced, "mosaic_calls": mosaic,
            "delta_rule": rule_traced, "delta_rule_scan": scan_traced,
            "delta_rule_calls": len(rule),
            "losses": [round(l, 4) for l in losses],
            "assignments": routing["assignments"].tolist(),
            "unheld_frac": [round(float(x), 4)
                            for x in routing["unheld_frac"]]}


# ----------------------------------------------------------------- serve --

def phase_serve(*, vocab: int = 32768, dim: int = 1024, depth: int = 8,
                heads: int = 16, max_len: int = 1024, slots: int = 8,
                prompt_lens=(5, 12, 40, 100, 300), max_new: int = 8,
                prefill_chunk: int = 64, stream_new: int = 96,
                tol: float = SERVE_LOGIT_TOL) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import random

    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.serve import DecodeEngine, ServeClient, ServeServer

    lm = transformer_lm(vocab=vocab, dim=dim, depth=depth, heads=heads,
                        max_len=max_len)
    params, _ = lm.init(random.PRNGKey(0))
    rng = np.random.RandomState(1)
    page = 16

    # lm.py --serve hands the engine host arrays and no compute dtype
    engine = DecodeEngine(jax.device_get(params), num_slots=slots,
                          max_len=max_len, page=page)
    srv = ServeServer(engine, port=0, prefix_cache=True, spec_k=4,
                      prefill_chunk=prefill_chunk).start()
    done: list[tuple[np.ndarray, dict]] = []
    lock = threading.Lock()

    def ask(prompt, new, on_chunk=None):
        with ServeClient(srv.host, srv.port) as c:
            res = c.generate(prompt, new, timeout=600.0, on_chunk=on_chunk)
        _require(res["reason"] == "complete" and len(res["tokens"]) == new,
                 f"stream ended {res['reason']} after "
                 f"{len(res['tokens'])}/{new} tokens")
        with lock:
            done.append((np.asarray(prompt, np.int32), res))
        return res

    try:
        # (a) one prompt per prefill bucket, one at a time
        prompts = [rng.randint(0, vocab, n).astype(np.int32)
                   for n in prompt_lens]
        for p in prompts:
            ask(p, max_new)
        buckets = sorted({engine.bucket_for(len(p)) for p in prompts})
        _require(len(buckets) >= min(3, len(prompt_lens)),
                 f"prompts hit only prefill buckets {buckets}")

        # (b) prefix-cache hit: the longest prompt again with a new tail
        tail = rng.randint(0, vocab, page + 4).astype(np.int32)
        hit = ask(np.concatenate([prompts[-1], tail]), max_new)
        want = (len(prompts[-1]) // page) * page
        _require(hit["cached_tokens"] == want,
                 f"prefix cache served {hit['cached_tokens']} tokens, "
                 f"expected {want}")

        # (c) chunked prefill: a fresh long prompt lands while another
        # stream is decoding, so it prefills in bounded chunks
        chunks0 = engine._m_chunks.value
        started = threading.Event()
        failed: list[BaseException] = []

        def decode_stream():
            try:
                ask(prompts[1], stream_new, lambda _toks: started.set())
            except Exception as e:      # noqa: BLE001 — raised on main below
                failed.append(e)
            finally:
                started.set()

        stream = threading.Thread(target=decode_stream)
        stream.start()
        _require(started.wait(600.0), "decode stream never started")
        fresh = rng.randint(0, vocab,
                            3 * prefill_chunk + 8).astype(np.int32)
        ask(fresh, max_new)
        stream.join(600.0)
        _require(not stream.is_alive(), "decode stream never finished")
        if failed:
            raise failed[0]
        chunked = int(engine._m_chunks.value - chunks0)
        _require(chunked >= 2, f"long prompt prefilled in {chunked} "
                 "chunk dispatch(es): chunked prefill did not engage")

        # (d) speculative verify: the n-gram drafter proposes whenever a
        # stream's context repeats — a self-repeating prompt, or the loops
        # greedy decoding of a random-weight model falls into — and the
        # tick then goes through the verify program
        motif = rng.randint(0, vocab, 4).astype(np.int32)
        ask(np.tile(motif, 3), 3 * max_new)
        verifies = int(engine._m_verifies.value)
        _require(verifies >= 1, "no speculative verify dispatch")
    finally:
        srv.stop()

    # -- agreement with the float32 reference, on logits ---------------------
    width = max(len(p) + len(r["tokens"]) for p, r in done)
    toks = np.zeros((len(done), width), np.int32)
    for i, (p, r) in enumerate(done):
        toks[i, :len(p) + len(r["tokens"])] = np.concatenate(
            [p, r["tokens"]])

    @jax.jit
    def reference(p, t):
        with jax.default_matmul_precision("highest"):
            logits, _ = lm.apply(p, {}, t, train=False)
        lg = logits[:, :-1].astype(jnp.float32)
        got = jnp.take_along_axis(lg, t[:, 1:, None], -1)[..., 0]
        return lg.max(-1) - got, lg.argmax(-1)

    gap, best = (np.asarray(a) for a in reference(params, jnp.asarray(toks)))
    gaps, agree = [], []
    for i, (p, r) in enumerate(done):
        sl = slice(len(p) - 1, len(p) - 1 + len(r["tokens"]))
        gaps.append(gap[i, sl])
        agree.append(best[i, sl] == np.asarray(r["tokens"]))
    gaps, agree = np.concatenate(gaps), np.concatenate(agree)
    _require(np.isfinite(gaps).all(), "non-finite reference logits")
    _require(gaps.max() <= tol,
             f"a served token sits {gaps.max():.4f} below the float32 "
             f"reference's best logit (tolerance {tol})")
    return {
        "slots": slots, "max_len": max_len, "dim": dim, "depth": depth,
        "engine_dtype": str(np.dtype(engine.cd)),
        "requests": len(done), "tokens": int(gaps.size),
        "prefill_buckets": buckets, "cached_tokens": hit["cached_tokens"],
        "chunk_dispatches": chunked, "verify_dispatches": verifies,
        "spec_accepted": sum(r["accepted"] for _, r in done),
        "logit_gap_max": round(float(gaps.max()), 5),
        "logit_gap_mean": round(float(gaps.mean()), 6),
        "logit_tol": tol,
        "argmax_agreement": round(float(agree.mean()), 4),
    }


# ---------------------------------------------------------- wire kernels --

def phase_wire_kernels(*, n: int = 1 << 20) -> dict:
    """ops/wire_kernels.py's device route on a device array against the
    numpy reference.  ``scale`` is bitwise; ``q`` may differ by one step,
    and only where ``d/scale`` lies within rounding of a .5 tie (the
    chip's f32 divide is not correctly rounded — 1 element in 2**20 on
    the v5e, none in interpret mode); either way the codec is
    self-consistent, ``q*scale + r == d`` to one ulp, and the fused
    dequantize-apply is within one ulp of the reference's."""
    import jax.numpy as jnp
    import numpy as np

    from distlearn_tpu.ops import wire_kernels as wk

    rng = np.random.default_rng(0)
    d = (rng.standard_normal(n) * 2).astype(np.float32)
    q, scale, r = wk.quantize_ef_jax(jnp.asarray(d))
    q_ref, r_ref = np.empty(n, np.int8), np.empty(n, np.float32)
    scale_ref = wk.quantize_ef_into(d, q_ref, r_ref)
    _require(scale == scale_ref, "device int8 scale differs from numpy's")
    st = np.float32(scale)
    off = np.flatnonzero(q != q_ref)
    quo = d[off].astype(np.float64) / np.float64(st)
    _require((np.abs(q[off].astype(int) - q_ref[off]) == 1).all()
             and (np.abs(quo - np.floor(quo) - 0.5)
                  <= 2 * np.spacing(np.float32(127))).all(),
             f"device int8 quantize differs from the numpy reference at "
             f"{off.size} element(s) that are not rounding ties")
    _require((np.abs(q.astype(np.float32) * st + r - d)
              <= np.spacing(np.abs(d))).all(),
             "device codec is not self-consistent: q*scale + r != d")
    c = rng.standard_normal(n).astype(np.float32)
    got = wk.dequant_add_jax(jnp.asarray(c), q_ref, scale)
    want = wk.dequant_add(c, q_ref, scale)
    mag = np.abs(c) + np.abs(q_ref.astype(np.float32) * st)
    _require((np.abs(got - want) <= np.spacing(mag)).all(),
             "device dequant-add is beyond one ulp of the numpy reference")
    return {"elements": n, "q_off_by_one_at_ties": int(off.size),
            "apply_bitwise": bool(np.array_equal(got, want))}


# ------------------------------------------------------------------ main --

def _versions() -> dict:
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def _host_tiers() -> dict:
    """Which host transport / codec tier this process got (both fall back
    silently when no compiler is on PATH)."""
    from distlearn_tpu.comm import native
    from distlearn_tpu.ops import wire_native
    return {"transport": "native C++ (g++)" if native.available()
            else "python sockets (native build unavailable)",
            "wire_codec": "native C" if wire_native.available()
            else f"blocked numpy ({wire_native.why_unavailable()})"}


def main() -> int:
    import jax
    devs = jax.devices()            # whatever JAX picked: no platform set here
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={d0.platform!r}, "
              f"{len(devs)} device(s)); refusing to run", file=sys.stderr)
        return 1
    from distlearn_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    n = len(devs)
    print(f"[smoke] platform={d0.platform} device_kind={d0.device_kind} "
          f"count={n} versions={json.dumps(_versions())}")
    print(f"[smoke] compile_cache={cache_dir} host={json.dumps(_host_tiers())}")
    lm_mesh = (2, 1, 2) if n >= 4 else (n, 1, 1)
    phases = (
        ("trainer", lambda: phase_trainer(num_nodes=n)),
        ("lm", lambda: phase_lm(mesh_shape=lm_mesh, batch=8 * lm_mesh[0])),
        ("hybrid_lm", phase_hybrid_lm),
        ("serve", phase_serve),
        ("wire_kernels", phase_wire_kernels),
    )
    t0 = time.perf_counter()
    for name, fn in phases:
        res = measure(fn)           # a failing phase raises: exit code != 0
        # the allocator's high-water mark since process start
        res["peak_hbm_gb_so_far"] = round(
            (d0.memory_stats() or {}).get("peak_bytes_in_use", 0) / 2**30, 2)
        print(f"[smoke] {name}: {json.dumps(res)}", flush=True)
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": n}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
