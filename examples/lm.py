#!/usr/bin/env python
"""Long-context transformer LM training over a (data, seq, model) mesh —
the framework's TPU-idiomatic extension beyond the reference's CNN-only
scope (SURVEY.md §2c: SP/TP/EP "explicitly absent" there; first-class
here).

One SPMD program runs data parallelism (gradient psum), sequence
parallelism (ring or all-to-all attention + cross-shard shifted targets),
tensor parallelism (Megatron sharded projections), and optionally expert
parallelism (routed MoE FFNs sharded over the data axis) — all inside a
single jitted step (distlearn_tpu/train/lm.py).

Run (8 virtual CPU devices):
    python examples/lm.py --dp 2 --sp 2 --tp 2
    python examples/lm.py --dp 4 --sp 2 --tp 1 --moeExperts 4
On a TPU (this process takes the chips; exits non-zero if JAX finds none):
    python examples/lm.py --tpu --dp 1 --sp 1 --tp 1 --dim 1024 --depth 8
Train then serve with continuous batching (docs/SERVING.md; tp>1
shards the decode tick too; drive with examples/lm_client.py):
    python examples/lm.py --dp 1 --sp 1 --tp 1 --serve 4 --servePort 9123
"""

from __future__ import annotations

from common import setup_platform
from distlearn_tpu.utils.flags import OBS_FLAGS, parse_flags


def main():
    opt = parse_flags("Train a transformer LM with 3D/4D parallelism.", {
        "dp": (2, "data-parallel mesh axis size"),
        "sp": (2, "sequence-parallel axis size (ring attention shards)"),
        "tp": (2, "tensor-parallel axis size (Megatron projections)"),
        "pp": (0, "pipeline-parallel stages (depth/pp blocks per stage; "
                  "requires --sp 1 --tp 1 and --depth % --pp == 0)"),
        "ppSchedule": ("gpipe", "pipeline schedule: gpipe | 1f1b (1f1b "
                       "starts each microbatch's backward as it leaves "
                       "the last stage — O(stages) activation liveness)"),
        "microbatches": (4, "pipeline microbatches per step (with --pp)"),
        "dim": (128, "model width"),
        "depth": (4, "number of blocks"),
        "vocab": (256, "vocabulary size"),
        "seqLen": (128, "global sequence length"),
        "batchSize": (8, "global batch size"),
        "steps": (30, "training steps"),
        "learningRate": (0.1, "SGD learning rate"),
        "seqImpl": ("ring", "sequence attention: ring | alltoall"),
        "seqLayout": ("contig", "sequence shard layout: contig | zigzag "
                      "(zigzag balances the causal ring so masked blocks "
                      "are never computed; needs --seqImpl ring)"),
        "attnImpl": ("", "force the single-device attention path: '' "
                     "(chosen from shape, dtype and backend) | xla | splash "
                     "(blockwise Pallas kernel: causal block skip, no "
                     "[B,H,L,L] buffer; interpreted off the TPU)"),
        "scanBlocks": (False, "scanned-depth layout: block params stacked,"
                       " depth loop as one lax.scan (program size flat in"
                       " depth; dense models only)"),
        "moeExperts": (0, "experts per MoE block (0 = dense; must equal "
                          "--dp, experts shard over the data axis)"),
        "moeTopK": (1, "experts per token (1 = Switch, 2 = GShard)"),
        "moeBalanceWeight": (0.01, "Switch load-balancing auxiliary loss "
                                   "weight (0 disables; without it top-1 "
                                   "routing collapses onto few experts)"),
        "remat": (False, "jax.checkpoint each block (long-context memory;"
                  " same as --rematMode full)"),
        "rematMode": ("", "'' | full | mlp — mlp checkpoints only the FFN "
                      "half, keeping attention residuals saved (selective "
                      "activation recomputation)"),
        "zero": (False, "train with Adam under ZeRO-1: optimizer state + "
                        "f32 masters sharded over the data axis, composed "
                        "with the sp/tp axes (train.build_lm_zero_mesh_step;"
                        " dense models only)"),
        "mixed": (False, "bf16 working params + replicated f32 masters: "
                         "every matmul pass reads 2-byte weights, the "
                         "update stays exact (train.build_lm_mixed_step / "
                         "build_lm_mixed_optax_step; not with --pp/--zero,"
                         " which manage their own param layouts)"),
        "fsdp": (False, "ZeRO-3 / fully-sharded data parallelism: params "
                        "LIVE sharded 1/dp per device, plain jit + GSPMD "
                        "inserts the gathers (train.build_lm_fsdp_step; "
                        "needs --sp 1 --tp 1, sgd, dense)"),
        "generate": (0, "after training, greedy-decode this many tokens "
                        "from held-out prompts with the KV-cached "
                        "decoder (models.greedy_generate; single-replica "
                        "param layouts: not --pp/--zero/--fsdp)"),
        "serve": (0, "after training, serve the model with this many "
                     "continuous-batching decode slots (distlearn_tpu."
                     "serve; 'G'/'R' frames, drive with examples/"
                     "lm_client.py; not --pp/--zero/--fsdp; SIGTERM or "
                     "Ctrl-C drains in-flight requests then exits)"),
        "servePort": (0, "serving port (0 = ephemeral, printed at "
                         "startup)"),
        "optimizer": ("sgd", "sgd | adam | adamw — non-sgd runs the "
                             "replicated-state optax step "
                             "(train.build_lm_optax_step; needs --tp 1)"),
        "accumSteps": (1, "gradient-accumulation microbatches per step "
                          "(memory lever; effective batch unchanged)"),
        "profile": ("", "capture a jax.profiler trace of steps 6..10 into "
                        "this directory (view in TensorBoard/Perfetto)"),
        "bf16": (False, "bfloat16 compute"),
        "tpu": (False, "run on the TPU backend"),
        "seed": (0, "init seed"),
        **OBS_FLAGS,
    })
    remat = opt.rematMode or ("full" if opt.remat else False)
    if opt.seqLayout not in ("contig", "zigzag"):
        raise SystemExit(f"--seqLayout {opt.seqLayout!r}: contig | zigzag")
    if opt.seqLayout == "zigzag":
        if opt.seqImpl != "ring":
            raise SystemExit("--seqLayout zigzag needs --seqImpl ring")
        if opt.pp or opt.zero:
            raise SystemExit("--seqLayout zigzag composes with the fused "
                             "sgd/optax steps (not --pp/--zero)")
    if opt.scanBlocks and (opt.moeExperts or opt.pp):
        raise SystemExit("--scanBlocks needs a homogeneous dense stack "
                         "and the non-pp step (pipeline stages shard the "
                         "per-block layout)")
    if opt.ppSchedule not in ("gpipe", "1f1b"):
        raise SystemExit(f"--ppSchedule {opt.ppSchedule!r}: gpipe | 1f1b")
    if opt.mixed and (opt.pp or opt.zero):
        raise SystemExit("--mixed composes with the fused sgd/optax steps "
                         "(--pp stages and --zero shards manage their own "
                         "parameter layouts)")
    if opt.fsdp and (opt.sp != 1 or opt.tp != 1 or opt.pp or opt.zero
                     or opt.mixed or opt.moeExperts
                     or opt.optimizer != "sgd"):
        raise SystemExit("--fsdp shards the whole model over the data "
                         "axis: pass --sp 1 --tp 1 and no "
                         "--pp/--zero/--mixed/--moeExperts/--optimizer")
    if opt.pp:
        if opt.sp != 1 or opt.tp != 1:
            raise SystemExit("--pp composes with data parallelism only: "
                             "pass --sp 1 --tp 1 (PP and TP/SP cover "
                             "different model regimes)")
        if opt.depth % opt.pp:
            raise SystemExit(f"--pp {opt.pp} needs --depth divisible by "
                             f"{opt.pp} (equal blocks per stage)")
        if (opt.accumSteps != 1 or opt.moeExperts or opt.zero
                or opt.optimizer != "sgd"):
            raise SystemExit("--pp does not support --accumSteps/"
                             "--moeExperts/--zero/--optimizer (GPipe "
                             "microbatching IS the accumulation lever on "
                             "this path; MoE/ZeRO/optax need the non-pp "
                             "step)")
        if remat == "mlp":
            raise SystemExit("--rematMode mlp is the non-pp step's "
                             "selective mode; the pipeline stage fn "
                             "checkpoints whole blocks — use --remat "
                             "(full) with --pp")
    if opt.serve and (opt.pp or opt.zero or opt.fsdp):
        raise SystemExit("--serve needs a single-replica param layout "
                         "(not --pp/--zero/--fsdp)")
    if opt.serve and opt.moeExperts:
        raise SystemExit("--serve supports dense models (per-tick MoE "
                         "routing would not match the trained capacity "
                         "math)")
    n_dev = opt.dp * opt.sp * opt.tp * max(1, opt.pp)
    setup_platform(n_dev, opt.tpu)
    from easgd_common import obs_finish, obs_setup
    obs_http = obs_setup(opt)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import random
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from contextlib import ExitStack

    from distlearn_tpu.models.transformer import (lm_loss, param_specs,
                                                  transformer_lm)
    from distlearn_tpu.train.lm import (build_lm_moe_metrics,
                                        build_lm_pp_1f1b_step,
                                        build_lm_pp_step, build_lm_step,
                                        stack_blocks)
    from distlearn_tpu.utils.logging import root_print
    from distlearn_tpu.utils.profiling import StepTimer, trace

    log = root_print(0)
    if opt.moeExperts and opt.moeExperts != opt.dp:
        raise SystemExit(f"--moeExperts {opt.moeExperts} must equal --dp "
                         f"{opt.dp} (one expert per data-parallel device)")
    devs = jax.devices()
    if len(devs) < n_dev:
        raise SystemExit(f"need {n_dev} devices (dp*sp*tp*pp), "
                         f"have {len(devs)}")
    cdtype = jnp.bfloat16 if opt.bf16 else None
    lm = transformer_lm(
        vocab=opt.vocab, dim=opt.dim, depth=opt.depth,
        heads=max(4, opt.dim // 64), max_len=opt.seqLen,
        compute_dtype=cdtype,
        seq_impl=opt.seqImpl, remat=remat,
        attn_impl=opt.attnImpl or None, scan_blocks=opt.scanBlocks,
        moe_experts=opt.moeExperts, moe_top_k=opt.moeTopK)
    params, _ = lm.init(random.PRNGKey(opt.seed))
    if opt.pp:
        mesh = Mesh(np.array(devs[:n_dev]).reshape(opt.dp, opt.pp),
                    ("data", "pipe"))
        log(f"mesh dp={opt.dp} pipe={opt.pp} on {devs[0].platform}; "
            f"{opt.microbatches} microbatches")
        shared, stacked = stack_blocks(params, opt.depth)
        shared = jax.device_put(shared, NamedSharding(mesh, P()))
        stacked = jax.device_put(stacked, NamedSharding(mesh, P("pipe")))
        builder = (build_lm_pp_1f1b_step if opt.ppSchedule == "1f1b"
                   else build_lm_pp_step)
        pp_step = builder(mesh, shared, stacked,
                          lr=opt.learningRate,
                          num_microbatches=opt.microbatches,
                          compute_dtype=cdtype, remat=bool(remat))
        state = {"shared": shared, "stacked": stacked}

        def step(st, tokens):
            sh, stk, loss = pp_step(st["shared"], st["stacked"], tokens)
            return {"shared": sh, "stacked": stk}, loss
        params = state
        tok_spec = P("data")
    else:
        mesh = Mesh(np.array(devs[:n_dev]).reshape(opt.dp, opt.sp, opt.tp),
                    ("data", "seq", "model"))
        log(f"mesh dp={opt.dp} sp={opt.sp} tp={opt.tp} on "
            f"{devs[0].platform}; seq_impl={opt.seqImpl}"
            + (f"; {opt.moeExperts} experts" if opt.moeExperts else ""))
        if opt.attnImpl and opt.sp > 1:
            log(f"NOTE: --attnImpl {opt.attnImpl} is inert with --sp "
                f"{opt.sp} > 1 — the ring/all-to-all blockwise path "
                "takes over (see parallel/sequence.py ring_attention)")
        ep_axis = "data" if opt.moeExperts else None
        placed = jax.device_put(
            params, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                param_specs(params, tp_axis="model", ep_axis=ep_axis)))
        if opt.zero:
            if opt.moeExperts or opt.accumSteps != 1 \
                    or opt.optimizer != "sgd":
                raise SystemExit("--zero supports dense models without "
                                 "--accumSteps/--moeExperts, and picks its "
                                 "own optimizer (Adam against the sharded "
                                 "f32 masters) — drop --optimizer")
            import optax

            from distlearn_tpu.train import (build_lm_zero_mesh_step,
                                             init_lm_zero_mesh_state)
            if opt.learningRate > 0.01:
                log(f"NOTE: --learningRate {opt.learningRate} is large "
                    "for Adam; --zero usually wants ~1e-3 (large Adam "
                    "steps diverge)")
            tx = optax.adam(opt.learningRate)
            step = build_lm_zero_mesh_step(lm, mesh, params, tx)
            params = init_lm_zero_mesh_state(placed, mesh, tx)
            log("ZeRO-1: Adam state + f32 masters sharded over the data "
                "axis (composed with sp/tp)")
        elif opt.optimizer != "sgd":
            if opt.tp != 1 or opt.moeExperts:
                raise SystemExit(f"--optimizer {opt.optimizer} uses the "
                                 "replicated-state optax step: pass --tp 1 "
                                 "(TP needs --zero's sharded masters) and "
                                 "no --moeExperts (expert-sharded state)")
            import optax

            from distlearn_tpu.train import (LMOptaxState,
                                             build_lm_optax_step)
            makers = {"adam": optax.adam, "adamw": optax.adamw}
            if opt.optimizer not in makers:
                raise SystemExit(f"unknown --optimizer {opt.optimizer!r} "
                                 f"(sgd | {' | '.join(makers)})")
            tx = makers[opt.optimizer](opt.learningRate)
            if opt.mixed:
                from distlearn_tpu.train import (
                    build_lm_mixed_optax_step, init_lm_mixed_optax_state)
                step = build_lm_mixed_optax_step(
                    lm, mesh, tx, accum_steps=opt.accumSteps,
                    seq_layout=opt.seqLayout)
                params = init_lm_mixed_optax_state(placed, tx)
                log(f"{opt.optimizer}, mixed precision: bf16 working "
                    "params + f32 masters")
            else:
                step = build_lm_optax_step(lm, mesh, tx,
                                           accum_steps=opt.accumSteps,
                                           seq_layout=opt.seqLayout)
                params = LMOptaxState(placed, tx.init(placed))
                log(f"{opt.optimizer} via the replicated-state optax "
                    "LM step")
        elif opt.fsdp:
            from distlearn_tpu.train import (build_lm_fsdp_step,
                                             init_lm_fsdp_params)
            step = build_lm_fsdp_step(lm, mesh, params,
                                      lr=opt.learningRate,
                                      accum_steps=opt.accumSteps)
            params = init_lm_fsdp_params(params, mesh)
            log("ZeRO-3/FSDP: params live sharded 1/dp per device; "
                "jit+GSPMD inserts the gathers")
        elif opt.mixed:
            from distlearn_tpu.train import (build_lm_mixed_step,
                                             init_lm_mixed_state)
            step = build_lm_mixed_step(
                lm, mesh, params, lr=opt.learningRate,
                ep_axis=ep_axis, accum_steps=opt.accumSteps,
                moe_balance_weight=(opt.moeBalanceWeight
                                    if opt.moeExperts else 0.0),
                seq_layout=opt.seqLayout)
            params = init_lm_mixed_state(placed)
            log("mixed precision: bf16 working params + f32 masters "
                "(matmuls read 2-byte weights; the update stays exact)")
        else:
            step = build_lm_step(
                lm, mesh, params, lr=opt.learningRate,
                ep_axis=ep_axis, accum_steps=opt.accumSteps,
                moe_balance_weight=(opt.moeBalanceWeight
                                    if opt.moeExperts else 0.0),
                seq_layout=opt.seqLayout)
            params = placed
        tok_spec = P("data", "seq")
        if opt.moeExperts:
            # template = the raw placed params (the train state may wrap
            # them, e.g. LMMixedState)
            moe_metrics = build_lm_moe_metrics(lm, mesh, placed,
                                               ep_axis=ep_axis)

    # Synthetic corpus: order-2 Markov tokens — learnable next-token
    # structure without any dataset download (zero-egress env).
    rng = np.random.RandomState(opt.seed)
    trans = rng.dirichlet(np.ones(opt.vocab) * 0.05,
                          size=opt.vocab).astype(np.float64)
    toks = np.zeros((opt.batchSize, opt.seqLen), np.int32)
    toks[:, 0] = rng.randint(0, opt.vocab, opt.batchSize)
    for t in range(1, opt.seqLen):
        for b in range(opt.batchSize):
            toks[b, t] = rng.choice(opt.vocab, p=trans[toks[b, t - 1]])
    if opt.seqLayout == "zigzag":
        from distlearn_tpu.parallel.sequence import zigzag_indices
        toks = toks[:, zigzag_indices(opt.sp, opt.seqLen)]
        log("zigzag sequence layout: balanced causal ring (masked blocks "
            "never computed)")
    tokens = jax.device_put(jnp.asarray(toks),
                            NamedSharding(mesh, tok_spec))

    timer = StepTimer()
    do_profile = bool(opt.profile) and opt.steps >= 6
    if opt.profile and not do_profile:
        log(f"--profile ignored: needs --steps >= 6 (warmup is steps 1-5), "
            f"got {opt.steps}")
    prof_stop = min(10, opt.steps)
    with ExitStack() as stack:            # guarantees stop_trace on error
        for i in range(1, opt.steps + 1):
            if do_profile and i == 6:     # skip compile + warmup steps
                # drain the async queue so warmup work isn't in the trace
                jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
                timer.reset_window()      # drain time is not a step
                stack.enter_context(trace(opt.profile))
            timer.tick()
            params, loss = step(params, tokens)
            if i == 1:                    # the step's program is traced now
                from distlearn_tpu.parallel.sequence import \
                    attention_paths_traced
                log(f"single-device attention paths traced "
                    f"(attn_kernel_total): "
                    f"{attention_paths_traced() or 'none'}")
                from distlearn_tpu.parallel.ep import grouped_paths_traced
                log(f"held experts' grouped products traced "
                    f"(moe_grouped_total): "
                    f"{grouped_paths_traced() or 'none'}")
                from distlearn_tpu.utils.compile_cache import programs
                log("set-up by program [trace s, lower s, compile s, cache, "
                    "compiles] (jit.* spans): " + "; ".join(
                        f"{r['fun']} {r['trace_s']:.2f} {r['lower_s']:.2f} "
                        f"{r['compile_s']:.2f} {r['cache'] or '-'} "
                        f"{r['count']}" for r in programs()[:12]))
            if do_profile and i == prof_stop:
                jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
                timer.reset_window()
                stack.close()
                log(f"profiler trace written to {opt.profile}")
            if i % 10 == 0 or i == opt.steps:
                extra = ""
                if opt.moeExperts and not opt.pp:
                    m = jax.device_get(moe_metrics(
                        getattr(params, "params", params), tokens))
                    extra = (f" [router balance "
                             f"{float(m['moe_balance_loss']):.3f}, dropped "
                             f"{float(m['moe_dropped_frac']):.3f}]")
                log(f"step {i}: loss {float(loss):.4f}{extra} "
                    f"({timer.steps_per_sec():.2f} steps/s)")
    jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
    if opt.generate:
        if opt.pp or opt.zero or opt.fsdp:
            raise SystemExit("--generate needs a single-replica param "
                             "layout (not --pp/--zero/--fsdp)")
        if opt.moeExperts:
            raise SystemExit("--generate supports dense models (per-tick "
                             "MoE routing would not match the trained "
                             "capacity math)")
        if opt.seqLayout == "zigzag":
            raise SystemExit("--generate decodes in natural order — drop "
                             "--seqLayout zigzag")
        from distlearn_tpu.models import greedy_generate
        # the trained params: unwrap mixed/optax states to the plain
        # tree, and GATHER any tp/sp-sharded leaves to the host — the
        # decoder runs single-replica regardless of the train mesh
        p = jax.device_get(getattr(params, "params", params))
        Pq = max(4, opt.seqLen // 8)
        steps = min(opt.generate, opt.seqLen - Pq)
        # two prompts of different lengths, left-padded to Pq: the
        # batched ragged path (prompt_lens) in one call
        plens = np.array([Pq, max(2, Pq // 2)], np.int32)
        prompts = np.zeros((2, Pq), np.int32)
        for b, L in enumerate(plens):
            prompts[b, Pq - L:] = toks[b % toks.shape[0], :L]
        gen = greedy_generate(p, jnp.asarray(prompts), steps,
                              attn_impl=opt.attnImpl or None,
                              prompt_lens=plens)
        for b, L in enumerate(plens):
            log(f"generated {gen.shape[1]} tokens (KV-cached greedy, "
                f"prompt len {L}): {np.asarray(gen[b]).tolist()}")
    if opt.serve:
        from distlearn_tpu.parallel.ha import install_signal_flush
        from distlearn_tpu.serve import DecodeEngine, ServeServer
        p = jax.device_get(getattr(params, "params", params))
        mesh_kw = {}
        if opt.tp > 1:
            # serve tp-sharded over a dedicated ("model",) submesh: the
            # decode tick is one jit/shard_map program, psums and all
            from jax.sharding import Mesh as _Mesh
            mesh_kw = {"mesh": _Mesh(np.array(jax.devices()[:opt.tp]),
                                     ("model",)),
                       "tp_axis": "model"}
        engine = DecodeEngine(p, num_slots=opt.serve, **mesh_kw)
        # warm the smallest prefill bucket + the tick program so the
        # first real request's TTFT is a tick, not a compile
        _slot, _ = engine.admit(np.ones(4, np.int32), 2)
        engine.tick()
        engine.finish(_slot)
        srv = ServeServer(engine, port=opt.servePort).start()
        install_signal_flush(srv)    # SIGTERM -> drain, then exit
        log(f"serving on {srv.host}:{srv.port} "
            f"({opt.serve} slots, max_len {engine.max_len}"
            + (f", tp={opt.tp}" if opt.tp > 1 else "") + ") — "
            f"drive with: python examples/lm_client.py "
            f"--port {srv.port}")
        try:
            while srv._thread is not None and srv._thread.is_alive():
                srv._thread.join(0.5)
        except KeyboardInterrupt:
            log("draining...")
            srv.checkpoint_now(wait=True)
        srv.stop()
        log("serve drained")
    obs_finish(opt, obs_http)
    log("done")


if __name__ == "__main__":
    main()
