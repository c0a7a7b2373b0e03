#!/usr/bin/env python
"""Distributed MNIST training with elastic averaging (AllReduceEA) — the
TPU-native counterpart of examples/mnist-ea.lua.

Reference cadence (SURVEY.md §3.2): one initial parameter sync
(mnist-ea.lua:63), per-step local SGD — collective-free — then every
``tau``-th step the fused elastic round (mnist-ea.lua:110,
lua/AllReduceEA.lua:31-45), end-of-epoch ``synchronizeCenter`` drift repair
(mnist-ea.lua:121).  tau=10 alpha=0.2 defaults match mnist-ea.lua:18.

Run:  python examples/mnist_ea.py --numNodes 4 [--tpu]
"""

from __future__ import annotations

from common import (setup_platform, resolve_num_nodes, device_stream,
                    device_stream_stacked)
from distlearn_tpu.utils.flags import (parse_flags, CKPT_FLAGS, NODE_FLAGS,
                                       TRAIN_FLAGS, EA_FLAGS)


def main():
    opt = parse_flags("Train MNIST with elastic averaging.", {
        **NODE_FLAGS,
        **TRAIN_FLAGS,
        **EA_FLAGS,
        "learningRate": (0.01, "learning rate"),
        "data": ("", "path to .npz (default: synthetic)"),
        "numExamples": (4096, "synthetic dataset size"),
        "reportEvery": (100, "steps between reports"),
        "scanCycle": (False, "run each tau-step EASGD cycle as ONE XLA "
                             "program (build_ea_cycle) — one host "
                             "dispatch per round instead of per step"),
        "momentum": (0.0, "local heavy-ball momentum — EAMSGD "
                          "(arXiv:1412.6651 §3); 0 = plain EASGD "
                          "(the reference)"),
        **CKPT_FLAGS,
    })
    setup_platform(opt.numNodes, opt.tpu)

    import jax
    import numpy as np
    from jax import random

    from distlearn_tpu.data import (PermutationSampler, load_npz, make_dataset,
                                    synthetic_mnist)
    from distlearn_tpu.models import mnist_cnn
    from distlearn_tpu.parallel import allreduce_ea
    from distlearn_tpu.parallel.mesh import MeshTree
    from distlearn_tpu.train import (build_ea_cycle, build_ea_steps,
                                     init_ea_state, reduce_confusion)
    from distlearn_tpu.utils import checkpoint as ckpt
    from distlearn_tpu.utils import metrics as M
    from distlearn_tpu.utils.logging import root_print
    from distlearn_tpu.utils.profiling import StepTimer

    log = root_print(0)
    tree = MeshTree(num_nodes=resolve_num_nodes(opt.numNodes, opt.tpu))
    log(f"mesh: {tree.num_nodes} nodes on {jax.devices()[0].platform}")

    if opt.data:
        x, y, nc = load_npz(opt.data)
    else:
        x, y, nc = synthetic_mnist(opt.numExamples, seed=opt.seed)
    ds = make_dataset(x, y, nc)

    model = mnist_cnn()
    ets = init_ea_state(model, tree, random.PRNGKey(opt.seed), nc)
    local_step, ea_round = build_ea_steps(model, tree, lr=opt.learningRate,
                                          alpha=opt.alpha,
                                          momentum=opt.momentum)
    tau = opt.communicationTime

    start_epoch = 1
    global_step = 0
    if opt.resume and opt.save and ckpt.latest_step(opt.save) is not None:
        restorable = {"params": ets.params, "model_state": ets.model_state,
                      "center": ets.center, "vel": ets.vel}
        try:
            restored, meta = ckpt.restore_checkpoint(opt.save, restorable)
        except KeyError:
            # pre-EAMSGD checkpoint without a velocity buffer: momentum
            # restarts from zero (ets.vel is already zeros)
            restorable.pop("vel")
            restored, meta = ckpt.restore_checkpoint(opt.save, restorable)
            restored["vel"] = None
        # re-place host arrays onto the mesh (stacked per-node sharding)
        ets = ets._replace(params=tree.put_per_node(restored["params"]),
                           model_state=tree.put_per_node(
                               restored["model_state"]),
                           center=tree.put_per_node(restored["center"]),
                           vel=(tree.put_per_node(restored["vel"])
                                if restored["vel"] is not None else ets.vel))
        start_epoch = meta["step"] + 1
        # resume the step counter too: the tau-spaced elastic-round cadence
        # must continue in phase with the uninterrupted run
        global_step = int(meta.get("global_step", 0))
        log(f"resumed from epoch {meta['step']} (step {global_step})")

    cycle = (build_ea_cycle(model, tree, lr=opt.learningRate, alpha=opt.alpha,
                            momentum=opt.momentum) if opt.scanCycle else None)
    timer = StepTimer()
    last_report = global_step   # scanCycle cadence: steps since last report
    for epoch in range(start_epoch, opt.numEpochs + 1):
        sampler = PermutationSampler(ds.size, seed=opt.seed + epoch)
        if opt.scanCycle:
            # τ local steps + elastic round per dispatch; a shorter final
            # group ends the epoch with an early round (the epoch-end
            # synchronizeCenter below follows it anyway).
            timer.reset_window()   # prime: first interval starts here
            timer.tick()
            for sxs, sys_ in device_stream_stacked(tree, ds, sampler,
                                                   opt.batchSize, tau):
                k = sxs.shape[0]
                ets, losses = cycle(ets, sxs, sys_)
                timer.tick(steps=k)   # interval since last tick = this cycle
                global_step += k
                # explicit steps-since-last-report: robust to a shorter
                # final group making global_step a non-multiple of tau, and
                # to reportEvery < tau (at most one report per cycle)
                if global_step - last_report >= opt.reportEvery:
                    last_report = global_step
                    cm = reduce_confusion(ets.cm)
                    log(f"step {global_step} loss "
                        f"{float(np.mean(np.asarray(losses))):.4f} "
                        f"{M.format_confusion(cm)}")
        else:
            timer.reset_window()   # epoch-boundary scatter/ckpt not a step
            for bx, by in device_stream(tree, ds, sampler, opt.batchSize):
                timer.tick()
                ets, losses = local_step(ets, bx, by)
                global_step += 1
                if global_step % tau == 0:       # mnist-ea.lua:110 cadence
                    ets = ea_round(ets)
                if global_step % opt.reportEvery == 0:
                    cm = reduce_confusion(ets.cm)
                    log(f"step {global_step} loss "
                        f"{float(np.mean(np.asarray(losses))):.4f} "
                        f"{M.format_confusion(cm)}")
        # end-of-epoch synchronizeCenter (mnist-ea.lua:121): broadcast node
        # 0's center replica — deterministic psums keep replicas identical,
        # this is the multi-host drift repair (lua/AllReduceEA.lua:74-84)
        ets = ets._replace(
            center=tree.scatter(ets.center, src=0),
            cm=jax.tree_util.tree_map(lambda c: c * 0, ets.cm))
        log(f"epoch {epoch}: ({timer.steps_per_sec():.1f} steps/s)")
        if opt.save:
            ckpt.save_checkpoint(
                opt.save, epoch,
                {"params": ets.params, "model_state": ets.model_state,
                 "center": ets.center, "vel": ets.vel},
                metadata={"epoch": epoch, "global_step": global_step,
                          "tau": tau, "alpha": opt.alpha,
                          "momentum": opt.momentum})
    jax.block_until_ready(ets.params)
    log("done")


if __name__ == "__main__":
    main()
