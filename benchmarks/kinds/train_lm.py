"""Kind ``train_lm``: a language model trained by ``build_lm_step`` on a
(data, seq, model) mesh, on a ring of seeded token batches resident on the
device.  The workload file gives ``mesh`` [dp, sp, tp], ``global_batch``,
``seq``, ``lr``, ``compute_dtype``, ``scan_blocks``, ``remat``,
``ring_batches``, ``in_flight`` (steps handed to the device at a time) and
the check's ``loss_tolerance``.

Correctness, outside the window, on one batch of the cell's own shape (so the
system compiles ONE step program): the loss before any update and after each
of ``check_steps`` SGD steps, system against the plain reference applied
layer by layer.  After the window one more call of the step on that same
batch returns its loss under the trained parameters, which must be finite and
below its first: one batch compared with itself (the window may end on a
batch of the ring that no step has seen, whose loss says nothing).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlearn_tpu.models.transformer import param_specs
from distlearn_tpu.train.lm import build_lm_step

from harness import Result, log, seed_key
from kinds._train_loop import measure, trained


def run(run) -> Result:
    wl, cfg, fam = run.workload, run.config, run.family
    dp, sp, tp = wl["mesh"]
    devices = run.devices[:dp * sp * tp]
    mesh = Mesh(np.array(devices).reshape(dp, sp, tp),
                ("data", "seq", "model"))
    batch, seq, lr = wl["global_batch"], wl["seq"], wl["lr"]
    model = fam.build(cfg, max_len=seq, compute_dtype=wl["compute_dtype"],
                      scan_blocks=wl["scan_blocks"], remat=wl["remat"])
    pkey, dkey = jax.random.split(seed_key(run.seed))
    template = jax.eval_shape(lambda k: model.init(k)[0], pkey)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(template, "model"))
    ring = jax.jit(
        lambda k: jax.random.randint(k, (wl["ring_batches"], batch, seq), 0,
                                     cfg["vocab_size"], jnp.int32),
        out_shardings=NamedSharding(mesh, P(None, "data", "seq")))(dkey)
    batches = [ring[i] for i in range(wl["ring_batches"])]

    # the reference first, alone on the device; its buffers are gone before
    # the system's step (which fills the memory) is built
    steps = int(wl.get("check_steps", 2))
    params = fam.init_params(model, pkey, shardings)
    ref_params = jax.device_put(fam.to_reference(params), devices[0])
    del params
    check_tokens = jax.device_put(batches[0], devices[0])
    ref_losses = run.reference.layerwise_sgd_losses(
        ref_params, check_tokens, lr, steps, micro=int(wl.get("check_micro", 2)))
    del ref_params, check_tokens

    params = fam.init_params(model, pkey, shardings)   # again, from the seed
    step = build_lm_step(model, mesh, template, lr=lr)
    sys_losses = []
    for _ in range(steps + 1):
        params, loss = step(params, batches[0])
        sys_losses.append(float(loss))
    gaps = [abs(a - b) for a, b in zip(sys_losses, ref_losses)]
    tol = float(wl["loss_tolerance"])
    fell = ref_losses[0] - ref_losses[-1]
    ok_check = all(math.isfinite(x) for x in sys_losses) and max(gaps) <= tol
    log(f"check: system {sys_losses} reference {ref_losses} "
        f"max gap {max(gaps):.3g} (tolerance {tol}), reference fell {fell:.4g}")

    run.open_window()
    n = len(batches)

    def step_once(i):
        nonlocal params
        params, loss = step(params, batches[i % n])
        return loss

    rate, stats = measure(step_once, seconds=run.window_seconds(),
                          samples_per_call=batch, in_flight=wl["in_flight"])
    inside = run.close_window()
    last = float(stats.pop("last"))
    params, after = step(params, batches[0])    # the check's batch, again
    after = float(after)
    ok_after = trained(sys_losses[0], after)
    log(f"window: {stats['calls']} steps in {stats['elapsed_s']:.3f}s, the "
        f"window's last loss {last:.4f}; the check's batch {sys_losses[0]:.4f}"
        f" -> {after:.4f}")
    compared = {f"loss_gap_step{i}": [g, tol] for i, g in enumerate(gaps)}
    compared["loss_after_minus_first"] = [after - sys_losses[0], 0.0]
    return Result(
        correct=bool(ok_check and ok_after), attempted=stats["calls"],
        failed=0, compared=compared,
        end_to_end={"train_samples_per_s": rate},
        window={**stats, "compiled_inside": inside, "chips": len(devices),
                "flops_per_sample": fam.train_flops_per_sample(cfg, seq),
                "params": fam.param_count(cfg), "steps_per_call": 1,
                "tokens_per_sample": seq, "check_gap_max": max(gaps)})
