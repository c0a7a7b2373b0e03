"""That the check of kind ``train_lm`` can fail, at toy size on the CPU: the
control (the plain reference with its PARAMETERS kept in bfloat16, the nearest
precision below the float32 the configurations state) reads outside the toy's
tolerance on three seeds, and a run driven through the kind with the timed
step broken underneath comes out not ``correct``.  The readings that the
cells' own tolerances were set from are chip runs (PERF.md section 4)."""

import jax
import jax.numpy as jnp
import pytest

import manifest_rules as rules
from manifest_rules import bench_run, harness

MAN = bench_run.manifest()
CELL = {w["name"]: w for w in MAN["workloads"]}[min(
    w["name"] for w in MAN["workloads"]
    if rules.kind_of(w["name"]) == "train_lm")]
TOY = rules.TOYS["train_lm"]


def _bf16(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), tree)


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**33 + 7])
def test_control_with_bfloat16_parameters_reads_outside_the_tolerance(seed):
    fam = harness.load_module("families", TOY["config"]["family"])
    ref = harness.load_module("reference", TOY["config"]["family"])
    wl = TOY["workload"]
    model = fam.build(TOY["config"], max_len=wl["seq"])
    pkey, dkey = jax.random.split(harness.seed_key(seed))
    tokens = jax.random.randint(dkey, (wl["global_batch"], wl["seq"]), 0,
                                TOY["config"]["vocab_size"], jnp.int32)
    params = fam.to_reference(fam.init_params(model, pkey))
    want = ref.layerwise_sgd_losses(
        jax.tree_util.tree_map(jnp.copy, params), tokens, wl["lr"],
        wl["check_steps"], micro=wl["check_micro"])
    # the control: the same steps with every parameter rounded to bfloat16
    # where it is kept
    got, p = [], _bf16(params)
    for _ in range(wl["check_steps"] + 1):
        loss, grads = ref.layerwise_loss_and_grads(p, tokens,
                                                   micro=wl["check_micro"])
        got.append(float(loss))
        p = _bf16(jax.tree_util.tree_map(
            lambda a, g: a - wl["lr"] * g, p, grads))
    gap = max(abs(a - b) for a, b in zip(got, want))
    assert gap > 3 * wl["loss_tolerance"], (gap, got, want)


def _broken(monkeypatch, breaker):
    """The kind with ``breaker(step)`` in place of the step it builds."""
    kind = harness.load_module("kinds", "train_lm")
    build = kind.build_lm_step
    monkeypatch.setattr(kind, "build_lm_step",
                        lambda *a, **kw: breaker(build(*a, **kw)))


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    # the second half of the rows replaced by the first: the mean is taken
    # over half of the batch
    def breaker(step):
        def half(params, tokens):
            n = tokens.shape[0] // 2
            return step(params, jnp.concatenate([tokens[:n], tokens[:n]]))
        return half
    _broken(monkeypatch, breaker)
    run, result = rules.toy_run(CELL, harness.CompileMeter(), seconds=0.05)
    gap, tol = result.compared["loss_gap_step0"]
    assert gap > 10 * tol and result.correct is False
    assert bench_run.result_line(MAN, run, result)["correct"] is False


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    def breaker(step):
        def unchanged(params, tokens):          # the step donates its state
            kept = jax.tree_util.tree_map(jnp.copy, params)
            return kept, step(params, tokens)[1]
        return unchanged
    _broken(monkeypatch, breaker)
    run, result = rules.toy_run(CELL, harness.CompileMeter(), seconds=0.05)
    assert result.compared["loss_gap_step1"][0] > 10 * result.compared[
        "loss_gap_step1"][1]
    assert result.compared["loss_after_minus_first"][0] == 0.0
    assert result.correct is False
