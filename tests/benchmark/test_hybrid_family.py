"""The hybrid family of the benchmark (``families/hybrid_lm.py``,
``reference/hybrid_lm.py``, the configuration and the cell
``solar-open2-250b.train-8k``), at toy size on the CPU: the system equals
the plain reference on logits, loss and EVERY gradient leaf; the layer-by-layer reference equals the whole-model one;
the analytic counts equal hand counts; the configuration is at its published
widths; the cell's toy twin runs end to end through the kind, and every
reader the cell lists gives what its ``source`` says.  Nothing here is a
measurement."""

import copy
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import manifest_rules as rules
from manifest_rules import BENCH, bench_run, harness

from distlearn_tpu.models.transformer import lm_loss  # noqa: E402

MAN = bench_run.manifest()
CELL = "solar-open2-250b.train-8k"
FAM = harness.load_module("families", "hybrid_lm")
REF = harness.load_module("reference", "hybrid_lm")

#: a period of 4 layers (1 softmax : 3 KDA), 16 experts of which 4 are held
TOY = {"family": "hybrid_lm", "hidden_size": 32, "num_hidden_layers": 4,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
       "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                              "num_heads": 4, "num_kv_heads": None},
       "kda_gate_rank": 8, "gqa_layers": [0, 4, 8], "n_routed_experts": 4,
       "held_experts": [1, 5, 6, 11], "n_router_outputs": 16,
       "num_experts_per_tok": 4, "moe_intermediate_size": 24,
       "n_shared_experts": 1, "vocab_size": 97, "rms_norm_eps": 1e-5,
       "max_position_embeddings": 256}
TOY_WL = {"kind": "train_lm", "mesh": [1, 1, 1], "global_batch": 1,
          "seq": 128, "lr": 0.05, "compute_dtype": None,
          "scan_blocks": False, "remat": "full", "ring_batches": 2,
          "in_flight": 2, "check_steps": 2, "check_micro": 1,
          "loss_tolerance": 1e-4, "trace_seconds": 0.3}


@pytest.fixture(scope="module")
def toy():
    """The toy model (L = 128 is four chunks of the shipped length), its
    parameters from a seed beyond 2**31, and the same in the reference's
    layout."""
    model = FAM.build(TOY, max_len=128)
    params = FAM.init_params(model, harness.seed_key(2**31 + 5))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 97,
                                jnp.int32)
    return model, params, FAM.to_reference(params), tokens


def test_system_equals_reference_on_logits_loss_and_every_gradient(toy):
    model, params, rp, tokens = toy
    got, state = model.apply(params, {}, tokens, train=False)
    want = REF.logits(rp, tokens)
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert int(state["moe_dropped"].sum()) == 0
    l_sys, g_sys = jax.value_and_grad(
        lambda p: lm_loss(model, p, tokens))(params)
    l_ref, g_ref = jax.value_and_grad(REF.loss)(rp, tokens)
    assert float(l_sys) == pytest.approx(float(l_ref), rel=1e-6)
    got_leaves = jax.tree_util.tree_leaves_with_path(FAM.to_reference(g_sys))
    want_leaves = jax.tree_util.tree_leaves_with_path(g_ref)
    assert len(got_leaves) == len(want_leaves) == 3 + 14 + 3 * 24
    for (path, a), (_, b) in zip(got_leaves, want_leaves):
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-6 + 2e-4 * float(jnp.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))
    # the step sizes the delta rule must take: both sides of 1
    beta = 2 * jax.nn.sigmoid(params["layer1"]["wb"].sum(0))
    assert float(beta.max()) > 1.0 > float(beta.min())


def test_layerwise_reference_equals_whole_model_and_streams_its_updates(toy):
    _, _, rp, tokens = toy
    want_l, want_g = jax.value_and_grad(REF.loss)(rp, tokens)
    got_l, got_g = REF.layerwise_loss_and_grads(rp, tokens, micro=1)
    assert got_l == pytest.approx(float(want_l), rel=1e-6)
    assert (got_g.held, got_g.top_k) == (rp.held, rp.top_k) == (
        (1, 5, 6, 11), 4)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-6 + 2e-5 * float(jnp.abs(b).max()))
    assert REF.layerwise_loss(rp, tokens, micro=1) == pytest.approx(
        float(want_l), rel=1e-6)
    # one micro-batch: each layer updated the moment its gradient is made;
    # several: the gradient tree accumulated — the same SGD
    fresh = lambda: jax.tree_util.tree_map(jnp.copy, rp)    # noqa: E731
    streamed = REF.layerwise_sgd_losses(fresh(), tokens, 0.05, 2, micro=2)
    summed = REF.layerwise_sgd_losses(fresh(), tokens, 0.05, 2, micro=1)
    assert streamed == pytest.approx(summed, rel=1e-5)
    assert streamed[2] < streamed[1] < streamed[0]


def test_reference_is_written_without_the_systems_model():
    text = open(os.path.join(BENCH, "reference", "hybrid_lm.py")).read()
    assert "distlearn_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan(step" in text          # the recurrence, not chunks


def test_counts_against_hand_counts():
    # D=4, one softmax layer (2 heads over 1 K/V head of 2) and one KDA
    # layer (2 heads of 2, rank 2, conv 4), 6 experts of width 3 of which 2
    # are held, 3 a token, 1 shared, vocabulary 10
    cfg = {"hidden_size": 4, "num_hidden_layers": 2,
           "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
           "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 2,
                                  "num_heads": 2}, "kda_gate_rank": 2,
           "gqa_layers": [0], "n_routed_experts": 2, "held_experts": [0, 4],
           "n_router_outputs": 6, "num_experts_per_tok": 3,
           "moe_intermediate_size": 3, "n_shared_experts": 1,
           "vocab_size": 10}
    moe = 4 * 6 + 3 * 4 * 3 + 2 * 3 * 4 * 3 + 2 * 4     # router shared held norms
    gqa = 3 * 4 * 4 + 2 * 4 * 2                         # q gate o; k v
    kda = 4 * 4 * 4 + 2 * (4 * 2 + 2 * 4) + 4 * 2 + 3 * 4 * 4 + 4 + 2 + 2
    assert FAM.param_count(cfg) == 2 * 10 * 4 + 4 + gqa + kda + 2 * moe
    # per token, forward, x2 a multiply-add
    moe_f = 2 * (4 * 6 + 3 * 4 * 3 + (3 * 2 / 6) * 3 * 4 * 3)
    gqa_f = 2 * gqa
    kda_f = 2 * (4 * 4 * 4 + 2 * (4 * 2 + 2 * 4) + 4 * 2 + 3 * 4 * 4) \
        + 7 * 2 * 2 * 2
    seq = 8
    want = 3 * (seq * (gqa_f + kda_f + 2 * moe_f + 2 * 4 * 10)
                + 2 * seq * seq * 2 * 2)
    assert FAM.train_flops_per_sample(cfg, seq) == pytest.approx(want)
    # the chunkwise delta rule at the yardstick's C=32, c=8 (pinned in the
    # family, not read from the program), K=2, one KDA layer, 2 heads, two
    # chunks:
    assert (FAM.ROOFLINE_CHUNK, FAM.ROOFLINE_SUB) == (32, 8)
    fwd = 4 * 32 * 8 * 2 + 2 * 2 * (1024 - 256) + 2 * 32768 / 3 \
        + 4 * 1024 * 2 + 6 * 32 * 4 + 2 * 1024 * 2
    ops, nbytes = FAM.delta_rule_cost(cfg, 64)
    assert ops == pytest.approx(4 * fwd * 2 * 2)
    io, state = 3 * 2 * 2 + 2 * 4 + 4, 2 * 2 * 4 / 32
    assert nbytes == pytest.approx(
        (2 * (io + 4 + state) + (2 * io + 4 + state)) * 64 * 2)
    with pytest.raises(ValueError, match="held here"):
        FAM.param_count(dict(cfg, n_routed_experts=3))
    with pytest.raises(ValueError, match="scan_blocks"):
        FAM.build(TOY, scan_blocks=True)


def test_solar_open2_is_at_its_published_widths():
    cfg = harness.load_json("configs", "solar-open2-250b.json")
    lin = cfg["linear_attn_config"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (4096, 64, 8, 128)
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (64, 128, 4)
    assert (cfg["moe_intermediate_size"], cfg["n_router_outputs"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["rms_norm_eps"]) == (1280, 320, 8, 1, 1e-5)
    assert cfg["tie_word_embeddings"] is False and cfg["use_rope"] is False
    # the cut: one whole period, 8 held experts, an eighth of the vocabulary
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 8, 24576)
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 320,
                                "vocab_size": 196608}
    assert cfg["held_experts"] == list(range(8))
    assert FAM._sizes(cfg)["types"] == ["gqa", "kda", "kda", "kda"]
    assert cfg["stands_for"] and cfg["assumed"] and cfg["departures"]
    assert FAM.param_count(cfg) == cfg["parameters"] == 1295086144
    # every number of the source's config is here under its own key
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.isfile(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Solar-Open2-250B")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    wl = harness.load_json("workloads", CELL + ".json")
    assert (wl["global_batch"], wl["seq"], wl["mesh"]) == (1, 8192, [1, 1, 1])
    assert wl["scan_blocks"] is False and wl["remat"] == "full"


@pytest.fixture(scope="module")
def toy_cell_run():
    cell = {w["name"]: w for w in MAN["workloads"]}[CELL]
    return bench_run.measure_cell(
        cell, copy.deepcopy(TOY), copy.deepcopy(TOY_WL), seed=2**31 + 7,
        seconds=0.5, trace=0, devices=jax.devices(), peaks=rules.PEAKS,
        meter=harness.CompileMeter(), t_process=time.perf_counter())


def test_cells_toy_twin_runs_end_to_end_through_the_kind(toy_cell_run):
    """The accepted kind ``train_lm`` takes the new family as data: the toy
    cell is checked against the reference at its own tolerance and trains."""
    run, result = toy_cell_run
    line = bench_run.result_line(MAN, run, result)
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s"}
    assert result.window["check_gap_max"] < 1e-4
    assert result.window["params"] == FAM.param_count(TOY)
    # untraced: the readers of the host's clock find their numbers, those
    # of the device trace nothing — the cell's own among them
    found = rules.readers_keep_the_source_rule(MAN, run, result)
    assert {"linattn_core_ms.train", "moe_ms.train", "linattn_roofline.train",
            "fwd_ms.train", "unscoped_share.train"} <= set(found)
    assert found["mfu.train"] > 0 and found["compile_s"] > 0


def test_the_cells_own_readers_find_their_instructions_in_the_toy_step(
        toy_cell_run, monkeypatch):
    """The family that feeds them: on the toy step's OWN optimized text,
    with a made-up trace in which every top-level instruction of the entry
    computation ran for a millisecond a step, the scope readers find
    ``linattn_core`` and ``moe``, and the roofline reader the instructions
    named ``delta_rule`` — a loop over chunks once, its body not again."""
    import scope_reduce
    run, result = toy_cell_run
    text = scope_reduce.step_hlo(run, result)       # untraced: no recompile
    where, calls, entry = scope_reduce.structure(text)
    calls_n = result.window["calls"]
    ops = {f"{n} f32[1]": [1e-3 * calls_n, calls_n, 1e-3 * calls_n]
           for n, comp in where.items() if comp == entry}
    window = {k: v for k, v in result.window.items()
              if k != "scope_reduction"}
    fake = type(result)(correct=True, attempted=1, failed=0, end_to_end={},
                        window=window)
    monkeypatch.setattr(run.trace, "reduction", {"ops": ops})
    read = lambda m: harness.load_module("layer_metrics", m).read(  # noqa: E731
        run, fake)
    assert read("linattn_core_ms.train") > read("moe_ms.train") > 0
    table = scope_reduce._lm_program()[2](text)
    named = {n for n, o in table.items()
             if "delta_rule" in scope_reduce.components(o)}
    top = {n for n in named - scope_reduce.enclosed_by(text, named)
           if where[n] == entry}
    assert top and any(n in calls for n in top)     # loops among them
    assert any(where[n] != entry for n in named)    # and bodies left out
    whole = scope_reduce.inner_whole_s(run, fake, "delta_rule")
    assert whole == pytest.approx(1e-3 * len(top))
    ops_1, bytes_1 = FAM.delta_rule_cost(TOY, TOY_WL["seq"])
    least = max(ops_1 / rules.PEAKS["bf16_flops_per_s"],
                bytes_1 / rules.PEAKS["hbm_bytes_per_s"])
    assert read("linattn_roofline.train") == pytest.approx(
        100 * least / whole)
