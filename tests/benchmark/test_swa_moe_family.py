"""The ``swa_moe_lm`` family of the benchmark (``families/swa_moe_lm.py``,
``reference/swa_moe_lm.py``, the configuration and the cell
``smallthinker-21b-a3b.train-16k``), at toy size on the CPU: the system
equals the plain reference on logits, loss and EVERY gradient leaf (float32
tight, bfloat16 compute loose); four planted faults each move the float32
loss out of the tight limit; the four chips' shares add up to the uncut
reference's layer; the layer-by-layer reference equals the whole-model one;
the analytic counts equal hand counts; the configuration is at its published
widths; the cell's toy twin runs end to end through the kind and every reader
the cell lists gives what its ``source`` says; the three readers the cell
brings against hand counts.  Nothing here is a measurement."""

import copy
import json
import os
import time
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import manifest_rules as rules
from manifest_rules import BENCH, bench_run, harness

import scope_reduce  # noqa: E402  (benchmarks/ is on the path by now)

from distlearn_tpu.models import hybrid  # noqa: E402
from distlearn_tpu.models.core import SCOPES  # noqa: E402
from distlearn_tpu.models.transformer import lm_loss  # noqa: E402
from distlearn_tpu.parallel import ep  # noqa: E402
from distlearn_tpu.utils.profiling import scope_table  # noqa: E402

MAN = bench_run.manifest()
CELL = "smallthinker-21b-a3b.train-16k"
CONFIG = "smallthinker-21b-a3b"
FAM = harness.load_module("families", "swa_moe_lm")
REF = harness.load_module("reference", "swa_moe_lm")

_LAYOUT = [0, 1, 1, 1] * 3
#: a period of 4 layers (1 full : 3 windowed over a band of 16), 16 experts
#: of which 4 are held, 3 a token
TOY = {"family": "swa_moe_lm", "hidden_size": 32, "num_hidden_layers": 4,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
       "rope_layout": _LAYOUT, "sliding_window_layout": _LAYOUT,
       "sliding_window_size": 16, "rope_theta": 1500000,
       "rope_scaling": None, "moe_num_primary_experts": 4,
       "held_experts": [1, 5, 6, 11], "n_router_outputs": 16,
       "moe_num_active_primary_experts": 3, "moe_ffn_hidden_size": 24,
       "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
       "tie_word_embeddings": False, "vocab_size": 97, "rms_norm_eps": 1e-6,
       "max_position_embeddings": 256}
TOY_WL = {"kind": "train_lm", "mesh": [1, 1, 1], "global_batch": 1,
          "seq": 64, "lr": 0.05, "compute_dtype": None,
          "scan_blocks": False, "remat": "full", "ring_batches": 2,
          "in_flight": 2, "check_steps": 2, "check_micro": 1,
          "loss_tolerance": 1e-4, "trace_seconds": 0.3}


def _toy(compute_dtype=None):
    """The toy model (L = 64 is four bands), its parameters from a seed
    beyond 2**31, and the same in the reference's layout."""
    model = FAM.build(TOY, max_len=64, compute_dtype=compute_dtype)
    params = FAM.init_params(model, harness.seed_key(2**31 + 5))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 97,
                                jnp.int32)
    return model, params, FAM.to_reference(params), tokens


@pytest.fixture(scope="module")
def toy():
    return _toy()


def test_system_equals_reference_on_logits_loss_and_every_gradient(toy):
    """float32 against float32: the limits are rounding alone (the same
    limits as the other pattern family's test; the two sides sum in
    different orders — grouped tiles against a masked loop, the kernel-less
    full square against blocks of queries)."""
    model, params, rp, tokens = toy
    got, state = model.apply(params, {}, tokens, train=False)
    np.testing.assert_allclose(got, REF.logits(rp, tokens), atol=3e-5)
    assert int(state["moe_dropped"].sum()) == 0
    assert state["moe_assignments"].shape == (4, 4)
    assert (rp.held, rp.top_k, rp.layout, rp.window, rp.theta) == (
        (1, 5, 6, 11), 3, (0, 1, 1, 1), 16, 1.5e6)
    l_sys, g_sys = jax.value_and_grad(
        lambda p: lm_loss(model, p, tokens))(params)
    l_ref, g_ref = jax.value_and_grad(REF.loss)(rp, tokens)
    assert float(l_sys) == pytest.approx(float(l_ref), rel=1e-6)
    got_leaves = jax.tree_util.tree_leaves_with_path(FAM.to_reference(g_sys))
    want_leaves = jax.tree_util.tree_leaves_with_path(g_ref)
    assert len(got_leaves) == len(want_leaves) == 3 + 4 * 10
    for (path, a), (_, b) in zip(got_leaves, want_leaves):
        assert float(jnp.abs(b).max()) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-6 + 2e-4 * float(jnp.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))


def test_bfloat16_compute_stays_near_the_float32_reference():
    """The cell's arithmetic (float32 parameters, bfloat16 products) at toy
    size, loose, each limit with its reason.  The loss within 2e-2 of the
    float32 reference's: bfloat16 keeps 8 bits, a logit of order 1 is off by
    4e-3 and the mean loss by about that (read: 2.8e-3; the float32
    comparison above holds 1e-6).  Every gradient leaf points where the
    reference's does, cosine at least 0.9: an entry-by-entry limit means
    nothing here, because the rounded activations swap the LAST of a few
    tokens' three experts (the choice is discrete) and those tokens'
    contributions move to another expert's rows — read: 0.945 for the last
    layer's router, 0.987 and up for every other leaf."""
    model, params, rp, tokens = _toy("bfloat16")
    l_sys, g_sys = jax.value_and_grad(
        lambda p: lm_loss(model, p, tokens))(params)
    l_ref, g_ref = jax.value_and_grad(REF.loss)(rp, tokens)
    assert abs(float(l_sys) - float(l_ref)) < 2e-2
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(FAM.to_reference(g_sys)),
            jax.tree_util.tree_leaves_with_path(g_ref)):
        a, b = (np.asarray(v, np.float64).ravel() for v in (a, b))
        cosine = a @ b / (np.linalg.norm(a) * np.linalg.norm(b))
        assert cosine > 0.9, (jax.tree_util.keystr(path), cosine)


def _fault(monkeypatch, name):
    """The system with one part of its mathematics left out."""
    if name == "windowed layers see the whole triangle":
        real = hybrid.local_attention
        monkeypatch.setattr(
            hybrid, "local_attention",
            lambda q, k, v, causal, window=None: real(q, k, v, causal=causal))
    elif name == "no rotation":
        monkeypatch.setattr(hybrid, "rotary", lambda x, pos, theta: x)
    elif name == "router reads the normed input":
        real = hybrid.moe_apply

        def normed(blk, x, cd, eps, held, top_k, ep_axis, route_from, act):
            return real(blk, x, cd, eps, held, top_k, ep_axis,
                        hybrid._rmsnorm(blk["ln1"], route_from, eps), act)
        monkeypatch.setattr(hybrid, "moe_apply", normed)
    else:
        assert name == "half of the held assignments dropped"
        real = ep.route_held

        def halved(*a, **kw):
            plan, slot_w, aux = real(*a, **kw)
            return plan, slot_w.at[1::2].set(0.0), aux
        monkeypatch.setattr(ep, "route_held", halved)


@pytest.mark.parametrize("name", [
    "windowed layers see the whole triangle", "no rotation",
    "router reads the normed input", "half of the held assignments dropped"])
def test_planted_fault_moves_the_float32_loss_out_of_the_limit(
        monkeypatch, toy, name):
    """The four faults the cell's check was read against on the chip
    (PERF.md section 4), each at toy size in float32, where the sound system
    holds 1e-6: the faulty system's loss is off by more than 1e-4.  Whatever
    the chip's bfloat16 check lets through, this does not."""
    _, params, rp, tokens = toy
    want = float(REF.loss(rp, tokens))
    _fault(monkeypatch, name)
    model = FAM.build(TOY, max_len=64)          # traced with the fault in
    assert abs(float(lm_loss(model, params, tokens)) - want) > 1e-4


def test_the_four_chips_shares_add_up_to_the_uncut_reference():
    """The guide's share test: 16 experts over 4 holders of 4.  Each
    holder's layer through the SYSTEM (its held experts' part; attention,
    the router and the residual computed alike by all) less the attention
    output, summed, plus the attention output ONCE, is the REFERENCE's
    layer with every expert held — for a full and for a windowed layer."""
    uncut = dict(TOY, moe_num_primary_experts=16,
                 held_experts=list(range(16)))
    model = FAM.build(uncut, max_len=64)
    params = FAM.init_params(model, harness.seed_key(7))
    whole = FAM.to_reference(params)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 32), jnp.float32)
    for n, windowed in ((0, False), (1, True)):
        blk = params[f"layer{n}"]
        want = REF.block(whole.tree["layers"][n], x, whole.held, 3, windowed,
                         16, 1.5e6)
        h = hybrid.gqa_apply(blk, x, jnp.float32, 1e-6,
                             *((16, 1.5e6) if windowed else ()))
        total, seen = h, 0
        for holder in range(4):
            held = tuple(range(4 * holder, 4 * holder + 4))
            part = dict(blk, **{k: blk[k][4 * holder:4 * holder + 4]
                                for k in ("we_gate", "we_up", "we_down")})
            y, aux = hybrid.moe_apply(part, h, jnp.float32, 1e-6, held, 3,
                                      None, route_from=x, act="relu")
            total = total + (y - h)
            seen += int(aux["assignments"].sum())
            assert int(aux["dropped"]) == 0
        assert seen == 2 * 64 * 3               # every assignment a holder
        np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


def test_layerwise_reference_equals_whole_model_and_streams_its_updates(toy):
    _, _, rp, tokens = toy
    want_l, want_g = jax.value_and_grad(REF.loss)(rp, tokens)
    got_l, got_g = REF.layerwise_loss_and_grads(rp, tokens, micro=1)
    assert got_l == pytest.approx(float(want_l), rel=1e-6)
    assert got_g.static == rp.static
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-6 + 2e-5 * float(jnp.abs(b).max()))
    assert REF.layerwise_loss(rp, tokens, micro=1) == pytest.approx(
        float(want_l), rel=1e-6)
    fresh = lambda: jax.tree_util.tree_map(jnp.copy, rp)    # noqa: E731
    streamed = REF.layerwise_sgd_losses(fresh(), tokens, 0.05, 2, micro=2)
    summed = REF.layerwise_sgd_losses(fresh(), tokens, 0.05, 2, micro=1)
    assert streamed == pytest.approx(summed, rel=1e-5)
    assert streamed[2] < streamed[1] < streamed[0]


def test_reference_attends_in_blocks_and_is_the_definition(monkeypatch):
    """Blocks of queries and of positions change no result: one block (the
    whole length) against eight, attention and loss; and the band is the
    definition's — a key ``window`` back is not attended."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 32, 4, 8), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)
            for _ in range(2))
    whole = REF.attention(q, k, v, 5)
    monkeypatch.setattr(REF, "_QUERY_BLOCK", 4)
    np.testing.assert_allclose(REF.attention(q, k, v, 5), whole, atol=1e-6)
    moved = jnp.abs(REF.attention(q, k, v.at[:, 10].add(100.0), 5)
                    - REF.attention(q, k, v, 5)).max(axis=(0, 2, 3))
    assert (moved[:10] == 0).all() and (moved[10:15] > 1e-3).all() \
        and (moved[15:] == 0).all()
    x = jnp.asarray(rng.randn(2, 32, 6), jnp.float32)
    head = jnp.asarray(rng.randn(6, 11), jnp.float32)
    tokens = jnp.asarray(rng.randint(0, 11, (2, 32)))
    lp = jax.nn.log_softmax(REF.head_logits(head, jnp.ones(6), x)[:, :-1], -1)
    want = -jnp.mean(jnp.take_along_axis(lp, tokens[:, 1:, None], -1))
    monkeypatch.setattr(REF, "_LOSS_BLOCK", 8)
    assert float(REF.head_loss(head, jnp.ones(6), x, tokens)) \
        == pytest.approx(float(want), rel=1e-6)


def test_reference_is_written_without_the_systems_model():
    text = open(os.path.join(BENCH, "reference", "swa_moe_lm.py")).read()
    assert "distlearn_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in text and "x @ layer[\"router\"]" in text


def test_counts_against_hand_counts():
    # D=4, two layers [full, windowed]: 2 query heads over 1 K/V head of 2,
    # a band of 3, 6 experts of width 3 of which 2 are held, 3 a token, no
    # shared expert, vocabulary 10
    cfg = {"hidden_size": 4, "num_hidden_layers": 2,
           "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
           "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
           "sliding_window_size": 3, "rope_theta": 1e4, "rope_scaling": None,
           "moe_num_primary_experts": 2, "held_experts": [0, 4],
           "n_router_outputs": 6, "moe_num_active_primary_experts": 3,
           "moe_ffn_hidden_size": 3, "moe_primary_router_apply_softmax": True,
           "norm_topk_prob": True, "tie_word_embeddings": False,
           "vocab_size": 10}
    attn = 2 * 4 * 4 + 2 * 4 * 2                        # q o; k v
    layer = attn + 4 * 6 + 2 * 3 * 4 * 3 + 2 * 4        # router held norms
    assert FAM.param_count(cfg) == 2 * 10 * 4 + 4 + 2 * layer
    # pairs: the triangle with its diagonal; the band i - 3 < j <= i
    assert FAM.attended_pairs(8, None) == 36
    assert FAM.attended_pairs(8, 3) == 1 + 2 + 6 * 3 == 21
    assert FAM.attended_pairs(2, 3) == FAM.attended_pairs(2, None) == 3
    assert FAM.attended_pairs(3, 3) == 6
    assert FAM.attended_pairs(16384, 4096) == 58722304
    assert FAM.attended_pairs(16384, None) == 134225920
    # per token, forward, x2 a multiply-add; experts 3 x 2 / 6 = 1 expected
    per_token = 2 * (2 * (attn + 4 * 6 + 1.0 * 3 * 4 * 3) + 4 * 10)
    seq = 8
    want = 3 * (seq * per_token + 4 * (36 + 21) * 2 * 2)
    assert FAM.train_flops_per_sample(cfg, seq) == pytest.approx(want)
    # a length below the window: both layers attend the triangle
    assert FAM.train_flops_per_sample(cfg, 2) == pytest.approx(
        3 * (2 * per_token + 4 * (3 + 3) * 2 * 2))
    # the windowed layer's kernel: 7 products a pair, x2, heads x head size
    assert FAM.ROOFLINE_PRODUCTS == 7
    ops, nbytes = FAM.window_attention_cost(cfg, seq)
    assert ops == 14 * 21 * 2 * 2
    q, kv, lse = seq * 2 * 2 * 2, seq * 1 * 2 * 2, seq * 2 * 4
    assert nbytes == (2 * q + 2 * kv + lse) + (4 * q + 4 * kv + lse)
    with pytest.raises(ValueError, match="held here"):
        FAM.param_count(dict(cfg, moe_num_primary_experts=3))
    with pytest.raises(ValueError, match="rotary AND windowed"):
        FAM.param_count(dict(cfg, rope_layout=[1, 1, 1, 1]))
    with pytest.raises(ValueError, match="scan_blocks"):
        FAM.build(TOY, scan_blocks=True)


@pytest.mark.parametrize("which", ["toy", "committed"])
def test_param_count_is_the_built_trees_leaf_count(which):
    cfg = TOY if which == "toy" else harness.load_json(
        "configs", CONFIG + ".json")
    model = FAM.build(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    built = sum(int(np.prod(s.shape))
                for s in jax.tree_util.tree_leaves(shapes))
    assert FAM.param_count(cfg) == built
    if which == "committed":
        assert built == cfg["parameters"] == 656529920
        # the issue's reckoning of the cell, from the committed file
        assert FAM.train_flops_per_sample(cfg, 16384) == pytest.approx(
            34.7e12, rel=5e-3)
        ops, nbytes = FAM.window_attention_cost(cfg, 16384)
        assert ops == 14 * 58722304 * 28 * 128 * 3
        assert ops / 197e12 == pytest.approx(44.9e-3, rel=2e-3)
        assert ops / 197e12 > nbytes / 819e9           # compute-bound


def test_smallthinker_is_at_its_published_widths():
    cfg = harness.load_json("configs", CONFIG + ".json")
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2560, 28, 4, 128)
    assert (cfg["moe_ffn_hidden_size"], cfg["n_router_outputs"],
            cfg["moe_num_active_primary_experts"], cfg["sliding_window_size"],
            cfg["rope_theta"], cfg["rms_norm_eps"]) == (
                768, 64, 6, 4096, 1500000, 1e-6)
    assert cfg["tie_word_embeddings"] is False
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13
    # the cut: one whole period, 16 held experts, a quarter of the vocabulary
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 16, 37984)
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "moe_num_primary_experts": 64,
                                "vocab_size": 151936}
    assert cfg["held_experts"] == list(range(16))
    assert 4 * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    assert FAM._sizes(cfg)["types"] == ["full", "window", "window", "window"]
    assert cfg["stands_for"] and cfg["departures"] and cfg["reduced_why"]
    assert {"router_input", "expert_activation", "bias",
            "rope_pairing"} <= set(cfg["assumed"])
    entry = {c["name"]: c for c in MAN["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    # every number of the source's config is here under its own key
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.isfile(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    wl = harness.load_json("workloads", CELL + ".json")
    assert (wl["global_batch"], wl["seq"], wl["mesh"]) == (1, 16384, [1, 1, 1])
    assert wl["seq"] == cfg["max_position_embeddings"]
    assert wl["scan_blocks"] is False and wl["remat"] == "full"
    assert wl["loss_tolerance_why"] and wl["lr_why"]


def test_the_cell_is_appended_and_the_manifest_keeps_its_rules():
    rules.names_units_and_limits(MAN)
    rules.cells_resolve_and_report(MAN)
    assert MAN["workloads"][-1]["name"] == CELL
    assert MAN["configs"][-1]["name"] == CONFIG
    assert MAN["workloads"][-1]["chips"] == 1
    mine = {m["name"] for m in bench_run.cell_metrics(MAN, CELL, "per_layer")}
    assert {"attn_window_ms.train", "attn_full_ms.train",
            "swa_roofline.train", "moe_ms.train", "attn_core_ms.train",
            "mfu.train", "compile_s"} <= mine
    assert not {"linattn_core_ms.train", "linattn_roofline.train",
                "collective_ms.train", "collective_mb.train"} & mine
    for m in MAN["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"
        assert m["moves"] == "train_samples_per_s"
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]


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
    found = rules.readers_keep_the_source_rule(MAN, run, result)
    assert {"attn_window_ms.train", "attn_full_ms.train",
            "swa_roofline.train", "moe_ms.train"} <= set(found)
    assert found["mfu.train"] > 0 and found["compile_s"] > 0


def test_the_cells_own_readers_find_their_instructions_in_the_toy_step(
        toy_cell_run, monkeypatch):
    """The family that feeds them: on the toy step's OWN optimized text,
    with a made-up trace in which every top-level instruction of the entry
    computation ran for a millisecond a step, the readers find the
    instructions named ``attn_window`` (three layers' worth) and
    ``attn_full`` (one layer's), every one of them under ``attn_core``, and
    ``rope`` inside ``attn_proj``."""
    run, result = toy_cell_run
    text = scope_reduce.step_hlo(run, result)       # untraced: no recompile
    where, _, entry = scope_reduce.structure(text)
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
    table = scope_reduce._lm_program()[2](text)
    by_inner = {inner: {n for n, o in table.items()
                        if inner in scope_reduce.components(o)}
                for inner in ("attn_window", "attn_full", "rope")}
    assert all(by_inner.values())
    for inner in ("attn_window", "attn_full"):
        assert all(scope_reduce.scope_of(table[n], SCOPES) == "attn_core"
                   for n in by_inner[inner])
    assert all(scope_reduce.scope_of(table[n], SCOPES) == "attn_proj"
               for n in by_inner["rope"])
    assert read("attn_window_ms.train") > read("attn_full_ms.train") > 0
    assert read("moe_ms.train") > 0 and read("attn_core_ms.train") > 0
    whole = scope_reduce.inner_whole_s(run, fake, "attn_window")
    ops_1, bytes_1 = FAM.window_attention_cost(TOY, TOY_WL["seq"])
    least = max(ops_1 / rules.PEAKS["bf16_flops_per_s"],
                bytes_1 / rules.PEAKS["hbm_bytes_per_s"])
    assert read("swa_roofline.train") == pytest.approx(100 * least / whole)


# ------------------------------- the three readers against hand counts --

# a step in miniature, in the TPU compiler's spelling: one full-attention
# layer and one windowed layer, each a forward and a backward Mosaic call
# under the scope attn_core and its inner name, a rotation under attn_proj,
# and an expert product
HLO = """\
HloModule jit_step, entry_computation_layout={(bf16[8,16]{1,0})->bf16[8,16]{1,0}}

%fused_computation.1 (p0: bf16[8,16]) -> bf16[8,16] {
  %p0 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %mul.9 = bf16[8,16]{1,0:T(8,128)(2,1)} multiply(%p0, %p0)
}

ENTRY %main.3 (param.0: bf16[8,16]) -> bf16[8,16] {
  %param.0 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)
  %fusion.1 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%param.0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(attn_proj)/rope/mul"}
  %splash_mqa_fwd_residuals.4 = bf16[8,16]{1,0:T(8,128)(2,1)} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn_core)/attn_full/vmap(vmap(jit(_splash_attention)))/splash_mqa_fwd_residuals/pallas_call"}
  %splash_mqa_fwd_residuals.5 = bf16[8,16]{1,0:T(8,128)(2,1)} custom-call(%splash_mqa_fwd_residuals.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn_core)/attn_window/vmap(vmap(jit(_splash_attention)))/splash_mqa_fwd_residuals/pallas_call"}
  %fusion.2 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%splash_mqa_fwd_residuals.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(attn_core)/attn_window/vmap(vmap(jit(_splash_attention)))/transpose"}
  %fusion.3 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%fusion.2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(moe)/while/body/dot_general"}
  %splash_mqa_dkv_no_residuals.8 = bf16[8,16]{1,0:T(8,128)(2,1)} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/attn_core/attn_window/vmap(vmap(jit(_splash_attention)))/splash_mqa_dkv_no_residuals/pallas_call"}
  ROOT %splash_mqa_dkv_no_residuals.7 = bf16[8,16]{1,0:T(8,128)(2,1)} custom-call(%splash_mqa_dkv_no_residuals.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/attn_core/attn_full/vmap(vmap(jit(_splash_attention)))/splash_mqa_dkv_no_residuals/pallas_call"}
}
"""

# two calls of it: {"<instruction> <shape>": [self s, count, whole s]}
OPS = {
    "fusion.1 bf16[8,16]": [0.02, 2, 0.02],                     # rope
    "splash_mqa_fwd_residuals.4 bf16[8,16]": [0.20, 2, 0.20],   # full  fwd
    "splash_mqa_fwd_residuals.5 bf16[8,16]": [0.08, 2, 0.08],   # band  fwd
    "fusion.2 bf16[8,16]": [0.02, 2, 0.02],                     # band  fwd
    "fusion.3 bf16[8,16]": [0.30, 2, 0.30],                     # moe
    "splash_mqa_dkv_no_residuals.8 bf16[8,16]": [0.16, 2, 0.16],  # band bwd
    "splash_mqa_dkv_no_residuals.7 bf16[8,16]": [0.40, 2, 0.40],  # full bwd
}
# the band's yardstick of the fake family: one sample needs 4e9 operations
# (4 ms at the fake matrix peak: compute-bound) and 1e8 bytes (1 ms at the
# fake HBM peak); four samples a step against the 0.13 s a step of the three
# instructions named attn_window
COST = (4e9, 1e8)
WANT = {"attn_window_ms.train": 130.0, "attn_full_ms.train": 300.0,
        "swa_roofline.train": 100 * 0.004 * 4 / 0.13,
        "attn_core_ms.train": 430.0, "moe_ms.train": 150.0}


class _Program:
    def __init__(self, text):
        self.text = text

    def hlo_text(self):
        return self.text


def _fake_run(reduction, monkeypatch, text=HLO, family=None):
    monkeypatch.setattr(jax, "clear_caches", lambda: None)
    monkeypatch.setattr(
        scope_reduce, "_lm_program",
        lambda: text and (_Program(text), SCOPES, scope_table))
    run = types.SimpleNamespace(
        trace=types.SimpleNamespace(reduction=reduction), t_process=0.0,
        setup_s=0.0, peaks=rules.PEAKS, config={}, workload={"seq": 16},
        family=family or types.SimpleNamespace(
            window_attention_cost=lambda cfg, seq: COST))
    result = types.SimpleNamespace(
        window={"calls": 2, "samples": 8, "chips": 1}, end_to_end={})
    return run, result


def _read(metric, run, result):
    return harness.load_module("layer_metrics", metric).read(run, result)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_is_silent_without_a_trace_and_right_with_one(metric,
                                                             monkeypatch):
    run, result = _fake_run(None, monkeypatch)
    assert _read(metric, run, result) is None
    run, result = _fake_run({"ops": OPS}, monkeypatch)
    assert _read(metric, run, result) == pytest.approx(WANT[metric])
    # a program without the catalog (the parent of the PR that brought the
    # names): nothing, no error
    run, result = _fake_run({"ops": OPS}, monkeypatch, text=None)
    assert _read(metric, run, result) is None


@pytest.mark.parametrize("metric", ["attn_window_ms.train",
                                    "attn_full_ms.train",
                                    "swa_roofline.train"])
def test_a_step_without_the_name_gives_its_reader_nothing_never_zero(
        metric, monkeypatch):
    """The other cells' steps: a model whose attention carries neither inner
    name (the dense model's), and a family with no cost function."""
    bare = HLO.replace("/attn_window/", "/").replace("/attn_full/", "/")
    run, result = _fake_run({"ops": OPS}, monkeypatch, text=bare)
    assert _read(metric, run, result) is None
    assert _read("attn_core_ms.train", run, result) == pytest.approx(430.0)
    if metric == "swa_roofline.train":
        run, result = _fake_run({"ops": OPS}, monkeypatch,
                                family=types.SimpleNamespace())
        assert _read(metric, run, result) is None
