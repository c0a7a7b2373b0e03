"""The ``mla_moe_lm`` family of the benchmark (``families/mla_moe_lm.py``,
``reference/mla_moe_lm.py``, the configuration and the cell
``joyai-llm-flash.train-16k``), at toy size on the CPU: the system equals the
plain reference on both heads' logits, the loss of the whole share — the
prediction module included — and EVERY gradient leaf (float32 tight,
bfloat16 compute loose); six planted faults each move the float32 loss out
of the tight limit; the shares of all the chips of a toy deployment add up
to the uncut reference's layer; the layer-by-layer reference equals the
whole-model one; the analytic counts equal hand counts; the configuration is
at its published widths; the cell's toy twin runs end to end through the kind
and every reader the cell lists gives what its ``source`` says; the four
readers the cell brings against hand counts.  Nothing here is a
measurement."""

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
from test_swa_moe_family import _fake_run as _swa_fake_run, _read

import scope_reduce  # noqa: E402  (benchmarks/ is on the path by now)

from distlearn_tpu.models import hybrid, transformer  # noqa: E402
from distlearn_tpu.models.core import SCOPES  # noqa: E402
from distlearn_tpu.models.transformer import lm_loss  # noqa: E402
from distlearn_tpu.parallel import ep  # noqa: E402

MAN = bench_run.manifest()
CELL = "joyai-llm-flash.train-16k"
CONFIG = "joyai-llm-flash"
FAM = harness.load_module("families", "mla_moe_lm")
REF = harness.load_module("reference", "mla_moe_lm")
READERS = ("attn_mla_ms.train", "mla_roofline.train", "mla_latent_ms.train",
           "mtp_ms.train")

#: the published file with every size cut to a toy's: a dense layer and two
#: mixture layers (16 experts of which 4 are held, 3 a token, one shared),
#: 4 heads of 12-wide scores (8 un-rotated + 4 rotated) over 8-wide values,
#: and the prediction module
TOY = dict(
    harness.load_json("configs", CONFIG + ".json"), hidden_size=32,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    qk_head_dim=12, v_head_dim=8, head_dim=4, intermediate_size=40,
    moe_intermediate_size=24, n_routed_experts=4, held_experts=[1, 5, 6, 11],
    n_router_outputs=16, num_experts_per_tok=3, vocab_size=97,
    max_position_embeddings=256)
TOY_WL = {"kind": "train_lm", "mesh": [1, 1, 1], "global_batch": 1,
          "seq": 64, "lr": 0.05, "compute_dtype": None,
          "scan_blocks": False, "remat": "full", "ring_batches": 2,
          "in_flight": 2, "check_steps": 2, "check_micro": 1,
          "loss_tolerance": 1e-4, "trace_seconds": 0.3}


def _toy(compute_dtype=None):
    """The toy model, its parameters from a seed beyond 2**31 (correction
    biases drawn, non-zero), and the same in the reference's layout."""
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
    limits as the other pattern families' tests; the two sides sum in
    different orders — grouped tiles against a masked scan, the kernel-less
    full square against blocks of queries).  The loss is the WHOLE share's:
    the main cross-entropy and the module's, weighted."""
    model, params, rp, tokens = toy
    got, state = model.apply(params, {}, tokens, train=True)
    np.testing.assert_allclose(got, REF.logits(rp, tokens), atol=3e-5)
    np.testing.assert_allclose(state["mtp_logits"],
                               REF.module_logits(rp, tokens), atol=3e-5)
    assert int(state["moe_dropped"].sum()) == 0
    assert state["moe_assignments"].shape == (3, 4)     # 2 layers + module
    assert rp.static == ((1, 5, 6, 11), 3, 2.5, 8, 3.2e7, 0.3)
    for blk in (params["layer1"], params["mtp"]["block"]):
        assert float(jnp.abs(blk["router_bias"]).min()) > 0
    l_sys, g_sys = jax.value_and_grad(
        lambda p: lm_loss(model, p, tokens))(params)
    l_ref, g_ref = jax.value_and_grad(REF.loss)(rp, tokens)
    assert float(l_sys) == pytest.approx(float(l_ref), rel=1e-6)
    got_leaves = jax.tree_util.tree_leaves_with_path(FAM.to_reference(g_sys))
    want_leaves = jax.tree_util.tree_leaves_with_path(g_ref)
    mla, mixture = 9, 9 + 2 + 3 + 3       # + router, bias; shared; held
    assert len(got_leaves) == len(want_leaves) \
        == 3 + (mla + 3) + 2 * mixture + (4 + mixture)
    for (path, a), (_, b) in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            # the bias enters the discrete choice alone: exactly zero
            assert float(jnp.abs(a).max()) == float(jnp.abs(b).max()) == 0.0
            continue
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-6 + 2e-4 * float(jnp.abs(b).max()),
            err_msg=name)


def test_bfloat16_compute_stays_near_the_float32_reference():
    """The cell's arithmetic (float32 parameters, bfloat16 products) at toy
    size, loose, each limit with its reason.  The loss within 3e-2 of the
    float32 reference's: bfloat16 keeps 8 bits, a logit of order 1 is off by
    4e-3 and each of the two mean losses by about that.  Every gradient leaf
    that has one points where the reference's does, cosine at least 0.9: an
    entry-by-entry limit means nothing here, because the rounded activations
    swap the LAST of a few tokens' three experts (the choice is discrete)."""
    model, params, rp, tokens = _toy("bfloat16")
    l_sys, g_sys = jax.value_and_grad(
        lambda p: lm_loss(model, p, tokens))(params)
    l_ref, g_ref = jax.value_and_grad(REF.loss)(rp, tokens)
    assert abs(float(l_sys) - float(l_ref)) < 3e-2
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(FAM.to_reference(g_sys)),
            jax.tree_util.tree_leaves_with_path(g_ref)):
        a, b = (np.asarray(v, np.float64).ravel() for v in (a, b))
        if not b.any():
            continue                                        # a router's bias
        cosine = a @ b / (np.linalg.norm(a) * np.linalg.norm(b))
        assert cosine > 0.9, (jax.tree_util.keystr(path), cosine)


FAULTS = ("rotate-half for the interleaved pairing",
          "scores scaled by 1/sqrt of the values' head size",
          "the bias left out of the choice", "the 2.5 left out",
          "the module's loss left out",
          "half of the held assignments dropped")


def _fault(monkeypatch, name):
    """The system with one part of its mathematics left out."""
    if name == FAULTS[0]:
        real = hybrid.rotary
        monkeypatch.setattr(
            hybrid, "rotary",
            lambda x, pos, theta, pairing="half": real(x, pos, theta, "half"))
    elif name == FAULTS[1]:
        real = hybrid.local_attention
        monkeypatch.setattr(
            hybrid, "local_attention", lambda q, k, v, causal: real(
                q * (q.shape[-1] / v.shape[-1]) ** 0.5, k, v, causal=causal))
    elif name in FAULTS[2:4]:
        real = ep.route_held
        drop = {"select_bias": None} if name == FAULTS[2] else {"scale": 1.0}
        monkeypatch.setattr(ep, "route_held",
                            lambda *a, **kw: real(*a, **dict(kw, **drop)))
    elif name == FAULTS[4]:
        monkeypatch.setattr(transformer, "_mtp_loss", lambda *a: None)
    else:
        assert name == FAULTS[5]
        real = ep.route_held

        def halved(*a, **kw):
            plan, slot_w, aux = real(*a, **kw)
            return plan, slot_w.at[1::2].set(0.0), aux
        monkeypatch.setattr(ep, "route_held", halved)


@pytest.mark.parametrize("name", FAULTS)
def test_planted_fault_moves_the_float32_loss_out_of_the_limit(
        monkeypatch, toy, name):
    """The six faults the cell's check was read against on the chip (PERF.md
    section 4), each at toy size in float32, where the sound system holds
    1e-6: the faulty system's loss is off by more than 1e-4.  Whatever the
    chip's bfloat16 check lets through, this does not."""
    _, params, rp, tokens = toy
    want = float(REF.loss(rp, tokens))
    _fault(monkeypatch, name)
    model = FAM.build(TOY, max_len=64)          # traced with the fault in
    assert abs(float(lm_loss(model, params, tokens)) - want) > 1e-4


def test_all_the_chips_shares_add_up_to_the_uncut_reference():
    """The guide's share test: 16 experts over 4 holders of 4.  Each
    holder's mixture layer through the SYSTEM (its held experts' part; the
    latent attention, the router with its bias, the shared expert and the
    residual computed alike by all), less what all compute alike, summed,
    plus that ONCE, is the REFERENCE's layer with every expert held."""
    uncut = dict(TOY, n_routed_experts=16, held_experts=list(range(16)))
    model = FAM.build(uncut, max_len=64)
    params = FAM.init_params(model, harness.seed_key(7))
    whole = FAM.to_reference(params)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 32), jnp.float32)
    blk = params["layer1"]
    want = REF.block(whole.tree["layers"][1], x, **whole.layer_static)
    h = hybrid.mla_apply(blk, x, jnp.float32, 1e-6, 3.2e7, 8, "interleaved")
    none = dict(blk, **{k: jnp.zeros_like(blk[k])
                        for k in ("we_gate", "we_up", "we_down")})
    alike, _ = hybrid.moe_apply(none, h, jnp.float32, 1e-6, tuple(range(16)),
                                3, None, score="sigmoid", scale=2.5)
    total, seen = alike, 0                  # attention + shared expert, once
    for holder in range(4):
        held = tuple(range(4 * holder, 4 * holder + 4))
        part = dict(blk, **{k: blk[k][4 * holder:4 * holder + 4]
                            for k in ("we_gate", "we_up", "we_down")})
        y, aux = hybrid.moe_apply(part, h, jnp.float32, 1e-6, held, 3, None,
                                  score="sigmoid", scale=2.5)
        total = total + (y - alike)
        seen += int(aux["assignments"].sum())
        assert int(aux["dropped"]) == 0
    assert seen == 2 * 64 * 3                   # every assignment a holder
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


def test_layerwise_reference_equals_whole_model_and_streams_its_updates(toy):
    """The module as one more stage after the stack; the embedding's two
    uses (the stack's and the module's) summed."""
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
    """Blocks of queries and of positions change no result; the scores are
    over q's head size and the output has v's; the module's loss counts the
    positions that have a token two ahead."""
    rng = np.random.RandomState(0)
    q, k = (jnp.asarray(rng.randn(1, 32, 2, 12), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)
    whole = REF.attention(q, k, v)
    assert whole.shape == (1, 32, 2, 8)
    monkeypatch.setattr(REF, "_QUERY_BLOCK", 4)
    np.testing.assert_allclose(REF.attention(q, k, v), whole, atol=1e-6)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(12.0)
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s,
                                 -jnp.inf), -1)
    np.testing.assert_allclose(whole, jnp.einsum("bhqk,bkhd->bqhd", p, v),
                               atol=1e-5)
    x = jnp.asarray(rng.randn(2, 32, 6), jnp.float32)
    head = jnp.asarray(rng.randn(6, 11), jnp.float32)
    tokens = jnp.asarray(rng.randint(0, 11, (2, 32)))
    monkeypatch.setattr(REF, "_LOSS_BLOCK", 8)
    for ahead in (1, 2):
        lp = jax.nn.log_softmax(
            REF.head_logits(head, jnp.ones(6), x)[:, :-ahead], -1)
        want = -jnp.mean(jnp.take_along_axis(
            lp, tokens[:, ahead:, None], -1))
        assert float(REF.head_loss(head, jnp.ones(6), x, tokens, ahead)) \
            == pytest.approx(float(want), rel=1e-6)
    # the rotation: neighbouring pairs, the distance alone
    u = jnp.asarray(rng.randn(1, 8, 1, 4), jnp.float32)
    turned = REF.rope(u, 3.2e7)
    np.testing.assert_array_equal(turned[:, 0], u[:, 0])
    z = (np.asarray(u)[0, 5, 0, 0] + 1j * np.asarray(u)[0, 5, 0, 1]) \
        * np.exp(5j)
    np.testing.assert_allclose(turned[0, 5, 0, :2], [z.real, z.imag],
                               atol=1e-5)


def test_reference_is_written_without_the_systems_model():
    text = open(os.path.join(BENCH, "reference", "mla_moe_lm.py")).read()
    assert "distlearn_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in text and 'm @ layer["router"]' in text
    assert "lax.scan(add_one" in text               # the held experts


def test_counts_against_hand_counts():
    # D=4, three layers (one dense), 2 heads of 3 + 2 over values of 3,
    # ranks 5 and 3, dense width 7, 6 experts of width 3 of which 2 are
    # held, 3 a token, one shared, vocabulary 10
    cfg = dict(TOY, hidden_size=4, num_hidden_layers=3,
               num_attention_heads=2, num_key_value_heads=2, q_lora_rank=5,
               kv_lora_rank=3, qk_nope_head_dim=3, qk_rope_head_dim=2,
               qk_head_dim=5, v_head_dim=3, intermediate_size=7,
               moe_intermediate_size=3, n_routed_experts=2,
               held_experts=[0, 4], n_router_outputs=6,
               num_experts_per_tok=3, vocab_size=10)
    attn = 4 * 5 + 5 * 2 * 5 + 4 * (3 + 2) + 3 * 2 * (3 + 3) + 2 * 3 * 4
    norms = 5 + 3 + 2 * 4
    dense = attn + norms + 3 * 4 * 7
    mixture = attn + norms + 4 * 6 + 6 + 3 * 4 * 3 + 2 * 3 * 4 * 3
    module = 2 * 4 + 8 * 4 + mixture + 4
    assert FAM.param_count(cfg) == 2 * 10 * 4 + 4 + dense + 2 * mixture \
        + module
    assert FAM.attended_pairs(8) == 36
    assert FAM.attended_pairs(16384) == 134225920
    # per token, forward, x2 a multiply-add; experts 3 x 2 / 6 = 1 expected
    mix_tok = attn + 4 * 6 + 3 * 4 * 3 + 1.0 * 3 * 4 * 3
    per_token = 2 * ((attn + 3 * 4 * 7) + 3 * mix_tok + 8 * 4 + 2 * 4 * 10)
    seq = 8
    want = 3 * (seq * per_token + 4 * 36 * 2 * (5 + 3) * 2)
    assert FAM.train_flops_per_sample(cfg, seq) == pytest.approx(want)
    # the kernel: 4 products over the 5 of the scores, 3 over the 3 of the
    # values, x2, a pair a head, four calls (three layers and the module)
    assert (FAM.QK_PRODUCTS, FAM.V_PRODUCTS) == (4, 3)
    ops, nbytes = FAM.mla_attention_cost(cfg, seq)
    assert ops == 2 * (4 * 5 + 3 * 3) * 36 * 2 * 4
    qk, v, lse = seq * 2 * 5 * 2, seq * 2 * 3 * 2, seq * 2 * 4
    assert nbytes == 4 * ((2 * qk + 2 * v + lse) + (4 * qk + 4 * v + lse))
    with pytest.raises(ValueError, match="held here"):
        FAM.param_count(dict(cfg, n_routed_experts=3))
    with pytest.raises(ValueError, match="group-limited"):
        FAM.param_count(dict(cfg, n_group=8, topk_group=4))
    with pytest.raises(ValueError, match="sigmoid scores"):
        FAM.param_count(dict(cfg, scoring_func="softmax"))
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
        assert built == cfg["parameters"] == 680441088
        # ISSUE 36's arithmetic, from the committed file: a mixture layer,
        # the dense layer, the module
        layer = lambda n: sum(int(np.prod(s.shape)) for s in  # noqa: E731
                              jax.tree_util.tree_leaves(shapes[n]))
        assert (layer("layer0"), layer("layer1"), layer("mtp")) == (
            70391808, 107092224, 115486976)
        assert FAM.train_flops_per_sample(cfg, 16384) == pytest.approx(
            80.4e12, rel=5e-3)
        ops, nbytes = FAM.mla_attention_cost(cfg, 16384)
        assert ops == 2 * (4 * 192 + 3 * 128) * 134225920 * 32 * 6
        assert ops / 197e12 == pytest.approx(301.4e-3, rel=2e-3)
        assert ops / 197e12 > nbytes / 819e9           # compute-bound


def test_joyai_llm_flash_is_at_its_published_widths():
    cfg = harness.load_json("configs", CONFIG + ".json")
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"]) == (2048, 32, 32)
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["qk_head_dim"],
            cfg["v_head_dim"]) == (1536, 512, 128, 64, 192, 128)
    assert (cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_router_outputs"], cfg["num_experts_per_tok"],
            cfg["scoring_func"], cfg["routed_scaling_factor"],
            cfg["n_shared_experts"], cfg["rope_theta"],
            cfg["rms_norm_eps"]) == (7168, 768, 256, 8, "sigmoid", 2.5, 1,
                                     32000000, 1e-6)
    assert cfg["first_k_dense_replace"] == 1 \
        and cfg["num_nextn_predict_layers"] == 1
    assert cfg["rope_interleave"] is True and cfg["rope_scaling"] is None
    assert cfg["tie_word_embeddings"] is False
    # the cut: the dense layer and four mixture layers, 16 held experts, an
    # eighth of the vocabulary — the guide's floors
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 16, 16160)
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "n_routed_experts": 256,
                                "vocab_size": 129280}
    assert cfg["held_experts"] == list(range(16))
    assert 8 * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert "one of 16 chips" in cfg["stands_for"]
    assert cfg["departures"] and cfg["reduced_why"]
    assert cfg["assumed"]["mtp_loss_weight"]["value"] == 0.3
    assert {"mtp_input", "mtp_shared", "rope_interleave", "router",
            "n_router_outputs"} <= set(cfg["assumed"])
    entry = {c["name"]: c for c in MAN["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    # every key of the source's config is here under its own name
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.isfile(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "JoyAI-LLM-Flash")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    wl = harness.load_json("workloads", CELL + ".json")
    assert (wl["global_batch"], wl["seq"], wl["mesh"]) == (1, 16384, [1, 1, 1])
    assert wl["scan_blocks"] is False and wl["remat"] == "full"
    assert (wl["ring_batches"], wl["in_flight"], wl["check_steps"],
            wl["check_micro"], wl["trace_seconds"]) == (8, 4, 2, 1, 5)
    assert wl["loss_tolerance_why"] and wl["lr_why"]


def test_the_cell_is_in_the_manifest_and_the_manifest_keeps_its_rules():
    """Wherever later PRs append theirs: nothing here reads a position."""
    rules.names_units_and_limits(MAN)
    rules.cells_resolve_and_report(MAN)
    cell = {w["name"]: w for w in MAN["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train-16k", 1)
    mine = {m["name"] for m in bench_run.cell_metrics(MAN, CELL, "per_layer")}
    assert set(READERS) | {"moe_ms.train", "attn_core_ms.train", "mfu.train",
                           "compile_s", "unscoped_share.train"} <= mine
    assert not {"linattn_core_ms.train", "linattn_roofline.train",
                "collective_ms.train", "collective_mb.train",
                "attn_window_ms.train", "attn_full_ms.train",
                "swa_roofline.train"} & mine
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"
        assert m["moves"] == "train_samples_per_s"
    assert by_name["mla_roofline.train"]["unit"] == "%"
    assert {m["name"] for m in bench_run.cell_metrics(
        MAN, CELL, "end_to_end")} == {"setup_s", "train_samples_per_s"}


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
    assert set(READERS) | {"moe_ms.train"} <= set(found)
    assert found["mfu.train"] > 0 and found["compile_s"] > 0


def test_the_cells_own_readers_find_their_instructions_in_the_toy_step(
        toy_cell_run, monkeypatch):
    """The family that feeds them: on the toy step's OWN optimized text,
    with a made-up trace in which every top-level instruction of the entry
    computation ran for a millisecond a step, the readers find the
    instructions named ``attn_mla`` (every one of them under ``attn_core``),
    ``mla_latent`` (under ``attn_proj`` or a norm inside it) and ``mtp``;
    the module holds an ``attn_mla`` of its own."""
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
                for inner in ("attn_mla", "mla_latent", "mtp", "rope")}
    assert all(by_inner.values())
    assert all(scope_reduce.scope_of(table[n], SCOPES) == "attn_core"
               for n in by_inner["attn_mla"])
    assert all(scope_reduce.scope_of(table[n], SCOPES) in ("attn_proj",
                                                           "norm")
               for n in by_inner["mla_latent"])
    assert by_inner["rope"] <= by_inner["mla_latent"]
    assert by_inner["mtp"] & by_inner["attn_mla"]
    assert by_inner["attn_mla"] - by_inner["mtp"]
    for metric in ("attn_mla_ms.train", "mla_latent_ms.train",
                   "mtp_ms.train", "moe_ms.train", "attn_core_ms.train"):
        assert read(metric) > 0, metric
    whole = scope_reduce.inner_whole_s(run, fake, "attn_mla")
    ops_1, bytes_1 = FAM.mla_attention_cost(TOY, TOY_WL["seq"])
    least = max(ops_1 / rules.PEAKS["bf16_flops_per_s"],
                bytes_1 / rules.PEAKS["hbm_bytes_per_s"])
    assert read("mla_roofline.train") == pytest.approx(100 * least / whole)


# -------------------------------- the four readers against hand counts --

# a step in miniature, in the TPU compiler's spelling: one latent-attention
# layer and the module's block, each a forward and a backward Mosaic call
# under the scope attn_core and the inner name attn_mla, the low-rank
# projections and the rotation under attn_proj / mla_latent, an expert
# product, and the module's own projection and head
HLO = """\
HloModule jit_step, entry_computation_layout={(bf16[8,16]{1,0})->bf16[8,16]{1,0}}

%fused_computation.1 (p0: bf16[8,16]) -> bf16[8,16] {
  %p0 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %mul.9 = bf16[8,16]{1,0:T(8,128)(2,1)} multiply(%p0, %p0)
}

ENTRY %main.3 (param.0: bf16[8,16]) -> bf16[8,16] {
  %param.0 = bf16[8,16]{1,0:T(8,128)(2,1)} parameter(0)
  %fusion.1 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%param.0), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(attn_proj)/mla_latent/dot_general"}
  %fusion.2 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(attn_proj)/mla_latent/rope/mul"}
  %splash_mha_fwd_residuals.4 = bf16[8,16]{1,0:T(8,128)(2,1)} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn_core)/attn_mla/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call"}
  %fusion.3 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%splash_mha_fwd_residuals.4), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(moe)/jit(_gmm)/gmm/pallas_call"}
  %fusion.4 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%fusion.3), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(mtp)/mlp/dot_general"}
  %fusion.5 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%fusion.4), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(mtp)/attn_proj/mla_latent/dot_general"}
  %splash_mha_fwd_residuals.5 = bf16[8,16]{1,0:T(8,128)(2,1)} custom-call(%fusion.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(mtp)/attn_core/attn_mla/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call"}
  %fusion.6 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%splash_mha_fwd_residuals.5), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(mtp)/head_loss/dot_general"}
  %splash_mha_dkv_no_residuals.8 = bf16[8,16]{1,0:T(8,128)(2,1)} custom-call(%fusion.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(mtp))/checkpoint/attn_core/attn_mla/vmap(jit(_splash_attention))/splash_mha_dkv_no_residuals/pallas_call"}
  ROOT %splash_mha_dkv_no_residuals.7 = bf16[8,16]{1,0:T(8,128)(2,1)} custom-call(%splash_mha_dkv_no_residuals.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(jvp()))/checkpoint/attn_core/attn_mla/vmap(jit(_splash_attention))/splash_mha_dkv_no_residuals/pallas_call"}
}
"""

# two calls of it: {"<instruction> <shape>": [self s, count, whole s]}
OPS = {
    "fusion.1 bf16[8,16]": [0.04, 2, 0.04],                     # latent
    "fusion.2 bf16[8,16]": [0.02, 2, 0.02],                     # its rope
    "splash_mha_fwd_residuals.4 bf16[8,16]": [0.20, 2, 0.20],   # layer fwd
    "fusion.3 bf16[8,16]": [0.30, 2, 0.30],                     # moe
    "fusion.4 bf16[8,16]": [0.02, 2, 0.02],                     # eh_proj
    "fusion.5 bf16[8,16]": [0.04, 2, 0.04],                     # mtp latent
    "splash_mha_fwd_residuals.5 bf16[8,16]": [0.20, 2, 0.20],   # mtp   fwd
    "fusion.6 bf16[8,16]": [0.10, 2, 0.10],                     # mtp  head
    "splash_mha_dkv_no_residuals.8 bf16[8,16]": [0.40, 2, 0.40],  # mtp bwd
    "splash_mha_dkv_no_residuals.7 bf16[8,16]": [0.40, 2, 0.40],  # lay bwd
}
# the yardstick of the fake family: one sample needs 4e10 operations (40 ms
# at the fake matrix peak: compute-bound) and 1e8 bytes (1 ms at the fake HBM
# peak); four samples a step against the 0.6 s a step of the four kernels
COST = (4e10, 1e8)
WANT = {"attn_mla_ms.train": 600.0, "mla_latent_ms.train": 50.0,
        "mtp_ms.train": 380.0, "mla_roofline.train": 100 * 0.04 * 4 / 0.6,
        "attn_core_ms.train": 600.0, "moe_ms.train": 150.0}


def _fake_run(reduction, monkeypatch, text=HLO, family=None):
    """``test_swa_moe_family``'s made-up run, on this file's text and with a
    family that has this cell's cost function."""
    return _swa_fake_run(
        reduction, monkeypatch, text=text,
        family=family or types.SimpleNamespace(
            mla_attention_cost=lambda cfg, seq: COST))


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


@pytest.mark.parametrize("metric", READERS)
def test_a_step_without_the_name_gives_its_reader_nothing_never_zero(
        metric, monkeypatch):
    """The other cells' steps (and the parent's): a model whose step carries
    none of the three inner names, and a family with no cost function."""
    bare = HLO.replace("/attn_mla/", "/").replace("/mla_latent/", "/") \
        .replace("jvp(mtp)", "jvp()")
    run, result = _fake_run({"ops": OPS}, monkeypatch, text=bare)
    assert _read(metric, run, result) is None
    assert _read("attn_core_ms.train", run, result) == pytest.approx(600.0)
    if metric == "mla_roofline.train":
        run, result = _fake_run({"ops": OPS}, monkeypatch,
                                family=types.SimpleNamespace())
        assert _read(metric, run, result) is None
