"""The ``gated_swa_moe_lm`` family of the benchmark
(``families/gated_swa_moe_lm.py``, ``reference/gated_swa_moe_lm.py``, the
configuration and the cell ``laguna-s-2.1.train-16k``), at toy size on the
CPU: the system equals the plain reference on logits, loss and EVERY gradient
leaf (float32 tight, bfloat16 compute loose); the gate a head alone; YaRN's
frequencies against a hand count; five planted faults each move the float32
loss out of the tight limit; the shares of the 32 chips of the deployment add
up to the uncut reference's layer; the layer-by-layer reference equals the
whole-model one; the analytic counts equal hand counts; the configuration is
at its published widths; the four configurations the benchmark had before
this one build the step programs they built before (their hashes, pinned);
the new counters count as stated; the cell's toy twin runs end to end through
the kind and every reader the cell lists gives what its ``source`` says; the
reader the cell brings against a hand count.  Nothing here is a
measurement."""

import copy
import hashlib
import json
import math
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import manifest_rules as rules
from manifest_rules import BENCH, bench_run, harness
from program_util import program_text
from test_swa_moe_family import HLO as _SWA_HLO, OPS as _SWA_OPS
from test_swa_moe_family import _fake_run, _read

import scope_reduce  # noqa: E402  (benchmarks/ is on the path by now)

from distlearn_tpu.models import hybrid  # noqa: E402
from distlearn_tpu.models.core import SCOPES  # noqa: E402
from distlearn_tpu.models.transformer import (Yarn, lm_loss,  # noqa: E402
                                              rotary, rotary_calls_traced,
                                              yarn_frequencies)
from distlearn_tpu.parallel import ep  # noqa: E402
from distlearn_tpu.train.lm import build_lm_step  # noqa: E402

MAN = bench_run.manifest()
CELL = "laguna-s-2.1.train-16k"
CONFIG = "laguna-s-2.1"
FAM = harness.load_module("families", "gated_swa_moe_lm")
REF = harness.load_module("reference", "gated_swa_moe_lm")
PUBLISHED = harness.load_json("configs", CONFIG + ".json")

#: the published file with every size cut to a toy's: a dense full layer,
#: three sliding layers and a full one, 4 query heads in a full layer and 6
#: in a sliding one over 2 K/V heads of 8 (the full layers rotate the first
#: 4), a band of 16, 16 experts of which 4 are held, 3 a token, one shared
TOY = dict(
    PUBLISHED, hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, num_attention_heads_per_layer=[4, 6, 6, 6] * 12,
    sliding_window=16, intermediate_size=40, moe_intermediate_size=24,
    shared_expert_intermediate_size=24, num_experts=4,
    held_experts=[1, 5, 6, 11], n_router_outputs=16, num_experts_per_tok=3,
    vocab_size=97, max_position_embeddings=256)
TOY_WL = {"kind": "train_lm", "mesh": [1, 1, 1], "global_batch": 1,
          "seq": 64, "lr": 0.05, "compute_dtype": None,
          "scan_blocks": False, "remat": "full", "ring_batches": 2,
          "in_flight": 2, "check_steps": 2, "check_micro": 1,
          "loss_tolerance": 1e-4, "trace_seconds": 0.3}
#: the full layers' YaRN, as published
YARN = Yarn(**{k: PUBLISHED["rope_parameters"]["full_attention"][k]
               for k in Yarn._fields})


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
    limits as the other pattern families' tests)."""
    model, params, rp, tokens = toy
    got, state = model.apply(params, {}, tokens, train=False)
    np.testing.assert_allclose(got, REF.logits(rp, tokens), atol=3e-5)
    assert int(state["moe_dropped"].sum()) == 0
    assert state["moe_assignments"].shape == (4, 4)   # the dense layer: none
    assert rp.static == ((1, 5, 6, 11), 3, 2.5, (0, 1, 1, 1, 0), 16, 10000.0,
                         500000.0, 0.5, tuple(float(c) for c in YARN))
    assert [params[f"layer{i}"]["wg"].shape for i in range(5)] == [
        (32, 4), (32, 6), (32, 6), (32, 6), (32, 4)]
    l_sys, g_sys = jax.value_and_grad(
        lambda p: lm_loss(model, p, tokens))(params)
    l_ref, g_ref = jax.value_and_grad(REF.loss)(rp, tokens)
    assert float(l_sys) == pytest.approx(float(l_ref), rel=1e-6)
    got_leaves = jax.tree_util.tree_leaves_with_path(FAM.to_reference(g_sys))
    want_leaves = jax.tree_util.tree_leaves_with_path(g_ref)
    attn = 5 + 2                          # wq wk wv wg wo; two norms
    assert len(got_leaves) == len(want_leaves) \
        == 3 + (attn + 3) + 4 * (attn + 1 + 3 + 3)
    for (path, a), (_, b) in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-6 + 2e-4 * float(jnp.abs(b).max()),
            err_msg=name)


def test_bfloat16_compute_stays_near_the_float32_reference():
    """The cell's arithmetic (float32 parameters, bfloat16 products) at toy
    size, loose, each limit with its reason, as the other pattern families'
    tests: the loss within 3e-2 (bfloat16 keeps 8 bits, a logit of order 1 is
    off by 4e-3); every gradient leaf points where the reference's does,
    cosine at least 0.9 (the rounded activations swap the LAST of a few
    tokens' three experts, a discrete choice)."""
    model, params, rp, tokens = _toy("bfloat16")
    l_sys, g_sys = jax.value_and_grad(
        lambda p: lm_loss(model, p, tokens))(params)
    l_ref, g_ref = jax.value_and_grad(REF.loss)(rp, tokens)
    assert abs(float(l_sys) - float(l_ref)) < 3e-2
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(FAM.to_reference(g_sys)),
            jax.tree_util.tree_leaves_with_path(g_ref)):
        a, b = (np.asarray(v, np.float64).ravel() for v in (a, b))
        cosine = a @ b / (np.linalg.norm(a) * np.linalg.norm(b))
        assert cosine > 0.9, (jax.tree_util.keystr(path), cosine)


@pytest.mark.parametrize("n", [0, 1])
def test_the_gate_a_head_alone(toy, n):
    """A full layer (YaRN over half a head) and a sliding one: the system's
    mixer is the reference's; it is the sum over heads of each head's
    UNGATED part (``a_h wo_h``, the ungated layer with the other heads'
    rows of ``wo`` zeroed) times that head's ``sigmoid(u W_g)_h``; and with
    ``W_g = 0`` every gate is 1/2."""
    _, params, rp, _ = toy
    blk = params[f"layer{n}"]
    H = blk["wg"].shape[1]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 32), jnp.float32)
    args = (16, 1e4) if n else (None, 5e5)
    kw = {} if n else {"rope_fraction": 0.5, "yarn": YARN}

    def mixer(b):
        return hybrid.gqa_apply(b, x, jnp.float32, 1e-6, *args, **kw) - x

    static = REF._statics(rp)[n]
    want = REF.gated_attention(rp.tree["layers"][n], x, static["window"],
                               static["theta"], static["fraction"],
                               static["yarn"])
    np.testing.assert_allclose(mixer(blk), want, rtol=1e-5, atol=2e-6)
    ungated = {k: v for k, v in blk.items() if k != "wg"}
    gate = jax.nn.sigmoid(hybrid._rmsnorm(blk["ln1"], x, 1e-6) @ blk["wg"])
    by_head = sum(gate[..., h:h + 1] * mixer(dict(
        ungated, wo=blk["wo"] * (jnp.arange(H) == h)[:, None, None]))
        for h in range(H))
    np.testing.assert_allclose(mixer(blk), by_head, rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(mixer(dict(blk, wg=jnp.zeros_like(blk["wg"]))),
                               0.5 * mixer(ungated), rtol=1e-5, atol=1e-6)


def test_yarn_frequencies_against_a_hand_count():
    """The full layers' 32 frequencies: the base's for d <= 9, a 128th of
    it for d >= 18, the linear ramp between (low = floor(9.04), high =
    ceil(17.49)); the factor on cos and sin is 0.1 ln 128 + 1."""
    base = 5e5 ** (-np.arange(32) / 32.0)
    c = lambda r: 64 * math.log(8192 / (2 * math.pi * r)) \
        / (2 * math.log(5e5))                               # noqa: E731
    assert (c(32), c(1)) == (pytest.approx(9.04, abs=5e-3),
                             pytest.approx(17.49, abs=5e-3))
    ramp = np.clip((np.arange(32) - 9) / 9.0, 0, 1)
    got = yarn_frequencies(5e5, 64, YARN)
    assert got.dtype == np.float32 and got.shape == (32,)
    np.testing.assert_allclose(got[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(got[18:], base[18:] / 128, rtol=1e-6)
    np.testing.assert_allclose(got, base * (1 - ramp) + base / 128 * ramp,
                               rtol=1e-6)
    assert got[13] == pytest.approx(base[13] * (5 / 9 + 4 / 9 / 128),
                                    rel=1e-6)
    assert PUBLISHED["rope_parameters"]["full_attention"][
        "attention_factor"] == pytest.approx(0.1 * math.log(128) + 1)
    # the reference's own count of the same
    ref, factor = REF.frequencies(64, 5e5, tuple(YARN))
    np.testing.assert_allclose(np.asarray(ref), got, rtol=1e-6)
    assert factor == YARN.attention_factor
    # at position 0 nothing turns: the rotated half is scaled by the factor
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 2, 128), jnp.float32)
    out = rotary(x, jnp.arange(3), 5e5, fraction=0.5, yarn=YARN)
    np.testing.assert_allclose(out[:, 0, :, :64],
                               YARN.attention_factor * x[:, 0, :, :64],
                               rtol=1e-6)


def test_partial_rotation_leaves_the_last_half_of_a_head_untouched():
    """``fraction=0.5`` over a head of 128: dimensions 64-127 pass through
    bit for bit, the first 64 are the rotation of that slice alone (pairs
    ``(d, d + 32)``), and the result keeps the input's dtype."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 3, 128), jnp.float32)
    pos = jnp.arange(40) * 401
    for yarn in (None, YARN):
        got = rotary(x, pos, 5e5, fraction=0.5, yarn=yarn)
        np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
        np.testing.assert_array_equal(got[..., :64],
                                      rotary(x[..., :64], pos, 5e5, yarn=yarn))
        assert float(jnp.abs(got[..., :64] - x[..., :64]).max()) > 0.1
    assert rotary(x.astype(jnp.bfloat16), pos, 5e5, fraction=0.5,
                  yarn=YARN).dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="even head size"):
        rotary(x, pos, 5e5, fraction=0.3)


FAULTS = ("the gate left out", "YaRN left out",
          "full rotation on the full layers",
          "the sliding layers see the whole triangle", "the 2.5 left out")


def _fault(monkeypatch, name):
    """The system with one part of its mathematics left out."""
    real = hybrid.gqa_apply
    if name == FAULTS[0]:
        monkeypatch.setattr(hybrid, "gqa_apply", lambda blk, *a, **kw: real(
            {k: v for k, v in blk.items() if k != "wg"}, *a, **kw))
    elif name in FAULTS[1:3]:
        drop = {"yarn": None} if name == FAULTS[1] else {"rope_fraction": 1.0}
        monkeypatch.setattr(hybrid, "gqa_apply", lambda *a, **kw: real(
            *a, **(dict(kw, **drop) if kw else kw)))
    elif name == FAULTS[3]:
        att = hybrid.local_attention
        monkeypatch.setattr(
            hybrid, "local_attention",
            lambda q, k, v, causal, window=None: att(q, k, v, causal=causal))
    else:
        assert name == FAULTS[4]
        route = ep.route_held
        monkeypatch.setattr(ep, "route_held", lambda *a, **kw: route(
            *a, **dict(kw, scale=1.0)))


@pytest.mark.parametrize("name", FAULTS)
def test_planted_fault_moves_the_float32_loss_out_of_the_limit(
        monkeypatch, toy, name):
    """Each fault at toy size in float32, where the sound system holds 1e-6:
    the faulty system's loss is off by more than 1e-4.  Whatever the chip's
    bfloat16 check lets through (PERF.md section 4), this does not."""
    _, params, rp, tokens = toy
    want = float(REF.loss(rp, tokens))
    _fault(monkeypatch, name)
    model = FAM.build(TOY, max_len=64)          # traced with the fault in
    assert abs(float(lm_loss(model, params, tokens)) - want) > 1e-4


def test_the_32_chips_shares_add_up_to_the_uncut_reference():
    """The share test at the deployment's count: 64 experts over 32
    holders of 2.  Each holder's expert layer through the SYSTEM (its held
    experts' part, without the shared expert), summed, plus the attention
    and the shared expert ONCE, is the REFERENCE's layer with every expert
    held — for a full and for a sliding layer."""
    uncut = dict(TOY, num_experts=64, held_experts=list(range(64)),
                 n_router_outputs=64)
    model = FAM.build(uncut, max_len=64)
    params = FAM.init_params(model, harness.seed_key(7))
    whole = FAM.to_reference(params)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 32), jnp.float32)
    for n in (4, 1):
        blk = params[f"layer{n}"]
        want = REF.block(whole.tree["layers"][n], x, **REF._statics(whole)[n])
        h = hybrid.gqa_apply(blk, x, jnp.float32, 1e-6,
                             *((16, 1e4) if n == 1 else (None, 5e5)),
                             **({} if n == 1 else
                                {"rope_fraction": 0.5, "yarn": YARN}))
        m = hybrid._rmsnorm(blk["ln2"], h, 1e-6)
        shared = (jax.nn.silu(m @ blk["ws_gate"]) * (m @ blk["ws_up"])) \
            @ blk["ws_down"]
        total, seen = h + shared, 0
        for holder in range(32):
            held = (2 * holder, 2 * holder + 1)
            part = {k: v for k, v in blk.items() if not k.startswith("ws_")}
            part.update({k: blk[k][2 * holder:2 * holder + 2]
                         for k in ("we_gate", "we_up", "we_down")})
            y, aux = hybrid.moe_apply(part, h, jnp.float32, 1e-6, held, 3,
                                      None, score="softmax", scale=2.5)
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


def test_reference_is_written_without_the_systems_model():
    text = open(os.path.join(BENCH, "reference",
                             "gated_swa_moe_lm.py")).read()
    assert "distlearn_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in text and "yarn_frequencies" not in text


def test_counts_against_hand_counts():
    # D=4: a dense full layer (2 query heads) and a sliding mixture layer (4
    # query heads) over 1 K/V head of 2, a band of 3, a dense MLP 5 wide,
    # 6 experts of width 3 of which 2 are held, 3 a token, one shared of 3,
    # vocabulary 10
    cfg = dict(PUBLISHED, hidden_size=4, num_hidden_layers=2,
               num_attention_heads_per_layer=[2, 4] * 24,
               num_key_value_heads=1, head_dim=2, sliding_window=3,
               intermediate_size=5, moe_intermediate_size=3,
               shared_expert_intermediate_size=3, num_experts=2,
               held_experts=[0, 4], n_router_outputs=6,
               num_experts_per_tok=3, vocab_size=10)
    attn = lambda h: 2 * 4 * h * 2 + 4 * h + 2 * 4 * 1 * 2  # noqa: E731
    dense = attn(2) + 3 * 4 * 5 + 2 * 4
    mixture = attn(4) + 4 * 6 + 3 * 4 * 3 + 2 * 3 * 4 * 3 + 2 * 4
    assert FAM.param_count(cfg) == 2 * 10 * 4 + 4 + dense + mixture
    assert FAM.attended_pairs(8, None) == 36
    assert FAM.attended_pairs(8, 3) == 1 + 2 + 6 * 3 == 21
    assert FAM.attended_pairs(16384, 512) == 8257792
    assert FAM.attended_pairs(16384, None) == 134225920
    # per token, forward, x2 a multiply-add; experts 3 x 2 / 6 = 1 expected
    per_token = 2 * (attn(2) + 3 * 4 * 5 + attn(4) + 4 * 6
                     + (1 + 1.0) * 3 * 4 * 3) + 2 * 4 * 10
    want = 3 * (8 * per_token + 4 * (36 * 2 + 21 * 4) * 2)
    assert FAM.train_flops_per_sample(cfg, 8) == pytest.approx(want)
    # the sliding layer's kernel at ITS heads: 7 products a pair, x2
    assert FAM.ROOFLINE_PRODUCTS == 7
    ops, nbytes = FAM.window_attention_cost(cfg, 8)
    assert ops == 14 * 21 * 4 * 2
    q, kv, lse = 8 * 4 * 2 * 2, 8 * 1 * 2 * 2, 8 * 4 * 4
    assert nbytes == (2 * q + 2 * kv + lse) + (4 * q + 4 * kv + lse)
    with pytest.raises(ValueError, match="held here"):
        FAM.param_count(dict(cfg, num_experts=3))
    with pytest.raises(ValueError, match="gate a head on every layer"):
        FAM.param_count(dict(cfg, gating="element"))
    with pytest.raises(ValueError, match="leading dense"):
        FAM.param_count(dict(cfg, mlp_only_layers=[1]))
    with pytest.raises(ValueError, match="scan_blocks"):
        FAM.build(TOY, scan_blocks=True)


@pytest.mark.parametrize("which", ["toy", "committed"])
def test_param_count_is_the_built_trees_leaf_count(which):
    cfg = TOY if which == "toy" else PUBLISHED
    model = FAM.build(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    built = sum(int(np.prod(s.shape))
                for s in jax.tree_util.tree_leaves(shapes))
    assert FAM.param_count(cfg) == built
    if which == "committed":
        # the count by layer: 157,440,000 (layer 0) + 3 x 148,862,976 +
        # 129,914,880 + 2 x 12,544 x 3,072 + 3,072
        assert built == cfg["parameters"] == 811017216 == (
            157440000 + 3 * 148862976 + 129914880 + 2 * 12544 * 3072 + 3072)
        assert FAM.train_flops_per_sample(cfg, 16384) == pytest.approx(
            69.94e12, rel=1e-3)
        ops, nbytes = FAM.window_attention_cost(cfg, 16384)
        assert ops == 14 * 8257792 * 72 * 128 * 3
        assert ops / 197e12 > nbytes / 819e9           # compute-bound


def test_laguna_is_at_its_published_widths():
    cfg = PUBLISHED
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (3072, 48, 8, 128)
    assert (cfg["moe_intermediate_size"], cfg["n_router_outputs"],
            cfg["num_experts_per_tok"], cfg["sliding_window"],
            cfg["moe_routed_scaling_factor"], cfg["intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["rms_norm_eps"]) == (
                1024, 256, 10, 512, 2.5, 12288, 1024, 1e-6)
    assert cfg["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    assert cfg["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + (["full_attention"] + [
            "sliding_attention"] * 3) * 11
    s = FAM._sizes(cfg)
    assert s["types"] == ["full", "window", "window", "window", "full"]
    assert s["heads"] == [48, 72, 72, 72, 48] and s["dense"] == 1
    # the cut: the dense layer and one whole period, 8 held experts, an
    # eighth of the vocabulary
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 12544)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                                "vocab_size": 100352}
    assert cfg["held_experts"] == list(range(8))
    assert 8 * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    assert cfg["num_hidden_layers"] - len(cfg["mlp_only_layers"]) >= 4
    assert "one of 32 chips" in cfg["stands_for"]
    assert cfg["departures"] and cfg["reduced_why"]
    assert {"router_scoring", "gate_input", "partial_rotation", "yarn",
            "qk_norm", "shared_expert_gate", "bias", "kv_heads"} \
        <= set(cfg["assumed"])
    entry = {c["name"]: c for c in MAN["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    # every key of the source's config is here under its own name, where a
    # catalog of architectures (JSON lines) is named by MODEL_CATALOG
    catalog = os.environ.get("MODEL_CATALOG", "")
    if catalog and os.path.isfile(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Laguna-S-2.1")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    wl = harness.load_json("workloads", CELL + ".json")
    assert (wl["global_batch"], wl["seq"], wl["mesh"]) == (1, 16384, [1, 1, 1])
    assert wl["seq"] == 2 * cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"]
    assert wl["scan_blocks"] is False and wl["remat"] == "full"
    assert (wl["ring_batches"], wl["in_flight"], wl["check_steps"],
            wl["check_micro"], wl["trace_seconds"]) == (8, 4, 2, 1, 5)
    assert wl["compute_dtype"] == "bfloat16"
    assert wl["loss_tolerance_why"] and wl["lr_why"]


# --------------------------- the configurations the benchmark had before --

#: the four configurations trained before this family, each with its widths
#: cut to a toy's (pattern, router, activations and options as committed),
#: and the hash of the step program their cell's settings build, with the
#: serial numbers of private functions left out (``program_util``); pinned
#: from the build of the commit before the per-head gate, the rotation of
#: full layers and the heads by layer were added
BEFORE = {
    "gpt2-large": ("gpt2-large.train", dict(
        n_embd=32, n_layer=2, n_head=4, vocab_size=97, n_positions=64),
        "9a315184d001eab324a716a98077cefd2670fbd2be8ffd15d4a5635e82e87c96"),
    "solar-open2-250b": ("solar-open2-250b.train-8k", dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, linear_attn_config={"short_conv_kernel_size": 4,
                                        "head_dim": 8, "num_heads": 4,
                                        "num_kv_heads": None},
        kda_gate_rank=8, n_routed_experts=4, held_experts=[1, 5, 6, 11],
        n_router_outputs=16, num_experts_per_tok=4, moe_intermediate_size=24,
        vocab_size=97),
        "7e4b782d7c54d25deed9698128a616ed6608644a0ac9e8314625869b7380d096"),
    "smallthinker-21b-a3b": ("smallthinker-21b-a3b.train-16k", dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, sliding_window_size=16, moe_num_primary_experts=4,
        held_experts=[1, 5, 6, 11], n_router_outputs=16,
        moe_num_active_primary_experts=3, moe_ffn_hidden_size=24,
        vocab_size=97),
        "d38d75887ef8bec4c80eb7922bc5cf80037559e9a0ef63f40e66f797dcc9025b"),
    "joyai-llm-flash": ("joyai-llm-flash.train-16k", dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, qk_head_dim=12, v_head_dim=8,
        intermediate_size=40, moe_intermediate_size=24, n_routed_experts=4,
        held_experts=[1, 5, 6, 11], n_router_outputs=16,
        num_experts_per_tok=3, vocab_size=97),
        "26cf6bb1c85749c9559cadc3f87fefb409970d5c4d498a91e19a6b77b91f5329"),
}


def _step_program(config: str, seq: int = 64) -> str:
    """The lowered step of ``config``'s cell, as its benchmark process
    builds it: 64-bit types off (the suite turns them on)."""
    with jax.enable_x64(False):
        return _lowered_step(config, seq)


def _lowered_step(config: str, seq: int) -> str:
    cell, shrink, _ = BEFORE[config]
    cfg = dict(harness.load_json("configs", config + ".json"), **shrink)
    wl = harness.load_json("workloads", cell + ".json")
    fam = harness.load_module("families", cfg["family"])
    model = fam.build(cfg, max_len=seq, compute_dtype=wl["compute_dtype"],
                      scan_blocks=wl["scan_blocks"], remat=wl["remat"])
    template = jax.eval_shape(lambda k: model.init(k)[0],
                              jax.random.PRNGKey(0))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("data", "seq", "model"))
    step = build_lm_step(model, mesh, template, lr=wl["lr"])
    return program_text(step.lower(
        template, jax.ShapeDtypeStruct((1, seq), jnp.int32)))


@pytest.mark.parametrize("config", sorted(BEFORE))
def test_the_configurations_before_this_one_build_the_programs_they_built(
        config):
    text = _step_program(config)
    assert hashlib.sha256(text.encode()).hexdigest() == BEFORE[config][2]


def test_gate_and_rotary_counters_count_as_stated():
    """A traced model counts ``attn_gate_total{gate=}`` once a softmax
    layer that has a gate and ``rotary_total{scaling=}`` once a rotated q
    or k: this toy's five layers are gated a head; its two full layers
    rotate at YaRN's frequencies, its three sliding ones at default angles.
    The elementwise gate of a ``"gqa"`` layer counts as ``element``."""
    model, params, _, tokens = _toy()
    before = (hybrid.attn_gates_traced(), rotary_calls_traced())
    jax.eval_shape(lambda p, t: model.apply(p, {}, t, train=False),
                   params, tokens)
    gates, turns = hybrid.attn_gates_traced(), rotary_calls_traced()
    if not gates:                                   # DISTLEARN_OBS=0
        return
    grew = lambda now, was, k: now.get(k, 0) - was.get(k, 0)  # noqa: E731
    assert (grew(gates, before[0], "head"), grew(gates, before[0],
                                                 "element")) == (5, 0)
    assert (grew(turns, before[1], "yarn"),
            grew(turns, before[1], "none")) == (4, 6)
    solar = harness.load_module("families", "hybrid_lm").build(dict(
        harness.load_json("configs", "solar-open2-250b.json"),
        **BEFORE["solar-open2-250b"][1]), max_len=64)
    was = hybrid.attn_gates_traced()
    jax.eval_shape(lambda p, t: solar.apply(p, {}, t, train=False),
                   solar.init(jax.random.PRNGKey(0))[0], tokens)
    now = hybrid.attn_gates_traced()
    assert (grew(now, was, "element"), grew(now, was, "head")) == (1, 0)


# ------------------------------------------------------ the cell itself --

def test_the_cell_is_in_the_manifest_and_the_manifest_keeps_its_rules():
    """Wherever later PRs append theirs: nothing here reads a position."""
    rules.names_units_and_limits(MAN)
    rules.cells_resolve_and_report(MAN)
    cell = {w["name"]: w for w in MAN["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train-16k", 1)
    mine = {m["name"] for m in bench_run.cell_metrics(MAN, CELL, "per_layer")}
    assert {"attn_gate_ms.train", "attn_window_ms.train",
            "attn_full_ms.train", "swa_roofline.train", "moe_ms.train",
            "attn_core_ms.train", "mfu.train", "compile_s",
            "unscoped_share.train", "setup_run_s"} <= mine
    assert not {"linattn_core_ms.train", "linattn_roofline.train",
                "collective_ms.train", "collective_mb.train",
                "attn_mla_ms.train", "mla_roofline.train",
                "mla_latent_ms.train", "mtp_ms.train"} & mine
    gate = {m["name"]: m for m in MAN["per_layer"]}["attn_gate_ms.train"]
    assert gate["workloads"] == [CELL] and gate["source"] == "device_trace"
    assert gate["moves"] == "train_samples_per_s"
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
    cell is checked against the reference at its own tolerance and trains;
    on the CPU the gate's reader, of the device trace, gives nothing."""
    run, result = toy_cell_run
    line = bench_run.result_line(MAN, run, result)
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s"}
    assert result.window["check_gap_max"] < 1e-4
    assert result.window["params"] == FAM.param_count(TOY)
    found = rules.readers_keep_the_source_rule(MAN, run, result)
    assert found["attn_gate_ms.train"] is None
    assert {"attn_window_ms.train", "attn_full_ms.train",
            "swa_roofline.train", "moe_ms.train"} <= set(found)
    assert found["mfu.train"] > 0 and found["compile_s"] > 0


def test_the_cells_own_readers_find_their_instructions_in_the_toy_step(
        toy_cell_run, monkeypatch):
    """The family that feeds them: on the toy step's OWN optimized text,
    with a made-up trace in which every top-level instruction of the entry
    computation ran for a millisecond a step, the readers find the
    instructions named ``attn_gate`` (every one under ``attn_proj``),
    ``attn_window`` (three layers' worth) and ``attn_full`` (two layers'),
    those under ``attn_core``, and ``rope`` inside ``attn_proj``."""
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
                for inner in ("attn_gate", "attn_window", "attn_full",
                              "rope")}
    assert all(by_inner.values())
    for inner in ("attn_window", "attn_full"):
        assert all(scope_reduce.scope_of(table[n], SCOPES) == "attn_core"
                   for n in by_inner[inner])
    for inner in ("attn_gate", "rope"):
        assert all(scope_reduce.scope_of(table[n], SCOPES) == "attn_proj"
                   for n in by_inner[inner])
    for metric in ("attn_gate_ms.train", "attn_window_ms.train",
                   "attn_full_ms.train", "moe_ms.train",
                   "attn_core_ms.train"):
        assert read(metric) > 0, metric
    whole = scope_reduce.inner_whole_s(run, fake, "attn_window")
    ops_1, bytes_1 = FAM.window_attention_cost(TOY, TOY_WL["seq"])
    least = max(ops_1 / rules.PEAKS["bf16_flops_per_s"],
                bytes_1 / rules.PEAKS["hbm_bytes_per_s"])
    assert read("swa_roofline.train") == pytest.approx(100 * least / whole)


# ------------------------------- the reader the cell brings, by hand --

# the other pattern family's step in miniature with a gate a head added: its
# projection forward and its product backward, under attn_proj / attn_gate
HLO = _SWA_HLO.replace(
    "  ROOT %splash_mqa_dkv_no_residuals.7",
    '  %fusion.8 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%fusion.1), '
    'kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/'
    'jvp(attn_proj)/attn_gate/dot_general"}\n'
    '  %fusion.9 = bf16[8,16]{1,0:T(8,128)(2,1)} fusion(%fusion.8), '
    'kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/'
    'transpose(jvp(jvp()))/checkpoint/attn_proj/attn_gate/mul"}\n'
    "  ROOT %splash_mqa_dkv_no_residuals.7")
OPS = dict(_SWA_OPS, **{"fusion.8 bf16[8,16]": [0.03, 2, 0.03],
                        "fusion.9 bf16[8,16]": [0.01, 2, 0.01]})


def test_gate_reader_is_silent_without_a_trace_and_right_with_one(
        monkeypatch):
    run, result = _fake_run(None, monkeypatch, text=HLO)
    assert _read("attn_gate_ms.train", run, result) is None
    run, result = _fake_run({"ops": OPS}, monkeypatch, text=HLO)
    assert _read("attn_gate_ms.train", run, result) == pytest.approx(20.0)
    assert _read("attn_window_ms.train", run, result) == pytest.approx(130.0)
    # a program without the catalog (the parent of the PR that brought the
    # name) and a step without the name (every other cell's): nothing
    run, result = _fake_run({"ops": OPS}, monkeypatch, text=None)
    assert _read("attn_gate_ms.train", run, result) is None
    run, result = _fake_run({"ops": OPS}, monkeypatch,
                            text=HLO.replace("/attn_gate/", "/"))
    assert _read("attn_gate_ms.train", run, result) is None
