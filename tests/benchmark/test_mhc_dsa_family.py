"""The ``mhc_dsa_lm`` family of the benchmark (``families/mhc_dsa_lm.py``,
``reference/mhc_dsa_lm.py``, the configuration and the cell
``glm-5.3-flash.train-8k``), at toy size on the CPU: the system equals the
plain reference on logits, loss and EVERY gradient leaf (float32); each
reading the configuration file records under ``assumed`` is pinned; four
planted faults each move the float32 loss out of the tight limit; the shares
of the chips of a toy deployment (experts, and heads) add up to the uncut
reference's layer; the layer-by-layer reference equals the whole-model one;
the analytic counts equal the built tree and the op's own count; the
configuration is at its published widths; the cell's toy twin runs end to
end through the kind and the cell's readers find their instructions; and
with the new options at their defaults, the accepted pattern cells' toy
steps lower to the programs they lowered to before the residual was
factored out of the mixers.  Nothing here is a measurement."""

import copy
import hashlib
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import manifest_rules as rules
from manifest_rules import bench_run, harness
from program_util import program_text

import scope_reduce  # noqa: E402  (benchmarks/ is on the path by now)

from distlearn_tpu.models import hybrid  # noqa: E402
from distlearn_tpu.models.core import SCOPES  # noqa: E402
from distlearn_tpu.models.transformer import lm_loss  # noqa: E402
from distlearn_tpu.ops import sparse_attention  # noqa: E402
from distlearn_tpu.parallel import ep  # noqa: E402

MAN = bench_run.manifest()
CELL = "glm-5.3-flash.train-8k"
CONFIG = "glm-5.3-flash"
FAM = harness.load_module("families", "mhc_dsa_lm")
REF = harness.load_module("reference", "mhc_dsa_lm")
READERS = ("mhc_ms.train", "dsa_index_ms.train", "attn_dsa_ms.train",
           "dsa_roofline.train")
PUBLISHED = harness.load_json("configs", CONFIG + ".json")

#: the published file with every size cut to a toy's: the held layers 2-3
#: (a dense KDA layer, a sparse mixture layer; 4 of 16 experts held, 3 a
#: token, one shared), 4 latent heads of 8 over a 16-wide latent,
#: an indexer of 4 heads x 8 keeping 16 positions in blocks of 4, 4 KDA
#: heads of 8, four streams; the SwiGLU clamp at 1, so that it bites
TOY = dict(
    PUBLISHED, hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_head_dim=8,
    v_head_dim=8, index_n_heads=4, index_head_dim=8, index_topk=16,
    held_layers=[2, 3], num_hidden_layers=2,
    linear_attn_config=dict(PUBLISHED["linear_attn_config"], num_heads=4,
                            head_dim=8),
    kda_gate_rank=8, intermediate_size=40, moe_intermediate_size=24,
    n_routed_experts=4, held_experts=[1, 5, 6, 11], n_router_outputs=16,
    num_experts_per_tok=3, vocab_size=97, max_position_embeddings=256,
    swiglu_limit=1.0)
TOY_WL = {"kind": "train_lm", "mesh": [1, 1, 1], "global_batch": 1,
          "seq": 64, "lr": 0.05, "compute_dtype": None,
          "scan_blocks": False, "remat": "full", "ring_batches": 2,
          "in_flight": 2, "check_steps": 2, "check_micro": 1,
          "loss_tolerance": 1e-4, "trace_seconds": 0.3}


def _toy(cfg=TOY, seed=2**31 + 5):
    """The toy model, its parameters from a seed beyond 2**31 (correction
    biases drawn, non-zero), the same in the reference's layout, tokens."""
    model = FAM.build(cfg, max_len=64)
    params = FAM.init_params(model, harness.seed_key(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 97,
                                jnp.int32)
    return model, params, FAM.to_reference(params), tokens


def _loss(model):
    return jax.jit(lambda p, t: lm_loss(model, p, t))


@pytest.fixture(scope="module")
def toy():
    return _toy()


@pytest.fixture(scope="module")
def ref_grads(toy):
    """The reference's loss and gradient on the toy, compiled once."""
    _, _, rp, tokens = toy
    return jax.jit(jax.value_and_grad(REF.loss))(rp, tokens)


@pytest.fixture(scope="module")
def ref_loss(ref_grads):
    return float(ref_grads[0])


def test_system_equals_reference_on_logits_loss_and_every_gradient(
        toy, ref_grads):
    """float32 against float32: the limits are rounding alone (the two sides
    sum in different orders — the absorbed latent against explicit keys and
    values, the chunked delta rule against the recurrence, grouped tiles
    against a masked scan)."""
    model, params, rp, tokens = toy
    got, state = jax.jit(lambda p, t: model.apply(p, {}, t, train=True))(
        params, tokens)
    np.testing.assert_allclose(got, jax.jit(REF.logits)(rp, tokens),
                               atol=3e-5)
    assert int(state["moe_dropped"].sum()) == 0
    assert state["moe_assignments"].shape == (1, 4)     # 1 mixture layer
    assert rp.static[:2] == (("kda", "dsa"), 1)
    l_sys, g_sys = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(model, p, tokens)))(params)
    l_ref, g_ref = ref_grads
    assert float(l_sys) == pytest.approx(float(l_ref), rel=1e-6)
    got_leaves = jax.tree_util.tree_leaves_with_path(FAM.to_reference(g_sys))
    want_leaves = jax.tree_util.tree_leaves_with_path(g_ref)
    assert len(got_leaves) == len(want_leaves)
    for (path, a), (_, b) in zip(got_leaves, want_leaves):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name or "idx_" in name:
            # the bias and the indexer enter a discrete choice alone
            assert float(jnp.abs(a).max()) == float(jnp.abs(b).max()) == 0.0
            continue
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-6 + 2e-4 * float(jnp.abs(b).max()),
            err_msg=name)


# ---------------------------------------- the readings under ``assumed`` --

def test_sinkhorn_makes_rows_and_columns_sum_to_one_and_the_draw_is_mixed(
        toy):
    """After 20 rounds on the seeded draw's maps (and on a spread of random
    ones) every row and column sums to 1 within 1e-5 (the hc_eps in each
    division leaves 4e-6); one round leaves the rows off."""
    _, params, _, _ = toy
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 4, 32))
    pre, post, res = hybrid.hc_maps(params["layer1"]["hc_mix"], x, 20, 1e-6,
                                    1e-5)
    m = jnp.exp(0.5 * jax.random.normal(jax.random.PRNGKey(0), (64, 4, 4)))
    for got in (res, hybrid.sinkhorn(m, 20, 1e-6)):
        np.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-5)
        np.testing.assert_allclose(got.sum(-2), 1.0, atol=1e-5)
    assert float(jnp.abs(hybrid.sinkhorn(m, 1, 1e-6).sum(-1) - 1).max()) \
        > 1e-3
    # the seeded draw: H_res neither the identity nor uniform, h_pre varies
    diag = jnp.diagonal(res, axis1=-2, axis2=-1)
    assert 0.5 < float(diag.mean()) < 0.9
    assert float(jnp.std(pre)) > 1e-3 and 0.3 < float(pre.mean()) < 0.7
    np.testing.assert_allclose(post.mean(), 1.0, atol=0.05)


def test_h_post_is_twice_a_sigmoid_and_h_pre_a_sigmoid():
    hc = {"phi": jnp.zeros((8, 4 + 4 + 16)), "alpha": jnp.ones(3),
          "b_pre": jnp.full(4, 0.3), "b_post": jnp.full(4, -0.7),
          "b_res": jnp.zeros((4, 4))}
    pre, post, res = hybrid.hc_maps(hc, jnp.ones((1, 2, 4, 2)), 20, 1e-6,
                                    1e-5)
    np.testing.assert_allclose(pre, jax.nn.sigmoid(0.3), rtol=1e-6)
    np.testing.assert_allclose(post, 2 * jax.nn.sigmoid(-0.7), rtol=1e-6)
    np.testing.assert_allclose(res, 0.25, rtol=1e-5)     # uniform from 0


def test_kda_gate_is_bounded_and_beta_has_no_factor_two(monkeypatch):
    """The bounded gate lies in (-5, 0) over sigmoid arguments from -12 to
    12 (rate 1, the pre-activation its bias alone) and comes within 1e-3 of
    the bound; the softplus gate at the same inputs passes it.  beta is one
    sigmoid; the defaults keep the softplus gate and 2 sigmoid."""
    seen = {}

    def spy(q, k, v, g, beta, **kw):     # the rule itself is not under test
        seen.setdefault("g", []).append(g)
        seen.setdefault("beta", []).append(beta)
        return jnp.zeros_like(v), None
    monkeypatch.setattr(hybrid, "chunked_delta_rule", spy)
    _, params, _, _ = _toy(dict(TOY, held_layers=[4], num_hidden_layers=1))
    blk = params["layer0"]
    H, K = blk["dt_bias"].shape
    blk = dict(blk, a_log=jnp.zeros(H), wa2=jnp.zeros_like(blk["wa2"]),
               dt_bias=jnp.linspace(-12.0, 12.0, H * K).reshape(H, K))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 32))
    hybrid.kda_branch(blk, x, jnp.float32, 1e-5, -5.0, 1.0)
    hybrid.kda_branch(blk, x, jnp.float32, 1e-5)
    (g_b, g_s), (b_b, b_s) = seen["g"], seen["beta"]
    np.testing.assert_allclose(
        g_b[0, :, 0], -5.0 * jax.nn.sigmoid(blk["dt_bias"]), rtol=1e-6)
    assert float(g_b.max()) < 0 and float(g_b.min()) > -5
    assert float(g_b.min()) < -4.999
    assert float(g_s.min()) < -5                         # softplus: unbounded
    np.testing.assert_allclose(b_s, 2 * b_b, rtol=1e-6)
    assert float(b_b.max()) < 1


def test_swiglu_clamp_is_active_past_its_limit():
    a = jnp.array([-20.0, -3.0, 0.5, 3.0, 20.0])
    b = jnp.array([-20.0, -3.0, 0.5, 3.0, 20.0])
    got = ep.glu_gate(a, "silu", 10.0) * ep.glu_up(b, 10.0)
    want = jax.nn.silu(jnp.minimum(a, 10.0)) * jnp.clip(b, -10.0, 10.0)
    np.testing.assert_allclose(got, want)
    assert float(got[-1]) == pytest.approx(float(jax.nn.silu(10.0)) * 10.0)
    np.testing.assert_array_equal(ep.glu_gate(a) * ep.glu_up(b),
                                  jax.nn.silu(a) * b)    # no limit: none
    # the grouped product's hand-written backward through the clamp
    h, pull = ep._hidden(a, b, "silu", jnp.float32, 10.0)
    db, da = pull(jnp.ones(5))
    auto = jax.grad(lambda u, v: jnp.sum(
        ep.glu_gate(u, "silu", 10.0) * ep.glu_up(v, 10.0)), (0, 1))(a, b)
    np.testing.assert_allclose(h, got)
    np.testing.assert_allclose(da, auto[0], atol=1e-6)
    np.testing.assert_allclose(db, auto[1], atol=1e-6)
    assert float(da[-1]) == float(db[-1]) == float(db[0]) == 0.0


# ------------------------------------------------------ planted faults --

FAULTS = ("Sinkhorn at one iteration", "the selection ignored",
          "h_post without its factor 2", "the softplus gate")


def _fault(monkeypatch, name):
    """The system with one part of its mathematics changed."""
    if name == FAULTS[0]:
        real = hybrid.sinkhorn
        monkeypatch.setattr(hybrid, "sinkhorn",
                            lambda m, iters, eps: real(m, 1, eps))
    elif name == FAULTS[1]:
        monkeypatch.setattr(sparse_attention, "select_blocks",
                            lambda score, *a: jnp.ones(score.shape, bool))
    elif name == FAULTS[2]:
        real = hybrid.hc_maps

        def once(*a):
            pre, post, res = real(*a)
            return pre, post / 2, res
        monkeypatch.setattr(hybrid, "hc_maps", once)
    else:
        assert name == FAULTS[3]
        real = hybrid.kda_branch
        monkeypatch.setattr(hybrid, "kda_branch",
                            lambda blk, x, cd, eps, *a, **kw:
                            real(blk, x, cd, eps))


@pytest.mark.parametrize("name", FAULTS)
def test_planted_fault_moves_the_float32_loss_out_of_the_limit(
        monkeypatch, toy, ref_loss, name):
    """Each fault at toy size in float32, where the sound system holds
    1e-6: the faulty system's loss is off by more than 1e-4."""
    _, params, _, tokens = toy
    _fault(monkeypatch, name)
    model = FAM.build(TOY, max_len=64)          # traced with the fault in
    assert abs(float(_loss(model)(params, tokens)) - ref_loss) > 1e-4


# ---------------------------------------------------------- the shares --

def test_all_the_chips_shares_add_up_to_the_uncut_reference():
    """The guide's share test, for both cuts of the configuration: the 16
    experts over 4 holders of 4, and the heads of the sparse layer and of a
    KDA layer over a tensor-parallel group of 2.  Each holder's part through
    the SYSTEM, less what all compute alike, summed, plus that ONCE, is the
    REFERENCE's layer with everything held."""
    uncut = dict(TOY, n_routed_experts=16, held_experts=list(range(16)),
                 held_layers=[3, 4])                 # a sparse, a KDA layer
    model = FAM.build(uncut, max_len=64)
    params = FAM.init_params(model, harness.seed_key(7))
    whole = FAM.to_reference(params)
    st = whole.layer_static
    f32, eps = jnp.float32, st["eps"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 32), f32)
    # experts: the sparse layer's mixture
    blk, ref = params["layer0"], whole.tree["layers"][0]
    m = REF.rmsnorm(x, ref["ln_2"], eps)
    with jax.default_matmul_precision("highest"):
        want = REF.swiglu(m, ref["ws_gate"], ref["ws_up"], ref["ws_down"],
                          st["limit"]) + REF.experts(
            ref, m, st["held"], st["top_k"], st["scale"], st["limit"])
    kw = dict(score="sigmoid", scale=st["scale"], limit=st["limit"])
    none = dict(blk, **{k: jnp.zeros_like(blk[k])
                        for k in ("we_gate", "we_up", "we_down")})
    alike = sum(hybrid.moe_branch(none, x, f32, eps, tuple(range(16)), 3,
                                  None, **kw)[0][i].reshape(x.shape)
                for i in range(2))
    total, seen = alike, 0
    for holder in range(4):
        held = tuple(range(4 * holder, 4 * holder + 4))
        part = dict(blk, **{k: blk[k][4 * holder:4 * holder + 4]
                            for k in ("we_gate", "we_up", "we_down")})
        terms, aux = hybrid.moe_branch(part, x, f32, eps, held, 3, None, **kw)
        total = total + terms[1].reshape(x.shape) \
            - alike + terms[0]
        seen += int(aux["assignments"].sum())
    assert seen == 2 * 64 * 3                   # every assignment a holder
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    # heads of the sparse layer: two holders of two; the latent
    # down-projections and the indexer alike in both
    u = REF.rmsnorm(x, ref["ln_1"], eps)
    with jax.default_matmul_precision("highest"):
        want = REF.mix_dsa(ref, u, st["pool"], st["topk"], eps)
    dsa = jax.jit(hybrid.dsa_branch, static_argnums=(2, 3, 4, 5))
    got = sum(dsa(dict(blk, wq_b=blk["wq_b"][:, hs],
                                     wkv_b=blk["wkv_b"][:, hs],
                                     wo=blk["wo"][hs]),
                                x, f32, eps, st["pool"], st["topk"])
              for hs in (slice(0, 2), slice(2, 4)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # heads of a KDA layer: every leaf that holds a head axis cut by head
    blk, ref = params["layer1"], whole.tree["layers"][1]
    u = REF.rmsnorm(x, ref["ln_1"], eps)
    with jax.default_matmul_precision("highest"):
        want = REF.mix_kda(ref, u, st["lb"], eps)
    K = blk["dt_bias"].shape[1]

    def share(hs):
        cols = slice(hs.start * K, hs.stop * K)
        return dict(blk, **{
            "wq": blk["wq"][:, hs], "wk": blk["wk"][:, hs],
            "wv": blk["wv"][:, hs], "wb": blk["wb"][:, hs],
            "dt_bias": blk["dt_bias"][hs], "a_log": blk["a_log"][hs],
            "wa2": blk["wa2"][:, cols], "wg2": blk["wg2"][:, cols],
            "conv_q": blk["conv_q"][:, cols], "conv_k": blk["conv_k"][:, cols],
            "conv_v": blk["conv_v"][:, cols], "wo": blk["wo"][cols]})
    kda = jax.jit(hybrid.kda_branch, static_argnums=(2, 3, 4, 5))
    got = sum(kda(share(hs), x, f32, eps, st["lb"], 1.0)
              for hs in (slice(0, 2), slice(2, 4)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_layerwise_reference_equals_whole_model_and_streams_its_updates(
        toy, ref_grads):
    _, _, rp, tokens = toy
    want_l, want_g = ref_grads
    got_l, got_g = REF.layerwise_loss_and_grads(rp, tokens, micro=1)
    assert got_l == pytest.approx(float(want_l), rel=1e-6)
    assert got_g.static == rp.static
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-6 + 2e-5 * float(jnp.abs(b).max()))
    fresh = lambda: jax.tree_util.tree_map(jnp.copy, rp)    # noqa: E731
    streamed = REF.layerwise_sgd_losses(fresh(), tokens, 0.05, 2, micro=2)
    summed = REF.layerwise_sgd_losses(fresh(), tokens, 0.05, 2, micro=1)
    assert streamed == pytest.approx(summed, rel=1e-5)
    assert streamed[2] < streamed[1] < streamed[0]


def test_reference_is_written_without_the_systems_model():
    text = open(os.path.join(rules.BENCH, "reference", "mhc_dsa_lm.py")).read()
    assert "distlearn_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in text and "argsort" in text


# ------------------------------------------------------------ the counts --

@pytest.mark.parametrize("which", ["toy", "committed"])
def test_param_count_is_the_built_trees_leaf_count(which):
    cfg = TOY if which == "toy" else PUBLISHED
    model = FAM.build(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    built = sum(int(np.prod(s.shape))
                for s in jax.tree_util.tree_leaves(shapes))
    assert FAM.param_count(cfg) == built
    if which == "committed":
        assert built == cfg["parameters"] == 1407738062
        layer = lambda n: sum(int(np.prod(s.shape)) for s in  # noqa: E731
                              jax.tree_util.tree_leaves(shapes[n]))
        assert [layer(f"layer{i}") for i in range(5)] == [
            187009222, 271067734, 263686630, 263686630, 263686630]
        hc = sum(int(np.prod(s.shape)) for blk in ("hc_mix", "hc_ffn")
                 for s in jax.tree_util.tree_leaves(shapes["layer1"][blk]))
        assert hc == 786486


def test_the_pairs_of_the_yardstick_are_the_ops_own_count():
    cfg = PUBLISHED
    pairs = FAM.selected_pairs(8192, 4, 2048)
    assert pairs == sparse_attention.selected_pairs(8192, 4, 2048) \
        == 14671872
    ops, nbytes = FAM.dsa_attention_cost(cfg, 8192)
    assert ops == 2 * (4 + 3) * 512 * 16 * pairs
    assert ops / 197e12 > nbytes / 819e9               # compute-bound
    # the FLOP count holds the attention over those pairs, x3
    base = FAM.train_flops_per_sample(
        dict(cfg, index_topk=4), 8192)               # one block a query
    with_sel = FAM.train_flops_per_sample(cfg, 8192)
    small = FAM.selected_pairs(8192, 4, 4)
    assert with_sel - base == pytest.approx(
        3 * 4 * 16 * 512 * (pairs - small))
    assert FAM.train_flops_per_sample(cfg, 8192) == pytest.approx(
        28.32e12, rel=1e-3)


# --------------------------------------------------- the configuration --

def test_glm_5_3_flash_is_at_its_published_widths():
    cfg = PUBLISHED
    assert (cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"]) == (4096, 1536, 512, 256, 0, 256)
    assert (cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"],
            cfg["index_kpool"]) == (32, 128, 2048, 4)
    lin = cfg["linear_attn_config"]
    assert (lin["head_dim"], lin["short_conv_kernel_size"],
            lin["gate_lower_bound"]) == (128, 4, -5)
    assert (cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["n_router_outputs"], cfg["num_experts_per_tok"],
            cfg["scoring_func"], cfg["routed_scaling_factor"],
            cfg["swiglu_limit"]) == (2048, 12288, 288, 8, "sigmoid", 2.5, 10)
    assert (cfg["hc_mult"], cfg["hc_sinkhorn_iters"], cfg["hc_eps"]) == (
        4, 20, 1e-6)
    # the cut: layer 2 (the last leading dense one) and one period, 8 held
    # experts, 16 of 64 heads, an eighth of the vocabulary
    assert cfg["held_layers"] == [2, 3, 4, 5, 6]
    assert [cfg["layer_types"][i] for i in cfg["held_layers"]] == [
        "linear_attention", "deepseek_sparse_attention"] \
        + ["linear_attention"] * 3
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["num_attention_heads"],
            lin["num_heads"]) == (5, 8, 19360, 16, 16)
    assert 8 * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    assert cfg["held_experts"] == list(range(8))
    assert "one of 36 chips" in cfg["stands_for"]
    assert set(cfg["reduced"]) <= set(cfg["reduced_why"])
    assert {"hc_init", "index_kpool", "kda_gate", "kda_beta",
            "swiglu_limit", "mhc", "dsa_indexer"} <= set(cfg["assumed"])
    assert (cfg["assumed"]["hc_init"]["alpha"],
            cfg["assumed"]["hc_init"]["b_res_diag"]) == (hybrid.HC_ALPHA,
                                                         hybrid.HC_RES_DIAG)
    assert any("KL objective" in d for d in cfg["departures"])
    assert any("vision tower" in d for d in cfg["departures"])
    entry = {c["name"]: c for c in MAN["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    # the cut keys, and nothing else, differ from the published values
    for key in cfg["reduced"]:
        assert key in cfg["reduced_why"], key
    assert cfg["published"]["linear_attn_config.num_heads"] == 64
    assert (cfg["published"]["num_hidden_layers"], len(cfg["layer_types"]),
            len(cfg["mlp_layer_types"])) == (45, 45, 45)
    wl = harness.load_json("workloads", CELL + ".json")
    assert (wl["global_batch"], wl["seq"], wl["mesh"]) == (1, 8192, [1, 1, 1])
    assert wl["scan_blocks"] is False and wl["remat"] == "full"
    assert (wl["ring_batches"], wl["in_flight"], wl["check_steps"],
            wl["check_micro"], wl["trace_seconds"]) == (8, 4, 2, 1, 5)
    assert wl["loss_tolerance_why"] and wl["lr_why"]


def test_the_cell_is_in_the_manifest_and_the_manifest_keeps_its_rules():
    rules.names_units_and_limits(MAN)
    rules.cells_resolve_and_report(MAN)
    cell = {w["name"]: w for w in MAN["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train-8k", 1)
    mine = {m["name"] for m in bench_run.cell_metrics(MAN, CELL, "per_layer")}
    assert set(READERS) | {"moe_ms.train", "linattn_core_ms.train",
                           "linattn_roofline.train", "mfu.train",
                           "compile_s", "setup_run_s"} <= mine
    # mla_latent_ms.train would find the sparse layer's low-rank paths (the
    # readers' test below), but its accepted list is joyai's cell alone
    assert not {"attn_mla_ms.train", "mla_roofline.train",
                "mla_latent_ms.train", "collective_ms.train",
                "swa_roofline.train"} & mine
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"
        assert m["moves"] == "train_samples_per_s"
    assert by_name["dsa_roofline.train"]["unit"] == "%"
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
    run, result = toy_cell_run
    line = bench_run.result_line(MAN, run, result)
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s"}
    assert result.window["check_gap_max"] < 1e-4
    assert result.window["params"] == FAM.param_count(TOY)
    found = rules.readers_keep_the_source_rule(MAN, run, result)
    assert set(READERS) | {"moe_ms.train", "linattn_core_ms.train"} \
        <= set(found)
    assert found["mfu.train"] > 0 and found["compile_s"] > 0


def test_the_cells_own_readers_find_their_instructions_in_the_toy_step(
        toy_cell_run, monkeypatch):
    """On the toy step's OWN optimized text, with a made-up trace in which
    every top-level instruction of the entry computation ran for a
    millisecond a step: the instructions named ``attn_dsa`` and
    ``dsa_index`` all sit under ``attn_core``, the scope ``mhc`` is there,
    and each of the four readers, and ``mla_latent_ms.train``'s, gives a
    number."""
    run, result = toy_cell_run
    text = scope_reduce.step_hlo(run, result)
    where, _, entry = scope_reduce.structure(text)
    calls_n = result.window["calls"]
    ops = {f"{n} f32[1]": [1e-3 * calls_n, calls_n, 1e-3 * calls_n]
           for n, comp in where.items() if comp == entry}
    window = {k: v for k, v in result.window.items()
              if k != "scope_reduction"}
    fake = type(result)(correct=True, attempted=1, failed=0, end_to_end={},
                        window=window)
    monkeypatch.setattr(run.trace, "reduction", {"ops": ops})
    table = scope_reduce._lm_program()[2](text)
    for inner in ("attn_dsa", "dsa_index"):
        named = {n for n, o in table.items()
                 if inner in scope_reduce.components(o)}
        assert named, inner
        # a few reductions inside the scans' bodies carry the inner name
        # alone: the reader finds them, the scope table counts them unscoped
        scopes = [scope_reduce.scope_of(table[n], SCOPES) for n in named]
        assert set(scopes) <= {"attn_core", scope_reduce.UNSCOPED}, inner
        assert scopes.count("attn_core") > len(scopes) / 2, inner
    assert any(scope_reduce.scope_of(o, SCOPES) == "mhc"
               for o in table.values())
    for metric in READERS + ("mla_latent_ms.train",):
        value = harness.load_module("layer_metrics", metric).read(run, fake)
        assert value and value > 0, metric


# ------------------------- the accepted cells' programs, options at rest --

#: sha256 of the lowered text (``program_util.program_text``) of the value
#: and gradient of the loss of each accepted pattern family's toy, bfloat16
#: compute, ``remat="full"`` — taken from the tree BEFORE the mixers'
#: residual was factored out of them and the new options were added: with
#: every new option at its default the programs are the same.  A change that
#: means to change one of these programs updates its hash in the same
#: commit.
LOWERED = {
    "hybrid_lm": ("test_hybrid_family", 128,
                  "b12c1f6146cec5e1349dd2790d2c4aca"
                  "4b3f8ef65adfffb18202916fc0c147d0"),
    "mla_moe_lm": ("test_mla_moe_family", 64,
                   "02e6edb0fb75bfa8951b4bfe0a365569"
                   "253182439feab9e0ba7a295d2d4c3cd6"),
    "gated_swa_moe_lm": ("test_gated_swa_moe_family", 64,
                         "ea5c50152aeb36f92db2df91e82342cc"
                         "eac5c28c4f450aa88b485cd6cc2ac5dd"),
    "swa_moe_lm": ("test_swa_moe_family", 64,
                   "49398b760aa656103b0800f431aca528"
                   "54ee8cf944b4f5e7dc645a00103c9344"),
}


@pytest.mark.parametrize("family", sorted(LOWERED))
def test_accepted_pattern_cells_lower_to_the_programs_they_had(family):
    import importlib
    module, seq, want = LOWERED[family]
    cfg = importlib.import_module(module).TOY
    model = harness.load_module("families", family).build(
        cfg, max_len=seq, compute_dtype="bfloat16", remat="full")
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    with jax.enable_x64(False):
        lowered = jax.jit(jax.value_and_grad(
            lambda p, t: lm_loss(model, p, t))).lower(shapes, tokens)
    assert hashlib.sha256(program_text(lowered).encode()).hexdigest() == want
