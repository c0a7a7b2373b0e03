"""The ``mhc_dsa_lm`` family: the repo's pattern LM
(``distlearn_tpu.models.hybrid.hybrid_lm``) built as a decoder whose residual
is several streams joined by manifold-constrained hyper-connections, whose
layers are gated delta-rule linear attention with a bounded decay gate
(``"kda"``) and, every fourth, DeepSeek sparse attention over latent
attention with no rotary part (``"dsa"``: a lightning indexer over pooled
keys picks the positions each query attends); the leading layers have a
dense clamped-SwiGLU MLP, the rest a routed mixture of clamped-SwiGLU experts
beside a shared one, scored by sigmoid and chosen through a correction bias;
from a configuration file in the source's key names, its weights made on the
device from the seed, its parameter tree renamed into the plain reference's
layout, the analytic count of the operations one chip's SHARE of the model
requires, and the operations and bytes of its delta rule and of its sparse
attention for the rooflines.

The configuration holds the published per-layer lists whole; ``held_layers``
names the layers held here (``num_hidden_layers`` of them).
``n_routed_experts`` counts the experts HELD here (``held_experts`` names
them); the router keeps ``n_router_outputs``, the published count.
``num_attention_heads`` and ``linear_attn_config.num_heads`` are the heads
held here; ``vocab_size`` is the slice of the vocabulary held here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from distlearn_tpu.models.hybrid import hybrid_lm

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, None: None}

#: the share and the sizes of the model last built that its arrays do not
#: show (the reference's :class:`Params` carries them, and ``to_reference``
#: is handed nothing but the parameter tree)
_static: tuple | None = None

#: the standard deviation of the seeded correction bias: the value and the
#: reason of ``families/mla_moe_lm.py::BIAS_STD`` (a router of the same kind)
BIAS_STD = 0.01

_KINDS = {"linear_attention": "kda", "deepseek_sparse_attention": "dsa"}


def _sizes(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    held = list(cfg["held_experts"])
    layers = list(cfg["held_layers"])
    if len(held) != cfg["n_routed_experts"]:
        raise ValueError(
            "n_routed_experts counts the experts held here: "
            f"{cfg['n_routed_experts']} != {len(held)} held")
    if len(layers) != cfg["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers held here: "
                         f"{cfg['num_hidden_layers']} != {len(layers)}")
    dense = [cfg["mlp_layer_types"][i] == "dense" for i in layers]
    if dense != sorted(dense, reverse=True):
        raise ValueError("the held dense layers must lead the held ones")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or (cfg["scoring_func"], cfg["topk_method"]) \
            != ("sigmoid", "noaux_tc") or not cfg["norm_topk_prob"] \
            or cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu" \
            or cfg["attention_bias"] or not cfg["mhc"] \
            or not cfg["mla_use_nope"] or cfg["qk_rope_head_dim"] \
            or cfg["num_nextn_predict_layers"] \
            or not cfg["index_kpool_compress"] \
            or not cfg["index_kpool_always_select_tail"]:
        raise ValueError(
            "the family builds sigmoid scores chosen through a correction "
            "bias (noaux_tc, one group) renormalised over the top-k, SwiGLU, "
            "an untied head, no attention bias, hyper-connections, latent "
            "attention with no rotary part, pooled indexer keys with the "
            "tail block kept, and no prediction module")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("qk_head_dim is qk_nope_head_dim; latent attention "
                         "has as many K/V heads as heads")
    return {
        "depth": len(layers), "layers": layers,
        "types": [_KINDS[cfg["layer_types"][i]] for i in layers],
        "dense": sum(dense), "D": cfg["hidden_size"],
        "H": cfg["num_attention_heads"], "qr": cfg["q_lora_rank"],
        "kvr": cfg["kv_lora_rank"], "dk": cfg["qk_nope_head_dim"],
        "dv": cfg["v_head_dim"], "J": cfg["index_n_heads"],
        "dI": cfg["index_head_dim"], "topk": cfg["index_topk"],
        "pool": cfg["index_kpool"], "Hk": lin["num_heads"],
        "K": lin["head_dim"], "W": lin["short_conv_kernel_size"],
        "lb": lin["gate_lower_bound"], "r": cfg["kda_gate_rank"],
        "Fd": cfg["intermediate_size"], "F": cfg["moe_intermediate_size"],
        "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "E": cfg["n_router_outputs"], "held": held,
        "k": cfg["num_experts_per_tok"], "scale": cfg["routed_scaling_factor"],
        "limit": cfg["swiglu_limit"], "V": cfg["vocab_size"],
        "n": cfg["hc_mult"], "iters": cfg["hc_sinkhorn_iters"],
        "hc_eps": cfg["hc_eps"], "eps": cfg["rms_norm_eps"]}


def build(cfg: dict, *, max_len: int | None = None, compute_dtype=None,
          scan_blocks: bool = False, remat=False):
    """The model through the repo's constructor, at the configuration's
    sizes.  The layers differ in kind, so nothing is scanned."""
    global _static
    if scan_blocks:
        raise ValueError("the layers differ in kind: scan_blocks must be "
                         "false")
    s = _sizes(cfg)
    _static = (tuple(s["types"]), s["dense"], tuple(s["held"]), s["k"],
               s["scale"], s["limit"], s["lb"], s["pool"], s["topk"],
               s["iters"], s["hc_eps"], s["eps"])
    return hybrid_lm(
        vocab=s["V"], dim=s["D"], layer_types=s["types"], heads=s["H"],
        kv_heads=s["H"], head_dim=s["dk"], kda_heads=s["Hk"],
        kda_head_dim=s["K"], conv_kernel=s["W"], kda_rank=s["r"],
        q_lora_rank=s["qr"], kv_lora_rank=s["kvr"],
        qk_nope_head_dim=s["dk"], qk_rope_head_dim=0, v_head_dim=s["dv"],
        index_heads=s["J"], index_head_dim=s["dI"], index_topk=s["topk"],
        index_pool=s["pool"], dense_layers=s["dense"], dense_width=s["Fd"],
        n_routed_experts=s["E"], held_experts=s["held"],
        experts_per_tok=s["k"], expert_width=s["F"],
        n_shared_experts=cfg["n_shared_experts"], router_score="sigmoid",
        routed_scale=s["scale"], swiglu_limit=s["limit"],
        kda_gate_lower_bound=s["lb"], kda_beta_scale=1.0, hc_mult=s["n"],
        hc_sinkhorn_iters=s["iters"], hc_eps=s["hc_eps"], eps=s["eps"],
        max_len=max_len or cfg["max_position_embeddings"],
        compute_dtype=_DTYPES[compute_dtype], remat=remat)


def init_params(model, key, sharding=None):
    """The whole tree in ONE jitted call on the device, float32: the
    constructor's own draw with the embedding rows at unit scale and every
    router's correction bias drawn N(0, :data:`BIAS_STD`) from the seed —
    ``families/mla_moe_lm.py::init_params``'s two changes, for its
    reasons."""
    def make(k):
        params = model.init(k)[0]
        bias_key = jax.random.fold_in(k, 0xB1A5)
        rows = params["embed"]
        out = dict(params, embed=rows * math.sqrt(rows.shape[1]))
        for n in range(sum(name.startswith("layer") for name in params)):
            blk = params[f"layer{n}"]
            if "router_bias" in blk:
                b = blk["router_bias"]
                out[f"layer{n}"] = dict(blk, router_bias=BIAS_STD
                                        * jax.random.normal(
                                            jax.random.fold_in(bias_key, n),
                                            b.shape, b.dtype))
        return out
    return jax.jit(make, out_shardings=sharding)(key)


def _hc_params(s: dict) -> int:
    n = s["n"]
    return n * s["D"] * (2 * n + n * n) + 3 + 2 * n + n * n


def _mix_params(s: dict, kind: str) -> int:
    D = s["D"]
    if kind == "dsa":
        return (D * s["qr"] + s["qr"] + s["qr"] * s["H"] * s["dk"]
                + D * s["kvr"] + s["kvr"] + s["kvr"] * s["H"] * (s["dk"]
                                                                 + s["dv"])
                + s["H"] * s["dv"] * D + s["qr"] * s["J"] * s["dI"]
                + D * s["dI"] + 2 * s["dI"] + D * s["J"])
    HK = s["Hk"] * s["K"]
    return (4 * D * HK + 2 * (D * s["r"] + s["r"] * HK) + D * s["Hk"]
            + 3 * s["W"] * HK + HK + s["Hk"] + s["K"])


def _layer_params(s: dict, kind: str, dense: bool) -> int:
    D = s["D"]
    ffn = 3 * D * s["Fd"] if dense else (D * s["E"] + s["E"] + 3 * D * s["Fs"]
                                         + len(s["held"]) * 3 * D * s["F"])
    return _mix_params(s, kind) + ffn + 2 * D + 2 * _hc_params(s)


def param_count(cfg: dict) -> int:
    s = _sizes(cfg)
    return 2 * s["V"] * s["D"] + s["D"] + sum(
        _layer_params(s, kind, i < s["dense"])
        for i, kind in enumerate(s["types"]))


def selected_pairs(seq: int, pool: int, topk: int) -> int:
    """``sum_t |S_t|`` of one sequence: the tail block up to ``t`` and
    ``min(topk / pool - 1, t // pool)`` whole earlier blocks a query."""
    t = np.arange(seq, dtype=np.int64)
    return int(np.sum(t % pool + 1 + pool * np.minimum(topk // pool - 1,
                                                       t // pool)))


def _indexer_blocks(seq: int, pool: int) -> int:
    """(query, earlier pooled block) pairs the indexer scores."""
    return int(np.sum(np.arange(seq, dtype=np.int64) // pool))


def train_flops_per_sample(cfg: dict, seq: int) -> float:
    """Operations the forward and backward passes of this SHARE require for
    one sequence of ``seq`` tokens (a multiply-add is 2; backward = 2 x
    forward; recomputation not counted): the matrix products of every
    layer's mixer, of the dense MLP or the router, shared expert and the
    routed experts a token is EXPECTED to find held here (``top_k x held /
    router outputs``), the hyper-connections' maps and the mixing of the
    streams; the KDA recurrence's own work (7 operations a state element a
    token); the sparse layer's attention over the SELECTED pairs in the
    absorbed form (scores and values over the latent: 4 x heads x
    kv_lora_rank a pair forward) and its absorption and value maps; the
    indexer forward only (it gets no gradient): its projections and its
    scores of every earlier pooled block; the head over the held slice."""
    s = _sizes(cfg)
    D, n = s["D"], s["n"]
    HK = s["Hk"] * s["K"]
    kda = 2 * (4 * D * HK + 2 * (D * s["r"] + s["r"] * HK) + D * s["Hk"]
               + 3 * s["W"] * HK) + 7 * s["Hk"] * s["K"] * s["K"]
    dsa = 2 * (D * s["qr"] + s["qr"] * s["H"] * s["dk"] + D * s["kvr"]
               + s["kvr"] * s["H"] * (s["dk"] + s["dv"])
               + s["H"] * s["dv"] * D)
    routed = s["k"] * len(s["held"]) / s["E"]
    moe = 2 * (D * s["E"] + 3 * D * s["Fs"] + routed * 3 * D * s["F"])
    mlp = 2 * 3 * D * s["Fd"]
    hc = 2 * (n * D * (2 * n + n * n) + n * D + n * n * D + n * D)
    per_token = sum((kda if kind == "kda" else dsa)
                    + (mlp if i < s["dense"] else moe) + 2 * hc
                    for i, kind in enumerate(s["types"])) + 2 * D * s["V"]
    n_dsa = s["types"].count("dsa")
    attention = n_dsa * 4 * s["H"] * s["kvr"] * selected_pairs(
        seq, s["pool"], s["topk"])
    indexer = n_dsa * (2 * seq * (s["qr"] * s["J"] * s["dI"] + D * s["dI"]
                                  + D * s["J"])
                       + 2 * s["J"] * (s["dI"] + 1)
                       * _indexer_blocks(seq, s["pool"]))
    return 3.0 * (seq * per_token + attention) + indexer


#: the chunk length and sub-block of the delta rule's yardstick: the pinned
#: values of ``families/hybrid_lm.py`` (ROOFLINE_CHUNK / ROOFLINE_SUB), so
#: this model's KDA layers are measured against the same work
ROOFLINE_CHUNK = 32
ROOFLINE_SUB = 8


def delta_rule_cost(cfg: dict, seq: int) -> tuple[float, float]:
    """``(operations, bytes)`` of the chunkwise delta rule for one sequence
    through all the KDA layers of a rematerialised train step:
    ``families/hybrid_lm.py::delta_rule_cost``'s formula, word for word, at
    this model's heads and layers."""
    s = _sizes(cfg)
    layers = s["types"].count("kda")
    K, C, c = s["K"], ROOFLINE_CHUNK, ROOFLINE_SUB
    fwd = (4 * C * c * K + 2 * K * (C * C - C * c) + 2 * C ** 3 / 3
           + 4 * C * C * K + 6 * C * K * K + 2 * C * C * K)
    ops = 4 * fwd * (seq // C) * s["Hk"] * layers
    io = 3 * K * 2 + K * 4 + 4            # q k v, g, beta of a position
    state = K * K * 4 / C                 # a float32 state a chunk
    fwd_bytes = io + K * 2 + state
    bwd_bytes = io + K * 2 + state + io
    return ops, (2 * fwd_bytes + bwd_bytes) * seq * s["Hk"] * layers


#: products of one SELECTED (query, key) pair a head in a sparse layer's
#: train step, the roofline's yardstick, PINNED here and not read from the
#: program — ``families/mla_moe_lm.py``'s count (the scores forward, the
#: scores again, dq and dk backward: 4; p v forward, dp and dv backward: 3;
#: none recomputed) in the absorbed form, where both run over the latent's
#: ``kv_lora_rank``.  The pairs are the selection's, whatever the kernel
#: multiplies.  A multiply-add is 2 operations.
QK_PRODUCTS, V_PRODUCTS = 4, 3


def dsa_attention_cost(cfg: dict, seq: int) -> tuple[float, float]:
    """``(operations, bytes)`` of the attention over the selection in every
    sparse layer for one sequence through a train step: 2 x
    (:data:`QK_PRODUCTS` + :data:`V_PRODUCTS`) x kv_lora_rank operations a
    selected pair a head.  Bytes, the least any implementation moves: the
    absorbed queries and the latent read and the output written forward
    (bfloat16) with a float32 log-sum-exp; backward the queries, the
    latent, the output, its cotangent and the log-sum-exp read, the
    queries' and the latent's gradients written."""
    s = _sizes(cfg)
    calls = s["types"].count("dsa")
    R, H = s["kvr"], s["H"]
    ops = 2 * (QK_PRODUCTS + V_PRODUCTS) * R * H \
        * selected_pairs(seq, s["pool"], s["topk"]) * calls
    q_like, c_like, lse = seq * H * R * 2, seq * R * 2, seq * H * 4
    fwd = 2 * q_like + c_like + lse                # q c; out
    bwd = 4 * q_like + 2 * c_like + lse            # q out dout dq; c dc
    return float(ops), float((fwd + bwd) * calls)


def to_reference(params):
    """The system's tree in the reference's layout and names (a
    ``reference/mhc_dsa_lm.py`` :class:`Params` with the share and sizes of
    the model last built).  The leaves SHARE the system's buffers: drop the
    system's tree before handing this one to a reference that donates it."""
    from harness import load_module
    f32 = lambda a: jnp.asarray(a, jnp.float32)              # noqa: E731

    def layer(blk):
        out = {k: f32(v) for k, v in blk.items() if not isinstance(v, dict)}
        for ours, theirs in (("ln1", "ln_1"), ("ln2", "ln_2"),
                             ("q_norm", "q_norm"), ("kv_norm", "kv_norm"),
                             ("o_norm", "o_norm")):
            if ours in blk:
                out[theirs] = f32(blk[ours]["scale"])
        if "a_log" in blk:
            D = blk["wq"].shape[0]
            for name in ("wq", "wk", "wv"):
                out[name] = out[name].reshape(D, -1)
        if "idx_k_norm" in blk:
            out["idx_k_scale"] = f32(blk["idx_k_norm"]["scale"])
            out["idx_k_bias"] = f32(blk["idx_k_norm"]["bias"])
        for name in ("hc_mix", "hc_ffn"):
            out[name] = {k: f32(v) for k, v in blk[name].items()}
        return out

    depth = sum(1 for k in params if k.startswith("layer"))
    tree = {"embed": f32(params["embed"]), "head": f32(params["head"]),
            "ln_f": f32(params["out_norm"]["scale"]),
            "layers": [layer(params[f"layer{i}"]) for i in range(depth)]}
    return load_module("reference", "mhc_dsa_lm").Params(tree, *_static)
