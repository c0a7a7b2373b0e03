"""The ``mla_moe_lm`` family: the repo's pattern LM
(``distlearn_tpu.models.hybrid.hybrid_lm``) built as a decoder of
latent-attention (MLA) layers — low-rank q and K/V paths, scores over a
``qk_nope_head_dim + qk_rope_head_dim`` head, values over ``v_head_dim`` —
whose first ``first_k_dense_replace`` layers have a dense SwiGLU MLP and the
rest a routed mixture of SwiGLU experts beside a shared expert, scored by
sigmoid and chosen through a correction bias, with a multi-token-prediction
module after the stack; from a configuration file in the source's key names,
its weights made on the device from the seed, its parameter tree renamed into
the plain reference's layout, the analytic count of the operations one
chip's SHARE of the model requires, and the operations and bytes of its
attention for the roofline.

The configuration's ``n_routed_experts`` counts the experts HELD here
(``held_experts`` names them); the router keeps ``n_router_outputs``, the
published count.  ``vocab_size`` is the slice of the vocabulary held here.
``assumed.mtp_loss_weight`` is the weight of the module's loss.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from distlearn_tpu.models.hybrid import hybrid_lm

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, None: None}

#: the share and the sizes of the model last built that its arrays do not
#: show (the reference's :class:`Params` carries them, and ``to_reference``
#: is handed nothing but the parameter tree)
_static: tuple | None = None

#: the standard deviation of the seeded correction bias.  A random-weight
#: router's sigmoid scores spread 0.21 across the 256 experts and lie 0.012
#: apart near the eighth: at 0.01 the bias changes 0.7-0.8 of a token's eight
#: choices (six tokens in ten keep fewer than all eight) and leaves an
#: expert's load within 0.6-1.5 x the mean; at 0.05, tried first, it changed
#: three of the eight, starved some experts and sent others five times their
#: share (a held expert 3 to 2,557 rows of 512 expected), and the step's
#: length became a draw of the seed (PERF.md section 6, PR 36)
BIAS_STD = 0.01


def _sizes(cfg: dict) -> dict:
    held = list(cfg["held_experts"])
    if len(held) != cfg["n_routed_experts"]:
        raise ValueError(
            "n_routed_experts counts the experts held here: "
            f"{cfg['n_routed_experts']} != {len(held)} held")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError(
            "group-limited choice of experts is not built: n_group and "
            f"topk_group must be 1, got {cfg['n_group']} and "
            f"{cfg['topk_group']}")
    if (cfg["scoring_func"], cfg["topk_method"]) != ("sigmoid", "noaux_tc") \
            or not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["rope_scaling"] or not cfg["rope_interleave"] \
            or cfg["hidden_act"] != "silu" or cfg["attention_bias"] \
            or cfg["moe_layer_freq"] != 1 \
            or cfg["num_nextn_predict_layers"] != 1:
        raise ValueError(
            "the family builds sigmoid scores chosen through a correction "
            "bias (noaux_tc) and renormalised over the top-k, SwiGLU, an "
            "untied head, unscaled interleaved rotary angles, no attention "
            "bias, a mixture in every layer after the dense ones and one "
            "prediction module")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("latent attention has as many K/V heads as heads")
    return {
        "depth": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"],
        "D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "qr": cfg["q_lora_rank"], "kvr": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "theta": cfg["rope_theta"],
        "Fd": cfg["intermediate_size"], "F": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"], "E": cfg["n_router_outputs"],
        "held": held, "k": cfg["num_experts_per_tok"],
        "scale": cfg["routed_scaling_factor"], "V": cfg["vocab_size"],
        "weight": cfg["assumed"]["mtp_loss_weight"]["value"]}


def build(cfg: dict, *, max_len: int | None = None, compute_dtype=None,
          scan_blocks: bool = False, remat=False):
    """The model through the repo's constructor, at the configuration's
    sizes.  The first layer differs from the rest, so nothing is scanned."""
    global _static
    if scan_blocks:
        raise ValueError("the layers differ in kind: scan_blocks must be "
                         "false")
    s = _sizes(cfg)
    _static = (tuple(s["held"]), s["k"], s["scale"], s["nope"], s["theta"],
               s["weight"])
    return hybrid_lm(
        vocab=s["V"], dim=s["D"], layer_types=["mla"] * s["depth"],
        heads=s["H"], kv_heads=s["H"], head_dim=s["nope"] + s["rope"],
        q_lora_rank=s["qr"], kv_lora_rank=s["kvr"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
        v_head_dim=s["dv"], rope_theta=s["theta"],
        rope_pairing="interleaved", dense_layers=s["dense"],
        dense_width=s["Fd"], n_routed_experts=s["E"],
        held_experts=s["held"], experts_per_tok=s["k"], expert_width=s["F"],
        n_shared_experts=s["shared"], router_score="sigmoid",
        routed_scale=s["scale"], mtp_depth=1, mtp_weight=s["weight"],
        eps=cfg["rms_norm_eps"],
        max_len=max_len or cfg["max_position_embeddings"],
        compute_dtype=_DTYPES[compute_dtype], remat=remat)


def init_params(model, key, sharding=None):
    """The whole tree in ONE jitted call on the device, float32: the
    constructor's own draw, with two changes.  The embedding rows at UNIT
    scale (its rows, drawn N(0, 1/dim), times sqrt(dim): N(0, 1), the usual
    default of an embedding table) — PR 34's lesson, found again here: at
    1/dim the residual stream of a random-weight model is all branch output,
    whose common component every token shares, so every token's router picks
    the same few experts (a held expert got 0 to 3,667 of 16,384 x 8 / 256 =
    512 expected rows, 38-68 % of the tokens found none of their eight held,
    PERF.md section 6, PR 36), which no trained model does, and the step's
    length becomes a draw of the seed; at unit scale a router sees mostly
    the token's own row.  And every router's correction bias (zeros in the
    constructor, as a model starts its training) drawn N(0,
    :data:`BIAS_STD`) from the seed: the bias a trained checkpoint carries
    is not zero, and a zero bias would leave the choice through it
    untested."""
    def make(k):
        params = model.init(k)[0]
        bias_key = jax.random.fold_in(k, 0xB1A5)

        def biased(blk, n):
            if "router_bias" not in blk:            # a dense layer
                return blk
            b = blk["router_bias"]
            return dict(blk, router_bias=BIAS_STD * jax.random.normal(
                jax.random.fold_in(bias_key, n), b.shape, b.dtype))
        depth = sum(name.startswith("layer") for name in params)
        rows = params["embed"]
        out = dict(params, embed=rows * math.sqrt(rows.shape[1]), mtp=dict(
            params["mtp"], block=biased(params["mtp"]["block"], depth)))
        for n in range(depth):
            out[f"layer{n}"] = biased(params[f"layer{n}"], n)
        return out
    return jax.jit(make, out_shardings=sharding)(key)


def _layer_products(s: dict) -> tuple[int, int, int]:
    """Parameters every token multiplies in, a multiply-add each: a layer's
    attention projections, a dense layer's MLP, a mixture layer's router
    and shared expert."""
    attn = s["D"] * s["qr"] + s["qr"] * s["H"] * (s["nope"] + s["rope"]) \
        + s["D"] * (s["kvr"] + s["rope"]) \
        + s["kvr"] * s["H"] * (s["nope"] + s["dv"]) + s["H"] * s["dv"] * s["D"]
    return (attn, 3 * s["D"] * s["Fd"],
            s["D"] * s["E"] + s["shared"] * 3 * s["D"] * s["F"])


def param_count(cfg: dict) -> int:
    s = _sizes(cfg)
    attn, mlp, mix = _layer_products(s)
    norms = s["qr"] + s["kvr"] + 2 * s["D"]
    dense = attn + norms + mlp
    mixture = attn + norms + mix + s["E"] \
        + len(s["held"]) * 3 * s["D"] * s["F"]
    module = 2 * s["D"] + 2 * s["D"] * s["D"] + mixture + s["D"]
    return 2 * s["V"] * s["D"] + s["D"] + s["dense"] * dense \
        + (s["depth"] - s["dense"]) * mixture + module


def attended_pairs(seq: int) -> int:
    """(query, key) pairs of a causal layer over ``seq`` positions: the
    triangle, diagonal included."""
    return seq * (seq + 1) // 2


def train_flops_per_sample(cfg: dict, seq: int) -> float:
    """Operations the forward and backward passes of this SHARE require for
    one sequence of ``seq`` tokens (a multiply-add is 2; backward = 2 x
    forward; recomputation not counted): a layer's latent projections, the
    dense MLP or the router, shared expert and the routed experts a token is
    EXPECTED to find held here (``top_k x held / router outputs``), the two
    attention products over the triangle (q k^T over the 192 of the scores,
    p v over the 128 of the values); the module's projection and block; the
    head over the held slice, twice (the main logits and the module's)."""
    s = _sizes(cfg)
    attn, mlp, mix = _layer_products(s)
    routed = s["k"] * len(s["held"]) / s["E"] * 3 * s["D"] * s["F"]
    mixture = attn + mix + routed
    per_token = 2 * (s["dense"] * (attn + mlp)
                     + (s["depth"] - s["dense"] + 1) * mixture
                     + 2 * s["D"] * s["D"] + 2 * s["D"] * s["V"])
    pair = 2 * (s["nope"] + s["rope"] + s["dv"]) * s["H"]
    return 3.0 * (seq * per_token
                  + (s["depth"] + 1) * attended_pairs(seq) * pair)


#: products of one (query, key) pair a head in a latent-attention layer's
#: train step, the roofline's yardstick, PINNED here and not read from the
#: program: over the ``qk`` of the scores q k^T forward, and the scores
#: again, dq and dk backward (4); over the ``dv`` of the values p v forward,
#: dp and dv backward (3); none recomputed — a rematerialised block keeps the
#: kernel's output and log-sum-exp (PR 30).  It counts the MODEL's head
#: sizes, whatever the kernel pads them to.  A multiply-add is 2 operations.
QK_PRODUCTS, V_PRODUCTS = 4, 3


def mla_attention_cost(cfg: dict, seq: int) -> tuple[float, float]:
    """``(operations, bytes)`` of the blockwise attention of ALL the
    latent-attention calls (every layer's and the module's) for one sequence
    through a train step: 2 x (:data:`QK_PRODUCTS` x qk + :data:`V_PRODUCTS`
    x dv) operations a pair a head.  Bytes: q, k, v read and the output
    written forward (bfloat16) with the float32 log-sum-exp; backward q, k,
    v, the output, its cotangent and the log-sum-exp read, dq, dk, dv
    written — k with its shared rotated part broadcast to every head, as the
    kernel is handed it."""
    s = _sizes(cfg)
    calls = s["depth"] + 1
    qk, dv = s["nope"] + s["rope"], s["dv"]
    ops = 2 * (QK_PRODUCTS * qk + V_PRODUCTS * dv) * attended_pairs(seq) \
        * s["H"] * calls
    qk_like, v_like = seq * s["H"] * qk * 2, seq * s["H"] * dv * 2
    lse = seq * s["H"] * 4
    fwd = 2 * qk_like + 2 * v_like + lse           # q k; v out
    bwd = 4 * qk_like + 4 * v_like + lse           # q k dq dk; v out dout dv
    return float(ops), float((fwd + bwd) * calls)


def to_reference(params):
    """The system's tree in the reference's layout and names (a
    ``reference/mla_moe_lm.py`` :class:`Params` with the share and sizes of
    the model last built).  The leaves SHARE the system's buffers: drop the
    system's tree before handing this one to a reference that donates it."""
    from harness import load_module
    f32 = lambda a: jnp.asarray(a, jnp.float32)              # noqa: E731

    def layer(blk):
        out = {k: f32(v) for k, v in blk.items() if not isinstance(v, dict)}
        for ours, theirs in (("ln1", "ln_1"), ("ln2", "ln_2"),
                             ("q_norm", "q_norm"), ("kv_norm", "kv_norm")):
            out[theirs] = f32(blk[ours]["scale"])
        return out

    depth = sum(1 for k in params if k.startswith("layer"))
    mtp = params["mtp"]
    tree = {"embed": f32(params["embed"]), "head": f32(params["head"]),
            "ln_f": f32(params["out_norm"]["scale"]),
            "layers": [layer(params[f"layer{i}"]) for i in range(depth)],
            "mtp": {"enorm": f32(mtp["enorm"]["scale"]),
                    "hnorm": f32(mtp["hnorm"]["scale"]),
                    "eh_proj": f32(mtp["eh_proj"]),
                    "block": layer(mtp["block"]),
                    "norm": f32(mtp["norm"]["scale"])}}
    return load_module("reference", "mla_moe_lm").Params(tree, *_static)
