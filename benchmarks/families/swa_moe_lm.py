"""The ``swa_moe_lm`` family: the repo's pattern LM
(``distlearn_tpu.models.hybrid.hybrid_lm``) built as a decoder of ungated
grouped-query softmax layers — rotary sliding-window layers among full-causal
layers that have no positional term — each with a routed mixture of
ReLU-gated experts, no shared expert, and a router that reads the layer's
input; from a configuration file in the source's key names, its weights made
on the device from the seed, its parameter tree renamed into the plain
reference's layout, the analytic count of the operations one chip's SHARE of
the model requires, and the operations and bytes of windowed attention for
its roofline.

The configuration's ``moe_num_primary_experts`` counts the experts HELD here
(``held_experts`` names them); the router keeps ``n_router_outputs``, the
published count.  ``vocab_size`` is the slice of the vocabulary held here.
``rope_layout`` and ``sliding_window_layout`` are the source's lists, whole;
the entries below ``num_hidden_layers`` name a layer here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from distlearn_tpu.models.hybrid import hybrid_lm

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, None: None}

#: the share and the pattern of the model last built (the reference's
#: :class:`Params` carries them, and ``to_reference`` is handed nothing but
#: the parameter tree)
_static: tuple | None = None


def _sizes(cfg: dict) -> dict:
    held = list(cfg["held_experts"])
    if len(held) != cfg["moe_num_primary_experts"]:
        raise ValueError(
            "moe_num_primary_experts counts the experts held here: "
            f"{cfg['moe_num_primary_experts']} != {len(held)} held")
    depth = cfg["num_hidden_layers"]
    layout = list(cfg["sliding_window_layout"][:depth])
    if layout != list(cfg["rope_layout"][:depth]) or len(layout) != depth:
        raise ValueError(
            "a layer is rotary AND windowed, or neither: rope_layout and "
            "sliding_window_layout must agree on the first "
            f"{depth} layers")
    if not (cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]
            ) or cfg["tie_word_embeddings"] or cfg["rope_scaling"]:
        raise ValueError("the family builds softmax router scores "
                         "renormalised over the top-k, an untied head and "
                         "unscaled rotary angles")
    return {
        "depth": depth, "layout": layout,
        "types": ["window" if w else "full" for w in layout],
        "D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "Hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "window": cfg["sliding_window_size"], "theta": cfg["rope_theta"],
        "E": cfg["n_router_outputs"], "held": held,
        "k": cfg["moe_num_active_primary_experts"],
        "F": cfg["moe_ffn_hidden_size"], "V": cfg["vocab_size"]}


def build(cfg: dict, *, max_len: int | None = None, compute_dtype=None,
          scan_blocks: bool = False, remat=False):
    """The model through the repo's constructor, at the configuration's
    sizes.  The layers differ in kind, so there is nothing to scan."""
    global _static
    if scan_blocks:
        raise ValueError("the layers differ in kind: scan_blocks must be "
                         "false")
    s = _sizes(cfg)
    _static = (tuple(s["held"]), s["k"], tuple(s["layout"]), s["window"],
               s["theta"])
    return hybrid_lm(
        vocab=s["V"], dim=s["D"], layer_types=s["types"], heads=s["H"],
        kv_heads=s["Hkv"], head_dim=s["hd"], window=s["window"],
        rope_theta=s["theta"], n_routed_experts=s["E"],
        held_experts=s["held"], experts_per_tok=s["k"], expert_width=s["F"],
        n_shared_experts=0, expert_act="relu", router_input="layer_input",
        eps=cfg["rms_norm_eps"],
        max_len=max_len or cfg["max_position_embeddings"],
        compute_dtype=_DTYPES[compute_dtype], remat=remat)


def init_params(model, key, sharding=None):
    """The whole tree in ONE jitted call on the device, float32: the
    constructor's own draw, with the embedding rows at UNIT scale (its rows,
    drawn N(0, 1/dim), times sqrt(dim): N(0, 1), the usual default of an
    embedding table).  With the rows at 1/dim the residual stream of a
    random-weight model is all branch output, whose common component grows
    with depth: the routers, which read that stream un-normed, collapse
    onto a few experts (one held expert got 11,173 of 16,384 rows and
    another none, PERF.md section 6, PR 34), which no trained model does,
    and the step's length becomes a draw of the seed.  At unit scale every
    layer's router sees mostly the token's own row and the load is even."""
    def make(k):
        params = model.init(k)[0]
        rows = params["embed"]
        return dict(params, embed=rows * math.sqrt(rows.shape[1]))
    return jax.jit(make, out_shardings=sharding)(key)


def _attn_products(s: dict) -> int:
    """Parameters of a layer's attention projections (a multiply-add a token
    each): q and o over the query heads, k and v over the K/V heads."""
    return 2 * s["D"] * s["H"] * s["hd"] + 2 * s["D"] * s["Hkv"] * s["hd"]


def param_count(cfg: dict) -> int:
    s = _sizes(cfg)
    layer = _attn_products(s) + s["D"] * s["E"] \
        + len(s["held"]) * 3 * s["D"] * s["F"] + 2 * s["D"]
    return 2 * s["V"] * s["D"] + s["D"] + s["depth"] * layer


def attended_pairs(seq: int, window: int | None) -> int:
    """(query, key) pairs of a causal layer over ``seq`` positions: the
    triangle, diagonal included, or — with a ``window`` shorter than the
    sequence — the band ``i - window < j <= i``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * seq - window * (window - 1) // 2


def train_flops_per_sample(cfg: dict, seq: int) -> float:
    """Operations the forward and backward passes of this SHARE require for
    one sequence of ``seq`` tokens (a multiply-add is 2; backward = 2 x
    forward; recomputation not counted): the attention projections, the
    router, the routed experts a token is EXPECTED to find held here
    (``top_k x held / router outputs``), the two attention products over
    the pairs a layer attends (the band in a windowed layer, the triangle in
    a full one), and the head over the held slice."""
    s = _sizes(cfg)
    routed = s["k"] * len(s["held"]) / s["E"]
    per_token = s["depth"] * 2 * (
        _attn_products(s) + s["D"] * s["E"] + routed * 3 * s["D"] * s["F"]) \
        + 2 * s["D"] * s["V"]
    pairs = sum(attended_pairs(seq, s["window"] if w else None)
                for w in s["layout"])
    return 3.0 * (seq * per_token + 4 * pairs * s["H"] * s["hd"])


#: products of one (query, key) pair a head-size element in a windowed
#: layer's train step, the roofline's yardstick, PINNED here and not read
#: from the program: two forward (q k^T, p v), five backward (the scores
#: again, dp, dv, dq, dk), none recomputed — a rematerialised block keeps the
#: kernel's output and log-sum-exp (PR 30).  A multiply-add is 2 operations.
ROOFLINE_PRODUCTS = 7


def window_attention_cost(cfg: dict, seq: int) -> tuple[float, float]:
    """``(operations, bytes)`` of the blockwise attention of ALL the
    windowed layers for one sequence through a train step: 2 x
    :data:`ROOFLINE_PRODUCTS` operations a pair a query head a head-size
    element.  Bytes: q, k, v read and the output written forward (bfloat16)
    with the float32 log-sum-exp; backward q, k, v, the output, its
    cotangent and the log-sum-exp read, dq, dk, dv written — K and V counted
    once a K/V head (the grouped-query call reads them once for the group)."""
    s = _sizes(cfg)
    layers = sum(s["layout"])
    ops = 2 * ROOFLINE_PRODUCTS * attended_pairs(seq, s["window"]) \
        * s["H"] * s["hd"] * layers
    q_like, kv_like = seq * s["H"] * s["hd"] * 2, seq * s["Hkv"] * s["hd"] * 2
    lse = seq * s["H"] * 4
    fwd = 2 * q_like + 2 * kv_like + lse
    bwd = 4 * q_like + 4 * kv_like + lse
    return float(ops), float((fwd + bwd) * layers)


def to_reference(params):
    """The system's tree in the reference's layout and names (a
    ``reference/swa_moe_lm.py`` :class:`Params` with the share and pattern of
    the model last built).  The leaves SHARE the system's buffers: drop the
    system's tree before handing this one to a reference that donates it."""
    from harness import load_module
    f32 = lambda a: jnp.asarray(a, jnp.float32)              # noqa: E731

    def layer(blk):
        out = {k: f32(v) for k, v in blk.items() if not isinstance(v, dict)}
        out["ln_1"], out["ln_2"] = (f32(blk[n]["scale"])
                                    for n in ("ln1", "ln2"))
        return out

    depth = sum(1 for k in params if k.startswith("layer"))
    tree = {"embed": f32(params["embed"]), "head": f32(params["head"]),
            "ln_f": f32(params["out_norm"]["scale"]),
            "layers": [layer(params[f"layer{i}"]) for i in range(depth)]}
    return load_module("reference", "swa_moe_lm").Params(tree, *_static)
