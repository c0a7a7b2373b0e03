"""The ``gated_swa_moe_lm`` family: the repo's pattern LM
(``distlearn_tpu.models.hybrid.hybrid_lm``) built as a decoder of
grouped-query softmax layers with a sigmoid gate a head — full-causal layers
rotated over the first part of a head at YaRN's frequencies, among
sliding-window layers rotated over the whole head, each kind with query heads
of its own over the same K/V heads — whose first layers (``mlp_only_layers``)
have a dense SwiGLU MLP and the rest a routed mixture of SwiGLU experts
beside a shared expert, scored by a softmax, renormalised and scaled; from a
configuration file in the source's key names, its weights made on the device
from the seed, its parameter tree renamed into the plain reference's layout,
the analytic count of the operations one chip's SHARE of the model requires,
and the operations and bytes of windowed attention for its roofline.

The configuration's ``num_experts`` counts the experts HELD here
(``held_experts`` names them); the router keeps ``n_router_outputs``, the
published count.  ``vocab_size`` is the slice of the vocabulary held here.
``layer_types``, ``mlp_layer_types``, ``gating_types`` and
``num_attention_heads_per_layer`` are the source's lists, whole; the entries
below ``num_hidden_layers`` name a layer here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from distlearn_tpu.models.hybrid import hybrid_lm
from distlearn_tpu.models.transformer import Yarn

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, None: None}
#: the source's layer names, as ``hybrid_lm`` calls them
_KINDS = {"full_attention": "full", "sliding_attention": "window"}

#: the share and the sizes of the model last built that its arrays do not
#: show (the reference's :class:`Params` carries them, and ``to_reference``
#: is handed nothing but the parameter tree)
_static: tuple | None = None


def _sizes(cfg: dict) -> dict:
    held = list(cfg["held_experts"])
    if len(held) != cfg["num_experts"]:
        raise ValueError("num_experts counts the experts held here: "
                         f"{cfg['num_experts']} != {len(held)} held")
    depth = cfg["num_hidden_layers"]
    names = list(cfg["layer_types"][:depth])
    heads = list(cfg["num_attention_heads_per_layer"][:depth])
    if len(names) != depth or len(heads) != depth \
            or not set(names) <= set(_KINDS):
        raise ValueError(f"the first {depth} layers need a kind of "
                         f"{tuple(_KINDS)} and a head count each")
    dense = list(cfg["mlp_only_layers"])
    if dense != list(range(len(dense))) or list(
            cfg["mlp_layer_types"][:depth]) != [
                "dense" if i in dense else "sparse" for i in range(depth)]:
        raise ValueError("the family builds leading dense layers "
                         "(mlp_only_layers and mlp_layer_types agree)")
    rope = cfg["rope_parameters"]
    full, slide = rope["full_attention"], rope["sliding_attention"]
    if cfg["gating"] != "per-head" \
            or set(cfg["gating_types"][:depth]) != {"per_head"} \
            or full["rope_type"] != "yarn" \
            or slide["rope_type"] != "default" \
            or slide["partial_rotary_factor"] != 1 \
            or not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["attention_bias"] or cfg["decoder_sparse_step"] != 1 \
            or cfg["moe_router_logit_softcapping"] \
            or cfg["moe_apply_router_weight_on_input"] \
            or cfg["shared_expert_intermediate_size"] \
            % cfg["moe_intermediate_size"]:
        raise ValueError(
            "the family builds a sigmoid gate a head on every layer, YaRN "
            "on the full layers and default angles over the whole head on "
            "the sliding ones, softmax router scores renormalised over the "
            "top-k and applied to the experts' outputs, no soft cap, a "
            "mixture in every layer after the dense ones, shared experts as "
            "wide as the routed ones, an untied head and no attention bias")
    return {
        "depth": depth, "types": [_KINDS[n] for n in names], "heads": heads,
        "dense": len(dense), "D": cfg["hidden_size"],
        "Hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "window": cfg["sliding_window"], "theta": slide["rope_theta"],
        "full_theta": full["rope_theta"],
        "fraction": full["partial_rotary_factor"],
        "yarn": Yarn(*(full[k] for k in Yarn._fields)),
        "Fd": cfg["intermediate_size"], "F": cfg["moe_intermediate_size"],
        "shared": cfg["shared_expert_intermediate_size"]
        // cfg["moe_intermediate_size"],
        "E": cfg["n_router_outputs"], "held": held,
        "k": cfg["num_experts_per_tok"],
        "scale": cfg["moe_routed_scaling_factor"], "V": cfg["vocab_size"]}


def build(cfg: dict, *, max_len: int | None = None, compute_dtype=None,
          scan_blocks: bool = False, remat=False):
    """The model through the repo's constructor, at the configuration's
    sizes.  The layers differ in kind and in heads, so nothing is scanned."""
    global _static
    if scan_blocks:
        raise ValueError("the layers differ in kind: scan_blocks must be "
                         "false")
    s = _sizes(cfg)
    _static = (tuple(s["held"]), s["k"], s["scale"],
               tuple(t == "window" for t in s["types"]), s["window"],
               s["theta"], s["full_theta"], s["fraction"], tuple(s["yarn"]))
    return hybrid_lm(
        vocab=s["V"], dim=s["D"], layer_types=s["types"], heads=s["heads"],
        kv_heads=s["Hkv"], head_dim=s["hd"], window=s["window"],
        rope_theta=s["theta"], attn_gate="head",
        full_rope_theta=s["full_theta"], full_rope_fraction=s["fraction"],
        full_rope_yarn=s["yarn"]._asdict(), dense_layers=s["dense"],
        dense_width=s["Fd"], n_routed_experts=s["E"],
        held_experts=s["held"], experts_per_tok=s["k"], expert_width=s["F"],
        n_shared_experts=s["shared"], routed_scale=s["scale"],
        eps=cfg["rms_norm_eps"],
        max_len=max_len or cfg["max_position_embeddings"],
        compute_dtype=_DTYPES[compute_dtype], remat=remat)


def init_params(model, key, sharding=None):
    """The whole tree in ONE jitted call on the device, float32: the
    constructor's own draw, with the embedding rows at UNIT scale (its rows,
    drawn N(0, 1/dim), times sqrt(dim): N(0, 1), the usual default of an
    embedding table), as the other pattern families draw them: at 1/dim
    the residual stream of a
    random-weight model is all branch output, whose common component every
    token shares, so every router picks the same few experts."""
    def make(k):
        params = model.init(k)[0]
        rows = params["embed"]
        return dict(params, embed=rows * math.sqrt(rows.shape[1]))
    return jax.jit(make, out_shardings=sharding)(key)


def _attn_products(s: dict, heads: int) -> int:
    """Parameters of a layer's attention projections (a multiply-add a token
    each): q, o and the gate over the layer's query heads, k and v over the
    K/V heads."""
    return 2 * s["D"] * heads * s["hd"] + s["D"] * heads \
        + 2 * s["D"] * s["Hkv"] * s["hd"]


def _ffn_products(s: dict, dense: bool, routed: float) -> float:
    """What every token multiplies in after attention: a dense layer's MLP,
    or a mixture layer's router, shared expert and ``routed`` experts."""
    if dense:
        return 3 * s["D"] * s["Fd"]
    return s["D"] * s["E"] + (s["shared"] + routed) * 3 * s["D"] * s["F"]


def param_count(cfg: dict) -> int:
    s = _sizes(cfg)
    total = 2 * s["V"] * s["D"] + s["D"]
    for i, h in enumerate(s["heads"]):
        total += _attn_products(s, h) + 2 * s["D"] + int(_ffn_products(
            s, i < s["dense"], len(s["held"])))
    return total


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
    forward; recomputation not counted): each layer's attention projections
    and gate over its OWN query heads, the dense MLP or the router, shared
    expert and the routed experts a token is EXPECTED to find held here
    (``top_k x held / router outputs``), the two attention products over the
    pairs a layer attends (the band in a sliding layer, the triangle in a
    full one) at its own heads, and the head over the held slice."""
    s = _sizes(cfg)
    routed = s["k"] * len(s["held"]) / s["E"]
    per_token, pairs = 2 * s["D"] * s["V"], 0
    for i, (kind, h) in enumerate(zip(s["types"], s["heads"])):
        per_token += 2 * (_attn_products(s, h)
                          + _ffn_products(s, i < s["dense"], routed))
        pairs += attended_pairs(seq, s["window"] if kind == "window"
                                else None) * h
    return 3.0 * (seq * per_token + 4 * pairs * s["hd"])


#: products of one (query, key) pair a head-size element in a windowed
#: layer's train step, the roofline's yardstick, PINNED here and not read
#: from the program: two forward (q k^T, p v), five backward (the scores
#: again, dp, dv, dq, dk), none recomputed — a rematerialised block keeps the
#: kernel's output and log-sum-exp.  A multiply-add is 2 operations.
ROOFLINE_PRODUCTS = 7


def window_attention_cost(cfg: dict, seq: int) -> tuple[float, float]:
    """``(operations, bytes)`` of the blockwise attention of ALL the sliding
    layers for one sequence through a train step, each at its own query
    heads: 2 x :data:`ROOFLINE_PRODUCTS` operations a pair a query head a
    head-size element.  Bytes: q, k, v read and the output written forward
    (bfloat16) with the float32 log-sum-exp; backward q, k, v, the output,
    its cotangent and the log-sum-exp read, dq, dk, dv written — K and V
    counted once a K/V head (the grouped-query call reads them once for the
    group)."""
    s = _sizes(cfg)
    ops = nbytes = 0
    for kind, h in zip(s["types"], s["heads"]):
        if kind != "window":
            continue
        ops += 2 * ROOFLINE_PRODUCTS * attended_pairs(seq, s["window"]) \
            * h * s["hd"]
        q_like, kv_like = seq * h * s["hd"] * 2, seq * s["Hkv"] * s["hd"] * 2
        lse = seq * h * 4
        nbytes += (2 * q_like + 2 * kv_like + lse) \
            + (4 * q_like + 4 * kv_like + lse)
    return float(ops), float(nbytes)


def to_reference(params):
    """The system's tree in the reference's layout and names (a
    ``reference/gated_swa_moe_lm.py`` :class:`Params` with the share and
    sizes of the model last built).  The leaves SHARE the system's buffers:
    drop the system's tree before handing this one to a reference that
    donates it."""
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
    return load_module("reference", "gated_swa_moe_lm").Params(tree, *_static)
