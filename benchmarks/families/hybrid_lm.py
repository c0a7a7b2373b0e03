"""The ``hybrid_lm`` family: the repo's pattern LM
(``distlearn_tpu.models.hybrid.hybrid_lm``: softmax-GQA layers among gated
delta-rule layers, a routed mixture of experts beside a shared one in every
layer) built from a configuration file in the source's key names, its weights
made on the device from the seed, its parameter tree renamed into the plain
reference's layout, the analytic count of the operations one chip's SHARE
of the model requires, and the operations and bytes of the chunked delta
rule for its roofline.

The configuration's ``n_routed_experts`` counts the experts HELD here
(``held_experts`` names them); the router keeps ``n_router_outputs``, the
published count.  ``vocab_size`` is the slice of the vocabulary held here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distlearn_tpu.models.hybrid import hybrid_lm

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, None: None}

#: the share (held experts, experts a token) of the model last built: the
#: reference's :class:`Params` carries it, and ``to_reference`` is handed
#: nothing but the parameter tree
_share: tuple | None = None


def _sizes(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    held = list(cfg["held_experts"])
    if len(held) != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here: "
                         f"{cfg['n_routed_experts']} != {len(held)} held")
    depth = cfg["num_hidden_layers"]
    return {
        "depth": depth,
        "types": ["gqa" if i in cfg["gqa_layers"] else "kda"
                  for i in range(depth)],
        "D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "Hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "Hk": lin["num_heads"], "K": lin["head_dim"],
        "W": lin["short_conv_kernel_size"], "r": cfg["kda_gate_rank"],
        "E": cfg["n_router_outputs"], "held": held,
        "k": cfg["num_experts_per_tok"], "F": cfg["moe_intermediate_size"],
        "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "V": cfg["vocab_size"]}


def build(cfg: dict, *, max_len: int | None = None, compute_dtype=None,
          scan_blocks: bool = False, remat=False):
    """The model through the repo's constructor, at the configuration's
    sizes.  The layers differ in kind, so there is nothing to scan."""
    global _share
    if scan_blocks:
        raise ValueError("the hybrid LM's layers differ in kind: "
                         "scan_blocks must be false")
    s = _sizes(cfg)
    _share = (tuple(s["held"]), s["k"])
    return hybrid_lm(
        vocab=s["V"], dim=s["D"], layer_types=s["types"], heads=s["H"],
        kv_heads=s["Hkv"], head_dim=s["hd"], kda_heads=s["Hk"],
        kda_head_dim=s["K"], conv_kernel=s["W"], kda_rank=s["r"],
        n_routed_experts=s["E"], held_experts=s["held"],
        experts_per_tok=s["k"], expert_width=s["F"],
        n_shared_experts=cfg["n_shared_experts"], eps=cfg["rms_norm_eps"],
        max_len=max_len or cfg["max_position_embeddings"],
        compute_dtype=_DTYPES[compute_dtype], remat=remat)


def init_params(model, key, sharding=None):
    """The whole tree in ONE jitted call on the device, float32."""
    return jax.jit(lambda k: model.init(k)[0], out_shardings=sharding)(key)


def _mix_products(s: dict, kind: str) -> int:
    """Parameters of a mixer's matrix products (a multiply-add a token
    each): q, gate, o and k, v of a softmax layer; q, k, v, o, the two
    low-rank gates and beta of a linear-attention one."""
    D, HK = s["D"], s["Hk"] * s["K"]
    if kind == "gqa":
        return 3 * D * s["H"] * s["hd"] + 2 * D * s["Hkv"] * s["hd"]
    return 4 * D * HK + 2 * (D * s["r"] + s["r"] * HK) + D * s["Hk"]


def _layer_params(s: dict, kind: str) -> int:
    D, HK = s["D"], s["Hk"] * s["K"]
    mix = _mix_products(s, kind)
    if kind == "kda":       # conv taps, dt_bias, a_log, the head norm
        mix += 3 * s["W"] * HK + HK + s["Hk"] + s["K"]
    moe = D * s["E"] + 3 * D * s["Fs"] + len(s["held"]) * 3 * D * s["F"]
    return mix + moe + 2 * D


def param_count(cfg: dict) -> int:
    s = _sizes(cfg)
    return 2 * s["V"] * s["D"] + s["D"] \
        + sum(_layer_params(s, kind) for kind in s["types"])


def train_flops_per_sample(cfg: dict, seq: int) -> float:
    """Operations the forward and backward passes of this SHARE require for
    one sequence of ``seq`` tokens (a multiply-add is 2; backward = 2 x
    forward; recomputation not counted): the matrix products outside the
    experts, the shared expert, the routed experts a token is EXPECTED to
    find held here (``top_k x held / router outputs``), the causal
    half-square of the softmax layers, for the linear-attention layers the
    recurrence's own work (decay, read, rank-1 write, query: 7 operations a
    state element a token), and the head over the held slice."""
    s = _sizes(cfg)
    D, HK = s["D"], s["Hk"] * s["K"]
    gqa = 2 * _mix_products(s, "gqa")
    kda = 2 * (_mix_products(s, "kda") + 3 * s["W"] * HK) \
        + 7 * s["Hk"] * s["K"] * s["K"]
    routed = s["k"] * len(s["held"]) / s["E"]
    moe = 2 * (D * s["E"] + 3 * D * s["Fs"] + routed * 3 * D * s["F"])
    n_gqa = s["types"].count("gqa")
    per_token = n_gqa * gqa + (s["depth"] - n_gqa) * kda \
        + s["depth"] * moe + 2 * D * s["V"]
    attention = n_gqa * 2 * seq * seq * s["H"] * s["hd"]
    return 3.0 * (seq * per_token + attention)


#: the chunk length and sub-block of the roofline's yardstick: those the
#: program shipped with in PR 29, PINNED here and not read from the program,
#: so a program that later changes its chunk length is measured against the
#: same work and a change of these two is a visible change of the metric
ROOFLINE_CHUNK = 32
ROOFLINE_SUB = 8


def delta_rule_cost(cfg: dict, seq: int) -> tuple[float, float]:
    """``(operations, bytes)`` of the CHUNKWISE delta rule (the algorithm of
    ``distlearn_tpu/ops/delta_rule.py`` at :data:`ROOFLINE_CHUNK` /
    :data:`ROOFLINE_SUB`, whatever implements it) for one sequence through
    all the linear-attention layers of a rematerialised train step:
    forward, the same again recomputed, and a backward pass of twice the
    forward's products.

    Operations a chunk a head (C = chunk, c = sub, K = V = head size): pair
    sums inside the sub-blocks 4CcK, between them 2K(C^2 - Cc), the
    triangular inverse 2C^3/3, ``X (beta v)`` and ``X (beta k exp G)``
    2C^2(K + V), the three products with the state 6CKV and ``Aqk U``
    2C^2 V.  Bytes: q, k, v, o in bfloat16 and the log-decay in float32 once
    each way, beta, and ONE float32 state a chunk written forward and read
    back; the backward pass reads all of that and the output's cotangent and
    writes five gradients."""
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


def to_reference(params):
    """The system's tree in the reference's layout and names (a
    ``reference/hybrid_lm.py`` :class:`Params` with the share of the model
    last built).  Leaves that need no reshaping SHARE the system's buffers:
    drop the system's tree before handing this one to a reference that
    donates it."""
    from harness import load_module
    held, top_k = _share
    f32 = lambda a: jnp.asarray(a, jnp.float32)              # noqa: E731

    def layer(blk):
        out = {k: f32(v) for k, v in blk.items() if not isinstance(v, dict)}
        out["ln_1"], out["ln_2"] = (f32(blk[n]["scale"])
                                    for n in ("ln1", "ln2"))
        if "a_log" in blk:
            D = blk["wq"].shape[0]
            for name in ("wq", "wk", "wv"):
                out[name] = out[name].reshape(D, -1)
            out["o_norm"] = f32(blk["o_norm"]["scale"])
        return out

    depth = sum(1 for k in params if k.startswith("layer"))
    tree = {"embed": f32(params["embed"]), "head": f32(params["head"]),
            "ln_f": f32(params["out_norm"]["scale"]),
            "layers": [layer(params[f"layer{i}"]) for i in range(depth)]}
    return load_module("reference", "hybrid_lm").Params(tree, held, top_k)
