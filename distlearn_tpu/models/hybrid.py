"""A decoder-only LM whose layers are a PATTERN (:data:`LAYER_TYPES`, one
name a layer): softmax attention with grouped queries — with an output gate
(``"gqa"``), without one over the whole causal triangle (``"full"``), or
without one over a causal band with rotary positions (``"window"``) — latent
attention (``"mla"``: low-rank q and K/V paths, scores over a wider head than
the values), or gated delta-rule linear attention (``"kda"``); and in every
layer a routed mixture of gated-linear-unit experts (SwiGLU or ReGLU), beside
a shared expert or without one — or, in the first ``dense_layers`` layers, a
dense SwiGLU MLP instead.  After the stack, optionally, a multi-token-
prediction module.  The second LM constructor beside
:func:`distlearn_tpu.models.transformer.transformer_lm`; it returns the same
:class:`~distlearn_tpu.models.core.Model` and its ``apply`` takes the same
keywords, so ``lm_loss`` and every LM step builder drive it unchanged.

One layer (pre-norm, residual, no bias; no positional term but the rotation
of a ``"window"`` or ``"mla"`` layer — elsewhere the causal mask, the
convolution and the recurrence carry the order):

    h = x + Mix(rmsnorm(x));        y = h + FFN(rmsnorm(h))
    FFN = MoE, or below ``dense_layers``  (silu(m Wg) * (m Wu)) Wd

``Mix`` of a softmax layer, ``H`` query heads over ``Hkv`` K/V heads:

    q, k, v = x Wq, x Wk, x Wv
    "window":  q, k = rope(q, i), rope(k, i)        (models.transformer.rotary)
    allowed(i, j) = j <= i   and, "window" only,   i - j < window
    a = softmax(q k^T / sqrt(D) + mask) v
    "gqa":  out = (sigmoid(x Wg) * a) Wo            (elementwise gate)
    "full", "window":  out = a Wo

``Mix`` of an ``"mla"`` layer, ``H`` heads, ``u`` the normed input at
position ``i`` (``nope`` + ``rope`` = the head size of the scores, ``dv``
that of the values):

    cq = rmsnorm(u Wqa)                        [q_lora_rank]
    q_h = cq Wqb                               [nope + rope] = [qn_h ; qr_h]
    [ckv ; kr] = u Wkva                        [kv_lora_rank + rope]
    [kn_h ; v_h] = rmsnorm(ckv) Wkvb           [nope + dv] a head
    qr_h, kr = rope(qr_h, i), rope(kr, i)      kr is ONE head, shared by all H
    q_h = [qn_h ; qr_h];   k_h = [kn_h ; kr]
    a_h = softmax(q_h k_h^T / sqrt(nope + rope) + causal) v_h      [dv]
    out = concat_h(a_h) Wo

``Mix`` of a ``"kda"`` layer, per head (``conv`` a causal depthwise
convolution over time):

    q~, k~, v~ = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))
    q = l2norm(q~) / sqrt(K);      k = l2norm(k~)
    g_t = -exp(A_h) softplus((x Wa1) Wa2 + b)       per channel, <= 0
    beta_t = 2 sigmoid(x w_beta)
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    out = (sigmoid((x Wg1) Wg2) * rmsnorm_head(o)) Wo

``MoE`` (:func:`distlearn_tpu.parallel.ep.moe_held_ffn`): a router over all
``n_routed_experts``, top-k renormalised, of which THIS model holds
``held_experts`` and computes their part, plus the shared expert (if the
model has one) on every token.  The router reads the experts' own input
``rmsnorm(h)`` or, with ``router_input="layer_input"``, the layer's input
``x`` as it came in, before the mixer and before any norm.  Its scores
``s`` are a softmax over the experts or, ``router_score="sigmoid"``, each
logit's sigmoid; that router CHOOSES its top-k by ``s + b`` (``b``: the
layer's per-expert correction bias ``router_bias``, which no gradient
reaches) and WEIGHS by ``routed_scale * s_e / (sum over chosen of s +
1e-20)``.

The multi-token-prediction module (``mtp_depth=1``; training only), ``g_i``
the stack's final hidden state at ``i`` AFTER the final norm, ``t`` the
tokens, ``Emb`` and ``Head`` the model's own:

    z_i = Weh [ rmsnorm_e(Emb(t_{i+1})) ; rmsnorm_h(g_i) ]      (2 dim -> dim)
    z'  = Layer(z)                 one more mixture layer, its own weights
    logits2_i = Head(rmsnorm_s(z'_i))              predicts t_{i+2}

whose logits and ``mtp_weight`` ride the returned state to
:func:`distlearn_tpu.models.transformer.lm_loss`.

Arithmetic: parameters in ``dtype`` (float32); the matrix products in
``compute_dtype``; in float32 regardless: the norms' statistics, the softmax
of attention (inside the kernel), the rotary angles and the rotation, the
KDA decay (softplus, exp, cumulative sums), ``beta``, the l2 norms, the triangular inverse and the carried state
(``ops/delta_rule.py``), the router's scores (softmax or sigmoid) and its
choice.
"""

from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax, random

from distlearn_tpu.models.core import Model, checkpoint_block
from distlearn_tpu.models.transformer import (ROPE_PAIRINGS, _norm_init,
                                              _rmsnorm, rotary)
from distlearn_tpu.ops.delta_rule import chunked_delta_rule
from distlearn_tpu.parallel.ep import GATE_ACTS, ROUTER_SCORES, moe_held_ffn
from distlearn_tpu.parallel.sequence import local_attention

PyTree = Any
LAYER_TYPES = ("gqa", "kda", "full", "window", "mla")
#: what the router of a layer may read: the experts' input (the norm after
#: the mixer) or the layer's own input, un-normed, before the mixer
ROUTER_INPUTS = ("ffn_norm", "layer_input")


def _dense(key, shape, fan_in, dtype):
    return random.normal(key, shape, dtype) * (1.0 / math.sqrt(fan_in))


def causal_conv(x: jax.Array, w: jax.Array, axis: int = 1) -> jax.Array:
    """Depthwise causal convolution over time (``axis`` of ``x``):
    ``y_t = sum_j w[j] x_{t-W+1+j}`` with zeros before the start.  ``w[j]``
    broadcasts against ``x`` with the time axis taken out of neither: x
    [B, L, C] with w [W, C], or x [B, H, L, K] with w [W, H, 1, K]."""
    W, L = w.shape[0], x.shape[axis]
    pad = jnp.pad(x, [(W - 1, 0) if a == axis else (0, 0)
                      for a in range(x.ndim)])
    return sum(lax.slice_in_dim(pad, j, j + L, axis=axis) * w[j]
               for j in range(W))


def _l2norm(x):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def gqa_apply(blk: PyTree, x: jax.Array, cd, eps: float,
              window: int | None = None, rope_theta: float | None = None):
    """A softmax layer's mixer with its residual: gated where the layer has
    a ``wg``, q and k rotated where ``rope_theta`` is given, the mask cut to
    a band where ``window`` is.  The attention call carries the inner name
    ``attn_window`` or ``attn_full`` inside the declared ``attn_core``."""
    h = _rmsnorm(blk["ln1"], x, eps)
    with jax.named_scope("attn_proj"):
        q = jnp.einsum("ble,ehd->blhd", h, blk["wq"].astype(cd))
        k = jnp.einsum("ble,ehd->blhd", h, blk["wk"].astype(cd))
        v = jnp.einsum("ble,ehd->blhd", h, blk["wv"].astype(cd))
        if "wg" in blk:
            gate = jnp.einsum("ble,ehd->blhd", h, blk["wg"].astype(cd))
        if rope_theta is not None:
            pos = jnp.arange(x.shape[1])
            q, k = rotary(q, pos, rope_theta), rotary(k, pos, rope_theta)
    with jax.named_scope("attn_core"):
        with jax.named_scope("attn_full" if window is None
                             else "attn_window"):
            att = local_attention(q, k, v, causal=True, window=window)
    with jax.named_scope("attn_proj"):
        if "wg" in blk:
            att = jax.nn.sigmoid(gate.astype(jnp.float32)).astype(cd) * att
        return x + jnp.einsum("blhd,hde->ble", att, blk["wo"].astype(cd))


def mla_apply(blk: PyTree, x: jax.Array, cd, eps: float, rope_theta: float,
              nope: int, pairing: str = "interleaved"):
    """A latent-attention layer's mixer with its residual (the equations at
    the top of the module).  q and K/V go through low-rank paths with a norm
    at the waist; the first ``nope`` dimensions of a head's q and k are
    un-rotated, the rest rotated (``pairing``), and k's rotated part is ONE
    head that all heads share, broadcast to them for the kernel; v has a
    size of its own.  Everything before the attention call carries the inner
    name ``mla_latent`` inside ``attn_proj``; the call itself ``attn_mla``
    inside ``attn_core``."""
    h = _rmsnorm(blk["ln1"], x, eps)
    rank = blk["kv_norm"]["scale"].shape[0]
    with jax.named_scope("attn_proj"), jax.named_scope("mla_latent"):
        cq = _rmsnorm(blk["q_norm"], h @ blk["wq_a"].astype(cd), eps)
        q = jnp.einsum("blr,rhd->blhd", cq, blk["wq_b"].astype(cd))
        ckv = h @ blk["wkv_a"].astype(cd)
        kv = jnp.einsum("blr,rhd->blhd",
                        _rmsnorm(blk["kv_norm"], ckv[..., :rank], eps),
                        blk["wkv_b"].astype(cd))
        pos = jnp.arange(x.shape[1])
        qr = rotary(q[..., nope:], pos, rope_theta, pairing)
        kr = rotary(ckv[:, :, None, rank:], pos, rope_theta, pairing)
        q = jnp.concatenate([q[..., :nope], qr], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(kr, kr.shape[:2] + (q.shape[2], kr.shape[3]))],
            axis=-1)
        v = kv[..., nope:]
    with jax.named_scope("attn_core"), jax.named_scope("attn_mla"):
        att = local_attention(q, k, v, causal=True)
    with jax.named_scope("attn_proj"):
        return x + jnp.einsum("blhd,hde->ble", att, blk["wo"].astype(cd))


def mlp_apply(blk: PyTree, x: jax.Array, cd, eps: float):
    """A dense layer's SwiGLU MLP with its residual (a layer below
    ``dense_layers``: no router, no expert)."""
    h = _rmsnorm(blk["ln2"], x, eps)
    with jax.named_scope("mlp"):
        return x + (jax.nn.silu(h @ blk["w_gate"].astype(cd))
                    * (h @ blk["w_up"].astype(cd))) \
            @ blk["w_down"].astype(cd)


def kda_apply(blk: PyTree, x: jax.Array, cd, eps: float):
    """The linear-attention layer's mixer with its residual.  Everything
    between the projections is HEAD-MAJOR ([B, H, L, .]): the projections
    write that layout directly and the output projection reads it, so the
    chunked core (whose chunks are blocks of one head's positions) costs no
    transpose."""
    H, K = blk["dt_bias"].shape
    rank = blk["wa1"].shape[1]
    h = _rmsnorm(blk["ln1"], x, eps)
    with jax.named_scope("attn_proj"):
        q = jnp.einsum("ble,ehd->bhld", h, blk["wq"].astype(cd))
        k = jnp.einsum("ble,ehd->bhld", h, blk["wk"].astype(cd))
        v = jnp.einsum("ble,ehd->bhld", h, blk["wv"].astype(cd))
        a = jnp.einsum("blr,rhd->bhld", h @ blk["wa1"].astype(cd),
                       blk["wa2"].astype(cd).reshape(rank, H, K))
        gate = jnp.einsum("blr,rhd->bhld", h @ blk["wg1"].astype(cd),
                          blk["wg2"].astype(cd).reshape(rank, H, K))
        b_in = jnp.einsum("ble,eh->bhl", h, blk["wb"].astype(cd))
    with jax.named_scope("linattn_core"):
        def mix(t, w):              # conv over time and SiLU, head by head
            return jax.nn.silu(causal_conv(
                t, w.astype(cd).reshape(-1, H, 1, K), axis=2))
        q = _l2norm(mix(q, blk["conv_q"])) * (1.0 / math.sqrt(K))
        k = _l2norm(mix(k, blk["conv_k"]))
        v = mix(v, blk["conv_v"])
        g = -jnp.exp(blk["a_log"].astype(jnp.float32))[:, None, None] \
            * jax.nn.softplus(a.astype(jnp.float32)
                              + blk["dt_bias"].astype(jnp.float32)[:, None])
        beta = 2.0 * jax.nn.sigmoid(b_in.astype(jnp.float32))
        # an undeclared name inside the declared scope: a profile, or a
        # roofline reader, finds the delta rule's own operations by it
        with jax.named_scope("delta_rule"):
            o, _ = chunked_delta_rule(q, k, v, g, beta, compute_dtype=cd)
    o = _rmsnorm(blk["o_norm"], o, eps)                         # per head
    with jax.named_scope("attn_proj"):
        o = jax.nn.sigmoid(gate.astype(jnp.float32)).astype(cd) * o.astype(cd)
        return x + jnp.einsum("bhld,hde->ble", o,
                              blk["wo"].astype(cd).reshape(H, K, -1))


def moe_apply(blk: PyTree, x: jax.Array, cd, eps: float, held, top_k: int,
              ep_axis: str | None, route_from: jax.Array | None = None,
              act: str = "silu", score: str = "softmax", scale: float = 1.0):
    """The shared expert (where the layer has one) + the held experts' part
    of the routed ones, with the residual; returns ``(y, routing
    counters)``.  ``route_from`` [B, L, D]: what the router reads instead of
    the experts' input; ``act``: the experts' gate activation; ``score`` and
    ``scale``: how the router scores and the factor on its weights
    (:func:`distlearn_tpu.parallel.ep.route_held`), its choice corrected by
    the layer's ``router_bias`` where it has one."""
    B, L, D = x.shape
    h = _rmsnorm(blk["ln2"], x, eps)
    if "ws_gate" in blk:
        with jax.named_scope("mlp"):
            shared = (GATE_ACTS[act](h @ blk["ws_gate"].astype(cd))
                      * (h @ blk["ws_up"].astype(cd))) \
                @ blk["ws_down"].astype(cd)
    with jax.named_scope("moe"):
        routed, aux = moe_held_ffn(
            h.reshape(B * L, D), blk["router"],
            (blk["we_gate"], blk["we_up"], blk["we_down"]), held, top_k,
            compute_dtype=cd, ep_axis=ep_axis, act=act,
            route_from=None if route_from is None
            else route_from.reshape(B * L, D), score=score,
            select_bias=blk.get("router_bias"), scale=scale)
        if "ws_gate" in blk:
            x = x + shared
        return x + routed.reshape(B, L, D), aux


def hybrid_lm(vocab: int, dim: int, layer_types: Sequence[str], *,
              heads: int, kv_heads: int, head_dim: int,
              kda_heads: int | None = None, kda_head_dim: int | None = None,
              conv_kernel: int = 4, kda_rank: int | None = None,
              window: int | None = None, rope_theta: float | None = None,
              n_routed_experts: int, held_experts: Sequence[int],
              experts_per_tok: int, expert_width: int,
              n_shared_experts: int = 1, expert_act: str = "silu",
              router_input: str = "ffn_norm", eps: float = 1e-5,
              max_len: int = 2048, dtype=jnp.float32, compute_dtype=None,
              remat: bool | str = False,
              q_lora_rank: int | None = None, kv_lora_rank: int | None = None,
              qk_nope_head_dim: int | None = None,
              qk_rope_head_dim: int | None = None,
              v_head_dim: int | None = None, rope_pairing: str = "half",
              dense_layers: int = 0, dense_width: int | None = None,
              router_score: str = "softmax", routed_scale: float = 1.0,
              mtp_depth: int = 0, mtp_weight: float = 0.0) -> Model:
    """Returns a :class:`Model` mapping int tokens [B, L] to next-token
    logits [B, L, vocab] (untied head).

    ``layer_types``: one of :data:`LAYER_TYPES` per layer — the pattern is
    data.  ``heads`` / ``kv_heads`` / ``head_dim`` size the softmax layers
    (``"gqa"``: output gate, whole triangle, no positions; ``"full"``: the
    same without the gate; ``"window"``: no gate, q and k rotated at
    ``rope_theta`` and the mask cut to the last ``window`` positions —
    both required by a pattern that has such a layer), ``kda_heads`` /
    ``kda_head_dim`` (keys and values alike; required by a pattern with a
    ``"kda"`` layer) the linear-attention ones, whose decay and output
    gates are low-rank through ``kda_rank`` (default: ``kda_head_dim``).

    ``n_routed_experts`` is the router's width; ``held_experts`` names the
    experts whose weights live HERE (a chip's share of an expert-parallel
    layer, or ``range(n_routed_experts)`` for all of them): the model
    computes their part of every layer's result and leaves the rest out —
    see :func:`distlearn_tpu.parallel.ep.moe_held_ffn`.  The shared expert
    (``n_shared_experts`` x ``expert_width`` wide) runs on every token;
    with ``n_shared_experts=0`` the layers have none and no ``ws_*`` leaf.
    ``expert_act`` is the experts' gate activation (``"silu"`` | ``"relu"``),
    ``router_input`` one of :data:`ROUTER_INPUTS`: what every layer's
    router reads.  ``router_score`` (``"softmax"`` | ``"sigmoid"``) and
    ``routed_scale``: how it scores and the factor on its combine weights; a
    sigmoid router has a ``router_bias`` leaf [n_routed_experts] (zeros at
    init) that corrects its CHOICE and that no gradient reaches.

    An ``"mla"`` layer takes ``heads`` heads and its five sizes
    ``q_lora_rank`` / ``kv_lora_rank`` / ``qk_nope_head_dim`` /
    ``qk_rope_head_dim`` / ``v_head_dim`` with ``rope_theta`` (all required
    by a pattern that has one); ``rope_pairing`` (``"half"`` |
    ``"interleaved"``) is how ITS rotation pairs dimensions (a ``"window"``
    layer rotates by halves).  The first ``dense_layers`` layers have a
    dense SwiGLU MLP ``dense_width`` wide (``w_gate / w_up / w_down``) and
    no router or expert leaf.  ``mtp_depth=1`` adds the prediction module
    (``params["mtp"]``: ``enorm``, ``hnorm``, ``eh_proj`` [2 dim, dim], one
    more layer ``block`` of the LAST layer's kind with a mixture, ``norm``),
    run in training only; ``mtp_weight`` is the weight ``lm_loss`` gives its
    loss (also ``apply.mtp_weight``, for whoever builds a step).

    ``remat`` (True = ``"full"``) makes each layer one checkpoint
    (:func:`distlearn_tpu.models.core.checkpoint_block`): its activations
    are recomputed in the backward pass.  The checkpoint holds the layer's
    input and, in a softmax layer whose attention runs on the blockwise
    kernel, that kernel's output (``[B, L, heads * head_dim]`` in the
    compute dtype) and float32 log-sum-exp (``[B, heads, L]``), which the
    kernel's backward call reads: the forward kernel runs once a layer, at
    the price of one more array of the input's size order held a softmax
    layer.  A linear-attention layer holds its input alone.
    ``apply``'s ``seq_axis`` / ``tp_axis`` may name mesh axes of size 1 (the
    LM step builders always pass them); sequence or tensor parallelism of
    these layers is not written and a larger axis raises.  ``ep_axis`` is
    handed to the expert layer, which raises until its exchange exists.

    ``apply`` returns the routing counters as its state: ``moe_assignments``
    [layers, held] (assignments each held expert received), and per layer
    ``moe_unheld_frac`` and ``moe_dropped`` (always 0: the layer has no
    capacity to overflow) — a row a mixture layer (a dense layer has none)
    and, in training, one more for the prediction module's block, whose
    logits ``mtp_logits`` [B, L, vocab] and ``mtp_weight`` the state then
    carries too."""
    layer_types = tuple(layer_types)
    if not layer_types or any(t not in LAYER_TYPES for t in layer_types):
        raise ValueError(f"layer_types must be a non-empty sequence of "
                         f"{LAYER_TYPES}, got {layer_types!r}")
    if heads % kv_heads:
        raise ValueError(f"heads={heads} is not a multiple of "
                         f"kv_heads={kv_heads}")
    if "kda" in layer_types and not (kda_heads and kda_head_dim):
        raise ValueError("a 'kda' layer needs kda_heads and kda_head_dim")
    if "window" in layer_types and not (window and rope_theta):
        raise ValueError("a 'window' layer needs window and rope_theta, got "
                         f"window={window!r} rope_theta={rope_theta!r}")
    mla = {"q_lora_rank": q_lora_rank, "kv_lora_rank": kv_lora_rank,
           "qk_nope_head_dim": qk_nope_head_dim,
           "qk_rope_head_dim": qk_rope_head_dim, "v_head_dim": v_head_dim,
           "rope_theta": rope_theta}
    if "mla" in layer_types and not all(mla.values()):
        raise ValueError("an 'mla' layer needs " + ", ".join(
            k for k, v in mla.items() if not v) + f" (got {mla!r})")
    if rope_pairing not in ROPE_PAIRINGS:
        raise ValueError(f"rope_pairing must be one of {ROPE_PAIRINGS}, got "
                         f"{rope_pairing!r}")
    if router_score not in ROUTER_SCORES:
        raise ValueError(f"router_score must be one of {ROUTER_SCORES}, got "
                         f"{router_score!r}")
    if not 0 <= dense_layers <= len(layer_types) \
            or (dense_layers and not dense_width):
        raise ValueError(f"dense_layers={dense_layers} of "
                         f"{len(layer_types)} layers needs a dense_width, "
                         f"got {dense_width!r}")
    if mtp_depth not in (0, 1):
        raise ValueError("mtp_depth must be 0 or 1 (one prediction module), "
                         f"got {mtp_depth!r}")
    if expert_act not in GATE_ACTS:
        raise ValueError(f"expert_act must be one of {tuple(GATE_ACTS)}, "
                         f"got {expert_act!r}")
    if router_input not in ROUTER_INPUTS:
        raise ValueError(f"router_input must be one of {ROUTER_INPUTS}, "
                         f"got {router_input!r}")
    if isinstance(remat, str) and remat != "full":
        raise ValueError(f"remat must be False, True or 'full', got {remat!r}")
    held = tuple(int(e) for e in held_experts)
    G, F = len(held), expert_width
    Fs = n_shared_experts * expert_width
    rank = kda_rank or kda_head_dim
    H, K = kda_heads, kda_head_dim
    cd = compute_dtype or dtype
    depth = len(layer_types)

    # a softmax router's call is the one it always was (no new keyword)
    router_kw = {} if router_score == "softmax" and routed_scale == 1.0 \
        else {"score": router_score, "scale": routed_scale}

    def init_layer(key, kind, dense=False):
        ks = iter(random.split(key, 24))
        nk = lambda: next(ks)                                # noqa: E731
        if kind == "mla":
            qk = qk_nope_head_dim + qk_rope_head_dim
            blk = {
                "wq_a": _dense(nk(), (dim, q_lora_rank), dim, dtype),
                "q_norm": _norm_init((q_lora_rank,), dtype),
                "wq_b": _dense(nk(), (q_lora_rank, heads, qk), q_lora_rank,
                               dtype),
                "wkv_a": _dense(nk(), (dim, kv_lora_rank + qk_rope_head_dim),
                                dim, dtype),
                "kv_norm": _norm_init((kv_lora_rank,), dtype),
                "wkv_b": _dense(nk(), (kv_lora_rank, heads,
                                       qk_nope_head_dim + v_head_dim),
                                kv_lora_rank, dtype),
                "wo": _dense(nk(), (heads, v_head_dim, dim),
                             heads * v_head_dim, dtype),
            }
        elif kind != "kda":
            blk = {
                "wq": _dense(nk(), (dim, heads, head_dim), dim, dtype),
                "wk": _dense(nk(), (dim, kv_heads, head_dim), dim, dtype),
                "wv": _dense(nk(), (dim, kv_heads, head_dim), dim, dtype),
            }
            if kind == "gqa":
                blk["wg"] = _dense(nk(), (dim, heads, head_dim), dim, dtype)
            blk["wo"] = _dense(nk(), (heads, head_dim, dim), heads * head_dim,
                               dtype)
        else:
            conv = lambda: random.uniform(                   # noqa: E731
                nk(), (conv_kernel, H * K), dtype, -1.0, 1.0) \
                / math.sqrt(conv_kernel)
            # decay rates and time steps drawn as the published layer
            # initialises them: A in [1, 16], softplus(b) in [1e-3, 1e-1]
            dt = jnp.exp(random.uniform(nk(), (H, K), dtype,
                                        math.log(1e-3), math.log(1e-1)))
            blk = {
                "wq": _dense(nk(), (dim, H, K), dim, dtype),
                "wk": _dense(nk(), (dim, H, K), dim, dtype),
                "wv": _dense(nk(), (dim, H, K), dim, dtype),
                "conv_q": conv(), "conv_k": conv(), "conv_v": conv(),
                "wa1": _dense(nk(), (dim, rank), dim, dtype),
                "wa2": _dense(nk(), (rank, H * K), rank, dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1
                "a_log": jnp.log(random.uniform(nk(), (H,), dtype, 1.0,
                                                16.0)),
                "wb": _dense(nk(), (dim, H), dim, dtype),
                "wg1": _dense(nk(), (dim, rank), dim, dtype),
                "wg2": _dense(nk(), (rank, H * K), rank, dtype),
                "o_norm": _norm_init((K,), dtype),
                "wo": _dense(nk(), (H * K, dim), H * K, dtype),
            }
        blk.update({"ln1": _norm_init((dim,), dtype),
                    "ln2": _norm_init((dim,), dtype)})
        if dense:
            return dict(blk, **{
                "w_gate": _dense(nk(), (dim, dense_width), dim, dtype),
                "w_up": _dense(nk(), (dim, dense_width), dim, dtype),
                "w_down": _dense(nk(), (dense_width, dim), dense_width,
                                 dtype)})
        blk["router"] = _dense(nk(), (dim, n_routed_experts), dim, dtype)
        if router_score == "sigmoid":
            blk["router_bias"] = jnp.zeros((n_routed_experts,), dtype)
        shared = {
            "ws_gate": _dense(nk(), (dim, Fs), dim, dtype),
            "ws_up": _dense(nk(), (dim, Fs), dim, dtype),
            "ws_down": _dense(nk(), (Fs, dim), Fs, dtype)} if Fs else {}
        blk.update(shared, **{
            "we_gate": _dense(nk(), (G, dim, F), dim, dtype),
            "we_up": _dense(nk(), (G, dim, F), dim, dtype),
            "we_down": _dense(nk(), (G, F, dim), F, dtype),
        })
        return blk

    def init(key):
        keys = random.split(key, depth + 2)
        params = {"embed": _dense(keys[0], (vocab, dim), dim, dtype),
                  "head": _dense(keys[1], (dim, vocab), dim, dtype),
                  "out_norm": _norm_init((dim,), dtype)}
        for i, kind in enumerate(layer_types):
            params[f"layer{i}"] = init_layer(keys[2 + i], kind,
                                             i < dense_layers)
        if mtp_depth:
            k_eh, k_blk = random.split(random.fold_in(key, depth))
            params["mtp"] = {
                "enorm": _norm_init((dim,), dtype),
                "hnorm": _norm_init((dim,), dtype),
                "eh_proj": _dense(k_eh, (2 * dim, dim), 2 * dim, dtype),
                "block": init_layer(k_blk, layer_types[-1]),
                "norm": _norm_init((dim,), dtype)}
        return params, {}

    def apply(params, state, tokens, train=True, rng=None, axis_name=None,
              bn_weight=None, seq_axis=None, tp_axis=None, ep_axis=None,
              seq_layout="contig"):
        for what, axis in (("sequence", seq_axis), ("tensor", tp_axis)):
            if axis is not None and lax.axis_size(axis) != 1:
                raise NotImplementedError(
                    f"hybrid_lm: {what} parallelism over axis {axis!r} of "
                    f"size {lax.axis_size(axis)} is not written for these "
                    "layers (the delta rule's state and the window's band "
                    "would have to cross the shards); use a size-1 axis")
        if seq_layout != "contig":
            raise ValueError("hybrid_lm keeps the sequence contiguous, got "
                             f"seq_layout={seq_layout!r}")
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(cd)

        def make_layer(kind, dense):
            def layer(blk, x):
                route_from = x if router_input == "layer_input" else None
                if kind == "kda":
                    x = kda_apply(blk, x, cd, eps)
                elif kind == "mla":
                    x = mla_apply(blk, x, cd, eps, rope_theta,
                                  qk_nope_head_dim, rope_pairing)
                elif kind == "window":
                    x = gqa_apply(blk, x, cd, eps, window, rope_theta)
                else:
                    x = gqa_apply(blk, x, cd, eps)
                if dense:
                    return mlp_apply(blk, x, cd, eps), None
                return moe_apply(blk, x, cd, eps, held, experts_per_tok,
                                 ep_axis, route_from, expert_act, **router_kw)
            return checkpoint_block(layer) if remat else layer

        # one wrapper a kind, reused down the depth (transformer_lm's note:
        # a fresh checkpoint closure a layer stops XLA sharing the
        # rematerialised computation)
        kinds = [(kind, i < dense_layers)
                 for i, kind in enumerate(layer_types)]
        module = (layer_types[-1], False)       # the module's block's kind
        layers = {k: make_layer(*k)
                  for k in set(kinds) | ({module} if mtp_depth else set())}
        counters = []
        for i, k in enumerate(kinds):
            x, aux = layers[k](params[f"layer{i}"], x)
            counters.append(aux)
        x = _rmsnorm(params["out_norm"], x, eps)
        with jax.named_scope("head_loss"):
            logits = (x @ params["head"].astype(cd)).astype(dtype)
        if mtp_depth and train:
            with jax.named_scope("mtp"):
                mtp = params["mtp"]
                with jax.named_scope("embed"):       # t_{i+1}; the last wraps
                    e = params["embed"][jnp.roll(tokens, -1, axis=1)].astype(cd)
                z = jnp.concatenate([_rmsnorm(mtp["enorm"], e, eps),
                                     _rmsnorm(mtp["hnorm"], x, eps)], axis=-1)
                with jax.named_scope("mlp"):
                    z = z @ mtp["eh_proj"].astype(cd)
                z, aux = layers[module](mtp["block"], z)
                counters.append(aux)
                z = _rmsnorm(mtp["norm"], z, eps)
                with jax.named_scope("head_loss"):
                    state = dict(state, mtp_weight=mtp_weight, mtp_logits=(
                        z @ params["head"].astype(cd)).astype(dtype))
        counters = [c for c in counters if c is not None]
        if counters:
            state = dict(state, **{
                f"moe_{k}": jnp.stack([c[k] for c in counters])
                for k in ("assignments", "unheld_frac", "dropped")})
        return logits, state

    apply.mtp_weight = float(mtp_weight) if mtp_depth else 0.0
    return Model(init=init, apply=apply, name="hybrid_lm",
                 input_shape=(max_len,), num_classes=vocab)
