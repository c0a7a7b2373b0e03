"""Plain reference of the hybrid LM (softmax-GQA layers among gated
delta-rule layers, every layer a routed mixture of experts beside a shared
one), written from the equations and independent of ``distlearn_tpu.models``:
float32, matmul precision "highest", plain ``jax.numpy``, no kernels, no
chunking of the recurrence, no grouping of tokens.

    x_0 = embed[tokens]                         (no positional term)
    h   = x + Mix_l(rmsnorm(x; ln_1))
    x'  = h + MoE_l(rmsnorm(h; ln_2))
    logits = rmsnorm(x_depth; ln_f) . head      (untied)
    loss = mean over batch and positions 0..L-2 of -log softmax(logits)[next]

    rmsnorm(x; g) = x / sqrt(mean(x^2) + eps) * g,   eps = 1e-5
    l2norm(x)     = x / sqrt(sum(x^2) + 1e-6)

``Mix`` of a softmax layer (a layer with ``wg``): H query heads, Hkv K/V
heads, query head i attends K/V head i // (H / Hkv), the full square:

    q, k, v = x wq, x wk, x wv
    a_i = softmax(q_i k_{i // (H/Hkv)}^T / sqrt(D) + causal) v_{i // (H/Hkv)}
    out = (sigmoid(x wg) * a) . wo

``Mix`` of a linear-attention layer (a layer with ``a_log``), per head, the
state computed by its RECURRENCE position by position:

    q~, k~, v~ = silu(conv(x wq)), silu(conv(x wk)), silu(conv(x wv))
        conv(u)_t = sum_{j<W} c[j] u_{t-W+1+j}    (causal, depthwise, zeros before 0)
    q = l2norm(q~) / sqrt(K),   k = l2norm(k~)
    alpha_t = exp(-exp(a_log_h) * softplus((x wa1) wa2 + dt_bias))   in (0,1)^K
    beta_t  = 2 sigmoid(x wb)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,  S_0 = 0
    o_t = S_t^T q_t
    out = (sigmoid((x wg1) wg2) * rmsnorm(o; o_norm)) . wo      (norm per head)

``MoE``, of which this holder keeps the experts ``held`` (what the others
would add is left out):

    s = softmax(x router) in R^E;  I = top-k(s);  w_i = s_i / sum_{j in I} s_j
    y = swiglu_shared(x) + sum_{i in I and held} w_i swiglu_i(x)
    swiglu(x) = (silu(x w_gate) * (x w_up)) w_down

Parameters: :class:`Params` — a dict ``{"embed": [V,E], "head": [E,V],
"ln_f": [E], "layers": [layer]*depth}`` with the share (``held``, ``top_k``)
beside it as static data.

Two ways through the same mathematics, as ``reference/transformer_lm.py``:
whole-model (:func:`logits`, :func:`loss`) for ``jax.grad`` in the CPU tests,
and LAYER BY LAYER (:func:`layerwise_sgd_losses`) for the chip, where one
program over all layers would outgrow the compile cache's entries and the
memory: one jitted function a KIND of layer and its ``vjp``, the activations
in a Python list, the state of the recurrence stored once a block of
positions (two nested scans, the inner one rematerialised), the square of
attention one query head at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_EPS = 1e-5
_SCAN_BLOCK = 64


@jax.tree_util.register_pytree_node_class
class Params:
    """The parameter dict with the holder's share as static data."""

    def __init__(self, tree: dict, held: tuple, top_k: int):
        self.tree, self.held, self.top_k = tree, tuple(held), int(top_k)

    def tree_flatten(self):
        return (self.tree,), (self.held, self.top_k)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, g):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + _EPS) * g


def l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def conv(u, c):
    """u [B, L, C], c [W, C]: causal depthwise convolution over time."""
    W, L = c.shape[0], u.shape[1]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(pad[:, j:j + L] * c[j] for j in range(W))


def softmax_attention(q, k, v):
    """q [B, L, H, D], k, v [B, L, Hkv, D]: the causal full square, one
    query head at a time (rematerialised, so its gradient never holds more
    than one head's square either)."""
    B, L, H, D = q.shape
    group = H // k.shape[2]
    causal = jnp.tril(jnp.ones((L, L), bool))

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv                                     # [B, L, D]
        s = jnp.einsum("bqd,bkd->bqk", qh, kh) / jnp.sqrt(jnp.float32(D))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, vh)

    heads = lambda a: jnp.moveaxis(a, 2, 0)                  # noqa: E731
    out = lax.map(one_head, (heads(q), jnp.repeat(heads(k), group, axis=0),
                             jnp.repeat(heads(v), group, axis=0)))
    return jnp.moveaxis(out, 0, 2)


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, position by position.  q, k, alpha [B, L, H, K];
    v [B, L, H, V]; beta [B, L, H].  Returns o [B, L, H, V]."""
    B, L, H, K = q.shape
    V = v.shape[-1]

    def step(S, x):
        q_t, k_t, v_t, a_t, b_t = x
        S = a_t[..., None] * S
        err = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S)
        S = S + b_t[..., None, None] * k_t[..., None] * err[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)

    T = next(t for t in (_SCAN_BLOCK, 32, 16, 8, 4, 2, 1) if L % t == 0)

    @jax.checkpoint
    def block(S, xs):
        return lax.scan(step, S, xs)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((L // T, T) + a.shape[:1]
                                             + a.shape[2:])
               for a in (q, k, v, alpha, beta))
    _, o = lax.scan(block, jnp.zeros((B, H, K, V), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(L, B, H, V), 0, 1)


def mix_softmax(layer, x):
    q = jnp.einsum("ble,ehd->blhd", x, layer["wq"])
    k = jnp.einsum("ble,ehd->blhd", x, layer["wk"])
    v = jnp.einsum("ble,ehd->blhd", x, layer["wv"])
    gate = jax.nn.sigmoid(jnp.einsum("ble,ehd->blhd", x, layer["wg"]))
    return jnp.einsum("blhd,hde->ble", gate * softmax_attention(q, k, v),
                      layer["wo"])


def mix_linear(layer, x):
    B, L, _ = x.shape
    H, K = layer["dt_bias"].shape
    heads = lambda a: a.reshape(B, L, H, -1)                 # noqa: E731
    q = heads(jax.nn.silu(conv(x @ layer["wq"], layer["conv_q"])))
    k = heads(jax.nn.silu(conv(x @ layer["wk"], layer["conv_k"])))
    v = heads(jax.nn.silu(conv(x @ layer["wv"], layer["conv_v"])))
    q, k = l2norm(q) / jnp.sqrt(jnp.float32(K)), l2norm(k)
    rate = jax.nn.softplus(heads((x @ layer["wa1"]) @ layer["wa2"])
                           + layer["dt_bias"])
    alpha = jnp.exp(-jnp.exp(layer["a_log"])[:, None] * rate)
    beta = 2.0 * jax.nn.sigmoid(x @ layer["wb"])
    o = rmsnorm(delta_rule(q, k, v, alpha, beta), layer["o_norm"])
    gate = jax.nn.sigmoid((x @ layer["wg1"]) @ layer["wg2"])
    return (gate * o.reshape(B, L, H * K)) @ layer["wo"]


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe(layer, x, held, top_k):
    s = jax.nn.softmax(x @ layer["router"], axis=-1)
    top, chosen = lax.top_k(s, top_k)
    w = top / jnp.sum(top, axis=-1, keepdims=True)
    y = swiglu(x, layer["ws_gate"], layer["ws_up"], layer["ws_down"])
    for j, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)
        y = y + w_e * swiglu(x, layer["we_gate"][j], layer["we_up"][j],
                             layer["we_down"][j])
    return y


def block(layer, x, held, top_k):
    mix = mix_linear if "a_log" in layer else mix_softmax
    h = x + mix(layer, rmsnorm(x, layer["ln_1"]))
    return h + moe(layer, rmsnorm(h, layer["ln_2"]), held, top_k)


def head_logits(head, ln_f, x):
    return rmsnorm(x, ln_f) @ head


def head_loss(head, ln_f, x, tokens):
    lp = jax.nn.log_softmax(head_logits(head, ln_f, x)[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, tokens[:, 1:, None], -1))


# ------------------------------------------------------------ whole model --

def _through(params: Params, tokens):
    p = params.tree
    x = p["embed"][tokens]
    for layer in p["layers"]:
        x = block(layer, x, params.held, params.top_k)
    return x


@_highest
def logits(params: Params, tokens):
    return head_logits(params.tree["head"], params.tree["ln_f"],
                       _through(params, tokens))


@_highest
def loss(params: Params, tokens):
    return head_loss(params.tree["head"], params.tree["ln_f"],
                     _through(params, tokens), tokens)


# --------------------------------------------------------- layer by layer --

_STATIC = ("held", "top_k")
_block = jax.jit(_highest(block), static_argnames=_STATIC)


@functools.partial(jax.jit, static_argnames=_STATIC)
@_highest
def _block_vjp(layer, x, dy, held, top_k):
    _, pull = jax.vjp(lambda l, a: block(l, a, held, top_k), layer, x)
    return pull(dy)                                   # (d layer, d x)


@jax.jit
@_highest
def _head_loss_grad(head, ln_f, x, tokens):
    return jax.value_and_grad(head_loss, argnums=(0, 1, 2))(head, ln_f, x,
                                                            tokens)


_head_loss = jax.jit(_highest(head_loss))
_embed = jax.jit(lambda embed, tokens: embed[tokens])


@jax.jit
def _embed_vjp(embed, tokens, dx):
    return jax.vjp(lambda e: e[tokens], embed)[1](dx)[0]


_scale = jax.jit(lambda g, w: jax.tree_util.tree_map(lambda v: w * v, g),
                 donate_argnums=(0,))
_axpy = jax.jit(lambda a, b, w: jax.tree_util.tree_map(
    lambda u, v: u + w * v, a, b), donate_argnums=(0,))


def _forward(params: Params, tokens, keep: bool):
    x = _embed(params.tree["embed"], tokens)
    acts = [x]
    for layer in params.tree["layers"]:
        x = _block(layer, x, held=params.held, top_k=params.top_k)
        if keep:
            acts.append(x)
    return x, acts


def layerwise_loss(params: Params, tokens, micro: int = 1):
    total = 0.0
    for i in range(0, tokens.shape[0], micro):
        t = tokens[i:i + micro]
        x, _ = _forward(params, t, keep=False)
        total += float(_head_loss(params.tree["head"], params.tree["ln_f"],
                                  x, t)) * t.shape[0]
    return total / tokens.shape[0]


def _backward(params: Params, t, sink):
    """Loss of the micro-batch ``t``; every gradient goes to
    ``sink(where, grad)`` the moment it is made (``where``: a layer's index,
    or ``"top"`` for ``{"embed", "head", "ln_f"}``), last layer first."""
    p = params.tree
    x, acts = _forward(params, t, keep=True)
    l, (d_head, d_lnf, dx) = _head_loss_grad(p["head"], p["ln_f"], x, t)
    for j in reversed(range(len(p["layers"]))):
        d_layer, dx = _block_vjp(p["layers"][j], acts[j], dx,
                                 held=params.held, top_k=params.top_k)
        acts[j + 1] = None
        sink(j, d_layer)
        del d_layer
    sink("top", {"embed": _embed_vjp(p["embed"], t, dx), "head": d_head,
                 "ln_f": d_lnf})
    return float(l)


def layerwise_loss_and_grads(params: Params, tokens, micro: int = 1):
    """Mean loss over ``tokens`` [B, L] and its gradient (a :class:`Params`),
    ``micro`` sequences at a time, every layer's gradient added into the
    running sum as soon as it is made."""
    B = tokens.shape[0]
    depth = len(params.tree["layers"])
    total, got = 0.0, {}
    for i in range(0, B, micro):
        t = tokens[i:i + micro]
        w = jnp.float32(t.shape[0] / B)

        def sink(where, g, w=w):
            got[where] = _scale(g, w) if where not in got \
                else _axpy(got[where], g, w)
        total += _backward(params, t, sink) * t.shape[0] / B
    return total, Params(dict(got["top"],
                              layers=[got[j] for j in range(depth)]),
                         params.held, params.top_k)


def layerwise_sgd_losses(params: Params, tokens, lr: float, steps: int,
                         micro: int = 1):
    """``[loss(P_0), ..., loss(P_steps)]`` on the one batch ``tokens``, with
    ``P_{i+1} = P_i - lr * grad(P_i)`` — plain SGD.  ``params`` is consumed.

    Where the batch is one micro-batch (the chip's check: 1 x 8192) each
    layer is UPDATED the moment its gradient is made — the layers before it
    never read it again in that step — so no gradient tree is ever held: the
    float32 model and one layer's temporaries are all the memory there is."""
    losses = []
    step = jnp.float32(-lr)
    for _ in range(steps):
        if tokens.shape[0] <= micro:
            p = params.tree

            def sink(where, g):
                if where == "top":
                    for name in g:
                        p[name] = _axpy(p[name], g[name], step)
                else:
                    p["layers"][where] = _axpy(p["layers"][where], g, step)
            losses.append(_backward(params, tokens, sink))
        else:
            l, g = layerwise_loss_and_grads(params, tokens, micro)
            losses.append(l)
            params = _axpy(params, g, step)
            del g
    losses.append(layerwise_loss(params, tokens, micro))
    return losses
