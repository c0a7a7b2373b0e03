"""Plain reference of the repo's decoder-only LM, written from the equations
and independent of ``distlearn_tpu.models``: float32, matmul precision
"highest", plain ``jax.numpy``, no kernels, no cache, no batching tricks.

The architecture (GPT-2's, with the departures ``configs/*.json`` lists):

    x_0   = wte[tokens] + wpe[:L]
    x_l'  = x_l  + concat_h(softmax(causal(q_h k_h^T / sqrt(D))) v_h) . wo
            with q,k,v = rmsnorm(x_l; ln_1) . (wq, wk, wv)       (no biases)
    x_l+1 = x_l' + gelu_tanh(rmsnorm(x_l'; ln_2) . w_fc + b_fc) . w_proj + b_proj
    logits = rmsnorm(x_depth; ln_f) . wte^T                      (tied head)
    loss   = mean over batch and positions 0..L-2 of -log softmax(logits)[next token]

    rmsnorm(x; g) = x / sqrt(mean(x^2) + 1e-6) * g

Parameters: ``{"wte": [V,E], "wpe": [P,E], "ln_f": [E], "layers": [layer]*depth}``
with ``layer = {"ln_1": [E], "wq","wk","wv": [E,H,D], "wo": [H,D,E],
"ln_2": [E], "w_fc": [E,F], "b_fc": [F], "w_proj": [F,E], "b_proj": [E]}``.

Two ways through the same mathematics:

* whole-model (:func:`logits`, :func:`loss`) — one function over all layers,
  for ``jax.grad`` in the CPU tests;
* LAYER BY LAYER (:func:`layerwise_loss`, :func:`layerwise_loss_and_grads`,
  :func:`layerwise_sgd_losses`) — one small jitted block function, and its
  ``vjp``, reused for every layer with the activations kept in a Python list.
  Its compile does not grow with depth (a float32 ``grad`` over 36 unrolled
  layers serialises to a 200 MB program, more than the chip machine's
  192 MiB compile cache keeps), and it never holds more than one layer's
  attention probabilities.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_EPS = 1e-6


def _highest(fn):
    """Trace ``fn`` with float32 matmuls at full precision (on a TPU the
    default is a single bf16 pass)."""
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def rmsnorm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + _EPS) * g


def block(layer, x):
    """One pre-norm block on ``x`` [B, L, E] (float32)."""
    B, L, _ = x.shape
    D = layer["wq"].shape[-1]
    h = rmsnorm(x, layer["ln_1"])
    q = jnp.einsum("ble,ehd->bhld", h, layer["wq"])
    k = jnp.einsum("ble,ehd->bhld", h, layer["wk"])
    v = jnp.einsum("ble,ehd->bhld", h, layer["wv"])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
    causal = jnp.tril(jnp.ones((L, L), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bhkd->bqhd", p, v)
    x = x + jnp.einsum("bqhd,hde->bqe", a, layer["wo"])
    h = rmsnorm(x, layer["ln_2"])
    h = jax.nn.gelu(h @ layer["w_fc"] + layer["b_fc"], approximate=True)
    return x + h @ layer["w_proj"] + layer["b_proj"]


def embed(wte, wpe, tokens):
    return wte[tokens] + wpe[:tokens.shape[1]][None]


def head_logits(wte, ln_f, x):
    return rmsnorm(x, ln_f) @ wte.T


def head_loss(wte, ln_f, x, tokens):
    lp = jax.nn.log_softmax(head_logits(wte, ln_f, x)[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, tokens[:, 1:, None], -1))


# ------------------------------------------------------------ whole model --

@_highest
def logits(params, tokens):
    x = embed(params["wte"], params["wpe"], tokens)
    for layer in params["layers"]:
        x = block(layer, x)
    return head_logits(params["wte"], params["ln_f"], x)


@_highest
def loss(params, tokens):
    x = embed(params["wte"], params["wpe"], tokens)
    for layer in params["layers"]:
        x = block(layer, x)
    return head_loss(params["wte"], params["ln_f"], x, tokens)


# --------------------------------------------------------- layer by layer --

_embed = jax.jit(_highest(embed))
_block = jax.jit(_highest(block))


@jax.jit
@_highest
def _block_vjp(layer, x, dy):
    _, pull = jax.vjp(block, layer, x)
    return pull(dy)                       # (d layer, d x)


@jax.jit
@_highest
def _head_loss_grad(wte, ln_f, x, tokens):
    return jax.value_and_grad(head_loss, argnums=(0, 1, 2))(wte, ln_f, x,
                                                            tokens)


_head_loss = jax.jit(_highest(head_loss))


@jax.jit
@_highest
def _embed_vjp(wte, wpe, tokens, dx):
    _, pull = jax.vjp(lambda a, b: embed(a, b, tokens), wte, wpe)
    return pull(dx)


_scale = jax.jit(lambda g, w: jax.tree_util.tree_map(lambda v: w * v, g),
                 donate_argnums=(0,))
_axpy = jax.jit(lambda a, b, w: jax.tree_util.tree_map(
    lambda u, v: u + w * v, a, b), donate_argnums=(0,))


def _forward(params, tokens, keep: bool):
    x = _embed(params["wte"], params["wpe"], tokens)
    acts = [x]
    for layer in params["layers"]:
        x = _block(layer, x)
        if keep:
            acts.append(x)
    return x, acts


def layerwise_loss(params, tokens, micro: int = 2):
    total = 0.0
    for i in range(0, tokens.shape[0], micro):
        t = tokens[i:i + micro]
        x, _ = _forward(params, t, keep=False)
        total += float(_head_loss(params["wte"], params["ln_f"], x, t)) \
            * t.shape[0]
    return total / tokens.shape[0]


def layerwise_loss_and_grads(params, tokens, micro: int = 2):
    """Mean loss over ``tokens`` [B, L] and its gradient, ``micro`` sequences
    at a time (every sequence has the same number of targets, so the mean of
    the micro-batch means, weighted by their sizes, is the batch mean).  Each
    layer's gradient is added into the running sum as soon as it is made:
    the device never holds more than the parameters, one gradient tree and
    one layer's worth of temporaries."""
    B = tokens.shape[0]
    depth = len(params["layers"])
    total, grads = 0.0, {"layers": [None] * depth, "top": None}

    def accumulate(old, new, w):
        return _scale(new, w) if old is None else _axpy(old, new, w)

    for i in range(0, B, micro):
        t = tokens[i:i + micro]
        w = jnp.float32(t.shape[0] / B)
        x, acts = _forward(params, t, keep=True)
        l, (d_wte, d_lnf, dx) = _head_loss_grad(params["wte"], params["ln_f"],
                                                x, t)
        total += float(l) * t.shape[0] / B
        for j in reversed(range(depth)):
            d_layer, dx = _block_vjp(params["layers"][j], acts[j], dx)
            acts[j + 1] = None
            grads["layers"][j] = accumulate(grads["layers"][j], d_layer, w)
        e_wte, d_wpe = _embed_vjp(params["wte"], params["wpe"], t, dx)
        grads["top"] = accumulate(
            grads["top"], {"wte": d_wte + e_wte, "wpe": d_wpe, "ln_f": d_lnf},
            w)
    return total, dict(grads["top"], layers=grads["layers"])


def layerwise_sgd_losses(params, tokens, lr: float, steps: int,
                         micro: int = 2):
    """``[loss(P_0), loss(P_1), ..., loss(P_steps)]`` on the one batch
    ``tokens``, with ``P_{i+1} = P_i - lr * grad(P_i)`` — plain SGD.
    ``params`` is consumed (its buffers are donated to the updates)."""
    losses = []
    for _ in range(steps):
        l, g = layerwise_loss_and_grads(params, tokens, micro)
        losses.append(l)
        params = _axpy(params, g, jnp.float32(-lr))
        del g
    losses.append(layerwise_loss(params, tokens, micro))
    return losses
