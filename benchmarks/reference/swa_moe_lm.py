"""Plain reference of a decoder whose layers mix sliding-window rotary
attention with full-causal attention that has no positional term, each layer
a routed mixture of ReLU-gated experts whose router reads the LAYER'S INPUT:
written from the equations and independent of ``distlearn_tpu.models``:
float32, matmul precision "highest", plain ``jax.numpy``, no kernels, no
grouping of tokens.  ``x`` is a layer's input, one row a position ``i``:

    x_0 = embed[tokens]
    r   = x router                              E logits, from x itself, before any norm
    n   = rmsnorm(x; ln_1);   q, k, v = n wq, n wk, n wv      H query / Hkv K/V heads, no bias
    if layout[l] = 1:   q, k = rope(q, i), rope(k, i)
        rope(u, i)[d], rope(u, i)[d + D/2]  =  the pair (u[d], u[d + D/2]) turned by
        the angle  i * theta^(-2 d / D),  d < D/2   ("rotate half", the whole head)
    allowed(i, j) = j <= i   and, if layout[l] = 1,   i - j < window
    a_h = softmax(q_h k_{h // (H/Hkv)}^T / sqrt(D) + mask) v_{h // (H/Hkv)}
    h   = x + a wo
    m   = rmsnorm(h; ln_2);   S = top-k of r;   w = softmax(r)[S] / sum(softmax(r)[S])
    x'  = h + sum over e in S and HELD of  w_e (relu(m we_gate_e) * (m we_up_e)) we_down_e
    logits = rmsnorm(x_depth; ln_f) . head      (untied)
    loss = mean over batch and positions 0..L-2 of -log softmax(logits)[next]

    rmsnorm(x; g) = x / sqrt(mean(x^2) + eps) * g,   eps = 1e-6

Departures from the published model, each shared with the system: this
holder keeps the experts ``held`` and what the others would add is left out;
the vocabulary is the slice held here; weights are seeded; no auxiliary
balance loss; the "secondary experts" of the family's description have no key
in the published config and are not built.

Parameters: :class:`Params` — a dict ``{"embed": [V,E], "head": [E,V],
"ln_f": [E], "layers": [layer]*depth}`` with the share and the pattern
(``held``, ``top_k``, ``layout``, ``window``, ``theta``) beside it as static
data.

Two ways through the same mathematics, as the other references: whole-model
(:func:`logits`, :func:`loss`) for ``jax.grad`` in the CPU tests, and LAYER
BY LAYER (:func:`layerwise_sgd_losses`) for the chip, where one program over
all layers would outgrow the compile cache's entries and the memory: one
jitted function a KIND of layer and its ``vjp``, the activations in a Python
list.  Attention runs over BLOCKS OF QUERIES (rematerialised), so the
``[H, L, L]`` scores of a 16k sequence are never one array, and the loss over
blocks of positions, so neither are the ``[L, V]`` logits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_EPS = 1e-6
_QUERY_BLOCK = 256
_LOSS_BLOCK = 2048


@jax.tree_util.register_pytree_node_class
class Params:
    """The parameter dict with the holder's share and the layer pattern as
    static data: ``layout[l]`` is 1 for a rotary sliding-window layer, 0 for
    a full-causal layer with no positional term."""

    def __init__(self, tree: dict, held, top_k: int, layout, window: int,
                 theta: float):
        self.tree = tree
        self.held, self.top_k = tuple(held), int(top_k)
        self.layout = tuple(int(b) for b in layout)
        self.window, self.theta = int(window), float(theta)

    @property
    def static(self):
        return (self.held, self.top_k, self.layout, self.window, self.theta)

    def tree_flatten(self):
        return (self.tree,), self.static

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def _divisor(n: int, most: int) -> int:
    return next(t for t in range(min(n, most), 0, -1) if n % t == 0)


def rmsnorm(x, g):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + _EPS) * g


def rope(u, theta):
    """u [B, L, H, D] at positions 0..L-1: each pair (d, d + D/2) turned by
    ``position * theta ** (-2 d / D)``."""
    L, D = u.shape[1], u.shape[-1]
    half = D // 2
    freq = jnp.float32(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(L, dtype=jnp.float32)[:, None, None] * freq
    a, b = u[..., :half], u[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def attention(q, k, v, window):
    """q [B, L, H, D], k, v [B, L, Hkv, D]; ``window`` None for the whole
    causal triangle.  A block of queries at a time against every key."""
    B, L, H, D = q.shape
    k, v = (jnp.repeat(a, H // k.shape[2], axis=2) for a in (k, v))
    T = _divisor(L, _QUERY_BLOCK)
    j = jnp.arange(L)

    @jax.checkpoint
    def one_block(args):
        start, qb = args                                     # [B, T, H, D]
        i = start + jnp.arange(T)
        allowed = j[None, :] <= i[:, None]
        if window is not None:
            allowed &= i[:, None] - j[None, :] < window
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(jnp.float32(D))
        p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    blocks = jnp.moveaxis(q.reshape(B, L // T, T, H, D), 1, 0)
    out = lax.map(one_block, (jnp.arange(0, L, T), blocks))
    return jnp.moveaxis(out, 0, 1).reshape(B, L, H, D)


def reglu(x, w_gate, w_up, w_down):
    return (jax.nn.relu(x @ w_gate) * (x @ w_up)) @ w_down


def experts(layer, m, r, held, top_k):
    """The held experts' part for inputs ``m`` under router logits ``r``:
    every held expert applied to EVERY row, weighted by the row's combine
    weight for it (zero where it was not chosen); a scan over the held
    experts, so the program holds one expert's products, not sixteen."""
    s = jax.nn.softmax(r, axis=-1)
    top, chosen = lax.top_k(s, top_k)
    w = top / jnp.sum(top, axis=-1, keepdims=True)

    def add_one(y, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)
        return y + w_e * reglu(m, w_gate, w_up, w_down), None

    y, _ = lax.scan(add_one, jnp.zeros_like(m), (
        jnp.asarray(held), layer["we_gate"], layer["we_up"],
        layer["we_down"]))
    return y


def block(layer, x, held, top_k, windowed, window, theta):
    r = x @ layer["router"]                 # the layer's input, un-normed
    n = rmsnorm(x, layer["ln_1"])
    q = jnp.einsum("ble,ehd->blhd", n, layer["wq"])
    k = jnp.einsum("ble,ehd->blhd", n, layer["wk"])
    v = jnp.einsum("ble,ehd->blhd", n, layer["wv"])
    if windowed:
        q, k = rope(q, theta), rope(k, theta)
    a = attention(q, k, v, window if windowed else None)
    h = x + jnp.einsum("blhd,hde->ble", a, layer["wo"])
    return h + experts(layer, rmsnorm(h, layer["ln_2"]), r, held, top_k)


def head_logits(head, ln_f, x):
    return rmsnorm(x, ln_f) @ head


def head_loss(head, ln_f, x, tokens):
    """Mean next-token loss, a block of positions at a time."""
    B, L, D = x.shape
    T = _divisor(L, _LOSS_BLOCK)
    target = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    counted = jnp.arange(L) < L - 1                  # the last has no next

    @jax.checkpoint
    def one_block(args):
        xb, tb, cb = args
        lp = jax.nn.log_softmax(head_logits(head, ln_f, xb), axis=-1)
        picked = jnp.take_along_axis(lp, tb[..., None], -1)[..., 0]
        return -jnp.sum(jnp.where(cb, picked, 0.0))

    cut = lambda a: jnp.moveaxis(                            # noqa: E731
        a.reshape((B, L // T, T) + a.shape[2:]), 1, 0)
    sums = lax.map(one_block, (cut(x), cut(target), counted.reshape(-1, T)))
    return jnp.sum(sums) / (B * (L - 1))


# ------------------------------------------------------------ whole model --

def _through(params: Params, tokens):
    p = params.tree
    x = p["embed"][tokens]
    for layer, windowed in zip(p["layers"], params.layout):
        x = block(layer, x, params.held, params.top_k, windowed,
                  params.window, params.theta)
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

_STATIC = ("held", "top_k", "windowed", "window", "theta")
_block = jax.jit(_highest(block), static_argnames=_STATIC)


@functools.partial(jax.jit, static_argnames=_STATIC)
@_highest
def _block_vjp(layer, x, dy, **static):
    _, pull = jax.vjp(lambda l, a: block(l, a, **static), layer, x)
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


def _statics(params: Params):
    return [dict(held=params.held, top_k=params.top_k, windowed=bool(w),
                 window=params.window, theta=params.theta)
            for w in params.layout]


def _forward(params: Params, tokens, keep: bool):
    x = _embed(params.tree["embed"], tokens)
    acts = [x]
    for layer, static in zip(params.tree["layers"], _statics(params)):
        x = _block(layer, x, **static)
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
    statics = _statics(params)
    x, acts = _forward(params, t, keep=True)
    l, (d_head, d_lnf, dx) = _head_loss_grad(p["head"], p["ln_f"], x, t)
    for n in reversed(range(len(p["layers"]))):
        d_layer, dx = _block_vjp(p["layers"][n], acts[n], dx, **statics[n])
        acts[n + 1] = None
        sink(n, d_layer)
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
                              layers=[got[n] for n in range(depth)]),
                         *params.static)


def layerwise_sgd_losses(params: Params, tokens, lr: float, steps: int,
                         micro: int = 1):
    """``[loss(P_0), ..., loss(P_steps)]`` on the one batch ``tokens``, with
    ``P_{i+1} = P_i - lr * grad(P_i)`` — plain SGD.  ``params`` is consumed.

    Where the batch is one micro-batch (the chip's check: 1 x 16384) each
    layer is UPDATED the moment its gradient is made — the layers before it
    never read it again in that step — so no gradient tree is ever held."""
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
