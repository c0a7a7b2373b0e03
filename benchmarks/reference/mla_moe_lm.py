"""Plain reference of a latent-attention (MLA) mixture decoder with a leading
dense layer, a sigmoid router chosen through a correction bias, and a
multi-token-prediction module: written from the equations and independent of
``distlearn_tpu.models``: float32, matmul precision "highest", plain
``jax.numpy``, no kernels, no grouping of tokens.  ``x`` is a layer's input,
one row a position ``i``; ``h`` a head of ``H``; ``n(.; g)`` the RMS norm:

    x_0 = embed[tokens]
    u   = n(x; ln_1)
    cq  = n(u wq_a; q_norm)                    [q_rank]      q_h = cq wq_b[:, h]      [nope + rope] = [qn_h ; qr_h]
    [ckv ; kr] = u wkv_a                       [kv_rank + rope]
    [kn_h ; v_h] = n(ckv; kv_norm) wkv_b[:, h]                [nope + dv]
    qr_h, kr <- rope(., i):  the pair (2j, 2j+1) turned by the angle  i * theta^(-2j / rope),  j < rope/2
                             (neighbours: "interleaved"); kr is ONE head shared by all H
    q_h = [qn_h ; qr_h],   k_h = [kn_h ; kr]
    a_h = softmax(q_h k_h^T / sqrt(nope + rope) + causal) v_h                         [dv]
    x'  = x + concat_h(a_h) wo
    layer < first_dense:   y = x' + (silu(m w_gate) * (m w_up)) w_down,     m = n(x'; ln_2)
    else:   s = sigmoid(m router)  [E];   S = top-k of (s + router_bias);   w_e = scale * s_e / (sum_{S} s + 1e-20)
            y = x' + (silu(m ws_gate) * (m ws_up)) ws_down                  (the shared expert)
                   + sum over e in S and HELD of  w_e (silu(m we_gate_e) * (m we_up_e)) we_down_e
    g = n(x_depth; ln_f);      logits = g head                                (untied)
    module:  z_i = [ n(embed[t_{i+1}]; enorm) ; n(g_i; hnorm) ] eh_proj       (the embedding half first; the last
             z'  = MixtureLayer(z; the module's own block)                     position takes t_0: it predicts nothing)
             logits2_i = n(z'_i; norm) head                                    predicts t_{i+2}
    loss = mean_{i < L-1} CE(logits_i, t_{i+1})  +  weight * mean_{i < L-2} CE(logits2_i, t_{i+2})

    n(x; g) = x / sqrt(mean(x^2) + eps) * g,   eps = 1e-6

Departures from the published model, each shared with the system: this
holder keeps the experts ``held`` and what the others would add is left out;
the vocabulary is the slice held here; weights are seeded; the correction
bias is held at its seeded value (it enters the discrete choice alone: its
gradient is exactly zero, and the out-of-gradient rule that moves it between
steps is not built); no auxiliary balance loss.

Parameters: :class:`Params` — a dict ``{"embed": [V,D], "head": [D,V],
"ln_f": [D], "layers": [layer]*depth, "mtp": {"enorm", "hnorm", "eh_proj",
"block": layer, "norm"}}`` with the share and the sizes the arrays do not
show (``held``, ``top_k``, ``scale``, ``nope``, ``theta``, ``weight``)
beside it as static data.  A layer with a ``w_gate`` is dense.

Two ways through the same mathematics, as the other references: whole-model
(:func:`logits`, :func:`loss`) for ``jax.grad`` in the CPU tests, and LAYER
BY LAYER (:func:`layerwise_sgd_losses`) for the chip — the module one more
stage after the stack — where one program over all layers would outgrow the
compile cache's entries and the memory: one jitted function a KIND of layer
and its ``vjp``, the activations in a Python list.  Attention runs over
BLOCKS OF QUERIES (rematerialised), so the ``[H, L, L]`` scores of a 16k
sequence are never one array, the loss over blocks of positions, so neither
are the ``[L, V]`` logits, and the held experts one at a time in a scan.
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
    """The parameter dict with, as static data, the holder's share
    (``held``), the router's ``top_k`` and ``scale``, the un-rotated part of
    a head ``nope``, the rotation's ``theta`` and the module's loss
    ``weight``."""

    def __init__(self, tree: dict, held, top_k: int, scale: float, nope: int,
                 theta: float, weight: float):
        self.tree = tree
        self.held, self.top_k = tuple(held), int(top_k)
        self.scale, self.nope = float(scale), int(nope)
        self.theta, self.weight = float(theta), float(weight)

    @property
    def static(self):
        return (self.held, self.top_k, self.scale, self.nope, self.theta,
                self.weight)

    @property
    def layer_static(self):
        return dict(held=self.held, top_k=self.top_k, scale=self.scale,
                    nope=self.nope, theta=self.theta)

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
    """u [B, L, H, R] at positions 0..L-1: each pair of NEIGHBOURS
    ``(2j, 2j + 1)`` turned by ``position * theta ** (-2 j / R)`` — the head
    as ``R/2`` complex numbers, each times ``exp(i angle_j)``."""
    B, L, H, R = u.shape
    freq = jnp.float32(theta) ** (-jnp.arange(R // 2, dtype=jnp.float32)
                                  / (R // 2))
    angle = jnp.arange(L, dtype=jnp.float32)[:, None, None] * freq
    pairs = u.reshape(B, L, H, R // 2, 2)
    z = lax.complex(pairs[..., 0], pairs[..., 1]) \
        * lax.complex(jnp.cos(angle), jnp.sin(angle))
    return jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1).reshape(u.shape)


def attention(q, k, v):
    """q, k [B, L, H, Dqk], v [B, L, H, Dv]: the whole causal triangle, the
    scores over ``Dqk``.  A block of queries at a time against every key."""
    B, L, H, D = q.shape
    T = _divisor(L, _QUERY_BLOCK)
    j = jnp.arange(L)

    @jax.checkpoint
    def one_block(args):
        start, qb = args                                     # [B, T, H, D]
        allowed = j[None, :] <= (start + jnp.arange(T))[:, None]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(jnp.float32(D))
        p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    blocks = jnp.moveaxis(q.reshape(B, L // T, T, H, D), 1, 0)
    out = lax.map(one_block, (jnp.arange(0, L, T), blocks))
    return jnp.moveaxis(out, 0, 1).reshape(B, L, H, v.shape[-1])


def latent_attention(layer, x, nope, theta):
    """The mixer's output ``concat_h(a_h) wo`` for the layer's input x."""
    u = rmsnorm(x, layer["ln_1"])
    rank = layer["kv_norm"].shape[0]
    q = jnp.einsum("blr,rhd->blhd", rmsnorm(u @ layer["wq_a"],
                                            layer["q_norm"]), layer["wq_b"])
    ckv = u @ layer["wkv_a"]
    kv = jnp.einsum("blr,rhd->blhd", rmsnorm(ckv[..., :rank],
                                             layer["kv_norm"]),
                    layer["wkv_b"])
    kr = rope(ckv[:, :, None, rank:], theta)                 # one head
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.repeat(kr, q.shape[2], axis=2)], axis=-1)
    return jnp.einsum("blhd,hde->ble", attention(q, k, kv[..., nope:]),
                      layer["wo"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def experts(layer, m, held, top_k, scale):
    """The held experts' part for inputs ``m``: every held expert applied to
    EVERY row, weighted by the row's combine weight for it (zero where it
    was not chosen); a scan over the held experts, so the program holds one
    expert's products, not sixteen."""
    s = jax.nn.sigmoid(m @ layer["router"])
    _, chosen = lax.top_k(s + lax.stop_gradient(layer["router_bias"]), top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

    def add_one(y, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)
        return y + w_e * swiglu(m, w_gate, w_up, w_down), None

    y, _ = lax.scan(add_one, jnp.zeros_like(m), (
        jnp.asarray(held), layer["we_gate"], layer["we_up"],
        layer["we_down"]))
    return y


def block(layer, x, held, top_k, scale, nope, theta):
    h = x + latent_attention(layer, x, nope, theta)
    m = rmsnorm(h, layer["ln_2"])
    if "w_gate" in layer:                                    # a dense layer
        return h + swiglu(m, layer["w_gate"], layer["w_up"], layer["w_down"])
    return h + swiglu(m, layer["ws_gate"], layer["ws_up"], layer["ws_down"]) \
        + experts(layer, m, held, top_k, scale)


def head_logits(head, ln, x):
    return rmsnorm(x, ln) @ head


def head_loss(head, ln, x, tokens, ahead: int = 1):
    """Mean over the positions that have one of the loss of
    ``head_logits(x)_i`` against the token ``ahead`` further on, a block of
    positions at a time."""
    B, L, D = x.shape
    T = _divisor(L, _LOSS_BLOCK)
    target = jnp.roll(tokens, -ahead, axis=1)
    counted = jnp.arange(L) < L - ahead

    @jax.checkpoint
    def one_block(args):
        xb, tb, cb = args
        lp = jax.nn.log_softmax(head_logits(head, ln, xb), axis=-1)
        picked = jnp.take_along_axis(lp, tb[..., None], -1)[..., 0]
        return -jnp.sum(jnp.where(cb, picked, 0.0))

    cut = lambda a: jnp.moveaxis(                            # noqa: E731
        a.reshape((B, L // T, T) + a.shape[2:]), 1, 0)
    sums = lax.map(one_block, (cut(x), cut(target), counted.reshape(-1, T)))
    return jnp.sum(sums) / (B * (L - ahead))


def module_input(mtp, embed, ln_f, x, tokens):
    """``z``: the module's block's input from the stack's last state ``x``
    (un-normed: the final norm is applied here) and the next tokens."""
    e = embed[jnp.roll(tokens, -1, axis=1)]
    return jnp.concatenate([rmsnorm(e, mtp["enorm"]),
                            rmsnorm(rmsnorm(x, ln_f), mtp["hnorm"])],
                           axis=-1) @ mtp["eh_proj"]


def top_loss(top, x, tokens, weight, **static):
    """Everything after the stack, from its last state ``x``: the main loss
    and the module's, weighted.  ``top`` = ``{"embed", "head", "ln_f",
    "mtp"}``."""
    mtp = top["mtp"]
    main = head_loss(top["head"], top["ln_f"], x, tokens)
    z = block(mtp["block"], module_input(mtp, top["embed"], top["ln_f"], x,
                                         tokens), **static)
    return main + weight * head_loss(top["head"], mtp["norm"], z, tokens, 2)


# ------------------------------------------------------------ whole model --

def _through(params: Params, tokens):
    x = params.tree["embed"][tokens]
    for layer in params.tree["layers"]:
        x = block(layer, x, **params.layer_static)
    return x


def _top(params: Params):
    return {k: params.tree[k] for k in ("embed", "head", "ln_f", "mtp")}


@_highest
def logits(params: Params, tokens):
    """The main model's logits (the module is a training objective)."""
    return head_logits(params.tree["head"], params.tree["ln_f"],
                       _through(params, tokens))


@_highest
def module_logits(params: Params, tokens):
    p = params.tree
    z = block(p["mtp"]["block"], module_input(
        p["mtp"], p["embed"], p["ln_f"], _through(params, tokens), tokens),
        **params.layer_static)
    return head_logits(p["head"], p["mtp"]["norm"], z)


@_highest
def loss(params: Params, tokens):
    return top_loss(_top(params), _through(params, tokens), tokens,
                    params.weight, **params.layer_static)


# --------------------------------------------------------- layer by layer --

_STATIC = ("held", "top_k", "scale", "nope", "theta")
_block = jax.jit(_highest(block), static_argnames=_STATIC)


@functools.partial(jax.jit, static_argnames=_STATIC)
@_highest
def _block_vjp(layer, x, dy, **static):
    _, pull = jax.vjp(lambda l, a: block(l, a, **static), layer, x)
    return pull(dy)                                   # (d layer, d x)


_top_loss = jax.jit(_highest(top_loss), static_argnames=_STATIC + ("weight",))


@functools.partial(jax.jit, static_argnames=_STATIC + ("weight",))
@_highest
def _top_loss_grad(top, x, tokens, weight, **static):
    """The module is the stage after the stack: its block's vjp, the two
    losses and the embedding's second use in one program of one layer's
    size."""
    return jax.value_and_grad(
        lambda t, a: top_loss(t, a, tokens, weight, **static),
        argnums=(0, 1))(top, x)


_embed = jax.jit(lambda embed, tokens: embed[tokens])


@jax.jit
def _embed_vjp(embed, tokens, dx):
    return jax.vjp(lambda e: e[tokens], embed)[1](dx)[0]


_scale = jax.jit(lambda g, w: jax.tree_util.tree_map(lambda v: w * v, g),
                 donate_argnums=(0,))
_axpy = jax.jit(lambda a, b, w: jax.tree_util.tree_map(
    lambda u, v: u + w * v, a, b), donate_argnums=(0,))
_add = jax.jit(lambda a, b: a + b, donate_argnums=(0,))


def _forward(params: Params, tokens, keep: bool):
    x = _embed(params.tree["embed"], tokens)
    acts = [x]
    for layer in params.tree["layers"]:
        x = _block(layer, x, **params.layer_static)
        if keep:
            acts.append(x)
    return x, acts


def layerwise_loss(params: Params, tokens, micro: int = 1):
    total = 0.0
    for i in range(0, tokens.shape[0], micro):
        t = tokens[i:i + micro]
        x, _ = _forward(params, t, keep=False)
        total += float(_top_loss(_top(params), x, t, params.weight,
                                 **params.layer_static)) * t.shape[0]
    return total / tokens.shape[0]


def _backward(params: Params, t, sink):
    """Loss of the micro-batch ``t``; every gradient goes to
    ``sink(where, grad)`` the moment it is made (``where``: a layer's index,
    or ``"top"`` for ``{"embed", "head", "ln_f", "mtp"}``), last layer
    first."""
    p = params.tree
    static = params.layer_static
    x, acts = _forward(params, t, keep=True)
    l, (d_top, dx) = _top_loss_grad(_top(params), x, t, params.weight,
                                    **static)
    for n in reversed(range(len(p["layers"]))):
        d_layer, dx = _block_vjp(p["layers"][n], acts[n], dx, **static)
        acts[n + 1] = None
        sink(n, d_layer)
        del d_layer
    # the embedding is read twice: by the module (in d_top) and by the stack
    d_top["embed"] = _add(d_top["embed"], _embed_vjp(p["embed"], t, dx))
    sink("top", d_top)
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
