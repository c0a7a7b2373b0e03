"""Plain reference of a decoder with hyper-connected residual streams, gated
delta-rule (KDA) layers with a bounded decay gate, DeepSeek sparse attention
over NoPE latent attention every few layers, a leading dense layer and a
sigmoid-routed mixture of clamped-SwiGLU experts beside a shared one:
written from the equations and independent of ``distlearn_tpu.models``:
float32, matmul precision "highest", plain ``jax.numpy``, no kernels, no
chunking of the recurrence, no grouping of tokens, no absorption.  ``X`` is
a layer's input, ``n`` streams of ``C`` a position; ``n(.; g)`` the RMS norm:

    X_0 = [embed[tokens]] * n                      (every stream the embedding)
    each sublayer F of a layer (the mixer, then the FFN):
        x^ = n(vec X; 1)                            over n C
        h_pre = sigmoid(alpha_0 x^ phi_pre + b_pre);  h_post = 2 sigmoid(alpha_1 x^ phi_post + b_post)
        H_res = Sinkhorn(exp(alpha_2 mat(x^ phi_res) + b_res))  (rows then columns / (sum + hc_eps), iters times)
        X'_i = sum_j H_res_ij X_j + h_post_i F(sum_i h_pre_i X_i)
    mixer F(x), u = n(x; ln_1):
      KDA:  q~, k~, v~ = silu(conv(u wq)), silu(conv(u wk)), silu(conv(u wv))
            q = l2(q~) / sqrt(K),  k = l2(k~)
            alpha_t = exp(lb sigmoid(exp(a_log_h) ((u wa1) wa2 + dt_bias)))      lb < 0
            beta_t  = sigmoid(u wb)
            S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,  o_t = S_t^T q_t
            out = (sigmoid((u wg1) wg2) * n(o; o_norm)) wo                       (norm per head)
      DSA:  cq = n(u wq_a; q_norm);  q_h = cq wq_b[:, h];  c = n(u wkv_a; kv_norm)
            k_h = c wkv_b[:, h, :dk];  v_h = c wkv_b[:, h, dk:]
            qI_j = cq idx_wq[:, j];  kI = LN(u idx_wk; idx_k_scale, idx_k_bias);  w_j = (u idx_w)_j / sqrt(J)
            I[t, b] = sum_j w_tj relu(qI_tj . mean(kI_{P b .. P b + P - 1})) / sqrt(dI)     b < t // P
            S_t = block t // P and the first min(topk / P - 1, t // P) earlier blocks of I[t] sorted down
            a_h,t = softmax_{s in S_t, s <= t}(q_h,t . k_h,s / sqrt(dk)) v_h,s;   out = sum_h a_h wo[h]
    FFN F(x), m = n(x; ln_2):
      dense:  swiglu(m; w_gate, w_up, w_down)
      else:   s = sigmoid(m router);  S = top-k of (s + router_bias);  w_e = scale s_e / (sum_S s + 1e-20)
              swiglu(m; ws_*) + sum over e in S and HELD of w_e swiglu(m; we_*[e])
      swiglu(m; g, u, d) = (silu(min(m g, c)) * clip(m u, -c, c)) d          c = swiglu_limit
    logits = n(sum_i X_depth,i; ln_f) head             (untied)
    loss = mean over batch and positions 0..L-2 of -log softmax(logits)[next]

Departures from the published model, each shared with the system: this
holder keeps the layers, heads and experts of its share, and what the others
would add is left out; the vocabulary is the slice held here; weights are
seeded; the correction bias is held at its seeded value; the indexer gets no
gradient (no KL objective); no auxiliary balance loss.

Parameters: :class:`Params` — a dict ``{"embed": [V,C], "head": [C,V],
"ln_f": [C], "layers": [layer]*depth}`` with the share and the sizes the
arrays do not show beside it as static data.  A layer with ``a_log`` is a
KDA layer, one with ``idx_wq`` a sparse one; one with ``w_gate`` is dense.

Two ways through the same mathematics, as the other references: whole-model
(:func:`logits`, :func:`loss`) for ``jax.grad`` in the CPU tests, and LAYER
BY LAYER (:func:`layerwise_sgd_losses`) for the chip: one jitted function a
KIND of layer and its ``vjp``, the activations in a Python list, each
sublayer and each held expert rematerialised, the recurrence's state stored
once a block of positions, the sparse attention
and its indexer a block of queries at a time (rematerialised), the loss a
block of positions at a time, and the held experts one at a time in a scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 256
_LOSS_BLOCK = 2048
_SCAN_BLOCK = 64


@jax.tree_util.register_pytree_node_class
class Params:
    """The parameter dict with, as static data: the layers' kinds
    (``types``) and how many lead with a dense FFN (``dense``), the share
    (``held``), the router's ``top_k`` and ``scale``, the SwiGLU's ``limit``,
    the KDA gate's lower bound ``lb``, the indexer's ``pool`` and ``topk``,
    Sinkhorn's ``iters`` and ``hc_eps``, the norms' ``eps``."""

    _NAMES = ("types", "dense", "held", "top_k", "scale", "limit", "lb",
              "pool", "topk", "iters", "hc_eps", "eps")

    def __init__(self, tree: dict, types, dense, held, top_k, scale, limit,
                 lb, pool, topk, iters, hc_eps, eps):
        self.tree = tree
        self.static = (tuple(types), int(dense), tuple(held), int(top_k),
                       float(scale), float(limit), float(lb), int(pool),
                       int(topk), int(iters), float(hc_eps), float(eps))

    @property
    def layer_static(self):
        s = dict(zip(self._NAMES, self.static))
        del s["types"], s["dense"]
        return s

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


def _divisor(n: int, most: int, step: int = 1) -> int:
    return next(t for t in range(min(n, most), 0, -1)
                if n % t == 0 and t % step == 0)


def rmsnorm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def layernorm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


# ------------------------------------------------------ hyper-connection --

def sinkhorn(m, iters, eps):
    def round_(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return lax.fori_loop(0, iters, round_, m)


def hc_maps(hc, X, iters, hc_eps, eps):
    """``(h_pre [B, L, n], h_post [B, L, n], H_res [B, L, n, n])``."""
    B, L, n, C = X.shape
    xh = rmsnorm(X.reshape(B, L, n * C), 1.0, eps)
    z = xh @ hc["phi"]
    a = hc["alpha"]
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + hc["b_pre"])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + hc["b_post"])
    res = sinkhorn(jnp.exp(a[2] * z[..., 2 * n:].reshape(B, L, n, n)
                           + hc["b_res"]), iters, hc_eps)
    return pre, post, res


def sublayer(hc, X, F, iters, hc_eps, eps):
    pre, post, res = hc_maps(hc, X, iters, hc_eps, eps)
    y = F(jnp.einsum("bln,blnc->blc", pre, X))
    return jnp.einsum("blij,bljc->blic", res, X) + post[..., None] * y[:, :, None]


# ------------------------------------------------------------------ KDA --

def conv(u, c):
    """u [B, L, C], c [W, C]: causal depthwise convolution over time."""
    W, L = c.shape[0], u.shape[1]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(pad[:, j:j + L] * c[j] for j in range(W))


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

    T = _divisor(L, _SCAN_BLOCK)

    @jax.checkpoint
    def block(S, xs):
        return lax.scan(step, S, xs)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((L // T, T) + a.shape[:1]
                                             + a.shape[2:])
               for a in (q, k, v, alpha, beta))
    _, o = lax.scan(block, jnp.zeros((B, H, K, V), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(L, B, H, V), 0, 1)


def mix_kda(layer, u, lb, eps):
    B, L, _ = u.shape
    H, K = layer["dt_bias"].shape
    heads = lambda a: a.reshape(B, L, H, -1)                 # noqa: E731
    q = heads(jax.nn.silu(conv(u @ layer["wq"], layer["conv_q"])))
    k = heads(jax.nn.silu(conv(u @ layer["wk"], layer["conv_k"])))
    v = heads(jax.nn.silu(conv(u @ layer["wv"], layer["conv_v"])))
    q, k = l2norm(q) / jnp.sqrt(jnp.float32(K)), l2norm(k)
    pre = heads((u @ layer["wa1"]) @ layer["wa2"]) + layer["dt_bias"]
    alpha = jnp.exp(lb * jax.nn.sigmoid(jnp.exp(layer["a_log"])[:, None]
                                        * pre))
    beta = jax.nn.sigmoid(u @ layer["wb"])
    o = rmsnorm(delta_rule(q, k, v, alpha, beta), layer["o_norm"], eps)
    gate = jax.nn.sigmoid((u @ layer["wg1"]) @ layer["wg2"])
    return (gate * o.reshape(B, L, H * K)) @ layer["wo"]


# ------------------------------------------------------------------ DSA --

def selection(I, t, pool, topk):
    """bool [B, T, N]: block t // pool, and of the earlier blocks (those
    ``I`` holds finite) the first ``topk / pool - 1`` in ``I``'s order,
    highest first."""
    N = I.shape[-1]
    order = jnp.argsort(-I, axis=-1)                     # -inf sorts last
    rank = jnp.argsort(order, axis=-1)                   # a block's place
    earlier = jnp.arange(N)[None, :] < (t // pool)[:, None]
    return (earlier & (rank < topk // pool - 1)) \
        | (jnp.arange(N)[None, :] == (t // pool)[:, None])


def sparse_attention(q, k, v, qI, w, kI, pool, topk):
    """q, k [B, L, H, dk], v [B, L, H, dv]; indexer qI [B, L, J, dI], w [B,
    L, J], kI [B, L, dI].  A block of queries at a time against every key,
    the mask the selection's."""
    B, L, H, dk = q.shape
    T = _divisor(L, _QUERY_BLOCK, pool)
    kbar = kI.reshape(B, L // pool, pool, -1).mean(axis=2)
    pos = jnp.arange(L)

    @jax.checkpoint
    def one_block(args):
        start, qb, qIb, wb = args
        t = start + jnp.arange(T)
        I = jnp.einsum("btj,btjn->btn", wb, jax.nn.relu(
            jnp.einsum("btjd,bnd->btjn", qIb, kbar))) \
            / jnp.sqrt(jnp.float32(qI.shape[-1]))
        I = jnp.where(jnp.arange(L // pool)[None, :] < (t // pool)[:, None],
                      I, -jnp.inf)
        sel = lax.stop_gradient(selection(I, t, pool, topk))
        allowed = jnp.repeat(sel, pool, axis=-1) \
            & (pos[None, :] <= t[:, None])
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(jnp.float32(dk))
        p = jax.nn.softmax(jnp.where(allowed[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    cut = lambda a: jnp.moveaxis(                            # noqa: E731
        a.reshape((B, L // T, T) + a.shape[2:]), 1, 0)
    qI, w, kbar = (lax.stop_gradient(a) for a in (qI, w, kbar))
    out = lax.map(one_block, (jnp.arange(0, L, T), cut(q), cut(qI), cut(w)))
    return jnp.moveaxis(out, 0, 1).reshape(B, L, H, v.shape[-1])


def mix_dsa(layer, u, pool, topk, eps):
    dk = layer["wq_b"].shape[2]
    J = layer["idx_w"].shape[1]
    cq = rmsnorm(u @ layer["wq_a"], layer["q_norm"], eps)
    q = jnp.einsum("blr,rhd->blhd", cq, layer["wq_b"])
    c = rmsnorm(u @ layer["wkv_a"], layer["kv_norm"], eps)
    k = jnp.einsum("blr,rhd->blhd", c, layer["wkv_b"][..., :dk])
    v = jnp.einsum("blr,rhd->blhd", c, layer["wkv_b"][..., dk:])
    qI = jnp.einsum("blr,rjd->bljd", cq, layer["idx_wq"])
    kI = layernorm(u @ layer["idx_wk"], layer["idx_k_scale"],
                   layer["idx_k_bias"], eps)
    w = (u @ layer["idx_w"]) / jnp.sqrt(jnp.float32(J))
    a = sparse_attention(q, k, v, qI, w, kI, pool, topk)
    return jnp.einsum("blhd,hde->ble", a, layer["wo"])


# ------------------------------------------------------------------ FFN --

def swiglu(x, w_gate, w_up, w_down, limit):
    return (jax.nn.silu(jnp.minimum(x @ w_gate, limit))
            * jnp.clip(x @ w_up, -limit, limit)) @ w_down


def experts(layer, m, held, top_k, scale, limit):
    """The held experts' part for inputs ``m``: every held expert applied to
    EVERY row, weighted by the row's combine weight for it (zero where it
    was not chosen); a scan over the held experts."""
    s = jax.nn.sigmoid(m @ layer["router"])
    _, chosen = lax.top_k(s + lax.stop_gradient(layer["router_bias"]), top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

    def add_one(y, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)
        return y + w_e * swiglu(m, w_gate, w_up, w_down, limit), None

    y, _ = lax.scan(jax.checkpoint(add_one), jnp.zeros_like(m), (
        jnp.asarray(held), layer["we_gate"], layer["we_up"],
        layer["we_down"]))
    return y


def block(layer, X, held, top_k, scale, limit, lb, pool, topk, iters,
          hc_eps, eps):
    def mixer(x):
        u = rmsnorm(x, layer["ln_1"], eps)
        if "a_log" in layer:
            return mix_kda(layer, u, lb, eps)
        return mix_dsa(layer, u, pool, topk, eps)

    def ffn(x):
        m = rmsnorm(x, layer["ln_2"], eps)
        if "w_gate" in layer:
            return swiglu(m, layer["w_gate"], layer["w_up"], layer["w_down"],
                          limit)
        return swiglu(m, layer["ws_gate"], layer["ws_up"], layer["ws_down"],
                      limit) + experts(layer, m, held, top_k, scale, limit)

    # each sublayer rematerialised: a layer's pull-back holds one
    # sublayer's intermediates at a time
    X = jax.checkpoint(lambda X: sublayer(layer["hc_mix"], X, mixer, iters,
                                          hc_eps, eps))(X)
    return jax.checkpoint(lambda X: sublayer(layer["hc_ffn"], X, ffn, iters,
                                             hc_eps, eps))(X)


def streams(embed, tokens, n):
    x = embed[tokens]
    return jnp.broadcast_to(x[:, :, None], x.shape[:2] + (n, x.shape[-1]))


def head_loss(head, ln, X, tokens, eps):
    """Mean over the positions that have one of the loss of the logits of
    the summed streams against the next token, a block of positions at a
    time."""
    x = jnp.sum(X, axis=2)
    B, L, D = x.shape
    T = _divisor(L, _LOSS_BLOCK)
    target = jnp.roll(tokens, -1, axis=1)
    counted = jnp.arange(L) < L - 1

    @jax.checkpoint
    def one_block(args):
        xb, tb, cb = args
        lp = jax.nn.log_softmax(rmsnorm(xb, ln, eps) @ head, axis=-1)
        picked = jnp.take_along_axis(lp, tb[..., None], -1)[..., 0]
        return -jnp.sum(jnp.where(cb, picked, 0.0))

    cut = lambda a: jnp.moveaxis(                            # noqa: E731
        a.reshape((B, L // T, T) + a.shape[2:]), 1, 0)
    sums = lax.map(one_block, (cut(x), cut(target), counted.reshape(-1, T)))
    return jnp.sum(sums) / (B * (L - 1))


# ------------------------------------------------------------ whole model --

def _n(params: Params) -> int:
    return params.tree["layers"][0]["hc_mix"]["b_pre"].shape[0]


def _through(params: Params, tokens):
    X = streams(params.tree["embed"], tokens, _n(params))
    for layer in params.tree["layers"]:
        X = block(layer, X, **params.layer_static)
    return X


@_highest
def logits(params: Params, tokens):
    p = params.tree
    x = jnp.sum(_through(params, tokens), axis=2)
    return rmsnorm(x, p["ln_f"], params.layer_static["eps"]) @ p["head"]


@_highest
def loss(params: Params, tokens):
    p = params.tree
    return head_loss(p["head"], p["ln_f"], _through(params, tokens), tokens,
                     params.layer_static["eps"])


# --------------------------------------------------------- layer by layer --

_STATIC = ("held", "top_k", "scale", "limit", "lb", "pool", "topk", "iters",
           "hc_eps", "eps")
_block = jax.jit(_highest(block), static_argnames=_STATIC)


@functools.partial(jax.jit, static_argnames=_STATIC)
@_highest
def _block_vjp(layer, X, dY, **static):
    _, pull = jax.vjp(lambda l, a: block(l, a, **static), layer, X)
    return pull(dY)                                   # (d layer, d X)


@functools.partial(jax.jit, static_argnames=("eps",))
@_highest
def _top_loss(top, X, tokens, eps):
    return head_loss(top["head"], top["ln_f"], X, tokens, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
@_highest
def _top_loss_grad(top, X, tokens, eps):
    return jax.value_and_grad(
        lambda t, a: head_loss(t["head"], t["ln_f"], a, tokens, eps),
        argnums=(0, 1))(top, X)


_embed = jax.jit(streams, static_argnums=(2,))


@jax.jit
def _embed_vjp(embed, tokens, dX):
    return jax.vjp(lambda e: streams(e, tokens, dX.shape[2]), embed)[1](dX)[0]


_axpy = jax.jit(lambda a, b, w: jax.tree_util.tree_map(
    lambda u, v: u + w * v, a, b), donate_argnums=(0,))
_scale = jax.jit(lambda g, w: jax.tree_util.tree_map(lambda v: w * v, g),
                 donate_argnums=(0,))


def _top(params: Params):
    return {k: params.tree[k] for k in ("head", "ln_f")}


def _forward(params: Params, tokens, keep: bool):
    X = _embed(params.tree["embed"], tokens, _n(params))
    acts = [X]
    for layer in params.tree["layers"]:
        X = _block(layer, X, **params.layer_static)
        if keep:
            acts.append(X)
    return X, acts


def layerwise_loss(params: Params, tokens, micro: int = 1):
    total = 0.0
    eps = params.layer_static["eps"]
    for i in range(0, tokens.shape[0], micro):
        t = tokens[i:i + micro]
        X, _ = _forward(params, t, keep=False)
        total += float(_top_loss(_top(params), X, t, eps)) * t.shape[0]
    return total / tokens.shape[0]


def _backward(params: Params, t, sink):
    """Loss of the micro-batch ``t``; every gradient goes to ``sink(where,
    grad)`` the moment it is made (``where``: a layer's index, ``"top"``
    for ``{"head", "ln_f"}``, ``"embed"``), last layer first."""
    p = params.tree
    static = params.layer_static
    X, acts = _forward(params, t, keep=True)
    l, (d_top, dX) = _top_loss_grad(_top(params), X, t, static["eps"])
    sink("top", d_top)
    for n in reversed(range(len(p["layers"]))):
        d_layer, dX = _block_vjp(p["layers"][n], acts[n], dX, **static)
        acts[n + 1] = None
        sink(n, d_layer)
        del d_layer
    sink("embed", _embed_vjp(p["embed"], t, dX))
    return float(l)


def layerwise_loss_and_grads(params: Params, tokens, micro: int = 1):
    """Mean loss over ``tokens`` [B, L] and its gradient (a :class:`Params`),
    ``micro`` sequences at a time."""
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
    return total, Params(dict(got["top"], embed=got["embed"],
                              layers=[got[n] for n in range(depth)]),
                         *params.static)


def layerwise_sgd_losses(params: Params, tokens, lr: float, steps: int,
                         micro: int = 1):
    """``[loss(P_0), ..., loss(P_steps)]`` on the one batch ``tokens``, with
    ``P_{i+1} = P_i - lr * grad(P_i)`` — plain SGD.  ``params`` is consumed.
    Where the batch is one micro-batch (the chip's check) each layer is
    UPDATED the moment its gradient is made, so no gradient tree is held."""
    losses = []
    step = jnp.float32(-lr)
    for _ in range(steps):
        if tokens.shape[0] <= micro:
            p = params.tree

            def sink(where, g):
                if where == "top":
                    for name in g:
                        p[name] = _axpy(p[name], g[name], step)
                elif where == "embed":
                    p["embed"] = _axpy(p["embed"], g, step)
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
