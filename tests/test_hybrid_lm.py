"""The pattern LM (``models/hybrid.py``) and the expert layer that is told
which experts it holds (``parallel/ep.py``), at toy size on the CPU mesh:
the SHARE test (every holder's part plus the shared expert once is the whole
layer), no assignment ever dropped, the grouped product's hand-written
backward pass, the routing counters, the LM step builders driving the model
unchanged, and what is refused until it is written; the same for the other
pattern the constructor builds — ungated softmax layers, full-causal without
positions among rotary sliding-window ones, ReLU-gated experts with no shared
expert, the router reading the layer's input."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlearn_tpu import obs
from distlearn_tpu.models import hybrid_lm
from distlearn_tpu.models.hybrid import causal_conv, gqa_apply, moe_apply
from distlearn_tpu.models.transformer import lm_loss
from distlearn_tpu.parallel import ep
from distlearn_tpu.parallel.ep import (grouped_glu, moe_held_ffn,
                                       route_held)
from distlearn_tpu.train import build_lm_routing_metrics, build_lm_step

N, D, F, E, K = 96, 16, 24, 16, 4


def _layer(seed=0, held=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = lambda i, *s: jax.random.normal(ks[i], s, jnp.float32)  # noqa: E731
    return (n(0, N, D), n(1, D, E), n(2, held, D, F) / 4, n(3, held, D, F) / 4,
            n(4, held, F, D) / 5)


_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _masked_loop(x, router, wg, wu, wd, held, act="silu", route_from=None):
    """The routed part by a loop over the held experts with masks: the plain
    formula, which autodiff differentiates."""
    s = jax.nn.softmax((x if route_from is None else route_from) @ router,
                       axis=-1)
    top, chosen = jax.lax.top_k(s, K)
    w = top / top.sum(-1, keepdims=True)
    y = 0.0
    for j, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1, keepdims=True)
        y = y + w_e * ((_ACTS[act](x @ wg[j]) * (x @ wu[j])) @ wd[j])
    return y


@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("tile", [8, 32])
def test_grouped_product_and_its_backward_pass_are_the_masked_loop(
        monkeypatch, tile, act):
    # the layer's one tile height is a module constant; small here so that
    # an expert's assignments span several tiles
    monkeypatch.setattr(ep, "GROUP_TILE", tile)
    held = (3, 5, 6, 11)
    args = _layer()

    def system(x, router, wg, wu, wd):
        return moe_held_ffn(x, router, (wg, wu, wd), held, K, act=act)[0]

    np.testing.assert_allclose(system(*args), _masked_loop(*args, held, act),
                               rtol=1e-5, atol=1e-6)
    c = jnp.cos(jnp.arange(N * D, dtype=jnp.float32).reshape(N, D))
    got = jax.grad(lambda *a: jnp.sum(system(*a) * c),
                   argnums=tuple(range(5)))(*args)
    want = jax.grad(lambda *a: jnp.sum(_masked_loop(*a, held, act) * c),
                    argnums=tuple(range(5)))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("act", ["silu", "relu"])
def test_router_reads_another_array_than_the_experts(act):
    """``route_from``: scores, choice and combine weights come from one
    array, the experts' products from another; the gradient reaches BOTH —
    the routing source through the combine weights alone."""
    held = (3, 5, 6, 11)
    x, router, wg, wu, wd = _layer()
    src = jax.random.normal(jax.random.PRNGKey(9), x.shape, jnp.float32)

    def system(x, src, router, wg, wu, wd):
        return moe_held_ffn(x, router, (wg, wu, wd), held, K, act=act,
                            route_from=src)

    def plain(x, src, router, wg, wu, wd):
        return _masked_loop(x, router, wg, wu, wd, held, act, route_from=src)

    args = (x, src, router, wg, wu, wd)
    y, aux = system(*args)
    np.testing.assert_allclose(y, plain(*args), rtol=1e-5, atol=1e-6)
    # the counters are the routing source's: those of route_held on it
    np.testing.assert_array_equal(
        aux["assignments"], route_held(router, src, K, held)[2]["assignments"])
    assert not np.array_equal(
        aux["assignments"], route_held(router, x, K, held)[2]["assignments"])
    c = jnp.sin(jnp.arange(N * D, dtype=jnp.float32).reshape(N, D))
    got = jax.grad(lambda *a: jnp.sum(system(*a)[0] * c),
                   argnums=tuple(range(6)))(*args)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * c),
                    argnums=tuple(range(6)))(*args)
    assert float(jnp.abs(want[1]).max()) > 0        # the source has a gradient
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


#: the kernel path's cases: (chunk rows or None = the whole plan, held
#: experts, compute dtype).  With tiles of 8 rows the four held experts get
#: 18 to 34 of the 384 assignments: every expert's rows end inside a tile.
_KERNEL_CASES = {
    "the_whole_plan_in_one_chunk": (None, (3, 5, 6, 11), jnp.float32),
    "a_chunk_boundary_inside_an_expert": (24, (3, 5, 6, 11), jnp.float32),
    "an_expert_with_no_rows": (64, (3, 5, 16, 11), jnp.float32),
    "most_of_the_worst_case_unused": (32, (3, 5), jnp.float32),
    "operands_in_bfloat16": (64, (3, 5, 6, 11), jnp.bfloat16),
}


@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_kernel_path_is_the_loop(monkeypatch, case, act):
    """``grouped_glu(impl="gmm")`` — the packed rows through the grouped
    matmul kernels, in Pallas interpret mode here — against the ``"xla"``
    loop on one plan: the output and all five gradients."""
    chunk, held, cd = _KERNEL_CASES[case]
    monkeypatch.setattr(ep, "GROUP_TILE", 8)
    x, router, wg, wu, wd = _layer(held=len(held))
    # a seventeenth expert no token chooses: a constant feature scores it
    x = x.at[:, 0].set(1.0)
    router = jnp.concatenate(
        [router, jnp.zeros((D, 1)).at[0].set(-1e3)], axis=1)
    plan, slot_w, aux = route_held(router, x, K, held)
    counts = np.asarray(aux["assignments"])
    assert (counts[counts > 0] % 8).any()       # rows end inside a tile
    if case == "an_expert_with_no_rows":
        assert counts[2] == 0 and counts.sum() > 0
    if case == "most_of_the_worst_case_unused":
        assert int(plan[2]) * 8 * 2 < plan[0].shape[0]
    if chunk is not None:
        assert int(plan[2]) * 8 > chunk         # a second chunk runs
    c = jnp.cos(jnp.arange(N * D, dtype=jnp.float32).reshape(N, D))

    def run(impl, chunk):
        def loss(x, wg, wu, wd, slot_w):
            y = grouped_glu(x.astype(cd), wg, wu, wd, slot_w, plan, cd, act,
                            impl, chunk)
            return jnp.sum(y * c), y
        return jax.value_and_grad(loss, argnums=tuple(range(5)),
                                  has_aux=True)(x, wg, wu, wd, slot_w)

    with jax.enable_x64(False):     # as on the chip: Mosaic takes no int64
        (_, want_y), want = run("xla", None)
        (_, got_y), got = run("gmm", chunk)
    tol = dict(rtol=1e-5, atol=1e-5) if cd == jnp.float32 \
        else dict(rtol=0.05, atol=0.05)
    np.testing.assert_allclose(got_y, want_y, **tol)
    for a, b in zip(got, want):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, **tol)


@pytest.mark.parametrize("act", ["silu", "relu"])
def test_kernel_path_through_the_layer_with_route_from(monkeypatch, act):
    """``moe_held_ffn`` on the kernel path (its selection answered for the
    chip here) with the router reading another array: the loop's output,
    counters and six gradients."""
    held = (3, 5, 6, 11)
    monkeypatch.setattr(ep, "GROUP_TILE", 8)
    x, router, wg, wu, wd = _layer()
    src = jax.random.normal(jax.random.PRNGKey(9), x.shape, jnp.float32)
    c = jnp.sin(jnp.arange(N * D, dtype=jnp.float32).reshape(N, D))

    def run(impl):
        monkeypatch.setattr(ep, "select_grouped", lambda *a: impl)

        def loss(x, src, router, wg, wu, wd):
            y, aux = moe_held_ffn(x, router, (wg, wu, wd), held, K, act=act,
                                  route_from=src)
            return jnp.sum(y * c), (y, aux)
        return jax.value_and_grad(loss, argnums=tuple(range(6)),
                                  has_aux=True)(x, src, router, wg, wu, wd)

    before = ep.grouped_paths_traced()
    with jax.enable_x64(False):
        (_, (want_y, want_aux)), want = run("xla")
        (_, (got_y, got_aux)), got = run("gmm")
    after = ep.grouped_paths_traced()
    assert {k: after[k] - before.get(k, 0) for k in after} \
        == {"xla": 1, "gmm": 1}
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    for k in want_aux:
        np.testing.assert_array_equal(got_aux[k], want_aux[k])
    assert int(got_aux["dropped"]) == 0
    assert float(jnp.abs(want[1]).max()) > 0        # the source has a gradient
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


_BF16, _F32 = "bfloat16", "float32"


@pytest.mark.parametrize("backend,dtype,d,f,rows,path", [
    # the cell the kernel path was measured on: 16,384 x 6 / 64 rows an expert
    ("tpu", _BF16, 2560, 768, 1536, "gmm"),
    ("tpu", _BF16, 4096, 1280, 2048, "gmm"),
    ("tpu", _BF16, 128, 128, 256, "gmm"),
    # 7/8 of a tile and more: the kernels (glm-5.3-flash.train-8k: 8,192 x
    # 8 / 288 rows an expert)
    ("tpu", _BF16, 4096, 2048, 227, "gmm"),
    ("tpu", _BF16, 2560, 768, 224, "gmm"),
    # an expert expected to get well under a tile has its weights used once
    ("tpu", _BF16, 4096, 1280, 204, "xla"),
    ("tpu", _BF16, 2560, 768, 223, "xla"),
    ("tpu", _BF16, 2560, 768, 0, "xla"),
    # widths the kernel's lanes do not tile; a dtype never measured
    ("tpu", _BF16, 2560, 760, 1536, "xla"),
    ("tpu", _BF16, 2500, 768, 1536, "xla"),
    ("tpu", _BF16, 16, 24, 1536, "xla"),
    ("tpu", _F32, 2560, 768, 1536, "xla"),
    ("tpu", "float16", 2560, 768, 1536, "xla"),
    # the kernel is a TPU kernel
    ("cpu", _BF16, 2560, 768, 1536, "xla"),
    ("gpu", _BF16, 2560, 768, 1536, "xla"),
])
def test_select_grouped_table(monkeypatch, backend, dtype, d, f, rows, path):
    for name in ("DISTLEARN_TPU_MOE", "DISTLEARN_TPU_GMM"):
        monkeypatch.setenv(name, "gmm")         # no environment decides
    assert ep.select_grouped(backend, dtype, d, f, rows) == path
    assert ep.select_grouped(backend, jnp.dtype(dtype), d, f, rows) == path
    assert path in ep.GROUPED_IMPLS


def test_moe_grouped_counter_counts_the_resolved_path():
    """``moe_grouped_total{impl=}`` moves once per traced ``moe_held_ffn``
    call — not once per run of a jitted program — and on the CPU under
    ``xla``; a refused call counts nothing."""
    x, router, wg, wu, wd = _layer()
    before = ep.grouped_paths_traced()
    moe_held_ffn(x, router, (wg, wu, wd), (3, 5, 6, 11), K)
    jitted = jax.jit(lambda x: moe_held_ffn(
        x, router, (wg, wu, wd), (3, 5, 6, 11), K, act="relu")[0])
    jitted(x)
    jitted(x)                                              # traced once
    with pytest.raises(ValueError):
        moe_held_ffn(x, router, (wg, wu, wd), (3, 5, 6, 11), K, act="gelu")
    after = ep.grouped_paths_traced()
    assert after.get("xla", 0) - before.get("xla", 0) == 2
    assert after.get("gmm", 0) == before.get("gmm", 0)
    assert set(after) <= set(ep.GROUPED_IMPLS)


@pytest.mark.parametrize("backend,dtype,k,v,chunk,sub,path", [
    # the cell the kernels were measured on: 64 heads of 128, chunk 32 / 8
    ("tpu", _BF16, 128, 128, 32, 8, "kernel"),
    ("tpu", _BF16, 256, 128, 64, 16, "kernel"),
    ("tpu", _BF16, 128, 256, 16, 8, "kernel"),
    # widths the lanes do not tile; a dtype whose products the chip would
    # round to bfloat16 on the way
    ("tpu", _BF16, 64, 128, 32, 8, "xla"),
    ("tpu", _BF16, 128, 96, 32, 8, "xla"),
    ("tpu", _F32, 128, 128, 32, 8, "xla"),
    # a sub-block that is not whole vector registers; a chunk that does
    # not tile the kernels' 128 positions, or cuts them into too many
    ("tpu", _BF16, 128, 128, 32, 4, "xla"),
    ("tpu", _BF16, 128, 128, 48, 8, "xla"),
    ("tpu", _BF16, 128, 128, 256, 8, "xla"),
    ("tpu", _BF16, 128, 128, 8, 8, "xla"),
    # off the TPU the kernels would run interpreted
    ("cpu", _BF16, 128, 128, 32, 8, "xla"),
    ("gpu", _BF16, 128, 128, 32, 8, "xla"),
])
def test_select_delta_rule_table(monkeypatch, backend, dtype, k, v, chunk, sub,
                                 path):
    from distlearn_tpu.ops import delta_rule
    monkeypatch.setenv("DISTLEARN_TPU_DELTA_RULE", "kernel")  # none decides
    select = delta_rule.select_delta_rule
    assert select(backend, dtype, k, v, chunk, sub) == path
    assert select(backend, jnp.dtype(dtype), k, v, chunk, sub) == path
    assert path in delta_rule.DELTA_RULE_IMPLS


def test_delta_rule_counter_counts_the_resolved_path():
    """``delta_rule_total{impl=}`` moves once per traced
    ``chunked_delta_rule`` call: a step of three linear-attention layers
    that share one rematerialised wrapper, traced on the CPU, reads
    ``xla`` and nothing under ``kernel``; a refused call counts nothing."""
    from distlearn_tpu.ops import delta_rule
    mesh, model = _mesh(), _toy(remat="full")
    params, _ = model.init(jax.random.PRNGKey(0))
    before = delta_rule.delta_rule_paths_traced()
    step = build_lm_step(model, mesh, params, lr=0.05, donate=False)
    step(params, _tokens(mesh))
    step(params, _tokens(mesh))                            # traced once
    traced = {k: v - before.get(k, 0)
              for k, v in delta_rule.delta_rule_paths_traced().items()}
    assert traced.get("xla", 0) >= 1 and not traced.get("kernel", 0)
    again = delta_rule.delta_rule_paths_traced()
    with pytest.raises(ValueError, match="multiple of"):
        a = jnp.zeros((1, 2, 40, 8))
        delta_rule.chunked_delta_rule(a, a, a, a, a[..., 0])
    assert delta_rule.delta_rule_paths_traced() == again
    assert set(again) <= set(delta_rule.DELTA_RULE_IMPLS)


def test_the_shares_of_all_holders_add_up_to_the_whole_layer():
    """16 experts over 4 holders of 4: each holder's routed part, with the
    shared expert counted ONCE, is the uncut layer (every expert held by one
    caller) — what ties a chip's share to the model."""
    ks = jax.random.split(jax.random.PRNGKey(1), 10)
    n = lambda i, *s: jax.random.normal(ks[i], s, jnp.float32)  # noqa: E731
    blk = {"ln2": {"scale": 1.0 + 0.1 * n(0, D)}, "router": n(1, D, E),
           "ws_gate": n(2, D, F) / 4, "ws_up": n(3, D, F) / 4,
           "ws_down": n(4, F, D) / 5, "we_gate": n(5, E, D, F) / 4,
           "we_up": n(6, E, D, F) / 4, "we_down": n(7, E, F, D) / 5}
    x = n(8, 2, N // 2, D)
    whole, aux = moe_apply(blk, x, jnp.float32, 1e-5, tuple(range(E)), K,
                           None)
    assert int(aux["assignments"].sum()) == N * K
    assert float(aux["unheld_frac"]) == 0.0
    shared_only = dict(blk, **{k: jnp.zeros_like(blk[k][:1]) for k in
                               ("we_gate", "we_up", "we_down")})
    total = moe_apply(shared_only, x, jnp.float32, 1e-5, (0,), K, None)[0]
    seen = 0
    for holder in range(4):
        held = tuple(range(4 * holder, 4 * holder + 4))
        part = dict(blk, **{k: blk[k][4 * holder:4 * holder + 4] for k in
                            ("we_gate", "we_up", "we_down")},
                    ws_down=jnp.zeros_like(blk["ws_down"]))
        y, a = moe_apply(part, x, jnp.float32, 1e-5, held, K, None)
        total = total + (y - x)             # the routed part alone
        seen += int(a["assignments"].sum())
        assert int(a["dropped"]) == 0
    assert seen == N * K                    # every assignment has a holder
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)


def test_nothing_is_dropped_when_the_router_collapses(monkeypatch):
    """A router forced onto one held expert: all N tokens land on it (a
    capacity bucket would drop most of them), every one is computed."""
    monkeypatch.setattr(ep, "GROUP_TILE", 8)
    x, _, wg, wu, wd = _layer()
    held = (3, 5, 6, 11)
    router = jnp.zeros((D, E)).at[:, 5].set(1.0)
    x = jnp.abs(x) + 0.1                    # positive scores for expert 5
    plan, slot_w, aux = route_held(router, x, K, held)
    assert int(aux["assignments"][1]) == N and int(aux["dropped"]) == 0
    assert int(plan[2]) >= N // 8
    y = moe_held_ffn(x, router, (wg, wu, wd), held, K)[0]
    np.testing.assert_allclose(y, _masked_loop(x, router, wg, wu, wd, held),
                               rtol=1e-5, atol=1e-6)
    assert bool(jnp.all(jnp.abs(y).sum(-1) > 0))    # no token lost


def test_held_layer_refuses_what_is_not_written():
    x, router, wg, wu, wd = _layer()
    with pytest.raises(NotImplementedError, match="exchange"):
        moe_held_ffn(x, router, (wg, wu, wd), (0, 1, 2, 3), K,
                     ep_axis="data")
    for bad in ((0, 0, 1, 2), (0, 1, 2, E), ()):
        with pytest.raises(ValueError, match="do not fit"):
            route_held(router, x, K, bad)
    with pytest.raises(ValueError, match="do not fit"):
        route_held(router, x, E + 1, (0, 1, 2, 3))
    with pytest.raises(ValueError, match="act must be one of"):
        moe_held_ffn(x, router, (wg, wu, wd), (0, 1, 2, 3), K, act="gelu")
    assert grouped_glu.__name__ == "grouped_glu"


def test_causal_conv_sees_no_future():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 5), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (4, 5), jnp.float32)
    y = causal_conv(x, w)
    want = sum(w[j] * jnp.pad(x, ((0, 0), (3, 0), (0, 0)))[:, j:j + 12]
               for j in range(4))
    np.testing.assert_allclose(y, want, rtol=1e-6)
    bumped = causal_conv(x.at[:, 7].add(1.0), w)
    np.testing.assert_array_equal(np.asarray(bumped[:, :7]),
                                  np.asarray(y[:, :7]))


# ------------------------------------------------------------- the model --

def _toy(**kw):
    kw = dict(dict(vocab=97, dim=32, layer_types=("gqa", "kda", "kda", "kda"),
                   heads=4, kv_heads=2, head_dim=8, kda_heads=4,
                   kda_head_dim=8, n_routed_experts=16,
                   held_experts=(1, 5, 6, 11), experts_per_tok=4,
                   expert_width=24, max_len=64), **kw)
    return hybrid_lm(**kw)


def _swa_toy(**kw):
    """The other pattern: [full, window, window, window], no shared expert,
    ReLU gates, the router on the layer's input; a band of 16 of 64."""
    kw = dict(dict(vocab=97, dim=32,
                   layer_types=("full", "window", "window", "window"),
                   heads=4, kv_heads=2, head_dim=8, window=16,
                   rope_theta=1.5e6, n_routed_experts=16,
                   held_experts=(1, 5, 6, 11), experts_per_tok=3,
                   expert_width=24, n_shared_experts=0, expert_act="relu",
                   router_input="layer_input", eps=1e-6, max_len=64), **kw)
    return hybrid_lm(**kw)


def _mla_toy(**kw):
    """The third pattern: latent attention (12-wide scores = 8 un-rotated +
    4 rotated by neighbouring pairs, 8-wide values, low-rank q and K/V
    paths), layer 0 dense, then mixtures scored by sigmoid beside a shared
    expert, and the prediction module after the stack."""
    kw = dict(dict(vocab=97, dim=32, layer_types=("mla",) * 3, heads=4,
                   kv_heads=4, head_dim=12, q_lora_rank=24, kv_lora_rank=16,
                   qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                   rope_theta=3.2e7, rope_pairing="interleaved",
                   dense_layers=1, dense_width=40, n_routed_experts=16,
                   held_experts=(1, 5, 6, 11), experts_per_tok=3,
                   expert_width=24, router_score="sigmoid", routed_scale=2.5,
                   mtp_depth=1, mtp_weight=0.3, eps=1e-6, max_len=64), **kw)
    return hybrid_lm(**kw)


_TOYS = {"gqa+kda": _toy, "full+window": _swa_toy, "mla": _mla_toy}


def _mesh(shape=(2, 1, 1)):
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape),
                ("data", "seq", "model"))


def _tokens(mesh, b=4, L=64):
    return jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (b, L), 0, 97, jnp.int32),
        NamedSharding(mesh, P("data", "seq")))


@pytest.mark.parametrize("pattern", sorted(_TOYS))
@pytest.mark.parametrize("remat", [False, "full"])
def test_build_lm_step_drives_the_hybrid_model_unchanged(remat, pattern):
    """Same builder, same call: two data-parallel steps are the gradient
    steps of ``lm_loss`` on the whole batch, and the loss falls."""
    mesh, model = _mesh(), _TOYS[pattern](remat=remat)
    params, _ = jax.jit(model.init)(jax.random.PRNGKey(0))
    tokens = _tokens(mesh)
    step = build_lm_step(model, mesh, params, lr=0.05, donate=False)
    # the plain loss and gradient, one compiled program (op by op, the
    # toy's loops and scans took most of the test's time)
    whole, grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(model, p, tokens)))(params)
    want = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, params, grads)
    got, loss = step(params, tokens)
    assert float(loss) == pytest.approx(float(whole), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)
    _, after = step(got, tokens)
    assert float(after) < float(loss)


def _remat_toy(monkeypatch, wrap, on_the_kernel=True):
    """The rematerialised toy at a length and head size the blockwise kernel
    takes (128, 64).  ``on_the_kernel`` steers its softmax layer onto that
    kernel as the chip would (interpreted here); ``wrap="bare"`` swaps the
    constructor's ``checkpoint_block`` for the bare ``jax.checkpoint`` it
    replaced."""
    from distlearn_tpu.models import hybrid
    from distlearn_tpu.parallel import sequence
    if on_the_kernel:
        monkeypatch.setattr(sequence, "select_attention",
                            lambda *a: "splash")
    if wrap == "bare":
        monkeypatch.setattr(hybrid, "checkpoint_block", jax.checkpoint)
    return _toy(head_dim=64, max_len=128, remat="full")


def _remat_swa_toy(monkeypatch, wrap):
    """One full and one windowed layer (a band of 48 of 128: the kernel's
    ``LocalMask``) on the blockwise kernel, rematerialised."""
    _remat_toy(monkeypatch, wrap)
    return _swa_toy(layer_types=("full", "window"), head_dim=64, window=48,
                    max_len=128, remat="full")


@pytest.mark.parametrize("pattern,wrap,calls", [
    ("gqa+kda", "named", 2), ("gqa+kda", "bare", 3),
    ("full+window", "named", 4), ("full+window", "bare", 6)])
def test_rematerialised_gqa_layer_runs_the_forward_kernel_once(
        monkeypatch, pattern, wrap, calls):
    """The grouped-query call of a softmax layer: forward and backward
    kernel in the gradient, no third call in the recomputation (the bare
    ``jax.checkpoint`` had one); the linear-attention layers hold none.  A
    WINDOWED call keeps its residuals under the same name, so it is two
    calls a layer as the full one."""
    from tests.program_util import pallas_calls
    model = (_remat_toy if pattern == "gqa+kda" else _remat_swa_toy)(
        monkeypatch, wrap)
    params = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda p: lm_loss(model, p, tokens)))(params)
    assert pallas_calls(jaxpr) == calls


@pytest.mark.parametrize("on_the_kernel", [True, False],
                         ids=["splash", "xla"])
def test_rematerialised_layers_are_bitwise_the_bare_checkpoints(
        monkeypatch, on_the_kernel):
    """Loss and parameters after one ``build_lm_step`` are bitwise those of
    the bare ``jax.checkpoint`` layers, with the softmax layer on the
    kernel and on the full-square path."""
    mesh = _mesh()
    tokens = _tokens(mesh, L=128)

    def one_step(wrap):
        model = _remat_toy(monkeypatch, wrap, on_the_kernel)
        params, _ = model.init(jax.random.PRNGKey(0))
        return build_lm_step(model, mesh, params, lr=0.05,
                             donate=False)(params, tokens)

    got, loss = one_step("named")
    want, loss0 = one_step("bare")
    assert float(loss) == float(loss0)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", ["gqa", "kda", "full", "window", "mla"])
def test_a_layer_with_no_kernel_keeps_its_input_alone(monkeypatch, kind):
    """On the full-square path, and in a linear-attention layer, nothing
    carries the kernel's name: the policy finds none, and the rematerialised
    layer's gradient is the bare checkpoint's program text for text.  (One
    kind at a time: with two kinds the lowered module repeats some of the
    expert layer's small private functions — ``_where``, ``cumsum`` — a
    different number of times under the two checkpoints.)"""
    from distlearn_tpu.models import hybrid
    from tests.program_util import program_text
    tokens = jnp.zeros((2, 64), jnp.int32)

    def lowered():
        model = _toy(layer_types=(kind,), remat="full", window=16,
                     rope_theta=1e4, q_lora_rank=24, kv_lora_rank=16,
                     qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
        params = jax.eval_shape(lambda k: model.init(k)[0],
                                jax.random.PRNGKey(0))
        return jax.jit(jax.grad(
            lambda p: lm_loss(model, p, tokens))).lower(params)

    named = lowered()
    monkeypatch.setattr(hybrid, "checkpoint_block", jax.checkpoint)
    assert program_text(named) == program_text(lowered())


@pytest.mark.parametrize("pattern,top_k", [("gqa+kda", 4),
                                           ("full+window", 3)])
def test_routing_metrics_count_into_obs(pattern, top_k):
    mesh, model = _mesh(), _TOYS[pattern]()
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(mesh)
    metrics = build_lm_routing_metrics(model, mesh, params)
    out = metrics(params, tokens)
    assert out["assignments"].shape == (4, 4)
    assert (out["dropped"] == 0).all()
    assert ((0 <= out["unheld_frac"]) & (out["unheld_frac"] < 1)).all()
    # every token has top_k experts of 16; the held 4 get their share
    assert 0 < out["assignments"].sum() < 4 * 4 * 64 * top_k
    family = obs.counter("moe_assignments_total", labels=("layer", "expert"))
    if family is not obs.NULL:
        before = sum(s["value"] for s in family.sample())
        metrics(params, tokens)
        assert sum(s["value"] for s in family.sample()) - before \
            == out["assignments"].sum()
    from distlearn_tpu.models import transformer_lm
    dense = transformer_lm(vocab=97, dim=32, depth=1, heads=4, max_len=64)
    dparams, _ = dense.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="no routing counters"):
        build_lm_routing_metrics(dense, mesh, dparams)(dparams, tokens)


@pytest.mark.parametrize("shape,what", [((1, 2, 1), "sequence"),
                                        ((1, 1, 2), "tensor")])
def test_sequence_and_tensor_axes_must_be_of_size_one(shape, what):
    mesh, model = _mesh(shape), _toy()
    params, _ = model.init(jax.random.PRNGKey(0))
    step = build_lm_step(model, mesh, params, lr=0.05, donate=False)
    with pytest.raises(NotImplementedError, match=what):
        step(params, _tokens(mesh, b=2))


def test_layers_router_reads_its_input_before_the_mixer():
    """``router_input="layer_input"``: the counters of a one-layer model are
    those of routing the EMBEDDING rows (the layer's input, un-normed), and
    differ from routing the normed post-attention stream, which
    ``"ffn_norm"`` reads on the same weights."""
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, 97)
    counts = {}
    for source in ("layer_input", "ffn_norm"):
        model = _swa_toy(layer_types=("window",), router_input=source)
        params, _ = model.init(jax.random.PRNGKey(0))
        counts[source] = model.apply(params, {}, tokens)[1][
            "moe_assignments"][0]
    x = params["embed"][tokens].reshape(-1, 32)
    want = route_held(params["layer0"]["router"], x, 3, (1, 5, 6, 11))[2]
    np.testing.assert_array_equal(counts["layer_input"], want["assignments"])
    assert not np.array_equal(counts["layer_input"], counts["ffn_norm"])


def test_layer_without_a_shared_expert_builds_no_leaf_for_one():
    params, _ = _swa_toy().init(jax.random.PRNGKey(0))
    for i in range(4):
        assert set(params[f"layer{i}"]) == {
            "ln1", "ln2", "wq", "wk", "wv", "wo", "router", "we_gate",
            "we_up", "we_down"}
    blk = params["layer1"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 32), jnp.float32)
    y, _ = moe_apply(blk, x, jnp.float32, 1e-6, (1, 5, 6, 11), 3, None,
                     act="relu")
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    routed = moe_held_ffn(h.reshape(-1, 32), blk["router"],
                          (blk["we_gate"], blk["we_up"], blk["we_down"]),
                          (1, 5, 6, 11), 3, act="relu")[0]
    np.testing.assert_allclose(y, x + routed.reshape(x.shape), atol=1e-6)
    # the gated pattern keeps its shared expert and its gate
    gated, _ = _toy().init(jax.random.PRNGKey(0))
    assert {"ws_gate", "ws_up", "ws_down", "wg"} <= set(gated["layer0"])


def test_windowed_layer_is_the_full_one_where_the_band_covers_the_length():
    """A softmax layer's mixer with a window of the whole length and no
    rotation is the full-causal mixer; a shorter band, or the rotation,
    changes it."""
    params, _ = _swa_toy().init(jax.random.PRNGKey(0))
    blk = params["layer1"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 32), jnp.float32)
    full = gqa_apply(blk, x, jnp.float32, 1e-6)
    np.testing.assert_array_equal(
        gqa_apply(blk, x, jnp.float32, 1e-6, window=64), full)
    band = gqa_apply(blk, x, jnp.float32, 1e-6, window=16)
    np.testing.assert_allclose(band[:, :16], full[:, :16], atol=1e-6)
    assert float(jnp.abs(band[:, 16:] - full[:, 16:]).max()) > 1e-3
    turned = gqa_apply(blk, x, jnp.float32, 1e-6, rope_theta=1.5e6)
    np.testing.assert_allclose(turned[:, :1], full[:, :1], atol=1e-6)
    assert float(jnp.abs(turned[:, 1:] - full[:, 1:]).max()) > 1e-3


def test_constructor_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="'full', 'window'"):
        _toy(layer_types=("gqa", "mamba"))
    with pytest.raises(ValueError, match="needs kda_heads"):
        _toy(kda_heads=None)
    with pytest.raises(ValueError, match="needs window and rope_theta"):
        _swa_toy(window=None)
    with pytest.raises(ValueError, match="needs window and rope_theta"):
        _swa_toy(rope_theta=None)
    with pytest.raises(ValueError, match="expert_act"):
        _swa_toy(expert_act="gelu")
    with pytest.raises(ValueError, match="router_input"):
        _swa_toy(router_input="attn_norm")
    with pytest.raises(ValueError, match="kv_heads"):
        _toy(kv_heads=3)
    with pytest.raises(ValueError, match="remat"):
        _toy(remat="mlp")
    mesh, model = _mesh((1, 1, 1)), _toy()
    params, _ = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="exchange"):
        model.apply(params, {}, _tokens(mesh, b=1), ep_axis="data")


# ------------------ latent attention, a dense layer, a prediction module --

def _biased(params, key=7):
    """``params`` with every router's correction bias drawn N(0, 0.1)."""
    def one(path, leaf):
        if getattr(path[-1], "key", None) != "router_bias":
            return leaf
        return 0.1 * jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(key),
                               zlib.crc32(str(path).encode())),
            leaf.shape, leaf.dtype)
    return jax.tree_util.tree_map_with_path(one, params)


def test_mla_model_has_the_leaves_its_equations_name():
    """The low-rank paths with the norms at their waists and the shared
    rotated key (one 4-wide head in ``wkv_a``'s last columns); the dense
    layer has an MLP and NO router, bias or expert leaf; a sigmoid router
    has its correction bias; the module its own norms, projection and
    block, and no embedding or head of its own."""
    params, _ = _mla_toy().init(jax.random.PRNGKey(0))
    shapes = jax.tree_util.tree_map(lambda a: a.shape, params)
    mla = {"wq_a": (32, 24), "q_norm": {"scale": (24,)},
           "wq_b": (24, 4, 12), "wkv_a": (32, 16 + 4),
           "kv_norm": {"scale": (16,)}, "wkv_b": (16, 4, 8 + 8),
           "wo": (4, 8, 32), "ln1": {"scale": (32,)}, "ln2": {"scale": (32,)}}
    mixture = dict(mla, router=(32, 16), router_bias=(16,),
                   ws_gate=(32, 24), ws_up=(32, 24), ws_down=(24, 32),
                   we_gate=(4, 32, 24), we_up=(4, 32, 24),
                   we_down=(4, 24, 32))
    assert shapes["layer0"] == dict(mla, w_gate=(32, 40), w_up=(32, 40),
                                    w_down=(40, 32))
    assert shapes["layer1"] == shapes["layer2"] == mixture
    assert shapes["mtp"] == {"enorm": {"scale": (32,)},
                             "hnorm": {"scale": (32,)}, "eh_proj": (64, 32),
                             "block": mixture, "norm": {"scale": (32,)}}
    assert set(shapes) == {"embed", "head", "out_norm", "layer0", "layer1",
                           "layer2", "mtp"}
    assert float(jnp.abs(params["layer1"]["router_bias"]).max()) == 0.0


def test_prediction_module_runs_in_training_alone_and_its_loss_is_weighted():
    """``train=False``: the main logits, a counter row a MIXTURE layer, no
    module.  ``train=True``: the module's logits and weight ride the state
    and its block is one more row; ``lm_loss`` is the main cross-entropy plus
    0.3 x the mean cross-entropy of the module's logits against the token
    TWO ahead — with and without a (size-1) sequence axis."""
    model = _mla_toy()
    params = _biased(model.init(jax.random.PRNGKey(0))[0])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 97,
                                jnp.int32)
    logits, st = model.apply(params, {}, tokens, train=False)
    assert "mtp_logits" not in st and st["moe_assignments"].shape == (2, 4)
    trained, st = model.apply(params, {}, tokens, train=True)
    np.testing.assert_array_equal(trained, logits)
    assert st["mtp_logits"].shape == (2, 64, 97) and st["mtp_weight"] == 0.3
    assert st["moe_assignments"].shape == (3, 4)
    assert int(st["moe_dropped"].sum()) == 0
    assert model.apply.mtp_weight == 0.3 and _toy().apply.mtp_weight == 0.0

    def ce(lg, ahead):
        lp = jax.nn.log_softmax(lg[:, :-ahead])
        return -jnp.take_along_axis(lp, tokens[:, ahead:, None], -1).mean()
    want = ce(logits, 1) + 0.3 * ce(st["mtp_logits"], 2)
    whole, g = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(model, p, tokens)))(params)
    assert float(whole) == pytest.approx(float(want), rel=1e-6)
    mesh = _mesh((1, 1, 1))
    sharded = jax.jit(jax.shard_map(
        lambda p, t: lm_loss(model, p, t, seq_axis="seq"), mesh=mesh,
        in_specs=(P(), P("data", "seq")), out_specs=P(), check_vma=False))
    assert float(sharded(params, tokens)) == pytest.approx(float(want),
                                                           rel=1e-6)
    # the module's loss reaches the module's own leaves and the embedding
    assert float(jnp.abs(g["mtp"]["eh_proj"]).max()) > 0
    assert float(jnp.abs(g["mtp"]["block"]["wq_a"]).max()) > 0
    for blk in (g["layer1"], g["layer2"], g["mtp"]["block"]):
        assert float(jnp.abs(blk["router_bias"]).max()) == 0.0
        assert float(jnp.abs(blk["router"]).max()) > 0


def test_mla_step_sets_the_modules_gauge_and_counts_its_router_and_calls():
    from distlearn_tpu.parallel.sequence import attention_paths_traced
    mesh, model = _mesh((1, 1, 1)), _mla_toy()
    params, _ = model.init(jax.random.PRNGKey(0))
    routers = obs.counter("moe_router_total", labels=("score",))
    count = lambda fam, **lb: sum(                          # noqa: E731
        s["value"] for s in fam.sample() if s["labels"] == lb)
    before = (attention_paths_traced(latent=True).get("xla", 0),
              routers is not obs.NULL and count(routers, score="sigmoid"))
    step = build_lm_step(model, mesh, params, lr=0.05, donate=False)
    step(params, _tokens(mesh, b=1))
    gauge = obs.gauge("train.mtp.loss_weight", labels=("step",))
    if gauge is not obs.NULL:
        assert [s["value"] for s in gauge.sample()
                if s["labels"] == {"step": "lm"}] == [0.3]
        # three layers and the module's block; the dense layer has no router
        assert attention_paths_traced(latent=True)["xla"] - before[0] >= 4
        assert count(routers, score="sigmoid") - before[1] >= 3
        build_lm_step(_toy(), mesh, _toy().init(jax.random.PRNGKey(0))[0],
                      lr=0.05)
        assert [s["value"] for s in gauge.sample()
                if s["labels"] == {"step": "lm"}] == [0.0]


def test_mla_layer_rotates_only_the_last_part_of_a_head_and_shares_one_key():
    """Against the equations written out in numpy: the first 8 of a head's
    12 are un-rotated, the last 4 rotated by neighbouring pairs; the rotated
    key is one head for all four; the scale is 1 / sqrt(12); v is 8 wide."""
    from distlearn_tpu.models.hybrid import mla_apply
    model = _mla_toy()
    blk = model.init(jax.random.PRNGKey(3))[0]["layer0"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 32), jnp.float32)
    got = mla_apply(blk, x, jnp.float32, 1e-6, 3.2e7, 8, "interleaved")
    b = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), blk)
    x64 = np.asarray(x, np.float64)[0]
    norm = lambda a, g: a / np.sqrt((a * a).mean(-1, keepdims=True)  # noqa: E731
                                    + 1e-6) * g

    def rope(u):                                            # [L, H, 4]
        z = (u[..., 0::2] + 1j * u[..., 1::2]) * np.exp(
            1j * np.arange(16)[:, None, None]
            * 3.2e7 ** (-np.arange(2) / 2.0))
        out = np.empty_like(u)
        out[..., 0::2], out[..., 1::2] = z.real, z.imag
        return out
    u = norm(x64, b["ln1"]["scale"])
    q = np.einsum("lr,rhd->lhd", norm(u @ b["wq_a"], b["q_norm"]["scale"]),
                  b["wq_b"])
    ckv = u @ b["wkv_a"]
    kv = np.einsum("lr,rhd->lhd", norm(ckv[:, :16], b["kv_norm"]["scale"]),
                   b["wkv_b"])
    kr = rope(ckv[:, None, 16:])
    q = np.concatenate([q[..., :8], rope(q[..., 8:])], -1)
    k = np.concatenate([kv[..., :8], np.repeat(kr, 4, axis=1)], -1)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(12.0)
    s = np.where(np.tril(np.ones((16, 16), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    a = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), kv[..., 8:])
    want = x64 + np.einsum("qhd,hde->qe", a, b["wo"])
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-4, atol=2e-5)


def test_mla_routing_metrics_count_the_modules_block_as_one_more_row():
    mesh, model = _mesh(), _mla_toy()
    params, _ = model.init(jax.random.PRNGKey(0))
    out = build_lm_routing_metrics(model, mesh, params)(params, _tokens(mesh))
    assert out["assignments"].shape == (3, 4)       # layers 1, 2, the module
    assert (out["dropped"] == 0).all()
    assert 0 < out["assignments"].sum() < 3 * 4 * 64 * 3


@pytest.mark.parametrize("missing", ["q_lora_rank", "kv_lora_rank",
                                     "qk_nope_head_dim", "qk_rope_head_dim",
                                     "v_head_dim", "rope_theta"])
def test_constructor_names_the_size_an_mla_layer_lacks(missing):
    with pytest.raises(ValueError, match=f"'mla' layer needs {missing} "):
        _mla_toy(**{missing: None})


def test_constructor_refuses_the_new_options_it_cannot_build():
    with pytest.raises(ValueError, match="rope_pairing"):
        _mla_toy(rope_pairing="pairs")
    with pytest.raises(ValueError, match="router_score"):
        _mla_toy(router_score="tanh")
    with pytest.raises(ValueError, match="dense_width"):
        _mla_toy(dense_width=None)
    with pytest.raises(ValueError, match="dense_layers=4"):
        _mla_toy(dense_layers=4)
    with pytest.raises(ValueError, match="mtp_depth"):
        _mla_toy(mtp_depth=2)
    mesh, model = _mesh((1, 2, 1)), _mla_toy()
    params, _ = model.init(jax.random.PRNGKey(0))
    step = build_lm_step(model, mesh, params, lr=0.05, donate=False)
    with pytest.raises(NotImplementedError, match="sequence"):
        step(params, _tokens(mesh, b=2))


def test_constructor_refuses_what_the_gated_pattern_cannot_build():
    """Query heads by layer, a gate a head and the full layers' rotation:
    a head count a layer that is no multiple of the K/V heads, or a list of
    another length than the pattern, an unknown gate, and a fraction or a
    YaRN scaling of the full layers' rotation without its theta."""
    with pytest.raises(ValueError, match="kv_heads"):
        _swa_toy(heads=(4, 6, 6, 5))
    with pytest.raises(ValueError, match="kv_heads"):
        _swa_toy(heads=(4, 6, 6))
    with pytest.raises(ValueError, match="attn_gate"):
        _swa_toy(attn_gate="element")
    with pytest.raises(ValueError, match="need full_rope_theta"):
        _swa_toy(full_rope_fraction=0.5)
    with pytest.raises(ValueError, match="need full_rope_theta"):
        _swa_toy(full_rope_yarn={"factor": 2, "beta_fast": 32, "beta_slow": 1,
                                 "original_max_position_embeddings": 32,
                                 "attention_factor": 1.0})
    model = _swa_toy(heads=(4, 6, 6, 6), attn_gate="head")
    params, _ = model.init(jax.random.PRNGKey(0))
    assert [params[f"layer{i}"]["wq"].shape[1] for i in range(4)] \
        == [4, 6, 6, 6]
    assert [params[f"layer{i}"]["wg"].shape for i in range(4)] \
        == [(32, 4), (32, 6), (32, 6), (32, 6)]
