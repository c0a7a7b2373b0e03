"""The data-parallel gradient sum made inside the backward scan
(``models.core.scan_reducing`` over ``parallel.mesh.StagedSum``), on the
CPU's virtual devices: the same update as the psum after the loop, every
replica the same bits, engaged by the mesh and the leaf sizes alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlearn_tpu import obs
from distlearn_tpu.models import core as core_lib
from distlearn_tpu.models import transformer as transformer_lib
from distlearn_tpu.models.transformer import param_specs, transformer_lm
from distlearn_tpu.parallel.mesh import (StagedSum, cut_axis, from_tiles,
                                         to_tiles)
from distlearn_tpu.train import lm
from tests.program_util import program_text

# a scanned dense toy whose layer (12 x 640^2 values and the small leaves,
# 19.7 MB in float32) is over lm.PIPELINED_LAYER_BYTES
BIG = dict(vocab=64, dim=640, depth=5, heads=4, max_len=16,
           dtype=jnp.float32, scan_blocks=True, remat="full")
# tests/benchmark/test_scope_metrics.py's toy: 12,704 values a layer
SMALL = dict(vocab=97, dim=32, depth=2, heads=4, max_len=64,
             dtype=jnp.float32, scan_blocks=True, remat="full")


def _mesh(dp, sp=1, tp=1):
    return Mesh(np.array(jax.devices()[:dp * sp * tp]).reshape(dp, sp, tp),
                ("data", "seq", "model"))


def _gauge(name):
    for family in obs.REGISTRY.snapshot():
        if family["name"] == name:
            return {s["labels"]["step"]: s["value"]
                    for s in family["samples"]}["lm"]
    raise KeyError(name)


def _reduced():
    return (_gauge("train.grad_reduce.pipelined_bytes"),
            _gauge("train.grad_reduce.tail_bytes"))


def _tokens(mesh, batch, length, vocab):
    toks = np.random.RandomState(0).randint(0, vocab, (batch, length))
    return jax.device_put(toks.astype(np.int32),
                          NamedSharding(mesh, P("data", "seq")))


def _tail_psum(monkeypatch):
    """The builder as it was before the sum moved into the loop."""
    monkeypatch.setattr(lm, "_pipelined", lambda *a, **k: False)


@pytest.mark.parametrize("dp,depth", [(4, 5), (2, 5), (4, 2)])
def test_pipelined_step_is_the_tail_psums_step(dp, depth, monkeypatch):
    """One step through the pipelined sum against the same step with the
    psum after the loop: parameters equal to the rounding of a ``dp``-term
    float32 sum made in another order, every replica bitwise the same,
    three steps' losses equal.  ``depth=2`` at ``dp=4``: fewer layers than
    stages, so every layer is finished after the loop."""
    mesh = _mesh(dp)
    model = transformer_lm(**dict(BIG, depth=depth))
    params, _ = model.init(jax.random.PRNGKey(0))
    n_bytes = 4 * sum(p.size for p in jax.tree_util.tree_leaves(params))
    tokens = _tokens(mesh, 2 * dp, 16, 64)
    sharded = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(params, "model"))

    def three_steps():
        step = lm.build_lm_step(model, mesh, params, lr=0.1, donate=False)
        gauges = _reduced()
        p, losses = jax.device_put(params, sharded), []
        for _ in range(3):
            p, loss = step(p, tokens)
            losses.append(float(loss))
        first, _ = step(jax.device_put(params, sharded), tokens)
        return first, losses, gauges, step.lower(p, tokens).as_text()

    got, losses, (inside, tail), text = three_steps()
    block_bytes = 4 * sum(
        p.size for p in jax.tree_util.tree_leaves(params["blocks"]))
    assert (inside, tail) == (block_bytes, n_bytes - block_bytes)
    # one ppermute is lm_loss's own: the targets' shift over the seq axis
    assert text.count("collective_permute") > 1
    for leaf in jax.tree_util.tree_leaves(got):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(shards) == dp
        for other in shards[1:]:
            np.testing.assert_array_equal(other, shards[0])

    _tail_psum(monkeypatch)
    want, want_losses, (inside, tail), text = three_steps()
    assert (inside, tail) == (0, n_bytes)
    assert text.count("collective_permute") == 1
    np.testing.assert_allclose(losses, want_losses, rtol=0, atol=1e-6)
    for a, b, p0 in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(params)):
        # the sum's order moves the gradient by a few of ITS last places,
        # and the parameter it is taken from rounds once more
        eps = float(np.finfo(np.float32).eps)
        step_size = float(jnp.max(jnp.abs(b - p0)))
        np.testing.assert_allclose(a, b, rtol=eps,
                                   atol=4 * eps * step_size)


def test_a_small_layer_keeps_the_tail_psum(monkeypatch):
    """The benchmark's toy (``tests/benchmark/test_scope_metrics.py``: 12 k
    values a layer, mesh [2, 1, 1]) is under the threshold: its program is
    the one the builder made before, text for text, and
    ``collective_mb.train``'s test there reads its all-reduce unedited."""
    mesh = _mesh(2)
    model = transformer_lm(**SMALL)
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(mesh, 4, 16, 97)
    text = program_text(lm.build_lm_step(model, mesh, params, lr=0.05)
                        .lower(params, tokens))
    n_bytes = 4 * sum(p.size for p in jax.tree_util.tree_leaves(params))
    assert _reduced() == (0, n_bytes)
    _tail_psum(monkeypatch)
    assert text == program_text(
        lm.build_lm_step(model, mesh, params, lr=0.05).lower(params, tokens))


@pytest.mark.parametrize("case", ["one_chip", "seq2", "accum2", "moe_ep",
                                  "dp3", "unrolled"])
def test_what_keeps_todays_program(case, monkeypatch):
    """Each of these keeps the psum after the loop, and the program is the
    one the builder made before, text for text: one chip (nothing to sum
    over: both gauges 0), a sequence axis of 2 (the blocks' gradients are
    summed over it too), accumulation (the scan runs once a microbatch),
    expert leaves on the data axis, a data axis that is no power of two,
    and layers that are not scanned."""
    kw, sizes, build = {}, dict(BIG), {}
    dp, sp = 2, 1
    if case == "one_chip":
        dp = 1
    elif case == "seq2":
        sp = 2
    elif case == "accum2":
        build = dict(accum_steps=2)
    elif case == "moe_ep":
        sizes.update(scan_blocks=False, moe_experts=2, moe_every=2)
        build = dict(ep_axis="data", moe_balance_weight=0.01)
        kw = dict(ep_axis="data")
    elif case == "dp3":
        dp = 3
    elif case == "unrolled":
        sizes.update(scan_blocks=False)
    mesh = _mesh(dp, sp)
    model = transformer_lm(**sizes)
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(mesh, 2 * dp, 16, 64)
    specs = param_specs(params, "model", **kw)
    assert not lm._pipelined(mesh, params, specs, "data", "seq",
                             build.get("ep_axis"),
                             build.get("accum_steps", 1))

    def text():
        return program_text(lm.build_lm_step(
            model, mesh, params, lr=0.1, **build).lower(params, tokens))

    got = text()
    inside, tail = _reduced()
    assert inside == 0 and (tail == 0) == (case == "one_chip")
    _tail_psum(monkeypatch)
    assert got == text()


def test_only_build_lm_step_sums_inside_the_scan(monkeypatch):
    """``build_lm_mixed_step`` and ``optim.build_lm_optax_step`` share
    ``lm_local_grads`` and keep the psum after the loop (they do not ask for
    the pipelined sum: the first reduces in ``grad_dtype``, the second hands
    whole gradients to optax); the two pipeline builders scan their own
    stage function.  Only ``build_lm_step`` reaches ``scan_reducing``."""
    from distlearn_tpu.train import optim
    import optax
    calls = []
    real = core_lib.scan_reducing
    monkeypatch.setattr(transformer_lib, "scan_reducing",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    mesh = _mesh(4)
    model = transformer_lm(**BIG)
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = _tokens(mesh, 8, 16, 64)

    mixed = lm.build_lm_mixed_step(model, mesh, params, lr=0.1, donate=False)
    text = mixed.lower(lm.init_lm_mixed_state(params), tokens).as_text()
    assert not calls and text.count("collective_permute") == 1
    opt_step = optim.build_lm_optax_step(model, mesh, optax.sgd(0.1),
                                         donate=False)
    state = optim.LMOptaxState(params, optax.sgd(0.1).init(params))
    text = opt_step.lower(state, tokens).as_text()
    assert not calls and text.count("collective_permute") == 1

    pmesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "pipe"))
    unscanned, _ = transformer_lm(**dict(BIG, depth=4, scan_blocks=False)) \
        .init(jax.random.PRNGKey(0))
    shared, stacked = lm.stack_blocks(unscanned, 4)
    ptokens = jax.device_put(np.zeros((4, 16), np.int32),
                             NamedSharding(pmesh, P("data")))
    for builder in (lm.build_lm_pp_step, lm.build_lm_pp_1f1b_step):
        builder(pmesh, shared, stacked, lr=0.1, num_microbatches=2,
                remat=True, donate=False).lower(shared, stacked, ptokens)
    assert not calls

    lm.build_lm_step(model, mesh, params, lr=0.1).lower(params, tokens)
    assert calls == [1]


@pytest.mark.parametrize("n", [2, 4, 8])
def test_staged_sum_is_the_psum_on_every_device(n):
    """All stages of one :class:`StagedSum`, run back to back, against
    ``lax.psum``: leaves that read as tiles (a ``[.., 128k]`` matrix, a
    projection with its 128-multiple axis first) and leaves that do not (a
    vector, a ragged matrix), cut along whichever axis ``n`` divides."""
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    rng = np.random.RandomState(n)
    tree = {"w": rng.randn(n, 64, 256), "proj": rng.randn(n, 128, 8, 16),
            "bias": rng.randn(n, 24 * n), "odd": rng.randn(n, 8, 3 * n)}
    tree = {k: jnp.asarray(v, jnp.float32) for k, v in tree.items()}

    def both(local):
        local = jax.tree_util.tree_map(lambda a: a[0], local)
        reduce = StagedSum(local, "data")
        slots = reduce.empty()
        stack = jax.tree_util.tree_map(lambda a: jnp.zeros_like(a)[None],
                                       local)
        slots[0] = reduce.enter(local)
        for stage in range(reduce.stages):
            slots, stack = reduce.advance(
                slots, stack, 0 if stage == reduce.m else None, first=stage)
        mine = stack
        want = jax.tree_util.tree_map(lambda a: lax.psum(a, "data")[None],
                                      local)
        return mine, want

    mine, want = jax.jit(shard_map(
        both, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))(tree)
    for k in tree:
        np.testing.assert_allclose(mine[k], want[k], rtol=1e-6, atol=1e-6)
        rows = np.asarray(mine[k])
        for other in rows[1:]:
            np.testing.assert_array_equal(other, rows[0])


@pytest.mark.parametrize("shape", [(16, 256), (128, 4, 16), (4, 16, 128),
                                   (24,), (8, 12), (3, 128)])
def test_tiles_round_trip(shape):
    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    tiles = to_tiles(x)
    assert tiles.shape[1:] == (8, 128)
    assert tiles.size - x.size < 1024
    np.testing.assert_array_equal(from_tiles(tiles, shape), x)


@pytest.mark.parametrize("shape,n,axis", [
    ((1280, 5120), 4, 0), ((1280, 20, 64), 4, 1), ((20, 64, 1280), 4, 0),
    ((1280,), 4, 0), ((6, 1280), 4, 1), ((1280, 3, 64), 8, 2),
    ((3, 5), 4, None)])
def test_cut_axis(shape, n, axis):
    """Not the axis the tiles read as columns while another divides; None
    where none does, and the builder then keeps the psum after the loop."""
    assert cut_axis(shape, n) == axis
