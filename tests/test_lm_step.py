"""3D-parallel LM train step: loss decreases; TP shards update consistently."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlearn_tpu.models.transformer import transformer_lm
from distlearn_tpu.train.lm import build_lm_step


def test_lm_step_3d_mesh_loss_decreases():
    dp, sp, tp = 2, 2, 2
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(dp, sp, tp),
                ("data", "seq", "model"))
    L = 16 * sp
    model = transformer_lm(vocab=32, dim=64, depth=2, heads=4, max_len=L)
    params, _ = model.init(jax.random.PRNGKey(0))
    step = build_lm_step(model, mesh, params, lr=0.1, donate=False)

    rng = np.random.RandomState(0)
    # learnable: repeated token pattern
    base = rng.randint(0, 32, (1, L)).astype(np.int32)
    tokens = jax.device_put(np.tile(base, (2 * dp, 1)),
                            NamedSharding(mesh, P("data", "seq")))
    losses = []
    for _ in range(12):
        params, loss = step(params, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


def test_lm_step_gradients_match_single_device_all_mesh_shapes():
    """The implied update (params - new_params)/lr must equal the
    single-device gradient of the same global batch for every dp/sp/tp
    factorization — guards the psum-transpose scaling bugs (dp unaveraged,
    sp loss-psum, tp without the f/g pattern)."""
    import jax.numpy as jnp
    from distlearn_tpu.models.transformer import lm_loss
    L = 32
    model = transformer_lm(vocab=32, dim=32, depth=2, heads=4, max_len=L,
                           dtype=jnp.float64)
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 32, (4, L)).astype(np.int32))
    _, ref_g = jax.value_and_grad(lambda p: lm_loss(model, p, tokens))(params)

    for dp, sp, tp in [(2, 1, 1), (1, 2, 1), (1, 1, 2), (2, 2, 2)]:
        mesh = Mesh(np.array(jax.devices()[:dp * sp * tp]).reshape(dp, sp, tp),
                    ("data", "seq", "model"))
        step = build_lm_step(model, mesh, params, lr=1.0, donate=False)
        tk = jax.device_put(tokens, NamedSharding(mesh, P("data", "seq")))
        newp, _ = step(params, tk)
        for a, b, g in zip(jax.tree_util.tree_leaves(params),
                           jax.tree_util.tree_leaves(newp),
                           jax.tree_util.tree_leaves(ref_g)):
            implied = np.asarray(a) - np.asarray(b)
            denom = max(1e-12, float(np.abs(np.asarray(g)).max()))
            err = float(np.abs(implied - np.asarray(g)).max()) / denom
            assert err < 1e-5, (dp, sp, tp, err)


def test_lm_mixed_step_f32_master_matches_plain_step():
    """With an f32 working copy the mixed step IS the plain step (same
    grads, same update applied to the master) — the equivalence anchor
    for the bf16 scheme (VERDICT r4 weak #2 / next #3)."""
    from distlearn_tpu.train.lm import (build_lm_mixed_step,
                                        init_lm_mixed_state,
                                        build_lm_step)
    dp, sp, tp = 2, 2, 2
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(dp, sp, tp),
                ("data", "seq", "model"))
    L = 16 * sp
    model = transformer_lm(vocab=32, dim=64, depth=2, heads=4, max_len=L)
    params, _ = model.init(jax.random.PRNGKey(0))
    plain = build_lm_step(model, mesh, params, lr=0.1, donate=False)
    mixed = build_lm_mixed_step(model, mesh, params, lr=0.1, donate=False)
    st = init_lm_mixed_state(params, param_dtype=jnp.float32)

    tokens = jax.device_put(
        np.random.RandomState(0).randint(0, 32, (2 * dp, L))
        .astype(np.int32), NamedSharding(mesh, P("data", "seq")))
    p_ref = params
    for _ in range(3):
        p_ref, l_ref = plain(p_ref, tokens)
        st, l_mx = mixed(st, tokens)
        np.testing.assert_allclose(float(l_mx), float(l_ref), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(st.master)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_lm_mixed_step_bf16_trains_and_keeps_invariant():
    """bf16 working copy: params == master.astype(bf16) after every step
    (the master is the source of truth) and the loss still decreases —
    the f32 master absorbs updates bf16 alone would underflow."""
    from distlearn_tpu.train.lm import (build_lm_mixed_step,
                                        init_lm_mixed_state)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                ("data", "seq", "model"))
    L = 32
    model = transformer_lm(vocab=32, dim=64, depth=2, heads=4, max_len=L)
    params, _ = model.init(jax.random.PRNGKey(0))
    step = build_lm_mixed_step(model, mesh, params, lr=0.1, donate=False)
    st = init_lm_mixed_state(params)
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(st.params))
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(st.master))

    base = np.random.RandomState(0).randint(0, 32, (1, L)).astype(np.int32)
    tokens = jax.device_put(np.tile(base, (4, 1)),
                            NamedSharding(mesh, P("data", "seq")))
    losses = []
    for _ in range(12):
        st, loss = step(st, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()
    for p, m in zip(jax.tree_util.tree_leaves(st.params),
                    jax.tree_util.tree_leaves(st.master)):
        np.testing.assert_array_equal(
            np.asarray(p), np.asarray(m.astype(jnp.bfloat16)))


def test_lm_mixed_step_accum_matches_single_shot():
    """Gradient accumulation under the mixed builder: k scanned
    microbatches must produce the same master update as the single-shot
    step (dense model, f32 working copy so the comparison is exact)."""
    from distlearn_tpu.train.lm import (build_lm_mixed_step,
                                        init_lm_mixed_state)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                ("data", "seq", "model"))
    L = 32
    model = transformer_lm(vocab=32, dim=32, depth=1, heads=2, max_len=L)
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        np.random.RandomState(0).randint(0, 32, (4, L)).astype(np.int32),
        NamedSharding(mesh, P("data", "seq")))
    one = build_lm_mixed_step(model, mesh, params, lr=0.1, donate=False)
    two = build_lm_mixed_step(model, mesh, params, lr=0.1, donate=False,
                              accum_steps=2)
    st1, _ = one(init_lm_mixed_state(params, jnp.float32), tokens)
    st2, _ = two(init_lm_mixed_state(params, jnp.float32), tokens)
    for a, b in zip(jax.tree_util.tree_leaves(st1.master),
                    jax.tree_util.tree_leaves(st2.master)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_lm_mixed_step_zigzag_layout_trains():
    """--mixed composes with the zigzag causal ring layout (the two
    features meet in lm_local_grads): loss finite and decreasing."""
    from distlearn_tpu.parallel.sequence import zigzag_indices
    from distlearn_tpu.train.lm import (build_lm_mixed_step,
                                        init_lm_mixed_state)
    sp, L = 4, 64
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, sp, 1),
                ("data", "seq", "model"))
    model = transformer_lm(vocab=32, dim=64, depth=2, heads=4, max_len=L)
    params, _ = model.init(jax.random.PRNGKey(0))
    step = build_lm_mixed_step(model, mesh, params, lr=0.1,
                               donate=False, seq_layout="zigzag")
    st = init_lm_mixed_state(params)
    base = np.random.RandomState(0).randint(0, 32, (1, L)).astype(np.int32)
    toks = np.tile(base, (4, 1))[:, zigzag_indices(sp, L)]
    tokens = jax.device_put(toks, NamedSharding(mesh, P("data", "seq")))
    losses = []
    for _ in range(10):
        st, loss = step(st, tokens)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_lm_mixed_optax_step_f32_matches_plain_optax():
    """Same equivalence anchor for the optax variant (adam)."""
    import optax
    from distlearn_tpu.train.optim import (LMOptaxState,
                                           build_lm_mixed_optax_step,
                                           build_lm_optax_step,
                                           init_lm_mixed_optax_state)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "seq"))
    L = 32
    model = transformer_lm(vocab=32, dim=32, depth=1, heads=2, max_len=L)
    params, _ = model.init(jax.random.PRNGKey(0))
    tx = optax.adam(1e-2)
    plain = build_lm_optax_step(model, mesh, tx, donate=False)
    mixed = build_lm_mixed_optax_step(model, mesh, tx, donate=False)
    st_p = LMOptaxState(params, tx.init(params))
    st_m = init_lm_mixed_optax_state(params, tx,
                                     param_dtype=jnp.float32)
    tokens = jax.device_put(
        np.random.RandomState(0).randint(0, 32, (4, L)).astype(np.int32),
        NamedSharding(mesh, P("data", "seq")))
    for _ in range(3):
        st_p, l_ref = plain(st_p, tokens)
        st_m, l_mx = mixed(st_m, tokens)
        np.testing.assert_allclose(float(l_mx), float(l_ref), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(st_p.params),
                    jax.tree_util.tree_leaves(st_m.master)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_lm_step_dp_only_matches_structure():
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    model = transformer_lm(vocab=32, dim=32, depth=1, heads=2, max_len=16)
    params, _ = model.init(jax.random.PRNGKey(0))
    step = build_lm_step(model, mesh, params, lr=0.1, seq_axis=None,
                         tp_axis=None, donate=False)
    tokens = jax.device_put(
        np.random.RandomState(0).randint(0, 32, (4, 16)).astype(np.int32),
        NamedSharding(mesh, P("data")))
    new_params, loss = step(params, tokens)
    assert np.isfinite(float(loss))
    # structure preserved
    assert jax.tree_util.tree_structure(new_params) == \
        jax.tree_util.tree_structure(params)


def test_lm_gradient_accumulation_matches_full():
    """accum_steps=2 must reproduce the single-shot LM step exactly (the
    transformer is deterministic — no dropout)."""
    import numpy as np
    from jax import random

    from distlearn_tpu.models.transformer import param_specs, transformer_lm
    from distlearn_tpu.train.lm import build_lm_step

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2, 1),
                ("data", "seq", "model"))
    lm = transformer_lm(vocab=64, dim=32, depth=2, heads=4, max_len=16)
    params, _ = lm.init(jax.random.PRNGKey(0))
    toks = jax.device_put(
        jnp.asarray(np.random.RandomState(1).randint(0, 64, (8, 16)),
                    jnp.int32),
        NamedSharding(mesh, P("data", "seq")))
    sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                param_specs(params, tp_axis="model"))
    outs = {}
    for k in (1, 2):
        step = build_lm_step(lm, mesh, params, lr=0.1, accum_steps=k,
                             donate=False)
        p = jax.device_put(params, sh)
        for _ in range(2):
            p, loss = step(p, toks)
        outs[k] = (float(loss), jax.tree_util.tree_leaves(p))
    np.testing.assert_allclose(outs[1][0], outs[2][0], rtol=1e-6)
    for a, b in zip(outs[1][1], outs[2][1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def _pp_vs_sequential(depth, n_stages, num_microbatches, remat,
                      unroll=False, schedule="gpipe"):
    """PP step on dp2 x pipe{n_stages} vs the plain single-mesh LM step:
    same loss, same updated params (gradient reassembly across pipe ranks
    is exact)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.train import (build_lm_pp_1f1b_step, build_lm_pp_step,
                                     build_lm_step, stack_blocks,
                                     unstack_blocks)

    dim, vocab, L, B = 32, 64, 16, 8
    lm = transformer_lm(vocab=vocab, dim=dim, depth=depth, heads=2,
                        max_len=L)
    params, _ = lm.init(jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(0, vocab, (B, L)) \
        .astype(np.int32)

    # reference: plain data-parallel step on a 1-device mesh (no seq/tp)
    mesh1 = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                 ("data", "seq", "model"))
    step_ref = build_lm_step(lm, mesh1, params, lr=0.1, donate=False)
    t_ref = jax.device_put(tokens,
                           NamedSharding(mesh1, P("data", "seq")))
    p_ref, loss_ref = step_ref(params, t_ref)

    mesh = Mesh(np.array(jax.devices()[:2 * n_stages]).reshape(2, n_stages),
                ("data", "pipe"))
    shared, stacked = stack_blocks(params, depth)
    shared_d = jax.device_put(shared, NamedSharding(mesh, P()))
    stacked_d = jax.device_put(stacked, NamedSharding(mesh, P("pipe")))
    if schedule == "1f1b":
        step_pp = build_lm_pp_1f1b_step(mesh, shared, stacked, lr=0.1,
                                        num_microbatches=num_microbatches,
                                        remat=remat, donate=False)
    else:
        step_pp = build_lm_pp_step(mesh, shared, stacked, lr=0.1,
                                   num_microbatches=num_microbatches,
                                   remat=remat, unroll=unroll, donate=False)
    t_pp = jax.device_put(tokens, NamedSharding(mesh, P("data")))
    shared_n, stacked_n, loss_pp = step_pp(shared_d, stacked_d, t_pp)

    np.testing.assert_allclose(float(loss_pp), float(loss_ref),
                               rtol=1e-5, atol=1e-6)
    got = unstack_blocks(jax.device_get(shared_n),
                         jax.device_get(stacked_n), depth)
    for (pa, a), (pb, b) in zip(
            sorted(jax.tree_util.tree_flatten_with_path(
                jax.device_get(p_ref))[0], key=lambda t: str(t[0])),
            sorted(jax.tree_util.tree_flatten_with_path(got)[0],
                   key=lambda t: str(t[0]))):
        assert str(pa) == str(pb)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6, err_msg=str(pa))


def test_lm_pp_step_matches_sequential():
    _pp_vs_sequential(depth=4, n_stages=4, num_microbatches=2, remat=False)


def test_lm_pp_step_k_blocks_per_stage_remat():
    """depth=8 over 4 stages (k=2 blocks per stage) with per-block remat —
    the generalized GPipe path — still matches the sequential step."""
    _pp_vs_sequential(depth=8, n_stages=4, num_microbatches=4, remat=True)


def test_lm_pp_step_unrolled_ticks_match():
    """unroll=True (inlined tick scan) must not change the math."""
    _pp_vs_sequential(depth=4, n_stages=2, num_microbatches=4, remat=False,
                      unroll=True)


def test_lm_ea_diverge_contract_converge():
    """EASGD on the transformer LM (the reference's core algorithm on the
    model family it never had): replicas diverge over collective-free
    local steps, one elastic round contracts them, training converges;
    center replicas stay bitwise identical."""
    from distlearn_tpu.parallel.mesh import MeshTree
    from distlearn_tpu.train import build_lm_ea_steps, init_lm_ea_state

    tree = MeshTree(num_nodes=4)
    vocab, L, B = 32, 16, 8
    lm = transformer_lm(vocab=vocab, dim=32, depth=2, heads=2, max_len=L)
    st = init_lm_ea_state(lm, tree, jax.random.PRNGKey(0))
    local, rnd = build_lm_ea_steps(lm, tree, lr=0.1, alpha=0.25,
                                   momentum=0.9, donate=False)
    rng = np.random.RandomState(0)
    sh = NamedSharding(tree.mesh, P("data"))

    def spread(s):
        leaf = jax.tree_util.tree_leaves(s.params)[0]
        arr = np.asarray(jax.device_get(leaf))
        return float(np.abs(arr - arr[0]).max())

    assert spread(st) == 0.0
    first = last = None
    for k in range(30):
        toks = jax.device_put(
            rng.randint(0, vocab, (B, L)).astype(np.int32), sh)
        st, losses = local(st, toks)
        m = float(np.mean(np.asarray(losses)))
        first = m if first is None else first
        last = m
        if k == 14:
            d_before = spread(st)
            assert d_before > 0      # replicas saw different shards
            st = rnd(st)
            assert spread(st) < d_before   # elastic round contracts
    assert last < first
    c = jax.tree_util.tree_leaves(st.center)[0]
    arr = np.asarray(jax.device_get(c))
    for i in range(1, arr.shape[0]):
        np.testing.assert_array_equal(arr[0], arr[i])


def test_lm_step_zigzag_matches_single_device():
    """seq_layout='zigzag' (balanced causal ring, masked blocks skipped)
    computes the SAME global objective: the implied update on
    column-permuted tokens must equal the single-device gradient of the
    natural-order batch — positions, shifted targets, and the loss mask
    all survive the layout change."""
    from distlearn_tpu.models.transformer import lm_loss
    from distlearn_tpu.parallel.sequence import zigzag_indices

    L = 32
    model = transformer_lm(vocab=32, dim=32, depth=2, heads=4, max_len=L,
                           dtype=jnp.float64)
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, 32, (4, L)).astype(np.int32))
    _, ref_g = jax.value_and_grad(lambda p: lm_loss(model, p, tokens))(params)

    for dp, sp in [(1, 2), (2, 4), (1, 8)]:
        mesh = Mesh(np.array(jax.devices()[:dp * sp]).reshape(dp, sp, 1),
                    ("data", "seq", "model"))
        step = build_lm_step(model, mesh, params, lr=1.0, donate=False,
                             seq_layout="zigzag")
        idx = zigzag_indices(sp, L)
        tk = jax.device_put(np.asarray(tokens)[:, idx],
                            NamedSharding(mesh, P("data", "seq")))
        newp, loss = step(params, tk)
        ref_loss = float(lm_loss(model, params, tokens))
        # the loss itself is reduced in f32 regardless of model dtype
        assert abs(float(loss) - ref_loss) < 1e-5, (sp, float(loss), ref_loss)
        for a, b, g in zip(jax.tree_util.tree_leaves(params),
                           jax.tree_util.tree_leaves(newp),
                           jax.tree_util.tree_leaves(ref_g)):
            implied = np.asarray(a) - np.asarray(b)
            denom = max(1e-12, float(np.abs(np.asarray(g)).max()))
            err = float(np.abs(implied - np.asarray(g)).max()) / denom
            assert err < 1e-5, (dp, sp, err)


def test_lm_zigzag_layout_validation():
    from distlearn_tpu.models.transformer import transformer_lm as tl
    model = tl(vocab=8, dim=8, depth=1, heads=1, max_len=8,
               seq_impl="alltoall")
    toks = jnp.zeros((1, 8), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2, 1),
                ("data", "seq", "model"))
    import pytest
    with pytest.raises(ValueError, match="ring"):
        build_lm_step(model, mesh, model.init(jax.random.PRNGKey(0))[0],
                      lr=0.1, seq_layout="zigzag")(
            model.init(jax.random.PRNGKey(0))[0],
            jax.device_put(np.zeros((1, 8), np.int32),
                           NamedSharding(mesh, P("data", "seq"))))
    model2 = tl(vocab=8, dim=8, depth=1, heads=1, max_len=8)
    with pytest.raises(ValueError, match="zigzag"):
        model2.apply(model2.init(jax.random.PRNGKey(0))[0], {}, toks,
                     seq_layout="zigzag")   # no seq axis


def test_lm_pp_1f1b_matches_sequential():
    """The 1F1B schedule (manual per-tick vjp, O(S) liveness) computes the
    SAME update as the sequential reference — drop-in with GPipe."""
    _pp_vs_sequential(depth=4, n_stages=4, num_microbatches=4,
                      remat=False, schedule="1f1b")


def test_lm_pp_1f1b_k_blocks_remat_matches_sequential():
    _pp_vs_sequential(depth=8, n_stages=4, num_microbatches=4,
                      remat=True, schedule="1f1b")


def test_lm_pp_1f1b_liveness_beats_gpipe():
    """The point of 1F1B: compiled temp memory stays O(S) while GPipe's
    autodiff residuals grow O(M).  At M=32 over 4 stages the 1F1B
    program's temp allocation must be well under GPipe's."""
    from distlearn_tpu.models.transformer import transformer_lm
    from distlearn_tpu.train import (build_lm_pp_1f1b_step,
                                     build_lm_pp_step, stack_blocks)

    S, M, L, dim = 4, 32, 64, 64
    lm = transformer_lm(vocab=64, dim=dim, depth=S, heads=4, max_len=L)
    params, _ = lm.init(jax.random.PRNGKey(0))
    shared, stacked = stack_blocks(params, S)
    mesh = Mesh(np.array(jax.devices()[:S]).reshape(1, S), ("data", "pipe"))
    toks = np.zeros((M * 2, L), np.int32)

    def temp_bytes(builder):
        step = builder(mesh, shared, stacked, lr=1.0, num_microbatches=M,
                       remat=True, donate=False)
        return step.lower(shared, stacked, toks).compile() \
            .memory_analysis().temp_size_in_bytes

    gpipe = temp_bytes(build_lm_pp_step)
    f1b = temp_bytes(build_lm_pp_1f1b_step)
    assert f1b < 0.6 * gpipe, (f1b, gpipe)


# ---------------------------------- scopes, the dispatch shim, its catalog --

def _scoped_lm(**kw):
    """A toy scanned, fully rematerialised LM on a (2, 1, 1) mesh: the
    benchmark's own shape of program."""
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                ("data", "seq", "model"))
    model = transformer_lm(vocab=97, dim=32, depth=3, heads=4, max_len=16,
                           scan_blocks=True, remat="full")
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 97, jnp.int32),
        NamedSharding(mesh, P("data", "seq")))
    return build_lm_step(model, mesh, params, lr=0.1, donate=False,
                         **kw), params, tokens


def _scoped_hybrid():
    """The pattern LM at toy size on the same mesh, fully rematerialised: one
    softmax layer, two linear-attention ones, 2 of 8 experts held."""
    from distlearn_tpu.models import hybrid_lm
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                ("data", "seq", "model"))
    model = hybrid_lm(vocab=97, dim=32, layer_types=("gqa", "kda", "kda"),
                      heads=4, kv_heads=2, head_dim=8, kda_heads=2,
                      kda_head_dim=8, n_routed_experts=8, held_experts=(1, 6),
                      experts_per_tok=2, expert_width=16,
                      max_len=32, remat="full")
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 97, jnp.int32),
        NamedSharding(mesh, P("data", "seq")))
    return build_lm_step(model, mesh, params, lr=0.1,
                         donate=False), params, tokens


#: the scopes of ``models.core.SCOPES`` only the pattern LM uses
_HYBRID_ONLY = {"linattn_core", "moe"}


@pytest.fixture(scope="module", params=["dense", "hybrid"])
def scoped_op_names(request):
    """``(model kind, op_name of every instruction of the toy step's
    optimized HLO)``, for the dense LM and for the pattern LM."""
    from distlearn_tpu.utils.profiling import scope_table
    build = _scoped_lm if request.param == "dense" else _scoped_hybrid
    step, params, tokens = build()
    step(params, tokens)
    table = scope_table(step.hlo_text())
    assert table
    return request.param, list(table.values())


def _has_scope(op_name, scope):
    return any(part in (scope, f"jvp({scope})", f"transpose(jvp({scope}))")
               for part in op_name.split("/"))


_IN_PHASE = {
    "forward": lambda n: "jvp(" in n and "transpose(" not in n,
    "recompute": lambda n: "rematted_computation" in n,
    "backward": lambda n: "transpose(" in n
    and "rematted_computation" not in n,
}


@pytest.mark.parametrize("phase", sorted(_IN_PHASE))
def test_every_block_scope_shows_in_every_pass(scoped_op_names, phase):
    """Forward / recompute / backward are JAX's own marks; the declared
    scopes ride inside each of them."""
    kind, op_names = scoped_op_names
    names = [n for n in op_names if _IN_PHASE[phase](n)]
    wanted = {"norm", "attn_proj", "attn_core", "mlp"}
    if kind == "hybrid":
        wanted |= _HYBRID_ONLY
    if phase != "recompute":        # embedding and head sit outside the scan
        wanted |= {"embed", "head_loss"}
    missing = {s for s in wanted if not any(_has_scope(n, s) for n in names)}
    assert not missing, (phase, missing)


def test_update_and_grad_reduce_sit_outside_the_passes(scoped_op_names):
    from distlearn_tpu.models.core import SCOPES
    kind, op_names = scoped_op_names
    for scope in ("update", "grad_reduce"):
        names = [n for n in op_names if _has_scope(n, scope)]
        assert names, scope
        assert not any("jvp(" in n or "transpose(" in n for n in names), scope
    # the declared list is what the programs use: nothing else, nothing
    # less — the dense model all of it but the pattern LM's two names
    used = {s for s in SCOPES
            if any(_has_scope(n, s) for n in op_names)}
    assert used == set(SCOPES) - (_HYBRID_ONLY if kind == "dense" else set())


def test_scopes_change_no_number(monkeypatch):
    """Scopes are metadata: the same build with ``jax.named_scope`` turned
    into a null context lowers to the same program text (locations apart,
    which is also how JAX keys its compile cache) and gives bitwise the
    same losses and parameters."""
    import contextlib

    def two_steps():
        step, params, tokens = _scoped_lm()
        text = step.lower(params, tokens).as_text()
        losses = []
        for _ in range(2):
            params, loss = step(params, tokens)
            losses.append(np.asarray(loss))
        return text, losses + jax.tree_util.tree_leaves(
            jax.device_get(params))

    text, numbers = two_steps()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_text, bare_numbers = two_steps()
    assert text == bare_text
    for a, b in zip(numbers, bare_numbers):
        np.testing.assert_array_equal(a, b)


def _lm_builders():
    """name -> a thunk building ``(step, args)`` for every LM builder."""
    from distlearn_tpu.parallel.mesh import MeshTree
    from distlearn_tpu.train import (build_lm_ea_steps, build_lm_mixed_step,
                                     build_lm_pp_1f1b_step, build_lm_pp_step,
                                     init_lm_ea_state, init_lm_mixed_state,
                                     stack_blocks)

    def plain():
        return _scoped_lm()

    def mixed():
        _, params, tokens = _scoped_lm()
        mesh = tokens.sharding.mesh
        model = transformer_lm(vocab=97, dim=32, depth=3, heads=4,
                               max_len=16, scan_blocks=True, remat="full")
        return (build_lm_mixed_step(model, mesh, params, lr=0.1,
                                    donate=False),
                init_lm_mixed_state(params), tokens)

    def pipelined(builder):
        def build():
            lm = transformer_lm(vocab=64, dim=32, depth=2, heads=2,
                                max_len=16)
            params, _ = lm.init(jax.random.PRNGKey(0))
            mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                        ("data", "pipe"))
            shared, stacked = stack_blocks(params, 2)
            return (builder(mesh, shared, stacked, lr=0.1,
                            num_microbatches=2, donate=False),
                    jax.device_put(shared, NamedSharding(mesh, P())),
                    jax.device_put(stacked, NamedSharding(mesh, P("pipe"))),
                    jax.device_put(np.zeros((4, 16), np.int32),
                                   NamedSharding(mesh, P("data"))))
        return build

    def elastic(which):
        def build():
            tree = MeshTree(num_nodes=2)
            lm = transformer_lm(vocab=32, dim=32, depth=2, heads=2,
                                max_len=16)
            st = init_lm_ea_state(lm, tree, jax.random.PRNGKey(0))
            local, rnd = build_lm_ea_steps(lm, tree, lr=0.1, alpha=0.25,
                                           donate=False)
            toks = jax.device_put(np.zeros((4, 16), np.int32),
                                  NamedSharding(tree.mesh, P("data")))
            return (local, st, toks) if which == "local" else (rnd, st)
        return build

    return {"lm": plain, "lm_mixed": mixed,
            "lm_pp": pipelined(build_lm_pp_step),
            "lm_pp_1f1b": pipelined(build_lm_pp_1f1b_step),
            "lm_ea_local": elastic("local"), "lm_ea_round": elastic("round")}


@pytest.mark.parametrize("name", ["lm", "lm_mixed", "lm_pp", "lm_pp_1f1b",
                                  "lm_ea_local", "lm_ea_round"])
def test_every_lm_builder_reports_its_dispatch(name):
    """Each LM builder returns the trainer's shim under its own name:
    ``.lower()`` still works, one call = one ``train.dispatch`` span + one
    histogram observation + one count, and the program can name its own
    instructions afterwards."""
    from distlearn_tpu import obs
    from distlearn_tpu.obs import core, trace
    from distlearn_tpu.train.trainer import _TimedStep, step_programs
    from distlearn_tpu.utils.profiling import scope_table
    core.configure(True)
    try:
        step, *args = _lm_builders()[name]()
        assert isinstance(step, _TimedStep)
        assert step_programs()[name] is step
        assert step.lower(*args).compile() is not None
        with pytest.raises(RuntimeError, match="not been called"):
            step.hlo_text()
        trace.clear()
        seen = step._h.count
        t_before = time.perf_counter()
        jax.block_until_ready(step(*args))
        t_after = time.perf_counter()
        spans = [s for s in obs.spans() if s["name"] == "train.dispatch"]
        assert len(spans) == 1 and spans[0]["labels"] == {"step": name}
        assert t_before <= spans[0]["t0"] <= t_after
        assert spans[0]["dur"] <= t_after - t_before
        assert step._h.count == seen + 1
        table = scope_table(step.hlo_text())
        assert table and all(isinstance(v, str) for v in table.values())
    finally:
        core.configure(None)


def test_obs_off_returns_the_bare_jit_and_records_nothing():
    from distlearn_tpu import obs
    from distlearn_tpu.obs import core, trace
    from distlearn_tpu.train.trainer import _TimedStep, step_programs
    before = step_programs()
    core.configure(False)
    try:
        step, params, tokens = _scoped_lm()
        assert not isinstance(step, _TimedStep)
        assert hasattr(step, "lower") and not hasattr(step, "hlo_text")
        trace.clear()
        jax.block_until_ready(step(params, tokens))
        assert obs.spans() == []
        assert step_programs() == before
    finally:
        core.configure(None)
