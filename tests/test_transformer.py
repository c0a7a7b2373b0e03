"""Transformer LM: sequence-parallel (ring attention) and tensor-parallel
outputs must match the single-device model exactly (same full params)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlearn_tpu.models.transformer import (lm_loss, param_specs,
                                              transformer_lm)


def _model_and_batch(seed=0, L=32):
    model = transformer_lm(vocab=64, dim=64, depth=2, heads=4, max_len=L)
    params, _ = model.init(jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    tokens = jnp.asarray(rng.randint(0, 64, (2, L)).astype(np.int32))
    return model, params, tokens


def test_seq_parallel_matches_local():
    model, params, tokens = _model_and_batch()
    ref_logits, _ = model.apply(params, {}, tokens, train=False)

    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    mapped = jax.jit(jax.shard_map(
        lambda p, t: model.apply(p, {}, t, seq_axis="seq")[0],
        mesh=mesh, in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))
    out = mapped(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_logits),
                               rtol=5e-4, atol=5e-5)


def test_tensor_parallel_matches_local():
    model, params, tokens = _model_and_batch(1)
    ref_logits, _ = model.apply(params, {}, tokens, train=False)

    mesh = Mesh(np.array(jax.devices()[:4]), ("model",))
    specs = param_specs(params, "model")
    mapped = jax.jit(jax.shard_map(
        lambda p, t: model.apply(p, {}, t, tp_axis="model")[0],
        mesh=mesh, in_specs=(specs, P()),
        out_specs=P(), check_vma=False))
    out = mapped(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_logits),
                               rtol=5e-4, atol=5e-5)


def test_seq_x_tensor_2d_mesh():
    """Combined SP x TP over a 2D mesh: still exact."""
    model, params, tokens = _model_and_batch(2)
    ref_logits, _ = model.apply(params, {}, tokens, train=False)

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("seq", "model"))
    specs = param_specs(params, "model")
    mapped = jax.jit(jax.shard_map(
        lambda p, t: model.apply(p, {}, t, seq_axis="seq",
                                 tp_axis="model")[0],
        mesh=mesh, in_specs=(specs, P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))
    out = mapped(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_logits),
                               rtol=5e-4, atol=5e-5)


def test_lm_loss_seq_parallel_matches_local():
    model, params, tokens = _model_and_batch(3)
    ref = lm_loss(model, params, tokens)

    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    mapped = jax.jit(jax.shard_map(
        lambda p, t: lm_loss(model, p, t, seq_axis="seq"),
        mesh=mesh, in_specs=(P(), P(None, "seq")), out_specs=P(),
        check_vma=False))
    np.testing.assert_allclose(float(mapped(params, tokens)), float(ref),
                               rtol=1e-4)


def test_lm_gradients_flow():
    model, params, tokens = _model_and_batch(4)
    grads = jax.grad(lambda p: lm_loss(model, p, tokens))(params)
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all()


def test_remat_matches_no_remat():
    """jax.checkpoint'ed blocks: identical logits and gradients, just a
    different backward-pass memory/compute trade."""
    import numpy as np
    from jax import random
    from distlearn_tpu.models.transformer import lm_loss, transformer_lm

    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)),
                       jnp.int32)
    outs, grads = {}, {}
    for remat in (False, True, "mlp"):
        lm = transformer_lm(vocab=64, dim=32, depth=2, heads=4, max_len=16,
                            remat=remat)
        params, _ = lm.init(random.PRNGKey(0))
        outs[remat] = np.asarray(lm.apply(params, {}, toks)[0])
        grads[remat] = jax.grad(
            lambda p: lm_loss(lm, p, toks))(params)
    for mode in (True, "mlp"):
        np.testing.assert_allclose(outs[False], outs[mode],
                                   rtol=1e-6, atol=1e-7)
        for a, b in zip(jax.tree_util.tree_leaves(grads[False]),
                        jax.tree_util.tree_leaves(grads[mode])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


def _remat_lm(monkeypatch, wrap, **kw):
    """A toy LM whose blocks the blockwise kernel can be forced on (head 64,
    length 128), with its tokens.  ``wrap="bare"`` swaps the constructor's
    ``checkpoint_block`` for the bare ``jax.checkpoint`` it replaced."""
    from distlearn_tpu.models import transformer
    if wrap == "bare":
        monkeypatch.setattr(transformer, "checkpoint_block", jax.checkpoint)
    lm = transformer_lm(vocab=64, dim=128, depth=2, heads=2, max_len=128,
                        **kw)
    toks = jnp.asarray(np.random.RandomState(5).randint(0, 64, (2, 128)),
                       jnp.int32)
    return lm, toks


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scanned"])
@pytest.mark.parametrize("wrap,a_layer", [("named", 2), ("bare", 3)])
def test_full_remat_runs_the_forward_kernel_once_a_layer(monkeypatch, scan,
                                                         wrap, a_layer):
    """``remat="full"``: the gradient holds the forward and the backward
    kernel of each layer (of the scan's one body, when scanned) — the bare
    ``jax.checkpoint`` held the forward kernel once more, in the
    recomputation."""
    from tests.program_util import pallas_calls
    lm, toks = _remat_lm(monkeypatch, wrap, remat="full", scan_blocks=scan,
                         attn_impl="splash")
    params = jax.eval_shape(lambda k: lm.init(k)[0], jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: lm_loss(lm, p, toks)))(params)
    assert pallas_calls(jaxpr) == a_layer * (1 if scan else 2)


def _one_step(lm, toks):
    """Loss, gradients, and the parameters after one ``build_lm_step`` —
    and the step's lowered program."""
    from distlearn_tpu.train.lm import build_lm_step
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                ("data", "seq", "model"))
    params, _ = lm.init(jax.random.PRNGKey(0))
    tk = jax.device_put(toks, NamedSharding(mesh, P("data", "seq")))
    step = build_lm_step(lm, mesh, params, lr=0.1, donate=False)
    grads = jax.jit(jax.grad(lambda p: lm_loss(lm, p, toks)))(params)
    return step(params, tk), grads, step.lower(params, tk)


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scanned"])
@pytest.mark.parametrize("impl", ["splash", "xla"])
def test_full_remat_is_bitwise_the_bare_checkpoint(monkeypatch, scan, impl):
    """What the checkpoint keeps changes no number: loss, gradients and the
    parameters after one step are bitwise those of the bare
    ``jax.checkpoint`` block (the kernel in Pallas interpret mode here).
    On the full-square path the policy finds no name, and the step is the
    bare checkpoint's program text for text."""
    from tests.program_util import program_text
    kw = dict(remat="full", scan_blocks=scan, attn_impl=impl)
    (got, loss), grads, lowered = _one_step(*_remat_lm(monkeypatch, "named",
                                                       **kw))
    (want, loss0), grads0, lowered0 = _one_step(*_remat_lm(monkeypatch,
                                                           "bare", **kw))
    assert float(loss) == float(loss0)
    for tree, tree0 in ((got, want), (grads, grads0)):
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(tree0)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (program_text(lowered) == program_text(lowered0)) == (impl == "xla")


@pytest.mark.parametrize("remat", [False, "mlp"])
def test_other_remat_modes_lower_to_the_text_they_had(monkeypatch, remat):
    """No checkpoint around the attention asks for the kernel's name, so
    ``remat=False`` and ``remat="mlp"`` lower to what they lowered to when
    the kernel call named nothing."""
    from distlearn_tpu.parallel import sequence
    from tests.program_util import program_text
    kw = dict(remat=remat, attn_impl="splash")
    *_, lowered = _one_step(*_remat_lm(monkeypatch, "named", **kw))
    monkeypatch.setattr(sequence, "ATTN_RESIDUALS", None)
    *_, unnamed = _one_step(*_remat_lm(monkeypatch, "named", **kw))
    assert program_text(lowered) == program_text(unnamed)


def test_remat_mode_validation():
    from distlearn_tpu.models.transformer import transformer_lm
    with pytest.raises(ValueError, match="remat"):
        transformer_lm(vocab=8, dim=8, depth=1, heads=1, remat="bogus")


def test_scan_blocks_matches_unrolled():
    """The scanned-depth layout is the same function: identical logits and
    gradients once the parameters are stacked."""
    from distlearn_tpu.models.transformer import (lm_loss,
                                                  stack_block_params,
                                                  transformer_lm,
                                                  unstack_block_params)
    depth = 3
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)),
                       jnp.int32)
    lm_u = transformer_lm(vocab=64, dim=32, depth=depth, heads=4, max_len=16)
    lm_s = transformer_lm(vocab=64, dim=32, depth=depth, heads=4, max_len=16,
                          scan_blocks=True)
    params_u, _ = lm_u.init(jax.random.PRNGKey(0))
    params_s = stack_block_params(params_u, depth)
    # round trip
    rt = unstack_block_params(params_s, depth)
    for a, b in zip(jax.tree_util.tree_leaves(params_u),
                    jax.tree_util.tree_leaves(rt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    out_u = np.asarray(lm_u.apply(params_u, {}, toks)[0])
    out_s = np.asarray(lm_s.apply(params_s, {}, toks)[0])
    # same math, different op order (gathered stacked leaves): f32 noise
    np.testing.assert_allclose(out_s, out_u, rtol=2e-5, atol=5e-6)

    g_u = jax.grad(lambda p: lm_loss(lm_u, p, toks))(params_u)
    g_s = jax.grad(lambda p: lm_loss(lm_s, p, toks))(params_s)
    g_s_unstacked = unstack_block_params(g_s, depth)
    for (pa, a), (pb, b) in zip(
            sorted(jax.tree_util.tree_flatten_with_path(g_u)[0],
                   key=lambda t: str(t[0])),
            sorted(jax.tree_util.tree_flatten_with_path(g_s_unstacked)[0],
                   key=lambda t: str(t[0]))):
        assert str(pa) == str(pb)
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=1e-6, err_msg=str(pa))


def test_scan_blocks_program_size_flat_in_depth():
    """The point of the scanned layout: the jitted program stops growing
    ~linearly with depth (the unrolled loop's growth is what made deep
    long-context configs exceed compile limits)."""
    from distlearn_tpu.models.transformer import lm_loss, transformer_lm

    def hlo_len(depth, scan):
        lm = transformer_lm(vocab=64, dim=32, depth=depth, heads=4,
                            max_len=16, scan_blocks=scan)
        params, _ = lm.init(jax.random.PRNGKey(0))
        toks = jnp.zeros((1, 16), jnp.int32)
        f = jax.jit(jax.grad(lambda p: lm_loss(lm, p, toks)))
        return len(f.lower(params).as_text())

    grow_unrolled = hlo_len(8, False) / hlo_len(2, False)
    grow_scanned = hlo_len(8, True) / hlo_len(2, True)
    assert grow_unrolled > 2.5, grow_unrolled    # ~4x expected
    assert grow_scanned < 1.4, grow_scanned      # ~flat


def test_scan_blocks_with_lm_step_and_tp():
    """The scanned layout composes with the fused train step: param_specs
    shifts the TP axes one right for the stacked leaves."""
    from distlearn_tpu.models.transformer import (lm_loss,
                                                  stack_block_params,
                                                  transformer_lm)
    from distlearn_tpu.train.lm import build_lm_step

    depth, L = 2, 32
    lm_u = transformer_lm(vocab=32, dim=32, depth=depth, heads=4, max_len=L)
    lm_s = transformer_lm(vocab=32, dim=32, depth=depth, heads=4, max_len=L,
                          scan_blocks=True)
    params_u, _ = lm_u.init(jax.random.PRNGKey(0))
    params_s = stack_block_params(params_u, depth)
    toks = np.random.RandomState(0).randint(0, 32, (4, L)).astype(np.int32)
    _, ref_g = jax.value_and_grad(
        lambda p: lm_loss(lm_u, p, jnp.asarray(toks)))(params_u)
    from distlearn_tpu.models.transformer import stack_block_params as sbp
    ref_g_s = sbp(ref_g, depth)

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "seq", "model"))
    step = build_lm_step(lm_s, mesh, params_s, lr=1.0, donate=False)
    tk = jax.device_put(toks, NamedSharding(mesh, P("data", "seq")))
    newp, _ = step(params_s, tk)
    for a, b, g in zip(jax.tree_util.tree_leaves(params_s),
                       jax.tree_util.tree_leaves(newp),
                       jax.tree_util.tree_leaves(ref_g_s)):
        implied = np.asarray(a) - np.asarray(b)
        denom = max(1e-12, float(np.abs(np.asarray(g)).max()))
        err = float(np.abs(implied - np.asarray(g)).max()) / denom
        assert err < 3e-5, err


def test_scan_blocks_rejects_moe():
    from distlearn_tpu.models.transformer import transformer_lm
    with pytest.raises(ValueError, match="scan_blocks"):
        transformer_lm(vocab=8, dim=8, depth=2, heads=1, scan_blocks=True,
                       moe_experts=2)


def test_greedy_generate_matches_no_cache_rollout():
    """The KV-cached decode must emit the SAME tokens as the naive
    rollout (re-run the full forward on the growing sequence, argmax the
    last position each time) — the cache is an optimization, not a
    different model."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distlearn_tpu.models.transformer import (greedy_generate,
                                                  transformer_lm)

    model = transformer_lm(vocab=43, dim=32, depth=2, heads=2, max_len=48)
    params, _ = model.init(jax.random.PRNGKey(3))
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 43, (2, 8)).astype(np.int32)
    steps = 12

    # naive rollout oracle
    seq = jnp.asarray(prompt)
    naive = []
    for _ in range(steps):
        logits, _ = model.apply(params, {}, seq, train=False)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        naive.append(np.asarray(nxt))
        seq = jnp.concatenate([seq, nxt[:, None].astype(jnp.int32)], 1)
    naive = np.stack(naive, axis=1)                 # [B, steps]

    got = np.asarray(greedy_generate(params, jnp.asarray(prompt), steps))
    np.testing.assert_array_equal(got, naive)


def test_greedy_generate_ragged_matches_per_row():
    """A left-padded ragged batch with ``prompt_lens`` must emit, per
    row, the same tokens as running that row alone at its true length —
    the pads must be invisible to positions and attention."""
    import jax
    import numpy as np

    from distlearn_tpu.models.transformer import (greedy_generate,
                                                  transformer_lm)

    model = transformer_lm(vocab=43, dim=32, depth=2, heads=2, max_len=48)
    params, _ = model.init(jax.random.PRNGKey(3))
    rng = np.random.RandomState(0)
    lens = [3, 8, 5, 1]
    P, steps = max(lens), 9
    rows = [rng.randint(0, 43, (n,)).astype(np.int32) for n in lens]
    batch = np.zeros((len(rows), P), np.int32)
    for b, row in enumerate(rows):
        batch[b, P - len(row):] = row                    # left-pad
    got = np.asarray(greedy_generate(params, batch, steps,
                                     prompt_lens=np.array(lens)))
    for b, row in enumerate(rows):
        ref = np.asarray(greedy_generate(params, row[None], steps))[0]
        np.testing.assert_array_equal(got[b], ref, err_msg=f"row {b}")


def test_greedy_generate_full_prompt_lens_identical():
    """``prompt_lens`` set to the full width is the no-padding case and
    must be bit-identical to the ``prompt_lens=None`` fast path."""
    import jax
    import numpy as np

    from distlearn_tpu.models.transformer import (greedy_generate,
                                                  transformer_lm)

    model = transformer_lm(vocab=43, dim=32, depth=2, heads=2, max_len=48)
    params, _ = model.init(jax.random.PRNGKey(3))
    prompt = np.random.RandomState(1).randint(0, 43, (3, 7)) \
        .astype(np.int32)
    want = np.asarray(greedy_generate(params, prompt, 10))
    got = np.asarray(greedy_generate(params, prompt, 10,
                                     prompt_lens=np.full(3, 7)))
    np.testing.assert_array_equal(got, want)


def test_greedy_generate_rejects_overlong():
    import jax
    import numpy as np
    import pytest as _pytest

    from distlearn_tpu.models.transformer import (greedy_generate,
                                                  transformer_lm)

    model = transformer_lm(vocab=17, dim=32, depth=1, heads=2, max_len=16)
    params, _ = model.init(jax.random.PRNGKey(0))
    with _pytest.raises(ValueError, match="max_len"):
        greedy_generate(params, np.zeros((1, 10), np.int32), 10)


def test_greedy_generate_scanned_layout_and_moe_gate():
    """Scanned-layout trees unstack automatically; MoE trees are
    rejected loudly (per-tick routing would not match the trained
    capacity math)."""
    import jax
    import numpy as np
    import pytest as _pytest

    from distlearn_tpu.models.transformer import (greedy_generate,
                                                  stack_block_params,
                                                  transformer_lm)

    model = transformer_lm(vocab=43, dim=32, depth=2, heads=2, max_len=48)
    params, _ = model.init(jax.random.PRNGKey(3))
    prompt = np.random.RandomState(0).randint(0, 43, (1, 8)) \
        .astype(np.int32)
    want = np.asarray(greedy_generate(params, prompt, 6))
    scanned = stack_block_params(params, 2)
    got = np.asarray(greedy_generate(scanned, prompt, 6))
    np.testing.assert_array_equal(got, want)

    moe = transformer_lm(vocab=43, dim=32, depth=2, heads=2, max_len=48,
                         moe_experts=2)
    mp, _ = moe.init(jax.random.PRNGKey(0))
    with _pytest.raises(ValueError, match="dense"):
        greedy_generate(mp, prompt, 4)


# ------------------------------------------------------- named scopes --

def test_decode_programs_get_the_shared_functions_scopes():
    """The decode side calls the same ``attn_qkv`` / ``attn_out`` /
    ``ffn_apply`` / ``decode_attend`` / ``_rmsnorm``: its programs carry
    the scopes without a line of their own."""
    from distlearn_tpu.models.transformer import greedy_generate
    from distlearn_tpu.utils.profiling import scope_table
    model = transformer_lm(vocab=32, dim=32, depth=2, heads=4, max_len=16)
    params, _ = model.init(jax.random.PRNGKey(0))
    prompt = jnp.zeros((2, 4), jnp.int32)
    text = jax.jit(lambda p, t: greedy_generate(p, t, 3)).lower(
        params, prompt).compile().as_text()
    names = "\n".join(scope_table(text).values())
    for scope in ("norm", "attn_proj", "attn_core", "mlp"):
        assert f"/{scope}/" in names, scope


def test_scope_table_reads_an_instruction_that_runs_over_lines():
    """A Pallas call that hands the profiler ``kernel_metadata`` prints it
    with line breaks before its ``op_name``; an instruction with no
    ``op_name`` must not borrow its neighbour's."""
    from distlearn_tpu.utils.profiling import scope_table
    text = """
  %copy-done.1 = bf16[8,20,1024,64]{3,2,1,0} copy-done(%copy-start.1)
  %splash_mha_fwd.7 = (f32[8,512,128]{2,1,0}, bf16[8,20,1024,64]{3,2,1,0}) custom-call(%copy-done.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512}"
}}, metadata={op_name="jit(step)/jvp()/while/body/checkpoint/attn_core/pallas_call" stack_frame_id=6}, backend_config={}
  %bitcast.3 = bf16[8,1024,20,64]{3,1,2,0} bitcast(%custom-call)
  ROOT %fusion.2 = f32[8]{0} fusion(%bitcast.3), kind=kLoop, metadata={op_name="jit(step)/jvp()/mlp/add"}
"""
    assert scope_table(text) == {
        "splash_mha_fwd.7":
            "jit(step)/jvp()/while/body/checkpoint/attn_core/pallas_call",
        "fusion.2": "jit(step)/jvp()/mlp/add"}


@pytest.fixture(scope="module")
def v5e_host():
    """The four described (not attached) chips of a v5e host: the TPU
    compiler runs here without them.  Inside a fixture, in this one file,
    so that only the worker that is given this file loads the TPU's
    library."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def v5e_chip(v5e_host):
    return v5e_host[0]


def _compiled_lm_step(model, devices, batch):
    """``build_lm_step``'s program for ``batch`` x 1024 tokens a chip,
    compiled for the described ``devices`` as a data-parallel mesh."""
    from jax.experimental.compilation_cache import compilation_cache
    from distlearn_tpu.train.lm import build_lm_step
    mesh = Mesh(np.array(devices).reshape(len(devices), 1, 1),
                ("data", "seq", "model"))
    template = jax.eval_shape(lambda k: model.init(k)[0],
                              jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                          sharding=NamedSharding(mesh, s)),
        template, param_specs(template, "model"))
    tokens = jax.ShapeDtypeStruct(
        (batch * len(devices), 1024), jnp.int32,
        sharding=NamedSharding(mesh, P("data", "seq")))
    # a compile for a described chip cannot be read back from the
    # persistent cache: keep it out, and the run silent
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        # conftest turns 64-bit types on; a program for the chip has them
        # off (with them on local_attention keeps the full-square path:
        # Mosaic takes no int64 loop counter)
        with jax.enable_x64(False):
            return build_lm_step(model, mesh, template, lr=0.03).lower(
                params, tokens).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("kind,backend", [("dense", "tpu"), ("dense", "cpu"),
                                          ("hybrid", "tpu")])
def test_tpu_compiler_keeps_every_scope_at_gpt2_large_width(
        v5e_chip, kind, backend, monkeypatch):
    """The benchmark's step at GPT-2-large's sizes (36 layers x 1280 x 20
    heads, vocab 50257, 8 x 1024 tokens, bf16, scanned, full remat: the
    scan makes the program no longer than at depth 2), compiled for the
    v5e: every declared scope survives the TPU compiler's fusion, in every
    pass it belongs to.  Nothing runs; no number of this is a measurement.

    ``kind="hybrid"``: the pattern LM's step at ITS published widths (4096,
    64 query heads over 8 K/V heads of 128, 64 KDA heads of 128, experts
    1280 wide, a router of 320, 8 a token; one softmax and one
    linear-attention layer, 2 experts held, 1 x 1024 tokens, a cut of the
    vocabulary) — the two scopes only it uses survive too, and its softmax
    layer's grouped queries ride the same two Mosaic calls, beside the
    three of the delta rule's per-chunk operands (``ops/delta_rule.py``:
    forward, recomputed forward, pull-back), which Mosaic takes at the
    published head size.

    ``local_attention`` picks its path from ``jax.default_backend()``,
    which here says "cpu" whatever the program is compiled for: ``"tpu"``
    steers it to what the chip runs (the blockwise kernel: two Mosaic calls
    under ``attn_core``, the forward and the backward pass's — the
    recomputation holds none, because the block's checkpoint keeps the two
    results the backward call reads, :func:`checkpoint_block` — and no
    ``[B, H, L, L]`` array), ``"cpu"`` leaves the full-square path short or
    ragged lengths keep.  What the dense step keeps for that fits the chip:
    the compiler's own temporary + argument bytes, at the full depth."""
    from distlearn_tpu.parallel import sequence
    monkeypatch.setattr(sequence, "_backend", lambda: backend)
    from distlearn_tpu.models.core import SCOPES
    from distlearn_tpu.utils.profiling import scope_table
    if kind == "dense":
        model = transformer_lm(vocab=50257, dim=1280, depth=36, heads=20,
                               max_len=1024, compute_dtype=jnp.bfloat16,
                               scan_blocks=True, remat="full")
        batch, kernel, square = 8, "splash_mha", "[8,20,1024,1024]"
        mine = set(SCOPES) - {"linattn_core", "moe"}
    else:
        from distlearn_tpu.models import hybrid_lm
        model = hybrid_lm(vocab=4096, dim=4096, layer_types=("gqa", "kda"),
                          heads=64, kv_heads=8, head_dim=128, kda_heads=64,
                          kda_head_dim=128, n_routed_experts=320,
                          held_experts=(0, 1), experts_per_tok=8,
                          expert_width=1280, max_len=1024,
                          compute_dtype=jnp.bfloat16, remat="full")
        batch, kernel, square = 1, "splash_mqa", "[1,64,1024,1024]"
        mine = set(SCOPES)
    compiled = _compiled_lm_step(model, [v5e_chip], batch)
    text = compiled.as_text()
    table = scope_table(text)
    names = list(table.values())
    kernels = {k: v for k, v in table.items() if k.startswith(kernel)}
    square = square in text
    if backend == "tpu":
        mosaic = re.findall(
            r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
        assert len([c for c in mosaic if c.startswith(kernel)]) == 2
        # every other Mosaic call is the delta rule's (the dense model has
        # none): its per-chunk operands in each pass — forward, recomputed
        # forward, pull-back — under the layer's scope and the rule's name
        rule = [table[c].replace("(", "/").replace(")", "/")
                for c in mosaic if not c.startswith(kernel)]
        assert len(rule) == (3 if kind == "hybrid" else 0)
        assert all("/linattn_core/" in n and "/delta_rule/" in n
                   for n in rule), rule
        assert sorted("vjp" if "delta_rule_operands_vjp" in n else
                      "remat" if "rematted_computation" in n else "fwd"
                      for n in rule) == ["fwd", "remat", "vjp"][:len(rule)]
        assert not square
        assert not any("rematted_computation" in n for n in kernels.values())
        passes = sorted(
            ("bwd" if "transpose(" in n else "fwd", k.split(".")[0])
            for k, n in kernels.items()
            if "/attn_core/" in n.replace("(", "/").replace(")", "/"))
        assert passes == [("bwd", f"{kernel}_dkv_no_residuals"),
                          ("fwd", f"{kernel}_fwd_residuals")]
        memory = compiled.memory_analysis()
        held = memory.temp_size_in_bytes + memory.argument_size_in_bytes
        assert held < 15.75e9, (
            f"the {kind} step holds {held / 1e9:.2f} GB of temporaries "
            "and arguments by the compiler's count: over the chip's "
            "15.75 GB")
    else:
        assert square and not kernels

    def seen(scope, *marks, without=()):
        return any(f"/{scope}/" in n.replace("(", "/").replace(")", "/")
                   and all(m in n for m in marks)
                   and not any(w in n for w in without) for n in names)

    for scope in mine - {"embed", "head_loss", "update", "grad_reduce"}:
        assert seen(scope, "jvp(", without=("transpose(",)), scope
        assert seen(scope, "rematted_computation"), scope
        assert seen(scope, "transpose(",
                    without=("rematted_computation",)), scope
    for scope in ("embed", "head_loss"):
        assert seen(scope, "jvp("), scope
    assert seen("update")
    # grad_reduce is all collectives and a scaling by 1/dp = 1: on one chip
    # the compiler folds it away, so all the model's names but that remain
    # — and the dense model shows none of the pattern LM's two
    assert mine - {"grad_reduce"} <= {s for s in SCOPES if seen(s)} <= mine


def test_tpu_compiler_hides_the_gradient_sum_behind_the_backward_scan(
        v5e_host, monkeypatch):
    """``gpt2-large.train-dp4``'s step (the sizes above on all four
    described chips, mesh ``[4, 1, 1]``), compiled for the v5e: the
    backward loop's body starts its ``collective-permute``s BEFORE the
    layer's backward attention kernel and awaits them AFTER it, so each
    layer's gradient crosses the links behind the layer that follows; no
    all-reduce of a ``[36, ...]`` stack is left after the loop; the loop we
    wrote keeps what ``checkpoint_block`` keeps (two Mosaic calls: the
    forward kernel is not back in the backward pass); and what the step
    holds fits the chip.  Nothing runs; no number of this is a
    measurement."""
    from distlearn_tpu.parallel import sequence
    monkeypatch.setattr(sequence, "_backend", lambda: "tpu")
    model = transformer_lm(vocab=50257, dim=1280, depth=36, heads=20,
                           max_len=1024, compute_dtype=jnp.bfloat16,
                           scan_blocks=True, remat="full")
    compiled = _compiled_lm_step(model, v5e_host, 8)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not re.search(r"= \(?f32\[36,[^=]*? all-reduce(-start)?\(", text)
    body, = [c for c in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\) -> )",
                                 text)
             if "splash_mha_dkv_no_residuals" in c
             and "fused_computation" not in c.split("\n", 1)[0]]
    kernel = body.index(" custom-call(", body.index(
        "%splash_mha_dkv_no_residuals"))
    starts = [m.start() for m in re.finditer(
        r" collective-permute-start\(", body)]
    dones = [m.start() for m in re.finditer(
        r" collective-permute-done\(", body)]
    # five transfers a layer: the halves and quarters of two layers'
    # gradients, and one layer's summed quarter to each of the other chips
    assert len(starts) == len(dones) == 5
    assert max(starts) < kernel < min(dones)
    memory = compiled.memory_analysis()
    held = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert held < 15.75e9, f"{held / 1e9:.2f} GB of temporaries and arguments"


@pytest.mark.parametrize("chunk,sub,B,H,L,K,V", [
    (32, 8, 1, 64, 8192, 128, 128),     # solar-open2-250b.train-8k's call
    (64, 16, 1, 2, 1024, 256, 128),     # two chunks a block, two lane tiles
    (16, 8, 2, 3, 416, 128, 256),       # eight chunks a block, the last
])                                      # block partly past the end
def test_tpu_compiler_takes_the_delta_rule_kernels(v5e_chip, monkeypatch,
                                                   chunk, sub, B, H, L, K, V):
    """The two Pallas calls of the delta rule's per-chunk operands
    (``ops/delta_rule.py``), forward and pull-back, compiled for the v5e
    at every kind of shape ``select_delta_rule`` sends them: Mosaic takes
    the slices, reshapes and products, and the VMEM they need.  Nothing
    runs; no number of this is a measurement."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from distlearn_tpu.ops import delta_rule
    from distlearn_tpu.parallel import sequence
    monkeypatch.setattr(sequence, "_backend", lambda: "tpu")
    bf16 = jnp.bfloat16
    assert delta_rule.select_delta_rule("tpu", bf16, K, V, chunk, sub) \
        == "kernel"
    one = SingleDeviceSharding(v5e_chip)
    arg = lambda X, dt: jax.ShapeDtypeStruct(                   # noqa: E731
        (B, H, L) + X, dt, sharding=one)

    def loss(q, k, v, g, beta):
        ops = delta_rule._chunk_operands(q, k, v, g, beta, chunk, sub, bf16,
                                         "kernel")
        return sum(jnp.sum(o.astype(jnp.float32)) for o in ops)

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
                arg((K,), bf16), arg((K,), bf16), arg((V,), bf16),
                arg((K,), jnp.float32), arg((), jnp.float32)
            ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "delta_rule_operands_vjp" in text


def test_tpu_compiler_takes_the_windowed_kernel_at_its_published_widths(
        v5e_chip, monkeypatch):
    """The blockwise kernel with a causal BAND at the widths a windowed
    layer is trained at (28 query heads over 4 K/V heads of 128, one
    sequence of 16,384, a band of 4,096, bf16), forward and backward,
    compiled for the v5e: two Mosaic calls (grouped queries:
    ``splash_mqa``), no ``[28, 16384, 16384]`` array, and the rotation
    before it fused by the compiler at that size.  Nothing runs; no number
    of this is a measurement."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from distlearn_tpu.models.transformer import rotary
    from distlearn_tpu.parallel import sequence
    monkeypatch.setattr(sequence, "_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e_chip)
    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        pos = jnp.arange(q.shape[1])
        out = sequence.local_attention(
            rotary(q, pos, 1.5e6), rotary(k, pos, 1.5e6), v, causal=True,
            window=4096)
        return jnp.sum(out.astype(jnp.float32))

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                q, kv, kv).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "splash_mqa_fwd" in text and "splash_mqa_dkv" in text
    assert "16384,16384" not in text


@pytest.mark.parametrize("n,d,f,routed,held,top_k,act", [
    # smallthinker-21b-a3b.train-16k: 16 of 64 ReLU-gated experts, top-6
    (16384, 2560, 768, 64, 16, 6, "relu"),
    # solar-open2-250b.train-8k's widths at a load that selects the kernel
    # path (the cell itself expects 204 rows an expert and keeps the loop)
    (16384, 4096, 1280, 64, 8, 4, "silu"),
])
def test_tpu_compiler_takes_the_grouped_kernels_at_published_widths(
        v5e_chip, monkeypatch, n, d, f, routed, held, top_k, act):
    """The held experts' layer on its kernel path (``parallel/ep.py``:
    packed rows through its grouped-product kernels, the combine through
    its one-hot product), forward and backward, compiled for the v5e at the
    widths the pattern cells train: every tile this module chooses from the shapes fits
    the chip's scoped VMEM (the compiler refuses one that does not), the
    path is the selection's own, and the program holds the kernels it should
    — three products and the unpack forward; three products, three weight
    gradients and the unpack backward (what lies beyond the packed buffer is
    the loop's, which has no kernel).  Nothing runs; no number of this is a
    measurement."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from distlearn_tpu.parallel import ep, sequence
    monkeypatch.setattr(sequence, "_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e_chip)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    args = (S((n, d), jnp.bfloat16), S((d, routed), jnp.float32),
            S((held, d, f), jnp.float32), S((held, d, f), jnp.float32),
            S((held, f, d), jnp.float32))

    def loss(x, router, wg, wu, wd):
        y, _ = ep.moe_held_ffn(x, router, (wg, wu, wd), tuple(range(held)),
                               top_k, compute_dtype=jnp.bfloat16, act=act)
        return jnp.sum(y.astype(jnp.float32))

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    before = ep.grouped_paths_traced()
    try:
        with jax.enable_x64(False):
            text = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile(
                ).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    after = ep.grouped_paths_traced()
    assert after.get("gmm", 0) - before.get("gmm", 0) == 1
    assert after.get("xla", 0) == before.get("xla", 0)
    assert text.count('custom_call_target="tpu_custom_call"') == 4 + 7


def test_tpu_compiler_takes_the_latent_kernel_at_its_published_widths(
        v5e_chip, monkeypatch):
    """The blockwise kernel at the sizes a latent-attention layer is trained
    at (32 heads, 192-wide q and k beside a 128-wide v, one sequence of
    16,384, bf16), forward and backward, compiled for the v5e: two Mosaic
    calls (``splash_mha``), no ``[32, 16384, 16384]`` array, and the fused
    backward's unreduced dq cut to 8 copies of q by ``_dkv_block`` (at the
    512-wide block of the other calls it is 32 copies, 6.4 GB, and the whole
    step does not fit the chip).  Nothing runs; no number of this is a
    measurement."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from distlearn_tpu.parallel import sequence
    monkeypatch.setattr(sequence, "_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e_chip)
    qk = jax.ShapeDtypeStruct((1, 16384, 32, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        return jnp.sum(sequence.local_attention(
            q, k, v, causal=True).astype(jnp.float32))

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                qk, qk, v).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
    assert "16384,16384" not in text
    q_bytes = 16384 * 32 * 192 * 2
    assert sequence._dkv_block(512, q_bytes, 16384) == 2048
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5e9
    # every call the package made before keeps the block it had
    assert sequence._dkv_block(512, 16384 * 28 * 128 * 2, 16384) == 512
    assert sequence._dkv_block(512, 8 * 1024 * 20 * 64 * 2, 1024) == 512
    # the widest that tiles the length, or the block as it was
    assert sequence._dkv_block(512, 8 * q_bytes, 3 * 1024) == 1024
    assert sequence._dkv_block(512, 8 * q_bytes, 3 * 512) == 512


# --- rotary positions -------------------------------------------------------

def test_rotary_is_the_complex_rotation_of_the_half_split_pairs():
    """Pair ``(d, d + D/2)`` read as the complex number ``x[d] + i x[d +
    D/2]`` is multiplied by ``exp(i p theta^(-2d/D))``: against that formula
    written out in numpy's complex128, at positions up to 16k."""
    from distlearn_tpu.models.transformer import rotary
    rng = np.random.RandomState(0)
    x = rng.randn(2, 6, 3, 16).astype(np.float32)
    pos = np.array([0, 1, 2, 977, 4096, 16383])
    theta = 1.5e6
    z = x[..., :8].astype(np.complex128) + 1j * x[..., 8:]
    turn = np.exp(1j * pos[:, None] * theta ** (-np.arange(8) / 8.0))
    want = z * turn[None, :, None, :]
    got = np.asarray(rotary(jnp.asarray(x), jnp.asarray(pos), theta))
    # float32 angles: position 16383 x frequency 1 is held to 1e-3 rad
    np.testing.assert_allclose(got[..., :8], want.real, atol=4e-3)
    np.testing.assert_allclose(got[..., 8:], want.imag, atol=4e-3)
    np.testing.assert_allclose(got[:, :3, :, :8], want.real[:, :3], atol=1e-6)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])       # position 0
    # a rotation: every pair keeps its length
    np.testing.assert_allclose(got[..., :8] ** 2 + got[..., 8:] ** 2,
                               x[..., :8] ** 2 + x[..., 8:] ** 2, rtol=1e-5)
    assert rotary(jnp.asarray(x, jnp.bfloat16), jnp.asarray(pos),
                  theta).dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="even head size"):
        rotary(jnp.zeros((1, 2, 1, 7)), jnp.arange(2), theta)


@pytest.mark.parametrize("shift", [1, 37, 4096])
def test_rotary_scores_depend_on_the_distance_alone(shift):
    """q and k rotated at positions ``p + shift`` give the scores they give
    at ``p``: the attention of a rotary layer is invariant to a common shift
    of the positions."""
    from distlearn_tpu.models.transformer import rotary
    rng = np.random.RandomState(1)
    q, k = (jnp.asarray(rng.randn(1, 24, 2, 32).astype(np.float32))
            for _ in range(2))
    pos = jnp.arange(24)

    def scores(p):
        return jnp.einsum("bqhd,bkhd->bhqk", rotary(q, p, 1e4),
                          rotary(k, p, 1e4))

    base = scores(pos)
    np.testing.assert_allclose(np.asarray(scores(pos + shift)),
                               np.asarray(base), atol=2e-3 if shift > 100
                               else 2e-4)
    # and they are NOT the unrotated scores
    assert float(jnp.abs(base - jnp.einsum("bqhd,bkhd->bhqk", q, k)).max()) \
        > 0.1


def test_rotary_interleaved_is_the_complex_rotation_of_neighbouring_pairs():
    """``pairing="interleaved"``: the pair ``(2d, 2d + 1)`` read as the
    complex number ``x[2d] + i x[2d+1]`` is multiplied by ``exp(i p
    theta^(-2d/D))`` and left in its places: against that formula in
    numpy's complex128, at theta 32e6 and positions up to 16k; and it is NOT
    the rotation by halves."""
    from distlearn_tpu.models.transformer import rotary
    rng = np.random.RandomState(2)
    x = rng.randn(2, 6, 3, 16).astype(np.float32)
    pos = np.array([0, 1, 2, 977, 4096, 16383])
    theta = 3.2e7
    z = x[..., 0::2].astype(np.complex128) + 1j * x[..., 1::2]
    turn = np.exp(1j * pos[:, None] * theta ** (-np.arange(8) / 8.0))
    want = z * turn[None, :, None, :]
    got = np.asarray(rotary(jnp.asarray(x), jnp.asarray(pos), theta,
                            pairing="interleaved"))
    np.testing.assert_allclose(got[..., 0::2], want.real, atol=4e-3)
    np.testing.assert_allclose(got[..., 1::2], want.imag, atol=4e-3)
    np.testing.assert_allclose(got[:, :3, :, 0::2], want.real[:, :3],
                               atol=1e-6)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])       # position 0
    np.testing.assert_allclose(got[..., 0::2] ** 2 + got[..., 1::2] ** 2,
                               x[..., 0::2] ** 2 + x[..., 1::2] ** 2,
                               rtol=1e-5)
    halves = np.asarray(rotary(jnp.asarray(x), jnp.asarray(pos), theta))
    assert np.abs(halves - got).max() > 0.1
    # the default is the rotation by halves, argument or none
    np.testing.assert_array_equal(halves, np.asarray(rotary(
        jnp.asarray(x), jnp.asarray(pos), theta, pairing="half")))
    assert rotary(jnp.asarray(x, jnp.bfloat16), jnp.asarray(pos), theta,
                  "interleaved").dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="pairing must be one of"):
        rotary(jnp.asarray(x), jnp.asarray(pos), theta, pairing="pairs")


@pytest.mark.parametrize("shift", [1, 37, 4096])
def test_rotated_slice_of_a_head_keeps_scores_on_the_distance_alone(shift):
    """A head whose LAST 8 of 24 dimensions are rotated by neighbouring
    pairs and whose first 16 are not (the latent-attention head): q and k at
    positions ``p + shift`` give the scores they give at ``p``."""
    from distlearn_tpu.models.transformer import rotary
    rng = np.random.RandomState(3)
    q, k = (jnp.asarray(rng.randn(1, 24, 2, 24).astype(np.float32))
            for _ in range(2))
    pos = jnp.arange(24)

    def scores(p):
        turn = lambda u: jnp.concatenate(                   # noqa: E731
            [u[..., :16], rotary(u[..., 16:], p, 3.2e7, "interleaved")], -1)
        return jnp.einsum("bqhd,bkhd->bhqk", turn(q), turn(k))

    base = scores(pos)
    np.testing.assert_allclose(np.asarray(scores(pos + shift)),
                               np.asarray(base), atol=2e-3 if shift > 100
                               else 2e-4)
    assert float(jnp.abs(base - jnp.einsum("bqhd,bkhd->bhqk", q, k)).max()) \
        > 0.1
