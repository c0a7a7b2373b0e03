"""chip_smoke.py's phases at toy size on the 8-device CPU mesh (the
"run it here first" step: control flow and checks are proven before chip
minutes are spent), its refusal to run without a TPU, and the import
hygiene a launcher relies on: importing the package takes no chip."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

import chip_smoke  # noqa: E402


def test_trainer_phase_toy(devices):
    res = chip_smoke.phase_trainer(
        num_nodes=8, per_node_batch=2, scan_k=2, tau=2, lr=0.01, bf16=False,
        fused=True,                 # interpret-mode Pallas on the CPU
        dispatches=1, require_falling=False)
    assert res["nodes"] == 8 and res["global_batch"] == 16
    # interpreted here; on the chip the same programs must hold Mosaic calls
    assert res["mosaic_calls_sgd_step"] == 0


def test_lm_phase_toy(devices):
    res = chip_smoke.phase_lm(
        mesh_shape=(2, 1, 2), vocab=128, dim=64, depth=2, heads=4, batch=4,
        seq=64, long_seq=256, steps=3, bf16=False)
    assert res["losses"][-1] < res["losses"][0]
    # off the TPU (and at toy lengths) the default attention is the
    # full-square path; 2 blocks, each traced once for the step's program
    assert res["attn_kernels"] == {"64": {"xla": 2}, "256": {"xla": 2}}
    assert abs(res["loss_xla"] - res["losses"][0]) < 1e-3
    assert res["long_loss"] > 0


def test_hybrid_lm_phase_toy():
    res = chip_smoke.phase_hybrid_lm(
        vocab=97, dim=32, heads=4, kv_heads=2, head_dim=8, kda_heads=2,
        kda_head_dim=8, experts=8, held=(1, 6), top_k=2, expert_width=16,
        seq=128, steps=3, lr=0.05, bf16=False)
    assert res["losses"][-1] < res["losses"][0]
    assert res["attn_kernels"] == {"xla": 1} and res["mosaic_calls"] == 0
    assert set(res["moe_grouped"]) == {"xla"}
    assert set(res["delta_rule"]) == set(res["delta_rule_scan"]) == {"xla"}
    assert res["delta_rule_calls"] == 0
    assert np.shape(res["assignments"]) == (4, 2)


def test_serve_phase_toy():
    res = chip_smoke.phase_serve(
        vocab=97, dim=64, depth=2, heads=4, max_len=128, slots=4,
        prompt_lens=(5, 12, 20, 40), max_new=6, prefill_chunk=8,
        stream_new=40)
    assert res["prefill_buckets"] == [8, 16, 32, 64]
    assert res["cached_tokens"] == 32
    assert res["chunk_dispatches"] >= 2 and res["verify_dispatches"] >= 1
    # float32 engine against the float32 reference: the same tokens
    assert res["argmax_agreement"] == 1.0 and res["logit_gap_max"] < 1e-4


def test_wire_kernels_phase_toy():
    assert chip_smoke.phase_wire_kernels(n=5000)["elements"] == 5000


def test_chip_smoke_refuses_the_cpu():
    """No TPU visible: non-zero exit, a one-line reason, no result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "JAX found no TPU" in out.stderr
    assert "Traceback" not in out.stderr


def test_imports_initialise_no_backend():
    """A launcher or client that imports the package (or the driver's
    entry module, or the smoke) must not take the chip: no JAX backend
    comes up on import."""
    code = (
        "import json, sys\n"
        "import distlearn_tpu, distlearn_tpu.serve, distlearn_tpu.train\n"
        "import distlearn_tpu.models, distlearn_tpu.ops\n"
        "import __graft_entry__, chip_smoke\n"
        "from jax._src import xla_bridge\n"
        "print(json.dumps(xla_bridge.backends_are_initialized()))\n")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=ROOT,
                         env=dict(env, PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) is False
