"""Parity-harness smoke: the example's --parity mode must emit a valid JSON
accuracy line and demonstrably learn on the synthetic set (docs/PARITY.md)."""

import json
import os
import subprocess
import sys

_EXAMPLES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples")


def test_mnist_parity_line():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)       # example sets its own device count
    out = subprocess.run(
        [sys.executable, os.path.join(_EXAMPLES, "mnist.py"),
         "--numNodes", "2", "--numEpochs", "3", "--batchSize", "64",
         "--numExamples", "512", "--learningRate", "0.05",
         "--reportEvery", "1000", "--parity"],
        capture_output=True, text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    rec = json.loads(line)
    assert rec["example"] == "mnist" and rec["data"] == "synthetic"
    assert rec["nodes"] == 2 and rec["epochs"] == 3
    # synthetic set is separable: 3 epochs must beat chance by a wide margin
    # (docs/PARITY.md synthetic row; probe run reached ~0.9 by epoch 3)
    assert rec["train_acc"] > 0.5, rec


def test_example_tpu_flag_refuses_the_cpu():
    """``--tpu`` means "use the TPU": with none visible the example exits
    non-zero with a one-line reason instead of training on the CPU."""
    out = subprocess.run(
        [sys.executable, os.path.join(_EXAMPLES, "mnist.py"),
         "--numNodes", "1", "--tpu"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "JAX found no TPU" in out.stderr
    assert "Traceback" not in out.stderr
