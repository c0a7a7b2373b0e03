"""Test harness: multi-node without a cluster.

The reference tests spawn worker threads with fresh Lua states connected over
real localhost TCP (``ipc.map`` — test/test_AllReduceSGD.lua:26-35).  The
TPU-native analogue is a virtual multi-device CPU mesh: force 8 host-platform
devices so every collective runs through the real shard_map/psum code path
(SURVEY.md §4 "implication for the TPU build").  Must be set before jax import.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402  (import after env setup)

# Tests run on the 8-device virtual CPU mesh whatever JAX_PLATFORMS says: a
# developer shell on a TPU host must not hand the suite (or one xdist worker)
# the chip.
jax.config.update("jax_platforms", "cpu")

# The reference's tensors are torch DoubleTensors by default; the EA invariant
# test needs float64 to reproduce its <1e-6 oracle (test_AllReduceEA.lua:38).
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

# tests/benchmark's files are the benchmark's and are not edited: what one
# of its tests needs repaired besides its own conftest.py comes as a plugin
pytest_plugins = ["benchmark.manifest_of_its_day"]


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs
