"""Backend pinning helpers.

Every entry point that needs a virtual CPU mesh (tests, the examples
without ``--tpu``, the driver's multichip dryrun) takes the
same two steps, centralized here: replace any
``xla_force_host_platform_device_count`` already in ``XLA_FLAGS`` (a
stale value must not override the caller's count), then pin the platform
through the config knob, which wins over ``JAX_PLATFORMS``.  Call BEFORE
any device query.  A process pinned this way never takes a TPU chip —
which is what lets a launcher run CPU roles beside the one process that
owns the chip.
"""

from __future__ import annotations

import os


def set_host_device_count(n: int) -> None:
    """Set ``--xla_force_host_platform_device_count=n``, replacing any
    existing value (a pre-set flag must not silently override the caller's
    requested count)."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def force_cpu(num_devices: int | None = None) -> None:
    """Pin the CPU backend (reliably, via the config knob), optionally with
    ``num_devices`` virtual devices."""
    if num_devices is not None:
        set_host_device_count(num_devices)
    import jax
    jax.config.update("jax_platforms", "cpu")
