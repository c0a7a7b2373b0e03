"""Shared example plumbing (the reference's examples share Data.lua/Model.lua;
here: platform setup + data/stream helpers).

One SPMD process drives ALL nodes: where the reference launches N OS
processes connected by TCP (examples/mnist.sh spawning ``th mnist.lua
--nodeIndex i &``), a JAX program places one program over an N-device mesh.
``--numNodes`` picks the mesh size; ``--nodeIndex`` is accepted for CLI
parity and used only to label multi-host processes.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup_platform(num_nodes: int, tpu: bool):
    """Pick the backend BEFORE any device query.

    ``--tpu`` means "use the TPU": this process takes the chip(s) — one
    process per chip, so in a multi-process launcher only the roles that
    compute pass it — and exits non-zero when JAX finds none, never
    training on the CPU under a TPU label.  Otherwise: CPU, explicitly,
    with ``num_nodes`` virtual host devices (the reference's
    LocalhostTree analogue, SURVEY.md §4).
    """
    if tpu:
        import jax
        platform = jax.devices()[0].platform
        if platform != "tpu":
            raise SystemExit(
                f"--tpu: JAX found no TPU (platform={platform!r}, "
                f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    else:
        from distlearn_tpu.utils.platform import force_cpu
        force_cpu(num_nodes)
    # last, not first: its ``process.ready`` mark stands where this process
    # holds its devices (the pinned CPU's come up at the first query, in
    # milliseconds); nothing above compiles
    from distlearn_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()


def resolve_num_nodes(requested: int, tpu: bool) -> int:
    """``--numNodes`` against the attached backend.  On CPU the requested
    count is virtualized by :func:`setup_platform`, so it always fits.
    On the TPU an SPMD mesh has exactly one program per device (the
    reference instead time-slices N processes on one GPU,
    examples/cifar10-cuda.sh), so a request for more nodes than chips is
    an error — a quietly narrower mesh is a different run.
    """
    if tpu:
        import jax
        avail = len(jax.devices())
        if requested > avail:
            raise SystemExit(f"--numNodes {requested} exceeds the {avail} "
                             "attached TPU chip(s): an SPMD mesh needs one "
                             "device per node")
    return requested


def data_sharding(tree):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(tree.mesh, P(tree.axis_name))


def device_stream(tree, ds, sampler, batch, prefetch=2):
    from distlearn_tpu.data import batch_iterator, prefetch_to_device
    sh = data_sharding(tree)
    return prefetch_to_device(batch_iterator(ds, sampler, batch),
                              size=prefetch, sharding=sh)


def device_stream_stacked(tree, ds, sampler, batch, k, prefetch=2):
    """Group ``k`` consecutive batches into one ``[k, B, ...]`` super-batch
    for the scanned trainers (``train.build_sgd_scan_step`` /
    ``train.build_ea_cycle``): the step axis is replicated, the batch axis
    sharded over the mesh.  A shorter final group is yielded as-is (the scan
    reads its length from the shape; one extra compile per distinct length).
    """
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distlearn_tpu.data import batch_iterator, prefetch_to_device
    sh = NamedSharding(tree.mesh, P(None, tree.axis_name))

    def groups():
        xs, ys = [], []
        for bx, by in batch_iterator(ds, sampler, batch):
            xs.append(bx)
            ys.append(by)
            if len(xs) == k:
                yield np.stack(xs), np.stack(ys)
                xs, ys = [], []
        if xs:
            yield np.stack(xs), np.stack(ys)
    return prefetch_to_device(groups(), size=prefetch, sharding=sh)
