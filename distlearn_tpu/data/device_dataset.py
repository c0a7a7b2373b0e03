"""Device-resident datasets — batches gathered ON the accelerator.

The reference's torch-dataset has a ``cuda`` batcher flag that lands each
batch directly in GPU memory (examples/Data.lua:27, consumed by the EASGD
trio).  The TPU-native upgrade goes further: upload the WHOLE dataset to
device memory once, then each step transfers only the batch's int32 index
vector (a few hundred bytes) and gathers the batch with an on-device
``jnp.take``.  This removes the per-step host-to-device batch upload,
which for a small model can cost more than the step itself.

Fits-in-HBM datasets only (MNIST/CIFAR-scale: tens to hundreds of MB);
streaming sets keep using the host prefetch pipeline (data/prefetch.py).
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

import jax
import jax.numpy as jnp


class DeviceDataset:
    """(x, y) resident in device memory; ``gather`` batches by index.

    ``sharding``: optional ``jax.sharding.Sharding`` for the RESIDENT
    copies (default: single-device / replicated placement as jax chooses).
    ``out_sharding``: sharding for gathered BATCHES — pass the data-axis
    sharding of the train step so the gathered batch lands pre-sharded.
    """

    def __init__(self, x: np.ndarray, y: np.ndarray, num_classes: int,
                 sharding=None, out_sharding=None):
        # device_put straight from host numpy: one transfer, already in the
        # resident sharding (no intermediate default-device copy)
        put = (lambda a: jax.device_put(a, sharding)) if sharding is not None \
            else jax.device_put
        self.x = put(np.ascontiguousarray(x))
        self.y = put(np.ascontiguousarray(y))
        self.num_classes = num_classes
        out = (out_sharding, out_sharding) if out_sharding is not None \
            else None
        self._gather = jax.jit(
            lambda xs, ys, idx: (jnp.take(xs, idx, axis=0),
                                 jnp.take(ys, idx, axis=0)),
            out_shardings=out)

    @property
    def size(self) -> int:
        return int(self.y.shape[0])

    def batches_per_epoch(self, batch_size: int) -> int:
        return self.size // batch_size

    def gather(self, idx: np.ndarray):
        """One batch in ONE dispatch: host→device transfer is just the
        index vector."""
        idx_dev = jax.device_put(np.ascontiguousarray(idx, np.int32))
        return self._gather(self.x, self.y, idx_dev)

    def batches(self, sampler, batch_size: int) -> Iterator[tuple]:
        """One epoch of device-resident batches via a data/samplers.py
        sampler (permutation, label-uniform, ...)."""
        for idx in sampler.epoch(batch_size):
            yield self.gather(idx)
