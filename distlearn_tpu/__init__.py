"""distlearn_tpu — a TPU-native distributed learning framework.

A ground-up JAX/XLA rebuild of the capabilities of ``shanlior/torch-distlearn``
(Torch7/Lua): synchronous data-parallel **AllReduceSGD**, synchronous elastic
averaging **AllReduceEA** expressed as a single fused collective, and
asynchronous client/server **AsyncEA** (EASGD parameter server).

Where the reference delegates communication to torch-ipc's C++ TCP tree
(reference: lua/AllReduceSGD.lua, lua/AllReduceEA.lua, lua/AsyncEA.lua), this
framework uses an ICI device mesh: parameters and gradients are XLA device
buffers, ``all_reduce``/``scatter`` lower to ``lax.psum``/broadcast inside
jitted step functions, and the AsyncEA push-pull runs over a host-side TCP
control plane (native C++ transport with a pure-Python fallback) against a
pinned center variable.

Layout (mirrors SURVEY.md §7's proposed layout):
  parallel/  — MeshTree (the ``tree`` replacement), AllReduceSGD, AllReduceEA,
               AsyncEA, tensor/sequence-parallel extensions
  comm/      — host-side transport: native C++ TCP sockets + tree allreduce
  models/    — functional model zoo (MNIST CNN, CIFAR convnet, ResNet-50)
  ops/       — Pallas TPU kernels for the hot fused updates
  data/      — partitioned datasets, samplers, device prefetch
  train/     — fused train-step builders (the TPU hot path)
  utils/     — flags, metrics, logging, checkpointing, profiling
"""

__version__ = "0.1.0"

from distlearn_tpu.parallel.mesh import MeshTree, all_reduce, broadcast_from, node_index
from distlearn_tpu.parallel.allreduce_sgd import AllReduceSGD
from distlearn_tpu.parallel.allreduce_ea import AllReduceEA
from distlearn_tpu.parallel.async_ea import (AsyncEAClient, AsyncEAServer,
                                             AsyncEATester)

__all__ = [
    "MeshTree",
    "AllReduceSGD",
    "AllReduceEA",
    "AsyncEAServer",
    "AsyncEAClient",
    "AsyncEATester",
    "all_reduce",
    "broadcast_from",
    "node_index",
    "__version__",
]
