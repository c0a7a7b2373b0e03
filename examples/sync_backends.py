#!/usr/bin/env python
"""One EASGD loop, three collective backends — the ISSUE 20 demo.

The same :class:`~distlearn_tpu.parallel.allreduce_ea.AllReduceEA`
driver runs over every :class:`~distlearn_tpu.comm.backend` topology:

* ``--backend mesh``   — all N nodes are devices in one SPMD mesh
  (the fused in-process fast path).
* ``--backend host``   — every node its own TCP tree rank (the
  reference torch-ipc topology; here localhost threads).
* ``--backend hybrid`` — N nodes split over ``--numHosts`` host ranks,
  each fronting N/numHosts device-nodes: in-mesh reduce-scatter, ONE
  TCP leg per host, in-mesh all-gather.

With dyadic-exact arithmetic (dyadic f64 params, dyadic alpha whose
center recursion ``|1 - N*alpha|`` stays contractive, so magnitudes
never outgrow the 53-bit mantissa)
the three trajectories are BITWISE identical — the printed digest is
the same line for every ``--backend`` — while the hybrid host leg
moves ~numNodes/numHosts-fold fewer TCP bytes than the flat host tree
(tests/test_backend.py asserts both properties).

Run:  python examples/sync_backends.py --backend mesh --numNodes 8
      python examples/sync_backends.py --backend host --numNodes 8
      python examples/sync_backends.py --backend hybrid --numNodes 8 \
          --numHosts 2
"""

from __future__ import annotations

import hashlib

from common import setup_platform
from distlearn_tpu.utils.flags import parse_flags


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _node_step(params, rank, r):
    """One deterministic dyadic 'gradient' step — stands in for a real
    per-node training step, exact in f64 so reduction order can't show."""
    import numpy as np
    g = (np.arange(params.size, dtype=np.float64).reshape(params.shape)
         % 7 + rank + r) * 0.25
    return params - 0.5 * g


def _run_rank(backend, rank, local, rounds, tau, alpha, dim):
    """Drive ``local`` logical nodes' EASGD over one backend handle.

    Plain HostBackend handles see one node (``local == 1``, plain
    arrays); mesh/hybrid handles see a stacked ``[local, dim]`` slice."""
    import numpy as np

    from distlearn_tpu.parallel.allreduce_ea import AllReduceEA

    ea = AllReduceEA(backend, tau, alpha)
    lo = backend.node_offset
    if getattr(backend, "stacked_nodes", None) is None:
        params = np.zeros(dim, np.float64)
        for r in range(rounds):
            params = _node_step(params, lo, r)
            params = ea.average_parameters(params)
    else:
        params = np.zeros((local, dim), np.float64)
        for r in range(rounds):
            params = np.stack([_node_step(params[i], lo + i, r)
                               for i in range(local)])
            params = ea.average_parameters(params)
    return np.asarray(ea._center), np.asarray(params)


def main():
    opt = parse_flags(
        "EASGD over the topology-aware collective backends.", {
            "backend": ("mesh", "mesh | host | hybrid"),
            "numNodes": (8, "logical nodes"),
            "numHosts": (2, "host ranks (hybrid only)"),
            "rounds": (20, "elastic rounds"),
            "tau": (1, "steps between averaging rounds"),
            "alpha": (0.0625, "elastic moving rate (dyadic AND "
                              "contractive at N nodes => bitwise "
                              "across backends)"),
            "dim": (64, "parameter vector length"),
            "tpu": (False, "run on the TPU backend"),
        })
    setup_platform(opt.numNodes, opt.tpu)

    import jax
    import numpy as np

    # integer-valued f64 + dyadic alpha is the bitwise-parity contract;
    # without x64 the mesh/hybrid paths would silently round in f32
    jax.config.update("jax_enable_x64", True)

    from distlearn_tpu.comm.backend import (HostBackend, HybridBackend,
                                            MeshBackend)

    n, rounds = opt.numNodes, opt.rounds
    if opt.backend == "mesh":
        b = MeshBackend(num_nodes=n)
        center, _ = _run_rank(b, 0, n, rounds, opt.tau, opt.alpha, opt.dim)
        center = b.node_slice(center, 0) if center.ndim > 1 else center

    elif opt.backend == "host":
        from distlearn_tpu.comm.tree import tree_map_spawn
        port = _free_port()

        def node(rank):
            b = HostBackend.create(rank, n, "127.0.0.1", port, base=2)
            out = _run_rank(b, rank, 1, rounds, opt.tau, opt.alpha,
                            opt.dim)
            b.close()
            return out
        center = tree_map_spawn(node, n, timeout=300)[0][0]

    elif opt.backend == "hybrid":
        from distlearn_tpu.comm.tree import tree_map_spawn
        hosts = opt.numHosts
        if n % hosts:
            raise SystemExit(f"--numNodes {n} not divisible by "
                             f"--numHosts {hosts}")
        local = n // hosts
        devs = jax.devices()
        port = _free_port()

        def node(rank):
            # disjoint device slices: each host rank's in-mesh
            # collectives rendezvous only within its own slice
            b = HybridBackend(rank, hosts, "127.0.0.1", port,
                              devices=devs[rank * local:(rank + 1) * local])
            out = _run_rank(b, rank, local, rounds, opt.tau, opt.alpha,
                            opt.dim)
            b.close()
            return out
        res = tree_map_spawn(node, hosts, timeout=300)
        center = res[0][0]
        center = center[0] if center.ndim > 1 else center

    else:
        raise SystemExit(f"unknown --backend {opt.backend!r}")

    center = np.asarray(center, np.float64).reshape(-1)
    digest = hashlib.sha256(center.tobytes()).hexdigest()[:16]
    print(f"backend={opt.backend} nodes={n} rounds={rounds} "
          f"center[0:4]={center[:4].tolist()} digest={digest}")


if __name__ == "__main__":
    main()
