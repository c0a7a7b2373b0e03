"""Topology-aware collective backends — ONE sync API over host TCP and
device SPMD.

The reference framework is an L1/L2 split: thin sync algorithms
(lua/AllReduceSGD.lua, lua/AllReduceEA.lua) over a swappable native
transport — torch-ipc's ``tree`` handle — and the algorithms never see a
socket.  This module rebuilds that split for the TPU port, where "node"
can mean an OS process on DCN (``comm.tree.Tree`` / ``comm.ring.Ring``)
*or* a device on an ICI mesh (``parallel.mesh.MeshTree``) — or BOTH at
once, a pod slice of L devices behind one host NIC.

:class:`CollectiveBackend` is the protocol (``all_reduce`` /
``all_reduce_ex`` / ``scatter`` / ``barrier`` / ``node_index`` /
``num_nodes`` / ``close``); three implementations ship:

* :class:`HostBackend` — behavior-preserving adapter over an existing
  TCP :class:`~distlearn_tpu.comm.tree.Tree` or
  :class:`~distlearn_tpu.comm.ring.Ring` handle (one logical node per
  OS process, plain per-node pytrees on the wire).
* :class:`MeshBackend` — the collective as a jitted ``shard_map``
  ``psum`` over the device mesh; values are *stacked node arrays*
  (leading ``num_nodes`` axis, one row per device), extending
  :class:`~distlearn_tpu.parallel.mesh.MeshTree` with the protocol
  extras (``all_reduce_ex`` riders, ``barrier``, ``close``).
* :class:`HybridBackend` — the hierarchical allreduce: in-mesh
  ``psum_scatter`` leaves each local device holding a distinct
  shard-sum, the shards D2H-stage into ONE
  :class:`~distlearn_tpu.comm.wire.FrameBuffer`-backed flat vector
  (``ops.staging``), ONE host TCP leg per host reduces that vector
  across hosts (``Conn.send_packed`` single-iovec frames, optional
  fused int8/fp16 codec), and an in-mesh ``all_gather`` fans the
  result back over the slice.  A host's NIC then moves ONE node's
  payload T up and one down an allreduce (2T) whatever the local device
  count L, where L per-device TCP ranks each move 2T a tree link: at
  2 hosts x 8 devices x 2 MB, 4.19 MB a host against 92.28 MB (the 22
  link ends of the busier host's 8 ranks in the base-2 tree).  Pinned
  by tests/test_backend.py::test_hybrid_host_leg_moves_one_payload_a_host
  and tests/test_ring.py::test_per_nic_bytes_tree_root_4t_ring_rank_3t.

Value conventions (``stacked_nodes`` tells callers which one a backend
speaks):

* ``stacked_nodes is None`` — plain per-node pytrees, one logical node
  per handle (HostBackend; the reference's process-per-node shape).
* ``stacked_nodes == k`` — every leaf carries a leading ``[k]`` node
  axis; the handle drives logical nodes ``node_offset ..
  node_offset+k-1``.  After ``all_reduce`` every row holds the global
  reduction (the in-place torch semantics, per row).

The shared TCP-collective plumbing (``walk`` / ``node_index`` /
``set_op_timeout`` / ``barrier`` / reduction identities) that
``comm/tree.py`` and ``comm/ring.py`` used to copy-paste lives here as
:class:`HostCollectiveBase`, so the adapter wraps a single surface.
This module imports neither jax nor the concrete transports at module
scope — host-only deployments can build a :class:`HostBackend` without
touching jax, and tree/ring import the base from here without a cycle.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Protocol, Sequence, runtime_checkable

import numpy as np

try:  # pytree walking without importing all of jax at module import
    import jax.tree_util as _jtu
except Exception:  # pragma: no cover
    _jtu = None

from distlearn_tpu import obs

PyTree = Any


def _identity(dtype: np.dtype, op: str):
    """Reduction identity for a non-contributing rank's slot."""
    if op == "sum":
        return 0
    if op == "max":
        return -np.inf if np.issubdtype(dtype, np.floating) \
            else np.iinfo(dtype).min
    if op == "min":
        return np.inf if np.issubdtype(dtype, np.floating) \
            else np.iinfo(dtype).max
    raise ValueError(f"unknown op {op!r}")


# ---------------------------------------------------------------------------
# Telemetry (docs/OBSERVABILITY.md "sync" catalog): one family each,
# labelled by backend, shared by every handle in the process.
# ---------------------------------------------------------------------------

def _sync_rounds():
    return obs.counter("sync_rounds_total",
                       "collective rounds completed, by backend",
                       labels=("backend",))


def _sync_host_bytes():
    return obs.counter("sync_host_leg_bytes_total",
                       "TCP bytes this handle moved during collective "
                       "rounds (NIC in+out), by backend",
                       labels=("backend",))


def _sync_logical_bytes():
    return obs.counter("sync_logical_bytes_total",
                       "logical payload bytes reduced per round, "
                       "by backend", labels=("backend",))


def _sync_seconds():
    return obs.histogram("sync_seconds",
                         "one collective round wall time, by backend",
                         labels=("backend",))


# ---------------------------------------------------------------------------
# Shared host-collective base (the tree/ring dedup target)
# ---------------------------------------------------------------------------

class HostCollectiveBase:
    """Everything a TCP collective handle shares regardless of topology.

    Subclasses (:class:`~distlearn_tpu.comm.tree.Tree`,
    :class:`~distlearn_tpu.comm.ring.Ring`) provide ``rank``,
    ``num_nodes``, ``_links()`` (their live data-plane conns) and
    ``all_reduce_ex``; the walkTable parity, op-timeout arming, NIC
    accounting, and the ``all_reduce``/``barrier`` derivations live
    here once.
    """

    rank: int
    num_nodes: int

    def _links(self) -> list:
        """Live data-plane conns of this handle (subclass hook)."""
        raise NotImplementedError

    # -- walkTable parity ---------------------------------------------------
    @staticmethod
    def walk(tree: PyTree, fn: Callable) -> PyTree:
        return _jtu.tree_map(fn, tree)

    @property
    def node_index(self) -> int:
        return self.rank

    def set_op_timeout(self, seconds: float | None):
        """(Re)arm failure detection on every live link: any collective
        that waits longer than this many seconds on one peer raises
        :class:`TimeoutError` instead of wedging the job (the reference
        blocks forever — SURVEY.md §5).  ``None`` restores the
        reference's block-forever semantics."""
        self.op_timeout = seconds
        for conn in self._links():
            conn.set_timeout(seconds)

    def nic_bytes(self) -> int:
        """Total TCP payload bytes this handle has moved (in + out over
        every live link) — the per-NIC traffic number the ``sync_*``
        metrics report."""
        return sum(c.bytes_sent + c.bytes_received for c in self._links())

    # -- derived collectives ------------------------------------------------
    def all_reduce(self, value: PyTree, op: str = "sum",
                   contrib: bool = True) -> tuple[PyTree, int]:
        """Allreduce; returns ``(reduced, n_contributors)``.

        ``contrib=False`` reproduces the reference's zero-contribution
        flush (lua/AllReduceSGD.lua:37): this rank's values count as the
        reduction identity and it is excluded from ``n`` — but it still
        serves the reduction for the rest of the topology, which is how
        stopped nodes keep stragglers' reductions alive.  ``None`` means
        "contributes" (the protocol-wide default, matching the mesh
        backends' all-contribute convention).
        """
        reduced, n, _ = self.all_reduce_ex(
            value, op=op, contrib=(True if contrib is None else contrib))
        return reduced, n

    def all_reduce_ex(self, value: PyTree, op: str = "sum",
                      contrib: bool = True, rider: int = 0
                      ) -> tuple[PyTree, int, int]:
        raise NotImplementedError

    def barrier(self):
        """All ranks rendezvous (reduce of a scalar)."""
        self.all_reduce(np.zeros((), np.int32))


# ---------------------------------------------------------------------------
# The protocol
# ---------------------------------------------------------------------------

@runtime_checkable
class CollectiveBackend(Protocol):
    """What a sync algorithm (:class:`~distlearn_tpu.parallel.
    allreduce_sgd.AllReduceSGD`, :class:`~distlearn_tpu.parallel.
    allreduce_ea.AllReduceEA`, the host algorithms, the AsyncEA client's
    slice reduction) may assume about its transport — the torch-ipc
    ``tree`` handle surface, topology-neutral.

    ``num_nodes`` counts LOGICAL nodes; ``stacked_nodes``/``node_offset``
    say how many of them this handle drives and which (module
    docstring).  ``rider`` in :meth:`all_reduce_ex` is an out-of-band
    integer summed **per logical node** across the whole topology — a
    handle driving k nodes contributes ``rider * k`` — carrying round
    metadata for the uneven-step flush protocol
    (distlearn_tpu.parallel.host_algorithms).
    """

    num_nodes: int
    stacked_nodes: int | None
    node_offset: int

    @property
    def node_index(self) -> int: ...

    def all_reduce(self, value: PyTree, op: str = "sum",
                   contrib=True) -> tuple[PyTree, int]: ...

    def all_reduce_ex(self, value: PyTree, op: str = "sum",
                      contrib=True, rider: int = 0
                      ) -> tuple[PyTree, int, int]: ...

    def scatter(self, value: PyTree, src: int = 0) -> PyTree: ...

    def barrier(self) -> None: ...

    def set_op_timeout(self, seconds: float | None) -> None: ...

    def close(self) -> None: ...


# ---------------------------------------------------------------------------
# HostBackend — adapter over Tree / Ring
# ---------------------------------------------------------------------------

class HostBackend:
    """Behavior-preserving adapter over a TCP :class:`Tree` or
    :class:`Ring` handle: one logical node per process, plain per-node
    pytrees, every collective delegating to the wrapped handle — the
    existing ctors and semantics (op_timeout, fault injection, dtype
    skew errors) survive unchanged, the algorithms just stop naming the
    concrete class.

    The one protocol method the raw handles lack is ``scatter(value,
    src != 0)`` (torch-ipc scatter is root-broadcast only): it is
    derived as a masked allreduce — ``src`` contributes its values,
    everyone else the additive identity — the same bitwise-exact winner
    broadcast the reference's ``synchronizeParameters`` performs
    (lua/AllReduceSGD.lua:44-50).
    """

    stacked_nodes: int | None = None

    def __init__(self, handle: HostCollectiveBase):
        self.handle = handle
        self.num_nodes = handle.num_nodes
        self.node_offset = handle.rank
        self._c_rounds = _sync_rounds()
        self._c_bytes = _sync_host_bytes()
        self._c_logical = _sync_logical_bytes()
        self._h_secs = _sync_seconds()

    @classmethod
    def create(cls, rank: int, num_nodes: int, host: str, port: int,
               transport: str = "tree", **kw) -> "HostBackend":
        """Build the underlying handle too (lazy imports keep this
        module transport-agnostic).  ``transport``: ``"tree"`` (extra
        kwarg ``base``) or ``"ring"``; remaining kwargs forward to the
        handle ctor (``timeout``, ``op_timeout``, ``listen_host``,
        ``advertise_host``, ``fault_plan`` ...)."""
        if transport == "tree":
            from distlearn_tpu.comm.tree import Tree
            return cls(Tree(rank, num_nodes, host, port, **kw))
        if transport == "ring":
            from distlearn_tpu.comm.ring import Ring
            return cls(Ring(rank, num_nodes, host, port, **kw))
        raise ValueError(f"unknown host transport {transport!r} "
                         "(supported: tree, ring)")

    # -- protocol -----------------------------------------------------------
    @property
    def node_index(self) -> int:
        return self.handle.node_index

    @staticmethod
    def walk(tree: PyTree, fn: Callable) -> PyTree:
        return _jtu.tree_map(fn, tree)

    def all_reduce(self, value: PyTree, op: str = "sum",
                   contrib: bool = True) -> tuple[PyTree, int]:
        reduced, n, _ = self.all_reduce_ex(value, op=op, contrib=contrib)
        return reduced, n

    def all_reduce_ex(self, value: PyTree, op: str = "sum",
                      contrib: bool = True, rider: int = 0
                      ) -> tuple[PyTree, int, int]:
        contrib = True if contrib is None else bool(contrib)
        t0 = time.perf_counter()
        b0 = self.handle.nic_bytes()
        out = self.handle.all_reduce_ex(value, op=op, contrib=contrib,
                                        rider=rider)
        self._c_rounds.labels(backend="host").inc()
        self._c_bytes.labels(backend="host").inc(
            self.handle.nic_bytes() - b0)
        self._c_logical.labels(backend="host").inc(
            sum(np.asarray(x).nbytes for x in _jtu.tree_leaves(value)))
        self._h_secs.labels(backend="host").observe(
            time.perf_counter() - t0)
        return out

    def scatter(self, value: PyTree, src: int = 0) -> PyTree:
        if src == 0:
            return self.handle.scatter(value)
        if not 0 <= src < self.num_nodes:
            raise ValueError(
                f"src={src} out of range for {self.num_nodes} nodes")
        mine = value if self.handle.rank == src else _jtu.tree_map(
            lambda x: np.zeros_like(np.asarray(x)), value)
        out, _ = self.handle.all_reduce(mine, contrib=(
            self.handle.rank == src))
        return out

    def barrier(self):
        self.handle.barrier()

    def set_op_timeout(self, seconds: float | None):
        self.handle.set_op_timeout(seconds)

    def close(self):
        self.handle.close()


# ---------------------------------------------------------------------------
# MeshBackend — the collective as a jitted shard_map psum
# ---------------------------------------------------------------------------

class MeshBackend:
    """Device-mesh implementation of the protocol: one process drives
    ALL ``num_nodes`` logical nodes as devices of a
    :class:`~distlearn_tpu.parallel.mesh.MeshTree`; values are stacked
    node arrays and every collective is a cached jitted ``shard_map``
    over ICI (the multi-process pjit idiom).  Only ``op="sum"`` lowers
    to a psum; max/min control-plane reductions stay on the host
    backends.

    ``barrier``/``close``/``set_op_timeout`` are no-ops: a single
    gang-scheduled XLA program has nothing to rendezvous or tear down,
    and there is no socket to time out — kept so algorithm code is
    backend-oblivious.
    """

    def __init__(self, num_nodes: int | None = None,
                 devices: Sequence | None = None,
                 axis_name: str = "data",
                 mesh_tree=None):
        from distlearn_tpu.parallel.mesh import MeshTree
        self.mesh_tree = mesh_tree if mesh_tree is not None else MeshTree(
            num_nodes=num_nodes, devices=devices, axis_name=axis_name)
        self.num_nodes = self.mesh_tree.num_nodes
        self.stacked_nodes: int | None = self.num_nodes
        self.node_offset = 0
        self.axis_name = self.mesh_tree.axis_name
        self.mesh = self.mesh_tree.mesh
        self.op_timeout: float | None = None
        self._c_rounds = _sync_rounds()
        self._c_logical = _sync_logical_bytes()
        self._h_secs = _sync_seconds()

    # -- MeshTree passthrough (so AllReduceEA's fused spmd path and the
    # trainers keep working against a MeshBackend) --------------------------
    @property
    def node_sharding(self):
        return self.mesh_tree.node_sharding

    def node_spec(self):
        return self.mesh_tree.node_spec()

    def spmd(self, fn, in_specs, out_specs, static_argnums=()):
        return self.mesh_tree.spmd(fn, in_specs, out_specs,
                                   static_argnums=static_argnums)

    def put_per_node(self, tree: PyTree) -> PyTree:
        return self.mesh_tree.put_per_node(tree)

    def replicate(self, tree: PyTree) -> PyTree:
        return self.mesh_tree.replicate(tree)

    def node_slice(self, tree: PyTree, i: int) -> PyTree:
        return self.mesh_tree.node_slice(tree, i)

    # -- protocol -----------------------------------------------------------
    @property
    def node_index(self) -> int:
        """First logical node this handle drives (it drives them all)."""
        return 0

    @staticmethod
    def walk(tree: PyTree, fn: Callable) -> PyTree:
        return _jtu.tree_map(fn, tree)

    def _contrib_vec(self, contrib):
        """Normalize the protocol's ``contrib`` (bool | per-node vector |
        None) onto MeshTree's per-node mask vector (or None = all)."""
        if contrib is None or contrib is True:
            return None
        if contrib is False:
            return np.zeros(self.num_nodes, np.int32)
        return np.asarray(contrib)

    def all_reduce(self, value: PyTree, op: str = "sum",
                   contrib=True) -> tuple[PyTree, int]:
        if op != "sum":
            raise NotImplementedError(
                f"MeshBackend lowers only op='sum' to a psum (got {op!r});"
                " use a host backend for control-plane max/min")
        t0 = time.perf_counter()
        out, n = self.mesh_tree.all_reduce(
            value, contrib=self._contrib_vec(contrib))
        self._c_rounds.labels(backend="mesh").inc()
        self._c_logical.labels(backend="mesh").inc(
            sum(int(np.prod(x.shape[1:], dtype=np.int64))
                * np.dtype(x.dtype).itemsize
                for x in _jtu.tree_leaves(value)))
        self._h_secs.labels(backend="mesh").observe(
            time.perf_counter() - t0)
        return out, int(n)

    def all_reduce_ex(self, value: PyTree, op: str = "sum",
                      contrib=True, rider: int = 0
                      ) -> tuple[PyTree, int, int]:
        """Rider is per logical node: one whole-mesh handle contributes
        ``rider`` for each of its ``num_nodes`` rows (so a draining mesh
        reports every node flushing, matching ``n_flush == num_nodes``
        checks in the host algorithms)."""
        out, n = self.all_reduce(value, op=op, contrib=contrib)
        return out, n, int(rider) * self.num_nodes

    def scatter(self, value: PyTree, src: int = 0) -> PyTree:
        return self.mesh_tree.scatter(value, src=src)

    def barrier(self):
        pass

    def set_op_timeout(self, seconds: float | None):
        self.op_timeout = seconds

    def close(self):
        pass


# ---------------------------------------------------------------------------
# HybridBackend — in-mesh reduce-scatter + one host TCP leg per host
# ---------------------------------------------------------------------------

def plan_chunks(total: int, parts: int) -> tuple[int, list[tuple[int, int]]]:
    """Even flat-element chunking for the hybrid reduce-scatter: pad
    ``total`` elements up to a multiple of ``parts`` and return
    ``(padded_total, [(lo, hi), ...])`` — ``parts`` equal half-open
    ranges.  ``psum_scatter`` requires equal shards; the pad is zeros
    and never leaves the device side."""
    if parts < 1:
        raise ValueError("parts must be >= 1")
    if total < 0:
        raise ValueError("total must be >= 0")
    pad = (-total) % parts
    padded = total + pad
    per = padded // parts
    return padded, [(i * per, (i + 1) * per) for i in range(parts)]


class HybridBackend:
    """Hierarchical allreduce: L local device-nodes behind ONE host TCP
    rank (the "client is a whole pod slice" deployment, ROADMAP item 1).

    ``all_reduce`` runs three phases:

    1. **In-mesh reduce-scatter** — one jitted ``shard_map``: each leaf's
       local rows flatten + concatenate per dtype group, and
       ``lax.psum_scatter`` leaves device ``i`` holding the local sum of
       chunk ``i`` (:func:`plan_chunks` bounds).
    2. **One host TCP leg over only its shard-sums** — the per-device
       shards D2H-stage straight into a reusable
       :class:`~distlearn_tpu.comm.wire.FrameBuffer`
       (:func:`distlearn_tpu.ops.staging.stage_into`), and the wrapped
       :class:`Tree`/:class:`Ring` reduces that ONE flat vector across
       hosts — ``Conn.send_packed`` single-iovec frames, optionally
       through the fused int8/fp16 codec kernels (``codec=``).  Per-host
       host-leg traffic is ~1 payload instead of the L payloads that L
       per-device TCP ranks would move.
    3. **In-mesh all-gather** — the reduced vector H2D-shards back one
       chunk per device and a jitted ``all_gather`` leaves every row of
       the stacked result holding the global sum.

    Values are stacked node arrays with leading axis
    ``stacked_nodes == L`` (this host's slice); ``num_nodes = H * L``.
    Lossless by default (``codec="raw"`` — the host leg moves exact
    dtypes); int8/fp16 quantize per hop with no cross-round error
    feedback, the same tradeoff as the AsyncEA wire codecs.

    ``num_hosts=1`` skips the TCP leg but keeps the reduce-scatter /
    all-gather pair (the degenerate single-host case — also what the
    ``sync`` lint family compiles and budgets).  ``op_timeout`` and
    fault injection (``fault_plan``) forward to the host leg, so a
    partition mid-collective surfaces the same typed error as the raw
    tree path (tests/test_backend.py).
    """

    def __init__(self, rank: int = 0, num_hosts: int = 1,
                 host: str | None = None, port: int | None = None, *,
                 devices: Sequence | None = None, num_devices: int | None = None,
                 axis_name: str = "data", transport: str = "tree",
                 base: int = 2, timeout: float = 60.0,
                 listen_host: str | None = None,
                 advertise_host: str | None = None,
                 op_timeout: float | None = None,
                 codec: str = "raw",
                 fault_plan=None, fault_link: str = "hybrid"):
        from distlearn_tpu.comm import wire
        from distlearn_tpu.parallel.mesh import MeshTree
        if not 0 <= rank < num_hosts:
            raise ValueError(f"rank {rank} out of range for {num_hosts} hosts")
        if codec not in wire.CODECS:
            raise ValueError(f"unknown wire codec {codec!r} "
                             f"(supported: {', '.join(wire.CODECS)})")
        self.mesh_tree = MeshTree(num_nodes=num_devices, devices=devices,
                                  axis_name=axis_name)
        self.rank = rank
        self.num_hosts = int(num_hosts)
        self.local_nodes = self.mesh_tree.num_nodes
        self.stacked_nodes: int | None = self.local_nodes
        self.num_nodes = self.num_hosts * self.local_nodes
        self.node_offset = rank * self.local_nodes
        self.axis_name = self.mesh_tree.axis_name
        self.codec = codec
        self._fb = wire.FrameBuffer()
        self._jit_cache: dict = {}
        self.host_leg = None
        if num_hosts > 1:
            if host is None or port is None:
                raise ValueError(
                    "num_hosts > 1 needs the coordinator host/port")
            if transport == "tree":
                from distlearn_tpu.comm.tree import Tree
                self.host_leg = Tree(
                    rank, num_hosts, host, port, base=base, timeout=timeout,
                    listen_host=listen_host, advertise_host=advertise_host,
                    op_timeout=op_timeout, fault_plan=fault_plan,
                    fault_link=fault_link)
            elif transport == "ring":
                from distlearn_tpu.comm.ring import Ring
                if codec != "raw":
                    raise ValueError(
                        "ring host leg is raw-only (chunked per-tensor "
                        "frames have nowhere to carry a scale)")
                self.host_leg = Ring(
                    rank, num_hosts, host, port, timeout=timeout,
                    listen_host=listen_host, advertise_host=advertise_host,
                    op_timeout=op_timeout, fault_plan=fault_plan,
                    fault_link=fault_link)
            else:
                raise ValueError(f"unknown host transport {transport!r}")
        self.op_timeout = op_timeout
        self._c_rounds = _sync_rounds()
        self._c_bytes = _sync_host_bytes()
        self._c_logical = _sync_logical_bytes()
        self._h_secs = _sync_seconds()

    # -- protocol surface ---------------------------------------------------
    @property
    def node_index(self) -> int:
        """First logical node of this host's slice."""
        return self.node_offset

    @staticmethod
    def walk(tree: PyTree, fn: Callable) -> PyTree:
        return _jtu.tree_map(fn, tree)

    def set_op_timeout(self, seconds: float | None):
        self.op_timeout = seconds
        if self.host_leg is not None:
            self.host_leg.set_op_timeout(seconds)

    def barrier(self):
        if self.host_leg is not None:
            self.host_leg.barrier()

    def close(self):
        if self.host_leg is not None:
            self.host_leg.close()

    # -- data movement parity ----------------------------------------------
    def put_per_node(self, tree: PyTree) -> PyTree:
        """Place this host's slice (leading axis == local_nodes)."""
        return self.mesh_tree.put_per_node(tree)

    def replicate(self, tree: PyTree) -> PyTree:
        return self.mesh_tree.replicate(tree)

    def node_slice(self, tree: PyTree, i: int) -> PyTree:
        """Local row ``i`` (0-based within this host's slice)."""
        return self.mesh_tree.node_slice(tree, i)

    # -- the hierarchical allreduce ----------------------------------------
    def _plan(self, value: PyTree):
        """Static layout for one stacked pytree: per-dtype leaf groups,
        flat sizes, chunk bounds — the jit cache key."""
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(value)
        shapes, dtypes, sizes = [], [], []
        for x in leaves:
            shape = tuple(x.shape)
            if len(shape) < 1 or shape[0] != self.local_nodes:
                raise ValueError(
                    f"hybrid values are stacked node arrays: leaf shape "
                    f"{shape} does not lead with local_nodes="
                    f"{self.local_nodes}")
            shapes.append(shape)
            dtypes.append(np.dtype(x.dtype))
            sizes.append(int(np.prod(shape[1:], dtype=np.int64)))
        groups: dict[np.dtype, list[int]] = {}
        for i, dt in enumerate(dtypes):
            groups.setdefault(dt, []).append(i)
        gplans = []
        for dt, idxs in sorted(groups.items(), key=lambda kv: kv[0].name):
            total = sum(sizes[i] for i in idxs)
            padded, chunks = plan_chunks(total, self.local_nodes)
            gplans.append((dt, tuple(idxs), total, padded, chunks))
        key = (treedef, tuple(shapes), tuple(dt.name for dt in dtypes))
        return key, treedef, shapes, dtypes, sizes, gplans

    def _programs(self, key, treedef, shapes, dtypes, sizes, gplans):
        """The jitted reduce-scatter and all-gather shard_maps for one
        layout (cached; steady state compiles once per pytree shape)."""
        if key in self._jit_cache:
            return self._jit_cache[key]
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P
        axis = self.axis_name
        L = self.local_nodes

        def _rs(t, c):
            # per-device view: leaves [1, *shape], contrib row [1]
            leaves = jax.tree_util.tree_leaves(t)
            cr = jnp.squeeze(c, 0)
            outs = []
            for dt, idxs, total, padded, _chunks in gplans:
                flats = [jnp.reshape(leaves[i] * cr.astype(leaves[i].dtype),
                                     (-1,)) for i in idxs]
                if padded > total:
                    flats.append(jnp.zeros((padded - total,), dt))
                flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
                # device i ends holding sum-over-local-rows of chunk i
                outs.append(lax.psum_scatter(flat, axis,
                                             scatter_dimension=0,
                                             tiled=True))
            n = lax.psum(cr.astype(jnp.int32), axis)
            return tuple(outs), n[None]

        rs = jax.jit(self.mesh_tree.spmd(
            _rs,
            in_specs=(P(axis), P(axis)),
            out_specs=(tuple(P(axis) for _ in gplans), P(axis))))

        def _ag(*gflats):
            # per-device view: one [padded // L] chunk per dtype group
            full = {}
            for (dt, idxs, total, padded, _chunks), chunk in zip(gplans,
                                                                 gflats):
                full[dt.name] = lax.all_gather(chunk, axis, tiled=True)
            out, off = [None] * len(shapes), {}
            for dt, idxs, total, padded, _chunks in gplans:
                o = 0
                for i in idxs:
                    piece = lax.dynamic_slice_in_dim(full[dt.name], o,
                                                     sizes[i], 0)
                    out[i] = jnp.reshape(piece, (1,) + shapes[i][1:])
                    o += sizes[i]
            return jax.tree_util.tree_unflatten(treedef, out)

        ag = jax.jit(self.mesh_tree.spmd(
            _ag,
            in_specs=tuple(P(axis) for _ in gplans),
            out_specs=P(axis)))
        self._jit_cache[key] = (rs, ag)
        return rs, ag

    def all_reduce(self, value: PyTree, op: str = "sum",
                   contrib=True) -> tuple[PyTree, int]:
        reduced, n, _ = self.all_reduce_ex(value, op=op, contrib=contrib)
        return reduced, n

    def all_reduce_ex(self, value: PyTree, op: str = "sum",
                      contrib=True, rider: int = 0
                      ) -> tuple[PyTree, int, int]:
        """Hierarchical allreduce of a stacked slice; ``contrib`` is a
        bool for the whole slice or a per-local-row mask ``[L]``; the
        contributor count and rider cross the host leg as extra int64
        leaves of the SAME reduction, so the count stays exact without a
        second round trip."""
        import jax
        from distlearn_tpu.ops import staging
        if op != "sum":
            raise NotImplementedError(
                f"HybridBackend reduces op='sum' only (got {op!r}); use a "
                "host backend for control-plane max/min")
        t0 = time.perf_counter()
        key, treedef, shapes, dtypes, sizes, gplans = self._plan(value)
        rs, ag = self._programs(key, treedef, shapes, dtypes, sizes, gplans)
        if contrib is True or contrib is None:
            cvec = np.ones(self.local_nodes, np.int32)
        elif contrib is False:
            cvec = np.zeros(self.local_nodes, np.int32)
        else:
            cvec = np.asarray(contrib, np.int32)
            if cvec.shape != (self.local_nodes,):
                raise ValueError(
                    f"contrib mask shape {cvec.shape} != "
                    f"({self.local_nodes},)")
        shard_sums, n_local = rs(value, cvec)
        n_local = int(np.asarray(jax.device_get(n_local))[0])
        r_local = int(rider) * self.local_nodes

        # D2H: every device's shard-sum lands in ONE contiguous
        # FrameBuffer-backed flat vector per dtype group (ops.staging).
        host_flats = staging.stage_into(self._fb, shard_sums,
                                        [dt for dt, *_ in gplans])
        logical = sum(v.nbytes for v in host_flats)
        if self.host_leg is not None:
            b0 = self.host_leg.nic_bytes()
            hv = {"g": host_flats,
                  "n": np.asarray(n_local, np.int64),
                  "r": np.asarray(r_local, np.int64)}
            red, _, _ = self.host_leg.all_reduce_ex(
                hv, op="sum", contrib=True, rider=0, codec=self.codec)
            host_flats = red["g"]
            # the tree folds into 0-d buffers but may hand back [1] views
            n_total = int(np.asarray(red["n"]).reshape(()))
            r_total = int(np.asarray(red["r"]).reshape(()))
            self._c_bytes.labels(backend="hybrid").inc(
                self.host_leg.nic_bytes() - b0)
        else:
            n_total, r_total = n_local, r_local

        # H2D one chunk per device + in-mesh all-gather back to rows.
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(self.mesh_tree.mesh, P(self.axis_name))
        dev_flats = []
        for flat in host_flats:
            arr = np.ascontiguousarray(flat)
            dev_flats.append(jax.make_array_from_callback(
                arr.shape, sh, lambda idx, a=arr: a[idx]))
        out = ag(*dev_flats)
        self._c_rounds.labels(backend="hybrid").inc()
        self._c_logical.labels(backend="hybrid").inc(logical)
        self._h_secs.labels(backend="hybrid").observe(
            time.perf_counter() - t0)
        return out, n_total, r_total

    def scatter(self, value: PyTree, src: int = 0) -> PyTree:
        """Logical node ``src``'s row broadcast to every row of every
        host: the owning host extracts the row, a masked host-leg
        allreduce moves it across hosts (additive identity elsewhere —
        bitwise the owner's values), and every host replicates it over
        its slice."""
        if not 0 <= src < self.num_nodes:
            raise ValueError(
                f"src={src} out of range for {self.num_nodes} nodes")
        h, row = divmod(src, self.local_nodes)
        if self.rank == h:
            mine = self.node_slice(value, row)
        else:
            mine = _jtu.tree_map(
                lambda x: np.zeros(tuple(x.shape[1:]), np.dtype(x.dtype)),
                value)
        if self.host_leg is not None:
            mine, _ = self.host_leg.all_reduce(mine,
                                               contrib=(self.rank == h))
        return self.replicate(mine)
