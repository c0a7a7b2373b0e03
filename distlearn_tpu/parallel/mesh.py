"""The device-mesh communication layer: TPU-native replacement for torch-ipc's ``tree``.

The reference framework's entire communication backend is the external torch-ipc
C++ library: a base-b tree of TCP sockets with ``tree.allReduce`` /
``tree.scatter`` / ``tree.walkTable`` / ``tree.nodeIndex`` / ``tree.numNodes``
(reference call sites: lua/AllReduceSGD.lua:12-52, lua/AllReduceEA.lua:41-96,
examples/mnist.lua:16).  On TPU the idiomatic equivalent is *not* a socket tree:
"nodes" are devices in a :class:`jax.sharding.Mesh`, per-node values are arrays
with a leading node axis sharded over that mesh, and every collective lowers to
an XLA ICI collective (``lax.psum``) inside a jitted function.

Two API levels:

* **In-step collectives** (:func:`all_reduce`, :func:`broadcast_from`,
  :func:`node_index`): pure functions referencing a mesh axis name, for
  composing *inside* ``shard_map``-ped train steps — the hot path, where the
  collective fuses with the surrounding compute in one XLA program.

* **Host-level ops** (:class:`MeshTree`): mirrors the reference ``tree``
  surface (``all_reduce``, ``scatter``, ``walk``, ``node_index``,
  ``num_nodes``) operating on *stacked node arrays* — pytrees whose leaves have
  a leading ``num_nodes`` axis, sharded one-slice-per-device.  Each call is a
  jitted ``shard_map``.  This is the 1:1 translation surface for porting
  reference-style scripts; real training loops should prefer the fused
  builders in :mod:`distlearn_tpu.train`.

``walkTable`` needs no replacement: JAX pytrees + ``jax.tree_util.tree_map``
are the first-class equivalent; :meth:`MeshTree.walk` is provided for parity.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

DEFAULT_AXIS = "data"


# ---------------------------------------------------------------------------
# In-step collectives (use inside shard_map / pjit-ed step functions)
# ---------------------------------------------------------------------------

def node_index(axis_name: str = DEFAULT_AXIS) -> jax.Array:
    """This node's 0-based index along the mesh axis (ref: ``tree.nodeIndex``,
    which is 1-based; here 0-based, matching JAX convention)."""
    return lax.axis_index(axis_name)


def all_reduce(tree: PyTree, axis_name: str = DEFAULT_AXIS,
               contrib: jax.Array | None = None) -> tuple[PyTree, jax.Array]:
    """Sum a pytree across the mesh axis; returns ``(reduced_tree, n)``.

    Mirrors ``tree.allReduce(value, add) -> _, n`` (lua/AllReduceSGD.lua:12):
    ``n`` is the number of *contributing* nodes.  The reference's tree lets
    non-stepping nodes keep the reduction alive by contributing zeros via a
    ``zeroFn``; on a gang-scheduled mesh every device always participates, so
    the same observable semantics are expressed with a participation mask:
    non-contributors' values are zeroed before the psum and ``n`` counts the
    mask (SURVEY.md §7 "hard parts").

    Args:
      tree: pytree of per-node arrays (local shard view, no node axis).
      axis_name: mesh axis to reduce over.
      contrib: optional boolean/0-1 scalar — whether *this* node contributes.
        ``None`` means all nodes contribute.
    """
    if contrib is None:
        n = jnp.asarray(lax.psum(1, axis_name))
        return jax.tree_util.tree_map(lambda x: lax.psum(x, axis_name), tree), n
    c = jnp.asarray(contrib)
    n = lax.psum(c.astype(jnp.int32), axis_name)
    masked = jax.tree_util.tree_map(lambda x: x * c.astype(x.dtype), tree)
    return jax.tree_util.tree_map(lambda x: lax.psum(x, axis_name), masked), n


def broadcast_from(tree: PyTree, src, axis_name: str = DEFAULT_AXIS) -> PyTree:
    """Broadcast ``src``'s values to every node along the axis.

    Replaces ``tree.scatter`` (root broadcast — lua/AllReduceSGD.lua:52,
    lua/AllReduceEA.lua:83,93): implemented as a psum of masked values, which
    XLA lowers to an ICI all-reduce (or all-gather+select) — deterministic and
    bitwise identical on every replica.
    """
    idx = lax.axis_index(axis_name)
    mask = (idx == src)

    def _sel(x):
        return lax.psum(jnp.where(mask, x, jnp.zeros_like(x)), axis_name)

    return jax.tree_util.tree_map(_sel, tree)


def all_gather_scalar(x: jax.Array, axis_name: str = DEFAULT_AXIS) -> jax.Array:
    """Gather a per-node scalar into a ``[num_nodes]`` vector on every node."""
    return lax.all_gather(x, axis_name)


def bit_reversed(index, bits: int):
    """``index`` (a Python int or a traced one) with its low ``bits`` bits
    in reverse order."""
    out = 0
    for b in range(bits):
        out = out | (((index >> b) & 1) << (bits - 1 - b))
    return out


def exchange_pairs(n: int, distance: int) -> list[tuple[int, int]]:
    """The ``ppermute`` pairs in which every device of an axis of ``n``
    sends to the one whose index differs by ``distance`` under XOR — an
    exchange: the two of a pair send to each other."""
    return [(j, j ^ distance) for j in range(n)]


def to_tiles(x: jax.Array) -> jax.Array:
    """``x``'s values as ``[tiles, 8, 128]``, in an order that costs the
    TPU no pass over memory of its own.  The axis that goes last is the
    last one if it is a multiple of 128, else the first that is (a
    ``[dim, heads, 64]`` projection is laid out ``dim``-minor by the
    compiler anyway); with a multiple of 8 rows before it the array is
    then read tile by tile as it lies in memory — the reshapes and
    transposes below are bitcasts to the compiler.  Anything else is
    flattened and padded with zeros to whole tiles.  :func:`from_tiles` is
    the inverse."""
    last, rows, cols = _tile_view(x.shape)
    if last is None:
        flat = x.reshape(-1)
        return jnp.pad(flat, (0, -flat.size % 1024)).reshape(-1, 8, 128)
    return jnp.moveaxis(x, last, -1).reshape(
        rows // 8, 8, cols // 128, 128).transpose(0, 2, 1, 3).reshape(
            -1, 8, 128)


def from_tiles(tiles: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """The array of ``shape`` that :func:`to_tiles` made ``tiles`` from."""
    last, rows, cols = _tile_view(shape)
    if last is None:
        return tiles.reshape(-1)[:math.prod(shape)].reshape(shape)
    moved = shape[:last] + shape[last + 1:] + (cols,)
    return jnp.moveaxis(tiles.reshape(rows // 8, cols // 128, 8, 128)
                        .transpose(0, 2, 1, 3).reshape(moved), -1, last)


def _minor_axis(shape) -> int | None:
    """The axis :func:`to_tiles` reads as columns; None if no axis is a
    multiple of 128."""
    if shape and shape[-1] % 128 == 0:
        return len(shape) - 1
    return next((a for a, d in enumerate(shape) if d % 128 == 0), None)


def cut_axis(shape, n: int) -> int | None:
    """The axis along which :class:`StagedSum` cuts a leaf of ``shape`` in
    ``n`` chunks: the first that ``n`` divides, but the one its tiles read
    as columns last of all (a chunk then is whole rows of tiles); None if
    ``n`` divides no axis."""
    minor = _minor_axis(shape)
    axes = [a for a, d in enumerate(shape) if d % n == 0]
    return next((a for a in axes if a != minor), axes[0] if axes else None)


def _tile_view(shape):
    """``(minor axis, rows, cols)`` of ``shape`` seen as rows of its minor
    axis; ``(None, 0, 0)`` where there is no such view in whole tiles."""
    last = _minor_axis(shape)
    if last is None or (math.prod(shape) // shape[last]) % 8:
        return None, 0, 0
    return last, math.prod(shape) // shape[last], shape[last]


class StagedSum:
    """The sum of a pytree over a mesh axis (a power of two, ``n = 2**m``),
    cut into ``m + 1`` STAGES that a caller runs one at a time with other
    work between them — each stage's transfers are ``ppermute`` calls, which
    the TPU compiler starts and awaits apart (``collective-permute-start``
    / ``-done``), where its own all-reduce is one synchronous instruction.
    Built inside ``shard_map`` (it asks the axis its size).

    Every leaf is cut in ``n`` CHUNKS (:func:`cut_axis`); chunk ``c`` of
    the tree is chunk ``c`` of every leaf, as tiles (:func:`to_tiles`), one
    leaf after the other.  :meth:`enter` packs a tree into ``m + 1`` PARTS,
    ordered by when they leave: part ``s < m`` is the half of what is still
    held that stage ``s`` sends, part ``m`` the one chunk this device ends
    up owning.  Stage ``s < m`` (recursive halving) exchanges part ``s``
    with the device whose index differs in bit ``s`` and adds what arrives
    onto the parts after it, so after ``m`` stages the owned chunk is the
    sum over the axis, made on this device alone — which is why every
    replica ends with the same bits.  Stage ``m`` sends the owned chunk to
    each of the other ``n - 1`` devices.  No part is sliced, moved or
    reordered between its packing and its landing: a stage's operand is a
    whole buffer, and each arriving chunk is written straight into its
    place in a stack of such trees.  A device sends ``2 (n - 1) / n`` of the
    tree in all, what a ring sends, in ``log2(n) + 1`` dependent steps and
    ``log2(n) + n - 1`` transfers (five at ``n = 4``, the number the TPU
    compiler keeps in flight at once).

    A pipeline of these sums is a list ``slots`` (:meth:`empty`):
    ``slots[s]`` holds the parts of the tree that waits for stage ``s``;
    :meth:`advance` runs every stage once, on ``m + 1`` different trees."""

    def __init__(self, like: PyTree, axis_name: str):
        self.axis_name = axis_name
        self.n = n = lax.axis_size(axis_name)
        self.m = n.bit_length() - 1
        if n < 2 or n != 1 << self.m:
            raise ValueError(f"axis {axis_name!r} has {n} devices: the "
                             "exchange pairs devices by one bit of their index")
        self.stages = self.m + 1
        leaves, self.tree = jax.tree_util.tree_flatten(like)
        self.dtype = leaves[0].dtype
        self.shapes, self.cuts = [], []
        for leaf in leaves:
            cut = cut_axis(leaf.shape, n)
            if cut is None:
                raise ValueError(f"no axis of a leaf of shape {leaf.shape} "
                                 f"divides by {n}")
            self.cuts.append(cut)
            self.shapes.append(leaf.shape[:cut] + (leaf.shape[cut] // n,)
                               + leaf.shape[cut + 1:])
        #: tiles a chunk of each leaf takes, and a chunk of the tree
        self.tiles = [-(-math.prod(shape) // 1024) for shape in self.shapes]
        self.chunk = sum(self.tiles)

    def _owned(self):
        """The chunk this device ends up owning: stage ``s`` keeps the half
        that bit ``s`` of its index names."""
        return bit_reversed(lax.axis_index(self.axis_name), self.m)

    def enter(self, tree: PyTree) -> list[jax.Array]:
        """``tree`` packed into its ``m + 1`` parts."""
        leaves = jax.tree_util.tree_leaves(tree)
        n, own = self.n, self._owned()

        def chunk(c):
            return [to_tiles(lax.dynamic_slice_in_dim(
                        leaf, c * shape[cut], shape[cut], cut))
                    for leaf, shape, cut in zip(leaves, self.shapes,
                                                self.cuts)]

        # position p of the packed order holds chunk own ^ (n - 1 - p): the
        # first half goes at stage 0, the next quarter at stage 1, ...
        edges = [n - (n >> s) for s in range(self.stages)] + [n]
        return [jnp.concatenate([x for p in range(lo, hi)
                                 for x in chunk(own ^ (n - 1 - p))])
                for lo, hi in zip(edges, edges[1:])]

    def empty(self) -> list:
        """A pipeline with nothing in it: zeros, which :meth:`advance` moves
        like anything else, and None where the next tree's parts go."""
        sizes = [self.chunk * max(self.n >> (s + 1), 1)
                 for s in range(self.stages)]
        return [None] + lax.optimization_barrier(
            [[jnp.zeros((size, 8, 128), self.dtype) for size in sizes[s:]]
             for s in range(1, self.stages)])

    def advance(self, slots, stack: PyTree, row, first: int = 0):
        """Every stage from ``first`` on, once: ``(slots, stack)`` with
        ``slots[0]`` left None for the next tree's parts and the tree that
        left the last stage, summed, in row ``row`` of ``stack`` (the tree
        with one more leading axis; a row past the end is clamped onto the
        last, None lands nothing).  Every transfer reads ``slots`` alone,
        and nothing is written where a transfer or the landing still reads:
        a loop that carries ``slots`` needs no second copy of them."""
        n, m = self.n, self.m
        got = {s: lax.ppermute(slots[s][0], self.axis_name,
                               exchange_pairs(n, 1 << s))
               for s in range(first, m)}
        own, = slots[m]
        mine = self._owned()
        arrived = [(own, mine)] + [
            (lax.ppermute(own, self.axis_name, exchange_pairs(n, d)),
             mine ^ bit_reversed(d, m)) for d in range(1, n)]
        if row is not None:
            stack = self._land(stack, arrived, row)
        got, stack = lax.optimization_barrier((got, stack))
        out = [None] * (m + 1)
        for s in range(first, m):
            at, out[s + 1] = 0, []
            for part in slots[s][1:]:
                out[s + 1].append(part + got[s][at:at + len(part)])
                at += len(part)
        return out, stack

    def _land(self, stack: PyTree, arrived, row) -> PyTree:
        """The ``(chunk, index)`` pairs ``arrived`` written into row ``row``
        of ``stack``, each leaf's share straight into its place."""
        leaves = jax.tree_util.tree_leaves(stack)
        for tiles, c in arrived:
            at = 0
            for k, (shape, cut, size) in enumerate(
                    zip(self.shapes, self.cuts, self.tiles)):
                start = [row] + [0] * len(shape)
                start[cut + 1] = c * shape[cut]
                leaves[k] = lax.dynamic_update_slice(
                    leaves[k], from_tiles(tiles[at:at + size], shape)[None],
                    [jnp.asarray(j, jnp.int32) for j in start])
                at += size
        return jax.tree_util.tree_unflatten(self.tree, leaves)


def squeeze_node(tree: PyTree) -> PyTree:
    """Drop the local size-1 node axis inside a shard_map over stacked node
    arrays (each device sees its [1, ...] slice of the stack)."""
    return jax.tree_util.tree_map(lambda x: jnp.squeeze(x, 0), tree)


def expand_node(tree: PyTree) -> PyTree:
    """Re-add the local node axis before returning from a shard_map."""
    return jax.tree_util.tree_map(lambda x: x[None], tree)


# ---------------------------------------------------------------------------
# Host-level MeshTree
# ---------------------------------------------------------------------------

class MeshTree:
    """Host-side handle over a device mesh, mirroring the reference ``tree``.

    Per-node values are **stacked node arrays**: every leaf has a leading
    ``num_nodes`` axis, sharded one-row-per-device along ``axis_name``.  This
    is the TPU analogue of "each process holds its own tensor": one global
    jax.Array whose shards live device-side, collectives run over ICI.

    Construction mirrors ``ipc.LocalhostTree(nodeIndex, numNodes)``
    (examples/mnist.lua:16) — except a single SPMD program drives all nodes,
    so there is no per-process handshake; multi-host pods join via
    ``jax.distributed.initialize`` before constructing the mesh.
    """

    def __init__(self, num_nodes: int | None = None,
                 devices: Sequence[jax.Device] | None = None,
                 axis_name: str = DEFAULT_AXIS):
        if devices is None:
            devices = jax.devices()
        if num_nodes is not None:
            if num_nodes > len(devices):
                raise ValueError(
                    f"num_nodes={num_nodes} exceeds available devices ({len(devices)})")
            devices = devices[:num_nodes]
        self.axis_name = axis_name
        self.mesh = Mesh(np.asarray(devices), (axis_name,))
        self.num_nodes = len(devices)
        self._jit_cache: dict = {}

    # -- shardings ---------------------------------------------------------
    @property
    def node_sharding(self) -> NamedSharding:
        """Sharding for stacked node arrays: leading axis split over nodes."""
        return NamedSharding(self.mesh, P(self.axis_name))

    @property
    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def node_spec(self) -> P:
        return P(self.axis_name)

    # -- data movement -----------------------------------------------------
    def _put_global(self, x, sharding: NamedSharding):
        """Host value -> global jax.Array under ``sharding``.  Built with
        ``make_array_from_callback`` so it also works when the mesh spans
        multiple processes (jax.distributed) and this process addresses only
        some devices — ``device_put`` would reject that."""
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, sharding,
                                            lambda idx: x[idx])

    def put_per_node(self, tree: PyTree) -> PyTree:
        """Place a stacked pytree (leading axis == num_nodes) onto the mesh."""
        def _put(x):
            x = np.asarray(x)
            if x.shape[0] != self.num_nodes:
                raise ValueError(
                    f"leading axis {x.shape[0]} != num_nodes {self.num_nodes}")
            return self._put_global(x, self.node_sharding)
        return jax.tree_util.tree_map(_put, tree)

    def replicate(self, tree: PyTree) -> PyTree:
        """Stack one value to all nodes: v -> [num_nodes, *v.shape], sharded."""
        def _rep(x):
            x = np.asarray(x)
            stacked = np.broadcast_to(x[None], (self.num_nodes,) + x.shape)
            return self._put_global(stacked, self.node_sharding)
        return jax.tree_util.tree_map(_rep, tree)

    # -- collectives on stacked node arrays --------------------------------
    def _shard_fn(self, key: str, fn: Callable, n_node_args: int,
                  out_replicated: bool = False):
        """jit(shard_map(fn)) with per-node in-specs; cached by key."""
        cache_key = (key, n_node_args, out_replicated)
        if cache_key not in self._jit_cache:
            in_specs = tuple(P(self.axis_name) for _ in range(n_node_args))
            out_specs = P() if out_replicated else P(self.axis_name)
            mapped = shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False)
            self._jit_cache[cache_key] = jax.jit(mapped)
        return self._jit_cache[cache_key]

    def all_reduce_program(self, masked: bool = False):
        """The cached jitted shard_map behind :meth:`all_reduce` — exposed
        so distlint's ``sync`` family can lower and budget the collective
        program itself without executing it.  ``masked=True`` returns the
        contrib-vector variant (``(tree, contrib[num_nodes]) -> (tree,
        n[num_nodes])``)."""
        axis = self.axis_name
        if not masked:
            def _ar(t):
                red, _ = all_reduce(squeeze_node(t), axis)
                return expand_node(red)
            return self._shard_fn("all_reduce", _ar, 1)

        def _arm(t, c):
            c = jnp.squeeze(c, 0)
            red, n = all_reduce(squeeze_node(t), axis, contrib=c)
            return expand_node(red), n[None]
        return self._shard_fn("all_reduce_masked", _arm, 2)

    def all_reduce(self, tree: PyTree, contrib: jax.Array | None = None
                   ) -> tuple[PyTree, int]:
        """Sum per-node values; every node's row ends up holding the sum.

        Mirrors ``tree.allReduce(value, function(a,b) return a:add(b) end)``
        (lua/AllReduceSGD.lua:12,20): returns ``(reduced, n_contributors)``;
        the reduced stacked array has identical rows (each node's buffer now
        holds the reduction, like the in-place torch semantics).
        """
        if contrib is None:
            out = self.all_reduce_program(False)(tree)
            return out, self.num_nodes
        contrib = jnp.asarray(contrib)
        out, n = self.all_reduce_program(True)(tree, contrib)
        return out, int(n[0])

    def scatter(self, tree: PyTree, src: int = 0) -> PyTree:
        """Broadcast node ``src``'s row to every node (ref: ``tree.scatter``)."""
        if not 0 <= src < self.num_nodes:
            raise ValueError(f"src={src} out of range for {self.num_nodes} nodes")
        axis = self.axis_name

        def _sc(t):
            out = broadcast_from(squeeze_node(t), src, axis)
            return expand_node(out)
        return self._shard_fn(f"scatter_{src}", _sc, 1)(tree)

    def spmd(self, fn: Callable, in_specs, out_specs, static_argnums=()):
        """shard_map + jit a step function over this mesh (the hot path)."""
        mapped = shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
        return jax.jit(mapped, static_argnums=static_argnums)

    # -- parity helpers ----------------------------------------------------
    @staticmethod
    def walk(tree: PyTree, fn: Callable) -> PyTree:
        """``tree.walkTable`` parity: map ``fn`` over every leaf."""
        return jax.tree_util.tree_map(fn, tree)

    def node_slice(self, tree: PyTree, i: int) -> PyTree:
        """Pull node ``i``'s row back to host (for tests / debugging)."""
        return jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x[i])), tree)
