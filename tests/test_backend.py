"""Topology-aware collective backend tests (comm/backend.py).

The load-bearing invariant is TRAJECTORY PARITY: the same EASGD run —
same nodes, same per-node steps — must produce BITWISE-identical
parameters whether the collective is the device-mesh psum
(``MeshBackend``), the reference's flat TCP tree (``HostBackend``), or
the hierarchical in-mesh-reduce-scatter / one-TCP-leg-per-host /
in-mesh-all-gather pipeline (``HybridBackend``).  Dyadic-exact values
(integer f64 grads, alpha=0.5, non-expanding recursion) make float
addition associative, so ANY reduction-order difference would show as
an exact mismatch.

Everything else supports that: the protocol surface, the value
conventions (plain vs stacked-slice pytrees, node_offset), chunk
planning and D2H staging, rider/contrib semantics across value
conventions, scatter from an arbitrary (cross-host) source, the
degenerate 1-host/1-device topologies, and — the satellite regression —
that op_timeout + FaultPlan semantics survive the backend adapter: a
partition mid-collective surfaces the SAME typed error through the
HybridBackend host leg as through a raw Tree.
"""

from __future__ import annotations

import os
import threading

import numpy as np
import pytest

from distlearn_tpu.comm.backend import (CollectiveBackend, HostBackend,
                                        HybridBackend, MeshBackend,
                                        plan_chunks)
from distlearn_tpu.comm.tree import LocalhostTree, tree_map_spawn

from tests.net_util import reserve_port_window


def _port() -> int:
    return reserve_port_window(1)


# ------------------------------------------------------------ chunk planning

def test_plan_chunks_even_and_padded():
    padded, spans = plan_chunks(16, 4)
    assert padded == 16
    assert spans == [(0, 4), (4, 8), (8, 12), (12, 16)]
    padded, spans = plan_chunks(10, 4)      # pads 10 -> 12
    assert padded == 12
    assert spans == [(0, 3), (3, 6), (6, 9), (9, 12)]
    assert spans[-1][1] == padded


def test_plan_chunks_degenerate():
    padded, spans = plan_chunks(3, 8)       # fewer elements than parts
    assert padded == 8
    assert len(spans) == 8 and all(hi - lo == 1 for lo, hi in spans)
    padded, spans = plan_chunks(5, 1)       # single part: no padding
    assert (padded, spans) == (5, [(0, 5)])


def test_stage_into_roundtrip_mixed_dtypes():
    from distlearn_tpu.comm.wire import FrameBuffer
    from distlearn_tpu.ops.staging import stage_into

    fb = FrameBuffer()
    a = np.arange(7, dtype=np.float32)
    b = np.arange(5, dtype=np.int64) * -3
    views = stage_into(fb, [a, b], [a.dtype, b.dtype])
    for v, src in zip(views, (a, b)):
        assert v.dtype == src.dtype
        np.testing.assert_array_equal(v, src)
    # windows are 16B-aligned within the frame: a is 28 bytes, so b's
    # window starts at offset 32, not 28
    assert views[1].ctypes.data - views[0].ctypes.data == 32
    # views alias fb.buf: staging a second time reuses the allocation
    views2 = stage_into(fb, [a * 2, b * 2], [a.dtype, b.dtype])
    np.testing.assert_array_equal(views2[0], a * 2)
    np.testing.assert_array_equal(views2[1], b * 2)


# ------------------------------------------------------------ protocol

def test_all_backends_satisfy_protocol():
    mesh = MeshBackend(num_nodes=4)
    hybrid = HybridBackend(0, 1, num_devices=4)
    assert isinstance(mesh, CollectiveBackend)
    assert isinstance(hybrid, CollectiveBackend)
    assert mesh.stacked_nodes == 4 and mesh.node_offset == 0
    assert hybrid.stacked_nodes == 4 and hybrid.node_offset == 0
    port = _port()

    def node(rank):
        b = HostBackend(LocalhostTree(rank, 2, port))
        ok = isinstance(b, CollectiveBackend)
        off = b.node_offset
        b.close()
        return ok, off, b.stacked_nodes
    for rank, (ok, off, stacked) in enumerate(tree_map_spawn(node, 2)):
        assert ok and off == rank and stacked is None


# ------------------------------------------------------------ host adapter

def test_host_backend_matches_raw_tree_and_scatter_src():
    """The adapter is behavior-preserving: sum/rider/contrib identical
    to the raw handle; scatter(src != 0) — the one derived op — selects
    the source's values bitwise on every rank."""
    n, port = 4, _port()
    vals = [np.arange(6, dtype=np.float64).reshape(2, 3) * (r + 1)
            for r in range(n)]

    def node(rank):
        b = HostBackend(LocalhostTree(rank, n, port))
        red, m, rid = b.all_reduce_ex({"v": vals[rank]}, rider=rank)
        masked, m2 = b.all_reduce({"v": vals[rank]}, contrib=(rank != 1))
        sc = b.scatter({"v": vals[rank]}, src=2)
        b.barrier()
        b.close()
        return red["v"], m, rid, masked["v"], m2, sc["v"]

    expect = np.sum(vals, axis=0)
    expect_masked = expect - vals[1]
    for red, m, rid, masked, m2, sc in tree_map_spawn(node, n):
        np.testing.assert_array_equal(red, expect)
        assert (m, rid) == (n, sum(range(n)))
        np.testing.assert_array_equal(masked, expect_masked)
        assert m2 == n - 1
        np.testing.assert_array_equal(sc, vals[2])


# ------------------------------------------------------------ mesh backend

def test_mesh_backend_stacked_allreduce_rider_and_contrib():
    n = 8
    b = MeshBackend(num_nodes=n)
    rows = np.arange(n * 5, dtype=np.float64).reshape(n, 5)
    red, m, rid = b.all_reduce_ex({"w": rows}, rider=3)
    assert m == n
    assert rid == 3 * n          # rider is summed per logical node
    got = b.node_slice(red, 0)["w"]
    np.testing.assert_array_equal(got, rows.sum(axis=0))
    # per-row contrib vector: row 2 excluded from the sum AND the count
    cvec = np.ones(n, bool)
    cvec[2] = False
    red, m = b.all_reduce({"w": rows}, contrib=cvec)
    assert m == n - 1
    np.testing.assert_array_equal(b.node_slice(red, 5)["w"],
                                  rows.sum(axis=0) - rows[2])
    with pytest.raises(NotImplementedError):
        b.all_reduce({"w": rows}, op="max")


# ------------------------------------------------------------ hybrid: 1 host

def test_hybrid_single_host_matches_mesh_bitwise():
    """H=1 skips the TCP leg but keeps reduce-scatter/all-gather; the
    result must be bitwise the mesh psum's (dyadic-exact values)."""
    n = 8
    mesh = MeshBackend(num_nodes=n)
    hyb = HybridBackend(0, 1, num_devices=n)
    assert hyb.num_nodes == n and hyb.host_leg is None
    val = {"w": np.arange(n * 16, dtype=np.float64).reshape(n, 16) * 0.5,
           "b": (np.arange(n * 3) % 5).astype(np.float64).reshape(n, 3)}
    m_red, m_n = mesh.all_reduce(val)
    h_red, h_n = hyb.all_reduce(val)
    assert m_n == h_n == n
    for k in val:
        np.testing.assert_array_equal(np.asarray(mesh.node_slice(m_red, 0)[k]),
                                      np.asarray(hyb.node_slice(h_red, 0)[k]))
    # rider sums per logical node; contrib row-mask drops row sums
    _, m, rid = hyb.all_reduce_ex(val, rider=2)
    assert (m, rid) == (n, 2 * n)
    cvec = np.ones(n, bool)
    cvec[3] = False
    red, m = hyb.all_reduce(val, contrib=cvec)
    assert m == n - 1
    np.testing.assert_array_equal(
        np.asarray(hyb.node_slice(red, 0)["w"]),
        val["w"].sum(axis=0) - val["w"][3])


def test_hybrid_single_device_degenerate():
    """L=1: reduce-scatter/all-gather over one device are identities;
    the backend still honors the stacked [1, ...] convention."""
    hyb = HybridBackend(0, 1, num_devices=1)
    assert hyb.num_nodes == 1 and hyb.stacked_nodes == 1
    val = {"w": np.arange(4, dtype=np.float64)[None]}
    red, m = hyb.all_reduce(val)
    assert m == 1
    np.testing.assert_array_equal(np.asarray(hyb.node_slice(red, 0)["w"]),
                                  val["w"][0])


# ------------------------------------------------------------ hybrid: 2 hosts

def _disjoint_devices(local):
    import jax
    devs = jax.devices()
    return [devs[h * local:(h + 1) * local] for h in range(2)]


def test_hybrid_two_hosts_allreduce_rider_scatter():
    """Full pipeline across a real TCP leg: mixed-dtype leaves reduce
    exactly; contributor count and rider cover all H*L logical nodes;
    scatter from a row owned by the OTHER host replicates bitwise."""
    hosts, local = 2, 2
    n = hosts * local
    port = _port()
    slices = _disjoint_devices(local)
    rows_w = np.arange(n * 8, dtype=np.float64).reshape(n, 8) * 0.25
    rows_i = (np.arange(n * 4) % 9).astype(np.int64).reshape(n, 4)

    def node(rank):
        b = HybridBackend(rank, hosts, "127.0.0.1", port,
                          devices=slices[rank])
        lo = b.node_offset
        val = {"w": rows_w[lo:lo + local], "i": rows_i[lo:lo + local]}
        red, m, rid = b.all_reduce_ex(val, rider=lo + 1)
        out_w = np.asarray(b.node_slice(red, 0)["w"])
        out_i = np.asarray(b.node_slice(red, 1)["i"])
        sc = b.scatter(val, src=3)          # host 1's second row
        sc_w = np.asarray(b.node_slice(sc, 0)["w"])
        bytes_leg = b.host_leg.nic_bytes()
        b.barrier()
        b.close()
        return out_w, out_i, m, rid, sc_w, bytes_leg

    res = tree_map_spawn(node, hosts, timeout=120)
    for out_w, out_i, m, rid, sc_w, bytes_leg in res:
        np.testing.assert_array_equal(out_w, rows_w.sum(axis=0))
        np.testing.assert_array_equal(out_i, rows_i.sum(axis=0))
        assert m == n
        # rider is per LOGICAL node: host h contributes rider_h * L
        assert rid == (0 + 1) * local + (local + 1) * local
        np.testing.assert_array_equal(sc_w, rows_w[3])
        assert bytes_leg > 0                  # the TCP leg really ran
    # both hosts bitwise identical
    np.testing.assert_array_equal(res[0][0], res[1][0])


@pytest.mark.parametrize("local", [2, 4])
def test_hybrid_host_leg_moves_one_payload_a_host(local):
    """What the hierarchy is for, as a count: a host's TCP leg carries ONE
    node's payload T up and one down per allreduce (2T through its NIC),
    whatever the number of local devices behind it — the flat tree gives
    each of those devices a rank that moves 2T a link (tests/test_ring.py
    pins 4T at a base-2 root)."""
    hosts, t_bytes = 2, 1 << 18
    port = _port()
    slices = _disjoint_devices(local)

    def node(rank):
        b = HybridBackend(rank, hosts, "127.0.0.1", port,
                          devices=slices[rank])
        val = np.ones((local, t_bytes // 4), np.float32)
        before = b.host_leg.nic_bytes()
        b.all_reduce(val)
        moved = b.host_leg.nic_bytes() - before
        b.barrier()
        b.close()
        return moved

    for moved in tree_map_spawn(node, hosts, timeout=120):
        assert 2 * t_bytes <= moved < 2.02 * t_bytes


# ------------------------------------------------------------ EASGD parity

_N, _ROUNDS, _ALPHA, _DIM = 4, 24, 0.5, 24


def _grad(rank: int, r: int) -> np.ndarray:
    """Integer-valued deterministic per-node 'gradient' (dyadic-exact:
    with alpha=0.5 and N*alpha=2 the recursion never outgrows f64)."""
    return (np.arange(_DIM, dtype=np.float64) % 5 + 3 * rank + r) * 1.0


def _easgd_trajectory(backend, local: int) -> np.ndarray:
    """Run the shared EASGD schedule over one backend handle; returns
    [rounds, dim] of this handle's row-0 params after each round."""
    from distlearn_tpu.parallel.allreduce_ea import AllReduceEA
    ea = AllReduceEA(backend, tau=1, alpha=_ALPHA)
    lo = backend.node_offset
    traj = []
    if getattr(backend, "stacked_nodes", None) is None:
        params = np.zeros(_DIM, np.float64)
        for r in range(_ROUNDS):
            params = params - _grad(lo, r)
            params = ea.average_parameters(params)
            traj.append(np.asarray(params, np.float64).copy())
    else:
        params = np.zeros((local, _DIM), np.float64)
        for r in range(_ROUNDS):
            params = np.stack([params[i] - _grad(lo + i, r)
                               for i in range(local)])
            params = ea.average_parameters(params)
            traj.append(np.asarray(params, np.float64)[0].copy())
    return np.stack(traj)


def test_easgd_trajectory_bitwise_identical_across_backends():
    """THE acceptance invariant: the same EASGD run over MeshBackend,
    HostBackend (4 TCP tree ranks) and HybridBackend (2 hosts x 2
    devices) produces bitwise-identical trajectories at S=1 over
    >= 20 rounds."""
    mesh_traj = _easgd_trajectory(MeshBackend(num_nodes=_N), _N)

    port = _port()

    def host_node(rank):
        b = HostBackend(LocalhostTree(rank, _N, port))
        traj = _easgd_trajectory(b, 1)
        b.close()
        return traj
    host_trajs = tree_map_spawn(host_node, _N, timeout=120)

    port2 = _port()
    slices = _disjoint_devices(2)

    def hybrid_node(rank):
        b = HybridBackend(rank, 2, "127.0.0.1", port2,
                          devices=slices[rank])
        traj = _easgd_trajectory(b, 2)
        b.close()
        return traj
    hybrid_trajs = tree_map_spawn(hybrid_node, 2, timeout=120)

    # rank 0's row-0 trajectory must match EXACTLY everywhere
    np.testing.assert_array_equal(mesh_traj, host_trajs[0])
    np.testing.assert_array_equal(mesh_traj, hybrid_trajs[0])
    # and the collective leaves every handle's view identical
    assert not np.array_equal(mesh_traj[0], np.zeros(_DIM))


def test_allreduce_sgd_winner_scatter_across_hosts():
    """synchronize_parameters picks the GLOBAL most-stepped node (the
    reference's last-max winner) even when the per-handle step counts
    live on different hosts of a hybrid slice — exercising the partial-
    view stacked `_global_steps` allreduce AND the cross-host scatter."""
    from distlearn_tpu.parallel.allreduce_sgd import AllReduceSGD
    hosts, local = 2, 2
    port = _port()
    slices = _disjoint_devices(local)

    def node(rank):
        b = HybridBackend(rank, hosts, "127.0.0.1", port,
                          devices=slices[rank])
        sgd = AllReduceSGD(b)
        params = {"w": np.full((local, 4), float(b.node_offset),
                               np.float64)}
        sgd._bump(True)                     # every node steps once
        if rank == 1:
            sgd._bump(np.array([0, 1]))     # logical node 3 pulls ahead
        out = sgd.synchronize_parameters(params)
        w = np.asarray(b.node_slice(out, 0)["w"])
        b.close()
        return w

    res = tree_map_spawn(node, hosts, timeout=120)
    # steps [1, 1, 1, 2] -> winner = logical node 3 -> host 1's fill
    # value (node_offset == 2.0) replicated onto every row of every host
    for w in res:
        np.testing.assert_array_equal(w, np.full(4, 2.0))


# ------------------------------------------------------------ faults parity

def _partition_error(run):
    """Run ``run(rank) -> None`` on 2 ranks; collect the exception type
    each rank surfaces (the collective must fail, not hang)."""
    errs = [None, None]

    def node(rank):
        try:
            run(rank)
        except Exception as e:  # noqa: BLE001 — the type IS the assertion
            errs[rank] = type(e)
            return
        errs[rank] = None
    tree_map_spawn(node, 2, timeout=120)
    return errs


def test_fault_partition_surfaces_same_error_raw_tree_vs_hybrid():
    """ISSUE 20 satellite: a FaultPlan partition during the HybridBackend
    host leg surfaces the SAME typed error (TimeoutError, via op_timeout)
    as the identical partition on a raw Tree collective."""
    from distlearn_tpu.comm.faults import FaultPlan

    plan_tree = FaultPlan(seed=0)
    plan_tree.partition("tree")
    port = _port()

    def raw_tree(rank):
        t = LocalhostTree(rank, 2, port, op_timeout=1.0,
                          fault_plan=plan_tree)
        try:
            t.all_reduce(np.ones(4, np.float64))
        finally:
            t.close()
    tree_errs = _partition_error(raw_tree)

    plan_hyb = FaultPlan(seed=0)
    plan_hyb.partition("hybrid")
    port2 = _port()
    slices = _disjoint_devices(1)

    def hybrid(rank):
        b = HybridBackend(rank, 2, "127.0.0.1", port2,
                          devices=slices[rank], op_timeout=1.0,
                          fault_plan=plan_hyb)
        try:
            b.all_reduce({"w": np.ones((1, 4), np.float64)})
        finally:
            b.close()
    hyb_errs = _partition_error(hybrid)

    assert TimeoutError in tree_errs     # the partition bit the raw tree
    assert TimeoutError in hyb_errs      # ... and the adapter's host leg
    # parity: the hybrid path surfaces nothing the raw path would not
    assert {e for e in hyb_errs if e} <= {e for e in tree_errs if e}


# ------------------------------------------------------------ AsyncEA slice

def test_async_ea_slice_client_one_leg_for_l_rows():
    """A slice client (slice_backend=MeshBackend) pushes ONE wire delta
    for its L device rows; the server center moves by the SUM of the
    per-row deltas and every row keeps its own elastic pull."""
    from distlearn_tpu.parallel.async_ea import AsyncEAClient, AsyncEAServer
    L, alpha = 4, 0.5
    port = reserve_port_window(8)
    out = {}

    def client_fn():
        c = AsyncEAClient("127.0.0.1", port, node=1, tau=1, alpha=alpha,
                          slice_backend=MeshBackend(num_nodes=L))
        p = c.init_client({"w": np.zeros(3, np.float32)})
        assert p["w"].shape == (L, 3)      # stacked [L, *shape] rows
        drift = (np.arange(1, L + 1, dtype=np.float32)[:, None]
                 * np.ones(3, np.float32))
        p = {"w": p["w"] + drift}          # rows drift by 1, 2, 3, 4
        p, synced = c.sync_client(p)
        assert synced
        out["p"] = p
        c.close()

    th = threading.Thread(target=client_fn)
    th.start()
    srv = AsyncEAServer("127.0.0.1", port, num_nodes=1)
    srv.init_server({"w": np.zeros(3, np.float32)})
    new_params = srv.sync_server({"w": np.zeros(3, np.float32)})
    th.join(timeout=60)
    srv.close()
    assert "p" in out, "slice client never finished its sync"
    # per-row pull: row i keeps (i+1) - (i+1)*alpha
    np.testing.assert_allclose(
        out["p"]["w"],
        (np.arange(1, L + 1, dtype=np.float32) * alpha)[:, None]
        * np.ones(3, np.float32))
    # center moved by the SUM of row deltas: (1+2+3+4) * 0.5 = 5.0
    np.testing.assert_allclose(new_params["w"], 5.0)


# ------------------------------------------------------------ compile cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _compile_cache_probe(env_dir):
    """enable_compile_cache() in a fresh interpreter (it reconfigures
    process-global jax state — never in the suite's own process):
    which config keys it set in code, and the directory in effect."""
    import json
    import subprocess
    import sys

    code = (
        "import json, jax\n"
        "from unittest import mock\n"
        "from distlearn_tpu.utils import compile_cache as cc\n"
        "with mock.patch.object(jax.config, 'update',\n"
        "                       wraps=jax.config.update) as up:\n"
        "    d = cc.enable_compile_cache()\n"
        "print(json.dumps({'set': [c.args[0] for c in up.call_args_list],\n"
        "                  'dir': d, 'default': cc.DEFAULT_DIR}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd="/",
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: no code path names a directory
    (jax reads the env itself).  Unset: the fixed <checkout>/.jax_cache,
    whatever the working directory."""
    rec = _compile_cache_probe(str(tmp_path / "xla"))
    assert "jax_compilation_cache_dir" not in rec["set"], rec
    assert rec["dir"] == str(tmp_path / "xla")

    rec = _compile_cache_probe(None)
    assert rec["set"].count("jax_compilation_cache_dir") == 1, rec
    assert rec["dir"] == rec["default"] == os.path.join(_ROOT, ".jax_cache")


# ------------------------------------------------------------ lint hooks

def test_distlint_sync_family_is_clean():
    """The committed lint/budgets/sync.json lockfile matches the lowered
    mesh-allreduce and hybrid reduce-scatter/all-gather programs."""
    from distlearn_tpu.lint.registry import run_family
    results = run_family("sync")
    assert results, "sync family registered no units"
    for r in results:
        assert r.findings == [], (
            f"{r.name}: " + "; ".join(map(str, r.findings)))
