"""Ring-allreduce backend tests: the same collective contract as the tree
backend (contributor count, flush identity, rider, scatter), the reference's
bitwise SGD invariant running unchanged over the ring, and a tree-vs-ring
numerical agreement check.  Threads over real localhost TCP, as in the
reference's ``ipc.map`` fixture (test/test_AllReduceSGD.lua:26-35)."""

import numpy as np
import pytest

from distlearn_tpu.comm.ring import LocalhostRing
from distlearn_tpu.comm.tree import LocalhostTree, tree_map_spawn
from distlearn_tpu.parallel.host_algorithms import TreeAllReduceSGD

from tests.net_util import reserve_port_window


def _port() -> int:
    return reserve_port_window(1)


@pytest.mark.parametrize("n", [2, 3, 4, 8])
def test_ring_allreduce_sum_and_count(n):
    port = _port()
    rng = np.random.RandomState(0)
    values = [rng.randn(37, 5).astype(np.float32) for _ in range(n)]

    def node(rank):
        r = LocalhostRing(rank, n, port)
        red, m = r.all_reduce({"v": values[rank],
                               "s": np.float32(rank)})
        r.close()
        return red, m

    expected = np.sum(values, axis=0)
    for red, m in tree_map_spawn(node, n):
        np.testing.assert_allclose(red["v"], expected, rtol=1e-5)
        np.testing.assert_allclose(red["s"], sum(range(n)), rtol=1e-6)
        assert m == n


def test_ring_mixed_dtypes_and_scalar_leaves():
    """Leaves of different dtypes ride separate dtype-grouped ring passes;
    int64 sums are exact, scalars and empty-ish chunks (size < N) work."""
    n, port = 4, _port()

    def node(rank):
        r = LocalhostRing(rank, n, port)
        red, m = r.all_reduce({"f": np.full((9,), 1.5, np.float64),
                               "i": np.arange(3, dtype=np.int64) + rank,
                               "tiny": np.int64(1)})
        r.close()
        return red, m

    for red, m in tree_map_spawn(node, n):
        np.testing.assert_array_equal(red["f"], 6.0)
        np.testing.assert_array_equal(
            red["i"], n * np.arange(3) + sum(range(n)))
        assert red["tiny"] == n
        assert m == n


def test_ring_flush_and_rider():
    """contrib=False ranks count as op-identity and are excluded from n, but
    the rider sums across ALL ranks (Tree.all_reduce_ex contract)."""
    n, port = 4, _port()

    def node(rank):
        r = LocalhostRing(rank, n, port)
        red, m, rid = r.all_reduce_ex(np.ones(6, np.float64),
                                      contrib=(rank < 2), rider=10 + rank)
        mx, m2 = r.all_reduce(np.array([-3.0 - rank]), op="max",
                              contrib=(rank != 0))
        r.close()
        return red, m, rid, mx, m2

    for red, m, rid, mx, m2 in tree_map_spawn(node, n):
        np.testing.assert_array_equal(red, 2.0)
        assert m == 2
        assert rid == 10 + 11 + 12 + 13
        np.testing.assert_array_equal(mx, -4.0)  # rank 0 excluded (identity)
        assert m2 == n - 1


@pytest.mark.parametrize("n", [2, 3, 5])
def test_ring_scatter(n):
    port = _port()

    def node(rank):
        r = LocalhostRing(rank, n, port)
        sc = r.scatter({"v": np.full((4, 4), float(rank), np.float32),
                        "u": np.arange(5) + rank})
        r.barrier()
        r.close()
        return sc

    for sc in tree_map_spawn(node, n):
        np.testing.assert_array_equal(sc["v"], 0.0)   # rank 0's everywhere
        np.testing.assert_array_equal(sc["u"], np.arange(5))


def test_ring_matches_tree_bitwise():
    """Same float64 inputs through both backends: the ring's chunked
    reduction must agree with the tree to float64 round-off; int64 exactly."""
    n = 4
    rng = np.random.RandomState(5)
    values = [rng.randn(1000).astype(np.float64) for _ in range(n)]
    ints = [rng.randint(-100, 100, 257).astype(np.int64) for _ in range(n)]

    port_t = _port()

    def tnode(rank):
        t = LocalhostTree(rank, n, port_t)
        red, _ = t.all_reduce({"f": values[rank], "i": ints[rank]})
        t.close()
        return red

    port_r = _port()

    def rnode(rank):
        r = LocalhostRing(rank, n, port_r)
        red, _ = r.all_reduce({"f": values[rank], "i": ints[rank]})
        r.close()
        return red

    tree_res = tree_map_spawn(tnode, n)
    ring_res = tree_map_spawn(rnode, n)
    np.testing.assert_array_equal(tree_res[0]["i"], ring_res[0]["i"])
    np.testing.assert_allclose(tree_res[0]["f"], ring_res[0]["f"],
                               rtol=0, atol=1e-12)
    # all ring ranks agree among themselves bitwise
    for res in ring_res[1:]:
        np.testing.assert_array_equal(ring_res[0]["f"], res["f"])


def test_per_nic_bytes_tree_root_4t_ring_rank_3t():
    """The byte counts the ring exists for, read from the connections' own
    counters at N=4: the base-2 tree's root moves the payload T up and down
    for each of its two children (4T through one NIC, whatever N), every
    ring rank moves 2T(N-1)/N out and the same in (3T)."""
    n, t_bytes = 4, 1 << 20
    payload = np.ones(t_bytes // 4, np.float32)

    def run(make, port):
        def node(rank):
            h = make(rank, n, port)
            before = h.nic_bytes()
            h.all_reduce(payload)
            moved = h.nic_bytes() - before
            h.close()
            return moved
        return tree_map_spawn(node, n)

    tree_moved = run(LocalhostTree, _port())
    ring_moved = run(LocalhostRing, _port())
    assert 4 * t_bytes <= tree_moved[0] < 4.01 * t_bytes      # the root
    assert max(tree_moved) == tree_moved[0]
    for moved in ring_moved:
        assert 3 * t_bytes <= moved < 3.01 * t_bytes


def test_ring_sgd_reference_invariant():
    """The reference's AllReduceSGD bitwise oracle (test_AllReduceSGD.lua:38)
    over the RING backend: host_algorithms runs on either backend because the
    collective surface is identical."""
    rng = np.random.RandomState(11)
    n = int(rng.choice([2, 4, 8]))
    port = _port()

    def node(rank):
        r = LocalhostRing(rank, n, port)
        sgd = TreeAllReduceSGD(r)
        rr = np.random.RandomState(300 + rank)
        params = {"w": np.zeros((4, 3), np.float64)}
        for ep in range(2):
            for _ in range(int(rr.randint(4, 14))):  # uneven steps
                g, m = sgd.sum_and_normalize_gradients({"w": rr.randn(4, 3)})
                params = {"w": params["w"] - 0.01 * g["w"]}
            params = sgd.synchronize_parameters(params)
        r.close()
        return params["w"]

    results = tree_map_spawn(node, n)
    for w in results[1:]:
        np.testing.assert_array_equal(results[0], w)


def test_ring_single_node():
    r = LocalhostRing(0, 1, _port())
    red, m, rid = r.all_reduce_ex({"v": np.ones(3)}, rider=7)
    np.testing.assert_array_equal(red["v"], 1.0)
    assert (m, rid) == (1, 7)
    sc = r.scatter({"v": np.zeros(2)})
    np.testing.assert_array_equal(sc["v"], 0.0)
    r.close()


def test_ring_op_timeout_detects_dead_rank():
    """A dead neighbor raises TimeoutError/ConnectionError instead of
    wedging (SURVEY.md §5: the reference wedges)."""
    import time
    port = _port()

    def node(rank):
        r = LocalhostRing(rank, 2, port)
        if rank == 1:
            r.close()
            return None
        r.set_op_timeout(0.5)
        t0 = time.monotonic()
        try:
            r.all_reduce({"v": np.ones((4,), np.float32)})
            return ("no-error", time.monotonic() - t0)
        except (TimeoutError, ConnectionError) as e:
            return (type(e).__name__, time.monotonic() - t0)
        finally:
            r.close()

    results = tree_map_spawn(node, 2, timeout=30)
    kind, dt = results[0]
    # PeerClosed is the clean-FIN ConnectionError subclass: a dead peer
    # may be seen either mid-frame (reset/timeout) or between frames
    assert kind in ("TimeoutError", "ConnectionError", "PeerClosed"), kind
    assert dt < 10.0
