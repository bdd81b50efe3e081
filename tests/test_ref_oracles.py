"""The set-at-a-time numpy oracles in ``repro.core.ref`` against the
textbook loop versions they replaced (kept here as the reference).

The vectorized oracles validate scale-22 graphs in seconds; these
loops would take minutes there, so they run on small graphs only.
Integer results must be equal; distances are exact sums of integer
weights, so they must be equal too; PageRank sums in another order, so
it gets a float64-rounding tolerance.
"""
from __future__ import annotations

import heapq

import numpy as np
import pytest

from repro.core import graph as G
from repro.core import ref as R


def _csr(g):
    return (np.asarray(g.row_offsets), g.cols_np(),
            None if g.edge_values is None else np.asarray(g.edge_values))


def bfs_loop(g, src):
    ro, ci, _ = _csr(g)
    depth = np.full(len(ro) - 1, -1, np.int32)
    depth[src] = 0
    frontier, d = [src], 0
    while frontier:
        d += 1
        nxt = []
        for u in frontier:
            for e in range(ro[u], ro[u + 1]):
                if depth[ci[e]] < 0:
                    depth[ci[e]] = d
                    nxt.append(ci[e])
        frontier = nxt
    return depth


def dijkstra_loop(g, src):
    ro, ci, w = _csr(g)
    dist = np.full(len(ro) - 1, np.inf)
    dist[src] = 0.0
    heap = [(0.0, src)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for e in range(ro[u], ro[u + 1]):
            if d + w[e] < dist[ci[e]]:
                dist[ci[e]] = d + w[e]
                heapq.heappush(heap, (d + w[e], ci[e]))
    return dist.astype(np.float32)


def union_find_loop(g):
    ro, ci, _ = _csr(g)
    n = len(ro) - 1
    parent = np.arange(n)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for u, v in zip(np.repeat(np.arange(n), np.diff(ro)), ci):
        ru, rv = find(u), find(v)
        if ru != rv:
            parent[max(ru, rv)] = min(ru, rv)
    return np.array([find(x) for x in range(n)], np.int32)


def pagerank_loop(g, damping=0.85, iters=20):
    ro, ci, _ = _csr(g)
    n = len(ro) - 1
    deg = np.diff(ro)
    pr = np.full(n, 1.0 / n)
    src = np.repeat(np.arange(n), deg)
    for _ in range(iters):
        contrib = np.where(deg > 0, pr / np.maximum(deg, 1), 0.0)
        nxt = np.zeros(n)
        np.add.at(nxt, ci, contrib[src])
        pr = (1 - damping) / n + damping * (nxt + pr[deg == 0].sum() / n)
    return pr.astype(np.float32)


GRAPHS = {
    "rmat": lambda: G.rmat(8, 4, seed=1, weighted=True),
    "rmat_directed": lambda: G.rmat(9, 2, seed=7, weighted=True,
                                    undirected=False),
    "grid": lambda: G.grid2d(12, weighted=True),
    "rgg": lambda: G.random_geometric(300, 0.08, seed=1, weighted=True),
    "demo_multi_edge": G.demo_graph,
}


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_vectorized_oracles_match_loops(name):
    g = GRAPHS[name]()
    n = g.num_vertices
    for s in (0, n // 2, n - 1):
        assert np.array_equal(R.bfs_ref(g, s), bfs_loop(g, s))
        assert np.array_equal(R.reach_ref(g, s, 2),
                              (bfs_loop(g, s) >= 0) & (bfs_loop(g, s) <= 2))
        if g.edge_values is not None:
            assert np.array_equal(R.sssp_ref(g, s), dijkstra_loop(g, s))
    assert np.array_equal(R.cc_ref(g), union_find_loop(g))
    np.testing.assert_allclose(R.pagerank_ref(g), pagerank_loop(g),
                               rtol=1e-6, atol=0)
