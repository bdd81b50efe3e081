"""The plain reference: textbook set-at-a-time numpy versions of the
traversals and of PageRank, over the benchmark's own CSR
(``graphdata.GraphData.csr``). Adapted from the program's numpy oracles
so that a change to the program cannot move the yardstick; it imports
nothing of the program."""
from __future__ import annotations

import numpy as np


def _out_edges(ro: np.ndarray, rows: np.ndarray) -> tuple:
    """Edge positions of every out-edge of ``rows`` and the per-row
    counts."""
    starts = ro[rows]
    counts = ro[rows + 1] - starts
    before = np.cumsum(counts) - counts
    pos = (np.repeat(starts - before, counts)
           + np.arange(int(counts.sum()), dtype=np.int64))
    return pos, counts


def bfs(csr, src: int, max_depth: int | None = None) -> np.ndarray:
    """BFS depth of every vertex from ``src`` (-1 = not reached), one
    level at a time; stops after ``max_depth`` levels where given."""
    ro, ci, _ = csr
    depth = np.full(len(ro) - 1, -1, dtype=np.int32)
    depth[src] = 0
    frontier = np.asarray([src], np.int64)
    d = 0
    while len(frontier) and (max_depth is None or d < max_depth):
        d += 1
        pos, _ = _out_edges(ro, frontier)
        nbr = ci[pos]
        frontier = np.unique(nbr[depth[nbr] < 0])
        depth[frontier] = d
    return depth


def reach(csr, src: int, k: int) -> np.ndarray:
    """Vertices within ``k`` hops of ``src``."""
    return bfs(csr, src, max_depth=k) >= 0


def sssp(csr, src: int, rounding=None) -> np.ndarray:
    """Shortest-path distances (inf = not reached) by Bellman-Ford over
    the vertices improved in the previous round, in float64. With
    ``rounding`` (a numpy type such as ``ml_dtypes.bfloat16``) every
    weight and every sum is rounded to that type: the control."""
    ro, ci, w = csr
    if w is None:
        raise ValueError("sssp needs edge weights")

    def rnd(x):
        return x if rounding is None else x.astype(rounding).astype(
            np.float64)

    w = rnd(np.asarray(w, np.float64))
    dist = np.full(len(ro) - 1, np.inf)
    dist[src] = 0.0
    active = np.asarray([src], np.int64)
    while len(active):
        pos, counts = _out_edges(ro, active)
        nbr = ci[pos]
        cand = rnd(np.repeat(dist[active], counts) + w[pos])
        better = cand < dist[nbr]
        nbr, cand = nbr[better], cand[better]
        np.minimum.at(dist, nbr, cand)
        active = np.unique(nbr)
    return dist


def pagerank(csr, damping: float, sweeps: int) -> np.ndarray:
    """Power-iteration PageRank in float64 with uniform teleport; the
    rank of dangling vertices is spread uniformly."""
    ro, ci, _ = csr
    n = len(ro) - 1
    deg = np.diff(ro)
    rows = np.repeat(np.arange(n), deg)
    pr = np.full(n, 1.0 / n)
    for _ in range(sweeps):
        contrib = np.where(deg > 0, pr / np.maximum(deg, 1), 0.0)
        acc = np.bincount(ci, weights=contrib[rows], minlength=n)
        pr = (1 - damping) / n + damping * (acc + pr[deg == 0].sum() / n)
    return pr


def component_edges(csr) -> np.ndarray:
    """For every vertex, the undirected edges of its connected component
    (the sum of its members' degrees over 2): what a traversal from it
    has to cover, as Graph500 counts TEPS."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components

    ro, ci, _ = csr
    n = len(ro) - 1
    adj = csr_matrix((np.ones(len(ci), np.int8), ci, ro), shape=(n, n))
    _, label = connected_components(adj, directed=False)
    per_comp = np.bincount(label, weights=np.diff(ro)) / 2
    return per_comp[label]
