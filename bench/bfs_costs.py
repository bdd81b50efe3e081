"""Bytes that a breadth-first search has to move, counted from the
graph alone, whatever implements it: a lower bound for a traversal's
roofline share that holds under direction optimization too (a
bottom-up step reads no fewer offsets and columns than it settles)."""
from __future__ import annotations

import numpy as np

INT32 = 4
# per vertex a traversal reaches: its depth written, and at least one
# row offset and one column index read to reach it
BYTES_PER_REACHED_VERTEX = 3 * INT32


def bfs_least_bytes(reached_vertices) -> int:
    """Least bytes of traversals that reach ``reached_vertices`` vertices
    in all (a number, or an array of per-traversal counts)."""
    return int(BYTES_PER_REACHED_VERTEX * np.sum(reached_vertices))


def component_vertices(csr) -> np.ndarray:
    """For every vertex, the vertex count of its connected component:
    what a traversal from it reaches."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components

    ro, ci, _ = csr
    n = len(ro) - 1
    adj = csr_matrix((np.ones(len(ci), np.int8), ci, ro), shape=(n, n))
    _, label = connected_components(adj, directed=False)
    return np.bincount(label)[label]
