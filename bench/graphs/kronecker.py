"""Graph500 Kronecker (R-MAT) edge list: Graph500 specification, Kernel 1
input. ``2**scale`` vertices, ``edge_factor * 2**scale`` edge tuples,
each placed by ``scale`` independent quadrant choices with the
initiator probabilities (A, B, C, D), then every vertex id permuted
to remove the locality the recursion leaves. Self-loops and duplicates
stay in the list: the graph build removes them."""
from __future__ import annotations

import numpy as np


def edges(cfg: dict, rng: np.random.Generator):
    scale, ef = int(cfg["scale"]), int(cfg["edge_factor"])
    a, b, c, _d = (float(x) for x in cfg["initiator"])
    n = 1 << scale
    m = n * ef
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for level in range(scale):
        r = rng.random(m)
        # quadrants: a (0,0), b (0,1), c (1,0), d (1,1)
        right = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        down = r >= a + b
        src |= down.astype(np.int64) << level
        dst |= right.astype(np.int64) << level
    perm = rng.permutation(n)
    return perm[src], perm[dst], n
