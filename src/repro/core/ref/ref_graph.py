"""Pure-numpy oracle implementations of every graph primitive.

These are the correctness references for the JAX/Pallas engine — serial,
textbook versions (the same algorithms the paper's hardwired baselines
implement). Used by unit/property tests and the benchmark harness's
validation pass.
"""
from __future__ import annotations

import numpy as np


def _csr(graph):
    return (np.asarray(graph.row_offsets), graph.cols_np(),
            None if graph.edge_values is None
            else np.asarray(graph.edge_values))


def _out_edges(ro: np.ndarray, rows: np.ndarray):
    """Edge positions of every out-edge of ``rows`` (row-major), and the
    per-row counts — the vectorized CSR gather the set-at-a-time oracles
    below share."""
    starts = ro[rows].astype(np.int64)
    counts = ro[rows + 1].astype(np.int64) - starts
    before = np.cumsum(counts) - counts
    pos = (np.repeat(starts - before, counts)
           + np.arange(int(counts.sum()), dtype=np.int64))
    return pos, counts


def bfs_ref(graph, src: int) -> np.ndarray:
    """Breadth-first search depths (-1 = unreachable), one level at a
    time: the next level is every unvisited out-neighbor of this one."""
    ro, ci, _ = _csr(graph)
    n = len(ro) - 1
    depth = np.full(n, -1, dtype=np.int32)
    depth[src] = 0
    frontier = np.asarray([src], np.int64)
    d = 0
    while len(frontier):
        d += 1
        pos, _ = _out_edges(ro, frontier)
        nbr = ci[pos]
        frontier = np.unique(nbr[depth[nbr] < 0])
        depth[frontier] = d
    return depth


def sssp_ref(graph, src: int) -> np.ndarray:
    """Shortest-path distances (inf = unreachable) by Bellman-Ford over
    the set of vertices improved in the previous round, in float64."""
    ro, ci, w = _csr(graph)
    assert w is not None, "sssp needs edge weights"
    n = len(ro) - 1
    w = np.asarray(w, np.float64)
    dist = np.full(n, np.inf, dtype=np.float64)
    dist[src] = 0.0
    active = np.asarray([src], np.int64)
    while len(active):
        pos, counts = _out_edges(ro, active)
        nbr = ci[pos]
        cand = np.repeat(dist[active], counts) + w[pos]
        better = cand < dist[nbr]
        nbr, cand = nbr[better], cand[better]
        np.minimum.at(dist, nbr, cand)
        active = np.unique(nbr)
    return dist.astype(np.float32)


def pagerank_ref(graph, damping: float = 0.85, iters: int = 20,
                 tol: float = 0.0) -> np.ndarray:
    """Power-iteration PageRank with uniform teleport.

    Dangling mass is redistributed uniformly (standard formulation).
    """
    ro, ci, _ = _csr(graph)
    n = len(ro) - 1
    deg = np.diff(ro)
    pr = np.full(n, 1.0 / n)
    src = np.repeat(np.arange(n), deg)
    for _ in range(iters):
        contrib = np.where(deg > 0, pr / np.maximum(deg, 1), 0.0)
        nxt = np.bincount(ci, weights=contrib[src], minlength=n)
        dangling = pr[deg == 0].sum() / n
        new = (1 - damping) / n + damping * (nxt + dangling)
        if tol > 0 and np.abs(new - pr).max() < tol:
            pr = new
            break
        pr = new
    return pr.astype(np.float32)


def cc_ref(graph) -> np.ndarray:
    """Weakly connected components labelled by their smallest vertex id.

    Every edge hooks the larger of its endpoints' labels onto the
    smaller (both directions), then labels pointer-jump to their roots;
    repeat until no edge joins two labels."""
    ro, ci, _ = _csr(graph)
    n = len(ro) - 1
    src = np.repeat(np.arange(n), np.diff(ro))
    dst = np.asarray(ci, np.int64)
    label = np.arange(n)
    while True:
        lu, lv = label[src], label[dst]
        cross = lu != lv
        if not cross.any():
            return label.astype(np.int32)
        lu, lv = lu[cross], lv[cross]
        np.minimum.at(label, np.maximum(lu, lv), np.minimum(lu, lv))
        while True:
            jumped = label[label]
            if np.array_equal(jumped, label):
                break
            label = jumped


def bc_ref(graph, src: int) -> np.ndarray:
    """Brandes betweenness centrality contribution from one source."""
    ro, ci, _ = _csr(graph)
    n = len(ro) - 1
    sigma = np.zeros(n)
    sigma[src] = 1.0
    depth = np.full(n, -1, dtype=np.int64)
    depth[src] = 0
    order = [src]
    frontier = [src]
    d = 0
    while frontier:
        d += 1
        nxt = []
        for u in frontier:
            for e in range(ro[u], ro[u + 1]):
                v = ci[e]
                if depth[v] < 0:
                    depth[v] = d
                    nxt.append(v)
                    order.append(v)
                if depth[v] == d:
                    sigma[v] += sigma[u]
        frontier = nxt
    delta = np.zeros(n)
    for u in reversed(order):
        for e in range(ro[u], ro[u + 1]):
            v = ci[e]
            if depth[v] == depth[u] + 1 and sigma[v] > 0:
                delta[u] += sigma[u] / sigma[v] * (1.0 + delta[v])
    bc = delta.copy()
    bc[src] = 0.0
    return bc.astype(np.float32)


def tc_ref(graph) -> int:
    """Exact triangle count of an undirected graph (forward algorithm)."""
    ro, ci, _ = _csr(graph)
    n = len(ro) - 1
    deg = np.diff(ro)
    count = 0
    adj = [set(ci[ro[u]:ro[u + 1]]) for u in range(n)]
    for u in range(n):
        for e in range(ro[u], ro[u + 1]):
            v = ci[e]
            # orient edges from higher-degree to lower-degree (paper §6.6):
            # each triangle is then charged to exactly 3 oriented edges,
            # once per edge, with full-adjacency intersections.
            if (deg[u], u) > (deg[v], v):
                count += len(adj[u] & adj[v])
    return count // 3


def reach_ref(graph, src: int, k: int) -> np.ndarray:
    """k-hop reachability oracle: bfs depth within [0, k]."""
    depth = bfs_ref(graph, src)
    return (depth >= 0) & (depth <= k)


def label_propagation_ref(graph, max_iter: int = 30,
                          labels: np.ndarray | None = None) -> np.ndarray:
    """Synchronous label propagation — the exact mirror of the device
    rule: every vertex adopts the most frequent neighbor label (ties →
    smallest label; no neighbors / no votes → keep), all vertices
    updating simultaneously, until stable or max_iter."""
    ro, ci, _ = _csr(graph)
    n = len(ro) - 1
    lab = (np.arange(n, dtype=np.int64) if labels is None
           else np.asarray(labels, np.int64).copy())
    for _ in range(max_iter):
        new = lab.copy()
        for u in range(n):
            nbr = ci[ro[u]:ro[u + 1]]
            if len(nbr) == 0:
                continue
            cnt = np.bincount(lab[nbr], minlength=n)
            if cnt.max() > 0:
                new[u] = int(np.argmax(cnt))    # first max = smallest label
        if np.array_equal(new, lab):
            break
        lab = new
    return lab.astype(np.int32)


def ppr_ref(graph, src: int, damping: float = 0.85,
            iters: int = 30) -> np.ndarray:
    """Personalized PageRank with teleport to ``src``."""
    ro, ci, _ = _csr(graph)
    n = len(ro) - 1
    deg = np.diff(ro)
    pr = np.zeros(n)
    pr[src] = 1.0
    e_src = np.repeat(np.arange(n), deg)
    for _ in range(iters):
        contrib = np.where(deg > 0, pr / np.maximum(deg, 1), 0.0)
        nxt = np.zeros(n)
        np.add.at(nxt, ci, contrib[e_src])
        dangling = pr[deg == 0].sum()
        new = damping * nxt
        new[src] += (1 - damping) + damping * dangling
        pr = new
    return pr.astype(np.float32)


def salsa_ref(graph, hubs: np.ndarray, iters: int = 10):
    """Bipartite SALSA on the subgraph induced by ``hubs`` (bool mask over
    vertices) and their out-neighbors. Returns (hub_scores, auth_scores)."""
    ro, ci, _ = _csr(graph)
    n = len(ro) - 1
    hubs = np.asarray(hubs, dtype=bool)
    auth_set = np.zeros(n, dtype=bool)
    edges = []
    for u in np.nonzero(hubs)[0]:
        for e in range(ro[u], ro[u + 1]):
            edges.append((u, ci[e]))
            auth_set[ci[e]] = True
    if not edges:
        return np.zeros(n, np.float32), np.zeros(n, np.float32)
    es = np.array(edges)
    hub_deg = np.zeros(n)
    np.add.at(hub_deg, es[:, 0], 1.0)
    auth_deg = np.zeros(n)
    np.add.at(auth_deg, es[:, 1], 1.0)
    h = hubs / max(hubs.sum(), 1)
    a = np.zeros(n)
    for _ in range(iters):
        # hub -> auth
        a = np.zeros(n)
        contrib = np.where(hub_deg > 0, h / np.maximum(hub_deg, 1), 0.0)
        np.add.at(a, es[:, 1], contrib[es[:, 0]])
        # auth -> hub
        h = np.zeros(n)
        contrib = np.where(auth_deg > 0, a / np.maximum(auth_deg, 1), 0.0)
        np.add.at(h, es[:, 0], contrib[es[:, 1]])
    return h.astype(np.float32), a.astype(np.float32)
