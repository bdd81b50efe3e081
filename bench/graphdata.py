"""The benchmark's graphs: made from the seed, handed to the program as
an edge list, and rebuilt here as the plain reference's own CSR.

The configuration's file names a generator (``bench/graphs/<name>.py``,
a function ``edges(cfg, rng) -> (src, dst, n)``), its parameters and
``graph_seed``, the dataset's own seed (as DIMACS10's ``rgg_n_2_k_s0``
is one fixed graph). A run's ``--seed`` relabels that graph's vertices
by a random permutation, as Graph500's generator does: every seed then
gets the same weighted graph (so the same compiled programs and the
same work) under other vertex ids.
Weights, where the configuration asks for them, are a hash of the
unordered pair of the dataset's vertex ids with ``graph_seed``, so both
directions of an edge and every duplicate of it carry the same weight,
whichever copy a build keeps, and every seed the same weights.
"""
from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed,
    negative or past 64 bits, maps to one."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def load_generator(name: str, root: str = HERE):
    path = os.path.join(root, "graphs", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no graph generator {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_graph_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer over uint64 (wrapping arithmetic)."""
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def pair_weights(u, v, n: int, seed: int, lo: float,
                 hi: float) -> np.ndarray:
    """Real weights, uniform in ``[lo, hi)``, of the unordered pairs
    ``{u, v}``: 24 random bits each, so that every weight of ``[0, 1)``
    is exact in float32, as Graph500's Kernel 3 draws them."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    key = (np.minimum(u, v) * n + np.maximum(u, v)).astype(np.uint64)
    salt = _mix(np.asarray([int(seed) % (1 << 64)], np.uint64))[0]
    frac = (_mix(key ^ salt) >> np.uint64(40)).astype(np.float64) / 2.0 ** 24
    return (lo + (hi - lo) * frac).astype(np.float32)


@dataclass
class GraphData:
    """One configuration's graph at one seed."""
    cfg: dict
    seed: int
    n: int
    src: np.ndarray            # the edge list as generated (int64)
    dst: np.ndarray
    weights: Optional[np.ndarray]
    perm: Optional[np.ndarray] = None   # dataset id -> this seed's id
    _csr: Optional[tuple] = field(default=None, repr=False)

    def relabel(self, ids) -> np.ndarray:
        """This seed's ids of the dataset's vertices ``ids``."""
        ids = np.asarray(ids, np.int64)
        return ids if self.perm is None else self.perm[ids]

    def sources(self) -> np.ndarray:
        """Vertices of degree >= 1 once self-loops are dropped (Graph500
        search keys), ascending."""
        return np.setdiff1d(np.arange(self.n), self.isolated())

    def isolated(self) -> np.ndarray:
        """Vertices of degree 0, ascending."""
        deg = np.zeros(self.n, bool)
        keep = self.src != self.dst
        deg[self.src[keep]] = deg[self.dst[keep]] = True
        return np.nonzero(~deg)[0]

    def warm_sources(self, k: int) -> np.ndarray:
        """``k`` sources for warming the programs up: vertices of degree
        0 where the graph has any, so that a warm-up call runs the same
        compiled program with next to no work, else search keys."""
        pool = self.isolated()
        if not len(pool):
            pool = self.sources()
        return np.resize(pool, k).astype(np.int32)

    def csr(self):
        """The reference's own CSR (row offsets, columns, weights or
        None): symmetrized, self-loops and duplicates removed, columns
        sorted. Built from the edge list alone."""
        if self._csr is None:
            s = np.concatenate([self.src, self.dst])
            d = np.concatenate([self.dst, self.src])
            keep = s != d
            key = np.unique(s[keep] * self.n + d[keep])
            s, d = key // self.n, key % self.n
            ro = np.zeros(self.n + 1, np.int64)
            np.cumsum(np.bincount(s, minlength=self.n), out=ro[1:])
            w = None
            if self.weights is not None:
                w = self.weight_of(s, d)
            self._csr = (ro, d.astype(np.int64), w)
        return self._csr

    def weight_of(self, u, v) -> np.ndarray:
        """The weights of the edges ``{u, v}`` (this seed's ids): the
        dataset's, hashed from its own ids."""
        if self.perm is not None:
            inv = np.argsort(self.perm)
            u, v = inv[u], inv[v]
        lo, hi = self.cfg["weights"]
        return pair_weights(u, v, self.n, self.cfg["graph_seed"], lo, hi)

    @property
    def stored_edges(self) -> int:
        return int(self.csr()[0][-1])


def make(cfg: dict, seed: Optional[int], root: str = HERE) -> GraphData:
    """The configuration's graph relabelled for ``seed``; ``seed=None``
    gives the dataset's own ids."""
    gen = load_generator(cfg["generator"], root)
    src, dst, n = gen.edges(cfg, rng_for(cfg["graph_seed"], 0))
    w = None
    if cfg.get("weights"):
        lo, hi = cfg["weights"]
        w = pair_weights(src, dst, n, cfg["graph_seed"], lo, hi)
    perm = None
    if seed is not None:
        perm = rng_for(seed, 0).permutation(n)
        src, dst = perm[src], perm[dst]
    seed = 0 if seed is None else int(seed)
    return GraphData(cfg=cfg, seed=seed, n=int(n), src=src, dst=dst,
                     weights=w, perm=perm)


def search_keys(cfg: dict, k: int, root: str = HERE) -> np.ndarray:
    """``k`` search keys of the dataset (vertices of degree >= 1, drawn
    with the dataset's seed, as Graph500 draws its 64), ordered by their
    BFS eccentricity, deepest first. Dealing them round-robin into
    batches gives every batch the same spread of depths, so that any
    run of batches does the same work. The configuration file stores
    the result (``search_keys``); a test recomputes it."""
    from . import reference as R
    gd = make(cfg, None, root)
    keys = rng_for(cfg["graph_seed"], 1).choice(gd.sources(), k,
                                                replace=False)
    csr = gd.csr()
    ecc = np.array([int(R.bfs(csr, int(v)).max()) for v in keys])
    return keys[np.lexsort((keys, -ecc))]
