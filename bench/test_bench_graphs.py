"""Generators, relabelling, weights and the reference's CSR."""
import math

import numpy as np
import pytest

from bench import graphdata, harness, reference


def _cfg(cell):
    return harness.load_spec(cell).config


def test_g500_s15_edge_counts():
    gd = graphdata.make(_cfg("g500-serve"), seed=0)
    assert gd.n == 32768
    assert len(gd.src) == 16 * 32768          # edge tuples as generated
    assert gd.stored_edges == 882046          # symmetrized, deduplicated
    assert len(gd.sources()) == 24172         # vertices of degree >= 1
    assert gd.weights is not None and gd.weights.dtype == np.float32


def test_g500_s16_edge_counts():
    cfg = _cfg("g500-bfs")
    assert (cfg["scale"], cfg["edge_factor"]) == (16, 16)
    assert cfg["initiator"] == [0.57, 0.19, 0.19, 0.05]
    gd = graphdata.make(cfg, seed=0)
    assert gd.n == 65536 and len(gd.src) == 16 * 65536
    assert gd.stored_edges == 1819076
    assert len(gd.sources()) == 46716
    assert gd.weights is None                 # Kernel 2: BFS only


def test_rgg_n2_16_parameters_and_edge_counts():
    cfg = _cfg("rgg-bfs")
    gen = graphdata.load_generator("rgg")
    assert cfg["n"] == 2 ** 16
    assert gen.radius(cfg) == pytest.approx(
        0.55 * math.sqrt(math.log(2 ** 16) / 2 ** 16))
    gd = graphdata.make(cfg, seed=0)
    assert gd.stored_edges == 688126
    assert 10.0 < gd.stored_edges / gd.n < 11.0   # ~10.5 neighbours
    assert len(gd.isolated()) == 1


def test_seed_relabels_one_dataset():
    cfg = dict(_cfg("g500-serve"), scale=10)
    base = graphdata.make(cfg, None)
    a, b, a2 = (graphdata.make(cfg, s) for s in (5, 6, 5))
    assert np.array_equal(a.src, a2.src) and np.array_equal(a.dst, a2.dst)
    assert not np.array_equal(a.src, b.src)
    da, db = (np.sort(np.diff(x.csr()[0])) for x in (a, b))
    assert np.array_equal(da, db)             # same shape, other ids
    # the same weights on the same edges, under other ids
    for x in (a, b):
        assert np.array_equal(x.weights, base.weights)
        ro, ci, w = x.csr()
        u = np.repeat(np.arange(x.n), np.diff(ro))
        assert np.array_equal(w, x.weight_of(u, ci))
        assert np.array_equal(np.sort(w), np.sort(base.csr()[2]))


def test_pair_weights_are_symmetric_and_in_range():
    u = np.arange(1000)
    v = (u * 7919) % 1000
    w = graphdata.pair_weights(u, v, 1000, 2 ** 31 + 3, 0, 1)
    assert np.array_equal(w, graphdata.pair_weights(v, u, 1000,
                                                    2 ** 31 + 3, 0, 1))
    assert w.min() >= 0 and w.max() < 1 and w.dtype == np.float32
    # real weights, 24 bits each: exact in float32, not integers
    assert np.array_equal(w * 2.0 ** 24, np.floor(w * 2.0 ** 24))
    assert len(np.unique(w)) > 900     # pairs {u, v} repeat in u, v
    assert 0.45 < w.mean() < 0.55
    assert not np.array_equal(w, graphdata.pair_weights(u, v, 1000, 4, 0,
                                                        1))


def test_kronecker_matches_the_programs_rmat():
    from repro.core.graph import rmat
    cfg = dict(_cfg("g500-bfs"), scale=9)
    src, dst, n = graphdata.load_generator("kronecker").edges(
        cfg, np.random.default_rng(3))
    gd = graphdata.GraphData(cfg=cfg, seed=0, n=n, src=src, dst=dst,
                             weights=None)
    g = rmat(9, 16, seed=3)
    ro, ci, _ = gd.csr()
    assert np.array_equal(ro, np.asarray(g.row_offsets))
    assert np.array_equal(ci, g.cols_np())


def test_rgg_matches_the_programs_random_geometric():
    from repro.core.graph import random_geometric
    cfg = dict(_cfg("rgg-bfs"), n=3000)
    gen = graphdata.load_generator("rgg")
    src, dst, n = gen.edges(cfg, np.random.default_rng(4))
    gd = graphdata.GraphData(cfg=cfg, seed=0, n=n, src=src, dst=dst,
                             weights=None)
    g = random_geometric(3000, gen.radius(cfg), seed=4)
    ro, ci, _ = gd.csr()
    assert np.array_equal(ro, np.asarray(g.row_offsets))
    assert np.array_equal(ci, g.cols_np())


def test_reference_agrees_with_scipy():
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import shortest_path
    cfg = dict(_cfg("g500-serve"), scale=8)
    gd = graphdata.make(cfg, 9)
    ro, ci, w = gd.csr()
    n = gd.n
    src = int(gd.sources()[0])
    hops = shortest_path(csr_matrix((np.ones(len(ci)), ci, ro), (n, n)),
                         unweighted=True, indices=src)
    depth = reference.bfs(gd.csr(), src)
    assert np.array_equal(np.where(np.isinf(hops), -1, hops), depth)
    dist = shortest_path(csr_matrix((w.astype(np.float64), ci, ro), (n, n)),
                         indices=src)
    assert np.array_equal(dist, reference.sssp(gd.csr(), src))
    assert np.array_equal(reference.reach(gd.csr(), src, 2),
                          (depth >= 0) & (depth <= 2))
    ce = reference.component_edges(gd.csr())
    assert ce[src] == np.diff(ro)[depth >= 0].sum() / 2


@pytest.mark.parametrize("cell", ["g500-bfs", "rgg-bfs"])
def test_stored_search_keys_are_the_datasets(cell):
    cfg = _cfg(cell)
    keys = graphdata.search_keys(cfg, len(cfg["search_keys"]))
    assert keys.tolist() == cfg["search_keys"]
    gd = graphdata.make(cfg, None)
    assert np.isin(keys, gd.sources()).all()


def test_every_seed_gets_the_same_sources_relabelled():
    cfg = dict(_cfg("g500-bfs"), scale=10)
    base = graphdata.make(cfg, None)
    for seed in (3, 2 ** 31 + 7):
        gd = graphdata.make(cfg, seed)
        assert np.array_equal(np.sort(gd.relabel(base.sources())),
                              gd.sources())
        assert np.array_equal(gd.relabel(base.isolated()).size,
                              gd.isolated().size)
