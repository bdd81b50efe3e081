"""Sharded-placement behavior: registry semantics (in-process) and
bit-parity of sharded vs single-device primitives (subprocess with fake
host-platform devices, like tests/test_distributed.py)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


# ---------------------------------------------------------------------------
# placement as a registry dimension (no devices needed)
# ---------------------------------------------------------------------------


def test_placement_resolution_precedence(monkeypatch):
    from repro.core import backend as B
    assert B.resolve_placement() == B.SINGLE
    monkeypatch.setenv(B.PLACEMENT_ENV_VAR, B.SHARDED)
    assert B.resolve_placement() == B.SHARDED
    with B.use_placement(B.SINGLE):
        assert B.resolve_placement() == B.SINGLE          # context > env
        assert B.resolve_placement(B.SHARDED) == B.SHARDED  # call > ctx
    monkeypatch.delenv(B.PLACEMENT_ENV_VAR)
    with pytest.raises(ValueError):
        B.resolve_placement("mesh")


def test_placement_context_carries_mesh():
    from repro.core import backend as B
    assert B.placement_mesh() is None
    sentinel = object()
    with B.use_placement(B.SHARDED, mesh=sentinel, axis="g"):
        assert B.placement_mesh() == (sentinel, "g")
        with B.use_placement(B.SINGLE):      # inner ctx without a mesh
            assert B.placement_mesh() == (sentinel, "g")
    assert B.placement_mesh() is None


def test_sharded_providers_registered():
    from repro.core import backend as B
    for op in ("advance", "spmv", "spmm", "mxm"):
        assert B.registered(op, B.XLA, B.SHARDED), op
    # single-placement registrations are untouched by the new dimension
    for op in ("spmv", "spmm", "mxm"):
        assert B.registered(op, B.XLA), op
        assert B.registered(op, B.PALLAS), op


def test_sharded_dispatch_never_falls_back_to_single():
    from repro.core import backend as B
    # "compact" has single-placement impls only: sharded dispatch must
    # raise, not silently run the single-device path
    with pytest.raises(KeyError):
        B.dispatch("compact", B.XLA, B.SHARDED)
    # pallas backend falls back across BACKENDS to the xla sharded
    # provider (kernels under shard_map are future work)
    assert B.dispatch("spmv", B.PALLAS, B.SHARDED) \
        is B.dispatch("spmv", B.XLA, B.SHARDED)


def test_plain_graph_under_sharded_placement_is_an_error():
    from repro.core import backend as B
    from repro.core import graph as G
    with pytest.raises(ValueError, match="ShardedGraph"):
        B.resolve_graph_placement(G.demo_graph(), B.SHARDED)


# ---------------------------------------------------------------------------
# bit-parity: sharded vs single device
# ---------------------------------------------------------------------------


def test_sharded_parity_all_primitives():
    """bfs/sssp/cc/pagerank/label_propagation/reach at 2/4/8-way
    partitions bit-match the single-device primitives. The graph has a
    non-divisible vertex count (padded tail part) and an isolated tail
    (parts whose local frontier stays empty every iteration)."""
    out = run_sub("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import graph as G
        from repro.core.partition import partition_1d
        from repro.core.distributed import (
            distributed_bfs, distributed_sssp, distributed_cc,
            distributed_pagerank, distributed_label_propagation,
            distributed_reach)
        from repro.core.primitives import (
            bfs, sssp, connected_components, pagerank,
            label_propagation, reach_batch)

        base = G.rmat(7, 8, seed=3, weighted=True)
        se, de = G.edge_list(base)
        vals = np.asarray(base.edge_values)
        # non-divisible n: 2*128 + 7; vertices [128, 263) are isolated,
        # so tail parts own only empty frontiers
        n2 = base.num_vertices * 2 + 7
        g = G.from_edge_list(se, de, n=n2, values=vals)
        deg = np.diff(np.asarray(g.row_offsets))
        src = int(np.argmax(deg))
        r1 = bfs(g, src); s1 = sssp(g, src)
        c1 = connected_components(g)
        p1 = pagerank(g, max_iter=12)
        l1 = label_propagation(g, max_iter=8)
        srcs = [0, 5, 17]
        rr1 = reach_batch(g, srcs, 3)
        for p in (2, 4, 8):
            pg = partition_1d(g, p)
            assert p * pg.verts_per_part > g.num_vertices  # padded tail
            mesh = Mesh(np.array(jax.devices()[:p]), ("graph",))
            rd = distributed_bfs(pg, src, mesh)
            assert np.array_equal(np.asarray(rd.labels),
                                  np.asarray(r1.labels)), ("bfs", p)
            # the empty-frontier parts really are empty: the isolated
            # tail is unreachable
            assert np.asarray(r1.labels)[base.num_vertices:].max() < 0
            sd = distributed_sssp(pg, src, mesh)
            assert np.array_equal(np.asarray(sd.dist),
                                  np.asarray(s1.dist)), ("sssp", p)
            cd = distributed_cc(pg, mesh)
            assert np.array_equal(np.asarray(cd.labels),
                                  np.asarray(c1.labels)), ("cc", p)
            assert int(cd.num_components) == int(c1.num_components)
            pd = distributed_pagerank(pg, mesh, iters=12)
            assert np.array_equal(np.asarray(pd),
                                  np.asarray(p1.rank)), ("pagerank", p)
            ld = distributed_label_propagation(pg, mesh, max_iter=8)
            assert np.array_equal(np.asarray(ld.labels),
                                  np.asarray(l1.labels)), ("lp", p)
            xd = distributed_reach(pg, srcs, 3, mesh=mesh)
            assert np.array_equal(np.asarray(xd.reached),
                                  np.asarray(rr1.reached)), ("reach", p)
        print("SHARDED_PARITY_OK")
    """)
    assert "SHARDED_PARITY_OK" in out


def test_sharded_storage_plan_parity():
    """PR 6: a source graph built under any storage plan (narrow ids,
    delta columns) shards into the canonical dense-int32 per-shard
    layout, and distributed bfs/sssp/pagerank bit-match the
    single-device run of the int64-under-x64 widest baseline at 2- and
    4-way partitions."""
    out = run_sub("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import graph as G
        from repro.core.distributed import (
            distributed_bfs, distributed_pagerank, distributed_sssp)
        from repro.core.partition import partition_1d
        from repro.core.primitives import bfs, pagerank, sssp

        with jax.enable_x64(True):
            g64 = G.rmat(7, 8, seed=5, weighted=True, index_dtype="int64")
            src = int(np.argmax(np.diff(np.asarray(g64.row_offsets))))
            labels = np.asarray(bfs(g64, src).labels)
            dist = np.asarray(sssp(g64, src).dist)
            rank = np.asarray(pagerank(g64, max_iter=12).rank)
        for kw in ({"index_dtype": "int32"}, {"encoding": "delta"}):
            g = G.rmat(7, 8, seed=5, weighted=True, **kw)
            for p in (2, 4):
                pg = partition_1d(g, p)
                mesh = Mesh(np.array(jax.devices()[:p]), ("graph",))
                rd = distributed_bfs(pg, src, mesh)
                assert np.array_equal(np.asarray(rd.labels), labels), \\
                    ("bfs", kw, p)
                sd = distributed_sssp(pg, src, mesh)
                assert np.array_equal(np.asarray(sd.dist), dist), \\
                    ("sssp", kw, p)
                pd = distributed_pagerank(pg, mesh, iters=12)
                assert np.array_equal(np.asarray(pd), rank), \\
                    ("pagerank", kw, p)
        print("SHARDED_STORAGE_OK")
    """, devices=4)
    assert "SHARDED_STORAGE_OK" in out


def test_sharded_linalg_ops_parity():
    """The public linalg wrappers route a ShardedGraph through the
    sharded providers: masked spmv/spmm across all five semirings and a
    masked SpGEMM (sharded expansion side, replicated probe side) all
    bit-match the single-device results."""
    out = run_sub("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import graph as G
        from repro.core.partition import partition_1d
        from repro import linalg

        g = G.rmat(7, 8, seed=2, weighted=True)
        n = g.num_vertices
        pg = partition_1d(g, 4)
        mesh = Mesh(np.array(jax.devices()[:4]), ("graph",))
        sg = pg.shard(mesh)
        rng = np.random.default_rng(0)
        x = rng.random(n).astype(np.float32)
        X = rng.random((n, 5)).astype(np.float32)
        mask = rng.random(n) > 0.4
        for srn in ("plus_times", "min_plus", "or_and", "max_min",
                    "plus_and"):
            y1 = linalg.spmv(g, x, semiring=srn, mask=mask)
            y2 = linalg.spmv(sg, x, semiring=srn, mask=mask)
            assert np.array_equal(np.asarray(y1), np.asarray(y2)), srn
            z1 = linalg.spmm(g, X, semiring=srn, mask=mask,
                             complement=True)
            z2 = linalg.spmm(sg, X, semiring=srn, mask=mask,
                             complement=True)
            assert np.array_equal(np.asarray(z1), np.asarray(z2)), srn
        t1 = linalg.spmv(g, x, transpose=True)
        t2 = linalg.spmv(sg, x, transpose=True)
        assert np.array_equal(np.asarray(t1), np.asarray(t2))
        se, de = G.edge_list(g)
        c1 = linalg.mxm(g, g, (se, de), semiring=linalg.plus_and,
                        b_transpose=True, structural=True)
        c2 = linalg.mxm(sg, g, (se, de), semiring=linalg.plus_and,
                        b_transpose=True, structural=True)
        assert np.array_equal(np.asarray(c1), np.asarray(c2))
        print("SHARDED_LINALG_OK")
    """, devices=4)
    assert "SHARDED_LINALG_OK" in out


# ---------------------------------------------------------------------------
# 2-D vertex-cut placement (placement="2d")
# ---------------------------------------------------------------------------


def test_2d_placement_registry():
    from repro.core import backend as B
    from repro.core import graph as G
    assert B.TWOD in B.PLACEMENTS
    assert B.resolve_placement("2d") == B.TWOD
    for op in ("advance", "advance_filter", "spmv", "spmm", "mxm"):
        assert B.registered(op, B.XLA, B.TWOD), op
    # 2d dispatch never falls back to the single placement …
    with pytest.raises(KeyError):
        B.dispatch("compact", B.XLA, B.TWOD)
    # … but the pallas backend falls back to the xla 2d provider
    assert B.dispatch("spmv", B.PALLAS, B.TWOD) \
        is B.dispatch("spmv", B.XLA, B.TWOD)
    with pytest.raises(ValueError, match="Sharded2DGraph"):
        B.resolve_graph_placement(G.demo_graph(), B.TWOD)


def test_2d_balance_reports_edge_and_vertex_imbalance():
    """Satellite: balance() surfaces edge-balance (the stat hub skew
    shows up in) next to vertex-balance on BOTH partition containers,
    and the 2-D container adds the vertex-cut mirror stats."""
    from repro.core import graph as G
    from repro.core.partition import partition_1d, partition_2d
    g = G.rmat(7, 8, seed=3)
    b1 = partition_1d(g, 4).balance()
    assert b1["edge_imbalance"] >= 1.0
    assert b1["vertex_imbalance"] >= 1.0
    assert len(b1["edges_per_part"]) == 4
    pg = partition_2d(g, 2, 2)
    b2 = pg.balance()
    assert b2["mesh"] == [2, 2]
    assert b2["edge_imbalance"] >= 1.0
    assert b2["vertex_imbalance"] >= 1.0
    assert np.sum(b2["edges_per_block"]) == g.num_edges
    # every vertex has at least its owner copy; mirrors only add
    assert b2["mirror_factor"] >= 1.0
    # comm model: the 2-D bfs exchange is chunk-proportional and beats
    # the 1-D n-proportional exchange at equal device count
    from repro.core.distributed import exchange_bytes_per_step
    assert exchange_bytes_per_step(pg, "bfs") \
        < exchange_bytes_per_step(partition_1d(g, 4), "bfs")


def test_2d_parity_all_primitives():
    """bfs/sssp/cc/pagerank/label_propagation/reach on 2×2 and 2×4
    meshes bit-match the single-device primitives. n is non-divisible
    on BOTH axes (263 = 2·132−1 rows, 4·66−1 cols) and the isolated
    tail gives whole blocks whose frontier stays empty every
    iteration."""
    out = run_sub("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import graph as G
        from repro.core.partition import partition_2d
        from repro.core.distributed import (
            distributed_bfs, distributed_sssp, distributed_cc,
            distributed_pagerank, distributed_label_propagation,
            distributed_reach)
        from repro.core.primitives import (
            bfs, sssp, connected_components, pagerank,
            label_propagation, reach_batch)

        base = G.rmat(7, 8, seed=3, weighted=True)
        se, de = G.edge_list(base)
        vals = np.asarray(base.edge_values)
        n2 = base.num_vertices * 2 + 7
        g = G.from_edge_list(se, de, n=n2, values=vals)
        deg = np.diff(np.asarray(g.row_offsets))
        src = int(np.argmax(deg))
        r1 = bfs(g, src); s1 = sssp(g, src)
        c1 = connected_components(g)
        p1 = pagerank(g, max_iter=12)
        l1 = label_propagation(g, max_iter=8)
        srcs = [0, 5, 17]
        rr1 = reach_batch(g, srcs, 3)
        for (R, C) in ((2, 2), (2, 4)):
            pg = partition_2d(g, R, C)
            # both axes genuinely padded (non-divisible n)
            assert R * pg.vpr > g.num_vertices
            assert C * pg.vpc > g.num_vertices
            mesh = Mesh(np.array(jax.devices()[:R * C]).reshape(R, C),
                        ("row", "col"))
            rd = distributed_bfs(pg, src, mesh)
            assert np.array_equal(np.asarray(rd.labels),
                                  np.asarray(r1.labels)), ("bfs", R, C)
            # the empty-frontier blocks really are empty: the isolated
            # tail is unreachable
            assert np.asarray(r1.labels)[base.num_vertices:].max() < 0
            sd = distributed_sssp(pg, src, mesh)
            assert np.array_equal(np.asarray(sd.dist),
                                  np.asarray(s1.dist)), ("sssp", R, C)
            cd = distributed_cc(pg, mesh)
            assert np.array_equal(np.asarray(cd.labels),
                                  np.asarray(c1.labels)), ("cc", R, C)
            assert int(cd.num_components) == int(c1.num_components)
            pd = distributed_pagerank(pg, mesh, iters=12)
            assert np.array_equal(np.asarray(pd),
                                  np.asarray(p1.rank)), ("pr", R, C)
            ld = distributed_label_propagation(pg, mesh, max_iter=8)
            assert np.array_equal(np.asarray(ld.labels),
                                  np.asarray(l1.labels)), ("lp", R, C)
            xd = distributed_reach(pg, srcs, 3, mesh=mesh)
            assert np.array_equal(np.asarray(xd.reached),
                                  np.asarray(rr1.reached)), ("rc", R, C)
        print("2D_PARITY_OK")
    """)
    assert "2D_PARITY_OK" in out


def test_2d_degenerate_meshes_match_1d_and_single():
    """1×C and R×1 meshes are honest members of the placement axis:
    they bit-match BOTH the existing 1-D sharded path and the
    single-device primitives (same graph, same sources)."""
    out = run_sub("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import graph as G
        from repro.core.partition import partition_1d, partition_2d
        from repro.core.distributed import (
            distributed_bfs, distributed_sssp, distributed_pagerank)
        from repro.core.primitives import bfs, sssp, pagerank

        base = G.rmat(7, 8, seed=3, weighted=True)
        se, de = G.edge_list(base)
        n2 = base.num_vertices * 2 + 7
        g = G.from_edge_list(se, de, n=n2,
                             values=np.asarray(base.edge_values))
        src = int(np.argmax(np.diff(np.asarray(g.row_offsets))))
        labels = np.asarray(bfs(g, src).labels)
        dist = np.asarray(sssp(g, src).dist)
        rank = np.asarray(pagerank(g, max_iter=12).rank)
        pg1 = partition_1d(g, 4)
        mesh1 = Mesh(np.array(jax.devices()[:4]), ("graph",))
        l1 = np.asarray(distributed_bfs(pg1, src, mesh1).labels)
        d1 = np.asarray(distributed_sssp(pg1, src, mesh1).dist)
        r1 = np.asarray(distributed_pagerank(pg1, mesh1, iters=12))
        assert np.array_equal(l1, labels) and np.array_equal(d1, dist)
        assert np.array_equal(r1, rank)
        for (R, C) in ((1, 4), (4, 1)):
            pg = partition_2d(g, R, C)
            mesh = Mesh(np.array(jax.devices()[:4]).reshape(R, C),
                        ("row", "col"))
            l2 = np.asarray(distributed_bfs(pg, src, mesh).labels)
            d2 = np.asarray(distributed_sssp(pg, src, mesh).dist)
            r2 = np.asarray(distributed_pagerank(pg, mesh, iters=12))
            assert np.array_equal(l2, l1) and np.array_equal(l2, labels)
            assert np.array_equal(d2, d1) and np.array_equal(d2, dist)
            assert np.array_equal(r2, r1) and np.array_equal(r2, rank)
        print("2D_DEGENERATE_OK")
    """, devices=4)
    assert "2D_DEGENERATE_OK" in out


def test_2d_linalg_ops_parity():
    """The public linalg wrappers route a Sharded2DGraph through the 2d
    providers: masked spmv/spmm across all five semirings (the pre-fold
    product exchange is exact for every ⊕) and a plus_and masked SpGEMM
    all bit-match the single-device results."""
    out = run_sub("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import graph as G
        from repro.core.partition import partition_2d
        from repro import linalg

        g = G.rmat(7, 8, seed=2, weighted=True)
        n = g.num_vertices
        pg = partition_2d(g, 2, 4)
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ("row", "col"))
        sg = pg.shard(mesh)
        rng = np.random.default_rng(0)
        x = rng.random(n).astype(np.float32)
        X = rng.random((n, 5)).astype(np.float32)
        mask = rng.random(n) > 0.4
        for srn in ("plus_times", "min_plus", "or_and", "max_min",
                    "plus_and"):
            y1 = linalg.spmv(g, x, semiring=srn, mask=mask)
            y2 = linalg.spmv(sg, x, semiring=srn, mask=mask)
            assert np.array_equal(np.asarray(y1), np.asarray(y2)), srn
            z1 = linalg.spmm(g, X, semiring=srn, mask=mask,
                             complement=True)
            z2 = linalg.spmm(sg, X, semiring=srn, mask=mask,
                             complement=True)
            assert np.array_equal(np.asarray(z1), np.asarray(z2)), srn
        t1 = linalg.spmv(g, x, transpose=True)
        t2 = linalg.spmv(sg, x, transpose=True)
        assert np.array_equal(np.asarray(t1), np.asarray(t2))
        se, de = G.edge_list(g)
        c1 = linalg.mxm(g, g, (se, de), semiring=linalg.plus_and,
                        b_transpose=True, structural=True)
        c2 = linalg.mxm(sg, g, (se, de), semiring=linalg.plus_and,
                        b_transpose=True, structural=True)
        assert np.array_equal(np.asarray(c1), np.asarray(c2))
        print("2D_LINALG_OK")
    """)
    assert "2D_LINALG_OK" in out


def test_graph_serve_2d_mesh_smoke():
    """graph_serve --mesh RxC serves the mixed stream from the 2-D
    vertex cut with oracle validation, reports the mesh shape and the
    vertex-cut balance stats, and rejects bad mesh specs with clear
    errors."""
    out = run_sub("""
        import json, numpy as np
        from repro.launch.graph_serve import main
        main(["--graph", "rmat", "--scale", "7", "--kinds",
              "bfs,sssp,pagerank,reach", "--requests", "8", "--batch",
              "4", "--mesh", "2x4", "--validate", "--json",
              "/tmp/_serve_mesh_test.json"])
        row = json.load(open("/tmp/_serve_mesh_test.json"))[-1]
        assert row["parts"] == 8
        assert row["mesh"] == [2, 4]
        assert row["validation_failures"] == 0
        bal = row["balance"]
        assert bal["mesh"] == [2, 4]
        assert bal["edge_imbalance"] >= 1.0
        assert bal["vertex_imbalance"] >= 1.0
        assert bal["mirror_factor"] >= 1.0
        for argv, frag in (
                (["--mesh", "4x4"], "devices"),        # R*C > visible
                (["--mesh", "2x"], "RxC"),             # malformed
                (["--mesh", "2x2", "--parts", "4"],
                 "mutually exclusive")):
            try:
                main(["--graph", "rmat", "--scale", "7", "--requests",
                      "4", "--batch", "4"] + argv)
            except SystemExit as e:
                assert frag in str(e), (argv, e)
            else:
                raise AssertionError(f"no error for {argv}")
        print("SERVE_2D_OK")
    """)
    assert "SERVE_2D_OK" in out


def test_graph_serve_sharded_smoke():
    """graph_serve --parts serves a mixed stream from the mesh with
    oracle validation and reports partition balance."""
    out = run_sub("""
        import json, numpy as np
        from repro.launch.graph_serve import main
        main(["--graph", "rmat", "--scale", "7", "--kinds",
              "bfs,sssp,pagerank,reach", "--requests", "8", "--batch",
              "4", "--parts", "4", "--validate", "--json",
              "/tmp/_serve_parts_test.json"])
        row = json.load(open("/tmp/_serve_parts_test.json"))[-1]
        assert row["parts"] == 4
        assert row["validation_failures"] == 0
        bal = row["balance"]
        assert len(bal["edges_per_part"]) == 4
        assert sum(bal["vertices_per_part"]) == 128
        print("SERVE_PARTS_OK")
    """, devices=4)
    assert "SERVE_PARTS_OK" in out
