"""The batched 2-D BFS: a (B,) batch of sources in one distributed
program over the R×C vertex cut. Depths and per-lane iterations against
the plain reference and the single-device ``bfs_batch`` (subprocess
with four fake host devices, like tests/test_sharded.py); the slot-row
form against the ``searchsorted`` form it replaced; the exchange model's
batch axis."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 4, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(REPO, "src"), REPO])
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_batched_2d_bfs_matches_reference_and_bfs_batch():
    """2×2, 1×4 and 4×1 meshes; n divisible by no mesh axis; a batch with
    duplicate sources and an isolated one. The runner serves a bfs flush
    as one program, and graph_serve's exchange gauge counts the batch."""
    out = run_sub("""
        import re, tempfile, os
        import numpy as np, jax
        from jax.sharding import Mesh
        from bench import reference
        from repro.core import distributed as D, graph as G
        from repro.core.partition import partition_2d
        from repro.launch.graph_run import make_graph
        from repro.core.primitives import bfs_batch
        from repro.launch import graph_serve

        base = G.rmat(7, 8, seed=3)
        se, de = G.edge_list(base)
        n = base.num_vertices * 2 + 7          # odd: no axis divides it
        g = G.from_edge_list(se, de, n=n)      # [128, n) isolated
        ro = np.asarray(g.row_offsets)
        csr = (ro, g.cols_np(), None)
        hub = int(np.argmax(np.diff(ro)))
        srcs = np.array([hub, 3, hub, n - 1, 17, 5, 3, 100], np.int32)
        want = bfs_batch(g, srcs)
        want_l = np.asarray(want.labels)
        for i, s in enumerate(srcs):
            assert np.array_equal(want_l[i], reference.bfs(csr, int(s)))
        for shape in ((2, 2), (1, 4), (4, 1)):
            pg = partition_2d(g, *shape)
            # padded chunks on every split axis
            assert all(k * v > n for k, v in zip(shape, (pg.vpr, pg.vpc))
                       if k > 1)
            mesh = Mesh(np.array(jax.devices()[:4]).reshape(shape),
                        ("row", "col"))
            r = D.distributed_bfs(pg, srcs, mesh, ("row", "col"))
            assert r.labels.shape == (8, n), shape
            assert np.array_equal(np.asarray(r.labels), want_l), shape
            assert np.array_equal(np.asarray(r.iterations),
                                  np.asarray(want.iterations)), shape
            one = D.distributed_bfs(pg, hub, mesh, ("row", "col"))
            assert one.labels.shape == (n,)
            assert np.array_equal(np.asarray(one.labels), want_l[0])
            assert int(one.iterations) == int(want.iterations[0])
        assert int(want.iterations[3]) == 1     # the isolated source

        # one device program per bfs flush
        pg = partition_2d(g, 2, 2)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("row", "col"))
        calls = []
        impl = D._bfs_2d_impl

        def counted(*a, **kw):
            calls.append(a[-1].shape)
            return impl(*a, **kw)

        D._bfs_2d_impl = counted
        run = graph_serve.make_sharded_runner(pg, mesh, ("row", "col"))
        field, _ = run("bfs", srcs, "xla", 3)
        D._bfs_2d_impl = impl
        assert calls == [(8,)], calls
        assert np.array_equal(np.asarray(field), want_l)

        path = os.path.join(tempfile.mkdtemp(), "m.prom")
        graph_serve.main(["--graph", "rmat", "--scale", "7", "--kinds",
                          "bfs", "--requests", "8", "--batch", "4",
                          "--mesh", "2x2", "--validate", "--metrics",
                          path])
        text = open(path).read()
        got = re.search(r'^graph_serve_exchange_bytes_per_step'
                        r'\\{kind="bfs"\\} (\\S+)$', text, re.M)
        # the graph graph_serve built (its --edge-factor and --seed
        # defaults): chunk widths follow its degrees
        pg7 = partition_2d(make_graph("rmat", 7, 16, 0), 2, 2)
        assert float(got.group(1)) == D.exchange_bytes_per_step(
            pg7, "bfs", batch=4), got
        print("BFS_2D_BATCH_OK")
    """)
    assert "BFS_2D_BATCH_OK" in out


@pytest.mark.parametrize("case", ["ragged", "empty_rows", "one_row",
                                  "no_edges"])
def test_slot_rows_equal_the_searchsorted_form(case):
    from repro.core.distributed import _slot_rows
    rng = np.random.default_rng(7)
    deg = {"ragged": rng.integers(0, 9, 40),
           "empty_rows": np.r_[0, 0, 5, 0, 0, 3, 0, 1, 0, 0],
           "one_row": np.array([6]),
           "no_edges": np.zeros(5, np.int64)}[case]
    ro = np.r_[0, np.cumsum(deg)].astype(np.int32)
    rows = len(deg)
    slots = int(ro[-1]) + 5                    # pad slots past the end
    slot = np.arange(slots)
    want = np.clip(np.searchsorted(ro, slot, side="right") - 1, 0,
                   rows - 1)
    got = np.asarray(_slot_rows(jnp.asarray(ro), slots, rows))
    assert np.array_equal(got, want)


def test_exchange_bytes_count_the_batch():
    from repro.core import graph as G
    from repro.core.distributed import exchange_bytes_per_step
    from repro.core.partition import partition_1d, partition_2d
    g = G.rmat(8, 8, seed=1, weighted=True)
    n = g.num_vertices
    pg2, pg1 = partition_2d(g, 2, 2), partition_1d(g, 4)
    vpc = pg2.vpc
    # 2-D bfs: per lane, tiles row psums of the uint8 chunk
    # (2·(R−1)/R·vpc each) and the column gather ((C−1)·vpc)
    assert exchange_bytes_per_step(pg2, "bfs", tiles=2, batch=8) == \
        8 * (2 * vpc + vpc)
    assert exchange_bytes_per_step(pg2, "sssp", batch=8) == \
        8 * (vpc + vpc) * 4
    assert exchange_bytes_per_step(pg1, "bfs", batch=8) == 8 * 6 * n
    for pg in (pg2, pg1):
        for kind in ("bfs", "sssp"):
            assert exchange_bytes_per_step(pg, kind, batch=8) == \
                8 * exchange_bytes_per_step(pg, kind)
        for kind in ("cc", "pagerank"):
            assert exchange_bytes_per_step(pg, kind, batch=8) == \
                exchange_bytes_per_step(pg, kind)


@pytest.mark.parametrize("case", ["ragged", "star", "no_edges",
                                  "more_parts_than_edges"])
def test_edge_balanced_bounds_split_the_degree_sum(case):
    from repro.core.partition import _edge_balanced_bounds
    rng = np.random.default_rng(5)
    deg, parts = {"ragged": (rng.integers(0, 50, 101), 4),
                  "star": (np.r_[99, np.ones(99, np.int64)], 2),
                  "no_edges": (np.zeros(9, np.int64), 4),
                  "more_parts_than_edges": (np.r_[0, 3, 0, 0, 0], 4)}[case]
    b = _edge_balanced_bounds(deg, parts)
    assert len(b) == parts + 1 and b[0] == 0 and b[-1] == len(deg)
    assert np.all(np.diff(b) >= 0)
    cum = np.r_[0, np.cumsum(deg)]
    share = cum[-1] / parts
    if cum[-1] == 0:
        assert np.array_equal(b, np.minimum(np.arange(parts + 1)
                                            * -(-len(deg) // parts),
                                            len(deg)))
    # each cut is the vertex boundary nearest its share of the edges
    for k in range(1, parts):
        assert abs(cum[b[k]] - k * share) == \
            np.abs(cum - k * share).min()


def test_2d_blocks_balance_edges_whatever_the_hubs_ids():
    """R-MAT keeps its hubs at the lowest ids: the uniform cut puts 26%
    more edges than the mean in one block; the degree-balanced cut gives
    each block about a quarter, with chunks of unequal width (so the 2-D parity
    tests run the unpadding of unequal chunks)."""
    from repro.core import graph as G
    from repro.core.partition import partition_2d
    g = G.rmat(10, 8, seed=2)
    n = g.num_vertices
    ro, ci = np.asarray(g.row_offsets), g.cols_np()
    src = np.repeat(np.arange(n), np.diff(ro))
    half = -(-n // 2)
    uniform = np.bincount((src >= half) * 2 + (ci >= half), minlength=4)
    pg = partition_2d(g, 2, 2)
    assert uniform.max() / uniform.mean() > 1.2
    assert pg.block_edges.max() / pg.block_edges.mean() < 1.03
    assert pg.block_edges.sum() == g.num_edges
    assert sum(pg.row_sizes) == n and sum(pg.col_sizes) == n
    assert pg.row_sizes[0] != pg.row_sizes[1]
    assert pg.vpr == max(pg.row_sizes) and pg.vpc == max(pg.col_sizes)
    # the designated owner's chunks hold the vertex
    v = np.arange(n)
    r, c = pg.owner_of(v)
    assert np.all((pg.row_base[r] <= v)
                  & (v < pg.row_base[r] + np.take(pg.row_sizes, r)))
    assert np.all((pg.col_base[c] <= v)
                  & (v < pg.col_base[c] + np.take(pg.col_sizes, c)))
