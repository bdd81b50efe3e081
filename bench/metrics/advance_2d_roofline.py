"""Share (%) of the bandwidth roofline that the 2-D BFS program reached
in the traced window: the least time its traversals need (the bytes of
``bench/bfs_costs.py`` for every traversal completed in the window, from
the reference's own graph, over the peak HBM bandwidth of all the
cell's chips) over the mean per-chip device self time under the
program's ``op.*`` scopes."""
from bench.bfs_costs import bfs_least_bytes, component_vertices
from bench.scopes import scopes


def read(run):
    sc = scopes(run)
    if sc is None or not sc.scoped or not run.peaks:
        return None
    busy_s = sc.ms(root="op") * 1e-3
    srcs = [int(s) for it in run.items for s in it.get("sources", ())]
    if not busy_s or not srcs:
        return None
    verts = component_vertices(run.graph.csr())
    chips = int(run.spec.cell.get("chips", 1))
    least_s = bfs_least_bytes(verts[srcs]) / (
        chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / busy_s
