"""Bandwidth-proportional storage layer (PR 6).

Contracts under test:
  * plan ladder: narrowest safe index dtype from n, explicit overrides
    validated (never silently narrowed), bad knobs rejected;
  * delta encoding: exact round trip through decode_cols / gather_cols,
    including the uint16 escape side-list on rows spanning > 0xFFFE ids;
  * x64 drift regression: Graph build under jax_enable_x64 pins every
    structural array to the plan dtype, and int64 plans refuse to build
    without the switch;
  * end-to-end parity: bfs / sssp / pagerank are BIT-identical across
    {int16, int32, delta} storage on both backends (exact semirings
    decode exactly);
  * mixed precision: bf16 PageRank within the documented tolerance,
    bf16 rejected for non-plus-accumulating semirings;
  * resident_bytes accounting matches the arrays it describes.
"""
import jax
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import storage as S
from repro.core.primitives import bfs, pagerank, sssp
from repro.linalg import semiring as SR

BACKENDS = ["xla", "pallas"]
STORAGE_KW = {
    "int16": {},                          # auto ladder picks int16 at n=2^9
    "int32": {"index_dtype": "int32"},
    "delta": {"encoding": "delta"},
}


@pytest.fixture(scope="module")
def storage_graphs():
    """The same scale-9 weighted rmat under every storage plan (one
    topology, three layouts — the parity matrix's fixtures)."""
    return {tag: G.rmat(9, 8, seed=7, weighted=True, **kw)
            for tag, kw in STORAGE_KW.items()}


# ---------------------------------------------------------------------------
# plan ladder
# ---------------------------------------------------------------------------


def test_plan_ladder_picks_narrowest():
    assert S.plan_for(100).index_dtype == "int16"
    assert S.plan_for(2**15).index_dtype == "int16"      # max id 32767
    assert S.plan_for(2**15 + 1).index_dtype == "int32"
    assert S.plan_for(2**31).index_dtype == "int32"
    assert S.plan_for(2**31 + 1).index_dtype == "int64"
    assert S.plan_for(0).index_dtype == "int16"


def test_plan_override_widens_never_narrows():
    assert S.plan_for(100, index_dtype="int64").index_dtype == "int64"
    with pytest.raises(ValueError, match="cannot hold"):
        S.plan_for(10**6, index_dtype="int16")
    with pytest.raises(ValueError):
        S.plan_for(100, index_dtype="int8")
    with pytest.raises(ValueError):
        S.plan_for(100, encoding="rle")
    with pytest.raises(ValueError):
        S.plan_for(100, value_dtype="fp16")


def test_plan_is_static_aux(storage_graphs):
    """The plan rides pytree aux data: hashable, equal across leaves-only
    transforms, and part of the jit cache key."""
    g = storage_graphs["delta"]
    assert g.plan == S.StoragePlan(index_dtype="int16", encoding="delta")
    leaves, treedef = jax.tree_util.tree_flatten(g)
    assert jax.tree_util.tree_unflatten(treedef, leaves).plan == g.plan
    hash(g.plan)


# ---------------------------------------------------------------------------
# delta encoding round trip
# ---------------------------------------------------------------------------


def test_delta_roundtrip(storage_graphs):
    gd, g32 = storage_graphs["delta"], storage_graphs["int32"]
    st = gd.col_store
    assert isinstance(st, S.EncodedCols)
    assert st.delta.dtype == np.uint16
    dense = np.asarray(g32.col_indices)
    assert np.array_equal(np.asarray(S.decode_cols(st)), dense)
    assert np.array_equal(np.asarray(S.decode_cols(gd.csc_store)),
                          np.asarray(g32.csc_indices))
    # gather at random positions, with and without the src hint
    eid = np.random.default_rng(0).integers(0, gd.num_edges, 64)
    row = np.asarray(gd.row_seg)[eid]
    assert np.array_equal(np.asarray(S.gather_cols(st, eid)), dense[eid])
    assert np.array_equal(np.asarray(S.gather_cols(st, eid, row)),
                          dense[eid])


def test_delta_escape_side_list():
    """One row spanning > 0xFFFE vertex ids forces the escape path: the
    sentinel slot reads its true value from the sorted side list while
    inline slots are untouched."""
    n = 70_000
    src = np.array([0, 0, 0, 1], np.int64)
    dst = np.array([1, 2, n - 1, 2], np.int64)       # 0→(n-1): delta 69998
    g = G.from_edge_list(src, dst, n=n, encoding="delta")
    st = g.col_store
    assert st.num_escapes >= 1
    dense = np.asarray(
        G.from_edge_list(src, dst, n=n).col_indices).astype(np.int64)
    assert np.array_equal(np.asarray(S.decode_cols(st)), dense)
    eid = np.arange(g.num_edges)
    assert np.array_equal(np.asarray(S.gather_cols(st, eid)), dense)
    # traversal through the escape store still reaches the far vertex
    labels = np.asarray(bfs(g, 0, backend="xla").labels)
    assert labels[n - 1] == 1


def test_delta_requires_sorted_rows():
    ro = np.array([0, 2], np.int64)
    cols = np.array([5, 1], np.int64)                # descending row
    with pytest.raises(ValueError, match="sorted"):
        S.encode_delta(ro, cols, np.zeros(2, np.int64))


def test_gather_cols_edgeless_store():
    e = np.zeros(0, np.int64)
    for enc in ("dense", "delta"):
        g = G.from_edge_list(e, e, n=4, encoding=enc)
        out = S.gather_cols(g.col_store, np.zeros(3, np.int32))
        assert out.shape == (3,) and np.all(np.asarray(out) == 0)


# ---------------------------------------------------------------------------
# x64 dtype-drift regression (satellite: graph build under enable_x64)
# ---------------------------------------------------------------------------


def test_x64_build_keeps_plan_dtypes():
    with jax.enable_x64(True):
        g = G.rmat(6, 4, seed=1, weighted=True)
        assert g.plan.index_dtype == "int16"
        assert g.col_indices.dtype == np.int16
        assert g.row_offsets.dtype == np.int32
        assert g.row_seg.dtype == np.int32
        r = bfs(g, 0, backend="xla")
        assert np.asarray(r.labels).dtype == np.int32
    # and the graph built under x64 keeps working outside the context
    r2 = bfs(g, 0, backend="xla")
    assert np.array_equal(np.asarray(r.labels), np.asarray(r2.labels))


def test_int64_plan_requires_x64():
    e = np.zeros(0, np.int64)
    with pytest.raises(RuntimeError, match="jax_enable_x64"):
        G.from_edge_list(e, e, n=4, index_dtype="int64")
    with jax.enable_x64(True):
        g = G.from_edge_list(e, e, n=4, index_dtype="int64")
        assert g.col_indices.dtype == np.int64


# ---------------------------------------------------------------------------
# end-to-end parity: every storage plan, both backends, bit-identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("storage", ["int16", "delta"])
def test_traversal_parity_across_storage(storage_graphs, storage, backend):
    g32 = storage_graphs["int32"]
    g = storage_graphs[storage]
    src = int(np.argmax(np.diff(np.asarray(g32.row_offsets))))
    for name, run in [
        ("bfs", lambda gg: bfs(gg, src, backend=backend).labels),
        ("sssp", lambda gg: sssp(gg, src, backend=backend).dist),
        ("pagerank", lambda gg: pagerank(gg, max_iter=10,
                                         backend=backend).rank),
    ]:
        assert np.array_equal(np.asarray(run(g32)), np.asarray(run(g))), (
            name, storage, backend)


# ---------------------------------------------------------------------------
# mixed precision
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_bf16_pagerank_within_tolerance(storage_graphs, backend):
    g = storage_graphs["delta"]
    full = np.asarray(pagerank(g, max_iter=10, backend=backend).rank)
    half = np.asarray(pagerank(g, max_iter=10, backend=backend,
                               precision="bf16").rank)
    assert half.dtype == np.float32          # fp32 accumulate throughout
    assert float(np.abs(full - half).max()) < 1e-2


def test_bf16_only_for_plus_accumulation():
    sr = SR.with_precision(SR.plus_times, "bf16")
    assert sr.precision == "bf16"
    assert SR.with_precision(sr, "fp32").precision == "fp32"
    with pytest.raises(ValueError, match="plus"):
        SR.with_precision(SR.min_plus, "bf16")
    with pytest.raises(ValueError):
        SR.with_precision(SR.plus_times, "fp8")


def test_bf16_rounds_the_product():
    sr = SR.with_precision(SR.plus_times, "bf16")
    x = np.float32(1.0 + 2.0**-12)           # below bf16 resolution
    assert float(sr.round_prod(x)) == 1.0
    assert float(SR.plus_times.round_prod(x)) == float(x)
    assert float(sr.mul_op(np.float32(3.0), x)) == 3.0


# ---------------------------------------------------------------------------
# resident-byte accounting
# ---------------------------------------------------------------------------


def test_resident_bytes_accounting(storage_graphs):
    rb16 = S.resident_bytes(storage_graphs["int16"])
    rb32 = S.resident_bytes(storage_graphs["int32"])
    rbd = S.resident_bytes(storage_graphs["delta"])
    m = storage_graphs["int32"].num_edges
    # dense column bytes are exactly width × m per direction
    assert rb16["arrays"]["col_storage"] == 2 * m
    assert rb32["arrays"]["col_storage"] == 4 * m
    # delta stream: uint16 per edge + int32 anchor per vertex (+ empty
    # escape lists) per direction — under int32, above bare uint16
    n = storage_graphs["delta"].num_vertices
    assert rbd["arrays"]["col_storage"] == 2 * m + 4 * n
    assert rbd["column_bytes"] < rb32["column_bytes"]
    assert rb16["total_bytes"] == sum(rb16["arrays"].values())
    assert rb16["plan"] == {"index_dtype": "int16", "encoding": "dense",
                            "value_dtype": "fp32"}
    assert rbd["bytes_per_edge"] == round(rbd["column_bytes"] / m, 3)
