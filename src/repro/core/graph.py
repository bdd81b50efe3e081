"""Graph containers and generators for the Gunrock-JAX engine.

Gunrock stores graphs in CSR (compressed sparse row) for vertex-centric
operations and optionally COO for edge-centric operations (paper §5.4).
We mirror that: ``Graph`` is a frozen pytree of int32 arrays

    row_offsets : (n+1,)  CSR offsets
    col_indices : (m,)    neighbor vertex IDs
    edge_values : (m,)    optional per-edge weights (float32)

plus an optional CSC mirror (``csc_*``) used by pull-direction traversal
(paper §5.1.4) and reverse advance (BC backward pass).

All shapes are static; n and m are Python ints so a Graph can be closed
over by jitted functions without retracing on content changes.

Storage is planned at build time (core/storage.py): ``from_csr`` /
``from_edge_list`` pick the narrowest safe vertex-id dtype (or honor an
explicit ``index_dtype=``), optionally delta-encode the CSR/CSC columns
(``encoding="delta"``), and pin EVERY structural array to the plan's
dtype — under ``jax_enable_x64`` JAX would otherwise silently widen
index arrays to int64 and double the traversal bandwidth. The chosen
:class:`~repro.core.storage.StoragePlan` rides the pytree aux data, so
storage format is part of every jit cache key, like the mesh of a
ShardedGraph.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import storage as S
from ..obs.tracing import span


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Graph:
    """Static-topology graph in CSR (+ optional CSC) form."""

    row_offsets: jax.Array          # (n+1,) int32
    col_indices: Optional[jax.Array]  # (m,) plan index dtype; None when
    #                                   the columns are delta-encoded
    edge_values: Optional[jax.Array] = None   # (m,) float32
    # CSC mirror (for pull traversal / reverse advance)
    csc_offsets: Optional[jax.Array] = None   # (n+1,) int32
    csc_indices: Optional[jax.Array] = None   # (m,)  int32
    csc_edge_values: Optional[jax.Array] = None
    # mapping from CSC slot -> original edge id (for edge-centric pulls)
    csc_edge_ids: Optional[jax.Array] = None
    # edge→row maps (slot e ⇒ owning row): loop-invariant structure that
    # the edge-sweep hot paths (SpMV segment reduce, pull advance, THREAD
    # expansion) would otherwise re-derive by binary search EVERY
    # iteration inside their jitted while loops — XLA does not reliably
    # hoist it. Built once with the CSR.
    row_seg: Optional[jax.Array] = None       # (m,) int32
    csc_row_seg: Optional[jax.Array] = None   # (m,) int32
    # compacted ELL-overflow edge lists (positions + owning rows of edges
    # whose within-row rank ≥ ell width): the hybrid XLA SpMV reduces
    # the first `ell_width` edges of every row with a dense rank-aligned
    # tree and lets ONLY these edges take the serial-scatter path.
    # Ascending edge order (the fold-continuation contract).
    over_pos: Optional[jax.Array] = None       # (K,) int32
    over_row: Optional[jax.Array] = None       # (K,) int32
    csc_over_pos: Optional[jax.Array] = None   # (Kc,) int32
    csc_over_row: Optional[jax.Array] = None   # (Kc,) int32
    # Delta-encoded column stores (storage plan encoding="delta"): when
    # set, the matching dense ``*_indices`` child is None and consumers
    # go through ``col_store``/``cols()`` (storage.gather_cols decodes
    # per touched edge; storage.decode_cols is the dense fallback).
    col_enc: Optional[S.EncodedCols] = None
    csc_enc: Optional[S.EncodedCols] = None
    # Host-side (static) kernel metadata, computed at build time so jitted
    # code never synchronizes to pick kernel shapes: ELL pack width for the
    # hybrid SpMV kernel, out-degree (CSR) and in-degree (CSC) flavours.
    ell_width: Optional[int] = None
    csc_ell_width: Optional[int] = None
    # The build-time storage decision (static aux: part of every jit
    # cache key). None only for hand-constructed Graphs.
    plan: Optional[S.StoragePlan] = None

    # --- pytree plumbing -------------------------------------------------
    def tree_flatten(self):
        children = (self.row_offsets, self.col_indices, self.edge_values,
                    self.csc_offsets, self.csc_indices, self.csc_edge_values,
                    self.csc_edge_ids, self.row_seg, self.csc_row_seg,
                    self.over_pos, self.over_row,
                    self.csc_over_pos, self.csc_over_row,
                    self.col_enc, self.csc_enc)
        return children, (self.ell_width, self.csc_ell_width, self.plan)

    @classmethod
    def tree_unflatten(cls, aux, children):
        ell, csc_ell, plan = aux if aux is not None else (None, None, None)
        return cls(*children, ell_width=ell, csc_ell_width=csc_ell,
                   plan=plan)

    # --- basic properties -------------------------------------------------
    @property
    def num_vertices(self) -> int:
        return int(self.row_offsets.shape[0]) - 1

    @property
    def num_edges(self) -> int:
        if self.col_indices is not None:
            return int(self.col_indices.shape[0])
        return self.col_enc.num_edges

    # --- storage access ---------------------------------------------------
    @property
    def col_store(self) -> S.ColStore:
        """CSR column storage as the registry passes it: the dense array
        (plan index dtype) or the EncodedCols pytree."""
        return self.col_indices if self.col_enc is None else self.col_enc

    @property
    def csc_store(self) -> Optional[S.ColStore]:
        if self.csc_enc is not None:
            return self.csc_enc
        return self.csc_indices

    def cols(self) -> jax.Array:
        """Dense int32 CSR column view (decode-to-dense when delta)."""
        return S.decode_cols(self.col_store)

    def csc_cols(self) -> jax.Array:
        assert self.has_csc, "graph has no CSC mirror"
        return S.decode_cols(self.csc_store)

    def cols_np(self) -> np.ndarray:
        """Host-side dense int32 columns (partitioning, edge recovery)."""
        return np.asarray(self.cols())

    @property
    def degrees(self) -> jax.Array:
        return self.row_offsets[1:] - self.row_offsets[:-1]

    @property
    def has_csc(self) -> bool:
        return self.csc_offsets is not None

    @property
    def weighted(self) -> bool:
        return self.edge_values is not None

    def neighbors_padded(self, max_degree: int) -> tuple[jax.Array, jax.Array]:
        """Dense (n, max_degree) neighbor table + validity mask (ELL format)."""
        n = self.num_vertices
        lanes = jnp.arange(max_degree, dtype=jnp.int32)[None, :]
        starts = self.row_offsets[:-1, None]
        deg = self.degrees[:, None]
        idx = jnp.minimum(starts + lanes, self.num_edges - 1)
        nbrs = self.cols()[idx]
        mask = lanes < deg
        return jnp.where(mask, nbrs, -1), mask

    @classmethod
    def from_csr(cls, row_offsets, col_indices, edge_values=None, *,
                 build_csc: bool = True,
                 sort_neighbors: bool = True,
                 index_dtype: Optional[str] = None,
                 encoding: str = "dense",
                 value_dtype: str = "fp32",
                 validate: bool = False) -> "Graph":
        """Build a Graph from host-side CSR arrays.

        ALL static kernel metadata — the CSC mirror and both ELL pack
        widths — is computed here, exactly once, at build time. Jitted
        code (the pallas SpMV/SpMM hot paths in particular) reads the
        widths as static attributes and never synchronizes to the host;
        hand-constructing ``Graph(...)`` directly skips this and leaves
        the metadata ``None``, which the pallas backend rejects.

        Neighbor lists are sorted within each row (values permuted
        along) unless ``sort_neighbors=False`` — segmented intersection
        and the SpGEMM probe binary-search rows and silently miscount on
        unsorted input (paper §4.3 assumes sorted adjacency lists).

        The storage plan (``index_dtype`` / ``encoding`` /
        ``value_dtype``, see core/storage.py) is resolved here and every
        structural array is pinned to it — notably under
        ``jax_enable_x64``, where index arrays would otherwise drift to
        int64. ``encoding="delta"`` requires sorted neighbor lists.

        ``validate=True`` runs :func:`validate_csr` on the RAW input
        arrays — before any dtype cast can silently truncate a bad id —
        and raises :class:`GraphValidationError` with the offending
        row/edge named. Off by default: trusted in-process builders
        (rmat, from_edge_list) construct valid CSR by construction.
        """
        ro = np.asarray(row_offsets, np.int64)
        n = len(ro) - 1
        plan = S.plan_for(n, index_dtype=index_dtype, encoding=encoding,
                          value_dtype=value_dtype)
        if validate:
            validate_csr(row_offsets, col_indices, edge_values, plan=plan)
        # delta encoding needs sorted rows; callers that pre-sort (e.g.
        # from_edge_list) pass sort_neighbors=False and encode_delta
        # itself rejects genuinely unsorted input.
        ci = np.asarray(col_indices, plan.np_index_dtype)
        vals = (None if edge_values is None
                else np.asarray(edge_values, np.float32))
        counts = np.diff(ro)
        if sort_neighbors and len(ci):
            order = np.lexsort((ci, np.repeat(np.arange(n), counts)))
            ci = ci[order]
            if vals is not None:
                vals = vals[order]
        csc = (None, None, None, None)
        csc_ell = None
        csc_seg = None
        csc_over = (None, None)
        src = np.repeat(np.arange(n, dtype=np.int32), counts)
        ell_w = ell_width_for(counts)
        over = _overflow_edges(ro, src, ell_w)
        if build_csc:
            with span("graph.csc", category="setup"):
                csc = _build_csc(n, src, ci.astype(np.int64), vals)
                csc_ell = ell_width_for(np.diff(csc[0]))
                csc_seg = np.repeat(np.arange(n, dtype=np.int32),
                                    np.diff(csc[0]))
                csc_over = _overflow_edges(csc[0], csc_seg, csc_ell)

        def _idx(a):
            """Pin a structural index array to the plan's dtype on
            device, and verify the dtype survived the transfer (without
            jax_enable_x64 JAX silently truncates int64 to int32 —
            corrupting ids on a >2^31-vertex graph, so refuse)."""
            out = jnp.asarray(np.asarray(a, plan.np_index_dtype))
            if out.dtype != plan.jnp_index_dtype:
                raise RuntimeError(
                    f"index_dtype={plan.index_dtype!r} needs "
                    "jax_enable_x64 (JAX truncated the array to "
                    f"{out.dtype})")
            return out

        # host → device: the transfer is fenced, so the span times it
        with span("graph.transfer", category="setup"):
            col_enc = csc_enc = None
            col_dense = _idx(ci)
            csc_dense = _idx(csc[1]) if csc[1] is not None else None
            if plan.encoding == "delta":
                col_enc = S.encode_delta(ro, ci, src)
                col_dense = None
                if csc[1] is not None:
                    csc_enc = S.encode_delta(csc[0], csc[1], csc_seg)
                    csc_dense = None
            # value_dtype="bf16" halves resident value bytes; compute
            # promotes back through float32 (semiring.with_precision is the
            # compute-side knob — the two compose but are independent)
            vdt = jnp.bfloat16 if plan.value_dtype == "bf16" else jnp.float32
            g = cls(
                row_offsets=jnp.asarray(ro.astype(np.int32)),
                col_indices=col_dense,
                edge_values=(jnp.asarray(vals, vdt)
                             if vals is not None else None),
                csc_offsets=(jnp.asarray(csc[0].astype(np.int32))
                             if csc[0] is not None else None),
                csc_indices=csc_dense,
                csc_edge_values=(jnp.asarray(csc[2], vdt)
                                 if csc[2] is not None else None),
                csc_edge_ids=(jnp.asarray(csc[3])
                              if csc[3] is not None else None),
                row_seg=jnp.asarray(src),
                csc_row_seg=(jnp.asarray(csc_seg)
                             if csc_seg is not None else None),
                over_pos=jnp.asarray(over[0]),
                over_row=jnp.asarray(over[1]),
                csc_over_pos=(jnp.asarray(csc_over[0])
                              if csc_over[0] is not None else None),
                csc_over_row=(jnp.asarray(csc_over[1])
                              if csc_over[1] is not None else None),
                col_enc=col_enc,
                csc_enc=csc_enc,
                ell_width=ell_w,
                csc_ell_width=csc_ell,
                plan=plan,
            )
            jax.block_until_ready(g)
        return g


class GraphValidationError(ValueError):
    """Structurally invalid CSR input (see :func:`validate_csr`)."""


def validate_csr(row_offsets, col_indices, edge_values=None, *,
                 plan: Optional[S.StoragePlan] = None) -> tuple[int, int]:
    """Strict structural validation of host-side CSR arrays.

    Runs on the raw (pre-cast) arrays so a column id that would overflow
    the storage plan's index dtype is caught instead of silently
    truncated. Checks, each with the offending row/edge in the message:

      * indptr is 1-D, non-empty, starts at 0, and is non-decreasing;
      * ``indptr[-1]`` equals ``len(col_indices)`` (edge-count match);
      * every column id is in ``[0, n)``;
      * ids and ``n`` fit the storage plan's index dtype (when given);
      * ``edge_values`` (when given) has one finite value per edge.

    Returns ``(num_vertices, num_edges)``; raises
    :class:`GraphValidationError` on the first violation.
    """
    ro = np.asarray(row_offsets, np.int64)
    ci = np.asarray(col_indices, np.int64)
    if ro.ndim != 1 or len(ro) < 1:
        raise GraphValidationError(
            f"row_offsets must be a 1-D array of n+1 offsets; got "
            f"shape {ro.shape}")
    n = len(ro) - 1
    if len(ro) and ro[0] != 0:
        raise GraphValidationError(
            f"row_offsets[0] must be 0 (CSR rows start at the origin), "
            f"got {int(ro[0])}")
    diffs = np.diff(ro)
    bad = np.nonzero(diffs < 0)[0]
    if len(bad):
        i = int(bad[0])
        raise GraphValidationError(
            f"non-monotone row_offsets at row {i}: offsets[{i}]="
            f"{int(ro[i])} > offsets[{i + 1}]={int(ro[i + 1])}; each "
            f"row's edge range must be non-decreasing")
    if int(ro[-1]) != len(ci):
        raise GraphValidationError(
            f"indptr/edge-count mismatch: row_offsets[-1]={int(ro[-1])} "
            f"but col_indices has {len(ci)} entries — the offsets claim "
            f"a different edge count than the column array holds")
    if len(ci):
        oob = np.nonzero((ci < 0) | (ci >= n))[0]
        if len(oob):
            e = int(oob[0])
            raise GraphValidationError(
                f"column id out of range at edge {e}: {int(ci[e])} not "
                f"in [0, {n}) — every destination must name an existing "
                f"vertex")
    if plan is not None:
        info = np.iinfo(plan.np_index_dtype)
        top = max(n - 1, int(ci.max()) if len(ci) else 0)
        if top > info.max:
            raise GraphValidationError(
                f"index dtype overflow: storage plan "
                f"index_dtype={plan.index_dtype!r} holds ids up to "
                f"{info.max} but the graph needs {top}; pass a wider "
                f"index_dtype (or index_dtype=None to auto-size)")
    if edge_values is not None:
        ev = np.asarray(edge_values, np.float64)
        if len(ev) != len(ci):
            raise GraphValidationError(
                f"edge_values length {len(ev)} != edge count {len(ci)}")
        nf = np.nonzero(~np.isfinite(ev))[0]
        if len(nf):
            e = int(nf[0])
            raise GraphValidationError(
                f"non-finite edge value at edge {e}: {ev[e]!r}; weights "
                f"must be finite")
    return n, len(ci)


def validate_graph(g: "Graph") -> tuple[int, int]:
    """Re-run structural validation on a built ``Graph`` (the CLI
    ``--validate`` hook): pulls the device CSR back to host and applies
    :func:`validate_csr` against the graph's own storage plan, plus the
    CSC mirror's offsets/edge-count when one exists."""
    ro = np.asarray(g.row_offsets)
    cols = g.cols_np()
    vals = (None if g.edge_values is None
            else np.asarray(g.edge_values, np.float32))
    shape = validate_csr(ro, cols, vals, plan=g.plan)
    if g.has_csc:
        validate_csr(np.asarray(g.csc_offsets), np.asarray(g.csc_cols()),
                     plan=g.plan)
    return shape


def _overflow_edges(offsets: np.ndarray, seg: np.ndarray,
                    width: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side (build-time): positions + owning rows of the edges whose
    within-row rank ≥ ``width`` — the serial-scatter remainder of the
    hybrid ELL SpMV. Ascending edge order by construction."""
    m = len(seg)
    rank = np.arange(m, dtype=np.int64) - offsets[:-1][seg]
    pos = np.nonzero(rank >= width)[0].astype(np.int32)
    return pos, seg[pos].astype(np.int32)


def row_segments_of(offsets: jax.Array, m: int) -> jax.Array:
    """Edge→row map derived from CSR offsets under jit, O(m): cumsum of
    row-start marks. Bit-identical to the searchsorted formulation
    (``searchsorted(offsets, e, 'right') - 1``) at ~3× less cost — the
    fallback for hand-built Graphs whose ``row_seg`` metadata is None."""
    marks = jnp.zeros((m,), jnp.int32).at[offsets[1:-1]].add(
        1, mode="drop")
    return jnp.cumsum(marks)


def ell_width_for(degrees: np.ndarray) -> int:
    """Default ELL pack width for the hybrid SpMV kernel: covers ≥95% of
    edges, clamped to [1, 1024]. Host-side, run once at Graph build time —
    the old on-demand jax.device_get default broke under jit."""
    if len(degrees) == 0:
        return 1
    w = int(np.percentile(np.asarray(degrees), 95))
    return max(min(w, 1024), 1)


def _build_csc(n: int, src: np.ndarray, dst: np.ndarray,
               vals: Optional[np.ndarray]):
    """Transpose an edge list into CSC arrays (numpy, host-side)."""
    order = np.argsort(dst, kind="stable")
    csc_indices = src[order].astype(np.int32)
    csc_edge_ids = order.astype(np.int32)
    counts = np.bincount(dst, minlength=n)
    csc_offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(counts, out=csc_offsets[1:])
    csc_vals = vals[order].astype(np.float32) if vals is not None else None
    return csc_offsets, csc_indices, csc_vals, csc_edge_ids


def from_edge_list(src, dst, n: Optional[int] = None, values=None,
                   undirected: bool = False, build_csc: bool = True,
                   sort_neighbors: bool = True,
                   remove_self_loops: bool = True,
                   deduplicate: bool = True,
                   index_dtype: Optional[str] = None,
                   encoding: str = "dense",
                   value_dtype: str = "fp32") -> Graph:
    """Build a Graph from host-side edge arrays.

    Mirrors the paper's dataset preparation: optionally symmetrize,
    remove self loops and duplicate edges (paper Table 4 note). Spans:
    ``graph.build`` around ``graph.symmetrize`` (with self-loop removal
    and deduplication), ``graph.csr``, and ``from_csr``'s ``graph.csc``
    and ``graph.transfer``.
    """
    with span("graph.build", category="setup"):
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        if values is not None:
            values = np.asarray(values, dtype=np.float32)
        if n is None:
            n = (int(max(src.max(initial=-1), dst.max(initial=-1))) + 1
                 if len(src) else 0)
        with span("graph.symmetrize", category="setup"):
            if undirected:
                src, dst = (np.concatenate([src, dst]),
                            np.concatenate([dst, src]))
                if values is not None:
                    values = np.concatenate([values, values])
            if remove_self_loops and len(src):
                keep = src != dst
                src, dst = src[keep], dst[keep]
                if values is not None:
                    values = values[keep]
            if deduplicate and len(src):
                key = src * n + dst
                _, first = np.unique(key, return_index=True)
                first.sort()
                src, dst = src[first], dst[first]
                if values is not None:
                    values = values[first]
        with span("graph.csr", category="setup"):
            # CSR: sort by (src, dst) so neighbor lists are sorted (needed
            # by segmented intersection; paper §4.3 assumes sorted
            # adjacency lists).
            if sort_neighbors and len(src):
                order = np.lexsort((dst, src))
            else:
                order = np.argsort(src, kind="stable")
            src, dst = src[order], dst[order]
            if values is not None:
                values = values[order]
            counts = np.bincount(src, minlength=n)
            row_offsets = np.zeros(n + 1, dtype=np.int32)
            np.cumsum(counts, out=row_offsets[1:])
        # Graph.from_csr is the single build-time home of kernel
        # metadata (CSC mirror + ELL pack widths) — computed once, never
        # under jit. Rows are already in the order this function's flags
        # chose, so the constructor must not re-sort them.
        # ``encoding="delta"`` needs sorted rows (storage.encode_delta
        # validates).
        if encoding == "delta" and not sort_neighbors:
            raise ValueError("encoding='delta' requires sort_neighbors=True")
        return Graph.from_csr(row_offsets, dst, values,
                              build_csc=build_csc, sort_neighbors=False,
                              index_dtype=index_dtype, encoding=encoding,
                              value_dtype=value_dtype)


def edge_list(graph: Graph) -> tuple[np.ndarray, np.ndarray]:
    """Recover (src, dst) host arrays from CSR."""
    ro = np.asarray(graph.row_offsets)
    ci = graph.cols_np()
    src = np.repeat(np.arange(len(ro) - 1, dtype=np.int32), np.diff(ro))
    return src, ci


# ---------------------------------------------------------------------------
# Generators (paper Table 4 families: scale-free R-MAT, random geometric,
# mesh-like road networks).
# ---------------------------------------------------------------------------

def rmat(scale: int, edge_factor: int = 16, a: float = 0.57, b: float = 0.19,
         c: float = 0.19, seed: int = 0, weighted: bool = False,
         undirected: bool = True, index_dtype: Optional[str] = None,
         encoding: str = "dense", value_dtype: str = "fp32") -> Graph:
    """R-MAT / Kronecker generator with Graph500 parameters (paper §7).

    a=0.57, b=0.19, c=0.19, d=0.05 is the Graph500 initiator used in the
    paper's rmat_s22_e64 etc. datasets.
    """
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for level in range(scale):
        r = rng.random(m)
        # quadrant probabilities: a (0,0), b (0,1), c (1,0), d (1,1)
        go_right = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        go_down = r >= a + b
        src |= go_down.astype(np.int64) << level
        dst |= go_right.astype(np.int64) << level
    # permute vertex IDs to remove locality bias
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    values = rng.integers(1, 64, size=m).astype(np.float32) if weighted else None
    return from_edge_list(src, dst, n=n, values=values,
                          undirected=undirected, index_dtype=index_dtype,
                          encoding=encoding, value_dtype=value_dtype)


def random_geometric(n: int, radius: float, seed: int = 0,
                     weighted: bool = False,
                     index_dtype: Optional[str] = None,
                     encoding: str = "dense",
                     value_dtype: str = "fp32") -> Graph:
    """Random geometric graph on the unit square (paper's rgg datasets)."""
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 2))
    # grid-bucket neighbor search to stay O(n) at small radius
    cell = max(radius, 1e-6)
    gx = (pts[:, 0] / cell).astype(np.int64)
    gy = (pts[:, 1] / cell).astype(np.int64)
    ncell = int(1.0 / cell) + 1
    bucket = gx * ncell + gy
    order = np.argsort(bucket)
    src_l, dst_l = [], []
    sorted_bucket = bucket[order]
    starts = np.searchsorted(sorted_bucket, np.arange(ncell * ncell))
    r2 = radius * radius
    for dxy in ((0, 0), (0, 1), (1, -1), (1, 0), (1, 1)):
        nb = (gx + dxy[0]) * ncell + (gy + dxy[1])
        valid = (gx + dxy[0] < ncell) & (gy + dxy[1] >= 0) & (gy + dxy[1] < ncell)
        for i in np.nonzero(valid)[0]:
            b = nb[i]
            if b < 0 or b >= ncell * ncell:
                continue
            lo = starts[b]
            hi = starts[b + 1] if b + 1 < len(starts) else n
            cand = order[lo:hi]
            if dxy == (0, 0):
                cand = cand[cand > i]
            d2 = ((pts[cand] - pts[i]) ** 2).sum(axis=1)
            close = cand[d2 <= r2]
            src_l.append(np.full(len(close), i, dtype=np.int64))
            dst_l.append(close.astype(np.int64))
    src = np.concatenate(src_l) if src_l else np.zeros(0, np.int64)
    dst = np.concatenate(dst_l) if dst_l else np.zeros(0, np.int64)
    values = (rng.integers(1, 64, size=len(src)).astype(np.float32)
              if weighted else None)
    return from_edge_list(src, dst, n=n, values=values, undirected=True,
                          index_dtype=index_dtype, encoding=encoding,
                          value_dtype=value_dtype)


def grid2d(side: int, weighted: bool = False, seed: int = 0,
           index_dtype: Optional[str] = None, encoding: str = "dense",
           value_dtype: str = "fp32") -> Graph:
    """2-D grid — the mesh-like / road-network stand-in (large diameter,
    uniform small degree, like the paper's roadnet_USA)."""
    rng = np.random.default_rng(seed)
    idx = np.arange(side * side, dtype=np.int64).reshape(side, side)
    right = np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=0)
    down = np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=0)
    src = np.concatenate([right[0], down[0]])
    dst = np.concatenate([right[1], down[1]])
    values = (rng.integers(1, 64, size=len(src)).astype(np.float32)
              if weighted else None)
    return from_edge_list(src, dst, n=side * side, values=values,
                          undirected=True, index_dtype=index_dtype,
                          encoding=encoding, value_dtype=value_dtype)


def bipartite_random(n_users: int, n_items: int, avg_degree: int,
                     seed: int = 0) -> Graph:
    """Random bipartite follow-graph for the WTF primitive (paper §7.5).

    Users [0, n_users) point at items [n_users, n_users+n_items).
    Directed; CSC gives the reverse (who-follows-me) direction.
    """
    rng = np.random.default_rng(seed)
    m = n_users * avg_degree
    src = rng.integers(0, n_users, size=m).astype(np.int64)
    dst = (n_users + rng.integers(0, n_items, size=m)).astype(np.int64)
    return from_edge_list(src, dst, n=n_users + n_items, undirected=False)


@functools.lru_cache(maxsize=32)
def demo_graph() -> Graph:
    """The 7-node / 15-edge sample graph from paper Fig. 5/6."""
    src = [0, 0, 0, 1, 1, 2, 2, 3, 3, 4, 4, 5, 5, 6, 6]
    dst = [1, 2, 3, 2, 4, 3, 5, 4, 5, 5, 6, 6, 0, 0, 2]
    return from_edge_list(src, dst, n=7, undirected=False,
                          deduplicate=False, remove_self_loops=False)
