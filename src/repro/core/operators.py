"""Gunrock's graph operators in JAX (paper §3–§5).

Operators:
  advance               — neighbor expansion (V→V, V→E, E→V, E→E), the
                          irregular workhorse. Implemented with the paper's
                          merge-based Load-Balanced partitioning (LB):
                          prefix-sum over degrees + per-output-slot binary
                          search (sorted search), which is the TPU-native
                          translation of Davidson/Merrill load balancing.
  advance_pull          — pull/reverse advance over CSC from an unvisited
                          frontier (direction-optimized traversal, §5.1.4).
  filter                — stream compaction with exact or heuristic
                          uniquification (§4.2, §5.2.1).
  neighborhood_reduce   — advance + per-source segmented reduction (§8.2.3).
  segmented_intersect   — pairwise sorted neighbor-list intersection (§4.3),
                          SmallLarge binary-probe scheme.
  compute               — per-element map over a frontier (fused by XLA into
                          adjacent traversal ops — the paper's kernel fusion).

Conventions:
  * All shapes static. Invalid lanes carry id == -1 and mask == False.
  * "Functors" are *vectorized*: they receive whole vectors
    (src, dst, edge_id, rank) + problem-data pytree and return
    (keep_mask, new_data). This is the JAX translation of Gunrock's
    per-edge cond/apply functors; XLA fuses them into the traversal,
    exactly as Gunrock fuses functors into operator kernels at
    compile time (§5.3).
  * Load-balancing strategy is selectable (LB | TWC | THREAD) to support the
    paper's Fig.-20 ablation; LB is the default (the paper's LB_CULL).

Backends:
  Every operator takes ``backend=`` ("xla" | "pallas" | "auto" | None) and
  dispatches its hot path through the registry in ``core.backend``:

    advance               — "advance": XLA sorted-search + gathers below, or
                            the fused Pallas kernel (kernels/advance_fused.py)
                            that does search + CSR gathers in one pass.
    filter / compaction   — "compact": XLA scatter compaction or the Pallas
                            filter_compact kernel (tile-local scan).
    segmented_intersect   — "segment_search" for the binary probe, plus
                            "advance" for its expansion and "compact" for
                            its output.

  ``backend=None`` defers to the ambient selection (context manager /
  REPRO_BACKEND env var; see core/backend.py). THREAD has no Pallas
  implementation — it is the deliberately-unbalanced ablation baseline —
  and silently runs the XLA path on every backend. Design notes:
  DESIGN.md.

Batched operators:
  ``advance_batch`` / ``filter_frontier_batch`` / ``advance_pull_batch``
  run B traversal lanes over one shared topology in a single program —
  the frontier-matrix view (GraphBLAST's multi-source BFS). Hot paths
  dispatch through "advance_batch" (vmapped XLA expansion, or the fused
  Pallas kernel with an explicit (B, tiles) grid) and vmapped "compact".
  Functors keep their single-lane signature and are vmapped over the
  batch axis, so BFS/SSSP share one functor between the single- and
  multi-source paths; problem-data pytrees carry a leading batch axis.

Every public operator runs under an ``op.<name>`` ``jax.named_scope``
(``op.advance``, ``op.advance_filter``, ``op.pull``, ``op.filter``, ...),
so a profiler trace names its device ops (DESIGN.md §10).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import backend as B
from . import storage as S
from .frontier import (INVALID, BatchedDenseFrontier, BatchedSparseFrontier,
                       DenseFrontier, SparseFrontier, compact_values,
                       compact_values_batch)
from .graph import Graph, row_segments_of

# ---------------------------------------------------------------------------
# Expansion geometry: given per-input segment sizes, map output slots back to
# (input position, rank within segment). This is the LB sorted-search.
# ---------------------------------------------------------------------------


class Expansion(NamedTuple):
    in_pos: jax.Array    # (cap_out,) int32: which input item produced the slot
    rank: jax.Array      # (cap_out,) int32: index within the input's segment
    valid: jax.Array     # (cap_out,) bool
    total: jax.Array     # () int32: true number of output items


def lb_expand(sizes: jax.Array, valid_in: jax.Array, cap_out: int) -> Expansion:
    """Merge-based load-balanced expansion (paper §5.1.3, Fig. 11).

    sizes: (cap_in,) int32 per-input segment length (0 for invalid lanes).
    Every output slot costs O(log cap_in) — perfectly balanced by output.
    """
    sizes = jnp.where(valid_in, sizes, 0).astype(jnp.int32)
    offsets = jnp.cumsum(sizes, dtype=jnp.int32) - sizes    # exclusive scan
    total = (offsets[-1] + sizes[-1]) if sizes.shape[0] else jnp.int32(0)
    slots = jnp.arange(cap_out, dtype=jnp.int32)
    # sorted search: which segment does each output slot land in?
    in_pos = jnp.searchsorted(offsets, slots, side="right").astype(jnp.int32) - 1
    in_pos = jnp.clip(in_pos, 0, max(sizes.shape[0] - 1, 0))
    rank = slots - offsets[in_pos]
    valid = slots < total
    return Expansion(in_pos=in_pos, rank=rank, valid=valid,
                     total=total.astype(jnp.int32))


def twc_order(sizes: jax.Array) -> jax.Array:
    """TWC size-class grouping permutation — the dynamic-grouping (TWC)
    emulation of paper §5.1.2. GPU TWC arbitrates threads/warps/CTAs;
    that mechanism has no TPU analogue (documented in DESIGN.md). We keep
    its *grouping* idea: a stable sort of segments into small ≤ 32
    "thread", ≤ 256 "warp", else "block" classes, so each class is
    processed together by the LB machinery — identical output multiset,
    distinct scheduling order (the Fig.-20 ablation contrast). Consumed
    by the TWC path of ``advance``."""
    cls = jnp.where(sizes <= 32, 0, jnp.where(sizes <= 256, 1, 2))
    return jnp.argsort(cls, stable=True)


@B.register("advance", B.XLA, encodings=("dense", "delta"))
def _advance_xla(row_offsets: jax.Array, col_indices: S.ColStore,
                 base: jax.Array, sizes: jax.Array, cap_out: int):
    """XLA advance hot path: LB sorted search + CSR gathers as separate
    (XLA-fused) passes. Shares the registry contract with the fused Pallas
    kernel: (src, dst, edge_id, in_pos, rank, valid, total), with
    src/dst/edge_id masked to INVALID and rank to 0 on dead lanes.

    ``col_indices`` is the column *store* — a dense array at any index
    width, or the delta EncodedCols pytree. gather_cols decodes per
    touched edge (the src gather already in hand supplies the owning
    row, so delta decode adds exactly one uint16 gather + one add) and
    always yields int32 — storage width never leaks into frontier ids.
    """
    exp = lb_expand(sizes, jnp.ones(sizes.shape, bool), cap_out)
    src = base[exp.in_pos]
    edge_id = row_offsets[src] + exp.rank
    edge_id = jnp.where(exp.valid, edge_id, 0)
    dst = S.gather_cols(col_indices, edge_id, src)
    return (jnp.where(exp.valid, src, INVALID),
            jnp.where(exp.valid, dst, INVALID),
            jnp.where(exp.valid, edge_id, INVALID), exp.in_pos,
            jnp.where(exp.valid, exp.rank, 0), exp.valid, exp.total)


# ---------------------------------------------------------------------------
# advance
# ---------------------------------------------------------------------------


class AdvanceResult(NamedTuple):
    src: jax.Array        # (cap_out,) int32 source vertex of each output slot
    dst: jax.Array        # (cap_out,) int32 destination vertex
    edge_id: jax.Array    # (cap_out,) int32 CSR edge index
    in_pos: jax.Array     # (cap_out,) int32 input-frontier lane of each slot
    valid: jax.Array      # (cap_out,) bool
    total: jax.Array      # () int32 number of valid outputs (pre-functor)


def _frontier_base_vertices(graph: Graph, frontier: SparseFrontier,
                            input_kind: str):
    """Resolve the vertex whose neighbor list each input item expands."""
    ids = jnp.where(frontier.valid_mask, frontier.ids, 0)
    if input_kind == "vertex":
        return ids, frontier.valid_mask
    if input_kind == "edge":
        # an edge item expands the neighbor list of its destination
        # vertex (ids are edge positions; decode-on-gather handles every
        # storage plan and returns int32 vertex ids)
        return S.gather_cols(graph.col_store, ids), frontier.valid_mask
    raise ValueError(f"unknown input_kind {input_kind}")


@jax.named_scope("op.advance")
def advance(graph: Graph, frontier: SparseFrontier, cap_out: int,
            functor: Optional[Callable] = None, data=None,
            input_kind: str = "vertex", strategy: str = "LB", *,
            backend: Optional[str] = None,
            use_kernel: Optional[bool] = None
            ) -> tuple[AdvanceResult, object]:
    """Gunrock advance (push): expand neighbor lists of the input frontier.

    functor(src, dst, edge_id, rank, valid, data) -> (keep_mask, data')
    applied in the same pass (kernel fusion). Returns the raw expansion (so
    callers can build V or E output frontiers) plus updated problem data.
    The expansion+gather hot path dispatches through the "advance" backend
    registry entry (see module docstring).
    """
    bk = B.resolve(backend, use_kernel)
    if strategy == "THREAD":
        # Static per-vertex mapping (ThreadExpand, §5.1.1) — the
        # Harish-Narayanan quadratic mapping the paper cites [32]: sweep
        # EVERY CSR slot and keep those whose source is in the frontier.
        # No load balancing, no compaction of the work list; cost is
        # O(m) per advance regardless of frontier size (the ablation
        # contrast to LB/TWC). Vertex frontiers only.
        assert input_kind == "vertex", "THREAD supports vertex frontiers"
        n, m = graph.num_vertices, graph.num_edges
        flags = frontier.to_dense(n).flags
        slot = jnp.arange(m, dtype=jnp.int32)
        src_of = (graph.row_seg if graph.row_seg is not None
                  else row_segments_of(graph.row_offsets, m))
        valid = flags[src_of]
        res = AdvanceResult(
            src=jnp.where(valid, src_of, INVALID)[:cap_out],
            dst=jnp.where(valid, graph.cols(), INVALID)[:cap_out],
            edge_id=jnp.where(valid, slot, INVALID)[:cap_out],
            in_pos=src_of[:cap_out],
            valid=valid[:cap_out],
            total=jnp.sum(valid, dtype=jnp.int32))
        if functor is None:
            return res, data
        keep, data = functor(res.src, res.dst, res.edge_id,
                             jnp.zeros_like(res.src), res.valid, data)
        keep = keep & res.valid
        return AdvanceResult(src=jnp.where(keep, res.src, INVALID),
                             dst=jnp.where(keep, res.dst, INVALID),
                             edge_id=jnp.where(keep, res.edge_id, INVALID),
                             in_pos=res.in_pos, valid=keep,
                             total=res.total), data

    if strategy not in ("LB", "TWC"):
        raise ValueError(f"unknown strategy {strategy}")
    if graph.num_edges == 0:
        bk = B.XLA          # nothing to gather; skip the kernel path
    base, valid_in = _frontier_base_vertices(graph, frontier, input_kind)
    deg = graph.row_offsets[base + 1] - graph.row_offsets[base]
    sizes = jnp.where(valid_in, deg, 0).astype(jnp.int32)
    order = None
    if strategy == "TWC":
        # dynamic-grouping emulation (§5.1.2): stably reorder segments by
        # size class, expand with the LB machinery, map lanes back
        order = twc_order(sizes)
        base, sizes = base[order], sizes[order]
    expand = B.dispatch("advance", bk, B.SINGLE)
    cols = B.storage_arg("advance", bk, B.SINGLE, graph=graph)
    src, dst, edge_id, in_pos, rank, valid, total = expand(
        graph.row_offsets, cols, base, sizes, cap_out)
    if order is not None:
        in_pos = order[in_pos]
    res = AdvanceResult(src=src, dst=dst, edge_id=edge_id, in_pos=in_pos,
                        valid=valid, total=total)
    if functor is None:
        return res, data
    keep, data = functor(res.src, res.dst, res.edge_id, rank, res.valid,
                         data)
    keep = keep & res.valid
    res = AdvanceResult(src=jnp.where(keep, res.src, INVALID),
                        dst=jnp.where(keep, res.dst, INVALID),
                        edge_id=jnp.where(keep, res.edge_id, INVALID),
                        in_pos=res.in_pos,
                        valid=keep, total=res.total)
    return res, data


@B.register("advance_batch", B.XLA, encodings=("dense", "delta"))
def _advance_batch_xla(row_offsets: jax.Array, col_indices: S.ColStore,
                       base: jax.Array, sizes: jax.Array, cap_out: int):
    """XLA batched advance: vmap the single-lane expansion over the batch
    axis (base/sizes (B, cap_in)); the CSR is closed over and shared.
    Contract mirrors "advance" with batched outputs and totals (B,)."""
    return jax.vmap(
        lambda b, s: _advance_xla(row_offsets, col_indices, b, s, cap_out)
    )(base, sizes)


@jax.named_scope("op.advance")
def advance_batch(graph: Graph, frontier: BatchedSparseFrontier,
                  cap_out: int, functor: Optional[Callable] = None,
                  data=None, input_kind: str = "vertex",
                  strategy: str = "LB", *,
                  backend: Optional[str] = None
                  ) -> tuple[AdvanceResult, object]:
    """Multi-source push advance: expand B frontier lanes in one program.

    Same semantics as ``advance`` per lane. ``functor`` keeps its
    single-lane signature and is vmapped over the batch axis, so problem
    data must carry a leading batch axis on every leaf. Returns an
    ``AdvanceResult`` whose fields are (B, cap_out) with ``total`` (B,).
    """
    bk = B.resolve(backend)
    if strategy == "THREAD":
        # batched ThreadExpand: one shared O(m) sweep, per-lane masks
        assert input_kind == "vertex", "THREAD supports vertex frontiers"
        n, m = graph.num_vertices, graph.num_edges
        flags = frontier.to_dense(n).flags               # (B, n)
        slot = jnp.arange(m, dtype=jnp.int32)
        src_of = (graph.row_seg if graph.row_seg is not None
                  else row_segments_of(graph.row_offsets, m))
        valid = flags[:, src_of] if m else jnp.zeros((frontier.batch, 0),
                                                     bool)
        res = AdvanceResult(
            src=jnp.where(valid, src_of[None, :], INVALID)[:, :cap_out],
            dst=jnp.where(valid, graph.cols()[None, :],
                          INVALID)[:, :cap_out],
            edge_id=jnp.where(valid, slot[None, :], INVALID)[:, :cap_out],
            in_pos=jnp.broadcast_to(src_of[None, :],
                                    valid.shape)[:, :cap_out],
            valid=valid[:, :cap_out],
            total=jnp.sum(valid, dtype=jnp.int32, axis=1))
    else:
        if strategy not in ("LB", "TWC"):
            raise ValueError(f"unknown strategy {strategy}")
        if graph.num_edges == 0:
            bk = B.XLA
        # the helper is pure indexing on ids/valid_mask, so it serves the
        # batched frontier unchanged
        base, valid_in = _frontier_base_vertices(graph, frontier,
                                                 input_kind)
        deg = graph.row_offsets[base + 1] - graph.row_offsets[base]
        sizes = jnp.where(valid_in, deg, 0).astype(jnp.int32)
        order = None
        if strategy == "TWC":
            order = jax.vmap(twc_order)(sizes)
            base = jnp.take_along_axis(base, order, axis=1)
            sizes = jnp.take_along_axis(sizes, order, axis=1)
        expand = B.dispatch("advance_batch", bk, B.SINGLE)
        cols = B.storage_arg("advance_batch", bk, B.SINGLE, graph=graph)
        src, dst, edge_id, in_pos, rank, valid, total = expand(
            graph.row_offsets, cols, base, sizes, cap_out)
        if order is not None:
            in_pos = jnp.take_along_axis(order, in_pos, axis=1)
        res = AdvanceResult(src=src, dst=dst, edge_id=edge_id,
                            in_pos=in_pos, valid=valid, total=total)
    if functor is None:
        return res, data
    rank_arg = (jnp.zeros_like(res.src) if strategy == "THREAD" else rank)
    keep, data = jax.vmap(functor)(res.src, res.dst, res.edge_id, rank_arg,
                                   res.valid, data)
    keep = keep & res.valid
    return AdvanceResult(src=jnp.where(keep, res.src, INVALID),
                         dst=jnp.where(keep, res.dst, INVALID),
                         edge_id=jnp.where(keep, res.edge_id, INVALID),
                         in_pos=res.in_pos, valid=keep,
                         total=res.total), data


def frontier_workload(graph: Graph, frontier) -> jax.Array:
    """Upper bound on the advance output size of ``frontier``: the sum of
    out-degrees of its live vertices. (B,) for a batched frontier, ()
    for a single one. This is the traced quantity the tiered dispatch
    switches on (backend.tier_plan / enactor.tiered_step): computing it
    costs one degree gather — frontier-shaped, never edge-shaped."""
    ids = jnp.where(frontier.valid_mask, frontier.ids, 0)
    deg = graph.row_offsets[ids + 1] - graph.row_offsets[ids]
    deg = jnp.where(frontier.valid_mask, deg, 0)
    return jnp.sum(deg, axis=-1).astype(jnp.int32)


@B.register("advance_filter", B.XLA, encodings=("dense", "delta"))
def _advance_filter_xla(row_offsets: jax.Array, col_indices: S.ColStore,
                        base: jax.Array, sizes: jax.Array,
                        visited: jax.Array, cap_out: int, cap_front: int):
    """XLA advance_filter: the unfused composition the fused Pallas
    megakernel must match bit for bit — LB expansion, visited-bitmap
    predicate, exact FIRST-occurrence culling (min-lane winner, so the
    surviving order is ascending slot order — exactly the order the
    sequential kernel emits), compaction of (dst, src) into cap_front
    slots. Returns (ids, srcs, length, total)."""
    src, dst, _, _, _, valid, _ = _advance_xla(row_offsets, col_indices,
                                               base, sizes, cap_out)
    n = visited.shape[0]
    safe = jnp.where(valid, dst, 0)
    keep = valid & (visited.astype(jnp.int32)[safe] == 0)
    lane = jnp.arange(cap_out, dtype=jnp.int32)
    first = jnp.full((n,), cap_out, jnp.int32)
    first = first.at[safe].min(jnp.where(keep, lane, cap_out), mode="drop")
    keep = keep & (first[safe] == lane)
    ids, length = compact_values(dst, keep, cap_front, backend=B.XLA)
    srcs, _ = compact_values(src, keep, cap_front, backend=B.XLA)
    # int32-pinned: under jax_enable_x64 jnp.sum would widen the total
    # and split the while_loop carry dtypes between push and pull
    return ids, srcs, length, jnp.sum(
        keep.astype(jnp.int32)).astype(jnp.int32)


@B.register("advance_filter_batch", B.XLA, encodings=("dense", "delta"))
def _advance_filter_batch_xla(row_offsets: jax.Array,
                              col_indices: S.ColStore, base: jax.Array,
                              sizes: jax.Array, visited: jax.Array,
                              cap_out: int, cap_front: int):
    """Batched XLA advance_filter: vmap the single-lane composition
    (base/sizes/visited carry a leading batch axis, CSR shared)."""
    return jax.vmap(
        lambda b, s, v: _advance_filter_xla(row_offsets, col_indices,
                                            b, s, v, cap_out, cap_front)
    )(base, sizes, visited)


@jax.named_scope("op.advance_filter")
def advance_filter(graph: Graph, frontier: SparseFrontier,
                   visited: jax.Array, cap_out: int,
                   cap_front: Optional[int] = None, *,
                   backend: Optional[str] = None
                   ) -> tuple[SparseFrontier, jax.Array, jax.Array]:
    """Fused advance→filter (paper §5.3 taken whole): expand the
    frontier, keep destinations whose ``visited`` bit is clear, cull
    duplicates exactly (first discovering slot wins), and compact the
    survivors — without materializing the intermediate edge tuple.

    Returns ``(new_frontier, srcs, total)``: the compacted discovered
    frontier (capacity ``cap_front``, default the input's capacity), the
    discovering source of each surviving slot (aligned with
    ``new_frontier.ids``; the predecessor scatter BFS needs), and the
    true pre-clamp survivor count. Dispatches "advance_filter": the XLA
    composition above, or one fused Pallas megakernel
    (kernels/advance_filter_fused.py).
    """
    bk = B.resolve(backend)
    if graph.num_edges == 0:
        bk = B.XLA
    cap_front = frontier.capacity if cap_front is None else cap_front
    base, valid_in = _frontier_base_vertices(graph, frontier, "vertex")
    deg = graph.row_offsets[base + 1] - graph.row_offsets[base]
    sizes = jnp.where(valid_in, deg, 0).astype(jnp.int32)
    impl = B.dispatch("advance_filter", bk, B.SINGLE)
    cols = B.storage_arg("advance_filter", bk, B.SINGLE, graph=graph)
    ids, srcs, length, total = impl(graph.row_offsets, cols,
                                    base, sizes,
                                    visited.astype(jnp.int32),
                                    cap_out, cap_front)
    return SparseFrontier(ids=ids, length=length), srcs, total


@jax.named_scope("op.advance_filter")
def advance_filter_batch(graph: Graph, frontier: BatchedSparseFrontier,
                         visited: jax.Array, cap_out: int,
                         cap_front: Optional[int] = None, *,
                         backend: Optional[str] = None
                         ) -> tuple[BatchedSparseFrontier, jax.Array,
                                    jax.Array]:
    """Multi-source fused advance→filter; per-lane semantics identical
    to ``advance_filter`` (``visited`` is (B, n), outputs batched)."""
    bk = B.resolve(backend)
    if graph.num_edges == 0:
        bk = B.XLA
    cap_front = frontier.capacity if cap_front is None else cap_front
    base, valid_in = _frontier_base_vertices(graph, frontier, "vertex")
    deg = graph.row_offsets[base + 1] - graph.row_offsets[base]
    sizes = jnp.where(valid_in, deg, 0).astype(jnp.int32)
    impl = B.dispatch("advance_filter_batch", bk, B.SINGLE)
    cols = B.storage_arg("advance_filter_batch", bk, B.SINGLE, graph=graph)
    ids, srcs, lengths, totals = impl(graph.row_offsets,
                                      cols, base, sizes,
                                      visited.astype(jnp.int32),
                                      cap_out, cap_front)
    return BatchedSparseFrontier(ids=ids, lengths=lengths), srcs, totals


def advance_to_vertex_frontier(res: AdvanceResult,
                               cap: Optional[int] = None,
                               backend: Optional[str] = None
                               ) -> SparseFrontier:
    """Compact an advance result's destinations into a vertex frontier."""
    cap = int(res.dst.shape[0]) if cap is None else cap
    buf, length = compact_values(res.dst, res.valid, cap, backend=backend)
    return SparseFrontier(ids=buf, length=length)


def advance_to_edge_frontier(res: AdvanceResult,
                             cap: Optional[int] = None,
                             backend: Optional[str] = None) -> SparseFrontier:
    cap = int(res.edge_id.shape[0]) if cap is None else cap
    buf, length = compact_values(res.edge_id, res.valid, cap,
                                 backend=backend)
    return SparseFrontier(ids=buf, length=length)


def advance_to_vertex_frontier_batch(res: AdvanceResult,
                                     cap: Optional[int] = None,
                                     backend: Optional[str] = None
                                     ) -> BatchedSparseFrontier:
    """Per-lane compaction of a batched advance's destinations."""
    cap = int(res.dst.shape[1]) if cap is None else cap
    buf, lengths, _ = compact_values_batch(res.dst, res.valid, cap,
                                           backend=backend)
    return BatchedSparseFrontier(ids=buf, lengths=lengths)


@jax.named_scope("op.pull")
def advance_pull(graph: Graph, unvisited: DenseFrontier,
                 current: DenseFrontier, return_preds: bool = False):
    """Pull-based advance (paper §5.1.4, Fig. 13).

    For every unvisited vertex, test whether any in-neighbor (CSC) is in the
    current frontier; those become the new frontier. Dense formulation: a
    masked segment-max over CSC — one sweep of the edge list, which is the
    pull phase's defining cost (and why it wins only when the active
    frontier is large).
    """
    assert graph.has_csc, "pull advance requires a CSC mirror"
    n = graph.num_vertices
    m = graph.num_edges
    # For each CSC slot e: dst vertex = segment owner, src = csc_indices[e].
    # The edge→row map is loop-invariant graph structure: build-time
    # metadata when available (Graph.from_csr), else derived here.
    seg = graph.csc_row_seg
    if seg is None:
        seg = row_segments_of(graph.csc_offsets, m)
    # the pull sweep touches every CSC slot, so the dense decoded view
    # costs nothing extra under delta storage (same O(m) stream); going
    # through the store keeps this generic over Graph / ShardedGraph
    csc = S.decode_cols(graph.csc_store)
    pred_active = current.flags[csc]
    # ONE segment-max serves both outputs: the max surviving in-neighbor
    # id is ≥ 0 exactly where any in-neighbor is active (ids are
    # non-negative), so the hit test rides the predecessor sweep free.
    pred_id = jnp.where(pred_active, csc, -1)
    preds = jax.ops.segment_max(pred_id, seg, num_segments=n,
                                indices_are_sorted=True)
    new_flags = (preds >= 0) & unvisited.flags
    if not return_preds:
        return DenseFrontier(new_flags)
    return DenseFrontier(new_flags), preds


@jax.named_scope("op.pull")
def advance_pull_batch(graph: Graph, unvisited: BatchedDenseFrontier,
                       current: BatchedDenseFrontier,
                       return_preds: bool = False):
    """Per-lane pull advance: vmap the dense CSC sweep over the batch
    axis (one shared edge-list sweep per lane, lockstep)."""
    def fn(u, c):
        return advance_pull(graph, DenseFrontier(u), DenseFrontier(c),
                            return_preds=return_preds)

    if return_preds:
        out, preds = jax.vmap(fn)(unvisited.flags, current.flags)
        return BatchedDenseFrontier(out.flags), preds
    out = jax.vmap(fn)(unvisited.flags, current.flags)
    return BatchedDenseFrontier(out.flags)


# ---------------------------------------------------------------------------
# filter
# ---------------------------------------------------------------------------


def _uniquify_exact(ids: jax.Array, keep: jax.Array, n: int) -> jax.Array:
    """Global scatter winner test: exactly one surviving lane per id.
    Single-lane; the batched filter vmaps it."""
    capacity = ids.shape[0]
    slot_of = jnp.full((n,), INVALID, jnp.int32)
    lane = jnp.arange(capacity, dtype=jnp.int32)
    safe = jnp.where(keep, ids, 0)
    slot_of = slot_of.at[safe].max(jnp.where(keep, lane, INVALID),
                                   mode="drop")
    return keep & (slot_of[safe] == lane)


def _uniquify_hash(ids: jax.Array, keep: jax.Array,
                   hash_size: int) -> jax.Array:
    """Heuristic history-hashtable culling (§5.2.1): removes only some
    duplicates, never valid items. Single-lane; vmapped by the batched
    filter."""
    capacity = ids.shape[0]
    lane = jnp.arange(capacity, dtype=jnp.int32)
    slot = jnp.where(keep, ids % hash_size, hash_size)
    h_id = jnp.full((hash_size + 1,), INVALID, jnp.int32)
    h_ln = jnp.full((hash_size + 1,), INVALID, jnp.int32)
    h_id = h_id.at[slot].set(ids, mode="drop")
    h_ln = h_ln.at[slot].set(lane, mode="drop")
    dup = (h_id[slot] == ids) & (h_ln[slot] != lane)
    return keep & ~dup


@jax.named_scope("op.filter")
def filter_frontier(frontier: SparseFrontier,
                    functor: Optional[Callable] = None, data=None,
                    n: Optional[int] = None, uniquify: str = "none",
                    cap: Optional[int] = None,
                    hash_size: int = 1024,
                    backend: Optional[str] = None,
                    use_kernel: Optional[bool] = None
                    ) -> tuple[SparseFrontier, object]:
    """Gunrock filter: predicate + compaction (+ optional uniquification).

    functor(ids, valid, data) -> (keep_mask, data')
    uniquify: 'none' | 'exact' (global scatter winner test) |
              'hash' (heuristic history-hashtable culling, §5.2.1 — removes
              only some duplicates, never valid items).
    The compaction dispatches through the "compact" registry entry (the
    Pallas filter_compact kernel under backend="pallas").
    """
    bk = B.resolve(backend, use_kernel)
    ids, valid = frontier.ids, frontier.valid_mask
    keep = valid
    if functor is not None:
        fkeep, data = functor(ids, valid, data)
        keep = keep & fkeep
    if uniquify == "exact":
        assert n is not None, "exact uniquify needs vertex count n"
        keep = _uniquify_exact(ids, keep, n)
    elif uniquify == "hash":
        keep = _uniquify_hash(ids, keep, hash_size)
    cap = frontier.capacity if cap is None else cap
    buf, length = compact_values(ids, keep, cap, backend=bk)
    return SparseFrontier(ids=buf, length=length), data


@jax.named_scope("op.filter")
def filter_frontier_batch(frontier: BatchedSparseFrontier,
                          functor: Optional[Callable] = None, data=None,
                          n: Optional[int] = None, uniquify: str = "none",
                          cap: Optional[int] = None,
                          hash_size: int = 1024,
                          backend: Optional[str] = None
                          ) -> tuple[BatchedSparseFrontier, object,
                                     jax.Array]:
    """Per-lane filter: predicate + compaction (+ uniquification).

    Same semantics as ``filter_frontier`` per lane; ``functor`` keeps its
    single-lane signature and is vmapped (batched problem data). Returns
    ``(frontier, data, overflow)`` where ``overflow`` (B,) counts the
    surviving items dropped by the output-capacity clamp — nonzero only
    when heuristic uniquification leaves more than ``cap`` duplicates, and
    the signal that a capped run must not be trusted silently.
    """
    bk = B.resolve(backend)
    ids, valid = frontier.ids, frontier.valid_mask
    keep = valid
    if functor is not None:
        fkeep, data = jax.vmap(functor)(ids, valid, data)
        keep = keep & fkeep
    if uniquify == "exact":
        assert n is not None, "exact uniquify needs vertex count n"
        keep = jax.vmap(lambda i, k: _uniquify_exact(i, k, n))(ids, keep)
    elif uniquify == "hash":
        keep = jax.vmap(lambda i, k: _uniquify_hash(i, k, hash_size))(
            ids, keep)
    cap = frontier.capacity if cap is None else cap
    buf, lengths, totals = compact_values_batch(ids, keep, cap, backend=bk)
    overflow = jnp.maximum(totals - cap, 0)
    return (BatchedSparseFrontier(ids=buf, lengths=lengths), data,
            overflow)


def partition_frontier(frontier: SparseFrontier, predicate: jax.Array,
                       cap_near: Optional[int] = None,
                       cap_far: Optional[int] = None,
                       backend: Optional[str] = None
                       ) -> tuple[SparseFrontier, SparseFrontier]:
    """Two-way split of a frontier (the 2-level priority queue, §5.1.5):
    items with predicate=True go to the near pile, others to the far pile."""
    valid = frontier.valid_mask
    near_mask = valid & predicate
    far_mask = valid & ~predicate
    cap_near = frontier.capacity if cap_near is None else cap_near
    cap_far = frontier.capacity if cap_far is None else cap_far
    nbuf, nlen = compact_values(frontier.ids, near_mask, cap_near,
                                backend=backend)
    fbuf, flen = compact_values(frontier.ids, far_mask, cap_far,
                                backend=backend)
    return (SparseFrontier(nbuf, nlen), SparseFrontier(fbuf, flen))


# ---------------------------------------------------------------------------
# neighborhood reduction
# ---------------------------------------------------------------------------


@jax.named_scope("op.neighborhood_reduce")
def neighborhood_reduce(graph: Graph, frontier: SparseFrontier, cap_out: int,
                        edge_map: Callable, reduce_op: str = "add",
                        init=None, data=None, strategy: str = "LB",
                        backend: Optional[str] = None) -> jax.Array:
    """Advance + per-source segmented reduction (paper §8.2.3).

    edge_map(src, dst, edge_id, valid, data) -> values (cap_out,)
    Returns (cap_in,) reduced values aligned with the input frontier lanes.
    """
    res, _ = advance(graph, frontier, cap_out, strategy=strategy,
                     backend=backend)
    vals = edge_map(res.src, res.dst, res.edge_id, res.valid, data)
    seg_fn = {"add": jax.ops.segment_sum, "max": jax.ops.segment_max,
              "min": jax.ops.segment_min}[reduce_op]
    neutral = {"add": 0.0, "max": -jnp.inf, "min": jnp.inf}[reduce_op]
    vals = jnp.where(res.valid, vals, jnp.asarray(neutral, vals.dtype))
    # in_pos is monotone for LB (slot order) and THREAD (CSR order) but
    # TWC returns order[in_pos] (grouped by size class), where the
    # sorted-indices fast path would be unsound
    out = seg_fn(vals, res.in_pos, num_segments=frontier.capacity,
                 indices_are_sorted=(strategy != "TWC"))
    if init is not None:
        out = jnp.where(frontier.valid_mask, out, init)
    return out


# ---------------------------------------------------------------------------
# segmented intersection (paper §4.3)
# ---------------------------------------------------------------------------


def _searchsorted_segment(haystack: jax.Array, lo: jax.Array, hi: jax.Array,
                          needles: jax.Array, iters: int = 32,
                          locate: bool = False) -> jax.Array:
    """Vectorized binary search of ``needles`` within haystack[lo:hi) per
    lane; returns True where found — or, with ``locate=True``, the
    matched position (−1 when absent; the value-gathering probe the
    semiring SpGEMM needs). The SmallLarge kernel's probe (§4.3)."""
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)

    def body(_, carry):
        lo_, hi_ = carry
        mid = (lo_ + hi_) // 2
        mid_val = haystack[jnp.clip(mid, 0, haystack.shape[0] - 1)]
        go_right = mid_val < needles
        lo_ = jnp.where(go_right & (lo_ < hi_), mid + 1, lo_)
        hi_ = jnp.where(~go_right & (lo_ < hi_), mid, hi_)
        return lo_, hi_

    lo_f, _ = jax.lax.fori_loop(0, iters, body, (lo, hi))
    in_range = lo_f < hi
    found_val = haystack[jnp.clip(lo_f, 0, haystack.shape[0] - 1)]
    found = in_range & (found_val == needles)
    if locate:
        return jnp.where(found, lo_f, -1).astype(jnp.int32)
    return found


class IntersectResult(NamedTuple):
    items: jax.Array      # (cap_out,) intersected vertex IDs (compacted)
    pair_of: jax.Array    # (cap_out,) which input pair produced the item
    length: jax.Array     # () int32
    counts: jax.Array     # (cap_in,) per-pair intersection sizes
    total: jax.Array      # () int32 global intersection count


@B.register("segment_search", B.XLA)
def _segment_search_xla(haystack: jax.Array, lo: jax.Array, hi: jax.Array,
                        needles: jax.Array) -> jax.Array:
    return _searchsorted_segment(haystack, lo, hi, needles)


@jax.named_scope("op.intersect")
def segmented_intersect(graph: Graph, fa: SparseFrontier, fb: SparseFrontier,
                        cap_out: int, *, backend: Optional[str] = None,
                        use_kernel: Optional[bool] = None
                        ) -> IntersectResult:
    """Intersect neighbor lists of paired items from two frontiers.

    Adjacency lists must be sorted (graph.from_edge_list guarantees it).
    Strategy: expand the *smaller* list of each pair (LB), binary-search each
    element in the larger list (SmallLarge scheme; TwoSmall is subsumed since
    a binary probe of a tiny list is equally cheap on the VPU). The
    expansion runs through the "advance" registry entry (so the fused
    Pallas kernel also serves intersection), the probe through
    "segment_search", the output compaction through "compact".
    """
    bk = B.resolve(backend, use_kernel)
    if graph.num_edges == 0:
        bk = B.XLA
    valid_pair = fa.valid_mask & fb.valid_mask
    a = jnp.where(valid_pair, fa.ids, 0)
    b = jnp.where(valid_pair, fb.ids, 0)
    deg_a = graph.row_offsets[a + 1] - graph.row_offsets[a]
    deg_b = graph.row_offsets[b + 1] - graph.row_offsets[b]
    a_small = deg_a <= deg_b
    small = jnp.where(a_small, a, b)
    large = jnp.where(a_small, b, a)
    sizes = jnp.where(valid_pair,
                      jnp.where(a_small, deg_a, deg_b), 0).astype(jnp.int32)
    # fused expansion: dst of the small-side advance IS the probe needle
    expand = B.dispatch("advance", bk, B.SINGLE)
    cols = B.storage_arg("advance", bk, B.SINGLE, graph=graph)
    _, needles, _, pair, _, exp_valid, _ = expand(
        graph.row_offsets, cols, small, sizes, cap_out)
    l_vert = large[pair]
    search = B.dispatch("segment_search", bk, B.SINGLE)
    # the probe binary-searches column VALUES in place, so it gets the
    # dense view (narrow dense compares fine; delta decodes once here)
    found = search(B.storage_arg("segment_search", bk, B.SINGLE,
                                 graph=graph),
                   graph.row_offsets[l_vert],
                   graph.row_offsets[l_vert + 1], needles)
    found = found & exp_valid
    counts = jax.ops.segment_sum(found.astype(jnp.int32), pair,
                                 num_segments=fa.capacity,
                                 indices_are_sorted=True)
    items, length = compact_values(needles, found, cap_out, backend=bk)
    pair_c, _ = compact_values(pair, found, cap_out, backend=bk)
    return IntersectResult(items=items, pair_of=pair_c, length=length,
                           counts=counts, total=jnp.sum(counts))


# ---------------------------------------------------------------------------
# compute
# ---------------------------------------------------------------------------


def compute(frontier: SparseFrontier, functor: Callable, data):
    """Per-element operation on all frontier elements (paper §3 'compute').

    functor(ids, valid, data) -> data'. XLA fuses this with neighbors.
    """
    return functor(jnp.where(frontier.valid_mask, frontier.ids, 0),
                   frontier.valid_mask, data)


# ---------------------------------------------------------------------------
# scatter helpers (atomic-replacement semantics, §5.2)
# ---------------------------------------------------------------------------


def scatter_min(values: jax.Array, index: jax.Array, valid: jax.Array,
                target: jax.Array) -> jax.Array:
    """atomicMin replacement: segment-min merged into ``target``."""
    safe_idx = jnp.where(valid, index, 0)
    big = jnp.asarray(jnp.inf, target.dtype) if jnp.issubdtype(
        target.dtype, jnp.floating) else jnp.iinfo(target.dtype).max
    vals = jnp.where(valid, values, big)
    return target.at[safe_idx].min(vals, mode="drop")


def scatter_add(values: jax.Array, index: jax.Array, valid: jax.Array,
                target: jax.Array) -> jax.Array:
    """atomicAdd replacement."""
    safe_idx = jnp.where(valid, index, 0)
    vals = jnp.where(valid, values, jnp.zeros((), target.dtype))
    return target.at[safe_idx].add(vals, mode="drop")


def scatter_or(index: jax.Array, valid: jax.Array,
               target: jax.Array) -> jax.Array:
    """Idempotent visited-bit set — no atomics needed (paper §5.2.1)."""
    safe_idx = jnp.where(valid, index, 0)
    return target.at[safe_idx].max(valid.astype(target.dtype), mode="drop")
