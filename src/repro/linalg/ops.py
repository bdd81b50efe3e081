"""Semiring sparse-linear-algebra operators (the GraphBLAST view).

Every frontier-engine hot path has an algebraic twin:

  advance + segment reduce      ↔  SpMV  y⟨m⟩ = A ⊗ x      (dense x)
  advance from a sparse frontier ↔ SpMSpV y⟨m⟩ = A ⊗ x     (sparse x)
  B batched advances             ↔  SpMM  Y⟨m⟩ = A ⊗ X     (dense n×k X)
  segmented intersection         ↔  masked SpGEMM  C⟨M⟩ = A ⊗ B

The three dense-output products are first-class backend-registry ops
(``"spmv"``, ``"spmm"``, ``"mxm"`` in ``repro.core.backend``): this
module registers the XLA implementations (gather + semiring segment
reduce — XLA fuses the ⊗ functor into the sweep) and
``repro.kernels.ops`` registers the Pallas ones (the fused
masked-semiring ELL row kernel + LB-expansion probe). The public
wrappers below resolve Graph vs raw-CSR inputs, masks/complement, and
static ELL metadata, then dispatch.

Registry contracts (shared by both backends):

  "spmv" (offsets, indices, values|None, x (nx,), sr, ell_width, mask|None,
          row_seg|None, over_pos|None, over_row|None)
         → y (n,)  f32
  "spmm" (offsets, indices, values|None, x (nx,k), sr, ell_width, mask|None,
          row_seg|None)
         → y (n,k) f32

  "mxm"  (a_off, a_idx, a_vals|None, bt_off, bt_idx, bt_vals|None,
          base (E,), probe_rows (E,), sr, cap_out)
         → c (E,) f32   — the dot formulation over a mask pattern;
           ``base`` rows of the expansion structure are LB-expanded
           (row-tiled by the advance kernels), each emitted column id is
           probed in ``probe_rows`` of the B-transpose structure, and
           matches are ⊗-combined and ⊕-reduced per mask edge.

``row_seg`` is the optional loop-invariant edge→row map ((m,) int32,
``Graph.row_seg`` / ``Graph.csc_row_seg`` build-time metadata). The XLA
sweep's segment reduce needs it every call; deriving it in-loop by
binary search was the single largest per-iteration cost of the PageRank
sweep. When absent (raw-CSR callers, sharded stacked slices) providers
derive it with the O(m) cumsum formulation — bit-identical, still ~3×
cheaper than searchsorted.

Masked-out rows carry the semiring's ⊕-identity. ``values=None`` means a
structural (pattern-only) matrix: every stored entry is the ⊗-identity.

The same three ops carry ``placement="sharded"`` providers
(``repro.core.distributed``) that accept the (p, …) stacked per-device
slices of a ``ShardedGraph`` and run under shard_map; the public
wrappers route a ShardedGraph operand there automatically, and results
bit-match the single-device sweeps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as B
from repro.core import operators as _ops
from repro.core import storage as St
from repro.core.graph import Graph

from . import semiring as S
from .semiring import Semiring, plus_times

# ---------------------------------------------------------------------------
# XLA implementations
# ---------------------------------------------------------------------------


def _row_segments(offsets: jax.Array, m: int) -> jax.Array:
    from repro.core.graph import row_segments_of
    return row_segments_of(offsets, m)


def _apply_mask(y: jax.Array, mask: Optional[jax.Array], zero: float):
    if mask is None:
        return y
    m = mask if y.ndim == 1 else mask[:, None]
    return jnp.where(m, y, zero)


def hybrid_ell_reduce(offsets, indices, values, x, sr: Semiring,
                      width: int, *, over_pos=None, over_row=None,
                      row_seg=None, edge_valid=None):
    """Shared hybrid row reduction: y[i] = ⊕ over row i's edges of
    (values ⊗ x[dst]) — the XLA twin of the Pallas ELL kernel, designed
    for *placement-stable bits* (the PR-4 discipline: explicit
    elementwise dataflow only, no compiler-grouped reduces, no
    division):

      * the first ``width`` edges of each row land in a rank-aligned
        (rows, pow2(width)) block (pure gathers) and are ⊕-folded by an
        EXPLICIT pairwise halving tree — the grouping is the dataflow,
        so the single-device sweep and every shard_map row slice compute
        identical bits for identical rows;
      * edges past ``width`` (the heavy-tail remainder) continue the
        fold through the serial ⊕-scatter, in ascending edge order —
        either compacted build-time lists (``over_pos``/``over_row``,
        the fast single-device path: only ~the 95th-percentile overflow
        pays the serial scatter) or a masked drop-scatter over all edges
        (the per-shard path, where no compacted metadata exists; same
        per-row sequence, same bits).

    ``edge_valid`` masks padding lanes of stacked per-shard edge arrays.
    Returns the raw (rows,) folded vector — callers clamp empty rows and
    apply masks.
    """
    nrows = int(offsets.shape[0]) - 1
    m = St.store_num_edges(indices)
    width = max(int(width), 1)
    wp = 1
    while wp < width:
        wp *= 2
    starts = offsets[:-1]
    deg = offsets[1:] - offsets[:-1]
    lanes = jnp.arange(wp, dtype=jnp.int32)
    e = jnp.minimum(starts[:, None] + lanes[None, :], max(m - 1, 0))
    lane_ok = lanes[None, :] < jnp.minimum(deg, width)[:, None]
    # gather_cols decodes the touched (row, lane) slots in place when the
    # store is delta-encoded — the ELL block never materializes dense ids
    xi = x[jnp.clip(St.gather_cols(indices, e), 0,
                    x.shape[0] - 1)]                  # pad ids may be -1
    prod = sr.round_prod(xi) if values is None else sr.mul_op(values[e], xi)
    prod = jnp.where(lane_ok, prod, sr.zero)
    k = wp
    while k > 1:                      # explicit halving: grouping fixed
        k //= 2
        prod = sr.add_op(prod[:, :k], prod[:, k:2 * k])
    y = prod[:, 0]
    if over_pos is not None:
        if int(over_pos.shape[0]):
            ov = x[St.gather_cols(indices, over_pos)]
            ov = (sr.round_prod(ov) if values is None
                  else sr.mul_op(values[over_pos], ov))
            y = sr.scatter_accum(y, over_row, ov)
        return y
    # masked drop-scatter fallback (per-shard): rank ≥ width continues
    # the fold, everything else targets the drop slot
    seg = _row_segments(offsets, m) if row_seg is None else row_seg
    rank = jnp.arange(m, dtype=jnp.int32) - starts[seg]
    over = rank >= width
    if edge_valid is not None:
        over = over & edge_valid
    ov = x[jnp.clip(St.decode_cols(indices), 0, x.shape[0] - 1)]
    ov = sr.round_prod(ov) if values is None else sr.mul_op(values, ov)
    return sr.scatter_accum(y, jnp.where(over, seg, nrows), ov)


def fold_products(offsets, prods, sr: Semiring, width: int, *,
                  row_seg=None, edge_valid=None):
    """``hybrid_ell_reduce``'s product-level twin for pre-multiplied
    edge buffers: fold an (m,) per-slot product vector into per-row
    values with the IDENTICAL dataflow — same rank-aligned ELL gather,
    same explicit pairwise halving tree, same ascending-order overflow
    drop-scatter. A caller that ⊕-merged per-edge products across
    devices first (the 2-D vertex cut's pre-fold product exchange,
    where disjoint slot ownership makes the merge identity-only) then
    lands on the same bits as the single-device sweep for EVERY
    semiring. ``prods`` is indexed by CSR slot; slots past
    ``offsets[-1]`` are padding that ``edge_valid`` masks off the
    overflow scatter (the ELL lanes never touch them)."""
    nrows = int(offsets.shape[0]) - 1
    m = int(prods.shape[0])
    width = max(int(width), 1)
    wp = 1
    while wp < width:
        wp *= 2
    starts = offsets[:-1]
    deg = offsets[1:] - offsets[:-1]
    lanes = jnp.arange(wp, dtype=jnp.int32)
    e = jnp.minimum(starts[:, None] + lanes[None, :], max(m - 1, 0))
    lane_ok = lanes[None, :] < jnp.minimum(deg, width)[:, None]
    p = jnp.where(lane_ok, prods[e], sr.zero)
    k = wp
    while k > 1:                      # explicit halving: grouping fixed
        k //= 2
        p = sr.add_op(p[:, :k], p[:, k:2 * k])
    y = p[:, 0]
    seg = _row_segments(offsets, m) if row_seg is None else row_seg
    rank = jnp.arange(m, dtype=jnp.int32) - starts[seg]
    over = rank >= width
    if edge_valid is not None:
        over = over & edge_valid
    return sr.scatter_accum(y, jnp.where(over, seg, nrows), prods)


@B.register("spmv", B.XLA, encodings=("dense", "delta"))
def _spmv_xla(offsets, indices, values, x, sr: Semiring, ell_width, mask,
              row_seg=None, over_pos=None, over_row=None):
    """Hybrid ELL-tree + overflow-scatter sweep when the Graph's static
    width metadata is available (the hot path — PageRank's loop lives
    here); gather + semiring segment reduce otherwise (raw-CSR callers,
    bit-identical to the pre-refactor pagerank sweep). ``indices`` may
    be a delta-encoded store: the ELL block decodes per touched slot
    (gather_cols); the whole-edge fallback decodes vectorized."""
    n = int(offsets.shape[0]) - 1
    m = St.store_num_edges(indices)
    if ell_width is not None and m > 0 and over_pos is not None:
        y = hybrid_ell_reduce(offsets, indices, values, x, sr,
                              int(ell_width), over_pos=over_pos,
                              over_row=over_row)
    else:
        seg = _row_segments(offsets, m) if row_seg is None else row_seg
        xv = x[St.decode_cols(indices)]
        prod = sr.round_prod(xv) if values is None else sr.mul_op(values, xv)
        y = sr.segment_reduce(prod, seg, n, indices_are_sorted=True)
    deg = offsets[1:] - offsets[:-1]
    y = jnp.where(deg > 0, y, sr.zero)  # empty rows ⇒ ⊕-identity
    return _apply_mask(y, mask, sr.zero).astype(jnp.float32)


@B.register("spmm", B.XLA, encodings=("dense", "delta"))
def _spmm_xla(offsets, indices, values, x, sr: Semiring, ell_width, mask,
              row_seg=None):
    del ell_width
    n = int(offsets.shape[0]) - 1
    m = St.store_num_edges(indices)
    seg = _row_segments(offsets, m) if row_seg is None else row_seg
    xv = x[St.decode_cols(indices)]                   # (m, k)
    prod = (sr.round_prod(xv) if values is None
            else sr.mul_op(values[:, None], xv))
    y = sr.segment_reduce(prod, seg, n, indices_are_sorted=True)
    deg = offsets[1:] - offsets[:-1]
    y = jnp.where((deg > 0)[:, None], y, sr.zero)
    return _apply_mask(y, mask, sr.zero).astype(jnp.float32)


def _locate_xla(haystack: jax.Array, lo: jax.Array, hi: jax.Array,
                needles: jax.Array) -> jax.Array:
    """Position-returning probe (−1 when absent): the ``locate`` flavour
    of the shared SmallLarge binary search in core.operators."""
    return _ops._searchsorted_segment(haystack, lo, hi, needles,
                                      locate=True)


def make_mxm_impl(expand, locate):
    """Build a masked-SpGEMM registry impl from an LB-expansion hot path
    (the "advance" contract) and a position-returning probe. The same
    machinery serves both backends: xla passes the jnp expansion and
    search, kernels.ops passes the fused Pallas kernels."""

    def impl(a_off, a_idx, a_vals, bt_off, bt_idx, bt_vals,
             base, probe_rows, sr: Semiring, cap_out: int):
        e = int(base.shape[0])
        sizes = (a_off[base + 1] - a_off[base]).astype(jnp.int32)
        # row-tiled expansion of the mask edges' expansion-side rows: the
        # emitted column id IS the probe needle, in_pos the mask edge.
        _, needles, eid, pair, _, valid, _ = expand(
            a_off, a_idx, base, sizes, cap_out)
        rows = probe_rows[pair]
        pos = locate(bt_idx, bt_off[rows], bt_off[rows + 1], needles)
        found = (pos >= 0) & valid
        sv = (jnp.float32(sr.one) if a_vals is None
              else a_vals[jnp.clip(eid, 0, int(a_idx.shape[0]) - 1)])
        lv = (jnp.float32(sr.one) if bt_vals is None
              else bt_vals[jnp.clip(pos, 0, int(bt_idx.shape[0]) - 1)])
        prod = jnp.where(found, sr.mul_op(sv, lv), sr.zero)
        c = sr.segment_reduce(prod.astype(jnp.float32), pair, e,
                              indices_are_sorted=True)
        return jnp.where(sizes > 0, c, sr.zero).astype(jnp.float32)

    return impl


_mxm_xla = B.register("mxm", B.XLA)(
    make_mxm_impl(_ops._advance_xla, _locate_xla))


# ---------------------------------------------------------------------------
# public wrappers
# ---------------------------------------------------------------------------


def _csr_side(a, transpose: bool):
    """Resolve (offsets, store, values, ell_width, row_seg) from a
    Graph / ShardedGraph (CSR or its CSC mirror) or a raw (offsets,
    indices, values) triple. The column slot is the graph's *native*
    store (dense at the plan dtype, or the EncodedCols delta pytree) —
    wrappers run it through ``B.coerce_store`` for the provider that
    will execute. A ShardedGraph yields the (p, …) stacked per-device
    slices the sharded registry providers understand (its per-shard
    edge→row maps are derived locally, so row_seg is None). A
    Sharded2DGraph yields (R, C, …) blocked arrays with Blocks2D column
    stores for the 2d providers."""
    from repro.core.partition import Sharded2DGraph, ShardedGraph
    if isinstance(a, (Graph, ShardedGraph, Sharded2DGraph)):
        if transpose:
            if not a.has_csc:
                raise ValueError("transpose=True needs the CSC mirror "
                                 "(build_csc=True)")
            return (a.csc_offsets, a.csc_store, a.csc_edge_values,
                    a.csc_ell_width, a.csc_row_seg, a.csc_over_pos,
                    a.csc_over_row)
        return (a.row_offsets, a.col_store, a.edge_values, a.ell_width,
                a.row_seg, a.over_pos, a.over_row)
    if transpose:
        raise ValueError(
            "a raw (offsets, indices, values) triple carries no CSC "
            "mirror to transpose through; pass a Graph, or pass the "
            "transposed structure explicitly (for mxm: b_transpose=True "
            "with bᵀ's CSR)")
    offsets, indices, values = a
    return offsets, indices, values, None, None, None, None


def _resolve_mask(mask, complement: bool):
    if mask is None:
        if complement:
            raise ValueError("complement=True requires a mask")
        return None
    mask = jnp.asarray(mask)
    if mask.dtype != jnp.bool_:
        mask = mask.astype(bool)
    return ~mask if complement else mask


def _ell_or_raise(ell_width, meta, bk: str):
    if ell_width is None:
        ell_width = meta
    if ell_width is None and bk == B.PALLAS:
        raise ValueError(
            "the pallas backend needs a static ELL width; build the Graph "
            "via Graph.from_csr / from_edge_list (width is computed once "
            "at build time) or pass ell_width= explicitly")
    return None if ell_width is None else int(ell_width)


@jax.named_scope("op.spmv")
def spmv(a, x, *, semiring=plus_times, mask=None, complement: bool = False,
         transpose: bool = False, structural: bool = False,
         ell_width: Optional[int] = None, backend: Optional[str] = None,
         use_kernel: Optional[bool] = None,
         placement: Optional[str] = None,
         precision: str = "fp32") -> jax.Array:
    """Masked semiring SpMV: ``y⟨mask⟩ = A ⊗ x`` (y (n,), x dense).

    ``transpose=True`` multiplies by Aᵀ via the CSC mirror (the pull /
    PageRank direction). ``structural=True`` ignores stored edge values
    (every entry is the ⊗-identity). ``mask`` is a (n,) output row mask;
    ``complement=True`` flips it. Masked-out rows hold the ⊕-identity.
    ``a`` may be a ``ShardedGraph`` (``partition_1d(...).shard(mesh)``):
    the sweep then runs row-partitioned under shard_map and bit-matches
    the single-device result. ``precision="bf16"`` rounds the ⊗ operands
    to bfloat16 (fp32 accumulate); only the plus-accumulating semirings
    admit it (see semiring.with_precision).
    """
    sr = S.with_precision(semiring, precision)
    bk = B.resolve(backend, use_kernel)
    pl, ctx = B.resolve_graph_placement(a, placement)
    off, idx, vals, meta_w, seg, opos, orow = _csr_side(a, transpose)
    idx = B.coerce_store("spmv", bk, pl, store=idx)
    if structural:
        vals = None
    w = _ell_or_raise(ell_width, meta_w, bk if pl == B.SINGLE else B.XLA)
    m = _resolve_mask(mask, complement)
    x = jnp.asarray(x, jnp.float32)
    with ctx:
        return B.dispatch("spmv", bk, pl)(off, idx, vals, x, sr, w, m,
                                          seg, opos, orow)


@jax.named_scope("op.spmm")
def spmm(a, x, *, semiring=plus_times, mask=None, complement: bool = False,
         transpose: bool = False, structural: bool = False,
         ell_width: Optional[int] = None, backend: Optional[str] = None,
         use_kernel: Optional[bool] = None,
         placement: Optional[str] = None,
         precision: str = "fp32") -> jax.Array:
    """Dense-accumulator semiring SpMM: ``Y⟨mask⟩ = A ⊗ X`` (X (nx, k)).

    The whole-frontier batched product: each column of X is one lane
    (a reachability source, a label block). Same mask/transpose/
    structural/placement/precision semantics as ``spmv``.
    """
    sr = S.with_precision(semiring, precision)
    bk = B.resolve(backend, use_kernel)
    pl, ctx = B.resolve_graph_placement(a, placement)
    off, idx, vals, meta_w, seg, _, _ = _csr_side(a, transpose)
    idx = B.coerce_store("spmm", bk, pl, store=idx)
    if structural:
        vals = None
    w = _ell_or_raise(ell_width, meta_w, bk if pl == B.SINGLE else B.XLA)
    m = _resolve_mask(mask, complement)
    x = jnp.asarray(x, jnp.float32)
    if x.ndim != 2:
        raise ValueError(f"spmm needs a dense (n, k) operand, got {x.shape}")
    with ctx:
        return B.dispatch("spmm", bk, pl)(off, idx, vals, x, sr, w, m,
                                          seg)


@jax.named_scope("op.spmsv")
def spmsv(a, ids, xvals=None, *, semiring=plus_times, mask=None,
          complement: bool = False, structural: bool = False,
          cap_out: Optional[int] = None, backend: Optional[str] = None,
          use_kernel: Optional[bool] = None) -> jax.Array:
    """Sparse-vector semiring product (SpMSpV, push direction):
    ``y⟨mask⟩[v] = ⊕_{u active} x[u] ⊗ A[u, v]`` with x given sparsely as
    frontier ``ids`` (−1 ⇒ dead lane) and per-lane ``xvals`` (None ⇒
    ⊗-identity). This is exactly an advance whose functor is ⊗ and whose
    scatter is ⊕ — it dispatches the expansion through the "advance"
    registry entry, so the fused Pallas kernel serves the algebra too.
    Output is dense (n,) — the direction-optimization contract: callers
    pick spmsv (push) for small frontiers and spmv (pull) for large ones.
    """
    from repro.core.partition import Sharded2DGraph, ShardedGraph
    if isinstance(a, (ShardedGraph, Sharded2DGraph)):
        raise ValueError(
            "spmsv has no sharded/2d provider (the push expansion is "
            "frontier-shaped); use spmv/spmm on the partitioned graph, "
            "or run spmsv on the unpartitioned source graph")
    sr = S.get(semiring)
    bk = B.resolve(backend, use_kernel)
    off, idx, vals, _, _, _, _ = _csr_side(a, transpose=False)
    # spmsv's expansion runs the "advance" hot path, whose providers
    # decode the delta stream natively — coerce against that op
    idx = B.coerce_store("advance", bk, B.SINGLE, store=idx)
    if structural:
        vals = None
    n = int(off.shape[0]) - 1
    m = St.store_num_edges(idx)
    ids = jnp.asarray(ids, jnp.int32)
    valid_in = ids >= 0
    base = jnp.where(valid_in, ids, 0)
    deg = off[base + 1] - off[base]
    sizes = jnp.where(valid_in, deg, 0).astype(jnp.int32)
    if cap_out is None:
        # duplicate frontier ids expand their row once PER lane, so a
        # plain m default under-counts; outside jit (the wrapper's
        # normal life) size the expansion exactly — host-side capacity
        # planning, like every frontier cap. Under jit nothing concrete
        # is available and a guessed cap would truncate silently, so
        # demand an explicit static one.
        if isinstance(ids, jax.core.Tracer) or \
                isinstance(off, jax.core.Tracer):
            raise ValueError(
                "spmsv under jit needs an explicit static cap_out "
                "(the exact default sizing is host-side; a guessed "
                "capacity would silently truncate duplicate-id "
                "expansions)")
        ro = np.asarray(off)
        live = np.asarray(ids)
        live = live[live >= 0]
        cap = int((ro[live + 1] - ro[live]).sum()) if len(live) else 1
    else:
        cap = int(cap_out)
    expand = B.dispatch("advance", bk, B.SINGLE)
    _, dst, eid, in_pos, _, exp_valid, _ = expand(off, idx, base, sizes,
                                                  max(cap, 1))
    sv = (jnp.float32(sr.one) if xvals is None
          else jnp.asarray(xvals, jnp.float32)[in_pos])
    av = (jnp.float32(sr.one) if vals is None
          else vals[jnp.clip(eid, 0, max(m - 1, 0))])
    prod = jnp.where(exp_valid, sr.mul_op(sv, av), sr.zero)
    tgt = jnp.where(exp_valid, dst, n)            # n ⇒ dropped
    y = jnp.full((n,), sr.zero, jnp.float32)
    y = sr.scatter_accum(y, tgt, prod.astype(jnp.float32))
    return _apply_mask(y, _resolve_mask(mask, complement), sr.zero)


@jax.named_scope("op.mxm")
def mxm(a, b, mask, *, semiring=plus_times, b_transpose: bool = False,
        structural: bool = False, cap_out: Optional[int] = None,
        backend: Optional[str] = None,
        use_kernel: Optional[bool] = None,
        placement: Optional[str] = None) -> jax.Array:
    """Row-tiled masked semiring SpGEMM (dot formulation):
    ``C⟨M⟩ = A ⊗ B`` computed only at the mask pattern.

    ``mask`` is the nnz pattern of M as ``(src_ids, dst_ids)`` int
    arrays; the result is ``c (E,)`` with
    ``c[e] = ⊕_w A[src_e, w] ⊗ B[w, dst_e]``.

    ``b_transpose=True`` computes ``A ⊗ bᵀ`` — column ``dst_e`` of B is
    then row ``dst_e`` of b's CSR (the triangle-counting case
    ``C = A ⊗ Aᵀ``); otherwise b's CSC mirror provides column access.

    When both operands share one structure (``C = A ⊗ Aᵀ``), each mask
    edge expands its *smaller* endpoint row and probes the larger — the
    SmallLarge workload reduction of paper §4.3, sound here because the
    dot is symmetric in the two rows and every supported ⊗ commutes.
    Capacity planning (``cap_out``) is host-side, like every frontier
    capacity in this engine; call the wrapper outside jit.

    Sharded: pass a ``ShardedGraph`` as ``a`` (the expansion side is
    row-partitioned over the mesh) with a plain Graph as ``b`` (the
    probe side stays replicated — the 1-D SpGEMM split). The SmallLarge
    swap is disabled there (the sides live in different layouts).
    """
    from repro.core.partition import Sharded2DGraph, ShardedGraph
    sr = S.get(semiring)
    bk = B.resolve(backend, use_kernel)
    pl, ctx = B.resolve_graph_placement(a, placement)
    if isinstance(b, (ShardedGraph, Sharded2DGraph)):
        # the probe side is ALWAYS replicated (the 1-D SpGEMM split):
        # stacked per-device slices can neither be probed globally nor
        # feed the single-device path's degree planning
        raise ValueError(
            "mxm keeps the probe side (b) replicated; pass the "
            "expansion side (a) as a ShardedGraph and b as a plain "
            "Graph (e.g. pg.source)")
    a_off, a_idx, a_vals = _csr_side(a, transpose=False)[:3]
    bt_off, bt_idx, bt_vals = _csr_side(b, transpose=not b_transpose)[:3]
    # decide shared-structure on the native stores (identity), THEN
    # coerce — decoding twice would break the `is` check and the
    # SmallLarge swap with it
    shared_store = (a_off is bt_off) and (a_idx is bt_idx)
    a_idx = B.coerce_store("mxm", bk, pl, store=a_idx)
    bt_idx = a_idx if shared_store else B.coerce_store("mxm", bk, pl,
                                                       store=bt_idx)
    if structural:
        a_vals = bt_vals = None
    msrc = np.asarray(mask[0], np.int32)
    mdst = np.asarray(mask[1], np.int32)
    if pl == B.SHARDED:
        # stacked (p, vpp+1) offsets → global out-degrees, pads → 0
        deg_all = np.diff(np.asarray(a_off), axis=1).reshape(-1)
        deg_a = deg_all[:a.num_vertices][msrc]
    elif pl == B.TWOD:
        # (R, C, vpr+1) block offsets: a row's global out-degree is the
        # SUM of its per-column-block degrees
        deg_all = np.diff(np.asarray(a_off), axis=2).sum(axis=1) \
                    .reshape(-1)
        deg_a = deg_all[:a.num_vertices][msrc]
    else:
        deg_a = np.diff(np.asarray(a_off))[msrc]
    deg_b = np.diff(np.asarray(bt_off))[mdst]
    shared = shared_store
    if shared:
        a_small = deg_a <= deg_b
        base = np.where(a_small, msrc, mdst)
        probe_rows = np.where(a_small, mdst, msrc)
        cap = int(np.minimum(deg_a, deg_b).sum())
    else:
        base, probe_rows = msrc, mdst
        cap = int(deg_a.sum())
    cap = max(cap, 1) if cap_out is None else int(cap_out)
    impl = B.dispatch("mxm", bk, pl)
    mesh_key = ((a.mesh, a.axis) if pl == B.SHARDED
                else (a.mesh, a.axes) if pl == B.TWOD else None)
    with ctx:
        run = _jit_mxm(impl, sr, cap, mesh_key)
        return run(a_off, a_idx, a_vals, bt_off, bt_idx, bt_vals,
                   jnp.asarray(base, jnp.int32),
                   jnp.asarray(probe_rows, jnp.int32))


@functools.lru_cache(maxsize=64)
def _jit_mxm(impl, sr: Semiring, cap: int, mesh_key=None):
    """One cached jit wrapper per (impl, semiring, capacity, mesh) —
    repeated mxm calls of the same shape reuse one trace. ``mesh_key``
    keys sharded traces by their (mesh, axis) so a cached program can
    never run against the wrong mesh."""
    return jax.jit(lambda *args: impl(*args, sr, cap))
