"""Distributed graph primitives + the sharded registry providers
(paper §8.2.1; Pan et al. [56]).

Gunrock's multi-GPU design keeps the single-GPU engine unchanged and
adds communication + partition modules; we do the same, but behind the
backend registry's *placement* dimension: this module registers the
``placement="sharded"`` providers for the operator hot paths, so the
same dispatch that picks xla-vs-pallas kernels also picks
single-vs-mesh execution.

The 1-D partition (partition.py) gives each device a CSR slice (and a
CSC slice when the source graph carries the mirror); the providers run
under ``shard_map`` with two exchange strategies:

  * "advance" (sharded) — bitmask exchange: each device expands its
    owned frontier slice into a *global* discovered bitmask and the
    masks are OR-combined with an all-reduce. O(n) bytes/device/step,
    independent of frontier raggedness — the BSP-safe translation of
    Gunrock's frontier segment exchange (which needed p2p queues).
    Contract (called INSIDE an active shard_map):
      (local_ro (vpp+1,), local_ci (me,), frontier (n,), base (),
       vpp, axis) → (n,) bool discovered mask, already all-reduced.
  * "spmv"/"spmm" (sharded) — classic 1-D row-partitioned products:
    the dense operand stays replicated (the all-gather side), each
    device reduces its owned rows locally with exactly the
    single-device gather+segment formulation, and the row blocks
    concatenate — no reduction crosses devices, so results are
    bit-identical to the single-device sweep. Same positional contract
    as the single providers, with (p, …) stacked CSR operands.
  * "mxm" (sharded) — 1-D SpGEMM: the expansion side is row-partitioned
    (each device expands the mask edges whose base row it owns), the
    probe side stays replicated, and per-edge partials ⊕-combine across
    the mesh (disjoint ownership ⇒ identity merge ⇒ bit parity).

Traversal loops (BFS / SSSP / CC) run whole-loop inside one shard_map
with replicated (n,)-sized state and local edge sweeps; every state
update is an exact min/OR combine, so labels and distances bit-match
the single-device primitives. The 2-D BFS runs a (B,) batch of sources
in one loop ((B, n) state, one program per batch); the 1-D BFS and
both SSSP placements serve one source per program. All impls are
module-level jits with the mesh as a static argument — repeated calls
(the serving driver) reuse one trace per (shape, mesh).

placement="2d" (the vertex-cut R×C mesh, ``partition_2d``) registers a
second provider family with different exchange geometry:

  * "advance"/"advance_filter" (2d) — batched chunked bitmask
    exchange: device (i, j) expands its edge block from a (B, n) batch
    of frontiers into (B, vpc) *column-chunk* masks, the R devices
    of each mesh column psum-OR their chunks (row-axis collective), and
    the C chunks all-gather along the column axis into the global
    (B, n) mask (the mirror-merge: every mirror's discoveries fold into
    the owner chunk's lane). The chunk exchange is DOUBLE-BUFFERED over
    static edge tiles — the psum for tile t is consumed one loop
    iteration after it is issued, so tile t+1's local gathers overlap
    the collective (XLA overlaps the in-flight psum with the next
    tile's scatter; OR is idempotent and order-free, so the overlap
    cannot change bits). Per-device bytes/step drop from the 1-D
    2·(p−1)/p·n·4 per source to
    B·(tiles·2·(R−1)/R·vpc + (C−1)·vpc) uint8 lanes for a batch. The
    block slots' source rows are computed once per program
    (``_block_slots``), outside the level loop. The local expansion
    runs under ``op.advance_filter`` (or ``op.advance``), the row psum
    and the column all-gather under ``op.exchange``.
  * "spmv"/"spmm" (2d) — pre-fold product exchange: each device
    computes its block's per-edge products (bit-identical IEEE ops),
    scatters them at their ``Blocks2D.epos`` slots into one
    ⊕-identity-background (chunk_emax,) buffer, and the mesh row
    ⊕-all-reduces — slots are DISJOINT across the row, so the combine
    merges identities only and is exact for every semiring. The merged
    chunk then replays the exact single-device per-row fold
    (``fold_products``, the product-level twin of hybrid_ell_reduce),
    keeping PR-4 bit parity through the vertex cut.
  * "mxm" (2d) — both axes expand their block slices of the owned mask
    rows; per-edge partials ⊕-combine over the whole mesh (exact for
    the exact-⊕ and integer-sum semirings, which covers the tc
    workload; arbitrary-float plus-times SpGEMM regroups, documented).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P
from jax import shard_map

from . import backend as B
from .partition import (Partitioned2DGraph, PartitionedGraph,
                        check_mesh_axes, check_mesh_axis, unpad_chunks)

# a plain Python int on purpose: this module is imported LAZILY by the
# registry, possibly in the middle of someone else's jit trace, and a
# module-level jnp constant created there would be a leaked tracer
INT_BIG = 2 ** 30


class DistBFSResult(NamedTuple):
    labels: jax.Array      # (B, n) global depths; (n,) for one source
    iterations: jax.Array  # (B,) per-lane BSP iterations; () for one


class DistSSSPResult(NamedTuple):
    dist: jax.Array        # (n,) float32 distances
    iterations: jax.Array


class DistCCResult(NamedTuple):
    labels: jax.Array
    num_components: jax.Array
    iterations: jax.Array


# how many static edge tiles the 2-D bitmask exchange double-buffers
# over (the comm–compute overlap depth); 1 disables the overlap
DEFAULT_EXCHANGE_TILES = 2


def _axes_arg(axis) -> tuple:
    """Normalize the ``axis`` argument of the distributed entry points
    for a 2-D partition: an explicit (row, col) pair passes through, the
    1-D default name maps to the canonical ("row", "col") axes."""
    if isinstance(axis, (tuple, list)):
        if len(axis) != 2:
            raise ValueError(f"2-D placement needs two mesh axes, got "
                             f"{tuple(axis)}")
        return tuple(axis)
    return ("row", "col")


def _check_mesh(pg, mesh: Mesh, axis) -> None:
    if isinstance(pg, Partitioned2DGraph):
        check_mesh_axes(mesh, _axes_arg(axis), (pg.rows, pg.cols))
    else:
        check_mesh_axis(mesh, axis, pg.num_parts)


def _shard_any(pg, mesh: Mesh, axis):
    """Shard either partition container on its mesh (the entry-point
    glue that keeps 1-D and 2-D one code path, not a fork)."""
    if isinstance(pg, Partitioned2DGraph):
        return pg.shard(mesh, _axes_arg(axis))
    return pg.shard(mesh, axis)


def _require_placement_mesh():
    ctx = B.placement_mesh()
    if ctx is None:
        raise RuntimeError(
            "distributed dispatch needs an active placement context "
            "that carries a mesh: with backend.use_placement('sharded', "
            "mesh=mesh, axis='graph'): ... (or '2d' with "
            "axis=('row', 'col'))")
    return ctx


def _require_2d_mesh():
    mesh, axes = _require_placement_mesh()
    if not (isinstance(axes, tuple) and len(axes) == 2):
        raise RuntimeError(
            "2d providers need a (row, col) mesh-axis pair: "
            "use_placement('2d', mesh=mesh, axis=('row', 'col')) — "
            f"got axis={axes!r}")
    return mesh, axes


def _all_reduce(sr, x: jax.Array, axis: str) -> jax.Array:
    """⊕-combine per-device partials across the mesh axis."""
    if sr.add == "plus":
        return jax.lax.psum(x, axis)
    if sr.add == "min":
        return jax.lax.pmin(x, axis)
    return jax.lax.pmax(x, axis)          # max | or


# ---------------------------------------------------------------------------
# local sweeps (the per-device half of each exchange strategy)
# ---------------------------------------------------------------------------


def _slot_rows(offsets: jax.Array, slots: int, rows: int) -> jax.Array:
    """Local source row of each of ``slots`` CSR slots, clamped to
    [0, rows − 1] (pad slots past ``offsets[-1]`` land on the last row):
    the cumsum form of ``clip(searchsorted(offsets, slot, 'right') − 1)``
    (``graph.row_segments_of``), bit-identical to it and O(slots)."""
    from .graph import row_segments_of
    return jnp.minimum(row_segments_of(offsets, slots), rows - 1)


def _local_slots(local_ro: jax.Array, local_ci: jax.Array, vpp: int):
    """Map local CSR slots back to (local source row, validity)."""
    me = local_ci.shape[0]
    slot = jnp.arange(me, dtype=jnp.int32)
    src_local = _slot_rows(local_ro, me, vpp)
    valid = (slot < local_ro[-1]) & (local_ci >= 0)
    return src_local, valid


def _local_expand_mask(local_ro, local_ci, frontier_slice, n, vpp):
    """Expand the owned frontier slice; return a global discovered bitmask.

    frontier_slice: (vpp,) bool of owned active vertices.
    Dense formulation: every local CSR slot whose source vertex is active
    marks its destination.
    """
    src_local, valid = _local_slots(local_ro, local_ci, vpp)
    active = frontier_slice[src_local] & valid
    mask = jnp.zeros((n,), bool)
    tgt = jnp.where(active, local_ci, n)
    mask = mask.at[tgt].set(True, mode="drop")
    return mask


# ---------------------------------------------------------------------------
# sharded registry providers
# ---------------------------------------------------------------------------


def _owned_slice(vec: jax.Array, base, vpp: int, fill=0):
    """The (…, vpp) owned slice of a replicated (…, n) vector along its
    last axis, correct for the padded tail part: ``dynamic_slice``
    CLAMPS an out-of-range start, so slicing (n,) state directly would
    hand the tail part a shifted window whenever p·vpp > n — pad by one
    part first so every start is in range (pad lanes belong to no real
    row and never survive the validity masks)."""
    pad = [(0, 0)] * (vec.ndim - 1) + [(0, vpp)]
    padded = jnp.pad(vec, pad, constant_values=fill)
    return jax.lax.dynamic_slice_in_dim(padded, base, vpp, axis=-1)


@B.register("advance", B.XLA, B.SHARDED)
def _advance_bitmask_exchange(local_ro, local_ci, frontier, base, vpp: int,
                              axis: str):
    """Bitmask-exchange advance step — see the module docstring contract.
    Must be called inside an active shard_map over ``axis``."""
    n = frontier.shape[0]
    my_slice = _owned_slice(frontier, base, vpp)
    disc = _local_expand_mask(local_ro, local_ci, my_slice, n, vpp)
    return jax.lax.psum(disc.astype(jnp.int32), axis) > 0


@B.register("spmm", B.XLA, B.SHARDED)
def _spmm_sharded(offsets, indices, values, x, sr, ell_width, mask,
                  row_seg=None):
    """1-D row-partitioned semiring SpMM: Y⟨mask⟩ = A ⊗ X.

    ``offsets``/``indices``/``values`` are (p, …) stacked per-device row
    slices; ``x`` (n, k) and ``mask`` (n,) stay replicated. Each device
    reduces its owned rows with the single-device gather+segment
    formulation (bit parity); row blocks concatenate over the mesh axis.
    Requires a square operand (the 1-D vertex partition), i.e.
    x.shape[0] == the global row count.
    """
    del ell_width                      # single-pallas-only metadata
    del row_seg     # per-shard edge->row maps are derived locally below
    mesh, axis = _require_placement_mesh()
    vpp = int(offsets.shape[1]) - 1
    n = int(x.shape[0])
    part, rep = P(axis), P()

    def local_rows(ro_s, ci_s, ev_s, xg):
        ro, ci = ro_s[0], ci_s[0]
        src_local, valid = _local_slots(ro, ci, vpp)
        xv = xg[jnp.where(valid, ci, 0)]                       # (me, k)
        ev = None if ev_s is None else ev_s[0]
        prod = xv if ev is None else sr.mul_op(ev[:, None], xv)
        prod = jnp.where(valid[:, None], prod, sr.zero)
        y = sr.segment_reduce(prod.astype(jnp.float32), src_local, vpp,
                              indices_are_sorted=True)
        deg = ro[1:] - ro[:-1]
        return jnp.where((deg > 0)[:, None], y, sr.zero)

    if values is None:
        run = shard_map(lambda ro, ci, xg: local_rows(ro, ci, None, xg),
                        mesh=mesh, in_specs=(part, part, rep),
                        out_specs=part, check_vma=False)
        y = run(offsets, indices, x)
    else:
        run = shard_map(local_rows, mesh=mesh,
                        in_specs=(part, part, part, rep),
                        out_specs=part, check_vma=False)
        y = run(offsets, indices, values, x)
    y = y[:n]                                   # drop tail-part padding rows
    if mask is not None:
        y = jnp.where(mask[:, None], y, sr.zero)
    return y.astype(jnp.float32)


@B.register("spmv", B.XLA, B.SHARDED)
def _spmv_sharded(offsets, indices, values, x, sr, ell_width, mask,
                  row_seg=None, over_pos=None, over_row=None):
    """1-D row-partitioned semiring SpMV.

    With ``ell_width`` metadata (a ShardedGraph built from a
    ``Graph.from_csr`` source) each device runs the SAME hybrid
    ELL-tree + overflow-fold as the single-device sweep on its local row
    slice — identical per-row fold dataflow, so bits match across
    placements (the PR-4 parity discipline). The compacted overflow
    lists have no stacked counterpart, so shards take the masked
    drop-scatter flavour (same per-row edge sequence, same bits; the
    sharded path is a parity/serving path, not the single-device hot
    loop). Without metadata, falls back to the k=1 SpMM column.
    """
    del row_seg, over_pos, over_row        # derived/absent per shard
    if ell_width is None:
        return _spmm_sharded(offsets, indices, values, x[:, None], sr,
                             None, mask)[:, 0]
    from repro.linalg.ops import hybrid_ell_reduce
    mesh, axis = _require_placement_mesh()
    vpp = int(offsets.shape[1]) - 1
    n = int(x.shape[0])
    part, rep = P(axis), P()

    def local_rows(ro_s, ci_s, ev_s, xg):
        ro, ci = ro_s[0], ci_s[0]
        ev = None if ev_s is None else ev_s[0]
        me = ci.shape[0]
        edge_valid = jnp.arange(me, dtype=jnp.int32) < ro[-1]
        y = hybrid_ell_reduce(ro, ci, ev, xg, sr, int(ell_width),
                              edge_valid=edge_valid)
        deg = ro[1:] - ro[:-1]
        return jnp.where(deg > 0, y, sr.zero)

    if values is None:
        run = shard_map(lambda ro, ci, xg: local_rows(ro, ci, None, xg),
                        mesh=mesh, in_specs=(part, part, rep),
                        out_specs=part, check_vma=False)
        y = run(offsets, indices, x)
    else:
        run = shard_map(local_rows, mesh=mesh,
                        in_specs=(part, part, part, rep),
                        out_specs=part, check_vma=False)
        y = run(offsets, indices, values, x)
    y = y[:n]
    if mask is not None:
        y = jnp.where(mask, y, sr.zero)
    return y.astype(jnp.float32)


# advance_filter has no sharded (1-D) provider BY DESIGN, not omission:
# the fused predicate needs the global visited bitmap coherent per tile,
# and the 1-D exchange only reconciles it per BSP step — the sharded BFS
# path composes advance + a post-exchange filter instead. The 2-D path
# registers one because its row-axis psum-OR makes the bitmap coherent
# inside the step. Declared so the registry contract checker (CT001)
# reads the hole as a decision, while dispatch still refuses to drop to
# single-device.
B.declare_fallback(
    "advance_filter", B.SHARDED,
    reason="1-D exchange cannot keep the visited bitmap coherent inside "
           "a fused tile sweep; sharded BFS composes advance + filter "
           "around the frontier exchange instead")


@B.register("mxm", B.XLA, B.SHARDED)
def _mxm_sharded(a_off, a_idx, a_vals, bt_off, bt_idx, bt_vals,
                 base, probe_rows, sr, cap_out: int):
    """1-D masked SpGEMM: the expansion side (A) is row-partitioned, the
    probe side (Bᵀ) replicated. Each device LB-expands the mask edges
    whose ``base`` row it owns and probes the replicated structure;
    per-edge partials ⊕-combine across the mesh (ownership is disjoint,
    so the combine only merges identities — bit parity with the
    single-device dot formulation)."""
    from . import operators as _ops
    mesh, axis = _require_placement_mesh()
    vpp = int(a_off.shape[1]) - 1
    e = int(base.shape[0])
    part, rep = P(axis), P()
    # one shard_map signature serves the structural/valued combinations:
    # absent value operands ride as zero-size placeholders, the closure
    # flags decide whether the slots index them
    has_av = a_vals is not None
    has_btv = bt_vals is not None
    av_in = (a_vals if has_av
             else jnp.zeros((int(a_off.shape[0]), 0), jnp.float32))
    btv_in = bt_vals if has_btv else jnp.zeros((0,), jnp.float32)

    def local(ao_s, ai_s, av_s, bto, bti, btv, base_g, rows_g):
        ao, ai = ao_s[0], ai_s[0]
        me = int(ai.shape[0])
        my_base = jax.lax.axis_index(axis).astype(jnp.int32) * vpp
        owned = (base_g >= my_base) & (base_g < my_base + vpp)
        base_l = jnp.where(owned, base_g - my_base, 0)
        deg = ao[base_l + 1] - ao[base_l]
        sizes = jnp.where(owned, deg, 0).astype(jnp.int32)
        _, needles, eid, pair, _, valid, _ = _ops._advance_xla(
            ao, ai, base_l, sizes, cap_out)
        rows = rows_g[pair]
        pos = _ops._searchsorted_segment(bti, bto[rows], bto[rows + 1],
                                         needles, locate=True)
        found = (pos >= 0) & valid
        sv = (av_s[0][jnp.clip(eid, 0, me - 1)] if has_av
              else jnp.float32(sr.one))
        lv = (btv[jnp.clip(pos, 0, int(bti.shape[0]) - 1)] if has_btv
              else jnp.float32(sr.one))
        prod = jnp.where(found, sr.mul_op(sv, lv), sr.zero)
        c = sr.segment_reduce(prod.astype(jnp.float32), pair, e,
                              indices_are_sorted=True)
        c = _all_reduce(sr, c, axis)
        gsizes = jax.lax.psum(sizes, axis)
        return jnp.where(gsizes > 0, c, sr.zero).astype(jnp.float32)

    run = shard_map(local, mesh=mesh,
                    in_specs=(part, part, part, rep, rep, rep, rep, rep),
                    out_specs=rep, check_vma=False)
    return run(a_off, a_idx, av_in, bt_off, bt_idx, btv_in, base,
               probe_rows)


# ---------------------------------------------------------------------------
# 2-D vertex-cut providers (placement="2d")
# ---------------------------------------------------------------------------


def _block_slots(block_ro, block_ci, vpr: int):
    """(local source row, validity) of every block CSR slot — the block
    twin of ``_local_slots``. Traversals build it once per program,
    outside their level loop, and hand it to the providers."""
    return _local_slots(block_ro, block_ci, vpr)


def _block_discover_chunk(slots, block_ci, frontier, row_base, col_base,
                          vpr: int, vpc: int, row_ax: str, tiles: int):
    """The per-device half of the 2-D bitmask exchange: expand this
    block's edges from the owned slice of every lane's frontier
    (``frontier`` (B, n)) into (B, vpc) column-chunk masks, psum-OR'd
    along the mesh row — double-buffered over ``tiles`` static edge
    tiles so the collective for tile t is in flight while tile t+1's
    local gathers run (OR is idempotent and order-free, so the overlap
    cannot change bits; a tile's clamped re-read at the ragged tail
    re-marks targets idempotently for the same reason). uint8 lanes
    keep the exchange byte-proportional to the chunk, not to n."""
    src_rows, valid = slots
    b = frontier.shape[0]
    # lanes minor: one index gathers a slot's B frontier bits and one
    # scatter-max marks its target in all B lanes
    my_src = _owned_slice(frontier, row_base, vpr).T.astype(jnp.uint8)
    be = int(block_ci.shape[0])
    tiles = max(int(tiles), 1)
    ept = max(-(-be // tiles), 1)

    def tile_mask(t):
        def cut(x):
            return jax.lax.dynamic_slice(x, (t * ept,), (ept,))
        active = my_src[cut(src_rows)]                       # (ept, B)
        # an invalid (pad) slot targets vpc and is dropped
        tgt = jnp.where(cut(valid), cut(block_ci) - col_base, vpc)
        return jnp.zeros((vpc, b), jnp.uint8).at[tgt].max(
            active, mode="drop").T

    def row_or(mask):
        with jax.named_scope("op.exchange"):
            return jax.lax.psum(mask, row_ax)

    def body(t, carry):
        acc, inflight = carry
        cur = tile_mask(t)                 # local gathers for tile t …
        acc = jnp.maximum(acc, inflight)   # … overlap tile t−1's psum
        return acc, row_or(cur)

    inflight0 = row_or(tile_mask(0))
    acc0 = jnp.zeros((b, vpc), jnp.uint8)
    if tiles > 1:
        acc, inflight = jax.lax.fori_loop(1, tiles, body,
                                          (acc0, inflight0))
    else:
        acc, inflight = acc0, inflight0
    return jnp.maximum(acc, inflight) > 0


def _gather_chunks(chunk, col_ax: str, col_sizes: tuple):
    """Column-axis mirror-merge: assemble the global (…, n) vector from
    the C per-chunk lanes along the last axis (each chunk is already the
    exact row-combined value for its vertices — concatenate and drop
    each chunk's padding past its ``col_sizes`` vertices)."""
    with jax.named_scope("op.exchange"):
        full = jax.lax.all_gather(chunk, col_ax, axis=chunk.ndim - 1,
                                  tiled=True)
    return unpad_chunks(full, int(chunk.shape[-1]), col_sizes)


@B.register("advance", B.XLA, B.TWOD)
@jax.named_scope("op.advance")
def _advance_2d(slots, block_ci, frontier, row_base, col_base,
                vpr: int, col_sizes: tuple, axes: tuple,
                tiles: int = DEFAULT_EXCHANGE_TILES):
    """2-D chunked bitmask-exchange advance over a batch of frontiers.
    Must be called inside an active shard_map over both mesh axes.
    Contract:
      (slots = _block_slots(block_ro, block_ci, vpr), block_ci (be,),
       frontier (B, n), row_base (), col_base (), vpr, col_sizes (the
       C column chunks' vertex counts), axes, tiles) → (B, n) bool
    discovered masks, already row-psum'd and column-gathered (identical
    on every device)."""
    row_ax, col_ax = axes
    chunk = _block_discover_chunk(slots, block_ci, frontier, row_base,
                                  col_base, vpr, max(col_sizes), row_ax,
                                  tiles)
    return _gather_chunks(chunk, col_ax, col_sizes)


@B.register("advance_filter", B.XLA, B.TWOD)
@jax.named_scope("op.advance_filter")
def _advance_filter_2d(slots, block_ci, frontier, visited, row_base,
                       col_base, vpr: int, col_sizes: tuple, axes: tuple,
                       tiles: int = DEFAULT_EXCHANGE_TILES):
    """Fused 2-D advance+filter: the visited filter applies to the
    merged column chunks BEFORE the column-axis gather, so the filter
    costs no extra exchange (the 2-D analogue of the single-device
    fused megakernel). Same contract as the 2d "advance" plus the
    replicated (B, n) visited masks; returns the (B, n) new
    frontiers."""
    row_ax, col_ax = axes
    vpc = max(col_sizes)
    chunk = _block_discover_chunk(slots, block_ci, frontier, row_base,
                                  col_base, vpr, vpc, row_ax, tiles)
    my_visited = _owned_slice(visited, col_base, vpc)
    return _gather_chunks(chunk & ~my_visited, col_ax, col_sizes)


def _merge_block_products(store_leaf, valid, prod, sr, emax: int,
                          col_ax: str):
    """Scatter this block's per-edge products to their row-chunk slice
    positions and ⊕-merge the mesh row: slots are disjoint across the
    row's blocks, so the all-reduce only ever combines a product with
    ⊕-identities — exact for every semiring, including float plus
    (the pre-fold product exchange that keeps 2-D spmv/spmm
    bit-identical to the single-device sweep)."""
    merged = jnp.full(((emax,) + prod.shape[1:]), sr.zero, jnp.float32)
    tgt = jnp.where(valid, store_leaf, emax)
    merged = merged.at[tgt].set(prod.astype(jnp.float32), mode="drop")
    return _all_reduce(sr, merged, col_ax)


@B.register("spmv", B.XLA, B.TWOD)
def _spmv_2d(offsets, store, values, x, sr, ell_width, mask,
             row_seg=None, over_pos=None, over_row=None):
    """2-D vertex-cut semiring SpMV: pre-fold product exchange along
    the mesh row, then the EXACT single-device per-row fold on the
    merged chunk (``fold_products`` — the product-level twin of
    hybrid_ell_reduce, same ELL tree, same overflow scatter order), row
    chunks concatenating over the row axis. ``store`` is the
    ``Blocks2D`` pytree a Sharded2DGraph's col/csc store yields."""
    del row_seg, over_pos, over_row
    if ell_width is None:
        return _spmm_2d(offsets, store, values, x[:, None], sr, None,
                        mask)[:, 0]
    from repro.linalg.ops import fold_products
    mesh, axes = _require_2d_mesh()
    row_ax, col_ax = axes
    vpr = int(offsets.shape[2]) - 1
    emax = int(store.chunk_emax)
    blk, rep = P(row_ax, col_ax), P()

    def local(ro_s, st, ev_s, xg):
        ro = ro_s[0, 0]
        ci, ep, cro = st.cols[0, 0], st.epos[0, 0], st.chunk_ro[0, 0]
        ev = None if ev_s is None else ev_s[0, 0]
        _, valid = _block_slots(ro, ci, vpr)
        xv = xg[jnp.where(valid, ci, 0)]
        prod = sr.round_prod(xv) if ev is None else sr.mul_op(ev, xv)
        merged = _merge_block_products(ep, valid, prod, sr, emax, col_ax)
        edge_valid = jnp.arange(emax, dtype=jnp.int32) < cro[-1]
        y = fold_products(cro, merged, sr, int(ell_width),
                          edge_valid=edge_valid)
        deg = cro[1:] - cro[:-1]
        return jnp.where(deg > 0, y, sr.zero)

    if values is None:
        run = shard_map(lambda ro, st, xg: local(ro, st, None, xg),
                        mesh=mesh, in_specs=(blk, blk, rep),
                        out_specs=P(row_ax), check_vma=False)
        y = run(offsets, store, x)
    else:
        run = shard_map(local, mesh=mesh,
                        in_specs=(blk, blk, blk, rep),
                        out_specs=P(row_ax), check_vma=False)
        y = run(offsets, store, values, x)
    y = unpad_chunks(y, vpr, store.row_sizes)
    if mask is not None:
        y = jnp.where(mask, y, sr.zero)
    return y.astype(jnp.float32)


@B.register("spmm", B.XLA, B.TWOD)
def _spmm_2d(offsets, store, values, x, sr, ell_width, mask,
             row_seg=None):
    """2-D vertex-cut semiring SpMM: the same pre-fold product exchange
    as the 2d spmv, then the single-device gather+segment formulation
    on the merged (chunk_emax, k) products (per-row value sequence
    identical to the 1-D/single sweeps ⇒ bit parity)."""
    del ell_width, row_seg
    mesh, axes = _require_2d_mesh()
    row_ax, col_ax = axes
    vpr = int(offsets.shape[2]) - 1
    emax = int(store.chunk_emax)
    blk, rep = P(row_ax, col_ax), P()

    def local(ro_s, st, ev_s, xg):
        ci, ep, cro = st.cols[0, 0], st.epos[0, 0], st.chunk_ro[0, 0]
        ev = None if ev_s is None else ev_s[0, 0]
        _, valid = _block_slots(ro_s[0, 0], ci, vpr)
        xv = xg[jnp.where(valid, ci, 0)]                       # (be, k)
        prod = xv if ev is None else sr.mul_op(ev[:, None], xv)
        prod = jnp.where(valid[:, None], prod, sr.zero)
        merged = _merge_block_products(ep, valid, prod, sr, emax, col_ax)
        seg = _slot_rows(cro, emax, vpr)
        y = sr.segment_reduce(merged, seg, vpr, indices_are_sorted=True)
        deg = cro[1:] - cro[:-1]
        return jnp.where((deg > 0)[:, None], y, sr.zero)

    if values is None:
        run = shard_map(lambda ro, st, xg: local(ro, st, None, xg),
                        mesh=mesh, in_specs=(blk, blk, rep),
                        out_specs=P(row_ax), check_vma=False)
        y = run(offsets, store, x)
    else:
        run = shard_map(local, mesh=mesh,
                        in_specs=(blk, blk, blk, rep),
                        out_specs=P(row_ax), check_vma=False)
        y = run(offsets, store, values, x)
    y = unpad_chunks(y, vpr, store.row_sizes, axis=0)
    if mask is not None:
        y = jnp.where(mask[:, None], y, sr.zero)
    return y.astype(jnp.float32)


@B.register("mxm", B.XLA, B.TWOD)
def _mxm_2d(a_off, a_store, a_vals, bt_off, bt_idx, bt_vals,
            base, probe_rows, sr, cap_out: int):
    """2-D masked SpGEMM: every device expands ITS block slice of the
    mask edges whose base row its mesh row owns (the row's edges are
    split across the C column blocks), probes the replicated Bᵀ
    structure, and per-edge partials ⊕-combine over the whole mesh.
    Block ownership of A-edges is disjoint, so the combine is exact for
    the exact-⊕ semirings and for integer-valued sums (plus_and
    triangle counts); arbitrary-float plus-times regroups the per-edge
    dot (documented 2-D caveat — use the 1-D placement for bit-exact
    float SpGEMM)."""
    from . import operators as _ops
    mesh, axes = _require_2d_mesh()
    row_ax, col_ax = axes
    vpr = int(a_off.shape[2]) - 1
    e = int(base.shape[0])
    a_idx = a_store.cols if hasattr(a_store, "cols") else a_store
    starts = np.cumsum((0,) + a_store.row_sizes[:-1])   # row chunks' 1st ids
    blk, rep = P(row_ax, col_ax), P()
    has_av = a_vals is not None
    has_btv = bt_vals is not None
    av_in = (a_vals if has_av
             else jnp.zeros(a_idx.shape[:2] + (0,), jnp.float32))
    btv_in = bt_vals if has_btv else jnp.zeros((0,), jnp.float32)

    def local(ao_s, ai_s, av_s, bto, bti, btv, base_g, rows_g):
        ao, ai = ao_s[0, 0], ai_s[0, 0]
        me = int(ai.shape[0])
        my_base = jnp.asarray(starts, jnp.int32)[
            jax.lax.axis_index(row_ax)]
        owned = (base_g >= my_base) & (base_g < my_base + vpr)
        base_l = jnp.where(owned, base_g - my_base, 0)
        deg = ao[base_l + 1] - ao[base_l]       # this block's slice only
        sizes = jnp.where(owned, deg, 0).astype(jnp.int32)
        _, needles, eid, pair, _, valid, _ = _ops._advance_xla(
            ao, ai, base_l, sizes, cap_out)
        rows = rows_g[pair]
        pos = _ops._searchsorted_segment(bti, bto[rows], bto[rows + 1],
                                         needles, locate=True)
        found = (pos >= 0) & valid
        sv = (av_s[0, 0][jnp.clip(eid, 0, me - 1)] if has_av
              else jnp.float32(sr.one))
        lv = (btv[jnp.clip(pos, 0, int(bti.shape[0]) - 1)] if has_btv
              else jnp.float32(sr.one))
        prod = jnp.where(found, sr.mul_op(sv, lv), sr.zero)
        c = sr.segment_reduce(prod.astype(jnp.float32), pair, e,
                              indices_are_sorted=True)
        c = _all_reduce(sr, c, (row_ax, col_ax))
        gsizes = jax.lax.psum(sizes, (row_ax, col_ax))
        return jnp.where(gsizes > 0, c, sr.zero).astype(jnp.float32)

    run = shard_map(local, mesh=mesh,
                    in_specs=(blk, blk, blk, rep, rep, rep, rep, rep),
                    out_specs=rep, check_vma=False)
    return run(a_off, a_idx, av_in, bt_off, bt_idx, btv_in, base,
               probe_rows)


# ---------------------------------------------------------------------------
# traversal primitives (whole loop inside one shard_map)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("n", "vpp", "mesh", "axis", "backend"))
def _bfs_dist_impl(ro, ci, base, src, *, n: int, vpp: int, mesh: Mesh,
                   axis: str, backend: str):
    expand = B.dispatch("advance", backend, B.SHARDED)
    part, rep = P(axis), P()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(part, part, part, rep),
        out_specs=(rep, rep),
        check_vma=False)
    def run(ro_s, ci_s, base_s, src_v):
        local_ro = ro_s[0]
        local_ci = ci_s[0]
        my_base = base_s[0]

        def cond(carry):
            labels, frontier, it = carry
            return jnp.any(frontier) & (it <= n)

        def body(carry):
            labels, frontier, it = carry
            # bitmask-exchange advance (OR-combined across devices)
            disc = expand(local_ro, local_ci, frontier, my_base, vpp,
                          axis)
            new = disc & (labels < 0)
            labels = jnp.where(new, it + 1, labels)
            return labels, new, it + 1

        labels0 = jnp.full((n,), -1, jnp.int32).at[src_v].set(0)
        frontier0 = jnp.zeros((n,), bool).at[src_v].set(True)
        labels, _, it = jax.lax.while_loop(cond, body,
                                           (labels0, frontier0,
                                            jnp.int32(0)))
        return labels, it

    return run(ro, ci, base, src)


@functools.partial(jax.jit,
                   static_argnames=("n", "vpr", "col_sizes", "mesh",
                                    "axes", "tiles", "backend"))
def _bfs_2d_impl(ro, ci, row_base, col_base, srcs, *, n: int, vpr: int,
                 col_sizes: tuple, mesh: Mesh, axes: tuple, tiles: int,
                 backend: str):
    """One BFS per lane of ``srcs`` (B,), all lanes in one level loop
    until every lane's frontier is empty; returns (B, n) depths and the
    (B,) levels each lane ran with a non-empty frontier (the count
    ``run_until_any`` gives ``bfs_batch``)."""
    af = B.dispatch("advance_filter", backend, B.TWOD)
    row_ax, col_ax = axes
    blk, rep = P(row_ax, col_ax), P()
    b = srcs.shape[0]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(blk, blk, P(row_ax), P(col_ax), rep),
        out_specs=(rep, rep),
        check_vma=False)
    def run(ro_s, ci_s, rb_s, cb_s, src_v):
        block_ci = ci_s[0, 0]
        my_rb, my_cb = rb_s[0], cb_s[0]
        with jax.named_scope("primitive.init"):
            slots = _block_slots(ro_s[0, 0], block_ci, vpr)
            lane = jnp.arange(b)
            labels0 = jnp.full((b, n), -1, jnp.int32).at[lane, src_v].set(0)
            frontier0 = jnp.zeros((b, n), bool).at[lane, src_v].set(True)

        def cond(carry):
            _, frontier, it, _ = carry
            return jnp.any(frontier) & (it <= n)

        def body(carry):
            labels, frontier, it, lane_iters = carry
            # fused 2-D advance+filter: row-psum'd chunk discovery with
            # the visited filter applied pre-gather
            new = af(slots, block_ci, frontier, labels >= 0, my_rb, my_cb,
                     vpr, col_sizes, axes, tiles)
            with jax.named_scope("op.apply"):
                labels = jnp.where(new, it + 1, labels)
            lane_iters = lane_iters + jnp.any(frontier, axis=1)
            return labels, new, it + 1, lane_iters

        labels, _, _, lane_iters = jax.lax.while_loop(
            cond, body, (labels0, frontier0, jnp.int32(0),
                         jnp.zeros((b,), jnp.int32)))
        return labels, lane_iters

    # the whole program is the level loop: what the compiler hoists out
    # of it or makes without a name counts under enactor.loop
    with jax.named_scope("enactor.loop"):
        return run(ro, ci, row_base, col_base, srcs)


def distributed_bfs(pg, srcs, mesh: Mesh, axis="graph",
                    backend: Optional[str] = None,
                    tiles: int = DEFAULT_EXCHANGE_TILES) -> DistBFSResult:
    """Multi-device BFS (bitmask-exchange advance). A Partitioned2DGraph
    runs the vertex-cut 2-D placement (``axis`` may name the (row, col)
    axis pair; ``tiles`` sets the double-buffer depth of the chunked
    bitmask exchange) over a (B,) array of sources as ONE program: (B, n)
    labels and (B,) per-lane iterations. A PartitionedGraph runs the 1-D
    row placement (``mesh`` must have a 1-D axis named ``axis`` whose
    size equals pg.num_parts), one source per program. A scalar source
    gives the (n,) labels and () iterations of that one lane. Labels are
    bit-identical to the single-device ``bfs_batch`` either way."""
    if isinstance(pg, Partitioned2DGraph):
        axes = _axes_arg(axis)
        _check_mesh(pg, mesh, axes)
        sg = pg.shard(mesh, axes)
        lanes = jnp.asarray(srcs, jnp.int32)
        labels, it = _bfs_2d_impl(
            sg.row_offsets, sg.col_indices, sg.row_base, sg.col_base,
            lanes.reshape(-1), n=pg.n, vpr=pg.vpr, col_sizes=pg.col_sizes,
            mesh=mesh, axes=axes, tiles=max(int(tiles), 1),
            backend=B.resolve(backend))
        if lanes.ndim == 0:
            labels, it = labels[0], it[0]
        return DistBFSResult(labels=labels, iterations=it)
    if jnp.ndim(srcs):
        raise ValueError(
            "the 1-D placement runs one BFS source per program; batch "
            "sources over the 2-D placement (partition_2d)")
    sg = pg.shard(mesh, axis)            # cached device arrays per mesh
    labels, it = _bfs_dist_impl(
        sg.row_offsets, sg.col_indices, sg.vertex_base, jnp.int32(srcs),
        n=pg.n, vpp=pg.verts_per_part, mesh=mesh, axis=axis,
        backend=B.resolve(backend))
    return DistBFSResult(labels=labels, iterations=it)


@functools.partial(jax.jit,
                   static_argnames=("n", "vpp", "use_delta", "mesh", "axis"))
def _sssp_dist_impl(ro, ci, ev, base, src, delta, *, n: int, vpp: int,
                    use_delta: bool, mesh: Mesh, axis: str):
    part, rep = P(axis), P()
    inf = jnp.float32(jnp.inf)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(part, part, part, part, rep, rep),
        out_specs=(rep, rep),
        check_vma=False)
    def run(ro_s, ci_s, ev_s, base_s, src_v, delta_v):
        local_ro, local_ci, local_ev = ro_s[0], ci_s[0], ev_s[0]
        my_base = base_s[0]
        src_local, valid = _local_slots(local_ro, local_ci, vpp)

        def relax_step(st):
            # dense relax of the owned near-frontier rows: candidate
            # distances scatter-min locally, min-combine across devices
            # (min is exact — the atomicMin of paper §5.2 twice over)
            dist, near, far, bucket = st
            my_near = _owned_slice(near, my_base, vpp)
            my_dist = _owned_slice(dist, my_base, vpp)
            active = my_near[src_local] & valid
            cand_v = my_dist[src_local] + local_ev
            cand = jnp.full((n,), inf, jnp.float32)
            tgt = jnp.where(active, local_ci, n)
            cand = cand.at[tgt].min(jnp.where(active, cand_v, inf),
                                    mode="drop")
            cand = jax.lax.pmin(cand, axis)
            new_dist = jnp.minimum(dist, cand)
            improved = new_dist < dist
            thresh = (bucket.astype(jnp.float32) + 1.0) * delta_v
            if use_delta:
                add_near = improved & (new_dist < thresh)
                add_far = improved & (new_dist >= thresh)
            else:
                add_near = improved
                add_far = jnp.zeros_like(improved)
            far2 = (far | add_far) & ~add_near
            return new_dist, add_near, far2, bucket

        def pop_far(st):
            # near pile empty: advance the bucket to the smallest far
            # distance (replicated state ⇒ every device agrees)
            dist, near, far, bucket = st
            far_min = jnp.min(jnp.where(far, dist, inf))
            new_bucket = jnp.where(jnp.isfinite(far_min),
                                   (far_min / delta_v).astype(jnp.int32),
                                   bucket + 1)
            thresh = (new_bucket.astype(jnp.float32) + 1.0) * delta_v
            near2 = far & (dist < thresh)
            return dist, near2, far & ~near2, new_bucket

        def body(carry):
            st, it = carry
            st = jax.lax.cond(jnp.any(st[1]), relax_step, pop_far, st)
            return st, it + 1

        def cond(carry):
            (dist, near, far, bucket), it = carry
            return (jnp.any(near) | jnp.any(far)) & (it < 4 * n + 8)

        dist0 = jnp.full((n,), inf, jnp.float32).at[src_v].set(0.0)
        near0 = jnp.zeros((n,), bool).at[src_v].set(True)
        far0 = jnp.zeros((n,), bool)
        (dist, _, _, _), it = jax.lax.while_loop(
            cond, body, ((dist0, near0, far0, jnp.int32(0)), jnp.int32(0)))
        return dist, it

    return run(ro, ci, ev, base, src, delta)


@functools.partial(jax.jit,
                   static_argnames=("n", "vpr", "col_sizes", "use_delta",
                                    "mesh", "axes"))
def _sssp_2d_impl(ro, ci, ev, row_base, col_base, src, delta, *, n: int,
                  vpr: int, col_sizes: tuple, use_delta: bool, mesh: Mesh,
                  axes: tuple):
    row_ax, col_ax = axes
    vpc = max(col_sizes)
    blk, rep = P(row_ax, col_ax), P()
    inf = jnp.float32(jnp.inf)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(blk, blk, blk, P(row_ax), P(col_ax), rep, rep),
        out_specs=(rep, rep),
        check_vma=False)
    def run(ro_s, ci_s, ev_s, rb_s, cb_s, src_v, delta_v):
        block_ro, block_ci, block_ev = ro_s[0, 0], ci_s[0, 0], ev_s[0, 0]
        my_rb, my_cb = rb_s[0], cb_s[0]
        src_local, valid = _block_slots(block_ro, block_ci, vpr)

        def relax_step(st):
            # dense relax of this block's edges: candidates scatter-min
            # into the (vpc,) column chunk, min-combine the mesh row,
            # then chunks concatenate over the column axis (min is
            # exact, so the 2-D regrouping cannot move a bit)
            dist, near, far, bucket = st
            my_near = _owned_slice(near, my_rb, vpr)
            my_dist = _owned_slice(dist, my_rb, vpr)
            active = my_near[src_local] & valid
            cand_v = my_dist[src_local] + block_ev
            chunk = jnp.full((vpc,), inf, jnp.float32)
            tgt = jnp.where(active, block_ci - my_cb, vpc)
            chunk = chunk.at[tgt].min(jnp.where(active, cand_v, inf),
                                      mode="drop")
            chunk = jax.lax.pmin(chunk, row_ax)
            cand = _gather_chunks(chunk, col_ax, col_sizes)
            new_dist = jnp.minimum(dist, cand)
            improved = new_dist < dist
            thresh = (bucket.astype(jnp.float32) + 1.0) * delta_v
            if use_delta:
                add_near = improved & (new_dist < thresh)
                add_far = improved & (new_dist >= thresh)
            else:
                add_near = improved
                add_far = jnp.zeros_like(improved)
            far2 = (far | add_far) & ~add_near
            return new_dist, add_near, far2, bucket

        def pop_far(st):
            dist, near, far, bucket = st
            far_min = jnp.min(jnp.where(far, dist, inf))
            new_bucket = jnp.where(jnp.isfinite(far_min),
                                   (far_min / delta_v).astype(jnp.int32),
                                   bucket + 1)
            thresh = (new_bucket.astype(jnp.float32) + 1.0) * delta_v
            near2 = far & (dist < thresh)
            return dist, near2, far & ~near2, new_bucket

        def body(carry):
            st, it = carry
            st = jax.lax.cond(jnp.any(st[1]), relax_step, pop_far, st)
            return st, it + 1

        def cond(carry):
            (dist, near, far, bucket), it = carry
            return (jnp.any(near) | jnp.any(far)) & (it < 4 * n + 8)

        dist0 = jnp.full((n,), inf, jnp.float32).at[src_v].set(0.0)
        near0 = jnp.zeros((n,), bool).at[src_v].set(True)
        far0 = jnp.zeros((n,), bool)
        (dist, _, _, _), it = jax.lax.while_loop(
            cond, body, ((dist0, near0, far0, jnp.int32(0)), jnp.int32(0)))
        return dist, it

    return run(ro, ci, ev, row_base, col_base, src, delta)


def distributed_sssp(pg, src: int, mesh: Mesh, axis="graph",
                     delta: Optional[float] = None) -> DistSSSPResult:
    """Multi-device delta-stepping SSSP: per-bucket dense relaxation of
    owned rows (1-D) or owned blocks (2-D vertex cut) with
    min-all-reduced distance improvements. Distances are bit-identical
    to the single-device ``sssp`` (every relaxation value ``dist[u] + w``
    is computed the same way and min is exact)."""
    assert pg.edge_values is not None, "SSSP needs edge weights"
    if delta is None:
        if pg.source is not None:
            from .primitives.sssp import _auto_delta
            delta = _auto_delta(pg.source)
        else:
            real = np.asarray(pg.col_indices) >= 0
            mean_w = float(np.asarray(pg.edge_values)[real].mean())
            delta = mean_w * max(pg.m / max(pg.n, 1), 1.0) / 2.0
    use_delta = bool(jnp.isfinite(delta)) and delta > 0
    if isinstance(pg, Partitioned2DGraph):
        axes = _axes_arg(axis)
        _check_mesh(pg, mesh, axes)
        sg = pg.shard(mesh, axes)
        dist, it = _sssp_2d_impl(
            sg.row_offsets, sg.col_indices, sg.edge_values, sg.row_base,
            sg.col_base, jnp.int32(src), jnp.float32(delta),
            n=pg.n, vpr=pg.vpr, col_sizes=pg.col_sizes,
            use_delta=use_delta, mesh=mesh, axes=axes)
        return DistSSSPResult(dist=dist, iterations=it)
    sg = pg.shard(mesh, axis)
    dist, it = _sssp_dist_impl(
        sg.row_offsets, sg.col_indices, sg.edge_values, sg.vertex_base,
        jnp.int32(src), jnp.float32(delta),
        n=pg.n, vpp=pg.verts_per_part, use_delta=use_delta, mesh=mesh,
        axis=axis)
    return DistSSSPResult(dist=dist, iterations=it)


@functools.partial(jax.jit, static_argnames=("n", "vpp", "mesh", "axis"))
def _cc_dist_impl(ro, ci, base, *, n: int, vpp: int, mesh: Mesh, axis: str):
    part, rep = P(axis), P()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(part, part, part),
        out_specs=(rep, rep),
        check_vma=False)
    def run(ro_s, ci_s, base_s):
        local_ro, local_ci = ro_s[0], ci_s[0]
        my_base = base_s[0]
        src_local, valid = _local_slots(local_ro, local_ci, vpp)
        src_g = my_base + src_local
        dst = jnp.where(valid, local_ci, 0)

        def pointer_jump(cid):
            return jax.lax.while_loop(lambda c: jnp.any(c[c] != c),
                                      lambda c: c[c], cid)

        def body(carry):
            cid, live, n_live, it = carry
            cu = cid[src_g]
            cv = cid[dst]
            live = live & (cu != cv)
            lo = jnp.minimum(cu, cv)
            hi = jnp.maximum(cu, cv)
            # hooking: scatter-min the local live edges, min-combine the
            # label candidates across devices (all-reduced label mins)
            tgt = jnp.where(live, hi, n)
            cand = jnp.full((n,), INT_BIG, jnp.int32)
            cand = cand.at[tgt].min(jnp.where(live, lo, INT_BIG),
                                    mode="drop")
            cand = jax.lax.pmin(cand, axis)
            cid = pointer_jump(jnp.minimum(cid, cand))
            still = live & (cid[src_g] != cid[dst])
            n_live = jax.lax.psum(
                jnp.sum(still, dtype=jnp.int32), axis)
            return cid, still, n_live, it + 1

        def cond(carry):
            _, _, n_live, it = carry
            return (n_live > 0) & (it < n + 1)

        cid0 = jnp.arange(n, dtype=jnp.int32)
        cid, _, _, it = jax.lax.while_loop(
            cond, body,
            (cid0, valid, jnp.int32(1), jnp.int32(0)))
        return cid, it

    labels, it = run(ro, ci, base)
    ncomp = jnp.sum(labels == jnp.arange(n), dtype=jnp.int32)
    return labels, ncomp, it


@functools.partial(jax.jit, static_argnames=("n", "vpr", "mesh", "axes"))
def _cc_2d_impl(ro, ci, row_base, *, n: int, vpr: int, mesh: Mesh,
                axes: tuple):
    row_ax, col_ax = axes
    blk, rep = P(row_ax, col_ax), P()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(blk, blk, P(row_ax)),
        out_specs=(rep, rep),
        check_vma=False)
    def run(ro_s, ci_s, rb_s):
        block_ro, block_ci = ro_s[0, 0], ci_s[0, 0]
        my_rb = rb_s[0]
        src_local, valid = _block_slots(block_ro, block_ci, vpr)
        src_g = my_rb + src_local
        dst = jnp.where(valid, block_ci, 0)

        def pointer_jump(cid):
            return jax.lax.while_loop(lambda c: jnp.any(c[c] != c),
                                      lambda c: c[c], cid)

        def body(carry):
            cid, live, n_live, it = carry
            cu = cid[src_g]
            cv = cid[dst]
            live = live & (cu != cv)
            lo = jnp.minimum(cu, cv)
            hi = jnp.maximum(cu, cv)
            # hooking: labels target arbitrary component ids, so the
            # candidate vector stays (n,) and min-combines over the
            # WHOLE mesh (both axes) — a vertex cut cannot shrink this
            # exchange, which exchange_bytes_per_step reports honestly
            tgt = jnp.where(live, hi, n)
            cand = jnp.full((n,), INT_BIG, jnp.int32)
            cand = cand.at[tgt].min(jnp.where(live, lo, INT_BIG),
                                    mode="drop")
            cand = jax.lax.pmin(cand, (row_ax, col_ax))
            cid = pointer_jump(jnp.minimum(cid, cand))
            still = live & (cid[src_g] != cid[dst])
            n_live = jax.lax.psum(jnp.sum(still, dtype=jnp.int32),
                                  (row_ax, col_ax))
            return cid, still, n_live, it + 1

        def cond(carry):
            _, _, n_live, it = carry
            return (n_live > 0) & (it < n + 1)

        cid0 = jnp.arange(n, dtype=jnp.int32)
        cid, _, _, it = jax.lax.while_loop(
            cond, body,
            (cid0, valid, jnp.int32(1), jnp.int32(0)))
        return cid, it

    labels, it = run(ro, ci, row_base)
    ncomp = jnp.sum(labels == jnp.arange(n), dtype=jnp.int32)
    return labels, ncomp, it


def distributed_cc(pg, mesh: Mesh, axis="graph") -> DistCCResult:
    """Multi-device connected components: hooking over owned edges (1-D
    rows or 2-D blocks) with all-reduced label mins + replicated
    pointer-jumping. Labels are bit-identical to the single-device
    ``connected_components`` (every combine is an exact integer min)."""
    if isinstance(pg, Partitioned2DGraph):
        axes = _axes_arg(axis)
        _check_mesh(pg, mesh, axes)
        sg = pg.shard(mesh, axes)
        labels, ncomp, it = _cc_2d_impl(
            sg.row_offsets, sg.col_indices, sg.row_base,
            n=pg.n, vpr=pg.vpr, mesh=mesh, axes=axes)
        return DistCCResult(labels=labels, num_components=ncomp,
                            iterations=it)
    sg = pg.shard(mesh, axis)
    labels, ncomp, it = _cc_dist_impl(
        sg.row_offsets, sg.col_indices, sg.vertex_base,
        n=pg.n, vpp=pg.verts_per_part, mesh=mesh, axis=axis)
    return DistCCResult(labels=labels, num_components=ncomp, iterations=it)


def distributed_pagerank(pg, mesh: Mesh, axis="graph",
                         damping: float = 0.85,
                         iters: int = 20) -> jax.Array:
    """SpMV PageRank through the sharded/2d "spmv" provider: the rank
    vector stays replicated, each device reduces its owned CSC rows
    (1-D) or ⊕-merges its CSC block's pre-fold products (2-D). This
    runs the SAME ``_pagerank_impl`` as the single-device primitive —
    only the dispatched spmv differs — so ranks are bit-identical to
    ``pagerank``, not merely close."""
    from .primitives.pagerank import pagerank
    _check_mesh(pg, mesh, axis)
    if not pg.has_csc:
        raise ValueError(
            "distributed_pagerank needs the partitioned CSC mirror; "
            "partition a Graph built with build_csc=True")
    return pagerank(_shard_any(pg, mesh, axis), damping=damping,
                    max_iter=iters).rank


# ---------------------------------------------------------------------------
# algebraic primitives on a partition (delegate to the Graph primitives —
# they dispatch through the sharded providers via ShardedGraph)
# ---------------------------------------------------------------------------


def distributed_label_propagation(pg, mesh: Mesh, axis="graph",
                                  **kwargs):
    """Label propagation on the partition (1-D or 2-D): the one-hot
    SpMM blocks run through the placement's "spmm" provider; labels
    bit-match the single-device primitive (the vote sums are
    small-integer-valued floats, exact under any regrouping)."""
    from .primitives.label_propagation import label_propagation
    _check_mesh(pg, mesh, axis)
    return label_propagation(_shard_any(pg, mesh, axis), **kwargs)


def distributed_reach(pg, srcs, k: int = 3, *,
                      mesh: Mesh, axis="graph", **kwargs):
    """Batched k-hop reachability on the partition (or-and SpMM closure
    through the placement's provider)."""
    from .primitives.reach import reach_batch
    _check_mesh(pg, mesh, axis)
    return reach_batch(_shard_any(pg, mesh, axis), srcs, k, **kwargs)


# ---------------------------------------------------------------------------
# comm-volume model (the benchmark's bytes-per-step column)
# ---------------------------------------------------------------------------


def exchange_bytes_per_step(pg, primitive: str = "bfs",
                            tiles: int = DEFAULT_EXCHANGE_TILES,
                            batch: int = 1) -> int:
    """Analytic bytes exchanged PER DEVICE in one BSP step of
    ``primitive`` under ``pg``'s placement, with the standard ring
    cost model (an all-reduce of b bytes moves 2·(p−1)/p·b per device;
    an all-gather of b-byte shards moves (p−1)·b). ``batch`` is the
    number of sources a step serves: bfs and sssp exchange once per
    source (in one program's (B, …) chunks for the 2-D bfs, in one
    program per source elsewhere), so their bytes scale with it; cc and
    pagerank answer any batch with one whole-graph run.

    1-D exchanges are n-proportional (the replicated-vector tax the
    2-D cut removes): bfs/sssp/cc all-reduce an (n,) candidate vector,
    pagerank all-gathers its (n/p,) spmv output shard. 2-D traversal
    exchanges are chunk-proportional: bfs psums ``tiles`` uint8
    (vpc,)-chunk tiles along the R-row and gathers C chunks; sssp the
    float32 twin; pagerank trades them for a (chunk_emax,) product
    psum along the column axis plus the output-row gather. cc hooks
    into arbitrary component ids, so its exchange stays (n,) on any
    mesh — reported as-is, not hidden."""
    tiles = max(int(tiles), 1)
    batch = max(int(batch), 1)
    n = pg.n
    if isinstance(pg, Partitioned2DGraph):
        r, c = pg.rows, pg.cols
        if primitive == "bfs":
            return int(batch * (tiles * 2 * (r - 1) / r * pg.vpc
                                + (c - 1) * pg.vpc))
        if primitive == "sssp":
            return int(batch * (2 * (r - 1) / r * pg.vpc
                                + (c - 1) * pg.vpc) * 4)
        if primitive == "cc":
            p = r * c
            return int(2 * (p - 1) / p * n * 4)
        if primitive == "pagerank":
            return int(2 * (c - 1) / c * pg.csc_chunk_emax * 4
                       + (r - 1) * pg.vpr * 4)
        raise ValueError(f"unknown primitive {primitive!r}")
    p = pg.num_parts
    if primitive in ("bfs", "sssp"):
        return int(batch * 2 * (p - 1) / p * n * 4)
    if primitive == "cc":
        return int(2 * (p - 1) / p * n * 4)
    if primitive == "pagerank":
        return int((p - 1) / p * n * 4)
    raise ValueError(f"unknown primitive {primitive!r}")
