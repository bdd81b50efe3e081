"""Multi-device graph partitioning (paper §8.2.1 Scale-Out; Pan et al. [56]).

1-D contiguous vertex partition: device d owns vertices
[d·ceil(n/p), (d+1)·ceil(n/p)) and the out-edges (CSR rows) of those
vertices. Per-device CSR slices are rebased and padded to the max local
edge count so the partition stacks into dense (p, …) arrays that
shard_map can split over the mesh. When the source graph carries a CSC
mirror, the mirror is partitioned the same way (device d owns the
*in*-edges of its vertices), which is what lets pull-direction algebra
(PageRank's contribution sweep, reach's CSC SpMM) run row-local and
bit-identical to the single-device sweep.

This is the same partitioning Gunrock's multi-GPU framework uses; the
frontier exchange strategies and the sharded registry providers live in
core/distributed.py.

2-D vertex-cut partition (placement="2d"): edges are blocked on an R×C
device mesh — device (i, j) holds the edges whose source lies in row
chunk i and whose destination lies in column chunk j. Chunks are
contiguous id ranges cut where the running out-degree (rows) or
in-degree (columns) sum crosses each 1/R (1/C) share of the edges, so
every block holds about a 1/(R·C) share of them whatever ids the hubs
carry; chunk arrays are padded to the widest chunk (vpr, vpc). Every
vertex has one designated owner device
(``owner_of``); the other devices touching it hold *mirrors* (the
vertex-cut replication the balance stats account). Frontier exchange
then shrinks from the 1-D all-reduce over (n,) to a psum along the R
row devices of one column chunk plus an all-gather of the C chunks —
the comm-volume win measured by benchmarks/distributed_scale.

Containers:

  ``PartitionedGraph``    — host-side 1-D numpy slices + balance stats.
  ``ShardedGraph``        — device-side 1-D pytree (``.shard(mesh)``):
                            stacked (p, …) jnp arrays named like
                            ``Graph`` attributes so primitives written
                            against Graph run on it unchanged, with the
                            mesh + axis carried as static aux data
                            (part of every jit cache key).
  ``Partitioned2DGraph``  — host-side R×C edge blocks + mirror tables.
  ``Sharded2DGraph``      — device-side 2-D pytree: (R, C, …) stacked
                            blocks, same Graph-mirroring attribute
                            names; its column stores are ``Blocks2D``
                            pytrees carrying the block↔row-chunk edge
                            alignment the exact 2-D semiring providers
                            need (see core/distributed.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from . import storage as S
from .graph import Graph

# What every shard actually holds, whatever the source graph's storage
# plan chose: partitioning decodes to dense int32 columns and fp32
# values (the pad sentinel -1 and the shard_map collectives both assume
# the canonical layout; compressing per-shard slices is future work —
# the plan still rides the ShardedGraph aux for reporting/provenance).
SHARD_PLAN = S.StoragePlan(index_dtype="int32", encoding="dense",
                           value_dtype="fp32")


def check_mesh_axis(mesh, axis: str, num_parts: int) -> None:
    """Validate that ``mesh`` carries a 1-D axis ``axis`` of size
    ``num_parts`` (the one mesh precondition every sharded entry point
    shares)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if sizes.get(axis) != num_parts:
        raise ValueError(
            f"mesh axis {axis!r} (size {sizes.get(axis)}) must match "
            f"the partition's {num_parts} parts")


def check_mesh_axes(mesh, axes, shape) -> None:
    """2-D twin of ``check_mesh_axis``: ``axes`` = (row_name, col_name)
    must exist on ``mesh`` with sizes ``shape`` = (R, C)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for ax, want in zip(axes, shape):
        if sizes.get(ax) != want:
            raise ValueError(
                f"mesh axis {ax!r} (size {sizes.get(ax)}) must match "
                f"the 2-D partition's {tuple(shape)} blocks")


def _placer(mesh, spec):
    """Host array -> device array split over ``mesh`` by ``spec``: block
    p of the leading (stacked) axis lands on the device that owns it, so
    shard_map runs on it in place. None passes through."""
    sharding = NamedSharding(mesh, spec)

    def put(a):
        return None if a is None else jax.device_put(a, sharding)

    return put


def _slice_rows(ro: np.ndarray, ci: np.ndarray, ev: Optional[np.ndarray],
                n: int, num_parts: int, vpp: int):
    """Rebase + pad per-part row slices of one CSR-like structure."""
    max_edges = 0
    slices = []
    for p in range(num_parts):
        lo_v = min(p * vpp, n)
        hi_v = min((p + 1) * vpp, n)
        lo_e, hi_e = int(ro[lo_v]), int(ro[hi_v])
        local_ro = ro[lo_v:hi_v + 1] - ro[lo_v]
        # pad vertex dim (parts at the tail may own fewer vertices)
        pad_v = vpp - (hi_v - lo_v)
        if pad_v:
            local_ro = np.concatenate(
                [local_ro, np.full(pad_v, local_ro[-1], local_ro.dtype)])
        slices.append((local_ro, ci[lo_e:hi_e],
                       ev[lo_e:hi_e] if ev is not None else None, lo_v))
        max_edges = max(max_edges, hi_e - lo_e)
    max_edges = max(max_edges, 1)
    p_ro = np.stack([s[0] for s in slices]).astype(np.int32)
    p_ci = np.full((num_parts, max_edges), -1, np.int32)
    p_ev = (np.zeros((num_parts, max_edges), np.float32)
            if ev is not None else None)
    base = np.zeros((num_parts,), np.int32)
    for p, (_, c, v, lo_v) in enumerate(slices):
        p_ci[p, :len(c)] = c
        if v is not None:
            p_ev[p, :len(v)] = v
        base[p] = lo_v
    return p_ro, p_ci, p_ev, base


@dataclass(frozen=True)
class PartitionedGraph:
    """Host-side stacked per-device CSR (+ CSC) slices (leading axis =
    device). ``source`` keeps the unpartitioned Graph around for
    replicated operands (the probe side of a sharded SpGEMM, oracle
    validation, degree vectors) — 1-D partitioning distributes the sweep,
    not the whole dataset."""

    n: int                     # global vertex count
    m: int                     # global edge count
    num_parts: int
    verts_per_part: int        # ceil(n / p)
    row_offsets: np.ndarray    # (p, verts_per_part+1) rebased local CSR
    col_indices: np.ndarray    # (p, max_local_edges) global dst ids, pad -1
    edge_values: Optional[np.ndarray]  # (p, max_local_edges)
    vertex_base: np.ndarray    # (p,) first global vertex id of each part
    # CSC mirror slices (in-edges of owned vertices), same layout
    csc_row_offsets: Optional[np.ndarray] = None
    csc_col_indices: Optional[np.ndarray] = None
    csc_edge_values: Optional[np.ndarray] = None
    source: Optional[Graph] = None

    @property
    def max_local_edges(self) -> int:
        return int(self.col_indices.shape[1])

    @property
    def has_csc(self) -> bool:
        return self.csc_row_offsets is not None

    def owner_of(self, v: np.ndarray) -> np.ndarray:
        return v // self.verts_per_part

    def balance(self) -> dict:
        """Per-device load accounting (for serving --json / benchmarks):
        owned vertex and edge counts per part plus BOTH imbalance
        factors (max/mean — 1.0 is perfectly balanced). On rmat graphs
        the vertex factor is ~1.0 while the edge factor is not: the
        contiguous 1-D cut balances ownership, not work — the hub skew
        that motivates the 2-D vertex-cut placement."""
        verts = [int(min((p + 1) * self.verts_per_part, self.n)
                     - min(p * self.verts_per_part, self.n))
                 for p in range(self.num_parts)]
        edges = [int(self.row_offsets[p, -1]) for p in range(self.num_parts)]
        mean_e = max(sum(edges) / max(self.num_parts, 1), 1e-9)
        mean_v = max(sum(verts) / max(self.num_parts, 1), 1e-9)
        return {
            "parts": self.num_parts,
            "vertices_per_part": verts,
            "edges_per_part": edges,
            "edge_imbalance": round(max(edges) / mean_e, 3),
            "vertex_imbalance": round(max(verts) / mean_v, 3),
        }

    def shard(self, mesh, axis: str = "graph") -> "ShardedGraph":
        """Device-side view for the sharded registry providers. ``mesh``
        must carry a 1-D axis ``axis`` of size ``num_parts``; part p of
        every stacked array lives on the p-th device of that axis. Views
        are cached per (mesh, axis): repeated calls (every query of a
        serving loop goes through here) reuse one set of device arrays
        instead of re-uploading the partition."""
        check_mesh_axis(mesh, axis, self.num_parts)
        cache = self.__dict__.get("_shard_cache")
        if cache is None:
            object.__setattr__(self, "_shard_cache", {})  # frozen dc
            cache = self.__dict__["_shard_cache"]
        key = (mesh, axis)
        if key in cache:
            return cache[key]
        put = _placer(mesh, P(axis))
        cache[key] = ShardedGraph(
            row_offsets=put(self.row_offsets),
            col_indices=put(self.col_indices),
            edge_values=put(self.edge_values),
            csc_offsets=put(self.csc_row_offsets),
            csc_indices=put(self.csc_col_indices),
            csc_edge_values=put(self.csc_edge_values),
            vertex_base=put(self.vertex_base),
            n=self.n, m=self.m, verts_per_part=self.verts_per_part,
            mesh=mesh, axis=axis,
            ell_width=(self.source.ell_width
                       if self.source is not None else None),
            csc_ell_width=(self.source.csc_ell_width
                           if self.source is not None else None),
            source_plan=(self.source.plan
                         if self.source is not None else None))
        return cache[key]


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class ShardedGraph:
    """Stacked per-device graph slices as a jit-friendly pytree.

    Attribute names mirror ``Graph`` (``row_offsets``, ``csc_offsets``,
    ``num_vertices``, …) so algebra primitives written against Graph
    dispatch on it unchanged — the sharded registry providers understand
    the stacked (p, …) array layout. ``mesh``/``axis`` are static aux
    data: they ride the pytree treedef, so every jit cache key that
    closes over a ShardedGraph includes the mesh identity and a cached
    trace can never run against the wrong mesh. ELL *widths* are carried
    as aux from the source graph — the sharded hybrid SpMV needs the
    same fold shape as the single-device sweep — but the providers stay
    xla-backed (a pallas-under-shard_map provider would re-pack per
    device).
    """

    row_offsets: jax.Array            # (p, vpp+1)
    col_indices: jax.Array            # (p, max_local_edges)
    edge_values: Optional[jax.Array]
    csc_offsets: Optional[jax.Array]  # (p, vpp+1)
    csc_indices: Optional[jax.Array]
    csc_edge_values: Optional[jax.Array]
    vertex_base: jax.Array            # (p,)
    n: int
    m: int
    verts_per_part: int
    mesh: object
    axis: str
    # ELL pack widths copied from the SOURCE graph: the sharded hybrid
    # SpMV must fold each row with exactly the same tree shape as the
    # single-device sweep (placement bit-parity), so the width is shared
    # static metadata, not a per-shard choice.
    ell_width: Optional[int] = None
    csc_ell_width: Optional[int] = None
    # the source graph's storage plan (provenance/reporting); the shards
    # themselves always hold SHARD_PLAN storage — see module constant
    source_plan: Optional[S.StoragePlan] = None

    # per-shard edge→row maps and overflow lists are derived locally by
    # the sharded providers (local offsets differ per device); the
    # Graph-level metadata has no stacked counterpart by design
    row_seg = None
    csc_row_seg = None
    over_pos = None
    over_row = None
    csc_over_pos = None
    csc_over_row = None

    def tree_flatten(self):
        children = (self.row_offsets, self.col_indices, self.edge_values,
                    self.csc_offsets, self.csc_indices,
                    self.csc_edge_values, self.vertex_base)
        aux = (self.n, self.m, self.verts_per_part, self.mesh, self.axis,
               self.ell_width, self.csc_ell_width, self.source_plan)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def num_vertices(self) -> int:
        return self.n

    @property
    def num_edges(self) -> int:
        return self.m

    @property
    def num_parts(self) -> int:
        return int(self.row_offsets.shape[0])

    @property
    def has_csc(self) -> bool:
        return self.csc_offsets is not None

    @property
    def weighted(self) -> bool:
        return self.edge_values is not None

    @property
    def plan(self) -> S.StoragePlan:
        """The storage plan of the shard arrays themselves (always
        SHARD_PLAN — dense int32/fp32); the source graph's plan is
        ``source_plan``."""
        return SHARD_PLAN

    @property
    def col_store(self):
        """Stacked dense column slices — ShardedGraph storage is always
        dense, so the store IS the array (keeps ``B.storage_arg``
        placement-generic in primitives that accept either container)."""
        return self.col_indices

    @property
    def csc_store(self):
        return self.csc_indices

    @property
    def degrees(self) -> jax.Array:
        """Global out-degree vector (n,), assembled from the local row
        slices (pad rows repeat the final offset ⇒ degree 0)."""
        local = self.row_offsets[:, 1:] - self.row_offsets[:, :-1]
        return local.reshape(-1)[:self.n]


# ---------------------------------------------------------------------------
# 2-D vertex-cut partition (placement="2d")
# ---------------------------------------------------------------------------


def _chunk_sizes(base, n: int) -> tuple:
    return tuple(int(x) for x in np.diff(np.append(np.asarray(base), n)))


def unpad_chunks(v, width: int, sizes: tuple, axis: int = -1):
    """The chunks laid out ``width`` apart along ``axis`` of ``v``, each
    cut to its first ``sizes[k]`` entries and concatenated: the global
    (sum(sizes),) vertex axis (static slices, no gather)."""
    cut = jax.lax.slice_in_dim
    if all(s == width for s in sizes[:-1]):
        return cut(v, 0, sum(sizes), axis=axis)
    return jnp.concatenate(
        [cut(v, k * width, k * width + s, axis=axis)
         for k, s in enumerate(sizes)], axis=axis)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Blocks2D:
    """The column-store operand of a ``Sharded2DGraph``: per-block
    column ids plus the block↔row-chunk alignment metadata the exact 2-D
    semiring providers need. Riding inside one pytree keeps the registry
    contracts positional and placement-generic — ``B.storage_arg`` hands
    this to the 2-D spmv/spmm providers in the slot a dense column array
    occupies elsewhere.

    ``epos`` maps every block edge to its position inside the owning row
    chunk's 1-D CSR slice (``chunk_ro``): devices along one mesh row
    scatter their per-edge products into disjoint slots of one
    (chunk_emax,) buffer and ⊕-combine — merging identities only, so the
    subsequent per-row fold replays the single-device sequence exactly
    (the PR-4 bit-parity discipline survives the vertex cut)."""

    cols: jax.Array       # (R, C, be) global dst ids, pad -1
    epos: jax.Array       # (R, C, be) edge position in the row chunk
    chunk_ro: jax.Array   # (R, C, vpr+1) row-chunk CSR offsets (col-repl.)
    chunk_emax: int       # static: max edges of any row chunk
    row_sizes: tuple      # static: vertices of each row chunk

    def tree_flatten(self):
        return ((self.cols, self.epos, self.chunk_ro),
                (self.chunk_emax, self.row_sizes))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _edge_balanced_bounds(degrees: np.ndarray, parts: int) -> np.ndarray:
    """(parts+1,) ascending vertex-id cuts of ``parts`` contiguous chunks
    holding about equal shares of the degree sum: each cut sits at the
    vertex boundary whose running sum lies nearest its share (ties to
    the lower). A graph without edges gets the uniform cut."""
    n = len(degrees)
    cum = np.concatenate([[0], np.cumsum(degrees, dtype=np.int64)])
    if cum[-1] == 0:
        return np.minimum(np.arange(parts + 1) * -(-n // parts), n)
    share = cum[-1] * np.arange(1, parts) / parts
    hi = np.searchsorted(cum, share)          # first cut at or past it
    lo = np.maximum(hi - 1, 0)
    cut = np.where(share - cum[lo] <= cum[hi] - share, lo, hi)
    return np.concatenate([[0], cut, [n]]).astype(np.int64)


def _slice_blocks(ro: np.ndarray, ci: np.ndarray, ev: Optional[np.ndarray],
                  row_bounds: np.ndarray, col_bounds: np.ndarray,
                  vpr: int, vpc: int):
    """Block one CSR-like structure on the R×C vertex cut: row chunk i
    is the ids [row_bounds[i], row_bounds[i+1]), column chunk j
    [col_bounds[j], col_bounds[j+1]); ``vpr``/``vpc`` are the widest.

    Returns stacked (R, C, …) block arrays (rebased offsets, global
    column ids padded with -1, values, row-chunk edge positions), the
    (R, vpr+1) row-chunk offsets, the max chunk edge count, and the host
    accounting tables (per-block edge counts / ELL widths / distinct
    vertices materialized per block — the mirror table)."""
    from .graph import ell_width_for
    rows, cols = len(row_bounds) - 1, len(col_bounds) - 1
    blocks: list = []
    chunk_ros = []
    be_max, chunk_emax = 1, 1
    block_edges = np.zeros((rows, cols), np.int64)
    block_ell = np.ones((rows, cols), np.int64)
    mirrors = np.zeros((rows, cols), np.int64)
    for i in range(rows):
        lo_v, hi_v = int(row_bounds[i]), int(row_bounds[i + 1])
        lo_e, hi_e = int(ro[lo_v]), int(ro[hi_v])
        cro = (ro[lo_v:hi_v + 1] - ro[lo_v]).astype(np.int64)
        pad_v = vpr - (hi_v - lo_v)
        if pad_v:
            cro = np.concatenate(
                [cro, np.full(pad_v, cro[-1], cro.dtype)])
        chunk_ros.append(cro)
        chunk_emax = max(chunk_emax, hi_e - lo_e)
        c_ci = ci[lo_e:hi_e]
        c_ev = ev[lo_e:hi_e] if ev is not None else None
        epos = np.arange(hi_e - lo_e, dtype=np.int64)
        row_of = np.repeat(np.arange(hi_v - lo_v),
                           np.diff(ro[lo_v:hi_v + 1]))
        row_blocks = []
        for j in range(cols):
            sel = (c_ci >= col_bounds[j]) & (c_ci < col_bounds[j + 1])
            cnt = np.bincount(row_of[sel], minlength=vpr)[:vpr]
            b_ro = np.concatenate(
                [[0], np.cumsum(cnt)]).astype(np.int32)
            row_blocks.append((b_ro, c_ci[sel],
                               c_ev[sel] if c_ev is not None else None,
                               epos[sel]))
            ne = int(sel.sum())
            be_max = max(be_max, ne)
            block_edges[i, j] = ne
            block_ell[i, j] = ell_width_for(cnt[cnt > 0])
            # vertex copies materialized on device (i, j): distinct
            # source rows with a block edge + distinct destinations
            mirrors[i, j] = int((cnt > 0).sum()) + \
                len(np.unique(c_ci[sel]))
        blocks.append(row_blocks)
    b_ro = np.stack([np.stack([b[0] for b in r]) for r in blocks])
    b_ci = np.full((rows, cols, be_max), -1, np.int32)
    b_ep = np.zeros((rows, cols, be_max), np.int32)
    b_ev = (np.zeros((rows, cols, be_max), np.float32)
            if ev is not None else None)
    for i in range(rows):
        for j in range(cols):
            _, c, v, e = blocks[i][j]
            b_ci[i, j, :len(c)] = c
            b_ep[i, j, :len(e)] = e
            if v is not None:
                b_ev[i, j, :len(v)] = v
    chunk_ro = np.stack(chunk_ros).astype(np.int32)
    return (b_ro, b_ci, b_ev, b_ep, chunk_ro, int(chunk_emax),
            block_edges, block_ell, mirrors)


def partition_1d(graph: Graph, num_parts: int) -> PartitionedGraph:
    ro = np.asarray(graph.row_offsets)
    # decode-to-dense before slicing: shards hold SHARD_PLAN storage
    # regardless of the source plan (narrow/delta/bf16 sources partition
    # fine; exact-semiring results stay bit-identical because decode is
    # exact and fp32 round-trips bf16 values losslessly)
    ci = graph.cols_np()
    ev = (np.asarray(graph.edge_values, np.float32)
          if graph.edge_values is not None else None)
    n = graph.num_vertices
    vpp = -(-n // num_parts)  # ceil
    p_ro, p_ci, p_ev, base = _slice_rows(ro, ci, ev, n, num_parts, vpp)
    c_ro = c_ci = c_ev = None
    if graph.has_csc:
        c_ro, c_ci, c_ev, _ = _slice_rows(
            np.asarray(graph.csc_offsets),
            np.asarray(graph.csc_cols()),
            (np.asarray(graph.csc_edge_values, np.float32)
             if graph.csc_edge_values is not None else None),
            n, num_parts, vpp)
    return PartitionedGraph(n=n, m=graph.num_edges, num_parts=num_parts,
                            verts_per_part=vpp, row_offsets=p_ro,
                            col_indices=p_ci, edge_values=p_ev,
                            vertex_base=base,
                            csc_row_offsets=c_ro, csc_col_indices=c_ci,
                            csc_edge_values=c_ev, source=graph)


@dataclass(frozen=True)
class Partitioned2DGraph:
    """Host-side R×C vertex-cut edge blocks + mirror/balance accounting.

    Device (i, j) holds the block of edges with source in row chunk i
    and destination in column chunk j. ``chunk_offsets`` keeps each row
    chunk's un-blocked 1-D CSR offsets — the fold shape the exact 2-D
    semiring providers replay after merging block products — and
    ``edge_pos`` aligns every block edge back into that slice.
    ``source`` keeps the unpartitioned Graph for replicated operands and
    oracle validation, exactly like the 1-D container."""

    n: int
    m: int
    rows: int                    # R (mesh rows)
    cols: int                    # C (mesh columns)
    vpr: int                     # widest row chunk's vertices
    vpc: int                     # widest column chunk's vertices
    row_offsets: np.ndarray      # (R, C, vpr+1) rebased block CSR
    col_indices: np.ndarray      # (R, C, be) global dst ids, pad -1
    edge_values: Optional[np.ndarray]
    edge_pos: np.ndarray         # (R, C, be) position in the row chunk
    chunk_offsets: np.ndarray    # (R, vpr+1) row-chunk CSR offsets
    chunk_emax: int
    row_base: np.ndarray         # (R,) first vertex id of each row chunk
    col_base: np.ndarray         # (C,) first vertex id of each col chunk
    block_edges: np.ndarray      # (R, C) host accounting
    block_ell_width: np.ndarray  # (R, C) per-block ELL widths
    mirrors: np.ndarray          # (R, C) vertex copies per device
    # CSC mirror blocks (in-edges), same layout
    csc_row_offsets: Optional[np.ndarray] = None
    csc_col_indices: Optional[np.ndarray] = None
    csc_edge_values: Optional[np.ndarray] = None
    csc_edge_pos: Optional[np.ndarray] = None
    csc_chunk_offsets: Optional[np.ndarray] = None
    csc_chunk_emax: int = 1
    source: Optional[Graph] = None

    @property
    def num_parts(self) -> int:
        return self.rows * self.cols

    @property
    def has_csc(self) -> bool:
        return self.csc_row_offsets is not None

    def owner_of(self, v):
        """Designated owner device (mesh row, mesh col) of vertex v —
        the device whose row chunk AND column chunk both contain v;
        every other device touching v holds a mirror."""
        v = np.asarray(v)
        return (np.searchsorted(self.row_base, v, side="right") - 1,
                np.searchsorted(self.col_base, v, side="right") - 1)

    @property
    def row_sizes(self) -> tuple:
        """Vertices of each row chunk (static: the 2-D providers
        unpad row-chunk outputs with it)."""
        return _chunk_sizes(self.row_base, self.n)

    @property
    def col_sizes(self) -> tuple:
        """Vertices of each column chunk."""
        return _chunk_sizes(self.col_base, self.n)

    def balance(self) -> dict:
        """2-D load accounting: per-block edge counts, both imbalance
        factors, and the vertex-cut replication stats (mean/max copies
        of a vertex across the mesh — 2-D placements trade mirrors for
        smaller exchanges)."""
        edges = self.block_edges
        mean_e = max(edges.sum() / max(self.num_parts, 1), 1e-9)
        verts = list(self.row_sizes)
        mean_v = max(sum(verts) / max(self.rows, 1), 1e-9)
        return {
            "parts": self.num_parts,
            "mesh": [self.rows, self.cols],
            "vertices_per_chunk": verts,
            "edges_per_block": edges.astype(int).tolist(),
            "edge_imbalance": round(float(edges.max()) / mean_e, 3),
            "vertex_imbalance": round(max(verts) / mean_v, 3),
            "block_ell_width": self.block_ell_width.astype(int).tolist(),
            "mirror_factor": round(float(self.mirrors.sum())
                                   / max(self.n, 1), 3),
            "max_block_mirrors": int(self.mirrors.max()),
        }

    def shard(self, mesh, axes=("row", "col")) -> "Sharded2DGraph":
        """Device-side view for the 2-D registry providers. ``mesh``
        must carry axes ``axes`` of sizes (R, C); block (i, j) lives on
        mesh device (i, j). Cached per (mesh, axes) like the 1-D
        container."""
        axes = tuple(axes)
        check_mesh_axes(mesh, axes, (self.rows, self.cols))
        cache = self.__dict__.get("_shard_cache")
        if cache is None:
            object.__setattr__(self, "_shard_cache", {})  # frozen dc
            cache = self.__dict__["_shard_cache"]
        key = (mesh, axes)
        if key in cache:
            return cache[key]

        def repl(chunk_ro):
            # replicate the (R, vpr+1) chunk offsets along the column
            # axis so they shard like every other (R, C, …) block leaf
            return np.broadcast_to(chunk_ro[:, None, :],
                                   (self.rows, self.cols,
                                    chunk_ro.shape[1])).copy()

        put = _placer(mesh, P(*axes))
        cache[key] = Sharded2DGraph(
            row_offsets=put(self.row_offsets),
            col_indices=put(self.col_indices),
            edge_values=put(self.edge_values),
            edge_pos=put(self.edge_pos),
            chunk_offsets=put(repl(self.chunk_offsets)),
            csc_offsets=put(self.csc_row_offsets),
            csc_indices=put(self.csc_col_indices),
            csc_edge_values=put(self.csc_edge_values),
            csc_edge_pos=put(self.csc_edge_pos),
            csc_chunk_offsets=(put(repl(self.csc_chunk_offsets))
                               if self.csc_chunk_offsets is not None
                               else None),
            row_base=_placer(mesh, P(axes[0]))(self.row_base),
            col_base=_placer(mesh, P(axes[1]))(self.col_base),
            n=self.n, m=self.m, rows=self.rows, cols=self.cols,
            vpr=self.vpr, vpc=self.vpc,
            row_sizes=self.row_sizes, col_sizes=self.col_sizes,
            chunk_emax=self.chunk_emax,
            csc_chunk_emax=self.csc_chunk_emax,
            mesh=mesh, axes=axes,
            ell_width=(self.source.ell_width
                       if self.source is not None else None),
            csc_ell_width=(self.source.csc_ell_width
                           if self.source is not None else None),
            source_plan=(self.source.plan
                         if self.source is not None else None))
        return cache[key]


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Sharded2DGraph:
    """(R, C, …) stacked edge blocks as a jit-friendly pytree.

    Attribute names mirror ``Graph``/``ShardedGraph`` so primitives
    written against Graph dispatch on it unchanged; the 2-D registry
    providers understand the blocked layout. ``mesh``/``axes`` are
    static aux data (part of every jit cache key), like the 1-D
    container. ``col_store``/``csc_store`` return ``Blocks2D`` pytrees —
    the column ids plus the chunk-alignment metadata the exact semiring
    providers consume in the contract's column slot."""

    row_offsets: jax.Array            # (R, C, vpr+1)
    col_indices: jax.Array            # (R, C, be)
    edge_values: Optional[jax.Array]
    edge_pos: jax.Array               # (R, C, be)
    chunk_offsets: jax.Array          # (R, C, vpr+1) column-replicated
    csc_offsets: Optional[jax.Array]
    csc_indices: Optional[jax.Array]
    csc_edge_values: Optional[jax.Array]
    csc_edge_pos: Optional[jax.Array]
    csc_chunk_offsets: Optional[jax.Array]
    row_base: jax.Array               # (R,)
    col_base: jax.Array               # (C,)
    n: int
    m: int
    rows: int
    cols: int
    vpr: int
    vpc: int
    row_sizes: tuple                  # vertices of each row chunk
    col_sizes: tuple                  # vertices of each column chunk
    chunk_emax: int
    csc_chunk_emax: int
    mesh: object
    axes: tuple
    # ELL widths copied from the SOURCE graph: the 2-D fold must use the
    # same tree shape as the single-device sweep (placement bit-parity)
    ell_width: Optional[int] = None
    csc_ell_width: Optional[int] = None
    source_plan: Optional[S.StoragePlan] = None

    # like ShardedGraph: no stacked counterparts by design
    row_seg = None
    csc_row_seg = None
    over_pos = None
    over_row = None
    csc_over_pos = None
    csc_over_row = None

    def tree_flatten(self):
        children = (self.row_offsets, self.col_indices, self.edge_values,
                    self.edge_pos, self.chunk_offsets, self.csc_offsets,
                    self.csc_indices, self.csc_edge_values,
                    self.csc_edge_pos, self.csc_chunk_offsets,
                    self.row_base, self.col_base)
        aux = (self.n, self.m, self.rows, self.cols, self.vpr, self.vpc,
               self.row_sizes, self.col_sizes, self.chunk_emax,
               self.csc_chunk_emax, self.mesh, self.axes, self.ell_width,
               self.csc_ell_width, self.source_plan)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def num_vertices(self) -> int:
        return self.n

    @property
    def num_edges(self) -> int:
        return self.m

    @property
    def num_parts(self) -> int:
        return self.rows * self.cols

    @property
    def has_csc(self) -> bool:
        return self.csc_offsets is not None

    @property
    def weighted(self) -> bool:
        return self.edge_values is not None

    @property
    def plan(self) -> S.StoragePlan:
        return SHARD_PLAN

    @property
    def col_store(self) -> Blocks2D:
        return Blocks2D(cols=self.col_indices, epos=self.edge_pos,
                        chunk_ro=self.chunk_offsets,
                        chunk_emax=self.chunk_emax,
                        row_sizes=self.row_sizes)

    @property
    def csc_store(self) -> Blocks2D:
        return Blocks2D(cols=self.csc_indices, epos=self.csc_edge_pos,
                        chunk_ro=self.csc_chunk_offsets,
                        chunk_emax=self.csc_chunk_emax,
                        row_sizes=self.row_sizes)

    @property
    def degrees(self) -> jax.Array:
        """Global out-degree vector (n,) from the row-chunk offsets
        (pad rows repeat the final offset ⇒ degree 0)."""
        local = self.chunk_offsets[:, 0, 1:] - self.chunk_offsets[:, 0, :-1]
        return unpad_chunks(local.reshape(-1), self.vpr, self.row_sizes)


def partition_2d(graph: Graph, rows: int, cols: int) -> Partitioned2DGraph:
    """Vertex-cut 2-D partition of ``graph`` on an R×C mesh. Like
    ``partition_1d``, blocks hold SHARD_PLAN storage whatever the source
    plan chose (decode is exact)."""
    ro = np.asarray(graph.row_offsets)
    ci = graph.cols_np()
    ev = (np.asarray(graph.edge_values, np.float32)
          if graph.edge_values is not None else None)
    n = graph.num_vertices
    row_bounds = _edge_balanced_bounds(np.diff(ro), rows)
    col_bounds = _edge_balanced_bounds(
        np.bincount(ci, minlength=n), cols)
    vpr = max(int(np.diff(row_bounds).max()), 1)
    vpc = max(int(np.diff(col_bounds).max()), 1)
    (b_ro, b_ci, b_ev, b_ep, chunk_ro, chunk_emax,
     block_edges, block_ell, mirrors) = _slice_blocks(
        ro, ci, ev, row_bounds, col_bounds, vpr, vpc)
    kw: dict = {}
    if graph.has_csc:
        (c_ro, c_ci, c_ev, c_ep, c_cro, c_emax, _, _, _) = _slice_blocks(
            np.asarray(graph.csc_offsets),
            np.asarray(graph.csc_cols()),
            (np.asarray(graph.csc_edge_values, np.float32)
             if graph.csc_edge_values is not None else None),
            row_bounds, col_bounds, vpr, vpc)
        kw = dict(csc_row_offsets=c_ro, csc_col_indices=c_ci,
                  csc_edge_values=c_ev, csc_edge_pos=c_ep,
                  csc_chunk_offsets=c_cro, csc_chunk_emax=c_emax)
    return Partitioned2DGraph(
        n=n, m=graph.num_edges, rows=rows, cols=cols, vpr=vpr, vpc=vpc,
        row_offsets=b_ro, col_indices=b_ci, edge_values=b_ev,
        edge_pos=b_ep, chunk_offsets=chunk_ro, chunk_emax=chunk_emax,
        row_base=row_bounds[:-1].astype(np.int32),
        col_base=col_bounds[:-1].astype(np.int32),
        block_edges=block_edges, block_ell_width=block_ell,
        mirrors=mirrors, source=graph, **kw)
