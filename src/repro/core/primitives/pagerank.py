"""PageRank (paper §6.5) — the algebra layer's flagship consumer.

Each iteration is one plus-times SpMV over the CSC transpose
(rank mass flows along reversed edges: ``acc = Aᵀ ⊗ contrib``) plus a
convergence filter that retires settled vertices. The paper implements
the same sweep as an advance with atomicAdd; GraphBLAST's observation —
PR *is* SpMV over the plus-times semiring — is taken literally here:
the contribution sweep dispatches through the ``"spmv"`` registry op of
``repro.linalg`` on BOTH backends (xla: gather + segment-sum, fused by
XLA; pallas: the fused masked-semiring ELL row kernel).

The ELL pack width is static graph metadata computed exactly once at
build time (``Graph.from_csr`` → ``Graph.csc_ell_width``); the impl is
jit-clean end to end — no host synchronization inside the iteration
loop (asserted by a one-trace test in tests/test_linalg.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.linalg import semiring as SR

from repro.analysis import sanitize

from .. import backend as B
from ..enactor import run_until
from ..graph import Graph


class PRState(NamedTuple):
    rank: jax.Array       # (n,) float32
    active: jax.Array     # (n,) bool — the frontier (unconverged vertices)
    n_active: jax.Array   # () int32
    iters: jax.Array      # () int32


class PRResult(NamedTuple):
    rank: jax.Array
    iterations: jax.Array
    # () bool: ranks settled below tol OR the *requested* sweep count ran
    # to completion; False only when a query budget cut sweeps short
    converged: jax.Array = None


def _fixed_tree_sum(x: jax.Array) -> jax.Array:
    """Float sum with an accumulation grouping fixed by construction:
    explicit pairwise halving, each step one elementwise add. A plain
    ``jnp.sum`` leaves the grouping to per-program codegen, which drifts
    by an ulp between the single-device and shard_map programs; here the
    tree IS the dataflow, so both placements compute identical bits."""
    n = int(x.shape[0])
    k = 1
    while k < n:
        k *= 2
    x = jnp.pad(x, (0, k - n))
    while k > 1:
        k //= 2
        x = x[:k] + x[k:]
    return x[0]


@functools.partial(jax.jit, static_argnames=("max_iter", "backend",
                                             "ell_width", "placement",
                                             "precision", "telemetry",
                                             "full_iter"))
def _pagerank_impl(graph: Graph, inv_deg: jax.Array, damping: jax.Array,
                   tol: jax.Array, max_iter: int, backend: str,
                   ell_width: Optional[int],
                   placement: str = B.SINGLE,
                   precision: str = "fp32",
                   telemetry: bool = False,
                   full_iter: Optional[int] = None):
    sanitize.trace_probe("pagerank")   # compile counter: body runs only on a jit cache miss
    n = graph.num_vertices
    # PageRank's sweep is dense — every row contributes every iteration —
    # so it is explicitly PINNED to the top capacity tier (pin=True); the
    # frontier-proportional tier ladder applies to traversal, not to
    # dense algebra. Sharded placements pin for a second reason:
    # collective shapes must agree across devices.
    spmv_op, _tiers = B.dispatch_tiered("spmv", backend, placement,
                                        cap=n, pin=True)
    # the storage-plan column store when the provider decodes it
    # natively, else the dense fallback view (decoded once, hoisted out
    # of the iteration loop)
    csc = B.storage_arg("spmv", backend, placement, graph=graph,
                        side="csc")
    sr = SR.with_precision(SR.plus_times, precision)

    def body(st: PRState):
        # contribution split: rank × (host-precomputed) reciprocal
        # out-degree. The reciprocal is NOT computed in-loop on purpose:
        # XLA's per-kernel codegen emits an approximate (±1 ulp)
        # division depending on what the op is fused with, and the
        # fusion context differs between a single-device gather sweep
        # and a shard_map call — sharded ranks then drift from
        # single-device ranks. A single IEEE multiply has no such
        # freedom, so placement bit-parity (a tested contract) holds.
        # inv_deg is 0 on dangling vertices, folding the deg>0 guard in.
        with jax.named_scope("op.spmv"):
            contrib = st.rank * inv_deg
            # acc = Aᵀ ⊗ contrib over plus-times (structural adjacency).
            # The CSC edge→row map rides along as build-time metadata so
            # the sweep never re-derives it inside the loop (it was the
            # largest single per-iteration cost of this impl).
            acc = spmv_op(graph.csc_offsets, csc, None, contrib,
                          sr, ell_width, None, graph.csc_row_seg,
                          graph.csc_over_pos, graph.csc_over_row)
        with jax.named_scope("op.apply"):
            # grouping-fixed sum — see _fixed_tree_sum for why jnp.sum
            # would break placement bit-parity here
            dangling = _fixed_tree_sum(
                jnp.where(inv_deg == 0, st.rank, 0.0)) / n
            new_rank = (1.0 - damping) / n + damping * (acc + dangling)
            # convergence filter: retire vertices whose rank has settled
            still = jnp.abs(new_rank - st.rank) > tol
            return PRState(rank=new_rank, active=still,
                           n_active=jnp.sum(still).astype(jnp.int32),
                           iters=st.iters + 1)

    # float32-pinned: under jax_enable_x64 the bare python literal would
    # seed a float64 rank vector and the whole loop would run (and
    # retrace) in double precision
    with jax.named_scope("primitive.init"):
        state = PRState(rank=jnp.full((n,), 1.0 / n, jnp.float32),
                        active=jnp.ones((n,), bool),
                        n_active=jnp.int32(n), iters=jnp.int32(0))
    # the caller's *requested* sweep count: "converged" means ranks
    # settled OR the requested sweeps all ran — only a budget cutting
    # max_iter below full_iter can make it False
    fi = max_iter if full_iter is None else full_iter

    @jax.named_scope("primitive.result")
    def _conv(final, iters):
        return (final.n_active == 0) | (iters >= fi)

    if telemetry:
        # per-sweep active (not-yet-converged) vertex count: the dense
        # analogue of a frontier trajectory — with tol=0 it stays n
        # until the final sweep, with tol>0 it charts convergence
        from ...obs.telemetry import TelemetryBuffer
        buf0 = TelemetryBuffer.make(max_iter, {
            "active": ((), jnp.int32)})
        final, iters, buf = run_until(
            lambda st: st.n_active > 0, body, state, max_iter=max_iter,
            probe=lambda prev, new: {"active": new.n_active},
            telemetry=buf0)
        return PRResult(rank=final.rank, iterations=iters,
                        converged=_conv(final, iters)), buf
    final, iters = run_until(lambda st: st.n_active > 0, body, state,
                             max_iter=max_iter)
    return PRResult(rank=final.rank, iterations=iters,
                    converged=_conv(final, iters))


def pagerank(graph, *, damping: float = 0.85, tol: float = 0.0,
             max_iter: int = 20, backend: Optional[str] = None,
             use_kernel: Optional[bool] = None,
             ell_width: Optional[int] = None,
             placement: Optional[str] = None,
             precision: str = "fp32", telemetry: bool = False,
             budget=None):
    """``graph`` may be a ``Graph`` or a ``ShardedGraph``
    (``partition_1d(...).shard(mesh)``) — a sharded graph routes the
    SpMV sweep through the mesh providers and the SAME impl otherwise,
    so ranks bit-match across placements. ``precision="bf16"`` runs the
    sweep's ⊗ in bfloat16 (fp32 accumulate) — ranks then agree with the
    fp32 run to ~1e-2 absolute on a unit-mass vector (the documented
    parity tolerance; see DESIGN.md §8), not bit-exactly.

    ``budget`` (``repro.ft.Budget``) caps the sweep count below
    ``max_iter``: a cut-short run returns the partial ranks with
    ``converged=False``; without a budget the result is bit-identical to
    the historical path."""
    assert graph.has_csc, "pagerank uses the CSC transpose"
    bk = B.resolve(backend, use_kernel)
    pl, ctx = B.resolve_graph_placement(graph, placement)
    if ell_width is None:
        # static kernel metadata, computed exactly once at Graph build
        # time (Graph.from_csr) — never recomputed here, so the impl
        # stays synchronization-free on every path
        ell_width = graph.csc_ell_width
    if ell_width is None and bk == B.PALLAS and pl == B.SINGLE:
        raise ValueError(
            "pagerank on the pallas backend needs Graph.csc_ell_width; "
            "build the Graph via Graph.from_csr / from_edge_list (the "
            "width is computed once at build time) or pass ell_width=")
    effective = max_iter if budget is None else budget.cap_iters(max_iter)
    with ctx:
        return _pagerank_impl(
            graph, _inv_out_degrees(graph), jnp.float32(damping),
            jnp.float32(tol), effective, bk,
            None if ell_width is None else int(ell_width), pl,
            precision, telemetry, full_iter=max_iter)


def _inv_out_degrees(graph) -> jax.Array:
    """Exact host-side reciprocal out-degrees (0 on dangling vertices);
    see the in-loop comment for why the division never happens on
    device. Memoized on the graph instance — the host sync + transfer
    happens once per graph, not once per serving-loop call. (Both graph
    containers are frozen dataclasses; the cache rides ``__dict__``
    outside the pytree fields.)"""
    cached = graph.__dict__.get("_inv_deg")
    if cached is None:
        import numpy as np
        deg = np.asarray(graph.degrees).astype(np.float32)
        inv = np.where(deg > 0, np.float32(1.0) / np.maximum(deg, 1.0),
                       np.float32(0.0)).astype(np.float32)
        cached = jnp.asarray(inv)
        object.__setattr__(graph, "_inv_deg", cached)
    return cached
