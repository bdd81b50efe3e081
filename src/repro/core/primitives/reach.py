"""k-hop reachability — or-and batched over sources (an algebraic BFS).

``reach_batch`` answers B reachability queries against one shared
topology as a single jitted program: the frontier matrix R (n, B) holds
one 0/1 column per source lane, and each hop is one dense-accumulator
SpMM over the or-and semiring through the CSC mirror
(``R'[v, b] = ⋁_u A[u, v] ∧ R[u, b]``), ⊕-merged into R. This is the
linear-algebra reading of ``bfs_batch`` with depths erased — exactly
GraphBLAST's boolean closure — and it exercises the masked product for
real: rows every lane has already reached are masked out of the sweep
(the complement of the all-reached set), which is the algebraic twin of
BFS's visited-set culling.

Batched over sources like ``bfs_batch``: every result field carries a
leading batch axis; the single-source ``reach`` is a squeezed
batch-of-1 call. Oracle: lane b of ``reached`` equals
``0 <= bfs depth <= k``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.linalg import semiring as SR

from .. import backend as B
from ..graph import Graph


class ReachResult(NamedTuple):
    reached: jax.Array    # (B, n) bool — within k hops of srcs[b]
    counts: jax.Array     # (B,) int32 reachable-set sizes
    hops: jax.Array       # () int32 the k that was run
    # () bool: all requested hops ran; False only when a budget clamps k
    converged: jax.Array = None


@functools.partial(jax.jit, static_argnames=("k", "backend", "ell_width",
                                             "placement"))
def _reach_impl(graph: Graph, srcs: jax.Array, k: int, backend: str,
                ell_width: Optional[int],
                placement: str = B.SINGLE) -> ReachResult:
    n = graph.num_vertices
    b = srcs.shape[0]
    spmm_op = B.dispatch("spmm", backend, placement)
    csc = B.storage_arg("spmm", backend, placement, graph=graph,
                        side="csc")
    with jax.named_scope("primitive.init"):
        r0 = jnp.zeros((n, b), jnp.float32).at[
            srcs, jnp.arange(b, dtype=jnp.int32)].set(1.0)

    def hop(_, r):
        with jax.named_scope("op.spmm"):
            # complemented mask: rows already reached by EVERY lane
            # cannot change (R is monotone under ⋁), so skip their sweep
            need = jnp.min(r, axis=1) < 1.0
            new = spmm_op(graph.csc_offsets, csc, None, r,
                          SR.or_and, ell_width, need, graph.csc_row_seg)
        with jax.named_scope("op.apply"):
            return jnp.maximum(r, new)

    with jax.named_scope("enactor.loop"):
        r = jax.lax.fori_loop(0, k, hop, r0)
    with jax.named_scope("primitive.result"):
        reached = r.T > 0
        return ReachResult(reached=reached,
                           counts=jnp.sum(reached, axis=1).astype(
                               jnp.int32),
                           hops=jnp.int32(k),
                           converged=jnp.bool_(True))


def reach_batch(graph, srcs, k: int = 3, *,
                backend: Optional[str] = None,
                use_kernel: Optional[bool] = None,
                placement: Optional[str] = None,
                budget=None) -> ReachResult:
    """B-source k-hop reachability as ONE jitted or-and program.
    ``graph`` may be a ``ShardedGraph`` — each hop's CSC SpMM then runs
    through the sharded registry provider (bit-matching results).
    ``budget`` clamps ``k`` to ``budget.max_iters``: a clamped run
    answers the smaller neighborhood (``hops`` records what actually ran,
    ``converged=False``)."""
    assert graph.has_csc, "reach uses the CSC transpose (pull sweeps)"
    bk = B.resolve(backend, use_kernel)
    pl, ctx = B.resolve_graph_placement(graph, placement)
    ell_width = graph.csc_ell_width
    if ell_width is None and bk == B.PALLAS and pl == B.SINGLE:
        raise ValueError(
            "reach on the pallas backend needs Graph.csc_ell_width; "
            "build the Graph via Graph.from_csr / from_edge_list")
    srcs = jnp.asarray(srcs, jnp.int32).reshape(-1)
    k_eff = int(k) if budget is None else budget.cap_iters(int(k))
    with ctx:
        res = _reach_impl(graph, srcs, k_eff, bk,
                          None if ell_width is None else int(ell_width),
                          pl)
    if k_eff < int(k):
        res = res._replace(converged=jnp.bool_(False))
    return res


def reach(graph: Graph, src: int, k: int = 3, *,
          backend: Optional[str] = None,
          use_kernel: Optional[bool] = None) -> ReachResult:
    """Single-source k-hop reachability — a squeezed batch-of-1 call."""
    r = reach_batch(graph, [src], k, backend=backend, use_kernel=use_kernel)
    return ReachResult(reached=r.reached[0], counts=r.counts[0],
                       hops=r.hops)
