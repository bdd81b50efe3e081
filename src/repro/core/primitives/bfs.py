"""Breadth-first search (paper §6.1) with the full optimization surface:

  * push advance with LB / TWC / THREAD workload mapping (Fig. 20 ablation)
  * direction-optimized push↔pull switching with do_a/do_b (Fig. 21)
  * idempotent mode: skip exact uniquification, rely on the heuristic
    hash/bitmask culling filter (Fig. 19 ablation)
  * predecessor recording

The LB push is the fused tiered path: one "advance_filter" dispatch per
iteration (expansion + visited test + exact first-occurrence culling +
compaction in a single op — paper §5.3's fusion applied to the whole
step), run at the smallest power-of-two capacity tier that holds the
frontier's degree sum (``enactor.tiered_step``), so an iteration's cost
tracks the live frontier instead of worst-case m. In a batch's mixed
step (some lanes pull, some push) that degree sum is the push lanes'
alone: the pull lanes' large frontiers never size the push. In-op
culling is exact for free (the bitmap is already in hand), which makes
``idempotence`` moot there; the flag keeps selecting hash-vs-exact
uniquify on the unfused TWC/THREAD ablation path.

The engine is *multi-source*: ``bfs_batch`` runs B traversals over one
shared topology as a single jitted batched BSP loop (the frontier-matrix
view — GraphBLAST's multi-source BFS), with per-lane convergence masking
in ``run_until_any`` so ragged lanes freeze as they finish. The
single-source ``bfs`` is a squeezed batch-of-1 call — one code path.

Frontier capacities: edge frontiers (the raw advance output) are sized at
m, but vertex frontiers are *post-uniquify* and need only min(n, m) slots.
Heuristic uniquification (idempotent mode) can leave more duplicates than
that; the per-lane ``overflow`` counter in ``BFSResult`` records any
discoveries dropped by the clamp so capped runs are detectable instead of
silent (a nonzero count means rerun with ``idempotence=False``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.analysis import sanitize

from .. import backend as B
from .. import operators as ops
from ..direction import PULL, PUSH, DirectionParams, decide_direction
from ..enactor import run_until_any, select_lanes, tiered_step
from ..frontier import (BatchedDenseFrontier, BatchedSparseFrontier,
                        from_ids_batch)
from ..graph import Graph


class BFSState(NamedTuple):
    labels: jax.Array        # (B, n) int32 depth, -1 unvisited
    preds: jax.Array         # (B, n) int32 predecessor, -1 none
    frontier: BatchedSparseFrontier  # sparse rep (push), (B, cap_v)
    dense: jax.Array         # (B, n) bool current frontier bitmap (pull)
    visited: jax.Array       # (B, n) bool status-check array (§5.2.1)
    n_f: jax.Array           # (B,) int32 current frontier size
    n_u: jax.Array           # (B,) int32 unvisited count
    depth: jax.Array         # (B,) int32
    mode: jax.Array          # (B,) int32 PUSH/PULL
    pull_iters: jax.Array    # (B,) int32 (for characterization)
    overflow: jax.Array      # (B,) int32 discoveries dropped by cap_v clamp


class BFSResult(NamedTuple):
    labels: jax.Array
    preds: jax.Array
    iterations: jax.Array
    pull_iters: jax.Array
    edges_visited: jax.Array
    overflow: jax.Array
    # (B,) bool: lane's frontier drained (False = an iteration budget cut
    # the traversal short and labels are partial). Defaults keep older
    # construction sites valid.
    converged: jax.Array = None


@functools.partial(jax.jit, static_argnames=(
    "direction", "idempotence", "strategy", "record_preds", "backend",
    "tiered", "telemetry", "max_iters"))
def _bfs_impl(graph: Graph, srcs: jax.Array, do_a: float, do_b: float,
              direction: bool, idempotence: bool, strategy: str,
              record_preds: bool, backend: str,
              tiered: bool = True, telemetry: bool = False,
              max_iters: Optional[int] = None):
    sanitize.trace_probe("bfs")   # compile counter: body runs only on a jit cache miss
    n, m = graph.num_vertices, graph.num_edges
    b = srcs.shape[0]
    # edge frontiers are worst-case expansion (m); vertex frontiers are
    # post-uniquify and need only min(n, m) — overflow past that is
    # counted per lane instead of silently sized away. The floor of 1
    # keeps the seed frontier representable on an edgeless graph.
    cap_v = max(min(n, m), 1)
    cap_e = m
    # LB push runs the fused advance_filter over a capacity-tier ladder:
    # each iteration expands in the smallest tier holding its live
    # workload (the frontier's degree sum) instead of worst-case cap_e.
    # Tier choice never changes results — tested bit-exact against the
    # pinned top tier (tiered=False). TWC/THREAD keep the unfused
    # ablation path at full capacity.
    caps_e = (B.tier_plan("advance_filter", cap_e)
              if (tiered and strategy == "LB" and cap_e > 0) else
              (max(cap_e, 1),))
    params = DirectionParams(do_a=do_a, do_b=do_b, enabled=direction)

    with jax.named_scope("primitive.init"):
        lane = jnp.arange(b)
        labels = jnp.full((b, n), -1, jnp.int32).at[lane, srcs].set(0)
        preds = jnp.full((b, n), -1, jnp.int32)
        visited = jnp.zeros((b, n), bool).at[lane, srcs].set(True)
        frontier = from_ids_batch(srcs, cap_v)
        state = BFSState(labels=labels, preds=preds, frontier=frontier,
                         dense=visited, visited=visited,
                         n_f=jnp.ones((b,), jnp.int32),
                         n_u=jnp.full((b,), n - 1, jnp.int32),
                         depth=jnp.zeros((b,), jnp.int32),
                         mode=jnp.full((b,), PUSH),
                         pull_iters=jnp.zeros((b,), jnp.int32),
                         overflow=jnp.zeros((b,), jnp.int32))

    def fused_push_at(cap_t: int):
        """LB push at one capacity tier: the fused advance_filter does
        expansion, visited test, exact first-occurrence culling and
        compaction in one dispatch — the (cap_t,) edge tuple never
        escapes the op, and every scatter below is frontier-shaped
        (cap_v), not edge-shaped (cap_e)."""

        def push_step(st: BFSState):
            depth1 = st.depth + 1
            new_frontier, srcs, totals = ops.advance_filter_batch(
                graph, st.frontier, st.visited, cap_t, cap_front=cap_v,
                backend=backend)
            with jax.named_scope("op.apply"):
                ids = new_frontier.ids
                tgt = jnp.where(ids >= 0, ids, n)    # n = out of bounds
                # apply: set depth (one surviving slot per discovery, so
                # the scatters are conflict-free; paper §5.2.1)
                labels = jax.vmap(
                    lambda l, t, d1: l.at[t].set(d1, mode="drop"))(
                        st.labels, tgt, depth1)
                if record_preds:
                    preds = jax.vmap(
                        lambda p, t, s: p.at[t].set(s, mode="drop"))(
                            st.preds, tgt, srcs)
                else:
                    preds = st.preds
                visited = jax.vmap(
                    lambda v, t: v.at[t].set(True, mode="drop"))(
                        st.visited, tgt)
                # exact culling can never exceed the min(n, m) vertex
                # frontier; the counter stays for the state contract
                ovf = jnp.maximum(totals - new_frontier.lengths, 0)
                return st._replace(labels=labels, preds=preds,
                                   frontier=new_frontier, dense=visited,
                                   visited=visited,
                                   n_f=new_frontier.lengths,
                                   n_u=st.n_u - new_frontier.lengths,
                                   depth=depth1,
                                   overflow=st.overflow + ovf)

        return push_step

    def legacy_push_step(st: BFSState):
        # TWC/THREAD ablation path: unfused advance → filter with the
        # idempotence-selected uniquify, at full capacity
        with jax.named_scope("op.apply"):
            depth1 = st.depth + 1

        def functor(s, d, e, rank, valid, data):
            # cond functor: discover unvisited destinations (single-lane
            # signature — advance_batch vmaps it over the batch axis)
            unseen = ~data["visited"][jnp.where(valid, d, 0)]
            return valid & unseen, data

        res, _ = ops.advance_batch(graph, st.frontier, cap_e,
                                   functor=functor,
                                   data={"visited": st.visited},
                                   strategy=strategy, backend=backend)
        with jax.named_scope("op.apply"):
            # apply: set depth (idempotent write — same value for all
            # dups, so no atomics are needed; paper §5.2.1)
            tgt = jnp.where(res.valid, res.dst, n)   # n = out of bounds
            labels = jax.vmap(
                lambda l, t, d1: l.at[t].set(d1, mode="drop"))(
                    st.labels, tgt, depth1)
            if record_preds:
                preds = jax.vmap(
                    lambda p, t, s: p.at[t].set(s, mode="drop"))(
                        st.preds, tgt, res.src)
            else:
                preds = st.preds
            visited = jax.vmap(ops.scatter_or)(res.dst, res.valid,
                                               st.visited)
        with jax.named_scope("op.filter"):
            # contract: compact the full expansion, then uniquify down
            # into the cap_v vertex frontier (exact unless idempotent
            # mode; idempotent mode uses the cheap hash-culling
            # heuristic, whose leftover duplicates are the only way to
            # overflow cap_v)
            wide = ops.advance_to_vertex_frontier_batch(res, cap_e,
                                                        backend=backend)
            uniq = "hash" if idempotence else "exact"
            new_frontier, _, ovf = ops.filter_frontier_batch(
                wide, n=n, uniquify=uniq, cap=cap_v, backend=backend)
        with jax.named_scope("op.apply"):
            return st._replace(labels=labels, preds=preds,
                               frontier=new_frontier, dense=visited,
                               visited=visited, n_f=new_frontier.lengths,
                               n_u=st.n_u - new_frontier.lengths,
                               depth=depth1, overflow=st.overflow + ovf)

    def push_step(st: BFSState):
        if strategy != "LB":
            return legacy_push_step(st)
        with jax.named_scope("enactor.tier"):
            need = jnp.max(ops.frontier_workload(graph, st.frontier))
        return tiered_step(need, caps_e, fused_push_at, st)

    def pull_step(st: BFSState):
        with jax.named_scope("op.apply"):
            depth1 = st.depth + 1
        current = BatchedDenseFrontier(st.dense)
        with jax.named_scope("op.pull"):
            unvisited = BatchedDenseFrontier(~st.visited)
        new_dense, pull_preds = ops.advance_pull_batch(
            graph, unvisited, current, return_preds=True)
        with jax.named_scope("op.apply"):
            labels = jnp.where(new_dense.flags, depth1[:, None], st.labels)
            preds = (jnp.where(new_dense.flags, pull_preds, st.preds)
                     if record_preds else st.preds)
            visited = st.visited | new_dense.flags
        with jax.named_scope("enactor.direction"):
            n_new = new_dense.lengths
            sparse = new_dense.to_sparse(cap_v, backend=backend)
        with jax.named_scope("op.apply"):
            return st._replace(labels=labels, preds=preds, frontier=sparse,
                               dense=new_dense.flags, visited=visited,
                               n_f=n_new, n_u=st.n_u - n_new, depth=depth1,
                               pull_iters=st.pull_iters + 1)

    @jax.named_scope("enactor.direction")
    def body(st: BFSState):
        if not direction:
            return push_step(st)
        mode = jax.vmap(
            lambda md, nf, nu: decide_direction(md, nf, nu, n, m, params)
        )(st.mode, st.n_f, st.n_u)
        st = st._replace(mode=mode)
        # dense rep of the *current* frontier is required by pull;
        # push_step keeps `dense` = visited, so rebuild it.
        dense_cur = st.frontier.to_dense(n).flags
        st = st._replace(dense=dense_cur)
        if b == 1:
            # batch-of-1 (the single-source path): a real branch, so the
            # idle direction costs nothing
            return jax.lax.cond(mode[0] == PULL, pull_step, push_step, st)

        @jax.named_scope("mixed")
        def mixed_step(st):
            # lanes disagree: compute both directions in lockstep and
            # select per lane. The push half sees only the push lanes'
            # frontiers (pull lanes' lengths zeroed), so its rung follows
            # their workload, not the pull lanes' large frontiers; what
            # it computes for the pull lanes is discarded either way.
            pull = mode == PULL
            fr = st.frontier
            pushed = st._replace(frontier=BatchedSparseFrontier(
                ids=fr.ids, lengths=jnp.where(pull, 0, fr.lengths)))
            return select_lanes(pull, pull_step(st), push_step(pushed))

        # direction decisions correlate strongly across lanes (shared
        # topology), so branch on the homogeneous cases and pay the
        # both-directions mixed step only when lanes actually disagree.
        # Converged lanes are frozen by run_until_any whatever we compute
        # for them, so only *active* lanes count toward homogeneity.
        active = st.n_f > 0
        return jax.lax.cond(
            jnp.all(~active | (mode == PUSH)), push_step,
            lambda s2: jax.lax.cond(jnp.all(~active | (mode == PULL)),
                                    pull_step, mixed_step, s2),
            st)

    # a query budget just lowers the loop guard — the loop stays
    # jit-clean and lanes still running at the cap come back partial
    mi = n + 1 if max_iters is None else min(n + 1, max_iters)
    buf = None
    if telemetry:
        # read-only probe: per-lane frontier size / direction / overflow
        # delta after each step; the tier rung the push half ran at,
        # recomputed from the prev frontier of the lanes that pushed (the
        # bottom rung when none did); and whether the step was mixed
        # (active lanes ran both directions).
        from ...obs.telemetry import TelemetryBuffer
        from ..frontier import tier_index
        caps_arr = jnp.asarray(caps_e, jnp.int32)

        def probe(prev: BFSState, new: BFSState) -> dict:
            push = new.mode == PUSH
            work = ops.frontier_workload(graph, prev.frontier)
            need = jnp.max(jnp.where(push, work, 0))
            tier = caps_arr[tier_index(need, caps_e)]
            active = prev.n_f > 0
            mixed = jnp.any(active & push) & jnp.any(active & ~push)
            return {"frontier": new.n_f, "tier": tier,
                    "direction": new.mode, "mixed": mixed,
                    "overflow": new.overflow - prev.overflow}

        buf0 = TelemetryBuffer.make(n + 1, {
            "frontier": ((b,), jnp.int32),
            "tier": ((), jnp.int32),
            "direction": ((b,), jnp.int32),
            "mixed": ((), jnp.int32),
            "overflow": ((b,), jnp.int32)})
        final, lane_iters, _, buf = run_until_any(
            lambda st: st.n_f > 0, body, state, max_iter=mi,
            probe=probe, telemetry=buf0)
    else:
        final, lane_iters, _ = run_until_any(lambda st: st.n_f > 0, body,
                                             state, max_iter=mi)
    with jax.named_scope("primitive.result"):
        edges = jnp.sum(jnp.where(final.labels >= 0,
                                  graph.degrees[None, :], 0),
                        axis=1).astype(jnp.int32)
        result = BFSResult(labels=final.labels, preds=final.preds,
                           iterations=lane_iters,
                           pull_iters=final.pull_iters,
                           edges_visited=edges, overflow=final.overflow,
                           converged=final.n_f == 0)
    return (result, buf) if telemetry else result


def bfs_batch(graph: Graph, srcs, *, direction: bool = True,
              do_a: float = 0.001, do_b: float = 0.2,
              idempotence: bool = True, strategy: str = "LB",
              record_preds: bool = True,
              backend: Optional[str] = None,
              tiered: bool = True, telemetry: bool = False,
              budget=None):
    """Multi-source BFS: one jitted batched BSP loop over ``srcs``.

    Every ``BFSResult`` field carries a leading batch axis; lane i is
    bit-identical to ``bfs(graph, srcs[i])``. All lanes share one trace —
    batches of the same size never retrace, which is the contract the
    query-serving driver (launch/graph_serve.py) relies on.

    ``tiered=False`` pins every push to the top capacity tier (the
    worst-case-sized program) — results are bit-identical to the tiered
    default; the flag exists for the tier-parity tests and A/B
    benchmarking.

    ``telemetry=True`` returns ``(BFSResult, TelemetryBuffer)`` — the
    buffer holds per-iteration frontier size / tier (the push's rung) /
    direction / mixed (0/1) / overflow columns (``obs.telemetry.trim``
    converts to host arrays); the result itself is bit-identical to
    ``telemetry=False``.

    ``budget`` (``repro.ft.Budget``) caps BSP iterations per query: lanes
    cut short come back with partial labels and ``converged=False``; the
    wall-clock half of the budget is the serving loop's job. ``budget=None``
    (or an unlimited budget) is bit-identical to the historical path."""
    if direction and not graph.has_csc:
        direction = False
    srcs = jnp.asarray(srcs, dtype=jnp.int32).reshape(-1)
    max_iters = None if budget is None else budget.max_iters
    return _bfs_impl(graph, srcs, do_a, do_b, direction, idempotence,
                     strategy, record_preds, B.resolve(backend),
                     tiered, telemetry, max_iters)


def bfs(graph: Graph, src: int, *, direction: bool = True,
        do_a: float = 0.001, do_b: float = 0.2, idempotence: bool = True,
        strategy: str = "LB", record_preds: bool = True,
        backend: Optional[str] = None,
        use_kernel: Optional[bool] = None, telemetry: bool = False):
    """Run BFS from ``src`` — a squeezed batch-of-1 ``bfs_batch`` call.

    ``backend`` selects the operator backend ("xla" | "pallas" | "auto";
    None defers to the ambient context / REPRO_BACKEND). ``use_kernel``
    is the deprecated alias (public wrapper only) and always warns.
    ``telemetry=True`` returns ``(BFSResult, TelemetryBuffer)`` with the
    result squeezed but the buffer keeping its lane axis (lane 0)."""
    r = bfs_batch(graph, [src], direction=direction, do_a=do_a, do_b=do_b,
                  idempotence=idempotence, strategy=strategy,
                  record_preds=record_preds,
                  backend=B.resolve(backend, use_kernel),
                  telemetry=telemetry)
    if telemetry:
        res, buf = r
        return jax.tree_util.tree_map(lambda x: x[0], res), buf
    return jax.tree_util.tree_map(lambda x: x[0], r)
