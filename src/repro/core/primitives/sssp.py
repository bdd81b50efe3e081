"""Single- and multi-source shortest path (paper §6.2, Algorithm 1).

Delta-stepping [Davidson et al. / Meyer-Sanders] via Gunrock's two-level
priority queue (§5.1.5): each iteration advances the *near* frontier,
relaxes distances with a segment-min (the atomicMin replacement), filters
redundant discoveries, and splits the improved set into near/far piles by
the current bucket threshold. When the near pile drains, the bucket index
advances and the far pile is re-split.

``sssp_batch`` runs B sources as one jitted batched BSP loop: every lane
keeps its own near/far piles and bucket counter, each step computes the
relax and the bucket-pop for all lanes in lockstep and selects per lane
(the pop is a cheap mask split, so idle-direction work is negligible),
and ``run_until_any`` freezes converged lanes until the stragglers drain.
``sssp`` is a squeezed batch-of-1 call — one code path.

``delta=None`` selects the auto heuristic; a huge delta degenerates to
Bellman-Ford mode (everything is near — the Ligra comparison baseline).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.analysis import sanitize

from .. import backend as B
from .. import operators as ops
from ..enactor import run_until_any, select_lanes, tiered_step
from ..frontier import BatchedDenseFrontier
from ..graph import Graph

INF = jnp.float32(jnp.inf)


class SSSPState(NamedTuple):
    dist: jax.Array       # (B, n) float32
    preds: jax.Array      # (B, n) int32
    near: jax.Array       # (B, n) bool  near-pile membership mask
    far: jax.Array        # (B, n) bool  far-pile membership mask
    bucket: jax.Array     # (B,) int32   current priority level
    n_near: jax.Array     # (B,) int32
    relaxations: jax.Array  # (B,) int32 total edge relaxations per lane


class SSSPResult(NamedTuple):
    dist: jax.Array
    preds: jax.Array
    iterations: jax.Array
    relaxations: jax.Array
    # (B,) bool: both piles drained (False = iteration budget cut the
    # relaxation short and dist is an upper bound, not the fixpoint)
    converged: jax.Array = None


@functools.partial(jax.jit, static_argnames=("use_delta", "strategy",
                                             "backend", "tiered",
                                             "telemetry", "max_iters"))
def _sssp_impl(graph: Graph, srcs: jax.Array, delta: jax.Array,
               use_delta: bool, strategy: str,
               backend: str, tiered: bool = True,
               telemetry: bool = False,
               max_iters: Optional[int] = None):
    sanitize.trace_probe("sssp")   # compile counter: body runs only on a jit cache miss
    n, m = graph.num_vertices, graph.num_edges
    b = srcs.shape[0]
    # relax sweeps run at the smallest capacity tier holding the near
    # pile's degree sum — delta-stepping's whole point is small near
    # piles, so most relaxes run orders of magnitude below worst-case m.
    # Results are bit-identical across tiers (tested vs tiered=False).
    # THREAD pins to the top tier: its O(m) static sweep is truncated at
    # cap_out, not workload-bounded, so a smaller tier would drop edges.
    # ladder keyed under "advance" — the op the expansion kernels tile
    # (advance_fused_batch_kernel's tuner key), so the floor coupling
    # reads the entries the probes actually write
    caps_e = (B.tier_plan("advance", m)
              if (tiered and m > 0 and strategy != "THREAD")
              else (max(m, 1),))
    with jax.named_scope("primitive.init"):
        lane = jnp.arange(b)
        dist = jnp.full((b, n), INF).at[lane, srcs].set(0.0)
        preds = jnp.full((b, n), -1, jnp.int32)
        near = jnp.zeros((b, n), bool).at[lane, srcs].set(True)
        state = SSSPState(dist=dist, preds=preds, near=near,
                          far=jnp.zeros((b, n), bool),
                          bucket=jnp.zeros((b,), jnp.int32),
                          n_near=jnp.ones((b,), jnp.int32),
                          relaxations=jnp.zeros((b,), jnp.int32))

    def relax_at(cap_t: int):
        def relax_step(st: SSSPState):
            return _relax_step(st, cap_t)
        return relax_step

    def relax_step(st: SSSPState):
        with jax.named_scope("enactor.tier"):
            need = jnp.max(jnp.sum(
                jnp.where(st.near, graph.degrees[None, :], 0), axis=1))
        return tiered_step(need, caps_e, relax_at, st)

    @jax.named_scope("op.relax")
    def _relax_step(st: SSSPState, cap_t: int):
        frontier = BatchedDenseFrontier(st.near).to_sparse(
            n, backend=backend)

        def functor(s, d, e, rank, valid, data):
            return valid, data

        res, _ = ops.advance_batch(graph, frontier, cap_t,
                                   functor=functor,
                                   strategy=strategy, backend=backend)
        w = graph.edge_values[jnp.where(res.valid, res.edge_id, 0)]
        safe_src = jnp.where(res.valid, res.src, 0)
        cand = jnp.take_along_axis(st.dist, safe_src, axis=1) + w
        # atomicMin replacement: segment-min into dist (paper Update_Label)
        with jax.named_scope("op.apply"):
            new_dist = jax.vmap(ops.scatter_min)(cand, res.dst, res.valid,
                                                 st.dist)
        improved = new_dist < st.dist
        # Set_Pred: the winning edge writes the predecessor
        safe_dst = jnp.where(res.valid, res.dst, 0)
        winner = res.valid & (cand <= jnp.take_along_axis(new_dist,
                                                          safe_dst, axis=1))
        with jax.named_scope("op.apply"):
            preds = jax.vmap(lambda p, wn, d, s: p.at[
                jnp.where(wn, d, n)].set(s, mode="drop"))(
                    st.preds, winner, res.dst, res.src)
        # priority-queue split (near/far) on the improved vertices
        thresh = (st.bucket.astype(jnp.float32) + 1.0) * delta
        if use_delta:
            add_near = improved & (new_dist < thresh[:, None])
            add_far = improved & (new_dist >= thresh[:, None])
        else:
            add_near = improved
            add_far = jnp.zeros_like(improved)
        # vertices stay in far until their bucket comes up; improved ones
        # migrate piles according to their *new* distance
        far = (st.far | add_far) & ~add_near
        relax = st.relaxations + res.total
        return st._replace(dist=new_dist, preds=preds, near=add_near,
                           far=far,
                           n_near=jnp.sum(add_near, axis=1,
                                          dtype=jnp.int32),
                           relaxations=relax)

    @jax.named_scope("op.bucket")
    def pop_far(st: SSSPState):
        # near pile empty: advance the bucket to the smallest far distance
        far_min = jnp.min(jnp.where(st.far, st.dist, INF), axis=1)
        new_bucket = jnp.where(jnp.isfinite(far_min),
                               (far_min / delta).astype(jnp.int32),
                               st.bucket + 1)
        thresh = (new_bucket.astype(jnp.float32) + 1.0) * delta
        near = st.far & (st.dist < thresh[:, None])
        far = st.far & ~near
        return st._replace(near=near, far=far, bucket=new_bucket,
                           n_near=jnp.sum(near, axis=1, dtype=jnp.int32))

    def body(st: SSSPState):
        if b == 1:
            # batch-of-1 (the single-source path): a real branch, so
            # bucket-pop iterations never pay an idle relax sweep
            return jax.lax.cond(st.n_near[0] > 0, relax_step, pop_far, st)

        def mixed_step(st):
            # lanes disagree (relax vs bucket pop); the pop is a cheap
            # mask split, so compute both and select per lane
            return select_lanes(st.n_near > 0, relax_step(st), pop_far(st))

        # bucket advances tend to synchronize on a shared topology: when
        # no lane has near work, skip the idle full-edge relax sweep
        return jax.lax.cond(jnp.any(st.n_near > 0), mixed_step, pop_far,
                            st)

    def cond(st: SSSPState):
        return (st.n_near > 0) | jnp.any(st.far, axis=1)

    # query budget: lower the guard, keep the loop jit-clean
    mi = 4 * n + 8 if max_iters is None else min(4 * n + 8, max_iters)
    buf = None
    if telemetry:
        # per-step near-pile size, bucket level, relaxation delta, and
        # the relax tier the step's workload selected (bucket-pop steps
        # record the hypothetical tier of their empty near pile — rung 0)
        from ...obs.telemetry import TelemetryBuffer
        from ..frontier import tier_index
        caps_arr = jnp.asarray(caps_e, jnp.int32)

        def probe(prev: SSSPState, new: SSSPState) -> dict:
            need = jnp.max(jnp.sum(
                jnp.where(prev.near, graph.degrees[None, :], 0), axis=1))
            tier = caps_arr[tier_index(need, caps_e)]
            return {"frontier": new.n_near, "tier": tier,
                    "bucket": new.bucket,
                    "relaxations": new.relaxations - prev.relaxations}

        buf0 = TelemetryBuffer.make(4 * n + 8, {
            "frontier": ((b,), jnp.int32),
            "tier": ((), jnp.int32),
            "bucket": ((b,), jnp.int32),
            "relaxations": ((b,), jnp.int32)})
        final, lane_iters, _, buf = run_until_any(
            cond, body, state, max_iter=mi,
            probe=probe, telemetry=buf0)
    else:
        final, lane_iters, _ = run_until_any(cond, body, state,
                                             max_iter=mi)
    with jax.named_scope("primitive.result"):
        result = SSSPResult(dist=final.dist, preds=final.preds,
                            iterations=lane_iters,
                            relaxations=final.relaxations,
                            converged=~cond(final))
    return (result, buf) if telemetry else result


def _auto_delta(graph: Graph) -> float:
    """Avg weight × avg degree heuristic from Davidson et al."""
    mean_w = float(jnp.mean(graph.edge_values))
    avg_deg = max(graph.num_edges / max(graph.num_vertices, 1), 1.0)
    return mean_w * avg_deg / 2.0


def sssp_batch(graph: Graph, srcs, *, delta: Optional[float] = None,
               strategy: str = "LB",
               backend: Optional[str] = None,
               tiered: bool = True, telemetry: bool = False,
               budget=None):
    """Multi-source delta-stepping: one jitted batched program over
    ``srcs``; lane i is bit-identical to ``sssp(graph, srcs[i])``.
    ``tiered=False`` pins relax sweeps to the worst-case capacity
    (bit-identical results; the tier-parity test hook).
    ``telemetry=True`` returns ``(SSSPResult, TelemetryBuffer)`` with
    per-iteration near-pile size / tier / bucket / relaxation columns;
    the result is bit-identical to ``telemetry=False``.
    ``budget`` caps BSP iterations per query (``converged=False`` on lanes
    cut short — their ``dist`` is an upper bound, not the fixpoint)."""
    assert graph.weighted, "SSSP needs edge weights"
    if delta is None:
        delta = _auto_delta(graph)
    use_delta = bool(jnp.isfinite(delta)) and delta > 0
    srcs = jnp.asarray(srcs, dtype=jnp.int32).reshape(-1)
    max_iters = None if budget is None else budget.max_iters
    return _sssp_impl(graph, srcs, jnp.float32(delta), use_delta,
                      strategy, B.resolve(backend), tiered, telemetry,
                      max_iters)


def sssp(graph: Graph, src: int, *, delta: Optional[float] = None,
         strategy: str = "LB", backend: Optional[str] = None,
         use_kernel: Optional[bool] = None, telemetry: bool = False):
    """Delta-stepping SSSP — a squeezed batch-of-1 ``sssp_batch`` call.
    ``delta=None`` = auto heuristic; ``use_kernel`` is the deprecated
    alias (public wrapper only) and always warns."""
    r = sssp_batch(graph, [src], delta=delta, strategy=strategy,
                   backend=B.resolve(backend, use_kernel),
                   telemetry=telemetry)
    if telemetry:
        res, buf = r
        return jax.tree_util.tree_map(lambda x: x[0], res), buf
    return jax.tree_util.tree_map(lambda x: x[0], r)


def sssp_bellman_ford(graph: Graph, src: int, *,
                      strategy: str = "LB",
                      backend: Optional[str] = None) -> SSSPResult:
    """Bellman-Ford-style full relaxation (the Ligra comparison baseline):
    a batch-of-1 run with the priority queue disabled."""
    srcs = jnp.asarray([src], dtype=jnp.int32)
    r = _sssp_impl(graph, srcs, jnp.float32(1e30), False, strategy,
                   B.resolve(backend))
    return jax.tree_util.tree_map(lambda x: x[0], r)
