"""Graph query-serving driver — batched mixed-kind query serving.

The inference-side drivers (launch/serve.py) pack token requests into
fixed-shape batch slots; this driver applies the same slot discipline to
*graph queries*, the ROADMAP's heavy-traffic scenario. A stream of
queries is packed into batches of ``--batch`` fixed slots and each batch
runs as ONE jitted multi-source program: the first batch of a kind pays
the trace, every later batch of the same (kind, shape) reuses it, and a
ragged final batch is padded with repeated sources on dead-weight slots
rather than retracing at a new shape.

The stream is no longer traversal-only: ``--kinds bfs,sssp,pagerank,reach``
serves MIXED query kinds from one stream — each kind keeps its own slot
queue (one compiled program per kind) and flushes when full, so
traversal queries (``bfs_batch`` / ``sssp_batch``), algebraic queries
(``reach_batch`` — or-and k-hop reachability) and global analytics
queries (``pagerank`` — one run answers its whole batch) interleave on
one engine. Per-kind latency is reported alongside the aggregate, and
lands in ``--json``.

Reports per-query latency (enqueue → batch completion, so queuing delay
from batch formation is included; each query's enqueue time is stamped
when it joins its slot queue) and aggregate queries/sec.

``--parts P`` serves the same stream from a mesh: the graph is 1-D
partitioned once at startup, traversal kinds run the distributed
engine (bitmask-exchange advance), algebraic kinds the sharded
spmv/spmm providers — results bit-match single-device serving, and
``--json`` rows gain per-device balance accounting (edge AND vertex
imbalance — on rmat graphs the former is what hub skew shows up in).
``--mesh RxC`` serves from the 2-D vertex-cut placement instead
(``--parts P`` is the 1-D alias): edges are blocked on an R×C device
mesh and the frontier exchange is chunk-proportional, not
n-proportional. Results bit-match either way.

  PYTHONPATH=src python -m repro.launch.graph_serve --graph rmat \
      --scale 10 --kinds bfs,pagerank,reach --requests 64 --batch 8

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.graph_serve --graph rmat \
      --scale 10 --parts 4 --kinds bfs,sssp,pagerank,reach \
      --requests 64 --batch 8

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.graph_serve --graph rmat \
      --scale 10 --mesh 2x4 --kinds bfs,sssp,pagerank,reach \
      --requests 64 --batch 8

  PYTHONPATH=src python -m repro.launch.graph_serve --graph rmat \
      --scale 10 --primitive bfs --requests 64 --batch 8 --backend xla
"""
from __future__ import annotations

import argparse
import itertools
import json
import time

import jax
import numpy as np

from repro import compile_cache, ft, obs
from repro.core import backend as B
from repro.core import ref as R
from repro.core.storage import resident_bytes
from repro.core.primitives import bfs_batch, pagerank, reach_batch, \
    sssp_batch
from repro.ft import inject
from repro.obs.metrics import Metrics, latency_summary

from .graph_run import make_graph

KINDS = ("bfs", "sssp", "pagerank", "reach")

# query terminal statuses (the per-query contract of serve_mixed) and
# the metrics counter each one lands in — the reconciliation invariant
# the chaos suite asserts: counter sums == status counts in the results
STATUSES = ("ok", "degraded", "deadline_exceeded", "shed", "error")
_STATUS_COUNTER = {
    "ok": "queries_ok_total",
    "degraded": "queries_degraded_total",
    "deadline_exceeded": "queries_deadline_total",
    "shed": "queries_shed_total",
    "error": "queries_error_total",
}

# injected-straggler stall: long enough that the watchdog's robust-median
# multiple flags it on any realistic batch cadence
_STRAGGLER_SLEEP_S = 0.2

log = obs.get_logger("graph_serve")


class PoisonedResultError(RuntimeError):
    """A kernel output failed the NaN/Inf guardrail probe."""


def serve(g, primitive: str, sources: np.ndarray, batch: int,
          backend: str, validate: bool = False,
          metrics: Metrics | None = None) -> dict:
    """Serve ``sources`` in fixed batches; returns latency/qps stats.
    Quantiles are linearly interpolated (``obs.metrics.latency_summary``)
    and reported alongside their sample count. An optional ``metrics``
    registry collects per-kind latency histograms / occupancy gauges /
    counters for the ``--metrics`` Prometheus dump."""
    run = {"bfs": bfs_batch, "sssp": sssp_batch}[primitive]
    n_q = len(sources)
    if n_q == 0:
        raise ValueError("empty query stream (requests must be > 0)")
    lat_ms = np.zeros(n_q)
    failures = 0
    overflow = 0                 # BFS discoveries dropped by the cap clamp
    answers = []                 # validated after the clock stops
    t_start = time.monotonic()
    enqueue = np.full(n_q, t_start)        # closed loop: all queries queued
    done = 0
    batches = 0
    while done < n_q:
        sl = sources[done:done + batch]
        # static-shape slots: pad the ragged tail by repeating the last
        # query (padding lanes are computed but not reported)
        srcs = np.concatenate(
            [sl, np.full(batch - len(sl), sl[-1], sl.dtype)])
        r = run(g, srcs, backend=backend)
        field = r.dist if primitive == "sssp" else r.labels
        jax.block_until_ready(field)
        t_done = time.monotonic()
        if primitive == "bfs":
            # nonzero means a capped frontier dropped discoveries — the
            # lane's answer is untrustworthy and must not ship silently
            overflow += int(np.asarray(r.overflow)[:len(sl)].sum())
        if validate:
            answers.append((sl, np.asarray(field)))
        batch_lat = (t_done - enqueue[done:done + len(sl)]) * 1e3
        lat_ms[done:done + len(sl)] = batch_lat
        if metrics is not None:
            _observe_batch(metrics, primitive, batch_lat,
                           len(sl), batch, queue_depth=n_q - done)
        done += len(sl)
        batches += 1
    total_s = time.monotonic() - t_start
    if validate:
        # oracle traversals are slow; keep them off the serving clock
        oracle = R.sssp_ref if primitive == "sssp" else R.bfs_ref
        for sl, field in answers:
            for i, s in enumerate(sl):
                ok = (np.allclose(field[i], oracle(g, int(s)), rtol=1e-5)
                      if primitive == "sssp"
                      else np.array_equal(field[i], oracle(g, int(s))))
                failures += not ok
    if metrics is not None:
        _count_totals(metrics, batches, overflow)
    return {
        "primitive": primitive, "backend": backend, "batch": batch,
        "requests": n_q, "batches": batches, "total_s": round(total_s, 4),
        "qps": round(n_q / total_s, 2),
        **latency_summary(lat_ms),
        "overflow": overflow,
        "validation_failures": failures if validate else None,
    }


def _observe_batch(m: Metrics, kind: str, batch_lat, real: int,
                   batch: int, queue_depth: int) -> None:
    """One flushed batch's worth of serving metrics: per-kind latency
    observations, batch-slot occupancy, and the queue-depth high-water
    mark at flush time."""
    for v in np.asarray(batch_lat, np.float64).reshape(-1):
        m.observe("latency_ms", float(v),
                  help="per-query latency, enqueue to batch completion",
                  kind=kind)
    m.counter("queries_total", real,
              help="queries answered", kind=kind)
    m.observe("batch_occupancy", real / max(batch, 1),
              help="fraction of batch slots holding real queries",
              kind=kind)
    m.gauge_max("queue_depth_peak", queue_depth,
                help="high-water mark of queued-but-unflushed queries")


def _count_totals(m: Metrics, batches: int, overflow: int) -> None:
    """Stream-level counters. Cache hits/misses are declared at zero —
    the serving scheduler the ROADMAP plans (answer caching, continuous
    batching) increments them; the exposition shows the series now so
    dashboards don't break when it lands."""
    m.counter("batches_total", batches, help="batches flushed")
    m.counter("overflow_total", overflow,
              help="BFS discoveries dropped by capped frontiers")
    m.counter("cache_hits_total", 0, help="answer-cache hits")
    m.counter("cache_misses_total", 0, help="answer-cache misses")


# one id per serve_mixed call: its serve.flush spans carry it as "mixed"
_MIXED_IDS = itertools.count()


def _run_kind(g, kind: str, srcs: np.ndarray, backend: str, hops: int,
              budget=None):
    """Execute one flushed batch of ``kind``; returns the ready field,
    per-lane BFS overflow counts (zeros for other kinds — callers trim
    the ragged-tail padding lanes before summing), and the primitive's
    ``converged`` flags (per-lane or scalar; lanes cut short by an
    iteration budget report False and carry partial answers)."""
    zeros = np.zeros(len(srcs), np.int64)
    if kind == "bfs":
        r = bfs_batch(g, srcs, backend=backend, budget=budget)
        jax.block_until_ready(r.labels)
        return r.labels, np.asarray(r.overflow), np.asarray(r.converged)
    if kind == "sssp":
        r = sssp_batch(g, srcs, backend=backend, budget=budget)
        jax.block_until_ready(r.dist)
        return r.dist, zeros, np.asarray(r.converged)
    if kind == "reach":
        r = reach_batch(g, srcs, hops, backend=backend, budget=budget)
        jax.block_until_ready(r.reached)
        return r.reached, zeros, np.asarray(r.converged)
    if kind == "pagerank":
        # a global analytics query: one run answers every slot of the
        # batch (sources are ignored; the slot discipline still bounds
        # how many queries ride one execution)
        r = pagerank(g, backend=backend, budget=budget)
        jax.block_until_ready(r.rank)
        return r.rank, zeros, np.asarray(r.converged)
    raise ValueError(kind)


def make_sharded_runner(pg, mesh, axis="graph"):
    """Mesh-backed query runner: every kind is served from the 1-D (or
    2-D vertex-cut) partition built once at startup. On the 2-D cut a
    bfs flush runs as ONE batched distributed program over all its
    lanes; sssp, and bfs on the 1-D partition, run one cached
    distributed trace per distinct source (the trace is keyed on the
    partition shapes + mesh, so lanes reuse it); algebraic kinds run the
    placement's "spmm"/"spmv" providers through the unchanged
    primitives. Results bit-match the single-device runner, so the
    oracle validation path needs no sharded variant."""
    import jax.numpy as jnp

    from repro.core.distributed import (_shard_any, distributed_bfs,
                                        distributed_sssp)
    from repro.core.partition import Partitioned2DGraph
    from repro.core.primitives import pagerank, reach_batch

    sg = _shard_any(pg, mesh, axis)
    batched_bfs = isinstance(pg, Partitioned2DGraph)

    def _per_source(srcs, one):
        # padding lanes repeat the final real query — run each distinct
        # source once and fan the result back out to its lanes
        memo = {}
        rows = []
        for s in srcs:
            s = int(s)
            if s not in memo:
                memo[s] = one(s)
            rows.append(memo[s])
        return jnp.stack(rows)

    def run(kind: str, srcs: np.ndarray, backend: str, hops: int):
        zeros = np.zeros(len(srcs), np.int64)
        if kind == "bfs":
            out = (distributed_bfs(pg, np.asarray(srcs), mesh, axis,
                                   backend=backend).labels
                   if batched_bfs else
                   _per_source(srcs, lambda s: distributed_bfs(
                       pg, s, mesh, axis, backend=backend).labels))
            jax.block_until_ready(out)
            return out, zeros           # dense bitmask advance: no caps,
        if kind == "sssp":              # so no overflow to report
            out = _per_source(srcs, lambda s: distributed_sssp(
                pg, s, mesh, axis).dist)
            jax.block_until_ready(out)
            return out, zeros
        if kind == "reach":
            r = reach_batch(sg, srcs, hops, backend=backend)
            jax.block_until_ready(r.reached)
            return r.reached, zeros
        if kind == "pagerank":
            r = pagerank(sg, backend=backend)
            jax.block_until_ready(r.rank)
            return r.rank, zeros
        raise ValueError(kind)

    return run


def _validate_kind(g, kind: str, srcs, field, hops: int) -> int:
    fails = 0
    if kind == "pagerank":
        return int(not np.allclose(np.asarray(field),
                                   R.pagerank_ref(g, iters=20), atol=1e-6))
    for i, s in enumerate(srcs):
        a = np.asarray(field[i])
        if kind == "bfs":
            ok = np.array_equal(a, R.bfs_ref(g, int(s)))
        elif kind == "sssp":
            ok = np.allclose(a, R.sssp_ref(g, int(s)), rtol=1e-5)
        else:
            ok = np.array_equal(a, R.reach_ref(g, int(s), hops))
        fails += not ok
    return fails


def _norm_run(out):
    """Normalize a runner return to (field, overflow, converged). The
    runner contract is 2-tuple (field, overflow); the default in-process
    runner adds the primitives' ``converged`` flags as a third element,
    and runners that don't surface convergence report None (= assume
    converged — they ran to completion by construction)."""
    if len(out) == 3:
        return out
    field, ovf = out
    return field, ovf, None


def _guardrail(kind: str, field: np.ndarray) -> None:
    """NaN/Inf guardrail: reject poisoned float outputs before they ship.

    Reads the already-host-side result array — a pure probe, so healthy
    results stay bit-identical. Per-kind semantics: sssp distances are
    legitimately +inf on unreachable vertices (NaN is the poison there);
    pagerank ranks must be finite; bfs/reach fields are integral and
    can't carry float poison."""
    if field.dtype.kind != "f":
        return
    if kind == "sssp":
        bad = np.isnan(field)
    else:
        bad = ~np.isfinite(field)
    if bad.any():
        frac = float(bad.mean())
        raise PoisonedResultError(
            f"{kind} output failed the NaN/Inf guardrail "
            f"({frac:.1%} of entries non-finite)")


def serve_mixed(g, queries, batch: int, backend: str, hops: int = 3,
                validate: bool = False, runner=None,
                metrics: Metrics | None = None,
                budget: ft.Budget | None = None,
                admission: ft.AdmissionPolicy | None = None,
                retry: ft.RetryPolicy | None = None,
                placement: str = "single",
                watchdog=None) -> dict:
    """Serve a mixed-kind query stream through per-kind fixed batch slots.

    ``queries`` is a sequence of ``(kind, source)`` pairs, kinds drawn
    from ``KINDS``. Each kind owns a slot queue: queries accumulate in
    arrival order and a queue flushes as ONE jitted batched program the
    moment it fills (ragged tails flush padded at end-of-stream). Returns
    aggregate stats plus a ``per_kind`` latency/qps breakdown.

    Per-query latency is enqueue → batch completion: each query's
    enqueue time is recorded when it joins its slot queue and subtracted
    at flush. (Measuring from stream start instead — the old behavior —
    charged every query all the batches that ran before it joined the
    queue, so mixed-stream p50/p95 grew with stream position.)

    ``runner(kind, srcs, backend, hops)`` overrides query execution (the
    sharded driver passes a mesh-backed runner); defaults to the
    single-device ``_run_kind``. ``metrics`` (an ``obs.metrics.Metrics``)
    collects per-kind latency histograms, queue-depth / batch-occupancy
    gauges, and counters for the ``--metrics`` Prometheus dump.

    Request-lifecycle hardening (the robustness layer):

      * every query ends in exactly one terminal status — ``ok``,
        ``degraded``, ``deadline_exceeded``, ``shed`` or ``error`` —
        returned per-query under ``stats["queries"]`` and counted in the
        matching metrics counter; malformed input (unknown kind,
        out-of-range source) becomes a per-query ``error``, never an
        exception out of the stream;
      * ``budget`` bounds each query: ``max_iters`` rides into the
        primitives (lanes cut short → ``deadline_exceeded`` with partial
        answers), ``wall_ms`` is checked host-side at flush boundaries
        (already-expired queries are not dispatched; late completions
        are stamped ``deadline_exceeded``);
      * ``admission`` bounds the slot queues — arrivals over the cap are
        shed with a structured rejection;
      * batch dispatch runs under ``retry`` (exponential backoff,
        deterministic jitter). Under an installed fault plan it
        escalates through the ``repro.ft.degrade`` ladder (pallas→xla,
        placement→single, reach reduced-hop); a downgraded batch's
        queries are stamped ``degraded`` and every rung change is
        declared + logged. Without one, retries rerun the requested
        configuration only: a real failure is never answered from
        another backend or placement;
      * a NaN/Inf guardrail probes each batch's host-side output and
        aborts a poisoned batch cleanly (retryable; terminal ``error``
        if the ladder runs dry);
      * a :class:`repro.ft.StepWatchdog` times every flush — the
        robust-median straggler multiple lands in ``--metrics``.

    Program spans (``obs.span``, on the profiler's clock): one
    ``serve.mixed`` per call (``id``), a ``serve.flush`` per batch
    (``kind``, live ``lanes``, ``attempts``, ``mixed`` = that id), and
    inside each attempt ``serve.dispatch`` (the runner call),
    ``serve.host_copy`` and ``serve.guardrail``.
    """
    n_q = len(queries)
    if n_q == 0:
        raise ValueError("empty query stream (requests must be > 0)")
    retry = retry if retry is not None else ft.RetryPolicy()
    wd = watchdog if watchdog is not None else ft.StepWatchdog()
    plan = inject.active()
    # a custom runner may not need the graph at all (stub/mesh drivers
    # pass g=None); range hardening then has no bound to check against
    num_v = None if g is None else g.num_vertices
    results: list = [None] * n_q
    lat_ms = {k: [] for k in KINDS}
    pending: dict = {k: [] for k in KINDS}   # (qid, src, t_enq, deadline)
    status_counts = {s: 0 for s in STATUSES}
    failures = 0
    overflow = 0
    retried = 0
    answers = []
    batches = 0
    if metrics is not None:
        # declare every lifecycle counter up front so the reconciliation
        # invariant (counters == per-query statuses) holds even for
        # fault classes that never fire in this run
        for s in STATUSES:
            metrics.counter(_STATUS_COUNTER[s], 0,
                            help=f"queries finished with status={s}")
        metrics.counter("queries_retried_total", 0,
                        help="queries whose batch needed >=1 retry")
    # reprolint: disable=RL004 -- run_kind fences internally (block_until_ready before return)
    t_start = time.monotonic()
    mixed_id = next(_MIXED_IDS)

    def finish(qid, kind, src, status, t_enq, t_done=None, reason=None,
               attempts=1, degraded_to=None):
        t_done = time.monotonic() if t_done is None else t_done
        rec = {"id": qid, "kind": kind, "source": src, "status": status,
               "lat_ms": round((t_done - t_enq) * 1e3, 3),
               "attempts": attempts}
        if reason:
            rec["reason"] = reason
        if degraded_to:
            rec["degraded_to"] = degraded_to
        results[qid] = rec
        status_counts[status] += 1
        if metrics is not None:
            metrics.counter(_STATUS_COUNTER[status], 1,
                            help=f"queries finished with status={status}",
                            kind=str(kind))
        return rec

    def dispatch(kind, srcs):
        """One batch through retry + the degradation ladder. Returns
        (field, ovf, conv, attempts, rung, error): on success ``error``
        is None; when the ladder runs dry ``field`` is None and
        ``error`` carries the terminal exception."""
        rungs = [r for r in ft.ladder(kind, backend, placement,
                                      hops=hops if kind == "reach"
                                      else None)
                 # rungs we can realize here: the runner's own placement,
                 # or the in-process single-device fallback
                 if r.placement in (placement, "single")]
        if plan is None:
            # outside a chaos run a failure is a fault to surface, never a
            # reason to answer from another backend or placement: retries
            # rerun the requested configuration only
            rungs = rungs[:1]
        run_default = lambda k, s, bk2, h: _run_kind(g, k, s, bk2, h,
                                                     budget)
        run_kind = runner if runner is not None else run_default
        state = {"attempts": 1}

        def attempt(a):
            state["attempts"] = a + 1
            rung = ft.rung_for_attempt(rungs, a)
            state["rung"] = rung
            if rung.reason:
                ft.engage(kind, rung)
            if plan is not None and plan.should("provider_miss", kind):
                raise B.ProviderMissError(
                    kind, rung.backend, rung.placement,
                    detail="injected by repro.ft.inject")
            if (placement != "single" and rung.placement == placement
                    and plan is not None
                    and plan.should("shard_loss", kind)):
                raise inject.ShardLossError(
                    f"injected shard loss during {kind} flush")
            h = rung.hops if rung.hops is not None else hops
            with obs.span("serve.dispatch", category="serve",
                          args={"kind": kind, "attempt": a + 1}):
                if rung.placement != placement:
                    out = run_default(kind, srcs, rung.backend, h)
                else:
                    out = run_kind(kind, srcs, rung.backend, h)
            field, ovf, conv = _norm_run(out)
            with obs.span("serve.host_copy", category="serve"):
                field = np.asarray(field)
            if (plan is not None and field.dtype.kind == "f"
                    and plan.should("nan", kind)):
                field = field.copy()
                field.reshape(-1)[0] = np.nan
            if plan is not None and plan.should("straggler", kind):
                time.sleep(_STRAGGLER_SLEEP_S)
            with obs.span("serve.guardrail", category="serve"):
                _guardrail(kind, field)
            return field, ovf, conv

        def on_retry(a, exc):
            log.warning(f"{kind} batch attempt {a + 1} failed "
                        f"({type(exc).__name__}: {exc}); backing off")

        try:
            (field, ovf, conv), attempts = ft.with_retry(
                attempt, retry, seed=batches, sleep=time.sleep,
                on_retry=on_retry)
            return field, ovf, conv, attempts, state["rung"], None
        except Exception as exc:   # declared retry boundary: ladder dry
            log.error(f"{kind} batch failed after {state['attempts']} "
                      f"attempts: {type(exc).__name__}: {exc}")
            return (None, None, None, state["attempts"],
                    state.get("rung"), exc)

    def flush(kind):
        nonlocal batches, overflow, retried, failures
        q = pending[kind]
        if not q:
            return
        pending[kind] = []
        # serving latency deliberately includes queue wait; the device is
        # fenced inside dispatch (np.asarray pulls the result to host)
        now = time.monotonic()  # reprolint: disable=RL004 -- queue latency is the metric; dispatch fences
        live = []
        for qid, src, t_enq, dl in q:
            if dl is not None and now >= dl:
                # expired while queued: don't spend a batch slot on it
                finish(qid, kind, src, "deadline_exceeded", t_enq,
                       t_done=now, reason="deadline expired in queue")
            else:
                live.append((qid, src, t_enq, dl))
        if not live:
            return
        sl = np.asarray([src for _, src, _, _ in live], np.int64)
        srcs = np.concatenate([sl, np.full(batch - len(sl), sl[-1],
                                           sl.dtype)])
        with obs.span("serve.flush", category="serve",
                      args={"kind": kind, "lanes": len(live),
                            "mixed": mixed_id}) as flush_args:
            wd.start(batches)
            field, ovf, conv, attempts, rung, err = dispatch(kind, srcs)
            dt = wd.stop()
            t_done = time.monotonic()
            flush_args["attempts"] = attempts
        batches += 1
        if metrics is not None and wd.median():
            metrics.gauge_max(
                "straggler_multiple_max", dt / wd.median(),
                help="worst batch wall time as a multiple of the "
                     "robust-median batch time")
        if field is None:
            # retries + the whole ladder failed: the queries get a
            # structured error, the stream lives on
            for qid, src, t_enq, _ in live:
                finish(qid, kind, src, "error", t_enq, t_done=t_done,
                       reason=f"{type(err).__name__}: {err}",
                       attempts=attempts)
            if metrics is not None:
                metrics.counter("queries_retried_total", len(live),
                                kind=kind)
            retried += len(live)
            return
        # padding lanes repeat the last real query; don't double-count
        # their overflow (same trim as serve())
        overflow += int(ovf[:len(sl)].sum())
        # degraded = the answer came from a lower rung; a retry that
        # recovered at the requested rung is full-fidelity "ok" (the
        # attempts field and retried counter still record it)
        degraded = bool(rung.reason)
        conv_arr = (None if conv is None
                    else np.asarray(conv).reshape(-1))
        if validate and not degraded and (conv_arr is None
                                          or conv_arr.all()):
            # oracle-comparable only when nothing was cut short or
            # approximated (a reduced-hop reach answers a different
            # question than the oracle's)
            answers.append((kind, sl, field))
        batch_lat = []
        for i, (qid, src, t_enq, dl) in enumerate(live):
            conv_i = (True if conv_arr is None else
                      bool(conv_arr[min(i, len(conv_arr) - 1)]))
            late = dl is not None and t_done > dl
            if not conv_i:
                st = "deadline_exceeded"
                reason = "iteration budget exhausted (partial result)"
            elif late:
                st = "deadline_exceeded"
                reason = "completed after deadline"
            elif degraded:
                st = "degraded"
                reason = None
            else:
                st = "ok"
                reason = None
            finish(qid, kind, src, st, t_enq, t_done=t_done,
                   reason=reason, attempts=attempts,
                   degraded_to=rung.reason if degraded else None)
            batch_lat.append((t_done - t_enq) * 1e3)
        if attempts > 1:
            retried += len(live)
            if metrics is not None:
                metrics.counter("queries_retried_total", len(live),
                                kind=kind)
        lat_ms[kind].extend(batch_lat)
        if metrics is not None:
            depth = sum(len(p) for p in pending.values())
            _observe_batch(metrics, kind, batch_lat, len(sl), batch,
                           queue_depth=depth)

    with obs.span("serve.mixed", category="serve",
                  args={"id": mixed_id, "queries": n_q}):
        for qid, (kind, src) in enumerate(queries):
            t_enq = time.monotonic()
            # input hardening: malformed queries become structured
            # per-query errors — never an exception that kills the stream
            if kind not in KINDS:
                finish(qid, str(kind), src, "error", t_enq,
                       reason=f"unknown kind {kind!r}; expected one of "
                              f"{','.join(KINDS)}")
                continue
            try:
                src = int(src)
            except (TypeError, ValueError):
                finish(qid, kind, src, "error", t_enq,
                       reason=f"source {src!r} is not an integer")
                continue
            if num_v is not None and not 0 <= src < num_v:
                finish(qid, kind, src, "error", t_enq,
                       reason=f"source {src} out of range [0, {num_v})")
                continue
            if admission is not None:
                shed_reason = admission.admit(kind, pending)
                if shed_reason is not None:
                    finish(qid, kind, src, "shed", t_enq,
                           reason=shed_reason)
                    continue
            dl = None if budget is None else budget.deadline_from(t_enq)
            pending[kind].append((qid, src, t_enq, dl))
            if metrics is not None:
                metrics.gauge_max(
                    "queue_depth_peak",
                    sum(len(p) for p in pending.values()),
                    help="high-water mark of queued-but-unflushed "
                         "queries")
            if len(pending[kind]) == batch:
                flush(kind)
        for kind in KINDS:                   # ragged tails, padded
            flush(kind)
    total_s = time.monotonic() - t_start

    if validate:                         # oracles off the serving clock
        with obs.span("validate", category="validate"):
            for kind, sl, field in answers:
                failures += _validate_kind(g, kind, sl, field, hops)
    if metrics is not None:
        _count_totals(metrics, batches, overflow)
        metrics.counter("straggler_batches_total", len(wd.stragglers),
                        help="flushes the watchdog flagged as stragglers")

    all_lat = np.asarray(sum(lat_ms.values(), []))
    per_kind = {}
    for kind in KINDS:
        lk = np.asarray(lat_ms[kind])
        if not len(lk):
            continue
        per_kind[kind] = {"requests": int(len(lk)),
                          **latency_summary(lk)}
    return {
        "kinds": sorted(per_kind), "backend": backend, "batch": batch,
        "hops": hops, "requests": n_q, "batches": batches,
        "total_s": round(total_s, 4), "qps": round(n_q / total_s, 2),
        **latency_summary(all_lat),
        "per_kind": per_kind,
        "overflow": overflow,
        "queries": results,
        "status_counts": status_counts,
        "retried": retried,
        "stragglers": len(wd.stragglers),
        "validation_failures": failures if validate else None,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Serve a stream of graph queries in fixed-shape "
                    "batch slots (one jitted multi-source program per "
                    "(kind, batch shape); --kinds mixes query kinds in "
                    "one stream).")
    ap.add_argument("--graph", default="rmat",
                    choices=("rmat", "rgg", "grid"))
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--edge-factor", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--index-dtype", default=None,
                    choices=("int16", "int32", "int64"),
                    help="vertex-id width for the served graph (default: "
                         "narrowest safe width)")
    ap.add_argument("--encoding", default="dense",
                    choices=("dense", "delta"),
                    help="CSR/CSC column storage encoding")
    ap.add_argument("--primitive", default="bfs", choices=("bfs", "sssp"))
    ap.add_argument("--kinds", default=None, metavar="K0,K1,...",
                    help=f"serve a MIXED stream over these query kinds "
                         f"(subset of {','.join(KINDS)}); overrides "
                         f"--primitive")
    ap.add_argument("--hops", type=int, default=3,
                    help="k for reach queries (k-hop reachability)")
    ap.add_argument("--requests", type=int, default=64,
                    help="number of queries to serve")
    ap.add_argument("--batch", type=int, default=8,
                    help="fixed batch-slot count (B traversal lanes)")
    ap.add_argument("--warmup", type=int, default=1,
                    help="untimed warmup batches (pays the jit trace)")
    ap.add_argument("--parts", type=int, default=None, metavar="P",
                    help="serve from a P-way 1-D partition over the "
                         "first P local devices (sharded placement; "
                         "builds the partition once, reports per-device "
                         "balance in --json)")
    ap.add_argument("--mesh", default=None, metavar="RxC",
                    help="serve from an R×C 2-D vertex-cut partition "
                         "(2d placement) over the first R*C local "
                         "devices; --parts P is the 1-D alias")
    ap.add_argument("--validate", action="store_true",
                    help="structurally validate the built graph "
                         "(Graph.validate_graph) and check every lane "
                         "against the numpy oracle")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-query wall-clock budget: queries that "
                         "expire in queue or complete late are stamped "
                         "deadline_exceeded")
    ap.add_argument("--max-iters", type=int, default=None,
                    help="per-query BSP iteration budget: lanes cut "
                         "short return partial results stamped "
                         "deadline_exceeded")
    ap.add_argument("--retries", type=int, default=2,
                    help="batch dispatch retries before the query is "
                         "declared failed (escalates through the "
                         "degradation ladder)")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="admission control: shed arrivals once this "
                         "many queries are queued (structured per-query "
                         "rejection, never an exception)")
    ap.add_argument("--backend", default=None,
                    choices=(B.XLA, B.PALLAS, B.AUTO))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append the stats row to a JSON file")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write serving metrics (per-kind latency "
                         "histograms with p50/p95/p99, gauges, counters) "
                         "as Prometheus text; '-' prints to stdout")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write phase spans as Chrome trace-event JSON "
                         "(open at ui.perfetto.dev)")
    args = ap.parse_args(argv)
    if not args.trace:
        return _main(args)
    with obs.capture():
        try:
            return _main(args)
        finally:
            n_ev = obs.export_chrome_trace(args.trace)
            log.info(f"wrote {n_ev} trace events to {args.trace}")


def _main(args):
    # chaos rig: a seeded REPRO_FAULTS spec installs the fault plan for
    # the whole serving process (no-op when unset)
    plan = inject.install_from_env()
    if plan is not None:
        log.warning(f"fault injection ACTIVE: {plan.spec!r} "
                    f"seed={plan.seed}")
    try:
        bk = B.resolve(args.backend)
    except B.PallasUnavailableError as exc:
        raise SystemExit(str(exc))
    compile_cache.enable()
    # device health probe, once at startup: a device that fails it stops
    # the server before it takes a query
    failed = [dev for dev, ok in ft.check_devices().items() if not ok]
    if failed:
        raise SystemExit(f"device health probe failed on {failed}")
    metrics = Metrics() if args.metrics else None
    with obs.span("build_graph", category="setup",
                  args={"kind": args.graph, "scale": args.scale}):
        g = make_graph(args.graph, args.scale, args.edge_factor,
                       args.seed, index_dtype=args.index_dtype,
                       encoding=args.encoding)
        jax.block_until_ready(g.row_offsets)
    if args.validate:
        from repro.core.graph import validate_graph
        validate_graph(g)    # raises GraphValidationError with the
        log.info("structural validation: CSR/CSC clean")   # bad row/edge
    storage = resident_bytes(g)
    rng = np.random.default_rng(args.seed)
    kinds = None
    if args.kinds:
        kinds = [k.strip() for k in args.kinds.split(",")]
        for k in kinds:
            if k not in KINDS:
                raise SystemExit(f"unknown query kind {k!r}; pick from "
                                 f"{KINDS}")
    mesh_shape = None
    if args.mesh:
        if args.parts:
            raise SystemExit(
                "--mesh and --parts are mutually exclusive (--parts P "
                "is the 1-D alias of --mesh 1xP; pick one)")
        try:
            r, c = (int(t) for t in args.mesh.lower().split("x"))
            if r < 1 or c < 1:
                raise ValueError(args.mesh)
        except ValueError:
            raise SystemExit(
                f"--mesh wants RxC with positive integers (e.g. 2x4), "
                f"got {args.mesh!r}")
        mesh_shape = (r, c)
    if (args.parts or mesh_shape) and not kinds:
        kinds = [args.primitive]     # sharded serving goes through the
    runner = None                    # mixed-kind (runner-based) path
    pg = None
    if args.parts or mesh_shape:
        need = args.parts if args.parts else mesh_shape[0] * mesh_shape[1]
        flag = (f"--parts {args.parts}" if args.parts
                else f"--mesh {mesh_shape[0]}x{mesh_shape[1]} "
                     f"(= {need} devices)")
        if len(jax.devices()) < need:
            raise SystemExit(
                f"{flag} needs {need} devices but "
                f"only {len(jax.devices())} are visible (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need} "
                f"for host-platform serving)")
        from jax.sharding import Mesh
        with obs.span("partition", category="setup",
                      args={"parts": need}):
            if mesh_shape:
                from repro.core.partition import partition_2d
                pg = partition_2d(g, *mesh_shape)
                mesh = Mesh(
                    np.array(jax.devices()[:need]).reshape(mesh_shape),
                    ("row", "col"))
                axis = ("row", "col")
            else:
                from repro.core.partition import partition_1d
                pg = partition_1d(g, args.parts)
                mesh = Mesh(np.array(jax.devices()[:need]), ("graph",))
                axis = "graph"
            runner = make_sharded_runner(pg, mesh, axis)
        bal = pg.balance()
        shape = (f"{mesh_shape[0]}x{mesh_shape[1]} mesh" if mesh_shape
                 else f"{need} parts")
        log.info(f"partition: {shape}, "
                 f"edge imbalance {bal['edge_imbalance']}x, "
                 f"vertex imbalance {bal['vertex_imbalance']}x")
        if metrics is not None:
            # analytic per-BSP-step exchange volume of a flush (the
            # comm model, batch lanes) per served kind — the distributed
            # counterpart of the single-device telemetry columns
            from repro.core.distributed import exchange_bytes_per_step
            for kind in (kinds or [args.primitive]):
                try:
                    metrics.gauge(
                        "exchange_bytes_per_step",
                        exchange_bytes_per_step(pg, kind,
                                                batch=args.batch),
                        help="analytic per-device exchange bytes per "
                             "BSP step (comm model)", kind=kind)
                except (KeyError, ValueError):
                    pass            # kind without a comm-model entry
    what = ",".join(kinds) if kinds else args.primitive
    placement = ("2d" if mesh_shape
                 else "sharded" if args.parts else "single")
    log.info(f"{args.graph} scale={args.scale}: "
             f"n={g.num_vertices} m={g.num_edges} kinds={what} "
             f"batch={args.batch} backend={bk} placement={placement}")
    pl = storage["plan"]
    log.info(f"storage: {pl['index_dtype']}/{pl['encoding']} "
             f"{storage['total_bytes'] / 2**20:.1f} MiB resident, "
             f"{storage['bytes_per_edge']} column bytes/edge "
             f"({storage['total_bytes_per_edge']} total)")

    if kinds:
        run_warm = runner if runner is not None else \
            (lambda k, srcs, b, h: _run_kind(g, k, srcs, b, h))
        with obs.span("warmup", category="compile",
                      args={"kinds": ",".join(kinds)}):
            for _ in range(args.warmup):        # one trace per kind
                for k in kinds:
                    srcs = rng.integers(0, g.num_vertices, args.batch)
                    if plan is None:
                        run_warm(k, srcs, bk, args.hops)
                        continue
                    try:
                        run_warm(k, srcs, bk, args.hops)
                    except Exception as exc:
                        # under an installed fault plan a cold trace can
                        # hit an injected provider miss here; serving
                        # traces the kind on first flush, inside the
                        # retry boundary
                        log.warning(f"warmup {k} failed "
                                    f"({type(exc).__name__}: {exc}); "
                                    f"first flush will pay the trace")
        queries = [(kinds[i % len(kinds)],
                    int(rng.integers(0, g.num_vertices)))
                   for i in range(args.requests)]
        budget = (ft.Budget(max_iters=args.max_iters,
                            wall_ms=args.deadline_ms)
                  if (args.max_iters or args.deadline_ms) else None)
        admission = (ft.AdmissionPolicy(max_pending=args.max_pending)
                     if args.max_pending else None)
        with obs.span("serve", category="serve",
                      args={"requests": args.requests}):
            stats = serve_mixed(g, queries, args.batch, bk,
                                hops=args.hops, validate=args.validate,
                                runner=runner, metrics=metrics,
                                budget=budget, admission=admission,
                                retry=ft.RetryPolicy(retries=args.retries),
                                placement=placement)
        if pg is not None:
            stats["parts"] = pg.num_parts
            if mesh_shape:
                stats["mesh"] = list(mesh_shape)
            stats["balance"] = pg.balance()
    else:
        run = {"bfs": bfs_batch, "sssp": sssp_batch}[args.primitive]
        with obs.span("warmup", category="compile",
                      args={"kinds": args.primitive}):
            for _ in range(args.warmup):
                w = run(g, rng.integers(0, g.num_vertices, args.batch),
                        backend=bk)
                jax.block_until_ready(
                    w.dist if args.primitive == "sssp" else w.labels)
        sources = rng.integers(0, g.num_vertices, args.requests)
        with obs.span("serve", category="serve",
                      args={"requests": args.requests}):
            stats = serve(g, args.primitive, sources, args.batch, bk,
                          validate=args.validate, metrics=metrics)
    stats["storage"] = storage
    log.info(f"{stats['requests']} queries in "
             f"{stats['total_s']:.2f}s = {stats['qps']:.1f} q/s  "
             f"(lat ms mean {stats.get('lat_ms_mean', 0)} "
             f"p50 {stats.get('lat_ms_p50', 0)} "
             f"p95 {stats.get('lat_ms_p95', 0)} "
             f"p99 {stats.get('lat_ms_p99', 0)}, n={stats['samples']})")
    counts = stats.get("status_counts")
    if counts:
        log.info("statuses: " + " ".join(
            f"{s}={counts[s]}" for s in STATUSES))
    for k, row in stats.get("per_kind", {}).items():
        log.info(f"  {k:9s} {row['requests']:4d} queries  "
                 f"lat ms mean {row['lat_ms_mean']} "
                 f"p50 {row['lat_ms_p50']} p95 {row['lat_ms_p95']} "
                 f"p99 {row['lat_ms_p99']}")
    if stats["overflow"]:
        log.warning(f"{stats['overflow']} BFS discoveries dropped by "
                    f"capped frontiers — rerun the affected queries "
                    f"with idempotence=False")
    if args.validate:
        log.info(f"validation failures: {stats['validation_failures']}")
        if stats["validation_failures"]:
            raise SystemExit("validation failed")
    if args.metrics:
        text = metrics.render()
        if args.metrics == "-":
            print(text, end="")  # reprolint: disable=RL005 -- --metrics "-" selects stdout
        else:
            with open(args.metrics, "w") as f:
                f.write(text)
            log.info(f"wrote Prometheus metrics to {args.metrics}")
    if args.json:
        try:
            with open(args.json) as f:
                rows = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            rows = []
        rows.append(stats)
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    return stats


if __name__ == "__main__":
    main()
