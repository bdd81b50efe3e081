"""Graph-analytics driver — the paper's own application kind.

Generates (or loads) a graph, runs the requested primitives, validates
against the numpy oracles, and reports runtime + MTEPS exactly as the
paper's evaluation does (§7: runtime is GPU-kernel time; MTEPS = edges
visited / runtime).

  PYTHONPATH=src python -m repro.launch.graph_run --graph rmat --scale 14 \
      --primitives bfs,sssp,pagerank,cc,bc,tc --validate --backend pallas

Multi-source: ``--sources 3,99,512`` runs bfs/sssp as ONE batched
multi-source program over the listed roots (per-lane validation) instead
of a single-source run; ``bc`` accumulates exactly those roots. For the
continuous-serving version of the same idea see launch/graph_serve.py.

Observability: ``--stats`` reruns each primitive with ``telemetry=``
and prints the per-iteration trajectory (frontier size, tier,
direction — the characterization tables of paper §5); ``--trace
out.json`` writes the phase spans (build/dispatch/validate) as Chrome
trace-event JSON, loadable at ui.perfetto.dev.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import compile_cache, obs
from repro.core import backend as B
from repro.core import graph as G
from repro.core import ref as R
from repro.core.primitives import (bc, bc_batch, bfs, bfs_batch,
                                   connected_components, label_propagation,
                                   pagerank, reach, reach_batch, sssp,
                                   sssp_batch, triangle_count,
                                   who_to_follow)
from repro.obs import telemetry as T

log = obs.get_logger("graph")


def make_graph(kind: str, scale: int, edge_factor: int, seed: int,
               index_dtype: str | None = None, encoding: str = "dense"):
    plan = dict(index_dtype=index_dtype, encoding=encoding)
    if kind == "rmat":
        return G.rmat(scale, edge_factor, seed=seed, weighted=True, **plan)
    if kind == "rgg":
        n = 1 << scale
        import math
        radius = math.sqrt(8.0 / n)   # ~avg degree 8·π/4
        return G.random_geometric(n, radius, seed=seed, weighted=True,
                                  **plan)
    if kind == "grid":
        side = int((1 << scale) ** 0.5)
        return G.grid2d(side, weighted=True, seed=seed, **plan)
    raise ValueError(kind)


def _warn_overflow(overflow: np.ndarray) -> None:
    """A nonzero BFSResult.overflow means a capped frontier dropped
    discoveries (possible only under idempotent hash culling) — the
    labels are untrustworthy and must not pass silently."""
    total = int(np.sum(overflow))
    if total:
        log.warning(f"bfs dropped {total} frontier entries "
                    f"(overflow); rerun with idempotence=False")


def run_primitive(name: str, g, src: int, validate: bool,
                  backend: str | None = None,
                  sources: list[int] | None = None,
                  hops: int = 3):
    bk = B.resolve(backend)
    t0 = time.monotonic()
    edges = g.num_edges
    ok = None
    if name == "bfs" and sources:
        r = bfs_batch(g, sources, backend=bk)
        jax.block_until_ready(r.labels)
        dt = time.monotonic() - t0
        edges = int(np.sum(np.asarray(r.edges_visited)))
        _warn_overflow(np.asarray(r.overflow))
        if validate:
            ok = all(np.array_equal(np.asarray(r.labels[i]),
                                    R.bfs_ref(g, s))
                     for i, s in enumerate(sources))
    elif name == "sssp" and sources:
        r = sssp_batch(g, sources, backend=bk)
        jax.block_until_ready(r.dist)
        dt = time.monotonic() - t0
        if validate:
            ok = all(np.allclose(np.asarray(r.dist[i]), R.sssp_ref(g, s),
                                 rtol=1e-5)
                     for i, s in enumerate(sources))
    elif name == "bc" and sources:
        r = bc_batch(g, sources, backend=bk)
        total = np.asarray(r.bc).sum(axis=0)
        dt = time.monotonic() - t0
        edges = 2 * g.num_edges * len(sources)
        if validate:
            ref = sum(R.bc_ref(g, s).astype(np.float64) for s in sources)
            ok = np.allclose(total, ref, rtol=1e-3, atol=1e-3)
    elif name == "bfs":
        r = bfs(g, src, backend=bk)
        jax.block_until_ready(r.labels)
        dt = time.monotonic() - t0
        edges = int(r.edges_visited)
        _warn_overflow(np.asarray(r.overflow))
        if validate:
            ok = np.array_equal(np.asarray(r.labels), R.bfs_ref(g, src))
    elif name == "sssp":
        r = sssp(g, src, backend=bk)
        jax.block_until_ready(r.dist)
        dt = time.monotonic() - t0
        if validate:
            ok = np.allclose(np.asarray(r.dist), R.sssp_ref(g, src),
                             rtol=1e-5)
    elif name == "pagerank":
        r = pagerank(g, max_iter=20, backend=bk)
        jax.block_until_ready(r.rank)
        dt = time.monotonic() - t0
        if validate:
            ok = np.allclose(np.asarray(r.rank), R.pagerank_ref(g,
                                                                iters=20),
                             atol=1e-6)
    elif name == "cc":
        r = connected_components(g, backend=bk)
        jax.block_until_ready(r.labels)
        dt = time.monotonic() - t0
        if validate:
            ref = R.cc_ref(g)
            a, b = np.asarray(r.labels), ref
            ok = len(np.unique(a)) == len(np.unique(b)) and np.array_equal(
                a[a == np.arange(len(a))], b[b == np.arange(len(b))])
    elif name == "bc":
        r = bc(g, src, backend=bk)
        jax.block_until_ready(r.bc)
        dt = time.monotonic() - t0
        edges = 2 * g.num_edges
        if validate:
            ok = np.allclose(np.asarray(r.bc), R.bc_ref(g, src),
                             rtol=1e-3, atol=1e-3)
    elif name == "tc":
        r = triangle_count(g, backend=bk)
        jax.block_until_ready(r.total)
        dt = time.monotonic() - t0
        if validate:
            ok = int(r.total) == R.tc_ref(g)
    elif name == "label_propagation":
        r = label_propagation(g, backend=bk)
        jax.block_until_ready(r.labels)
        dt = time.monotonic() - t0
        edges = g.num_edges * int(r.iterations)
        if validate:
            ok = np.array_equal(np.asarray(r.labels),
                                R.label_propagation_ref(g))
    elif name == "reach" and sources:
        r = reach_batch(g, sources, hops, backend=bk)
        jax.block_until_ready(r.reached)
        dt = time.monotonic() - t0
        edges = g.num_edges * hops * len(sources)
        if validate:
            ok = all(np.array_equal(np.asarray(r.reached[i]),
                                    R.reach_ref(g, s, hops))
                     for i, s in enumerate(sources))
    elif name == "reach":
        r = reach(g, src, hops, backend=bk)
        jax.block_until_ready(r.reached)
        dt = time.monotonic() - t0
        edges = g.num_edges * hops
        if validate:
            ok = np.array_equal(np.asarray(r.reached),
                                R.reach_ref(g, src, hops))
    elif name == "wtf":
        r = who_to_follow(g, src, k=min(1000, g.num_vertices - 1),
                          backend=bk)
        jax.block_until_ready(r.auth_scores)
        dt = time.monotonic() - t0
        ok = None
    else:
        raise ValueError(name)
    mteps = edges / dt / 1e6
    return dt, mteps, ok, bk


def collect_stats(name: str, g, src: int,
                  sources: list[int] | None = None,
                  backend: str | None = None, hops: int = 3):
    """Rerun ``name`` with ``telemetry=`` and return the trimmed host
    trace (lane 0 of a batched run), or None for primitives without a
    telemetry hook. A separate run on purpose: the timed run stays the
    exact program the perf numbers describe."""
    bk = B.resolve(backend)
    if name == "bfs":
        r, buf = bfs_batch(g, sources if sources else [src],
                           backend=bk, telemetry=True)
        return T.trim(buf, np.asarray(r.iterations)).lane(0)
    if name == "sssp":
        r, buf = sssp_batch(g, sources if sources else [src],
                            backend=bk, telemetry=True)
        return T.trim(buf, np.asarray(r.iterations)).lane(0)
    if name == "pagerank":
        _, buf = pagerank(g, max_iter=20, backend=bk, telemetry=True)
        return T.trim(buf)
    if name == "cc":
        _, buf = connected_components(g, backend=bk, telemetry=True)
        return T.trim(buf)
    if name == "bc":
        _, buf = bc_batch(g, sources if sources else [src],
                          backend=bk, telemetry=True)
        return T.trim(buf).lane(0)
    if name == "tc":
        _, buf = triangle_count(g, backend=bk, telemetry=True)
        return T.trim(buf)
    return None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="rmat",
                    choices=("rmat", "rgg", "grid"))
    ap.add_argument("--scale", type=int, default=14)
    ap.add_argument("--edge-factor", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--primitives",
                    default="bfs,sssp,pagerank,cc,bc,tc")
    ap.add_argument("--validate", action="store_true")
    ap.add_argument("--hops", type=int, default=3,
                    help="k for the reach primitive (k-hop reachability)")
    ap.add_argument("--src", type=int, default=None)
    ap.add_argument("--sources", default=None, metavar="S0,S1,...",
                    help="comma-separated source vertices: bfs/sssp run "
                         "as one batched multi-source program over these "
                         "roots (validated per lane), bc accumulates "
                         "exactly these roots")
    ap.add_argument("--backend", default=None,
                    choices=(B.XLA, B.PALLAS, B.AUTO),
                    help="operator backend (default: ambient context / "
                         "REPRO_BACKEND env / xla)")
    ap.add_argument("--stats", action="store_true",
                    help="print each primitive's per-iteration telemetry "
                         "trajectory (frontier / tier / direction)")
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
    try:
        bk = B.resolve(args.backend)
    except B.PallasUnavailableError as exc:
        raise SystemExit(str(exc))
    compile_cache.enable()

    with obs.span("build_graph", category="setup",
                  args={"kind": args.graph, "scale": args.scale}):
        g = make_graph(args.graph, args.scale, args.edge_factor,
                       args.seed)
        jax.block_until_ready(g.row_offsets)
    if args.validate:
        # structural validation first: a malformed CSR fails loudly with
        # the offending row/edge named, instead of as a wrong oracle
        from repro.core.graph import validate_graph
        validate_graph(g)
        log.info("structural validation: CSR/CSC clean")
    deg = np.diff(np.asarray(g.row_offsets))
    src = args.src if args.src is not None else int(np.argmax(deg))
    sources = ([int(s) for s in args.sources.split(",")]
               if args.sources else None)
    log.info(f"{args.graph} scale={args.scale}: n={g.num_vertices} "
             f"m={g.num_edges} max_deg={deg.max()} "
             f"src={sources if sources else src} backend={bk}")

    failures = 0
    results = []
    for name in args.primitives.split(","):
        name = name.strip()
        t0 = time.monotonic()
        with obs.span(f"run:{name}", category="dispatch",
                      args={"backend": bk}):
            dt, mteps, ok, _ = run_primitive(
                name, g, src, args.validate, bk,
                sources=sources, hops=args.hops)
        validate_s = time.monotonic() - t0 - dt
        status = "" if ok is None else ("  PASS" if ok else "  FAIL")
        log.info(f"{name:9s} {dt*1000:9.2f} ms  {mteps:9.2f} MTEPS"
                 f"  backend={bk}{status}")
        results.append({"primitive": name, "seconds": dt, "mteps": mteps,
                        "valid": ok, "validate_seconds": validate_s,
                        "backend": bk})
        if ok is False:
            failures += 1
        if args.stats:
            with obs.span(f"stats:{name}", category="dispatch"):
                trace = collect_stats(name, g, src, sources=sources,
                                      backend=args.backend,
                                      hops=args.hops)
            if trace is not None and trace.steps:
                log.info(f"{name} per-iteration trajectory"
                         + (" (lane 0)" if sources else "") + ":")
                # reprolint: disable=RL005 -- multi-line table artifact; stdout is the CLI contract
                print(trace.format_table(prefix="  "))
    if failures:
        raise SystemExit(f"{failures} primitives failed validation")
    return results


if __name__ == "__main__":
    main()
