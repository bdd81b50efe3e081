"""Bandwidth-proportional storage benchmark (PR 6; → BENCH_pr6.json).

Traversal on this engine is memory-bound: every advance / SpMV sweep
streams the CSR (or CSC) column array, so *bytes per edge* bounds
throughput. This module measures exactly that tradeoff across the
storage-plan grid introduced by ``repro.core.storage``:

  storage axis   int64 (the widest baseline, run under jax_enable_x64),
                 int32 (the classic layout), delta (narrow auto dtype +
                 per-row anchored uint16 deltas)
  value axis     fp32 everywhere; bf16 additionally for PageRank (the
                 one inexact-semiring workload in the sweep)

Workloads are the paper's three traversal archetypes — BFS, SSSP,
PageRank — on weighted R-MAT at scales 12–14. For each (workload,
scale) the int64 run is the parity oracle: int32 and delta results must
be BIT-identical (exact semirings decode exactly); bf16 PageRank must
agree within the documented ~1e-2 absolute tolerance (DESIGN.md §8).

Timing is compile-once-then-median (benchmarks.common.timed); on this
CPU container the numbers are relative, not TPU-absolute — the metric
that transfers is the ratio between storage formats at identical
topology, plus the exact resident-byte accounting from
``storage.resident_bytes``.

Run:     PYTHONPATH=src python -m benchmarks.bandwidth
Quick:   PYTHONPATH=src python -m benchmarks.bandwidth --quick
         (scale 12 only, 1 repeat — the CI bench-schema smoke)
Output:  BENCH_pr6.json (override with --json PATH)
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from benchmarks.common import emit, timed
from repro.core import backend as B
from repro.core import graph as G
from repro.core import storage as S
from repro.core.primitives import bfs, pagerank, sssp

SCALES = (12, 13, 14)
EDGE_FACTOR = 8
BF16_TOL = 1e-2

# storage tag -> Graph build kwargs (the plan knobs of from_edge_list)
STORAGES = {
    "int64": {"index_dtype": "int64"},
    "int32": {"index_dtype": "int32"},
    "delta": {"encoding": "delta"},
}

WORKLOADS = {
    "bfs": lambda g, src: bfs(g, src).labels,
    "sssp": lambda g, src: sssp(g, src).dist,
    "pagerank": lambda g, src: pagerank(g, max_iter=20).rank,
}


def _build(scale: int, storage: str):
    kw = STORAGES[storage]
    return G.rmat(scale, EDGE_FACTOR, seed=scale, weighted=True, **kw)


def _source(g) -> int:
    return int(np.argmax(np.diff(np.asarray(g.row_offsets))))


def run(scales=SCALES, repeats: int = 3, json_path: str = "BENCH_pr6.json",
        quick: bool = False):
    if quick:
        scales, repeats = scales[:1], 1
    backend = B.resolve()
    rows = []
    speedups = {}
    drops = {}
    for scale in scales:
        # the int64 baseline needs real 64-bit arrays, which JAX only
        # provides under the x64 switch; the whole baseline branch
        # (build + run) lives inside the context so nothing narrows.
        with jax.enable_x64(True):
            g64 = _build(scale, "int64")
            src = _source(g64)
            base_ms, base_out, base_bpe = {}, {}, None
            rb = S.resident_bytes(g64)
            base_bpe = rb["bytes_per_edge"]
            for wl, fn in WORKLOADS.items():
                out, sec = timed(fn, g64, src, repeats=repeats)
                base_ms[wl] = sec * 1e3
                base_out[wl] = np.asarray(out)
                rows.append(dict(
                    workload=wl, scale=scale, storage="int64",
                    value_dtype="fp32", ms=round(base_ms[wl], 3),
                    bytes_per_edge=base_bpe,
                    total_bytes=rb["total_bytes"], parity="baseline",
                    speedup_vs_int64=1.0))
        for storage in ("int32", "delta"):
            g = _build(scale, storage)
            rb = S.resident_bytes(g)
            bpe = rb["bytes_per_edge"]
            drops[f"{storage}_s{scale}"] = round(1.0 - bpe / base_bpe, 3)
            for wl, fn in WORKLOADS.items():
                out, sec = timed(fn, g, src, repeats=repeats)
                ms = sec * 1e3
                ok = np.array_equal(base_out[wl], np.asarray(out))
                sp = base_ms[wl] / ms if ms > 0 else float("inf")
                speedups[f"{wl}_s{scale}_{storage}"] = round(sp, 3)
                rows.append(dict(
                    workload=wl, scale=scale, storage=storage,
                    value_dtype="fp32", ms=round(ms, 3),
                    bytes_per_edge=bpe, total_bytes=rb["total_bytes"],
                    parity="bit" if ok else "FAIL",
                    speedup_vs_int64=round(sp, 3)))
                assert ok, (
                    f"{wl} scale={scale} {storage}: results must be "
                    f"bit-identical to the int64 baseline")
            # the inexact-semiring axis: bf16 PageRank on this storage
            out, sec = timed(lambda g_: pagerank(
                g_, max_iter=20, precision="bf16").rank, g,
                repeats=repeats)
            diff = float(np.abs(base_out["pagerank"]
                                - np.asarray(out)).max())
            rows.append(dict(
                workload="pagerank", scale=scale, storage=storage,
                value_dtype="bf16", ms=round(sec * 1e3, 3),
                bytes_per_edge=bpe, total_bytes=rb["total_bytes"],
                parity=f"maxabs={diff:.2e}",
                speedup_vs_int64=round(base_ms["pagerank"] / (sec * 1e3),
                                       3)))
            assert diff < BF16_TOL, (
                f"bf16 pagerank drifted {diff} > {BF16_TOL}")
    header = ("workload", "scale", "storage", "value_dtype", "ms",
              "bytes_per_edge", "total_bytes", "parity",
              "speedup_vs_int64")
    emit([[r[h] for h in header] for r in rows], header,
         table="bandwidth")
    best = max(speedups.values()) if speedups else 0.0
    from benchmarks.common import provenance
    payload = {
        "schema": "bandwidth-v1",
        "backend": backend,
        "quick": quick,
        "scales": list(scales),
        "rows": rows,
        "speedups": speedups,
        "best_traversal_speedup": best,
        "bytes_per_edge_drop": drops,
        "provenance": provenance(),
    }
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    print(f"# wrote {json_path}: best speedup vs int64 = {best:.2f}x, "
          f"bytes/edge drops = {drops}")
    return payload


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="storage bandwidth benchmark")
    ap.add_argument("--quick", action="store_true",
                    help="scale 12 only, 1 repeat (CI smoke)")
    ap.add_argument("--json", default="BENCH_pr6.json")
    args = ap.parse_args(argv)
    run(json_path=args.json, quick=args.quick)


if __name__ == "__main__":
    main()
