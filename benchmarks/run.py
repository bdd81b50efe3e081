"""Benchmark harness — one module per paper table/figure (§7):

  table6_primitives   runtime + MTEPS per primitive × dataset (Table 6)
  table7_scaling      size scaling on Kronecker graphs (Table 7)
  table8_utilization  load-balance quality / lane utilization (Table 8)
  fig19_optimizations idempotence × direction-optimization (Fig. 19)
  fig20_strategies    LB / TWC / THREAD workload mappings (Fig. 20)
  fig21_doab          do_a/do_b direction-parameter sweep (Fig. 21)
  fig25_tc            TC filtered vs full vs CPU baseline (Fig. 25)
  table10_wtf         Who-To-Follow pipeline + scaling (Tables 9-11)
  roofline            LM dry-run roofline tables (deliverable g)
  frontier_scaling    tiered/fused traversal vs pinned worst-case +
                      frontier-occupancy sweep (PR 5; → BENCH_pr5.json)
  bandwidth           storage-plan grid {int64,int32,delta}×{fp32,bf16}:
                      ms + bytes-per-edge + parity (PR 6; →
                      BENCH_pr6.json)

Run all:  PYTHONPATH=src python -m benchmarks.run
One:      PYTHONPATH=src python -m benchmarks.run --only fig25_tc
Backend:  PYTHONPATH=src python -m benchmarks.run --backend pallas \
              --json bench_pallas.json
"""
from __future__ import annotations

import argparse
import importlib
import os
import time
import traceback

MODULES = [
    "table6_primitives",
    "table7_scaling",
    "table8_utilization",
    "fig19_optimizations",
    "fig20_strategies",
    "fig21_doab",
    "fig25_tc",
    "table10_wtf",
    "roofline",
    "frontier_scaling",
    "bandwidth",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--backend", default=None,
                    choices=("xla", "pallas", "auto"),
                    help="operator backend for every module (emitted as a "
                         "column in the CSV/JSON output)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all emitted rows (backend column included) "
                         "as JSON")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    if args.backend:
        os.environ["REPRO_BACKEND"] = args.backend
    mods = [args.only] if args.only else MODULES
    failures = []
    for name in mods:
        print(f"\n===== {name} =====", flush=True)
        # reprolint: disable=RL004 -- progress wall-clock around a whole module run
        t0 = time.monotonic()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.run()
            print(f"# {name} done in {time.monotonic()-t0:.1f}s",
                  flush=True)
        except Exception:
            traceback.print_exc()
            failures.append(name)
    # resident-bytes accounting for every dataset the run touched (plus
    # the zoo defaults when run standalone) — the storage side of every
    # ms number above
    print("\n===== storage =====", flush=True)
    try:
        from benchmarks.common import _CACHE, dataset, emit_storage
        if not _CACHE:
            dataset("rmat_s12_e16")
        emit_storage(dict(_CACHE))
    except Exception:
        traceback.print_exc()
        failures.append("storage")
    if args.json:
        from benchmarks.common import write_json
        write_json(args.json)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
