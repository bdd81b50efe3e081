"""Readings of the program and of its control, for setting the limits
of ``correct`` (not part of a benchmark run).

    python3 bench/control.py --workload g500-serve --seeds 1,2,3 --seconds 10

For each seed, one run of the cell as the benchmark makes it, at the
cell's own size and load, then the control in the program's place, and
one JSON line per seed with every number compared, the program's and
the control's. The controls: for a traversal or serving mix, each
kind's control of ``bench/kinds.py`` (the reference a level or a hop
short; distances in bfloat16), read from the same run's sampled
queries; for PageRank, the program's own ``bf16`` sweep, a run of its
own.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _numbers(checks) -> dict:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


def main(argv=None) -> int:
    from bench import harness
    from repro import compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    compile_cache.enable()
    spec = harness.load_spec(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        _, run = harness.run_cell(spec, seed, args.seconds, control=True)
        program = run.program_checks
        if program is None:     # the control ran as a program of its own
            _, prun = harness.run_cell(spec, seed, args.seconds)
            program = prun.checks
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "units": len(run.items),
                          "program": _numbers(program),
                          "control": _numbers(run.checks)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
