"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run.py --workload g500-bfs --seed 7 --seconds 30 --trace 0

The cell, its configuration and its traffic mix come from
``BENCHMARK.json`` at the checkout's root. The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, ``checks``); the run exits nonzero and prints no
such line where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T0 = time.monotonic()   # set-up is measured from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# JAX's persistent compilation cache lives in the checkout, at a fixed
# path, whatever the environment names, and keeps every entry: only a
# cell's first run in a checkout compiles, and two checkouts share nothing
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, "bench", ".out",
                                                       "jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
# The TPU runtime pins a host buffer for transfers, 4 GiB by default. On
# a host without transparent hugepages that takes 6-12 s of every run's
# set-up, a different amount each time; 256 MiB holds every transfer a
# cell makes, all at once (a graph's arrays, 49 MiB at the most).
os.environ["TPU_PREMAPPED_BUFFER_SIZE"] = str(256 << 20)
os.environ["TPU_PREMAPPED_BUFFER_TRANSFER_THRESHOLD_BYTES"] = str(256 << 20)

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(sys.argv[1:], t0=T0))
