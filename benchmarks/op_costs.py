"""Warm device time of the single XLA ops the batched traversals are
built from, each in its own jitted program, on 1-D int32 arrays of the
edge-frontier length ``m`` and the vertex count ``n``. The defaults are
the shapes of the scale-20 R-MAT graph (n = 2^20 vertices, m = 2^24 edge
slots).

  PYTHONPATH=src python -m benchmarks.op_costs
  PYTHONPATH=src python -m benchmarks.op_costs --log-n 12 --log-m 16

Each op runs once to compile, then five times; the line per op is the
median wall time of those warm runs, each ended by
``block_until_ready``. The last line is the whole table as JSON, named
with the device kind the times belong to.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

BATCH = 8          # sources per batched traversal
REPEATS = 5


def make_ops(n: int, m: int) -> dict:
    """name -> (function, arguments): random data from a fixed seed."""
    rng = np.random.default_rng(0)
    deg = rng.integers(0, 31, n).astype(np.int32)
    offs = jnp.asarray(np.cumsum(deg) - deg)
    idx = jnp.asarray(rng.integers(0, n, m).astype(np.int32))
    perm = jnp.asarray(rng.permutation(m).astype(np.int32))
    x = jnp.asarray(rng.integers(0, 1 << 30, n).astype(np.int32))
    vals = jnp.asarray(rng.integers(0, 1 << 30, m).astype(np.int32))
    mask = jnp.asarray(rng.random(m) < 0.5)
    slots = jnp.arange(m, dtype=jnp.int32)
    seg = jnp.sort(idx)

    def cummax_pos(o):
        # the row of each edge slot without a search: mark each row's
        # first slot, then carry the mark forward
        marks = jnp.zeros((m,), jnp.int32).at[o].max(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
        return jax.lax.cummax(marks)

    def compact(v, mk, unique):
        mi = mk.astype(jnp.int32)
        tgt = jnp.where(mk, jnp.cumsum(mi, dtype=jnp.int32) - mi, m)
        return jnp.full((m,), -1, jnp.int32).at[tgt].set(
            v, mode="drop", unique_indices=unique)

    return {
        "gather m from n": (lambda t, i: t[i], (x, idx)),
        "gather m from m": (lambda t, i: t[i], (vals, perm)),
        "searchsorted m into n (scan)": (
            lambda o, s: jnp.searchsorted(o, s, side="right"),
            (offs, slots)),
        "searchsorted m into n (sort)": (
            lambda o, s: jnp.searchsorted(o, s, side="right",
                                          method="sort"), (offs, slots)),
        "scatter-max n + cummax m": (cummax_pos, (offs,)),
        "cumsum m": (lambda v: jnp.cumsum(v, dtype=jnp.int32), (vals,)),
        "compact m (cumsum + scatter)": (
            lambda v, k: compact(v, k, False), (vals, mask)),
        "compact m, unique_indices": (
            lambda v, k: compact(v, k, True), (vals, mask)),
        "scatter-min m into n": (
            lambda i, v: jnp.full((n,), m, jnp.int32).at[i].min(
                v, mode="drop"), (idx, vals)),
        "sort m": (jnp.sort, (vals,)),
        "segment_max m sorted into n": (
            lambda v, s: jax.ops.segment_max(v, s, num_segments=n,
                                             indices_are_sorted=True),
            (vals, seg)),
        f"vmapped gather {BATCH} x m from n": (
            jax.vmap(lambda t, i: t[i], in_axes=(None, 0)),
            (x, jnp.broadcast_to(idx, (BATCH, m)))),
    }


def median_ms(fn, args) -> float:
    f = jax.jit(fn)
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e3


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--log-n", type=int, default=20)
    ap.add_argument("--log-m", type=int, default=24)
    args = ap.parse_args(argv)
    n, m = 1 << args.log_n, 1 << args.log_m
    dev = jax.devices()[0]
    rows = {}
    for name, (fn, fargs) in make_ops(n, m).items():
        rows[name] = median_ms(fn, fargs)
        print(f"op {name:32s} {rows[name]:10.3f} ms "
              f"(median of {REPEATS}, warm)", flush=True)
    out = {"device": dev.device_kind, "platform": dev.platform,
           "n": n, "m": m, "ms": rows}
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
