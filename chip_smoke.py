"""Bring-up smoke run of the graph engine on a TPU.

Default (one chip): the main path through the entry points a user calls,
on an R-MAT graph of scale 18, edge factor 8, weighted, seed 0 (262K
vertices, 3.94M stored edges after symmetrization). The target was
scale 22, the size of soc-LiveJournal1 (4.19M vertices, 65.2M edges).
Two limits cut it:

  memory  the TPU compiler sizes the batched traversals for a v5e
          (15.75 GiB): at scale 22 bfs_batch needs 16.25 GiB and
          sssp_batch 18.38 GiB; at scale 21 sssp_batch needs 14.97 GiB,
          which leaves no room for the 1.19 GiB graph. A smaller batch
          does not help (the (B, m) edge frontiers pad B to 8
          sublanes). Scale 20 fits.
  time    on a v5e at scale 20 the first call of bfs_batch on one batch
          of 8 takes 284 s and that of sssp_batch 546 s, compile
          included; the run calls each twice (graph_run, then serving),
          which does not fit a 20-minute run. Scale 18 takes under 5
          minutes; scale 19 has not been run on a chip.

The run is:

  1. ``repro.launch.graph_run.main`` runs bfs, sssp (one batch of 8
     sources), pagerank and cc with ``--validate`` (numpy oracles in
     ``repro.core.ref``); XLA's compile events split each first call
     into compile and run seconds;
  2. ``repro.launch.graph_serve.main`` serves a mixed bfs, sssp,
     pagerank, reach stream of 32 queries in batches of 8, validated,
     on the programs step 1 compiled (so no warmup batch).

``--four-chips`` runs only the placement phase on a 2x2 host: the same
graph, partitioned 1-D over 4 devices (``--parts 4``) and 2-D over a
2x2 mesh (``--mesh 2x2``), answers one batch of each query kind through
the serving runners, and checks that every answer bit-matches the
exact one: the numpy oracle's for bfs, sssp and reach (the
single-device program matches these exactly), the single-device
program's for pagerank.

  python chip_smoke.py
  python chip_smoke.py --four-chips

Every earlier line names its quantity and unit; the last line is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
The run exits nonzero, printing no such line, when JAX finds no TPU,
when Pallas would run interpreted, when REPRO_FORCE_INTERPRET or
REPRO_FAULTS is set, when a validation fails, or when a query ends in a
status other than "ok".
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

SCALE, EDGE_FACTOR, SEED = 18, 8, 0
BATCH = 8
HOPS = 3
KINDS = ("bfs", "sssp", "pagerank", "reach")


def graph_args() -> list:
    return ["--graph", "rmat", "--scale", str(SCALE), "--edge-factor",
            str(EDGE_FACTOR), "--seed", str(SEED)]


def build_graph(scale: int):
    """The smoke graph, built here for its sizes and sources (each CLI
    builds its own from the same seed)."""
    import jax
    from repro.launch import graph_run
    t0 = time.monotonic()
    g = graph_run.make_graph("rmat", scale, EDGE_FACTOR, SEED)
    jax.block_until_ready(g.row_offsets)
    return g, time.monotonic() - t0


def smoke_sources(g) -> list:
    """The highest-degree vertex and random non-isolated ones: one
    batch of sources whose traversals do real work."""
    import numpy as np
    deg = np.diff(np.asarray(g.row_offsets))
    rng = np.random.default_rng(SEED)
    return [int(np.argmax(deg))] + [
        int(v) for v in rng.choice(np.flatnonzero(deg), BATCH - 1)]


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


def preflight(need: int):
    """Refuse to run anywhere but a TPU, natively, without chaos."""
    for var in ("REPRO_FORCE_INTERPRET", "REPRO_FAULTS"):
        if os.environ.get(var) is not None:
            fail(f"{var} is set; the smoke run measures the plain path")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}; run from a checkout")
    sys.path.insert(0, SRC)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < need:
        fail(f"needs {need} TPU devices, JAX sees {len(devices)}")
    from repro.kernels import runtime
    if runtime.interpret_mode():
        fail("Pallas would run in interpret mode")
    from repro import compile_cache
    say(f"device kind: {devices[0].device_kind}  count: {len(devices)}")
    say(f"compile cache: {compile_cache.enable()}")
    return devices


# the jitted program each primitive runs, as XLA's compile events name it
PROGRAM = {"bfs": "_bfs_impl", "sssp": "_sssp_impl",
           "pagerank": "_pagerank_impl", "cc": "_cc_impl",
           "reach": "_reach_impl"}


def compile_seconds() -> dict:
    """A dict that fills, from here on, with the seconds JAX spends
    tracing, lowering and compiling each jitted program (a persistent
    compile-cache hit counts its load time)."""
    import jax
    seconds: dict = {}

    def on_event(event, duration, fun_name="?", **_):
        if event.startswith("/jax/core/compile/"):
            name = fun_name.removeprefix("jit(").removesuffix(")")
            seconds[name] = seconds.get(name, 0.0) + duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seconds


def span_seconds(name: str) -> float:
    from repro import obs
    return obs.tracing.registry().total_ns(name) / 1e9


def peak_mib(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**20:.1f} MiB"


def run_graph_run(sources):
    from repro.launch import graph_run
    t0 = time.monotonic()
    results = graph_run.main(graph_args() + [
        "--primitives", "bfs,sssp,pagerank,cc", "--backend", "xla",
        "--sources", ",".join(map(str, sources)), "--validate"])
    return results, time.monotonic() - t0


def one_chip(devices) -> None:
    from repro import obs
    from repro.core import backend as B
    from repro.core.storage import resident_bytes
    from repro.launch import graph_serve

    g, build_s = build_graph(SCALE)
    say(f"graph: n={g.num_vertices} vertices  m={g.num_edges} edges  "
        f"resident={resident_bytes(g)['total_bytes'] / 2**20:.1f} MiB  "
        f"build {build_s:.2f} s")
    compiled = compile_seconds()
    # bfs/sssp run as one batch: the same program shape the server runs
    sources = smoke_sources(g)
    results, total_s = run_graph_run(sources)
    say(f"phase graph_run: total {total_s:.2f} s  sources {sources}")
    for r in results:
        c = compiled.get(PROGRAM[r["primitive"]], 0.0)
        say(f"  {r['primitive']:9s} first call {r['seconds']:.3f} s = "
            f"compile {c:.3f} s + run {r['seconds'] - c:.3f} s  "
            f"validate {r['validate_seconds']:.2f} s  "
            f"{'PASS' if r['valid'] else 'FAIL'}")
        if r["valid"] is not True:
            fail(f"graph_run {r['primitive']} did not validate")

    t0 = time.monotonic()
    with obs.capture():     # span_seconds reads what this records
        stats = graph_serve.main(graph_args() + [
            "--kinds", ",".join(KINDS), "--requests", "32",
            "--batch", str(BATCH), "--backend", "xla", "--validate",
            "--warmup", "0"])
    serve_s = time.monotonic() - t0
    validate_s = span_seconds("validate")
    say(f"phase graph_serve: total {serve_s:.2f} s  "
        f"serve {stats['total_s']:.3f} s  validate {validate_s:.2f} s  "
        f"reach compile {compiled.get(PROGRAM['reach'], 0.0):.3f} s "
        f"(in its first batch)")
    for kind, row in stats["per_kind"].items():
        say(f"  {kind:9s} {row['requests']} queries  latency ms "
            f"p50 {row['lat_ms_p50']} p99 {row['lat_ms_p99']}")
    say(f"  validation: "
        f"{'PASS' if stats['validation_failures'] == 0 else 'FAIL'} "
        f"({stats['validation_failures']} failures)")
    say(f"  status_counts: {json.dumps(stats['status_counts'])}")
    if stats["validation_failures"] != 0:
        fail("graph_serve validation failed")
    if stats["status_counts"]["ok"] != stats["requests"]:
        fail(f"queries ended in statuses {stats['status_counts']}")

    for op, (_, bk, pl) in sorted(B.served().items()):
        say(f"provider: {op} -> {bk}/{pl}")
    if any(bk != B.XLA for _, bk, _ in B.served().values()):
        fail("an op was served by a provider other than xla")
    say(f"peak_bytes_in_use: {peak_mib(devices[0])}")


def expected_answers(g, srcs) -> dict:
    """kind -> (what every placement must return, its source, seconds).
    The traversals' exact answers come from the numpy oracles, which the
    single-device program matches exactly; pagerank's float sums are
    compared with the single-device program itself."""
    import numpy as np
    from repro.core import ref as R
    from repro.launch import graph_serve
    oracles = {"bfs": lambda s: R.bfs_ref(g, s),
               "sssp": lambda s: R.sssp_ref(g, s),
               "reach": lambda s: R.reach_ref(g, s, HOPS)}
    out = {}
    for kind in KINDS:
        t0 = time.monotonic()
        if kind in oracles:
            field = np.stack([oracles[kind](int(s)) for s in srcs])
            source = "oracle"
        else:
            field = np.asarray(
                graph_serve._run_kind(g, kind, srcs, "xla", HOPS)[0])
            source = "single"
        out[kind] = (field, source, time.monotonic() - t0)
    return out


def four_chips(devices) -> None:
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.distributed import _shard_any
    from repro.core.partition import partition_1d, partition_2d
    from repro.launch import graph_serve

    g, build_s = build_graph(SCALE)
    say(f"graph: n={g.num_vertices} vertices  m={g.num_edges} edges  "
        f"build {build_s:.2f} s")
    srcs = np.asarray(smoke_sources(g))
    expected = expected_answers(g, srcs)
    for kind, (_, source, seconds) in expected.items():
        say(f"  expected {kind:9s} from {source:6s} {seconds:.2f} s")
    t0 = time.monotonic()
    layouts = {
        "parts 4": (partition_1d(g, 4),
                    Mesh(np.array(devices[:4]), ("graph",)), "graph"),
        "mesh 2x2": (partition_2d(g, 2, 2),
                     Mesh(np.array(devices[:4]).reshape(2, 2),
                          ("row", "col")), ("row", "col")),
    }
    say(f"partition (1-D and 2-D): {time.monotonic() - t0:.2f} s")
    answers = {}
    for name, (pg, mesh, axis) in layouts.items():
        run = graph_serve.make_sharded_runner(pg, mesh, axis)
        for kind in KINDS:
            t0 = time.monotonic()
            field, _ = run(kind, srcs, "xla", HOPS)
            answers[name, kind] = np.asarray(field)
            say(f"  {name:8s} {kind:9s} first call "
                f"{time.monotonic() - t0:.2f} s")
        say(f"  {name} graph bytes per device: "
            f"{shard_mib(_shard_any(pg, mesh, axis), devices[:4])}")
    for kind in KINDS:
        want, source, _ = expected[kind]
        for name in layouts:
            got = answers[name, kind]
            same = got.shape == want.shape and np.array_equal(got, want)
            say(f"parity {kind:9s} {name:8s} vs {source}: "
                f"{'bit-match' if same else 'MISMATCH'}")
            if not same:
                fail(f"{kind} answers under {name} differ from {source}")
    say("peak_bytes_in_use per device at the end: "
        + ", ".join(f"{d.id}: {peak_mib(d)}" for d in devices[:4]))


def shard_mib(sharded_graph, devices) -> str:
    """MiB of the partitioned graph's arrays that each device holds."""
    import jax
    per = {d.id: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves(sharded_graph):
        for shard in leaf.addressable_shards:
            per[shard.device.id] += shard.data.nbytes
    return ", ".join(f"device {d} {b / 2**20:.1f} MiB"
                     for d, b in per.items())


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device placement parity phase")
    args = ap.parse_args()
    need = 4 if args.four_chips else 1
    devices = preflight(need)
    (four_chips if args.four_chips else one_chip)(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
