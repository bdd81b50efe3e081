"""Run one cell of ``BENCHMARK.json`` once: set-up, a measured window,
the check against the plain reference, and the result line.

Everything particular to one configuration, traffic mix or metric is
found by its name: ``bench/configs/<config>.json`` (through the
``file`` of its entry), ``bench/traffic/<traffic>.json``, the driver
that mix names in ``bench/drivers/<driver>.py``, the graph generator a
configuration names in ``bench/graphs/<generator>.py``, and one reader
per metric in ``bench/metrics/<metric>.py``.

A window runs closed-loop units (a batch, a chunk of queries, an
analytic run) back to back, in cycles that each do the same work in an
order drawn from the seed (one pass over the search keys or the
dataset's chunks, one analytic run), and stops at the first end of a
cycle once ``--seconds`` have passed: every run does whole cycles of
the same work, and every rate is the work completed over the time from
the window's start to the last completion.
"""
from __future__ import annotations

import argparse
import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from . import graphdata
from . import reference as R

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, ".out")      # listed in bench/.gitignore
# device events and host spans of one traced run are reduced from here
TRACE_WINDOW = "bench.window"


class Refused(RuntimeError):
    """The run cannot measure what it was asked to (no chip, an unknown
    chip, a cell that is not declared)."""


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Spec:
    """What ``BENCHMARK.json`` and the files it names say of one cell."""
    cell: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench_dir: str = HERE

    @property
    def name(self) -> str:
        return self.cell["name"]

    def metrics(self, trace: bool) -> list:
        """The metrics this cell reports: end-to-end ones with
        ``--trace 0``, per-layer ones with ``--trace 1``."""
        e2e = [m for m in self.end_to_end
               if self.name in m.get("workloads", [self.name])]
        if not trace:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.per_layer
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]

    def reader(self, metric: str):
        return _load_module(
            os.path.join(self.bench_dir, "metrics", f"{metric}.py"),
            f"bench_metric_{metric.replace('.', '_').replace('-', '_')}")

    def driver(self):
        name = self.traffic["driver"]
        return _load_module(
            os.path.join(self.bench_dir, "drivers", f"{name}.py"),
            f"bench_driver_{name.replace('-', '_')}")


def load_spec(workload: str, root: str = ROOT,
              bench_dir: str = HERE) -> Spec:
    bm = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json; "
                      f"declared: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    config = _load_json(os.path.join(root, entry["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      f"{cell['traffic']}.json"))
    return Spec(cell=cell, config=config, traffic=traffic,
                end_to_end=bm["end_to_end"], per_layer=bm["per_layer"],
                bench_dir=bench_dir)


@dataclass
class Check:
    """One number compared with the reference, beside its limit: the
    run is correct when every value is at most its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclass
class Run:
    """Everything one run records; the metric readers read it."""
    spec: Spec
    seed: int
    seconds: float
    trace: bool
    control: bool = False
    graph: Optional[graphdata.GraphData] = None
    peaks: Optional[dict] = None
    setup_s: float = 0.0
    graph_build_s: float = 0.0
    window_t0: float = 0.0
    window_t1: float = 0.0
    items: list = field(default_factory=list)     # one per unit
    queries: list = field(default_factory=list)   # served queries
    spans: list = field(default_factory=list)     # (name, t0, t1)
    phases: dict = field(default_factory=dict)    # set-up seconds
    compiles_in_window: int = 0
    trace_summary: Optional[dict] = None
    checks: list = field(default_factory=list)
    program_checks: Optional[list] = None   # beside a control's checks
    attempted: int = 0
    failed: int = 0
    _component_edges: Optional[np.ndarray] = None

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0

    def component_edges(self) -> np.ndarray:
        if self._component_edges is None:
            self._component_edges = R.component_edges(self.graph.csr())
        return self._component_edges


def _device_info(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if require_tpu and platform != "tpu":
        raise Refused(f"no TPU: JAX found {platform} devices only")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips; JAX found "
                      f"{len(devs)}")
    return devs[:chips]


def _memory_peak(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def build_graph(gd: graphdata.GraphData):
    """The program's own host build (CSR + CSC) and its transfer."""
    import jax
    from repro.core.graph import from_edge_list
    g = from_edge_list(gd.src, gd.dst, n=gd.n, values=gd.weights,
                       undirected=True)
    jax.block_until_ready(g)
    return g


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool = False,
             t0: Optional[float] = None, control: bool = False,
             require_tpu: bool = True,
             t_imports: Optional[float] = None) -> tuple:
    """One run of one cell. Returns ``(result, run)``; ``result`` is the
    dict that the last line of output prints. ``t_imports``, where given,
    marks the end of the imports, so that set-up's first phase is split
    into importing JAX and reaching the chip."""
    import jax
    import jax.monitoring as monitoring

    t0 = time.monotonic() if t0 is None else t0
    devs = _device_info(int(spec.cell.get("chips", 1)), require_tpu)
    kind = devs[0].device_kind
    run = Run(spec=spec, seed=int(seed), seconds=float(seconds),
              trace=bool(trace), control=control)
    if require_tpu:
        from .peaks import peaks_for
        run.peaks = peaks_for(kind)
    driver = spec.driver()
    marks = [("start", t0),
             *([("imports", t_imports)] if t_imports is not None else []),
             ("devices", time.monotonic())]
    run.graph = graphdata.make(spec.config, seed, spec.bench_dir)
    marks.append(("generate", time.monotonic()))
    g = build_graph(run.graph)
    marks.append(("build", time.monotonic()))
    run.graph_build_s = marks[-1][1] - marks[-2][1]
    state = driver.prepare(run, g, spec.traffic)
    marks.append(("prepare", time.monotonic()))
    driver.warm(run, state)
    marks.append(("warm", time.monotonic()))
    run.phases = {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}

    compiles = []

    def on_event(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.monotonic())

    monitoring.register_event_duration_secs_listener(on_event)
    trace_dir = os.path.join(OUT, "trace", spec.name)
    try:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        run.window_t0 = time.monotonic()
        run.setup_s = run.window_t0 - t0
        unit = f"bench.{spec.traffic['driver']}"
        per_cycle = driver.cycle(state)
        with jax.profiler.TraceAnnotation(TRACE_WINDOW):
            i = 0
            while True:
                with jax.profiler.TraceAnnotation(unit):
                    driver.step(run, state, i)
                i += 1
                if (i % per_cycle == 0 and
                        run.items[-1]["t1"] - run.window_t0 >= seconds):
                    break
        run.window_t1 = run.items[-1]["t1"]
        if trace:
            jax.profiler.stop_trace()
    finally:
        monitoring.unregister_event_duration_listener(on_event)
    run.compiles_in_window = sum(run.window_t0 <= c <= run.window_t1
                                 for c in compiles)

    memory_peak = _memory_peak(devs)
    driver.collect(run, state)
    del g
    gc.collect()
    if control and not getattr(driver, "CONTROL_IS_A_PROGRAM_PATH", False):
        # the control takes the place of this run's own answers: read
        # the program's numbers first, from the same run
        run.control = False
        run.program_checks = driver.check(run, state)
        run.control = True
    run.checks = driver.check(run, state)
    if trace:
        from .trace import reduce_trace
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if files:
            run.trace_summary = reduce_trace(max(files, key=os.path.getmtime),
                                             TRACE_WINDOW)

    metrics = {}
    for m in spec.metrics(trace):
        value = spec.reader(m["name"]).read(run)
        if value is None:
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    if trace and run.trace_summary is not None:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
    result = {"correct": all(c.ok for c in run.checks) and bool(run.checks),
              "attempted": run.attempted, "failed": run.failed,
              "metrics": metrics, "device": device}
    if trace and run.trace_summary is not None:
        result["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"],
            "idle_gaps": run.trace_summary["idle_gaps"]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in run.checks}
    return result, run


def _finite(x):
    """JSON has no inf or NaN: a value that is neither finite nor a
    number prints as null (a failed query's latency, a NaN rank)."""
    return x if not isinstance(x, float) or math.isfinite(x) else None


def main(argv, t0: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_spec(args.workload)
        from repro import compile_cache
        import jax
        compile_cache.enable()
        # every program of a cell is cached, so that a second run of it
        # in this checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        t_imports = time.monotonic()
        result, run = run_cell(spec, args.seed, args.seconds,
                               bool(args.trace), t0=t0, t_imports=t_imports)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(f"setup_s {run.setup_s:.4f}  graph_build_s "
          f"{run.graph_build_s:.4f}  window_s {run.window_s:.4f}  units "
          f"{len(run.items)}  compiles_in_window {run.compiles_in_window}",
          file=sys.stderr)
    print("setup phases " + "  ".join(f"{k} {v:.4f}"
                                      for k, v in run.phases.items()),
          file=sys.stderr)
    units = run.items[:100]
    print("unit ms " + ",".join(f"{(u['t1'] - u['t0']) * 1e3:.0f}"
                                for u in units), file=sys.stderr)
    if any(u.get("iterations") for u in units):
        print("unit iterations " + ",".join(str(u.get("iterations"))
                                            for u in units),
              file=sys.stderr)
    for c in run.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    for m in (*result["metrics"].values(), *result["checks"].values()):
        m["value"] = _finite(m["value"])
    print(json.dumps(result, allow_nan=False))
    sys.stdout.flush()
    return 0
