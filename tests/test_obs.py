"""Observability layer (PR 8): telemetry oracles + bit-parity, span
tracing, serving metrics, the log knob, and the bench-regression gate."""
import json
import logging
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import graph as G
from repro.core.primitives import (bc_batch, bfs, bfs_batch,
                                   connected_components, pagerank, sssp,
                                   sssp_batch, triangle_count)
from repro.obs import telemetry as T
from repro.obs.metrics import Histogram, Metrics, latency_summary, quantile

BACKENDS = ("xla", "pallas")


@pytest.fixture(scope="module")
def small_graph():
    return G.rmat(7, 8, seed=3, weighted=True)


def _level_sizes(labels: np.ndarray, steps: int) -> np.ndarray:
    """BFS oracle: telemetry step t records the size of depth-(t+1)
    level (the frontier *after* the step); the final step records 0."""
    lab = labels[labels >= 0]
    counts = np.bincount(lab, minlength=steps + 1)
    expect = np.zeros(steps, np.int64)
    upto = min(steps, len(counts) - 1)
    expect[:upto] = counts[1:upto + 1]
    return expect


# ---------------------------------------------------------------- telemetry

@pytest.mark.parametrize("backend", BACKENDS)
def test_bfs_telemetry_matches_level_oracle(rmat_graph, high_degree_src,
                                            backend):
    r, buf = bfs_batch(rmat_graph, [high_degree_src], backend=backend,
                       telemetry=True)
    trace = T.trim(buf, np.asarray(r.iterations)).lane(0)
    assert trace.steps == int(r.iterations[0])
    expect = _level_sizes(np.asarray(r.labels[0]), trace.steps)
    assert np.array_equal(trace["frontier"], expect)
    # direction column is the per-step push/pull mode: 0 or 1 only
    assert set(np.unique(trace["direction"])) <= {0, 1}
    assert np.all(trace["tier"] > 0)


def test_run_until_any_lane_iters_match_buffer(rmat_graph,
                                               high_degree_src):
    # a ragged batch: the hub plus a low-degree vertex have different
    # eccentricities, so lane iteration counts differ
    deg = np.diff(np.asarray(rmat_graph.row_offsets))
    lo = int(np.argmin(np.where(deg > 0, deg, deg.max() + 1)))
    srcs = [high_degree_src, lo]
    r, buf = bfs_batch(rmat_graph, srcs, telemetry=True)
    lane_iters = np.asarray(r.iterations)
    trace = T.trim(buf, lane_iters)
    # the buffer records every wall-clock step: the slowest lane's count
    assert trace.steps == int(lane_iters.max())
    assert int(buf.cursor) == trace.steps
    for b in range(len(srcs)):
        lane = trace.lane(b)
        assert lane.steps == int(lane_iters[b])
        expect = _level_sizes(np.asarray(r.labels[b]), lane.steps)
        assert np.array_equal(lane["frontier"], expect)
        assert lane["frontier"][-1] == 0        # termination step


def _run(prim, g, src, backend, telemetry):
    if prim == "bfs":
        r = bfs(g, src, backend=backend, telemetry=telemetry)
    elif prim == "sssp":
        r = sssp(g, src, backend=backend, telemetry=telemetry)
    elif prim == "pagerank":
        r = pagerank(g, max_iter=10, backend=backend,
                     telemetry=telemetry)
    elif prim == "cc":
        r = connected_components(g, backend=backend, telemetry=telemetry)
    elif prim == "bc":
        r = bc_batch(g, [src], backend=backend, telemetry=telemetry)
    else:
        r = triangle_count(g, backend=backend, telemetry=telemetry)
    return r[0] if telemetry else r


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("prim", ("bfs", "sssp", "pagerank", "cc", "bc",
                                  "tc"))
def test_telemetry_changes_no_result_bit(small_graph, backend, prim):
    deg = np.diff(np.asarray(small_graph.row_offsets))
    src = int(np.argmax(deg))
    plain = _run(prim, small_graph, src, backend, False)
    with_t = _run(prim, small_graph, src, backend, True)
    la, lb = jax.tree_util.tree_leaves(plain), \
        jax.tree_util.tree_leaves(with_t)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), prim


def test_sssp_telemetry_columns(rmat_graph, high_degree_src):
    r, buf = sssp_batch(rmat_graph, [high_degree_src], telemetry=True)
    trace = T.trim(buf, np.asarray(r.iterations)).lane(0)
    assert set(trace.names) == {"frontier", "tier", "bucket",
                                "relaxations"}
    assert np.all(trace["relaxations"] >= 0)
    assert np.all(np.diff(trace["bucket"]) >= 0)    # buckets only grow


def test_distributed_trace_comm_model(rmat_graph, high_degree_src):
    from repro.core.distributed import exchange_bytes_per_step
    from repro.core.partition import partition_1d
    pg = partition_1d(rmat_graph, 2)
    r = bfs(rmat_graph, high_degree_src)
    steps = int(r.iterations)
    trace = T.distributed_trace(pg, "bfs", steps,
                                labels=np.asarray(r.labels))
    assert trace.steps == steps
    per = exchange_bytes_per_step(pg, "bfs")
    assert np.all(trace["exchange_bytes"] == per) and per > 0
    # the frontier column recovered from labels is the same level oracle
    assert np.array_equal(trace["frontier"],
                          _level_sizes(np.asarray(r.labels), steps))


def test_buffer_overflow_drops_but_counts():
    buf = T.TelemetryBuffer.make(2, {"x": ((), np.int32)})
    for i in range(5):
        buf = buf.record(x=i)
    assert int(buf.cursor) == 5
    trace = T.trim(buf)
    assert trace.steps == 2                         # capped at capacity
    assert np.array_equal(trace["x"], [0, 1])       # drops kept rows


def test_format_table_renders_direction():
    buf = T.TelemetryBuffer.make(2, {"frontier": ((1,), np.int32),
                                     "direction": ((1,), np.int32)})
    buf = buf.record(frontier=np.array([7]), direction=np.array([0]))
    buf = buf.record(frontier=np.array([3]), direction=np.array([1]))
    table = T.trim(buf).format_table()
    assert "push" in table and "pull" in table and "frontier" in table


# ------------------------------------------------------------------ metrics

def test_quantiles_linear_interpolation_small_samples():
    xs = [10.0, 20.0]
    assert quantile(xs, 0.5) == pytest.approx(15.0)
    s = latency_summary(xs)
    assert s["samples"] == 2
    assert s["lat_ms_p50"] == pytest.approx(15.0)
    assert s["lat_ms_p99"] == pytest.approx(
        float(np.quantile(xs, 0.99)), abs=0.01)
    one = latency_summary([5.0])
    assert one["lat_ms_p50"] == one["lat_ms_p99"] == 5.0


def test_histogram_streaming_quantiles():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(1.0, 0.7, size=5000)
    h = Histogram()
    h.observe_many(xs)
    for q in (0.5, 0.95, 0.99):
        exact = float(np.quantile(xs, q))
        est = h.quantile(q)
        # log-bucketed with growth sqrt(2): relative error < one bucket
        assert abs(est - exact) / exact < 0.5, (q, est, exact)
    assert h.quantile(0.0) == pytest.approx(float(xs.min()))
    assert h.quantile(1.0) == pytest.approx(float(xs.max()))


def test_histogram_merge_and_layout_guard():
    a, b = Histogram(), Histogram()
    a.observe_many([1.0, 2.0, 4.0])
    b.observe_many([8.0, 16.0])
    a.merge(b)
    assert a.total == 5
    assert a.quantile(1.0) == pytest.approx(16.0)
    with pytest.raises(ValueError):
        a.merge(Histogram(buckets=4))


def test_metrics_render_parseable_prometheus():
    m = Metrics()
    for v in (1.0, 2.0, 3.0, 50.0):
        m.observe("latency_ms", v, help="per-query latency", kind="bfs")
    m.counter("queries_total", 4, help="queries", kind="bfs")
    m.gauge_max("queue_depth_peak", 7, help="peak depth")
    text = m.render()
    import re
    sample = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
                        r"(\{[^}]*\})? -?[0-9eE.+-]+(\.[0-9]+)?$|"
                        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \+?Inf$")
    names = set()
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        assert sample.match(line), f"bad exposition line: {line!r}"
        names.add(line.split("{")[0].split(" ")[0])
    assert "graph_serve_latency_ms_bucket" in names
    assert "graph_serve_latency_ms_count" in names
    assert "graph_serve_latency_ms_quantile" in names
    assert "graph_serve_queries_total" in names
    assert "graph_serve_queue_depth_peak" in names
    # histogram buckets must be cumulative and end at the sample count
    counts = [float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines()
              if ln.startswith("graph_serve_latency_ms_bucket")]
    assert counts == sorted(counts) and counts[-1] == 4.0


# ------------------------------------------------------------------ tracing

def test_span_registry_and_chrome_export(tmp_path):
    obs.reset()
    with obs.capture():
        with obs.span("outer", category="setup"):
            with obs.span("inner", category="dispatch",
                          args={"k": 1}):
                pass
    events = obs.registry().events
    assert [e.name for e in events] == ["inner", "outer"]
    out = tmp_path / "trace.json"
    n = obs.export_chrome_trace(str(out))
    assert n == 2
    doc = json.loads(out.read_text())
    assert isinstance(doc["traceEvents"], list)
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert ev["dur"] >= 0 and "ts" in ev and ev["name"]
    inner = [e for e in doc["traceEvents"] if e["name"] == "inner"][0]
    assert inner["args"] == {"k": 1}
    obs.reset()
    assert not obs.registry().events


def test_span_records_only_under_a_capture():
    obs.reset()
    with obs.span("outside", args={"k": 1}):
        pass
    assert not obs.registry().events
    with obs.capture():
        with obs.capture():           # nested: one registry, no reset
            with obs.span("first"):
                pass
        with obs.span("second") as late:
            late["attempts"] = 2
    with obs.span("after"):
        pass
    ev = obs.registry().events
    assert [e.name for e in ev] == ["first", "second"]
    assert ev[1].args == {"attempts": 2}
    with obs.capture():               # a new capture starts empty
        pass
    assert not obs.registry().events


def test_span_lands_on_the_profilers_host_plane(tmp_path):
    """A span is a ``jax.profiler`` annotation: it shows on the host
    plane of a capture, its args (the late ones too) as the event's
    stats, while the span registry stays empty without ``capture()``."""
    import glob

    import jax

    obs.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("serve.flush", args={"kind": "bfs", "lanes": 3}) as a:
            a["attempts"] = 1
    finally:
        jax.profiler.stop_trace()
    assert not obs.registry().events
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = [dict(e.stats)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "serve.flush"]
    assert found == [{"kind": "bfs", "lanes": 3, "attempts": 1}]


# ---------------------------------------------------------------------- log

def test_logger_hierarchy_and_env_knob(monkeypatch):
    from repro.obs import log as L
    lg = L.get_logger("tuner")
    assert lg.name == "repro.tuner"
    # no-arg configure is idempotent once installed; forcing a fresh
    # configure re-reads the env knob (keeps the lazy-stdout handler)
    monkeypatch.setenv(L.ENV_VAR, "debug")
    monkeypatch.setattr(L, "_configured", False)
    assert L.configure().level == logging.DEBUG
    monkeypatch.setenv(L.ENV_VAR, "warning")
    monkeypatch.setattr(L, "_configured", False)
    assert L.configure().level == logging.WARNING
    monkeypatch.delenv(L.ENV_VAR)
    L.configure(level=logging.INFO)     # restore the default for the rest


def test_deprecated_still_warns():
    from repro.obs.log import deprecated
    with pytest.warns(DeprecationWarning, match="gone soon"):
        deprecated("gone soon")


def test_use_kernel_deprecation_unchanged(rmat_graph, high_degree_src):
    with pytest.warns(DeprecationWarning, match="use_kernel"):
        bfs(rmat_graph, high_degree_src, use_kernel=False)


# ------------------------------------------------------------ compare gate

COMPARE = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                       "compare.py")


def _compare(tmp_path, fresh_rows, base_rows, threshold="0.25"):
    fp, bp = tmp_path / "fresh.json", tmp_path / "base.json"
    fp.write_text(json.dumps(fresh_rows))
    bp.write_text(json.dumps(base_rows))
    return subprocess.run(
        [sys.executable, COMPARE, str(fp), "--baseline", str(bp),
         "--threshold", threshold],
        capture_output=True, text=True)


def _row(ms, **kw):
    row = {"bench": "frontier_scaling", "primitive": "bfs",
           "backend": "xla", "tiered": True, "n": 512, "m": 4096,
           "ms": ms, "platform": "cpu"}
    row.update(kw)
    return row


def test_compare_passes_within_threshold(tmp_path):
    r = _compare(tmp_path, [_row(11.0)], [_row(10.0)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_compare_fails_on_injected_slowdown(tmp_path):
    r = _compare(tmp_path, [_row(20.0)], [_row(10.0)])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout


def test_compare_ignores_unshared_and_cross_platform(tmp_path):
    # different n => different cell; different platform => not compared
    r = _compare(tmp_path,
                 [_row(99.0, n=1024), _row(99.0, platform="gpu")],
                 [_row(10.0)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "no shared" in r.stdout


def test_compare_skips_rows_without_ms(tmp_path):
    occ = {"bench": "frontier_occupancy", "backend": "xla",
           "frontier": 32, "ms_tiered": 0.1, "ms_pinned": 1.0}
    r = _compare(tmp_path, [_row(10.0), occ], [_row(10.0), occ])
    assert r.returncode == 0
    assert "1 shared cells" in r.stdout or "OK" in r.stdout
