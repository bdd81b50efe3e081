"""Metric arithmetic, trace reduction, byte counts, peaks, and the
shape of BENCHMARK.json."""
import json
import os
import re

import numpy as np
import pytest

from bench import costs, harness, peaks, stats, trace


def _run(cell="g500-bfs", **kw):
    run = harness.Run(spec=harness.load_spec(cell), seed=1, seconds=10.0,
                      trace=False)
    for k, v in kw.items():
        setattr(run, k, v)
    return run


def _read(run, metric):
    return run.spec.reader(metric).read(run)


def test_rates_run_to_the_last_completion():
    # the window was asked for 10 s; the batch in flight ended at 12.5 s
    items = [{"t0": 100.0 + 2.5 * i, "t1": 102.5 + 2.5 * i,
              "sources": np.array([0, 1]), "iterations": 5}
             for i in range(5)]
    run = _run(window_t0=100.0, window_t1=112.5, items=items,
               _component_edges=np.array([3.0, 4.0]))
    assert run.window_s == 12.5
    assert _read(run, "teps") == pytest.approx(5 * 7.0 / 12.5)
    assert _read(run, "bsp_iter_ms") == pytest.approx(2500.0 / 5)
    pr = _run("g500-pagerank", window_t0=100.0, window_t1=112.5,
              items=items)
    assert _read(pr, "analytic_s") == pytest.approx(2.5)


def test_query_p95_counts_failed_queries_as_slowest():
    lat = [{"ok": True, "lat_ms": float(i)} for i in range(1, 101)]
    run = _run("g500-serve", window_t0=0.0, window_t1=20.0, queries=lat)
    assert _read(run, "query_p95_ms") == pytest.approx(
        np.quantile(np.arange(1, 101), 0.95))
    assert _read(run, "queries_per_s") == pytest.approx(100 / 20.0)
    for q in lat[:6]:
        q.update(ok=False, lat_ms=float("inf"))
    assert _read(run, "query_p95_ms") == float("inf")
    assert _read(run, "queries_per_s") == pytest.approx(94 / 20.0)


def test_engine_host_share_counts_runner_time_in_the_window():
    run = _run("g500-serve", window_t0=0.0, window_t1=10.0,
               spans=[("runner", 1.0, 4.0), ("runner", 5.0, 8.0),
                      ("runner", 11.0, 12.0)])
    assert _read(run, "engine_host_share.serve") == pytest.approx(40.0)


def test_quantile_interpolates_like_numpy():
    x = np.random.default_rng(0).random(37)
    for q in (0.5, 0.95, 0.99):
        assert stats.quantile(x, q) == pytest.approx(np.quantile(x, q))


def test_trace_reduction_busy_union_gaps_and_breakdown():
    dev = {"/device:TPU:0": [("fusion.1", 10, 20), ("fusion.2", 20, 30),
                             ("while.3", 50, 60), ("fusion.4", 52, 58),
                             ("fusion.1", 95, 130)]}
    host = [("bench.batch", 0, 100), ("serve.flush", 32, 48)]
    tr = trace.reduce_events(dev, host, (0, 100))
    assert tr["window_s"] == pytest.approx(100e-9)
    assert tr["busy_s"] == pytest.approx((20 + 10 + 5) * 1e-9)
    assert tr["device_ops"][0] == ["fusion.1", pytest.approx(15e-9)]
    assert dict(tr["device_ops"])["while.3"] == pytest.approx(4e-9)
    assert dict(tr["device_ops"])["fusion.4"] == pytest.approx(6e-9)
    assert trace.op_name("%fusion.4 = f32[8]{0} fusion(%p)") == "fusion.4"
    assert tr["idle_gaps"][0] == ["bench.batch", pytest.approx(35e-9)]
    assert tr["idle_gaps"][1] == ["serve.flush", pytest.approx(20e-9)]
    assert tr["idle_gaps"][2] == ["bench.batch", pytest.approx(10e-9)]
    assert trace.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert trace._is_chip("/device:TPU:0")
    assert not trace._is_chip("/device:TPU:0 SparseCore")


def test_idle_share_and_roofline_from_a_trace_summary():
    run = _run("g500-pagerank", trace_summary={"busy_s": 4.0,
                                               "window_s": 5.0},
               peaks=peaks.peaks_for("TPU v5 lite"),
               items=[{"t0": 0, "t1": 1, "sweeps": 20}] * 3)
    run.graph = type("G", (), {"n": 32768, "stored_edges": 882046})()
    assert _read(run, "device_idle_share.pagerank") == pytest.approx(20.0)
    least = 60 * costs.pull_spmv_sweep_bytes(32768, 882046) / 819e9
    assert _read(run, "spmv_roofline") == pytest.approx(100 * least / 4.0)
    run.trace_summary = None
    assert _read(run, "spmv_roofline") is None
    assert _read(run, "device_idle_share.pagerank") is None


def test_spmv_sweep_bytes():
    assert costs.pull_spmv_sweep_bytes(32768, 882046) == (
        4 * 882046 + 4 * 32769 + 12 * 32768)


def test_peaks_match_exactly_and_refuse_unknown_kinds():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("TPU v5", "tpu v5 lite", "cpu"):
        with pytest.raises(KeyError):
            peaks.peaks_for(kind)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_is_complete():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    metrics = bm["end_to_end"] + bm["per_layer"]
    e2e = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in e2e
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in metrics:
        assert NAME.match(m["name"])
        assert os.path.isfile(os.path.join(harness.HERE, "metrics",
                                           m["name"] + ".py"))
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
    for c in bm["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
    for w in bm["workloads"]:
        spec = harness.load_spec(w["name"])
        assert os.path.isfile(os.path.join(
            harness.HERE, "drivers", spec.traffic["driver"] + ".py"))
        e, p = spec.metrics(False), spec.metrics(True)
        assert "setup_s" in [m["name"] for m in e] and len(e) >= 2
        assert p
        for m in p:
            assert m["moves"] in [x["name"] for x in e]
