"""The four-chip cell ``g500-s21-4chip`` driven end to end at a tiny size
on four fake host devices (a subprocess, so that the test process keeps one
device): correct against the reference, not correct with the control in
the program's place; its per-layer readers on a hand-made trace; the
roofline's byte count by hand; the stored search keys' record."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import bfs_costs, harness, peaks, scopes

MS = 1_000_000      # ns
CELL = "g500-s21-4chip"


def test_tiny_mesh_cell_is_correct_and_its_control_fails():
    code = textwrap.dedent("""
        import dataclasses, json
        from bench import graphdata, harness
        from bench.conftest import TINY

        spec = harness.load_spec("%s")
        cfg = dict(spec.config, **TINY["kronecker"])
        cfg["search_keys"] = graphdata.search_keys(
            cfg, len(cfg["search_keys"]), spec.bench_dir).tolist()
        spec = dataclasses.replace(spec, config=cfg)
        out = {}
        for name, kw in (("plain", {}), ("control", {"control": True}),
                         ("traced", {"trace": True})):
            result, run = harness.run_cell(spec, 2**31 + 11, 0.5,
                                           require_tpu=False, **kw)
            out[name] = {"result": result, "units": len(run.items),
                         "keys": len(cfg["search_keys"]),
                         "iterations": [u["iterations"]
                                        for u in run.items],
                         "compiles": run.compiles_in_window}
        print("RESULT " + json.dumps(out))
    """ % CELL)
    env = dict(os.environ, REPRO_BACKEND="xla", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([harness.ROOT, os.path.join(
                   harness.ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    line = next(x for x in proc.stdout.splitlines()
                if x.startswith("RESULT "))
    out = json.loads(line[len("RESULT "):])
    plain = out["plain"]
    r = plain["result"]
    assert r["correct"], r["checks"]
    assert r["checks"]["bfs_mismatches"]["value"] == 0
    assert r["device"]["count"] == 4
    assert r["attempted"] == 8 * plain["units"]
    assert plain["units"] % (plain["keys"] // 8) == 0     # whole cycles
    assert all(i >= 2 for i in plain["iterations"])
    assert set(r["metrics"]) == {"setup_s", "teps"}
    ctl = out["control"]["result"]
    assert not ctl["correct"]
    assert ctl["checks"]["bfs_mismatches"]["value"] >= 1
    traced = out["traced"]
    assert traced["result"]["correct"]
    assert traced["compiles"] == 0
    # on the CPU the trace holds no chip, so the device readers are
    # silent and only the host-clock ones read
    assert set(traced["result"]["metrics"]) == {"graph_build_s",
                                                "bsp_iter_ms"}


def _mesh_run(sc, summary):
    run = harness.Run(spec=harness.load_spec(CELL), seed=1, seconds=10.0,
                      trace=True, trace_summary=summary,
                      peaks=peaks.peaks_for("TPU v5 lite"))
    run.__dict__["_bench_scopes"] = sc
    return run


def test_mesh_readers_on_a_hand_made_trace():
    # per chip: 300 ms local expansion, 40 ms apply, 60 ms exchange,
    # 20 ms enactor; two batches of 5 and 5 iterations
    sc = scopes.Scopes({"leaf": {"op.advance_filter": 300 * MS,
                                 "op.apply": 40 * MS,
                                 "op.exchange": 60 * MS,
                                 "enactor.loop": 20 * MS},
                        "root": {"op": 400 * MS, "enactor": 20 * MS},
                        "step": {}, "busy": 420 * MS},
                       [], (0, 500 * MS), [])
    run = _mesh_run(sc, {"busy_s": 0.42, "window_s": 0.5})
    # a path 0-1-2 and an edge 3-4: components of 3 and 2 vertices
    ro = np.array([0, 1, 3, 4, 5, 6])
    ci = np.array([1, 0, 2, 1, 4, 3])
    run.graph = type("G", (), {"csr": lambda self: (ro, ci, None)})()
    run.items = [{"sources": np.array([0, 2, 3]), "iterations": 5,
                  "t0": 1.0, "t1": 1.2},
                 {"sources": np.array([4]), "iterations": 5,
                  "t0": 1.2, "t1": 1.5}]
    run.graph_build_s = 31.5
    read = {m["name"]: run.spec.reader(m["name"]).read(run)
            for m in run.spec.metrics(True)}
    assert set(read) == {"local_ms_per_iter.mesh",
                         "exchange_ms_per_iter.mesh",
                         "device_idle_share.traversal", "advance_2d_roofline",
                         "enactor_ms_per_iter.traversal", "graph_build_s",
                         "bsp_iter_ms"}
    assert read["local_ms_per_iter.mesh"] == pytest.approx(34.0)
    assert read["exchange_ms_per_iter.mesh"] == pytest.approx(6.0)
    assert read["device_idle_share.traversal"] == pytest.approx(16.0)
    assert read["enactor_ms_per_iter.traversal"] == pytest.approx(2.0)
    assert read["graph_build_s"] == 31.5
    assert read["bsp_iter_ms"] == pytest.approx(50.0)
    least_s = 12 * (3 + 3 + 2 + 2) / (4 * 819e9)
    assert read["advance_2d_roofline"] == pytest.approx(
        100 * least_s / 0.4)
    # an untraced run, or a program without scopes, reads nothing on
    # the device
    device = [m for m in read if m not in ("graph_build_s", "bsp_iter_ms")]
    bare = scopes.Scopes({"leaf": {scopes.NONE: 1},
                          "root": {scopes.NONE: 1}, "step": {}, "busy": 1},
                         [], (0, 1), [])
    for sc2, summary in ((None, None), (bare, None)):
        run2 = _mesh_run(sc2, summary)
        run2.items = run.items
        run2.graph = run.graph
        assert all(run2.spec.reader(m).read(run2) is None
                   for m in device)


def test_bfs_least_bytes_by_hand():
    # a triangle 0-1-2, an edge 3-4, an isolated vertex 5
    ro = np.array([0, 2, 4, 6, 7, 8, 8])
    ci = np.array([1, 2, 0, 2, 0, 1, 4, 3])
    verts = bfs_costs.component_vertices((ro, ci, None))
    assert verts.tolist() == [3, 3, 3, 2, 2, 1]
    # depth written (4 B), one offset and one column read (4 B each)
    assert bfs_costs.bfs_least_bytes(verts[[0, 3, 5]]) == 12 * 6
    assert bfs_costs.bfs_least_bytes(3) == 36


def test_stored_scale_21_keys_are_graph500_keys_ordered_by_depth():
    """Recomputing the keys takes the numpy reference some minutes at
    scale 21, so the record is checked instead: Graph500's 64 keys (8
    batches of 8 lanes), distinct vertices of the graph, and ordered as
    ``graphdata.search_keys`` orders them (eccentricity falling, ids
    rising within a depth)."""
    cfg = harness.load_spec(CELL).config
    keys = np.asarray(cfg["search_keys"])
    ecc = np.asarray(cfg["search_key_eccentricity"])
    assert len(keys) == 64
    assert len(set(keys.tolist())) == len(keys)
    assert keys.min() >= 0 and keys.max() < 2 ** cfg["scale"]
    assert len(ecc) == len(keys) and ecc.min() >= 1
    assert np.array_equal(np.lexsort((keys, -ecc)), np.arange(len(keys)))
