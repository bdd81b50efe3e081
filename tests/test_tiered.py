"""Frontier-proportional performance layer: capacity tiers, the fused
advance_filter megakernel, and the kernel autotuner.

Contracts under test:
  * tier machinery: ladder construction, rung selection, pinning;
  * fused advance_filter == the unfused advance→filter composition,
    bit for bit, on both backends (single-lane and batched, empty
    frontiers, duplicate-heavy expansions, cap_front overflow);
  * bfs/sssp results are bit-identical between the tiered dispatch and
    the pinned top tier, on both backends, with frontier sizes
    straddling the tier ladder's rungs (the rmat fixture's BFS crosses
    512 within two hops);
  * a batch's mixed direction step sizes its push by the push lanes'
    workload alone, with results unchanged;
  * tuner: clamped default heuristic, cache round trip, env switches.
"""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as B
from repro.core import frontier as F
from repro.core import graph as G
from repro.core import operators as ops
from repro.core import ref as R
from repro.core.enactor import tiered_step
from repro.core.primitives import bfs, bfs_batch, sssp_batch
from repro.kernels import runtime, tuner
from repro.obs import telemetry as T

BACKENDS = ["xla", "pallas"]


# ---------------------------------------------------------------------------
# tier metadata
# ---------------------------------------------------------------------------


def test_tier_caps_ladder():
    assert F.tier_caps(100) == (100,)
    assert F.tier_caps(512) == (512,)
    assert F.tier_caps(513) == (512, 513)
    assert F.tier_caps(5000) == (512, 1024, 2048, 4096, 5000)
    # top rung is exactly the cap, never a rounded-up power of two
    assert F.tier_caps(97194)[-1] == 97194


def test_tier_index_picks_smallest_sufficient_rung():
    caps = (512, 1024, 2048, 4096)
    for need, want in [(0, 0), (1, 0), (512, 0), (513, 1), (1024, 1),
                       (2049, 3), (4096, 3), (999999, 3)]:
        assert int(F.tier_index(jnp.int32(need), caps)) == want, need


def test_tier_plan_floor_and_pinning():
    caps = B.tier_plan("advance_filter", 4096)
    assert caps[0] >= F.MIN_TIER and caps[-1] == 4096
    impl, pinned = B.dispatch_tiered("advance", cap=4096, pin=True)
    assert pinned == (4096,)
    assert callable(impl)


def test_tiered_step_runs_selected_branch():
    caps = (4, 8, 16)
    out = tiered_step(jnp.int32(5), caps, lambda c: (lambda s: s + c),
                      jnp.int32(0))
    assert int(out) == 8
    # single-rung ladder: no switch, just the one branch
    out = tiered_step(jnp.int32(5), (32,), lambda c: (lambda s: s + c),
                      jnp.int32(0))
    assert int(out) == 32


def test_frontier_workload_counts_live_degrees(rmat_graph):
    fr = F.from_ids([0, 1, 2], 8)
    deg = np.diff(np.asarray(rmat_graph.row_offsets))
    want = int(deg[0] + deg[1] + deg[2])
    assert int(ops.frontier_workload(rmat_graph, fr)) == want
    # dead lanes contribute nothing
    assert int(ops.frontier_workload(rmat_graph, F.empty(8))) == 0


# ---------------------------------------------------------------------------
# fused advance_filter vs the unfused composition
# ---------------------------------------------------------------------------


def _compose_reference(g, fr, visited, cap_out, cap_front):
    """The definitional oracle: unfused advance, visited predicate,
    first-occurrence culling, compaction — in plain numpy."""
    res, _ = ops.advance(g, fr, cap_out, backend="xla")
    dst = np.asarray(res.dst)
    src = np.asarray(res.src)
    valid = np.asarray(res.valid)
    vis = np.asarray(visited).astype(bool)
    seen = set()
    ids, srcs = [], []
    total = 0
    for i in range(cap_out):
        if not valid[i] or vis[dst[i]] or dst[i] in seen:
            continue
        seen.add(dst[i])
        total += 1
        if len(ids) < cap_front:
            ids.append(dst[i])
            srcs.append(src[i])
    pad = cap_front - len(ids)
    return (np.array(ids + [-1] * pad, np.int32),
            np.array(srcs + [-1] * pad, np.int32), len(ids), total)


@pytest.mark.parametrize("backend", BACKENDS)
def test_advance_filter_matches_composition(rmat_graph, backend):
    g = rmat_graph
    n = g.num_vertices
    rng = np.random.default_rng(3)
    fr = F.from_ids(rng.integers(0, n, 12), 32)
    visited = jnp.asarray(rng.random(n) < 0.3)
    out, srcs, total = ops.advance_filter(g, fr, visited, 2048, 64,
                                          backend=backend)
    w_ids, w_srcs, w_len, w_total = _compose_reference(
        g, fr, visited, 2048, 64)
    assert np.array_equal(np.asarray(out.ids), w_ids)
    assert np.array_equal(np.asarray(srcs), w_srcs)
    assert int(out.length) == w_len
    assert int(total) == w_total


def test_advance_filter_backend_parity_matrix(rmat_graph, grid_graph):
    """xla and pallas providers agree bit for bit across graphs,
    visited densities and cap_front overflow."""
    rng = np.random.default_rng(11)
    for g in (rmat_graph, grid_graph):
        n = g.num_vertices
        for density, cap_front in [(0.0, 256), (0.5, 256), (0.9, 8)]:
            fr = F.from_ids(rng.integers(0, n, 24), 32)
            visited = jnp.asarray(rng.random(n) < density)
            ox, sx, tx = ops.advance_filter(g, fr, visited, 4096,
                                            cap_front, backend="xla")
            op_, sp, tp = ops.advance_filter(g, fr, visited, 4096,
                                             cap_front, backend="pallas")
            key = (density, cap_front)
            assert np.array_equal(np.asarray(ox.ids),
                                  np.asarray(op_.ids)), key
            assert np.array_equal(np.asarray(sx), np.asarray(sp)), key
            assert int(ox.length) == int(op_.length), key
            assert int(tx) == int(tp), key


@pytest.mark.parametrize("backend", BACKENDS)
def test_advance_filter_empty_frontier(rmat_graph, backend):
    out, srcs, total = ops.advance_filter(
        rmat_graph, F.empty(16),
        jnp.zeros(rmat_graph.num_vertices, bool), 512, 32,
        backend=backend)
    assert int(out.length) == 0 and int(total) == 0
    assert np.all(np.asarray(out.ids) == -1)
    assert np.all(np.asarray(srcs) == -1)


@pytest.mark.parametrize("backend", BACKENDS)
def test_advance_filter_batch_matches_single(rmat_graph, backend):
    g = rmat_graph
    n = g.num_vertices
    rng = np.random.default_rng(7)
    lanes = [rng.integers(0, n, 6) for _ in range(3)]
    bf = F.BatchedSparseFrontier(
        ids=jnp.stack([F.from_ids(l, 16).ids for l in lanes]),
        lengths=jnp.asarray([len(l) for l in lanes], jnp.int32))
    visited = jnp.asarray(rng.random((3, n)) < 0.4)
    bout, bsrcs, btot = ops.advance_filter_batch(g, bf, visited, 1024,
                                                 128, backend=backend)
    for i, l in enumerate(lanes):
        out, srcs, tot = ops.advance_filter(
            g, F.from_ids(l, 16), visited[i], 1024, 128, backend=backend)
        assert np.array_equal(np.asarray(bout.ids[i]),
                              np.asarray(out.ids)), i
        assert np.array_equal(np.asarray(bsrcs[i]), np.asarray(srcs)), i
        assert int(btot[i]) == int(tot), i


# ---------------------------------------------------------------------------
# tiered primitives bit-match the pinned top tier across tier boundaries
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_bfs_tiered_bitmatch_across_boundaries(rmat_graph,
                                               high_degree_src, backend):
    """The hub source's first expansion exceeds 512 while later
    iterations collapse under it, so one traversal crosses rungs in
    both directions; corner sources stay sub-tier throughout."""
    g = rmat_graph
    assert B.tier_plan("advance_filter", g.num_edges)[0] < g.num_edges
    srcs = [high_degree_src, 0, g.num_vertices - 1]
    rt = bfs_batch(g, srcs, backend=backend, tiered=True)
    ru = bfs_batch(g, srcs, backend=backend, tiered=False)
    for f in rt._fields:
        assert np.array_equal(np.asarray(getattr(rt, f)),
                              np.asarray(getattr(ru, f))), (f, backend)
    for i, s in enumerate(srcs):
        assert np.array_equal(np.asarray(rt.labels[i]),
                              R.bfs_ref(g, s)), i


@pytest.mark.parametrize("backend", BACKENDS)
def test_sssp_tiered_bitmatch(rmat_graph, high_degree_src, backend):
    g = rmat_graph
    srcs = [high_degree_src, 0]
    rt = sssp_batch(g, srcs, backend=backend, tiered=True)
    ru = sssp_batch(g, srcs, backend=backend, tiered=False)
    for f in rt._fields:
        assert np.array_equal(np.asarray(getattr(rt, f)),
                              np.asarray(getattr(ru, f))), (f, backend)
    assert np.allclose(np.asarray(rt.dist[0]),
                       R.sssp_ref(g, high_degree_src), rtol=1e-5)


# ---------------------------------------------------------------------------
# the mixed direction step: its push half runs at the push lanes' rung
# ---------------------------------------------------------------------------


def _hub_and_leaves(g):
    """The hub pulls from its second step on while degree-1 sources
    still push, so the batch takes at least one mixed step."""
    deg = np.diff(np.asarray(g.row_offsets))
    leaves = [int(v) for v in np.argsort(deg, kind="stable") if deg[v] > 0]
    return [int(np.argmax(deg))] + leaves[:2]


def _push_lane_needs(g, res, trace):
    """Per step: the largest degree sum over the lanes that pushed (the
    vertices at depth t are the frontier step t expands), the largest
    over every lane, and whether any active lane pushed."""
    deg = np.diff(np.asarray(g.row_offsets))
    labels = np.asarray(res.labels)
    iters = np.asarray(res.iterations)
    rows = []
    for t in range(trace.steps):
        work = np.array([deg[lab == t].sum() for lab in labels])
        push = (iters > t) & (trace["direction"][t] == 0)
        rows.append((int(work[push].max(initial=0)), int(work.max()),
                     bool(push.any())))
    return rows


def _rung(caps, need):
    return caps[int(F.tier_index(jnp.int32(need), caps))]


@pytest.mark.parametrize("backend", BACKENDS)
def test_bfs_mixed_step_bitmatch(rmat_graph, backend):
    """A batch whose lanes disagree on direction gives the same labels,
    preds and iterations as the pinned top tier and as each source run
    on its own."""
    g = rmat_graph
    srcs = _hub_and_leaves(g)
    rt, buf = bfs_batch(g, srcs, backend=backend, telemetry=True)
    trace = T.trim(buf, np.asarray(rt.iterations))
    assert trace["mixed"].any()
    ru = bfs_batch(g, srcs, backend=backend, tiered=False)
    for f in rt._fields:
        assert np.array_equal(np.asarray(getattr(rt, f)),
                              np.asarray(getattr(ru, f))), (f, backend)
    for i, s in enumerate(srcs):
        one = bfs(g, s, backend=backend)
        for f in ("labels", "preds", "iterations"):
            assert np.array_equal(np.asarray(getattr(rt, f)[i]),
                                  np.asarray(getattr(one, f))), (f, i)


def test_bfs_mixed_step_tier_follows_push_lanes(rmat_graph):
    """The telemetry rung of a mixed step is the push lanes' own rung,
    strictly below the rung every lane's frontier would pick."""
    g = rmat_graph
    caps = B.tier_plan("advance_filter", g.num_edges)
    r, buf = bfs_batch(g, _hub_and_leaves(g), telemetry=True)
    trace = T.trim(buf, np.asarray(r.iterations))
    mixed = np.flatnonzero(trace["mixed"])
    assert len(mixed) > 0
    rows = _push_lane_needs(g, r, trace)
    below = 0
    for t in mixed:
        push_need, all_need, _ = rows[t]
        assert trace["tier"][t] <= _rung(caps, push_need), t
        below += int(trace["tier"][t] < _rung(caps, all_need))
    assert below > 0


def test_bfs_mixed_step_dispatches_push_lanes_workload(rmat_graph,
                                                       monkeypatch):
    """The workload the push dispatch switches on, read from inside the
    compiled loop, is the push lanes' largest degree sum in every step
    that pushes: the pull lanes' frontiers never size the push."""
    bfs_mod = importlib.import_module("repro.core.primitives.bfs")
    seen = []

    def spy(need, caps, step_of, state, **kw):
        jax.debug.callback(lambda x: seen.append(int(x)), need,
                           ordered=True)
        return tiered_step(need, caps, step_of, state, **kw)

    g = rmat_graph
    monkeypatch.setattr(bfs_mod, "tiered_step", spy)
    bfs_mod._bfs_impl.clear_cache()
    try:
        r, buf = bfs_batch(g, _hub_and_leaves(g), telemetry=True)
        jax.effects_barrier()
    finally:
        bfs_mod._bfs_impl.clear_cache()
    trace = T.trim(buf, np.asarray(r.iterations))
    assert trace["mixed"].any()
    want = [need for need, _, pushed in _push_lane_needs(g, r, trace)
            if pushed]
    assert seen == want


# ---------------------------------------------------------------------------
# degenerate graphs through the tiered dispatch (PR 6 satellite):
# shapes where the tier ladder collapses (0/1 rungs), rows expand to
# nothing, or one row exceeds every non-top rung by itself
# ---------------------------------------------------------------------------


def _tiered_equals_pinned(g, srcs, backend):
    rt = bfs_batch(g, srcs, backend=backend, tiered=True)
    ru = bfs_batch(g, srcs, backend=backend, tiered=False)
    for f in rt._fields:
        assert np.array_equal(np.asarray(getattr(rt, f)),
                              np.asarray(getattr(ru, f))), (f, backend)
    for i, s in enumerate(srcs):
        assert np.array_equal(np.asarray(rt.labels[i]), R.bfs_ref(g, s)), i
    return rt


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("encoding", ["dense", "delta"])
def test_tiered_edgeless_graph(backend, encoding):
    """Zero edges: the expansion cap is 0, so the fused tiered path is
    skipped entirely — every source terminates at depth 0."""
    e = np.zeros(0, np.int64)
    g = G.from_edge_list(e, e, n=8, encoding=encoding)
    assert g.num_edges == 0
    rt = _tiered_equals_pinned(g, [0, 7], backend)
    want = np.full(8, -1, np.int32)
    want[0] = 0
    assert np.array_equal(np.asarray(rt.labels[0]), want)


@pytest.mark.parametrize("backend", BACKENDS)
def test_tiered_single_vertex(backend):
    e = np.zeros(0, np.int64)
    g = G.from_edge_list(e, e, n=1)
    rt = _tiered_equals_pinned(g, [0], backend)
    assert np.asarray(rt.labels[0]).tolist() == [0]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("encoding", ["dense", "delta"])
def test_tiered_all_self_loops(backend, encoding):
    """Every row is exactly one self-loop: frontiers expand into already-
    visited vertices only, so the traversal must settle after one step
    (a filter that never compacts anything new)."""
    ids = np.arange(16, dtype=np.int64)
    g = G.from_edge_list(ids, ids, n=16, remove_self_loops=False,
                         encoding=encoding)
    assert g.num_edges == 16
    rt = _tiered_equals_pinned(g, [3], backend)
    want = np.full(16, -1, np.int32)
    want[3] = 0
    assert np.array_equal(np.asarray(rt.labels[0]), want)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("encoding", ["dense", "delta"])
def test_tiered_star_degree_exceeds_lower_rungs(backend, encoding):
    """A hub whose single-row expansion (1500 edges) exceeds every
    power-of-two rung below the top: the first step must select the top
    (exact-cap) rung while the return wave (1500 leaves × degree 1) fits
    a bottom rung — both directions of the ladder in one traversal."""
    hub = np.zeros(1500, np.int64)
    leaves = np.arange(1, 1501, dtype=np.int64)
    w = np.random.default_rng(0).integers(1, 64, 1500).astype(np.float32)
    g = G.from_edge_list(hub, leaves, n=1501, undirected=True, values=w,
                         encoding=encoding)
    caps = B.tier_plan("advance_filter", g.num_edges)
    assert caps[0] < 1500 <= caps[-1]
    rt = _tiered_equals_pinned(g, [0, 1500], backend)
    assert int(np.asarray(rt.labels[0]).max()) == 1
    sr = sssp_batch(g, [0], backend=backend, tiered=True)
    su = sssp_batch(g, [0], backend=backend, tiered=False)
    assert np.array_equal(np.asarray(sr.dist), np.asarray(su.dist))


def test_bfs_tiered_overflow_lane_stays_frozen(rmat_graph):
    """A lane that converges early (empty frontier ⇒ workload 0) keeps
    selecting the bottom rung while the straggler drives the switch —
    frozen lanes must stay bit-stable regardless of the rung chosen."""
    g = rmat_graph
    deg = np.diff(np.asarray(g.row_offsets))
    leaf = int(np.argmin(deg))
    rt = bfs_batch(g, [leaf, int(np.argmax(deg))], tiered=True)
    ru = bfs_batch(g, [leaf, int(np.argmax(deg))], tiered=False)
    assert np.array_equal(np.asarray(rt.labels), np.asarray(ru.labels))
    assert np.array_equal(np.asarray(rt.iterations),
                          np.asarray(ru.iterations))


# ---------------------------------------------------------------------------
# tuner + runtime
# ---------------------------------------------------------------------------


def test_default_tile_clamps_to_padded_output():
    """The satellite fix: a small capacity must never inflate the tile
    past pow2_ceil(cap) (the old heuristic pinned 512 minimum)."""
    assert tuner.default_tile(40) == 64
    assert tuner.default_tile(1) == 1
    assert tuner.default_tile(512) == 512
    # the grid bound still grows tiles for big caps…
    assert tuner.default_tile(512 * 1024) > 512
    # …but never past the padded output size, even under a tiny grid
    # budget that would have doubled forever pre-fix
    assert tuner.default_tile(700, min_tile=512, max_grid=1) == 1024
    assert tuner.default_tile(40, max_grid=1) == 64


def test_tile_for_prefers_cache_entry(tmp_path, monkeypatch):
    path = tmp_path / "cache.json"
    key = f"advance|{tuner.tier_of(4096)}|{runtime.platform()}|dense"
    path.write_text(json.dumps(
        {"version": 2, "entries": {key: {"tile": 2048}}}))
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    monkeypatch.delenv("REPRO_TUNE", raising=False)
    assert tuner.tile_for("advance", 4096) == 2048
    # REPRO_TUNE=0 ignores the cache (pure heuristic)
    monkeypatch.setenv("REPRO_TUNE", "0")
    assert tuner.tile_for("advance", 4096) == tuner.default_tile(4096)
    # stale schema versions are ignored wholesale — v1 entries lacked
    # the encoding axis, so the v2 bump invalidates them
    monkeypatch.delenv("REPRO_TUNE", raising=False)
    path.write_text(json.dumps(
        {"version": 1, "entries": {key.rsplit("|", 1)[0]: {"tile": 2048}}}))
    assert tuner.tile_for("advance", 4096) == tuner.default_tile(4096)


def test_tile_for_encoding_axis(tmp_path, monkeypatch):
    """The v2 cache keys on storage encoding: a delta launch prefers its
    own measurement, falls back to the dense entry at the same tier, and
    a dense launch never reads the delta entry."""
    path = tmp_path / "cache.json"
    tier = tuner.tier_of(4096)
    plat = runtime.platform()
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    monkeypatch.delenv("REPRO_TUNE", raising=False)
    # dense-only cache: delta launches borrow the dense measurement
    path.write_text(json.dumps({"version": 2, "entries": {
        f"advance|{tier}|{plat}|dense": {"tile": 2048}}}))
    assert tuner.tile_for("advance", 4096, encoding="delta") == 2048
    # both present: each encoding reads its own entry
    path.write_text(json.dumps({"version": 2, "entries": {
        f"advance|{tier}|{plat}|dense": {"tile": 2048},
        f"advance|{tier}|{plat}|delta": {"tile": 1024}}}))
    assert tuner.tile_for("advance", 4096, encoding="delta") == 1024
    assert tuner.tile_for("advance", 4096, encoding="dense") == 2048
    # delta-only cache: a dense launch does NOT borrow backwards
    path.write_text(json.dumps({"version": 2, "entries": {
        f"advance|{tier}|{plat}|delta": {"tile": 1024}}}))
    assert tuner.tile_for("advance", 4096) == tuner.default_tile(4096)


def test_autotune_persists_measured_tile(tmp_path, monkeypatch):
    path = tmp_path / "cache.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    monkeypatch.setenv("REPRO_TUNE", "1")
    calls = []

    def probe(cap, tile):
        calls.append(tile)
        return 0.001 if tile == 256 else 0.01

    tile = tuner.autotune("fake_op", 1024, probe, repeats=1, force=True)
    assert tile == 256
    data = json.loads(path.read_text())
    assert data["version"] == 2
    entry = data["entries"][
        f"fake_op|{tuner.tier_of(1024)}|{runtime.platform()}|dense"]
    assert entry["tile"] == 256
    # a second call hits the cache, not the probe
    calls.clear()
    assert tuner.tile_for("fake_op", 1024) == 256
    assert calls == []


def test_probes_registered_for_hot_ops():
    import repro.kernels.ops  # noqa: F401  registers on import
    for op in ("advance", "advance_filter", "compact", "lb_expand",
               "spmv"):
        assert op in tuner.PROBES, op


def test_interpret_mode_resolution(monkeypatch):
    assert runtime.interpret_mode(True) is True
    assert runtime.interpret_mode(False) is False
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
    assert runtime.interpret_mode(None) is False
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    assert runtime.interpret_mode(None) is True
    monkeypatch.delenv("REPRO_FORCE_INTERPRET")
    import jax
    assert runtime.interpret_mode(None) == (jax.default_backend()
                                            != "tpu")
    # the tuner's platform key distinguishes interpret mode
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    assert runtime.platform().endswith("+interpret")


def test_registry_has_advance_filter_both_backends():
    for op in ("advance_filter", "advance_filter_batch"):
        assert B.registered(op, B.XLA), op
        assert B.registered(op, B.PALLAS), op
