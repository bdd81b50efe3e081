"""The batch and PageRank mixes driven end to end at a tiny size on the
CPU (the look for a chip skipped): correct against the reference, and
not correct with an answer altered where the program produces it or
with the control in the program's place."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench import kinds
from bench.conftest import run_tiny


def test_batches_cycle_over_the_dealt_search_keys(tiny_spec):
    from bench.drivers import batches
    spec = tiny_spec("g500-bfs")
    runs = []
    for seed in (5, 6):
        run = type("R", (), {})()
        run.seed, run.spec = seed, spec
        from bench import graphdata
        run.graph = graphdata.make(spec.config, seed, spec.bench_dir)
        st = batches.prepare(run, None, spec.traffic)
        keys = run.graph.relabel(spec.config["search_keys"])
        cycle = st.stream[:len(keys)].reshape(-1, 8)
        assert sorted(cycle.ravel()) == sorted(keys)
        # each batch holds one key of each depth band
        for b in cycle:
            pos = sorted(list(keys).index(k) for k in b)
            assert [p // 8 for p in pos] == list(range(8))
        runs.append(st.stream[:64])
    assert not np.array_equal(runs[0], runs[1])


@pytest.mark.parametrize("cell", ["g500-bfs", "rgg-bfs"])
def test_bfs_batches_are_correct(tiny_spec, cell):
    result, run = run_tiny(tiny_spec(cell))
    assert result["correct"], result["checks"]
    assert result["checks"]["bfs_mismatches"]["value"] == 0
    assert result["attempted"] == 8 * len(run.items)
    # the window ran whole passes over the search keys
    assert len(run.items) % (len(run.spec.config["search_keys"]) // 8) == 0
    assert set(result["metrics"]) == {"setup_s", "teps"}
    assert list(result)[-1] == "checks"


FAULTS = {
    # one depth wrong where the program produces it
    "altered_depth": lambda r, srcs: r.labels.at[0, srcs[0]].add(1),
    # half of the batch left out: its lanes come back unreached
    "half_batch_left_out": lambda r, srcs: r.labels.at[4:].set(-1),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_bfs_batches_catch_a_fault(tiny_spec, monkeypatch, fault):
    orig = kinds.KINDS["bfs"]

    def altered(g, srcs, p):
        r = orig.run(g, srcs, p)
        return r._replace(labels=FAULTS[fault](r, srcs))

    monkeypatch.setitem(kinds.KINDS, "bfs",
                        dataclasses.replace(orig, run=altered))
    result, _ = run_tiny(tiny_spec("g500-bfs"))
    assert not result["correct"]
    assert result["checks"]["bfs_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", ["g500-bfs", "rgg-bfs"])
def test_bfs_control_fails(tiny_spec, cell):
    result, _ = run_tiny(tiny_spec(cell), control=True)
    assert not result["correct"]
    assert result["checks"]["bfs_mismatches"]["value"] >= 1


def test_pagerank_runs_are_correct(tiny_spec):
    result, run = run_tiny(tiny_spec("g500-pagerank"))
    assert result["correct"], result["checks"]
    assert result["attempted"] == len(run.items) >= 1
    assert set(result["metrics"]) == {"setup_s", "analytic_s"}


def test_pagerank_catches_an_altered_rank(tiny_spec, monkeypatch):
    import repro.core.primitives as P
    orig = P.pagerank

    def altered(g, **kw):
        r = orig(g, **kw)
        return r._replace(rank=r.rank.at[0].add(10.0 / g.num_vertices))

    monkeypatch.setattr(P, "pagerank", altered)
    result, _ = run_tiny(tiny_spec("g500-pagerank"))
    assert not result["correct"]


def test_pagerank_control_fails(tiny_spec):
    spec = tiny_spec("g500-pagerank")
    result, _ = run_tiny(spec, control=True)
    err = result["checks"]["rank_err"]
    assert not result["correct"], err
    assert err["value"] > 3 * run_tiny(spec)[0]["checks"]["rank_err"][
        "value"]


def test_traced_run_reports_per_layer_metrics(tiny_spec):
    # on the CPU the trace holds no chip, so only host-clock metrics show
    result, run = run_tiny(tiny_spec("g500-bfs"), trace=True)
    assert result["correct"]
    assert set(result["metrics"]) == {"graph_build_s", "bsp_iter_ms"}
    assert run.compiles_in_window == 0
    assert jnp.isfinite(result["metrics"]["bsp_iter_ms"]["value"])


def test_distances_compare_by_relative_gap_and_the_bf16_control_fails(
        tiny_spec):
    from bench import graphdata, reference
    spec = tiny_spec("g500-serve")
    gd = graphdata.make(spec.config, 2 ** 31 + 5)
    csr = gd.csr()
    src = int(gd.sources()[3])
    sssp = kinds.KINDS["sssp"]
    ref = sssp.reference(csr, src, {})
    f32 = reference.sssp((csr[0], csr[1], csr[2]), src,
                         rounding=np.float32)
    assert kinds.rel_gap(f32, ref) < 1e-6        # float32 rounding
    ctl = sssp.control(csr, src, {})
    assert kinds.rel_gap(ctl, ref) > 1e-3        # bfloat16
    assert kinds.mismatches(ctl, ref) == 0       # reached alike
    bad = ref.copy()
    bad[src] = 1e-30                             # the source moved
    assert kinds.rel_gap(bad, ref) == float("inf")
    bad = ref.copy()
    bad[np.isfinite(ref).argmin()] = 1.0         # an unreached vertex
    assert kinds.mismatches(bad, ref) == int(not np.isfinite(ref).all())
