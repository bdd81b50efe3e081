"""The serving mix driven end to end at a tiny size on the CPU (the
look for a chip skipped): every compared answer matches the reference,
an answer altered where the engine's runner produces it is caught, and
so is the control (each kind's control in ``bench/kinds.py``: the
reference a level or a hop short, and in bfloat16 for distances)."""
import numpy as np
import pytest

from bench.conftest import run_tiny
from bench.drivers import serve


def test_chunk_counts_add_up_by_largest_remainder():
    from bench import harness
    shares = serve.ldbc_shares(harness.load_spec("g500-serve").traffic[
        "ldbc"])
    # IC1 reach, IC13 bfs, IC14 sssp: one read per 26, 19, 49 updates
    total = 1 / 26 + 1 / 19 + 1 / 49
    assert shares["reach"] == pytest.approx(1 / 26 / total)
    assert shares["bfs"] == pytest.approx(1 / 19 / total)
    counts = serve.chunk_counts(shares, 64)
    assert counts == {"reach": 22, "bfs": 30, "sssp": 12}
    assert sum(serve.chunk_counts({"a": 1 / 3, "b": 1 / 3, "c": 1 / 3},
                                  64).values()) == 64


def test_serving_is_correct_and_times_every_query(tiny_spec):
    result, run = run_tiny(tiny_spec("g500-serve", check_per_kind=1000))
    assert result["correct"], result["checks"]
    assert len(run.queries) == 64 * len(run.items) == result["attempted"]
    assert len(run.items) % 2 == 0                # whole cycles of chunks
    assert all(q["ok"] and q["lat_ms"] > 0 for q in run.queries)
    chunk0 = run.items[0]
    assert max(q["lat_ms"] for q in run.queries[:64]) <= (
        chunk0["t1"] - chunk0["t0"]) * 1e3
    assert set(result["metrics"]) == {"setup_s", "queries_per_s",
                                      "query_p95_ms"}


def test_serving_catches_an_altered_answer(tiny_spec, monkeypatch):
    from repro.launch import graph_serve
    orig = graph_serve._run_kind

    def altered(g, kind, srcs, backend, hops, budget=None):
        field, ovf, conv = orig(g, kind, srcs, backend, hops, budget)
        field = np.array(field)
        lanes = np.arange(len(srcs))
        if field.dtype == bool:
            field[lanes, srcs] = ~field[lanes, srcs]
        else:
            field[lanes, srcs] += 1
        return field, ovf, conv

    monkeypatch.setattr(graph_serve, "_run_kind", altered)
    result, _ = run_tiny(tiny_spec("g500-serve"))
    assert not result["correct"]
    assert result["checks"]["answer_mismatches"]["value"] > 0


def test_serving_control_fails(tiny_spec):
    spec = tiny_spec("g500-serve", check_per_kind=1000)
    result, run = run_tiny(spec, control=True)
    assert not result["correct"]
    assert result["checks"]["answer_mismatches"]["value"] > 0
    # the same run's own answers, read first, pass
    program = {c.name: c for c in run.program_checks}
    assert all(c.ok for c in program.values())
    # the bf16 reference's distances lie far past the program's float32
    gap = result["checks"]["sssp_rel_err"]
    assert gap["value"] > gap["limit"]
    assert gap["value"] > 30 * program["sssp_rel_err"].value


def test_serving_catches_a_query_that_never_comes(tiny_spec, monkeypatch):
    from repro.launch import graph_serve
    orig = graph_serve._run_kind

    def failing(g, kind, srcs, backend, hops, budget=None):
        if kind == "sssp":
            raise RuntimeError("sssp flush lost")
        return orig(g, kind, srcs, backend, hops, budget)

    monkeypatch.setattr(graph_serve, "_run_kind", failing)
    result, run = run_tiny(tiny_spec("g500-serve"))
    assert not result["correct"]
    assert result["checks"]["queries_not_ok"]["value"] == 12 * len(
        run.items)
    assert result["metrics"]["query_p95_ms"]["value"] == float("inf")
