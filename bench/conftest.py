"""Fixtures of the benchmark's own tests: each cell of BENCHMARK.json
cut to a size that runs on the CPU in seconds."""
import dataclasses
import os

import pytest

TINY = {"kronecker": {"scale": 9}, "rgg": {"n": 4096}}


@pytest.fixture
def tiny_spec(monkeypatch):
    """``tiny_spec(cell, **traffic)``: the cell's spec with its graph cut
    to TINY and its traffic parameters overridden."""
    from bench import harness

    monkeypatch.setenv("REPRO_BACKEND", "xla")

    def make(cell: str, root: str = harness.ROOT, **traffic):
        from bench import graphdata
        spec = harness.load_spec(cell, root=root,
                                 bench_dir=os.path.join(root, "bench"))
        cfg = dict(spec.config, **TINY.get(spec.config["generator"], {}))
        if "search_keys" in cfg:
            cfg["search_keys"] = graphdata.search_keys(
                cfg, len(cfg["search_keys"]), spec.bench_dir).tolist()
        return dataclasses.replace(spec, config=cfg,
                                   traffic=dict(spec.traffic, **traffic))
    return make


def run_tiny(spec, seed=2**31 + 11, seconds=0.5, **kw):
    from bench import harness
    return harness.run_cell(spec, seed, seconds, require_tpu=False, **kw)
