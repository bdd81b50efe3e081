"""Scope names on the compiled programs (DESIGN.md §10).

Every device op of ``bfs_batch``, ``sssp_batch``, ``reach_batch`` and
``pagerank`` carries an ``op_name`` under ``enactor.``, ``op.`` or
``primitive.``, read as the benchmark's trace reducer reads it
(``bench/scopes.py``): an instruction the compiler made without a name
(a copy, a fusion with a made-up root) counts under its nearest named
producer or user. Counted: the instructions of the computations that run
(the entry, loop bodies and conditions, branches; not fusion bodies or
reducers) that write an array — parameters, constants, tuples and
scalars are left out.
"""
import functools
import re
import sys

import jax.numpy as jnp
import pytest

from bench.scopes import NONE, TIER, hlo_op_names, scope_of
from repro.core import backend as B
from repro.core import graph as G
from repro.core.primitives import bfs_batch  # noqa: F401  (loads the modules)

NO_WORK = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}
_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\S+)\s.*?"
                    r"([\w\-]+)\(")


@functools.lru_cache(maxsize=None)
def _graph():
    return G.rmat(9, 8, seed=0, weighted=True)


@functools.lru_cache(maxsize=None)
def _hlo(program: str) -> str:
    mod = {p: sys.modules[f"repro.core.primitives.{p}"]
           for p in ("bfs", "sssp", "reach", "pagerank")}
    g = _graph()
    srcs = jnp.arange(8, dtype=jnp.int32)
    ew = int(g.csc_ell_width)
    lowered = {
        "bfs_batch": lambda: mod["bfs"]._bfs_impl.lower(
            g, srcs, 0.001, 0.2, True, True, "LB", True, B.XLA),
        "sssp_batch": lambda: mod["sssp"]._sssp_impl.lower(
            g, srcs, jnp.float32(1.0), True, "LB", B.XLA),
        "reach_batch": lambda: mod["reach"]._reach_impl.lower(
            g, srcs, 3, B.XLA, ew),
        "pagerank": lambda: mod["pagerank"]._pagerank_impl.lower(
            g, mod["pagerank"]._inv_out_degrees(g), jnp.float32(0.85),
            jnp.float32(0.0), 20, B.XLA, ew),
    }[program]()
    return lowered.compile().as_text()


def _device_instructions(text: str) -> list:
    inner = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    comp, out = None, []
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if (m and comp not in inner and m.group(3) not in NO_WORK
                and not m.group(2).endswith("[]")):
            out.append(m.group(1))
    return out


@pytest.mark.parametrize("program", ["bfs_batch", "sssp_batch",
                                     "reach_batch", "pagerank"])
def test_device_ops_carry_a_scope(program):
    text = _hlo(program)
    resolve = hlo_op_names(text)
    names = _device_instructions(text)
    leaves = [scope_of(resolve(n))[0] for n in names]
    unscoped = [n for n, leaf in zip(names, leaves) if leaf == NONE]
    assert len(names) > 10
    assert len(unscoped) <= 0.05 * len(names), unscoped
    roots = {leaf.split(".")[0] for leaf in leaves if leaf != NONE}
    assert {"op", "primitive"} <= roots


def test_bfs_rungs_and_mixed_step_are_tagged():
    text = _hlo("bfs_batch")
    caps = B.tier_plan("advance_filter", _graph().num_edges)
    assert len(caps) > 1
    tags = set(re.findall(r"tier_\d+", text))
    assert tags == {f"tier_{c}" for c in caps}
    assert all(TIER.match(t) for t in tags)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(scope_of(n)[2] for n in names)           # the mixed step
    leaves = {scope_of(n)[0] for n in names}
    assert {"enactor.loop", "enactor.select_lanes", "enactor.tier",
            "enactor.direction", "op.advance_filter", "op.pull",
            "op.apply", "primitive.init", "primitive.result"} <= leaves


def _bfs_2d_hlo() -> str:
    """The batched 2-D BFS program on a 2×2 mesh of four fake host
    devices, compiled in a subprocess (the test process keeps one device)."""
    import os
    import subprocess
    import textwrap
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core import distributed as D, graph as G
        from repro.core.partition import partition_2d
        pg = partition_2d(G.rmat(9, 8, seed=0), 2, 2)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("row", "col"))
        sg = pg.shard(mesh)
        print(D._bfs_2d_impl.lower(
            sg.row_offsets, sg.col_indices, sg.row_base, sg.col_base,
            jnp.arange(8, dtype=jnp.int32), n=pg.n, vpr=pg.vpr,
            col_sizes=pg.col_sizes, mesh=mesh, axes=("row", "col"), tiles=2,
            backend="xla").compile().as_text())
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout


def test_2d_bfs_ops_carry_a_scope_and_collectives_are_the_exchange():
    text = _bfs_2d_hlo()
    resolve = hlo_op_names(text)
    names = _device_instructions(text)
    leaves = [scope_of(resolve(n))[0] for n in names]
    unscoped = [n for n, leaf in zip(names, leaves) if leaf == NONE]
    assert len(names) > 10
    assert len(unscoped) <= 0.05 * len(names), unscoped
    assert {"op.advance_filter", "op.exchange", "op.apply",
            "enactor.loop", "primitive.init"} <= set(leaves)
    collectives = [n for n in names
                   if re.match(r"(all-reduce|all-gather)", n)]
    assert collectives
    assert {scope_of(resolve(n))[0] for n in collectives} == \
        {"op.exchange"}
