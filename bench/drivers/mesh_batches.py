"""Closed loop of batched traversals over a 2-D vertex cut of the run's
chips: the graph is partitioned once on an R×C mesh (traffic parameter
``mesh``), and each batch of ``batch`` sources runs as one distributed
program through the batched entry point that ``graph_serve``'s mesh
runner calls (``repro.core.distributed.distributed_bfs``).

The dealing of the search keys into batches, the window's units, the
sampled answers and their comparison with the reference are those of
``bench/drivers/batches.py``; only the program that answers a batch
differs. Traffic parameters: ``primitive`` (``bfs``), ``batch``,
``mesh`` ``[R, C]`` and ``check_batches``.
"""
from __future__ import annotations

import dataclasses
import inspect

from bench.drivers.batches import (State, check, collect, cycle,  # noqa: F401
                                   prepare as _deal, step, warm)
from repro.core.distributed import distributed_bfs

AXES = ("row", "col")

# a program whose 2-D BFS serves one source per call cannot run this mix:
# refuse before the graph is made, not after
if "srcs" not in inspect.signature(distributed_bfs).parameters:
    raise RuntimeError("this program's distributed_bfs runs one source "
                       "per program; the mix needs its batched 2-D BFS")


def prepare(run, g, traffic: dict) -> State:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.partition import partition_2d

    if traffic["primitive"] != "bfs":
        raise ValueError(f"no batched 2-D program for "
                         f"{traffic['primitive']!r}")
    rows, cols = (int(x) for x in traffic["mesh"])
    devs = jax.devices()
    if len(devs) < rows * cols:
        raise ValueError(f"a {rows}x{cols} mesh needs {rows * cols} "
                         f"devices; JAX found {len(devs)}")
    mesh = Mesh(np.array(devs[:rows * cols]).reshape(rows, cols), AXES)
    st = _deal(run, g, traffic)
    st.g = partition_2d(g, rows, cols)
    st.kind = dataclasses.replace(
        st.kind, run=lambda pg, srcs, p: distributed_bfs(pg, srcs, mesh,
                                                         AXES))
    return st
