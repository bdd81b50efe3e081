"""Compile the graph engine's main path for a described TPU v5e.

Nothing here needs a chip: ``jax.experimental.topologies`` describes a
``v5e:2x2`` host and XLA's TPU compiler compiles for it, so a program
the chip would refuse (a kernel the TPU lowering cannot handle, a
program that does not fit device memory) fails here first.

  * the xla-backend programs that ``graph_run`` and ``graph_serve``
    run (``bfs_batch``, ``sssp_batch``, ``pagerank``, ``cc``,
    ``reach_batch``) compile on a scale-14 R-MAT;
  * every Pallas graph kernel the TPU lowering refuses is a strict
    xfail quoting ``backend.PALLAS_TPU_REFUSED``: the day one compiles,
    its test turns red and the table entry (and the ``auto``/``pallas``
    rule in ``core/backend.py``) must be revisited;
  * CPU unit tests for the backend-resolution rule on a TPU and for
    the compile-cache location.

The topology is described inside a module fixture, never at import:
only one process may load the TPU compiler library at a time, and
test workers import every test file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro import compile_cache
from repro.core import backend as B
from repro.core import graph as G
from repro.core.primitives import bfs_batch, reach_batch
from repro.core.primitives.cc import _cc_impl
from repro.core.primitives.pagerank import _pagerank_impl
from repro.core.primitives.sssp import _sssp_impl
from repro.linalg import semiring as SR

SCALE = 14
BATCH = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip can be written to the
    # persistent cache but never read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def shapes_of(tree, sharding):
    """The pytree's array leaves as ShapeDtypeStructs placed on
    ``sharding`` (static aux data such as ELL widths is kept)."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def main_path_programs(g, sharding, batch: int = BATCH) -> dict:
    """name -> thunk returning the ``Lowered`` program that graph_run /
    graph_serve dispatch for graph shapes ``g`` on the xla backend."""
    n, m = g.num_vertices, g.num_edges

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    srcs = sds((batch,), jnp.int32)
    return {
        "bfs_batch": lambda: jax.jit(
            lambda g, s: bfs_batch(g, s, backend="xla")).lower(g, srcs),
        # the wrapper reads delta's value on the host; the program only
        # sees it as an operand
        "sssp_batch": lambda: jax.jit(
            lambda g, s, d: _sssp_impl(g, s, d, True, "LB", "xla")
        ).lower(g, srcs, sds((), jnp.float32)),
        "pagerank": lambda: jax.jit(
            lambda g, inv: _pagerank_impl(
                g, inv, jnp.float32(0.85), jnp.float32(0.0), 20, "xla",
                g.csc_ell_width, "single", "fp32", False, full_iter=20)
        ).lower(g, sds((n,), jnp.float32)),
        "cc": lambda: jax.jit(lambda g, src: _cc_impl(g, src)).lower(
            g, sds((m,), jnp.int32)),
        "reach_batch": lambda: jax.jit(
            lambda g, s: reach_batch(g, s, 3, backend="xla").reached
        ).lower(g, srcs),
    }


@pytest.fixture(scope="module")
def graph_shapes(one_chip):
    return shapes_of(G.rmat(SCALE, 8, seed=0, weighted=True), one_chip)


@pytest.mark.parametrize("program", ["bfs_batch", "sssp_batch", "pagerank",
                                     "cc", "reach_batch"])
def test_xla_main_path_compiles_for_v5e(graph_shapes, one_chip, program):
    compiled = main_path_programs(graph_shapes, one_chip)[program]().compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes >= 0
    assert mem.argument_size_in_bytes > 0


def test_batched_2d_bfs_compiles_for_a_2x2_v5e(topo):
    """The batched 2-D BFS over a 2×2 vertex cut of the described host:
    one program, its row psum and column all-gather inside."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import distributed as D
    from repro.core.partition import partition_2d
    pg = partition_2d(G.rmat(SCALE, 8, seed=0), 2, 2)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("row", "col"))

    def sds(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    blk = P("row", "col")
    compiled = D._bfs_2d_impl.lower(
        sds(pg.row_offsets, blk), sds(pg.col_indices, blk),
        sds(pg.row_base, P("row")), sds(pg.col_base, P("col")),
        jax.ShapeDtypeStruct((BATCH,), jnp.int32,
                             sharding=NamedSharding(mesh, P())),
        n=pg.n, vpr=pg.vpr, col_sizes=pg.col_sizes, mesh=mesh,
        axes=("row", "col"),
        tiles=D.DEFAULT_EXCHANGE_TILES, backend=B.XLA).compile()
    text = compiled.as_text()
    assert re.search(r"\ball-reduce(-start)?\(", text)
    assert re.search(r"\ball-gather(-start)?\(", text)
    assert compiled.memory_analysis().argument_size_in_bytes > 0


# ---------------------------------------------------------------------------
# Pallas graph kernels: refused by the TPU lowering today
# ---------------------------------------------------------------------------

N, M, FRONTIER = 65536, 1 << 20, 16384


def _kernel_call(name: str, sharding):
    """Thunk compiling one Pallas graph kernel natively (interpret off)
    at the shapes of a 64K-vertex, 1M-edge graph and a 16K frontier."""
    from repro.kernels.advance_filter_fused import \
        advance_filter_fused_kernel
    from repro.kernels.advance_fused import advance_fused_kernel
    from repro.kernels.filter_compact import filter_compact_kernel
    from repro.kernels.lb_expand import lb_expand_kernel
    from repro.kernels.segment_search import segment_search_kernel
    from repro.kernels.semiring_spmv import semiring_ell_kernel

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    offsets, base = sds((FRONTIER + 1,)), sds((FRONTIER,))
    ro, ci = sds((N + 1,)), sds((M,))
    width = 8

    def spmv(k):
        return lambda: jax.jit(lambda a, v, x, mk: semiring_ell_kernel(
            a, v, x, mk, SR.plus_times, interpret=False)).lower(
                sds((N, width)), sds((N, width), jnp.float32),
                sds((N, k), jnp.float32), sds((N,)))

    calls = {
        "advance_fused": lambda: jax.jit(lambda o, b, r, c: (
            advance_fused_kernel(o, b, r, c, M, interpret=False))).lower(
                offsets, base, ro, ci),
        "advance_filter_fused": lambda: jax.jit(lambda o, b, r, c, v: (
            advance_filter_fused_kernel(o, b, r, c, v, M, N,
                                        interpret=False))).lower(
                offsets, base, ro, ci, sds((N,))),
        "lb_expand": lambda: jax.jit(lambda o: lb_expand_kernel(
            o, M, interpret=False)).lower(offsets),
        "segment_search": lambda: jax.jit(lambda h, lo, hi, nd: (
            segment_search_kernel(h, lo, hi, nd, interpret=False))).lower(
                ci, base, base, base),
        "semiring_spmv_k1": spmv(1),
        "semiring_spmv_k8": spmv(8),
        "filter_compact": lambda: jax.jit(lambda i, k: filter_compact_kernel(
            i, k, interpret=False)).lower(sds((FRONTIER,)),
                                          sds((FRONTIER,), jnp.bool_)),
    }
    return calls[name]


@pytest.mark.parametrize("kernel", [
    pytest.param(k, marks=pytest.mark.xfail(
        strict=True, reason=f"TPU lowering refuses {k}: "
                            f"{B.PALLAS_TPU_REFUSED[k.split('_k')[0]]}"))
    for k in ("advance_fused", "advance_filter_fused", "lb_expand",
              "segment_search", "semiring_spmv_k1", "semiring_spmv_k8",
              "filter_compact")])
def test_pallas_kernel_compiles_for_v5e(one_chip, kernel):
    _kernel_call(kernel, one_chip)().compile()


# ---------------------------------------------------------------------------
# CPU rules: backend resolution on a TPU, compile-cache location
# ---------------------------------------------------------------------------


def test_auto_resolves_to_xla_and_pallas_raises_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert B.resolve("auto") == B.XLA
    assert B.resolve("xla") == B.XLA
    with pytest.raises(B.PallasUnavailableError) as err:
        B.resolve("pallas")
    for kernel in B.PALLAS_TPU_REFUSED:
        assert kernel in str(err.value)
    monkeypatch.setenv(B.ENV_VAR, "pallas")
    with pytest.raises(B.PallasUnavailableError):
        B.resolve()


def test_pallas_resolves_off_tpu():
    assert jax.default_backend() == "cpu"
    assert B.resolve("pallas") == B.PALLAS
    assert B.resolve("auto") == B.XLA


@pytest.mark.parametrize("cli", ["graph_run", "graph_serve"])
def test_cli_exits_at_startup_for_pallas_on_tpu(monkeypatch, cli):
    import importlib
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mod = importlib.import_module(f"repro.launch.{cli}")
    monkeypatch.setattr(mod, "make_graph", lambda *a, **k: pytest.fail(
        "built a graph before refusing the backend"))
    with pytest.raises(SystemExit) as exit_:
        mod.main(["--scale", "4", "--backend", "pallas"])
    assert "advance_fused" in str(exit_.value.code)


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    set_to = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_to.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert set_to == []                # JAX reads the variable itself


def test_compile_cache_defaults_to_checkout(monkeypatch):
    set_to = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_to.append((k, v)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    assert compile_cache.enable() == want
    assert compile_cache.enable() == want        # stable across calls
    assert set_to == [("jax_compilation_cache_dir", want)] * 2
