"""Backend registry + selection context for the operator layer.

Gunrock reaches its performance by fusing functors into a small set of
optimized operator kernels at compile time (paper §5.3); GraphBLAST gets
the same effect by routing every primitive through one backend layer.
This module is that layer for the JAX reproduction: every operator hot
path (advance expansion+gather, filter compaction, intersection probe,
SpMV sweep) is registered here once per backend, and primitives select a
backend instead of hand-threading ``use_kernel`` booleans.

Backends:
  "xla"    — pure jnp formulations (gather/scatter/segment ops). The
             portable default; XLA fuses the functor into the sweep.
  "pallas" — hand-written Pallas TPU kernels from ``repro.kernels``
             (interpret mode off-TPU, which is the correctness contract).
             On a TPU the kernels must compile natively, and the TPU
             lowering refuses the graph kernels listed in
             ``PALLAS_TPU_REFUSED``: asking for "pallas" there raises
             ``PallasUnavailableError`` instead of running interpreted or
             dropping to "xla".
  "auto"   — "pallas" on a TPU once no graph kernel is refused there,
             "xla" otherwise (today: "xla" everywhere).

Placements (the second registry dimension, paper §8.2.1 scale-out):
  "single"  — one device holds the whole graph (the default).
  "sharded" — the graph is 1-D partitioned over a mesh axis
              (``core.partition``); registered sharded providers run the
              hot path under ``shard_map`` with mesh collectives for the
              frontier/vector exchange (``core.distributed``). A sharded
              provider's array contract differs from its single twin:
              CSR/CSC operands arrive as (num_parts, …) stacked
              per-device slices (``ShardedGraph``), dense vectors stay
              replicated.
  "2d"      — the graph is vertex-cut 2-D partitioned over an R×C mesh
              (``partition_2d``): edge blocks are sharded over BOTH mesh
              axes, frontier discovery psum-ORs along the row axis and
              outputs mirror-merge along the column axis. CSR/CSC
              operands arrive as (R, C, …) stacked blocks
              (``Sharded2DGraph``), dense vectors stay replicated.

There is NO silent fallback from a distributed placement ("sharded" or
"2d") to "single" — dropping to one device would silently change what
the caller asked for — but a pallas-backend distributed dispatch falls
back to the xla provider of the SAME placement (kernels inside
shard_map are future work).

Selection precedence (first hit wins), identical for both dimensions:
  1. per-call override          advance(..., backend="pallas")
                                spmv(..., placement="sharded")
  2. deprecated use_kernel=     True -> "pallas", False -> "xla"
                                (backend only)
  3. context manager            with backend.use_backend("pallas"): ...
                                with backend.use_placement("sharded",
                                    mesh=mesh, axis="graph"): ...
  4. environment variable       REPRO_BACKEND=pallas / REPRO_PLACEMENT=…
  5. the default                "xla" / "single"

Resolution happens at *trace* time: jitted primitives resolve in their
Python wrapper and pass the concrete name down as a static argument, so
a cached trace can never observe a stale context/env value. The
placement context additionally carries the (mesh, axis) pair sharded
providers build their ``shard_map`` against; ``placement_mesh()`` reads
it at trace time.
"""
from __future__ import annotations

import importlib
import os
import threading
from contextlib import contextmanager
from typing import Callable, Optional

XLA = "xla"
PALLAS = "pallas"
AUTO = "auto"
BACKENDS = (XLA, PALLAS, AUTO)

SINGLE = "single"
SHARDED = "sharded"
TWOD = "2d"
PLACEMENTS = (SINGLE, SHARDED, TWOD)

ENV_VAR = "REPRO_BACKEND"
PLACEMENT_ENV_VAR = "REPRO_PLACEMENT"

_tls = threading.local()


class ProviderMissError(KeyError):
    """No provider for a (op, backend, placement) dispatch.

    Subclasses ``KeyError`` (the pinned public contract) but carries the
    structured miss — which op, which resolved backend/placement, the
    requested encoding when one was in play, and the nearest registered
    key — so a miss reads as "you asked for X, the registry has Y"
    instead of a bare repr.
    """

    def __init__(self, op: str, backend: str, placement: str,
                 encoding: Optional[str] = None,
                 nearest: Optional[tuple] = None,
                 detail: str = ""):
        self.op = op
        self.backend = backend
        self.placement = placement
        self.encoding = encoding
        self.nearest = nearest
        self.detail = detail
        super().__init__(str(self))

    def __str__(self) -> str:
        want = f"op={self.op!r} backend={self.backend!r} " \
               f"placement={self.placement!r}"
        if self.encoding is not None:
            want += f" encoding={self.encoding!r}"
        msg = f"no provider registered for {want}"
        if self.detail:
            msg += f" ({self.detail})"
        if self.nearest is not None:
            n_op, n_bk, n_pl = self.nearest
            msg += (f"; nearest registered key: op={n_op!r} "
                    f"backend={n_bk!r} placement={n_pl!r}")
        return msg


# (op, placement) -> reason. A distributed placement hole an op has
# consciously opted out of: dispatch still raises (the no-silent-drop
# rule stands), but the contract checker (repro.analysis.contracts)
# treats the hole as documented instead of flagging missing coverage.
_DECLARED_FALLBACKS: dict[tuple[str, str], str] = {}


def declare_fallback(op: str, placement: str, *, reason: str) -> None:
    """Declare that ``op`` intentionally has no ``placement`` provider.

    This does NOT change dispatch — a distributed miss still raises
    ``ProviderMissError`` — it makes the gap explicit so the registry
    contract checker can tell a declared design decision from an
    accidentally missing provider."""
    _check_placement(placement)
    if not reason:
        raise ValueError("declare_fallback requires a non-empty reason")
    _DECLARED_FALLBACKS[(op, placement)] = reason


def declared_fallback(op: str, placement: str) -> Optional[str]:
    """The declared-fallback reason for (op, placement), or None."""
    return _DECLARED_FALLBACKS.get((op, placement))


# (op_name, backend, placement) -> implementation. Populated by @register
# decorators in core.operators / core.frontier (xla), kernels.ops
# (pallas) and core.distributed (sharded).
_REGISTRY: dict[tuple[str, str, str], Callable] = {}

# (op_name, backend, placement) -> column encodings the provider decodes
# natively (third registry dimension, PR 6). Every provider accepts
# "dense" (any index width — gathers cast at the access point); a
# provider that also understands the delta stream declares
# encodings=("dense", "delta") and receives the EncodedCols pytree in
# the positional slot the dense array normally occupies. storage_arg()
# inserts the decode-to-dense fallback for everyone else, so every
# (op, backend, placement) combination works under every storage plan.
_ENCODINGS: dict[tuple[str, str, str], tuple] = {}

# op -> registry key of the provider its latest dispatch resolved to,
# fallbacks included: what actually served the op (``served()``).
_SERVED: dict[str, tuple[str, str, str]] = {}

# Backends whose implementations live in a module that registers itself on
# import — imported lazily so `import repro.core` never pulls in Pallas.
_LAZY_PROVIDERS = {PALLAS: "repro.kernels.ops"}
# Same discipline for the distributed placements: their providers live
# with the mesh/shard_map machinery and register on import.
_LAZY_PLACEMENT_PROVIDERS = {SHARDED: "repro.core.distributed",
                             TWOD: "repro.core.distributed"}
_loaded: set[str] = set()

# Ops whose xla implementations live outside repro.core (the algebra
# layer): imported on first dispatch so `import repro.core` stays cheap
# and repro.linalg never has to be imported explicitly before use.
_LAZY_OPS = {
    "spmv": "repro.linalg.ops",
    "spmm": "repro.linalg.ops",
    "mxm": "repro.linalg.ops",
}


def _stack() -> list:
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


def _pstack() -> list:
    if not hasattr(_tls, "pstack"):
        _tls.pstack = []
    return _tls.pstack


def _check(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(
            f"unknown backend {name!r}; expected one of {BACKENDS}")
    return name


def _check_placement(name: str) -> str:
    if name not in PLACEMENTS:
        raise ValueError(
            f"unknown placement {name!r}; expected one of {PLACEMENTS}")
    return name


# Pallas graph kernels the TPU compiler (Mosaic, jax 0.9, TPU v5e) refuses,
# with its reason. tests/test_tpu_compile.py holds one strict xfail per
# entry, so a kernel that starts compiling turns the suite red until its
# entry goes; once the table is empty "auto" picks "pallas" on a TPU again.
PALLAS_TPU_REFUSED = {
    "advance_fused": "NotImplementedError: Only 2D gather is supported",
    "advance_filter_fused": "NotImplementedError: Only 2D gather is supported",
    "lb_expand": "NotImplementedError: Only 2D gather is supported",
    "segment_search": "NotImplementedError: Only 2D gather is supported",
    "semiring_spmv": "AssertionError in the gather lowering (k=1); the "
                     "(nx, 1) x-column block is not (8, 128)-aligned (k>1)",
    "filter_compact": "ValueError: the rank-1 (1,) counts block is not a "
                      "multiple of the 128-lane tiling (widened, the body "
                      "then needs cumsum, which the lowering lacks)",
}


class PallasUnavailableError(RuntimeError):
    """The pallas backend was asked for on a TPU whose compiler refuses
    its kernels (see ``PALLAS_TPU_REFUSED``)."""


def _on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def _auto() -> str:
    return PALLAS if _on_tpu() and not PALLAS_TPU_REFUSED else XLA


def _require_pallas() -> None:
    if PALLAS_TPU_REFUSED and _on_tpu():
        refused = "; ".join(f"{k}: {why}"
                            for k, why in PALLAS_TPU_REFUSED.items())
        raise PallasUnavailableError(
            f"backend 'pallas' cannot run on this TPU: the TPU compiler "
            f"refuses its graph kernels ({refused}); use 'xla' or 'auto'")


def resolve(backend: Optional[str] = None,
            use_kernel: Optional[bool] = None) -> str:
    """Resolve a concrete backend name ("xla" | "pallas").

    ``backend`` is the per-call override; ``use_kernel`` is the deprecated
    boolean alias kept for one release (True -> pallas, False -> xla).
    Passing ``use_kernel`` always warns, even alongside an explicit
    ``backend`` (which wins).
    """
    if use_kernel is not None:
        # the obs.log funnel: a real DeprecationWarning (the pinned API
        # contract) plus a debug log under REPRO_LOG=debug
        from repro.obs.log import deprecated
        deprecated(
            "use_kernel= is deprecated; pass backend='pallas'/'xla' or use "
            "repro.core.backend.use_backend(...)", stacklevel=3)
        if backend is None:
            backend = PALLAS if use_kernel else XLA
    if backend is None:
        stack = _stack()
        backend = stack[-1] if stack else None
    if backend is None:
        backend = os.environ.get(ENV_VAR) or XLA
    _check(backend)
    if backend == AUTO:
        return _auto()
    if backend == PALLAS:
        _require_pallas()
    return backend


def resolve_placement(placement: Optional[str] = None) -> str:
    """Resolve a concrete placement name ("single" | "sharded"),
    mirroring backend resolution: per-call → context → env → default."""
    if placement is None:
        stack = _pstack()
        placement = stack[-1][0] if stack else None
    if placement is None:
        placement = os.environ.get(PLACEMENT_ENV_VAR) or SINGLE
    return _check_placement(placement)


@contextmanager
def use_backend(name: str):
    """Context manager: route operator dispatch through ``name``."""
    _check(name)
    _stack().append(name)
    try:
        yield
    finally:
        _stack().pop()


@contextmanager
def use_placement(name: str, mesh=None, axis="graph"):
    """Context manager: route operator dispatch through placement
    ``name``. For "sharded", ``mesh``/``axis`` name the 1-D mesh axis
    the providers shard over; for "2d", ``axis`` is the ("row", "col")
    axis-name pair of the R×C mesh. Providers read them at trace time
    via ``placement_mesh()``."""
    _check_placement(name)
    _pstack().append((name, mesh, axis))
    try:
        yield
    finally:
        _pstack().pop()


def placement_mesh():
    """The (mesh, axis) of the innermost placement context that carries
    one, or None. Distributed providers call this at trace time to build
    their shard_map (``axis`` is a name for 1-D placements, a name pair
    for 2-D)."""
    for name, mesh, axis in reversed(_pstack()):
        if mesh is not None:
            return mesh, axis
    return None


def resolve_graph_placement(graph, placement: Optional[str] = None):
    """Resolve placement for a Graph / ShardedGraph / Sharded2DGraph
    operand.

    Returns ``(placement, context)``: a ``ShardedGraph`` operand implies
    "sharded", a ``Sharded2DGraph`` implies "2d", and the context
    activates the container's mesh for the providers; a plain Graph
    resolves normally. Mismatches are errors, never silent overrides: a
    plain Graph under a distributed selection has nothing to shard over,
    and an explicit per-call placement that contradicts the operand's
    own layout cannot be honoured (re-assemble via ``pg.source`` to run
    single-device).
    Use as ``pl, ctx = resolve_graph_placement(g); with ctx: ...``.
    """
    import contextlib

    from .partition import Sharded2DGraph, ShardedGraph
    implied = (SHARDED if isinstance(graph, ShardedGraph)
               else TWOD if isinstance(graph, Sharded2DGraph) else None)
    if implied is not None:
        if placement is not None and placement != implied:
            raise ValueError(
                f"placement={placement!r} with a "
                f"{type(graph).__name__} operand: the per-device "
                f"slices only run the {implied!r} path; pass the "
                f"unpartitioned graph (the partition's .source) to run "
                f"elsewhere")
        axis = graph.axis if implied == SHARDED else graph.axes
        return implied, use_placement(implied, mesh=graph.mesh, axis=axis)
    pl = resolve_placement(placement)
    if pl == SHARDED:
        raise ValueError(
            "sharded placement needs a ShardedGraph operand "
            "(partition_1d(graph, p).shard(mesh)); got a single-device "
            "graph")
    if pl == TWOD:
        raise ValueError(
            "2d placement needs a Sharded2DGraph operand "
            "(partition_2d(graph, r, c).shard(mesh)); got a "
            "single-device graph")
    return pl, contextlib.nullcontext()


def register(op: str, backend: str, placement: str = SINGLE,
             encodings: tuple = ("dense",)):
    """Decorator: register ``fn`` as the ``backend`` implementation of
    operator hot path ``op`` under ``placement``. ``encodings`` declares
    which column storage encodings the provider decodes natively (see
    ``_ENCODINGS`` / ``storage_arg``)."""
    _check(backend)
    _check_placement(placement)
    for enc in encodings:
        if enc not in ("dense", "delta"):
            raise ValueError(f"unknown storage encoding {enc!r}")

    def deco(fn: Callable) -> Callable:
        _REGISTRY[(op, backend, placement)] = fn
        _ENCODINGS[(op, backend, placement)] = tuple(encodings)
        return fn

    return deco


def _load_lazy(op: str, bk: str, pl: str) -> None:
    if bk in _LAZY_PROVIDERS and bk not in _loaded:
        importlib.import_module(_LAZY_PROVIDERS[bk])
        _loaded.add(bk)
    if pl in _LAZY_PLACEMENT_PROVIDERS and pl not in _loaded:
        importlib.import_module(_LAZY_PLACEMENT_PROVIDERS[pl])
        _loaded.add(pl)
    if (op, bk, SINGLE) not in _REGISTRY and op in _LAZY_OPS:
        importlib.import_module(_LAZY_OPS.pop(op))


def dispatch(op: str, backend: Optional[str] = None,
             placement: Optional[str] = None) -> Callable:
    """Look up the implementation of ``op`` for the resolved backend and
    placement.

    Single placement falls back to the "xla" implementation when the
    backend has none registered (e.g. ops with no Pallas kernel yet).
    Distributed placements ("sharded", "2d") fall back only across
    *backends* (pallas → the xla provider of the same placement) and
    raise if the op has no provider for that placement at all — a
    silent drop to single-device execution would not be the program the
    caller selected. Internal call sites pass ``backend`` /
    ``placement`` only — the deprecated ``use_kernel`` alias lives
    solely in the public wrappers, which resolve it (with a warning)
    before anything reaches the registry.
    """
    bk = resolve(backend)
    pl = resolve_placement(placement)
    return _lookup(op, bk, pl)[1]


def _lookup(op: str, bk: str, pl: str) -> tuple[tuple, Callable]:
    """Resolved (registry key, impl) — the key identifies the provider
    that will actually run (fallbacks included), which is what encoding
    acceptance must be read from.

    Chaos hook: an installed ``repro.ft.inject`` plan with a
    ``provider_miss`` clause makes this lookup fail deterministically as
    if the table had no entry — the injection point the retry/degradation
    ladder is tested against. With no plan installed the hook is a single
    ``None`` check."""
    plan = _fault_plan()
    if plan is not None and plan.should("provider_miss", op):
        raise ProviderMissError(op, bk, pl, nearest=_nearest_key(op, bk, pl),
                                detail="injected by repro.ft.inject")
    _load_lazy(op, bk, pl)
    key = (op, bk, pl)
    impl = _REGISTRY.get(key)
    if impl is None:
        key = (op, XLA, pl)
        impl = _REGISTRY.get(key)
    if impl is None:
        if pl != SINGLE:
            raise ProviderMissError(
                op, bk, pl, nearest=_nearest_key(op, bk, pl),
                detail=f"{pl} dispatch never falls back to the "
                       f"single-device path")
        raise ProviderMissError(op, bk, pl,
                                nearest=_nearest_key(op, bk, pl))
    _SERVED[op] = key
    return key, impl


def served() -> dict[str, tuple[str, str, str]]:
    """op -> (op, backend, placement) of the provider that last served
    it in this process. Dispatch runs at trace time, so this records the
    providers compiled into the programs that ran."""
    return dict(_SERVED)


def _fault_plan():
    """The active ``repro.ft.inject`` plan, or None. Imported lazily so
    the registry module never pulls ``repro.ft`` (and its jax-importing
    health probes) at import time."""
    import sys
    mod = sys.modules.get("repro.ft.inject")
    if mod is None:
        return None
    return mod.active()


def _nearest_key(op: str, bk: str, pl: str) -> Optional[tuple]:
    """The registered key closest to the missed (op, bk, pl): prefer the
    same op under another backend/placement, else the closest op name."""
    same_op = [k for k in _REGISTRY if k[0] == op]
    if same_op:
        # same backend beats same placement beats anything
        return min(same_op, key=lambda k: (k[1] != bk, k[2] != pl, k))
    import difflib
    names = sorted({k[0] for k in _REGISTRY})
    close = difflib.get_close_matches(op, names, n=1)
    if close:
        return min(k for k in _REGISTRY if k[0] == close[0])
    return None


def registered(op: str, backend: str, placement: str = SINGLE) -> bool:
    """True if ``op`` has a native (non-fallback) impl for ``backend``
    under ``placement``."""
    _load_lazy(op, backend, placement)
    return (op, backend, placement) in _REGISTRY


def declared_encodings(op: str, backend: Optional[str] = None,
                       placement: Optional[str] = None) -> tuple:
    """Column encodings natively decoded by the provider that dispatch
    would select for (op, backend, placement), fallbacks included."""
    bk = resolve(backend)
    pl = resolve_placement(placement)
    key, _ = _lookup(op, bk, pl)
    return _ENCODINGS.get(key, ("dense",))


def coerce_store(op: str, backend: Optional[str] = None,
                 placement: Optional[str] = None, *, store):
    """The registry-level decode-to-dense fallback on a raw column
    store: returns ``store`` unchanged when it is already dense or when
    the provider dispatch would select declared its encoding, else the
    decoded dense int32 view."""
    from . import storage as S
    if not isinstance(store, S.EncodedCols):
        return store
    if "delta" in declared_encodings(op, backend, placement):
        return store
    return S.decode_cols(store)


def storage_arg(op: str, backend: Optional[str] = None,
                placement: Optional[str] = None, *, graph,
                side: str = "csr"):
    """The column-storage operand to pass in the registry contract's
    ``col_indices`` slot: the graph's native store when the selected
    provider declared its encoding, else the decoded dense int32 view
    (the registry-level decode-to-dense fallback). ``side`` picks the
    CSR or CSC mirror."""
    store = graph.col_store if side == "csr" else graph.csc_store
    return coerce_store(op, backend, placement, store=store)


# ---------------------------------------------------------------------------
# Capacity tiers (the frontier-proportional dispatch axis)
# ---------------------------------------------------------------------------


def tier_plan(op: str, cap: int, *, min_tier: Optional[int] = None
              ) -> tuple[int, ...]:
    """Static capacity ladder for ``op`` up to ``cap``.

    Primitives ``lax.switch`` their per-iteration step over this ladder
    so an iteration with a 40-vertex frontier does ~one-tile work
    instead of worst-case ``cap``. The ladder is keyed by op because its
    *floor* is the tuner's tile choice for that op on this platform
    (kernels/tuner.py): a tier smaller than one kernel tile would pad
    right back up to the tile, buying switch overhead for nothing.
    Tier choice never affects results — every rung computes the same
    masked expansion, larger rungs just carry more dead lanes — which is
    the tier/untier bit-parity contract tests/test_tiered.py pins.
    """
    from repro.core.frontier import MIN_TIER, tier_caps
    if min_tier is None:
        try:
            from repro.kernels import tuner
            min_tier = tuner.tier_floor(op, MIN_TIER)
        except ImportError:          # tuner unavailable: heuristic floor
            min_tier = MIN_TIER
    return tier_caps(cap, min_tier=min_tier)


def dispatch_tiered(op: str, backend: Optional[str] = None,
                    placement: Optional[str] = None, *, cap: int,
                    pin: bool = False) -> tuple[Callable, tuple[int, ...]]:
    """Resolve ``op`` plus the capacity ladder its call site may switch
    over: ``(impl, caps)``.

    ``pin=True`` and the distributed placements both pin to the top
    tier (single-rung ladder): a dense sweep touches every row
    regardless of the frontier, and sharded/2d providers run
    collectives whose shapes must agree across devices no matter what
    any one device's frontier holds — per-device tier choices would
    deadlock the exchange.
    """
    bk = resolve(backend)
    pl = resolve_placement(placement)
    impl = dispatch(op, bk, pl)
    if pin or pl != SINGLE:
        return impl, (max(int(cap), 1),)
    return impl, tier_plan(op, cap)
