"""Graceful-degradation ladder for graph queries.

When a batch keeps failing after retries under an installed fault plan
(``repro.ft.inject``), the serving loop walks down a ladder of
cheaper/safer configurations instead of failing the queries outright;
outside a chaos run it retries the requested configuration only:

  backend    pallas → xla              (same placement, same results)
  placement  2d → sharded → single     (same results, less parallelism)
  algorithm  bc exact → sampled        (approximate, ``samples=k``)
             reach k hops → k//2 hops  (approximate, smaller neighborhood)

Every step down is *declared* through the PR 9 registry machinery
(:func:`repro.core.backend.declare_fallback`) and logged through
``repro.obs``, and the serving layer stamps ``degraded=true`` on the
affected queries — a downgrade is never silent.

:func:`ladder` builds the rung sequence for a query; the serve loop indexes
into it with the retry attempt number, so attempt 0 runs the requested
configuration and each subsequent attempt runs one rung lower.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import List, Optional

from ..core import backend as B
from ..obs import get_logger

_log = get_logger("repro.ft.degrade")

# placement ladder, strongest first; degradation walks left→right
_PLACEMENT_ORDER = (B.TWOD, B.SHARDED, B.SINGLE)


@dataclass(frozen=True)
class Rung:
    """One configuration on the degradation ladder."""

    backend: str
    placement: str
    hops: Optional[int] = None    # reach: reduced neighborhood radius
    sampled: bool = False         # bc: Brandes-Pich estimator
    reason: str = ""              # how this rung differs from the one above

    @property
    def approximate(self) -> bool:
        return self.sampled or self.reason.startswith("reach")


def ladder(kind: str, backend: str, placement: str = B.SINGLE,
           *, hops: Optional[int] = None) -> List[Rung]:
    """Rung sequence for ``kind`` starting at the requested configuration.

    Rung 0 is always the request itself (``reason=""``); later rungs each
    change exactly one thing, ordered exact-preserving first (backend, then
    placement) and approximation last.
    """
    rungs = [Rung(backend=backend, placement=placement, hops=hops)]

    def _push(reason, **kw):
        rungs.append(replace(rungs[-1], reason=reason, **kw))

    if backend == B.PALLAS:
        _push("backend pallas→xla", backend=B.XLA)
    if placement in _PLACEMENT_ORDER:
        for lower in _PLACEMENT_ORDER[_PLACEMENT_ORDER.index(placement) + 1:]:
            _push(f"placement {rungs[-1].placement}→{lower}",
                  placement=lower)
    if kind == "bc":
        _push("bc exact→sampled", sampled=True)
    if kind == "reach" and hops is not None and hops > 1:
        _push(f"reach hops {hops}→{max(1, hops // 2)}",
              hops=max(1, hops // 2))
    return rungs


def rung_for_attempt(rungs: List[Rung], attempt: int) -> Rung:
    """The rung to run on retry ``attempt`` (clamped to the bottom)."""
    return rungs[min(attempt, len(rungs) - 1)]


def engage(kind: str, rung: Rung, exc: Optional[BaseException] = None) -> None:
    """Record a downgrade: declare it in the registry and log it.

    Idempotent per (kind, placement) — ``declare_fallback`` just overwrites
    the reason — so a hot serve loop can call it on every degraded flush.
    """
    if not rung.reason:
        return
    B.declare_fallback(kind, rung.placement,
                       reason=f"serve-time degradation: {rung.reason}")
    cause = f" after {type(exc).__name__}: {exc}" if exc is not None else ""
    _log.warning("degrade kind=%s %s%s", kind, rung.reason, cause)
